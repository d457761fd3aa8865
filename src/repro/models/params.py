"""Single source of truth for parameters: shapes + logical axes + init.

``build_param_specs(cfg)`` returns a nested dict of ParamSpec. From it we derive
- ``init_params(cfg, key)``        — concrete fp32 arrays (smoke tests, examples)
- ``abstract_params(cfg)``         — ShapeDtypeStruct tree (dry-run, no allocation)
- ``param_pspecs(cfg, sharder)``   — PartitionSpec tree (jit in_shardings)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"     # normal | zeros | ones | ssm_a | dt_bias | embed
    fan_in: Optional[int] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _norm(cfg: ModelConfig, prefix_shape=()) -> dict:
    d = {"scale": ParamSpec(prefix_shape + (cfg.d_model,), (None,) * len(prefix_shape) + ("embed_vec",), "ones")}
    if cfg.norm_type == "layernorm":
        d["bias"] = ParamSpec(prefix_shape + (cfg.d_model,), (None,) * len(prefix_shape) + ("embed_vec",), "zeros")
    return d


def _inner_norm(cfg: ModelConfig, width: int, prefix_shape=()) -> dict:
    # SSM gated-norm scale over d_inner
    return {"scale": ParamSpec(prefix_shape + (width,), (None,) * len(prefix_shape) + ("inner",), "ones")}


def _attn_specs(cfg: ModelConfig, L: int, cross: bool = False) -> dict:
    pre = (L,) if L else ()
    pl = (None,) * len(pre)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    d = {
        "wq": ParamSpec(pre + (D, H * hd), pl + ("embed", "heads"), fan_in=D),
        "wk": ParamSpec(pre + (D, KV * hd), pl + ("embed", "kv"), fan_in=D),
        "wv": ParamSpec(pre + (D, KV * hd), pl + ("embed", "kv"), fan_in=D),
        "wo": ParamSpec(pre + (H * hd, D), pl + ("heads", "embed"), fan_in=H * hd),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamSpec(pre + (H * hd,), pl + ("heads",), "zeros")
        d["bk"] = ParamSpec(pre + (KV * hd,), pl + ("kv",), "zeros")
        d["bv"] = ParamSpec(pre + (KV * hd,), pl + ("kv",), "zeros")
    if cfg.qk_norm:
        d["q_norm"] = ParamSpec(pre + (hd,), pl + (None,), "ones")
        d["k_norm"] = ParamSpec(pre + (hd,), pl + (None,), "ones")
    if cfg.out_bias:
        d["bo"] = ParamSpec(pre + (D,), pl + ("embed",), "zeros")
    return d


def _mlp_specs(cfg: ModelConfig, L: int, d_ff: Optional[int] = None) -> dict:
    pre = (L,) if L else ()
    pl = (None,) * len(pre)
    D, F = cfg.d_model, d_ff or cfg.d_ff
    d = {
        "wi": ParamSpec(pre + (D, F), pl + ("embed", "mlp"), fan_in=D),
        "wo": ParamSpec(pre + (F, D), pl + ("mlp", "embed"), fan_in=F),
    }
    if cfg.mlp_gated:
        d["wg"] = ParamSpec(pre + (D, F), pl + ("embed", "mlp"), fan_in=D)
    if cfg.out_bias:
        d["bi"] = ParamSpec(pre + (F,), pl + ("mlp",), "zeros")
        d["bo"] = ParamSpec(pre + (D,), pl + ("embed",), "zeros")
    return d


def _moe_specs(cfg: ModelConfig, L: int) -> dict:
    pre = (L,) if L else ()
    pl = (None,) * len(pre)
    D, F = cfg.d_model, cfg.moe_d_ff
    E = cfg.n_experts_padded
    d = {
        "router": ParamSpec(pre + (D, cfg.n_routed_experts),
                            pl + ("embed_vec", None), fan_in=D),
        "wi": ParamSpec(pre + (E, D, F), pl + ("experts", None, "moe_mlp"), fan_in=D),
        "wg": ParamSpec(pre + (E, D, F), pl + ("experts", None, "moe_mlp"), fan_in=D),
        "wo": ParamSpec(pre + (E, F, D), pl + ("experts", "moe_mlp", None), fan_in=F),
    }
    if cfg.n_shared_experts:
        SF = cfg.moe_d_ff * cfg.n_shared_experts
        d["shared_wi"] = ParamSpec(pre + (D, SF), pl + ("embed", "mlp"), fan_in=D)
        d["shared_wg"] = ParamSpec(pre + (D, SF), pl + ("embed", "mlp"), fan_in=D)
        d["shared_wo"] = ParamSpec(pre + (SF, D), pl + ("mlp", "embed"), fan_in=SF)
    return d


def _ssm_specs(cfg: ModelConfig, L: int) -> dict:
    pre = (L,) if L else ()
    pl = (None,) * len(pre)
    D, DI, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    K = cfg.ssm_conv_width
    d = {
        "wz": ParamSpec(pre + (D, DI), pl + ("embed", "inner"), fan_in=D),
        "wx": ParamSpec(pre + (D, DI), pl + ("embed", "inner"), fan_in=D),
        "wB": ParamSpec(pre + (D, N), pl + ("embed", "state"), fan_in=D),
        "wC": ParamSpec(pre + (D, N), pl + ("embed", "state"), fan_in=D),
        "wdt": ParamSpec(pre + (D, H), pl + ("embed", "ssm_heads"), fan_in=D),
        "conv_x": ParamSpec(pre + (K, DI), pl + (None, "inner"), "conv"),
        "conv_B": ParamSpec(pre + (K, N), pl + (None, "state"), "conv"),
        "conv_C": ParamSpec(pre + (K, N), pl + (None, "state"), "conv"),
        "A_log": ParamSpec(pre + (H,), pl + ("ssm_heads",), "ssm_a"),
        "Dskip": ParamSpec(pre + (H,), pl + ("ssm_heads",), "ones"),
        "dt_bias": ParamSpec(pre + (H,), pl + ("ssm_heads",), "dt_bias"),
        "gnorm": _inner_norm(cfg, DI, pre)["scale"],
        "wout": ParamSpec(pre + (DI, D), pl + ("inner", "embed"), fan_in=DI),
    }
    return d


def _decoder_layer_specs(cfg: ModelConfig, L: int) -> dict:
    d = {"ln1": _norm_stacked(cfg, L)}
    if cfg.family == "moe":
        d["attn"] = _attn_specs(cfg, L)
        d["ln2"] = _norm_stacked(cfg, L)
        d["moe"] = _moe_specs(cfg, L)
    elif cfg.family == "ssm":
        d["ssm"] = _ssm_specs(cfg, L)
    else:  # dense
        d["attn"] = _attn_specs(cfg, L)
        d["ln2"] = _norm_stacked(cfg, L)
        d["mlp"] = _mlp_specs(cfg, L)
        if cfg.attn_logit_softcap:  # gemma2 sandwich norms
            d["post_attn_ln"] = _norm_stacked(cfg, L)
            d["post_mlp_ln"] = _norm_stacked(cfg, L)
    return d


def _norm_stacked(cfg: ModelConfig, L: int) -> dict:
    pre = (L,) if L else ()
    pl = (None,) * len(pre)
    d = {"scale": ParamSpec(pre + (cfg.d_model,), pl + ("embed_vec",), "ones")}
    if cfg.norm_type == "layernorm":
        d["bias"] = ParamSpec(pre + (cfg.d_model,), pl + ("embed_vec",), "zeros")
    return d


def build_param_specs(cfg: ModelConfig) -> dict:
    V, D = cfg.vocab_padded, cfg.d_model
    specs: dict = {
        "embed": {"table": ParamSpec((V, D), ("vocab", "embed"), "embed")},
        "final_norm": _norm_stacked(cfg, 0),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"w": ParamSpec((D, V), ("embed", "vocab"), fan_in=D)}

    fam = cfg.family
    if fam in ("dense", "moe", "ssm"):
        specs["layers"] = _decoder_layer_specs(cfg, cfg.n_layers)
    elif fam == "hybrid":
        period = cfg.hybrid_attn_period
        n_groups = cfg.n_layers // period
        rem = cfg.n_layers - n_groups * period
        ssm_cfg = dataclasses.replace(cfg, family="ssm")
        specs["groups"] = {
            "ln1": _norm_stacked_2d(cfg, n_groups, period),
            "ssm": _nest_stack(_ssm_specs(ssm_cfg, period), n_groups),
        }
        if rem:
            specs["tail"] = {
                "ln1": _norm_stacked(cfg, rem),
                "ssm": _ssm_specs(ssm_cfg, rem),
            }
        # the SHARED attention block (single set of params, reused each period)
        specs["shared_attn"] = {
            "ln1": _norm_stacked(cfg, 0),
            "attn": _attn_specs(cfg, 0),
            "ln2": _norm_stacked(cfg, 0),
            "mlp": _mlp_specs(cfg, 0),
        }
    elif fam == "encdec":
        specs["enc_layers"] = {
            "ln1": _norm_stacked(cfg, cfg.encoder_layers),
            "attn": _attn_specs(cfg, cfg.encoder_layers),
            "ln2": _norm_stacked(cfg, cfg.encoder_layers),
            "mlp": _mlp_specs(cfg, cfg.encoder_layers),
        }
        specs["enc_final_norm"] = _norm_stacked(cfg, 0)
        specs["layers"] = {
            "ln1": _norm_stacked(cfg, cfg.n_layers),
            "attn": _attn_specs(cfg, cfg.n_layers),
            "ln_x": _norm_stacked(cfg, cfg.n_layers),
            "xattn": _attn_specs(cfg, cfg.n_layers),
            "ln2": _norm_stacked(cfg, cfg.n_layers),
            "mlp": _mlp_specs(cfg, cfg.n_layers),
        }
    else:
        raise ValueError(fam)
    return specs


def _nest_stack(spec_tree: dict, n: int) -> dict:
    """Prepend a group axis to every spec in the tree."""
    def f(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (None,) + s.logical, s.init, s.fan_in)
    return jax.tree_util.tree_map(f, spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def _norm_stacked_2d(cfg: ModelConfig, n: int, m: int) -> dict:
    d = {"scale": ParamSpec((n, m, cfg.d_model), (None, None, "embed_vec"), "ones")}
    if cfg.norm_type == "layernorm":
        d["bias"] = ParamSpec((n, m, cfg.d_model), (None, None, "embed_vec"), "zeros")
    return d


# ------------------------------------------------------------------ derivers
def _init_leaf(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, jnp.float32)
    if spec.init == "ones":
        return jnp.ones(spec.shape, jnp.float32)
    if spec.init == "ssm_a":
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u)
    if spec.init == "dt_bias":
        # inverse-softplus of dt ~ U[1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, spec.shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if spec.init == "conv":
        fan = spec.shape[-2] if len(spec.shape) >= 2 else 4
        return jax.random.normal(key, spec.shape, jnp.float32) / math.sqrt(fan)
    if spec.init == "embed":
        return jax.random.normal(key, spec.shape, jnp.float32) * 0.02
    fan = spec.fan_in or spec.shape[-2]
    return jax.random.normal(key, spec.shape, jnp.float32) / math.sqrt(fan)


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    specs = build_param_specs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [_init_leaf(s, k) for s, k in zip(leaves, keys)])


def abstract_params(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    specs = build_param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_pspecs(cfg: ModelConfig, sharder) -> dict:
    specs = build_param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda s: sharder.pspec(s.logical),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count_exact(cfg: ModelConfig) -> int:
    specs = build_param_specs(cfg)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(math.prod(s.shape) for s in leaves)

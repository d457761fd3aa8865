"""Public model API: forward dispatch across families, loss, cache builders."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Sharder, NULL_SHARDER, cast_params, dtype_of
from repro.models.encdec import forward_encdec
from repro.models.lm import forward_lm


def forward(cfg: ModelConfig, params, batch: dict, sh: Sharder = NULL_SHARDER,
            *, mode="train", cache=None, cache_pos=None,
            q_chunk: Optional[int] = None, logits_at=None):
    """batch: {"tokens": (B,S) int32 [, "frames": (B,Se,D) f32]}.

    Returns (logits_f32, aux_loss, new_cache). The logits are of every
    position, (B,S,V); with ``logits_at``, a position index that may be
    traced, of that position alone, (B,1,V): the final norm and the LM
    head then run on one row."""
    params = cast_params(params, dtype_of(cfg))
    if cfg.family == "encdec":
        return forward_encdec(cfg, params, batch["tokens"], sh,
                              frames=batch.get("frames"), mode=mode,
                              cache=cache, cache_pos=cache_pos,
                              q_chunk=q_chunk, logits_at=logits_at)
    return forward_lm(cfg, params, batch["tokens"], sh, mode=mode,
                      cache=cache, cache_pos=cache_pos, q_chunk=q_chunk,
                      logits_at=logits_at)


def loss_fn(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
            mask: Optional[jax.Array] = None, z_loss: float = 1e-4):
    """Causal LM cross-entropy with SPMD-friendly one-hot label pick.

    logits: (B,S,V) fp32, labels: (B,S) int32, mask: (B,S) {0,1}.
    """
    V = logits.shape[-1]
    lse = jax.nn.logsumexp(logits, axis=-1)  # (B,S)
    oh_dt = jnp.bfloat16 if cfg.loss_onehot_bf16 else logits.dtype
    onehot = jax.nn.one_hot(labels, V, dtype=oh_dt)
    label_logit = jnp.sum(logits * onehot.astype(logits.dtype), axis=-1)
    nll = lse - label_logit
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    zl = z_loss * jnp.sum(jnp.square(lse) * mask) / denom
    return loss + zl, {"nll": loss, "z_loss": zl}


def shift_labels(tokens: jax.Array):
    """labels[i] = tokens[i+1]; the final position is masked out."""
    labels = jnp.roll(tokens, -1, axis=-1)
    mask = jnp.ones_like(tokens, jnp.float32)
    mask = mask.at[..., -1].set(0.0)
    return labels, mask


# ------------------------------------------------------------------ caches
def _kv_cache_shapes(cfg, L, B, T):
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    return {"k": ((L, B, T, KV, hd), jnp.bfloat16),
            "v": ((L, B, T, KV, hd), jnp.bfloat16)}


def _ssm_state_shapes(cfg, pre, B):
    K, DI, N = cfg.ssm_conv_width, cfg.d_inner, cfg.ssm_state
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    return {
        "conv_x": (pre + (B, K - 1, DI), jnp.bfloat16),
        "conv_B": (pre + (B, K - 1, N), jnp.bfloat16),
        "conv_C": (pre + (B, K - 1, N), jnp.bfloat16),
        "ssm": (pre + (B, H, P, N), jnp.float32),
    }


def cache_shapes(cfg: ModelConfig, B: int, T: int) -> dict:
    """Nested dict of (shape, dtype) mirroring the cache pytree."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        return _kv_cache_shapes(cfg, cfg.n_layers, B, T)
    if fam == "ssm":
        return _ssm_state_shapes(cfg, (cfg.n_layers,), B)
    if fam == "hybrid":
        period = cfg.hybrid_attn_period
        G = cfg.n_layers // period
        rem = cfg.n_layers - G * period
        d = {
            "groups_ssm": _ssm_state_shapes(cfg, (G, period), B),
            "attn": _kv_cache_shapes(cfg, G, B, T),
        }
        if rem:
            d["tail_ssm"] = _ssm_state_shapes(cfg, (rem,), B)
        else:
            d["tail_ssm"] = None
        return d
    if fam == "encdec":
        Se = T // cfg.encoder_frames_ratio
        d = _kv_cache_shapes(cfg, cfg.n_layers, B, T)
        d["xk"] = ((cfg.n_layers, B, Se, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
        d["xv"] = d["xk"]
        return d
    raise ValueError(fam)


def _is_shape_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def abstract_cache(cfg, B, T, sharder: Optional[Sharder] = None):
    shapes = cache_shapes(cfg, B, T)
    pspecs = cache_pspecs(cfg, B, T, sharder) if sharder else None

    def mk(sd, ps):
        if sd is None:
            return None
        shape, dt = sd
        if ps is not None and sharder is not None and sharder.mesh is not None:
            from jax.sharding import NamedSharding
            from repro.dist.partitioning import sanitize_pspec
            ps = sanitize_pspec(shape, ps, sharder.mesh)
            return jax.ShapeDtypeStruct(shape, dt,
                                        sharding=NamedSharding(sharder.mesh, ps))
        return jax.ShapeDtypeStruct(shape, dt)

    if pspecs is None:
        return jax.tree_util.tree_map(lambda sd: mk(sd, None), shapes,
                                      is_leaf=_is_shape_leaf)
    return jax.tree_util.tree_map(mk, shapes, pspecs, is_leaf=_is_shape_leaf)


def init_cache(cfg, B, T):
    shapes = cache_shapes(cfg, B, T)
    return jax.tree_util.tree_map(
        lambda sd: jnp.zeros(sd[0], sd[1]) if sd else None, shapes,
        is_leaf=_is_shape_leaf)


def cache_pspecs(cfg, B, T, sh: Sharder):
    """PartitionSpec tree matching cache_shapes."""
    from jax.sharding import PartitionSpec as P
    shapes = cache_shapes(cfg, B, T)

    model_size = 1
    if sh.mesh is not None and "model" in getattr(sh.mesh, "axis_names", ()):
        model_size = sh.mesh.shape["model"]

    def spec(path_leaf, sd):
        if sd is None:
            return None
        shape, _ = sd
        nd = len(shape)
        name = path_leaf
        if name in ("k", "v", "xk", "xv"):
            # (L, B, T, KV, hd). When KV heads don't divide the model axis
            # the cache would end up REPLICATED across it (25+ GiB/chip for
            # 32k decode): shard the sequence dim over "model" instead.
            if shape[3] % model_size != 0:
                return sh.pspec((None, "batch", "cache_seq_model", None, None))
            return sh.pspec((None, "batch", "cache_seq", "kv_act", None))
        if name == "ssm":
            # (pre..., B, H, P, N)
            pre = nd - 4
            return sh.pspec((None,) * pre + ("batch", "ssm_heads_act", None, None))
        if name.startswith("conv_x"):
            pre = nd - 3
            return sh.pspec((None,) * pre + ("batch", None, "inner_act"))
        if name.startswith("conv_"):
            pre = nd - 3
            return sh.pspec((None,) * pre + ("batch", None, None))
        return P()

    def walk(tree):
        out = {}
        for k, v in tree.items():
            if v is None:
                out[k] = None
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = spec(k, v)
        return out

    return walk(shapes)

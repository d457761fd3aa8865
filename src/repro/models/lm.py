"""Decoder LM forward passes for dense / MoE / SSM / hybrid families.

All families scan over stacked layer params (small HLO, compile-friendly at
512-way SPMD) with optional remat on the layer body. Three modes:

- "train":   full-sequence causal forward -> logits (no cache kept)
- "prefill": full-sequence forward -> logits + cache (KV / SSM states)
- "decode":  one token + cache -> logits + updated cache
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import Sharder, apply_norm, activation, dtype_of, softcap, sinusoidal_positions
from repro.models.moe import moe_layer
from repro.models.ssm import mamba2_block


# ---------------------------------------------------------------- helpers
def is_local_flags(cfg) -> Optional[jax.Array]:
    """Per-layer bool: True => sliding-window (local) attention."""
    if not cfg.sliding_window:
        return None
    p = cfg.local_global_period
    L = cfg.n_layers
    if p == 0:
        return None
    if p == 1:
        return jnp.ones((L,), bool)
    return (jnp.arange(L) % p) != (p - 1)


def embed_tokens(cfg, params, tokens, sh: Sharder):
    dt = dtype_of(cfg)
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(dt)
    if cfg.attn_logit_softcap:  # gemma2 scales embeddings
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    if cfg.rope_theta == 0.0 and cfg.family in ("encdec",):
        pass  # positions added by caller (needs offset)
    return sh.act(x, "batch", "seq", None)


def lm_logits(cfg, params, x, sh: Sharder):
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(x.dtype)  # (V, D)
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["w"].astype(x.dtype))
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return sh.act(logits, "batch", "seq", "vocab_act")


def final_logits(cfg, params, x, sh: Sharder, at=None):
    """The final norm and LM head over x:(B,S,D). With ``at``, a position
    index (it may be traced), over that position alone -> (B,1,V)."""
    if at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, at, 1, axis=1)
    x = apply_norm(cfg, x, params["final_norm"])
    return lm_logits(cfg, params, x, sh)


def _mlp(cfg, p, x, sh: Sharder, d_ff_override=None):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
    if "bi" in p:
        h = h + p["bi"].astype(x.dtype)
    if cfg.mlp_gated:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
        h = activation(cfg.mlp_act, g) * h
    else:
        h = activation(cfg.mlp_act, h)
    h = sh.act(h, "batch", "seq", "heads_act")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(x.dtype)
    return sh.act(y, "batch", "seq", None)


def _maybe_remat(cfg, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------- dense/moe
def _attn_block_full(cfg, lp, x, sh, is_local, q_chunk):
    h = apply_norm(cfg, x, lp["ln1"])
    out, kv = attn.full_attention(cfg, lp["attn"], h, sh, causal=True,
                                  is_local=is_local, q_chunk=q_chunk)
    if "post_attn_ln" in lp:
        out = apply_norm(cfg, out, lp["post_attn_ln"])
    x = x + out
    h2 = apply_norm(cfg, x, lp["ln2"])
    if cfg.family == "moe":
        y, aux = moe_layer(cfg, lp["moe"], h2, sh)
    else:
        y, aux = _mlp(cfg, lp["mlp"], h2, sh), jnp.float32(0)
    if "post_mlp_ln" in lp:
        y = apply_norm(cfg, y, lp["post_mlp_ln"])
    return x + y, kv, aux


def _dense_forward(cfg, params, tokens, sh, mode, cache, cache_pos, q_chunk,
                   logits_at):
    x = embed_tokens(cfg, params, tokens, sh)
    flags = is_local_flags(cfg)
    xs_flags = flags if flags is not None else jnp.zeros((cfg.n_layers,), bool)
    keep_cache = mode == "prefill"

    if mode in ("train", "prefill"):
        def body(x, xs):
            lp, is_local = xs
            il = is_local if flags is not None else None
            x, kv, aux = _attn_block_full(cfg, lp, x, sh, il, q_chunk)
            ys = (kv if keep_cache else None, aux)
            return x, ys

        x, (kvs, auxs) = jax.lax.scan(_maybe_remat(cfg, body), x,
                                      (params["layers"], xs_flags))
        new_cache = None
        if keep_cache:
            k, v = kvs
            new_cache = {"k": k, "v": v}  # (L, B, S, KV, hd)
        aux = jnp.sum(auxs)
    else:  # decode: the stacked cache is carried and updated in place
        def body(carry, xs):
            x, ck, cv = carry
            lp, layer, is_local = xs
            il = is_local if flags is not None else None
            h = apply_norm(cfg, x, lp["ln1"])
            out, ck, cv = attn.decode_attention_stacked(
                cfg, lp["attn"], h, ck, cv, layer, cache_pos, sh, is_local=il)
            if "post_attn_ln" in lp:
                out = apply_norm(cfg, out, lp["post_attn_ln"])
            x = x + out
            h2 = apply_norm(cfg, x, lp["ln2"])
            if cfg.family == "moe":
                y, _ = moe_layer(cfg, lp["moe"], h2, sh)
            else:
                y = _mlp(cfg, lp["mlp"], h2, sh)
            if "post_mlp_ln" in lp:
                y = apply_norm(cfg, y, lp["post_mlp_ln"])
            return (x + y, ck, cv), None

        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, nk, nv), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]),
                                      (params["layers"], layers, xs_flags))
        new_cache = {"k": nk, "v": nv}
        aux = jnp.float32(0)

    return final_logits(cfg, params, x, sh, logits_at), aux, new_cache


# ---------------------------------------------------------------- ssm
def _ssm_forward(cfg, params, tokens, sh, mode, cache, cache_pos, logits_at):
    x = embed_tokens(cfg, params, tokens, sh)
    keep = mode != "train"

    if mode in ("train", "prefill"):
        def body(x, lp):
            h = apply_norm(cfg, x, lp["ln1"])
            y, st = mamba2_block(cfg, lp["ssm"], h, sh, mode=mode)
            return x + y, (st if keep else None)

        x, sts = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])
        new_cache = sts if keep else None
    else:
        def body(x, xs):
            lp, st = xs
            h = apply_norm(cfg, x, lp["ln1"])
            y, nst = mamba2_block(cfg, lp["ssm"], h, sh, mode="decode", state=st)
            return x + y, nst

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))

    logits = final_logits(cfg, params, x, sh, logits_at)
    return logits, jnp.float32(0), new_cache


# ---------------------------------------------------------------- hybrid
def _shared_attn_block_full(cfg, sp, x, sh, q_chunk, keep_cache):
    h = apply_norm(cfg, x, sp["ln1"])
    out, kv = attn.full_attention(cfg, sp["attn"], h, sh, causal=True,
                                  q_chunk=q_chunk)
    x = x + out
    h2 = apply_norm(cfg, x, sp["ln2"])
    x = x + _mlp(cfg, sp["mlp"], h2, sh)
    return x, (kv if keep_cache else None)


def _hybrid_forward(cfg, params, tokens, sh, mode, cache, cache_pos, q_chunk,
                    logits_at):
    x = embed_tokens(cfg, params, tokens, sh)
    sp = params["shared_attn"]
    ssm_cfg = dataclasses.replace(cfg, family="ssm")
    keep = mode == "prefill"

    if mode in ("train", "prefill"):
        def group_body(x, gp):
            def ssm_body(x, lp):
                h = apply_norm(cfg, x, {"scale": lp["ln1_scale"]})
                y, st = mamba2_block(ssm_cfg, lp["ssm"], h, sh, mode=mode)
                return x + y, (st if keep else None)

            lp_tree = {"ln1_scale": gp["ln1"]["scale"], "ssm": gp["ssm"]}
            x, sts = jax.lax.scan(ssm_body, x, lp_tree)
            x, kv = _shared_attn_block_full(cfg, sp, x, sh, q_chunk, keep)
            return x, (sts, kv)

        x, (g_sts, g_kvs) = jax.lax.scan(_maybe_remat(cfg, group_body), x,
                                         params["groups"])
        tail_sts = None
        if "tail" in params:
            def tail_body(x, lp):
                h = apply_norm(cfg, x, {"scale": lp["ln1_scale"]})
                y, st = mamba2_block(ssm_cfg, lp["ssm"], h, sh, mode=mode)
                return x + y, (st if keep else None)

            tp = {"ln1_scale": params["tail"]["ln1"]["scale"],
                  "ssm": params["tail"]["ssm"]}
            x, tail_sts = jax.lax.scan(_maybe_remat(cfg, tail_body), x, tp)
        new_cache = None
        if keep:
            k, v = g_kvs
            new_cache = {"groups_ssm": g_sts, "tail_ssm": tail_sts,
                         "attn": {"k": k, "v": v}}
    else:  # decode
        def group_body(x, xs):
            gp, g_state, ck, cv = xs

            def ssm_body(x, xs2):
                lp, st = xs2
                h = apply_norm(cfg, x, {"scale": lp["ln1_scale"]})
                y, nst = mamba2_block(ssm_cfg, lp["ssm"], h, sh,
                                      mode="decode", state=st)
                return x + y, nst

            lp_tree = {"ln1_scale": gp["ln1"]["scale"], "ssm": gp["ssm"]}
            x, nsts = jax.lax.scan(ssm_body, x, (lp_tree, g_state))
            h = apply_norm(cfg, x, sp["ln1"])
            out, nk, nv = attn.decode_attention(cfg, sp["attn"], h, ck, cv,
                                                cache_pos, sh)
            x = x + out
            h2 = apply_norm(cfg, x, sp["ln2"])
            x = x + _mlp(cfg, sp["mlp"], h2, sh)
            return x, (nsts, nk, nv)

        x, (ng_sts, nk, nv) = jax.lax.scan(
            group_body, x,
            (params["groups"], cache["groups_ssm"],
             cache["attn"]["k"], cache["attn"]["v"]))
        n_tail = None
        if "tail" in params:
            def tail_body(x, xs2):
                lp, st = xs2
                h = apply_norm(cfg, x, {"scale": lp["ln1_scale"]})
                y, nst = mamba2_block(ssm_cfg, lp["ssm"], h, sh,
                                      mode="decode", state=st)
                return x + y, nst

            tp = {"ln1_scale": params["tail"]["ln1"]["scale"],
                  "ssm": params["tail"]["ssm"]}
            x, n_tail = jax.lax.scan(tail_body, x, (tp, cache["tail_ssm"]))
        new_cache = {"groups_ssm": ng_sts, "tail_ssm": n_tail,
                     "attn": {"k": nk, "v": nv}}

    logits = final_logits(cfg, params, x, sh, logits_at)
    return logits, jnp.float32(0), new_cache


# ---------------------------------------------------------------- dispatch
def forward_lm(cfg, params, tokens, sh: Sharder, *, mode="train",
               cache=None, cache_pos=None, q_chunk: Optional[int] = None,
               logits_at=None):
    """tokens: (B, S) int32. Returns (logits_f32, aux_loss, new_cache);
    the logits of every position, or of position ``logits_at`` alone."""
    if cfg.family in ("dense", "moe"):
        return _dense_forward(cfg, params, tokens, sh, mode, cache,
                              cache_pos, q_chunk, logits_at)
    if cfg.family == "ssm":
        return _ssm_forward(cfg, params, tokens, sh, mode, cache, cache_pos,
                            logits_at)
    if cfg.family == "hybrid":
        return _hybrid_forward(cfg, params, tokens, sh, mode, cache,
                               cache_pos, q_chunk, logits_at)
    raise ValueError(cfg.family)

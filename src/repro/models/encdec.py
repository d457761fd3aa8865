"""Whisper-style encoder-decoder. The conv audio frontend is a STUB per the
assignment: ``frames`` are precomputed frame embeddings (B, S_enc, D) provided
by input_specs(). Positions are sinusoidal (rope_theta == 0)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import (Sharder, apply_norm, dtype_of,
                                 sinusoidal_positions)
from repro.models.lm import _maybe_remat, _mlp, final_logits


def encode(cfg, params, frames, sh: Sharder):
    """frames: (B, Se, D) stub frame embeddings -> encoder output (B, Se, D)."""
    dt = dtype_of(cfg)
    x = frames.astype(dt)
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)[None]
    x = sh.act(x, "batch", "seq", None)

    def body(x, lp):
        h = apply_norm(cfg, x, lp["ln1"])
        out, _ = attn.full_attention(cfg, lp["attn"], h, sh, causal=False)
        x = x + out
        h2 = apply_norm(cfg, x, lp["ln2"])
        return x + _mlp(cfg, lp["mlp"], h2, sh), None

    x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, params["enc_layers"])
    return apply_norm(cfg, x, params["enc_final_norm"])


def forward_encdec(cfg, params, tokens, sh: Sharder, *, frames=None,
                   enc_out=None, mode="train", cache=None, cache_pos=None,
                   q_chunk: Optional[int] = None, logits_at=None):
    """Teacher-forced decoder over encoder output.

    train/prefill: ``frames`` required; decode: ``cache`` holds self K/V and
    precomputed cross K/V (encoder ran at prefill).
    Returns (logits, aux, new_cache); the logits of every position, or of
    position ``logits_at`` alone.
    """
    dt = dtype_of(cfg)
    B, S = tokens.shape
    keep = mode == "prefill"

    if mode in ("train", "prefill"):
        if enc_out is None:
            enc_out = encode(cfg, params, frames, sh)
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(dt)
        x = x + sinusoidal_positions(S, cfg.d_model).astype(dt)[None]
        x = sh.act(x, "batch", "seq", None)

        def body(x, lp):
            h = apply_norm(cfg, x, lp["ln1"])
            out, kv = attn.full_attention(cfg, lp["attn"], h, sh, causal=True,
                                          q_chunk=q_chunk)
            x = x + out
            hx = apply_norm(cfg, x, lp["ln_x"])
            ek, ev = attn.encode_kv(cfg, lp["xattn"], enc_out)
            x = x + attn.cross_attention(cfg, lp["xattn"], hx, ek, ev, sh)
            h2 = apply_norm(cfg, x, lp["ln2"])
            x = x + _mlp(cfg, lp["mlp"], h2, sh)
            ys = (kv, (ek, ev)) if keep else None
            return x, ys

        x, ys = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])
        new_cache = None
        if keep:
            (k, v), (ek, ev) = ys
            new_cache = {"k": k, "v": v, "xk": ek, "xv": ev}
    else:  # decode
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(dt)
        pos = sinusoidal_positions(1, cfg.d_model, offset=cache_pos)
        x = x + pos.astype(dt)  # (1, D), or (B, 1, D) per-slot
        x = sh.act(x, "batch", "seq", None)

        def body(x, xs):
            lp, ck, cv, xk, xv = xs
            h = apply_norm(cfg, x, lp["ln1"])
            out, nk, nv = attn.decode_attention(cfg, lp["attn"], h, ck, cv,
                                                cache_pos, sh)
            x = x + out
            hx = apply_norm(cfg, x, lp["ln_x"])
            x = x + attn.cross_attention(cfg, lp["xattn"], hx, xk, xv, sh)
            h2 = apply_norm(cfg, x, lp["ln2"])
            return x + _mlp(cfg, lp["mlp"], h2, sh), (nk, nv)

        x, (nk, nv) = jax.lax.scan(body, x,
                                   (params["layers"], cache["k"], cache["v"],
                                    cache["xk"], cache["xv"]))
        new_cache = {"k": nk, "v": nv, "xk": cache["xk"], "xv": cache["xv"]}

    logits = final_logits(cfg, params, x, sh, logits_at)
    return logits, jnp.float32(0), new_cache

"""GQA attention: train/prefill (full-seq, optionally q-chunked), decode
(single token vs KV cache), cross-attention, bidirectional encoder attention.

Supports RoPE, qk-norm, qkv and output biases, logit softcap (gemma2),
sliding-window local layers alternating with global layers. Every path is
pure jnp; the Pallas flash kernel (kernels/flash_attention) is not called
from here.

Decode writes each sequence's new K/V row into the stacked (L,B,T,KV,hd)
cache in place and attends over the layer's whole T positions under a
causal (and, on local layers, sliding-window) mask.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import Sharder, apply_rope, rms_norm, softcap

NEG_INF = -2.0e38


def _project_qkv(cfg, p, x, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _out_proj(p, out, dtype):
    """The output projection of attention heads out:(B,S,H*hd), and its
    bias where the model has one, with the weights in the residual's dtype."""
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(dtype))
    if "bo" in p:
        y = y + p["bo"].astype(dtype)
    return y


def _mask(qpos, kpos, causal: bool, window: int, is_local) -> jax.Array:
    """(..., Sq, Sk) boolean mask. is_local may be a traced scalar bool."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    m = (k <= q) if causal else (jnp.zeros_like(k - q) == 0)
    if window and is_local is not None:
        local = m & (q - k < window)
        m = jnp.where(is_local, local, m)
    elif window and is_local is None:
        m = m & (q - k < window)
    return m


def _sdpa(cfg, q, k, v, mask, sh: Sharder):
    """q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd) mask:(Sq,Sk) or (B,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if cfg.attn_traffic_stub:
        # measurement stand-in: linear-traffic product with NO (Sq x Sk)
        # tensor; grads still flow through q, k, v.
        km = jnp.mean(k, axis=1, keepdims=True)   # (B,1,KV,hd)
        vm = jnp.mean(v, axis=1, keepdims=True)
        qg = q.reshape(B, Sq, KV, G, hd)
        w = jnp.einsum("bskgd,btkd->bskg", qg, km) * (hd ** -0.5)
        out = jnp.einsum("bskg,btkd->bskgd", jax.nn.sigmoid(w), vm)
        out = out.reshape(B, Sq, H, hd)
        return sh.act(out, "batch", "seq", "heads_act", None)
    q = q.reshape(B, Sq, KV, G, hd)
    # Perf knob: writing the (s x s) score matrix in bf16 halves its HBM
    # traffic; the softmax still reduces in f32 (converts fuse into the read).
    score_dt = jnp.bfloat16 if cfg.attn_scores_bf16 else jnp.float32
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=score_dt)
    scores = scores.astype(jnp.float32) * (hd ** -0.5)
    scores = softcap(scores, cfg.attn_logit_softcap)
    if mask.ndim == 3:  # (B, Sq, Sk): per-sequence positions
        mask = mask[:, None, None]
    else:  # (Sq, Sk)
        mask = mask[None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    out = out.reshape(B, Sq, H, hd)
    return sh.act(out, "batch", "seq", "heads_act", None)


def full_attention(cfg, p, x, sh: Sharder, *, causal=True, is_local=None,
                   q_chunk: Optional[int] = None, positions=None):
    """Train/prefill self-attention over the whole sequence.

    Returns (out, (k, v)) so prefill can keep the cache.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(cfg, p, x, positions)
    q = sh.act(q, "batch", "seq", "heads_act", None)
    k = sh.act(k, "batch", "seq", "kv_act", None)
    v = sh.act(v, "batch", "seq", "kv_act", None)
    kpos = jnp.arange(S, dtype=jnp.int32)

    if q_chunk is None or q_chunk >= S:
        mask = _mask(jnp.arange(S, dtype=jnp.int32), kpos, causal,
                     cfg.sliding_window, is_local)
        out = _sdpa(cfg, q, k, v, mask, sh)
    else:
        nq = S // q_chunk
        qs = q.reshape(B, nq, q_chunk, *q.shape[2:]).swapaxes(0, 1)

        def body(_, args):
            qi, qc = args
            qpos = qi * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
            mask = _mask(qpos, kpos, causal, cfg.sliding_window, is_local)
            return None, _sdpa(cfg, qc, k, v, mask, sh)

        _, outs = jax.lax.scan(body, None,
                               (jnp.arange(nq, dtype=jnp.int32), qs))
        out = outs.swapaxes(0, 1).reshape(B, S, q.shape[2], q.shape[3])
    y = _out_proj(p, out.reshape(B, S, -1), x.dtype)
    y = sh.act(y, "batch", "seq", None)
    return y, (k, v)


def _cache_names(cache, sh: Sharder):
    """Logical axes of a stacked cache (L, B, T, KV, hd)."""
    model_size = 1
    if sh.mesh is not None and "model" in getattr(sh.mesh, "axis_names", ()):
        model_size = sh.mesh.shape["model"]
    if cache.shape[3] % model_size == 0:
        return (None, "batch", "cache_seq", "kv_act", None)
    # KV heads can't cover the TP axis: shard cache sequence instead
    return (None, "batch", "cache_seq_model", None, None)


def _write_row(cache, new, layer, cache_pos):
    """Write new:(B,1,KV,hd) into cache:(L,B,T,KV,hd) at [layer, b, pos[b]]
    (per-sequence positions) or [layer, :, pos] (scalar), in place."""
    new = new.astype(cache.dtype)
    if cache_pos.ndim == 1:
        return cache.at[layer, jnp.arange(new.shape[0]), cache_pos].set(
            new[:, 0])
    return jax.lax.dynamic_update_slice(cache, new[None],
                                        (layer, 0, cache_pos, 0, 0))


def decode_attention_stacked(cfg, p, x, cache_k, cache_v, layer, cache_pos,
                             sh: Sharder, *, is_local=None):
    """Single-token decode of layer ``layer`` against the stacked cache.

    x:(B,1,D); cache:(L,B,T,KV,hd); cache_pos is a scalar (aligned batch) or
    an int32 (B,) vector (continuous batching: per-sequence positions). The
    new K/V row goes into the stacked cache in place, and attention reads
    ``cache[layer]`` after the write, so a layer scan that carries the cache
    keeps one copy of it.

    Returns (out, new_cache_k, new_cache_v), the caches stacked.
    """
    B = x.shape[0]
    T = cache_k.shape[2]
    cache_pos = jnp.asarray(cache_pos, jnp.int32)
    if cache_pos.ndim == 1:
        positions = cache_pos[:, None]  # (B, 1)
        qpos = positions
    else:
        positions = jnp.full((B, 1), cache_pos, dtype=jnp.int32)
        qpos = jnp.full((1,), cache_pos, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    names = _cache_names(cache_k, sh)
    cache_k = sh.act(_write_row(cache_k, k_new, layer, cache_pos), *names)
    cache_v = sh.act(_write_row(cache_v, v_new, layer, cache_pos), *names)
    kpos = jnp.arange(T, dtype=jnp.int32)
    mask = _mask(qpos, kpos, True, cfg.sliding_window, is_local)
    out = _sdpa(cfg, q, cache_k[layer], cache_v[layer], mask, sh)
    return _out_proj(p, out.reshape(B, 1, -1), x.dtype), cache_k, cache_v


def decode_attention(cfg, p, x, cache_k, cache_v, cache_pos, sh: Sharder,
                     *, is_local=None):
    """Single-token decode against one layer's cache (B,T,KV,hd): the
    stacked path over a stack of one.

    Returns (out, new_cache_k, new_cache_v).
    """
    y, ck, cv = decode_attention_stacked(cfg, p, x, cache_k[None],
                                         cache_v[None], 0, cache_pos, sh,
                                         is_local=is_local)
    return y, ck[0], cv[0]


def cross_attention(cfg, p, x, enc_k, enc_v, sh: Sharder):
    """Decoder cross-attention over precomputed encoder K/V (B,Se,KV,hd)."""
    B, S, _ = x.shape
    positions = jnp.zeros((B, S), dtype=jnp.int32)  # no rope on cross-attn
    cfg_norope = cfg
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    Se = enc_k.shape[1]
    mask = jnp.ones((S, Se), bool)
    out = _sdpa(cfg_norope, q, enc_k, enc_v, mask, sh)
    return _out_proj(p, out.reshape(B, S, -1), x.dtype)


def encode_kv(cfg, p, enc_out):
    """Project encoder output to cross-attn K/V once (cached for decode)."""
    B, Se, _ = enc_out.shape
    k = jnp.einsum("bsd,dh->bsh", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dh->bsh", enc_out, p["wv"].astype(enc_out.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    k = k.reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
    return k, v

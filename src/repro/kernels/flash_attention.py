"""Pallas TPU flash attention (blocked online-softmax).

TPU-native adaptation (DESIGN.md §Hardware-adaptation): no CUDA warp
mechanics — tiles are sized for VMEM and the 128x128 MXU. The grid is
(batch, q_heads, q_blocks, kv_blocks) with the kv dimension iterated
sequentially ("arbitrary" semantics): each (b, h, qi) revisits its VMEM
scratch accumulators (acc, running max m, running sum l) across kv tiles, so
only one (block_q x hd) query tile and one (block_k x hd) KV tile are VMEM-
resident at a time. Supports causal masking, sliding windows, logit softcap
and GQA (kv-head broadcast through the BlockSpec index_map — no repeat).

Out-of-diagonal (causal) and out-of-window KV blocks are skipped with
pl.when, so the compute matches the ~S^2/2 causal ideal at block
granularity.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale, block_q, block_k, causal, window, softcap):
    qi = pl.program_id(2)
    kk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kk * block_k
    live = jnp.bool_(True)
    if causal:
        live = live & (k_start <= q_start + block_q - 1)
    if window:
        live = live & (q_start - (k_start + block_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # (block_q, hd)
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur[:, None]
        l_ref[...] = l_cur[:, None]

    @pl.when(kk == nk - 1)
    def _fini():
        l = l_ref[...][:, 0]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows stay zero
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=False):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    H must be a multiple of KV (GQA): q head h reads kv head h // (H//KV).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)

    scale = 1.0 / math.sqrt(hd)
    qt = q.transpose(0, 2, 1, 3)  # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, Sq // block_q, Sk // block_k)
    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, i, kk: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, i, kk: (b, h // group, kk, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, i, kk: (b, h // group, kk, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, i, kk: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

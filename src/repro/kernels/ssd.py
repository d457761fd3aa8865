"""Pallas TPU kernel for Mamba2 SSD (state-space duality) chunked scan.

TPU adaptation: one grid cell per (batch, head, chunk); the chunk dimension
is sequential ("arbitrary") and the running SSM state (head_dim x d_state,
fp32) lives in VMEM scratch, exactly like the flash-attention accumulators.
Within a chunk the computation is three MXU matmuls on (chunk x d_state) /
(chunk x head_dim) tiles:

  scores = C B^T . decay_mask       (chunk x chunk)
  y      = scores @ Xd  +  (C . exp(cs)) @ state^T
  state  = exp(cs_last) * state + Xd^T (B . decay_states)

The decay quantities come from a cumulative sum of dt*A over the chunk,
taken as two small lower-triangular matmuls because Mosaic cannot lower
``cumsum``. B/C are single-group (shared across heads): their BlockSpec
index_map drops the head index, so no materialized per-head broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fs_ref, state_ref,
                *, chunk):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)          # (chunk, p)
    dt = dt_ref[...].astype(jnp.float32)        # (chunk, 1)
    A = a_ref[0, 0]                             # scalar
    B = b_ref[...].astype(jnp.float32)          # (chunk, n)
    C = c_ref[...].astype(jnp.float32)          # (chunk, n)

    dA = dt * A                                  # (chunk, 1) negative
    Xd = x * dt                                  # (chunk, p)

    # Prefix sum of dA as lower-triangular matmuls on the MXU (Mosaic has no
    # cumsum). D[i, k] = dA_i; tri[i, j] = (i >= j).
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    tri = causal.astype(jnp.float32)
    D = jnp.broadcast_to(dA, (chunk, chunk))
    hi = jax.lax.Precision.HIGHEST
    cs_rows = jax.lax.dot_general(tri, D, (((1,), (0,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    cs_cols = jax.lax.dot_general(D, tri, (((0,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    cs = cs_rows[:, :1]                          # (chunk, 1): cs_i
    cs_last = jnp.sum(dA, axis=0, keepdims=True)  # (1, 1)

    # intra-chunk: decay-masked scores
    seg = cs_rows - cs_cols                      # cs_i - cs_j
    decay = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * decay, Xd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                       # (p, n) fp32
    Cd = C * jnp.exp(cs)                         # (chunk, n)
    y = y + jax.lax.dot_general(Cd, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: S' = exp(cs_last) S + Xd^T (B . decay_states)
    decay_states = jnp.exp(cs_last - cs)         # (chunk, 1)
    upd = jax.lax.dot_general(Xd, B * decay_states,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (p, n)
    state_ref[...] = state * jnp.exp(cs_last) + upd

    @pl.when(ci == nc - 1)
    def _fini():
        fs_ref[...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_pallas(x, dt, A, B, C, chunk: int = 128, interpret=False):
    """Same contract as models.ssm.ssd_chunked (single group, zero init):

    x: (b, l, h, p); dt: (b, l, h) fp32+; A: (h,); B, C: (b, l, n)
    -> (y: (b, l, h, p), final_state: (b, h, p, n) fp32)
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    xt = x.transpose(0, 2, 1, 3)                       # (b, h, l, p)
    dtt = dt.astype(jnp.float32).transpose(0, 2, 1)[..., None]  # (b,h,l,1)
    At = A.astype(jnp.float32).reshape(h, 1, 1)

    grid = (b, h, nc)
    y, fs = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, 1, 1), lambda bi, hi, ci: (hi, 0, 0)),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, At, B, C)
    return y.transpose(0, 2, 1, 3), fs

"""jit'd public wrappers: select Pallas kernels on TPU, pure-jnp oracles
elsewhere (CPU dry-run lowers the jnp path; kernels are validated in
interpret mode by tests/test_kernels_*)."""
from __future__ import annotations

import jax

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ssd import ssd_chunked_pallas as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              use_pallas=None, interpret=False):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                      interpret=interpret)
    return _ref.attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap)


def ssd(x, dt, A, B, C, chunk=128, *, use_pallas=None, interpret=False):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk)

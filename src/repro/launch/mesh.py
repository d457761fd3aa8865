"""Production mesh builders.

NOTE: these are functions (not module-level constants) so importing this
module never touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benchmarks see the real single CPU device.

The model code places activations with ``with_sharding_constraint`` and
leaves the rest to the partitioner, so mesh axes are ``Auto``: JAX's
default for ``make_mesh`` is ``Explicit``, under which an FSDP-sharded
contraction is refused as ambiguous.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
    Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model); the pod axis is
    pure DP (gradient all-reduce crosses DCN/ICI between pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

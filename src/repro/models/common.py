"""Shared model utilities: norms, activations, RoPE, and the Sharder.

The Sharder carries the (mesh, logical-axis rules) pair through model code so
every activation constraint comes from one table (dist/partitioning.py) and the
same model code runs on 1 CPU device (no-op) and on a 512-chip mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, tuple]


@dataclasses.dataclass(frozen=True)
class Sharder:
    """Maps logical axis names -> mesh axes and applies activation constraints."""

    mesh: Optional[Mesh]
    rules: dict  # logical name -> mesh axis (str | tuple | None)
    enabled: bool = True

    def axes(self, name: Optional[str]) -> Axis:
        if name is None:
            return None
        return self.rules.get(name, None)

    def pspec(self, names: Sequence[Optional[str]]) -> P:
        return P(*[self.axes(n) for n in names])

    def act(self, x: jax.Array, *names: Optional[str]) -> jax.Array:
        """with_sharding_constraint by logical names (len(names) == x.ndim).

        Dims that do not divide their assigned mesh axes are left
        unconstrained: forcing uneven shardings makes GSPMD insert
        full-rematerialization copies when einsums prefer a different
        (padded) layout.
        """
        if not self.enabled or self.mesh is None or self.mesh.empty:
            return x
        assert len(names) == x.ndim, (names, x.shape)
        resolved = []
        for dim, name in zip(x.shape, names):
            ax = self.axes(name)
            if ax is None:
                resolved.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= self.mesh.shape[a]
            resolved.append(ax if dim % n == 0 else None)
        spec = P(*resolved)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    @property
    def dp_axes(self) -> tuple:
        a = self.rules.get("batch")
        if a is None:
            return ()
        return a if isinstance(a, tuple) else (a,)

    @property
    def model_axis(self) -> Optional[str]:
        return self.rules.get("heads")

    @property
    def fsdp_axis(self) -> Optional[str]:
        return self.rules.get("embed")


NULL_SHARDER = Sharder(mesh=None, rules={}, enabled=False)


# ---------------------------------------------------------------- numerics
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(cfg, x, p) -> jax.Array:
    if cfg.norm_type == "layernorm":
        return layer_norm(x, p["scale"], p["bias"], cfg.rms_eps)
    return rms_norm(x, p["scale"], cfg.rms_eps)


def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------- positions
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    dt = x.dtype
    freqs = rope_frequencies(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    cos = jnp.cos(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(dt)


def sinusoidal_positions(seq_len: int, d_model: int, offset=0) -> jax.Array:
    """(seq_len, d_model) from a scalar offset; (B, seq_len, d_model) from a
    (B,) vector of per-sequence offsets."""
    offset = jnp.asarray(offset)[..., None]
    pos = (jnp.arange(seq_len) + offset)[..., None].astype(jnp.float32)
    dim = jnp.arange(0, d_model, 2).astype(jnp.float32)
    angle = pos / jnp.power(10_000.0, dim / d_model)
    pe = jnp.zeros(angle.shape[:-1] + (d_model,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(angle))
    pe = pe.at[..., 1::2].set(jnp.cos(angle[..., : (d_model - d_model // 2)]))
    return pe


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def cast_params(params, dtype):
    """Cast float params to compute dtype (master copies stay fp32)."""
    def c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(c, params)

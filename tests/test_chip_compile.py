"""Compile the main-path kernels and serve steps for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip, compiles
each program for a v5e that is described and not attached, and refuses what
the chip would refuse (an unsupported Pallas primitive, more VMEM than a
kernel may use, a program that does not fit in HBM). Interpret-mode kernel
tests cannot see any of these.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and under
several test workers only the worker given this file may do so.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd import ssd_chunked_pallas
from repro.launch.steps import TrainConfig, make_prefill_step
from repro.models import api as mapi
from repro.models.common import NULL_SHARDER
from repro.models.params import abstract_params
from repro.serve.engine import _decode_batch

# HBM the v5e compiler lets one program use
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= V5E_HBM_BYTES, (mem.argument_size_in_bytes,
                                   mem.temp_size_in_bytes)


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    q = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(flash_attention).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_kernel_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-1.3b")
    b, l, h, p, n = 1, 2048, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (h, p, n) == (64, 64, 128)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        ssd_chunked_pallas, chunk=cfg.ssm_chunk)).lower(
        s((b, l, h, p), jnp.bfloat16), s((b, l, h), jnp.float32),
        s((h,), jnp.float32), s((b, l, n), jnp.bfloat16),
        s((b, l, n), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serving_params(cfg, one_chip):
    return _on(one_chip, abstract_params(cfg, jnp.dtype(cfg.dtype)))


@pytest.fixture(scope="module")
def qwen3_decode(one_chip):
    """The serve engine's own decode step, full width, 8 slots x 4096, the
    cache donated, compiled for one v5e."""
    cfg = get_config("qwen3-1.7b")
    cache = _on(one_chip, mapi.abstract_cache(cfg, 8, 4096))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(_decode_batch, cfg, NULL_SHARDER),
                   donate_argnums=(1,))
    return step.lower(_serving_params(cfg, one_chip), cache, tokens,
                      pos).compile()


def test_qwen3_decode_step_fits_one_v5e(qwen3_decode):
    _fits(qwen3_decode)


def test_qwen3_decode_updates_cache_in_place(qwen3_decode):
    """The layer scan carries the stacked cache: no second cache in temp
    memory, and no copy or dynamic-update-slice of a whole K or V stack
    (the scan's per-layer outputs written into a fresh stack and copied
    back into the donated buffer)."""
    assert qwen3_decode.memory_analysis().temp_size_in_bytes < 64 * 2**20
    stack_ops = re.findall(r"= bf16\[28,8,4096,8,128\]\{[^}]*\} ([\w-]+)\(",
                           qwen3_decode.as_text())
    assert stack_ops, "no whole-stack op found: the pattern is stale"
    assert not {"copy", "dynamic-update-slice"} & set(stack_ops), stack_ops


def test_qwen3_prefill_fits_one_v5e(one_chip):
    """One full-width prefill of a 2048-token prompt."""
    cfg = get_config("qwen3-1.7b")
    batch = {"tokens": jax.ShapeDtypeStruct((1, 2048), jnp.int32,
                                            sharding=one_chip)}
    step = jax.jit(make_prefill_step(cfg, NULL_SHARDER, TrainConfig()))
    compiled = step.lower(_serving_params(cfg, one_chip), batch).compile()
    _fits(compiled)

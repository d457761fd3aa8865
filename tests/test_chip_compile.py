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
from repro.serve.engine import _decode_batch, _prefill_splice

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


def _decode_step(name, n_slots, max_seq, one_chip):
    """The serve engine's own decode step, full width, the cache donated,
    compiled for one v5e."""
    cfg = get_config(name)
    cache = _on(one_chip, mapi.abstract_cache(cfg, n_slots, max_seq))
    tokens = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(_decode_batch, cfg, NULL_SHARDER),
                   donate_argnums=(1,))
    return step.lower(_serving_params(cfg, one_chip), cache, tokens,
                      pos).compile()


def _assert_cache_in_place(compiled, stack):
    """The layer scan carries the stacked cache: no second cache in temp
    memory, and no copy or dynamic-update-slice of a whole K or V stack
    (the scan's per-layer outputs written into a fresh stack and copied
    back into the donated buffer)."""
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    stack_ops = re.findall(re.escape(f"= bf16[{stack}]") +
                           r"\{[^}]*\} ([\w-]+)\(", compiled.as_text())
    assert stack_ops, "no whole-stack op found: the pattern is stale"
    assert not {"copy", "dynamic-update-slice"} & set(stack_ops), stack_ops


def _prefill_step(name, n_tokens, one_chip):
    """One full-width prefill of an ``n_tokens`` prompt."""
    cfg = get_config(name)
    batch = {"tokens": jax.ShapeDtypeStruct((1, n_tokens), jnp.int32,
                                            sharding=one_chip)}
    step = jax.jit(make_prefill_step(cfg, NULL_SHARDER, TrainConfig()))
    return step.lower(_serving_params(cfg, one_chip), batch).compile()


@pytest.fixture(scope="module")
def qwen3_decode(one_chip):
    """Qwen3-1.7B's decode at 8 slots x 4096."""
    return _decode_step("qwen3-1.7b", 8, 4096, one_chip)


def test_qwen3_decode_step_fits_one_v5e(qwen3_decode):
    _fits(qwen3_decode)


def test_qwen3_decode_updates_cache_in_place(qwen3_decode):
    _assert_cache_in_place(qwen3_decode, "28,8,4096,8,128")


def test_qwen3_prefill_fits_one_v5e(one_chip):
    _fits(_prefill_step("qwen3-1.7b", 2048, one_chip))


@pytest.fixture(scope="module")
def starcoder2_decode(one_chip):
    """StarCoder2-3B's decode at 16 slots x 4096, as the benchmark's
    configuration file serves it: 6.06 GB of weights beside a 2.01 GB
    cache."""
    return _decode_step("starcoder2-3b", 16, 4096, one_chip)


def test_starcoder2_decode_step_fits_one_v5e(starcoder2_decode):
    _fits(starcoder2_decode)


def test_starcoder2_decode_updates_cache_in_place(starcoder2_decode):
    _assert_cache_in_place(starcoder2_decode, "30,16,4096,2,128")


def test_starcoder2_cache_has_no_tile_padding(starcoder2_decode):
    """The donated cache is exactly its elements: 30 layers x 16 slots x
    4096 positions x 2 KV heads x 128 x 2 bytes, for K and for V. A tiling
    that padded the 2-head dimension to 8 or 16 rows would hold it four or
    eight times over."""
    assert starcoder2_decode.memory_analysis().alias_size_in_bytes == \
        2 * 30 * 16 * 4096 * 2 * 128 * 2 == 2_013_265_920


def test_starcoder2_prefill_fits_one_v5e(one_chip):
    """The code-completion mix's longest prompt, 3584 tokens."""
    _fits(_prefill_step("starcoder2-3b", 3584, one_chip))


def _serve_prefill(name, n_slots, max_seq, bucket, one_chip):
    """The serve engine's prefill program at one length bucket, full
    width, the cache donated, compiled for one v5e."""
    cfg = get_config(name)
    cache = _on(one_chip, mapi.abstract_cache(cfg, n_slots, max_seq))

    def i32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(_prefill_splice, cfg, NULL_SHARDER,
                                     n_slots), donate_argnums=(1,))
    compiled = step.lower(_serving_params(cfg, one_chip), cache,
                          i32((1, bucket)), i32(()), i32(())).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    return cfg, compiled, cache_bytes


@pytest.mark.parametrize("name,n_slots,stack", [
    ("qwen3-1.7b", 8, "28,8,4096,8,128"),
    ("starcoder2-3b", 16, "30,16,4096,2,128")])
def test_serve_prefill_splices_in_place_and_emits_one_row(
        one_chip, name, n_slots, stack):
    """At the code mix's longest bucket, 3584: the program fits, writes
    the prompt's rows into the donated cache in place (the whole cache
    aliased, no copy of a K or V stack) and computes the logits of one
    position, never float32 logits at every position."""
    cfg, compiled, cache_bytes = _serve_prefill(name, n_slots, 4096, 3584,
                                                one_chip)
    _fits(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    text = compiled.as_text()
    stack_ops = re.findall(re.escape(f"= bf16[{stack}]") +
                           r"\{[^}]*\} ([\w-]+)\(", text)
    assert stack_ops, "no whole-stack op found: the pattern is stale"
    assert "copy" not in stack_ops, stack_ops
    shapes = {tuple(map(int, d.split(",")))
              for d in re.findall(r"\w+\[([\d,]+)\]", text)}
    assert not [d for d in shapes if {3584, cfg.vocab_size} <= set(d)]

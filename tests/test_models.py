"""Per-architecture smoke tests (reduced configs): one forward + one train
step on CPU, asserting shapes and finiteness; prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.steps import TrainConfig, init_train_state, make_train_step
from repro.models import api as mapi
from repro.models import forward, init_params
from repro.models.api import loss_fn, shift_labels
from repro.models.common import NULL_SHARDER
from repro.optim import AdamWConfig

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(
            KEY, (B, S // cfg.encoder_frames_ratio, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    logits, aux, _ = forward(cfg, params, batch, mode="train")
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits)))
    labels, mask = shift_labels(batch["tokens"])
    loss, _ = loss_fn(cfg, logits, labels, mask)
    assert bool(jnp.isfinite(loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=2))
    state = init_train_state(cfg, KEY, tc.optimizer)
    step = jax.jit(make_train_step(cfg, NULL_SHARDER, tc))
    state2, metrics = step(state, _batch(cfg))
    assert int(state2["step"]) == 1
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert metrics["grad_norm"] > 0.0  # params received gradients
    for leaf in jax.tree_util.tree_leaves(state2["params"])[:3]:
        assert bool(jnp.all(jnp.isfinite(leaf)))


def _batch_axis(cfg, T):
    """Per cache leaf, the axis that holds the batch."""
    one, two = mapi.cache_shapes(cfg, 1, T), mapi.cache_shapes(cfg, 2, T)
    return jax.tree_util.tree_map(
        lambda a, b: next(i for i, (m, n) in enumerate(zip(a[0], b[0]))
                          if m != n),
        one, two, is_leaf=mapi._is_shape_leaf)


def _kv_leaves(cache):
    """The (L, B, T, KV, hd) self-attention K/V stacks of a cache tree."""
    if "attn" in cache:
        cache = cache["attn"]
    return [cache[k] for k in ("k", "v") if k in cache]


@pytest.mark.parametrize("positions", ["scalar", "per_slot"])
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch, positions):
    """Each sequence is prefilled alone at its own length, spliced into a
    batched cache, and decoded one token at a scalar position (every
    sequence the same length) or per-slot positions (distinct lengths).
    The decode logits match a forward over the whole sequence; the decode
    writes exactly the K/V rows [layer, b, pos[b]] and they hold the new
    token's K/V."""
    cfg = get_config(arch, smoke=True)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)  # dropless
    params = init_params(cfg, KEY)
    B, S, T = 2, 16, 32
    lens = [S, S] if positions == "scalar" else [S - 7, S]
    batch = _batch(cfg, B, S)
    nxt = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size)
    if cfg.family == "encdec":  # the encoder cache fills T // ratio frames
        frames = jax.random.normal(
            KEY, (B, T // cfg.encoder_frames_ratio, cfg.d_model))

    def seq(b, n):
        one = {"tokens": batch["tokens"][b:b + 1, :n]}
        if cfg.family == "encdec":
            one["frames"] = frames[b:b + 1]
        return one

    cache = mapi.init_cache(cfg, B, T)
    axes = _batch_axis(cfg, T)
    refs = []
    for b, n in enumerate(lens):
        _, _, c = forward(cfg, params, seq(b, n), mode="prefill")
        cache = jax.tree_util.tree_map(
            lambda dst, src, ax: jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), b, ax), cache, c, axes)
        full = seq(b, n)
        full["tokens"] = jnp.concatenate([full["tokens"], nxt[b:b + 1]], 1)
        refs.append(forward(cfg, params, full, mode="prefill"))

    pos = S if positions == "scalar" else jnp.asarray(lens, jnp.int32)
    d_logits, _, new = forward(cfg, params, {"tokens": nxt}, mode="decode",
                               cache=cache, cache_pos=pos)
    for b, n in enumerate(lens):
        err = float(jnp.max(jnp.abs(refs[b][0][0, n] - d_logits[b, -1])))
        assert err < 2e-2, (b, err)

    for old, upd, *ref in zip(_kv_leaves(cache), _kv_leaves(new),
                              *(_kv_leaves(r[2]) for r in refs)):
        written = jnp.zeros(old.shape[:3], bool)
        for b, n in enumerate(lens):
            written = written.at[:, b, n].set(True)
            row = upd[:, b, n].astype(jnp.float32)
            want = ref[b][:, 0, n].astype(jnp.float32)
            assert float(jnp.max(jnp.abs(row - want))) < 5e-2, b
        kept = ~written[..., None, None]
        assert bool(jnp.all(jnp.where(kept, upd == old, True)))


def test_param_counts_close_to_published():
    """Full configs should land near the published model sizes."""
    import math
    from repro.models.params import param_count_exact
    targets = {  # (published-ish total params, tolerance)
        "starcoder2_3b": (3.0e9, 0.25),
        "qwen2_5_14b": (14.7e9, 0.25),
        "gemma2_27b": (27.2e9, 0.35),
        "qwen3_1_7b": (1.7e9, 0.40),
        "deepseek_moe_16b": (16.4e9, 0.25),
        "qwen2_moe_a2_7b": (14.3e9, 0.30),
        "chameleon_34b": (34e9, 0.25),
        "mamba2_1_3b": (1.3e9, 0.30),
        "whisper_tiny": (39e6, 0.60),
        "zamba2_7b": (7.4e9, 0.35),
    }
    for arch, (target, tol) in targets.items():
        n = param_count_exact(get_config(arch))
        assert abs(n - target) / target < tol, (arch, n, target)


def test_gemma2_local_global_masks_differ():
    cfg = get_config("gemma2_27b", smoke=True)
    params = init_params(cfg, KEY)
    B, S = 1, 24  # longer than window (8)
    batch = {"tokens": jnp.arange(S)[None] % cfg.vocab_size}
    logits, _, _ = forward(cfg, params, batch, mode="train")
    # degenerate check: same model with window disabled produces different
    # logits at positions beyond the window
    cfg2 = dataclasses.replace(cfg, sliding_window=0, local_global_period=0)
    logits2, _, _ = forward(cfg2, params, batch, mode="train")
    assert float(jnp.max(jnp.abs(logits - logits2))) > 1e-4

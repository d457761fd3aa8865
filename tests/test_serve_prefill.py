"""The serve engine's compiled prefill (serve/engine.py ``_prefill_splice``):
one program per prompt-length bucket that runs the forward, takes the first
token from the last real position's logits alone, and splices the prompt's
cache into its slot of the donated batched cache.

Each prefill is checked against the eager ``forward(mode="prefill")`` at the
prompt's exact length: the same first token, the slot's cache rows [0:L]
equal (on the bucket grid) or within bf16 rounding (off it, where the padded
program reduces longer rows), the other slot untouched."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import TaskRuntime, Tracer
from repro.models import forward, init_params
from repro.serve.engine import (PREFILL_BUCKET, Request, ServeEngine,
                                prefill_bucket)

MAX_SEQ = 640  # buckets 256 and 512, and the cap at 640
DENSE = ["qwen3-1.7b", "starcoder2-3b"]
# (slot, prompt length): off the grid into slot 1, on it into slot 0, a
# second length in the first bucket, then the second bucket and the cap
PREFILLS = [(1, 300), (0, 256), (1, 200), (0, 512), (1, 600)]


def _prompt(cfg, n):
    return np.random.default_rng(n).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _host(tree):
    """A host copy: a zero-copy view of a float32 buffer on the CPU would
    hold it, and JAX then copies a donated buffer instead of taking it."""
    return jax.tree_util.tree_map(
        lambda a: None if a is None else np.array(a, np.float32), tree)


def _slot_leaves(cache, n_slots):
    """(path, leaf) of every cache leaf with a slot axis (axis 1)."""
    return [(jax.tree_util.keystr(p), a) for p, a in
            jax.tree_util.tree_leaves_with_path(cache)
            if a.ndim >= 3 and a.shape[1] == n_slots]


def _prefill_all(name, prefills):
    """Run each (slot, length) prefill through an engine whose decode loop
    is not started; per prefill: (slot, length, first token, eager first
    token, cache before, cache after, eager cache)."""
    cfg = get_config(name, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Tracer(enabled=True)
    rt = TaskRuntime(n_workers=1, tracer=tr).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=MAX_SEQ)
    steps = []
    try:
        for slot, n in prefills:
            prompt = _prompt(cfg, n)
            before, held = _host(eng.cache), eng.cache
            first = eng._prefill_exec(Request(prompt, max_new_tokens=2),
                                      slot)
            # the program took the cache donated and returned it updated
            assert all(a.is_deleted() for a in
                       jax.tree_util.tree_leaves(held))
            logits, _, ref = forward(cfg, params,
                                     {"tokens": jnp.asarray(prompt)[None]},
                                     mode="prefill")
            steps.append((slot, n, first, int(jnp.argmax(logits[0, -1])),
                          before, _host(eng.cache), _host(ref)))
            assert eng.pos[slot] == n
    finally:
        rt.shutdown()
    return cfg, eng, tr, steps


@pytest.fixture(scope="module", params=DENSE)
def dense(request):
    return _prefill_all(request.param, PREFILLS)


def test_first_token_is_the_eager_forwards(dense):
    *_, steps = dense
    for slot, n, first, want, *_ in steps:
        assert first == want, (slot, n)


def test_slot_rows_match_the_eager_cache_and_the_other_slot_is_untouched(
        dense):
    cfg, *_, steps = dense
    for slot, n, _, _, before, after, ref in steps:
        got = dict(_slot_leaves(after, 2))
        old = dict(_slot_leaves(before, 2))
        assert set(got) == {"['k']", "['v']"}
        for path, r in jax.tree_util.tree_leaves_with_path(ref):
            path = jax.tree_util.keystr(path)
            rows, want = got[path][:, slot, :n], r[:, 0]
            if n % PREFILL_BUCKET == 0:  # no pad: the same program rows
                np.testing.assert_array_equal(rows, want, err_msg=path)
            else:
                np.testing.assert_allclose(rows, want, rtol=2**-6,
                                           atol=2**-6, err_msg=path)
            np.testing.assert_array_equal(got[path][:, 1 - slot],
                                          old[path][:, 1 - slot],
                                          err_msg=path)


def test_programs_count_buckets_not_lengths_or_slots(dense):
    cfg, eng, *_ = dense
    buckets = {prefill_bucket(cfg, n, MAX_SEQ) for _, n in PREFILLS}
    assert buckets == {256, 512, MAX_SEQ}
    assert eng.stats["prefill_programs"] == len(buckets) == 3
    assert eng._prefill_fn._cache_size() == 3  # what jit built


def test_pad_event_carries_the_pad_tokens(dense):
    cfg, _, tr, _ = dense
    assert [a for _, a in tr.events("serve.prefill.pad")] == \
        [prefill_bucket(cfg, n, MAX_SEQ) - n for _, n in PREFILLS] == \
        [212, 0, 56, 0, 40]


def test_non_dense_prefills_at_the_exact_length():
    """An SSM has no pad to ignore: it compiles at each exact length and
    gives the eager forward's first token and final states."""
    cfg, eng, tr, steps = _prefill_all("mamba2-1.3b", [(1, 5), (0, 7)])
    assert cfg.family == "ssm"
    assert prefill_bucket(cfg, 5, MAX_SEQ) == 5
    assert eng.stats["prefill_programs"] == 2
    assert [a for _, a in tr.events("serve.prefill.pad")] == [0, 0]
    for slot, n, first, want, before, after, ref in steps:
        assert first == want, n
        got, old = dict(_slot_leaves(after, 2)), dict(_slot_leaves(before, 2))
        assert set(got) == {"['conv_x']", "['conv_B']", "['conv_C']",
                            "['ssm']"}
        for path, r in jax.tree_util.tree_leaves_with_path(ref):
            path = jax.tree_util.keystr(path)
            np.testing.assert_allclose(got[path][:, slot], r[:, 0],
                                       rtol=1e-6, atol=1e-6, err_msg=path)
            np.testing.assert_array_equal(got[path][:, 1 - slot],
                                          old[path][:, 1 - slot])


@pytest.mark.parametrize("name", DENSE)
def test_decode_after_a_padded_prefill_is_greedy_decode(name):
    """Decode overwrites the pad rows before its mask reaches them: the
    served tokens of an off-grid prompt are the sequential greedy ones."""
    cfg = get_config(name, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = _prompt(cfg, 300)
    ref, toks = [], list(prompt)
    for _ in range(4):
        logits, _, _ = forward(cfg, params,
                               {"tokens": jnp.asarray(toks)[None]},
                               mode="train")
        ref.append(int(jnp.argmax(logits[0, -1])))
        toks.append(ref[-1])
    rt = TaskRuntime(n_workers=2).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=MAX_SEQ).start()
    r = eng.submit(prompt, max_new_tokens=3)
    assert eng.wait(r, timeout=120)
    assert eng.stop(drain=True, timeout=60)
    rt.shutdown()
    assert r.tokens == ref

"""End-to-end behaviour: training improves loss on learnable data,
checkpoint/restart resumes identically, serving completes requests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import TaskRuntime
from repro.data.pipeline import TokenSource
from repro.launch.train import TrainEngine
from repro.models import init_params
from repro.optim import AdamWConfig
from repro.serve import Request, ServeEngine


class PatternSource(TokenSource):
    """Learnable stream: token t+1 = (token t + 1) % V."""

    def batch(self, step, batch_size, seq_len, shard=0, n_shards=1):
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step, shard]))
        start = rng.integers(0, self.vocab_size, size=(batch_size, 1))
        return ((start + np.arange(seq_len)[None, :]) %
                self.vocab_size).astype(np.int32)


def _engine(tmp_path=None, **kw):
    cfg = get_config("qwen3-1.7b", smoke=True)
    eng = TrainEngine(cfg, batch_size=8, seq_len=32,
                      ckpt_dir=str(tmp_path) if tmp_path else None,
                      opt=AdamWConfig(lr=5e-3, warmup_steps=5,
                                      total_steps=200), **kw)
    eng.pipe.source = PatternSource(cfg.vocab_size, seed=0)
    return eng


def test_training_learns_pattern():
    eng = _engine()
    hist = eng.run(60, log_every=0)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    eng.close()
    assert last < first * 0.75, (first, last)


def test_checkpoint_restart_resumes(tmp_path):
    eng = _engine(tmp_path, ckpt_every=5)
    eng.run(10, log_every=0)
    state_w = np.asarray(jax.tree_util.tree_leaves(eng.state["params"])[0])
    eng.close()

    eng2 = _engine(tmp_path, ckpt_every=0)
    step = eng2.restore_latest()
    assert step == 10
    got_w = np.asarray(jax.tree_util.tree_leaves(eng2.state["params"])[0])
    np.testing.assert_array_equal(state_w, got_w)
    # continues from step 10 with the identical data stream
    hist = eng2.run(3, log_every=0)
    assert hist[0]["step"] == 10
    eng2.close()


def test_failure_recovery_path(tmp_path):
    eng = _engine(tmp_path, ckpt_every=4)
    with pytest.raises(RuntimeError, match="injected"):
        eng.run(10, log_every=0, inject_failure_at=6)
    eng.rt.barrier(timeout=60)
    # recover in-place (same process; multi-host would re-exec)
    step = eng.restore_latest()
    assert step == 4
    hist = eng.run(2, log_every=0)
    assert hist[0]["step"] == 4
    eng.close()


def test_serving_end_to_end():
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = TaskRuntime(n_workers=3).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=48).start()
    reqs = [eng.submit(np.arange(4 + i), max_new_tokens=5) for i in range(4)]
    for r in reqs:
        assert eng.wait(r, timeout=120)
        assert len(r.tokens) == 6  # first + 5 decoded
        assert all(0 <= t < cfg.vocab_padded for t in r.tokens)
    eng.stop()
    rt.barrier(timeout=60)
    rt.shutdown()
    assert eng.stats["prefills"] == 4


def test_serving_stop_without_drain_cancels_decode_chain():
    """stop(drain=False) cancels the engine's TaskGroup: the self-respawning
    decode chain stops at the next dequeue, no stale-task errors surface,
    and no pooled tasks leak."""
    import time

    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = TaskRuntime(n_workers=3).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=48).start()
    req = eng.submit(np.arange(4), max_new_tokens=40)  # long decode
    deadline = time.monotonic() + 120
    while eng.stats["decode_iters"] < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng.stats["decode_iters"] >= 3, "decode chain never started"
    assert eng.stop(drain=False)
    assert eng.group.cancelled
    # unfinished requests are released, not left hanging in wait()
    assert eng.wait(req, timeout=10), "cancelled request left waiter hanging"
    assert rt.barrier(timeout=60), "cancelled engine did not quiesce"
    iters = eng.stats["decode_iters"]
    time.sleep(0.2)
    assert eng.stats["decode_iters"] == iters, "decode chain kept running"
    assert eng.group.spawn(lambda: None) is None  # admission stays closed
    deadline = time.monotonic() + 5
    while rt.pool.outstanding and time.monotonic() < deadline:
        time.sleep(0.01)
    assert rt.pool.outstanding == 0, "cancelled engine leaked pooled tasks"
    rt.shutdown()  # raises if any stale-task / engine error was recorded


def test_serving_error_cancel_releases_waiters():
    """A failing engine task self-cancels the group (cancel_on_error);
    clients blocked in wait() must be released, not left to time out."""
    import time

    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = TaskRuntime(n_workers=2).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=32)
    eng._prefill_fn = lambda *args: (_ for _ in ()).throw(
        RuntimeError("injected prefill failure"))
    eng.start()
    req = eng.submit(np.arange(4), max_new_tokens=4)
    assert eng.wait(req, timeout=30), "client hung after engine error"
    assert eng.group.cancelled
    assert rt.barrier(timeout=60)
    # late submits on the dead engine complete immediately and don't
    # accumulate in the never-drained queue
    late = eng.submit(np.arange(3), max_new_tokens=2)
    assert eng.wait(late, timeout=10)
    assert not eng._queue, "terminal engine leaked late-submitted requests"
    with pytest.raises(RuntimeError, match="injected prefill failure"):
        rt.shutdown()


def test_serving_matches_sequential_decode():
    """Continuous-batching decode must equal per-request greedy decode."""
    from repro.models import forward
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = np.arange(6) % cfg.vocab_size

    # sequential greedy reference
    ref = []
    toks = list(prompt)
    for _ in range(4):
        logits, _, _ = forward(cfg, params,
                               {"tokens": jnp.asarray(toks)[None]},
                               mode="train")
        nxt = int(jnp.argmax(logits[0, -1]))
        ref.append(nxt)
        toks.append(nxt)

    rt = TaskRuntime(n_workers=2).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=32).start()
    r = eng.submit(prompt, max_new_tokens=4)
    assert eng.wait(r, timeout=120)
    eng.stop()
    rt.barrier(timeout=30)
    rt.shutdown()
    assert r.tokens[:4] == ref[:4] if len(r.tokens) >= 4 else False


def test_serving_decode_takes_weights_as_arguments():
    """The jitted decode gets the weights as arguments (a closed-over array
    would be lowered as a constant: at full width every weight would sit
    inside the program), donates the cache, and the engine holds its
    weights in cfg.dtype."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = TaskRuntime(n_workers=1).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=32)
    assert {x.dtype for x in jax.tree_util.tree_leaves(eng.params)} == \
        {jnp.dtype(cfg.dtype)}
    toks = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    lowered = eng._decode_fn.lower(eng.params, eng.cache, toks, pos)
    p_info, c_info, _, _ = lowered.args_info[0]
    assert len(jax.tree_util.tree_leaves(p_info)) == \
        len(jax.tree_util.tree_leaves(eng.params))
    assert not any(a.donated for a in jax.tree_util.tree_leaves(p_info))
    assert all(a.donated for a in jax.tree_util.tree_leaves(c_info))
    consts = [ln for ln in lowered.as_text().splitlines()
              if "stablehlo.constant" in ln]
    biggest = max(map(len, consts), default=0)
    assert biggest < 4096, f"an array is baked into the decode ({biggest} B)"
    rt.shutdown()


def test_serving_non_finite_logits_fail_loudly():
    """Greedy argmax of NaN logits would quietly emit token 0: the engine
    raises instead, the group cancels, waiters are released and stop()
    re-raises — in prefill and in the batched decode."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    params["final_norm"]["scale"] = params["final_norm"]["scale"] * jnp.nan
    rt = TaskRuntime(n_workers=2).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=32).start()
    req = eng.submit(np.arange(4), max_new_tokens=3)
    assert eng.wait(req, timeout=60), "client hung after non-finite prefill"
    assert eng.group.cancelled
    with pytest.raises(FloatingPointError, match="prefill"):
        eng.stop()
    rt.barrier(timeout=60)
    with pytest.raises(FloatingPointError):
        rt.shutdown()

    eng = ServeEngine(cfg, params, TaskRuntime(n_workers=1), n_slots=2,
                      max_seq=32)
    eng.active[1] = Request(np.arange(4, dtype=np.int32), tokens=[1])
    with pytest.raises(FloatingPointError, match=r"slots \[1\]"):
        eng._decode_exec([1])

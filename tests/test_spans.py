"""Spans of the CTF-style tracer (core/instrument.py): a disabled tracer
records nothing and opens no profiler annotation; an enabled one records
nested begin/end pairs with their ids; the serving engine, the runtime and
the training loop put them where the work happens."""
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.analyze.lint import run_lint
from repro.configs import get_config
from repro.core import TaskRuntime, Tracer
from repro.core.instrument import EVENTS, span_events
from repro.launch.train import TrainEngine
from repro.models import init_params
from repro.serve import ServeEngine, SimEngine

CHILDREN = {"serve.prefill": ("serve.prefill.forward", "serve.prefill.sync"),
            "serve.decode": ("serve.decode.inputs", "serve.decode.launch",
                             "serve.decode.sync")}


class _NoAnnotation:
    made = 0

    def __init__(self, *a, **kw):
        type(self).made += 1
        raise AssertionError("a disabled tracer opened a TraceAnnotation")


def test_disabled_span_is_the_shared_noop():
    tr = Tracer(enabled=False, annotate=True)
    a, b = tr.span("serve.prefill", 3), tr.span("task", 9, "x")
    assert a is b
    with a as inner:
        assert inner is a
    assert tr.counts() == {} and tr._buffers == []


def test_enabled_span_records_nested_pairs_with_ids():
    tr = Tracer(enabled=True)
    with tr.span("serve.decode", 7):
        with tr.span("serve.decode.sync", 7):
            pass
        tr.event("serve.depth", 2)
    names = {v: k for k, v in EVENTS.items()}
    recs = [(names[e], arg) for _, e, arg in tr._buffers[0][1].records]
    assert recs == [("serve.decode.begin", 7), ("serve.decode.sync.begin", 7),
                    ("serve.decode.sync.end", 7), ("serve.depth", 2),
                    ("serve.decode.end", 7)]
    (p0, p1, pid), = tr.spans("serve.decode")
    (c0, c1, cid), = tr.spans("serve.decode.sync")
    assert pid == cid == 7 and p0 <= c0 <= c1 <= p1
    assert [a for _, a in tr.events("serve.depth")] == [2]
    # a span given no id takes the innermost open span's, else 0
    with tr.span("task", 5):
        with tr.span("serve.emit"):
            pass
    with tr.span("serve.idle"):
        pass
    assert [i for _, _, i in tr.spans("serve.emit")] == [5]
    assert [i for _, _, i in tr.spans("serve.idle")] == [0]
    tr.clear()
    assert tr.spans("serve.decode") == [] and tr.dropped() == 0


def test_same_name_spans_pair_innermost_first():
    tr = Tracer(enabled=True)
    with tr.span("task", 1):
        with tr.span("task", 2):
            pass
    (o0, o1, oid), (i0, i1, iid) = tr.spans("task")
    assert (oid, iid) == (1, 2) and o0 <= i0 <= i1 <= o1
    # the task body keeps the runtime's older catalog pair
    assert span_events("task") == ("task.start", "task.end")


def test_span_name_must_be_in_the_catalog():
    tr = Tracer(enabled=True)
    with pytest.raises(ValueError, match="unregistered"):
        tr.span("made.up")


def test_lint_checks_span_names(tmp_path):
    (tmp_path / "core").mkdir()
    (tmp_path / "core" / "instrument.py").write_text(
        'EVENTS = {"task.start": 1, "task.end": 2, "serve.idle.begin": 3}\n')
    (tmp_path / "core" / "run.py").write_text(textwrap.dedent("""
        def go(tracer, name):
            with tracer.span("task", 1):
                pass
            with tracer.span("serve.idle"):
                pass
            with tracer.span(name):
                pass
    """))
    findings = run_lint([str(tmp_path)])
    # serve.idle has no end event; a non-literal name cannot be checked
    assert [(f.rule, f.line) for f in findings] == [("event-catalog", 5),
                                                    ("event-catalog", 7)]


def _sim_run(tracer, n=6):
    rt = TaskRuntime(n_workers=2, tracer=tracer).start()
    eng = SimEngine(rt, n_slots=2, max_seq=64, prefill_s=0.002,
                    decode_s=0.001).start()
    first = {}

    def on_token(i):
        return lambda _tok: first.setdefault(i, time.monotonic_ns())
    reqs = [eng.submit(np.arange(4), max_new_tokens=3, on_token=on_token(i))
            for i in range(n)]
    for r in reqs:
        assert eng.wait(r, timeout=30)
    assert eng.stop(drain=True, timeout=30)
    assert rt.barrier(timeout=30)
    rt.shutdown()
    return eng, reqs, first


def test_sim_engine_stamps_are_ordered():
    tr = Tracer(enabled=True)
    eng, reqs, first = _sim_run(tr)
    for i, r in enumerate(reqs):
        assert 0 < r.submit_ns <= r.slot_ns <= r.prefill_ns <= first[i]
    # one serve.prefill span per request, entered at its prefill stamp
    pre = {i: t0 for t0, _, i in tr.spans("serve.prefill")}
    assert sorted(pre) == sorted(r.id for r in reqs)
    assert all(r.prefill_ns <= pre[r.id] for r in reqs)
    assert len(tr.spans("serve.idle")) > 0
    assert tr.dropped() == 0


def test_disabled_tracer_records_nothing_on_the_serve_path(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _NoAnnotation)
    _NoAnnotation.made = 0
    tr = Tracer(enabled=False, annotate=True)
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = TaskRuntime(n_workers=2, tracer=tr).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=32).start()
    reqs = [eng.submit(np.arange(4 + i), max_new_tokens=3) for i in range(2)]
    for r in reqs:
        assert eng.wait(r, timeout=120) and len(r.tokens) == 4
        assert r.submit_ns <= r.slot_ns <= r.prefill_ns  # stamps stay on
    assert eng.stop(drain=True, timeout=60)
    rt.shutdown()
    assert _NoAnnotation.made == 0
    assert tr._buffers == [] and tr.counts() == {}


@pytest.fixture(scope="module")
def served():
    """A tiny ServeEngine run with every span recorded from the start."""
    tr = Tracer(enabled=True)
    cfg = get_config("qwen3-1.7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = TaskRuntime(n_workers=3, tracer=tr).start()
    eng = ServeEngine(cfg, params, rt, n_slots=2, max_seq=48).start()
    reqs = [eng.submit(np.arange(4 + i), max_new_tokens=4) for i in range(4)]
    for r in reqs:
        assert eng.wait(r, timeout=120) and len(r.tokens) == 5
    assert eng.stop(drain=True, timeout=60)
    assert rt.barrier(timeout=60)
    tasks_done = rt.counters.snapshot()["tasks_done"]
    rt.shutdown()
    return tr, eng, reqs, tasks_done


def test_span_counts_match_the_engine_stats(served):
    tr, eng, reqs, _ = served
    assert len(tr.spans("serve.prefill")) == eng.stats["prefills"] == 4
    assert len(tr.spans("serve.decode")) == eng.stats["decode_iters"] > 0
    assert len(tr.spans("serve.emit")) == eng.stats["decode_iters"]
    assert len(tr.spans("serve.admit")) >= eng.stats["decode_iters"]
    assert tr.dropped() == 0


@pytest.mark.parametrize("parent", sorted(CHILDREN))
def test_child_spans_nest_in_their_parent(served, parent):
    tr = served[0]
    outer = {i: (t0, t1) for t0, t1, i in tr.spans(parent)}
    assert outer
    for child in CHILDREN[parent]:
        spans = tr.spans(child)
        assert len(spans) == len(outer), child
        for t0, t1, i in spans:
            p0, p1 = outer[i]
            assert p0 <= t0 <= t1 <= p1, (child, i)


def test_every_task_body_is_one_task_span(served):
    tr, eng, _, tasks_done = served
    tasks = tr.spans("task")
    assert len(tasks) == tasks_done
    assert len({i for _, _, i in tasks}) == len(tasks)
    # each decode span lies in the body of the task whose id it carries
    body = {i: (t0, t1) for t0, t1, i in tasks}
    for t0, t1, i in tr.spans("serve.decode"):
        assert body[i][0] <= t0 <= t1 <= body[i][1]


def test_train_step_is_a_span():
    tr = Tracer(enabled=True)
    cfg = get_config("qwen3-1.7b", smoke=True)
    eng = TrainEngine(cfg, batch_size=2, seq_len=16, tracer=tr)
    try:
        eng.run(2, log_every=0)
    finally:
        eng.close()
    assert [i for _, _, i in tr.spans("step")] == [0, 1]

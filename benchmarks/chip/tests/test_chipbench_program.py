"""Readings of the program's own spans: the five readers of metrics/ on
hand-worked inputs, ``idle_by_span`` against the trace's idle time, the
program's spans read out of a CPU profiler trace apart from the
benchmark's, and the host-clock readers on a tiny CPU run with the
program's tracer on."""
import json
import os
import pathlib
import types

import numpy as np
import pytest

import spans
from chipbench import devtrace, harness, program

HERE = pathlib.Path(__file__).resolve().parent
FIXTURES = HERE / "fixtures"
BENCH_FIXTURE = FIXTURES / "bench"


def _read(name, ctx):
    return harness.metric_reader(name)(ctx)


def _hand():
    # device busy [5,30) [40,70) [100,130) [200,260); benchmark events set
    # the window to [0, 280]. Decode steps start at 0, 38, 80 and 215; a
    # prefill [140,210) lies between the last two
    ops = [["%a", 5, 25], ["%b", 40, 30], ["%c", 100, 30], ["%d", 200, 60]]
    prog = [["task", 0, 37, 1], ["serve.decode", 0, 35, 1],
            ["serve.decode.sync", 20, 14, 1], ["serve.emit", 35, 2, 1],
            ["serve.decode", 38, 35, 2], ["serve.decode", 80, 40, 3],
            ["serve.prefill", 140, 70, 9], ["serve.decode", 215, 40, 4]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": [["bench.submit", 0, 3], ["bench.submit", 270, 10]],
            "program": prog}


def test_device_idle_readers_by_hand():
    ctx = types.SimpleNamespace(trace=_hand())
    # stretches [0,38) and [38,80) (the third holds the prefill): 80 long,
    # 55 busy
    assert _read("decode_device_idle", ctx) == pytest.approx(100 * 25 / 80)
    # the prefill [140,210) is busy only over [200,210)
    assert _read("prefill_device_idle", ctx) == pytest.approx(100 * 60 / 70)
    for trace in (None, {k: v for k, v in _hand().items() if k != "program"}):
        ctx = types.SimpleNamespace(trace=trace)
        assert _read("decode_device_idle", ctx) is None
        assert _read("prefill_device_idle", ctx) is None


def test_idle_by_span_by_hand():
    ex = _hand()
    lo, hi = devtrace.window_of(ex)
    assert (lo, hi) == (0, 280)  # program spans do not move the window
    got = dict(program.idle_by_span(ex, lo, hi))
    # [0,5): decode and task open since 0, the shorter is innermost;
    # [30,40) mid 35: emit; [70,100) mid 85: the third decode;
    # [130,200): the prefill; [260,280): no span open
    assert got == {"serve.decode": 35e-9, "serve.emit": 10e-9,
                   "serve.prefill": 70e-9, "none": 20e-9}
    red = devtrace.reduce(ex)
    assert sum(got.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def _reqs(rows):
    return [types.SimpleNamespace(submit_ns=a, slot_ns=b, prefill_ns=c)
            for a, b, c in rows]


def test_request_wait_readers_by_hand():
    ms = 1_000_000
    rows = [(10 * ms, 10 * ms + k * ms, 10 * ms + 3 * k * ms)
            for k in range(1, 21)]
    rows += [(900 * ms, 990 * ms, 999 * ms),  # submitted after the window
             (20 * ms, 0, 0)]                 # never got a slot
    ctx = types.SimpleNamespace(window=(0, 500 * ms), requests=_reqs(rows))
    k = np.arange(1, 21)
    assert _read("slot_wait_p95_ms", ctx) == pytest.approx(
        np.percentile(k, 95))
    assert _read("prefill_wait_p95_ms", ctx) == pytest.approx(
        np.percentile(2 * k, 95))
    empty = types.SimpleNamespace(window=(0, 1))
    assert _read("slot_wait_p95_ms", empty) is None
    assert _read("prefill_wait_p95_ms", empty) is None


class _Tracer:
    """Recorded spans and events, as Tracer.spans / Tracer.events give."""
    enabled = True

    def __init__(self, spans, events):
        self._spans, self._events = spans, events

    def spans(self, name):
        return sorted(self._spans.get(name, []))

    def events(self, name):
        return sorted(self._events.get(name, []))


def test_decode_respawn_by_hand():
    us = 1000
    # bodies 1, 2, 4, 5 ran a decode step; 3 is a prefill between 2 and 4
    tasks = [(0, 100 * us, 1), (130 * us, 200 * us, 2),
             (205 * us, 260 * us, 3), (270 * us, 330 * us, 4),
             (350 * us, 400 * us, 5)]
    dec = [(t0 + us, t1 - us, i) for t0, t1, i in tasks if i != 3]
    ready = [(110 * us, 2), (345 * us, 5), (262 * us, 4)]
    tr = _Tracer({"task": tasks, "serve.decode": dec},
                 {"task.ready": [(ts, i) for ts, i in ready]})
    ctx = types.SimpleNamespace(window=(0, 10**9), tracer=tr)
    # pairs (1, 2) and (4, 5): 30 and 20 us
    assert _read("decode_respawn_us", ctx) == pytest.approx(25.0)
    split = program.respawn_split_us(tr, ctx.window)
    assert split == {"pairs": 2, "release_to_ready_us": 12.5,
                     "ready_to_start_us": 12.5}
    tr.enabled = False
    assert _read("decode_respawn_us", ctx) is None
    assert _read("decode_respawn_us", types.SimpleNamespace(window=(0, 1))) \
        is None


def test_program_spans_do_not_move_the_existing_reduction():
    with open(FIXTURES / "trace_qwen3_chat_v5e.json") as f:
        ex = json.load(f)
    before = devtrace.reduce(ex)
    lo, hi = devtrace.window_of(ex)
    # the decode calls of the benchmark, as program spans
    ex["program"] = [["serve.decode", s, d, i] for i, (n, s, d) in
                     enumerate(ex["host"]) if n == "bench.decode"]
    assert devtrace.reduce(ex) == before
    got = dict(program.idle_by_span(ex, lo, hi))
    assert sum(got.values()) == pytest.approx(
        before["window_s"] - before["busy_s"], rel=1e-9)
    # the benchmark's decode label and the program's decode span agree
    assert got["serve.decode"] == pytest.approx(
        dict(before["breakdown"]["idle_gaps"])["decode"], rel=1e-9)


def test_program_spans_come_back_apart_from_the_benchmarks(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import TaskRuntime, Tracer
    from repro.serve import SimEngine
    tr = Tracer(enabled=True, annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    rt = TaskRuntime(n_workers=2, tracer=tr).start()
    try:
        eng = SimEngine(rt, n_slots=2, max_seq=64, prefill_s=0.002,
                        decode_s=0.002).start()
        with TraceAnnotation("bench.submit"):
            reqs = [eng.submit(np.arange(4), max_new_tokens=3)
                    for _ in range(3)]
        for r in reqs:
            assert eng.wait(r, timeout=30)
        assert eng.stop(drain=True, timeout=30)
    finally:
        jax.profiler.stop_trace()
        rt.shutdown()
    path = devtrace.find_xplane(str(tmp_path))
    assert [h[0] for h in devtrace.extract(path)["host"]] == ["bench.submit"]
    prog = program.extract(path)
    for name in ("serve.prefill", "serve.decode", "serve.emit", "task"):
        ids = sorted(i for n, _, _, i in prog if n == name)
        assert ids == sorted(i for _, _, i in tr.spans(name)), name
    assert sorted({i for n, _, _, i in prog if n == "serve.prefill"}) == \
        sorted(r.id for r in reqs)


def test_host_readers_on_a_tiny_run_with_the_tracer_on():
    cell = harness.load_cell("tiny.chat", BENCH_FIXTURE / "spec.json",
                             BENCH_FIXTURE)
    hold = {}
    served, _ = harness.serve(cell, 2**31 + 91, 1.5, trace=False,
                              engine_cls=spans.traced_engine(False, hold))
    tracer = hold["tracer"]
    assert tracer.enabled and tracer.dropped() == 0
    # on the CPU the tiny cell's prefills fill most of a short window:
    # read the whole run
    ctx = types.SimpleNamespace(
        window=(0, 2**63), tracer=tracer, trace=None,
        requests=[s.req for s in served.client.sent if s.req is not None])
    for name in ("slot_wait_p95_ms", "prefill_wait_p95_ms",
                 "decode_respawn_us"):
        v = _read(name, ctx)
        assert v is not None and v > 0, name
    times = program.host_times_us(tracer, ctx.window)
    assert times["serve.decode"][0] == len(served.decode_spans)
    assert times["serve.prefill"][0] == len(served.prefill_spans)


def test_recorded_trace_with_program_spans():
    with open(FIXTURES / "trace_qwen3_chat_v5e_spans.json") as f:
        ex = json.load(f)
    red = devtrace.reduce(ex)
    assert devtrace.reduce({k: v for k, v in ex.items()
                            if k != "program"}) == red
    lo, hi = devtrace.window_of(ex)
    got = dict(program.idle_by_span(ex, lo, hi))
    idle = red["window_s"] - red["busy_s"]
    assert sum(got.values()) == pytest.approx(idle, rel=1e-6)
    # the prefill's idle is the host re-tracing its scan in the forward
    assert got["serve.prefill.forward"] > 0.8 * dict(
        red["breakdown"]["idle_gaps"])["prefill"]
    ctx = types.SimpleNamespace(trace=ex)
    # prefill_mfu about 6% here: the device idles through most of it
    assert 70 < _read("prefill_device_idle", ctx) < 98
    # about 3.5 ms of host work around a 38 ms decode program
    assert 3 < _read("decode_device_idle", ctx) < 15
    # one task span around each decode step, with the step's id
    tasks = {i: (s, s + d) for n, s, d, i in ex["program"] if n == "task"}
    for n, s, d, i in ex["program"]:
        if n == "serve.decode" and i in tasks:
            assert tasks[i][0] <= s and s + d <= tasks[i][1]

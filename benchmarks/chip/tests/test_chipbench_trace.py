"""The reduction from a profiler trace to busy time, idle share, program
time and idle gaps: on hand-worked events and on a trace recorded on a TPU
v5e (qwen3_1_7b.chat), checked against an independent timeline."""
import json
import os

import numpy as np

from chipbench import devtrace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_qwen3_chat_v5e.json")


def _hand():
    # device: a loop op [0, 100) holding body ops; a lone op [150, 170);
    # an op [160, 190) overlapping it; idle [100, 150) and [190, 200)
    ops = [["%while.1 = (s32[]) while(...)", 0, 100],
           ["%dot.1 = bf16[8,8]{1,0} dot(...)", 10, 30],
           ["%dot.1 = bf16[8,8]{1,0} dot(...)", 50, 30],
           ["%copy.2 = bf16[4]{0} copy(...)", 150, 20],
           ["%copy.3 = bf16[4]{0} copy(...)", 160, 30]]
    modules = [["jit_step(1)", 0, 100], ["jit_splice(2)", 120, 5],
               ["jit_step(1)", 150, 40]]
    host = [["bench.decode", -5, 110], ["bench.decode", 110, 30],
            ["bench.decode", 145, 50], ["bench.prefill", 95, 15]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_union_and_busy_by_hand():
    assert devtrace.union([(0, 10), (5, 20), (30, 40)], 0, 35) == \
        [[0, 20], [30, 35]]
    ex = _hand()
    ops = ex["devices"]["/device:TPU:0"]["ops"]
    assert devtrace.busy_ns(ops, 0, 200) == 100 + 40
    assert devtrace.busy_ns(ops, 50, 165) == 50 + 15


def test_reduce_by_hand():
    red = devtrace.reduce(_hand())
    lo, hi = -5, 195  # first and last event recorded
    assert red["window_s"] == (hi - lo) / 1e9
    assert red["busy_s"] == 140 / 1e9
    idle = dict(red["breakdown"]["idle_gaps"])
    # idle: [-5, 0) in a decode call, [100, 150) mid 125 in a decode call,
    # [190, 195) in a decode call
    assert idle["decode"] == (5 + 50 + 5) / 1e9
    assert "prefill" not in idle
    ops = dict(red["breakdown"]["device_ops"])
    assert "%while.1 (s32[])" not in ops  # the loop holds its body's ops
    assert ops["%dot.1 bf16[8,8]"] == 60 / 1e9
    assert ops["%copy.3 bf16[4]"] == 30 / 1e9


def test_program_times_picks_the_program_the_call_launches():
    # the splice queued by a prefill starts inside a decode call once; the
    # decode program starts in every decode call
    assert sorted(devtrace.program_times_ns(_hand(), "bench.decode")) == \
        [40, 100]


def _timeline_busy(ops, lo, hi):
    t = np.zeros(int(hi - lo) // 1000 + 1, bool)  # 1 us resolution
    for _, s, d in ops:
        a, b = int((s - lo) // 1000), int((s + d - lo) // 1000)
        t[max(a, 0):max(b, 0)] = True
    return t.sum() * 1000


def test_recorded_trace():
    with open(FIXTURE) as f:
        ex = json.load(f)
    dev = ex["devices"]["/device:TPU:0"]
    lo, hi = devtrace.window_of(ex)
    busy = devtrace.busy_ns(dev["ops"], lo, hi)
    # an independent count at 1 us resolution agrees to a few us per gap
    assert abs(busy - _timeline_busy(dev["ops"], lo, hi)) < 0.002 * (hi - lo)
    red = devtrace.reduce(ex)
    assert red["busy_s"] == busy / 1e9
    idle = 1 - red["busy_s"] / red["window_s"]
    # the window holds a prefill whose scan the host re-traces for ~140 ms
    assert 0.4 < idle < 0.7
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["prefill"] > 0.1 and gaps["longest.prefill"] > 0.1
    # the decode program: one run per decode call that starts on the
    # device inside it, about 38 ms each on this chip
    dec = devtrace.program_times_ns(ex, "bench.decode")
    n_calls = sum(n == "bench.decode" for n, _, _ in ex["host"])
    assert len(dec) == n_calls == 3
    assert all(37e6 < d < 39e6 for d in dec)
    # the prefill's cache splice, queued before the third decode call,
    # starts inside it too and is not taken for the decode program
    splice = [d for n, s, d in dev["modules"]
              if n.startswith("jit_dynamic_update_slice")]
    assert len(splice) == 2 and not set(splice) & set(dec)


def test_extract_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.decode"):
        f(x).block_until_ready()
    with TraceAnnotation("not.ours"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ex = devtrace.extract(devtrace.find_xplane(str(tmp_path)))
    assert [h[0] for h in ex["host"]] == ["bench.decode"]
    assert ex["host"][0][2] > 0
    assert ex["devices"] == {}  # no accelerator plane on the CPU

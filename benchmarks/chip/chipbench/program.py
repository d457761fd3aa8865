"""The program's own spans (``repro.core.instrument.Tracer``) as the
benchmark reads them.

With ``annotate=True`` the tracer mirrors every span into the profiler
trace: the ``serve.*`` spans of the serving engine, ``task`` (one task body
of the runtime) and ``step`` (one training step), each with its id.
``extract`` reads them out of a trace as ``[name, start_ns, duration_ns,
id]``, on the profiler's clock; a run keeps them under the key
``"program"`` of the ``devtrace.extract`` result, apart from the
benchmark's own ``bench.*`` events under ``"host"``, so that nothing the
existing reduction reads moves. The rest reduces them against the device
ops: idle time by the innermost span open, and the device idle share
inside prefills and between decode launches.
"""
from __future__ import annotations

import bisect
import collections
import heapq

from chipbench.devtrace import union

PREFIX = "serve."
NAMES = ("task", "step")
NONE = "none"  # an idle gap with no program span open at its middle


def extract(path: str) -> list:
    """[[name, start_ns, duration_ns, id]] of the program's spans in the
    ``.xplane.pb`` at ``path``, from every host plane."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX) or ev.name in NAMES:
                    out.append([ev.name, ev.start_ns, ev.duration_ns,
                                int(dict(ev.stats).get("id", 0))])
    return out


def _of(ex: dict, name: str) -> list:
    return [(s, s + d) for n, s, d, _ in ex["program"] if n == name]


def _busy(ex: dict, lo: float, hi: float) -> list:
    """Merged intervals in which an op runs, per device."""
    return [union(((s, s + d) for _, s, d in dev["ops"]), lo, hi)
            for dev in ex["devices"].values()]


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two lists of merged intervals."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_share(ex: dict, stretches: list, lo: float, hi: float):
    """Percent of the union of ``stretches`` in [lo, hi] during which no
    device op runs, over the devices; None where the stretches are empty."""
    m = union(stretches, lo, hi)
    total = sum(e - s for s, e in m)
    if not total:
        return None
    busy = [_overlap(m, b) for b in _busy(ex, lo, hi)]
    return 100.0 * (1.0 - sum(busy) / len(busy) / total)


def prefill_stretches(ex: dict) -> list:
    return _of(ex, "serve.prefill")


def decode_stretches(ex: dict) -> list:
    """From each ``serve.decode`` start to the next, where no
    ``serve.prefill`` overlaps the stretch: one decode step and the host
    work around it."""
    starts = sorted(s for s, _ in _of(ex, "serve.decode"))
    pre = _of(ex, "serve.prefill")
    return [(a, b) for a, b in zip(starts, starts[1:])
            if not any(p0 < b and p1 > a for p0, p1 in pre)]


def _innermost(spans: list) -> tuple:
    """(points, labels): the timeline cut at every span edge; labels[i] is
    the innermost span open over [points[i], points[i + 1]) (the latest
    started; of two that start together, the shorter), or NONE."""
    points = sorted({t for _, s, d, _ in spans for t in (s, s + d)})
    by_start = sorted(spans, key=lambda e: e[1])
    heap: list = []
    labels = []
    j = 0
    for p in points:
        while j < len(by_start) and by_start[j][1] <= p:
            n, s, d, _ = by_start[j]
            heapq.heappush(heap, (-s, s + d, n))
            j += 1
        while heap and heap[0][1] <= p:
            heapq.heappop(heap)
        labels.append(heap[0][2] if heap else NONE)
    return points, labels


def idle_by_span(ex: dict, lo: float, hi: float) -> list:
    """[[span, seconds]]: device idle time in [lo, hi] summed by the
    innermost program span open at each gap's middle (NONE where no span
    is open), most first. Over the devices, the entries sum to the idle
    time; without program spans everything falls under NONE."""
    points, labels = _innermost(ex.get("program") or [])
    out: dict = collections.Counter()
    for busy in _busy(ex, lo, hi):
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            i = bisect.bisect_right(points, (s + e) / 2) - 1
            out[labels[i] if i >= 0 else NONE] += e - s
    return [[k, v / 1e9] for k, v in out.most_common()]


# ------------------------------------------------------------ host clock
def in_window(spans: list, window: tuple) -> list:
    lo, hi = window
    return [sp for sp in spans if lo <= sp[0] and sp[1] <= hi]


def respawn_pairs(tracer, window: tuple) -> list:
    """[(end_ns of decode body N, start_ns of body N + 1, id of N + 1)]
    over task bodies in the window that follow each other with no other
    task body starting between them, both decode iterations that ran a
    step (their ids are ``serve.decode`` ids)."""
    decode = {i for _, _, i in tracer.spans("serve.decode")}
    tasks = in_window(tracer.spans("task"), window)
    return [(a[1], b[0], b[2]) for a, b in zip(tasks, tasks[1:])
            if a[2] in decode and b[2] in decode]


def respawn_split_us(tracer, window: tuple) -> dict:
    """Mean µs from one decode body's end to the next one's start, split at
    the next task's ``task.ready`` event: dependency release until ready,
    then scheduling and wake until its body starts."""
    ready = dict((arg, ts) for ts, arg in tracer.events("task.ready"))
    pairs = [(end, start, ready[i]) for end, start, i in
             respawn_pairs(tracer, window) if i in ready]
    if not pairs:
        return {}
    n = len(pairs)
    return {"pairs": n,
            "release_to_ready_us": sum(r - e for e, _, r in pairs) / n / 1e3,
            "ready_to_start_us": sum(s - r for _, s, r in pairs) / n / 1e3}


HOST_SPANS = ("task", "serve.decode", "serve.decode.inputs",
              "serve.decode.launch", "serve.decode.sync", "serve.emit",
              "serve.admit", "serve.idle", "serve.prefill",
              "serve.prefill.forward", "serve.prefill.sync",
              "serve.prefill.splice")


def host_times_us(tracer, window: tuple) -> dict:
    """{span: [count, mean µs, median µs]} of each span closed in the
    window."""
    out = {}
    for name in HOST_SPANS:
        d = sorted((t1 - t0) / 1e3 for t0, t1, _ in
                   in_window(tracer.spans(name), window))
        if d:
            out[name] = [len(d), sum(d) / len(d), d[len(d) // 2]]
    return out

"""Reduction from a profiler trace to device busy time, program time and the
idle gaps by what the host was doing.

``extract`` reads the ``.xplane.pb`` the JAX profiler writes and keeps only
what the reduction needs, as plain lists: the device operations ("XLA Ops"
line) and programs ("XLA Modules" line) of each accelerator plane, and the
benchmark's own host annotations (``bench.*``). Times are nanoseconds on the
profiler's clock, which the host annotations share.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# an idle gap is labelled by the benchmark span open at its middle
GAP_LABELS = (("bench.prefill", "prefill"), ("bench.decode", "decode"),
              ("bench.submit", "generator"))


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(found)}")
    return found[0]


def extract(path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}, each event [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key].extend([ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([ev.name, ev.start_ns, ev.duration_ns]
                                   for ev in line.events
                                   if ev.name.startswith(HOST_PREFIX))
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] intervals, clipped to [lo, hi]."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((st, st + d) for _, st, d in events),
                                       lo, hi))


def window_of(ex: dict) -> tuple:
    """The traced window: from the first to the last event recorded."""
    starts, ends = [], []
    for dev in ex["devices"].values():
        for _, s, d in dev["ops"] + dev["modules"]:
            starts.append(s)
            ends.append(s + d)
    for _, s, d in ex["host"]:
        starts.append(s)
        ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no events")
    return min(starts), max(ends)


def op_label(name: str) -> str:
    """'%copy.79 = bf16[28,8,4096,8,128]{...} copy(...)' -> '%copy.79
    bf16[28,8,4096,8,128]': the op and its result type, without layouts."""
    head, _, rest = name.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0]}".strip()


def leaf_events(events) -> list:
    """Events that hold no other event of their line (a loop's own event
    spans the ops of its body, which are events of their own)."""
    ev = sorted(events, key=lambda e: e[1])
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= e[1] + e[2]]


def program_times_ns(ex: dict, annotation: str) -> list:
    """Device durations of the program that the host call ``annotation``
    launches: of the programs that start on the device while such a call
    is open, the one that starts in the most calls. (Work queued before a
    call, such as a prefill's cache splice, may also start inside it.)"""
    m = union(((s, s + d) for n, s, d in ex["host"] if n == annotation),
              float("-inf"), float("inf"))
    starts = [a for a, _ in m]
    runs = collections.defaultdict(list)
    calls = collections.defaultdict(set)
    for dev in ex["devices"].values():
        for name, s, d in dev["modules"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < m[i][1]:
                runs[name].append(d)
                calls[name].add(i)
    if not runs:
        return []
    best = max(runs, key=lambda k: (len(calls[k]), sum(runs[k])))
    return runs[best]


def top_ops(ex: dict, n: int = 10) -> list:
    """[[op, seconds]] of the ops that took most device time, summed over
    the devices and over every run of the op."""
    tot: dict = collections.Counter()
    for dev in ex["devices"].values():
        for name, _, d in leaf_events(dev["ops"]):
            tot[op_label(name)] += d
    return [[k, v / 1e9] for k, v in tot.most_common(n)]


def _covers(merged: list, starts: list, t: float) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < merged[i][1]


def idle_gaps(ex: dict, lo: float, hi: float, n: int = 10) -> list:
    """[[label, seconds]]: device idle time summed by what the host was in
    at each gap's middle (prefill, decode, generator, or none of these:
    'gap'), then the single longest gap of each label."""
    open_spans = []
    for name, tag in GAP_LABELS:
        m = union(((s, s + d) for n_, s, d in ex["host"] if n_ == name),
                  lo, hi)
        open_spans.append((tag, m, [a for a, _ in m]))
    per_label: dict = collections.Counter()
    longest: dict = {}
    for dev in ex["devices"].values():
        busy = union(((s, s + d) for _, s, d in dev["ops"]), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            label = next((tag for tag, m, st in open_spans
                          if _covers(m, st, mid)), "gap")
            per_label[label] += e - s
            longest[label] = max(longest.get(label, 0.0), e - s)
    out = [[k, v / 1e9] for k, v in per_label.most_common()]
    out += [[f"longest.{k}", v / 1e9]
            for k, v in sorted(longest.items(), key=lambda kv: -kv[1])]
    return out[:n]


def reduce(ex: dict) -> dict:
    """Busy and window seconds averaged over the devices, and the
    breakdown the result line carries."""
    lo, hi = window_of(ex)
    devs = list(ex["devices"].values())
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = sum(busy_ns(d["ops"], lo, hi) for d in devs) / len(devs)
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "breakdown": {"device_ops": top_ops(ex),
                          "idle_gaps": idle_gaps(ex, lo, hi)}}

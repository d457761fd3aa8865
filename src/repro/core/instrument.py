"""Lightweight CTF-style instrumentation (paper §5).

Per-worker ring buffers of fixed-size binary records (ts_ns, event_id, arg),
no locks on the hot path (each worker owns its buffer; the GIL provides the
ordering the per-core buffers have natively in C). Buffers flush to one
binary file per worker — a time-ordered event subset, CTF's layout — plus a
JSON metadata file mapping event ids to names (the CTF metadata analogue).

Spans (``Tracer.span``) are a pair of catalog events, begin and end, with
the same id. A tracer built with ``annotate=True`` also opens a
``jax.profiler.TraceAnnotation`` per span, so the span lands in a profiler
trace on the device trace's clock, where a device-idle gap can be put down
to what the program was doing.
"""
from __future__ import annotations

import json
import os
import struct
import threading
import time
from typing import Optional

_REC = struct.Struct("<qii")  # ts_ns, event_id, arg

EVENTS = {
    "task.create": 1,
    "task.ready": 2,
    "task.start": 3,        # span "task": one task body (arg: task id)
    "task.end": 4,
    "dep.unregister": 6,
    "sched.add": 7,
    "sched.delegated": 9,
    "sched.served": 10,
    "worker.idle": 11,
    "worker.park": 12,
    "ckpt.begin": 14,
    "ckpt.end": 15,
    "data.prefetch": 16,
    "step.begin": 17,       # span "step" (arg: step)
    "step.end": 18,
    "worker.wake": 19,      # single-wake delivered to a parked worker
    "task.cancel": 20,      # group-cancelled task dropped (spawn or dequeue)
    "group.cancel": 21,     # TaskGroup.cancel() (arg: outstanding count)
    "sched.add_fallback": 22,  # producer blocked as DTLock ticket waiter
    "san.violation": 23,    # tasksan finding recorded (arg: running total)
    "explore.switch": 24,   # taskcheck: policy preempted the running thread
    "explore.expire": 25,   # taskcheck: policy force-expired a timed wait
    "explore.schedule": 26,  # taskcheck: one explored schedule finished
    "explore.replay": 27,   # taskcheck: a recorded trace was replayed
    "deadlock.cycle": 28,   # taskcheck: wait-for / lock-order cycle found
    "deadlock.livelock": 29,  # taskcheck: no-progress watchdog fired
    "ws.claim": 30,         # worksharing chunk claimed (arg: chunk index)
    "ws.finalize": 31,      # worksharing descriptor finalized by the last
                            # participant out (arg: task id)
    "serve.submit": 32,     # request handed to the router (arg: shard id)
    "serve.admit": 33,      # request accepted into a shard queue (arg: shard)
    "serve.shed": 34,       # affinity shard full, redirected (arg: shard)
    "serve.reject": 35,     # every shard full, request refused (arg: shard)
    "serve.depth": 36,      # admission-queue depth sample (arg: depth);
                            # emitted from the owning shard's threads, so
                            # per-worker streams separate shards
    "serve.complete": 37,   # request finished (arg: latency in µs)
    "serve.migrate.begin": 38,   # hash-slot migration started (arg: hslot)
    "serve.migrate.commit": 39,  # routing table flipped to dst (arg: hslot)
    "serve.migrate.abort": 40,   # migration cancelled/failed; src retained
                                 # ownership (arg: hslot)
    "tune.signal": 41,      # pathology detected by the online detector
                            # (arg: repro.core.tune.SIGNAL_IDS code)
    "tune.switch": 42,      # scheduler/policy hot-swap committed
                            # (arg: drained task count moved across)
    "tune.knob": 43,        # runtime knob adjusted (park bounds, wake
                            # fan-out, EWMA mult); arg: KNOB_IDS code
    # EngineCore spans (arg: the request id for prefill; the others take
    # the id of the task span they open in, the decode task's)
    "serve.prefill.begin": 44,  # _prefill_exec of one request
    "serve.prefill.end": 45,
    "serve.decode.begin": 46,   # _decode_exec of one batched iteration
    "serve.decode.end": 47,
    "serve.emit.begin": 48,     # per-slot tokens, callbacks and retires
    "serve.emit.end": 49,
    "serve.admit.begin": 50,    # queued requests moved into free slots
    "serve.admit.end": 51,
    "serve.idle.begin": 52,     # the decode loop's backoff with no slot live
    "serve.idle.end": 53,
    # ServeEngine child spans (same ids as their parent)
    "serve.prefill.forward.begin": 54,  # the bucket's prefill program's
    "serve.prefill.forward.end": 55,    # launch (forward, head, splice)
    "serve.prefill.sync.begin": 56,     # first token and finite flag read
    "serve.prefill.sync.end": 57,       # back: waits out the program
    "serve.prefill.splice.begin": 58,   # no longer recorded: the splice
    "serve.prefill.splice.end": 59,     # runs inside the prefill program
    "serve.decode.inputs.begin": 60,    # token and position arrays
    "serve.decode.inputs.end": 61,
    "serve.decode.launch.begin": 62,    # the jitted step's call
    "serve.decode.launch.end": 63,
    "serve.decode.sync.begin": 64,      # finite flags and tokens read back
    "serve.decode.sync.end": 65,
    "serve.prefill.pad": 66,    # one per prefill (arg: pad tokens between
                                # the prompt and its length bucket)
}

# A span named X records the catalog events "X.begin" and "X.end"; the task
# body keeps the runtime's older pair.
_SPAN_ALIASES = {"task": ("task.start", "task.end")}


def span_events(name: str) -> tuple:
    """The (begin, end) catalog event names of span ``name``."""
    return _SPAN_ALIASES.get(name) or (name + ".begin", name + ".end")


def register_event(name: str) -> int:
    """Register a new event name in the catalog and return its id.

    Every ``Tracer.event`` name must come from the catalog — ad-hoc strings
    silently mapped to id 0, which made traces unparseable and let call
    sites drift. Extensions (experiments, downstream subsystems) register
    here once at import time instead of inventing names inline."""
    eid = EVENTS.get(name)
    if eid is None:
        eid = max(EVENTS.values(), default=0) + 1
        EVENTS[name] = eid
    return eid


class _WorkerBuffer:
    __slots__ = ("records", "capacity", "dropped", "open_ids")

    def __init__(self, capacity: int):
        self.records: list = []
        self.capacity = capacity
        self.dropped = 0
        self.open_ids: list = []  # ids of this thread's open spans

    def append(self, rec):
        if len(self.records) < self.capacity:
            self.records.append(rec)
        else:
            self.dropped += 1


class _NoSpan:
    """The span a disabled tracer hands out: one shared, stateless object."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("buf", "ids", "arg", "ann")

    def __init__(self, buf, ids, arg, ann):
        self.buf, self.ids, self.arg, self.ann = buf, ids, arg, ann

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.buf.open_ids.append(self.arg)
        self.buf.append((time.monotonic_ns(), self.ids[0], self.arg))
        return self

    def __exit__(self, *exc):
        self.buf.append((time.monotonic_ns(), self.ids[1], self.arg))
        self.buf.open_ids.pop()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Tracer:
    """enabled=False costs a single attribute check per event or span call.

    ``annotate=True`` mirrors each span into the JAX profiler's trace (jax
    is imported on the first annotated span, so ``core/`` runs without it).
    Records stay in memory until ``flush()``; ``spans()`` and ``events()``
    read them back on the host's monotonic clock."""

    def __init__(self, enabled: bool = False, capacity_per_worker: int = 1 << 16,
                 out_dir: Optional[str] = None, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self.capacity = capacity_per_worker
        self.out_dir = out_dir
        self._tls = threading.local()
        self._buffers: list[tuple[int, _WorkerBuffer]] = []
        self._buffers_lock = threading.Lock()
        self._span_ids: dict = {}

    def _buf(self) -> _WorkerBuffer:
        b = getattr(self._tls, "buf", None)
        if b is None:
            b = _WorkerBuffer(self.capacity)
            self._tls.buf = b
            with self._buffers_lock:
                self._buffers.append((threading.get_ident(), b))
        return b

    @staticmethod
    def _eid(name: str) -> int:
        eid = EVENTS.get(name)
        if eid is None:
            # an unregistered name would serialize as id 0 and be
            # unrecoverable from the binary stream; fail at the call site
            raise ValueError(
                f"unregistered trace event {name!r}: add it to "
                "repro.core.instrument.EVENTS or call register_event()")
        return eid

    def event(self, name: str, arg: int = 0):
        if not self.enabled:
            return
        self._buf().append((time.monotonic_ns(), self._eid(name), int(arg)))

    def span(self, name: str, id: Optional[int] = None, label: str = ""):
        """Context manager around one stretch of work: begin and end records
        with ``id`` as their arg (by default the id of the innermost span
        open on this thread, else 0), and, when annotating, a
        TraceAnnotation ``name`` carrying ``id`` (and ``label``, where
        given)."""
        if not self.enabled:
            return _NO_SPAN
        ids = self._span_ids.get(name)
        if ids is None:
            ids = self._span_ids[name] = tuple(
                self._eid(e) for e in span_events(name))
        buf = self._buf()
        if id is None:
            id = buf.open_ids[-1] if buf.open_ids else 0
        ann = None
        if self.annotate:
            from jax.profiler import TraceAnnotation
            ann = TraceAnnotation(name, id=id, label=label) if label \
                else TraceAnnotation(name, id=id)
        return _Span(buf, ids, int(id), ann)

    # ---------------------------------------------------------------- read
    def _records(self) -> list:
        with self._buffers_lock:
            return [buf.records for _, buf in self._buffers]

    def events(self, name: str) -> list:
        """[(ts_ns, arg)] of every record of catalog event ``name``."""
        eid = self._eid(name)
        return sorted((ts, arg) for recs in self._records()
                      for ts, e, arg in list(recs) if e == eid)

    def spans(self, name: str) -> list:
        """[(t0_ns, t1_ns, id)] of every closed span ``name``, by start.
        Begin and end pair up per thread, innermost first."""
        begin, end = (self._eid(e) for e in span_events(name))
        out = []
        for recs in self._records():
            open_: list = []
            for ts, e, arg in list(recs):
                if e == begin:
                    open_.append(ts)
                elif e == end and open_:
                    out.append((open_.pop(), ts, arg))
        return sorted(out)

    def dropped(self) -> int:
        """Records refused by full buffers since the last ``clear()``."""
        with self._buffers_lock:
            return sum(buf.dropped for _, buf in self._buffers)

    def clear(self) -> None:
        """Drop every record so far (such as a warm-up's)."""
        with self._buffers_lock:
            for _, buf in self._buffers:
                buf.records.clear()
                buf.dropped = 0

    # ---------------------------------------------------------------- dump
    def flush(self, out_dir: Optional[str] = None) -> Optional[str]:
        out_dir = out_dir or self.out_dir
        if not out_dir or not self.enabled:
            return None
        os.makedirs(out_dir, exist_ok=True)
        meta = {"events": EVENTS, "record": "<qii (ts_ns, event_id, arg)",
                "workers": []}
        with self._buffers_lock:
            buffers = list(self._buffers)
        for tid, buf in buffers:
            path = os.path.join(out_dir, f"stream_{tid}.bin")
            with open(path, "wb") as f:
                for rec in buf.records:
                    f.write(_REC.pack(*rec))
            meta["workers"].append({"tid": tid, "file": os.path.basename(path),
                                    "n": len(buf.records),
                                    "dropped": buf.dropped})
        with open(os.path.join(out_dir, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=1)
        return out_dir

    def counts(self) -> dict:
        out: dict = {}
        with self._buffers_lock:
            buffers = list(self._buffers)
        inv = {v: k for k, v in EVENTS.items()}
        for _, buf in buffers:
            for _, eid, _ in buf.records:
                k = inv.get(eid, str(eid))
                out[k] = out.get(k, 0) + 1
        return out


# --------------------------------------------------------------- counters
# The tracer above records *per-event* call sites: great for offline
# analysis, but a controller that samples the runtime tens of times per
# second must not pay a callback per event. The counter plane is the
# near-zero-overhead alternative: per-worker counter structs (one writer
# each — the owning worker thread — so plain int `+=` is exact under the
# GIL) that hot paths bump unconditionally and a controller thread *samples*
# by reading the attributes racily. Reads of ints/floats cannot tear under
# the GIL; a sample is at worst one increment stale per counter.

_EWMA_TASK_ALPHA = 0.08  # task-duration smoothing (and its square, for CV)


class WorkerCounters:
    """One worker's counter cache line. Single writer (the owning worker);
    any thread may read. ``shared`` instances (wid < 0) are multi-writer
    and therefore racy-but-monotonic: a lost increment under-counts, which
    the detector tolerates (rates, not ledgers)."""

    __slots__ = ("wid", "tasks_done", "tasks_cancelled", "chunks_done",
                 "busy_ns", "ewma_task_ns", "ewma_task_sq",
                 "steals_hit", "steals_miss", "delegated", "served",
                 "fallbacks", "created")

    def __init__(self, wid: int = -1):
        self.wid = wid
        self.tasks_done = 0       # task bodies run to completion
        self.tasks_cancelled = 0  # dropped-at-dequeue group members
        self.chunks_done = 0      # worksharing chunks executed
        self.busy_ns = 0          # total body wall time
        self.ewma_task_ns = 0.0   # smoothed task duration
        self.ewma_task_sq = 0.0   # smoothed squared duration (bimodality)
        self.steals_hit = 0       # work-stealing: steal found a task
        self.steals_miss = 0      # work-stealing: full victim scan empty
        self.delegated = 0        # delegation: task served while waiting
        self.served = 0           # delegation: tasks served to waiters
        self.fallbacks = 0        # producer blocked as DTLock ticket waiter
        self.created = 0          # tasks spawned by this thread class

    def on_task(self, dur_ns: int) -> None:
        """Task body finished; fold its duration into the EWMAs."""
        self.tasks_done += 1
        self.busy_ns += dur_ns
        e = self.ewma_task_ns
        if e == 0.0:
            self.ewma_task_ns = float(dur_ns)
            self.ewma_task_sq = float(dur_ns) * dur_ns
        else:
            self.ewma_task_ns = e + _EWMA_TASK_ALPHA * (dur_ns - e)
            self.ewma_task_sq += _EWMA_TASK_ALPHA * \
                (float(dur_ns) * dur_ns - self.ewma_task_sq)


class CounterPlane:
    """Per-worker counter structs plus one shared struct for threads that
    are not runtime workers (external producers, the switch drainer).
    ``snapshot()`` merges everything into one flat dict — the controller
    diffs two snapshots to get rates; see ``repro.core.tune``."""

    __slots__ = ("workers", "shared")

    def __init__(self, n_workers: int):
        self.workers = [WorkerCounters(w) for w in range(max(1, n_workers))]
        self.shared = WorkerCounters(-1)

    def w(self, wid) -> WorkerCounters:
        """The struct a hot site should bump: the owning worker's, or the
        shared one when the caller is not a worker thread (or uses a
        synthetic out-of-range id, like the switch drainer)."""
        workers = self.workers
        if wid is not None and 0 <= wid < len(workers):
            return workers[wid]
        return self.shared

    _SUM_FIELDS = ("tasks_done", "tasks_cancelled", "chunks_done", "busy_ns",
                   "steals_hit", "steals_miss", "delegated", "served",
                   "fallbacks", "created")

    def snapshot(self) -> dict:
        """Racy but tear-free merged view (see class docstring)."""
        out = {k: getattr(self.shared, k) for k in self._SUM_FIELDS}
        ewma_max = 0.0
        ewma_sq = 0.0
        nested = 0
        for wc in self.workers:
            for k in self._SUM_FIELDS:
                out[k] += getattr(wc, k)
            nested += wc.created
            if wc.ewma_task_ns > ewma_max:
                ewma_max = wc.ewma_task_ns
                ewma_sq = wc.ewma_task_sq
        # worker-side spawns only (shared.created is external producers):
        # the detector's nested-production ratio needs the split
        out["nested_created"] = nested
        # the busiest worker's EWMA pair: per-worker streams are single-
        # writer exact, and max() picks the stream that saw real work
        out["ewma_task_ns"] = ewma_max
        out["ewma_task_sq"] = ewma_sq
        return out


#!/usr/bin/env python3
"""One cell served with the program's own tracer on: the readings of its
spans, request stamps and, with ``--profile``, the device idle time they
account for.

    python3 benchmarks/chip/spans.py --workload <cell> --seeds 1,2 \
        --seconds 51 [--profile | --tracer-off] [--out spans.jsonl] \
        [--save-trace <dir>]

Each seed serves the cell's traffic through the same path a run takes, with
the runtime's ``Tracer`` enabled (and mirrored into the profiler with
``--profile``, which records the window's first seconds as a traced run
does). Per seed it prints one JSON line: the end-to-end metrics and the
benchmark's own per-layer metrics (``--tracer-off`` on the same seed gives
the tracer's cost), the readers of ``metrics/`` that read the program's
spans and stamps, the count, mean and median host time of each span, the
split of the respawn between two decode bodies, ``idle_by_span`` and the
tracer's dropped records. ``--save-trace``
keeps each traced window's extract (``devtrace.extract`` plus the
program's spans under ``"program"``) as gzipped JSON. The benchmark's runs
never call this.
"""
import argparse
import dataclasses
import gzip
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

from chipbench import devtrace, harness, program  # noqa: E402
from chipbench.engine import SpanEngine, now_ns  # noqa: E402
from chipbench.work import Shapes  # noqa: E402

PROGRAM_METRICS = ("slot_wait_p95_ms", "prefill_wait_p95_ms",
                   "decode_respawn_us", "prefill_device_idle",
                   "decode_device_idle")


def traced_engine(annotate: bool, out: dict, enabled: bool = True):
    """A SpanEngine whose runtime tracer is on (unless ``enabled`` is
    False); ``out["tracer"]`` is set to it. Warm-up records go where the
    engine's own spans go."""

    class Traced(SpanEngine):
        def __init__(self, cfg, params, runtime, **kw):
            runtime.tracer.enabled = enabled
            runtime.tracer.annotate = annotate
            out["tracer"] = runtime.tracer
            super().__init__(cfg, params, runtime, **kw)

        def clear_spans(self):
            super().clear_spans()
            self.rt.tracer.clear()

    return Traced


class Profile:
    """A profiler session over the first ``seconds`` of the window, started
    from its own thread once set-up is done (``arm``)."""

    def __init__(self, lead_in_s: float, seconds: float):
        self.lead_in_ns = int(lead_in_s * 1e9)
        self.seconds_ns = int(seconds * 1e9)
        self.ex, self.host, self.error = None, None, None
        self._thread = None

    def arm(self):
        # the window opens 20 ms after set-up plus the lead-in (harness.serve)
        t0 = now_ns() + 20_000_000 + self.lead_in_ns
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        daemon=True)
        self._thread.start()

    def _run(self, t0: int):
        import jax
        try:
            harness._sleep_until(t0)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            log_dir = tempfile.mkdtemp(prefix="chipbench-spans-")
            try:
                start = now_ns()
                jax.profiler.start_trace(log_dir, profiler_options=opts)
                harness._sleep_until(start + self.seconds_ns)
                jax.profiler.stop_trace()
                path = devtrace.find_xplane(log_dir)
                self.ex = devtrace.extract(path)
                self.ex["program"] = program.extract(path)
                self.host = (start, start + self.seconds_ns)
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
        except Exception as e:  # reported by join()
            self.error = e

    def join(self):
        self._thread.join(120.0)
        if self._thread.is_alive():
            raise TimeoutError("the profiler thread did not finish")
        if self.error is not None:
            raise self.error


def readings(cell, seed: int, seconds: float, *, profile: bool,
             tracer_on: bool = True, peak=None, device=None,
             save_trace=None) -> dict:
    hold: dict = {}
    prof = Profile(cell.mix["lead_in_s"], harness.TRACE_SECONDS) \
        if profile else None
    t0 = time.monotonic()
    clock: dict = {}

    def setup_done():
        clock["setup_s"] = time.monotonic() - t0
        if prof is not None:
            prof.arm()
    served, _ = harness.serve(
        cell, seed, seconds, trace=False,
        engine_cls=traced_engine(profile, hold, tracer_on),
        on_setup_done=setup_done)
    tracer = hold["tracer"]
    row = {"workload": cell.name, "seed": seed, "profile": profile,
           "tracer": tracer_on,
           "end_to_end": harness.end_to_end(served, seconds,
                                            clock["setup_s"]),
           "dropped": tracer.dropped(),
           "records": sum(tracer.counts().values())}
    ctx = types.SimpleNamespace(
        window=served.window, prefill_spans=served.prefill_spans,
        decode_spans=served.decode_spans, trace=None,
        trace_host=served.trace_host, device=device,
        shapes=Shapes.from_config(cell.cfg), peak=peak, tracer=tracer,
        requests=[s.req for s in served.client.sent if s.req is not None])
    # the benchmark's own per-layer metrics: those of the device trace with
    # --profile only
    traced, dev = served, device
    if prof is not None:
        prof.join()
        ex = ctx.trace = prof.ex
        red = devtrace.reduce(ex)
        lo, hi = devtrace.window_of(ex)
        row.update(busy_s=red["busy_s"], window_s=red["window_s"],
                   idle_gaps=red["breakdown"]["idle_gaps"],
                   idle_by_span=program.idle_by_span(ex, lo, hi))
        traced = dataclasses.replace(served, trace=ex, trace_host=prof.host)
        dev = dict(device, busy_s=red["busy_s"], window_s=red["window_s"])
        if save_trace:
            os.makedirs(save_trace, exist_ok=True)
            with gzip.open(os.path.join(
                    save_trace, f"{cell.name}-{seed}.json.gz"), "wt") as f:
                json.dump(ex, f)
    row["bench_per_layer"] = harness.per_layer(cell, traced, dev, peak)
    row["per_layer"] = {
        m: harness.metric_reader(m)(ctx) for m in PROGRAM_METRICS}
    row["host_us"] = program.host_times_us(tracer, served.window)
    row["respawn"] = program.respawn_split_us(tracer, served.window)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--profile", action="store_true")
    mode.add_argument("--tracer-off", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--save-trace")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devs = harness.devices_or_fail(cell.chips)
    harness.use_compile_cache(harness.CACHE_DIR)
    peak = harness.peaks_for(devs[0].device_kind)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips}
    for seed in (int(s) for s in args.seeds.split(",")):
        row = readings(cell, seed, args.seconds, profile=args.profile,
                       tracer_on=not args.tracer_off, peak=peak,
                       device=device, save_trace=args.save_trace)
        line = json.dumps(row, default=float)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

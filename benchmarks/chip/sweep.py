#!/usr/bin/env python3
"""Knee sweep: serve one cell's traffic at several fixed rates in one
process and report, per rate, whether the system kept up.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 30 --seed <n> [--out sweep.json]

A rate is sustained when the backlog at the window's close (requests due in
the window whose prefill had not started) is at most the number of slots,
and the median time to first token of the window's second half is at most
twice that of its first half (no growing queue). The knee is the highest
sustained rate; a cell's mix file takes 0.8 of it. Run once per cell when
the cell is defined; the benchmark's runs never call it.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402


def rate_stats(served, cell) -> dict:
    w0, w1 = served.window
    win = [s for s in served.client.sent if s.arrival.in_window]
    started = {sp.info[1] for sp in served.prefill_spans if sp.t0 <= w1}
    backlog = sum(s.req is None or s.req.submit_ns not in started
                  for s in win)
    done = [s for s in win if harness._complete(s)]
    ttft = [(s.token_ns[0] - s.due_ns) / 1e6 for s in done]
    half = [(s.token_ns[0] - s.due_ns) / 1e6 for s in done
            if s.due_ns >= (w0 + w1) / 2]
    first = [(s.token_ns[0] - s.due_ns) / 1e6 for s in done
             if s.due_ns < (w0 + w1) / 2]
    toks = sum(w0 <= t <= w1 for s in served.client.sent for t in s.token_ns)
    grow = (float(np.median(half) / np.median(first))
            if half and first else None)
    return {"attempted": len(win), "completed": len(done),
            "backlog_at_close": backlog,
            "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
            "ttft_median_second_half_over_first": grow,
            "tok_s": toks / ((w1 - w0) / 1e9),
            "sustained": bool(backlog <= cell.cfg["serve"]["n_slots"]
                              and grow is not None and grow <= 2.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.devices_or_fail(cell.chips)
    harness.use_compile_cache(harness.CACHE_DIR)
    weights, rows = None, []
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.monotonic()
        served, weights = harness.serve(cell, args.seed, args.seconds,
                                           trace=False, rate=rate,
                                           weights=weights)
        row = {"workload": cell.name, "rate_per_s": rate,
               "seconds": args.seconds, "wall_s": time.monotonic() - t0,
               **rate_stats(served, cell)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    summary = {"workload": cell.name, "knee_per_s": knee,
               "rate_0p8_per_s": 0.8 * knee if knee else None}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, **summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

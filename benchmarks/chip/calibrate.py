#!/usr/bin/env python3
"""Readings that set the limit of ``max_logit_gap``: the program's on many
seeds and the int8 control's on the same requests, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 15 \
        [--out calibrate.jsonl]

For each seed the cell's traffic is served at its own rate and load for a
short window through the same path a run takes, the run's sample of
finished requests is drawn, and the float32 reference reads the widest gap
of the served tokens. On the control seeds the reference computed in int8
is put in the program's place: at every position of the same prompts and
served tokens it reads the gap of the token the int8 model puts first.
The benchmark's runs never call this.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.reference import Reference, control_gaps, served_gaps  # noqa: E402,E501


def readings(cell, seed, seconds, control: bool, *, engine_cls=None,
             spec_path=None) -> dict:
    kw = {} if engine_cls is None else {"engine_cls": engine_cls}
    served, weights = harness.serve(cell, seed, seconds, trace=False, **kw)
    sample = harness.sample_for_check(served, seed, cell.mix["check_sample"])
    ref = Reference(cell.cfg)
    ctl = Reference(cell.cfg, int8=True) if control else None
    prog, low = [], []
    for s in sample:
        seq, rows, tokens = harness.teacher_forced(ref, weights, s)
        lg = ref.logits(weights, seq, rows)
        prog.append(float(served_gaps(lg, tokens).max()))
        if ctl is not None:
            low.append(float(control_gaps(
                lg, ctl.logits(weights, seq, rows)).max()))
    out = {"workload": cell.name, "seed": seed,
           "requests": len(sample),
           "tokens": int(sum(len(s.req.tokens) for s in sample)),
           "incomplete": sum(not harness._complete(s)
                             for s in served.client.sent
                             if s.arrival.in_window),
           "program_gap": max(prog) if prog else None,
           "program_gap_per_request": prog}
    if control:
        out.update(control_gap=max(low) if low else None,
                   control_gap_per_request=low)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.devices_or_fail(cell.chips)
    harness.use_compile_cache(harness.CACHE_DIR)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for seed in seeds + sorted(ctl - set(seeds)):
        row = readings(cell, seed, args.seconds, seed in ctl)
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()  # this seed's weights go before the next seed's
    prog = [r["program_gap"] for r in rows if r["program_gap"] is not None]
    low = [r["control_gap"] for r in rows if r.get("control_gap") is not None]
    summary = {"workload": cell.name,
               "lower_reading": max(prog) if prog else None,
               "upper_reading": min(low) if low else None,
               "program_gaps": prog, "control_gaps": low,
               "program_gap_median": float(np.median(prog)) if prog else None}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            for r in rows + [summary]:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

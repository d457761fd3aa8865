#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json on the chips here.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix, makes the weights on the
device from the seed, starts the serving engine, warms up every shape the
schedule uses (all of that is ``setup_s``), serves the open-loop schedule
for a lead-in and then the measured window, waits for the window's requests,
checks what they served against the float32 reference, and prints one JSON
object as the last line of standard output. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` records a profiler trace of the
window and reports its per-layer metrics. Without an accelerator, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

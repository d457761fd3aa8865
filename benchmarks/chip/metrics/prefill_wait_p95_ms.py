"""core.runtime: 95th percentile of the wait from a request's slot until
its prefill task body is entered (``Request.prefill_ns - slot_ns``): the
dependency wait behind the running decode iteration and other prefills,
then scheduling; over requests submitted in the window (host clock)."""
import numpy as np


def read(ctx):
    reqs = getattr(ctx, "requests", None)
    if not reqs:
        return None
    lo, hi = ctx.window
    waits = [(r.prefill_ns - r.slot_ns) / 1e6 for r in reqs
             if lo <= r.submit_ns <= hi and r.slot_ns and r.prefill_ns]
    return float(np.percentile(waits, 95)) if waits else None

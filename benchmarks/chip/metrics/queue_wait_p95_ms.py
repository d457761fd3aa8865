"""serve.engine: 95th percentile of the wait from submit to the entry of the
request's prefill, over requests submitted in the window (host clock)."""
import numpy as np


def read(ctx):
    lo, hi = ctx.window
    waits = [(s.t0 - s.info[1]) / 1e6 for s in ctx.prefill_spans
             if lo <= s.info[1] <= hi]
    return float(np.percentile(waits, 95)) if waits else None

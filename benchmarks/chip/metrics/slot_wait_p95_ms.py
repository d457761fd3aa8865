"""serve.engine: 95th percentile of the wait from submit until the request
is popped into a free slot (``Request.slot_ns - submit_ns``, the engine's
own stamps), over requests submitted in the window (host clock)."""
import numpy as np


def read(ctx):
    reqs = getattr(ctx, "requests", None)
    if not reqs:
        return None
    lo, hi = ctx.window
    waits = [(r.slot_ns - r.submit_ns) / 1e6 for r in reqs
             if lo <= r.submit_ns <= hi and r.slot_ns]
    return float(np.percentile(waits, 95)) if waits else None

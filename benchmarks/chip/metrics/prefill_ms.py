"""models: mean host time of one prefill call (forward, first token and the
cache splice's dispatch), over prefills that started in the window."""


def read(ctx):
    lo, hi = ctx.window
    d = [(s.t1 - s.t0) / 1e6 for s in ctx.prefill_spans if lo <= s.t0 <= hi]
    return sum(d) / len(d) if d else None

"""core.runtime: mean host time from one decode call's return to the next
call's entry, over gaps in the window that hold no prefill: bookkeeping,
admission, the respawn through the dependency system, scheduling and the
idle sleep (host clock)."""


def read(ctx):
    lo, hi = ctx.window
    dec = [s for s in ctx.decode_spans if lo <= s.t0 and s.t1 <= hi]
    pre = sorted((s.t0, s.t1) for s in ctx.prefill_spans)
    gaps = []
    for a, b in zip(dec, dec[1:]):
        if not any(p0 < b.t0 and p1 > a.t1 for p0, p1 in pre):
            gaps.append((b.t0 - a.t1) / 1e6)
    return sum(gaps) / len(gaps) if gaps else None

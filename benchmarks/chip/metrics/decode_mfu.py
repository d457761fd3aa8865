"""step share of peak: model FLOPs of the decode iterations in the window
(at the live lengths) over the wall time from each decode launch to the
next, times the chip's peak."""


def read(ctx):
    lo, hi = ctx.window
    dec = [s for s in ctx.decode_spans if lo <= s.t0 <= hi]
    if len(dec) < 2:
        return None
    flops = sum(ctx.shapes.decode_flops(s.info) for s in dec[:-1])
    wall = (dec[-1].t0 - dec[0].t0) / 1e9
    return 100.0 * flops / (wall * ctx.peak["bf16_flops_per_s"])

"""step share of peak: model FLOPs of the prefills that started in the
window over their host wall time, times the chip's peak."""


def read(ctx):
    lo, hi = ctx.window
    pre = [s for s in ctx.prefill_spans if lo <= s.t0 <= hi]
    if not pre:
        return None
    flops = sum(ctx.shapes.prefill_flops(s.info[0]) for s in pre)
    wall = sum(s.t1 - s.t0 for s in pre) / 1e9
    return 100.0 * flops / (wall * ctx.peak["bf16_flops_per_s"])

"""step share of peak: the least time the decode iterations of the traced
window need (weights, plus keys and values read at each slot's live length
and written once per live slot, or their FLOPs if larger) over their device
time in the trace. Bound by memory at these shapes."""
from chipbench.devtrace import program_times_ns
from chipbench.work import least_seconds

DECODE_CALL = "bench.decode"  # the programs launched inside it


def read(ctx):
    if ctx.trace is None:
        return None
    times = program_times_ns(ctx.trace, DECODE_CALL)
    lo, hi = ctx.trace_host
    spans = [s for s in ctx.decode_spans if lo <= s.t0 <= hi]
    if not times or not spans:
        return None
    need = [least_seconds(ctx.shapes.decode_flops(s.info),
                          ctx.shapes.decode_bytes(s.info), ctx.peak)[0]
            for s in spans]
    mean_need = sum(need) / len(need)
    mean_dev = sum(times) / len(times) / 1e9
    return 100.0 * mean_need / mean_dev

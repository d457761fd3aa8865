"""serve.engine: share of the stretches from one ``serve.decode`` start to
the next, with no ``serve.prefill`` overlapping, during which no operation
runs on the device: the host's part of each decode step (profiler
trace)."""
from chipbench.devtrace import window_of
from chipbench.program import decode_stretches, idle_share


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("program"):
        return None
    lo, hi = window_of(ctx.trace)
    return idle_share(ctx.trace, decode_stretches(ctx.trace), lo, hi)

"""models: share of the program's ``serve.prefill`` spans in the traced
window during which no operation runs on the device (profiler trace)."""
from chipbench.devtrace import window_of
from chipbench.program import idle_share, prefill_stretches


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("program"):
        return None
    lo, hi = window_of(ctx.trace)
    return idle_share(ctx.trace, prefill_stretches(ctx.trace), lo, hi)

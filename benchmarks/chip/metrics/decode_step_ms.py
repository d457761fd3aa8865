"""models: mean device time of one run of the decode program, from the
profiler trace."""
from chipbench.devtrace import program_times_ns

DECODE_CALL = "bench.decode"  # the programs launched inside it


def read(ctx):
    if ctx.trace is None:
        return None
    times = program_times_ns(ctx.trace, DECODE_CALL)
    return sum(times) / len(times) / 1e6 if times else None

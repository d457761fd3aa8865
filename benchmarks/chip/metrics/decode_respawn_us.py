"""core.runtime: mean host time from the end of one decode iteration's task
body to the start of the next one's, over task bodies in the window that
follow each other and both ran a decode step (the program's ``task`` and
``serve.decode`` spans): completion, dependency release, scheduling and
wake (host clock)."""
from chipbench.program import respawn_pairs


def read(ctx):
    tracer = getattr(ctx, "tracer", None)
    if tracer is None or not tracer.enabled:
        return None
    pairs = respawn_pairs(tracer, ctx.window)
    if not pairs:
        return None
    return sum(start - end for end, start, _ in pairs) / len(pairs) / 1e3

"""device: share of the traced window in which no operation ran on the
device (1 - union of the device-op intervals over the window)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])

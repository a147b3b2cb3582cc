"""Device: share of the traced window in which no operation ran on the
device (profiler trace: 1 - union of op intervals / window)."""


def read(ctx):
    if not ctx.trace.window_s or not ctx.trace.busy_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)

"""idle_share.job: the share of the traced window in which no operation
ran on the device, in a cell of analytics jobs."""


def read(ctx):
    if ctx.trace is None or not ctx.jobs:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)

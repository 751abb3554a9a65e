"""idle_share.serve: the share of the traced window in which no operation
ran on the device, in a serving cell."""


def read(ctx):
    if ctx.trace is None or not ctx.queries:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)

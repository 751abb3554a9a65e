"""superstep_ms.job: device busy time in the traced window over the
supersteps the engine reported for the jobs in it."""


def read(ctx):
    steps = sum(j.supersteps for j in ctx.jobs)
    if ctx.trace is None or not steps:
        return None
    return 1000.0 * ctx.trace.busy_s / steps

"""batch_fill.serve: queries admitted per dispatch, over the plane's width
B, averaged over the window's dispatches (a count from the server)."""


def read(ctx):
    if not ctx.dispatches:
        return None
    return 100.0 * sum(d.admitted / d.batch for d in ctx.dispatches) / len(
        ctx.dispatches)

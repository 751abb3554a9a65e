"""dispatch_ms.serve: the mean of the server's own ``last_dispatch_s``
(host clock around ``Engine.run_batch``, answers on the host) over the
window's dispatches."""


def read(ctx):
    if not ctx.dispatches:
        return None
    return 1000.0 * sum(d.server_s for d in ctx.dispatches) / len(
        ctx.dispatches)

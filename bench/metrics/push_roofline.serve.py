"""push_roofline.serve: as push_roofline.job, for the server's dispatches:
each runs its slowest query's supersteps at the plane's B columns."""

import workcount


def read(ctx):
    return workcount.push_roofline(ctx, [(d.supersteps, d.batch)
                                         for d in ctx.dispatches])

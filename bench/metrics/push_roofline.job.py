"""push_roofline.job: the phase-1 push's least time over the device time
of the push's operations in the traced window, for analytics jobs.

The least time is ``workcount.push_bytes`` per superstep (edge ids, weights
where the program reads them, the vertex planes read and written), times
the supersteps of the jobs in the window, over the chip's peak HBM
bandwidth: the count of the work, whatever kernel does it."""

import workcount


def read(ctx):
    return workcount.push_roofline(ctx, [(j.supersteps, ctx.columns)
                                         for j in ctx.jobs])

"""query_p90_ms: 90th percentile, over every query due in the window, of
the time from when it was due to be sent to when its answer row was on the
host (host clock).  A query never answered counts as infinitely late.

The 90th, not the 95th: a window holds some 160 queries below the knee, and
the highest percentile with ten or more of them beyond it is about the
93rd."""

import math


def read(ctx):
    if not ctx.queries:
        return None
    lat = sorted((q.done - q.due) if q.done is not None else math.inf
                 for q in ctx.queries)
    # nearest rank: the smallest latency that 90% of queries meet
    return 1000.0 * lat[math.ceil(0.90 * len(lat)) - 1]

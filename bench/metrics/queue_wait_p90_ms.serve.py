"""queue_wait_p90_ms.serve: 90th percentile (nearest rank) of the window's
queries' wait in the server's queue, from ``submit`` to their admission in
``step``: the program's ``server.queue`` records, the last A of them, A the
window's answered queries.  An unanswered query counts as infinitely long,
as in ``query_p90_ms``."""

import math

import program_spans


def read(ctx):
    recs = program_spans.records("server.queue")
    answered = sum(q.done is not None for q in ctx.queries)
    if recs is None or not ctx.queries or len(recs) < answered:
        return None
    waits = [r.seconds for r in recs[len(recs) - answered:]]
    lat = sorted(waits + [math.inf] * (len(ctx.queries) - answered))
    return 1000.0 * lat[math.ceil(0.90 * len(lat)) - 1]

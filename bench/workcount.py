"""Bytes the phase-1 push must move, counted from shapes.

One superstep of the push reads every edge's source and destination id
(int32 each), every edge's weight where the program reads weights
(float32), the vertex plane it gathers from, and writes the plane it
scatters into, at ``columns`` query columns.  This is the algorithm's work,
not any implementation's: a padded layout, a band table or a one-hot
matmul moves more, and is read against the same count.
"""

from __future__ import annotations

ID_BYTES = 4
WEIGHT_BYTES = 4


def push_bytes(num_vertices: int, num_edges: int, columns: int,
               value_bytes: int, reads_weights: bool) -> int:
    edge = num_edges * (2 * ID_BYTES + (WEIGHT_BYTES if reads_weights
                                        else 0))
    planes = 2 * num_vertices * columns * value_bytes
    return edge + planes


def push_roofline(ctx, steps_at_width) -> float | None:
    """Percent: least push time over the push ops' device time.

    ``steps_at_width`` lists ``(supersteps, columns)`` of each job or
    dispatch in the traced window.  None when the trace found no push op.
    """
    if ctx.trace is None or not steps_at_width:
        return None
    push_s = ctx.trace.push_s
    if not push_s:
        return None
    total = sum(steps * push_bytes(ctx.shapes["num_vertices"],
                                   ctx.shapes["num_edges"], cols,
                                   ctx.work["value_bytes"],
                                   ctx.work["reads_weights"])
                for steps, cols in steps_at_width)
    least_s = total / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / push_s

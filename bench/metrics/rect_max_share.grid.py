"""rect_max_share.grid: the placement's largest rectangle, in % of the
edges: 100 x the largest of the ``rect_edges`` counts over their sum, from
the program's last grid ``graph.layout`` record (the layout the window ran
on).  A chip's push takes as long as its rectangle's edges, so the largest
one sets the superstep.  None where the program records no ``rect_edges``.
"""

import program_spans


def read(ctx):
    recs = [r for r in program_spans.records("graph.layout") or ()
            if "rect_edges" in r.attrs]
    if not recs:
        return None
    edges = recs[-1].attrs["rect_edges"]
    total = sum(edges)
    return 100.0 * max(edges) / total if total else None

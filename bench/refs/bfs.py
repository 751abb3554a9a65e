"""Plain BFS: hop depths from each query's source, by scipy's C BFS.

The engine answers a BFS query with the depth of every vertex (int32, with
``2**31 - 1`` for a vertex the source cannot reach) and the supersteps it
took: one per level, plus the one that found nothing new, so the source's
eccentricity plus one.  This reads the benchmark's edge list, not the
program's CSR, and imports nothing of the program.

Numbers compared, each with limit 0 (an exact comparison):
``wrong_answers``, checked answers whose depth row or superstep count
differs from the reference for the source the query asked about (a row
routed to another query's id differs too); ``wrong_vertices``, the vertices
wrong over all of them.

The control breaks the guarantee that every answer is exact: the reference
stopped one level short, as a superstep cap or an early exit would.
"""

from __future__ import annotations

import dataclasses

import numpy as np

UNREACHED = 2**31 - 1


def depths(edges, sources) -> tuple[np.ndarray, np.ndarray]:
    """[len(sources), V] int32 depths and the superstep count of each."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import shortest_path

    n = edges.num_vertices
    adj = sp.csr_matrix((np.ones(edges.num_edges, np.int8),
                         (edges.src, edges.dst)), shape=(n, n))
    uniq, inv = np.unique(np.asarray(sources, np.int64), return_inverse=True)
    dist = shortest_path(adj, directed=True, unweighted=True, indices=uniq)
    dist = np.atleast_2d(dist)
    reached = np.isfinite(dist)
    d = np.where(reached, dist, 0).astype(np.int64)
    steps = d.max(axis=1) + 1
    rows = np.where(reached, d, UNREACHED).astype(np.int32)
    return rows[inv], steps[inv]


def _score(answers, rows, steps) -> dict:
    wrong = wrong_v = 0
    for ans, row, st in zip(answers, rows, steps):
        got = np.asarray(ans.state)
        bad = (got.shape != row.shape) or int(ans.supersteps) != int(st)
        nv = int(np.count_nonzero(got != row)) if got.shape == row.shape \
            else row.size
        wrong += int(bad or nv > 0)
        wrong_v += nv
    return {"wrong_answers": float(wrong), "wrong_vertices": float(wrong_v)}


def numbers(edges, params: dict, answers) -> dict:
    if not answers:
        return {"wrong_answers": 0.0, "wrong_vertices": 0.0}
    rows, steps = depths(edges, [a.source for a in answers])
    return _score(answers, rows, steps)


def control_answers(edges, params: dict, answers):
    """The reference one level short, in the program's place."""
    rows, steps = depths(edges, [a.source for a in answers])
    out = []
    for ans, row, st in zip(answers, rows, steps):
        short = np.where(row >= st - 1, UNREACHED, row).astype(np.int32) \
            if st > 1 else row
        out.append(dataclasses.replace(ans, state=short,
                                       supersteps=int(max(st - 1, 1))))
    return out

"""Plain PageRank: the engine's Listing-1 semantics, vectorised numpy.

    a_0 = 0;  a_{t+1}(v) = (1 - alpha) + sum over edges u->v of
                           alpha * a_t(u) / max(outdeg(u), 1)

run for a fixed number of iterations, duplicate edges counted each time
(the COST paper's Listing 1, with sink degrees clipped to 1 as
``repro.core.pagerank`` states).  It reads the benchmark's edge list, not
the program's CSR, and imports nothing of the program.

The number compared is the largest relative error of any vertex of any
checked job against the float64 reference, and the count of jobs whose
iteration count is not ``iters``.  The control is the same loop with the
vertex planes rounded to bfloat16 after every step (sums in float32): the
precision a later change to the engine's state plane might reach for.
"""

from __future__ import annotations

import numpy as np


def _to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def pagerank(edges, alpha: float, iters: int, plane: str = "float64"):
    """The reference (``plane="float64"``) or the control (``"bfloat16"``)."""
    import scipy.sparse as sp

    n = edges.num_vertices
    deg = np.maximum(np.bincount(edges.src, minlength=n), 1)
    # adjacency with in-edges on rows; duplicate edges sum to their count
    adj = sp.csr_matrix((np.ones(edges.num_edges, np.float32),
                         (edges.dst, edges.src)), shape=(n, n))
    if plane == "float64":
        adj = adj.astype(np.float64)
        a = np.zeros(n, np.float64)
        for _ in range(iters):
            a = (1.0 - alpha) + adj @ (alpha * a / deg)
        return a
    if plane != "bfloat16":
        raise ValueError(f"unknown plane precision {plane!r}")
    a = np.zeros(n, np.float32)
    degf = deg.astype(np.float32)
    for _ in range(iters):
        b = _to_bf16(np.float32(alpha) * a / degf)
        a = _to_bf16(np.float32(1.0 - alpha) + adj @ b)
    return a


def numbers(edges, params: dict, answers) -> dict:
    """The compared numbers for the checked jobs' answers."""
    alpha, iters = float(params["alpha"]), int(params["iters"])
    ref = pagerank(edges, alpha, iters)
    worst = 0.0
    wrong_iters = 0
    for ans in answers:
        got = np.asarray(ans.state, np.float64)
        if got.shape != ref.shape or not np.all(np.isfinite(got)):
            worst = float("inf")
        else:
            worst = max(worst, float(np.max(np.abs(got - ref) / ref)))
        wrong_iters += int(ans.supersteps != iters)
    return {"max_rel_err": worst, "wrong_iters": float(wrong_iters)}


def control_answers(edges, params: dict, answers):
    """The control put in the program's place, for the same jobs."""
    import dataclasses

    iters = int(params["iters"])
    state = pagerank(edges, float(params["alpha"]), iters, plane="bfloat16")
    return [dataclasses.replace(a, state=state, supersteps=iters)
            for a in answers]

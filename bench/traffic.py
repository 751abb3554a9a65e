"""The one traffic generator: every mix is a data file it reads.

Two kinds of mix:

``"kind": "jobs"``
    Analytics jobs back to back, one program with fixed ``params``.  With
    ``"batch": B`` each job is one ``Engine.run_batch`` of B sources drawn
    by ``"sources"``; without it, one ``Engine.run``.

``"kind": "open_loop"``
    Queries sent on a schedule whatever the server does, at ``rate_qps``.
    ``"arrivals"`` is ``{"process": "poisson"}`` or ``{"process": "onoff",
    "on_s": .., "off_s": ..}`` (Poisson inside the on phases, at the rate
    that keeps the same mean).  ``"sources"`` draws each query's source.

Every seed gets the same amount of work: the gaps between arrivals are the
exponential distribution's quantiles at evenly spaced probabilities, and
the sources are the source distribution's quantiles likewise; the seed
only shuffles their order and picks which vertex holds which rank.  So two
seeds differ in the order and the graph, not in the load.

Source draws, ``{"dist": "zipf", "exponent": s}`` or ``{"dist":
"uniform"}``, rank the vertices that have out-edges in a seeded random
order; rank k is drawn with probability proportional to ``k ** -s``.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    """An open-loop schedule: ``due[i]`` seconds after the window opens,
    query i asks about ``sources[i]``."""

    due: np.ndarray  # [n] float64, nondecreasing
    sources: np.ndarray  # [n] int64


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use of the seed."""
    words = [ord(ch) for ch in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *words]))


def candidates(num_vertices: int, src: np.ndarray) -> np.ndarray:
    """Vertices with at least one out-edge: the ones a query can start at."""
    return np.flatnonzero(np.bincount(src, minlength=num_vertices) > 0)


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_sources(spec: dict, pool: np.ndarray, n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``n`` sources from ``pool`` under ``spec``, in a seeded order."""
    dist = spec["dist"]
    if dist == "uniform":
        s = 0.0
    elif dist == "zipf":
        s = float(spec["exponent"])
    else:
        raise ValueError(f"unknown source distribution {dist!r}")
    ranked = rng.permutation(pool)
    p = np.arange(1, len(ranked) + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(p) / p.sum()
    ranks = np.searchsorted(cdf, _stratified(n), side="right")
    ranks = np.minimum(ranks, len(ranked) - 1)
    return ranked[rng.permutation(ranks)].astype(np.int64)


def _gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    gaps = -np.log1p(-_stratified(n)) / rate
    return rng.permutation(gaps)


def arrivals(spec: dict, rate: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds)`` at mean rate ``rate``."""
    n = int(round(rate * seconds))
    process = spec["process"]
    if process == "poisson":
        t = np.cumsum(_gaps(n, rate, rng))
    elif process == "onoff":
        on, off = float(spec["on_s"]), float(spec["off_s"])
        busy = np.cumsum(_gaps(n, rate * (on + off) / on, rng))
        # lay the on-phase clock out over on/off periods
        t = np.floor(busy / on) * (on + off) + np.mod(busy, on)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return t[t < seconds]


def open_loop(traffic: dict, seconds: float, pool: np.ndarray,
              seed: int) -> Schedule:
    rate = float(traffic["rate_qps"])
    due = arrivals(traffic["arrivals"], rate, seconds, rng_for(seed, "due"))
    src = draw_sources(traffic["sources"], pool, len(due),
                       rng_for(seed, "sources"))
    return Schedule(due, src)


def job_sources(traffic: dict, pool: np.ndarray, seed: int, job: int):
    """The sources of batched job ``job`` (None for an unbatched mix)."""
    if "batch" not in traffic:
        return None
    return draw_sources(traffic["sources"], pool, int(traffic["batch"]),
                        rng_for(seed, f"job{job}"))

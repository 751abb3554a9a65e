"""Seeded graph generation for the benchmark's configurations.

One general generator reads a configuration's ``graph`` section and returns
the edge list as host arrays.  The graph is the benchmark's data: the program
under test gets it through ``repro.core.from_edges`` and the plain
references read these same arrays, never the program's CSR.

RMAT (Chakrabarti et al., SDM 2004; the Graph500 generator's model) draws
each edge's source and destination one bit per level: quadrant
``(src bit, dst bit)`` is (0,0) with probability a, (0,1) with b, (1,0)
with c and (1,1) with d = 1 - a - b - c.  The draw runs on the default
device in one jitted call, then a stable sort by source puts the edges
left out last, so the host slices them off and ``from_edges``' stable
argsort meets input that is already in order.

Every seed gives the same number of edges, ``edges``: the draw makes
``edge_draws`` (a few more), drops self-loops, and keeps the first
``edges`` in draw order; a symmetrized graph keeps a seeded choice of
``edges`` distinct undirected pairs.  Array shapes then do not change with
the seed, so a new seed reuses every compiled program of the last.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """COO edges, sorted by source; ``weight`` is None when unweighted."""

    num_vertices: int
    src: np.ndarray  # [E] int32
    dst: np.ndarray  # [E] int32
    weight: np.ndarray | None  # [E] float32
    directed: bool

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def key_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words from a seed of any size (it may exceed 2**32)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return int(w[0]), int(w[1])


def _rmat_device(words, scale, draws, keep_n, a, b, c, weights):
    """One jitted program: RMAT bits, optional uniform weights, and a stable
    sort by source with self-loops, and loop-free draws past the first
    ``keep_n``, keyed past every vertex."""
    import jax
    import jax.numpy as jnp

    d = 1.0 - a - b - c

    @jax.jit
    def gen(k0, k1):
        key = jax.random.fold_in(jax.random.key(k0), k1)
        kbits, kw = jax.random.split(key)

        def level(i, carry):
            src, dst = carry
            r = jax.random.uniform(jax.random.fold_in(kbits, i), (2, draws))
            low = r[0] >= a + b  # source bit 1: quadrants c, d
            dbit = jnp.where(low, r[1] < d / (c + d), r[1] < b / (a + b))
            return ((src << 1) | low.astype(jnp.int32),
                    (dst << 1) | dbit.astype(jnp.int32))

        zeros = jnp.zeros(draws, jnp.int32)
        src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
        loop_free = src != dst
        rank = jnp.cumsum(loop_free.astype(jnp.int32)) - 1
        kept = loop_free & (rank < keep_n)
        keyed = jnp.where(kept, src, jnp.int32(1 << scale))
        if weights is None:
            keyed, dst = jax.lax.sort((keyed, dst), num_keys=1,
                                      is_stable=True)
            return keyed, dst, kept.sum()
        low_w, high_w = weights
        w = jax.random.uniform(kw, (draws,), jnp.float32, low_w, high_w)
        keyed, dst, w = jax.lax.sort((keyed, dst, w), num_keys=1,
                                     is_stable=True)
        return keyed, dst, w, kept.sum()

    return gen(jnp.uint32(words[0]), jnp.uint32(words[1]))


def generate(graph_cfg: dict, seed: int) -> EdgeList:
    """The configuration's graph for ``seed`` (same seed, same edges)."""
    import jax

    kind = graph_cfg["generator"]
    if kind != "rmat":
        raise ValueError(f"unknown graph generator {kind!r}")
    scale = int(graph_cfg["scale"])
    draws, want = int(graph_cfg["edge_draws"]), int(graph_cfg["edges"])
    sym = bool(graph_cfg.get("symmetrize", False))
    a, b, c = (float(graph_cfg[k]) for k in ("a", "b", "c"))
    w_cfg = graph_cfg.get("weights")
    weights = None if w_cfg is None else (float(w_cfg["low"]),
                                          float(w_cfg["high"]))
    if sym and weights is not None:
        raise ValueError("symmetrize keeps no weights")
    out = jax.device_get(_rmat_device(key_words(seed), scale, draws,
                                      draws if sym else want, a, b, c,
                                      weights))
    kept = int(out[-1])
    if not sym and kept != want:
        raise ValueError(f"{draws} draws left {kept} loop-free edges, "
                         f"fewer than the {want} asked for")
    src = np.asarray(out[0][:kept], np.int32)
    dst = np.asarray(out[1][:kept], np.int32)
    w = None if weights is None else np.asarray(out[2][:kept], np.float32)
    n = 1 << scale
    if sym:
        src, dst = symmetrize(n, src, dst, want, seed)
        return EdgeList(n, src, dst, None, directed=False)
    return EdgeList(n, src, dst, w, directed=True)


def symmetrize(n: int, src: np.ndarray, dst: np.ndarray, pairs_kept: int,
               seed: int):
    """Both directions of ``pairs_kept`` distinct undirected pairs, chosen
    by the seed, sorted by source."""
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    pairs = np.unique(lo * n + hi)
    if len(pairs) < pairs_kept:
        raise ValueError(f"{len(pairs)} distinct pairs, fewer than the "
                         f"{pairs_kept} asked for")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    pairs = np.sort(rng.choice(pairs, pairs_kept, replace=False))
    u = (pairs // n).astype(np.int32)
    v = (pairs % n).astype(np.int32)
    s = np.concatenate([u, v])
    t = np.concatenate([v, u])
    order = np.argsort(s, kind="stable")
    return s[order], t[order]

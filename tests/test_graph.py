"""Graph substrate: CSR build, partitioning, pairwise layout, generators.

Deterministic tests only; the hypothesis property tests live in
``test_properties.py`` (module-level importorskip -- hypothesis is optional).
"""

import numpy as np

from repro.core import graph as G


def test_from_edges_roundtrip():
    src = np.array([0, 0, 1, 3], dtype=np.int32)
    dst = np.array([1, 2, 2, 0], dtype=np.int32)
    g = G.from_edges(4, src, dst)
    assert g.num_edges == 4
    assert g.out_degrees.tolist() == [2, 1, 0, 1]
    # CSR edge multiset == input multiset
    got = sorted(zip(g.src.tolist(), g.dst.tolist()))
    assert got == sorted(zip(src.tolist(), dst.tolist()))


def test_from_edges_sorted_input_skips_the_argsort(monkeypatch):
    rng = np.random.default_rng(3)
    src = rng.integers(0, 50, 400).astype(np.int32)
    dst = rng.integers(0, 50, 400).astype(np.int32)
    w = rng.random(400).astype(np.float32)
    want = G.from_edges(50, src, dst, weight=w)  # unsorted: argsorts
    order = np.argsort(src, kind="stable")
    s, d, ws = src[order], dst[order], w[order]
    calls = []
    orig = np.argsort
    monkeypatch.setattr(np, "argsort",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    got = G.from_edges(50, s, d, weight=ws)
    assert calls == []
    assert np.array_equal(got.indptr, want.indptr)
    assert np.array_equal(got.dst, want.dst)
    assert np.array_equal(got.weight, want.weight)
    # the graph holds its own arrays, as the sorting path's gathers did
    assert not np.shares_memory(got.dst, d)
    assert not np.shares_memory(got.weight, ws)


def test_partition_preserves_edges_and_weights():
    g = G.rmat(5, 120, seed=4, weighted=True)
    want = sorted(zip(g.src.tolist(), g.dst.tolist(),
                      g.edge_weights.tolist()))
    for chunks in (1, 2, 3):
        pg = G.partition(g, chunks)
        # reconstruct global (src, dst, weight) triples from both layouts
        for s_arr, d_arr, m_arr, w_arr in [
            (pg.src_local, pg.dst_global, pg.edge_valid, pg.edge_weight),
            (pg.sd_src_local, pg.sd_dst_global, pg.sd_edge_valid,
             pg.sd_edge_weight),
        ]:
            rec = []
            for c in range(chunks):
                sel = m_arr[c] == 1
                gs = s_arr[c][sel] + c * pg.chunk_size
                rec.extend(zip(gs.tolist(), d_arr[c][sel].tolist(),
                               w_arr[c][sel].tolist()))
            assert sorted(rec) == want


def test_sortdest_layout_is_dest_sorted():
    """sd layout order: destination segment block outermost, then source
    vertex block -- dest-sorted at kernel-tile granularity (the fused-kernel
    band invariant, DESIGN.md section 8)."""
    from repro.kernels.blocks import BLOCK_S, BLOCK_V

    # scale past one 256-tile so the block-granular ordering is non-trivial
    g = G.rmat(10, 4000, seed=6)
    pg = G.partition(g, 2)
    nsb = -(-pg.chunk_size // BLOCK_V)
    for c in range(pg.num_chunks):
        sel = pg.sd_edge_valid[c] == 1
        d = pg.sd_dst_global[c][sel].astype(np.int64)
        s = pg.sd_src_local[c][sel].astype(np.int64)
        key = (d // BLOCK_S) * nsb + s // BLOCK_V
        assert np.all(np.diff(key) >= 0), \
            "edges must be sorted by (dest block, src block)"
        assert np.all(np.diff(d // BLOCK_S) >= 0), \
            "dest segment blocks must be nondecreasing"


def test_out_weight_sums_outgoing():
    g = G.from_edges(4, np.array([0, 0, 1]), np.array([1, 2, 2]),
                     weight=np.array([2.0, 3.0, 4.0]))
    pg = G.partition(g, 2)
    ow = pg.out_weight.reshape(-1)[: g.num_vertices]
    # vertex 0: 2+3, vertex 1: 4, sinks fall back to 1 (div-0 clip)
    assert ow.tolist() == [5.0, 4.0, 1.0, 1.0]


def test_to_undirected_keeps_min_weight():
    g = G.from_edges(3, np.array([0, 1]), np.array([1, 0]),
                     weight=np.array([5.0, 2.0]))
    u = g.to_undirected()
    pairs = dict(zip(zip(u.src.tolist(), u.dst.tolist()),
                     u.edge_weights.tolist()))
    assert pairs == {(0, 1): 2.0, (1, 0): 2.0}


def test_to_undirected_symmetric():
    g = G.from_edges(4, np.array([0, 1, 2]), np.array([1, 2, 3]))
    u = g.to_undirected()
    pairs = set(zip(u.src.tolist(), u.dst.tolist()))
    for s, d in list(pairs):
        assert (d, s) in pairs
    assert u.num_edges == 6


def test_pairwise_layout_buckets():
    g = G.ring(8)
    pg = G.partition(g, 4)
    pw = G.build_pairwise(pg)
    total = int(pw.pb_valid.sum())
    assert total == g.num_edges
    # bucket (c, k) only contains edges from chunk c to chunk k
    K = pg.chunk_size
    for c in range(4):
        for k in range(4):
            sel = pw.pb_valid[c, k] == 1
            if sel.any():
                gsrc = pw.pb_src_local[c, k][sel] + c * K
                gdst = pw.pb_dst_local[c, k][sel] + k * K
                assert np.all(gsrc // K == c)
                assert np.all(gdst // K == k)


def test_generators():
    r = G.ring(10)
    assert r.num_edges == 10
    tc = G.two_cliques(10)
    assert tc.num_vertices == 10
    er = G.erdos_renyi(64, 200, seed=1)
    assert er.num_vertices == 64
    rm = G.rmat(6, 300, seed=2)
    assert rm.num_vertices == 64
    assert rm.num_edges > 100  # self-loops removed, most kept
    # power-law-ish: max degree much larger than mean
    assert rm.out_degrees.max() >= 3 * max(rm.out_degrees.mean(), 1)


def test_dataset_registry():
    for name in G.dataset_names():
        g = G.load_dataset(name, scale_log2=8)
        assert g.num_vertices == 256
        ratio = g.num_edges / g.num_vertices
        assert ratio > 5  # scaled E/V preserved approximately

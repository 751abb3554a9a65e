"""Fused band-pruned push kernels (ISSUE 3): property sweeps vs the jax.ops
oracles, band-metadata correctness across partitioners and degenerate
partitions, the tile-count acceptance on the scale-13 RMAT stand-in, the
engine push_fn hook equivalence sweep, and the device-buffer / compiled-fn
reuse regression for ``Engine.run``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import jax
from conftest import (ALL_PARTITIONERS, BAND_GRAPHS, graph, program_graph,
                      serial_ref, source_params)
from repro.core import (Engine, get_spec, load_dataset, partition, rmat,
                        run_parallel)
from repro.core import graph as G
from repro.core import programs as P
from repro.kernels import blocks, ops, push_min
from repro.kernels.blocks import BLOCK_E, BLOCK_S, BLOCK_V

SHAPES = [(1, 1), (7, 5), (256, 256), (257, 300), (700, 123), (2048, 640)]


def _coo(rng, E, V, sorted_by_block=False):
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    valid = rng.integers(0, 2, E).astype(np.int32)
    if sorted_by_block:  # the layouts' (seg block, src block) bucket order
        order = np.argsort((dst // BLOCK_S) * (V // BLOCK_V + 1)
                           + src // BLOCK_V, kind="stable")
        src, dst, valid = src[order], dst[order], valid[order]
    return jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid)


# ---------------------------------------------------------------------------
# Property sweeps: fused vs jax.ops.segment_{sum,min} oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,V", SHAPES)
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_add_float_matches_segment_sum(E, V, weighted, rng):
    src, dst, valid = _coo(rng, E, V, sorted_by_block=True)
    vals = jnp.asarray(rng.normal(size=V), jnp.float32)
    w = jnp.asarray(rng.uniform(1.0, 4.0, E), jnp.float32) if weighted else None
    got = ops.push(vals, src, dst, valid, V, combine="add", weight=w)
    c = jnp.where(valid != 0, vals[src], 0.0)
    if weighted:
        c = c * w
    want = jax.ops.segment_sum(c, dst, num_segments=V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("E,V", SHAPES)
def test_fused_add_int_matches_segment_sum_exactly(E, V, rng):
    src, dst, valid = _coo(rng, E, V)
    # values past float32's 2^24 integer range: the fused path must
    # accumulate ints as ints (the seed's f32 cast would round these)
    vals = jnp.asarray(rng.integers(1 << 24, 1 << 26, V), jnp.int32)
    got = ops.push(vals, src, dst, valid, V, combine="add")
    want = jax.ops.segment_sum(jnp.where(valid != 0, vals[src], 0), dst,
                               num_segments=V)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("E,V", SHAPES)
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_min_int_matches_segment_min(E, V, weighted, rng):
    src, dst, valid = _coo(rng, E, V, sorted_by_block=True)
    vals = jnp.asarray(rng.integers(0, 10_000, V), jnp.int32)
    w = jnp.asarray(rng.integers(0, 9, E), jnp.int32) if weighted else None
    got = ops.push(vals, src, dst, valid, V, combine="min", weight=w)
    c = jnp.where(valid != 0, vals[src], push_min.SENTINEL)
    if weighted:
        c = c + jnp.minimum(w, push_min.SENTINEL - c)
    want = jax.ops.segment_min(c, dst, num_segments=V)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("E,V", SHAPES)
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_min_float_matches_segment_min(E, V, weighted, rng):
    """FMIN monoid (SSSP distances): +inf identity round-trips the sentinel
    encoding; unreached stays +inf."""
    src, dst, valid = _coo(rng, E, V)
    vals = rng.uniform(0.0, 100.0, V).astype(np.float32)
    vals[rng.integers(0, 2, V).astype(bool)] = np.inf  # some unreached
    vals = jnp.asarray(vals)
    w = jnp.asarray(rng.uniform(0.0, 5.0, E), jnp.float32) if weighted else None
    got = ops.push(vals, src, dst, valid, V, combine="min", weight=w)
    c = jnp.where(valid != 0, vals[src], jnp.inf)
    if weighted:
        c = c + w
    want = jax.ops.segment_min(c, dst, num_segments=V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=0)


def test_fused_min_saturates_at_headroom_boundary():
    """A near-sentinel value + weight must clamp to the sentinel, not wrap
    int32 negative (the BFS hop transform at an unreached vertex)."""
    for v0 in (push_min.SENTINEL - 1, push_min.SENTINEL):
        vals = jnp.asarray([v0], jnp.int32)
        one = jnp.asarray([0], jnp.int32)
        out = ops.push(vals, one, one, jnp.asarray([1], jnp.int32), 1,
                       combine="min", weight=jnp.asarray([5], jnp.int32))
        assert int(out[0]) == push_min.SENTINEL


def test_fused_all_invalid_gives_identity(rng):
    E, V = 300, 64
    src = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    valid = jnp.zeros((E,), jnp.int32)
    out = ops.push(jnp.asarray(rng.normal(size=V), jnp.float32), src, dst,
                   valid, V, combine="add")
    assert np.all(np.asarray(out) == 0.0)
    out = ops.push(jnp.asarray(rng.integers(0, 9, V), jnp.int32), src, dst,
                   valid, V, combine="min")
    assert np.all(np.asarray(out) == push_min.SENTINEL)
    fout = ops.push(jnp.asarray(rng.normal(size=V), jnp.float32), src, dst,
                    valid, V, combine="min")
    assert np.all(np.isinf(np.asarray(fout)))


def test_fused_empty_edge_blocks_skipped(rng):
    """Edge blocks whose band is (0, -1) contribute nothing: padding a valid
    problem with whole invalid blocks must not change the answer."""
    E, V = 200, 300
    src, dst, valid = _coo(rng, E, V)
    vals = jnp.asarray(rng.normal(size=V), jnp.float32)
    base = ops.push(vals, src, dst, valid, V, combine="add")
    pad = 3 * BLOCK_E
    padded = ops.push(
        vals,
        jnp.concatenate([src, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([dst, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([valid, jnp.zeros((pad,), jnp.int32)]),
        V, combine="add")
    np.testing.assert_allclose(np.asarray(base), np.asarray(padded),
                               rtol=1e-6)


def test_segment_reduce_int_add_keeps_precision():
    """Satellite: the seed cast int data to f32 before scatter_sum, silently
    rounding sums above 2^24; ints must accumulate as ints."""
    data = jnp.full((3,), (1 << 24) + 1, jnp.int32)  # not an f32 integer
    seg = jnp.zeros((3,), jnp.int32)
    got = ops.segment_reduce(data, seg, 1, combine="add")
    assert got.dtype == jnp.int32
    assert int(got[0]) == 3 * ((1 << 24) + 1)


# ---------------------------------------------------------------------------
# Band metadata: partitioners x degenerate partitions (graphs from conftest)
# ---------------------------------------------------------------------------


def _check_bands(band, src, dst, valid):
    """Bands must exactly bound the valid edges' tile blocks per edge block
    (grouped fast path == rectangle reference == brute force)."""
    np.testing.assert_array_equal(band, blocks.edge_bands(src, dst, valid))
    C, emax = src.shape
    nb = band.shape[2]
    for c in range(C):
        for b in range(nb):
            sel = np.flatnonzero(valid[c][b * BLOCK_E:(b + 1) * BLOCK_E]) \
                + b * BLOCK_E
            sel = sel[sel < emax]
            if len(sel) == 0:
                assert band[c, 0, b] == 0 and band[c, 1, b] == -1
                assert band[c, 2, b] == 0 and band[c, 3, b] == -1
                continue
            sb = src[c][sel] // BLOCK_V
            db = dst[c][sel] // BLOCK_S
            assert (band[c, 0, b], band[c, 1, b]) == (sb.min(), sb.max())
            assert (band[c, 2, b], band[c, 3, b]) == (db.min(), db.max())


@pytest.mark.parametrize("pname", ALL_PARTITIONERS)
@pytest.mark.parametrize("gname", sorted(BAND_GRAPHS))
def test_band_metadata_correct(pname, gname):
    g = graph(gname)
    for chunks in (1, 2, 5):
        pg = G.partition(g, chunks, partitioner=pname)
        _check_bands(pg.band, pg.src_local, pg.dst_global, pg.edge_valid)
        _check_bands(pg.sd_band, pg.sd_src_local, pg.sd_dst_global,
                     pg.sd_edge_valid)


def test_band_pruning_tile_ratio_on_rmat_standin():
    """Acceptance: >=4x fewer tiles than the dense grid on the scale-13 RMAT
    stand-in, and the fused path is 1 launch vs 3 staged stages."""
    from benchmarks import kernelbench

    g = load_dataset("soc-lj1-mini", scale_log2=13, seed=1)
    for pes in (1, 8):
        cm = kernelbench.layout_cost_model(partition(g, pes))
        assert cm["tile_ratio"] >= 4.0, cm
        assert cm["fused"]["tiles"] < cm["staged"]["tiles"]
    assert cm["staged"]["launches"] == 3
    assert cm["fused"]["launches"] == 1


# ---------------------------------------------------------------------------
# Engine hook: the fused kernel under every strategy x program x partitioner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("partitioner", ALL_PARTITIONERS)
@pytest.mark.parametrize("strategy", ("reduction", "sortdest", "basic",
                                      "pairs"))
@pytest.mark.parametrize("name", sorted(P.PROGRAMS))
def test_push_hook_equivalence(name, strategy, partitioner):
    """With the kernel hook enabled (fused push_fn for the dense strategies,
    Pallas segment_fn for basic's receive side) every cell must still match
    the serial reference: bit-exact for min monoids, 1e-3 for add."""
    spec = get_spec(name)
    g = program_graph(name, "rmat6")
    params = source_params(spec)
    ref = serial_ref(name, "rmat6", tuple(sorted(params.items())))
    got, iters = run_parallel(g, name, num_pes=1, strategy=strategy,
                              partitioner=partitioner,
                              push_fn=ops.make_push_fn(),
                              segment_fn=ops.make_segment_fn(), **params)
    assert iters >= 1
    assert spec.matches(got, ref), (
        f"{name}/{strategy}/{partitioner}: max deviation "
        f"{np.max(np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64)))}")


def test_push_hook_bfs_ignores_edge_weights():
    """BFS declares edge_semiring='unit': the hook must count hops (+1 per
    edge), not add the graph's float weights -- hook and non-hook engines
    must agree on a *weighted* graph (regression: the hook used to pass the
    layout weights and silently compute weighted distances)."""
    g = G.random_weights(rmat(6, 300, seed=2), seed=5)  # weights in [1, 10)
    ref, _ = P.bfs_serial(g, source=3)
    got, _ = run_parallel(g, "bfs", num_pes=1, strategy="sortdest",
                          push_fn=ops.make_push_fn(), source=3)
    assert np.array_equal(got, ref)


def test_push_hook_falls_back_on_undeclared_transform():
    """A custom edge_value without an edge_semiring declaration must run the
    staged path (same result with and without the hook), never the canonical
    transform in its place."""
    import jax.numpy as jnp

    from repro.core import strategies as strat

    prog = P.VertexProgram(
        name="squared_weight", key=("squared_weight",), combiner=strat.ADD,
        init=lambda pg: np.ones((pg.num_chunks, pg.chunk_size), np.float32),
        update=lambda s, aux: s,
        edge_value=lambda v, w: v * w * w,  # not the canonical v * w
        apply=lambda s, inc, aux: inc,
        fixed_iters=1)
    g = G.random_weights(rmat(5, 150, seed=4), seed=6)
    pg = partition(g, 1)
    base, _ = Engine(pg).run(prog)
    hooked, _ = Engine(pg, push_fn=ops.make_push_fn()).run(prog)
    np.testing.assert_allclose(hooked, base, rtol=1e-6)
    w2 = np.bincount(g.dst, weights=g.edge_weights.astype(np.float64) ** 2,
                     minlength=g.num_vertices)
    np.testing.assert_allclose(hooked, w2.astype(np.float32), rtol=1e-4)


# ---------------------------------------------------------------------------
# Engine donation satellite: device buffers + compiled fns are reused
# ---------------------------------------------------------------------------


def test_engines_share_device_buffers_across_strategy_sweep():
    """Satellite regression: a strategy sweep over one partition must not
    re-upload layouts -- engines on the same layout alias the same device
    arrays, and each strategy ships only its own layout's buffers."""
    pg = partition(rmat(6, 200, seed=1), 1)
    e1 = Engine(pg, strategy="sortdest")
    e2 = Engine(pg, strategy="pairs")
    assert e1.arrays is e2.arrays  # both read the sd layout: one upload
    for k in e1.arrays:
        assert e1.arrays[k] is e2.arrays[k]
    r1 = Engine(pg, strategy="reduction")
    r2 = Engine(pg, strategy="reduction")
    assert r1.arrays is r2.arrays  # basic layout shared the same way
    assert e1.aux is r1.aux
    # the sd engines never shipped the basic layout and vice versa
    # (gate_blocks is the frontier-gating mask the engine derives from the
    # layout's own band table and parks in the shared upload cache)
    assert set(e1.arrays) == {"sd_src_local", "sd_dst_global",
                              "sd_edge_valid", "sd_edge_weight", "sd_band",
                              "gate_blocks"}
    assert set(r1.arrays) == {"src_local", "dst_global", "edge_valid",
                              "edge_weight", "band", "gate_blocks"}
    # pairwise layout cached the same way
    b1 = Engine(pg, strategy="basic")
    b2 = Engine(pg, strategy="basic")
    assert b1.arrays is b2.arrays


def test_engine_run_reuses_compiled_fn_and_buffers():
    """Satellite regression: two consecutive runs hit the compile cache and
    the state upload is the only fresh transfer (layouts stay put)."""
    pg = partition(rmat(6, 200, seed=1), 1)
    eng = Engine(pg)
    before = {k: v for k, v in eng.arrays.items()}
    r1 = eng.pagerank(iters=5)
    assert len(eng._compiled) == 1
    fn = next(iter(eng._compiled.values()))
    r2 = eng.pagerank(iters=5)
    assert len(eng._compiled) == 1
    assert next(iter(eng._compiled.values())) is fn
    for k, v in eng.arrays.items():  # same buffers, not re-created
        assert v is before[k]
    np.testing.assert_allclose(r1, r2)


def test_run_cost_partitions_once_per_cell():
    """run_cost shares one PartitionedGraph (and so one device upload)
    across the strategy axis; its engines must alias the same buffers."""
    from repro.core.graph import PartitionedGraph

    uploads = []
    orig = PartitionedGraph.device_arrays

    def counting(self, layout="both", mesh=None):
        first = layout != "both" and f"dense:{layout}" not in self._dev
        out = orig(self, layout, mesh)
        if first:
            uploads.append((id(self), layout))
        return out

    PartitionedGraph.device_arrays = counting
    try:
        from repro.core.cost import run_cost

        run_cost(rmat(6, 200, seed=1), "pagerank", pe_counts=(1,),
                 strategies=("reduction", "sortdest", "pairs"), repeats=1,
                 iters=2)
    finally:
        PartitionedGraph.device_arrays = orig
    # 3 strategies, one partition: exactly one upload per layout in use
    # (sd shared by sortdest+pairs, basic by reduction)
    assert sorted(u[1] for u in uploads) == ["basic", "sd"]
    assert len({u[0] for u in uploads}) == 1


# ---------------------------------------------------------------------------
# Fast-memory footprint (the shapes-only check the engine's dispatch uses)
# ---------------------------------------------------------------------------


def test_fused_footprint_counts_tiles_padding_and_batch():
    fp = blocks.fused_footprint
    body = blocks.VMEM_BODY_BYTES
    edges4 = 4 * 2 * blocks.EDGE_ROWS * BLOCK_E * 4  # double-buffered tiles
    # 1-D planes: [rows, 256] f32, rows padded to the 8-row sublane tile
    got = fp(1, 256, 256)
    assert got["vmem"] == 2 * 8 * 256 * 4 + edges4 + body
    assert fp(1, 8 * 256, 8 * 256)["vmem"] == got["vmem"]
    assert fp(1, 9 * 256, 9 * 256)["vmem"] == \
        2 * 16 * 256 * 4 + edges4 + body
    # no weight stream: one edge operand fewer; a seed plane: one more plane
    assert fp(1, 256, 256, weight=False)["vmem"] == \
        got["vmem"] - 2 * blocks.EDGE_ROWS * BLOCK_E * 4
    assert fp(1, 256, 256, init=True)["vmem"] == \
        got["vmem"] + 8 * 256 * 4
    # batched planes: [rows, B, 256], B padded to 8 sublanes, rows unpadded
    V = 1 << 16
    b16 = fp(1, V, V, batch=16)["vmem"] - edges4 - body
    assert b16 == 2 * (V // 256) * 16 * 256 * 4
    assert fp(1, V, V, batch=3)["vmem"] == fp(1, V, V, batch=8)["vmem"]
    # SMEM: the [4, NB] band table, NB padded to EDGE_ROWS, 512 B granules
    assert fp(1, 256, 256)["smem"] == 512 + blocks.SMEM_RESERVE
    assert fp(64504, 256, 256)["smem"] == \
        4 * 64504 * 4 + 128 + blocks.SMEM_RESERVE


def test_fused_misfit_names_the_overflowing_memory():
    kind = "TPU v5 lite"
    cap = blocks.TPU_FAST_MEMORY[kind]
    assert blocks.fused_misfit({"vmem": cap["vmem"], "smem": cap["smem"]},
                               kind) is None
    assert blocks.fused_misfit({"vmem": cap["vmem"] + 1, "smem": 0},
                               kind) == "vmem"
    assert blocks.fused_misfit({"vmem": 0, "smem": cap["smem"] + 1},
                               kind) == "smem"
    with pytest.raises(ValueError, match="device kind"):
        blocks.fused_misfit({"vmem": 0, "smem": 0}, "cpu")

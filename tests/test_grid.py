"""2-D grid partitioning (ISSUE 5): GridPlan invariants, rectangle layout
correctness, the grid2d two-phase reduce vs serial references, grid-aware
stats/dispatch/replan plumbing, and the 2-D wire-model acceptance.

Multi-rectangle grids need one mesh shard per rectangle, so real R x C
shapes at 2/4/8 PEs run in the ``test_multidevice`` subprocess suite; this
single-device process covers the host-side machinery at any shape and the
full engine path at ``grid(1,1)``.

Deterministic twins of the hypothesis grid properties live here (the
hypothesis-optional idiom: ``tests/test_properties.py`` skips cleanly when
hypothesis is absent).
"""

import numpy as np
import pytest

from conftest import (DEGENERATE_GRAPHS, EQUIV_GRAPHS, graph, program_graph,
                      serial_ref, source_params)
from repro.core import Engine, get_spec, run_cost, run_parallel, wire_model
from repro.core import graph as G
from repro.core import partitioners as PT
from repro.core import programs as P

SHAPES = ((1, 1), (1, 3), (3, 1), (2, 2), (2, 3))


def _grid_pg(gname, rows, cols, weighted=False):
    g = graph(gname)
    if weighted:
        g = G.random_weights(g, seed=5)
    return G.partition(g, rows * cols, partitioner=f"grid({rows},{cols})")


# ---------------------------------------------------------------------------
# Registry / family parsing
# ---------------------------------------------------------------------------


def test_grid_family_parsing():
    assert PT.grid_shape("grid(2,4)") == (2, 4)
    assert PT.grid_shape("grid(4x2)") == (4, 2)
    assert PT.grid_shape("contiguous") is None
    spec = PT.get_partitioner("grid(2,4)")
    assert spec.name == "grid(2,4)"
    assert PT.get_partitioner("grid(2,4)") is spec  # family specs cached
    # optional row/col policies must name registered 1-D partitioners
    assert PT.get_partitioner("grid(2,2,edge_balanced)") is not None
    with pytest.raises(ValueError):
        PT.get_partitioner("grid(2,2,metis)")
    with pytest.raises(ValueError):
        PT.get_partitioner("grid(0,2)")
    # the static 1-D registry is untouched by family lookups
    assert all(PT.grid_shape(n) is None for n in PT.partitioner_names())


def test_grid_plan_requires_matching_pe_count():
    g = graph("rmat6")
    with pytest.raises(ValueError, match="num_chunks"):
        PT.make_plan(g, 4, "grid(2,4)")


# ---------------------------------------------------------------------------
# GridPlan invariants (deterministic twins of the hypothesis properties)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("gname", sorted(EQUIV_GRAPHS + DEGENERATE_GRAPHS))
def test_grid_plan_invariants(gname, shape):
    """Every edge lands in exactly one rectangle, the rectangle bounds tile
    [0, E), and the row/col maps round-trip through relabel()."""
    rows, cols = shape
    g = graph(gname)
    plan = PT.make_plan(g, rows * cols, f"grid({rows},{cols})")
    V = g.num_vertices
    # each edge in exactly one rectangle: the per-edge rectangle id is a
    # total function of (src row chunk, dst col chunk), so counts sum to E
    rect = plan.row.vertex_chunk[g.src] * cols + plan.col.vertex_chunk[g.dst]
    assert np.array_equal(np.bincount(rect, minlength=rows * cols),
                          plan.rect_counts)
    assert int(plan.rect_counts.sum()) == g.num_edges
    # rectangle bounds tile [0, E)
    starts = plan.rect_starts
    assert starts[0] == 0
    assert np.array_equal(starts[1:], starts[:-1] + plan.rect_counts[:-1])
    assert int(starts[-1] + plan.rect_counts[-1]) == g.num_edges
    # row/col maps round-trip
    for axis in (plan.row, plan.col):
        g2l, l2g = axis.relabel()
        assert np.array_equal(l2g[g2l], np.arange(V))
        pad = np.ones(axis.num_chunks * axis.chunk_size, bool)
        pad[g2l] = False
        assert (l2g[pad] == -1).all()


@pytest.mark.parametrize("shape", ((2, 2), (2, 3)))
@pytest.mark.parametrize("gname", sorted(EQUIV_GRAPHS + DEGENERATE_GRAPHS))
def test_grid_layout_preserves_edges(gname, shape):
    """The packed rectangle layout reconstructs the exact original
    (src, dst, weight) edge multiset through the row/col relabels."""
    rows, cols = shape
    pg = _grid_pg(gname, rows, cols, weighted=graph(gname).num_edges > 0)
    g = pg.graph
    plan = pg.plan
    _, row_l2g = plan.row.relabel()
    _, col_l2g = plan.col.relabel()
    kr = plan.chunk_size
    rec = []
    for k in range(pg.num_chunks):
        r = k // cols
        sel = pg.gr_edge_valid[k] == 1
        src_orig = row_l2g[r * kr + pg.gr_src_local[k][sel]]
        dst_orig = col_l2g[pg.gr_dst_col[k][sel]]
        rec.extend(zip(src_orig.tolist(), dst_orig.tolist(),
                       pg.gr_edge_weight[k][sel].tolist()))
    want = sorted(zip(g.src.tolist(), g.dst.tolist(),
                      g.edge_weights.tolist()))
    assert sorted(rec) == want
    # and each rectangle's edges stay inside its own column block
    kc = pg.col_chunk_size
    for k in range(pg.num_chunks):
        sel = pg.gr_edge_valid[k] == 1
        if sel.any():
            assert (pg.gr_dst_col[k][sel] // kc == k % cols).all()


@pytest.mark.parametrize("shape", ((2, 2), (3, 1), (1, 3)))
def test_grid_replicated_state_planes(shape):
    """Per-vertex planes are the row layout replicated across each row's
    columns, and the relabel arrays agree with them."""
    rows, cols = shape
    pg = _grid_pg("rmat6", rows, cols)
    V = pg.graph.num_vertices
    vv = pg.vertex_valid.reshape(rows, cols, pg.chunk_size)
    dd = pg.out_degree.reshape(rows, cols, pg.chunk_size)
    ll = pg.local_to_global.reshape(rows, cols, pg.chunk_size)
    mm = pg.gr_row_to_col.reshape(rows, cols, pg.chunk_size)
    for c in range(1, cols):
        np.testing.assert_array_equal(vv[:, c], vv[:, 0])
        np.testing.assert_array_equal(dd[:, c], dd[:, 0])
        np.testing.assert_array_equal(ll[:, c], ll[:, 0])
        np.testing.assert_array_equal(mm[:, c], mm[:, 0])
    # g2l names the column-0 replica and round-trips every original id
    assert np.array_equal(pg.local_to_global[pg.global_to_local],
                          np.arange(V))
    # row_to_col maps live slots onto the column relabel
    col_g2l, _ = pg.plan.col.relabel()
    flat_l2g = pg.local_to_global
    flat_map = pg.gr_row_to_col.reshape(-1)
    live = flat_l2g >= 0
    np.testing.assert_array_equal(flat_map[live], col_g2l[flat_l2g[live]])
    assert (flat_map[~live] == -1).all()


def test_rect_degree_splits_out_degree_by_column():
    pg = _grid_pg("rmat6", 2, 3)
    rows, cols = pg.grid_shape
    rd = pg.rect_degree.reshape(rows, cols, pg.chunk_size)
    # summing a row's rectangles over the columns recovers the row chunk's
    # true out-degrees (degree-0 vertices stay 0 here, unlike pg.out_degree)
    summed = rd.sum(axis=1)
    _, row_l2g = pg.plan.row.relabel()
    want = np.zeros(rows * pg.chunk_size, np.int64)
    live = row_l2g >= 0
    want[live] = pg.graph.out_degrees[row_l2g[live]]
    np.testing.assert_array_equal(summed.reshape(-1), want)


# ---------------------------------------------------------------------------
# partition_stats on rectangles
# ---------------------------------------------------------------------------


def test_partition_stats_grid_fields():
    pg = _grid_pg("rmat6", 2, 2)
    st = PT.partition_stats(pg)
    assert st["grid_shape"] == (2, 2)
    assert int(st["edges_per_chare"].sum()) == pg.graph.num_edges
    assert st["edge_imbalance"] >= 1.0
    # frontier view charges each rectangle only its own column's edges
    frontier = np.ones((pg.num_chunks, pg.chunk_size), np.int32)
    stf = PT.partition_stats(pg, frontier=frontier)
    np.testing.assert_array_equal(stf["frontier_edges"],
                                  st["edges_per_chare"])
    assert stf["frontier_edge_imbalance"] == pytest.approx(
        st["edge_imbalance"])


# ---------------------------------------------------------------------------
# Engine: grid(1,1) end-to-end equivalence (multi-PE shapes: subprocess suite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("gname", sorted(EQUIV_GRAPHS))
@pytest.mark.parametrize("name", sorted(P.PROGRAMS))
def test_grid_equivalence_single_pe(name, gname):
    spec = get_spec(name)
    g = program_graph(name, gname)
    params = source_params(spec)
    ref = serial_ref(name, gname, tuple(sorted(params.items())))
    got, iters = run_parallel(g, name, num_pes=1, partitioner="grid(1,1)",
                              **params)
    assert iters >= 1
    if spec.exact:
        assert np.array_equal(np.asarray(got), np.asarray(ref))
    else:
        assert float(np.max(np.abs(np.asarray(got) - np.asarray(ref)))) < 1e-6


def test_engine_strategy_follows_partition():
    g = graph("rmat6")
    pg = G.partition(g, 1, partitioner="grid(1,1)")
    # any requested 1-D strategy resolves to grid2d on a grid partition
    eng = Engine(pg, strategy="reduction")
    assert eng.strategy == "grid2d"
    assert eng.dispatch["layout"] == "grid"
    assert eng.dispatch["choice"] in ("by plane", "staged")
    # grid2d on a 1-D partition is an error
    with pytest.raises(ValueError, match="grid"):
        Engine(G.partition(g, 1), strategy="grid2d")


def test_grid_partition_guards():
    pg = _grid_pg("rmat6", 2, 2)
    with pytest.raises(ValueError):
        pg._layout("basic")
    with pytest.raises(ValueError):
        _ = pg.sd_src_local
    with pytest.raises(ValueError):
        G.build_pairwise(pg)
    one_d = G.partition(graph("rmat6"), 2)
    with pytest.raises(ValueError):
        _ = one_d.gr_src_local
    with pytest.raises(ValueError):
        _ = one_d.col_chunk_size


def test_grid_repartition_roundtrip():
    """repartition() crosses dimensionality both ways and the composed row
    maps carry state placement (the replan contract)."""
    g = graph("rmat6")
    one_d = G.partition(g, 1, partitioner="contiguous")
    grid = one_d.repartition("grid(1,1)")
    assert grid.is_grid and grid.num_chunks == 1
    back = grid.repartition("contiguous")
    assert not back.is_grid
    assert back.plan.same_as(one_d.plan)
    assert not PT.make_plan(g, 1, "grid(1,1)").same_as(one_d.plan)
    # the grid's row plan composes with 1-D plans through the same algebra
    move = PT.row_plan_of(grid.plan).padded_map_from(
        PT.row_plan_of(one_d.plan))
    live = move >= 0
    assert live.sum() == g.num_vertices


def test_grid_replan_single_pe_bit_exact():
    """1-D <-> 2-D replans at C=1 (real multi-chare switches run in the
    subprocess suite): state carried through the composed row relabel."""
    from repro.core.engine import ReplanPolicy

    name = "sssp"
    g = program_graph(name, "rmat6")
    ref = serial_ref(name, "rmat6", (("source", 3),))
    for start, target in (("contiguous", "grid(1,1)"),
                          ("grid(1,1)", "edge_balanced")):
        got, _ = run_parallel(g, name, num_pes=1, partitioner=start,
                              source=3,
                              replan=ReplanPolicy(target, every=2,
                                                  mode="always"))
        assert np.array_equal(got, ref), (start, target)


@pytest.mark.parametrize("fused", (True, False))
def test_grid_push_hook_paths(fused):
    """Phase 1 of the two-phase reduce runs the per-rectangle band kernels:
    both the fused single-launch path and the staged dense pair must match
    the serial references when forced through the hook."""
    from repro.kernels import ops

    for name in ("sssp", "bfs"):
        spec = get_spec(name)
        g = program_graph(name, "rmat6")
        ref = serial_ref(name, "rmat6", (("source", 3),))
        got, _ = run_parallel(g, name, num_pes=1, partitioner="grid(1,1)",
                              push_fn=ops.make_push_fn(fused=fused), source=3)
        assert np.array_equal(np.asarray(got), np.asarray(ref)), (name, fused)


# ---------------------------------------------------------------------------
# COST harness threading
# ---------------------------------------------------------------------------


def test_run_cost_threads_grid_cells():
    g = graph("rmat6")
    report = run_cost(g, "pagerank", pe_counts=(1,),
                      partitioners=("contiguous", "grid(1,1)"), repeats=1)
    assert ("grid(1,1)", "grid2d", 1) in report.parallel_s
    assert ("grid(1,1)", "grid2d") in report.cost
    assert report.dispatch[("grid(1,1)", "grid2d", 1)]["layout"] == "grid"
    # 1-D cells are unaffected
    assert ("contiguous", "sortdest", 1) in report.parallel_s


def test_run_cost_skips_unmeasurable_grid_cells():
    """A grid whose R*C is not in the PE sweep must produce NO verdict --
    an unmeasured cell surfacing as COST=inf would misreport a
    configuration that was never timed."""
    g = graph("rmat6")
    report = run_cost(g, "pagerank", pe_counts=(1,),
                      partitioners=("contiguous", "grid(2,2)"), repeats=1)
    assert not any(k[0] == "grid(2,2)" for k in report.parallel_s)
    assert not any(k[0] == "grid(2,2)" for k in report.cost)
    assert ("contiguous", "sortdest") in report.cost


def test_replan_grid_shape_mismatch_fails_fast():
    """A replan target whose rectangle count differs from the engine's
    chare count must raise at run() entry, not when the trigger fires."""
    from repro.core.engine import ReplanPolicy

    eng = Engine(G.partition(graph("rmat6"), 1))
    with pytest.raises(ValueError, match="chares"):
        eng.run("bfs", source=0,
                replan=ReplanPolicy("grid(2,2)", mode="skew"))
    # unknown target names fail at entry too, not when the trigger fires
    with pytest.raises(ValueError, match="unknown partitioner"):
        eng.run("bfs", source=0,
                replan=ReplanPolicy("degre_sorted", mode="skew"))


# ---------------------------------------------------------------------------
# Wire model (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------


def test_wire_model_grid_terms():
    g = graph("rmat10")
    m = wire_model(g, 4, partitioner="grid(2,2)")
    assert set(m) == {"grid2d"}
    # degenerate axes: R=1 has no column combine, C=1 no redistribution
    plan = PT.make_plan(g, 2, "grid(1,2)")
    assert wire_model(g, 2, partitioner="grid(1,2)")["grid2d"] == \
        plan.chunk_size * 4 * (2 - 1) / 2
    only_combine = wire_model(g, 2, partitioner="grid(2,1)")["grid2d"]
    plan21 = PT.make_plan(g, 2, "grid(2,1)")
    assert only_combine == 2 * min(plan21.col_chunk_size,
                                   int(plan21.rect_counts.max())) * 4 / 2


@pytest.mark.slow
def test_wire_model_grid_beats_1d_basic_at_8pes():
    """Acceptance: on the scale-13 RMAT stand-in, grid(2,4)'s two-phase
    reduce puts fewer bytes on the wire than the basic variant under ANY
    registered 1-D partitioner at 8 PEs -- in 2-D the edge data never
    moves, so the payload is vertex- not cut-edge-proportional."""
    g = G.load_dataset("soc-lj1-mini", scale_log2=13)
    grid_bytes = wire_model(g, 8, partitioner="grid(2,4)")["grid2d"]
    for pname in PT.partitioner_names():
        basic = wire_model(g, 8, partitioner=pname)["basic"]
        assert grid_bytes < basic, (pname, grid_bytes, basic)

"""Superstep-adaptive execution (ISSUE 4): relabel composition algebra,
mid-run repartitioning equivalence (bit-exact for min monoids), replan
cache-invalidation regressions, the staged-vs-fused dispatch policy, and the
repartition-vs-from-scratch prep race.

Multi-chare replans (real 4-partitioner switches at 2/8 PEs) run in the
test_multidevice subprocess suite; in this single-device process the
built-in policies are mostly identity permutations at C=1, so the
``reversed`` test partitioner below guarantees a real state move through
the composed relabel.
"""

import functools

import numpy as np
import pytest

from conftest import (ALL_PARTITIONERS, graph, program_graph, race,
                      serial_ref, source_params)
from repro.core import (Engine, ReplanPolicy, get_spec, pagerank_serial,
                        run_parallel)
from repro.core import graph as G
from repro.core import partitioners as PT
from repro.core import programs as P
from repro.kernels import blocks

REPLAN_GRAPH = "rmat6"


@pytest.fixture
def reverse_partitioner():
    """A test-only policy whose permutation is never the identity (V > 1),
    so replans at C=1 exercise a real state move; removed on teardown to
    keep the registry sweep assertions exact."""

    def _plan(g, C):
        n = g.num_vertices
        K = -(-n // C) if n else 1
        counts = np.clip(n - K * np.arange(C, dtype=np.int64), 0, K)
        return PT.PartitionPlan(C, np.arange(n - 1, -1, -1, dtype=np.int64),
                                counts)

    PT.register_partitioner(
        PT.PartitionerSpec("reversed", _plan, wins="test-only"))
    yield "reversed"
    PT.PARTITIONERS.pop("reversed", None)


# ---------------------------------------------------------------------------
# Composition algebra (deterministic twins of the hypothesis properties)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("a", ALL_PARTITIONERS)
@pytest.mark.parametrize("b", ALL_PARTITIONERS)
def test_compose_equals_composed_mapping(a, b):
    """For any two partitioner plans: rebasing B onto A and composing back
    reproduces B exactly, and the padded map IS B's g2l applied on top of
    A's l2g -- the replan state-move contract."""
    g = graph(REPLAN_GRAPH)
    A = PT.make_plan(g, 3, a)
    B = PT.make_plan(g, 3, b)
    D = B.rebase(A)
    assert A.compose(D).same_as(B)
    np.testing.assert_array_equal(A.compose(D).order, A.order[D.order])
    m = B.padded_map_from(A)
    g2l_a, l2g_a = A.relabel()
    g2l_b, _ = B.relabel()
    live = l2g_a >= 0
    np.testing.assert_array_equal(m[live], g2l_b[l2g_a[live]])
    assert (m[~live] == -1).all()


def test_compose_identity_associativity_and_roundtrip():
    g = graph(REPLAN_GRAPH)
    ident = PT.make_plan(g, 3, "contiguous")  # identity permutation
    A = PT.make_plan(g, 3, "striped")
    B = PT.make_plan(g, 3, "degree_sorted")
    D = PT.make_plan(g, 3, "edge_balanced")
    assert ident.compose(B).same_as(B)
    assert A.compose(ident).order.tolist() == A.order.tolist()
    assert A.compose(B).compose(D).same_as(A.compose(B.compose(D)))
    # a composed plan is a valid plan: its relabel round-trips
    g2l, l2g = A.compose(B).relabel()
    assert np.array_equal(l2g[g2l], np.arange(g.num_vertices))
    with pytest.raises(ValueError):
        A.compose(PT.make_plan(graph("ring12"), 3, "contiguous"))
    with pytest.raises(ValueError):
        A.rebase(PT.make_plan(graph("ring12"), 3, "contiguous"))


def test_composed_plan_materializes_valid_layout():
    """A plan built by composition must materialize with the same layout
    invariants as a planned one: edges preserved in original ids, and the
    tile-granular sort-destination order intact."""
    g = graph("rmat10")
    A = PT.make_plan(g, 2, "striped")
    B = PT.make_plan(g, 2, "degree_sorted")
    comp = A.compose(B.rebase(A))  # == B, via the algebra
    pg = G.partition(g, 2).repartition("degree_sorted", plan=comp)
    l2g = pg.local_to_global
    rec = []
    for c in range(pg.num_chunks):
        sel = pg.sd_edge_valid[c] == 1
        padded_src = pg.sd_src_local[c][sel] + c * pg.chunk_size
        rec.extend(zip(l2g[padded_src].tolist(),
                       l2g[pg.sd_dst_global[c][sel]].tolist()))
    assert sorted(rec) == sorted(zip(g.src.tolist(), g.dst.tolist()))
    nsb = -(-pg.chunk_size // blocks.BLOCK_V)
    for c in range(pg.num_chunks):
        sel = pg.sd_edge_valid[c] == 1
        d = pg.sd_dst_global[c][sel].astype(np.int64)
        s = pg.sd_src_local[c][sel].astype(np.int64)
        key = (d // blocks.BLOCK_S) * nsb + s // blocks.BLOCK_V
        assert np.all(np.diff(key) >= 0), \
            "composed plan broke the tile-granular sd order"


# ---------------------------------------------------------------------------
# Repartition: equivalence to from-scratch builds, laziness, prep reuse
# ---------------------------------------------------------------------------


def test_repartition_equals_from_scratch_partition():
    g = graph("rmat10")
    pg = G.partition(g, 4)
    for target in ALL_PARTITIONERS:
        rp = pg.repartition(target)
        fs = G.partition(g, 4, target)
        for f in ("src_local", "dst_global", "edge_valid", "edge_weight",
                  "sd_src_local", "sd_dst_global", "sd_edge_weight", "band",
                  "sd_band", "out_degree", "out_weight", "vertex_valid",
                  "global_to_local", "local_to_global"):
            np.testing.assert_array_equal(getattr(rp, f), getattr(fs, f),
                                          err_msg=f"{target}.{f}")


def test_repartition_layouts_are_lazy_and_prep_is_shared():
    g = graph("rmat10")
    pg = G.partition(g, 2)
    assert set(pg._lazy) == {"basic", "sd"}  # partition() stays eager
    rp = pg.repartition("degree_sorted")
    assert rp._prep is pg._prep  # plan-independent prep reused, not rebuilt
    assert rp._lazy == {}  # nothing materialized yet
    rp.sd_band
    assert set(rp._lazy) == {"sd"}  # only the demanded layout built
    fs = G.partition(g, 2, "degree_sorted")
    np.testing.assert_array_equal(rp.sd_src_local, fs.sd_src_local)
    np.testing.assert_array_equal(rp.band, fs.band)  # on-demand, still right


@pytest.mark.slow
def test_repartition_cheaper_than_from_scratch_partition():
    """Acceptance: the replan path (repartition + materializing only the
    strategy's layout) is measurably cheaper than an eager from-scratch
    ``partition`` on the scale-13 stand-in -- it skips the COO/weight-sum
    prep AND the unused layout's radix sort + pack (measured ~0.45-0.65x;
    enforced at 0.85x for CI headroom)."""
    g = G.load_dataset("soc-lj1-mini", scale_log2=13, seed=1)
    pg = G.partition(g, 8)

    def replan_path():
        rp = pg.repartition("edge_balanced")
        rp.sd_band  # force what Engine._rebind ships for sortdest/pairs

    def from_scratch():
        G.partition(g, 8, "edge_balanced")

    replan_path(), from_scratch()  # warm caches
    t_re, t_full = race(replan_path, from_scratch, repeats=7)
    assert t_re < 0.85 * t_full, \
        f"replan path {t_re:.4f}s vs from-scratch {t_full:.4f}s"


# ---------------------------------------------------------------------------
# Mid-run repartition: replan == no-replan across programs and switches
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _no_replan(name):
    spec = get_spec(name)
    g = program_graph(name, REPLAN_GRAPH)
    return run_parallel(g, name, num_pes=1, strategy="sortdest",
                        **source_params(spec))


@pytest.mark.parametrize("start", ALL_PARTITIONERS)
@pytest.mark.parametrize("target", ALL_PARTITIONERS)
def test_bfs_replan_bit_exact_all_partitioner_pairs(start, target):
    """All 16 ordered partitioner switches, forced at every checkpoint:
    min-monoid results must be bit-exact vs the serial reference AND the
    no-replan run, with identical superstep counts."""
    g = program_graph("bfs", REPLAN_GRAPH)
    ref = serial_ref("bfs", REPLAN_GRAPH, (("source", 3),))
    base, base_iters = _no_replan("bfs")
    got, iters = run_parallel(
        g, "bfs", num_pes=1, strategy="sortdest", partitioner=start,
        source=3, replan=ReplanPolicy(target, every=2, mode="always"))
    assert np.array_equal(got, ref)
    assert np.array_equal(got, base)
    assert iters == base_iters


_ROTATED = list(zip(ALL_PARTITIONERS,
                    ALL_PARTITIONERS[1:] + ALL_PARTITIONERS[:1]))


@pytest.mark.parametrize("start,target", _ROTATED)
@pytest.mark.parametrize("name", sorted(P.PROGRAMS))
def test_all_programs_survive_midrun_repartition(name, start, target):
    """Every registered program x a rotating cover of partitioner switches:
    bit-exact for min programs, < 1e-6 vs no-replan for PageRank (the only
    difference is the float segment-combine order under the new layout)."""
    spec = get_spec(name)
    g = program_graph(name, REPLAN_GRAPH)
    base, base_iters = _no_replan(name)
    got, iters = run_parallel(
        g, name, num_pes=1, strategy="sortdest", partitioner=start,
        replan=ReplanPolicy(target, every=3, mode="always"),
        **source_params(spec))
    assert iters == base_iters
    if spec.exact:
        assert np.array_equal(got, base), f"{name}: {start}->{target}"
    else:
        dev = np.max(np.abs(np.asarray(got, np.float64)
                            - np.asarray(base, np.float64)))
        assert dev < 1e-6, f"{name}: {start}->{target} deviates {dev}"


@pytest.mark.parametrize("strategy", ("reduction", "basic", "pairs"))
def test_replan_under_every_strategy(strategy):
    """The rotated sweep runs sortdest; the other strategies (including
    basic's pairwise layout, rebuilt per replan) must survive a mid-run
    switch bit-exactly too."""
    g = program_graph("bfs", REPLAN_GRAPH)
    ref = serial_ref("bfs", REPLAN_GRAPH, (("source", 3),))
    got, _ = run_parallel(g, "bfs", num_pes=1, strategy=strategy, source=3,
                          replan=ReplanPolicy("degree_sorted", every=2,
                                              mode="always"))
    assert np.array_equal(got, ref)


def test_replan_to_reversed_is_a_real_state_move(reverse_partitioner):
    """At C=1 the built-ins are near-identity; the reversed policy forces an
    actual permutation change, so this exercises the composed-relabel state
    scatter (not just the checkpoint/resume plumbing)."""
    g = program_graph("sssp", REPLAN_GRAPH)
    ref = serial_ref("sssp", REPLAN_GRAPH, (("source", 3),))
    eng = Engine(G.partition(g, 1, "contiguous"))
    got, _ = eng.run("sssp", source=3,
                     replan=ReplanPolicy(reverse_partitioner, every=2,
                                         mode="always"))
    assert eng.pg.partitioner == reverse_partitioner
    assert not np.array_equal(eng.pg.global_to_local,
                              np.arange(g.num_vertices))
    assert np.array_equal(got, ref)  # bit-exact across the move


def test_replan_string_shorthand_and_skew_default():
    """``replan="name"`` wraps into the default skew-triggered policy; the
    result must match the serial reference whether or not a replan fires."""
    g = program_graph("bfs", REPLAN_GRAPH)
    ref = serial_ref("bfs", REPLAN_GRAPH, (("source", 3),))
    got, _ = run_parallel(g, "bfs", num_pes=1, source=3,
                          replan="edge_balanced")
    assert np.array_equal(got, ref)


def test_replan_policy_validation():
    with pytest.raises(ValueError):
        ReplanPolicy("edge_balanced", mode="sometimes")
    with pytest.raises(ValueError):
        ReplanPolicy("edge_balanced", every=0)


def test_engine_rejects_unknown_push_fn_string():
    """'fused'/'staged' are kernel-hook spellings, not engine push_fn values;
    an unrecognized string must fail at construction, not deep in tracing."""
    pg = G.partition(graph(REPLAN_GRAPH), 1)
    with pytest.raises(ValueError, match="push_fn"):
        Engine(pg, push_fn="fused")


def test_partition_stats_frontier_edges():
    """The skew trigger's input: per-chare out-edges of frontier vertices."""
    pg = G.partition(G.ring(8), 4)
    f = np.zeros((4, 2), np.int32)
    f[0] = 1  # both of chare 0's vertices active; each has out-degree 1
    st = PT.partition_stats(pg, frontier=f)
    assert st["frontier_edges"].tolist() == [2, 0, 0, 0]
    assert st["frontier_edge_imbalance"] == 4.0  # max 2 / mean 0.5
    assert "frontier_edges" not in PT.partition_stats(pg)


# ---------------------------------------------------------------------------
# Cache invalidation on replan (regression)
# ---------------------------------------------------------------------------


def test_replan_invalidates_device_and_compile_caches():
    """No stale reuse across a rebind: fresh device upload (band tables
    included), empty compile cache, and correct recomputation after."""
    g = graph(REPLAN_GRAPH)
    eng = Engine(G.partition(g, 1, "contiguous"))
    eng.run("bfs", source=0)
    old_arrays = eng.arrays
    assert len(eng._compiled) == 1
    new_pg = eng.pg.repartition("degree_sorted")
    assert new_pg._dev == {}  # nothing resident from the old placement
    eng._rebind(new_pg)
    assert eng.pg is new_pg
    assert eng._compiled == {}  # shapes/bands changed: no stale programs
    assert eng.arrays is not old_arrays
    assert eng.arrays["sd_band"] is not old_arrays["sd_band"]
    np.testing.assert_array_equal(np.asarray(eng.arrays["sd_band"]),
                                  new_pg.sd_band)  # fresh upload, new bands
    ref, _ = P.bfs_serial(g, source=0)
    got, _ = eng.run("bfs", source=0)
    assert np.array_equal(got, ref)  # no stale-result reuse
    with pytest.raises(ValueError):
        eng._rebind(G.partition(g, 2))  # replan must preserve chare count


# ---------------------------------------------------------------------------
# Dispatch policy: push_fn='auto'
# ---------------------------------------------------------------------------


def test_auto_dispatch_fused_on_rmat_staged_on_uniform():
    """Acceptance: auto mode picks fused on the scale-13 RMAT stand-in
    (power-law: narrow gather bands) and staged on a near-uniform gnp-style
    graph (bands degenerate toward the dense grid), asserted through the
    CostReport's recorded per-cell choice."""
    from repro.core.cost import run_cost

    reports = {
        "rmat": run_cost(G.load_dataset("soc-lj1-mini", scale_log2=13,
                                        seed=1),
                         "pagerank", pe_counts=(1,),
                         strategies=("sortdest",), repeats=1, iters=2),
        "uniform": run_cost(G.erdos_renyi(1 << 13, 2 * (1 << 13), seed=1),
                            "pagerank", pe_counts=(1,),
                            strategies=("sortdest",), repeats=1, iters=2),
    }
    d_rmat = reports["rmat"].dispatch[("contiguous", "sortdest", 1)]
    d_uni = reports["uniform"].dispatch[("contiguous", "sortdest", 1)]
    assert d_rmat["choice"] == "by plane"
    assert d_rmat["unbatched"]["mxu"]["choice"] == "fused"  # PageRank's
    assert d_uni["choice"] == "staged" and d_uni["reason"] == "occupancy"
    assert "unbatched" not in d_uni
    # the decision is the measured worst-side occupancy vs the threshold
    assert d_rmat["max_occupancy"] <= blocks.BAND_OCC_FUSED_MAX
    assert d_uni["max_occupancy"] > blocks.BAND_OCC_FUSED_MAX
    assert d_rmat["mode"] == "auto"
    assert d_rmat["tiles_fused"] < d_rmat["tiles_staged"]


def test_dispatch_prices_the_layouts_inner_side():
    """Each layout's outermost sort side is narrow by construction (sd:
    scatter, basic: gather) -- the rule must price the worse side, or the
    basic layout would pick fused on exactly the near-uniform graphs the
    policy exists to avoid (regression: gather-only rule)."""
    from benchmarks import kernelbench

    uni = G.partition(G.erdos_renyi(1 << 13, 2 * (1 << 13), seed=1), 2)
    rmat13 = G.partition(G.load_dataset("soc-lj1-mini", scale_log2=13,
                                        seed=1), 2)
    for layout in ("basic", "sd"):
        d_uni = kernelbench.layout_cost_model(uni, layout=layout)["dispatch"]
        d_rmat = kernelbench.layout_cost_model(rmat13,
                                               layout=layout)["dispatch"]
        assert d_uni["choice"] == "staged", (layout, d_uni)
        assert d_rmat["choice"] == "fused", (layout, d_rmat)
        # the basic layout's gather side alone would have said "fused"
        if layout == "basic":
            assert d_uni["gather_occupancy"] <= blocks.BAND_OCC_FUSED_MAX
            assert d_uni["scatter_occupancy"] > blocks.BAND_OCC_FUSED_MAX


def test_dispatch_explicit_override_preserved():
    from repro.kernels import ops

    pg = G.partition(graph(REPLAN_GRAPH), 1)
    hook = ops.make_push_fn()
    e = Engine(pg, push_fn=hook)
    assert e.push_fn is hook
    assert e.dispatch == {"choice": "explicit", "mode": "explicit",
                          "collectives": "full",  # 1-D: nothing to group
                          "residency": "resident", "builds": {}}
    e2 = Engine(pg, push_fn=None)
    assert e2.push_fn is None and e2.dispatch["mode"] == "explicit"
    e3 = Engine(pg, strategy="basic")  # no push loop to fuse
    assert e3.dispatch["choice"] == "staged" and "reason" in e3.dispatch


def test_dispatch_choice_consistent_with_threshold():
    """The engine's recorded choices always equal the rule applied to its
    own records, for every layout/strategy that fuses: the layout's
    occupancy against the threshold, then each plane's in-band tiles per
    edge block against the cap of its kernel body and width."""
    for gname in ("rmat6", "rmat10", "ring12"):
        pg = G.partition(graph(gname), 1)
        for strategy in ("sortdest", "reduction", "pairs"):
            eng = Engine(pg, strategy=strategy)
            eng.run("pagerank", iters=1)
            eng.run("bfs", source=0)
            eng.run_batch("bfs", sources=[0, 1], batch=16)
            d = eng.dispatch
            if d["max_occupancy"] > blocks.BAND_OCC_FUSED_MAX:
                assert d["choice"] == "staged", (gname, strategy, d)
                assert "unbatched" not in d and "batch" not in d
                continue
            assert d["choice"] == "by plane", (gname, strategy, d)
            planes = [*d["unbatched"].values(), *d["batch"][16].values()]
            assert {(r["body"], r["width"]) for r in planes} == {
                ("mxu", 1), ("vpu", 1), ("vpu", 16)}
            for rec in planes:
                cap = blocks.tiles_cap(rec["body"], rec["width"])
                want = ("fused" if d["tiles_per_edge_block"] <= cap
                        else "staged")
                assert rec["cap"] == cap, (gname, strategy, rec)
                assert rec["tiles_per_edge_block"] == d["tiles_per_edge_block"]
                assert rec["choice"] == want, (gname, strategy, rec)


def test_dispatch_declines_sweeps_too_wide_to_win(monkeypatch):
    """Occupancy is relative: bands that prune well can still leave each
    edge block more in-band tiles than the jnp push's per-edge cost
    allows.  The rule then declines with reason "tiles", before any
    footprint is counted, and the staged path answers (on a v5e, where a
    fused pick would install the Mosaic kernel)."""
    pg = G.partition(graph("rmat10"), 1)
    d = Engine(pg).dispatch
    assert d["choice"] == "by plane" and "reason" not in d
    assert d["tiles_per_edge_block"] == (d["tiles_fused"]
                                         / pg.sd_band.shape[-1])
    assert 1 < d["tiles_per_edge_block"] <= blocks.tiles_cap("vpu", 1)
    monkeypatch.setattr(blocks, "FUSED_TILES_PER_BLOCK_MAX", {
        plane: 1 for plane in blocks.FUSED_TILES_PER_BLOCK_MAX})
    _on_v5e(monkeypatch)
    eng = Engine(pg)
    got, it = eng.run("bfs", source=0)
    rec = eng.dispatch["unbatched"]["vpu"]
    assert rec["choice"] == "staged" and rec["reason"] == "tiles"
    assert rec["cap"] == 1
    assert "footprint" not in rec
    want, want_it = P.bfs_serial(graph("rmat10"), 0)
    assert np.array_equal(got, want) and it == want_it


@pytest.mark.parametrize("scale,choice", [(14, "fused"), (16, "staged")])
def test_dispatch_tile_rule_on_soc_lj1_stand_ins(scale, choice):
    """The float-add 1-D plane's tile cap splits the soc-lj1-mini
    stand-ins where the chip measurement put it: ~9.7 in-band tiles per
    edge block at 2^14 vertices, ~32 at 2^16, where the fused push lost to
    the jnp push."""
    pg = G.partition(G.load_dataset("soc-lj1-mini", scale_log2=scale,
                                    seed=1), 1)
    eng = Engine(pg)
    eng.run("pagerank", iters=1)
    d = eng.dispatch
    assert d["max_occupancy"] <= blocks.BAND_OCC_FUSED_MAX
    rec = d["unbatched"]["mxu"]  # the float-add 1-D plane
    assert rec["choice"] == choice, d
    assert rec.get("reason", "tiles") == "tiles"


def test_layout_cost_model_reports_dispatch():
    from benchmarks import kernelbench

    pg = G.partition(G.load_dataset("soc-lj1-mini", scale_log2=13, seed=1), 8)
    cm = kernelbench.layout_cost_model(pg)
    assert cm["dispatch"]["choice"] == "fused"
    assert cm["fused"]["tiles"] == cm["dispatch"]["tiles_fused"]
    assert cm["staged"]["tiles"] == cm["dispatch"]["tiles_staged"]


# -- fast-memory footprint check of the fused kernel (engine dispatch) -------


def _on_v5e(monkeypatch, vmem=None, smem=None):
    """Steer the dispatch as on a v5e: the mesh's device reports that kind,
    optionally with shrunken fast-memory capacities."""
    cap = dict(blocks.TPU_FAST_MEMORY["TPU v5 lite"])
    cap.update({k: v for k, v in (("vmem", vmem), ("smem", smem))
                if v is not None})
    monkeypatch.setattr(blocks, "TPU_FAST_MEMORY", {"TPU v5 lite": cap})
    monkeypatch.setattr(Engine, "_kernel_device_kind",
                        lambda self: "TPU v5 lite")


@pytest.mark.parametrize("space", ["vmem", "smem"])
def test_dispatch_declines_fused_where_fast_memory_overflows(monkeypatch,
                                                             space):
    pg = G.partition(graph("rmat10"), 1)
    # the band rule's pick for the float-add 1-D plane (PageRank)
    assert blocks.choose_push(Engine(pg).dispatch, body="mxu",
                              width=1)[0] == "fused"
    _on_v5e(monkeypatch, **{space: 1024})
    eng = Engine(pg)
    got, _ = eng.run("pagerank", iters=5)  # the staged path still answers
    rec = eng.dispatch["unbatched"]["mxu"]
    assert rec["choice"] == "staged"
    assert rec["reason"] == space
    fp = rec["footprint"]
    assert fp["device_kind"] == "TPU v5 lite"
    assert fp[f"{space}_bytes"] > 1024
    want = pagerank_serial(graph("rmat10"), iters=5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def _block_rings(n=4096):
    """A ring inside each BLOCK_V-vertex block: every edge block touches one
    source and one segment block, the narrowest bands a graph can have."""
    src = np.arange(n)
    width = blocks.BLOCK_V
    dst = src // width * width + (src % width + 1) % width
    return G.from_edges(n, src, dst, directed=True)


def test_dispatch_checks_each_batch_width(monkeypatch):
    """A plane that fits at B=1 can overflow at B=16: run_batch records
    its own decision per width and falls back to the staged push.  The
    bands are narrow enough for the float-add body (personalized PageRank)
    at both widths, so the footprint decides."""
    pg = G.partition(_block_rings(), 1)
    geom = dict(num_edge_blocks=blocks.num_edge_blocks(
        pg.edge_valid.shape[1]), V=pg.chunk_size, S=pg.chunk_size)
    one = blocks.fused_footprint(**geom)["vmem"]
    assert blocks.fused_footprint(**geom, batch=16)["vmem"] > one
    _on_v5e(monkeypatch, vmem=one)
    assert blocks.fused_misfit(blocks.fused_footprint(**geom),
                               "TPU v5 lite") is None
    eng = Engine(pg)
    assert eng.dispatch["tiles_per_edge_block"] <= blocks.tiles_cap("mxu", 16)
    seeds = [(0,), (1,)]
    plane, _ = eng.run_batch("personalized_pagerank", sources=seeds,
                             batch=16)
    rec = eng.dispatch["batch"][16]["mxu"]
    assert rec["choice"] == "staged" and rec["reason"] == "vmem"
    want, _ = Engine(pg, push_fn=None).run_batch(
        "personalized_pagerank", sources=seeds, batch=16)
    assert np.array_equal(plane, want)


def test_dispatch_b16_min_plane_declines_by_tiles(monkeypatch):
    """The VPU body's work grows with the plane's width: on rmat10 the B=16
    min plane is over the VPU cap at width 16, so it declines with reason
    "tiles" before any footprint is counted (on a v5e, where a fused pick
    would install the Mosaic kernel), and the staged path answers."""
    pg = G.partition(graph("rmat10"), 1)
    _on_v5e(monkeypatch)
    eng = Engine(pg)
    tiles = eng.dispatch["tiles_per_edge_block"]
    assert blocks.tiles_cap("vpu", 16) < tiles
    plane, iters = eng.run_batch("bfs", sources=[0, 1], batch=16)
    assert eng.dispatch["batch"][16]["vpu"] == {
        "body": "vpu", "width": 16, "tiles_per_edge_block": tiles,
        "cap": blocks.tiles_cap("vpu", 16), "choice": "staged",
        "reason": "tiles"}
    for i, s in enumerate((0, 1)):
        want, want_it = P.bfs_serial(graph("rmat10"), s)
        assert np.array_equal(plane[i], want) and iters[i] == want_it


def test_dispatch_float_add_plane_keeps_fused_on_the_same_bands():
    """The same bands pushed by the MXU body on a 1-D float-add plane
    (PageRank) stay under that body's cap: the rule picks fused."""
    eng = Engine(G.partition(graph("rmat10"), 1))
    eng.run("pagerank", iters=1)
    rec = eng.dispatch["unbatched"]["mxu"]
    assert rec["choice"] == "fused" and "reason" not in rec
    assert rec["cap"] == blocks.tiles_cap("mxu", 1)
    assert rec["tiles_per_edge_block"] <= rec["cap"]


@pytest.mark.parametrize("combine,dtype,body", [
    ("add", np.float32, "mxu"), ("add", "bfloat16", "mxu"),
    ("add", np.int32, "vpu"), ("min", np.int32, "vpu"),
    ("min", np.float32, "vpu")])
def test_push_body_follows_the_kernel(combine, dtype, body):
    """The body the rule prices is the one ``fused_push`` runs."""
    import jax.numpy as jnp

    assert blocks.push_body(combine, jnp.dtype(dtype)) == body


def test_tiles_cap_unlisted_width_takes_the_next_wider():
    caps = blocks.FUSED_TILES_PER_BLOCK_MAX
    for body in ("mxu", "vpu"):
        widths = sorted(w for b, w in caps if b == body)
        for w in widths:
            assert blocks.tiles_cap(body, w) == caps[body, w]
        for lo, hi in zip(widths, widths[1:]):
            if hi - lo > 1:
                assert blocks.tiles_cap(body, lo + 1) == caps[body, hi]
        assert blocks.tiles_cap(body, 4 * widths[-1]) == caps[
            body, widths[-1]]


def test_dispatch_unknown_tpu_kind_is_an_error(monkeypatch):
    monkeypatch.setattr(Engine, "_kernel_device_kind",
                        lambda self: "TPU v0 unknown")
    with pytest.raises(ValueError, match="device kind"):
        Engine(G.partition(graph("rmat10"), 1))


# ---------------------------------------------------------------------------
# Compile counter: dispatch["builds"] counts compile-cache misses per program
# ---------------------------------------------------------------------------


def test_builds_counts_one_per_key():
    eng = Engine(G.partition(program_graph("sssp", REPLAN_GRAPH), 1))
    assert eng.dispatch["builds"] == {}
    eng.run("pagerank", iters=3)
    assert eng.dispatch["builds"] == {"pagerank": 1}
    eng.run("pagerank", iters=3)  # the same key: no build
    assert eng.dispatch["builds"] == {"pagerank": 1}
    eng.run("pagerank", iters=4)  # the iteration count is in the key
    assert eng.dispatch["builds"] == {"pagerank": 2}


def test_builds_one_per_bfs_source_in_run_none_in_run_batch():
    """``Engine.run`` keys its program on the source, so each new source
    builds; ``run_batch`` strips it, so a new source list of the same
    width builds nothing."""
    eng = Engine(G.partition(program_graph("bfs", REPLAN_GRAPH), 1))
    for n, source in enumerate((0, 1, 2), start=1):
        eng.run("bfs", source=source)
        assert eng.dispatch["builds"]["bfs"] == n
    eng.run("bfs", source=1)
    assert eng.dispatch["builds"]["bfs"] == 3
    eng.run_batch("bfs", sources=[0, 1], batch=2)
    assert eng.dispatch["builds"]["bfs"] == 4
    eng.run_batch("bfs", sources=[5, 9], batch=2)
    eng.run_batch("bfs", sources=[7], batch=2)
    assert eng.dispatch["builds"]["bfs"] == 4


def test_builds_count_the_rebuild_after_a_replan(reverse_partitioner):
    """``_rebind`` drops the compile cache: the segment after the replan
    builds again, and the counter, unlike the cache, survives it."""
    g = program_graph("sssp", REPLAN_GRAPH)
    eng = Engine(G.partition(g, 1, "contiguous"))
    eng.run("sssp", source=3,
            replan=ReplanPolicy(reverse_partitioner, every=2, mode="always",
                                max_replans=1))
    assert eng.pg.partitioner == reverse_partitioner
    assert eng.dispatch["builds"] == {"sssp": 2}

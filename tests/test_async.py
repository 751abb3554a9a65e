"""Barrier-relaxed execution (DESIGN.md section 12): deterministic twins.

Single-device cells for the overlap/gating/grouped-collective machinery --
the real multi-PE acceptance sweep lives in the forced-8-device subprocess
suite (tests/test_multidevice.py, ASYNC_SCRIPT).  Everything here must be
bit-exact: overlap delivers stale reads, but min-monoid label correcting
converges to the same fixpoint, and the double-check quiescence protocol
may only ever *lengthen* the run, never change the answer.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import (Engine, bfs_serial, partition, random_weights, rmat,
                        run_parallel, sssp_serial)
from repro.core.cost import grid_collective_bytes
from repro.core.engine import ReplanPolicy
from repro.kernels import blocks, ref

G = rmat(7, 600, seed=3)
GW = random_weights(G, seed=5)
SSSP_REF, SSSP_IT = sssp_serial(GW, source=7)
BFS_REF, BFS_IT = bfs_serial(G, source=7)


# -- overlap vs barrier (deterministic twins of the subprocess sweep) --------


@pytest.mark.parametrize("strategy", ["reduction", "sortdest", "basic",
                                      "pairs"])
@pytest.mark.parametrize("algo", ["sssp", "bfs"])
def test_overlap_matches_barrier(algo, strategy):
    g = GW if algo == "sssp" else G
    want, want_it = (SSSP_REF, SSSP_IT) if algo == "sssp" else (BFS_REF,
                                                                BFS_IT)
    eng = Engine(partition(g, 1), strategy=strategy)
    got_b, it_b = eng.run(algo, source=7)
    got_o, it_o = eng.run(algo, source=7, sync="overlap")
    assert np.array_equal(got_b, want)
    assert np.array_equal(got_o, want)
    assert it_b == want_it
    # double-check bound: staleness-1 pipeline at most doubles the superstep
    # count, plus the two quiescent sweeps the protocol pays for
    assert it_b <= it_o <= 2 * it_b + 2


def test_overlap_gate_bit_exact_and_accounted():
    eng = Engine(partition(GW, 1))
    got, it = eng.run("sssp", source=7, sync="overlap", gate="frontier")
    assert np.array_equal(got, SSSP_REF)
    rec = eng.dispatch["gate"]
    assert rec["sync"] == "overlap" and rec["enabled"]
    assert rec["launch_slots"] == it + 1  # the pre-loop seed push
    assert rec["launched"] + rec["skipped_launches"] == rec["launch_slots"]
    # the overlap pipeline alternates live/empty frontiers (depth-2 bubble),
    # so at 1 PE the gate skips the empty half
    assert rec["skipped_fraction"] >= 0.4
    # barrier + gate: every executed superstep has a live frontier at 1 PE
    got_b, it_b = eng.run("sssp", source=7, gate="frontier")
    assert np.array_equal(got_b, SSSP_REF)
    rec_b = eng.dispatch["gate"]
    assert rec_b["sync"] == "barrier" and rec_b["launch_slots"] == it_b
    assert rec_b["skipped_launches"] <= rec_b["launch_slots"]


def test_gate_off_records_zero():
    eng = Engine(partition(G, 1))
    eng.run("bfs", source=7)
    rec = eng.dispatch["gate"]
    assert not rec["enabled"]
    assert rec["skipped_launches"] == 0
    assert rec["skipped_fraction"] == 0.0


def test_replan_mid_overlap_drains():
    # segments drain the in-flight partial before every checkpoint, so a
    # replan policy firing mid-overlap must not change the fixpoint
    got, it = run_parallel(GW, "sssp", num_pes=1, source=7, sync="overlap",
                           gate="frontier",
                           replan=ReplanPolicy("edge_balanced", every=2,
                                               mode="always"))
    assert np.array_equal(got, SSSP_REF)
    assert it <= 2 * SSSP_IT + 2


def test_batch_overlap_per_query():
    eng = Engine(partition(GW, 1), strategy="reduction")
    srcs = [7, 0, 91]
    plane, q_it = eng.run_batch("sssp", sources=srcs, batch=4,
                                sync="overlap", gate="frontier")
    for i, s in enumerate(srcs):
        want, want_it = sssp_serial(GW, source=s)
        assert np.array_equal(plane[i], want)
        assert want_it <= int(q_it[i]) <= 2 * want_it + 2


def test_validate_async_errors():
    eng = Engine(partition(G, 1))
    with pytest.raises(ValueError, match="min-monoid"):
        eng.run("pagerank", sync="overlap")
    with pytest.raises(ValueError, match="convergence"):
        eng.run("pagerank", gate="frontier")
    with pytest.raises(ValueError, match="sync"):
        eng.run("bfs", source=0, sync="async")
    with pytest.raises(ValueError, match="gate"):
        eng.run("bfs", source=0, gate="bands")
    with pytest.raises(ValueError, match="collectives"):
        Engine(partition(G, 1), collectives="ring")


# -- grouped collectives (compat shim; multi-group cells in the subprocess) --


def _one_device_grouped(x, combine, mode):
    mesh = compat.make_mesh((1,), ("pe",), axis_types=compat.auto_axes(1))
    fn = jax.shard_map(
        lambda v: compat.grouped_reduce(
            v[0], "pe", [[0]], combine,
            **({} if mode is None else {"mode": mode}))[None],
        mesh=mesh, in_specs=P("pe"), out_specs=P("pe"), check_vma=False)
    return jax.jit(fn)(x[None])[0]


@pytest.mark.parametrize("mode", [None, "native", "emulate"])
@pytest.mark.parametrize("combine", ["add", "min"])
def test_grouped_reduce_degenerate_group(combine, mode):
    x = jnp.asarray(np.random.default_rng(0).normal(size=16), jnp.float32)
    got = _one_device_grouped(x, combine, mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_grouped_reduce_raises_when_native_lowering_fails(monkeypatch):
    """No silent fallback: a failing axis_index_groups lowering surfaces
    instead of being emulated at full-axis wire cost."""

    def refuse(x, axis, axis_index_groups=None):
        if axis_index_groups is not None:
            raise NotImplementedError("grouped lowering unavailable")
        return x

    monkeypatch.setattr(jax.lax, "psum", refuse)
    monkeypatch.setattr(jax.lax, "pmin", refuse)
    for combine in ("add", "min"):
        with pytest.raises(NotImplementedError, match="grouped"):
            compat.grouped_reduce(jnp.zeros(4), "pe", [[0, 2], [1, 3]],
                                  combine)


def test_grouped_reduce_rejects_unknown_combine():
    with pytest.raises(ValueError, match="combine"):
        compat.grouped_reduce(jnp.zeros(4), "pe", [[0]], "max")


def test_grid_1x1_grouped_matches_full():
    for coll in ("grouped", "full"):
        got, _ = run_parallel(GW, "sssp", num_pes=1, partitioner="grid(1,1)",
                              source=7, collectives=coll)
        assert np.array_equal(got, SSSP_REF)


def test_step_hlo_lowers():
    eng = Engine(partition(GW, 1))
    text = eng.step_hlo("sssp", source=7)
    assert isinstance(text, str) and "ENTRY" in text


# -- collective wire model (grouped vs full lowering) ------------------------


def test_grid_collective_bytes_model():
    m = grid_collective_bytes(GW, 8, "grid(2,4)")
    assert m["full"] > m["grouped"] > 0
    assert m["ratio"] == pytest.approx(m["grouped"] / m["full"])
    # grid(2,4): grouped/full = (Kc + 3*Kr/2) / (7*Kc) = 4/7 with Kr = 2*Kc
    assert m["ratio"] == pytest.approx(4 / 7, rel=1e-6)
    assert m["ratio"] <= 0.6  # the ISSUE 7 acceptance bound
    m22 = grid_collective_bytes(GW, 4, "grid(2,2)")
    assert m22["full"] > m22["grouped"] > 0
    with pytest.raises(ValueError, match="grid"):
        grid_collective_bytes(GW, 8, "contiguous")


# -- frontier gating geometry ------------------------------------------------


def test_band_source_mask_geometry():
    # chare 0: edge blocks cover source blocks [0,1] and [2,3]; chare 1 has
    # one empty edge block (lo=0, hi=-1) which must contribute nothing
    band = np.zeros((2, 4, 2), np.int32)
    band[0, 0] = [0, 2]   # src_lo per edge block
    band[0, 1] = [1, 3]   # src_hi
    band[1, 0] = [4, 0]
    band[1, 1] = [4, -1]
    got = blocks.band_source_mask(band, 6)
    want = np.array([[1, 1, 1, 1, 0, 0],
                     [0, 0, 0, 0, 1, 0]], np.int32)
    np.testing.assert_array_equal(got, want)
    # a single [4, NB] table is promoted to one chare
    np.testing.assert_array_equal(
        blocks.band_source_mask(band[0], 6), want[:1])


def test_frontier_block_mask_geometry():
    K = 2 * blocks.BLOCK_V + 10
    f = np.zeros(K, np.int32)
    f[3] = 1                      # block 0
    f[2 * blocks.BLOCK_V + 1] = 1  # block 2 (the ragged tail)
    np.testing.assert_array_equal(blocks.frontier_block_mask(f, 3),
                                  np.array([1, 0, 1], np.int32))
    assert blocks.frontier_block_mask(np.zeros(K, np.int32), 3).sum() == 0


def test_engine_gate_mask_bound():
    # the engine's per-shard gate mask has one row per chare and only covers
    # blocks inside the padded chunk (multi-PE rows in the subprocess suite)
    eng = Engine(partition(GW, 1), strategy="sortdest")
    gm = np.asarray(eng.arrays["gate_blocks"])
    assert gm.shape == (1, eng._gate_nsb)
    assert set(np.unique(gm)) <= {0, 1}


# -- serial stale-read simulator (the async reference) ----------------------


def _edges_of(g):
    return np.asarray(g.src), np.asarray(g.dst)


def _sssp_init(g, source):
    init = np.full(g.num_vertices, np.inf, np.float32)
    init[source] = 0.0
    return init


def test_async_ref_sync_schedule_is_jacobi():
    # age 0 everywhere == synchronous Jacobi: same fixpoint AND the same
    # sweep count convention as sssp_serial (converge + 1 quiescent sweep)
    src, dst = _edges_of(GW)
    w = np.asarray(GW.edge_weights, np.float32)
    state, sweeps = ref.async_min_fixpoint_ref(
        src, dst, _sssp_init(GW, 7), weight=w, max_stale=0)
    assert np.array_equal(state, SSSP_REF)
    assert sweeps == SSSP_IT


def test_async_ref_stale_reads_same_fixpoint():
    src, dst = _edges_of(GW)
    w = np.asarray(GW.edge_weights, np.float32)
    for max_stale in (1, 2, 3):
        for seed in (0, 1, 2):
            state, sweeps = ref.async_min_fixpoint_ref(
                src, dst, _sssp_init(GW, 7), weight=w,
                max_stale=max_stale, seed=seed)
            assert np.array_equal(state, SSSP_REF), (max_stale, seed)
            # bounded staleness delays each relaxation by <= max_stale
            # sweeps; the double check appends max_stale + 1 quiet sweeps
            assert sweeps <= (max_stale + 1) * (SSSP_IT + 1)


def test_async_ref_explicit_schedule():
    # an adversarial all-stale schedule (every read as old as allowed)
    src, dst = _edges_of(GW)
    w = np.asarray(GW.edge_weights, np.float32)
    ages = np.full((1, len(src)), 2)
    state, _ = ref.async_min_fixpoint_ref(
        src, dst, _sssp_init(GW, 7), weight=w, max_stale=2, ages=ages)
    assert np.array_equal(state, SSSP_REF)


def test_async_ref_bfs_semiring():
    # BFS = min-plus with unit weights on the reachability depth
    src, dst = _edges_of(G)
    init = np.full(G.num_vertices, np.inf, np.float32)
    init[7] = 0.0
    state, _ = ref.async_min_fixpoint_ref(
        src, dst, init, weight=np.ones(len(src), np.float32),
        max_stale=1, seed=4)
    sentinel = np.iinfo(np.int32).max  # unreached, the engine's MIN identity
    want = np.where(np.asarray(BFS_REF) >= sentinel, np.inf,
                    np.asarray(BFS_REF, np.float64)).astype(np.float32)
    assert np.array_equal(state, want)

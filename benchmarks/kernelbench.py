"""Kernel micro-benchmarks: the paper's per-chare hot loop.

The Pallas kernels target TPU; on this CPU container they execute through
the interpreter (correctness only), so the numbers that are *measured* here
are the jit'd pure-jnp reference pipeline (what the engine actually runs on
CPU), plus the kernels' analytic TPU cost model for the roofline narrative.

Two kernel paths are modeled and validated side by side (ISSUE 3 /
DESIGN.md section 8):

  staged  gather + scatter as separate dense-grid ``pallas_call``s (3 jitted
          stages with the weight transform between them); tile work
          O((E/BE)*(V/BV) + (S/BS)*(E/BE)) and the [E] intermediate makes a
          full HBM round trip.
  fused   one band-pruned launch: tile work is the sum of per-edge-block
          band widths (the partition-time ``band``/``sd_band`` metadata),
          and the intermediate never leaves VMEM.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.blocks import (BLOCK_E, BLOCK_V, band_facts, band_tiles,
                                  choose_push, dense_grid, num_edge_blocks)

# launch/stage counts per push: staged = gather kernel + weight stage +
# scatter kernel; fused = one pallas_call
LAUNCHES = {"staged": 3, "fused": 1}


def bench_ref(E=1 << 16, V=1 << 14, repeats=5, seed=0):
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    valid = jnp.ones((E,), jnp.int32)
    vals = jnp.asarray(rng.normal(size=V), jnp.float32)

    fn = jax.jit(lambda: ref.push_ref(vals, src, dst, valid, V))
    fn().block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best, E


def kernel_cost_model(E=1 << 16, V=1 << 14, S=None, band=None, chares=1,
                      weighted=False):
    """Analytic TPU cost of one push superstep, staged vs fused.

    ``E`` is the (padded) edge count per chare, ``V`` the gather-side vertex
    count per chare, ``S`` the scatter-side segment count (defaults to
    ``V``), ``chares`` the number of per-chare sweeps in the superstep,
    ``weighted`` whether a per-edge weight stream rides along (SSSP,
    weighted PageRank; BFS's "unit" transform is a compile-time constant
    and streams nothing).

    Tile counts: the staged dense grid visits every (edge-block x
    vertex-block) gather tile and (segment-block x edge-block) scatter tile;
    the fused kernel visits only the in-band tiles recorded in ``band``
    (``[..., 4, NB]`` metadata from ``blocks.edge_bands``, pre-summed over
    all its chares; when absent the fused path is modeled at its worst
    case == the dense grid).

    Flops: every visited tile is one [BLOCK_E, BLOCK] one-hot matmul
    (2*BE*B flops).  HBM bytes: the staged path moves indices+values in and
    the [E] intermediate out *per half* (2 launches); the fused path reads
    the edge arrays + band table once and keeps vals/out resident in VMEM
    for the whole sweep -- the intermediate contributes zero HBM traffic.
    """
    if S is None:
        S = V
    ne = num_edge_blocks(E)
    dense_tiles = sum(dense_grid(E, V, S, chares))
    fused_tiles = band_tiles(np.asarray(band)) if band is not None \
        else dense_tiles
    tile_flops = 2 * BLOCK_E * BLOCK_V  # == 2*BE*BS; square blocks
    staged_flops = dense_tiles * tile_flops
    fused_flops = fused_tiles * tile_flops
    # per-edge data: src+dst+valid (+weight when streamed) in; vals in,
    # out out; the staged path additionally round-trips the [E] intermediate
    edge_bytes = chares * E * 4 * (4 if weighted else 3)
    vert_bytes = chares * (V + S) * 4
    staged_hbm = (edge_bytes + vert_bytes) * 2 + chares * E * 4 * 2
    fused_hbm = edge_bytes + vert_bytes + chares * ne * 4 * 4  # + band table
    model = lambda f, b: {
        "flops": f,
        "hbm_bytes": b,
        "mxu_s": f / 197e12,
        "hbm_s": b / 819e9,
        "bound": "memory" if b / 819e9 > f / 197e12 else "compute",
    }
    return {
        "staged": {"tiles": dense_tiles, "launches": LAUNCHES["staged"],
                   **model(staged_flops, staged_hbm)},
        "fused": {"tiles": fused_tiles, "launches": LAUNCHES["fused"],
                  **model(fused_flops, fused_hbm)},
        "tile_ratio": dense_tiles / max(fused_tiles, 1),
        "tile_occupancy": fused_tiles / dense_tiles,
    }


def layout_cost_model(pg, layout="sd"):
    """``kernel_cost_model`` fed by a real partition's band metadata: one
    fused sweep per chare per superstep, bands summed over all chares.
    ``dispatch`` reports what ``Engine(push_fn='auto')`` would pick for this
    layout's 1-D float-add plane, the one this model prices (the adaptive
    staged-vs-fused rule, ``blocks.choose_push``)."""
    band = pg.sd_band if layout == "sd" else pg.band
    E, V, S = (pg.edge_valid.shape[1], pg.chunk_size,
               pg.num_chunks * pg.chunk_size)
    cm = kernel_cost_model(E=E, V=V, S=S, band=band, chares=pg.num_chunks)
    facts = band_facts(band, E, V, S)
    choice, rec = choose_push(facts, body="mxu", width=1)
    cm["dispatch"] = {"choice": choice, "layout": layout, **facts, **rec}
    return cm


def batched_cost_model(pg, B, layout="sd", weighted=False):
    """Analytic TPU throughput of the batched [*, B] plane vs a per-query
    loop (DESIGN.md section 11): the acceptance model behind the >=4x
    queries/sec criterion.

    One batched superstep streams the edge layout (indices, weights, band
    table) ONCE for all B query columns -- edge-stream tiles per query drop
    B-fold -- while the vertex-state bytes and the combine flops scale with
    B (each visited tile's one-hot matmul widens to [BLOCK_E, BLOCK] @
    [BLOCK, B]; modeled conservatively as flops x B even though B <= 16
    rides in one MXU pass).  The sequential baseline pays the full edge
    stream B times.

        t(b) = max((edge_bytes + vert_bytes*b) / HBM_BW,
                   tiles * tile_flops * b / MXU_FLOPS)
        speedup = t(1) / (t(B) / B)

    On the scale-13 stand-in the single-query sweep is memory-bound on the
    edge stream, so batching moves it toward the compute roofline and the
    per-query time collapses.
    """
    band = pg.sd_band if layout == "sd" else pg.band
    E, V, S = (pg.edge_valid.shape[1], pg.chunk_size,
               pg.num_chunks * pg.chunk_size)
    chares = pg.num_chunks
    ne = num_edge_blocks(E)
    tiles = band_tiles(np.asarray(band))
    tile_flops = 2 * BLOCK_E * BLOCK_V
    edge_bytes = chares * E * 4 * (4 if weighted else 3) + chares * ne * 4 * 4
    vert_bytes = chares * (V + S) * 4

    def t(b):
        hbm = (edge_bytes + vert_bytes * b) / 819e9
        mxu = tiles * tile_flops * b / 197e12
        return max(hbm, mxu)

    seq_s, batched_s = t(1), t(B) / B
    return {
        "B": B,
        "tiles_per_query_seq": tiles,
        "tiles_per_query_batched": tiles / B,
        "seq_s_per_query": seq_s,
        "batched_s_per_query": batched_s,
        "queries_per_sec_seq": 1.0 / seq_s,
        "queries_per_sec_batched": 1.0 / batched_s,
        "speedup": seq_s / batched_s,
    }


def fixediter_cost_model(pg, B, iters=20, layout="sd", weighted=False):
    """Amortization model for the FIXED-ITERATION batched plane
    (personalized PageRank et al., DESIGN.md section 14): the per-superstep
    roofline of ``batched_cost_model`` times exactly ``iters`` supersteps.

    Two things distinguish it from the convergence model: every query
    column runs the same counted loop (no per-query mask, no [B] active
    psum per superstep -- modeled as a per-superstep ``mask_bytes`` term
    the sequential side pays via its B separate loop dispatches), and the
    sequential baseline re-streams the edge layout ``iters`` times PER
    QUERY, so the B-fold edge-stream amortization compounds over the whole
    fixed run rather than racing a convergence frontier.
    """
    band = pg.sd_band if layout == "sd" else pg.band
    E, V, S = (pg.edge_valid.shape[1], pg.chunk_size,
               pg.num_chunks * pg.chunk_size)
    chares = pg.num_chunks
    ne = num_edge_blocks(E)
    tiles = band_tiles(np.asarray(band))
    tile_flops = 2 * BLOCK_E * BLOCK_V
    edge_bytes = chares * E * 4 * (4 if weighted else 3) + chares * ne * 4 * 4
    vert_bytes = chares * (V + S) * 4
    mask_bytes = chares * S * 4  # frontier plane the counted loop drops

    def t(b, masked):
        hbm = (edge_bytes + vert_bytes * b
               + (mask_bytes * b if masked else 0)) / 819e9
        mxu = tiles * tile_flops * b / 197e12
        return iters * max(hbm, mxu)

    seq_s, batched_s = t(1, masked=True), t(B, masked=False) / B
    return {
        "B": B, "iters": iters,
        "seq_s_per_query": seq_s,
        "batched_s_per_query": batched_s,
        "queries_per_sec_seq": 1.0 / seq_s,
        "queries_per_sec_batched": 1.0 / batched_s,
        "speedup": seq_s / batched_s,
    }


def streaming_cost_model(pg, windows=8, batch=1):
    """Bandwidth/compute roofline of the double-buffered window schedule
    (DESIGN.md section 13): is each window's H2D copy hidden behind the
    previous window's fused sweep on the modeled TPU?

    Per window the pipeline overlaps copy(k+1) with compute(k), so the
    steady-state superstep time is

        t_pipe  = copy[0] + sum_k max(compute[k], copy[k+1]) + compute[-1]
        t_serial = sum(copy) + sum(compute)

    with copy = window_bytes / host_link_bw (PCIe-class infeed) and
    compute = max(tiles * tile_flops * batch / MXU, window_bytes / HBM_BW)
    -- the same constants as ``batched_cost_model``.  ``hiding`` is the
    fraction of the serialized schedule the pipeline removes (1 would mean
    copies are free); ``crossover_intensity`` is the flops/byte a window
    must sustain for compute to fully hide its own copy
    (MXU_FLOPS / HOST_LINK_BW) next to the layout's measured intensity --
    windows below the crossover are copy-bound and the streamed run pays
    the host link, exactly the regime the measured ``overlap_efficiency``
    in BENCH_cost.json's streaming section quantifies on this host.

    ``batch`` prices the batched [*, B] query plane (DESIGN.md section 15):
    each staged window is swept once for all B columns, so its copy time
    is unchanged while its compute scales ~B-fold, raising the effective
    intensity B x.  ``edge_bytes_per_query`` is the amortized H2D cost
    (total / batch) and ``crossover_batch`` is the smallest B at which the
    layout's B-scaled intensity clears the copy crossover -- above it the
    streamed superstep leaves the copy-bound regime entirely.
    """
    HOST_LINK_BW = 16e9   # PCIe-class host->device infeed, bytes/s
    HBM_BW, MXU_FLOPS = 819e9, 197e12
    band = np.asarray(pg.gr_band)           # [P, 4, nb]
    P, _, nb = band.shape
    nbw = max(-(-nb // windows), 1)
    tile_flops = 2 * BLOCK_E * BLOCK_V
    per_block = P * BLOCK_E * 16 + P * 4 * 4  # src/dst/valid/weight + band
    copy, comp = [], []
    for k in range(0, nb, nbw):
        blo, bhi = k, min(nb, k + nbw)
        wbytes = (bhi - blo) * per_block
        tiles = band_tiles(band[:, :, blo:bhi])
        copy.append(wbytes / HOST_LINK_BW)
        comp.append(max(tiles * tile_flops * batch / MXU_FLOPS,
                        wbytes / HBM_BW))
    t_serial = sum(copy) + sum(comp)
    t_pipe = copy[0] + sum(max(comp[i], copy[i + 1])
                           for i in range(len(copy) - 1)) + comp[-1]
    total_bytes = nb * per_block
    total_tiles = band_tiles(band)
    intensity = total_tiles * tile_flops / total_bytes
    crossover = MXU_FLOPS / HOST_LINK_BW
    return {
        "windows": len(copy),
        "batch": batch,
        "window_bytes": nbw * per_block,
        "total_edge_bytes": total_bytes,
        "edge_bytes_per_query": total_bytes / batch,
        "copy_s": sum(copy),
        "compute_s": sum(comp),
        "pipelined_superstep_s": t_pipe,
        "serialized_superstep_s": t_serial,
        "hiding": 1.0 - t_pipe / t_serial if t_serial else 0.0,
        "bound": "copy" if sum(copy) > sum(comp) else "compute",
        "intensity_flops_per_byte": intensity,
        "crossover_intensity": crossover,
        "crossover_batch": int(max(1, math.ceil(crossover / intensity)))
        if intensity else 1,
    }


def validate(E=4096, V=2048, seed=1, fused=True):
    """Max |err| of one push path vs the pure-jnp oracle (CI smoke)."""
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, E), jnp.int32)
    vals = jnp.asarray(rng.normal(size=V), jnp.float32)
    got = ops.push(vals, src, dst, valid, V, combine="add", fused=fused)
    want = ref.push_ref(vals, src, dst, valid, V, combine="add")
    add_err = float(jnp.max(jnp.abs(got - want)))
    ivals = jnp.asarray(rng.integers(0, 10_000, V), jnp.int32)
    got = ops.push(ivals, src, dst, valid, V, combine="min", fused=fused)
    want = ref.push_ref(ivals, src, dst, valid, V, combine="min")
    min_err = float(jnp.max(jnp.abs(got - want)))
    return max(add_err, min_err)

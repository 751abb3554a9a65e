"""Multi-device behaviour, run in ONE subprocess with 8 forced host devices
(the main pytest process keeps the real single device per the dry-run
contract -- XLA_FLAGS must not leak into other tests)."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat

# the CI async matrix re-runs the min-monoid cells barrier-relaxed: under
# REPRO_SYNC=overlap every SSSP/BFS cell below runs phase-overlapped with
# frontier gating and must stay bit-exact (double-check quiescence); the
# fixed-iteration / add-monoid cells are sync-agnostic and keep the barrier
SYNC = os.environ.get("REPRO_SYNC", "barrier")
GATE = "frontier" if SYNC == "overlap" else None

results = {}

# ---- 1) graph engine: every strategy x PE count vs serial oracles --------
from repro.core import (rmat, two_cliques, pagerank_serial, pagerank_parallel,
                        labelprop_serial, labelprop_parallel, components_oracle)
g = rmat(8, 2000, seed=3)
ref = pagerank_serial(g)
pr_err = {}
for strat in ("reduction", "sortdest", "basic", "pairs"):
    for pes in (2, 4, 8):
        got = pagerank_parallel(g, pes, strategy=strat)
        pr_err[f"{strat}@{pes}"] = float(np.max(np.abs(got - ref)))
results["pagerank_max_err"] = max(pr_err.values())

gu = two_cliques(40).to_undirected()
oracle = components_oracle(gu)
lp_ok = all(
    np.array_equal(labelprop_parallel(gu, pes, strategy=s)[0], oracle)
    for s in ("reduction", "sortdest", "basic", "pairs") for pes in (2, 4))
results["labelprop_ok"] = bool(lp_ok)

# ---- 1b) partitioner policies at real multi-PE: permuted placement must be
# invisible at the API boundary (sources in original ids, results unpermuted)
from repro.core import random_weights, run_parallel, sssp_serial, bfs_serial
gw = random_weights(g, seed=5)
sssp_ref, _ = sssp_serial(gw, source=7)
bfs_ref, _ = bfs_serial(g, source=7)
part_ok = True
for pname in ("contiguous", "edge_balanced", "striped", "degree_sorted"):
    for pes in (2, 8):
        got_s, _ = run_parallel(gw, "sssp", num_pes=pes, strategy="sortdest",
                                partitioner=pname, source=7,
                                sync=SYNC, gate=GATE)
        got_b, _ = run_parallel(g, "bfs", num_pes=pes, strategy="basic",
                                partitioner=pname, source=7,
                                sync=SYNC, gate=GATE)
        part_ok &= bool(np.array_equal(got_s, sssp_ref))
        part_ok &= bool(np.array_equal(got_b, bfs_ref))
    got_l, _ = run_parallel(gu, "labelprop", num_pes=4, strategy="pairs",
                            partitioner=pname)
    part_ok &= bool(np.array_equal(got_l, oracle))
results["partitioner_ok"] = bool(part_ok)

# ---- 1c) fused push hook at real multi-PE: band tables shard per chare,
# the pallas sweep runs inside shard_map, and results must still match the
# serial references (bit-exact for min monoids)
from repro.kernels import ops as K
hook_ok = True
hook_err = 0.0
for strat in ("reduction", "sortdest", "pairs"):
    for pes in (2, 8):
        got_p = run_parallel(g, "pagerank", num_pes=pes, strategy=strat,
                             push_fn=K.make_push_fn())[0]
        hook_err = max(hook_err, float(np.max(np.abs(got_p - ref))))
        got_s, _ = run_parallel(gw, "sssp", num_pes=pes, strategy=strat,
                                push_fn=K.make_push_fn(), source=7)
        hook_ok &= bool(np.array_equal(got_s, sssp_ref))
        got_b, _ = run_parallel(g, "bfs", num_pes=pes, strategy=strat,
                                push_fn=K.make_push_fn(), source=7)
        hook_ok &= bool(np.array_equal(got_b, bfs_ref))
results["push_hook_ok"] = bool(hook_ok)
results["push_hook_max_err"] = hook_err

# ---- 1d) mid-run repartition at real multi-PE: 4->4 partitioner switches
# with the state carried across the composed relabel; min programs must stay
# bit-exact vs the serial references, PageRank within float reorder noise
from repro.core.engine import ReplanPolicy
replan_ok = True
replan_err = 0.0
for pes in (2, 8):
    for target in ("edge_balanced", "striped", "degree_sorted"):
        got_s, _ = run_parallel(gw, "sssp", num_pes=pes, strategy="sortdest",
                                partitioner="contiguous", source=7,
                                sync=SYNC, gate=GATE,
                                replan=ReplanPolicy(target, every=2,
                                                    mode="always"))
        replan_ok &= bool(np.array_equal(got_s, sssp_ref))
    got_b, _ = run_parallel(g, "bfs", num_pes=pes, strategy="reduction",
                            partitioner="striped", source=7,
                            sync=SYNC, gate=GATE,
                            replan=ReplanPolicy("degree_sorted", every=3,
                                                mode="always"))
    replan_ok &= bool(np.array_equal(got_b, bfs_ref))
    got_p, _ = run_parallel(g, "pagerank", num_pes=pes, strategy="sortdest",
                            partitioner="contiguous",
                            replan=ReplanPolicy("edge_balanced", every=5,
                                                mode="always"))
    replan_err = max(replan_err, float(np.max(np.abs(got_p - ref))))
results["replan_ok"] = bool(replan_ok)
results["replan_pagerank_err"] = replan_err

# ---- 1e) batched [*, B] plane at real multi-PE: every query column of one
# run_batch sweep must equal its own sequential serial run, bit-exact, with
# per-query superstep counts intact (the batch axis rides through shard_map
# and the leading-axis collectives untouched)
from repro.core import Engine, partition
batch_ok = True
batch_srcs = [7, 0, 91, 200, 133]
for pes in (2, 8):
    for strat in ("reduction", "basic"):
        eng = Engine(partition(gw, pes, partitioner="edge_balanced"),
                     strategy=strat)
        plane, q_it = eng.run_batch("sssp", sources=batch_srcs, batch=8,
                                    sync=SYNC, gate=GATE)
        for i, s in enumerate(batch_srcs):
            want, want_it = sssp_serial(gw, source=s)
            batch_ok &= bool(np.array_equal(plane[i], want))
            if SYNC == "barrier":
                batch_ok &= int(q_it[i]) == want_it
            else:  # per-query double-check bound under overlap
                batch_ok &= want_it <= int(q_it[i]) <= 2 * want_it + 2
results["batch_ok"] = bool(batch_ok)

# ---- 1f) fixed-iteration batched plane: personalized PageRank columns of
# one run_batch sweep vs per-query Engine.run references, across every 1-D
# strategy at 2 and 8 PEs.  Fixed-iter programs keep the barrier (overlap is
# rejected for them), so these cells ignore SYNC; every query must report
# exactly the requested superstep count
ppr_seeds = [(0,), (7, 91), (3, 5, 200)]
ppr_ok = True
ppr_err = 0.0
for pes in (2, 8):
    for strat in ("reduction", "sortdest", "basic", "pairs"):
        eng = Engine(partition(g, pes, partitioner="edge_balanced"),
                     strategy=strat)
        plane, q_it = eng.run_batch("personalized_pagerank",
                                    sources=ppr_seeds, batch=4, iters=6)
        ppr_ok &= bool(np.all(np.asarray(q_it) == 6))
        for i, seeds in enumerate(ppr_seeds):
            want, want_it = eng.run("personalized_pagerank", seeds=seeds,
                                    iters=6)
            ppr_ok &= want_it == 6
            ppr_err = max(ppr_err, float(np.max(np.abs(
                np.asarray(plane[i]) - np.asarray(want)))))
results["ppr_batch_ok"] = bool(ppr_ok)
results["ppr_batch_err"] = ppr_err

# ---- 2) sharded MoE == dense reference ------------------------------------
from repro.models.config import ModelConfig
from repro.models import moe as MOE
cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                  layer_pattern=(("attn","moe"),), num_experts=8, top_k=2,
                  moe_d_ff=96, capacity_factor=8.0)
p = MOE.init_moe(jax.random.key(0), cfg)
x = jax.random.normal(jax.random.key(1), (4, 16, 64), jnp.bfloat16)
ref_moe, _ = MOE.moe_fwd_dense(p, x, cfg)
mesh = compat.make_mesh((2, 4), ("data", "model"),
                        axis_types=compat.auto_axes(2))
with jax.set_mesh(mesh):
    got_moe, _ = jax.jit(
        lambda p, x: MOE.moe_fwd(p, x, cfg),
        in_shardings=(jax.tree.map(lambda _: NamedSharding(mesh, P()), p),
                      NamedSharding(mesh, P("data", None, None))))(p, x)
results["moe_err"] = float(jnp.max(jnp.abs(
    got_moe.astype(jnp.float32) - ref_moe.astype(jnp.float32))))

# ---- 3) sharded train step == single-device train step --------------------
from repro.models import train as T
from repro.data import SyntheticLM
tcfg = ModelConfig(name="t2", family="dense", num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256)
opt = T.make_optimizer(peak_lr=1e-3, warmup=1, total=10)
pipe = SyntheticLM(256, batch=8, seq_len=32, seed=0)
batch = pipe.batch_at(0)

s_single = T.init_state(jax.random.key(0), tcfg, opt)
s_single, m_single = jax.jit(T.make_train_step(tcfg, opt))(s_single, batch)

mesh2 = compat.make_mesh((4, 2), ("data", "model"),
                         axis_types=compat.auto_axes(2))
with jax.set_mesh(mesh2):
    state = T.init_state(jax.random.key(0), tcfg, opt)
    specs = T.train_state_specs(jax.eval_shape(lambda: state), mesh2, zero=True)
    sh = jax.tree.map(lambda s: NamedSharding(mesh2, s), specs,
                      is_leaf=lambda s: isinstance(s, P))
    state = jax.device_put(state, sh)
    bspec = jax.tree.map(lambda s: NamedSharding(mesh2, s),
                         T.batch_specs(jax.eval_shape(lambda: batch), mesh2),
                         is_leaf=lambda s: isinstance(s, P))
    batch_sharded = jax.device_put(batch, bspec)
    state, m_sharded = jax.jit(T.make_train_step(tcfg, opt),
                               in_shardings=(sh, bspec),
                               out_shardings=(sh, None))(state, batch_sharded)
results["train_loss_delta"] = abs(float(m_single["loss"]) -
                                  float(m_sharded["loss"]))
params_delta = max(
    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    for a, b in zip(jax.tree.leaves(s_single.params),
                    jax.tree.leaves(state.params)))
results["train_params_delta"] = params_delta

# ---- 3b) ring attention == chunked attention -------------------------------
from repro.models import layers as LY
rcfg = ModelConfig(name="r", family="dense", num_layers=1, d_model=48,
                   num_heads=6, num_kv_heads=3, d_ff=96, vocab_size=64,
                   qkv_bias=True, remat="none")
rp = jax.tree.map(lambda a: a.astype(jnp.float32),
                  LY.init_attention(jax.random.key(7), rcfg))
rx = jax.random.normal(jax.random.key(8), (4, 64, 48), jnp.float32)
rref, _ = LY.attention_fwd(rp, rx, jnp.arange(64, dtype=jnp.int32), rcfg, "attn")
rmesh = compat.make_mesh((2, 4), ("data", "model"),
                         axis_types=compat.auto_axes(2))
with jax.set_mesh(rmesh):
    rgot = jax.jit(
        lambda p, x: LY.ring_attention_block(p, x, rcfg, "attn", rmesh, 4),
        in_shardings=(jax.tree.map(lambda _: NamedSharding(rmesh, P()), rp),
                      NamedSharding(rmesh, P("data", None, None))))(rp, rx)
results["ring_attn_err"] = float(jnp.max(jnp.abs(rgot - rref)))

# ---- 4) compressed_psum == psum -------------------------------------------
from repro.optim import compressed_psum
import functools
mesh3 = compat.make_mesh((8,), ("dp",), axis_types=compat.auto_axes(1))
xs = jax.random.normal(jax.random.key(5), (8, 1024), jnp.float32)

@functools.partial(jax.shard_map, mesh=mesh3, in_specs=P("dp"),
                   out_specs=P("dp"), check_vma=False)
def comp(v):
    return compressed_psum(v, "dp")[None]

@functools.partial(jax.shard_map, mesh=mesh3, in_specs=P("dp"),
                   out_specs=P("dp"), check_vma=False)
def exact(v):
    return jax.lax.psum(v, "dp")[None]

ce = jnp.max(jnp.abs(comp(xs) - exact(xs)))
scale = jnp.max(jnp.abs(xs)) / 127 * 8
results["compress_err_ratio"] = float(ce / scale)

print("RESULTS " + json.dumps(results))
"""


GRID_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np

from repro.core import (get_spec, random_weights, rmat, run_parallel,
                        two_cliques)
from repro.core.engine import ReplanPolicy

results = {}

# ---- grid2d two-phase reduce vs serial references (ISSUE 5 acceptance):
# bit-exact for min-monoid programs, < 1e-6 for PageRank, across degenerate
# (1xC / Rx1) and square/rectangular shapes at 2, 4, and 8 PEs
g = rmat(6, 300, seed=2)
gw = random_weights(g, seed=5)
gu = two_cliques(10).to_undirected()
GRAPHS = {"pagerank": g, "pagerank_weighted": gw, "sssp": gw, "bfs": g,
          "labelprop": gu}
refs = {}
for algo, gg in GRAPHS.items():
    spec = get_spec(algo)
    params = {"source": 3} if "source" in spec.defaults else {}
    refs[algo] = (np.asarray(spec.run_serial(gg, **params)), params)

exact_ok = True
float_err = 0.0
cells = 0
for R, C in ((1, 2), (2, 1), (2, 2), (4, 2), (2, 4)):
    pname = f"grid({R},{C})"
    for algo, gg in GRAPHS.items():
        ref, params = refs[algo]
        got, iters = run_parallel(gg, algo, num_pes=R * C,
                                  partitioner=pname, **params)
        cells += 1
        assert iters >= 1, (pname, algo)
        if get_spec(algo).exact:
            exact_ok &= bool(np.array_equal(np.asarray(got), ref))
        else:
            float_err = max(float_err,
                            float(np.max(np.abs(np.asarray(got) - ref))))
results["grid_cells"] = cells
results["grid_exact_ok"] = bool(exact_ok)
results["grid_pagerank_err"] = float_err

# ---- 1-D <-> 2-D replans: state carried through the composed ROW relabel
# (partitioners.row_plan_of), replicated into / collapsed out of the grid
replan_ok = True
replan_err = 0.0
sssp_ref = refs["sssp"][0]
for start, target in (("contiguous", "grid(2,4)"),
                      ("edge_balanced", "grid(4,2)"),
                      ("grid(2,4)", "degree_sorted"),
                      ("grid(4,2)", "grid(2,4)")):
    got, _ = run_parallel(gw, "sssp", num_pes=8, strategy="sortdest",
                          partitioner=start, source=3,
                          replan=ReplanPolicy(target, every=2,
                                              mode="always"))
    replan_ok &= bool(np.array_equal(np.asarray(got), sssp_ref))
got, _ = run_parallel(g, "pagerank", num_pes=8, partitioner="contiguous",
                      replan=ReplanPolicy("grid(2,4)", every=5,
                                          mode="always"))
replan_err = float(np.max(np.abs(np.asarray(got) - refs["pagerank"][0])))
results["grid_replan_ok"] = bool(replan_ok)
results["grid_replan_pagerank_err"] = replan_err

# ---- grid batched plane: personalized PageRank through run_batch on a 2-D
# partitioner (teleport plane replicated across grid columns) vs per-query
# Engine.run references
from repro.core import Engine, partition
ppr_seeds = [(0,), (3, 7)]
eng = Engine(partition(g, 8, partitioner="grid(2,4)"))
plane, q_it = eng.run_batch("personalized_pagerank", sources=ppr_seeds,
                            iters=6)
ppr_err = 0.0
for i, seeds in enumerate(ppr_seeds):
    want, _ = eng.run("personalized_pagerank", seeds=seeds, iters=6)
    ppr_err = max(ppr_err, float(np.max(np.abs(
        np.asarray(plane[i]) - np.asarray(want)))))
results["grid_ppr_err"] = ppr_err
results["grid_ppr_iters_ok"] = bool(np.all(np.asarray(q_it) == 6))

print("RESULTS " + json.dumps(results))
"""


STREAM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import tempfile
import numpy as np

from repro.core import Engine, StreamConfig, partition, random_weights, rmat

# the CI streaming matrix runs this leg twice: REPRO_RESIDENCY=resident is
# the baseline smoke (the same cells, edge planes device-resident);
# REPRO_RESIDENCY=stream adds the out-of-core cells and compares them
# bit-for-bit against the resident runs in the same process
RESIDENCY = os.environ.get("REPRO_RESIDENCY", "stream")

results = {"residency": RESIDENCY, "stream_cells": 0, "stream_exact_ok": True,
           "stream_iters_ok": True, "accounting_ok": True,
           "batch_cells": 0, "batch_exact_ok": True, "batch_iters_ok": True,
           "batch_bytes_ok": True}
g = random_weights(rmat(10, 6000, seed=3), seed=5)
cache = tempfile.mkdtemp(prefix="layout_cache_")
batch_sources = [7, 100, 3, 250, 9]  # 5 sources into B=8: padding columns
skip_max = 0.0

for shape, pes in (("grid(1,2)", 2), ("grid(2,4)", 8)):
    pg = partition(g, pes, shape)
    refs = {prog: Engine(pg).run(prog, source=7) for prog in ("sssp", "bfs")}
    brefs = {prog: Engine(pg).run_batch(prog, sources=batch_sources, batch=8)
             for prog in ("sssp", "bfs")}
    if RESIDENCY != "stream":
        continue
    eng = Engine(partition(g, pes, shape, eager=False), residency="stream",
                 stream=StreamConfig(windows=4, cache_dir=cache))
    for prog, (ref, ref_it) in refs.items():
        for gate in (None, "frontier"):
            got, it = eng.run(prog, source=7, gate=gate)
            results["stream_cells"] += 1
            results["stream_exact_ok"] &= bool(
                np.array_equal(np.asarray(got), np.asarray(ref)))
            results["stream_iters_ok"] &= bool(it == ref_it)
            st = eng.dispatch["stream"]
            # slots are rect-granular (pes x windows x supersteps); fetches
            # are window-granular (a window uploads once for all its rects)
            results["accounting_ok"] &= bool(
                st["fetch_slots"] == pes * st["windows"] * st["supersteps"]
                and st["fetches"] <= st["windows"] * st["supersteps"]
                and st["fetch_skipped"] <= st["fetch_slots"]
                and st["supersteps"] == it)
            if gate:
                skip_max = max(skip_max, st["fetch_skip_fraction"])
    # batched [*, 8] plane over the same streamed schedule (ISSUE 10):
    # bit-exact values AND per-query iteration counts vs the resident
    # batched plane, one window upload serving all 8 columns
    for prog, (ref, ref_it) in brefs.items():
        got, it = eng.run_batch(prog, sources=batch_sources, batch=8)
        results["batch_cells"] += 1
        results["batch_exact_ok"] &= bool(
            np.array_equal(np.asarray(got), np.asarray(ref)))
        results["batch_iters_ok"] &= bool(
            np.array_equal(np.asarray(it), np.asarray(ref_it)))
        st = eng.dispatch["stream"]
        results["batch_bytes_ok"] &= bool(
            st["batch"] == 8
            and st["fetched_bytes_per_query"] == st["fetched_bytes"] / 8
            and st["supersteps"] == int(np.asarray(it).max()))

if RESIDENCY == "stream":
    results["gate_skip_max"] = skip_max
    # warm restart at 2 PEs: same fingerprint, layout memory-mapped off the
    # cache the first 2-PE engine populated, still bit-exact
    eng = Engine(partition(g, 2, "grid(1,2)", eager=False),
                 residency="stream",
                 stream=StreamConfig(windows=4, cache_dir=cache))
    results["warm_origin"] = eng.dispatch["stream"]["origin"]
    pg = partition(g, 2, "grid(1,2)")
    ref, ref_it = Engine(pg).run("sssp", source=7)
    got, it = eng.run("sssp", source=7)
    results["warm_exact"] = bool(
        np.array_equal(np.asarray(got), np.asarray(ref)) and it == ref_it)

print("RESULTS " + json.dumps(results))
"""


ASYNC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np

from repro.core import (Engine, partition, random_weights, rmat, run_parallel,
                        bfs_serial, sssp_serial)
from repro.core.cost import grid_collective_bytes
from repro.launch import hloanalysis

results = {}
g = rmat(6, 300, seed=2)
gw = random_weights(g, seed=5)
sssp_ref, sssp_it = sssp_serial(gw, source=3)
bfs_ref, bfs_it = bfs_serial(g, source=3)

# ---- overlap + frontier gating vs barrier + serial refs (ISSUE 7
# acceptance): 4 partitioners x 4 strategies at 2/8 PEs plus the 2-D grids,
# bit-exact, iteration counts within the double-check bound
CELLS = [(p, s, pes)
         for p, s in zip(("contiguous", "edge_balanced", "striped",
                          "degree_sorted"),
                         ("reduction", "sortdest", "basic", "pairs"))
         for pes in (2, 8)]
CELLS += [("grid(2,2)", "grid2d", 4), ("grid(2,4)", "grid2d", 8)]
exact_ok = True
iters_ok = True
for pname, strat, pes in CELLS:
    for algo, gg, ref, ref_it in (("sssp", gw, sssp_ref, sssp_it),
                                  ("bfs", g, bfs_ref, bfs_it)):
        got_b, it_b = run_parallel(gg, algo, num_pes=pes, strategy=strat,
                                   partitioner=pname, source=3)
        got_o, it_o = run_parallel(gg, algo, num_pes=pes, strategy=strat,
                                   partitioner=pname, source=3,
                                   sync="overlap", gate="frontier")
        exact_ok &= bool(np.array_equal(np.asarray(got_b), np.asarray(ref)))
        exact_ok &= bool(np.array_equal(np.asarray(got_o), np.asarray(ref)))
        iters_ok &= bool(it_b == ref_it and it_b <= it_o <= 2 * it_b + 2)
results["async_cells"] = len(CELLS) * 2
results["async_exact_ok"] = bool(exact_ok)
results["async_iters_ok"] = bool(iters_ok)

# ---- grouped vs full phase-2 lowering: bit-exact on every grid shape, in
# native mode AND through the group-expanded emulation fallback
grouped_ok = True
for pname, pes in (("grid(2,2)", 4), ("grid(2,4)", 8), ("grid(4,2)", 8)):
    for coll in ("grouped", "full"):
        got, _ = run_parallel(gw, "sssp", num_pes=pes, partitioner=pname,
                              source=3, collectives=coll)
        grouped_ok &= bool(np.array_equal(np.asarray(got), sssp_ref))
# the group-expanded emulation (a reference asked for by name) agrees
# with the native axis_index_groups lowering on grid(2,4)'s column groups
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
mesh8 = compat.make_mesh((8,), ("pe",), axis_types=compat.auto_axes(1))
cols8 = [[c + 4 * r for r in range(2)] for c in range(4)]
x8 = jnp.asarray(np.random.default_rng(1).normal(size=(8, 16)), jnp.float32)
for comb in ("add", "min"):
    outs = [jax.jit(jax.shard_map(
        lambda v, m=m: compat.grouped_reduce(v[0], "pe", cols8, comb,
                                             mode=m)[None],
        mesh=mesh8, in_specs=P("pe"), out_specs=P("pe"),
        check_vma=False))(x8) for m in ("native", "emulate")]
    grouped_ok &= bool(np.allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                                   rtol=1e-6, atol=1e-6))
results["grouped_ok"] = bool(grouped_ok)

# ---- measured collective bytes: the grouped lowering's wire volume from
# the compiled step HLO must match the model's <= 0.6x full-axis bound
pg24 = partition(gw, 8, partitioner="grid(2,4)")
bytes_by = {}
for coll in ("grouped", "full"):
    eng = Engine(pg24, collectives=coll)
    text = eng.step_hlo("sssp", source=3)
    bytes_by[coll] = hloanalysis.analyze(text, 8).collective_bytes
results["measured_ratio"] = bytes_by["grouped"] / bytes_by["full"]
results["model_ratio"] = grid_collective_bytes(gw, 8, "grid(2,4)")["ratio"]

# ---- frontier gating on the grid: launch accounting from a real 8-PE run
eng = Engine(pg24)
got, it = eng.run("sssp", source=3, sync="overlap", gate="frontier")
results["gate_exact"] = bool(np.array_equal(np.asarray(got), sssp_ref))
results["gate"] = eng.dispatch["gate"]

# ---- batched plane under overlap at 8 PEs: per-query values bit-exact,
# counts within each query's own double-check bound
eng = Engine(partition(gw, 8, partitioner="edge_balanced"),
             strategy="reduction")
srcs = [3, 0, 17, 41]
plane, q_it = eng.run_batch("sssp", sources=srcs, batch=4,
                            sync="overlap", gate="frontier")
batch_ok = True
for i, s in enumerate(srcs):
    want, want_it = sssp_serial(gw, source=s)
    batch_ok &= bool(np.array_equal(plane[i], want))
    batch_ok &= bool(want_it <= int(q_it[i]) <= 2 * want_it + 2)
results["async_batch_ok"] = bool(batch_ok)

print("RESULTS " + json.dumps(results))
"""


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.slow
def test_grid2d_multidevice():
    """grid2d equivalence + 1-D<->2-D replans at real 2/4/8-PE grids (the
    ISSUE 5 acceptance cells; CI runs this leg standalone via ``-k grid``)."""
    res = _run_subprocess(GRID_SCRIPT)
    assert res["grid_cells"] == 25
    assert res["grid_exact_ok"]
    assert res["grid_pagerank_err"] < 1e-6
    assert res["grid_replan_ok"]
    assert res["grid_replan_pagerank_err"] < 1e-6
    assert res["grid_ppr_err"] <= 1e-6
    assert res["grid_ppr_iters_ok"]


@pytest.mark.slow
def test_async_multidevice():
    """Barrier-relaxed execution at real 2-8 PE meshes (the ISSUE 7
    acceptance cells; CI runs this leg standalone via ``-k async``):
    overlap + frontier gating bit-exact across partitioners, strategies,
    and grids; grouped phase-2 collectives bit-exact (native + emulated)
    with measured HLO wire bytes <= 0.6x the full-axis lowering."""
    res = _run_subprocess(ASYNC_SCRIPT)
    assert res["async_cells"] == 20
    assert res["async_exact_ok"]
    assert res["async_iters_ok"]
    assert res["grouped_ok"]
    assert res["measured_ratio"] <= 0.6
    assert res["model_ratio"] <= 0.6
    assert res["gate_exact"]
    gate = res["gate"]
    assert gate["enabled"] and gate["sync"] == "overlap"
    assert gate["launched"] + gate["skipped_launches"] == gate["launch_slots"]
    # the overlap pipeline's alternating empty frontiers plus band misses:
    # the gate must skip a large share of rectangle launches
    assert gate["skipped_fraction"] >= 0.4
    assert res["async_batch_ok"]


@pytest.mark.slow
def test_stream_multidevice():
    """Out-of-core streaming at real 2- and 8-PE grids (the ISSUE 8
    acceptance cells; CI runs this leg standalone via ``-k stream`` with a
    REPRO_RESIDENCY matrix): streamed SSSP/BFS bit-exact with identical
    iteration counts vs the resident engine, gated and ungated, window-slot
    accounting consistent, and a warm layout-cache restart served off disk."""
    res = _run_subprocess(STREAM_SCRIPT)
    if res["residency"] != "stream":
        return  # the resident baseline leg only smokes the reference cells
    assert res["stream_cells"] == 8  # 2 shapes x 2 programs x 2 gate modes
    assert res["stream_exact_ok"]
    assert res["stream_iters_ok"]
    assert res["accounting_ok"]
    assert res["batch_cells"] == 4  # 2 shapes x 2 programs, batched B=8
    assert res["batch_exact_ok"]
    assert res["batch_iters_ok"]
    assert res["batch_bytes_ok"]
    assert res["gate_skip_max"] > 0  # multi-rect grids must gate fetches
    assert res["warm_origin"] == "disk"
    assert res["warm_exact"]


@pytest.mark.slow
def test_multidevice_suite():
    res = _run_subprocess(SCRIPT)
    assert res["pagerank_max_err"] < 1e-3
    assert res["labelprop_ok"]
    assert res["partitioner_ok"]
    assert res["push_hook_ok"]
    assert res["push_hook_max_err"] < 1e-3
    assert res["replan_ok"]
    assert res["replan_pagerank_err"] < 1e-3
    assert res["batch_ok"]
    assert res["ppr_batch_ok"]
    assert res["ppr_batch_err"] <= 1e-6
    assert res["moe_err"] == 0.0
    assert res["ring_attn_err"] < 2e-6
    assert res["train_loss_delta"] < 1e-3
    assert res["train_params_delta"] < 2e-2  # bf16 params, reduction order
    assert res["compress_err_ratio"] < 1.5

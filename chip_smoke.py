#!/usr/bin/env python3
"""Bring-up smoke: the graph engine's main path on one TPU chip (or four).

    python chip_smoke.py              # phases A-D on one chip
    python chip_smoke.py --chips 4    # the grid(2,2) path on four chips,
                                      # against a 1-chip engine and serial

One process, every phase in it, every check a hard assert; nothing is
caught.  Graphs are RMAT stand-ins generated from ``--seed``.

  A. analytics, resident, 1 chip: the soc-LiveJournal1 stand-in at scale 22
     (4,194,304 vertices, ~58.7M edges; the published graph has 4.85M and
     69.0M) on ``Engine(partition(g, 1))`` with ``push_fn="auto"``:
     PageRank (20 iterations) within PR_RTOL of ``pagerank_serial``, SSSP
     and BFS bit-exact against ``sssp_serial``/``bfs_serial``.
  B. serving: ``GraphQueryServer(batch=8)`` answers 16 seeded BFS queries
     over the phase-A engine; two are checked bit-exact against serial.
  C. out-of-core: ``residency="stream"`` on grid(1,1), 4 edge windows, SSSP
     bit-exact with phase A, same iteration count.
  D. the fused Pallas push on the chip: a scale-14 stand-in where ``auto``
     picks the fused kernel and its footprint fits; the compiled step holds
     a ``tpu_custom_call``; PageRank, SSSP, BFS and a B=16 ``run_batch``
     match an engine built with ``push_fn=None``.

Every engine but phase D's reference takes the default ``push_fn="auto"``;
at scale 22 it declines the fused kernel (``dispatch["reason"]``: too many
in-band tiles per edge block to win, see ``blocks.choose_push``).

Earlier lines print device kind and count, per-phase set-up seconds
(compile included) and run seconds, each engine's ``dispatch`` and the
device's ``peak_bytes_in_use``: bring-up observations, not measurements.
The last line is one JSON object; off-TPU (a rehearsal, e.g. with
``JAX_PLATFORMS=cpu``, at scale 12/11 unless ``--scale``/``--fused-scale``
say otherwise) it says ``"ok": false`` and the script exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PR_RTOL = 1e-3  # PageRank: max |got - ref| / |ref| (f32 sums, any order)


def log(*parts):
    print(*parts, flush=True)


def check(ok, what):
    """A hard check that ``python -O`` cannot strip."""
    if not ok:
        raise AssertionError(what)


class Clock:
    """Wall-clock seconds per named span, printed as they close."""

    def __init__(self):
        self.spans = {}

    def __call__(self, name, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.spans[name] = time.perf_counter() - t0
        log(f"  {name}: {self.spans[name]:.2f} s")
        return out


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def show_dispatch(name, eng):
    d = {k: v for k, v in eng.dispatch.items()
         if k not in ("gate", "stream")}
    log(f"  dispatch[{name}]: {json.dumps(d, default=str, sort_keys=True)}")


def pr_error(got, ref):
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def weighted_graph(scale, seed):
    from repro.core import load_dataset, random_weights

    return random_weights(load_dataset("soc-lj1-mini", scale_log2=scale,
                                       seed=seed), seed=seed)


def phase_a(args, clk, dev):
    import numpy as np

    from repro.core import (Engine, bfs_serial, pagerank_serial, partition,
                            sssp_serial)

    log(f"[A] analytics, resident: soc-lj1-mini stand-in, scale {args.scale}")
    g = clk("A.generate", weighted_graph, args.scale, args.seed)
    log(f"  graph: {g.num_vertices} vertices, {g.num_edges} edges")
    eng = clk("A.partition+engine", lambda: Engine(partition(g, 1)))
    show_dispatch("A", eng)
    ref_pr = clk("A.pagerank_serial", pagerank_serial, g, iters=20)
    ref_ss, ref_ss_it = clk("A.sssp_serial", sssp_serial, g, 0)
    ref_bf, ref_bf_it = clk("A.bfs_serial", bfs_serial, g, 0)
    out = {}
    for name, call in (("pagerank", lambda: (eng.pagerank(iters=20), 20)),
                       ("sssp", lambda: eng.sssp(0)),
                       ("bfs", lambda: eng.bfs(0))):
        first = clk(f"A.{name}.setup(compile+run)", call)
        out[name] = clk(f"A.{name}.run", call)
        for got, it in (first, out[name]):
            if name == "pagerank":
                err = pr_error(got, ref_pr)
                check(err <= PR_RTOL, (name, err))
            else:
                ref, ref_it = ((ref_ss, ref_ss_it) if name == "sssp"
                               else (ref_bf, ref_bf_it))
                check(np.array_equal(np.asarray(got), ref), name)
                check(it == ref_it, (name, it, ref_it))
    log(f"  pagerank max rel err {pr_error(out['pagerank'][0], ref_pr):.3e}"
        f" (limit {PR_RTOL}); sssp {out['sssp'][1]} supersteps and bfs "
        f"{out['bfs'][1]} supersteps bit-exact")
    log(f"  peak_bytes_in_use after A: {peak_bytes(dev)}")
    return g, eng, out


def phase_b(args, clk, dev, g, eng):
    import numpy as np

    from repro.core import bfs_serial
    from repro.launch.serve import GraphQueryServer

    log("[B] serving: GraphQueryServer(batch=8), 16 BFS queries")
    rng = np.random.default_rng(args.seed)
    sources = rng.choice(np.flatnonzero(g.out_degrees > 0), 16,
                         replace=False)
    server = GraphQueryServer(eng, batch=8)
    ids = [server.submit("bfs", int(s)) for s in sources]
    clk("B.dispatch1(compile+run)", server.step, True)
    clk("B.dispatch2(run)", server.step, True)
    check(server.pending() == 0 and server.dispatches == 2,
          "16 queries in two dispatches")
    show_dispatch("B", eng)
    for k in (0, len(ids) - 1):
        row, it = server.result(ids[k])
        ref, ref_it = clk(f"B.bfs_serial[{int(sources[k])}]", bfs_serial, g,
                          int(sources[k]))
        check(np.array_equal(np.asarray(row), ref), k)
        check(it == ref_it, (k, it, ref_it))
    log(f"  queries {int(sources[0])} and {int(sources[-1])} bit-exact")
    log(f"  peak_bytes_in_use after B: {peak_bytes(dev)}")


def phase_c(args, clk, dev, g, ref):
    import numpy as np

    from repro.core import Engine, StreamConfig, partition

    log("[C] out-of-core: residency='stream', grid(1,1), 4 windows, SSSP")
    pg = clk("C.partition", partition, g, 1, partitioner="grid(1,1)")
    eng = clk("C.engine", Engine, pg, residency="stream",
              stream=StreamConfig(windows=4))
    show_dispatch("C", eng)
    dist, it = clk("C.sssp(compile+run)", eng.sssp, 0)
    check(np.array_equal(np.asarray(dist), np.asarray(ref[0])),
          "streamed sssp == resident")
    check(it == ref[1], (it, ref[1]))
    st = eng.dispatch["stream"]
    log(f"  bit-exact with A, {it} supersteps; {st['fetches']} window "
        f"fetches, {st['fetched_bytes']} bytes")
    log(f"  peak_bytes_in_use after C: {peak_bytes(dev)}")


def phase_d(args, clk, dev):
    import numpy as np

    from repro.core import Engine, partition

    log(f"[D] fused Pallas push: scale {args.fused_scale} stand-in")
    g = clk("D.generate", weighted_graph, args.fused_scale, args.seed)
    pg = partition(g, 1)
    fused = Engine(pg)
    staged = Engine(pg, push_fn=None)
    show_dispatch("D.auto", fused)
    show_dispatch("D.staged", staged)
    check(fused.dispatch["choice"] == "fused", fused.dispatch)
    hlo = clk("D.step_hlo", fused.step_hlo, "pagerank")
    if args.expect_tpu:
        check("tpu_custom_call" in hlo, "no Mosaic kernel in the step")
    for name, call in (("pagerank", lambda e: (e.pagerank(iters=20), 20)),
                       ("sssp", lambda e: e.sssp(0)),
                       ("bfs", lambda e: e.bfs(0))):
        got, it = clk(f"D.{name}.fused(compile+run)", call, fused)
        got, it = clk(f"D.{name}.fused(run)", call, fused)
        want, want_it = clk(f"D.{name}.staged(compile+run)", call, staged)
        want, want_it = clk(f"D.{name}.staged(run)", call, staged)
        if name == "pagerank":
            err = pr_error(got, want)
            check(err <= PR_RTOL, err)
            log(f"  pagerank fused vs staged max rel err {err:.3e}")
        else:
            check(np.array_equal(np.asarray(got), np.asarray(want)), name)
            check(it == want_it, (name, it, want_it))
    rng = np.random.default_rng(args.seed)
    srcs = [int(s) for s in rng.choice(np.flatnonzero(g.out_degrees > 0),
                                       16, replace=False)]
    for tag in ("compile+run", "run"):
        plane, q_it = clk(f"D.run_batch16.fused({tag})", fused.run_batch,
                          "bfs", sources=srcs, batch=16)
    for tag in ("compile+run", "run"):
        want, want_it = clk(f"D.run_batch16.staged({tag})",
                            staged.run_batch, "bfs", sources=srcs, batch=16)
    check(fused.dispatch["batch"][16]["choice"] == "fused", fused.dispatch)
    check(np.array_equal(plane, want) and np.array_equal(q_it, want_it),
          "run_batch16 fused == staged")
    show_dispatch("D.auto", fused)
    log("  fused == staged: pagerank within tolerance, sssp/bfs/run_batch16 "
        "bit-exact")
    log(f"  peak_bytes_in_use after D: {peak_bytes(dev)}")


def four_chips(args, clk, devs):
    import numpy as np

    from repro import compat
    from repro.core import (Engine, pagerank_serial, partition, sssp_serial)
    from repro.core.engine import AXIS

    log(f"[4] grid(2,2) on 4 chips vs 1 chip, scale {args.scale}")
    g = clk("4.generate", weighted_graph, args.scale, args.seed)
    log(f"  graph: {g.num_vertices} vertices, {g.num_edges} edges")
    pg4 = clk("4.partition grid(2,2)", partition, g, 4,
              partitioner="grid(2,2)")
    eng4 = clk("4.engine", Engine, pg4)
    show_dispatch("4", eng4)
    mesh1 = compat.make_mesh((1,), (AXIS,), axis_types=compat.auto_axes(1),
                             devices=devs[:1])
    eng1 = clk("1.partition+engine", lambda: Engine(
        partition(g, 1, eager=False), mesh=mesh1))
    show_dispatch("1", eng1)
    groups = sorted({len(m.split(",")) for m in re.findall(
        r"all-reduce\([^\n]*replica_groups=\{\{([0-9,]+)\}",
        eng4.step_hlo("sssp", source=0))})
    log(f"  phase-2 all-reduce replica group sizes in the compiled step: "
        f"{groups}")
    check(2 in groups, groups)
    ref_ss, ref_it = clk("sssp_serial", sssp_serial, g, 0)
    ref_pr = clk("pagerank_serial", pagerank_serial, g, iters=20)
    # the 1-chip reference engine runs once per program: its times are
    # phase A's, and this call pays four chips for every second
    for name, eng, tags in (("4", eng4, ("compile+run", "run")),
                            ("1", eng1, ("compile+run",))):
        for tag in tags:
            dist, it = clk(f"{name}.sssp({tag})", eng.sssp, 0)
            check(np.array_equal(np.asarray(dist), ref_ss), name)
            check(it == ref_it, (name, it, ref_it))
        for tag in tags:
            pr = clk(f"{name}.pagerank({tag})", eng.pagerank, iters=20)
        err = pr_error(pr, ref_pr)
        check(err <= PR_RTOL, (name, err))
        log(f"  {name} chip(s): sssp bit-exact in {it} supersteps, pagerank "
            f"max rel err {err:.3e}")
    for d in devs:
        log(f"  peak_bytes_in_use {d}: {peak_bytes(d)}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the grid(2,2) path on four chips")
    ap.add_argument("--scale", type=int,
                    help="log2 vertices of the phase-A/4-chip graph "
                         "(default 22 on a TPU, 12 elsewhere)")
    ap.add_argument("--fused-scale", type=int,
                    help="log2 vertices of the phase-D graph "
                         "(default 14 on a TPU, 11 elsewhere)")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    args.expect_tpu = dev.platform == "tpu"
    # off-TPU the run can only be a rehearsal, and it must not build the
    # 58.7M-edge graph on a host CPU unless asked to
    if args.scale is None:
        args.scale = 22 if args.expect_tpu else 12
    if args.fused_scale is None:
        args.fused_scale = 14 if args.expect_tpu else 11
    log(f"devices: {len(devs)} x {dev.platform} / {dev.device_kind}; "
        f"compile cache {cache}")
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"found {len(devs)}")
    clk = Clock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, clk, devs[:4])
    else:
        g, eng, out = phase_a(args, clk, dev)
        phase_b(args, clk, dev, g, eng)
        del eng
        phase_c(args, clk, dev, g, out["sssp"])
        del g
        phase_d(args, clk, dev)
    log(f"total: {time.perf_counter() - t0:.2f} s")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "device": device,
                          "reason": "no TPU: this run was a rehearsal"}))
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

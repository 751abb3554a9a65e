#!/usr/bin/env python3
"""Fused-vs-staged push timings by kernel body and plane width, on a chip.

    python scripts/push_breakeven.py                 # stand-ins 12-16 + snb
    python scripts/push_breakeven.py --scales 14 --no-snb --reps 1

For each graph, one ``partition(g, 1)`` and two engines on it: one with the
fused Pallas hook pinned (``ops.make_push_fn()``) and one with
``push_fn=None`` (XLA's gather and segment combine).  Each measurement is a
warm call on the host clock; every call returns host arrays, so the device
has finished when the clock stops.  The two engines alternate, ``--reps``
calls each after one warm-up call, and the median is kept.

    body "vpu" (min):        BFS ``Engine.run`` (1-D plane, width 1) and
                             ``run_batch`` at B = 4 and 16
    body "mxu" (float add):  PageRank ``Engine.run`` (1-D, 20 supersteps)
                             and personalized PageRank ``run_batch`` at B=16

Graphs: the ``soc-lj1-mini`` RMAT stand-ins of ``chip_smoke.py`` at each
``--scales`` entry, and (unless ``--no-snb``) the benchmark's ``snb-sf10``
graph, generated by ``bench/graphgen.py`` from its configuration file.
One JSON line per (graph, program, width) on stdout, and in the file
``--out`` names when given: the layout's in-band tiles per edge block,
supersteps, fused and staged seconds per call, and ``fused_over_staged``.
Off-TPU the fused hook runs in the Pallas interpreter: a rehearsal of the
control flow, never a timing.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

SNB_CONFIG = os.path.join(ROOT, "bench", "configs", "snb-sf10.json")

# (program, body, width, call): width 1 is the 1-D plane of Engine.run
CASES = (
    ("bfs", "vpu", 1, lambda e, s: e.run("bfs", source=s[0])),
    ("bfs", "vpu", 4, lambda e, s: e.run_batch("bfs", sources=s[:4],
                                               batch=4)),
    ("bfs", "vpu", 16, lambda e, s: e.run_batch("bfs", sources=s[:16],
                                                batch=16)),
    ("pagerank", "mxu", 1, lambda e, s: (e.run("pagerank", iters=20)[0],
                                         20)),
    ("personalized_pagerank", "mxu", 16,
     lambda e, s: e.run_batch("personalized_pagerank",
                              sources=[(v,) for v in s[:16]], batch=16)),
)


def graphs(args):
    """-> [(name, Graph)] in the order they are measured."""
    from repro.core import from_edges, load_dataset, random_weights

    for scale in args.scales:
        yield f"soc-lj1-mini.s{scale}", random_weights(
            load_dataset("soc-lj1-mini", scale_log2=scale, seed=args.seed),
            seed=args.seed)
    if args.snb:
        sys.path.insert(0, os.path.join(ROOT, "bench"))
        import graphgen

        with open(SNB_CONFIG) as f:
            cfg = json.load(f)
        e = graphgen.generate(cfg["graph"], args.seed)
        yield "snb-sf10", from_edges(e.num_vertices, e.src, e.dst,
                                     directed=e.directed, weight=e.weight)


def supersteps(out) -> int:
    it = out[1]
    return int(max(it)) if hasattr(it, "__len__") else int(it)


def same(a, b, program) -> bool:
    import numpy as np

    a, b = np.asarray(a[0]), np.asarray(b[0])
    if "pagerank" in program:
        return bool(np.allclose(a, b, rtol=1e-3, atol=1e-7))
    return bool(np.array_equal(a, b))


def measure(name, g, args, emit):
    import numpy as np

    from repro.core import Engine, partition
    from repro.kernels import ops

    pg = partition(g, 1)
    fused = Engine(pg, push_fn=ops.make_push_fn())
    staged = Engine(pg, push_fn=None)
    facts = Engine(pg).dispatch  # the layout's band facts, as auto sees them
    rng = np.random.default_rng(args.seed)
    srcs = [int(v) for v in rng.choice(np.flatnonzero(g.out_degrees > 0),
                                       16, replace=False)]
    for program, body, width, call in CASES:
        times = {"fused": [], "staged": []}
        res = {}
        for eng, path in ((fused, "fused"), (staged, "staged")):
            res[path] = call(eng, srcs)  # compile and warm
        for _ in range(args.reps):
            for eng, path in ((fused, "fused"), (staged, "staged")):
                t = time.perf_counter()
                call(eng, srcs)
                times[path].append(time.perf_counter() - t)
        f, s = (statistics.median(times[p]) for p in ("fused", "staged"))
        line = {"graph": name, "vertices": g.num_vertices,
                "edges": g.num_edges, "program": program, "body": body,
                "width": width,
                "tiles_per_edge_block": facts["tiles_per_edge_block"],
                "supersteps": supersteps(res["staged"]),
                "fused_s": f, "staged_s": s, "fused_over_staged": f / s,
                "fused_runs_s": times["fused"],
                "staged_runs_s": times["staged"],
                "same_answers": same(res["fused"], res["staged"], program)}
        emit(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scales", type=int, nargs="*",
                    default=[12, 13, 14, 15, 16])
    ap.add_argument("--no-snb", dest="snb", action="store_false")
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", help="also append each line to this file")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind}), flush=True)

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    for name, g in graphs(args):
        measure(name, g, args, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())

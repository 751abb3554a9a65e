"""Offered-load sweep of a serving cell, to find its knee once.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 120 \
        --settle 30 --rates 3.5 4 4.5

One process: the cell's graph, engine and server are built and warmed up
once, then the mix runs at each rate in turn for ``--seconds``, each from
an empty queue.  The dispatches that start in the first ``--settle``
seconds fill that queue and are left out; the rest are the steady part.
Each rate prints one JSON line: the offered and completed rates, the 50th,
90th and 95th percentile latencies, the backlog at the close (queries due
but not yet answered), the mean plane fill, and ``waiting``, the mean queue
at a dispatch's start in the steady part's first and second halves.

A rate is sustained when that queue does not grow (the second half's mean
exceeds the first's by at most ``GROWTH`` queries) and the 90th percentile
latency is within two of the steady part's mean dispatches: a query waits
out at most the dispatch running when it arrives and rides the next.  The
backlog at the close is no test: the dispatch in flight and the queue
behind it are there at any rate.  The knee is the highest sustained rate;
a cell's fixed rate is set below it from these lines.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import cells  # noqa: E402
import drivers  # noqa: E402
import harness  # noqa: E402
import traffic as traffic_mod  # noqa: E402


GROWTH = 2.0  # queries: an eighth of a 16-wide plane


def _pct(values, q):
    v = sorted(values)
    return v[math.ceil(q * len(v)) - 1] if v else None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--settle", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    if cell.traffic["kind"] != "open_loop":
        raise SystemExit("a sweep needs an open-loop mix")
    harness.enable_cache()
    edges, engine = harness.build(cell, args.seed, {})
    pool = traffic_mod.candidates(edges.num_vertices, edges.src)
    driver = harness.make_driver(cell, engine, pool, args.seed)
    driver.warm_up()
    server = driver.server
    for rate in args.rates:
        mix = dict(cell.traffic, rate_qps=rate, check={"sample": 1})
        d = drivers.OpenLoopDriver(server, mix, pool, args.seed)
        d.window(args.seconds)
        lat = [(q.done - q.due) if q.done is not None else math.inf
               for q in d.queries]
        backlog = sum(q.done is None or q.done > args.seconds
                      for q in d.queries)
        steady = [x for x in d.dispatches
                  if args.settle <= x.start < args.seconds]
        half = len(steady) // 2
        halves = [steady[:half], steady[half:]]
        waiting = [sum(x.waiting for x in h) / max(len(h), 1)
                   for h in halves]
        answered = sum(args.settle <= q.done <= args.seconds
                       for q in d.queries if q.done is not None)
        fill = sum(x.admitted for x in steady) / max(len(steady), 1) / \
            server.batch
        dispatch_ms = 1000 * sum(x.server_s for x in steady) / max(
            len(steady), 1)
        p90_ms = 1000 * _pct(lat, 0.9)
        print(json.dumps({
            "rate_qps": rate, "offered": len(d.queries),
            "completed_qps": answered / (args.seconds - args.settle),
            "backlog_at_close": backlog,
            "p50_ms": 1000 * _pct(lat, 0.5), "p90_ms": p90_ms,
            "p95_ms": 1000 * _pct(lat, 0.95),
            "dispatches": len(d.dispatches), "steady": len(steady),
            "fill": fill, "waiting": waiting, "dispatch_ms": dispatch_ms,
            "sustained": (waiting[1] - waiting[0] <= GROWTH
                          and p90_ms <= 2 * dispatch_ms)}), flush=True)


if __name__ == "__main__":
    main()

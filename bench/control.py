"""The control of a cell's check: the plain reference put in the program's
place with one guarantee broken (see each ``refs/<name>.py``), read by the
same comparison as a run, at the cell's own size, for several seeds.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 30]

One process for all the seeds.  Each prints the numbers the control gives
beside the cell's limits; every seed has to come out not correct.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402
import drivers  # noqa: E402
import graphgen  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def control_readings(cell: cells.Cell, seed: int, seconds: float) -> dict:
    """The control's compared numbers for one seed, beside their limits."""
    mix = cell.traffic
    edges = graphgen.generate(cell.config["graph"], seed)
    ref = cells.reference(mix["reference"])
    params = mix.get("params", {})
    pool = traffic_mod.candidates(edges.num_vertices, edges.src)
    if mix["kind"] == "open_loop":
        sched = traffic_mod.open_loop(mix, seconds, pool, seed)
        sources = [int(s) for s in sched.sources]
    else:
        keep = int(mix.get("check", {}).get("keep_answers", 4)) + 1
        sources = [None] * keep
    # the control replaces every answer; only the sources carry over
    placeholders = [drivers.Answer(s, None, 0) for s in sources]
    numbers = ref.numbers(edges, params, ref.control_answers(
        edges, params, placeholders))
    limits = mix["limits"]
    return {k: {"value": numbers[k], "limit": limits[k],
                "fails": numbers[k] > limits[k]} for k in limits
            if k in numbers}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    default=float(cells.benchmark()["run_seconds"]))
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    for seed in args.seeds:
        got = control_readings(cell, seed, args.seconds)
        caught = any(v["fails"] for v in got.values())
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_caught": caught, "numbers": got}),
              flush=True)


if __name__ == "__main__":
    main()

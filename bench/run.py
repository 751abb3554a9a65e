"""The benchmark: one run of one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine holding the chips the cell
asks for.  The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.
Everything else (set-up spans, dispatch records, compiles in the window,
the reference's time, the checks again) goes to standard error first.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import cells  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(n: int):
    """The chips this run may use, or exit 2: no device metric comes from
    anything but a TPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"no accelerator: {e}")
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}; this run measures "
              f"nothing", file=sys.stderr)
        sys.exit(2)
    if len(devices) < n:
        print(f"the cell needs {n} chips, JAX found {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices[:n]


def emit(result: dict):
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None):
    args = parse(argv)
    import repro  # noqa: F401  the system under test, from this checkout
    import harness

    cell = cells.load(args.workload)
    devices = find_chips(cell.chips)
    peaks = cells.peaks(devices[0].device_kind)
    emit(harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, peaks))


if __name__ == "__main__":
    main()

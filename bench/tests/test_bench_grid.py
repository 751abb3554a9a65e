"""The four-chip cell's readers, and a whole CPU run of a tiny copy of the
cell on four forced host devices (one subprocess, as the chip's cell runs
one process over four chips): it comes out correct, ``rect_max_share.grid``
reads the placement the program recorded, ``reduce_ms.grid`` reads nothing
without a trace, and the layout was built on the devices."""

import functools
import json
import os
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import cells  # noqa: E402

from repro import obs  # noqa: E402

CELL = "twitter-rv.grid22.pagerank20"
reduce_ms = cells.metric_reader("reduce_ms.grid")
rect_share = cells.metric_reader("rect_max_share.grid")


def _ctx(op_s=None, steps=(20, 20)):
    trace = None if op_s is None else types.SimpleNamespace(op_s=op_s)
    jobs = [types.SimpleNamespace(supersteps=s) for s in steps]
    return types.SimpleNamespace(trace=trace, jobs=jobs)


def test_reduce_reads_the_collectives_per_superstep():
    op_s = {"all-reduce.1 all-reduce": 0.004,
            "all-reduce-start.2 all-reduce-start": 0.001,
            "all-reduce-done.2 all-reduce-done": 0.002,
            "collective-permute.3 collective-permute": 0.001,
            "fusion.11 fusion kLoop": 9.0,
            "scatter.4 scatter": 3.0,
            "all-reduce-scatter-fusion.5 fusion kOutput": 1.0}
    assert reduce_ms(_ctx(op_s)) == pytest.approx(1000 * 0.008 / 40)


def test_reduce_reads_nothing_without_a_trace_or_a_collective():
    assert reduce_ms(_ctx(None)) is None
    assert reduce_ms(_ctx({"fusion.11 fusion kLoop": 9.0})) is None
    assert reduce_ms(_ctx({"all-reduce.1 all-reduce": 1.0}, steps=())) \
        is None


def test_rect_share_reads_the_last_grid_layout():
    obs.reset()
    obs.record("graph.layout", 0, 1, which="grid", where="host",
               rect_edges=[10, 30, 30, 30])
    obs.record("graph.layout", 1, 2, which="sd")
    obs.record("graph.layout", 2, 3, which="grid", where="sharded",
               rect_edges=[25, 25, 26, 24])
    assert rect_share(None) == pytest.approx(26.0)
    obs.reset()
    # a program that records no rectangles, as before they were recorded
    obs.record("graph.layout", 0, 1, which="grid")
    assert rect_share(None) is None
    obs.reset()


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json, sys, time
sys.path[:0] = [%(bench)r, os.path.join(%(bench)r, "tests")]
import cells, harness
from test_bench_faults import SEED, tiny

harness.enable_cache = lambda: "off"
cell = tiny(%(cell)r, 11)
# the per-layer readers read as end-to-end ones: a CPU run has no trace
cell = dataclasses.replace(cell, end_to_end=cell.end_to_end + cell.per_layer)
out = harness.run_cell(cell, SEED, 0.5, False, time.monotonic(),
                       cells.peaks("TPU v5 lite"))
out["per_layer"] = [m["name"] for m in cell.per_layer]
print("RESULT " + json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def tiny_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_DEVICE_BUILD="device",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"bench": BENCH, "cell": CELL}],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):]), out.stderr


def test_tiny_four_device_cell_is_correct():
    out, _ = tiny_run()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["count"] == 4


def test_tiny_four_device_cell_reads_its_placement_and_no_reduce():
    out, _ = tiny_run()
    assert {"reduce_ms.grid", "rect_max_share.grid"} <= set(out["per_layer"])
    assert "push_roofline.job" not in out["per_layer"]
    share = out["metrics"]["rect_max_share.grid"]["value"]
    assert 25.0 <= share < 35.0
    assert "reduce_ms.grid" not in out["metrics"]  # None without a trace


def test_tiny_four_device_cell_builds_its_layout_on_the_devices():
    _, err = tiny_run()
    (line,) = [ln for ln in err.splitlines() if ln.startswith("dispatch: ")]
    grid = json.loads(line[len("dispatch: "):])["grid"]
    assert grid == {"rows": 2, "cols": 2, "row_chunk": 1024,
                    "col_chunk": 1024, "collectives": "grouped",
                    "layout": "sharded"}

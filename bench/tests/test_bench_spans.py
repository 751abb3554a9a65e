"""The readers of the program's spans, on whole CPU runs of a tiny serving
cell and a tiny jobs cell: each returns a number, takes exactly the
window's records, agrees with the host-clock metrics beside it, and returns
None for a program without ``repro.obs``."""

import dataclasses
import functools
import math
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import harness  # noqa: E402
from test_bench_faults import SEED, tiny  # noqa: E402

from repro import obs  # noqa: E402

NEW = ("queue_wait_p90_ms.serve", "engine_host_ms.serve",
       "engine_host_ms.job", "layout_s")
CASES = {
    "lj1-s21.pagerank20": (10, {}),
    "snb-sf10.bfs": (9, {"rate_qps": 1000.0}),
}
SPANS = ("server.queue", "server.dispatch", "engine.run", "engine.loop",
         "graph.layout")


@functools.lru_cache(maxsize=None)
def traced(name):
    """One run of the cell, with its span metrics read as end-to-end ones
    (a CPU run has no device trace to read per-layer metrics beside)."""
    scale, mix = CASES[name]
    cell = tiny(name, scale, **mix)
    new = tuple(m for m in cell.per_layer if m["name"] in NEW)
    cell = dataclasses.replace(cell, end_to_end=cell.end_to_end + new)
    seen = []

    class Recording(harness.Context):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_cache", lambda: "off")
        mp.setattr(harness, "Context", Recording)
        obs.reset()
        out = harness.run_cell(cell, SEED, 0.3, False, time.monotonic(),
                               cells.peaks("TPU v5 lite"))
    (ctx,) = seen
    recs = {n: obs.records(n) for n in SPANS}
    return name, cell, out, ctx, recs


@pytest.mark.parametrize("name", sorted(CASES))
def test_each_reader_returns_a_number(name):
    _, cell, out, _, _ = traced(name)
    want = {"snb-sf10.bfs": {"queue_wait_p90_ms.serve",
                             "engine_host_ms.serve", "layout_s"},
            "lj1-s21.pagerank20": {"engine_host_ms.job", "layout_s"}}[name]
    assert out["correct"] is True
    assert {m["name"] for m in cell.end_to_end} & set(NEW) == want
    for m in want:
        value = out["metrics"][m]["value"]
        assert math.isfinite(value) and value >= 0.0, m
    assert out["metrics"]["layout_s"]["value"] > 0.0


@pytest.mark.parametrize("name", sorted(CASES))
def test_readers_take_exactly_the_window_records(name):
    """The warm-up's records come first, then one per dispatch, job or
    answered query of the window: the last N are the window's."""
    _, _, _, ctx, recs = traced(name)
    if name == "snb-sf10.bfs":
        batch = ctx.columns
        answered = sum(q.done is not None for q in ctx.queries)
        assert answered == len(ctx.queries)
        assert len(recs["server.queue"]) == batch + answered
        assert len(recs["server.dispatch"]) == 1 + len(ctx.dispatches)
        window = recs["server.dispatch"][1:]
        assert [r.attrs["dispatch"] for r in window] == list(
            range(1, len(ctx.dispatches) + 1))
        assert sum(d.admitted for d in ctx.dispatches) == answered
    else:
        assert len(recs["engine.run"]) == 1 + len(ctx.jobs)
        assert all(r.attrs["program"] == "pagerank"
                   for r in recs["engine.run"])


def test_queue_wait_within_each_query_latency():
    _, _, out, ctx, recs = traced("snb-sf10.bfs")
    # warm-up requests take ids 0..B-1, the window's the next, in order
    first = ctx.columns
    for r in recs["server.queue"][first:]:
        q = ctx.queries[r.attrs["rid"] - first]
        assert r.seconds <= q.done - q.due
    m = out["metrics"]
    assert (m["queue_wait_p90_ms.serve"]["value"]
            <= m["query_p90_ms"]["value"])
    dispatch_ms = 1000.0 * sum(d.server_s for d in ctx.dispatches) / len(
        ctx.dispatches)
    assert m["engine_host_ms.serve"]["value"] < dispatch_ms


@pytest.mark.parametrize("name", sorted(CASES))
def test_readers_return_none_without_the_module(name, monkeypatch):
    """On a checkout from before ``repro.obs`` every reader gives None."""
    import repro

    _, _, _, ctx, _ = traced(name)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    for metric in NEW:
        assert cells.metric_reader(metric)(ctx) is None, metric

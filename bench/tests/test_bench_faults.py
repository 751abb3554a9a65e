"""A whole run of each cell at a tiny size on the CPU, past the harness's
look for a chip: sound, it comes out correct; with the timed path broken
underneath, in each way the cell can break, it comes out not correct."""

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import harness  # noqa: E402

SEED = 2**35 + 17


def tiny_edges(g, scale):
    """Draws and kept edges at 2**scale vertices: a small RMAT draw repeats
    pairs and makes self-loops far more often, so keep fewer."""
    draws = (g["edge_draws"] >> g["scale"]) << scale
    keep = draws // 4 if g.get("symmetrize") else int(draws * 0.95)
    return {"edge_draws": draws, "edges": keep}


def tiny(name, scale, **mix):
    """The cell at 2**scale vertices; ``mix`` overrides traffic keys."""
    cell = cells.load(name)
    g = dict(cell.config["graph"])
    g.update(scale=scale, **tiny_edges(g, scale))
    return dataclasses.replace(cell, config=dict(cell.config, graph=g),
                               traffic=dict(cell.traffic, **mix))


def run(cell, seconds=1.0):
    return harness.run_cell(cell, SEED, seconds, False, time.monotonic(),
                            cells.peaks("TPU v5 lite"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # the harness turns on the persistent cache; the tests leave the
    # process's JAX settings as they found them
    monkeypatch.setattr(harness, "enable_cache", lambda: "off")


def _unchanged_run(orig):
    def run(self, program, **kw):
        state, it = orig(self, program, **kw)
        return np.zeros_like(state), it
    return run


def _altered_run(orig):
    def run(self, program, **kw):
        state, it = orig(self, program, **kw)
        state = state.copy()
        state[int(np.argmax(state))] *= 1.01
        return state, it
    return run


def _unchanged_batch(orig):
    def run_batch(self, program, sources=None, batch=None, **kw):
        plane, iters = orig(self, program, sources=sources, batch=batch,
                            **kw)
        init = np.full_like(plane, 2**31 - 1)
        for i, s in enumerate(sources):
            init[i, s] = 0
        return init, np.ones_like(iters)
    return run_batch


def _half_batch(orig):
    def run_batch(self, program, sources=None, batch=None, **kw):
        half = max(len(sources) // 2, 1)
        plane, iters = orig(self, program, sources=sources[:half],
                            batch=batch, **kw)
        take = np.arange(len(sources)) % half
        return plane[take], iters[take]
    return run_batch


def _altered_batch(orig):
    def run_batch(self, program, sources=None, batch=None, **kw):
        plane, iters = orig(self, program, sources=sources, batch=batch,
                            **kw)
        plane = plane.copy()
        plane[0, int(np.argmax(plane[0] < 2**31 - 1))] += 1
        return plane, iters
    return run_batch


def _misrouted_batch(orig):
    def run_batch(self, program, sources=None, batch=None, **kw):
        plane, iters = orig(self, program, sources=sources, batch=batch,
                            **kw)
        return np.roll(plane, 1, axis=0), np.roll(iters, 1)
    return run_batch


# the serving cell's rate is raised so that queries queue behind each
# dispatch and ride it together, as on the chip, where a dispatch is slow
CASES = {
    "lj1-s21.pagerank20": (11, {}, {"run": [_unchanged_run, _altered_run]}),
    "snb-sf10.bfs": (10, {"rate_qps": 1000.0},
                     {"run_batch": [_unchanged_batch, _half_batch,
                                    _altered_batch, _misrouted_batch]}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_sound_run_is_correct(name):
    scale, mix, _ = CASES[name]
    out = run(tiny(name, scale, **mix), seconds=0.5)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {m["name"] for m in
                                   cells.load(name).end_to_end}
    assert list(out)[-1] == "checks"


FAULTS = [(name, attr, f) for name, (_, _, by) in sorted(CASES.items())
          for attr, fs in by.items() for f in fs]


@pytest.mark.parametrize("name,attr,fault", FAULTS,
                         ids=[f"{n}-{f.__name__}" for n, _, f in FAULTS])
def test_broken_path_is_not_correct(name, attr, fault, monkeypatch):
    from repro.core.engine import Engine

    scale, mix, _ = CASES[name]
    monkeypatch.setattr(Engine, attr, fault(getattr(Engine, attr)))
    out = run(tiny(name, scale, **mix), seconds=0.5)
    assert out["correct"] is False and out["failed"] >= 1


@pytest.mark.parametrize("name", sorted(CASES))
def test_control_is_caught_at_a_tiny_size(name):
    import control

    scale, mix, _ = CASES[name]
    got = control.control_readings(tiny(name, scale, **mix), SEED, 0.5)
    assert any(v["fails"] for v in got.values())


def test_a_batched_jobs_mix_runs_and_checks():
    # the Graph500-style mix a later data-only cell can add: B sources
    # per job through one run_batch, each row checked against the BFS
    cell = tiny("snb-sf10.bfs", 9, kind="jobs", batch=4)
    out = run(cell, seconds=0.3)
    assert out["correct"] is True and out["attempted"] >= 1


def test_a_streamed_placement_runs_and_checks():
    # residency="stream" from the configuration file alone
    cell = tiny("lj1-s21.pagerank20", 10)
    place = dict(cell.config["placement"], partitioner="grid(1,1)",
                 residency="stream", stream={"windows": 2})
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 placement=place))
    out = run(cell, seconds=0.3)
    assert out["correct"] is True

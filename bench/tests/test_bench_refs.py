"""The plain references against the program's serial baselines, the
controls against the limits, the traffic generator, the generator of
graphs, and the push byte count by hand."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cells  # noqa: E402
import drivers  # noqa: E402
import graphgen  # noqa: E402
import traffic  # noqa: E402
import workcount  # noqa: E402

RMAT = dict(generator="rmat", a=0.57, b=0.19, c=0.19)


def _graph(scale, per_vertex, seed, symmetrize=False, weights=None):
    draws = per_vertex << scale
    cfg = dict(RMAT, scale=scale, edge_draws=draws, symmetrize=symmetrize,
               edges=draws // 4 if symmetrize else int(draws * 0.95))
    if weights:
        cfg["weights"] = weights
    return graphgen.generate(cfg, seed)


def _program_graph(edges):
    from repro.core import from_edges

    return from_edges(edges.num_vertices, edges.src, edges.dst,
                      directed=edges.directed, weight=edges.weight)


@pytest.mark.parametrize("seed", [1, 2**40 + 3])
def test_generator_is_seeded_sorted_and_loop_free(seed):
    a = _graph(9, 8, seed, weights=dict(low=1.0, high=10.0))
    b = _graph(9, 8, seed, weights=dict(low=1.0, high=10.0))
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.weight, b.weight)
    assert np.all(np.diff(a.src) >= 0) and not np.any(a.src == a.dst)
    assert a.weight.min() >= 1.0 and a.weight.max() < 10.0
    c = _graph(9, 8, seed + 1)
    assert not np.array_equal(c.dst[:100], a.dst[:100])


def test_symmetrized_graph_holds_each_pair_both_ways():
    e = _graph(8, 16, 7, symmetrize=True)
    fwd = set(zip(e.src.tolist(), e.dst.tolist()))
    assert all((v, u) in fwd for u, v in fwd)
    assert len(fwd) == e.num_edges and not e.directed


@pytest.mark.parametrize("seed", [3, 11, 2**35])
def test_pagerank_reference_agrees_with_serial_baseline(seed):
    from repro.core import pagerank_serial

    ref = cells.reference("pagerank")
    e = _graph(10, 14, seed)
    want = pagerank_serial(_program_graph(e), alpha=0.85, iters=20)
    got = ref.pagerank(e, 0.85, 20)
    assert np.max(np.abs(got - want) / got) < 1e-5


@pytest.mark.parametrize("seed,sym", [(5, True), (6, False), (2**36, True)])
def test_bfs_reference_agrees_with_serial_baseline(seed, sym):
    from repro.core import bfs_serial

    ref = cells.reference("bfs")
    e = _graph(9, 10, seed, symmetrize=sym)
    g = _program_graph(e)
    pool = traffic.candidates(e.num_vertices, e.src)
    sources = list(pool[:3]) + [int(pool[-1])]
    rows, steps = ref.depths(e, sources)
    for s, row, st in zip(sources, rows, steps):
        want, it = bfs_serial(g, int(s))
        assert np.array_equal(row, want) and st == it


def _answers(states, sources=None, steps=20):
    sources = sources or [None] * len(states)
    return [drivers.Answer(s, st, steps) for s, st in zip(sources, states)]


def test_pagerank_control_fails_the_limit_and_the_program_meets_it():
    from repro.core import Engine, partition

    limits = cells.load("lj1-s21.pagerank20").traffic["limits"]
    ref = cells.reference("pagerank")
    params = {"alpha": 0.85, "iters": 20}
    e = _graph(11, 14, 13, weights=dict(low=1.0, high=10.0))
    state, it = Engine(partition(_program_graph(e), 1)).run(
        "pagerank", **params)
    sound = ref.numbers(e, params, _answers([state], steps=it))
    assert sound["max_rel_err"] <= limits["max_rel_err"]
    assert sound["wrong_iters"] == 0
    ctrl = ref.numbers(e, params, ref.control_answers(
        e, params, _answers([state])))
    assert ctrl["max_rel_err"] > 3 * limits["max_rel_err"]


def test_bfs_control_fails_the_limit():
    limits = cells.load("snb-sf10.bfs").traffic["limits"]
    ref = cells.reference("bfs")
    e = _graph(10, 37, 21, symmetrize=True)
    pool = traffic.candidates(e.num_vertices, e.src)
    sources = [int(s) for s in pool[:8]]
    rows, steps = ref.depths(e, sources)
    sound = _answers(list(rows), sources)
    for a, st in zip(sound, steps):
        a.supersteps = int(st)
    assert ref.numbers(e, {}, sound)["wrong_answers"] == 0
    ctrl = ref.numbers(e, {}, ref.control_answers(e, {}, sound))
    assert ctrl["wrong_answers"] > limits["wrong_answers"]
    assert ctrl["wrong_vertices"] > limits["wrong_vertices"]


def test_push_bytes_by_hand():
    # 1,000 edges of two int32 ids, 100 vertices of 4 bytes at 2 columns,
    # read once and written once: 8,000 + 1,600 bytes; weights add 4,000
    assert workcount.push_bytes(100, 1000, 2, 4, False) == 9_600
    assert workcount.push_bytes(100, 1000, 2, 4, True) == 13_600
    # the scale-21 PageRank superstep of the cell: about 0.25 GB
    assert workcount.push_bytes(2**21, 29_360_128, 1, 4, False) == \
        29_360_128 * 8 + 2 * 2**21 * 4


@pytest.mark.parametrize("job_s,starts", [(10.0, [0, 10, 20, 30, 40]),
                                          (60.0, [0])])
def test_jobs_window_holds_the_whole_jobs_that_fit(job_s, starts,
                                                   monkeypatch):
    clock = [0.0]
    monkeypatch.setattr(drivers.time, "monotonic", lambda: clock[0])

    class Engine:
        def run(self, program, **params):
            clock[0] += job_s
            return np.zeros(4), 20

    d = drivers.JobsDriver(Engine(), {"program": "pagerank"}, np.arange(4),
                           1)
    d.window(51.0)
    # the next job starts only while it would still end in the window
    assert [j.start for j in d.jobs] == starts
    assert all(j.end - j.start == job_s for j in d.jobs)


def test_open_loop_schedule_same_load_for_every_seed():
    mix = cells.load("snb-sf10.bfs").traffic
    pool = np.arange(5, 1005)
    a = traffic.open_loop(mix, 30.0, pool, 2**34 + 1)
    b = traffic.open_loop(mix, 30.0, pool, 2**34 + 1)
    c = traffic.open_loop(mix, 30.0, pool, 99)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.sources,
                                                           b.sources)
    assert len(a.due) == len(c.due)
    assert abs(len(a.due) - 30 * mix["rate_qps"]) <= 2
    assert np.all(np.diff(a.due) >= 0) and a.due[-1] < 30.0
    assert np.allclose(np.sort(np.diff(a.due)), np.sort(np.diff(c.due)),
                       atol=0.2)
    # uniform, as the LDBC driver walks its parameter persons: no source
    # is asked about twice in a window
    assert len(np.unique(a.sources)) == len(a.sources)
    assert set(a.sources.tolist()) <= set(pool.tolist())
    # a skewed variant mix: Zipf(1)'s most asked-about source takes the
    # largest share
    z = traffic.open_loop(dict(mix, sources={"dist": "zipf", "exponent": 1.0}),
                          30.0, pool, 2**34 + 1)
    _, counts = np.unique(z.sources, return_counts=True)
    assert counts.max() >= 0.1 * len(z.sources)


def test_onoff_arrivals_keep_the_mean_rate():
    rng = traffic.rng_for(1, "due")
    t = traffic.arrivals({"process": "onoff", "on_s": 1.0, "off_s": 3.0},
                         10.0, 40.0, rng)
    assert abs(len(t) - 400) <= 40
    assert np.all(np.mod(t, 4.0) < 1.0 + 1e-9)

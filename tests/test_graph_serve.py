"""Graph-serving-path tests (ISSUE 9): personalized PageRank on the batched
add-monoid plane, the deadline-aware admission policy, and regressions for
the three serving bugfixes (empty-sources guard, read-once results, qps
measured only over the timed drain)."""

import argparse
import time

import numpy as np
import pytest

from conftest import ALL_STRATEGIES, program_graph
from repro.core import (Engine, partition, personalized_pagerank_serial,
                        rmat)
from repro.launch.serve import (DeadlinePolicy, GraphQueryServer,
                                QueryRequest, VirtualClock)

SEED_SETS = [(0,), (7, 61), (3, 5, 40)]  # rmat6: 64 vertices


def _server(batch=4, policy=None, clock=None, algo="sssp"):
    g = program_graph(algo, "rmat6")  # weighted: serves sssp AND bfs
    eng = Engine(partition(g, 1))
    return g, GraphQueryServer(eng, batch=batch, policy=policy, clock=clock)


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Tentpole: PPR through run_batch == per-query Engine.run references
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_ppr_batched_matches_sequential(strategy):
    """ISSUE 9 acceptance (1-PE leg; the 2/8-PE and grid legs live in
    test_multidevice): every column of one batched PPR sweep matches its
    own sequential ``Engine.run`` within 1e-6 with identical (fixed)
    iteration counts."""
    g = program_graph("personalized_pagerank", "rmat6")
    eng = Engine(partition(g, 1), strategy=strategy)
    plane, q_it = eng.run_batch("personalized_pagerank", sources=SEED_SETS,
                                batch=4, iters=7)
    assert list(q_it) == [7] * len(SEED_SETS)
    for i, seeds in enumerate(SEED_SETS):
        want, want_it = eng.run("personalized_pagerank", seeds=seeds,
                                iters=7)
        assert want_it == 7
        np.testing.assert_allclose(plane[i], want, atol=1e-6)
        # normalized per query: scores are a probability distribution
        assert abs(float(plane[i].sum()) - 1.0) < 1e-4


def test_ppr_matches_serial_reference():
    g = program_graph("personalized_pagerank", "rmat6")
    eng = Engine(partition(g, 1, partitioner="edge_balanced"))
    for seeds in SEED_SETS:
        got, _ = eng.run("personalized_pagerank", seeds=seeds, iters=30)
        want = personalized_pagerank_serial(g, seeds=seeds, iters=30)
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_ppr_empty_seed_set_rejected():
    g = program_graph("personalized_pagerank", "rmat6")
    eng = Engine(partition(g, 1))
    with pytest.raises(ValueError, match="empty seed set"):
        eng.run_batch("personalized_pagerank", sources=[(0,), ()])


def test_ppr_batched_amortization():
    """ISSUE 9 acceptance: serving B=16 PPR queries off one batched sweep
    is >= 3x faster than 16 sequential ``Engine.run`` calls (both fully
    warm; best-of-repeats so one scheduler hiccup cannot flake the bar)."""
    g = rmat(10, 8 * 2**10, seed=0)
    eng = Engine(partition(g, 1))
    sources = [int(s) for s in
               np.random.default_rng(0).integers(g.num_vertices, size=16)]

    def seq():
        for s in sources:
            eng.run("personalized_pagerank", seeds=(s,), iters=8)

    def batched():
        eng.run_batch("personalized_pagerank", sources=sources, batch=16,
                      iters=8)

    seq(), batched()  # warm both compiled paths
    t_seq = min(_timed(seq) for _ in range(2))
    t_bat = min(_timed(batched) for _ in range(2))
    assert t_seq / t_bat >= 3.0, (t_seq, t_bat)


# ---------------------------------------------------------------------------
# Server behaviour: B=1, mixed programs, deadlines
# ---------------------------------------------------------------------------


def test_server_batch_one():
    """A width-1 server degenerates to sequential serving but must keep the
    whole protocol intact (per-query results, stats, read-once)."""
    from repro.core import programs as P

    g, server = _server(batch=1)
    srcs = [1, 7, 22]
    ids = [server.submit("bfs", s) for s in srcs]
    assert server.drain() == 3
    assert server.dispatches == 3
    for rid, s in zip(ids, srcs):
        row, it = server.result(rid)
        want, want_it = P.bfs_serial(g, source=s)
        np.testing.assert_array_equal(row, want)
        assert it == want_it


def test_server_mixed_program_arrival_order():
    """Greedy admission across interleaved programs: each step serves the
    queue head's program, and WITHIN each program queries complete in
    arrival order even when the other program's traffic splits them
    across steps."""
    _, server = _server(batch=2)
    a0 = server.submit("bfs", 1)
    b0 = server.submit("sssp", 2)
    a1 = server.submit("bfs", 3)
    b1 = server.submit("sssp", 4)
    a2 = server.submit("bfs", 5)
    order = []
    while server.pending():
        order.extend(server.step())
    # head bfs admits a0+a1 (skipping b0), then sssp b0+b1, then bfs a2
    assert order == [a0, a1, b0, b1, a2]
    assert server.dispatches == 3


def test_server_deadline_expired_served_and_flagged():
    """An already-expired query is SERVED and flagged, never dropped."""
    clock = VirtualClock()
    _, server = _server(batch=2, policy=DeadlinePolicy(), clock=clock)
    rid = server.submit("bfs", 1, deadline=0.05)
    clock.advance(1.0)  # blow the deadline before any dispatch
    done = server.step()  # expired head: zero slack forces dispatch
    assert done == [rid]
    st = server.stats[rid]
    assert st.deadline_missed and st.latency >= 1.0
    row, _ = server.result(rid)  # the result is still there
    assert np.asarray(row).shape[0] >= 1


def test_server_rejects_empty_seed_set():
    _, server = _server()
    with pytest.raises(ValueError, match="non-empty seed set"):
        server.submit("personalized_pagerank", [])
    assert server.pending() == 0


def test_server_serves_ppr_seed_sets():
    g, server = _server(batch=4, algo="personalized_pagerank")
    ids = [server.submit("personalized_pagerank", seeds, iters=6)
           for seeds in SEED_SETS]
    assert server.drain() == len(ids)
    eng = Engine(partition(g, 1))
    for rid, seeds in zip(ids, SEED_SETS):
        row, it = server.result(rid)
        want, _ = eng.run("personalized_pagerank", seeds=seeds, iters=6)
        np.testing.assert_allclose(row, want, atol=1e-6)
        assert it == 6


# ---------------------------------------------------------------------------
# DeadlinePolicy unit behaviour (EDF, holds, slack dispatch, interleaving)
# ---------------------------------------------------------------------------


def _req(rid, program, deadline=None, params=()):
    return QueryRequest(rid, program, rid, tuple(params), submit_time=0.0,
                        deadline=deadline)


def test_deadline_policy_holds_underfull_then_dispatches_on_slack():
    pol = DeadlinePolicy()
    queue = (_req(0, "bfs", deadline=10.0), _req(1, "bfs", deadline=12.0))
    # under-full + ample slack (10s >> 1 x 0.1s dispatch): hold
    assert pol.select(queue, 4, now=0.0, est_dispatch_s=0.1, force=False) \
        == []
    # slack 0.05 < one dispatch time: waiting longer would miss it
    got = pol.select(queue, 4, now=9.95, est_dispatch_s=0.1, force=False)
    assert [r.id for r in got] == [0, 1]
    # force (the drain path) overrides any hold
    pol2 = DeadlinePolicy()
    assert len(pol2.select(queue, 4, now=0.0, est_dispatch_s=0.1,
                           force=True)) == 2


def test_deadline_policy_edf_and_interleave():
    pol = DeadlinePolicy()
    urgent = _req(5, "sssp", deadline=1.0)
    lax = (_req(0, "bfs", deadline=50.0), _req(1, "bfs", deadline=60.0))
    got = pol.select(lax + (urgent,), 1, 0.0, 0.01, force=False)
    assert [r.id for r in got] == [5]  # EDF: later-arriving urgent one wins
    # interleaving: with equal urgency the last-dispatched program ranks
    # behind, so a saturating sssp stream cannot starve bfs
    pol2 = DeadlinePolicy()
    queue = (_req(2, "sssp"), _req(3, "sssp"), _req(4, "bfs"))
    got = pol2.select(queue, 2, 0.0, 0.01, force=True)
    assert [r.id for r in got] == [2, 3]  # arrival order: sssp group first
    got = pol2.select(queue, 2, 0.0, 0.01, force=True)
    assert all(r.program == "bfs" for r in got)  # stale sssp ranks behind


def test_deadline_policy_end_to_end_mixed_traffic():
    """Mixed bfs + PPR under DeadlinePolicy on a virtual clock: everything
    drains, per-query stats are recorded, and dispatches alternate
    programs instead of exhausting one stream first."""
    clock = VirtualClock()
    g, server = _server(batch=2, policy=DeadlinePolicy(), clock=clock,
                        algo="personalized_pagerank")
    for i in range(8):
        prog = "bfs" if i % 2 == 0 else "personalized_pagerank"
        src = (i + 1) if prog == "bfs" else (i + 1, i + 2)
        kw = {} if prog == "bfs" else {"iters": 5}
        server.submit(prog, src, deadline=30.0, **kw)
    seq = []
    while server.pending():
        done = server.step(force=True)
        assert done  # force: no hold may stall the loop
        seq.append(server.stats[done[0]].program)
    assert len(server.stats) == 8
    assert not any(s.deadline_missed for s in server.stats.values())
    assert set(seq) == {"bfs", "personalized_pagerank"}
    # no-SLO-free case here: all deadlines equal, so the interleave rule
    # must alternate programs on every dispatch
    assert all(a != b for a, b in zip(seq, seq[1:]))


# ---------------------------------------------------------------------------
# Measured SLO calibration (ISSUE 10): per-(program, B-bucket) dispatch EWMA
# ---------------------------------------------------------------------------


def test_dispatch_times_recorded_per_program_bucket():
    """Each dispatched program gets its own (program, B) EWMA entry next
    to the global estimate, and ``est_dispatch`` prefers it."""
    _, server = _server(batch=2, algo="personalized_pagerank")
    for i in range(2):
        server.submit("bfs", i + 1)
        server.submit("personalized_pagerank", (i + 1,), iters=3)
    server.drain()
    assert set(server.dispatch_times) == {("bfs", 2),
                                          ("personalized_pagerank", 2)}
    for prog in ("bfs", "personalized_pagerank"):
        assert server.est_dispatch(prog) == server.dispatch_times[(prog, 2)]
        assert server.est_dispatch(prog) > 0.0


def test_est_dispatch_fallbacks():
    """A program never dispatched falls back to the global EWMA; a fully
    cold server reports 0.0 so a fresh queue is never held against a
    fictitious budget."""
    _, server = _server(batch=2)
    assert server.est_dispatch("bfs") == 0.0  # cold: no estimate at all
    server.submit("sssp", 1)
    server.submit("sssp", 2)
    server.drain()
    assert server.est_dispatch("sssp") == server.dispatch_times[("sssp", 2)]
    assert server.est_dispatch("bfs") == server.dispatch_time  # global fall


def test_deadline_policy_prices_per_program_estimate():
    """The hold test resolves a callable est_dispatch_s with the group
    being held: a cheap program dispatches on its own small budget while
    an expensive one is still held (same queue shape, same deadlines)."""
    pol = DeadlinePolicy()
    est = {"bfs": 0.01, "personalized_pagerank": 5.0}.get
    cheap = (_req(0, "bfs", deadline=10.0),)
    # slack 10 >> 0.01: held to let the plane fill
    assert pol.select(cheap, 4, now=0.0, est_dispatch_s=lambda p: est(p),
                      force=False) == []
    costly = (_req(1, "personalized_pagerank", deadline=10.0),)
    # the SAME slack is inside the expensive program's 5s budget: dispatch
    got = pol.select(costly, 4, now=6.0, est_dispatch_s=lambda p: est(p),
                     force=False)
    assert [r.id for r in got] == [1]
    # floats still work (one global estimate, the pre-calibration contract)
    assert pol.select(cheap, 4, now=0.0, est_dispatch_s=0.1,
                      force=False) == []


# ---------------------------------------------------------------------------
# Bugfix regressions (each fails on the pre-PR serving path)
# ---------------------------------------------------------------------------


def test_run_batch_empty_sources_clear_error():
    """Bugfix 1: an empty query list used to surface as an opaque failure
    deep in seed-set normalization; now it is rejected up front with an
    actionable message, and an empty server queue stays a no-op."""
    g = program_graph("bfs", "rmat6")
    eng = Engine(partition(g, 1))
    with pytest.raises(ValueError, match="at least one query"):
        eng.run_batch("bfs", sources=[])
    _, server = _server()
    assert server.step() == [] and server.drain() == 0  # empty queue is fine


def test_server_results_memory_bounded():
    """Bugfix 2: ``result()`` is read-once -- after every row is read the
    server holds NO [V]-sized result buffers (scalar stats persist)."""
    _, server = _server(batch=4)
    ids = [server.submit("bfs", s) for s in (1, 2, 3, 4, 5)]
    assert server.drain() == 5
    assert len(server._results) == 5
    for rid in ids:
        server.result(rid)
    assert server._results == {}  # drained + read => nothing pinned
    assert len(server.stats) == 5  # scalar records survive
    with pytest.raises(KeyError, match="not finished"):
        server.result(ids[0])  # second read: the row is gone


def test_graph_main_qps_counts_timed_drain_only():
    """Bugfix 3: steady-state qps counts only queries completed inside the
    timed drain -- the warm-up step's completions are excluded from the
    numerator exactly as their wall-clock is excluded from the
    denominator."""
    from repro.launch.serve import _graph_main

    args = argparse.Namespace(scale=6, queries=10, batch=4, policy="deadline",
                              programs="bfs,personalized_pagerank",
                              deadline=5.0, ppr_iters=4)
    m = _graph_main(args)
    assert m["queries"] == 10
    assert 1 <= m["warmup"] <= 4
    assert m["drained"] == m["queries"] - m["warmup"]
    assert m["qps"] == pytest.approx(m["drained"] / m["wall_s"], rel=1e-6)
    assert m["dispatches"] >= 2 and m["p50_s"] > 0.0


# ---------------------------------------------------------------------------
# Spans: each admitted query's wait in the queue, each dispatch's duration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("virtual", [False, True])
def test_server_queue_records_one_per_admitted_query(virtual):
    """One ``server.queue`` record per admitted query, carrying its id and
    the dispatch it rode, timed on the span clock: a ``VirtualClock``
    server still records the real wait."""
    from repro import obs

    obs.reset()
    clock = VirtualClock() if virtual else None
    _, server = _server(batch=2, clock=clock)
    rids = [server.submit("bfs", s) for s in (1, 2, 3)]
    time.sleep(0.02)
    assert server.step() == rids[:2]
    assert server.step() == rids[2:]
    queue = obs.records("server.queue")
    assert [(r.attrs["rid"], r.attrs["dispatch"]) for r in queue] == [
        (rids[0], 0), (rids[1], 0), (rids[2], 1)]
    assert all(r.seconds >= 0.02 for r in queue)
    dispatches = obs.records("server.dispatch")
    assert [r.attrs["dispatch"] for r in dispatches] == [0, 1]
    # the third query waited out the first dispatch
    assert queue[2].end_ns >= dispatches[0].end_ns


def test_last_dispatch_s_is_the_dispatch_span():
    from repro import obs

    obs.reset()
    _, server = _server(batch=2)
    server.submit("bfs", 1)
    server.step()
    (rec,) = obs.records("server.dispatch")
    assert server.last_dispatch_s == rec.seconds
    assert server.dispatch_time == rec.seconds
    (loop,) = obs.records("engine.loop")
    assert rec.start_ns <= loop.start_ns <= loop.end_ns <= rec.end_ns

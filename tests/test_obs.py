"""Host spans (``repro.obs``): nesting, the bounded ring, intervals that do
not nest, and the spans as a profiler trace holds them."""

import glob
import threading

import jax
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def _empty_ring():
    obs.reset()
    yield
    obs.reset()


def test_span_nesting_and_parent_id():
    with obs.span("outer", k=1) as outer:
        with obs.span("inner") as inner:
            pass
        with obs.span("inner"):
            pass
    recs = {r.span_id: r for r in obs.records("inner")}
    assert set(recs) == {inner.span_id, inner.span_id + 1}
    assert all(r.parent_id == outer.span_id for r in recs.values())
    (top,) = obs.records("outer")
    assert top.parent_id is None and top.attrs == {"k": 1}
    assert top.start_ns <= recs[inner.span_id].start_ns
    assert recs[inner.span_id].end_ns <= top.end_ns
    assert outer.seconds == top.seconds >= 0.0


def test_span_closes_on_error_and_threads_nest_apart():
    with pytest.raises(KeyError):
        with obs.span("failing"):
            raise KeyError("x")
    (failed,) = obs.records("failing")
    seen = {}

    def worker():
        with obs.span("worker") as s:
            seen["parent"] = s.parent_id

    with obs.span("main") as main:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with obs.span("after") as after:
            pass
    # the failed span left the stack; another thread's stack is its own
    assert main.parent_id is None and seen["parent"] is None
    assert after.parent_id == main.span_id and failed.parent_id is None


def test_ring_is_bounded():
    assert obs._ring.maxlen == obs.RING_SIZE
    for i in range(obs.RING_SIZE + 10):
        obs.record("tick", i, i + 1, i=i)
    ticks = obs.records("tick")
    assert len(ticks) == obs.RING_SIZE
    assert ticks[0].attrs["i"] == 10 and ticks[-1].attrs["i"] == (
        obs.RING_SIZE + 9)


def test_record_adds_an_interval_that_nests_under_nothing():
    t0 = obs.clock_ns()
    with obs.span("open") as s:
        rec = obs.record("server.queue", t0 - 5_000, t0, rid=7, dispatch=0)
    assert rec.parent_id is None and rec.span_id != s.span_id
    assert rec.seconds == 5e-6 and rec.attrs == {"rid": 7, "dispatch": 0}
    assert obs.records("server.queue") == [rec]
    assert obs.records("missing") == []


def test_profiler_trace_nests_engine_loop_in_server_dispatch(tmp_path):
    """The spans land in a CPU profiler trace on the calling thread's host
    line, nested as they ran, and on the ring's clock."""
    from jax.profiler import ProfileData

    from repro.core import Engine, partition, rmat
    from repro.launch.serve import GraphQueryServer

    server = GraphQueryServer(Engine(partition(rmat(6, 256, seed=0), 1)),
                              batch=2)
    server.submit("bfs", 1)
    server.step()  # compiles outside the trace
    obs.reset()
    server.submit("bfs", 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    start = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    host = pd.find_plane_with_name("/host:CPU")
    lines = [ln for ln in host.lines
             if any(ev.name == "server.dispatch" for ev in ln.events)]
    assert len(lines) == 1
    events = {}
    for ev in lines[0].events:
        events.setdefault(ev.name, []).append(ev)
    (disp,) = events["server.dispatch"]
    (loop,) = events["engine.loop"]
    assert disp.start_ns <= loop.start_ns <= loop.end_ns <= disp.end_ns
    assert dict(disp.stats)["dispatch"] == 1
    for name in ("engine.run_batch", "engine.prepare", "engine.fetch",
                 "engine.unpermute", "server.admit", "server.route"):
        assert name in events, name
    (rec,) = obs.records("server.dispatch")
    assert abs(start + disp.start_ns - rec.start_ns) < 1e6
    assert abs(start + disp.end_ns - rec.end_ns) < 1e6

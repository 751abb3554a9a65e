"""One run of one cell: set-up, the measured window, the check, the metrics.

``run_cell`` does, in order:

1. enables JAX's persistent compilation cache (``repro.compile_cache``: a
   fixed directory inside the checkout, or ``JAX_COMPILATION_CACHE_DIR``);
2. makes the graph from the seed (``graphgen``) and hands its edge list to
   the program: ``from_edges``, ``partition``, ``Engine``;
3. warms up the cell's only shapes through the driver the mix names;
4. measures the window (traced with ``--trace 1``);
5. reads the device's peak memory, frees the program's state, and runs the
   plain reference against the answers the window produced.

It returns the result line's object; ``run.py`` prints it.  Nothing here
looks for the chip: ``run.py`` does that before calling in.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import tempfile
import time

import cells
import drivers
import graphgen
import traffic as traffic_mod


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a metric reader reads (``metrics/<name>.py``)."""

    seconds: float
    setup_s: float
    spans: dict
    jobs: list
    queries: list
    dispatches: list
    columns: int
    shapes: dict
    work: dict
    peaks: dict | None
    trace: object | None


class CompileCounter:
    """Counts the programs XLA builds: ``built`` counts each compile or
    load from the persistent cache, ``hits`` the loads alone."""

    BUILT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.built = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._built)
        jax.monitoring.register_event_listener(self._hit)

    def _built(self, event, duration, **kw):
        self.built += event == self.BUILT

    def _hit(self, event, **kw):
        self.hits += event == self.HIT

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._built)
        jax.monitoring.unregister_event_listener(self._hit)


def enable_cache():
    import jax

    from repro.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    # every program goes to the cache, however quick its compile, so a
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build(cell: cells.Cell, seed: int, spans: dict):
    """Graph from the seed, then the program's host prep and engine."""
    from repro.core import Engine, from_edges, partition

    def timed(name, fn, *a, **kw):
        t = time.monotonic()
        out = fn(*a, **kw)
        spans[name] = time.monotonic() - t
        return out

    cfg = cell.config
    edges = timed("generate", graphgen.generate, cfg["graph"], seed)
    g = timed("from_edges", from_edges, edges.num_vertices, edges.src,
              edges.dst, directed=edges.directed, weight=edges.weight)
    place = cfg["placement"]
    pg = timed("partition", partition, g, int(place["num_chunks"]),
               partitioner=place.get("partitioner", "contiguous"))
    push = place.get("push_fn", "auto")
    kw = {"push_fn": None if push == "staged" else push}
    if place.get("residency", "resident") == "stream":
        from repro.core import StreamConfig

        kw.update(residency="stream",
                  stream=StreamConfig(**place.get("stream", {})))
    engine = timed("engine", Engine, pg, **kw)
    return edges, engine


def make_driver(cell, engine, pool, seed):
    kind = cell.traffic["kind"]
    if kind == "jobs":
        return drivers.JobsDriver(engine, cell.traffic, pool, seed)
    if kind == "open_loop":
        from repro.launch.serve import GraphQueryServer

        srv = cell.traffic["server"]
        if srv.get("policy", "greedy") != "greedy":
            raise ValueError(f"unknown admission policy {srv['policy']!r}")
        server = GraphQueryServer(engine, batch=int(srv["batch"]))
        return drivers.OpenLoopDriver(server, cell.traffic, pool, seed)
    raise ValueError(f"unknown traffic kind {kind!r}")


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _finite(x):
    return x if x is None or math.isfinite(x) else None


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None = None) -> dict:
    import jax

    spans: dict = {}
    cache = enable_cache()
    compiles = CompileCounter()
    devices = jax.devices()[:cell.chips]
    edges, engine = build(cell, seed, spans)
    log(f"graph: {edges.num_vertices} vertices, {edges.num_edges} edges; "
        f"compile cache {cache}")
    log("dispatch: " + _json({k: v for k, v in engine.dispatch.items()
                              if k != "stream"}))
    pool = traffic_mod.candidates(edges.num_vertices, edges.src)
    driver = make_driver(cell, engine, pool, seed)
    t = time.monotonic()
    driver.warm_up()
    spans["warm_up"] = time.monotonic() - t
    setup_s = time.monotonic() - t_start
    log("setup spans (s): " + _json(spans) + f"; setup_s {setup_s}")

    tdir = None
    if trace:
        tdir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir.name, profiler_options=opts)
    built, hits = compiles.built, compiles.hits
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles.close()
    log(f"programs built in set-up: {built} ({hits} from the persistent "
        f"cache); in the window: {compiles.built - built} "
        f"({compiles.hits - hits} from the cache)")
    log("dispatch after window: " + _json(
        {k: v for k, v in engine.dispatch.items() if k != "stream"}))
    mem = peak_bytes(devices)
    log(f"peak_bytes_in_use: {mem}")

    summary = None
    if trace:
        import xplane

        summary = xplane.summarize(xplane.find_trace(tdir.name),
                                    edges.num_edges)
        tdir.cleanup()
        log(f"trace: window {summary.window_s} s, busy {summary.busy_s} s, "
            f"push {summary.push_s} s from ops {summary.push_ops}")

    ctx = Context(
        seconds=float(seconds), setup_s=setup_s, spans=spans,
        jobs=list(getattr(driver, "jobs", [])),
        queries=list(getattr(driver, "queries", [])),
        dispatches=list(getattr(driver, "dispatches", [])),
        columns=driver.columns(),
        shapes={"num_vertices": edges.num_vertices,
                "num_edges": edges.num_edges},
        work=cell.traffic.get("work", {}), peaks=peaks, trace=summary)
    if ctx.jobs:
        log("jobs (s): " + _json([j.end - j.start for j in ctx.jobs]))
    if ctx.queries:
        late = max(q.sent - q.due for q in ctx.queries if q.sent is not None)
        log(f"server: {len(ctx.dispatches)} dispatches; queries reached the "
            f"server up to {late:.6f} s after they were due")
    attempted, unanswered = driver.attempted(), driver.unanswered()
    answers = driver.answers

    # the program's state goes before the reference runs
    del driver, engine
    gc.collect()

    checked = check(cell, edges, answers, unanswered)
    failed = unanswered + int(checked["wrong"])

    chosen = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in chosen:
        value = _finite(cells.metric_reader(m["name"])(ctx))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": checked["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checked["numbers"]
    return result


def check(cell, edges, answers, unanswered) -> dict:
    """The comparison with the plain reference that decides ``correct``."""
    t = time.monotonic()
    tr = cell.traffic
    ref = cells.reference(tr["reference"])
    numbers = ref.numbers(edges, tr.get("params", {}), answers)
    numbers["unanswered"] = float(unanswered)
    limits = dict(tr["limits"])
    limits.setdefault("unanswered", 0.0)
    out = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(answers) and all(
        v["value"] <= v["limit"] for v in out.values())
    # a check that counts no wrong answers fails every one it compared
    wrong = numbers.get("wrong_answers", 0.0 if correct else len(answers))
    log(f"reference: {len(answers)} answers checked in "
        f"{time.monotonic() - t:.3f} s")
    return {"correct": correct, "numbers": out, "wrong": wrong}


def _json(obj) -> str:
    import json

    return json.dumps(obj, default=str, sort_keys=True)

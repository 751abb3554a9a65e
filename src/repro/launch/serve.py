"""Serving drivers: the LM continuous-batching loop, and the persistent
graph-query server over ``Engine.run_batch`` (DESIGN.md section 11).

LM mode -- a minimal continuous-batching server: requests arrive with
prompts, get packed into a fixed batch, prefilled, then decoded together;
finished sequences are replaced from the queue (static shapes throughout --
slots are recycled, the XLA program never re-specializes).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --smoke \
        --requests 8 --gen 16

Graph mode -- a persistent multi-query serving loop: submitted queries
(program + source) queue up, each ``step()`` admits up to B compatible
requests (same program and params -- they must share one compiled plane)
and dispatches ONE fixed-width ``run_batch`` call, so steady-state traffic
always hits the warm B-bucket compile cache and every admitted query rides
the same edge sweep.  Admission is pluggable (DESIGN.md section 14):
``GreedyPolicy`` dispatches immediately in arrival order; ``DeadlinePolicy``
serves the earliest-deadline-compatible group, holds under-full planes
until the head's slack drops below one measured dispatch time, and
round-robins across programs so a long pagerank stream cannot starve BFS.

    PYTHONPATH=src python -m repro.launch.serve --graph --scale 10 \
        --queries 32 --batch 8 \
        --programs bfs,personalized_pagerank --policy deadline
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.models import model as M
from repro.models import serve as SV


class BatchedServer:
    """Slot-based continuous batching (static batch, recycled slots)."""

    def __init__(self, cfg, params, batch_slots: int, max_len: int):
        self.cfg, self.params = cfg, params
        self.B, self.max_len = batch_slots, max_len
        self.cache = M.init_cache(cfg, batch_slots, max_len)
        self.pos = 0
        self.tokens = jnp.zeros((batch_slots, 1), jnp.int32)
        self._decode = jax.jit(
            lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg),
            donate_argnums=(3,))

    def prefill(self, prompts: np.ndarray):
        """prompts: [B, S0] i32 -- runs the prompt through decode steps."""
        B, S0 = prompts.shape
        assert B == self.B
        logits = None
        for i in range(S0):
            logits, self.cache = self._decode(
                self.params, jnp.asarray(prompts[:, i:i + 1]),
                jnp.asarray(i), self.cache)
        self.pos = S0
        self.tokens = SV.sample_greedy(logits)
        return self.tokens

    def decode(self, steps: int):
        out = []
        for _ in range(steps):
            logits, self.cache = self._decode(
                self.params, self.tokens, jnp.asarray(self.pos), self.cache)
            self.tokens = SV.sample_greedy(logits)
            self.pos += 1
            out.append(np.asarray(self.tokens[:, 0]))
        return np.stack(out, axis=1)  # [B, steps]


# ---------------------------------------------------------------------------
# Graph-query serving over Engine.run_batch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QueryRequest:
    """One queued graph query: a program name, its seed, extra params, and
    the latency bookkeeping (``submit_time`` and an optional absolute
    ``deadline``, both on the server's clock)."""

    id: int
    program: str
    source: object  # original vertex id or a seed-id tuple
    params: tuple  # sorted (name, value) pairs beyond the source
    submit_time: float = 0.0
    deadline: float | None = None  # absolute clock time; None = no SLO

    @property
    def batch_key(self):
        """Requests sharing this key may ride one compiled batched plane."""
        return (self.program, self.params)

    def slack(self, now: float) -> float:
        """Seconds until the deadline (inf when the query has no SLO)."""
        return math.inf if self.deadline is None else self.deadline - now


@dataclasses.dataclass(frozen=True)
class QueryStats:
    """Per-query completion record -- scalars only, so retaining them for
    the server's lifetime costs O(queries) floats, not O(queries * V)."""

    id: int
    program: str
    latency: float  # completion - submit (queue wait + service)
    iters: int
    deadline: float | None
    deadline_missed: bool


class GreedyPolicy:
    """The PR-6 admission rule: dispatch immediately, filling the plane
    with queue-head-compatible requests in arrival order.  Never holds a
    query to wait for a fuller batch, never reorders."""

    def select(self, queue, batch, now, est_dispatch_s, force):
        head = queue[0]
        return [r for r in queue if r.batch_key == head.batch_key][:batch]


@dataclasses.dataclass
class DeadlinePolicy:
    """Earliest-deadline-first admission with slack-triggered early
    dispatch and cross-program interleaving.

    Groups the queue by ``batch_key`` and serves the group whose most
    urgent member has the earliest deadline (no-SLO groups rank last, by
    arrival).  A group smaller than the plane width is HELD -- letting
    traffic fill the batch -- until its head's slack drops below
    ``slack_factor`` x one measured dispatch time (then waiting longer
    would miss the deadline), or until ``force`` (the drain path).
    ``est_dispatch_s`` may be a plain float (one global estimate) or a
    callable ``program -> seconds``: the server passes its measured
    per-(program, B-bucket) EWMA, so the hold test prices the dispatch of
    the group actually being held -- a cheap BFS group is no longer held
    against an expensive PageRank budget or vice versa.  Among
    equally urgent groups the one dispatched last ranks behind the others,
    so steady mixed traffic alternates programs instead of letting a long
    stream starve the rest; with k live groups a group waits at most k-1
    dispatches for its turn (the starvation bound, DESIGN.md section 14).
    """

    slack_factor: float = 1.0
    interleave: bool = True
    _last_key: object = dataclasses.field(default=None, repr=False)

    def select(self, queue, batch, now, est_dispatch_s, force):
        groups: dict = {}
        for r in queue:
            groups.setdefault(r.batch_key, []).append(r)

        def rank(item):
            key, members = item
            urgency = min(m.slack(now) for m in members)
            stale = int(self.interleave and len(groups) > 1
                        and key == self._last_key)
            return (urgency, stale, members[0].id)

        key, members = min(groups.items(), key=rank)
        members = sorted(members, key=lambda m: (m.slack(now), m.id))
        take = members[:batch]
        if len(take) < batch and not force:
            est = (est_dispatch_s(key[0]) if callable(est_dispatch_s)
                   else est_dispatch_s)
            if min(m.slack(now) for m in take) > self.slack_factor * est:
                return []  # hold: the plane can still fill in time
        self._last_key = key
        return take


class VirtualClock:
    """Deterministic serving clock for benchmarks and tests: ``now`` only
    moves when the server ``advance``s it by each measured dispatch time,
    so arrival schedules are exact while service times stay measured."""

    def __init__(self, start: float = 0.0):
        self.now = float(start)

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> None:
        self.now += dt


class GraphQueryServer:
    """Persistent serving loop: fixed-B admission batching over one engine.

    ``submit`` enqueues; each ``step`` asks the admission ``policy`` for up
    to ``batch`` compatible requests (same program + params -- the compiled
    plane is per program) and dispatches one
    ``Engine.run_batch(..., batch=B)`` call.  The width is pinned so every
    dispatch after the first reuses the same compiled executable (the
    B-bucket cache).  ``GreedyPolicy`` (default) never holds a query
    hostage to fill the plane; ``DeadlinePolicy`` holds under-full batches
    until deadline slack forces dispatch.

    Results are per-query and READ-ONCE: ``result(id)`` -> (state row,
    supersteps) pops the [V]-sized row, so a long-running server's memory
    is bounded by in-flight queries, not total history.  Scalar
    ``QueryStats`` (latency, deadline hit/miss) stay in ``stats``.
    """

    def __init__(self, engine, batch: int = 8, policy=None, clock=None):
        if batch < 1:
            raise ValueError("batch must be >= 1")
        self.engine = engine
        self.batch = batch
        self.policy = GreedyPolicy() if policy is None else policy
        self.clock = time.monotonic if clock is None else clock
        self._queue: deque[QueryRequest] = deque()
        # each queued request's submit time on the span clock: its
        # ``server.queue`` record runs from there to its admission
        self._submitted_ns: dict[int, int] = {}
        self._results: dict[int, tuple] = {}
        self.stats: dict[int, QueryStats] = {}
        self._next_id = 0
        self.dispatches = 0  # run_batch calls issued (admission diagnostics)
        self.dispatch_time: float | None = None  # global EWMA of measured s
        # measured per-(program, B-bucket) dispatch budgets: the admission
        # policy prices each group's hold against ITS program's EWMA, and
        # tables.latency_table derives the SLO from the same record
        self.dispatch_times: dict[tuple, float] = {}
        self.last_dispatch_s: float | None = None

    def submit(self, program: str, source, deadline: float | None = None,
               **params) -> int:
        """Enqueue one query; ``deadline`` is relative seconds from now
        (stored absolute on the server's clock).  Returns the request id."""
        if not isinstance(source, (int, np.integer)):
            source = tuple(int(v) for v in source)
            if not source:
                raise ValueError(
                    f"query needs a non-empty seed set (program "
                    f"{program!r} submitted with an empty source)")
        else:
            source = int(source)
        rid = self._next_id
        self._next_id += 1
        self._submitted_ns[rid] = obs.clock_ns()
        now = self.clock()
        self._queue.append(QueryRequest(
            rid, program, source, tuple(sorted(params.items())),
            submit_time=now,
            deadline=None if deadline is None else now + float(deadline)))
        return rid

    def est_dispatch(self, program: str) -> float:
        """Measured dispatch-time estimate for ``program`` at this server's
        B-bucket -- the per-(program, B) EWMA, falling back to the global
        EWMA for a program not yet dispatched (and 0.0 cold, so a fresh
        server never holds on a fictitious budget)."""
        est = self.dispatch_times.get((program, self.batch),
                                      self.dispatch_time)
        return 0.0 if est is None else est

    def pending(self) -> int:
        return len(self._queue)

    def queued(self) -> tuple:
        """Snapshot of the waiting requests (for schedulers/benchmarks that
        need to see deadlines without reaching into the deque)."""
        return tuple(self._queue)

    def step(self, force: bool = False) -> list[int]:
        """Admit + dispatch one batch; returns the completed request ids.
        Returns [] both for an empty queue and when the policy elects to
        hold (waiting for the plane to fill); ``force`` overrides holds."""
        if not self._queue:
            return []
        now = self.clock()
        with obs.span("server.admit") as admit:
            admitted = self.policy.select(tuple(self._queue), self.batch,
                                          now, self.est_dispatch, force)
            if admitted:
                chosen = {r.id for r in admitted}
                self._queue = deque(r for r in self._queue
                                    if r.id not in chosen)
        if not admitted:
            return []
        n = self.dispatches
        for r in admitted:
            obs.record("server.queue", self._submitted_ns.pop(r.id),
                       admit.end_ns, rid=r.id, dispatch=n)
        with obs.span("server.dispatch", dispatch=n) as run:
            plane, iters = self.engine.run_batch(
                admitted[0].program, sources=[r.source for r in admitted],
                batch=self.batch, **dict(admitted[0].params))
        dt = run.seconds
        self.last_dispatch_s = dt
        self.dispatch_time = dt if self.dispatch_time is None \
            else 0.7 * self.dispatch_time + 0.3 * dt
        pkey = (admitted[0].program, self.batch)
        prev = self.dispatch_times.get(pkey)
        self.dispatch_times[pkey] = dt if prev is None \
            else 0.7 * prev + 0.3 * dt
        if hasattr(self.clock, "advance"):
            self.clock.advance(dt)
        done_t = self.clock()
        self.dispatches += 1
        with obs.span("server.route", dispatch=n):
            for i, req in enumerate(admitted):
                self._results[req.id] = (plane[i], int(iters[i]))
                # expired queries are SERVED and flagged, never dropped
                self.stats[req.id] = QueryStats(
                    id=req.id, program=req.program,
                    latency=done_t - req.submit_time, iters=int(iters[i]),
                    deadline=req.deadline,
                    deadline_missed=(req.deadline is not None
                                     and done_t > req.deadline))
        return [r.id for r in admitted]

    def drain(self) -> int:
        """Force-run steps until the queue is empty; returns the count of
        queries completed by THIS call (holds are overridden -- a drained
        server has dispatched everything)."""
        n = 0
        while self._queue:
            n += len(self.step(force=True))
        return n

    def result(self, rid: int):
        """Pop and return ``(state row, supersteps)`` for a finished query.
        READ-ONCE: the row is removed so completed state does not pin
        [V]-sized buffers forever; a second read raises KeyError."""
        if rid not in self._results:
            raise KeyError(f"request {rid} not finished (or unknown)")
        return self._results.pop(rid)


def _graph_main(args):
    from repro.core import Engine, partition, rmat
    from repro.core.engine import StreamConfig

    g = rmat(args.scale, 8 * (2 ** args.scale), seed=0, weighted=True)
    residency = getattr(args, "residency", "resident")
    if residency == "stream":
        # out-of-core serving (DESIGN.md section 15): the edge planes never
        # become device-resident; every dispatched batch sweeps each
        # prefetched edge window once for all B admitted queries
        eng = Engine(partition(g, 1, partitioner="grid(1,1)"),
                     residency="stream",
                     stream=StreamConfig(windows=getattr(args, "windows", 4)))
    else:
        eng = Engine(partition(g, 1))
    policy = DeadlinePolicy() if args.policy == "deadline" else GreedyPolicy()
    server = GraphQueryServer(eng, batch=args.batch, policy=policy)
    rng = np.random.default_rng(0)
    programs = [p.strip() for p in args.programs.split(",") if p.strip()]
    ids = []
    for q in range(args.queries):
        prog = programs[q % len(programs)]
        src = int(rng.integers(g.num_vertices))
        extra = dict(iters=args.ppr_iters) \
            if prog == "personalized_pagerank" else {}
        ids.append(server.submit(prog, src, deadline=args.deadline, **extra))
    # warm the B-bucket compile cache outside the timed loop (forced: the
    # deadline policy would otherwise hold an under-full first batch)
    warmed = len(server.step(force=True))
    t0 = time.time()
    drained = server.drain()
    dt = time.time() - t0
    # steady-state qps counts ONLY queries completed inside the timed
    # drain: the warm-up step's completions are excluded from the
    # numerator exactly as their wall-clock is excluded from the
    # denominator (counting them inflated qps by ~B/elapsed)
    qps = drained / max(dt, 1e-9)
    missed = sum(s.deadline_missed for s in server.stats.values())
    lat = sorted(s.latency for s in server.stats.values())
    metrics = dict(queries=len(ids), warmup=warmed, drained=drained,
                   wall_s=dt, qps=qps, dispatches=server.dispatches,
                   deadline_missed=missed,
                   p50_s=lat[len(lat) // 2] if lat else 0.0)
    print(f"[serve-graph] scale={args.scale} B={args.batch} "
          f"policy={args.policy}: {drained} queries in the timed drain "
          f"({warmed} warm-up, {server.dispatches} dispatches total), "
          f"steady-state {qps:.1f} queries/s, {missed} deadline misses")
    row, iters = server.result(ids[0])
    row = np.asarray(row)
    reach = int((row < 2**31 - 1).sum()) if row.dtype.kind == "i" \
        else int((row > 0).sum())
    print(f"[serve-graph] sample result: query {ids[0]} "
          f"iters={iters} reached={reach}")
    return metrics


def main():
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--graph", action="store_true",
                    help="serve graph queries (Engine.run_batch) instead of "
                         "LM decode")
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--programs", default="bfs",
                    help="comma-separated program mix for --graph traffic")
    ap.add_argument("--policy", choices=("greedy", "deadline"),
                    default="greedy")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-query SLO in seconds (relative to submit)")
    ap.add_argument("--ppr-iters", type=int, default=10,
                    help="fixed iterations for personalized_pagerank traffic")
    ap.add_argument("--residency", choices=("resident", "stream"),
                    default="resident",
                    help="graph residency for --graph serving: 'stream' "
                         "serves out-of-core, sweeping each prefetched edge "
                         "window once for all B admitted queries")
    ap.add_argument("--windows", type=int, default=4,
                    help="edge-window count for --residency=stream")
    args = ap.parse_args()

    if args.graph:
        return _graph_main(args)
    if args.arch is None:
        ap.error("--arch is required unless --graph is given")

    cfg = (configs.smoke_config if args.smoke else configs.get_config)(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    params = M.init_params(jax.random.key(0), cfg)
    max_len = args.prompt_len + args.gen + 1
    server = BatchedServer(cfg, params, args.requests, max_len)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.requests, args.prompt_len),
                           dtype=np.int32)
    t0 = time.time()
    server.prefill(prompts)
    t_prefill = time.time() - t0
    t0 = time.time()
    toks = server.decode(args.gen)
    t_decode = time.time() - t0
    tps = args.requests * args.gen / t_decode
    print(f"[serve] {args.requests} reqs: prefill {t_prefill:.2f}s, "
          f"decode {args.gen} steps in {t_decode:.2f}s ({tps:.1f} tok/s)")
    print("[serve] sample output tokens:", toks[0, :10])


if __name__ == "__main__":
    main()

"""The two ways a mix drives the system under test, warm-up and window.

``JobsDriver`` runs analytics jobs back to back through ``Engine.run`` (or
``Engine.run_batch`` for a batched mix).  ``OpenLoopDriver`` sends queries
on the traffic's schedule to ``GraphQueryServer`` and times each from when
it was due to when its answer row is on the host.  Both record what the
metric readers need and keep the answers the check compares.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

import traffic as traffic_mod


@dataclasses.dataclass
class Job:
    start: float  # host clock, seconds since the window opened
    end: float
    supersteps: int


@dataclasses.dataclass
class Query:
    due: float  # seconds since the window opened
    source: int
    sent: float | None = None
    done: float | None = None
    supersteps: int | None = None


@dataclasses.dataclass
class Dispatch:
    start: float
    end: float
    admitted: int
    batch: int
    supersteps: int  # the batched loop runs to its slowest column
    server_s: float  # the server's own ``last_dispatch_s``
    waiting: int  # queries queued when the dispatch began


@dataclasses.dataclass
class Answer:
    """One answer the check compares: ``source`` is None for a job of an
    unbatched program, ``state`` the row in original vertex order."""

    source: object
    state: np.ndarray
    supersteps: int


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class JobsDriver:
    def __init__(self, engine, traffic: dict, pool, seed: int):
        self.engine, self.traffic = engine, traffic
        self.pool, self.seed = pool, seed
        self.program = traffic["program"]
        self.params = dict(traffic.get("params", {}))
        self.jobs: list[Job] = []
        self.answers: list[Answer] = []
        self.keep = int(traffic.get("check", {}).get("keep_answers", 4))

    def _one(self, index: int):
        sources = traffic_mod.job_sources(self.traffic, self.pool, self.seed,
                                          index)
        if sources is None:
            state, it = self.engine.run(self.program, **self.params)
            return [(None, state, int(it))], int(it)
        plane, iters = self.engine.run_batch(
            self.program, sources=[int(s) for s in sources],
            batch=int(self.traffic["batch"]), **self.params)
        rows = [(int(s), plane[i], int(iters[i]))
                for i, s in enumerate(sources)]
        return rows, int(iters.max())

    def warm_up(self):
        """One whole job: compiles, or loads from the cache, the only
        program the window runs.  The program has no entry that compiles
        without running, and this file may not reach into its internals."""
        self._one(-1)

    def window(self, seconds: float):
        """Whole jobs back to back, as many as fit in ``seconds``: the next
        one starts only while the longest so far would still end in time
        (always at least one)."""
        t0 = time.monotonic()
        index = 0
        last = []
        longest = 0.0
        while True:
            start = time.monotonic() - t0
            if index and start + longest > seconds:
                break
            with _annotate("bench.job"):
                rows, steps = self._one(index)
            end = time.monotonic() - t0
            self.jobs.append(Job(start, end, steps))
            longest = max(longest, end - start)
            # the first jobs' answers, and the last one's, are checked
            if index < self.keep:
                self.answers.extend(Answer(*r) for r in rows)
            else:
                last = [Answer(*r) for r in rows]
            index += 1
        self.answers.extend(last)

    def attempted(self) -> int:
        return len(self.jobs)

    def unanswered(self) -> int:
        return 0

    def columns(self) -> int:
        return int(self.traffic.get("batch", 1))


class OpenLoopDriver:
    def __init__(self, server, traffic: dict, pool, seed: int):
        self.server, self.traffic = server, traffic
        self.pool, self.seed = pool, seed
        self.program = traffic["program"]
        self.params = dict(traffic.get("params", {}))
        self.queries: list[Query] = []
        self.dispatches: list[Dispatch] = []
        self.answers: list[Answer] = []
        self.checked: set[int] = set()

    def warm_up(self):
        """One full dispatch at the server's width: its only shape."""
        rng = traffic_mod.rng_for(self.seed, "warmup")
        for s in rng.choice(self.pool, self.server.batch):
            self.server.submit(self.program, int(s), **self.params)
        for rid in self.server.step(force=True):
            self.server.result(rid)

    def window(self, seconds: float, grace_s: float = 60.0):
        sched = traffic_mod.open_loop(self.traffic, seconds, self.pool,
                                      self.seed)
        self.queries = [Query(float(d), int(s))
                        for d, s in zip(sched.due, sched.sources)]
        sample = int(self.traffic.get("check", {}).get("sample", 0))
        n = len(self.queries)
        rng = traffic_mod.rng_for(self.seed, "check")
        self.checked = (set(range(n)) if sample <= 0 or sample >= n else
                        set(rng.choice(n, sample, replace=False).tolist()))
        by_rid: dict[int, int] = {}
        i = 0
        t0 = time.monotonic()
        give_up = seconds + grace_s
        while True:
            now = time.monotonic() - t0
            while i < n and self.queries[i].due <= now:
                rid = self.server.submit(self.program,
                                         self.queries[i].source,
                                         **self.params)
                self.queries[i].sent = now
                by_rid[rid] = i
                i += 1
            waiting = self.server.pending()
            if waiting:
                start = time.monotonic() - t0
                with _annotate("bench.server.step"):
                    done = self.server.step()
                end = time.monotonic() - t0
                steps = 0
                for rid in done:
                    row, it = self.server.result(rid)
                    qi = by_rid.pop(rid)
                    q = self.queries[qi]
                    q.done, q.supersteps = end, int(it)
                    steps = max(steps, int(it))
                    if qi in self.checked:
                        self.answers.append(Answer(q.source, row, int(it)))
                self.dispatches.append(Dispatch(
                    start, end, len(done), self.server.batch, steps,
                    float(self.server.last_dispatch_s), waiting))
            elif i < n:
                with _annotate("bench.wait_arrival"):
                    time.sleep(max(self.queries[i].due - now, 0.0))
            else:
                break
            if now > give_up:
                break

    def attempted(self) -> int:
        return len(self.queries)

    def unanswered(self) -> int:
        return sum(q.done is None for q in self.queries)

    def columns(self) -> int:
        return int(self.server.batch)

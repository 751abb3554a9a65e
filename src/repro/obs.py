"""Host spans of the program, on the profiler's clock.

``span(name, **attrs)`` is a context manager that opens a
``jax.profiler.TraceAnnotation`` (so the interval lands in a profiler trace,
on the calling thread's host line, on the clock of the device events) and on
exit appends one ``Record`` to a bounded in-process ring, so the same
interval can be read back without a profiler.  ``record`` adds an interval
that cannot be a nested annotation: a query's wait in the server's queue
overlaps the dispatches run while it waits.  ``records(name)`` reads the
ring, oldest first.

The clock is the profiler's own: TraceMe stamps host events with the wall
clock (CLOCK_REALTIME), which ``time.time_ns`` reads; an event of a trace
lies at the trace's ``profile_start_time`` plus its offset.

A span costs one TraceMe, inert while no profiler trace runs, and one
deque append.  Spans sit at host boundaries only: never inside jitted code,
never per vertex, edge or superstep of a whole-loop program.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax

RING_SIZE = 1 << 16
clock_ns = time.time_ns


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int | None  # the span open around it on its thread
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span("engine.loop", program="bfs") as s: ...``; after the
    block ``s.seconds`` is its duration and ``s.end_ns`` its end."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "start_ns",
                 "end_ns", "_annotation")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = _stack()
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(_ids)
        stack.append(self.span_id)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.attrs)
        self._annotation.__enter__()
        self.start_ns = clock_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = clock_ns()
        self._annotation.__exit__(*exc)
        _stack().pop()
        _ring.append(Record(self.name, self.start_ns, self.end_ns,
                            self.span_id, self.parent_id, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def record(name: str, start_ns: int, end_ns: int, **attrs) -> Record:
    """Add an interval measured elsewhere on ``clock_ns``; it nests under
    no span."""
    rec = Record(name, int(start_ns), int(end_ns), next(_ids), None, attrs)
    _ring.append(rec)
    return rec


def records(name: str) -> list[Record]:
    """The ring's records of ``name``, oldest first."""
    return [r for r in _ring if r.name == name]


def reset() -> None:
    _ring.clear()

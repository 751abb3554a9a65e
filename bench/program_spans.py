"""The program's own host spans (``repro.obs``), as the metric readers take
them.

The window's records of a name are its last N, N the jobs, dispatches or
answered queries the benchmark counted in the window: set-up and the
single warm-up job or dispatch come first, and nothing of the program runs
between the window and the readers.  Each function returns None where the
program has no ``repro.obs`` (a checkout from before it) or fewer records
than the window needs.
"""

from __future__ import annotations


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def records(name: str):
    """Every record of ``name`` in the ring, oldest first, or None."""
    obs = _obs()
    return None if obs is None else obs.records(name)


def last(name: str, n: int):
    """The window's ``n`` records of ``name``, or None."""
    recs = records(name)
    if recs is None or n <= 0 or len(recs) < n:
        return None
    return recs[-n:]


def host_ms(name: str, n: int):
    """Mean, over the window's ``n`` spans of ``name``, of each span's
    duration less the ``engine.loop`` spans inside it (the jitted loop up
    to the sync that ends it), in ms: the host's part of each."""
    spans = last(name, n)
    if spans is None:
        return None
    loops = records("engine.loop")
    total = 0
    for s in spans:
        inner = sum(lp.end_ns - lp.start_ns for lp in loops
                    if s.start_ns <= lp.start_ns and lp.end_ns <= s.end_ns)
        total += s.end_ns - s.start_ns - inner
    return total / n / 1e6

"""reduce_ms.grid: the phase-2 reduce's device time per superstep, in ms:
per chip, the self time of the collective operations in the traced window
(the ops whose opcode is ``all-reduce``, ``reduce-scatter``,
``all-gather``, ``collective-permute`` or ``all-to-all``, or the
``-start``/``-done`` halves of one), over the supersteps of the window's
jobs.  A grid placement's only collectives are its phase-2 reduces: the
column-group reduce of the ``Kc`` slice and the row-group reduce of the
row state, which a v5e trace shows as two plain ``all-reduce`` ops
(``psum.N``), one per group reduce.  None without a trace, or where no op
is a collective."""

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
OPCODES = {c + half for c in COLLECTIVES for half in ("", "-start", "-done")}


def is_collective(label: str) -> bool:
    """``label`` is ``bench/xplane.py``'s ``name opcode [kind]``."""
    parts = label.split()
    return len(parts) > 1 and parts[1] in OPCODES


def read(ctx):
    steps = sum(j.supersteps for j in ctx.jobs)
    if ctx.trace is None or not steps:
        return None
    times = [s for label, s in ctx.trace.op_s.items() if is_collective(label)]
    if not times:
        return None
    return 1000.0 * sum(times) / steps

"""prep_s: host prep inside set-up (host clock): graph generation,
``from_edges``, ``partition`` and ``Engine(...)``."""

PHASES = ("generate", "from_edges", "partition", "engine")


def read(ctx):
    if not all(p in ctx.spans for p in PHASES):
        return None
    return sum(ctx.spans[p] for p in PHASES)

"""layout_s: the edge layout builds inside set-up: the sum of the
program's ``graph.layout`` spans (each a layout ``partition`` or the engine
built on demand: radix sort, pack and band table)."""

import program_spans


def read(ctx):
    spans = program_spans.records("graph.layout")
    if not spans:
        return None
    return sum(s.seconds for s in spans)

"""engine_host_ms.serve: the host's part of a dispatch: the mean, over the
window's ``server.dispatch`` spans (one per dispatch in ``ctx.dispatches``),
of each span's duration less the ``engine.loop`` span inside it (program
init and upload, result copy, un-permute)."""

import program_spans


def read(ctx):
    return program_spans.host_ms("server.dispatch", len(ctx.dispatches))

"""engine_host_ms.job: the host's part of a job: the mean, over the
window's ``engine.run`` spans (one per job in ``ctx.jobs``), of each
span's duration less the ``engine.loop`` span inside it (program init and
upload, state copy, un-permute)."""

import program_spans


def read(ctx):
    return program_spans.host_ms("engine.run", len(ctx.jobs))

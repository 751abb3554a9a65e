"""setup_s: process start to the window's first timed operation (host
clock): imports, graph generation, host prep, engine build and warm-up,
compilation included."""


def read(ctx):
    return ctx.setup_s

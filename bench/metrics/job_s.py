"""job_s: the window's job time over the whole jobs in it (host clock).

Jobs run back to back, as many whole ones as fit in the window's seconds
(``JobsDriver.window``), so this is the mean time to one job's answer.
"""


def read(ctx):
    if not ctx.jobs:
        return None
    return sum(j.end - j.start for j in ctx.jobs) / len(ctx.jobs)

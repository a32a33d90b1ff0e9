"""Host time per generated token: the mean wall length of the engine's
host spans ``engine.decode_step``, each of which issues one decode step
and samples its token. None where the trace holds no such span (a
program without the engine's spans)."""

import statistics

SPAN = "engine.decode_step"


def read(ctx):
    steps = [e.end - e.start for e in ctx.trace.host if e.name == SPAN]
    return 1e3 * statistics.fmean(steps) if steps else None

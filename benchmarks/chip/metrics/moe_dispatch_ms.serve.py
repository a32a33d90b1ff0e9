"""Device time under ``moe_dispatch`` (the sort of each layer's
token-expert pairs, the gather of their rows and the scatter back) per
decode step: summed over the decode programs the trace holds (programs
that run the decode attention), over their number. None where no decode
program ran an operation under the scope."""

import traced

SCOPE = "moe_dispatch"


def read(ctx):
    runs = ctx.trace.runs_with_scope(traced.DECODE)
    measured = traced.scope_time_in(ctx.trace, SCOPE, runs)
    return 1e3 * measured / len(runs) if measured else None

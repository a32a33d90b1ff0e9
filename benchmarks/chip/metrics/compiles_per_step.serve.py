"""Programs compiled or fetched from the compile cache inside the window
(JAX's backend-compile events), per decode step. About one under eager
decode, which builds a new scan body on every step; 0 once decode is
one compiled program."""


def read(ctx):
    steps = ctx.run.work["decode_steps"]
    return ctx.compiles / steps if steps else None

"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the
window."""


def read(ctx):
    return 100 * (1 - ctx.trace.busy_s / ctx.trace.window_s)

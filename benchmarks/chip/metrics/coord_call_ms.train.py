"""Mean host time of a ClusterRegistry call made inside the window, as
the proxy registry that run_training is given timed it (two Raft appends
per step: the step report and the heartbeat)."""


def read(ctx):
    calls = ctx.run.work["coord_call_s"]
    return 1e3 * sum(calls) / len(calls) if calls else None

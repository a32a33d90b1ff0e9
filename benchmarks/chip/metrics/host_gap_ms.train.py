"""Mean device-idle gap between consecutive executions of the training
step's program (the program that takes most of the window's device
time): from the end of one step on the device to the start of the next.
The host's work between steps (the loss read back, two Raft appends, the
next batch made and copied) sits in it."""

import statistics


def read(ctx):
    runs = ctx.trace.busiest_program_runs()
    gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return 1e3 * statistics.fmean(gaps) if gaps else None

"""Time to first token: for each batch, from the start of the engine's
host span ``engine.generate`` to the end of the last device program that
ended before the batch's first decode program (the first program after
the span's start that runs the decode attention), averaged over the
batches. That program closes the batch's prefill, cache growth and first
sample. A host start is compared with a device end, which holds because
host and device share the trace's clock. None where the trace holds no
``engine.generate`` span (a program without the engine's spans)."""

import statistics

DECODE = "vmemkernel_decode_attention"
SPAN = "engine.generate"


def read(ctx):
    tr = ctx.trace
    decodes = [s for s, _ in tr.runs_with_scope(DECODE)]
    ends = [p.end for p in tr.devices[0]["programs"]]
    waits = []
    for start in sorted(e.start for e in tr.host if e.name == SPAN):
        first = min((d for d in decodes if d > start), default=None)
        if first is None:
            continue
        last = max((e for e in ends if e < first), default=None)
        if last is not None and last > start:
            waits.append(last - start)
    return 1e3 * statistics.fmean(waits) if waits else None

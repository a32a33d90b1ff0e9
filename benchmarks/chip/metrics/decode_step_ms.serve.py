"""Mean interval between the starts of consecutive decode programs on
the device (programs that run the decode attention), leaving out each
interval in which a prefill program (one that runs the prefill
attention) starts, so the time between batches does not count."""

import statistics

DECODE = "vmemkernel_decode_attention"
PREFILL = "vmemkernel_flash_attention"


def read(ctx):
    decodes = [s for s, _ in ctx.trace.runs_with_scope(DECODE)]
    prefills = [s for s, _ in ctx.trace.runs_with_scope(PREFILL)]
    steps = [b - a for a, b in zip(decodes, decodes[1:])
             if not any(a < p < b for p in prefills)]
    return 1e3 * statistics.fmean(steps) if steps else None

"""The least time the chip needs for the latent attention of the prefills
the trace holds, over the device time of the operations under the prefill
attention's scope in those batches.

Each batch's prefill attends, in every layer, every causal pair of each
prompt with keys and values expanded per head from the latent
(``work_mla_moe.mla_prefill_attention_work``). Only batches whose prefill
program the trace holds count, on both sides. None where there is none,
or no such scope.
"""

import traced
import work
import work_mla_moe


def read(ctx):
    w, cfg = ctx.run.work, ctx.config
    batches = traced.held_batches(ctx.trace)
    measured = traced.scope_time_in(ctx.trace, traced.PREFILL,
                                    [(a, b) for _, a, b in batches])
    if not measured:
        return None
    flops, nbytes = work_mla_moe.mla_prefill_attention_work(
        cfg, w["batch"], w["prompt_len"])
    least = work.least_time_s(flops, nbytes, ctx.peaks)[0]
    return 100 * least * cfg["n_layers"] * len(batches) / measured

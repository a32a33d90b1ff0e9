"""The least time the chip needs for the window's prefill attention, over
the device time of the operations under the prefill attention's scope.

Each batch's prefill attends, in every layer, the causal pairs inside
the sliding window (the masked half is not work), reading q, K and V
once and writing the output; at 256-token prompts that is bound by
bytes. A kernel that replaces this code keeps the scope name
``vmemkernel_flash_attention`` and is read against the same work.
"""

import work

SCOPE = "vmemkernel_flash_attention"


def read(ctx):
    measured = ctx.trace.scope_time_s(SCOPE)
    if not measured:
        return None
    w, cfg = ctx.run.work, ctx.config
    flops, nbytes = work.prefill_attention_work(cfg, w["batch"],
                                                w["prompt_len"])
    least = work.least_time_s(flops, nbytes, ctx.peaks)[0]
    return 100 * least * cfg["n_layers"] * w["batches"] / measured

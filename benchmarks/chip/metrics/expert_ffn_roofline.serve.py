"""The least time the chip needs for the held experts' grouped products
in the batches the trace holds whole (prefill and every decode step),
over the device time of the operations under ``moe_experts`` there,
the grouped-product kernels included (on the TPU their metadata names
only the kernel, ``ragged-dot-*``, and has lost the scope).

The work is what the engine counted for those batches: token-expert
pairs (FLOPs) and the weights of each held expert that had tokens in a
layer's call (bytes), prefill and decode each at its own bound (prefill
is bound by FLOPs, a decode step by bytes). None where no batch is held
whole or nothing ran under the scope (a program without it).
"""

import traced
import work
import work_mla_moe

SCOPE = "moe_experts"


def read(ctx):
    w, cfg = ctx.run.work, ctx.config
    if "expert_pairs" not in w:
        return None
    batches = traced.held_batches(ctx.trace, w["new_tokens"] - 1)
    measured = traced.scope_time_in(ctx.trace, SCOPE,
                                    [(a, b) for _, a, b in batches],
                                    kernels=(traced.GROUPED_PRODUCT,))
    if not measured:
        return None
    least = 0.0
    for i, _, _ in batches:
        for pairs, loads in zip(w["expert_pairs"][i], w["expert_loads"][i]):
            flops, nbytes = work_mla_moe.expert_work(cfg, pairs, loads)
            least += work.least_time_s(flops, nbytes, ctx.peaks)[0]
    return 100 * least / measured

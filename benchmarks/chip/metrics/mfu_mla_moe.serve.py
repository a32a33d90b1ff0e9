"""The model FLOPs the window's batches needed (``work_mla_moe``: latent
attention, the dense layer, the router and shared experts on every
position, the held experts' token-expert pairs as the engine counted
them, the head where a token is chosen), over the window's host-clock
length, as a share of the chip's bf16 peak. None without the engine's
counts."""

import work_mla_moe


def read(ctx):
    w = ctx.run.work
    if "expert_pairs" not in w:
        return None
    flops = sum(work_mla_moe.serve_batch_flops(
        ctx.config, w["batch"], w["prompt_len"], w["new_tokens"], sum(pairs))
        for pairs in w["expert_pairs"])
    return 100 * flops / w["generate_s"] / ctx.peaks.bf16_flops_per_s

"""The model FLOPs the window's batches needed (prefill over every prompt
position, one position per decode step, the head where a token is
chosen), over the window's host-clock length, as a share of the chip's
bf16 peak."""

import work


def read(ctx):
    w = ctx.run.work
    flops = w["batches"] * work.serve_batch_flops(
        ctx.config, w["batch"], w["prompt_len"], w["new_tokens"])
    return 100 * flops / w["generate_s"] / ctx.peaks.bf16_flops_per_s

"""Forward and backward FLOPs per trained token (recomputation not
counted) times the window's train_tokens_per_s, as a share of the chip's
bf16 peak."""

import work


def read(ctx):
    w = ctx.run.work
    rate = w["tokens"] / ctx.window_s
    flops = work.train_flops_per_token(ctx.config, w["seq_len"]) * rate
    return 100 * flops / ctx.peaks.bf16_flops_per_s

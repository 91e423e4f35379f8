"""Model FLOPs per training token (``counts.lm_flops_per_token``) times
the traced window's tokens per second, over the chips' bf16 peak."""


def read(ctx):
    w = ctx.trace.window[1] - ctx.trace.window[0]
    tokens = ctx.cell.work["tokens"] * ctx.window.rounds
    flops = ctx.cell.counts["flops_per_token"] * tokens
    return 100.0 * flops / (w * len(ctx.device_ids)
                            * ctx.peaks["bf16_flops"])

"""Model FLOPs done in the traced window over the window times the chip's
bf16 peak: prompts prefilled (every token's layer work, causal attention,
the head once) and tokens decoded (layer work and head, attention over the
live length)."""

import flops


def read(ctx):
    work = sum(flops.prefill_flops(ctx.conf, n) for _, _, n in ctx.admits)
    for _, _, lengths, _ in ctx.steps:
        work += sum(flops.token_flops(ctx.conf) + flops.attn_flops(ctx.conf, n)
                    for n in lengths)
    w0, w1 = ctx.trace_window
    if work <= 0:
        return None
    return 100.0 * work / ((w1 - w0) / 1e9 * ctx.peaks["bf16_flops_per_s"])

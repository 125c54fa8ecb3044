"""Share of its roofline the decode attention reaches: the least time the
chip needs for the attention work of the traced steps (per step the larger
of bytes / HBM bandwidth and FLOPs / peak, counted from the live lengths,
whatever implements it) over the megakernel's device time."""

import flops
import trace_reduce


def read(ctx):
    ns = trace_reduce.megakernel_ns(ctx.trace, *ctx.trace_window)
    if not ctx.steps or ns <= 0:
        return None
    least = 0.0
    for _, _, lengths, _ in ctx.steps:
        by = sum(flops.decode_attn_bytes(ctx.conf, n) for n in lengths)
        fl = sum(flops.attn_flops(ctx.conf, n) for n in lengths)
        least += max(by / ctx.peaks["hbm_bytes_per_s"], fl / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)

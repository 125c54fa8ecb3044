"""Device-busy time outside the WS megakernel (projections, cache
transposes and splices, prefill) per engine step, from the profiler trace."""

import trace_reduce


def read(ctx):
    if not ctx.steps:
        return None
    w = ctx.trace_window
    ns = trace_reduce.busy_ns(ctx.trace, *w) - trace_reduce.megakernel_ns(ctx.trace, *w)
    return ns / 1e6 / len(ctx.steps)

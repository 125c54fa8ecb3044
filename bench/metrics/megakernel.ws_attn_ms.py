"""Device time of the WS megakernel's launches per engine step, from the
profiler trace."""

import trace_reduce


def read(ctx):
    if not ctx.steps:
        return None
    ns = trace_reduce.megakernel_ns(ctx.trace, *ctx.trace_window)
    return ns / 1e6 / len(ctx.steps) if ns > 0 else None

"""Share of the traced window in which no operation ran on the device:
1 - (union of busy intervals) / window."""

import trace_reduce


def read(ctx):
    w0, w1 = ctx.trace_window
    busy = trace_reduce.busy_ns(ctx.trace, w0, w1)
    return 1.0 - busy / (w1 - w0) if busy > 0 else None

"""Engine step wall time (``SchedulerMetrics.step_latency_s``) summed over
the steps in the window, divided by those steps."""


def read(ctx):
    lat = [s[3] * 1e3 for s in ctx.window_steps]
    return sum(lat) / len(lat) if lat else None

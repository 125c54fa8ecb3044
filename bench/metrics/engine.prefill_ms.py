"""Mean wall time of ``ContinuousBatcher.admit`` (jitted batch-1 prefill,
cache splice, first-token sync) over the admissions in the window (host
clock, spans around ``admit``)."""


def read(ctx):
    ds = [(t1 - t0) * 1e3 for t0, t1, _ in ctx.window_admits]
    return sum(ds) / len(ds) if ds else None

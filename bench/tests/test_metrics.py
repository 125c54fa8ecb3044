"""Each per-layer reader, on the trace recorded on one v5e (three decode
steps of mistral-7b-v0.3-l16 at 4 slots, then one admission): it is found
by name, reads what the trace and the host record hold, and returns
nothing where there is nothing to read."""

import gzip
import os
from types import SimpleNamespace

import pytest

import peaks
import registry
import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_mistral7b_l16_3steps.json.gz")


@pytest.fixture(scope="module")
def ctx():
    with gzip.open(DATA, "rt") as fh:
        tr = T.Trace.from_json(fh.read())
    steps = [(s / 1e9, e / 1e9) for s, e, n in tr.host if n == "bench.step"]
    admits = [(s / 1e9, e / 1e9) for s, e, n in tr.host if n == "bench.admit"]
    w1 = max(e for _, e, _ in tr.host)
    lengths = [1550, 142, 270, 270]  # the probe's slots when it was traced
    return SimpleNamespace(
        conf=registry.load_config("mistral-7b-v0.3-l16"), peaks=peaks.peaks("TPU v5 lite"),
        trace=tr, trace_window=(0, w1),
        steps=[(a, b, [n + i for n in lengths], b - a) for i, (a, b) in enumerate(steps)],
        admits=[(a, b, 128) for a, b in admits],
        window_steps=[(a, b, [n + i for n in lengths], b - a) for i, (a, b) in enumerate(steps)],
        window_admits=[(a, b, 128) for a, b in admits])


def _read(name, ctx):
    return registry.load_metric_reader(name)(ctx)


def test_every_reader_reads_the_recorded_trace(ctx):
    vals = {m["name"]: _read(m["name"], ctx) for m in registry.load_benchmark()["per_layer"]}
    assert all(v is not None for v in vals.values()), vals
    assert vals["megakernel.ws_attn_ms"] == pytest.approx(74.704737 / 3)
    assert 0 < vals["megakernel.ws_attn_roofline"] < 100
    assert 0 < vals["step.mfu"] < 100
    assert 0 <= vals["device.idle_frac"] < 1
    busy_per_step = T.busy_ns(ctx.trace, *ctx.trace_window) / 1e6 / 3
    assert vals["device.xla_ms"] + vals["megakernel.ws_attn_ms"] == pytest.approx(busy_per_step)
    assert vals["engine.step_ms"] > vals["megakernel.ws_attn_ms"]


def test_roofline_counts_live_work_not_capacity(ctx):
    short = SimpleNamespace(**vars(ctx))
    short.steps = [(a, b, [n // 2 for n in ls], lat) for a, b, ls, lat in ctx.steps]
    full = _read("megakernel.ws_attn_roofline", ctx)
    half = _read("megakernel.ws_attn_roofline", short)
    assert half < full  # the same kernel time over less live work


def test_readers_return_nothing_without_work(ctx):
    empty = SimpleNamespace(**vars(ctx))
    empty.steps, empty.admits = [], []
    empty.window_steps, empty.window_admits = [], []
    empty.trace = T.Trace(ops={"0": []}, modules={"0": []}, host=[])
    for m in registry.load_benchmark()["per_layer"]:
        assert _read(m["name"], empty) is None, m["name"]

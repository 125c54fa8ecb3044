"""Trace reduction and peaks, on a trace recorded on one v5e (three WS
decode steps and one admission of mistral-7b-v0.3-l16 at 4 slots of 2048,
reduced to the benchmark's plain form) and on small made-up traces."""

import gzip
import os

import pytest

import peaks
import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_mistral7b_l16_3steps.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as fh:
        return T.Trace.from_json(fh.read())


def test_recorded_trace_busy_kernel_and_gaps(recorded):
    w1 = max(e for _, e, _ in recorded.host)
    busy = T.busy_ns(recorded, 0, w1)
    mk = T.megakernel_ns(recorded, 0, w1)
    # values read off this trace when it was recorded
    assert busy == pytest.approx(140_861_427.0)
    assert mk == pytest.approx(74_704_737.0)
    # 16 layers x 3 steps: one megakernel launch per layer per step
    assert sum(1 for op in recorded.ops["0"] if op[3]) == 48
    assert mk < busy < w1
    gaps = T.idle_gaps(recorded, 0, w1, 3)
    assert [g[0] for g in gaps] == ["bench.step"] * 3
    assert gaps[0][1] == pytest.approx(0.00304928)
    top = T.top_ops(recorded, 0, w1, 3)
    assert top[0] == ["ws_megakernel", pytest.approx(0.074704737)]


def _made_up():
    return T.Trace(
        ops={"0": [(0, 10, "fusion f32[4]", False), (5, 20, "tpu_custom_call", True),
                   (30, 40, "copy bf16[2]", False), (45, 50, "tpu_custom_call", False)]},
        modules={"0": [(0, 25, "jit_ws_decode_step(1)"), (30, 50, "jit_prefill_step(2)")]},
        host=[(0, 100, "bench.window"), (20, 32, "bench.step"), (24, 28, "bench.admit"),
              (50, 100, "bench.idle")])


def test_union_merges_overlaps():
    assert T.union([(5, 20), (0, 10), (30, 40), (40, 41)]) == [(0, 20), (30, 41)]


def test_busy_is_the_union_clipped_to_the_window():
    tr = _made_up()
    assert T.busy_ns(tr, 0, 100) == 20 + 10 + 5
    assert T.busy_ns(tr, 15, 35) == 5 + 5


def test_megakernel_only_in_decode_programs():
    assert T.megakernel_ns(_made_up(), 0, 100) == 15


def test_idle_gaps_named_by_innermost_host_span():
    gaps = T.idle_gaps(_made_up(), 0, 100)
    assert gaps == [["bench.idle", 50e-9], ["bench.admit", 10e-9], ["host", 5e-9]]


def test_window_span():
    assert T.window(_made_up()) == (0, 100)
    with pytest.raises(ValueError):
        T.window(T.Trace())


def test_json_round_trip():
    tr = _made_up()
    back = T.Trace.from_json(tr.to_json())
    assert back.ops == tr.ops and back.host == tr.host and back.modules == tr.modules


def test_short_names():
    assert T.short_name("%fusion.143 = bf16[128,14336]{1,0:T(8,128)} fusion(x)") == \
        "fusion bf16[128,14336]"
    assert T.short_name('%k.1 = (s32[8]) custom-call(a), custom_call_target="tpu_custom_call"') \
        == "tpu_custom_call"


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")

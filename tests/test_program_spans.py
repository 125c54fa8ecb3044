"""The serving path's profiler spans and device scopes
(``repro.wstrace.spans``), and the benchmark's reduction of them
(``bench/program_trace.py``).

On the CPU: a tiny ``ContinuousBatcher`` behind the frontend, run under
``jax.profiler.trace``, must leave its span tree in the trace; a forced
collection leaves a ``host.gc`` span; the lowered WS decode step puts every
operation under one of the four ``ws_decode/*`` scopes.  The reduction is
checked on made-up traces and on a trace recorded on one v5e (three WS
decode steps and one admission of mistral-7b-v0.3-l16 at 4 slots of 2048,
with the program's spans and each operation's scope).
"""

from __future__ import annotations

import gc
import glob
import gzip
import os
import re
import sys
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
sys.path.append(BENCH)

import peaks  # noqa: E402
import program_trace as P  # noqa: E402
import registry  # noqa: E402
import trace_reduce as T  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    ContinuousBatcher,
    Request,
    WorkStealingFrontend,
    jit_decode_step_ws,
)
from repro.wstrace import spans  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
OLD = os.path.join(DATA, "v5e_mistral7b_l16_3steps.json.gz")
NEW = os.path.join(DATA, "v5e_mistral7b_l16_spans.json.gz")
STEP_CHILDREN = [spans.STEP_INPUTS, spans.STEP_DISPATCH, spans.STEP_SYNC,
                 spans.STEP_SAMPLE, spans.STEP_COMMIT]
ADMIT_CHILDREN = [spans.ADMIT_PREFILL, spans.ADMIT_SPLICE, spans.ADMIT_FIRST_TOKEN]


# -- the program's spans, on the CPU ------------------------------------------

@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """One admission and two engine steps of a tiny batcher behind the
    frontend, then one forced collection, under the profiler."""
    cfg = get_config("llama3.2-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    fe = WorkStealingFrontend(
        lambda: ContinuousBatcher(params, cfg, slots=2, capacity=16, jit_ws=True),
        n_replicas=1)
    prompt = np.array([5, 6, 7], np.int32)
    fe.submit(0, Request(1, prompt, max_new=3))  # compiles every program
    fe.run()
    d = str(tmp_path_factory.mktemp("profile"))
    fe.submit(0, Request(7, prompt, max_new=3))
    gc.disable()
    try:
        with jax.profiler.trace(d):
            fe.run_iteration()
            fe.run_iteration()
            gc.collect()
    finally:
        gc.enable()
    return P.load(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0])


def _named(tr, name):
    return [s for s in tr.spans if s[2] == name]


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_admit_span_carries_the_request_and_its_three_parts(cpu_profile):
    (admit,) = _named(cpu_profile, spans.ENGINE_ADMIT)
    assert admit[3] == {"rid": 7, "slot": 0, "prompt_len": 3}
    parts = [s for s in cpu_profile.spans if s[2] in ADMIT_CHILDREN]
    assert [s[2] for s in parts] == ADMIT_CHILDREN
    assert all(_inside(s, admit) for s in parts)


def test_step_span_holds_its_five_children_in_order(cpu_profile):
    steps = _named(cpu_profile, spans.ENGINE_STEP)
    assert len(steps) == 2
    assert [s[3]["live"] for s in steps] == [1, 1]
    assert steps[1][3]["step"] == steps[0][3]["step"] + 1
    for st in steps:
        kids = [s for s in cpu_profile.spans if s[2] in STEP_CHILDREN and _inside(s, st)]
        assert [s[2] for s in kids] == STEP_CHILDREN
        assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def test_frontend_iteration_encloses_admit_and_steps(cpu_profile):
    its = _named(cpu_profile, spans.FRONTEND_ITERATION)
    assert len(its) == 2
    for name in (spans.ENGINE_ADMIT, spans.ENGINE_STEP):
        assert all(any(_inside(s, it) for it in its) for s in _named(cpu_profile, name))


def test_gc_span_around_a_forced_collection(cpu_profile):
    gcs = _named(cpu_profile, spans.HOST_GC)
    assert [s[3] for s in gcs] == [{"generation": 2}]
    assert gcs[0][0] >= max(s[1] for s in _named(cpu_profile, spans.FRONTEND_ITERATION))


def test_gc_hook_installs_once():
    spans.install_gc_spans()
    spans.install_gc_spans()
    assert sum(cb is spans._on_gc for cb in gc.callbacks) == 1


def test_host_ms_reads_the_cpu_profile(cpu_profile):
    t0 = min(s[0] for s in cpu_profile.spans)
    t1 = max(s[1] for s in cpu_profile.spans)
    ms = P.host_ms(cpu_profile, t0, t1)
    step_ms = sum(s[1] - s[0] for s in _named(cpu_profile, spans.ENGINE_STEP)) / 2e6
    assert 0 < ms
    assert P.host_ms(cpu_profile, t1, t1 + 1) is None
    # the host part of a step is the step less its wait, plus the frontend's own time
    sync_ms = sum(s[1] - s[0] for s in _named(cpu_profile, spans.STEP_SYNC)) / 2e6
    assert ms >= step_ms - sync_ms


# -- the decode step's scopes -------------------------------------------------

def test_lowered_decode_step_puts_every_op_in_one_scope():
    cfg = get_config("llama3.2-3b", smoke=True)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    from repro.models import init_caches

    caches = jax.eval_shape(lambda: init_caches(cfg, 2, 32))
    i32 = jax.numpy.int32
    text = jit_decode_step_ws(cfg).lower(
        params, caches, jax.ShapeDtypeStruct((2, 1), i32), jax.ShapeDtypeStruct((2,), i32),
    ).as_text(debug_info=True)
    for scope in spans.DECODE_SCOPES:
        assert f"/{scope}/" in text, scope
    # locations of the step's own operations (a private function's body,
    # such as jnp.where's, is named from its own root and called in a scope)
    locs = re.findall(r'loc\("(jit\(ws_decode_step\)/[^"]*)"', text)
    assert locs
    outside = [n for n in locs if not re.match(
        r"jit\(ws_decode_step\)/ws_decode/(dense|kv_layout|ws_put|ws_kernel)/", n)]
    assert outside == []
    # the megakernel's pallas_call carries the family's name
    assert re.search(r"ws_decode/ws_kernel/ws_decode", text)


# -- the reduction, on made-up traces -----------------------------------------

def _made_up():
    return P.ProgramTrace(
        ops={"0": [(0, 10, "fusion f32[4]", False), (12, 20, "tpu_custom_call", True),
                   (40, 50, "copy bf16[2]", False), (60, 70, "fusion bf16[4]", False)]},
        modules={"0": [(0, 22, "jit_ws_decode_step(1)"), (40, 70, "jit_ws_decode_step(1)")]},
        host=[(0, 100, "bench.window"), (0, 38, "bench.step"), (38, 75, "bench.step")],
        spans=[(0, 38, "engine.step", {"step": 0, "live": 2}),
               (0, 2, "engine.step.inputs", {}), (2, 4, "engine.step.dispatch", {}),
               (4, 24, "engine.step.sync", {}), (24, 34, "engine.step.sample", {}),
               (34, 38, "engine.step.commit", {}),
               (38, 75, "engine.step", {"step": 1, "live": 2}),
               (38, 39, "engine.step.inputs", {}), (39, 40, "engine.step.dispatch", {}),
               (40, 72, "engine.step.sync", {}), (72, 73, "engine.step.sample", {}),
               (73, 75, "engine.step.commit", {}),
               (80, 95, "host.gc", {"generation": 0})],
        scopes={"0": ["ws_decode/dense", "ws_decode/ws_kernel", "ws_decode/kv_layout", ""]},
        inherited={"0": [False, False, True, False]})


def test_idle_gap_named_by_the_innermost_program_span():
    assert P.idle_gaps(_made_up(), 0, 100) == [
        ["host.gc", 30e-9], ["engine.step.sample", 20e-9],
        ["engine.step.sync", 10e-9], ["engine.step.sync", 2e-9]]


def test_idle_gaps_without_program_spans_read_as_before():
    tr = _made_up()
    plain = T.Trace(tr.ops, tr.modules, tr.host)
    assert P.idle_gaps(P.ProgramTrace(tr.ops, tr.modules, tr.host), 0, 100) == \
        T.idle_gaps(plain, 0, 100)


def test_idle_time_charged_to_the_innermost_open_span():
    ms = {k: round(v * 1e6) for k, v in P.idle_by_span(_made_up(), 0, 100).items()}
    assert ms == {"engine.step.sync": 18, "host.gc": 15, "engine.step.sample": 11,
                  "host": 10, "engine.step.commit": 6, "engine.step.inputs": 1,
                  "engine.step.dispatch": 1}


def test_scope_readings_per_decode_program():
    tr = _made_up()
    ns = P.decode_scope_ns(tr, 0, 100)
    assert ns["ws_decode/dense"] == 5 and ns["ws_decode/ws_kernel"] == 4
    assert ns["ws_decode/kv_layout"] == 5 and ns["inherited"] == 5 and ns[""] == 5
    assert ns["program"] == (10 + 8 + 10 + 10) / 2
    assert P.scope_ms(tr, 0, 100, "ws_decode/ws_put") == 0.0
    unscoped = P.ProgramTrace(tr.ops, tr.modules, tr.host)
    assert P.scope_ms(unscoped, 0, 100, "ws_decode/dense") is None


def test_host_ms_is_step_less_wait_plus_frontend_self_time():
    tr = _made_up()
    # steps 38 - 20 and 37 - 32, no frontend.iteration: (18 + 5) / 2 ns per step
    assert P.host_ms(tr, 0, 100) == pytest.approx(11.5e-6)
    tr.spans.append((0, 80, "frontend.iteration", {}))
    assert P.host_ms(tr, 0, 100) == pytest.approx((23 + 80 - 75) / 2 / 1e6)


def test_hlo_scopes_own_and_inherited():
    hlo = "\n".join([
        "ENTRY %main (p: bf16[4]) -> bf16[4] {",
        '  %p = bf16[4]{0} parameter(0), metadata={op_name="c"}',
        "  %copy.1 = bf16[4]{0} copy(%p)",
        '  %fusion.2 = bf16[4]{0} fusion(%copy.1), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(s)/ws_decode/kv_layout/dynamic_update_slice"}',
        '  ROOT %dot.3 = bf16[4]{0} dot(%fusion.2, %p), '
        'metadata={op_name="jit(s)/ws_decode/dense/dot_general"}',
        "}"])
    got = P.hlo_scopes(hlo)
    assert got["fusion.2"] == ("ws_decode/kv_layout", False)
    assert got["dot.3"] == ("ws_decode/dense", False)
    assert got["copy.1"] == ("ws_decode/kv_layout", True)
    assert got["p"] == ("ws_decode/kv_layout", True)


def test_program_trace_json_round_trip_and_old_fixture():
    tr = _made_up()
    back = P.ProgramTrace.from_json(tr.to_json())
    assert back == tr
    with gzip.open(OLD, "rt") as fh:
        text = fh.read()
    old, plain = P.ProgramTrace.from_json(text), T.Trace.from_json(text)
    assert (old.ops, old.modules, old.host) == (plain.ops, plain.modules, plain.host)
    assert old.spans == [] and old.scopes == {}


# -- the recorded chip traces -------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(NEW, "rt") as fh:
        tr = P.ProgramTrace.from_json(fh.read())
    return tr, T.window(tr)


def test_recorded_fixture_is_three_steps_and_one_admission(recorded):
    tr, (w0, w1) = recorded
    assert len(_named(tr, spans.ENGINE_STEP)) == 3
    assert len(_named(tr, spans.ENGINE_ADMIT)) == 1
    assert len(P._decode_programs(tr, "0", w0, w1)) == 3


# values read off the recorded trace when it was made
RECORDED_MS = {"ws_decode/dense": 12.861954, "ws_decode/kv_layout": 4.429739333333333,
               "ws_decode/ws_put": 0.025440666666666667}


@pytest.mark.parametrize("scope", sorted(RECORDED_MS))
def test_new_scope_readings_read_the_recorded_trace(recorded, scope):
    tr, w = recorded
    assert P.scope_ms(tr, *w, scope) == pytest.approx(RECORDED_MS[scope], rel=1e-9)


def test_host_ms_reads_the_recorded_trace(recorded):
    tr, w = recorded
    assert P.host_ms(tr, *w) == pytest.approx(1.46912, rel=1e-9)


def test_recorded_unscoped_ops_are_at_most_5_percent(recorded):
    tr, w = recorded
    ns = P.decode_scope_ns(tr, *w)
    assert ns[""] <= 0.05 * ns["program"]


def test_recorded_scopes_add_up_to_the_decode_program(recorded):
    tr, w = recorded
    ns = P.decode_scope_ns(tr, *w)
    mk = T.megakernel_ns(tr, *w) / len(P._decode_programs(tr, "0", *w))
    assert ns["ws_decode/ws_kernel"] == pytest.approx(mk, rel=0.01)
    scoped = mk + sum(ns[s] for s in ("ws_decode/dense", "ws_decode/kv_layout",
                                      "ws_decode/ws_put"))
    assert scoped == pytest.approx(ns["program"], rel=0.05)


def test_recorded_idle_time_lies_in_program_spans(recorded):
    tr, w = recorded
    idle = P.idle_by_span(tr, *w)
    assert sum(idle.values()) == pytest.approx((w[1] - w[0] - T.busy_ns(tr, *w)) / 1e6)
    assert next(iter(idle)) == spans.STEP_SYNC
    harness = sum(v for k, v in idle.items() if not k.startswith(P.SPAN_PREFIXES))
    assert harness < 0.02 * sum(idle.values())


# -- the seven accepted readers, unchanged on the old fixture ----------------

@pytest.fixture(scope="module")
def old_ctx():
    """The context ``bench/tests/test_metrics.py`` builds on the old fixture."""
    with gzip.open(OLD, "rt") as fh:
        tr = T.Trace.from_json(fh.read())
    steps = [(s / 1e9, e / 1e9) for s, e, n in tr.host if n == "bench.step"]
    admits = [(s / 1e9, e / 1e9) for s, e, n in tr.host if n == "bench.admit"]
    w1 = max(e for _, e, _ in tr.host)
    lengths = [1550, 142, 270, 270]
    st = [(a, b, [n + i for n in lengths], b - a) for i, (a, b) in enumerate(steps)]
    ad = [(a, b, 128) for a, b in admits]
    return SimpleNamespace(
        conf=registry.load_config("mistral-7b-v0.3-l16"), peaks=peaks.peaks("TPU v5 lite"),
        trace=tr, trace_window=(0, w1), steps=st, admits=ad, window_steps=st,
        window_admits=ad)


# values the readers gave on the old fixture before the program had spans
ACCEPTED = {
    "engine.prefill_ms": 16.93481899999999,
    "engine.step_ms": 45.081072,
    "megakernel.ws_attn_ms": 24.901578999999998,
    "megakernel.ws_attn_roofline": 0.7236649335208628,
    "device.xla_ms": 22.052229999999998,
    "device.idle_frac": 0.0744677261045672,
    "step.mfu": 3.2836485854116995,
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_accepted_readers_read_the_old_fixture_as_before(old_ctx, name):
    value = registry.load_metric_reader(name)(old_ctx)
    assert value == pytest.approx(ACCEPTED[name], rel=1e-9)

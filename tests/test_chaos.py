"""Chaos harness tests (ISSUE 9 / DESIGN.md §9).

The fault model is the paper's §7 adversary made executable: every fault a
:class:`repro.chaos.FaultPlan` injects — program stalls, advisory
corruption, kill-and-relaunch, head-rewind storms, dropped/stale host
advisory writes — is a legal relaxed-memory behavior of the fence-free
protocol, so the *only* acceptable outcomes are the WS-WMULT guarantees:

  1. scheduler chaos — any seeded plan driven through
     ``run_with_faults`` leaves a trace the ``SafetyChecker`` accepts:
     no lost task, per-(program,queue,slot) uniqueness within a launch,
     the stale-republish multiplicity bound, and output parity with the
     fault-free oracle (bitwise via exact float replay for the
     single-source moe rows; allclose after normalization for the
     multi-source attention rows — the repo's existing rewind bar);
  2. fault-off bit-parity — ``fault_plan=None``, an omitted kwarg, and a
     zero ``FaultPlan()`` produce bitwise-identical ``WSRunResult``s
     (injection is free when off, like ``trace=False``);
  3. host-shim faults — dropped advisory writes and stale post-claim
     head republishes on ``PallasWSHost`` stay inside weak multiplicity
     under the deterministic adversarial simulator;
  4. serving chaos — replica crashes re-admit in-flight requests
     idempotently (no duplicate tokens, streams identical to an
     uninterrupted run), and transient admissions back off and give up
     visibly;
  5. checkpoint crash drill — a crash mid-publish can never tear
     ``latest_step`` (write-then-rename), and the async writer surfaces
     the error instead of swallowing it.

Scheduler checks are plain functions over a seed: hypothesis drives them
through arbitrary plans (deep under ``--hypothesis-profile=ci``), and
seeded deterministic slices always run.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

try:
    from hypothesis import given, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.chaos import (  # noqa: E402
    FaultPlan,
    ReplicaCrashPlan,
    SafetyChecker,
    run_with_faults,
)
from repro.core.simulator import (  # noqa: E402
    check_no_lost_tasks_fifo,
    check_no_process_duplicates,
    run_program,
)
from repro.moe_ws.dispatch import route_to_tasks, row_divisor  # noqa: E402
from repro.moe_ws.expert_kernel import run_moe_schedule  # noqa: E402
from repro.pallas_ws import (  # noqa: E402
    PallasWSHost,
    emit_flash_tasks,
    make_queue_state,
    multiplicity_divisor,
    ragged_attention_ref,
)
from repro.pallas_ws.kernel import default_rounds, run_ws_schedule  # noqa: E402
from repro.pallas_ws.queues import copy_state  # noqa: E402
from repro.serving.engine import Request, WorkStealingFrontend  # noqa: E402

P = 3  # programs: fewer than the expert count, so thieves roam


# ---------------------------------------------------------------------------
# problem builders (the steal-policy suite's fixed-size moe problem and the
# rewind drill's attention problem, reused as chaos substrates)
# ---------------------------------------------------------------------------


def _moe_problem(seed):
    rng = np.random.RandomState(seed % 2**31)
    E, T, k, bt = 4, int(rng.randint(6, 12)), 1, 2
    d, f = 4, 8
    idx = np.stack([rng.choice(E, k, replace=False) for _ in range(T)])
    gates = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    gates /= gates.sum(1, keepdims=True)
    ks = jax.random.split(jax.random.PRNGKey(seed % 997), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    w = (
        jax.random.normal(ks[1], (E, d, f), jnp.float32) / 2.0,
        jax.random.normal(ks[2], (E, d, f), jnp.float32) / 2.0,
        jax.random.normal(ks[3], (E, f, d), jnp.float32) / 2.0,
    )
    tasks, routed = route_to_tasks(idx, gates, E, bt=bt)
    state = make_queue_state(tasks, P, n_queues=E, partition="owner")
    return x, w, bt, tasks, routed, state


def _moe_launch(x, routed, w, bt, policy="cost", steal_run_cap=1):
    def launch(state, *, rounds, out, mult, fault_plan):
        return run_moe_schedule(
            state, x, routed.tok_idx, *w, bt=bt, steal=True,
            steal_policy=policy, rounds=rounds, out=out,
            mult=None if mult is None else jnp.asarray(mult),
            steal_run_cap=steal_run_cap, trace=True, fault_plan=fault_plan,
        )
    return launch


def check_moe_chaos(seed, policy="cost", steal_run_cap=1):
    """Any seeded plan through the moe megakernel: checker-clean, and the
    faulted accumulation is the BITWISE float replay of the fault-free
    output times the multiplicity (moe rows are single-source)."""
    x, w, bt, tasks, routed, state = _moe_problem(seed)
    plan = FaultPlan.from_seed(seed, n_programs=P)
    rounds = default_rounds(state, steal=True, steal_run_cap=steal_run_cap)
    oracle = run_moe_schedule(
        copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
        steal_policy=policy, rounds=rounds, steal_run_cap=steal_run_cap,
    )
    assert (oracle.mult[: state.n_tasks] == 1).all()

    chaos = run_with_faults(
        state, _moe_launch(x, routed, w, bt, policy,
                           steal_run_cap=steal_run_cap),
        plan, rounds=rounds)
    row_mult = row_divisor(tasks, chaos.res.mult, routed.n_rows)
    report = SafetyChecker().check(
        chaos, n_tasks=state.n_tasks,
        oracle_accumulated=np.asarray(oracle.out), row_mult=row_mult,
    )
    assert report.ok, report.summary()
    assert report.normalized_parity == "bitwise", report.summary()
    # segment structure mirrors the plan: kills, storms, then the final
    # full-budget drain
    kinds = [s.kind for s in chaos.segments]
    assert kinds == (["kill"] * len(plan.kills)
                     + ["storm"] * plan.storms + ["final"])
    return report


def check_attention_chaos(seed):
    """Attention rows are multi-source (several k-tiles each duplicated
    independently), so parity is allclose after multiplicity
    normalization — the same bar the repo's rewind drills use."""
    rng = np.random.RandomState(seed % 2**31)
    lengths = np.array([32, 8, 8, 16])[rng.permutation(4)]
    H, hd, bq, bk = 2, 8, 8, 8
    B, S = len(lengths), int(max(lengths))
    ks = jax.random.split(jax.random.PRNGKey(seed % 997), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    tasks = emit_flash_tasks(lengths, H, bq, bk, causal=True)
    state = make_queue_state(tasks, n_programs=4)
    plan = FaultPlan.from_seed(seed, n_programs=4)
    rounds = default_rounds(state, steal=True)

    def launch(state, *, rounds, out, mult, fault_plan):
        return run_ws_schedule(
            state, q, k, v, causal=True, bq=bq, bk=bk, steal=True,
            rounds=rounds, out=out,
            mult=None if mult is None else jnp.asarray(mult),
            trace=True, fault_plan=fault_plan,
        )

    chaos = run_with_faults(state, launch, plan, rounds=rounds)
    div = multiplicity_divisor(tasks, chaos.res.mult, (B, H, S))
    normalized = np.asarray(chaos.res.out) / np.asarray(div)[..., None]
    report = SafetyChecker().check(
        chaos, n_tasks=state.n_tasks,
        normalized=normalized,
        oracle_normalized=np.asarray(ragged_attention_ref(q, k, v, lengths)),
        rtol=1e-5, atol=1e-5,
    )
    assert report.ok, report.summary()
    assert report.normalized_parity in ("bitwise", "close"), report.summary()
    return report


# -- hypothesis sweeps (deep under --hypothesis-profile=ci) ----------------

if HAVE_HYPOTHESIS:

    @given(seed=st.integers(0, 2**31 - 1))
    def test_moe_chaos_any_plan_is_safe(seed):
        check_moe_chaos(seed)

    @given(seed=st.integers(0, 2**31 - 1))
    def test_attention_chaos_any_plan_is_safe(seed):
        check_attention_chaos(seed)


# -- seeded slices: always run, even without hypothesis --------------------


@pytest.mark.parametrize("seed", range(4))
def test_moe_chaos_seeded(seed):
    check_moe_chaos(seed)


@pytest.mark.parametrize("seed", range(2))
def test_attention_chaos_seeded(seed):
    check_attention_chaos(seed)


@pytest.mark.parametrize("seed", range(2))
def test_moe_chaos_halfrun_seeded(seed):
    """Half-run claims under the full fault battery: kills mid-run, head
    rewinds that re-arm whole claimed runs, garbage advisories — still
    checker-clean with bitwise normalized parity."""
    check_moe_chaos(seed, steal_run_cap=4)


def test_storm_halfrun_produces_real_duplication():
    """A head-rewind storm against run-length claims: the rewound head
    re-arms slots a thief already claimed as part of a run, so the relaunch
    duplicates real work (max_mult ≥ 2) and normalization must still
    recover the fault-free answer bitwise."""
    x, w, bt, tasks, routed, state = _moe_problem(3)
    plan = FaultPlan(seed=3, kills=(1,), storms=1, full_first_storm=True)
    rounds = default_rounds(state, steal=True, steal_run_cap=4)
    oracle = run_moe_schedule(
        copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
        rounds=rounds, steal_run_cap=4,
    )
    chaos = run_with_faults(
        state, _moe_launch(x, routed, w, bt, steal_run_cap=4), plan,
        rounds=rounds)
    report = SafetyChecker().check(
        chaos, n_tasks=state.n_tasks,
        oracle_accumulated=np.asarray(oracle.out),
        row_mult=row_divisor(tasks, chaos.res.mult, routed.n_rows),
    )
    assert report.ok, report.summary()
    assert report.max_mult >= 2, "the full storm re-armed nothing"
    assert report.normalized_parity == "bitwise"


def test_storm_plan_produces_real_duplication():
    """A kill + full storm must actually exercise the multiplicity path
    (max_mult ≥ 2), not vacuously pass an empty drill."""
    x, w, bt, tasks, routed, state = _moe_problem(3)
    plan = FaultPlan(seed=3, kills=(1,), storms=1, full_first_storm=True)
    rounds = default_rounds(state, steal=True)
    oracle = run_moe_schedule(
        copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
        rounds=rounds,
    )
    chaos = run_with_faults(state, _moe_launch(x, routed, w, bt), plan,
                            rounds=rounds)
    report = SafetyChecker().check(
        chaos, n_tasks=state.n_tasks,
        oracle_accumulated=np.asarray(oracle.out),
        row_mult=row_divisor(tasks, chaos.res.mult, routed.n_rows),
    )
    assert report.ok, report.summary()
    assert report.max_mult >= 2, "the full storm re-armed nothing"
    assert report.normalized_parity == "bitwise"


def test_checker_catches_violations():
    """The checker is not a rubber stamp: corrupt a clean run's counters /
    outputs and the matching clause must trip."""
    import dataclasses as dc

    x, w, bt, tasks, routed, state = _moe_problem(1)
    plan = FaultPlan(seed=1, storms=1, full_first_storm=True)
    rounds = default_rounds(state, steal=True)
    oracle = run_moe_schedule(
        copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
        rounds=rounds,
    )
    chaos = run_with_faults(state, _moe_launch(x, routed, w, bt), plan,
                            rounds=rounds)
    checker = SafetyChecker()

    # a lost task: zero one mult counter on the final segment's result
    clean_res = chaos.segments[-1].res
    mult = np.array(clean_res.mult)
    mult[0] = 0
    chaos.segments[-1].res = dc.replace(clean_res, mult=mult)
    rep = checker.check(chaos, n_tasks=state.n_tasks)
    assert not rep.ok
    assert any(v.kind in ("lost-task", "stream-mult-mismatch")
               for v in rep.violations)
    chaos.segments[-1].res = clean_res

    # output corruption: one flipped element must break bitwise parity
    bad_out = np.array(oracle.out)
    bad_out.flat[0] += 1.0
    rep = checker.check(
        chaos, n_tasks=state.n_tasks,
        normalized=bad_out, oracle_normalized=np.asarray(oracle.out),
    )
    assert rep.normalized_parity == "diverged"
    assert any(v.kind == "normalized-parity" for v in rep.violations)


# ---------------------------------------------------------------------------
# fault-off bit-parity: injection is free when off
# ---------------------------------------------------------------------------

_WS_FIELDS = ("out", "mult", "head", "local_head", "taken", "remaining",
              "clock", "work", "steals", "scanned")


def test_fault_plan_none_is_bit_identical():
    x, w, bt, tasks, routed, state = _moe_problem(7)
    rounds = default_rounds(state, steal=True)

    def run(**kw):
        return run_moe_schedule(
            copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
            rounds=rounds, **kw,
        )

    base = run()                       # kwarg omitted entirely
    off_none = run(fault_plan=None)    # explicit None
    off_zero = run(fault_plan=FaultPlan())  # a zero plan
    assert FaultPlan().is_off
    for res in (off_none, off_zero):
        for f in _WS_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(base, f)), np.asarray(getattr(res, f)),
                err_msg=f)


def test_stalled_programs_extract_nothing_before_release():
    """A stall is an initial clock offset: the stalled program's first
    trace event lands at round ≥ its stall, and the drain (with the
    auto-extended budget) still completes exactly once."""
    from repro.wstrace.ring import EV_PROG, EV_ROUND, decode_rings

    lengths = np.array([32, 8, 8, 16])
    H, bq, bk = 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B, S = len(lengths), int(max(lengths))
    q = jax.random.normal(ks[0], (B, H, S, 8))
    k = jax.random.normal(ks[1], (B, H, S, 8))
    v = jax.random.normal(ks[2], (B, H, S, 8))
    tasks = emit_flash_tasks(lengths, H, bq, bk, causal=True)
    state = make_queue_state(tasks, n_programs=4)
    plan = FaultPlan(stalls=(3, 0, 2, 0))
    res = run_ws_schedule(state, q, k, v, causal=True, bq=bq, bk=bk,
                          steal=True, trace=True, fault_plan=plan)
    assert (res.mult[: state.n_tasks] == 1).all(), "stalls must not drop work"
    stream, dropped = decode_rings(res.events, res.ev_cursor)
    assert (np.asarray(dropped) == 0).all()
    for p, stall in enumerate(plan.stalls):
        mine = stream[stream[:, EV_PROG] == p]
        if mine.shape[0]:
            assert int(mine[:, EV_ROUND].min()) >= stall, (
                f"program {p} extracted before its stall {stall} expired")


# ---------------------------------------------------------------------------
# host-shim faults under the adversarial simulator
# ---------------------------------------------------------------------------


def test_host_dropped_advisories_never_block_progress():
    plan = FaultPlan(drop_advisory_every=2)
    q = PallasWSHost(capacity=64, fault_plan=plan)
    for i in range(12):
        q.put(i)
    got = [q.take() for _ in range(6)] + [q.steal(1) for _ in range(6)]
    assert got == list(range(12)), "advisory drops are selection-only"
    assert q.faults_injected["dropped_advisories"] > 0


def test_host_stale_republish_creates_bounded_duplicates():
    """Republishing the pre-claim head after a claim is the §7 stale write:
    a thief may re-claim the slot (multiplicity!) but never the same
    process twice, and FIFO at-least-once still holds."""
    plan = FaultPlan(stale_head_every=1)
    q = PallasWSHost(capacity=64, fault_plan=plan)
    for i in range(4):
        q.put(i)
    a = q.take()          # owner claims slot 0, then republishes head=0
    b = q.steal(1)        # the thief re-claims the re-armed slot 0
    assert a == 0 and b == 0, "stale republish re-armed the claimed slot"
    assert q.faults_injected["stale_republishes"] >= 1
    # the same thief cannot take it a third time (its local bound advanced)
    c = q.steal(1)
    assert c != 0


@pytest.mark.parametrize("seed", range(4))
def test_host_faults_respect_weak_multiplicity(seed):
    import random as _random

    rng = _random.Random(seed)
    schedule = [rng.randrange(4) for _ in range(rng.randrange(50, 300))]
    prog = {0: [("put", i) for i in range(1, 9)] + [("take", None)] * 5}
    for t in (1, 2, 3):
        prog[t] = [("steal", None)] * 5
    plan = FaultPlan(drop_advisory_every=2, stale_head_every=3)
    records = run_program(
        lambda backend: PallasWSHost(backend=backend, capacity=64,
                                     fault_plan=plan),
        prog, schedule,
    )
    check_no_process_duplicates(records)  # weak multiplicity survives faults
    check_no_lost_tasks_fifo(records)     # at-least-once, FIFO prefix


# ---------------------------------------------------------------------------
# serving chaos: crash re-admission, backoff give-up
# ---------------------------------------------------------------------------


class SeqLenBatcher:
    """Deterministic greedy-decode stand-in: token k of a request is
    ``len(prompt) + k`` (the total sequence length at emission), so a
    crash-resumed stream — prompt extended by the tokens already emitted,
    budget reduced — continues EXACTLY where the uninterrupted stream
    would be.  Mirrors the engine's admit-emits-first-token contract."""

    def __init__(self, slots=2, cap=64):
        self.B, self.cap = slots, cap
        self.live = [None] * slots

    @property
    def n_live(self):
        return sum(r is not None for r in self.live)

    def admit(self, req):
        if not 0 < len(req.tokens) < self.cap:
            return False
        try:
            slot = self.live.index(None)
        except ValueError:
            return False
        req.out.append(len(req.tokens))  # first token at admit
        self.live[slot] = req
        return True

    def step(self):
        done = []
        for i, r in enumerate(self.live):
            if r is None:
                continue
            if len(r.out) < r.max_new:
                r.out.append(len(r.tokens) + len(r.out))
            if len(r.out) >= r.max_new:
                done.append(r)
                self.live[i] = None
        return done


def _expected_stream(prompt_len, max_new):
    return [prompt_len + i for i in range(max_new)]


def test_replica_crash_readmits_without_duplicate_tokens():
    prompts = {rid: np.arange(3 + rid % 4, dtype=np.int32)
               for rid in range(6)}
    fe = WorkStealingFrontend(
        lambda: SeqLenBatcher(slots=2), n_replicas=2,
        crash_plan=ReplicaCrashPlan({0: 2}),
    )
    for rid, p in prompts.items():
        fe.submit(rid % 2, Request(rid, p, max_new=5))
    completed = fe.run(max_iters=500)
    assert not fe.rejected
    assert set(completed) == set(prompts), "every request completed"
    for rid, r in completed.items():
        assert list(r.out) == _expected_stream(len(prompts[rid]), 5), (
            rid, r.out)
        np.testing.assert_array_equal(np.asarray(r.tokens), prompts[rid])
    assert fe.counters["crashed"] == 1
    # replica 0 had in-flight work at iteration 2: those requests were
    # resumed on the survivor, keyed by rid + tokens-so-far
    assert fe.counters["readmitted"] >= 1
    assert fe.counters["dup_completed"] == 0


def test_replica_crash_on_empty_engine_is_harmless():
    fe = WorkStealingFrontend(
        lambda: SeqLenBatcher(slots=1), n_replicas=2,
        crash_plan=ReplicaCrashPlan({1: 0}),
    )
    fe.submit(0, Request(0, np.array([1, 2], np.int32), max_new=3))
    completed = fe.run(max_iters=100)
    assert set(completed) == {0}
    assert fe.counters["crashed"] == 1
    assert fe.counters["readmitted"] == 0


def test_dead_replica_queue_remains_stealable():
    """The crash kills the engine, not the queue: work submitted to the
    dead replica's queue is stolen and completed by the survivor."""
    fe = WorkStealingFrontend(
        lambda: SeqLenBatcher(slots=2), n_replicas=2,
        crash_plan=ReplicaCrashPlan({0: 0}),
    )
    for rid in range(3):
        fe.submit(0, Request(rid, np.arange(2 + rid, dtype=np.int32),
                             max_new=3))
    completed = fe.run(max_iters=200)
    assert set(completed) == {0, 1, 2}
    for rid, r in completed.items():
        assert list(r.out) == _expected_stream(2 + rid, 3)
    assert fe.counters["stolen"] >= 3, "survivor stole from the dead queue"


def test_transient_admission_backs_off_and_gives_up():
    class Stuck:
        B, cap = 1, 64

        def __init__(self):
            self.live = [None]

        @property
        def n_live(self):
            return 0

        def admit(self, req):
            return False  # transient: the prompt fits, no slot frees up

        def step(self):
            return []

    fe = WorkStealingFrontend(lambda: Stuck(), n_replicas=1,
                              max_admission_retries=4)
    fe.submit(0, Request(0, np.array([1, 2], np.int32), max_new=2))
    completed = fe.run(max_iters=10_000)
    assert not completed
    assert 0 in fe.rejected, "the give-up is surfaced, not silently dropped"
    assert fe.counters["gave_up"] == 1
    assert fe.counters["rejected"] == 1
    # exponential backoff actually waited (2+4+8+16 iterations), and the
    # loop terminated instead of spinning to max_iters
    assert 30 <= fe._iter < 200, fe._iter


def test_permanent_rejection_bypasses_backoff():
    fe = WorkStealingFrontend(lambda: SeqLenBatcher(slots=1, cap=4),
                              n_replicas=1)
    fe.submit(0, Request(0, np.arange(9, dtype=np.int32), max_new=2))
    fe.run(max_iters=50)
    assert 0 in fe.rejected
    assert fe.counters["gave_up"] == 0, "over-capacity is permanent"


# ---------------------------------------------------------------------------
# checkpoint crash-mid-write drill (satellite b)
# ---------------------------------------------------------------------------


def test_checkpoint_crash_mid_publish_never_tears(tmp_path, monkeypatch):
    from repro.checkpoint import checkpoint as ckpt

    d = str(tmp_path)
    tree = {"w": np.arange(4.0), "b": np.zeros(2)}
    ckpt.save(d, 1, tree)
    assert ckpt.latest_step(d) == 1

    # crash exactly at the publish rename: the new step must never become
    # visible, the old step must never be damaged
    def crash(src, dst):
        raise OSError("simulated crash mid-publish")

    monkeypatch.setattr(ckpt.os, "rename", crash)
    with pytest.raises(OSError):
        ckpt.save(d, 2, {"w": np.arange(4.0) + 1, "b": np.ones(2)})
    assert ckpt.latest_step(d) == 1, "latest_step torn by a failed publish"

    # a crash that leaves a stale tmp dir behind (no cleanup ran at all):
    # restore/latest_step must ignore it even though it holds a manifest
    stale = tmp_path / "step_00000009.tmp-dead"
    stale.mkdir()
    (stale / "manifest.json").write_text("{}")
    assert ckpt.latest_step(d) == 1
    monkeypatch.undo()

    restored, step = ckpt.restore(d, tree)
    assert step == 1
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_async_checkpointer_surfaces_crash(tmp_path, monkeypatch):
    from repro.checkpoint import checkpoint as ckpt

    d = str(tmp_path)
    ckpt.save(d, 1, {"w": np.zeros(3)})
    ac = ckpt.AsyncCheckpointer(d)

    def crash(src, dst):
        raise OSError("simulated crash in the background writer")

    monkeypatch.setattr(ckpt.os, "rename", crash)
    ac.save(2, {"w": np.ones(3)})
    with pytest.raises(OSError):
        ac.wait()  # the error is surfaced, not swallowed
    assert ckpt.latest_step(d) == 1

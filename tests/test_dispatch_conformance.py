"""Adversarial conformance suite: host Put vs traced Put, one protocol.

The dropless dispatch now has two queue builders — ``route_to_tasks`` +
``make_queue_state`` (host-side numpy, compact padding) and
``route_to_tasks_jax`` + ``make_queue_state_jax`` (jit-compatible, static
worst-case padding with live masks).  Correctness under duplicated steals is
a *scheduling-order* property, so happy-path parity is not enough: for ANY
routing, and ANY adversarial schedule (steals via per-expert queues >
programs, head rewinds between launches, wiped per-program bounds,
under-provisioned partial relaunches that duplicate extractions), the two
builders must

1. lay out **identical Fig. 7 queue arrays** — identical live task prefixes
   per queue (op/expert/row_len/cost fields equal, ``row_start`` equal
   relative to each layout's expert offsets, ``tid`` equal under the static
   remap ``(e, i) ↦ e·tiles_per_expert + i``), identical tails, all-⊥
   suffixes, all-(-1) announcement rows;
2. drive the megakernel through **identical extraction sequences** — equal
   heads, clocks, work/steal counters, and per-tile multiplicities after
   every adversarial relaunch (the scan only sees queue contents, so layout
   conformance must imply schedule conformance);
3. produce **bit-identical multiplicity-normalized per-row outputs** (same
   tile membership → same kernel arithmetic → same floats), and combines
   that both match the ``moe_ffn_nodrop_ref``-style no-drop oracle.

The traced builder is additionally certified shape-stable: building under
``jit`` and eagerly yields bit-identical arrays.

The checks are plain functions over a ``draw_int``/``draw_bool`` source:
hypothesis drives them through arbitrary schedules (deep under the CI
``--hypothesis-profile=ci`` job), and seeded deterministic slices always
run so the tier-1 smoke keeps coverage even without hypothesis installed.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

try:
    from hypothesis import given, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.moe_ws.dispatch import (  # noqa: E402
    divisor_from_tiles,
    expert_queue_candidates,
    expert_rounds_bound,
    route_to_tasks,
    route_to_tasks_jax,
    route_to_tasks_pool_jax,
    row_divisor,
)
from repro.moe_ws.expert_kernel import run_moe_schedule  # noqa: E402
from repro.moe_ws.layer import expert_ffn_nodrop_ref  # noqa: E402
from repro.pallas_ws.queues import (  # noqa: E402
    make_pool_queue_state_jax,
    make_queue_state,
    make_queue_state_jax,
    owner_queue_candidates,
)
from repro.pallas_ws.tasks import (  # noqa: E402
    BOTTOM,
    F_COST,
    F_OP,
    F_RL,
    F_RS,
    F_TID,
    emit_decode_tasks,
)

# shared fault-drill mechanics (repro.chaos via conftest)
from conftest import apply_rewind, drawn_rewind, resume_state  # noqa: E402

P = 3  # programs: fewer than most drawn expert counts, so thieves roam


def _cdiv(a, b):
    return -(-a // b)


def _routing_from(draw_int):
    E = draw_int(2, 5)
    T = draw_int(1, 10)
    k = draw_int(1, min(2, E))
    bt = (2, 4)[draw_int(0, 1)]
    seed = draw_int(0, 2**16)
    rng = np.random.RandomState(seed)
    idx = np.stack([rng.choice(E, k, replace=False) for _ in range(T)])
    gates = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    gates /= gates.sum(1, keepdims=True)
    return E, T, k, bt, seed, idx, gates


def _host_state(idx, gates, E, bt):
    tasks, routed = route_to_tasks(idx, gates, E, bt=bt)
    state = make_queue_state(tasks, P, n_queues=E, partition="owner")
    return tasks, routed, state


def _traced_state(idx, gates, E, bt, *, under_jit):
    def build(i, g):
        records, live, routed = route_to_tasks_jax(i, g, E, bt=bt)
        cand, cand_live = expert_queue_candidates(records, live, E)
        return records, live, routed, cand, cand_live

    if under_jit:
        build = jax.jit(build)
    records, live, routed, cand, cand_live = build(idx, gates)
    state = make_queue_state_jax(
        cand, cand_live, P, n_tasks=records.shape[0] * records.shape[1]
    )
    # concrete jnp -> numpy so adversarial drills can mutate heads/bounds
    for f in ("tasks", "head", "tail", "local_head", "taken", "remaining"):
        setattr(state, f, np.asarray(getattr(state, f)))
    return np.asarray(records), np.asarray(live), routed, state


def _pool_state(idx, gates, E, bt, *, under_jit):
    """Shared-pool traced Put (route_to_tasks_pool_jax), numpy-ified for the
    adversarial drills."""

    def build(i, g):
        return route_to_tasks_pool_jax(i, g, E, bt=bt)

    if under_jit:
        build = jax.jit(build)
    records, tail, pool_off, routed = build(idx, gates)
    state = make_pool_queue_state_jax(
        records, tail, pool_off, routed.loads, P, n_tasks=records.shape[0]
    )
    for f in ("tasks", "head", "tail", "local_head", "taken", "remaining",
              "pool_off"):
        setattr(state, f, np.asarray(getattr(state, f)))
    return np.asarray(records), routed, state


def _tid_remap(loads, bt, tiles_per_e, layout="padded"):
    """Host tid (expert-major sequential over live tiles) -> traced tid.

    Padded layout: static ``e·tiles_per_e + i``.  Pool layout: dynamic pool
    slot ``toff[e] + i`` with ``toff`` the cumsum of per-expert live tile
    counts (recomputed host-side from the loads)."""
    remap = []
    if layout == "pool":
        toff = 0
        for load in loads:
            n_e = _cdiv(int(load), bt)
            remap.extend(toff + i for i in range(n_e))
            toff += n_e
    else:
        for e, load in enumerate(loads):
            remap.extend(e * tiles_per_e + i for i in range(_cdiv(int(load), bt)))
    return np.asarray(remap, dtype=np.int64)


# ---------------------------------------------------------------------------
# check 1: Fig. 7 layout conformance
# ---------------------------------------------------------------------------


def check_fig7_layout_conformance(draw_int):
    E, T, k, bt, seed, idx, gates = _routing_from(draw_int)
    tasks, routed_h, sh = _host_state(idx, gates, E, bt)
    rec_j, live_j, routed_j, sj = _traced_state(idx, gates, E, bt, under_jit=True)
    rec_e, live_e, routed_e, se = _traced_state(idx, gates, E, bt, under_jit=False)

    # jit-built == eager-built, bit for bit
    np.testing.assert_array_equal(rec_j, rec_e)
    np.testing.assert_array_equal(live_j, live_e)
    np.testing.assert_array_equal(sj.tasks, se.tasks)
    np.testing.assert_array_equal(
        np.asarray(routed_j.tok_idx), np.asarray(routed_e.tok_idx)
    )
    np.testing.assert_array_equal(
        np.asarray(routed_j.gates), np.asarray(routed_e.gates)
    )

    loads = np.bincount(idx.reshape(-1), minlength=E)
    np.testing.assert_array_equal(np.asarray(routed_j.loads), loads)
    np.testing.assert_array_equal(routed_h.loads, loads)

    tiles_per_e = _cdiv(min(T, T * k), bt)  # top-k: distinct experts/token
    off_h = routed_h.expert_off
    off_j = np.asarray(routed_j.expert_off)
    assert sh.n_queues == sj.n_queues == E
    np.testing.assert_array_equal(sj.head, np.zeros(E))
    assert (sj.taken == -1).all() and (sh.taken == -1).all()

    for e in range(E):
        n_e = _cdiv(int(loads[e]), bt)
        # identical tails: the owner's Put counter
        assert int(sh.tail[e]) == int(sj.tail[e]) == n_e
        h_rec = sh.tasks[e, :n_e]
        j_rec = sj.tasks[e, :n_e]
        # family-agnostic fields + operands, compared in queue order
        np.testing.assert_array_equal(h_rec[:, F_OP], j_rec[:, F_OP])
        np.testing.assert_array_equal(h_rec[:, 1], j_rec[:, 1])  # expert
        np.testing.assert_array_equal(h_rec[:, F_RL], j_rec[:, F_RL])
        np.testing.assert_array_equal(h_rec[:, F_COST], j_rec[:, F_COST])
        # row_start agrees relative to each layout's expert offset
        np.testing.assert_array_equal(
            h_rec[:, F_RS] - off_h[e], j_rec[:, F_RS] - off_j[e]
        )
        # traced tid is the static (e, i) code
        np.testing.assert_array_equal(
            j_rec[:, F_TID], e * tiles_per_e + np.arange(n_e)
        )
        # whole suffix is ⊥ in both layouts
        assert (sh.tasks[e, n_e:, F_OP] == BOTTOM).all()
        assert (sj.tasks[e, n_e:, F_OP] == BOTTOM).all()
        # routed rows carry the same tokens/gates at remapped positions
        ln = int(loads[e])
        np.testing.assert_array_equal(
            np.asarray(routed_h.tok_idx)[off_h[e]: off_h[e] + ln],
            np.asarray(routed_j.tok_idx)[off_j[e]: off_j[e] + ln],
        )
        np.testing.assert_array_equal(
            np.asarray(routed_h.gates)[off_h[e]: off_h[e] + ln],
            np.asarray(routed_j.gates)[off_j[e]: off_j[e] + ln],
        )
    # dead rows of the static layout are inert: gate 0 (token 0 by init)
    live_rows = np.zeros(routed_j.n_rows, dtype=bool)
    for e in range(E):
        live_rows[off_j[e]: off_j[e] + int(loads[e])] = True
    assert (np.asarray(routed_j.gates)[~live_rows] == 0).all()


def check_pool_layout_conformance(draw_int):
    """Shared-pool layout (DESIGN.md §3.6): queue ``e``'s pool segment must
    hold exactly the host layout's live records for expert ``e``, in queue
    order, with ``tid == pool slot``, an all-⊥ pool suffix, and the routed
    rows at the compact dynamic offsets."""
    E, T, k, bt, seed, idx, gates = _routing_from(draw_int)
    tasks, routed_h, sh = _host_state(idx, gates, E, bt)
    rec_j, routed_j, sj = _pool_state(idx, gates, E, bt, under_jit=True)
    rec_e, routed_e, se = _pool_state(idx, gates, E, bt, under_jit=False)

    # jit-built == eager-built, bit for bit
    np.testing.assert_array_equal(rec_j, rec_e)
    np.testing.assert_array_equal(sj.tasks, se.tasks)
    np.testing.assert_array_equal(sj.pool_off, se.pool_off)
    np.testing.assert_array_equal(
        np.asarray(routed_j.tok_idx), np.asarray(routed_e.tok_idx)
    )
    np.testing.assert_array_equal(
        np.asarray(routed_j.gates), np.asarray(routed_e.gates)
    )

    loads = np.bincount(idx.reshape(-1), minlength=E)
    n_tiles = -(-loads // bt)
    toff = np.concatenate([[0], np.cumsum(n_tiles)])
    pool_tiles = _cdiv(T * k, bt) + E
    assert sj.tasks.shape == (pool_tiles, 8)
    np.testing.assert_array_equal(sj.pool_off, toff)
    np.testing.assert_array_equal(sj.tail, n_tiles)
    np.testing.assert_array_equal(sj.remaining, loads)
    np.testing.assert_array_equal(np.asarray(routed_j.loads), loads)
    assert (sj.taken == -1).all() and sj.taken.shape == (pool_tiles,)
    assert routed_j.n_rows == pool_tiles * bt

    off_h = routed_h.expert_off
    off_j = np.asarray(routed_j.expert_off)
    np.testing.assert_array_equal(off_j, toff * bt)
    for e in range(E):
        n_e = int(n_tiles[e])
        assert int(sh.tail[e]) == n_e  # host agrees on live tile counts
        h_rec = sh.tasks[e, :n_e]
        j_rec = sj.tasks[toff[e]: toff[e] + n_e]
        np.testing.assert_array_equal(h_rec[:, F_OP], j_rec[:, F_OP])
        np.testing.assert_array_equal(h_rec[:, 1], j_rec[:, 1])  # expert
        np.testing.assert_array_equal(h_rec[:, F_RL], j_rec[:, F_RL])
        np.testing.assert_array_equal(h_rec[:, F_COST], j_rec[:, F_COST])
        # row_start agrees relative to each layout's expert offset
        np.testing.assert_array_equal(
            h_rec[:, F_RS] - off_h[e], j_rec[:, F_RS] - off_j[e]
        )
        # pool tid IS the pool slot index (mult needs no remap table)
        np.testing.assert_array_equal(
            j_rec[:, F_TID], toff[e] + np.arange(n_e)
        )
        # routed rows carry the same tokens/gates at the compact offsets
        ln = int(loads[e])
        np.testing.assert_array_equal(
            np.asarray(routed_h.tok_idx)[off_h[e]: off_h[e] + ln],
            np.asarray(routed_j.tok_idx)[off_j[e]: off_j[e] + ln],
        )
        np.testing.assert_array_equal(
            np.asarray(routed_h.gates)[off_h[e]: off_h[e] + ln],
            np.asarray(routed_j.gates)[off_j[e]: off_j[e] + ln],
        )
    # the pool suffix past the last live tile is all-⊥ with gate-0 rows
    assert (sj.tasks[toff[E]:, F_OP] == BOTTOM).all()
    live_rows = np.zeros(routed_j.n_rows, dtype=bool)
    for e in range(E):
        live_rows[off_j[e]: off_j[e] + int(loads[e])] = True
    assert (np.asarray(routed_j.gates)[~live_rows] == 0).all()
    # compactness: the whole point — pool never exceeds ceil(Tk/bt) + E
    # tiles, vs the padded layout's E · ceil(min(T, Tk)/bt)
    assert toff[E] <= pool_tiles


# ---------------------------------------------------------------------------
# checks 2+3: adversarial schedules — identical runs, exact combines
# ---------------------------------------------------------------------------


def check_adversarial_schedules(draw_int, draw_bool, steal_policy="cost",
                                layout="padded", steal_run_cap=1):
    E, T, k, bt, seed, idx, gates = _routing_from(draw_int)
    d, f = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(seed % 997), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    w = (
        jax.random.normal(ks[1], (E, d, f), jnp.float32) / 2.0,
        jax.random.normal(ks[2], (E, d, f), jnp.float32) / 2.0,
        jax.random.normal(ks[3], (E, f, d), jnp.float32) / 2.0,
    )
    tasks, routed_h, sh = _host_state(idx, gates, E, bt)
    if layout == "pool":
        _, routed_j, sj = _pool_state(idx, gates, E, bt, under_jit=True)
    else:
        _, _, routed_j, sj = _traced_state(idx, gates, E, bt, under_jit=True)

    loads = np.bincount(idx.reshape(-1), minlength=E)
    tiles_per_e = _cdiv(min(T, T * k), bt)  # top-k: distinct experts/token
    remap = _tid_remap(loads, bt, tiles_per_e, layout)
    rounds = expert_rounds_bound(T * k, bt, E, P, steal=True,
                                 steal_run_cap=steal_run_cap)

    def launch(state, tok_idx, out=None, mult=None, r=rounds):
        return run_moe_schedule(
            state, x, jnp.asarray(tok_idx), *w, bt=bt, steal=True,
            steal_policy=steal_policy, rounds=r, out=out, mult=mult,
            steal_run_cap=steal_run_cap,
        )

    res_h = launch(sh, routed_h.tok_idx)
    res_j = launch(sj, routed_j.tok_idx)

    n_relaunches = draw_int(1, 2)
    for step in range(n_relaunches):
        # identical adversarial staleness on both sides: ONE drawn
        # RewindSpec (targets read from the host heads — they agree, so
        # the spec is valid for both) replayed onto each layout-parity
        # state via the shared repro.chaos drill
        np.testing.assert_array_equal(res_h.head, res_j.head)
        spec = drawn_rewind(sh, res_h, draw_int, draw_bool,
                            heads=res_h.head)
        resume_state(sj, res_j)
        apply_rewind(sj, spec)
        # sometimes under-provision the relaunch: partial drains leave
        # uneven duplicate counts behind — the combine must still be exact
        r = draw_int(1, rounds)
        res_h = launch(sh, routed_h.tok_idx, out=res_h.out,
                       mult=jnp.asarray(res_h.mult), r=r)
        res_j = launch(sj, routed_j.tok_idx, out=res_j.out,
                       mult=jnp.asarray(res_j.mult), r=r)

    # identical extraction behavior, slot for slot
    np.testing.assert_array_equal(res_h.head, res_j.head)
    np.testing.assert_array_equal(res_h.clock, res_j.clock)
    np.testing.assert_array_equal(res_h.work, res_j.work)
    np.testing.assert_array_equal(res_h.steals, res_j.steals)
    mult_h = res_h.mult[: len(tasks)]
    np.testing.assert_array_equal(mult_h, res_j.mult[remap])
    # traced tiles outside the live remap never execute
    n_mult_j = res_j.mult.shape[0]
    dead = np.setdiff1d(np.arange(n_mult_j), remap)
    assert (res_j.mult[dead] == 0).all()
    assert (mult_h >= 1).all(), "first launch drained: dropless"

    # bit-identical multiplicity-normalized per-row outputs
    div_h = row_divisor(tasks, res_h.mult, routed_h.n_rows)
    starts_j = jnp.arange(n_mult_j, dtype=jnp.int32) * bt
    div_j = np.asarray(
        divisor_from_tiles(starts_j, bt, res_j.mult, routed_j.n_rows)
    )
    yr_h = np.asarray(res_h.out) / div_h[:, None]
    yr_j = np.asarray(res_j.out) / div_j[:, None]
    off_h, off_j = routed_h.expert_off, np.asarray(routed_j.expert_off)
    for e in range(E):
        ln = int(loads[e])
        np.testing.assert_array_equal(
            yr_h[off_h[e]: off_h[e] + ln], yr_j[off_j[e]: off_j[e] + ln]
        )

    # both combines reproduce the no-drop oracle
    ref = np.asarray(expert_ffn_nodrop_ref(idx, gates, x, *w))
    for routed, yr in ((routed_h, yr_h), (routed_j, yr_j)):
        y = np.zeros((T, d), np.float32)
        np.add.at(
            y, np.asarray(routed.tok_idx),
            np.asarray(routed.gates)[:, None] * yr,
        )
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode family: traced candidates compact to the host emitter's queues
# ---------------------------------------------------------------------------


def check_decode_layout_conformance(draw_int):
    from repro.pallas_ws.ragged import emit_decode_tasks_jax

    B = draw_int(1, 5)
    H = draw_int(1, 3)
    bk = (4, 8)[draw_int(0, 1)]
    nq = draw_int(1, 4)
    lengths = np.asarray([draw_int(0, 32) for _ in range(B)], dtype=np.int64)
    # query rows per tile: G query heads share each (KV) head's tile
    q_rows = draw_int(1, 4)

    tasks = emit_decode_tasks(lengths, H, bk, q_rows=q_rows)
    sh = make_queue_state(tasks, P, n_queues=nq, partition="batch")

    records, live = jax.jit(
        lambda ln: emit_decode_tasks_jax(ln, H, bk, q_rows=q_rows)
    )(jnp.asarray(lengths))
    cand, cand_live = owner_queue_candidates(records, live, nq)
    sj = make_queue_state_jax(cand, cand_live, P, n_tasks=B * H)

    sj_tasks = np.asarray(sj.tasks)
    sj_tail = np.asarray(sj.tail)
    for q in range(nq):
        n_q = int(sh.tail[q])
        assert int(sj_tail[q]) == n_q
        # identical live records except tid (host: dense sequential; traced:
        # static b·H + h) — the task payload the kernel reads is equal
        h_rec = sh.tasks[q, :n_q]
        j_rec = sj_tasks[q, :n_q]
        cols = [c for c in range(h_rec.shape[1]) if c != F_TID]
        np.testing.assert_array_equal(h_rec[:, cols], j_rec[:, cols])
        # traced tid encodes (b, h) statically
        np.testing.assert_array_equal(
            j_rec[:, F_TID], j_rec[:, 1] * H + j_rec[:, 2]
        )
        assert (sj_tasks[q, n_q:, F_OP] == BOTTOM).all()
        assert (sh.tasks[q, n_q:, F_OP] == BOTTOM).all()


# ---------------------------------------------------------------------------
# hypothesis drivers (depth set by the conftest profile; the CI conformance
# job runs --hypothesis-profile=ci for the deep derandomized sweep)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @given(data=st.data())
    def test_fig7_layout_conformance(data):
        check_fig7_layout_conformance(
            lambda lo, hi: data.draw(st.integers(lo, hi))
        )

    @given(data=st.data())
    def test_pool_layout_conformance(data):
        check_pool_layout_conformance(
            lambda lo, hi: data.draw(st.integers(lo, hi))
        )

    @given(data=st.data())
    def test_adversarial_schedules_identical_runs_and_exact_combines(data):
        policy = data.draw(st.sampled_from(["cost", "scan"]))
        # half-run claims require the cost policy (victim bounds feed the
        # run length); cap=1 keeps scan-policy draws on the per-slot path
        cap = data.draw(st.sampled_from([1, 2, 4])) if policy == "cost" else 1
        check_adversarial_schedules(
            lambda lo, hi: data.draw(st.integers(lo, hi)),
            lambda: data.draw(st.booleans()),
            steal_policy=policy,
            layout=data.draw(st.sampled_from(["padded", "pool"])),
            steal_run_cap=cap,
        )

    @given(data=st.data())
    def test_decode_family_layout_conformance(data):
        check_decode_layout_conformance(
            lambda lo, hi: data.draw(st.integers(lo, hi))
        )


# ---------------------------------------------------------------------------
# deterministic seeded slices — always run (no hypothesis needed), so the
# tier-1 smoke keeps conformance coverage in bare environments
# ---------------------------------------------------------------------------


def _rng_draws(seed):
    rng = random.Random(seed)
    return (lambda lo, hi: rng.randint(lo, hi)), (lambda: rng.random() < 0.5)


@pytest.mark.parametrize("seed", range(4))
def test_fig7_layout_conformance_seeded(seed):
    draw_int, _ = _rng_draws(seed)
    check_fig7_layout_conformance(draw_int)


@pytest.mark.parametrize("seed", range(4))
def test_pool_layout_conformance_seeded(seed):
    draw_int, _ = _rng_draws(500 + seed)
    check_pool_layout_conformance(draw_int)


@pytest.mark.parametrize("steal_policy", ["cost", "scan"])
@pytest.mark.parametrize("layout", ["padded", "pool"])
@pytest.mark.parametrize("seed", range(2))
def test_adversarial_schedules_seeded(seed, layout, steal_policy):
    draw_int, draw_bool = _rng_draws(100 + seed)
    check_adversarial_schedules(draw_int, draw_bool,
                                steal_policy=steal_policy, layout=layout)


@pytest.mark.parametrize("layout", ["padded", "pool"])
@pytest.mark.parametrize("seed", range(2))
def test_adversarial_schedules_halfrun_seeded(seed, layout):
    """The conformance contract survives run-length claims: padded and pool
    layouts stay slot-for-slot identical under steal_run_cap=4, including
    through drawn head-rewind relaunches."""
    draw_int, draw_bool = _rng_draws(900 + seed)
    check_adversarial_schedules(draw_int, draw_bool, steal_policy="cost",
                                layout=layout, steal_run_cap=4)


@pytest.mark.parametrize("seed", range(4))
def test_decode_layout_conformance_seeded(seed):
    draw_int, _ = _rng_draws(200 + seed)
    check_decode_layout_conformance(draw_int)


# ---------------------------------------------------------------------------
# mesh conformance (DESIGN.md §7): the cross-device dispatch must match the
# single-device no-drop oracle to float32 accumulation order
# (selfcheck.ORACLE_RTOL/ATOL) — for skewed/empty-expert routings, under
# arbitrarily stale advisories, and under adversarial steal plans — and a
# plan whose duplication is a power of two must normalize back to the clean
# dispatch bit for bit (odd duplication counts fall back to allclose:
# fl(3ŷ)/3 is not ŷ in float32, and no scheduler controls that).
#
# The emulation path (`emulate_mesh_dispatch`: same protocol, collectives
# replaced by stacking, certified bitwise-equal to the shard_map path by
# test_mesh_shard_map_matches_emulation) runs on one device, so the whole
# suite is tier-1; the real-collective path additionally runs via the D=1
# degenerate mesh, a skip-if-single-device multi-device case, and the
# forced-8-device subprocess selfcheck.
# ---------------------------------------------------------------------------

import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from repro.mesh_ws import (  # noqa: E402
    StealPlan,
    emulate_mesh_dispatch,
    expert_ffn_mesh_ws,
    expert_shard,
    route_local_pool_jax,
)
from repro.mesh_ws.selfcheck import ORACLE_ATOL, ORACLE_RTOL  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))


def _mesh_problem_from(draw_int):
    """Draw a mesh-sharded MoE problem: device count, expert shard, routing
    (uniform / hot-shard skewed / empty-expert), inputs and weights."""
    D = (2, 4)[draw_int(0, 1)]
    El = draw_int(1, 2)
    E = D * El
    T = draw_int(1, 10)
    k = draw_int(1, min(2, E))
    bt = (2, 4)[draw_int(0, 1)]
    seed = draw_int(0, 2**16)
    rng = np.random.RandomState(seed)
    shape = draw_int(0, 2)
    if shape == 0:        # uniform
        idx = np.stack([rng.choice(E, k, replace=False) for _ in range(T)])
    elif shape == 1:      # hot: mass on device 0's shard (the steal driver)
        hot = max(k, El)
        idx = np.stack([
            rng.choice(hot if rng.rand() < 0.75 else E, k, replace=False)
            for _ in range(T)
        ])
    else:                 # empty experts: restrict to a drawn subset
        alive = rng.choice(E, max(k, draw_int(k, E)), replace=False)
        idx = np.stack([rng.choice(alive, k, replace=False) for _ in range(T)])
    idx = idx.astype(np.int32)
    gates = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    gates /= gates.sum(1, keepdims=True)
    d, f = 4, 8
    x = rng.randn(T, d).astype(np.float32)
    wg = (0.1 * rng.randn(E, d, f)).astype(np.float32)
    wu = (0.1 * rng.randn(E, d, f)).astype(np.float32)
    wd = (0.1 * rng.randn(E, f, d)).astype(np.float32)
    return D, E, T, k, bt, idx, gates, x, wg, wu, wd


# Mesh-vs-oracle checks take the selfcheck's tolerance (float32
# accumulation order).  Bitwise checks stay where multiplicity
# normalization needs them: the shard_map path against its emulation, and
# duplicated execution against the clean run.


def assert_oracle_close(y, ref):
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=ORACLE_RTOL, atol=ORACLE_ATOL)


def _assert_mesh_coverage(em):
    """Every live tile of every device executed at least once."""
    for tail, mult in zip(em.tails, em.mult_total):
        n_live = int(np.asarray(tail).sum())
        if n_live:
            assert (np.asarray(mult)[:n_live] >= 1).all()


def check_mesh_oracle_conformance(draw_int):
    """Clean runs: the emulated mesh dispatch matches the no-drop oracle
    for any drawn routing/skew/device count."""
    D, E, T, k, bt, idx, gates, x, wg, wu, wd = _mesh_problem_from(draw_int)
    em = emulate_mesh_dispatch(
        x, idx, gates, wg, wu, wd, n_devices=D, bt=bt, n_programs=2,
    )
    ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)
    assert_oracle_close(em.y, ref)
    _assert_mesh_coverage(em)


def check_mesh_stale_advisories(draw_int):
    """Arbitrarily corrupt exchanged advisories (claiming load where none
    remains, hiding real load, everyone-idle): victim ranking degrades but
    the answer stays the clean run's — segment bounds come from the
    gathered head/tail snapshots, never from the advisory."""
    D, E, T, k, bt, idx, gates, x, wg, wu, wd = _mesh_problem_from(draw_int)
    adv = np.array([draw_int(0, T * k) for _ in range(D)], np.int32)
    em = emulate_mesh_dispatch(
        x, idx, gates, wg, wu, wd, n_devices=D, bt=bt, n_programs=2,
        adv_override=adv,
    )
    ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)
    assert_oracle_close(em.y, ref)
    _assert_mesh_coverage(em)


def check_mesh_adversarial_plans(draw_int, draw_bool):
    """Forced steal plans: a thief pulls a drawn segment of a victim's pool
    while the victim's donation accounting is adversarially *withheld*
    (``aware=False`` keeps the victim's full tails), so the segment
    executes on both devices — cross-device duplication only the
    multiplicity normalization can absorb.  A second thief may duplicate
    the same segment.  Total per-tile counts are 1/2/4 with an aware
    victim, 2/3 with an unaware one — with power-of-two counts the
    normalized result must equal the clean (duplicate-free) dispatch bit
    for bit; count 3 falls back to allclose (fl(3ŷ)/3 is not ŷ)."""
    D, E, T, k, bt, idx, gates, x, wg, wu, wd = _mesh_problem_from(draw_int)
    El = expert_shard(E, D)
    puts = [
        route_local_pool_jax(idx, gates, E, m * El, El, bt)
        for m in range(D)
    ]
    tails = [np.asarray(p.tail, np.int32) for p in puts]

    victim = draw_int(0, D - 1)
    thieves = [m for m in range(D) if m != victim]
    thief = thieves[draw_int(0, len(thieves) - 1)]
    double = draw_bool() and len(thieves) > 1
    thief2 = next(m for m in thieves if m != thief) if double else None
    aware = draw_bool()

    # drawn per-queue segment of the victim's live tiles; an aware victim
    # donates a whole suffix (it truncates its own tails to s_head), so the
    # thief's segment must run to the victim's tail or tiles would be lost
    s_head = np.zeros(El, np.int32)
    s_tail = np.zeros(El, np.int32)
    for q in range(El):
        if tails[victim][q]:
            s_head[q] = draw_int(0, int(tails[victim][q]) - 1)
            s_tail[q] = (tails[victim][q] if aware else
                         draw_int(int(s_head[q]), int(tails[victim][q])))
    take = int((s_tail - s_head).sum())

    def plan(m):
        new_tail = jnp.asarray(tails[m])
        stole = m == thief or (double and m == thief2)
        if m == victim and aware:
            new_tail = jnp.asarray(s_head)  # victim truncates to the donation
        return StealPlan(
            victim=jnp.int32(victim), stole=jnp.bool_(stole),
            s_head=jnp.asarray(s_head if stole else np.zeros(El, np.int32)),
            s_tail=jnp.asarray(s_tail if stole else np.zeros(El, np.int32)),
            new_tail=new_tail, take_tiles=jnp.int32(take if stole else 0),
        )

    em = emulate_mesh_dispatch(
        x, idx, gates, wg, wu, wd, n_devices=D, bt=bt, n_programs=2,
        plans_override=[plan(m) for m in range(D)],
    )
    ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)
    clean = emulate_mesh_dispatch(
        x, idx, gates, wg, wu, wd, n_devices=D, bt=bt, n_programs=2,
    )
    # aware victim: the stolen segment runs once (or per extra thief) on top
    # of nothing local -> counts {1, 2}; unaware: {2, 3} with a double thief
    mults = np.concatenate([np.asarray(m) for m in em.mult_total])
    power_of_two = ((mults & (mults - 1)) == 0).all()  # 0 and 2^k pass
    if aware and not double:
        _assert_mesh_coverage(em)
    if power_of_two:
        np.testing.assert_array_equal(np.asarray(em.y), np.asarray(clean.y))
    assert_oracle_close(em.y, ref)


def check_mesh_shard_map_conformance(draw_int):
    """The real-collective path (shard_map + ppermute/psum) over however
    many forced host devices this process has: bit-identical to the
    emulation, and within the oracle tolerance of the oracle."""
    import jax as _jax

    from repro.launch.mesh import make_expert_mesh

    avail = len(_jax.devices())
    if avail < 2:
        pytest.skip("single-device process; mesh CI job runs this at D=8")
    D, E, T, k, bt, idx, gates, x, wg, wu, wd = _mesh_problem_from(draw_int)
    while D > avail:
        D //= 2  # E = D_drawn · El stays divisible by any halving of D
    mesh = make_expert_mesh(E, D)
    y = expert_ffn_mesh_ws(
        idx, gates, x, wg, wu, wd, mesh=mesh, bt=bt, n_programs=2,
    )
    em = emulate_mesh_dispatch(
        x, idx, gates, wg, wu, wd, n_devices=D, bt=bt, n_programs=2,
    )
    ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(em.y))
    assert_oracle_close(y, ref)


if HAVE_HYPOTHESIS:

    @given(data=st.data())
    def test_mesh_oracle_conformance(data):
        check_mesh_oracle_conformance(
            lambda lo, hi: data.draw(st.integers(lo, hi))
        )

    @given(data=st.data())
    def test_mesh_stale_advisories(data):
        check_mesh_stale_advisories(
            lambda lo, hi: data.draw(st.integers(lo, hi))
        )

    @given(data=st.data())
    def test_mesh_adversarial_steal_plans(data):
        check_mesh_adversarial_plans(
            lambda lo, hi: data.draw(st.integers(lo, hi)),
            lambda: data.draw(st.booleans()),
        )

    @given(data=st.data())
    def test_mesh_shard_map_conformance(data):
        check_mesh_shard_map_conformance(
            lambda lo, hi: data.draw(st.integers(lo, hi))
        )


@pytest.mark.parametrize("seed", range(3))
def test_mesh_oracle_conformance_seeded(seed):
    draw_int, _ = _rng_draws(300 + seed)
    check_mesh_oracle_conformance(draw_int)


@pytest.mark.parametrize("seed", range(3))
def test_mesh_stale_advisories_seeded(seed):
    draw_int, _ = _rng_draws(400 + seed)
    check_mesh_stale_advisories(draw_int)


@pytest.mark.parametrize("seed", range(3))
def test_mesh_adversarial_plans_seeded(seed):
    draw_int, draw_bool = _rng_draws(600 + seed)
    check_mesh_adversarial_plans(draw_int, draw_bool)


def test_mesh_degenerate_single_device():
    """D=1 mesh: the full shard_map code path (ring of one, empty plan) on
    any host — equal to its emulation bitwise, and to the oracle within the
    oracle tolerance."""
    from repro.launch.mesh import make_expert_mesh

    draw_int, _ = _rng_draws(700)
    _, E, T, k, bt, idx, gates, x, wg, wu, wd = _mesh_problem_from(draw_int)
    mesh = make_expert_mesh(E, 1)
    y = expert_ffn_mesh_ws(
        idx, gates, x, wg, wu, wd, mesh=mesh, bt=bt, n_programs=2,
    )
    ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)
    em = emulate_mesh_dispatch(
        x, idx, gates, wg, wu, wd, n_devices=1, bt=bt, n_programs=2,
    )
    np.testing.assert_array_equal(np.asarray(y), np.asarray(em.y))
    assert_oracle_close(y, ref)


@pytest.mark.parametrize("seed", range(2))
def test_mesh_shard_map_conformance_seeded(seed):
    draw_int, _ = _rng_draws(800 + seed)
    check_mesh_shard_map_conformance(draw_int)


def test_mesh_selfcheck_subprocess_8_devices():
    """The acceptance gate on every host: re-exec with 8 forced host
    devices and assert the real shard_map dispatch within the oracle
    tolerance of the oracle, with cross-device steals observed."""
    p = subprocess.run(
        [sys.executable, "-m", "repro.mesh_ws.selfcheck",
         "--devices", "8", "--seeds", "2"],
        env=_ENV, capture_output=True, text=True, timeout=900, cwd=_ROOT,
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])

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
from conftest import (  # noqa: E402
    apply_rewind,
    drawn_rewind,
    resume_state,
    rng_draws,
)

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


@pytest.mark.parametrize("seed", range(4))
def test_fig7_layout_conformance_seeded(seed):
    draw_int, _ = rng_draws(seed)
    check_fig7_layout_conformance(draw_int)


@pytest.mark.parametrize("seed", range(4))
def test_pool_layout_conformance_seeded(seed):
    draw_int, _ = rng_draws(500 + seed)
    check_pool_layout_conformance(draw_int)


@pytest.mark.parametrize("steal_policy", ["cost", "scan"])
@pytest.mark.parametrize("layout", ["padded", "pool"])
@pytest.mark.parametrize("seed", range(2))
def test_adversarial_schedules_seeded(seed, layout, steal_policy):
    draw_int, draw_bool = rng_draws(100 + seed)
    check_adversarial_schedules(draw_int, draw_bool,
                                steal_policy=steal_policy, layout=layout)


@pytest.mark.parametrize("layout", ["padded", "pool"])
@pytest.mark.parametrize("seed", range(2))
def test_adversarial_schedules_halfrun_seeded(seed, layout):
    """The conformance contract survives run-length claims: padded and pool
    layouts stay slot-for-slot identical under steal_run_cap=4, including
    through drawn head-rewind relaunches."""
    draw_int, draw_bool = rng_draws(900 + seed)
    check_adversarial_schedules(draw_int, draw_bool, steal_policy="cost",
                                layout=layout, steal_run_cap=4)


@pytest.mark.parametrize("seed", range(4))
def test_decode_layout_conformance_seeded(seed):
    draw_int, _ = rng_draws(200 + seed)
    check_decode_layout_conformance(draw_int)

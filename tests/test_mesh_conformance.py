"""Mesh conformance (DESIGN.md §7): the cross-device dispatch must match the
single-device no-drop oracle to float32 accumulation order
(selfcheck.ORACLE_RTOL/ATOL) — for skewed/empty-expert routings, under
arbitrarily stale advisories, and under adversarial steal plans — and a
plan whose duplication is a power of two must normalize back to the clean
dispatch bit for bit (odd duplication counts fall back to allclose:
fl(3ŷ)/3 is not ŷ in float32, and no scheduler controls that).

The emulation path (`emulate_mesh_dispatch`: same protocol, collectives
replaced by stacking, certified bitwise-equal to the shard_map path by
test_mesh_shard_map_conformance) runs on one device, so the whole
suite is tier-1; the real-collective path additionally runs via the D=1
degenerate mesh, a skip-if-single-device multi-device case, and the
forced-8-device subprocess selfcheck.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

try:
    from hypothesis import given, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.mesh_ws import (  # noqa: E402
    StealPlan,
    emulate_mesh_dispatch,
    expert_ffn_mesh_ws,
    expert_shard,
    route_local_pool_jax,
)
from repro.mesh_ws.selfcheck import ORACLE_ATOL, ORACLE_RTOL  # noqa: E402
from repro.moe_ws.layer import expert_ffn_nodrop_ref  # noqa: E402

from conftest import rng_draws  # noqa: E402

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
    draw_int, _ = rng_draws(300 + seed)
    check_mesh_oracle_conformance(draw_int)


@pytest.mark.parametrize("seed", range(3))
def test_mesh_stale_advisories_seeded(seed):
    draw_int, _ = rng_draws(400 + seed)
    check_mesh_stale_advisories(draw_int)


@pytest.mark.parametrize("seed", range(3))
def test_mesh_adversarial_plans_seeded(seed):
    draw_int, draw_bool = rng_draws(600 + seed)
    check_mesh_adversarial_plans(draw_int, draw_bool)


def test_mesh_degenerate_single_device():
    """D=1 mesh: the full shard_map code path (ring of one, empty plan) on
    any host — equal to its emulation bitwise, and to the oracle within the
    oracle tolerance."""
    from repro.launch.mesh import make_expert_mesh

    draw_int, _ = rng_draws(700)
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
    draw_int, _ = rng_draws(800 + seed)
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

"""Grouped decode tiles: one WS decode tile per (slot, KV head).

A decode tile's q block holds the G = H / Hkv query heads that share its KV
head (zero-padded to whole float32 (8, 128) tiles), so each K/V block is
copied and swept once for all G rows.  These tests hold the grouped launch
to the dense oracle at G = 1, 3, 4 and 8, eagerly and under ``jit``, at the
fixed 64-position block and at the block :func:`decode_block` derives from
the cache; drill multiplicity on one grouped tile; and pin the launch's
structure — tasks, rounds bound, block length, q block — including that
multi-head attention (G = 1) keeps the one-row tiles and records it always
had.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from conftest import apply_rewind, resume_state, RewindSpec  # noqa: E402
from repro.pallas_ws import ragged  # noqa: E402
from repro.pallas_ws.queues import make_queue_state  # noqa: E402
from repro.pallas_ws.ragged import (  # noqa: E402
    decode_block,
    decode_q_block,
    decode_q_rows,
    decode_q_unblock,
    decode_rounds_bound,
    emit_decode_tasks_jax,
    ragged_decode_attention,
    ragged_decode_ref,
)
from repro.pallas_ws.kernel import run_ws_schedule  # noqa: E402
from repro.pallas_ws.tasks import (  # noqa: E402
    F_TID,
    OP_DECODE_TILE,
    emit_decode_tasks,
    multiplicity_divisor,
)

HKV, S, HD, BK = 8, 32, 8, 8
# dead slot, one token, exactly one block, one past a block, full capacity
LENGTHS = np.array([0, 1, BK, BK + 1, S])


# a float32 cache at hd 64 holds 512 positions in one derived block (128
# KiB), as a bf16 one at hd 128 does; its capacity holds two such blocks
EDGE_S, EDGE_HD = 1024, 64
EDGE_BK = decode_block(EDGE_S, EDGE_HD, jnp.float32)
# dead slot, one token, one short of the derived block, exactly one block,
# one past it, full capacity
EDGE_LENGTHS = np.array([0, 1, 511, 512, 513, EDGE_S])


def _inputs(H, Hkv=HKV, B=len(LENGTHS), seed=11, S=S, hd=HD):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    return q, k, v


@pytest.mark.parametrize(
    ("S", "hd", "dtype", "bk"),
    [
        (8192, 128, jnp.bfloat16, 512),   # both served configurations
        (8192, 128, jnp.float32, 256),
        (8192, 64, jnp.bfloat16, 1024),
        (300, 128, jnp.bfloat16, 300),    # capacity under the target
        (2112, 128, jnp.bfloat16, 64),    # 64 divides it, 512 does not
        (2048 + 256, 128, jnp.bfloat16, 256),
        (1000, 128, jnp.float32, 64),     # no power of two from 64 up divides it
    ],
    ids=["hd128-bf16", "hd128-f32", "hd64-bf16", "under-target", "S2112",
         "S2304", "fallback"],
)
def test_decode_block_rule(S, hd, dtype, bk):
    """About 128 KiB of K (or V) a block, capped at the capacity, and a
    whole number of blocks in the cache wherever 64 gave one: the rule
    adds no pad copy of the cache."""
    got = decode_block(S, hd, dtype)
    assert got == bk
    assert got * hd * jnp.dtype(dtype).itemsize <= ragged.DECODE_BLOCK_BYTES
    if S % 64 == 0 or S <= got:
        assert S % got == 0


@pytest.mark.parametrize("bk", [64, None], ids=["bk64", "derived"])
@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("H", [8, 24, 32, 64], ids=lambda h: f"G{h // HKV}")
def test_grouped_decode_matches_reference(H, mode, bk):
    """Lengths straddle the derived block's edge; ``bk=None`` is the rule."""
    assert EDGE_BK == 512
    q, k, v = _inputs(H, B=len(EDGE_LENGTHS), S=EDGE_S, hd=EDGE_HD)
    if mode == "eager":
        out = ragged_decode_attention(q, k, v, EDGE_LENGTHS, n_programs=4, bk=bk)
    else:
        out = jax.jit(
            lambda ln: ragged_decode_attention(q, k, v, ln, n_programs=4, bk=bk)
        )(jnp.asarray(EDGE_LENGTHS))
    ref = ragged_decode_ref(q, k, v, EDGE_LENGTHS)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(out[0]).max()) == 0.0  # the dead slot stays zero


def test_q_block_keeps_reference_head_order_and_zero_pad():
    """Row g of block kh is query head kh·G + g; pad rows are zero and
    unblocking drops them."""
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 24, HD)).astype(jnp.bfloat16)
    assert decode_q_rows(24, HKV) == (3, 8)
    qb = decode_q_block(q, HKV)
    assert qb.shape == (2, HKV, 8, HD) and qb.dtype == jnp.float32
    for kh in range(HKV):
        for g in range(3):
            np.testing.assert_array_equal(
                np.asarray(qb[:, kh, g]), np.asarray(q[:, kh * 3 + g], np.float32))
    assert float(jnp.abs(qb[:, :, 3:]).max()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(decode_q_unblock(qb, 24)), np.asarray(q, np.float32))
    assert decode_q_rows(8, 8) == (1, 1)     # multi-head: no pad rows
    assert decode_q_rows(64, 8) == (8, 8)    # a whole tile already
    assert decode_q_rows(80, 8) == (10, 16)


def test_grouped_tile_multiplicity_drill():
    """Rewind one queue's head by one slot (and wipe every program's local
    bound) after a finished launch, so exactly one (slot, KV head) tile runs
    a second time and adds a second copy of all its G rows.  Dividing by
    mult[tid] gives back the single-run output exactly.  Tiles sweep the
    derived block length."""
    H = 32
    G, G_pad = decode_q_rows(H, HKV)
    lengths = np.array([EDGE_S, EDGE_BK + 1, 3])
    q, k, v = _inputs(H, B=len(lengths), S=EDGE_S, hd=EDGE_HD)
    tasks = emit_decode_tasks(lengths, HKV, EDGE_BK, q_rows=G)
    state = make_queue_state(tasks, n_programs=4)
    qb = decode_q_block(q, HKV)

    def launch(**kw):
        return run_ws_schedule(state, qb, k, v, causal=False, bq=G_pad,
                               bk=EDGE_BK, steal=True, **kw)

    def normalized(res):
        div = multiplicity_divisor(tasks, res.mult, (len(lengths), HKV, G_pad))
        return decode_q_unblock(res.out / jnp.asarray(div)[..., None], H)

    res1 = launch()
    assert (res1.mult[: state.n_tasks] == 1).all()
    once = normalized(res1)

    resume_state(state, res1)
    head = np.asarray(res1.head)
    qv = int(np.argmax(head))  # a queue that held tasks
    rerun = int(state.tasks[qv, head[qv] - 1, F_TID])
    apply_rewind(state, RewindSpec(head_targets={qv: int(head[qv]) - 1},
                                   wiped=tuple(range(state.n_programs))))
    res2 = launch(out=res1.out, mult=jnp.asarray(res1.mult))

    expect = np.ones(state.n_tasks, np.int64)
    expect[rerun] = 2
    np.testing.assert_array_equal(np.asarray(res2.mult[: state.n_tasks]), expect)
    # the re-run tile's rows doubled before the divisor, not after it
    t = tasks[rerun]
    raw = np.asarray(res2.out)[t.b, t.h, :G]
    np.testing.assert_array_equal(raw, 2 * np.asarray(res1.out)[t.b, t.h, :G])
    np.testing.assert_array_equal(np.asarray(normalized(res2)), np.asarray(once))
    np.testing.assert_allclose(np.asarray(once),
                               np.asarray(ragged_decode_ref(q, k, v, lengths)),
                               rtol=1e-5, atol=1e-5)


def _spy_launch(monkeypatch):
    """Record what ragged_decode_attention hands the megakernel."""
    seen = {}
    real = ragged.run_ws_schedule

    def spy(state, q, k, v, **kw):
        seen.update(state=state, q_shape=q.shape, bq=kw["bq"], bk=kw["bk"],
                    rounds=kw["rounds"])
        return real(state, q, k, v, **kw)

    monkeypatch.setattr(ragged, "run_ws_schedule", spy)
    return seen


def test_traced_launch_builds_one_tile_per_kv_head(monkeypatch):
    """The serving cell's widths (4 slots, 32/8 heads of 128, 2048 bf16
    cache, 8 programs): B·Hkv = 32 tasks sweeping blocks of 512, so 20
    rounds; at the fixed 64-position block the same tiles needed 160
    rounds, and one tile per query head 128 tasks and 544 rounds."""
    B, H, Hkv, cap, hd = 4, 32, 8, 2048, 128
    assert decode_rounds_bound(B, Hkv, cap, 64, 8, 8, True) == 160
    assert decode_rounds_bound(B, H, cap, 64, 8, 8, True) == 544
    seen = _spy_launch(monkeypatch)
    q = jax.ShapeDtypeStruct((B, H, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, Hkv, cap, hd), jnp.bfloat16)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32)
    out = jax.eval_shape(ragged_decode_attention, q, kv, kv, ln)
    assert out.shape == (B, H, hd) and out.dtype == jnp.bfloat16
    assert seen["state"].n_tasks == B * Hkv
    assert seen["bk"] == 512 and seen["rounds"] == 20
    assert seen["q_shape"] == (B, Hkv, 8, hd) and seen["bq"] == 8


def test_multi_head_launch_keeps_one_row_tiles(monkeypatch):
    """G = 1: the q block is [B, H, 1, hd] with bq 1 (no pad rows), and the
    task records, host and traced, are the one-row records field for field."""
    H = HKV
    lengths = np.array([5, 0, S])
    expect = [
        [OP_DECODE_TILE, b, h, 0, 1, int(ln), tid, max(1, -(-int(ln) // BK))]
        for tid, (b, h, ln) in enumerate(
            (b, h, ln) for b, ln in enumerate(lengths) if ln > 0
            for h in range(H))
    ]
    host = np.stack([t.encode() for t in emit_decode_tasks(lengths, H, BK)])
    np.testing.assert_array_equal(host, np.asarray(expect, np.int32))

    records, live = emit_decode_tasks_jax(jnp.asarray(lengths), H, BK)
    rec = np.asarray(records)[np.asarray(live)]
    cols = [c for c in range(rec.shape[1]) if c != F_TID]  # traced tid: b·H + h
    np.testing.assert_array_equal(rec[:, cols], host[:, cols])

    seen = _spy_launch(monkeypatch)
    q, k, v = _inputs(H, B=len(lengths))
    _, stats = ragged_decode_attention(q, k, v, lengths, n_programs=4, bk=BK,
                                       return_stats=True)
    assert seen["q_shape"] == (len(lengths), H, 1, HD) and seen["bq"] == 1
    queued = np.asarray(seen["state"].tasks)
    queued = queued[queued[..., 0] >= 0]
    np.testing.assert_array_equal(queued[np.argsort(queued[:, F_TID])], host)
    assert stats.q_rows == 1 and stats.n_tasks == 2 * H


@pytest.mark.parametrize("H", [24, 32])
def test_stats_count_grouped_tiles(H):
    """Eager telemetry: q_rows is G and n_tasks counts (live slot, KV head)
    tiles, so n_tasks · q_rows is the live query heads."""
    q, k, v = _inputs(H)
    _, stats = ragged_decode_attention(q, k, v, LENGTHS, n_programs=4, bk=BK,
                                       return_stats=True)
    live = int((LENGTHS > 0).sum())
    assert stats.q_rows == H // HKV
    assert stats.n_tasks == live * HKV
    assert stats.n_tasks * stats.q_rows == live * H
    assert stats.mult_max == 1

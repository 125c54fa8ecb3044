"""Ragged attention front-ends for the work-stealing tile scheduler.

Variable sequence lengths are where a static grid hemorrhages tile-slots:
grid size is fixed by the *padded* length, so short sequences burn slots on
dead tiles while the one long sequence serializes on a single core.  These
front-ends emit only the live tiles (host-side, where lengths are concrete),
lay them out in the Fig. 7 queue arrays partitioned by batch row — the
natural serving placement, and the worst-case imbalance — and let the
megakernel's thieves flatten the skew.

``schedule="ws"`` steals; ``schedule="static"`` drains owner queues only
(same kernel, same cost accounting — an apples-to-apples makespan baseline).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.wstrace import spans

from .kernel import WSRunResult, run_ws_schedule
from .queues import make_queue_state, make_queue_state_jax, owner_queue_candidates, queue_costs
from .tasks import (
    OP_DECODE_TILE,
    emit_decode_tasks,
    emit_flash_tasks,
    multiplicity_divisor,
)

SCHEDULES = ("ws", "static")
_F32_SUBLANES = 8  # rows of one float32 (8, 128) tile on the chip
DECODE_BLOCK_BYTES = 128 * 2**10  # one K (or V) block a decode tile copies
_DECODE_BK_MIN = 64  # the fixed block length the rule falls back to


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def decode_block(S: int, hd: int, dtype) -> int:
    """KV positions a decode tile copies and sweeps at a time, from the
    cache's shape and dtype alone: one K (or V) block of about
    ``DECODE_BLOCK_BYTES`` (512 positions at hd 128 bf16, 256 in float32,
    1024 at hd 64 bf16), capped at the capacity ``S``.

    Past the cap it is the largest power of two, at most that target, that
    divides ``S``, so the cache is never padded to a whole number of
    blocks; below 64 it falls back to 64 positions and pads as before.  A
    longer block means fewer lockstep rounds (the clock counts blocks) and
    fewer copies to wait on, at the price of a masked tail of under one
    block per tile."""
    target = max(1, DECODE_BLOCK_BYTES // (hd * jnp.dtype(dtype).itemsize))
    if S <= target:
        return max(1, S)
    bk = 1 << (target.bit_length() - 1)
    while S % bk:
        bk //= 2
    return bk if bk >= _DECODE_BK_MIN else _DECODE_BK_MIN


@dataclass
class RaggedStats:
    """Scheduling telemetry for one launch (units: kv-block tile-slots).

    ``slots_scanned``/``scan_per_extraction`` are the victim-scan traffic
    counters of DESIGN.md §3.6: task-slot probes issued by the extraction
    path, total and per successful claim."""

    schedule: str
    steal_policy: str
    n_tasks: int
    makespan: int
    total_work: int
    wasted_slots: int
    steals: int
    mult_max: int
    slots_scanned: int
    extractions: int
    scan_per_extraction: float
    queue_loads: list
    q_rows: int = 1  # query heads a tile carries (G on a grouped decode launch)
    trace: object = None  # WSTrace when the launch recorded event rings

    @classmethod
    def from_run(cls, schedule, state, res: WSRunResult,
                 steal_policy: str = "cost", q_rows: int = 1) -> "RaggedStats":
        trace = None
        if res.events is not None:
            from repro.wstrace.trace import WSTrace

            trace = WSTrace.from_run(state, res)
        return cls(
            schedule=schedule,
            steal_policy=steal_policy,
            n_tasks=state.n_tasks,
            makespan=res.makespan,
            total_work=res.total_work,
            wasted_slots=res.wasted_slots,
            steals=int(res.steals.sum()),
            mult_max=int(res.mult[: max(1, state.n_tasks)].max()) if state.n_tasks else 0,
            slots_scanned=res.slots_scanned,
            extractions=res.extractions,
            scan_per_extraction=round(res.scan_per_extraction, 3),
            queue_loads=[int(c) for c in queue_costs(state)],
            q_rows=q_rows,
            trace=trace,
        )


def _pad_to(x, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _check_drained(state, res: WSRunResult) -> None:
    if state.n_tasks and not (res.mult[: state.n_tasks] >= 1).all():
        missing = int((res.mult[: state.n_tasks] == 0).sum())
        raise RuntimeError(
            f"scheduler under-provisioned: {missing}/{state.n_tasks} tasks "
            "never executed (rounds bound too small?)"
        )


def ragged_flash_attention(
    q,
    k,
    v,
    lengths,
    *,
    causal: bool = True,
    schedule: str = "ws",
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    n_programs: int = 8,
    partition: str = "batch",
    bq: int = 32,
    bk: int = 32,
    return_stats: bool = False,
    trace: bool = False,
):
    """Ragged flash attention via the persistent WS megakernel.

    q: [B, H, S, hd]; k, v: [B, Hkv, S, hd]; lengths: [B] host ints.
    Rows at or past ``lengths[b]`` return 0.  Output matches the dense
    length-masked reference exactly (up to fp32 accumulation order).
    ``trace=True`` records event rings and attaches the decoded
    :class:`~repro.wstrace.trace.WSTrace` to the returned stats.
    """
    assert schedule in SCHEDULES, schedule
    B, H, S, hd = q.shape
    lengths = np.asarray(lengths, dtype=np.int64)
    assert lengths.shape == (B,) and lengths.max(initial=0) <= S
    bq = min(bq, max(1, S))
    bk = min(bk, max(1, S))

    tasks = emit_flash_tasks(lengths, H, bq, bk, causal=causal)
    state = make_queue_state(tasks, n_programs, partition=partition)
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    res = run_ws_schedule(
        state, qp, kp, vp,
        causal=causal, bq=bq, bk=bk,
        steal=(schedule == "ws"), steal_policy=steal_policy,
        steal_run_cap=steal_run_cap if schedule == "ws" else 1,
        trace=trace, name="ws_flash",
    )
    _check_drained(state, res)
    div = multiplicity_divisor(tasks, res.mult, (B, H, qp.shape[2]))
    out = (res.out / jnp.asarray(div)[..., None])[:, :, :S].astype(q.dtype)
    if return_stats:
        return out, RaggedStats.from_run(schedule, state, res, steal_policy)
    return out


def emit_decode_tasks_jax(lengths, n_heads: int, bk: int, q_rows: int = 1):
    """Traced twin of :func:`repro.pallas_ws.tasks.emit_decode_tasks`: the
    full static ``[B, n_heads]`` candidate grid with live masks
    ``lengths > 0`` instead of a host loop that skips dead rows.  ``tid =
    b·n_heads + h`` is static, so the multiplicity buffer is provisioned at
    ``B·n_heads`` and dead slots simply stay 0.  Returns ``(records [B,
    n_heads, TASK_WIDTH], live [B, n_heads])`` ready for
    :func:`owner_queue_candidates`.
    """
    ln = jnp.asarray(lengths).astype(jnp.int32)
    B = ln.shape[0]
    H = n_heads
    cost = jnp.maximum(1, -(-ln // bk))             # kv blocks, >= 1 like host
    b_ids = jnp.arange(B, dtype=jnp.int32)[:, None]
    h_ids = jnp.arange(H, dtype=jnp.int32)[None, :]
    shape = (B, H)
    records = jnp.stack(
        [
            jnp.full(shape, OP_DECODE_TILE, jnp.int32),
            jnp.broadcast_to(b_ids, shape),
            jnp.broadcast_to(h_ids, shape),
            jnp.zeros(shape, jnp.int32),            # q_start
            jnp.full(shape, q_rows, jnp.int32),     # q_len
            jnp.broadcast_to(ln[:, None], shape),   # kv_end
            b_ids * H + h_ids,                      # tid (static, unique)
            jnp.broadcast_to(cost[:, None], shape),
        ],
        axis=-1,
    )
    live = jnp.broadcast_to(ln[:, None] > 0, shape)
    return records, live


def decode_rounds_bound(B: int, n_heads: int, S: int, bk: int,
                        n_queues: int, n_programs: int, steal: bool,
                        steal_run_cap: int = 1) -> int:
    """Static worst-case lockstep rounds for a traced decode launch of
    ``n_heads`` tiles per slot (one per KV head), every slot at full cache
    length ``S`` — the trace-time stand-in for
    :func:`repro.pallas_ws.kernel.default_rounds` (cost unit: kv blocks).

    Stealing: Graham's ``ceil(total/P) + max_cost`` with no scan slack —
    both steal policies claim whenever work exists (DESIGN.md §3.6); with
    half-run steals the tail term grows to ``steal_run_cap · max_cost``.
    No-steal: run compression drains owners in their first idle round."""
    blocks = max(1, _cdiv(S, bk))
    if steal:
        return (_cdiv(B * n_heads * blocks, n_programs)
                + max(1, steal_run_cap) * blocks)
    from .kernel import STATIC_COMPRESSED_ROUNDS

    return STATIC_COMPRESSED_ROUNDS


def decode_q_rows(n_heads: int, n_kv_heads: int) -> tuple[int, int]:
    """``(G, G_pad)``: the query heads one decode tile carries (those that
    share its KV head) and the rows of its q block.  The float32 q block is
    padded with zero rows to whole (8, 128) tiles; multi-head attention
    (G = 1) keeps its single row."""
    assert n_heads % n_kv_heads == 0, (n_heads, n_kv_heads)
    G = n_heads // n_kv_heads
    return G, (G if G == 1 else _cdiv(G, _F32_SUBLANES) * _F32_SUBLANES)


def decode_q_block(q, n_kv_heads: int):
    """q [B, H, hd] -> the float32 decode q block [B, Hkv, G_pad, hd]: row
    g of block kh is query head ``kh·G + g`` (``ragged_decode_ref``'s head
    order), pad rows are zero."""
    B, H, hd = q.shape
    G, G_pad = decode_q_rows(H, n_kv_heads)
    qb = q.astype(jnp.float32).reshape(B, n_kv_heads, G, hd)
    return _pad_to(qb, 2, G_pad)


def decode_q_unblock(out, n_heads: int):
    """Inverse of :func:`decode_q_block` on a tile output [B, Hkv, G_pad,
    hd]: drop the pad rows, back to [B, H, hd]."""
    B, Hkv, _, hd = out.shape
    return out[:, :, : n_heads // Hkv].reshape(B, n_heads, hd)


def ragged_decode_attention(
    q,
    k,
    v,
    lengths,
    *,
    schedule: str = "ws",
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    n_programs: int = 8,
    partition: str = "batch",
    bk: int | None = None,
    return_stats: bool = False,
    trace: bool = False,
):
    """Single-token decode over ragged KV caches: q [B, H, hd] attends slots
    ``[0, lengths[b])`` of k, v [B, Hkv, S, hd].  Dead rows (length 0)
    return 0.

    One tile per live (slot, KV head): its q block holds the G = H / Hkv
    query heads that share the KV head (:func:`decode_q_block`), so each
    K/V block is copied and swept once for all G rows; multi-head
    attention is the G = 1 case.  The multiplicity divisor is per (slot,
    KV head).  ``bk=None`` sweeps blocks of :func:`decode_block` (512
    positions of a bf16 cache at hd 128).

    Accepts traced ``lengths`` (the jitted serving decode): queue
    construction switches to the fixed-shape traced Put — the full
    [B, Hkv] candidate grid live-masked by ``lengths > 0``, compacted on
    device — with the static worst-case rounds bound, and telemetry
    (``return_stats``) stays eager-only.
    """
    assert schedule in SCHEDULES, schedule
    B, H, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G, G_pad = decode_q_rows(H, Hkv)
    bk = decode_block(S, hd, k.dtype) if bk is None else min(bk, max(1, S))
    steal = schedule == "ws"
    traced = isinstance(lengths, jax.core.Tracer)
    if traced and return_stats:
        raise ValueError("return_stats needs concrete telemetry; call eagerly")
    if traced and trace:
        raise ValueError("trace needs concrete event rings; call eagerly")

    with jax.named_scope(spans.WS_PUT):
        if traced:
            n_queues = n_programs  # partition="batch": queue = b % n_programs
            records, live = emit_decode_tasks_jax(lengths, Hkv, bk, q_rows=G)
            cand, cand_live = owner_queue_candidates(records, live, n_queues)
            state = make_queue_state_jax(cand, cand_live, n_programs, n_tasks=B * Hkv)
            rounds = decode_rounds_bound(
                B, Hkv, S, bk, n_queues, n_programs, steal,
                steal_run_cap=steal_run_cap if steal else 1,
            )
            tasks = None
        else:
            lengths = np.asarray(lengths, dtype=np.int64)
            assert lengths.shape == (B,) and lengths.max(initial=0) <= S
            tasks = emit_decode_tasks(lengths, Hkv, bk, q_rows=G)
            state = make_queue_state(tasks, n_programs, partition=partition)
            rounds = None
        # float32 q rows keep the block aligned to the chip's (8, 128) tiling
        # (a packed bf16 row is half a tile); the tile body computes in
        # float32 either way
        qb = decode_q_block(q, Hkv)
        kp = _pad_to(k, 2, bk)
        vp = _pad_to(v, 2, bk)
    with jax.named_scope(spans.WS_KERNEL):
        res = run_ws_schedule(
            state, qb, kp, vp,
            causal=False, bq=G_pad, bk=bk,
            steal=steal, steal_policy=steal_policy,
            steal_run_cap=steal_run_cap if steal else 1, rounds=rounds,
            trace=trace, name="ws_decode",
        )
    with jax.named_scope(spans.WS_PUT):
        if traced:
            # tid = b·Hkv + kh is static: the divisor is just the reshaped
            # multiplicity buffer (dead slots: mult 0 -> divisor 1, output 0)
            div = jnp.maximum(res.mult.reshape(B, Hkv), 1).astype(jnp.float32)
            out = res.out / div[:, :, None, None]
            return decode_q_unblock(out, H).astype(q.dtype)
        _check_drained(state, res)
        div = multiplicity_divisor(tasks, res.mult, (B, Hkv, G_pad))
        out = res.out / jnp.asarray(div)[..., None]
        out = decode_q_unblock(out, H).astype(q.dtype)
    if return_stats:
        return out, RaggedStats.from_run(schedule, state, res, steal_policy,
                                         q_rows=G)
    return out


# ---------------------------------------------------------------------------
# dense oracles


def ragged_attention_ref(q, k, v, lengths, *, causal: bool = True):
    """O(S^2) length-masked reference; rows >= lengths[b] are zero."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * hd**-0.5
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    ln = jnp.asarray(np.asarray(lengths))[:, None, None, None]
    mask = (kpos < ln) & (qpos < ln)
    if causal:
        mask &= qpos >= kpos
    s = jnp.where(mask, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    pr = jnp.where(jnp.isnan(pr), 0.0, pr)  # fully-masked rows -> 0
    out = jnp.einsum("bhqk,bhkd->bhqd", pr, vf)
    row_live = (qpos[:, 0][None, None, :, None] < ln)
    return jnp.where(row_live, out, 0.0).astype(q.dtype)


def ragged_decode_ref(q, k, v, lengths):
    """Decode oracle: q [B, H, hd] attends kv slots [0, lengths[b])."""
    B, H, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = H // Hkv
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), kf) * hd**-0.5
    kpos = jnp.arange(S)[None, None, :]
    ln = jnp.asarray(np.asarray(lengths))[:, None, None]
    s = jnp.where(kpos < ln, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    pr = jnp.where(jnp.isnan(pr), 0.0, pr)
    out = jnp.einsum("bhs,bhsd->bhd", pr, vf)
    return jnp.where(ln > 0, out, 0.0).astype(q.dtype)

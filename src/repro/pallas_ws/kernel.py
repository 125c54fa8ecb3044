"""Persistent-grid Pallas megakernel: fence-free work-stealing tile scheduler.

One ``pallas_call`` runs a whole tile workload.  Grid is ``(rounds,
n_programs)`` with the program dim innermost, so the execution order is
round-major: every program performs at most one Take/Steal per round, and a
program whose current task costs ``c`` tile-slots stays busy (``clock[p] >
r``) for the next ``c`` rounds.  This block-granular lockstep is the
deterministic serialization of P persistent cores running the same loop in
real time — the same modeling device as :mod:`repro.sched`'s lockstep
rounds, now *inside* one kernel over HBM-resident queue arrays.

The extraction protocol is WS-WMULT (paper Fig. 7) verbatim, on the
:mod:`repro.pallas_ws.queues` layout:

    h = max(local_head[p, v], head[v])          # inlined RMaxRead
    if tasks[v, h, OP] != ⊥:                    # lines 12-13
        head[v] = h + 1                         # plain write (RMaxWrite,
        local_head[p, v] = h + 1                #  read elided)
        taken[v, h] = p                         # announcement
        execute tile; mult[tid] += 1            # idempotent-accumulate

Plain loads and stores only — no CAS, no semaphore, no fence.  A stale
``head`` write may rewind a queue and hand the same tile to two programs;
the tile write is an *accumulate* and ``mult`` counts executions, so the
caller divides the duplicates back out (see ``tasks.multiplicity_divisor``
for attention, ``moe_ws.dispatch.row_divisor`` for expert tiles).  Each
program's ``local_head`` row is strictly increasing, so no program
re-extracts a slot it already extracted — the paper's weak multiplicity,
verified on-device by tests/test_pallas_ws.py.

Victim selection (DESIGN.md §3.6) is a *policy*, separate from the claim
protocol above, because it needs no synchronization at all — a victim chosen
from arbitrarily stale data costs at most wasted probes, never correctness:

* ``steal_policy="cost"`` (default) — O(1) task-slot loads per round.  An
  idle program probes its own queue, and on ⊥ picks the victim from the
  heads/tails plus the plain-write advisory ``remaining[q]`` cost summary
  (the first queue of maximal remaining work among those whose head view
  sits below their tail — one scalar pass over the queue metadata), then
  probes exactly one slot.  The
  advisory is updated best-effort by whoever claims a slot (plain read +
  plain write — stale values only mis-rank victims); the ``head < tail``
  mask alone guarantees an idle program claims *some* task whenever any
  queue is non-empty, which is what the tightened Graham rounds bound needs.
* ``steal_policy="scan"`` — the PR-1 p-relative sequential scan over every
  queue, kept for apples-to-apples comparison (`benchmarks/steal_policy.py`).

``scanned[p]`` counts the task-slot probes program ``p`` issued (the
op-field loads of the extraction scan; metadata vectors — head, tail,
remaining — are not slots).  Slot loads are guarded: a probe whose index is
out of range (``h >= capacity``, or ``h >= tail[v]`` on the pool layout)
never issues, so drained queues cost nothing per scan.

Everything scheduler-side is **task-family agnostic**: :func:`ws_try_extract`
(the protocol), :func:`ws_account` (clock/work/steal/multiplicity
bookkeeping), and :func:`launch_ws_grid` (queue-array plumbing around
``pallas_call``) never inspect the operand fields of a task record.  A family
plugs in by supplying an ``execute(rec, pure_refs, out_ref)`` body, where
``rec(field)`` reads one int32 field of the claimed task record — the
attention body lives here (:func:`run_ws_schedule`), the MoE expert-FFN body
in :mod:`repro.moe_ws.expert_kernel`.

Memory spaces (DESIGN.md §3.7).  Every array enters the launch in HBM
(``memory_space=pltpu.HBM``).  The scheduler state — task records, tails, pool
offsets, stage-open rounds and the nine mutable queue/telemetry arrays — is
copied once into SMEM scratch at the launch's first grid cell and back at
its last, so the protocol above is scalar SMEM loads and stores.  Family
operands and outputs stay in HBM: each tile copies the blocks it needs into
VMEM scratch and writes its accumulated output block back, so fast-memory
use does not grow with slots × capacity.  The DMA semaphores those copies
wait on belong to one program's own copy engine; nothing shared between
programs is ever synchronized.  The grid's dimensions are sequential
("arbitrary"): the programs share queue state, so no dimension may be split
across cores.

Pallas runs the kernel interpreted on a CPU backend and compiled on a TPU
(:func:`repro.interpret.interpret_mode`).  The interpreter executes grid
cells sequentially, which makes single-launch runs sequentially-exact
(mult == 1 everywhere) — duplicates are exercised by seeding adversarial
``head``/``local_head`` snapshots, mirroring the §7 drills of the host
tests.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..interpret import interpret_mode
from ..wstrace.ring import (
    EV_COST,
    EV_KIND,
    EV_MULT,
    EV_OP,
    EV_PROG,
    EV_RUN,
    EV_QUEUE,
    EV_ROUND,
    EV_SLOT,
    EV_TID,
    EV_VICTIM,
    EVENT_WIDTH,
    KIND_STEAL_COST,
    KIND_STEAL_REMOTE,
    KIND_STEAL_SCAN,
    KIND_TAKE,
)
from .queues import QueueState, queue_costs
from .tasks import (
    BOTTOM,
    F_B,
    F_COST,
    F_H,
    F_KV,
    F_OP,
    F_QL,
    F_QS,
    F_TID,
    TASK_WIDTH,
)

NEG_INF = -1e30

STEAL_POLICIES = ("cost", "scan")

# Scoped VMEM the compiled megakernel may use for its per-tile operand
# blocks (v5e has 128 MiB per core; the compiler's default scope is 16 MiB).
# The expert family's whole-expert weight blocks are what needs the room.
VMEM_LIMIT_BYTES = 100 * 2**20

# Order of the mutable (input-output aliased) queue/telemetry arrays every
# family launch carries: head, local_head, taken, remaining, clock, work,
# steals, scanned, mult, then the family outputs.  ``launch_ws_grid`` owns
# this layout.  A multi-output launch (``out`` given as a tuple — the
# unified engine step) carries one slot per output, and a traced launch
# (``trace=True``) appends two more — the event rings and their per-program
# cursors (``repro.wstrace.ring``) — after the outputs.  The scheduler
# arrays are passed flat (``local_head`` as ``[P·n_queues]``, ``taken`` and
# ``tasks`` row-major) so their SMEM mirrors are 1-D word arrays.
N_SCHED_MUTABLE = 9   # head..mult, before the family outputs


def _slot_row(pool_off_ref, v, s, *, pool: bool, capacity: int):
    """Flat record row of queue-slot ``(v, s)``.

    Dense layout: queue ``v`` owns rows ``[v·capacity, (v+1)·capacity)``.
    Pool layout: queue ``v``'s slots are the contiguous pool segment
    starting at ``pool_off[v]``, so the same logical slot is row
    ``pool_off[v] + s``.
    """
    if pool:
        return pool_off_ref[v] + s
    return v * capacity + s


def _slot_field(tasks_ref, pool_off_ref, v, s, field, *, pool: bool,
                capacity: int):
    """Read one int32 field of the task record at queue-slot ``(v, s)``
    from the flat ``[rows · TASK_WIDTH]`` record array."""
    row = _slot_row(pool_off_ref, v, s, pool=pool, capacity=capacity)
    return tasks_ref[row * TASK_WIDTH + field]


def _probe_slot(
    tasks_ref, pool_off_ref, tail_ref, v, h, want,
    *, pool: bool, capacity: int,
):
    """Guarded ⊥-probe of slot ``(v, h)``: load the op field only when
    ``want`` and the index is meaningful — ``h < capacity`` on the dense
    layout (the clamp-read fix: a drained queue's probe never issues), and
    ``h < tail[v]`` on the pool layout (a read past tail would land in the
    *next* queue's pool segment, so it must never issue at all).

    Returns ``(op, issued)`` with ``op == BOTTOM`` when the load was
    suppressed; ``issued`` feeds the ``scanned`` slot-read counter.
    """
    in_range = (h < tail_ref[v]) if pool else (h < capacity)
    issue = want & in_range
    op = jax.lax.cond(
        issue,
        lambda: _slot_field(tasks_ref, pool_off_ref, v, h, F_OP, pool=pool,
                            capacity=capacity),
        lambda: jnp.int32(BOTTOM),
    )
    return op, issue.astype(jnp.int32)


def ws_try_extract(
    r, p, head_ref, local_head_ref, tail_ref, remaining_ref, tasks_ref,
    clock_ref, pool_off_ref=None, stage_ref=None,
    *, n_queues: int, capacity: int, steal: bool,
    steal_policy: str = "cost", pool: bool = False, steal_run_cap: int = 1,
):
    """One Take/Steal attempt of WS-WMULT for program ``p`` at round ``r``.

    Probes its own queue first; when stealing, picks further victims by the
    configured policy and claims the first live slot with plain writes only.
    Returns ``(found, queue, slot, run, slots_read)``; no-op (found=False)
    while the program's clock says it is still busy with its previous tile.

    ``stage_ref`` (optional, [n_queues] int32): per-queue open rounds for
    stage-gated launches (the unified engine step) — a queue is invisible to
    probes and to the victim mask until ``stage_ref[q] <= r``.  Gating is a
    pure *input* (no cross-program signalling): the stage windows are sized
    on the host by the Graham bound so every task of stage ``s`` has
    finished before ``stage_ref`` opens stage ``s+1`` (DESIGN.md §5).

    ``steal_run_cap > 1`` (cost policy only) amortizes Steal probes: one
    successful victim probe claims ``min(ceil(rem/2), cap)`` *contiguous*
    slots — the half-run rule of ``mesh_ws/steal`` brought on device — with
    a single head-bump past the whole run.  ``rem = tail[v] - h`` is exact
    with respect to the tails (Put happens before launch, so tails are a
    static input); only *head* staleness can inflate it, and a stale head
    means the run's slots were already claimed once — re-executing them is
    a multiplicity event, never a correctness event (every claimed slot
    ``< tail[v]`` holds a live record by the compacted-prefix invariant, so
    the single ⊥-probe of the run's first slot certifies the whole run).
    ``run`` is 1 for Takes and for the default ``steal_run_cap=1`` lowering,
    which stays bit-identical to the per-slot claim.
    """
    assert steal_policy in STEAL_POLICIES, steal_policy
    assert steal_run_cap >= 1, steal_run_cap
    assert steal_run_cap == 1 or steal_policy == "cost", (
        "half-run claims are a cost-policy amortization"
    )
    idle = clock_ref[p] <= r
    probe = functools.partial(
        _probe_slot, tasks_ref, pool_off_ref, tail_ref,
        pool=pool, capacity=capacity,
    )

    def stage_open(v):
        return jnp.bool_(True) if stage_ref is None else stage_ref[v] <= r

    def lh(v):
        # flat [P·n_queues] local bounds: program p's row starts at p·n_queues
        return p * n_queues + v

    def claim_writes(v, h):
        head_ref[v] = h + 1              # plain write — no CAS
        local_head_ref[lh(v)] = h + 1    # persistent local bound

    def scan_extract():
        """PR-1 policy: p-relative sequential scan over every queue."""

        def scan_one(j, carry):
            found, fq, fs, nread = carry
            v = jax.lax.rem(p + j, n_queues)
            h = jnp.maximum(local_head_ref[lh(v)], head_ref[v])  # RMaxRead
            op, issued = probe(v, h, (~found) & stage_open(v))
            live = op != BOTTOM
            claim = (~found) & live

            @pl.when(claim)
            def _claim():
                claim_writes(v, h)

            return (
                found | live,
                jnp.where(claim, v, fq),
                jnp.where(claim, h, fs),
                nread + issued,
            )

        n_scan = n_queues if steal else 1
        zero = (jnp.bool_(False), jnp.int32(0), jnp.int32(0), jnp.int32(0))
        found, fq, fs, nread = jax.lax.fori_loop(0, n_scan, scan_one, zero)
        return found, fq, fs, jnp.int32(1), nread

    def cost_extract():
        """O(1) policy: own-queue probe, then cost-aware victim argmax."""
        own = jax.lax.rem(p, n_queues)
        h0 = jnp.maximum(local_head_ref[lh(own)], head_ref[own])  # RMaxRead
        op0, issued0 = probe(own, h0, stage_open(own))
        own_live = op0 != BOTTOM

        @pl.when(own_live)
        def _take():
            claim_writes(own, h0)

        if not steal:
            return own_live, own, h0, jnp.int32(1), issued0

        # Victim selection from plain scalar reads of the queue metadata —
        # no slot loads.  The `head < tail` mask is exact for any state the
        # protocol can reach (head never passes tail), so an idle program
        # always finds a claimable victim when one exists; the advisory
        # only *ranks* the stealable queues, so arbitrary staleness costs
        # ordering, never progress (max(adv, 1) keeps zeroed advisories
        # claimable).  The strict `>` keeps the first maximal queue, the
        # victim an argmax over the score vector picks.
        def rank(j, carry):
            best, bv, bh = carry
            hj = jnp.maximum(local_head_ref[lh(j)], head_ref[j])
            ok = (hj < tail_ref[j]) & stage_open(j)
            score = jnp.where(ok, jnp.maximum(remaining_ref[j], 1), 0)
            better = score > best
            return (jnp.where(better, score, best), jnp.where(better, j, bv),
                    jnp.where(better, hj, bh))

        zero3 = (jnp.int32(0), jnp.int32(0), jnp.int32(0))
        best, v, h = jax.lax.fori_loop(0, n_queues, rank, zero3)
        can = (~own_live) & (best > 0)
        op, issued = probe(v, h, can)
        live = can & (op != BOTTOM)

        if steal_run_cap == 1:
            @pl.when(live)
            def _steal():
                claim_writes(v, h)

            take = jnp.int32(1)
        else:
            # Half-run claim: bump the head past ceil(rem/2) slots (capped)
            # in one plain write per bound.  `rem >= 1` whenever `live`
            # (the victim passed the `heads < tails` mask), and every slot
            # of [h, h + take) is below tail[v], so the run is made of live
            # records certified by the single probe above.
            rem = tail_ref[v] - h
            take = jnp.clip((rem + 1) // 2, 1, steal_run_cap).astype(jnp.int32)

            @pl.when(live)
            def _steal():
                head_ref[v] = h + take             # plain write — no CAS
                local_head_ref[lh(v)] = h + take   # persistent local bound

        found = own_live | live
        fq = jnp.where(own_live, own, v)
        fs = jnp.where(own_live, h0, h)
        run = jnp.where(live, take, 1).astype(jnp.int32)
        return found, fq, fs, run, issued0 + issued

    zero = (jnp.bool_(False), jnp.int32(0), jnp.int32(0), jnp.int32(1),
            jnp.int32(0))
    body = scan_extract if steal_policy == "scan" else cost_extract
    return jax.lax.cond(idle, body, lambda: zero)


def ws_account(
    r, p, fq, fs, tid, cost,
    taken_ref, remaining_ref, clock_ref, work_ref, steals_ref, mult_ref,
    pool_off_ref=None,
    *, n_queues: int, capacity: int, pool: bool = False,
    advisory: bool = True,
):
    """Post-execution bookkeeping shared by every task family: announcement
    row, multiplicity counter, work/steal telemetry, lockstep clock bump,
    and the best-effort advisory decrement (plain read + plain write — a
    lost or stale update mis-ranks future victims, nothing more).

    ``advisory=False`` suppresses the per-extraction advisory write so a
    caller that drains a whole run inside one grid cell (round compression)
    can coalesce the updates into one plain write for the run — the clamp
    commutes (``max(max(r-c1,0)-c2,0) == max(r-c1-c2,0)`` for nonnegative
    costs), so the coalesced value is bit-identical."""
    mult_ref[tid] = mult_ref[tid] + 1
    taken_ref[_slot_row(pool_off_ref, fq, fs, pool=pool, capacity=capacity)] = p
    if advisory:
        remaining_ref[fq] = jnp.maximum(remaining_ref[fq] - cost, 0)
    work_ref[p] = work_ref[p] + cost
    own = jax.lax.rem(p, n_queues)
    steals_ref[p] = steals_ref[p] + jnp.where(fq != own, 1, 0)
    clock_ref[p] = jnp.maximum(clock_ref[p], r) + cost


def _generic_ws_kernel(
    *refs,
    n_pure: int,
    n_smem_pure: int,
    n_outs: int,
    pool: bool,
    staged: bool,
    trace: bool,
    **cell_kw,
):
    """Launch shell: SMEM mirrors of the scheduler state around the
    per-cell protocol (:func:`_ws_cell`).

    Ref layout (positional, fixed by :func:`launch_ws_grid`), every array in
    HBM: the mutable inputs (9 flat scheduler arrays, ``n_outs`` family
    outputs, + event rings and cursors when ``trace``), the scheduler's
    read-only inputs (flat task records, tails, the pool segment offsets
    when ``pool``, the stage-open rounds when ``staged``), the
    ``n_smem_pure`` family inputs the body reads as scalars, the ``n_pure``
    family inputs it copies blocks of; then the aliased outputs in the order
    of the mutable inputs; then the SMEM scratch — one mirror per scheduler
    array (mutable, then read-only) and per scalar family input, plus the
    cursor mirror and one event-record row when ``trace``.

    The first grid cell copies the scheduler state into its SMEM mirrors
    (reading the mutable arrays through their aliased output refs, which
    hold the input values); the last cell copies the mutable mirrors back.
    In between, every cell is plain scalar SMEM loads and stores.
    """
    n_mut = N_SCHED_MUTABLE + n_outs + (2 if trace else 0)
    n_ro = 2 + int(pool) + int(staged) + n_smem_pure
    n_in = n_mut + n_ro + n_pure
    ro_in = refs[n_mut: n_mut + n_ro]
    hbm_pure = refs[n_mut + n_ro: n_in]
    outs = refs[n_in: n_in + n_mut]
    smem = refs[n_in + n_mut:]
    sched_mut = smem[:N_SCHED_MUTABLE]
    ro = smem[N_SCHED_MUTABLE: N_SCHED_MUTABLE + n_ro]
    hbm_mut = outs[:N_SCHED_MUTABLE]
    if trace:
        ev_ref, cursor_hbm = outs[N_SCHED_MUTABLE + n_outs:]
        cursor_ref, ev_row_ref = smem[N_SCHED_MUTABLE + n_ro:]
        mirrored = tuple(sched_mut) + (cursor_ref,)
        hbm_mut = tuple(hbm_mut) + (cursor_hbm,)
    else:
        ev_ref = cursor_ref = ev_row_ref = None
        mirrored = tuple(sched_mut)

    r = pl.program_id(0)
    p = pl.program_id(1)
    first = (r == 0) & (p == 0)
    last = (r == pl.num_programs(0) - 1) & (p == pl.num_programs(1) - 1)

    @pl.when(first)
    def _load():
        pltpu.sync_copy((tuple(hbm_mut), tuple(ro_in)), (mirrored, tuple(ro)))

    tasks_ref, tail_ref = ro[:2]
    pool_off_ref = ro[2] if pool else None
    stage_ref = ro[2 + int(pool)] if staged else None
    pure = tuple(ro[n_ro - n_smem_pure:]) + tuple(hbm_pure)
    out_refs = outs[N_SCHED_MUTABLE: N_SCHED_MUTABLE + n_outs]
    _ws_cell(
        r, p, *sched_mut, tasks_ref, tail_ref, pool_off_ref, stage_ref,
        pure, out_refs, ev_ref, cursor_ref, ev_row_ref,
        pool=pool, staged=staged, trace=trace, **cell_kw,
    )

    @pl.when(last)
    def _store():
        pltpu.sync_copy(mirrored, tuple(hbm_mut))


def _ws_cell(
    r, p,
    head_ref, local_head_ref, taken_ref, remaining_ref, clock_ref, work_ref,
    steals_ref, scanned_ref, mult_ref,
    tasks_ref, tail_ref, pool_off_ref, stage_ref,
    pure, out_refs, ev_ref, ev_cursor_ref, ev_row_ref,
    *,
    execute: Callable,
    n_queues: int,
    capacity: int,
    steal: bool,
    steal_policy: str,
    pool: bool,
    compress: bool,
    steal_run_cap: int,
    multi_out: bool,
    staged: bool,
    trace: bool,
    trace_capacity: int,
    steal_kind: int,
):
    """One grid cell of the persistent WS grid: program ``p`` at round
    ``r`` tries one Take/Steal (or, compressed, drains its own queue) and
    runs the family ``execute`` body on each claimed record.

    ``multi_out`` launches call ``execute(rec, pure, outs, mult_ref)`` with
    the tuple of output refs plus the live multiplicity counters (the
    unified step's glue phases normalize accumulators in-kernel); the
    single-output convention stays ``execute(rec, pure, out_ref)``.
    """
    out_ref = out_refs if multi_out else out_refs[0]

    def trace_append(fq, fs, tid, cost, t0, op, run):
        """Append one extraction record to program ``p``'s event ring —
        plain stores only (guarded slot writes + a plain cursor bump), so
        the traced lowering stays inside the fence-free audit.  The ring
        never wraps: on overflow the record is *dropped* but the cursor
        keeps counting, so the host knows exactly how many were lost."""
        own = jax.lax.rem(p, n_queues)
        is_steal = fq != own
        if steal_kind == KIND_STEAL_REMOTE:
            # remote-segment launches (mesh_ws phase 2b): every claim works
            # a stolen segment, own-queue probes included
            kind = jnp.int32(KIND_STEAL_REMOTE)
        else:
            kind = jnp.where(is_steal, steal_kind, KIND_TAKE).astype(jnp.int32)
        nprog = pl.num_programs(1)
        victim = jnp.where(is_steal & (fq < nprog), fq, -1).astype(jnp.int32)
        c = ev_cursor_ref[p]

        @pl.when(c < trace_capacity)
        def _append():
            # assemble the record in SMEM, then one small copy into the
            # program's ring row in HBM (the rings outgrow SMEM)
            for field, val in (
                (EV_ROUND, t0), (EV_PROG, p), (EV_QUEUE, fq), (EV_SLOT, fs),
                (EV_TID, tid), (EV_COST, cost), (EV_KIND, kind),
                (EV_VICTIM, victim), (EV_MULT, mult_ref[tid]), (EV_OP, op),
                (EV_RUN, run),
            ):
                ev_row_ref[field] = jnp.asarray(val, jnp.int32)
            pltpu.sync_copy(ev_row_ref, ev_ref.at[p, c])

        ev_cursor_ref[p] = c + 1

    def account(fq, fs, advisory=True, run=1):
        rec = functools.partial(
            _slot_field, tasks_ref, pool_off_ref, fq, fs, pool=pool,
            capacity=capacity,
        )
        if trace:
            # virtual start of this execution — read before ws_account bumps
            # the lockstep clock, so the event's [t0, t0 + cost) interval is
            # the tile-slots the program is busy (also correct inside a
            # compressed drain run, where the clock advances per extraction)
            t0 = jnp.maximum(clock_ref[p], r)
        if multi_out:
            execute(rec, pure, out_ref, mult_ref)
        else:
            execute(rec, pure, out_ref)
        ws_account(
            r, p, fq, fs, rec(F_TID), rec(F_COST),
            taken_ref, remaining_ref, clock_ref, work_ref, steals_ref,
            mult_ref, pool_off_ref, n_queues=n_queues, capacity=capacity,
            pool=pool, advisory=advisory,
        )
        if trace:
            trace_append(fq, fs, rec(F_TID), rec(F_COST), t0, rec(F_OP), run)
        return rec(F_COST)

    if compress:
        # Round compression (DESIGN.md §3.6): with no thieves there is no
        # inter-round interleaving to model, so an idle owner drains its
        # whole queue as one run of consecutive Takes inside a single grid
        # cell — the clock still charges every tile-slot (identical
        # makespan/work telemetry to the per-round drain), but the grid
        # needs O(1) rounds instead of max-queue-cost rounds.
        assert not steal, "run compression models the no-steal schedule only"
        assert not staged, "stage gating needs the per-round lockstep"
        own = jax.lax.rem(p, n_queues)

        def probe_own():
            h = jnp.maximum(local_head_ref[p * n_queues + own], head_ref[own])
            op, issued = _probe_slot(
                tasks_ref, pool_off_ref, tail_ref, own, h, jnp.bool_(True),
                pool=pool, capacity=capacity,
            )
            scanned_ref[p] = scanned_ref[p] + issued
            return op != BOTTOM, h

        @pl.when(clock_ref[p] <= r)
        def _drain_run():
            def cond(carry):
                return carry[0]

            def body(carry):
                _, h, acc = carry
                head_ref[own] = h + 1
                local_head_ref[p * n_queues + own] = h + 1
                cost = account(own, h, advisory=False)
                live, nh = probe_own()
                return live, nh, acc + cost

            live0, h0 = probe_own()
            _, _, total = jax.lax.while_loop(
                cond, body, (live0, h0, jnp.int32(0))
            )
            # amortized synchronization (ROADMAP): ONE plain advisory write
            # for the whole drained run instead of one per extraction —
            # bit-identical to the sequential clamps since the run's costs
            # are nonnegative, and guarded so an empty run writes nothing
            # (exactly like zero per-extraction writes).
            @pl.when(total > 0)
            def _advise():
                remaining_ref[own] = jnp.maximum(remaining_ref[own] - total, 0)

        return

    found, fq, fs, run, nread = ws_try_extract(
        r, p, head_ref, local_head_ref, tail_ref, remaining_ref, tasks_ref,
        clock_ref, pool_off_ref, stage_ref,
        n_queues=n_queues, capacity=capacity, steal=steal,
        steal_policy=steal_policy, pool=pool, steal_run_cap=steal_run_cap,
    )
    scanned_ref[p] = scanned_ref[p] + nread

    if steal_run_cap == 1:
        @pl.when(found)
        def _execute():
            account(fq, fs)
    else:
        # Half-run execution (amortized synchronization, DESIGN.md §3.6):
        # the claim above already bumped the head past the whole run, so
        # execute its `run` consecutive slots back-to-back inside this grid
        # cell — per-slot events/counters keep the trace and multiplicity
        # semantics of per-slot claims, while the advisory decrement
        # coalesces into ONE plain write for the run (bit-identical to the
        # sequential clamps: costs are nonnegative, so the clamp commutes).
        @pl.when(found)
        def _execute_run():
            def body(i, total):
                return total + account(fq, fs + i, advisory=False, run=run)

            total = jax.lax.fori_loop(0, run, body, jnp.int32(0))
            remaining_ref[fq] = jnp.maximum(remaining_ref[fq] - total, 0)


@dataclass
class WSRunResult:
    """Post-launch queue/telemetry arrays.  Host numpy on eager launches;
    jax values (tracers) when the launch itself is being traced — the
    scalar properties below are host-only conveniences."""

    out: jax.Array          # family output, mult-weighted accumulation
    head: np.ndarray        # final shared heads            [n_queues]
    local_head: np.ndarray  # final per-program bounds      [n_programs, n_queues]
    taken: np.ndarray       # announcement rows             [n_queues, capacity]
                            #   (flat [capacity] on the pool layout)
    remaining: np.ndarray   # final advisory cost summaries [n_queues]
    clock: np.ndarray       # per-program completion time   [n_programs]
    work: np.ndarray        # tile-slots executed           [n_programs]
    steals: np.ndarray      # successful cross-queue grabs  [n_programs]
    scanned: np.ndarray     # task-slot probes issued       [n_programs]
    mult: np.ndarray        # per-task execution counts     [n_tasks]
    # event rings (trace=True launches only; None otherwise) — see
    # repro.wstrace.ring for the record schema and decode
    events: Optional[np.ndarray] = None     # [n_programs, cap, EVENT_WIDTH]
    ev_cursor: Optional[np.ndarray] = None  # [n_programs] appends attempted

    @property
    def makespan(self) -> int:
        return int(self.clock.max()) if self.clock.size else 0

    @property
    def total_work(self) -> int:
        return int(self.work.sum())

    @property
    def wasted_slots(self) -> int:
        """Idle tile-slots: programs waiting while the slowest one finishes."""
        return len(self.work) * self.makespan - self.total_work

    @property
    def slots_scanned(self) -> int:
        """Task-slot probes issued across the launch (scan traffic)."""
        return int(self.scanned.sum())

    @property
    def extractions(self) -> int:
        """Successful claims.  Exact for launches that started with a fresh
        multiplicity buffer (every claim bumps one counter)."""
        return int(self.mult.sum())

    @property
    def scan_per_extraction(self) -> float:
        """Slots read per successful extraction — the victim-scan overhead
        the cost policy exists to collapse."""
        return self.slots_scanned / max(1, self.extractions)

    @property
    def steal_ratio(self) -> float:
        """Fraction of extractions that were cross-queue steals (exact for
        launches that started with a fresh multiplicity buffer)."""
        return int(self.steals.sum()) / max(1, self.extractions)

    @property
    def per_queue_drained(self) -> np.ndarray:
        """Distinct slots claimed per queue.  Exact on the dense layout
        (one announcement row per queue); on the flat pool layout the
        announcement rows don't carry queue boundaries, so the final head
        watermark stands in (identical for completed drains)."""
        if self.taken.ndim == 2:
            return (np.asarray(self.taken) >= 0).sum(axis=1)
        return np.asarray(self.head).copy()


# Rounds the compressed no-steal drain needs: every owner empties its queue
# in its first idle grid cell; one slack round keeps the bound visibly safe
# for resumed states.
STATIC_COMPRESSED_ROUNDS = 2


def default_rounds(state: QueueState, steal: bool,
                   compress_runs: Optional[bool] = None,
                   steal_run_cap: int = 1) -> int:
    """Static upper bound on rounds to drain every queue (DESIGN.md §3.6).

    Stealing: Graham's greedy bound ``ceil(total/P) + max_cost`` — exact for
    this lockstep model because an idle program *always* claims a task when
    any queue is non-empty (the scan policy probes every queue; the cost
    policy's ``head < tail`` victim mask is exact), so no extra slack is
    needed.  With half-run steals (``steal_run_cap > 1``) the last claim can
    pull up to ``steal_run_cap`` slots at once, so the tail term grows to
    ``steal_run_cap * max_cost``.  No-steal: run compression drains each
    owner's queue in its first idle round, so the bound is O(1); without
    compression the heaviest queue runs alone (``max queue cost`` rounds).

    Needs concrete queue contents — trace-built states must pass an explicit
    static worst-case ``rounds`` to the launch (the grid size cannot depend
    on traced values).
    """
    if isinstance(state.tasks, jax.core.Tracer):
        raise ValueError(
            "rounds must be given explicitly for a trace-built QueueState: "
            "the grid is static, so use the family's worst-case bound "
            "(e.g. moe_ws.dispatch.expert_rounds_bound)"
        )
    compress = (not steal) if compress_runs is None else compress_runs
    costs = queue_costs(state)
    total = int(costs.sum())
    if total == 0:
        return 1
    from .tasks import max_cost

    mc = max_cost(state.task_list) if state.task_list else int(costs.max())
    if steal:
        return -(-total // state.n_programs) + max(1, steal_run_cap) * mc
    if compress:
        return STATIC_COMPRESSED_ROUNDS
    return int(costs.max())


def launch_ws_grid(
    state: QueueState,
    execute: Callable,
    pure: Sequence[jax.Array],
    out,
    *,
    steal: bool = True,
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    rounds: Optional[int] = None,
    mult: Optional[jax.Array] = None,
    compress_runs: Optional[bool] = None,
    stage_open: Optional[jax.Array] = None,
    trace: bool = False,
    trace_capacity: Optional[int] = None,
    trace_remote: bool = False,
    fault_plan=None,
    smem_pure: Sequence[jax.Array] = (),
    name: str = "ws_grid",
) -> WSRunResult:
    """Run the persistent WS grid with a family ``execute`` body.

    ``execute(rec, pure_refs, out_ref)`` performs the claimed tile —
    ``rec(field)`` reads one field of its task record — and *accumulates*
    into ``out_ref``; the shell handles extraction and bookkeeping.
    ``pure_refs`` are the ``smem_pure`` arrays (1-D inputs the body reads
    as scalars, mirrored into SMEM once per launch — the expert family's
    row → token map and row gates) followed by the ``pure`` arrays, which stay in HBM:
    the body copies the blocks it needs into VMEM scratch
    (``pltpu.sync_copy``), and likewise reads and writes back its output
    blocks.
    ``out``/``mult`` may be carried over from a previous launch (resume /
    multiplicity drills).  ``compress_runs`` defaults to ``not steal``:
    no-steal launches drain whole owner runs per grid cell (§3.6), steal
    launches keep the one-extraction-per-round lockstep so thief
    concurrency stays faithfully modeled.

    ``out`` may be a *tuple* of arrays (the unified engine step's caches,
    activation buffers, routing scratch, logits).  The shell then calls
    ``execute(rec, pure_refs, out_refs, mult_ref)`` — the tuple of live
    output refs plus the multiplicity counters, so mixed-family bodies can
    normalize accumulators in-kernel — and ``WSRunResult.out`` is the tuple
    in the same order.  ``stage_open`` ([n_queues] int32, optional) gates
    extraction per queue by round (see :func:`ws_try_extract`): the
    mixed-mode launch encodes its inter-stage dependencies as host-computed
    open rounds instead of device-side waiting, keeping the lowering free
    of fences.

    ``trace=True`` additionally records every extraction into per-program
    event rings (``WSRunResult.events``/``ev_cursor``; schema in
    :mod:`repro.wstrace.ring`) with plain stores only.  The default ring
    capacity is the static per-program claim bound — ``rounds`` for
    lockstep launches (one claim per round), the queue capacity for
    compressed drains — so nothing drops unless ``trace_capacity``
    deliberately shrinks the ring.  ``trace_remote`` tags every event
    ``steal-remote`` (mesh_ws stolen-segment launches).  ``trace=False``
    (the default) adds no refs and no kernel code: the lowering is
    bit-identical to the untraced build.

    ``steal_run_cap`` (cost policy, steal launches) caps the half-run Steal:
    one successful probe claims ``min(ceil(rem/2), cap)`` contiguous victim
    slots and executes them back-to-back in the claiming grid cell, with ONE
    coalesced advisory write per run (see :func:`ws_try_extract`).  The
    default ``1`` lowers bit-identically to the per-slot claim; ``> 1`` is
    incompatible with ``stage_open`` (the Graham stage windows assume
    per-slot claims) and with ``compress_runs``.  The Graham rounds bound
    and the default trace-ring capacity gain a ``cap`` slack term.

    ``fault_plan`` (a :class:`repro.chaos.FaultPlan`, optional) injects
    the plan's *launch-time* faults as initial array values only: program
    stalls become nonzero initial ``clock`` entries (a stalled program is
    "busy" until its stall round and extracts nothing before then) and
    advisory corruption replaces the initial ``remaining`` summaries.  No
    kernel code changes — ``fault_plan=None`` is the identical lowering,
    the same zero-cost bar as ``trace=False``.  When ``rounds`` is not
    given, the Graham default is extended by the maximum stall so stalled
    schedules still drain.  Cross-launch faults (storms, kills) live in
    :func:`repro.chaos.inject.run_with_faults`.

    ``name`` names the ``pallas_call``, one per task family (``ws_decode``,
    ``ws_flash``, ``ws_attention``, ``ws_expert``, ``ws_expert_grad``,
    ``ws_unified``), so that a profile says which family ran.
    """
    assert steal_policy in STEAL_POLICIES, steal_policy
    P = state.n_programs
    compress = (not steal) if compress_runs is None else compress_runs
    if compress and steal:
        raise ValueError("compress_runs models the no-steal schedule only")
    if stage_open is not None and compress:
        raise ValueError("stage_open needs the per-round lockstep "
                         "(compress_runs=False)")
    if steal_run_cap < 1:
        raise ValueError(f"steal_run_cap must be >= 1, got {steal_run_cap}")
    if steal_run_cap > 1:
        if not steal or steal_policy != "cost":
            raise ValueError("steal_run_cap > 1 amortizes cost-policy "
                             "steals — needs steal=True, steal_policy='cost'")
        if stage_open is not None:
            raise ValueError("steal_run_cap > 1 breaks the per-slot-claim "
                             "assumption of stage_open's Graham windows")
    multi_out = isinstance(out, (tuple, list))
    outs_in = tuple(out) if multi_out else (out,)
    rounds_given = rounds is not None
    rounds = (
        default_rounds(state, steal, compress_runs=compress,
                       steal_run_cap=steal_run_cap)
        if rounds is None else rounds
    )
    n_tasks = max(1, state.n_tasks)
    mult = jnp.zeros((n_tasks,), jnp.int32) if mult is None else mult
    pool = state.pool_off is not None
    remaining = state.remaining
    if remaining is None:
        remaining = queue_costs(state)
    clock0 = jnp.zeros((P,), jnp.int32)
    if fault_plan is not None:
        # chaos injection is pure data: a stalled program is a nonzero
        # initial clock, a stale advisory is a different initial value —
        # the lowering is the fault_plan=None build either way
        remaining = fault_plan.launch_remaining(remaining)
        if fault_plan.max_stall:
            clock0 = jnp.asarray(fault_plan.stall_vector(P), jnp.int32)
            if not rounds_given:
                rounds += fault_plan.max_stall
    if trace_capacity is None:
        # per-program events <= rounds for per-slot claims; a run of n slots
        # keeps its program busy >= n rounds, so runs only shift the bound
        # by the last (possibly cap-long) run: rounds + cap - 1.
        trace_capacity = (
            state.capacity if compress else rounds + steal_run_cap - 1
        )
    steal_kind = (
        KIND_STEAL_REMOTE if trace_remote
        else (KIND_STEAL_SCAN if steal_policy == "scan" else KIND_STEAL_COST)
    )

    kernel = functools.partial(
        _generic_ws_kernel,
        execute=execute,
        n_pure=len(pure),
        n_smem_pure=len(smem_pure),
        n_queues=state.n_queues,
        capacity=state.capacity,
        steal=steal,
        steal_policy=steal_policy,
        pool=pool,
        compress=compress,
        steal_run_cap=steal_run_cap,
        n_outs=len(outs_in),
        multi_out=multi_out,
        staged=stage_open is not None,
        trace=trace,
        trace_capacity=trace_capacity,
        steal_kind=steal_kind,
    )

    i32 = jnp.int32
    sched = [
        jnp.asarray(state.head, i32),
        jnp.asarray(state.local_head, i32).reshape(-1),
        jnp.asarray(state.taken, i32).reshape(-1),
        jnp.asarray(remaining, i32),
        clock0,                       # clock (stall faults start nonzero)
        jnp.zeros((P,), i32),         # work
        jnp.zeros((P,), i32),         # steals
        jnp.zeros((P,), i32),         # scanned
        jnp.asarray(mult, i32),
    ]
    mutable = sched + [jnp.asarray(o) for o in outs_in]
    if trace:
        mutable += [
            jnp.full((P, trace_capacity, EVENT_WIDTH), -1, i32),
            jnp.zeros((P,), i32),  # event cursors
        ]
    sched_in = [jnp.asarray(state.tasks, i32).reshape(-1),
                jnp.asarray(state.tail, i32)]
    if pool:
        sched_in.append(jnp.asarray(state.pool_off, i32))
    if stage_open is not None:
        sched_in.append(jnp.asarray(stage_open, i32))
    sched_in += [jnp.asarray(a) for a in smem_pure]
    pure_arrays = [jnp.asarray(a) for a in pure]
    smem = [pltpu.SMEM(a.shape, a.dtype) for a in sched + sched_in]
    if trace:
        smem += [pltpu.SMEM((P,), i32), pltpu.SMEM((EVENT_WIDTH,), i32)]
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    n_in = len(mutable) + len(sched_in) + len(pure_arrays)
    outs = pl.pallas_call(
        kernel,
        grid=(rounds, P),
        in_specs=[hbm] * n_in,
        out_specs=[hbm] * len(mutable),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in mutable],
        scratch_shapes=smem,
        input_output_aliases={i: i for i in range(len(mutable))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret_mode(),
        name=name,
    )(*mutable, *sched_in, *pure_arrays)
    n_live = N_SCHED_MUTABLE + len(outs_in)
    (head, local_head, taken, remaining, clock, work, steals, scanned,
     mult) = outs[:N_SCHED_MUTABLE]
    local_head = local_head.reshape(np.shape(state.local_head))
    taken = taken.reshape(np.shape(state.taken))
    out = (
        tuple(outs[N_SCHED_MUTABLE:n_live]) if multi_out
        else outs[N_SCHED_MUTABLE]
    )
    events, ev_cursor = outs[n_live:] if trace else (None, None)

    def host(a):
        # eager launches hand numpy views back to the drills/telemetry;
        # traced launches keep the jax values (np.asarray would throw)
        if a is None or isinstance(a, jax.core.Tracer):
            return a
        return np.asarray(a)

    return WSRunResult(
        out=out,
        head=host(head),
        local_head=host(local_head),
        taken=host(taken),
        remaining=host(remaining),
        clock=host(clock),
        work=host(work),
        steals=host(steals),
        scanned=host(scanned),
        mult=host(mult),
        events=host(events),
        ev_cursor=host(ev_cursor),
    )


# ---------------------------------------------------------------------------
# attention family: flash/decode tile body


def _attention_execute(
    rec, pure, out_ref,
    *, bq: int, bk: int, causal: bool, scale: float, g: int,
):
    """Flash-attention tile: online-softmax sweep of the task's kv range,
    accumulated into the task's disjoint q-block rows.

    ``q``/``k``/``v`` and the output stay in HBM: the tile copies its q
    block, then each kv block in turn, into VMEM scratch, and finally
    reads, adds to and writes back its own output block."""
    q_ref, k_ref, v_ref = pure
    b = rec(F_B)
    h = rec(F_H)
    qs = pl.multiple_of(rec(F_QS), bq)
    ql = rec(F_QL)
    kv_end = rec(F_KV)
    cost = rec(F_COST)
    kh = jax.lax.div(h, g)
    hd = q_ref.shape[-1]

    def body(q_buf, k_buf, v_buf, o_buf):
        pltpu.sync_copy(q_ref.at[b, h, pl.ds(qs, bq)], q_buf)
        qt = q_buf[...].astype(jnp.float32)

        def kv_block(ki, mla):
            m, l, acc = mla
            start = pl.multiple_of(ki * bk, bk)
            pltpu.sync_copy(
                (k_ref.at[b, kh, pl.ds(start, bk)],
                 v_ref.at[b, kh, pl.ds(start, bk)]),
                (k_buf, v_buf),
            )
            kt = k_buf[...].astype(jnp.float32)
            vt = v_buf[...].astype(jnp.float32)
            s = jax.lax.dot_general(
                qt, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [bq, bk]
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            valid = kpos < kv_end
            if causal:
                qpos = qs + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                valid &= kpos <= qpos
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + pexp.sum(axis=1, keepdims=True)
            acc_new = acc * corr + jax.lax.dot_general(
                pexp, vt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new)

        m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
        a0 = jnp.zeros((bq, hd), jnp.float32)
        # Dynamic trip count: a real persistent core sweeps only the live
        # blocks — this is exactly the cost the work counters account.
        m, l, acc = jax.lax.fori_loop(0, cost, kv_block, (m0, l0, a0))

        tile = acc / jnp.maximum(l, 1e-30)
        row_live = jax.lax.broadcasted_iota(jnp.int32, (bq, hd), 0) < ql
        tile = jnp.where(row_live, tile, 0.0)

        # Idempotent-accumulate: duplicates add whole extra copies of the
        # same tile, which mult[tid] normalizes out host-side.
        dst = out_ref.at[b, h, pl.ds(qs, bq)]
        pltpu.sync_copy(dst, o_buf)
        o_buf[...] = o_buf[...] + tile
        pltpu.sync_copy(o_buf, dst)

    pl.run_scoped(
        body,
        pltpu.VMEM((bq, hd), q_ref.dtype),
        pltpu.VMEM((bk, hd), k_ref.dtype),
        pltpu.VMEM((bk, hd), v_ref.dtype),
        pltpu.VMEM((bq, hd), out_ref.dtype),
    )


def run_ws_schedule(
    state: QueueState,
    q,
    k,
    v,
    *,
    causal: bool,
    bq: int,
    bk: int,
    steal: bool = True,
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    rounds: Optional[int] = None,
    out: Optional[jax.Array] = None,
    mult: Optional[jax.Array] = None,
    compress_runs: Optional[bool] = None,
    trace: bool = False,
    trace_capacity: Optional[int] = None,
    fault_plan=None,
    name: str = "ws_attention",
) -> WSRunResult:
    """Launch the attention megakernel over a prepared :class:`QueueState`.

    ``q``: [B, H, Sq, hd] with Sq a multiple of ``bq``; ``k``/``v``:
    [B, Hkv, Sk, hd] with Sk a multiple of ``bk``.  ``out``/``mult`` may be
    carried over from a previous launch (resume / multiplicity drills);
    fresh zeros otherwise.  ``trace=True`` records per-extraction event
    rings (see :func:`launch_ws_grid`).
    """
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Sq % bq == 0, (Sq, bq)
    assert Sk % bk == 0, (Sk, bk)
    g = H // Hkv
    out = jnp.zeros((B, H, Sq, hd), jnp.float32) if out is None else out
    execute = functools.partial(
        _attention_execute, bq=bq, bk=bk, causal=causal, scale=hd**-0.5, g=g
    )
    return launch_ws_grid(
        state, execute, (q, k, v), out,
        steal=steal, steal_policy=steal_policy, steal_run_cap=steal_run_cap,
        rounds=rounds, mult=mult,
        compress_runs=compress_runs,
        trace=trace, trace_capacity=trace_capacity, fault_plan=fault_plan,
        name=name,
    )

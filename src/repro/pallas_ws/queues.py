"""HBM-resident per-program task queues as flat int32 arrays (WS-WMULT Fig. 7).

The paper's shared objects map onto device arrays one-to-one:

==========================  =====================================================
paper (Fig. 7)              device layout (all plain loads/stores)
==========================  =====================================================
``tasks[1..∞]`` per queue   ``tasks[q, s, :]``  [n_queues, capacity, TASK_WIDTH]
``Head`` register           ``head[q]``         [n_queues]
process-local ``head``      ``local_head[p, q]`` [n_programs, n_queues]
(announcement)              ``taken[q, s]``     [n_queues, capacity] — extractor id
``tail`` (owner-local)      ``tail[q]``         [n_queues] — static: puts happen
                                                 host-side before launch
==========================  =====================================================

``local_head[p, q]`` is the persistent per-process lower bound of the inlined
RangeMaxRegister: every Take/Steal refreshes it with ``max(local, head[q])``
(the RMaxRead) and plainly writes ``head[q] = h+1`` on success (the RMaxWrite
with its read elided).  No CAS, no fence — a stale ``head`` write can rewind a
queue and cause re-extraction, but each program's bound is strictly
increasing, so no *program* extracts the same slot twice.

``taken[q, s]`` is the announcement row: the extracting program writes its id
after claiming slot ``s``.  It is diagnostic (multiplicity accounting /
drills), never consulted by the extraction protocol itself.

Three builders produce launch-compatible states:

* :func:`make_queue_state` — the host-side Put: concrete tasks laid out with
  numpy before launch (serving's eager paths, the drills);
* :func:`make_queue_state_jax` — the **traced** Put: fixed-shape candidate
  records compacted on device with jnp ops, so queue construction lives
  inside ``jit``/``scan``;
* :func:`make_pool_queue_state_jax` — the traced Put on the compact
  **shared-pool** layout (DESIGN.md §3.6): one flat slot pool with dynamic
  per-queue segment offsets (``pool_off``), cutting the per-queue
  worst-case padding the dense traced layout pays.

Every state also carries the ``remaining[q]`` advisory cost summaries the
cost-aware victim selection ranks by (plain writes, stale-tolerant).  The
megakernel launch consumes any of them through the one
:func:`repro.pallas_ws.kernel.launch_ws_grid` code path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .tasks import BOTTOM, TASK_WIDTH, TileTask


@dataclass
class QueueState:
    """Mirror of the device queue arrays.

    Host-built states hold numpy int32 arrays plus the concrete
    ``task_list``; trace-built states hold jnp values (possibly tracers)
    with ``task_list=None`` and the *static* ``n_tasks_hint`` sizing the
    multiplicity buffer (dead candidate slots keep mult 0).

    ``remaining[q]`` is the advisory per-queue cost summary the cost-aware
    victim selection ranks by (DESIGN.md §3.6): initialized to the enqueued
    cost, decremented best-effort by claimants with plain reads/writes.
    Stale values mis-rank victims but can never change results.

    Two layouts share the class.  The **dense** layout (``pool_off is
    None``): ``tasks[q, s, :]`` with a static per-queue ``capacity``.  The
    **shared-pool** layout: ``tasks[j, :]`` is one flat slot pool and queue
    ``q`` owns the contiguous segment ``[pool_off[q], pool_off[q+1])`` —
    slot ``(q, s)`` lives at pool index ``pool_off[q] + s`` and ``taken``
    is flat ``[pool_slots]``.  Segment boundaries are dynamic (trace-built
    from the router load), so the pool never pays the dense layout's
    per-queue worst-case padding.
    """

    tasks: np.ndarray        # [n_queues, capacity, TASK_WIDTH] | pool: [pool_slots, TASK_WIDTH]
    head: np.ndarray         # [n_queues]
    tail: np.ndarray         # [n_queues]
    local_head: np.ndarray   # [n_programs, n_queues]
    taken: np.ndarray        # [n_queues, capacity] | pool: [pool_slots]; -1 = not extracted
    task_list: Optional[List[TileTask]] = None
    n_tasks_hint: Optional[int] = None
    remaining: Optional[np.ndarray] = None  # [n_queues] advisory cost summary
    pool_off: Optional[np.ndarray] = None   # [n_queues + 1] pool segment offsets

    @property
    def n_queues(self) -> int:
        return self.head.shape[0]

    @property
    def n_programs(self) -> int:
        return self.local_head.shape[0]

    @property
    def capacity(self) -> int:
        """Global bound on slot indices: per-queue capacity on the dense
        layout, total pool slots on the shared-pool layout."""
        if self.pool_off is not None:
            return self.tasks.shape[0]
        return self.tasks.shape[1]

    @property
    def n_tasks(self) -> int:
        if self.task_list is not None:
            return len(self.task_list)
        return self.n_tasks_hint or 0

    def queue_array_bytes(self) -> int:
        """Total bytes of the queue-side arrays (tasks + head/tail +
        local bounds + announcements + advisory) — the HBM footprint the
        shared-pool layout exists to shrink."""
        arrays = [self.tasks, self.head, self.tail, self.local_head,
                  self.taken]
        if self.remaining is not None:
            arrays.append(self.remaining)
        if self.pool_off is not None:
            arrays.append(self.pool_off)
        total = 0
        for a in arrays:
            n = 1
            for d in a.shape:
                n *= int(d)
            total += n * 4  # int32 everywhere
        return total


def partition_tasks(
    tasks: Sequence[TileTask], n_queues: int, partition: str = "owner"
) -> List[List[TileTask]]:
    """Assign tasks to owner queues.

    * ``"owner"`` (alias ``"batch"``) — queue ``task.owner % n_queues``: all
      tiles of one logical owner (a sequence's batch row for attention, an
      expert for MoE dispatch) land on one queue — the natural placement and
      the one that produces the skew the thieves then erase.
    * ``"round_robin"`` — task-index striping (near-balanced baseline).
    """
    buckets: List[List[TileTask]] = [[] for _ in range(n_queues)]
    for i, t in enumerate(tasks):
        q = (t.owner if partition in ("owner", "batch") else i) % n_queues
        buckets[q].append(t)
    return buckets


def make_queue_state(
    tasks: Sequence[TileTask],
    n_programs: int,
    n_queues: int | None = None,
    partition: str = "batch",
) -> QueueState:
    """Lay tasks out in the Fig. 7 array format, ready for the megakernel.

    Slots beyond each queue's tail keep ``BOTTOM`` in field 0 — the paper's
    two-⊥-slot invariant degenerates to "the whole suffix is ⊥" because all
    Puts happen host-side before the kernel launches.
    """
    n_queues = n_programs if n_queues is None else n_queues
    buckets = partition_tasks(tasks, n_queues, partition)
    cap = max(4, max((len(b) for b in buckets), default=0) + 2)
    arr = np.full((n_queues, cap, TASK_WIDTH), BOTTOM, dtype=np.int32)
    tail = np.zeros((n_queues,), dtype=np.int32)
    remaining = np.zeros((n_queues,), dtype=np.int32)
    for q, bucket in enumerate(buckets):
        for s, t in enumerate(bucket):
            arr[q, s] = t.encode()
        tail[q] = len(bucket)
        remaining[q] = sum(t.cost for t in bucket)
    return QueueState(
        tasks=arr,
        head=np.zeros((n_queues,), dtype=np.int32),
        tail=tail,
        local_head=np.zeros((n_programs, n_queues), dtype=np.int32),
        taken=np.full((n_queues, cap), -1, dtype=np.int32),
        task_list=list(tasks),
        remaining=remaining,
    )


def copy_state(state: QueueState) -> QueueState:
    """Independent copy of a host-built queue state (numpy arrays copied,
    task_list shared — tasks are immutable records).  Fault-injection
    drills mutate head/local bounds/advisories in place; the fault-free
    oracle must run from a pristine copy."""

    def cp(a):
        return None if a is None else np.array(a)

    return QueueState(
        tasks=cp(state.tasks),
        head=cp(state.head),
        tail=cp(state.tail),
        local_head=cp(state.local_head),
        taken=cp(state.taken),
        task_list=state.task_list,
        n_tasks_hint=state.n_tasks_hint,
        remaining=cp(state.remaining),
        pool_off=cp(state.pool_off),
    )


def queue_costs(state: QueueState) -> np.ndarray:
    """Total tile-slot cost enqueued per queue (the static-schedule load)."""
    from .tasks import F_COST, F_OP

    if state.pool_off is not None:
        tasks = np.asarray(state.tasks)
        off = np.asarray(state.pool_off)
        tail = np.asarray(state.tail)
        costs = np.zeros((state.n_queues,), dtype=np.int64)
        live = tasks[:, F_OP] != BOTTOM
        for q in range(state.n_queues):
            seg = slice(int(off[q]), int(off[q]) + int(tail[q]))
            costs[q] = np.where(live[seg], tasks[seg, F_COST], 0).sum()
        return costs
    live = state.tasks[:, :, F_OP] != BOTTOM
    return np.where(live, state.tasks[:, :, F_COST], 0).sum(axis=1)


# ---------------------------------------------------------------------------
# traced (jit-compatible) queue construction — the device-side Put


def owner_queue_candidates(records, live, n_queues: int) -> Tuple:
    """Regroup per-owner candidate tiles into per-queue candidate arrays.

    ``records``: [n_owners, per_owner, TASK_WIDTH]; ``live``:
    [n_owners, per_owner] bool.  Owner ``o`` lands on queue ``o % n_queues``
    (the same placement :func:`partition_tasks` uses for ``"owner"``), its
    tiles ordered by ``o // n_queues`` within the queue — all with static
    shapes, so the regrouping traces.  Owners are padded with dead rows up
    to a multiple of ``n_queues``.
    """
    import jax.numpy as jnp

    records = jnp.asarray(records)
    live = jnp.asarray(live)
    n_owners, per_owner, width = records.shape
    if n_queues == n_owners:
        return records, live
    pad = (-n_owners) % n_queues
    if pad:
        records = jnp.pad(records, ((0, pad), (0, 0), (0, 0)),
                          constant_values=BOTTOM)
        live = jnp.pad(live, ((0, pad), (0, 0)), constant_values=False)
    rows = (n_owners + pad) // n_queues
    # owner o = j * n_queues + q  ->  queue q, block j
    records = records.reshape(rows, n_queues, per_owner, width)
    records = records.transpose(1, 0, 2, 3).reshape(n_queues, rows * per_owner, width)
    live = live.reshape(rows, n_queues, per_owner)
    live = live.transpose(1, 0, 2).reshape(n_queues, rows * per_owner)
    return records, live


def make_queue_state_jax(
    records,
    live,
    n_programs: int,
    *,
    n_tasks: int,
) -> QueueState:
    """Traced Put: materialize the Fig. 7 queue arrays as jnp values.

    ``records``: [n_queues, slots, TASK_WIDTH] candidate task records at
    their static slots; ``live``: [n_queues, slots] bool masks.  Each
    queue's live records are stably compacted to the slot prefix (the order
    a host-side Put loop would have produced) and every dead slot is set to
    the ⊥ record, restoring the "whole suffix is ⊥" invariant the extraction
    protocol scans for.  ``tail[q]`` is the live count — exactly the value
    the owner's Put counter would hold.  All ops are jnp, so this works on
    tracers; on concrete inputs it produces the same layout
    :func:`make_queue_state` builds host-side (certified by
    tests/test_dispatch_conformance.py).

    ``n_tasks`` is the static candidate count sizing the multiplicity
    buffer; dead candidates keep ``mult == 0`` and their ``tid`` is never
    extracted.

    Batched-Put segment-write contract (DESIGN.md §3.6): the whole queue
    array materializes as *per-queue vectorized writes* — one stable-argsort
    compaction and one masked store per queue segment, never a store per
    task — and each queue's ``tail``/``remaining`` advisory is published
    once per segment (the reductions above), not once per Put.  The
    downstream :func:`repro.moe_ws.dispatch.route_to_tasks_jax` /
    ``route_to_tasks_pool_jax`` builders feed this with gather-only
    segment materialization, so the complete traced Put lowers with zero
    scatter ops (``benchmarks/zero_cost.py`` audits the lowering text).
    """
    import jax.numpy as jnp

    from .tasks import F_COST

    records = jnp.asarray(records, jnp.int32)
    live = jnp.asarray(live)
    n_queues, slots, _ = records.shape
    # stable partition: live records first, original order preserved
    order = jnp.argsort(jnp.where(live, 0, 1).astype(jnp.int32),
                        axis=1, stable=True)
    arr = jnp.take_along_axis(records, order[:, :, None], axis=1)
    live_sorted = jnp.take_along_axis(live, order, axis=1)
    arr = jnp.where(live_sorted[:, :, None], arr, BOTTOM)
    # two trailing ⊥ slots: the paper's pre-clear invariant (and slack so a
    # full queue's head can step one past the last live slot)
    arr = jnp.pad(arr, ((0, 0), (0, 2), (0, 0)), constant_values=BOTTOM)
    cap = slots + 2
    return QueueState(
        tasks=arr,
        head=jnp.zeros((n_queues,), jnp.int32),
        tail=live.sum(axis=1).astype(jnp.int32),
        local_head=jnp.zeros((n_programs, n_queues), jnp.int32),
        taken=jnp.full((n_queues, cap), -1, jnp.int32),
        task_list=None,
        n_tasks_hint=int(n_tasks),
        remaining=jnp.where(live, records[:, :, F_COST], 0)
        .sum(axis=1).astype(jnp.int32),
    )


def make_pool_queue_state_jax(
    records,
    tail,
    pool_off,
    remaining,
    n_programs: int,
    *,
    n_tasks: int,
) -> QueueState:
    """Traced Put, shared-pool layout: wrap pre-compacted flat records.

    ``records``: [pool_slots, TASK_WIDTH] task records where queue ``q``'s
    live slots already occupy the contiguous segment ``[pool_off[q],
    pool_off[q] + tail[q])`` in queue order, the pool suffix all-⊥ (the
    builder — e.g. :func:`repro.moe_ws.dispatch.route_to_tasks_pool_jax` —
    produces exactly this, so no compaction pass is needed).  ``pool_off``:
    [n_queues + 1] dynamic segment offsets; ``tail``: [n_queues] live slot
    counts (``tail[q] == pool_off[q+1] - pool_off[q]`` for every non-suffix
    queue); ``remaining``: [n_queues] initial advisory cost summaries.

    ``n_tasks`` is the static pool slot count sizing the multiplicity
    buffer — pool slot index == ``tid`` == multiplicity index, so dead
    suffix slots keep ``mult == 0``.

    Batched-Put segment-write contract (DESIGN.md §3.6): the pool builder
    hands over whole per-expert segments, so this wrapper issues exactly one
    vectorized record write for the pool plus one publication each of the
    per-queue ``tail``/``pool_off``/``remaining`` advisories — the traced
    analogue of :meth:`repro.pallas_ws.host.PallasWSHost.put_segment`.
    """
    import jax.numpy as jnp

    records = jnp.asarray(records, jnp.int32)
    pool_slots = records.shape[0]
    n_queues = tail.shape[0]
    return QueueState(
        tasks=records,
        head=jnp.zeros((n_queues,), jnp.int32),
        tail=jnp.asarray(tail, jnp.int32),
        local_head=jnp.zeros((n_programs, n_queues), jnp.int32),
        taken=jnp.full((pool_slots,), -1, jnp.int32),
        task_list=None,
        n_tasks_hint=int(n_tasks),
        remaining=jnp.asarray(remaining, jnp.int32),
        pool_off=jnp.asarray(pool_off, jnp.int32),
    )

"""Device event-ring schema + host-side decode (numpy only — this module is
imported by the megakernel, so it must not pull in jax or any repro layer).

Device half (written by ``pallas_ws.kernel`` when ``trace=True``): every
successful Take/Steal appends one fixed-width int32 record to the claiming
program's ring row of a preallocated ``[n_programs, capacity, EVENT_WIDTH]``
HBM array, then bumps that program's plain-write cursor.  Both the record
stores and the cursor bump are plain stores — no RMW, no lock, no fence —
so tracing composes with the zero-cost audit instead of breaking it
(``benchmarks/zero_cost.py`` audits the traced-on lowering).

Rings never wrap: a record is written only while ``cursor < capacity``
(overflow-**drop**, not overwrite — the prefix of the run survives), but the
cursor keeps counting, so the host recovers the exact number of dropped
events as ``max(0, cursor - capacity)`` per program.

Record fields (all int32):

=========  ================================================================
EV_ROUND   virtual start round of the execution — ``max(clock[p], r)`` read
           *before* the lockstep clock bump, so ``[round, round + cost)`` is
           exactly the tile-slot interval the program was busy
EV_PROG    claiming program (redundant with the ring row; kept so a
           flattened event stream is self-describing)
EV_QUEUE   queue the slot was claimed from
EV_SLOT    logical slot index within that queue
EV_TID     task id of the claimed record
EV_COST    task cost in tile-slots
EV_KIND    KIND_TAKE / KIND_STEAL_SCAN / KIND_STEAL_COST / KIND_STEAL_REMOTE
EV_VICTIM  owner program of the stolen queue (steals where the queue has a
           same-numbered owner, i.e. ``queue < n_programs``); -1 for takes
           and for unowned queues (expert layouts with n_queues > P)
EV_MULT    the task's multiplicity counter *after* this execution
EV_OP      the claimed record's op id (``tasks.F_OP``) — identifies the task
           family of the event (a launch carries one family)
EV_RUN     slots claimed by the extraction this event belongs to — 1 for
           single-slot Take/Steal, the half-run length for amortized steals
           (``steal_run_cap > 1``), where one probe claims a contiguous run
           and every slot of the run records the same run length
=========  ================================================================
"""

from __future__ import annotations

import numpy as np

EVENT_WIDTH = 11
(EV_ROUND, EV_PROG, EV_QUEUE, EV_SLOT, EV_TID, EV_COST, EV_KIND, EV_VICTIM,
 EV_MULT, EV_OP, EV_RUN) = range(EVENT_WIDTH)

KIND_TAKE = 0
KIND_STEAL_SCAN = 1
KIND_STEAL_COST = 2
KIND_STEAL_REMOTE = 3
KIND_NAMES = ("take", "steal-scan", "steal-cost", "steal-remote")
STEAL_KINDS = (KIND_STEAL_SCAN, KIND_STEAL_COST, KIND_STEAL_REMOTE)


def decode_rings(events, cursor):
    """Flatten per-program rings into one event stream.

    ``events``: ``[n_programs, capacity, EVENT_WIDTH]`` int32 (unwritten
    slots hold -1); ``cursor``: ``[n_programs]`` total appends *attempted*
    per program (valid records are the first ``min(cursor, capacity)``).

    Returns ``(stream, dropped)`` — ``stream`` is ``[n_events, EVENT_WIDTH]``
    sorted by (round, program) so it reads as a timeline, ``dropped`` is the
    per-program count of records lost to ring overflow.
    """
    events = np.asarray(events)
    cursor = np.asarray(cursor)
    n_programs, capacity, width = events.shape
    assert width == EVENT_WIDTH, events.shape
    # Row-major boolean selection over [P, cap] is exactly the per-program
    # prefix concatenation (program-major, slot order preserved) the old
    # Python loop produced — one vectorized gather instead of P slices.
    valid = np.arange(capacity)[None, :] < np.minimum(cursor, capacity)[:, None]
    stream = events[valid].reshape(-1, EVENT_WIDTH)
    if stream.size:
        order = np.lexsort((stream[:, EV_PROG], stream[:, EV_ROUND]))
        stream = stream[order]
    dropped = np.maximum(cursor.astype(np.int64) - capacity, 0)
    return stream.astype(np.int32, copy=False), dropped

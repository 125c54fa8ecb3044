"""Task encoding + task-family registry for the device-resident WS scheduler.

A task is one idempotent tile of work.  Tasks are fixed-width int32 records
so they can live in an HBM array and be extracted with a single vector load —
the device-side analogue of the paper's ``tasks[i]`` cells (Fig. 7), where
``tasks[i] = ⊥`` becomes "field 0 == BOTTOM".

The record layout is family-agnostic: field 0 carries the op id, fields 1–5
are family-specific operands, and the tail two fields are shared by every
family (the multiplicity-counter index and the tile-slot cost the
round-lockstep clock charges).  The queue arrays, the Take/Steal extraction
protocol, and the clock/work accounting never look at the operand fields, so
new workloads plug in by registering a :class:`TaskFamily` and supplying a
kernel body — attention tiles (:mod:`repro.pallas_ws.kernel`) and MoE expert
tiles (:mod:`repro.moe_ws.expert_kernel`) share the whole scheduler.

Idempotence and multiplicity
----------------------------
Every task owns a *disjoint* slice of its family's output (q-block rows for
attention, routed-row ranges for expert FFN), and executing it computes that
slice's **entire** result.  Task execution *accumulates* into the output and
bumps a per-task multiplicity counter with plain loads/stores — so when the
relaxed scheduler extracts a task more than once (the paper's multiplicity),
the output is exactly ``mult[t] ×`` the true tile and the family's divisor
(:func:`multiplicity_divisor` / ``moe_ws.dispatch.row_divisor``) recovers the
exact answer.  This is why the Take/Steal path needs no CAS: duplicated tile
work is count-normalized, not forbidden.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

# int32 sentinel marking a never-filled task slot (the paper's ⊥).
BOTTOM = -1

# Record layout: 8 × int32 per task.  Field 0 and the tail two fields are
# family-agnostic; fields 1-5 are operands owned by the task family.
TASK_WIDTH = 8
F_OP = 0      # op id (>= 0 live; BOTTOM empty) — see TASK_FAMILIES
F_TID = 6     # global task id (indexes the multiplicity counter buffer)
F_COST = 7    # tile-slots this task occupies (the lockstep clock cost model)

# -- attention family operands (fields 1-5) ---------------------------------
F_B = 1       # batch row
F_H = 2       # head of the q block: query head (flash), KV head (decode)
F_QS = 3      # first q row of the tile
F_QL = 4      # number of live q rows (< bq on a ragged tail tile)
F_KV = 5      # kv end, exclusive (== sequence length)

# -- expert family operands (fields 1-3; 4-5 unused) ------------------------
F_E = 1       # expert id (indexes the stacked expert weight arrays)
F_RS = 2      # first routed row of the tile (into the grouped routed arrays)
F_RL = 3      # number of live routed rows (< bt on a ragged tail tile)

OP_FLASH_TILE = 0
OP_DECODE_TILE = 1
OP_EXPERT_TILE = 2


@dataclass(frozen=True)
class TaskFamily:
    """One workload plugged into the shared queue/kernel/clock machinery.

    ``ops``: the op codes the family owns; ``operands``: record fields 1-5 by
    name; ``cost_unit``: what one tile-slot of :data:`F_COST` measures — makespans
    are comparable only within a family.
    """

    name: str
    ops: Tuple[int, ...]
    operands: Tuple[str, ...]
    cost_unit: str


TASK_FAMILIES: Dict[str, TaskFamily] = {}
_OP_TO_FAMILY: Dict[int, TaskFamily] = {}


def register_family(family: TaskFamily) -> TaskFamily:
    """Register a task family; op codes must be globally unique."""
    for op in family.ops:
        prev = _OP_TO_FAMILY.get(op)
        if prev is not None and prev.name != family.name:
            raise ValueError(f"op {op} already owned by family {prev.name!r}")
        _OP_TO_FAMILY[op] = family
    TASK_FAMILIES[family.name] = family
    return family


def family_of(op: int) -> TaskFamily:
    return _OP_TO_FAMILY[op]


ATTENTION_FAMILY = register_family(
    TaskFamily(
        name="attention",
        ops=(OP_FLASH_TILE, OP_DECODE_TILE),
        operands=("b", "h", "q_start", "q_len", "kv_end"),
        cost_unit="kv blocks",
    )
)

EXPERT_FAMILY = register_family(
    TaskFamily(
        name="expert",
        ops=(OP_EXPERT_TILE,),
        operands=("expert", "row_start", "row_len"),
        cost_unit="routed token rows",
    )
)


@dataclass(frozen=True)
class TileTask:
    """Attention-family task: one (b, h, q-block) tile sweeping kv [0, kv_end)."""

    op: int
    b: int
    h: int
    q_start: int
    q_len: int
    kv_end: int
    tid: int
    cost: int

    @property
    def owner(self) -> int:
        """Owner-queue key for ``partition_tasks(partition="owner")``."""
        return self.b

    def encode(self) -> np.ndarray:
        return np.array(
            [self.op, self.b, self.h, self.q_start, self.q_len,
             self.kv_end, self.tid, self.cost],
            dtype=np.int32,
        )


@dataclass(frozen=True)
class ExpertTask:
    """Expert-family task: ``row_len`` routed rows of one expert's FFN.

    ``row_start`` indexes the expert-grouped routed arrays (token indices /
    gates laid out contiguously per expert — see ``moe_ws.dispatch``), so
    each task owns a disjoint contiguous slice of the routed output, exactly
    as an attention tile owns its q-block rows.  ``cost`` is the number of
    live rows: expert FFN work is tokens × d_ff and d_ff is uniform across
    experts, so token rows are the tile-slot unit.
    """

    expert: int
    row_start: int
    row_len: int
    tid: int
    cost: int
    op: int = OP_EXPERT_TILE

    @property
    def owner(self) -> int:
        return self.expert

    def encode(self) -> np.ndarray:
        return np.array(
            [self.op, self.expert, self.row_start, self.row_len,
             BOTTOM, BOTTOM, self.tid, self.cost],
            dtype=np.int32,
        )


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def emit_flash_tasks(lengths, n_heads: int, bq: int, bk: int, causal: bool = True):
    """One task per live (b, h, q-block) of a ragged batch.

    ``lengths[b]`` is the true sequence length of batch row ``b``; rows past
    it produce no tasks at all — this is where the ragged workload's
    imbalance comes from (a 4× longer sequence yields ~16× the causal tile
    cost, all landing on one batch row).
    """
    tasks = []
    tid = 0
    for b, ln in enumerate(np.asarray(lengths, dtype=np.int64)):
        ln = int(ln)
        for h in range(n_heads):
            for qi in range(_cdiv(ln, bq)):
                qs = qi * bq
                ql = min(bq, ln - qs)
                kv_end = min(qs + bq, ln) if causal else ln
                cost = max(1, _cdiv(kv_end, bk))
                tasks.append(
                    TileTask(OP_FLASH_TILE, b, h, qs, ql, ln, tid, cost)
                )
                tid += 1
    return tasks


def emit_decode_tasks(lengths, n_heads: int, bk: int, q_rows: int = 1):
    """One task per live (b, h): ``q_rows`` query rows sweeping kv [0, len).

    The decode front-end emits one task per (slot, KV head) — ``n_heads`` is
    Hkv and ``q_rows`` the G query heads that share each KV head, so a
    K/V block is read once for all G rows; multi-head attention is G = 1.
    """
    tasks = []
    tid = 0
    for b, ln in enumerate(np.asarray(lengths, dtype=np.int64)):
        ln = int(ln)
        if ln <= 0:
            continue
        for h in range(n_heads):
            tasks.append(
                TileTask(
                    OP_DECODE_TILE, b, h, 0, q_rows, ln, tid,
                    max(1, _cdiv(ln, bk)),
                )
            )
            tid += 1
    return tasks


def multiplicity_divisor(tasks, mult, out_shape) -> np.ndarray:
    """Per-output-row divisor [B, H, Sq] normalizing accumulated duplicates.

    Each q row belongs to exactly one task, so dividing its accumulated value
    by that task's execution count is exact.  Rows owned by no task (ragged
    padding) get divisor 1 and stay zero.
    """
    B, H, Sq = out_shape
    mult = np.asarray(mult)
    div = np.ones((B, H, Sq), dtype=np.float32)
    for t in tasks:
        div[t.b, t.h, t.q_start: t.q_start + t.q_len] = max(1, int(mult[t.tid]))
    return div


def total_cost(tasks) -> int:
    return int(sum(t.cost for t in tasks))


def max_cost(tasks) -> int:
    return max((t.cost for t in tasks), default=0)

"""Fault plan for the serving layer (the frontend's replicas).

:class:`ReplicaCrashPlan`, consumed by :mod:`repro.serving.engine`, kills
replicas at chosen frontend iterations.  The frontend collects the dead
replica's in-flight requests and re-admits them to survivors
**idempotently**: the resume request's prompt is the original prompt plus
the tokens already emitted, its budget is the remaining budget, and
completion reassembly splices the pre-crash emission back in front — so a
request's final stream is identical to an uninterrupted run (greedy decode
is deterministic) and no token is ever emitted twice.  The dead replica's
*queue* survives the crash: queued-but-unadmitted work is stolen by the
survivors, which is the paper's whole point.

The plan is data-only (no engine imports) so chaos stays a leaf package.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict


@dataclass(frozen=True)
class ReplicaCrashPlan:
    """``crash_at[replica] = frontend iteration`` at which that replica's
    batcher dies (slots lost, queue surviving)."""

    crash_at: Dict[int, int] = field(default_factory=dict)

    def due(self, iteration: int):
        return [r for r, it in self.crash_at.items() if it == iteration]

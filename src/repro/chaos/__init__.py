"""repro.chaos — deterministic fault injection + relaxed-semantics safety
checking for the fence-free work-stealing stack (DESIGN.md §9).

Scheduler layer: :class:`FaultPlan` (seeded stalls / advisory corruption /
head-rewind storms / kill-and-relaunch) driven through launch segments by
:func:`run_with_faults`, with :class:`SafetyChecker` verifying the paper's
§7 contract (no lost task, bounded multiplicity, normalized bit-parity)
over the trace rings.  Serving layer: :class:`ReplicaCrashPlan` for
replica crashes.
"""

from repro.chaos.checker import ChaosReport, SafetyChecker, Violation
from repro.chaos.inject import ChaosRunResult, Segment, run_with_faults
from repro.chaos.plan import (
    ADVISORY_MODES,
    FaultPlan,
    RewindSpec,
    apply_rewind,
    corrupt_advisory,
    resume_state,
    seed_advisory,
)
from repro.chaos.serving import ReplicaCrashPlan

__all__ = [
    "ADVISORY_MODES",
    "ChaosReport",
    "ChaosRunResult",
    "FaultPlan",
    "ReplicaCrashPlan",
    "RewindSpec",
    "SafetyChecker",
    "Segment",
    "Violation",
    "apply_rewind",
    "corrupt_advisory",
    "resume_state",
    "run_with_faults",
    "seed_advisory",
]

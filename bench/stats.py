"""Arithmetic from the host's record of a run to end-to-end metrics.

A ``Record`` holds, per request, when it was due (open loop) or sent
(closed loop), when it was admitted into a slot, and the time at which
each of its output tokens reached the host.  Every metric is taken over
the whole measured window ``[t0, t1)``: no median of chunks.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class ReqLog:
    rid: int
    sent: float                  # due time (open loop) or send time (closed)
    in_window: bool              # sent inside the measured window
    warm: bool = False           # a set-up request that only compiles
    admitted: float | None = None
    token_times: list = field(default_factory=list)
    completed: bool = False
    rejected: bool = False
    duplicated: bool = False
    dropped: bool = False        # admitted, then neither in a slot nor
                                 # finished with the tokens it asked for


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linearly interpolated (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def output_tok_s(logs, t0: float, t1: float) -> float:
    """Output tokens that reached the host inside the window, per second."""
    return sum(1 for r in logs for t in r.token_times if t0 <= t < t1) / (t1 - t0)


def itl_gaps_ms(logs, t0: float, t1: float) -> list[float]:
    """Gaps between consecutive output tokens of each request, both tokens
    inside the window."""
    gaps = []
    for r in logs:
        ts = [t for t in r.token_times if t0 <= t < t1]
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
    return gaps


def ttft_ms(logs) -> list[float]:
    """Time to first token of each request sent in the window, from when it
    was due (or sent); requests that never got a token are left out here
    and counted as failed."""
    return [(r.token_times[0] - r.sent) * 1e3
            for r in logs if r.in_window and r.token_times]


def attempted_failed(logs) -> tuple[int, int]:
    """Requests sent (the warm-up's aside), and those of them rejected,
    duplicated, dropped or never served a token by the end of the run."""
    sent = [r for r in logs if not r.warm]
    failed = sum(1 for r in sent
                 if r.rejected or r.duplicated or r.dropped or not r.token_times)
    return len(sent), failed

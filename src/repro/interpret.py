"""The one place that decides whether Pallas kernels run interpreted.

Pallas interprets a kernel exactly when JAX's default backend is the CPU,
where Mosaic cannot compile it; on a TPU every kernel is compiled.  No entry
point takes an ``interpret`` option: a kernel that ran interpreted on a TPU
would execute as emulated XLA ops and hide the device it is meant to
measure.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True when Pallas kernels must run in the interpreter (CPU backend)."""
    return jax.default_backend() == "cpu"

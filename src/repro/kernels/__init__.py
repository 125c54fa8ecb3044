"""repro.kernels — Pallas TPU kernels for the framework's compute hot spots.

The paper's contribution is synchronization-level (no kernel-level claims);
these kernels serve the model stack's hot spots per the mandate: fused
attention (train/prefill), SSD scan (mamba2/zamba2) and split-K decode
attention.  Each has a pure-jnp oracle in ref.py; Pallas interprets them on a CPU
backend and compiles them on a TPU (:mod:`repro.interpret`).
"""

from .decode_attention.ops import decode_attention
from .flash_attention.ops import flash_attention
from .ssd_scan.ops import ssd_scan

__all__ = ["decode_attention", "flash_attention", "ssd_scan"]

"""jit'd wrapper for the SSD scan kernel.

Backward: recompute via the chunked jnp formulation (models.ssm.ssd_chunked
is numerically identical); jax.vjp of that form gives exact gradients with
O(chunk^2) memory.  On real TPU the backward would be a mirrored Pallas
kernel running the recurrence in reverse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.ssm import ssd_chunked

from .kernel import ssd_scan as _ssd_scan_kernel


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, A, B, C, chunk=128):
    """x: [b,S,H,P]; dt: [b,S,H]; A: [H]; B,C: [b,S,N] -> y [b,S,H,P]."""
    y, _ = _ssd_scan_kernel(x, dt, A, B, C, chunk=chunk)
    return y


def _fwd(x, dt, A, B, C, chunk):
    y, _ = _ssd_scan_kernel(x, dt, A, B, C, chunk=chunk)
    return y, (x, dt, A, B, C)


def _bwd(chunk, res, dy):
    x, dt, A, B, C = res
    _, vjp = jax.vjp(lambda *args: ssd_chunked(*args, chunk=chunk)[0], x, dt, A, B, C)
    return vjp(dy.astype(jnp.result_type(x)))


ssd_scan.defvjp(_fwd, _bwd)

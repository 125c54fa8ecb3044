"""jit'd wrapper for the split-K decode attention kernel (inference-only:
no VJP needed — decode never backprops)."""

from __future__ import annotations

import functools

import jax

from .kernel import decode_attention as _kernel


@functools.partial(jax.jit, static_argnames=("window", "bk"))
def decode_attention(q, k, v, pos, *, window: int = 0, bk: int = 512):
    return _kernel(q, k, v, pos, window=window, bk=bk)


def ragged_decode_attention(
    q, k, v, lengths, *, schedule="ws", n_programs=8, bk=None,
    return_stats=False,
):
    """Decode attention over ragged KV caches (per-sequence lengths).

    ``schedule="ws"`` dispatches one task per live (batch, KV head), its
    query heads as the tile's rows, through the fence-free work-stealing
    megakernel (:mod:`repro.pallas_ws`) so long caches don't serialize one
    grid program; ``schedule="static"`` is the no-steal baseline.
    """
    from repro.pallas_ws.ragged import ragged_decode_attention as _impl

    return _impl(
        q, k, v, lengths, schedule=schedule, n_programs=n_programs,
        bk=bk, return_stats=return_stats,
    )

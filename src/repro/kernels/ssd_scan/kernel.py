"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid: (B, H, n_chunks) with the chunk dim innermost and sequential; the
recurrent state [P, N] lives in VMEM scratch and is carried across chunks
(the TPU-native replacement for the GPU warp-level scan: the MXU computes
the intra-chunk quadratic term; the inter-chunk recurrence is just a rank-1
update on a resident VMEM tile).

Per (b, h, chunk) block:
  y_diag = (C B^T ∘ L) (x·dt)          — intra-chunk, lower-tri decay L
  y_off  = C S_prev^T ∘ exp(cumsum dA) — contribution of the carried state
  S     <- exp(sum dA) * S_prev + (B decay)^T (x·dt)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.interpret import interpret_mode


def _ssd_kernel(
    x_ref, dt_ref, A_ref, B_ref, C_ref,  # inputs
    y_ref, fin_ref,  # outputs
    state_scr,  # scratch [P, N] f32
    *, nc: int, Q: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # [Q]
    A = A_ref[0]  # scalar f32
    Bm = B_ref[0, 0].astype(jnp.float32)  # [Q, N]
    Cm = C_ref[0, 0].astype(jnp.float32)  # [Q, N]

    xdt = x * dt[:, None]
    dA = dt * A  # [Q]
    cs = jnp.cumsum(dA)

    # intra-chunk: L[i, j] = exp(cs_i - cs_j) for i >= j
    ss = cs[:, None] - cs[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (Q, Q), 1
    )
    L = jnp.where(tri, jnp.exp(ss), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, Q]
    y = jax.lax.dot_general(
        scores * L, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, P]

    # carried state contribution: y_off[q] = exp(cs_q) * C_q . S_prev
    s_prev = state_scr[...]  # [P, N]
    y_off = jax.lax.dot_general(
        Cm, s_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, P]
    y = y + y_off * jnp.exp(cs)[:, None]

    # state update: S = exp(sum dA) * S_prev + sum_q decay_q * xdt_q B_q^T
    decay = jnp.exp(cs[-1] - cs)  # [Q]
    upd = jax.lax.dot_general(
        xdt * decay[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [P, N]
    state_scr[...] = jnp.exp(cs[-1]) * s_prev + upd

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _flush():
        fin_ref[0, 0] = state_scr[...]


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128):
    """x: [b, S, H, P]; dt: [b, S, H]; A: [H] f32; B, C: [b, S, N].

    Returns (y: [b, S, H, P] in x.dtype, final_state: [b, H, P, N] f32).
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    assert nc * Q == S, (S, Q)
    # layout: chunk-major per (b, h)
    xr = x.transpose(0, 2, 1, 3).reshape(b, H, nc, Q, P)
    dtr = dt.transpose(0, 2, 1).reshape(b, H, nc, Q)
    Br = B.reshape(b, nc, Q, N)
    Cr = C.reshape(b, nc, Q, N)
    A = A.astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, nc=nc, Q=Q)
    y, fin = pl.pallas_call(
        kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1,), lambda i, h, c: (h,)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, c: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, h, c: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, nc, Q, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(),
    )(xr, dtr, A, Br, Cr)
    y = y.reshape(b, H, S, P).transpose(0, 2, 1, 3)
    return y, fin

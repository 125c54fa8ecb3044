"""Single-token decode attention as a Pallas TPU kernel (split-K).

Grid: (B, H, nk) with the KV-block dim innermost/sequential; the running
online-softmax state (m, l, acc) lives in VMEM scratch across the KV sweep.
This is the flash-decoding pattern adapted to TPU: each KV block is a
[bk, hd] VMEM tile contracted on the MXU against one query row; partial
softmax states merge in registers rather than via a cross-SM reduction
(the GPU formulation) — on TPU the sequential grid IS the merge.

Blocks entirely past `pos` (or outside the sliding window) are skipped with
pl.when — decode reads only ~pos/S of the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.interpret import interpret_mode

NEG_INF = -1e30


def _decode_kernel(
    pos_ref,  # scalar prefetch-style input [1] int32
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, window: int, bk: int, nk: int,
):
    ki = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * bk
    live = k_start <= pos
    if window > 0:
        live &= pos - (k_start + bk - 1) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [1, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [1, bk]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = kpos <= pos
        if window > 0:
            valid &= pos - kpos < window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(
    q, k, v, pos, *, window: int = 0, bk: int = 512
):
    """q: [B, H, hd]; k, v: [B, Hkv, S, hd]; pos scalar int32 -> [B, H, hd]."""
    B, H, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = H // Hkv
    bk = min(bk, S)
    nk = S // bk
    assert nk * bk == S, (S, bk)
    q4 = q[:, :, None, :]  # [B, H, 1, hd]
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, scale=hd**-0.5, window=window, bk=bk, nk=nk
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # pos scalar
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(),
    )(pos_arr, q4, k, v)
    return out[:, :, 0, :]

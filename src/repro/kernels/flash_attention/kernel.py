"""Flash attention forward as a Pallas TPU kernel.

TPU adaptation of the paper-adjacent hot spot (see DESIGN.md §5): online-
softmax tiling sized for VMEM, MXU-aligned blocks (bq/bk/hd multiples of 128
on real hardware; tests sweep smaller shapes in interpret mode).

Grid: (B, H, nq, nk) with nk innermost and *sequentially* iterated, so the
running max / sum / accumulator live in VMEM scratch across the k sweep of
one (b, h, qi) cell.  GQA is handled in the BlockSpec index_map: query head
h reads kv head h // (H // Hkv) — no materialized head expansion.

Causal / sliding-window masking is applied inside the block; fully-masked
(q, k) block pairs are skipped with pl.when (the compute-roofline win of
causal flash: ~2x at long S).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.interpret import interpret_mode

NEG_INF = -1e30


def _fwd_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref, lse_ref,  # outputs
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, causal: bool, window: int, bq: int, bk: int, nk: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # block-level skip: a (q, k) block pair is live unless fully masked
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1  # newest q sees oldest k
    if window > 0:
        live &= q_start - (k_start + bk - 1) < window  # oldest q in window of newest k

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

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
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def flash_attention_fwd(
    q, k, v, *, causal: bool = True, window: int = 0,
    bq: int = 128, bk: int = 128,
):
    """q: [B, H, S, hd]; k, v: [B, Hkv, S, hd] -> (out, lse [B, H, S])."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    bq = min(bq, S)
    bk = min(bk, S)
    nq, nk = S // bq, S // bk
    assert nq * bq == S and nk * bk == S, (S, bq, bk)
    scale = hd**-0.5

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(),
    )(q, k, v)
    return out, lse

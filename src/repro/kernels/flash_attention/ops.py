"""jit'd wrapper for the flash attention kernel, with custom VJP.

Forward: the Pallas kernel (compiled on a TPU, interpreted on a CPU).
Backward: the standard flash backward recomputed from the saved logsumexp,
written as a chunked pure-jnp pass (O(chunk^2) memory).  On real TPU the
backward would also be a Pallas kernel; the jnp form keeps the same HLO
FLOPs and is exact, so roofline terms and numerics are unaffected.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_fwd


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(q, k, v, causal=True, window=0, bq=128, bk=128):
    """q: [B, H, S, hd]; k, v: [B, Hkv, S, hd] -> [B, H, S, hd]."""
    out, _ = flash_attention_fwd(
        q, k, v, causal=causal, window=window, bq=bq, bk=bk
    )
    return out


def _fwd(q, k, v, causal, window, bq, bk):
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, window=window, bq=bq, bk=bk
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, window, bq, bk, res, do):
    q, k, v, out, lse = res
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = hd**-0.5
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    D = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # [B, H, S]

    c = min(bq, S)
    nq = S // c
    qs = qf.reshape(B, H, nq, c, hd)
    dos = dof.reshape(B, H, nq, c, hd)
    lses = lse.reshape(B, H, nq, c)
    Ds = D.reshape(B, H, nq, c)
    qpos_base = jnp.arange(c, dtype=jnp.int32)
    kpos = jnp.arange(S, dtype=jnp.int32)

    def q_chunk(carry, xs):
        dk, dv = carry
        qi, qb, dob, lseb, Db = xs
        qpos = qi * c + qpos_base
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * scale
        mask = jnp.ones((c, S), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask &= qpos[:, None] - kpos[None, :] < window
        p = jnp.where(mask, jnp.exp(s - lseb[..., None]), 0.0)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, dob)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vf)
        ds = p * (dp - Db[..., None]) * scale
        dq_i = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qb)
        return (dk, dv), dq_i

    zeros = jnp.zeros((B, H, S, hd), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_chunk,
        (zeros, zeros),
        (
            jnp.arange(nq),
            qs.transpose(2, 0, 1, 3, 4),
            dos.transpose(2, 0, 1, 3, 4),
            lses.transpose(2, 0, 1, 3),
            Ds.transpose(2, 0, 1, 3),
        ),
    )
    dq = dqs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, hd)
    # GQA: fold query-head grads back onto kv heads
    dk = dk.reshape(B, Hkv, G, S, hd).sum(axis=2)
    dv = dv.reshape(B, Hkv, G, S, hd).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd, _bwd)


def ragged_flash_attention(
    q, k, v, lengths, *, causal=True, schedule="ws", n_programs=8,
    bq=32, bk=32, return_stats=False,
):
    """Ragged (variable-length) flash attention.

    ``schedule="ws"`` routes the imbalanced tile tasks through the
    device-resident fence-free work-stealing scheduler
    (:mod:`repro.pallas_ws`); ``schedule="static"`` drains the same queues
    without stealing — the static-grid baseline with identical numerics.
    """
    from repro.pallas_ws.ragged import ragged_flash_attention as _impl

    return _impl(
        q, k, v, lengths, causal=causal, schedule=schedule,
        n_programs=n_programs, bq=bq, bk=bk, return_stats=return_stats,
    )

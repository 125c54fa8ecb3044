"""Attention variants: GQA (full / causal / sliding-window), MLA (deepseek-v2).

Two execution paths per variant:

* train/prefill — chunked flash-style attention in pure jnp (`flash_ref`):
  outer scan over query chunks, inner scan over key chunks with an online
  softmax, so peak memory is O(chunk²) not O(S²).  This is also the oracle
  for the Pallas kernels in ``repro.kernels``; the dry-run lowers this path.
* decode — one query token against a [B, S, ...] KV cache.  The cache is
  sequence-sharded over the `model` mesh axis (flash-decoding split-K: the
  softmax reduction over S lowers to a psum), which is the only layout that
  both fits HBM at decode_32k/long_500k and needs no head divisibility.

MLA decode uses the *absorbed* formulation: the cache stores the kv_lora
latent (512+64 floats/token instead of 2·H·hd) and W_uk / W_uv are folded
into the query / output projections.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.wstrace import spans

from .common import apply_rope, dense_init
from .sharding import shard


def _pet(cfg):
    """Accumulation dtype for model-sharded contractions (cfg.bf16_reduce)."""
    return jnp.bfloat16 if getattr(cfg, "bf16_reduce", False) else None


# ---------------------------------------------------------------------------
# parameter init


def init_gqa(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.hd
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    Hp, Hkvp = cfg.eff_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (d, Hp, hd), dtype),
        "wk": dense_init(ks[1], d, (d, Hkvp, hd), dtype),
        "wv": dense_init(ks[2], d, (d, Hkvp, hd), dtype),
        "wo": dense_init(ks[3], H * hd, (Hp, hd, d), dtype),
    }
    if Hp != H or Hkvp != Hkv:
        # zero the padded slices: exactly fwd/bwd-equivalent (EXPERIMENTS §Perf)
        G, Gp = H // Hkv, Hp // Hkvp
        q_real = (jnp.arange(Hp) % Gp < G) & (jnp.arange(Hp) // Gp < Hkv)
        kv_real = jnp.arange(Hkvp) < Hkv
        p["wq"] = p["wq"] * q_real[None, :, None].astype(dtype)
        p["wo"] = p["wo"] * q_real[:, None, None].astype(dtype)
        p["wk"] = p["wk"] * kv_real[None, :, None].astype(dtype)
        p["wv"] = p["wv"] * kv_real[None, :, None].astype(dtype)
    return p


def init_mla(key, cfg, dtype):
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    qlr, kvlr, rhd, vhd = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_head_dim, cfg.v_hd
    ks = jax.random.split(key, 7)
    p = {
        "wdkv": dense_init(ks[0], d, (d, kvlr), dtype),
        "wkr": dense_init(ks[1], d, (d, rhd), dtype),
        "wuk": dense_init(ks[2], kvlr, (kvlr, H, hd), dtype),
        "wuv": dense_init(ks[3], kvlr, (kvlr, H, vhd), dtype),
        "wo": dense_init(ks[4], H * vhd, (H, vhd, d), dtype),
    }
    if qlr:
        p["wdq"] = dense_init(ks[5], d, (d, qlr), dtype)
        p["wuq"] = dense_init(ks[6], qlr, (qlr, H, hd + rhd), dtype)
    else:
        p["wq"] = dense_init(ks[5], d, (d, H, hd + rhd), dtype)
    return p


# ---------------------------------------------------------------------------
# chunked flash reference (train / prefill)


def _pick_chunk(S: int, chunk: int) -> int:
    """Largest divisor of S that is <= chunk (handles e.g. S=4352 for VLM
    patches+text sequences)."""
    c = min(chunk, S)
    while S % c != 0:
        c -= 1
    return c


def flash_ref(q, k, v, *, causal: bool, window, chunk: int = 1024):
    """Online-softmax attention. q,k,v: [B, S, H, hd] (kv already head-expanded).

    Returns [B, S, H, hd_v].  Masking: causal and/or sliding window
    (key within `window` positions behind the query).  `window` may be a
    *traced* int32 scalar (per-layer windows ride the layer scan); window<=0
    means full attention.

    A STATIC python-int window > 0 selects the *banded* implementation:
    each query chunk contracts only the ceil(window/chunk)+1 key chunks it
    can see, so compute and HBM traffic scale with S*window instead of S²
    (§Perf: the sliding-window archs' prefill/train win).

    Carries a custom VJP: the backward recomputes P blockwise from the saved
    logsumexp (flash semantics), so training memory is O(S·hd) per layer
    instead of O(S²/chunk) saved score blocks.
    """
    if (
        isinstance(window, int)
        and window > 0
        and causal
        and q.shape[1] == k.shape[1]
        and q.shape[1] > window
    ):
        return _flash_banded(q, k, v, window, chunk)
    win = jnp.asarray(window, jnp.int32)
    return _flash(q, k, v, win, jnp.int32(0), causal, chunk)


def _flash_banded(q, k, v, window: int, chunk: int):
    """Causal sliding-window attention over a static band of key chunks.

    Key chunks are gathered per query chunk with dynamic slices (scan-
    friendly: the band width nb = ceil(window/c)+1 is static), then handed
    to the same custom-VJP flash core with a query-position offset so the
    masking stays exact.
    """
    B, S, H, hd = q.shape
    c = _pick_chunk(S, min(chunk, max(window, 16)))
    nq = S // c
    nb = min(-(-window // c) + 1, nq)  # key chunks visible to one q chunk
    kr = k.reshape(B, nq, c, H, hd)
    vr = v.reshape(B, nq, c, H, v.shape[-1])
    qr = q.reshape(B, nq, c, H, hd).transpose(1, 0, 2, 3, 4)  # [nq, B, c, H, hd]
    win = jnp.asarray(window, jnp.int32)

    def q_block(_, qi_qb):
        qi, qb = qi_qb  # [B, c, H, hd]
        lo = jnp.maximum(qi - (nb - 1), 0)
        kb = jax.lax.dynamic_slice_in_dim(kr, lo, nb, axis=1)  # [B, nb, c, ...]
        vb = jax.lax.dynamic_slice_in_dim(vr, lo, nb, axis=1)
        kf = kb.reshape(B, nb * c, H, hd)
        vf = vb.reshape(B, nb * c, H, vb.shape[-1])
        qoff = (qi - lo) * c  # q-chunk start within the gathered band
        out = _flash(qb, kf, vf, win, qoff, True, c)
        return None, out

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qr))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def _flash_mask(s, qpos, kpos, causal, win):
    mask = jnp.ones(s.shape[-2:], dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    mask &= jnp.where(win > 0, qpos[:, None] - kpos[None, :] < win, True)
    return jnp.where(mask, s, -1e30)


def _flash_fwd_impl(q, k, v, win, qoff, causal, chunk):
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    hdv = v.shape[-1]
    c = _pick_chunk(Sq, chunk)
    ck = _pick_chunk(Sk, chunk)
    nq, nk = Sq // c, Sk // ck
    scale = hd ** -0.5
    qs = q.reshape(B, nq, c, H, hd).transpose(1, 0, 3, 2, 4)  # [nq, B, H, c, hd]
    ks_ = k.reshape(B, nk, ck, H, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, nk, ck, H, hdv).transpose(1, 0, 3, 2, 4)
    pos = jnp.arange(c, dtype=jnp.int32)
    posk = jnp.arange(ck, dtype=jnp.int32)

    def q_block(_, qi_qb):
        qi, qb = qi_qb  # [B, H, c, hd]
        qpos = qoff + qi * c + pos

        def k_block(carry, ki_kb_vb):
            m, l, acc = carry
            ki, kb, vb = ki_kb_vb
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb).astype(jnp.float32) * scale
            s = _flash_mask(s, qpos, ki * ck + posk, causal, win)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, H, c), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, c), jnp.float32)
        a0 = jnp.zeros((B, H, c, hdv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0), (jnp.arange(nk), ks_, vs)
        )
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]
        return None, (out.astype(q.dtype), m + jnp.log(l))

    _, (outs, lses) = jax.lax.scan(q_block, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hdv)
    lse = lses.transpose(1, 0, 3, 2).reshape(B, Sq, H)  # [nq,B,H,c]->[B,Sq,H]
    return out, lse


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k, v, win, qoff, causal, chunk):
    out, _ = _flash_fwd_impl(q, k, v, win, qoff, causal, chunk)
    return out


def _flash_vjp_fwd(q, k, v, win, qoff, causal, chunk):
    out, lse = _flash_fwd_impl(q, k, v, win, qoff, causal, chunk)
    return out, (q, k, v, win, qoff, out, lse)


def _flash_vjp_bwd(causal, chunk, res, do):
    """Flash backward: P recomputed per (q-chunk, k-chunk) block from the
    saved lse; transients are O(chunk²), dk/dv accumulate in f32."""
    q, k, v, win, qoff, out, lse = res
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    hdv = v.shape[-1]
    scale = hd ** -0.5
    c = _pick_chunk(Sq, chunk)
    ck = _pick_chunk(Sk, chunk)
    nq, nk = Sq // c, Sk // ck
    D = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,Sq,H]
    kpos_base = jnp.arange(ck, dtype=jnp.int32)
    qpos_base = jnp.arange(c, dtype=jnp.int32)
    ks_ = k.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4).astype(jnp.float32)
    vs = v.reshape(B, nk, ck, H, hdv).transpose(1, 0, 2, 3, 4).astype(jnp.float32)

    def q_chunk(carry, xs):
        dk, dv = carry  # [nk, B, ck, H, hd/v] f32
        qi, qb, dob, lseb, Db = xs  # qb [B,c,H,hd] f32
        qpos = qoff + qi * c + qpos_base

        def k_chunk(inner, xs2):
            dq_i, dk, dv = inner
            ki, kb, vb = xs2  # [B, ck, H, hd]
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * scale
            s = _flash_mask(s, qpos, ki * ck + kpos_base, causal, win)
            p = jnp.exp(s - lseb.transpose(0, 2, 1)[..., None])  # [B,H,c,ck]
            dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, dob)
            dp = jnp.einsum("bqhd,bkhd->bhqk", dob, vb)
            ds = p * (dp - Db.transpose(0, 2, 1)[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bhqk,bkhd->bqhd", ds, kb)
            dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, qb)
            dk = dk.at[ki].add(dk_blk)
            dv = dv.at[ki].add(dv_blk)
            return (dq_i, dk, dv), None

        dq0 = jnp.zeros((B, c, H, hd), jnp.float32)
        (dq_i, dk, dv), _ = jax.lax.scan(
            k_chunk, (dq0, dk, dv), (jnp.arange(nk), ks_, vs)
        )
        return (dk, dv), dq_i

    zk = jnp.zeros((nk, B, ck, H, hd), jnp.float32)
    zv = jnp.zeros((nk, B, ck, H, hdv), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_chunk,
        (zk, zv),
        (
            jnp.arange(nq),
            q.reshape(B, nq, c, H, hd).transpose(1, 0, 2, 3, 4).astype(jnp.float32),
            do.reshape(B, nq, c, H, hdv).transpose(1, 0, 2, 3, 4).astype(jnp.float32),
            lse.reshape(B, nq, c, H).transpose(1, 0, 2, 3),
            D.reshape(B, nq, c, H).transpose(1, 0, 2, 3),
        ),
    )
    dq = dqs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Sk, H, hd)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Sk, H, hdv)
    import numpy as _np

    dwin = _np.zeros(jnp.shape(win), jax.dtypes.float0)
    dqoff = _np.zeros(jnp.shape(qoff), jax.dtypes.float0)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dwin, dqoff





_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def expand_kv(k, n_rep: int):
    """[B, S, Hkv, hd] -> [B, S, Hkv*n_rep, hd] (GQA head expansion)."""
    if n_rep == 1:
        return k
    B, S, Hkv, hd = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (B, S, Hkv, n_rep, hd)
    ).reshape(B, S, Hkv * n_rep, hd)


# ---------------------------------------------------------------------------
# GQA


def gqa_train(x, p, cfg, positions, window, chunk: int = 1024):
    """Causal (optionally windowed) self-attention over [B, S, d].

    Head counts come from the weight shapes (cfg.eff_heads at init), so
    zero-padded-head configs flow through unchanged.
    """
    H, Hkv = p["wq"].shape[1], p["wk"].shape[1]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = shard(q, "dp", None, "tp", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k = expand_kv(k, H // Hkv)
    v = expand_kv(v, H // Hkv)
    k = shard(k, "dp", None, "tp", None)
    v = shard(v, "dp", None, "tp", None)
    o = flash_ref(q, k, v, causal=True, window=window, chunk=chunk)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"], preferred_element_type=_pet(cfg)).astype(x.dtype)


class KVCache(NamedTuple):
    k: jnp.ndarray  # [B, S, Hkv, hd]
    v: jnp.ndarray


def broadcast_pos(pos, B: int):
    """Scalar or [B] int32 -> [B] (per-slot decode positions)."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))


def _update_at(cache, new, pos_b):
    """cache: [B, S, ...]; new: [B, 1, ...]; pos_b: [B] -> per-row write."""
    return jax.vmap(
        lambda c, n, p_: jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (p_,) + (0,) * (c.ndim - 1)
        )
    )(cache, new, pos_b)


def _project_qkv(x, p, cfg, pos_b):
    """Project q/k/v for the new token and rope them at the per-slot
    positions: (q, k_new, v_new), each [B, 1, heads, hd]."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k_new = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v_new = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, pos_b[:, None], cfg.rope_theta)
    return q, k_new, v_new


def _decode_qkv(x, p, cfg, cache: KVCache, pos_b):
    """Shared decode prologue: :func:`_project_qkv`, then splice k/v into
    the cache.  Returns (q [B, 1, H, hd], updated KVCache)."""
    q, k_new, v_new = _project_qkv(x, p, cfg, pos_b)
    return q, KVCache(_update_at(cache.k, k_new, pos_b), _update_at(cache.v, v_new, pos_b))


def gqa_decode(x, p, cfg, cache: KVCache, pos, window):
    """One-token decode. x: [B, 1, d]; pos: scalar or [B] int32 (tokens so
    far per slot — continuous batching runs heterogeneous positions).

    Attends over cache slots [0, pos_b]; the new token's K/V is written at
    `pos_b`.  Scores are computed in the grouped layout (no head expansion)
    so the S-sharded cache is contracted directly: softmax over S -> psum.
    """
    B = x.shape[0]
    hd = cfg.hd
    H, Hkv = p["wq"].shape[1], p["wk"].shape[1]
    G = H // Hkv
    S = cache.k.shape[1]
    pos_b = broadcast_pos(pos, B)
    q, new_cache = _decode_qkv(x, p, cfg, cache, pos_b)
    kc, vc = new_cache.k, new_cache.v

    qg = q.reshape(B, Hkv, G, hd)
    # NOTE: a banded decode (dynamic window slice of the cache) was tried
    # and REFUTED in §Perf: with the split-K sequence-sharded cache the
    # per-slot window slice forces a reshard (collective) and net-loses;
    # the full-S masked contraction below keeps the reduction local.
    s = jnp.einsum("bkgd,bskd->bskg", qg, kc).astype(jnp.float32) * hd**-0.5
    kpos = jnp.arange(S, dtype=jnp.int32)
    valid = kpos[None, :] <= pos_b[:, None]  # [B, S]
    win = jnp.asarray(window, jnp.int32)
    valid &= jnp.where(win > 0, pos_b[:, None] - kpos[None, :] < win, True)
    s = jnp.where(valid[:, :, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=1)
    o = jnp.einsum("bskg,bske->bkge", w.astype(vc.dtype), vc)
    o = o.reshape(B, 1, H, hd)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"]), new_cache


def gqa_decode_ws(x, p, cfg, cache: KVCache, pos, *, schedule="ws", bk=None,
                  n_programs=8):
    """One-token decode with the attention core routed through the
    device-resident work-stealing scheduler (repro.pallas_ws).

    Same projections/rope/cache splice as :func:`gqa_decode`; the masked
    dense contraction is replaced by ragged decode tiles over the *live*
    per-slot lengths ``pos_b + 1`` — short slots stop at their length
    instead of sweeping the padded cache, and thieves drain the long slot's
    queue.  Full attention only (window == 0).  Traced positions (the
    jitted serving path) route through the fixed-shape traced Put inside
    ``ragged_decode_attention``; concrete positions keep the host-side Put
    with its scheduling telemetry.  ``bk=None`` sweeps the cache in blocks
    of ``repro.pallas_ws.ragged.decode_block`` (512 positions of a bf16
    cache at hd 128).
    """
    from repro.pallas_ws.ragged import ragged_decode_attention

    B = x.shape[0]
    hd = cfg.hd
    H = p["wq"].shape[1]
    with jax.named_scope(spans.WS_PUT):
        pos_b = broadcast_pos(pos, B)
        if isinstance(pos_b, jax.core.Tracer):
            lengths = pos_b.astype(jnp.int32) + 1
        else:
            lengths = np.asarray(jax.device_get(pos_b)).astype(np.int64) + 1
    with jax.named_scope(spans.DENSE):
        q, k_new, v_new = _project_qkv(x, p, cfg, pos_b)
        q = q.reshape(B, H, hd)
    with jax.named_scope(spans.KV_LAYOUT):
        new_cache = KVCache(_update_at(cache.k, k_new, pos_b),
                            _update_at(cache.v, v_new, pos_b))
        kt = new_cache.k.transpose(0, 2, 1, 3)  # [B, S, Hkv, hd] -> [B, Hkv, S, hd]
        vt = new_cache.v.transpose(0, 2, 1, 3)
    o = ragged_decode_attention(
        q, kt, vt, lengths,
        schedule=schedule,
        n_programs=n_programs,
        bk=bk,
    )
    with jax.named_scope(spans.DENSE):
        o = o.reshape(B, 1, H, hd).astype(x.dtype)
        return jnp.einsum("bshe,hed->bsd", o, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2)


def _mla_q(x, p, cfg, positions):
    H, hd, rhd = cfg.n_heads, cfg.hd, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = jnp.einsum("bsd,dr->bsr", x, p["wdq"])
        q = jnp.einsum("bsr,rhe->bshe", cq, p["wuq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_train(x, p, cfg, positions, window: int, chunk: int = 1024):
    B, S, _ = x.shape
    H, hd, rhd, vhd = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_hd
    q_nope, q_rope = _mla_q(x, p, cfg, positions)
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])
    k_rope = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["wkr"])[:, :, None, :], positions, cfg.rope_theta
    )  # [B, S, 1, rhd] shared across heads
    k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["wuk"])
    v = jnp.einsum("bsr,rhe->bshe", ckv, p["wuv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rhd))], axis=-1)
    o = flash_ref(q, k, v, causal=True, window=window, chunk=chunk)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"], preferred_element_type=_pet(cfg)).astype(x.dtype)


class MLACache(NamedTuple):
    ckv: jnp.ndarray  # [B, S, kv_lora]
    kr: jnp.ndarray  # [B, S, rhd]


def mla_decode(x, p, cfg, cache: MLACache, pos):
    """Absorbed MLA decode: scores/outputs computed in the latent space."""
    B = x.shape[0]
    H, hd, rhd, vhd = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_hd
    S = cache.ckv.shape[1]
    pos_b = broadcast_pos(pos, B)
    q_nope, q_rope = _mla_q(x, p, cfg, pos_b[:, None])  # [B, 1, H, hd/rhd]
    ckv_new = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])
    kr_new = apply_rope(
        jnp.einsum("bsd,de->bse", x, p["wkr"])[:, :, None, :],
        pos_b[:, None], cfg.rope_theta,
    )[:, :, 0, :]
    ckv = _update_at(cache.ckv, ckv_new, pos_b)
    kr = _update_at(cache.kr, kr_new, pos_b)

    # absorb W_uk into q: [B, H, kv_lora]
    q_abs = jnp.einsum("bhe,rhe->bhr", q_nope[:, 0], p["wuk"])
    s = jnp.einsum("bhr,bsr->bsh", q_abs, ckv)
    s = s + jnp.einsum("bhe,bse->bsh", q_rope[:, 0], kr)
    s = s.astype(jnp.float32) * (hd + rhd) ** -0.5
    kpos = jnp.arange(S, dtype=jnp.int32)
    s = jnp.where((kpos[None, :] <= pos_b[:, None])[:, :, None], s, -1e30)
    w = jax.nn.softmax(s, axis=1)
    o_lat = jnp.einsum("bsh,bsr->bhr", w.astype(ckv.dtype), ckv)
    o = jnp.einsum("bhr,rhe->bhe", o_lat, p["wuv"])[:, None]  # [B, 1, H, vhd]
    return jnp.einsum("bshe,hed->bsd", o, p["wo"]), MLACache(ckv, kr)

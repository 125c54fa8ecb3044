"""Public model API: loss_fn (train), prefill, decode_step, cache init.

All functions are pure and mesh-agnostic; sharding enters only through
`repro.models.sharding.shard` constraints, which no-op without a mesh.

Batch dict layouts per family (everything int32/bf16 jnp arrays):
  lm / moe / ssm / hybrid : {"tokens": [B, S]}
  vlm                     : {"tokens": [B, S_text], "patches": [B, n_patches, d]}
  encdec                  : {"tokens": [B, S_text], "frames": [B, enc_S, d]}

Loss is next-token CE over the token positions (VLM: text only).  The vocab
axis stays sharded end-to-end (gold logit via an iota==label mask, reductions
lower to psum over the `model` axis).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.wstrace import spans

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import transformer as tf
from .common import rms_norm, swiglu
from .sharding import shard

AUX_LOSS_W = 0.01


# ---------------------------------------------------------------------------
# helpers


def _embed(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    return shard(x, "dp", None, None)


def _unembed_matrix(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"].T  # [d, V]
    return params["unembed"]


def _positions(B, S, offset=0):
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32) + offset, (B, S))


def vocab_parallel_xent(hidden, w_un, labels, mask=None, valid_vocab=None, row_weights=None):
    """CE keeping V sharded: logits [.., V]; gold via iota==label reduction.

    `valid_vocab` masks the padded vocab columns (cfg.padded_vocab > vocab).
    `row_weights` [B]: return sum_b w_b * token-mean(nll_b) instead of the
    global token mean (the WS scheduler's 1/count multiplicity weighting).
    """
    logits = jnp.einsum("bsd,dv->bsv", hidden, w_un).astype(jnp.float32)
    logits = shard(logits, "dp", None, "tp")
    vpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        logits = jnp.where(vpos < valid_vocab, logits, -1e30)
    gold = jnp.sum(jnp.where(vpos == labels[..., None], logits, 0.0), axis=-1)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)) + m
    nll = lse - gold
    mk = (
        mask.astype(jnp.float32)
        if mask is not None
        else jnp.ones(nll.shape, jnp.float32)
    )
    if row_weights is not None:
        row_mean = (nll * mk).sum(axis=1) / jnp.maximum(mk.sum(axis=1), 1.0)
        return (row_mean * row_weights).sum()
    return (nll * mk).sum() / jnp.maximum(mk.sum(), 1.0)


# ---------------------------------------------------------------------------
# training loss


def loss_fn(params, cfg, batch, *, remat: bool = True, chunk: int = 1024, row_weights=None):
    """Mean next-token CE (+ MoE aux). Returns (loss, metrics).

    `row_weights` [B]: weighted per-row losses (see vocab_parallel_xent) —
    the work-stealing scheduler's multiplicity correction."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.family == "encdec":
        enc_out = tf.encode(params, cfg, batch["frames"], remat=remat, chunk=chunk)
        x = _embed(params, cfg, tokens)
        h = tf.decoder_hidden(
            params, cfg, x, _positions(B, S), enc_out, remat=remat, chunk=chunk
        )
        aux = jnp.float32(0.0)
    elif cfg.family == "vlm":
        patches = batch["patches"].astype(jnp.dtype(cfg.dtype))
        x = jnp.concatenate([patches, _embed(params, cfg, tokens)], axis=1)
        Sp = x.shape[1]
        h, aux = tf.lm_hidden(params, cfg, x, _positions(B, Sp), remat=remat, chunk=chunk)
        h = h[:, patches.shape[1]:, :]  # text positions only
    else:
        x = _embed(params, cfg, tokens)
        h, aux = tf.lm_hidden(params, cfg, x, _positions(B, S), remat=remat, chunk=chunk)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    ce = vocab_parallel_xent(
        h, _unembed_matrix(params, cfg), labels, mask,
        valid_vocab=cfg.vocab_size, row_weights=row_weights,
    )
    loss = ce + AUX_LOSS_W * aux * (
        jnp.sum(row_weights) if row_weights is not None else 1.0
    )
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# caches


class Caches(NamedTuple):
    """Stacked per-layer decode state.  Unused fields are ()."""

    kv: Any = ()  # attention archs: KVCache/MLACache of [L, B, S, ...]
    ssm: Any = ()  # ssm/hybrid: SSMCache of [L, B, ...]
    shared_kv: Any = ()  # hybrid: KVCache of [n_apps, B, S, ...]
    cross_kv: Any = ()  # encdec: KVCache [L, B, enc_S, Hkv, hd]


def init_caches(cfg, batch: int, capacity: int, dtype=None) -> Caches:
    """Zeroed caches with `capacity` sequence slots."""
    dt = jnp.dtype(dtype or cfg.dtype)
    L = cfg.n_layers
    if cfg.family in ("ssm", "hybrid"):
        one = ssm_mod.init_ssm_cache(batch, cfg, dt)
        ssm_c = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape), one
        )
        shared = ()
        if cfg.family == "hybrid" and cfg.hybrid_attn_every:
            n_apps = L // cfg.hybrid_attn_every
            shared = attn.KVCache(
                k=jnp.zeros((n_apps, batch, capacity, cfg.eff_heads[1], cfg.hd), dt),
                v=jnp.zeros((n_apps, batch, capacity, cfg.eff_heads[1], cfg.hd), dt),
            )
        return Caches(ssm=ssm_c, shared_kv=shared)
    if cfg.attn_kind == "mla":
        kv = attn.MLACache(
            ckv=jnp.zeros((L, batch, capacity, cfg.kv_lora_rank), dt),
            kr=jnp.zeros((L, batch, capacity, cfg.rope_head_dim), dt),
        )
        return Caches(kv=kv)
    n_layers = cfg.n_dec_layers if cfg.family == "encdec" else L
    kv = attn.KVCache(
        k=jnp.zeros((n_layers, batch, capacity, cfg.eff_heads[1], cfg.hd), dt),
        v=jnp.zeros((n_layers, batch, capacity, cfg.eff_heads[1], cfg.hd), dt),
    )
    if cfg.family == "encdec":
        cross = attn.KVCache(
            k=jnp.zeros((n_layers, batch, cfg.enc_seq_len, cfg.eff_heads[1], cfg.hd), dt),
            v=jnp.zeros((n_layers, batch, cfg.enc_seq_len, cfg.eff_heads[1], cfg.hd), dt),
        )
        return Caches(kv=kv, cross_kv=cross)
    return Caches(kv=kv)


def shard_caches(caches: Caches) -> Caches:
    """Decode caches: sequence-shard over `model` (split-K), batch over dp."""

    def kv_con(a):  # [L, B, S, ...]: seq over sp
        axes = [None, "dp", "sp"] + [None] * (a.ndim - 3)
        return shard(a, *axes)

    def ssm_con(a):  # [L, B, ...]: batch over dp only
        return shard(a, None, "dp", *([None] * (a.ndim - 2)))

    rep = lambda t, f: jax.tree_util.tree_map(f, t) if t != () else ()
    return Caches(
        kv=rep(caches.kv, kv_con),
        ssm=rep(caches.ssm, ssm_con),
        shared_kv=rep(caches.shared_kv, kv_con),
        cross_kv=rep(caches.cross_kv, kv_con),
    )


# ---------------------------------------------------------------------------
# decode


def _layer_cache(full, idx):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), full
    )


def _set_layer_cache(full, one, idx):
    return jax.tree_util.tree_map(
        lambda f, o: jax.lax.dynamic_update_slice_in_dim(f, o[None].astype(f.dtype), idx, 0),
        full,
        one,
    )


def decode_step(params, cfg, caches: Caches, tokens, pos):
    """One decode step. tokens: [B, 1] int32; pos: scalar int32 (slot for the
    new token; attends over cache[0..pos]).  Returns (logits [B, V], caches).
    """
    x = _embed(params, cfg, tokens)
    s = tf._res_scale(cfg)

    if cfg.family in ("ssm", "hybrid"):
        every = cfg.hybrid_attn_every
        shared_kv = caches.shared_kv

        def body(carry, xs):
            h, ssm_full, shared_c = carry
            p, idx = xs
            cache = _layer_cache(ssm_full, idx)
            hn = rms_norm(h, p["norm"], cfg.norm_eps)
            out, new_cache = ssm_mod.mamba_decode(hn, p["mamba"], cfg, cache)
            h = h + out
            ssm_full = _set_layer_cache(ssm_full, new_cache, idx)
            if cfg.family == "hybrid" and every:
                sp = tf._shared_block_params(params, idx, every)
                app = idx // every

                def with_attn(operand):
                    hh, sc = operand
                    hn2 = rms_norm(hh, sp["attn_norm"], cfg.norm_eps)
                    one = jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, app, 0, False), sc
                    )
                    a, new_one = attn.gqa_decode(hn2, sp["attn"], cfg, one, pos, 0)
                    hh = hh + a
                    hn3 = rms_norm(hh, sp["mlp_norm"], cfg.norm_eps)
                    hh = hh + swiglu(hn3, sp["mlp"]["wg"], sp["mlp"]["wu"], sp["mlp"]["wd"])
                    sc = jax.tree_util.tree_map(
                        lambda full, o: jax.lax.dynamic_update_slice_in_dim(
                            full, o[None], app, 0
                        ),
                        sc,
                        new_one,
                    )
                    return hh, sc

                h, shared_c = jax.lax.cond(
                    (idx + 1) % every == 0, with_attn, lambda o: o, (h, shared_c)
                )
            return (h, ssm_full, shared_c), None

        (h, new_ssm, shared_kv), _ = jax.lax.scan(
            body, (x, caches.ssm, shared_kv),
            (params["layers"], jnp.arange(cfg.n_layers)),
        )
        new_caches = Caches(ssm=new_ssm, shared_kv=shared_kv)
    elif cfg.family == "encdec":

        def body(carry, xs):
            h, kv_full = carry
            p, cross, idx = xs
            cache = _layer_cache(kv_full, idx)
            hn = rms_norm(h, p["attn_norm"], cfg.norm_eps)
            a, new_cache = attn.gqa_decode(hn, p["attn"], cfg, cache, pos, 0)
            h = h + a
            hn = rms_norm(h, p["cross_norm"], cfg.norm_eps)
            h = h + _cross_decode(hn, p["cross"], cfg, cross)
            hn = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
            h = h + swiglu(hn, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"])
            return (h, _set_layer_cache(kv_full, new_cache, idx)), None

        L = cfg.n_dec_layers
        (h, new_kv), _ = jax.lax.scan(
            body, (x, caches.kv), (params["layers"], caches.cross_kv, jnp.arange(L))
        )
        new_caches = Caches(kv=new_kv, cross_kv=caches.cross_kv)
    else:
        wtuple = cfg.layer_windows

        def one_layer(h, kv_full, p, w, idx):
            # the stacked cache rides the scan CARRY and is updated in place
            # (dynamic-update-slice aliases); emitting per-layer caches as
            # scan outputs would double-buffer the whole KV cache.
            cache = _layer_cache(kv_full, idx)
            hn = rms_norm(h, p["attn_norm"], cfg.norm_eps)
            if cfg.attn_kind == "mla":
                a, new_cache = attn.mla_decode(hn, p["attn"], cfg, cache, pos)
            else:
                a, new_cache = attn.gqa_decode(hn, p["attn"], cfg, cache, pos, w)
            h = h + s * a
            hn = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
            if "moe" in p:
                m, _ = moe_mod.moe_ffn_dispatch(hn, p["moe"], cfg)
            else:
                m = swiglu(hn, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"])
            h = h + s * m
            return h, _set_layer_cache(kv_full, new_cache, idx)

        if len(set(wtuple)) == 1:
            w_static = int(wtuple[0])  # static -> banded cache reads

            def body(carry, xs):
                h, kv_full = carry
                p, idx = xs
                h, kv_full = one_layer(h, kv_full, p, w_static, idx)
                return (h, kv_full), None

            (h, new_kv), _ = jax.lax.scan(
                body, (x, caches.kv), (params["layers"], jnp.arange(cfg.n_layers))
            )
        elif cfg.locals_per_global > 0:
            period = cfg.locals_per_global + 1
            n_groups = cfg.n_layers // period
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape((n_groups, period) + a.shape[1:]), params["layers"]
            )

            def body(carry, xs):
                h, kv_full = carry
                pgroup, gi = xs
                for j in range(period):
                    pj = jax.tree_util.tree_map(lambda a: a[j], pgroup)
                    h, kv_full = one_layer(
                        h, kv_full, pj, int(wtuple[j]), gi * period + j
                    )
                return (h, kv_full), None

            (h, new_kv), _ = jax.lax.scan(
                body, (x, caches.kv), (grouped, jnp.arange(n_groups))
            )
        else:
            windows = jnp.asarray(wtuple, jnp.int32)

            def body(carry, xs):
                h, kv_full = carry
                p, w, idx = xs
                h, kv_full = one_layer(h, kv_full, p, w, idx)
                return (h, kv_full), None

            (h, new_kv), _ = jax.lax.scan(
                body, (x, caches.kv),
                (params["layers"], windows, jnp.arange(cfg.n_layers)),
            )
        new_caches = Caches(kv=new_kv)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, _unembed_matrix(params, cfg))[:, 0]
    logits = _mask_pad_vocab(logits.astype(jnp.float32), cfg)
    return shard(logits, "dp", "tp"), new_caches


def ws_decode_supported(cfg) -> bool:
    """True when :func:`decode_step_ws` covers this architecture: full
    (unwindowed) GQA decoder families — the shapes continuous batching
    serves.  SSM/hybrid/encdec/MLA keep the dense jitted path."""
    return (
        cfg.family not in ("ssm", "hybrid", "encdec")
        and cfg.attn_kind == "gqa"
        and all(w == 0 for w in cfg.layer_windows)
    )


def decode_step_ws(
    params, cfg, caches: Caches, tokens, pos,
    *, schedule: str = "ws", bk: int | None = None, n_programs: int = 8,
):
    """One decode step with attention routed through the device-resident
    work-stealing scheduler (repro.pallas_ws) instead of the dense masked
    contraction baked into :func:`decode_step`.

    Same signature and semantics as :func:`decode_step` (``pos`` may be [B]
    for continuous batching's heterogeneous slots).  Jit-compatible: under
    tracing the per-slot lengths stay on device and the tile queues are
    built by the traced Put (``make_queue_state_jax``); eager calls keep
    the host-side Put with its telemetry.  The layer loop is a plain Python
    loop over the stacked params (statically unrolled when traced — see
    ``repro.serving.engine.jit_decode_step_ws`` for the compiled serving
    entry).  MoE layers route through ``moe_ffn_dispatch`` — with
    ``cfg.moe_dispatch == "ws"`` both the attention *and* the expert FFN of
    a decode step run on the scheduler, eager or compiled.
    """
    assert ws_decode_supported(cfg), cfg.name
    s = tf._res_scale(cfg)
    kv = caches.kv
    with jax.named_scope(spans.DENSE):
        h = _embed(params, cfg, tokens)
    for idx in range(cfg.n_layers):
        with jax.named_scope(spans.DENSE):
            p = jax.tree_util.tree_map(lambda a: a[idx], params["layers"])
            hn = rms_norm(h, p["attn_norm"], cfg.norm_eps)
        with jax.named_scope(spans.KV_LAYOUT):
            cache = _layer_cache(kv, idx)
        a, new_cache = attn.gqa_decode_ws(
            hn, p["attn"], cfg, cache, pos,
            schedule=schedule, bk=bk, n_programs=n_programs,
        )
        with jax.named_scope(spans.DENSE):
            h = h + s * a
            hn = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
            if "moe" in p:
                m, _ = moe_mod.moe_ffn_dispatch(hn, p["moe"], cfg)
            else:
                m = swiglu(hn, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"])
            h = h + s * m
        with jax.named_scope(spans.KV_LAYOUT):
            kv = _set_layer_cache(kv, new_cache, idx)
    with jax.named_scope(spans.DENSE):
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h, _unembed_matrix(params, cfg))[:, 0]
        logits = _mask_pad_vocab(logits.astype(jnp.float32), cfg)
        return shard(logits, "dp", "tp"), Caches(kv=kv)


def _cross_decode(x, p, cfg, cross: attn.KVCache):
    """Single-query cross-attention against precomputed encoder K/V."""
    B = x.shape[0]
    hd = cfg.hd
    H, Hkv = p["wq"].shape[1], p["wk"].shape[1]
    G = H // Hkv
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"]).reshape(B, Hkv, G, hd)
    sc = jnp.einsum("bkgd,bskd->bskg", q, cross.k).astype(jnp.float32) * hd**-0.5
    w = jax.nn.softmax(sc, axis=1)
    o = jnp.einsum("bskg,bske->bkge", w.astype(cross.v.dtype), cross.v)
    return jnp.einsum("bshe,hed->bsd", o.reshape(B, 1, H, hd), p["wo"])


# ---------------------------------------------------------------------------
# prefill


def _pad_seq(k, cap):
    """[B, S, ...] -> [B, cap, ...] (zero-padded cache slots)."""
    S = k.shape[1]
    if cap == S:
        return k
    pad = [(0, 0)] * k.ndim
    pad[1] = (0, cap - S)
    return jnp.pad(k, pad)


def prefill(params, cfg, batch, *, capacity: int | None = None, chunk: int = 1024):
    """Process a full prompt; returns (last-token logits [B, V], Caches)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = _positions(B, S)
    dt = jnp.dtype(cfg.dtype)
    s = tf._res_scale(cfg)

    if cfg.family in ("ssm", "hybrid"):
        h, caches = _prefill_ssm(params, cfg, x, positions, capacity or S, chunk)
    elif cfg.family == "encdec":
        cap = capacity or S
        enc_out = tf.encode(params, cfg, batch["frames"], remat=False, chunk=chunk)

        def body(h, p):
            hn = rms_norm(h, p["attn_norm"], cfg.norm_eps)
            k = jnp.einsum("bsd,dhe->bshe", hn, p["attn"]["wk"])
            v = jnp.einsum("bsd,dhe->bshe", hn, p["attn"]["wv"])
            k = attn.apply_rope(k, positions, cfg.rope_theta)
            h = h + tf._attn_fwd(hn, p, cfg, positions, 0, chunk)
            hn = rms_norm(h, p["cross_norm"], cfg.norm_eps)
            ck = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross"]["wk"])
            cv = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross"]["wv"])
            h = h + tf._cross_attn(hn, p["cross"], cfg, (ck, cv), chunk)
            hn = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
            h = h + swiglu(hn, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"])
            kv = attn.KVCache(_pad_seq(k.astype(dt), cap), _pad_seq(v.astype(dt), cap))
            return h, (kv, attn.KVCache(ck.astype(dt), cv.astype(dt)))

        h, (kv, cross) = jax.lax.scan(body, x, params["layers"])
        caches = Caches(kv=kv, cross_kv=cross)
    else:
        if cfg.family == "vlm":
            patches = batch["patches"].astype(dt)
            x = jnp.concatenate([patches, x], axis=1)
            S = x.shape[1]
            positions = _positions(B, S)
        cap = capacity or S
        windows = jnp.asarray(cfg.layer_windows, jnp.int32)

        def body(h, xs):
            p, w = xs
            hn = rms_norm(h, p["attn_norm"], cfg.norm_eps)
            if cfg.attn_kind == "mla":
                ckv = jnp.einsum("bsd,dr->bsr", hn, p["attn"]["wdkv"])
                kr = attn.apply_rope(
                    jnp.einsum("bsd,de->bse", hn, p["attn"]["wkr"])[:, :, None, :],
                    positions, cfg.rope_theta,
                )[:, :, 0, :]
                a = attn.mla_train(hn, p["attn"], cfg, positions, window=w, chunk=chunk)
                kv = attn.MLACache(_pad_seq(ckv.astype(dt), cap), _pad_seq(kr.astype(dt), cap))
            else:
                k = jnp.einsum("bsd,dhe->bshe", hn, p["attn"]["wk"])
                v = jnp.einsum("bsd,dhe->bshe", hn, p["attn"]["wv"])
                k = attn.apply_rope(k, positions, cfg.rope_theta)
                a = attn.gqa_train(hn, p["attn"], cfg, positions, window=w, chunk=chunk)
                kv = attn.KVCache(_pad_seq(k.astype(dt), cap), _pad_seq(v.astype(dt), cap))
            h = h + s * a
            hn = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
            if "moe" in p:
                m, _ = moe_mod.moe_ffn_dispatch(hn, p["moe"], cfg)
            else:
                m = swiglu(hn, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"])
            h = h + s * m
            return h, kv

        wtuple = cfg.layer_windows
        if len(set(wtuple)) == 1:
            w0 = int(wtuple[0])  # static -> banded flash for windowed archs
            h, kv = jax.lax.scan(lambda hh, p: body(hh, (p, w0)), x, params["layers"])
        elif cfg.locals_per_global > 0:
            period = cfg.locals_per_global + 1
            n_groups = cfg.n_layers // period
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape((n_groups, period) + a.shape[1:]), params["layers"]
            )

            def group_body(h, pgroup):
                kvs = []
                for j in range(period):
                    pj = jax.tree_util.tree_map(lambda a: a[j], pgroup)
                    h, kv_j = body(h, (pj, int(wtuple[j])))
                    kvs.append(kv_j)
                stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *kvs)
                return h, stacked

            h, kv = jax.lax.scan(group_body, x, grouped)
            kv = jax.tree_util.tree_map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), kv
            )
        else:
            h, kv = jax.lax.scan(body, x, (params["layers"], windows))
        caches = Caches(kv=kv)

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", h[:, -1], _unembed_matrix(params, cfg))
    logits = _mask_pad_vocab(logits.astype(jnp.float32), cfg)
    return shard(logits, "dp", "tp"), caches


def _mask_pad_vocab(logits, cfg):
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    vpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(vpos < cfg.vocab_size, logits, -1e30)


def _prefill_ssm(params, cfg, x, positions, cap, chunk):
    """SSM/hybrid prefill.  Hybrid runs as a scan over super-blocks
    (`every` mamba layers + one shared attention block) so the shared-block
    K/V can be collected as scan outputs without an [L, ...] blow-up.
    """
    every = cfg.hybrid_attn_every
    dt = jnp.dtype(cfg.dtype)

    def mamba_layer(h, p):
        hn = rms_norm(h, p["norm"], cfg.norm_eps)
        out, cache = ssm_mod.mamba_train(hn, p["mamba"], cfg, return_cache=True)
        return h + out, cache

    if cfg.family == "ssm" or not every:
        h, caches = jax.lax.scan(mamba_layer, x, params["layers"])
        return h, Caches(ssm=caches)

    n_apps = cfg.n_layers // every
    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_apps, every) + a.shape[1:]), params["layers"]
    )

    def super_block(carry, xs):
        h = carry
        pgroup, app = xs
        h, ssm_caches = jax.lax.scan(mamba_layer, h, pgroup)
        sp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, app % 2, 0, False),
            params["shared_attn"],
        )
        hn = rms_norm(h, sp["attn_norm"], cfg.norm_eps)
        k = jnp.einsum("bsd,dhe->bshe", hn, sp["attn"]["wk"])
        v = jnp.einsum("bsd,dhe->bshe", hn, sp["attn"]["wv"])
        k = attn.apply_rope(k, positions, cfg.rope_theta)
        h = h + attn.gqa_train(hn, sp["attn"], cfg, positions, window=0, chunk=chunk)
        hn = rms_norm(h, sp["mlp_norm"], cfg.norm_eps)
        h = h + swiglu(hn, sp["mlp"]["wg"], sp["mlp"]["wu"], sp["mlp"]["wd"])
        kv = attn.KVCache(_pad_seq(k.astype(dt), cap), _pad_seq(v.astype(dt), cap))
        return h, (ssm_caches, kv)

    h, (ssm_caches, shared_kv) = jax.lax.scan(
        super_block, x, (grouped, jnp.arange(n_apps))
    )
    # [n_apps, every, ...] -> [L, ...]
    ssm_caches = jax.tree_util.tree_map(
        lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), ssm_caches
    )
    return h, Caches(ssm=ssm_caches, shared_kv=shared_kv)

"""One megakernel per engine step: the unified mixed-mode launch.

`decode_step_ws` already routes decode attention and the MoE expert FFN
through the fence-free WS scheduler — but as *separate* `launch_ws_grid`
launches per layer, and prefill bypasses the scheduler entirely.  Serving
pays per-launch overhead ~2L+1 times per step and idle programs in one
launch cannot steal the other launch's work.

This module collapses a whole engine step — one decode token for every live
slot, optionally one folded-in prefill prompt — into a SINGLE persistent-grid
`launch_ws_grid` launch mixing all three task families of
`repro.pallas_ws.tasks`:

* **attention** — decode tiles (one `(b, kv head)` tile whose q block holds
  the G query heads sharing that kv head, sweeping its live kv range once)
  and prefill flash tiles (causal `(h, q-block)` tiles), exactly the records
  `emit_decode_tasks` / `emit_flash_tasks` produce;
* **expert** — shared-pool expert-FFN tiles per MoE layer and segment, with
  the *routing gathered in-kernel* from buffers a glue phase wrote;
* **step-glue** — the inter-stage phases (`GLUE_*` codes below): embed,
  per-layer norm/qkv/rope/cache-splice, attention combine + router Put,
  expert combine + shared experts, final logits.

Inter-stage dependencies are the host-computed `stage_open` windows of
`make_staged_queue_state` (Graham-bound prefix sums — DESIGN.md §5): a
stage's queues become visible to Take/Steal only after every task of the
previous stage has finished, so the launch needs no device-side waiting and
the lowering stays fence-free (`benchmarks/zero_cost.py` audits it).

Cost model per family (the mixed-mode queue build): attention tiles charge
kv blocks (`ceil(kv_end / bk)`), expert tiles charge their row capacity
`bt`, glue phases charge 1 — costs are only compared *within* a stage's
Graham window, so the units never mix.

Parity contract (tests/test_unified_step.py): on `float32` configs the
decode half is **bitwise** identical to the split-launch path
(`decode_step_ws`) — every glue phase replays the exact op sequence of the
eager step, the decode tiles are the same records `ragged_decode_attention`
schedules, and interpret mode executes grid cells sequentially so fresh
launches have mult == 1 and the divisors are exact 1.0.  The prefill half
matches `model.prefill` to float tolerance (the flash tiles reduce kv in
`bk`-block online-softmax order, not `flash_ref`'s chunk order); the spliced
k/v caches are bitwise (projection + rope, no reduction reorder).

Multiplicity stays honest in-kernel: tile accumulators are normalized by
`mult[tid]` gathers *inside* the consuming glue phase (the reason
`launch_ws_grid` hands multi-output bodies the live mult ref).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.interpret import interpret_mode
from repro.pallas_ws.kernel import WSRunResult, _attention_execute, launch_ws_grid
from repro.pallas_ws.queues import QueueState, make_staged_queue_state
from repro.pallas_ws.ragged import (
    _pad_to,
    decode_block,
    decode_q_block,
    decode_q_rows,
    decode_q_unblock,
)
from repro.pallas_ws.tasks import (
    BOTTOM,
    F_COST,
    F_E,
    F_LAYER,
    F_OP,
    F_PHASE,
    F_RL,
    F_RS,
    OP_DECODE_TILE,
    OP_EXPERT_TILE,
    OP_FLASH_TILE,
    OP_STEP_GLUE,
    StepGlueTask,
    emit_decode_tasks,
    emit_flash_tasks,
)

from . import attention as attn
from . import transformer as tf
from .common import apply_rope, rms_norm, swiglu
from .model import (
    Caches,
    _mask_pad_vocab,
    _pad_seq,
    _positions,
    _unembed_matrix,
    ws_decode_supported,
)

# Glue phase codes (tasks.F_PHASE of a step-glue record).  One glue task per
# (phase, layer) handles BOTH segments — the decode batch and the optional
# folded-in prefill prompt — since the phases are serial either way.
GLUE_EMBED = 0    # token embedding -> residual stream buffers
GLUE_PRE = 1      # attn norm, qkv + rope, cache splice, tile input staging
GLUE_POST = 2     # attention combine (mult-normalized), wo, mlp norm,
                  # then dense MLP or the MoE router Put
GLUE_COMB = 3     # expert combine (mult-normalized), shared experts, residual
GLUE_LOGITS = 4   # final norm + unembed -> logits buffers

GLUE_COST = 1  # glue phases are serial; cost only sizes their stage window

SEG_DECODE = 0
SEG_PREFILL = 1


@dataclass(frozen=True)
class _UTask:
    """Pre-encoded task record (the unified expert tiles): the queue builder
    only needs `.cost`, `.owner` and `.encode()`, so a raw field tuple is
    enough — operands are resolved in-kernel from the routing buffers."""

    fields: Tuple[int, ...]
    owner: int

    @property
    def cost(self) -> int:
        return int(self.fields[F_COST])

    def encode(self) -> np.ndarray:
        return np.asarray(self.fields, dtype=np.int32)


def _expert_pool_tiles(n_tokens: int, top_k: int, n_experts: int, bt: int) -> int:
    """Static shared-pool tile count for any routing of n_tokens·top_k pairs
    (`route_to_tasks_pool_jax`): ceil(Tk/bt) + E."""
    return -(-(n_tokens * top_k) // bt) + n_experts


# Per-length-vector stage-assembly memo (ROADMAP PR-8 follow-on): the host
# queue build is pure in its geometry inputs — the decode length vector, the
# pending-admission (prefill) shape, and the static tile/config knobs — so
# repeated steps with the same key (steady-state decode advances every
# length by 1, but batches that shrink/regrow repeat keys; repeated replays
# and drills repeat them constantly) reuse the built QueueState verbatim.
# Reuse is safe because launch_ws_grid never mutates its host inputs: every
# mutable array is copied via jnp.asarray and the aliased outputs are new
# buffers.  Bounded: the cache resets when it would exceed _STAGE_CACHE_MAX.
_STAGE_CACHE: Dict[tuple, tuple] = {}
_STAGE_CACHE_STATS = {"builds": 0, "hits": 0}
_STAGE_CACHE_MAX = 128


def stage_cache_stats() -> Dict[str, int]:
    """Copy of the unified-step stage-assembly cache counters (regression
    hook: one ``builds`` increment per unique key, ``hits`` otherwise)."""
    return dict(_STAGE_CACHE_STATS)


def clear_stage_cache() -> None:
    _STAGE_CACHE.clear()
    _STAGE_CACHE_STATS["builds"] = 0
    _STAGE_CACHE_STATS["hits"] = 0


def unified_step_supported(cfg) -> bool:
    """True when :func:`decode_step_unified` covers this architecture with
    its bitwise-decode parity contract: full-attention GQA decoder families
    in float32, token-only prompts, and (for MoE) the WS expert dispatch so
    the split-launch oracle runs the same dropless Put."""
    return (
        ws_decode_supported(cfg)
        and cfg.family != "vlm"
        and cfg.dtype == "float32"
        and (not cfg.is_moe or cfg.moe_dispatch == "ws")
    )


def require_interpreter() -> None:
    """The unified launch hands every weight of the model and every
    activation buffer to one kernel as whole arrays its glue bodies read in
    full, and its parity contract needs float32: no published width fits a
    chip's fast memory that way.  It runs in the Pallas interpreter only;
    on an accelerator backend this raises instead of interpreting."""
    if not interpret_mode():
        raise NotImplementedError(
            "the unified engine step runs in the Pallas interpreter only "
            f"(backend {jax.default_backend()!r}); serve with the split "
            "WS decode step (ContinuousBatcher(unified_step=False))"
        )


@dataclass
class UnifiedStepReport:
    """Telemetry and prefill results of one unified launch."""

    res: WSRunResult
    state: QueueState
    stage_open: np.ndarray
    rounds: int
    n_tasks: int
    prefill_logits: Optional[jax.Array] = None   # [1, V] when a prompt folded in
    prefill_kv: Optional[attn.KVCache] = None    # [L, 1, cap, Hkv, hd]
    tid_bases: Optional[Dict[str, int]] = None


def _check_drained(n_tasks: int, res: WSRunResult) -> None:
    mult = res.mult
    if isinstance(mult, jax.core.Tracer):
        return  # static Graham windows drain by construction
    if n_tasks and not (np.asarray(mult)[:n_tasks] >= 1).all():
        missing = int((np.asarray(mult)[:n_tasks] == 0).sum())
        raise RuntimeError(
            f"unified step under-provisioned: {missing}/{n_tasks} tasks "
            "never executed (stage windows too small?)"
        )


def decode_step_unified(
    params,
    cfg,
    caches: Caches,
    tokens,
    pos,
    *,
    prefill_tokens=None,
    bk: int = 64,
    bq: int = 32,
    bt: int = 8,
    n_programs: int = 8,
    steal: bool = True,
    steal_policy: str = "cost",
    trace: bool = False,
    check: bool = True,
):
    """One engine step as ONE `launch_ws_grid` launch (DESIGN.md §5).

    Decode semantics match :func:`model.decode_step_ws` bitwise on supported
    configs: ``tokens`` [B, 1] int32, ``pos`` scalar or [B] concrete int32
    (the host Put needs the live lengths), returns ``(logits [B, V] f32,
    Caches, UnifiedStepReport)``.  ``prefill_tokens`` [1, Lp] int32 folds one
    prompt's prefill into the same launch: its flash tiles and (MoE) expert
    tiles share the stage windows with the decode tiles, and the report
    carries the prompt's last-token logits plus its spliced [L, 1, cap, ...]
    k/v cache for the engine to install.

    The decode tiles sweep kv blocks of :func:`decode_block` of the cache's
    capacity, head size and dtype, as the split path's decode does;
    ``bq``/``bk`` size only the folded prompt's flash tiles.

    ``trace=True`` records the per-extraction event rings — a single ring
    stream containing every family's ops, the launch-count witness the
    acceptance criteria ask for.
    """
    assert unified_step_supported(cfg), cfg.name
    require_interpreter()
    B = tokens.shape[0]
    L = cfg.n_layers
    H, Hkv = cfg.eff_heads
    hd = cfg.hd
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    theta = cfg.rope_theta
    s = tf._res_scale(cfg)
    is_moe = cfg.is_moe
    E, top_k = cfg.n_experts, cfg.top_k

    cap = caches.kv.k.shape[2]
    pos_h = np.broadcast_to(
        np.asarray(jax.device_get(pos), dtype=np.int64).reshape(-1), (B,)
    )
    lengths = pos_h + 1

    # -- decode tile geometry: exactly what ragged_decode_attention schedules
    # (one tile per (slot, kv head), its G query heads as q rows)
    G, G_pad = decode_q_rows(H, Hkv)
    bk_d = decode_block(cap, hd, caches.kv.k.dtype)
    S_pad = -(-cap // bk_d) * bk_d

    has_prefill = prefill_tokens is not None
    if has_prefill:
        assert prefill_tokens.shape[0] == 1, prefill_tokens.shape
        Lp = int(prefill_tokens.shape[1])
        assert 0 < Lp <= cap, (Lp, cap)
        bq_p = min(bq, max(1, Lp))
        bk_p = min(bk, max(1, Lp))
        nq_p = -(-Lp // bq_p)
        Lp_pad = nq_p * bq_p
        Lpk_pad = -(-Lp // bk_p) * bk_p
        n_flash_l = H * nq_p
    else:
        Lp = Lp_pad = Lpk_pad = nq_p = n_flash_l = 0
        bq_p = bk_p = 1

    pool_dec = _expert_pool_tiles(B, top_k, E, bt) if is_moe else 0
    n_rows_dec = pool_dec * bt
    pool_pre = _expert_pool_tiles(Lp, top_k, E, bt) if (is_moe and has_prefill) else 0
    n_rows_pre = pool_pre * bt

    # -- tid allocation: family-grouped contiguous blocks with a constant
    # per-layer stride, so glue phases compute their mult-gather bases from
    # the traced layer index.  tids only index the multiplicity buffer —
    # they are independent of queue/stage placement.
    n_glue = 2 + L * (2 + int(is_moe))
    dec_att_base = n_glue
    pre_att_base = dec_att_base + L * B * Hkv
    exp_dec_base = pre_att_base + L * n_flash_l
    exp_pre_base = exp_dec_base + L * pool_dec
    n_tasks = exp_pre_base + L * pool_pre
    tid_bases = {
        "glue": 0,
        "dec_att": dec_att_base,
        "pre_att": pre_att_base,
        "exp_dec": exp_dec_base,
        "exp_pre": exp_pre_base,
        "n_tasks": n_tasks,
    }

    # -- mixed-mode queue build (the host Put)
    glue_tid = [0]

    def glue(phase, layer):
        t = StepGlueTask(phase, layer, BOTTOM, glue_tid[0], GLUE_COST)
        glue_tid[0] += 1
        return t

    def dec_tiles(layer):
        tasks = emit_decode_tasks(lengths, Hkv, bk_d, q_rows=G)
        base = dec_att_base + layer * B * Hkv
        return [dataclasses.replace(t, tid=base + t.tid) for t in tasks]

    def flash_tiles(layer):
        tasks = emit_flash_tasks([Lp], H, bq_p, bk_p, causal=True)
        base = pre_att_base + layer * n_flash_l
        return [dataclasses.replace(t, tid=base + t.tid) for t in tasks]

    def expert_tiles(layer, seg, pool, base_all):
        base = base_all + layer * pool
        return [
            _UTask(
                fields=(OP_EXPERT_TILE, layer, seg, j, BOTTOM, BOTTOM,
                        base + j, bt),
                owner=j,
            )
            for j in range(pool)
        ]

    def build_stages():
        stages = [[glue(GLUE_EMBED, 0)]]
        for lyr in range(L):
            stages.append([glue(GLUE_PRE, lyr)])
            att = dec_tiles(lyr)
            if has_prefill:
                att += flash_tiles(lyr)
            stages.append(att)
            stages.append([glue(GLUE_POST, lyr)])
            if is_moe:
                exp = expert_tiles(lyr, SEG_DECODE, pool_dec, exp_dec_base)
                if has_prefill:
                    exp += expert_tiles(lyr, SEG_PREFILL, pool_pre,
                                        exp_pre_base)
                stages.append(exp)
                stages.append([glue(GLUE_COMB, lyr)])
        stages.append([glue(GLUE_LOGITS, 0)])
        assert glue_tid[0] == n_glue, (glue_tid[0], n_glue)
        return make_staged_queue_state(stages, n_programs, partition="owner")

    # memo key: everything the assembly reads — the length vector, the
    # pending-admission shape, and the static geometry knobs
    cache_key = (
        tuple(int(x) for x in lengths), Lp, B, L, H, Hkv, bk_d, bq_p, bk_p,
        bt, n_programs, bool(is_moe), E, top_k, pool_dec, pool_pre,
    )
    cached = _STAGE_CACHE.get(cache_key)
    if cached is None:
        state, stage_open, rounds = build_stages()
        _STAGE_CACHE_STATS["builds"] += 1
        if len(_STAGE_CACHE) >= _STAGE_CACHE_MAX:
            _STAGE_CACHE.clear()
        _STAGE_CACHE[cache_key] = (state, stage_open, rounds)
    else:
        _STAGE_CACHE_STATS["hits"] += 1
        state, stage_open, rounds = cached
    assert state.n_tasks == n_tasks, (state.n_tasks, n_tasks)

    # -- output buffers (all accumulated/overwritten in-kernel)
    names = []
    outs = []

    def buf(name, arr):
        names.append(name)
        outs.append(arr)

    Vp = _unembed_matrix(params, cfg).shape[-1]
    buf("kc", jnp.asarray(caches.kv.k))
    buf("vc", jnp.asarray(caches.kv.v))
    buf("h", jnp.zeros((B, 1, d), dt))
    buf("qd", jnp.zeros((B, Hkv, G_pad, hd), jnp.float32))
    buf("ktd", jnp.zeros((B, Hkv, S_pad, hd), dt))
    buf("vtd", jnp.zeros((B, Hkv, S_pad, hd), dt))
    buf("attd", jnp.zeros((B, Hkv, G_pad, hd), jnp.float32))
    buf("logits", jnp.zeros((B, Vp), jnp.float32))
    if is_moe:
        buf("xfd", jnp.zeros((B, d), dt))
        buf("tokd", jnp.zeros((n_rows_dec,), jnp.int32))
        buf("gated", jnp.zeros((n_rows_dec,), jnp.float32))
        buf("ed", jnp.zeros((pool_dec,), jnp.int32))
        buf("rld", jnp.zeros((pool_dec,), jnp.int32))
        buf("yrd", jnp.zeros((n_rows_dec, d), jnp.float32))
    if has_prefill:
        buf("hp", jnp.zeros((1, Lp, d), dt))
        buf("qp", jnp.zeros((1, H, Lp_pad, hd), dt))
        buf("ktp", jnp.zeros((1, Hkv, Lpk_pad, hd), dt))
        buf("vtp", jnp.zeros((1, Hkv, Lpk_pad, hd), dt))
        buf("attp", jnp.zeros((1, H, Lp_pad, hd), jnp.float32))
        buf("kp", jnp.zeros((L, 1, cap, Hkv, hd), dt))
        buf("vp", jnp.zeros((L, 1, cap, Hkv, hd), dt))
        buf("logp", jnp.zeros((1, Vp), jnp.float32))
        if is_moe:
            buf("xfp", jnp.zeros((Lp, d), dt))
            buf("tokp", jnp.zeros((n_rows_pre,), jnp.int32))
            buf("gatep", jnp.zeros((n_rows_pre,), jnp.float32))
            buf("ep", jnp.zeros((pool_pre,), jnp.int32))
            buf("rlp", jnp.zeros((pool_pre,), jnp.int32))
            buf("yrp", jnp.zeros((n_rows_pre, d), jnp.float32))
    ix = {n: i for i, n in enumerate(names)}

    pos_arr = jnp.asarray(pos_h, jnp.int32)
    pure = [jnp.asarray(tokens, jnp.int32), pos_arr]
    if has_prefill:
        pure.append(jnp.asarray(prefill_tokens, jnp.int32))
        # prompt positions ride in as a pure input — host-built concrete
        # arrays cannot be captured by the kernel trace
        pure.append(jnp.asarray(_positions(1, Lp), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    n_fixed = len(pure)
    pure += [jnp.asarray(a) for a in leaves]

    # ------------------------------------------------------------------
    # the family-dispatching execute body

    def execute(rec, pure_refs, out_refs, mult_ref):
        def o(name):
            return out_refs[ix[name]]

        tok_ref, posr = pure_refs[0], pure_refs[1]
        ptok_ref = pure_refs[2] if has_prefill else None
        pos_p = pure_refs[3][...] if has_prefill else None
        pv = jax.tree_util.tree_unflatten(
            treedef, [r[...] for r in pure_refs[n_fixed:]]
        )

        op = rec(F_OP)

        @pl.when(op == OP_DECODE_TILE)
        def _decode_tile():
            _attention_execute(
                rec, (o("qd"), o("ktd"), o("vtd")), o("attd"),
                bq=G_pad, bk=bk_d, causal=False, scale=hd**-0.5, g=1,
            )

        if has_prefill:

            @pl.when(op == OP_FLASH_TILE)
            def _flash_tile():
                _attention_execute(
                    rec, (o("qp"), o("ktp"), o("vtp")), o("attp"),
                    bq=bq_p, bk=bk_p, causal=True, scale=hd**-0.5,
                    g=H // Hkv,
                )

        def layer_params(lyr):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, lyr, 0, keepdims=False),
                pv["layers"],
            )

        if is_moe:
            f32 = jnp.float32

            def expert_ffn(xf_ref, tok_r, e_ref, rl_ref, yr_ref, lyr, j):
                """`moe_ws.expert_kernel._expert_execute` verbatim, with the
                (expert, row_len) operands gathered from the routing buffers
                the post-glue wrote and the weights indexed [layer, expert]
                from the stacked params."""
                e = e_ref[j]
                rl = rl_ref[j]
                rs = j * bt
                p_l = layer_params(lyr)
                wg = jax.lax.dynamic_index_in_dim(
                    p_l["moe"]["we_g"], e, 0, keepdims=False
                ).astype(f32)
                wu = jax.lax.dynamic_index_in_dim(
                    p_l["moe"]["we_u"], e, 0, keepdims=False
                ).astype(f32)
                wd = jax.lax.dynamic_index_in_dim(
                    p_l["moe"]["we_d"], e, 0, keepdims=False
                ).astype(f32)
                idxr = tok_r[pl.ds(rs, bt)]
                xt = jnp.take(xf_ref[...], idxr, axis=0).astype(f32)
                hh = jax.nn.silu(
                    jax.lax.dot_general(
                        xt, wg, (((1,), (0,)), ((), ())),
                        preferred_element_type=f32,
                    )
                ) * jax.lax.dot_general(
                    xt, wu, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32,
                )
                yt = jax.lax.dot_general(
                    hh, wd, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32,
                )
                row_live = jax.lax.broadcasted_iota(jnp.int32, (bt, d), 0) < rl
                yt = jnp.where(row_live, yt, 0.0)
                cur = yr_ref[pl.ds(rs, bt), :]
                yr_ref[pl.ds(rs, bt), :] = cur + yt

            @pl.when(op == OP_EXPERT_TILE)
            def _expert_tile():
                lyr = rec(F_E)
                seg = rec(F_RS)
                j = rec(F_RL)

                @pl.when(seg == SEG_DECODE)
                def _dec():
                    expert_ffn(
                        o("xfd"), o("tokd"), o("ed"), o("rld"), o("yrd"),
                        lyr, j,
                    )

                if has_prefill:

                    @pl.when(seg == SEG_PREFILL)
                    def _pre():
                        expert_ffn(
                            o("xfp"), o("tokp"), o("ep"), o("rlp"), o("yrp"),
                            lyr, j,
                        )

        def route_put(x_flat, p_l, tok_r, gate_r, e_ref, rl_ref):
            """The MoE router + traced shared-pool Put (`moe_ffn_ws`'s exact
            routing math), landing in the segment's routing buffers for the
            expert tiles to gather."""
            from repro.moe_ws.dispatch import route_to_tasks_pool_jax
            from repro.moe_ws.layer import _router

            probs, gate_vals, idxs, aux = _router(x_flat, p_l["moe"], cfg, 1024)
            records, n_tiles, toff, routed = route_to_tasks_pool_jax(
                idxs, gate_vals, E, bt=bt
            )
            tok_r[...] = routed.tok_idx
            gate_r[...] = routed.gates
            e_ref[...] = jnp.clip(records[:, F_E], 0, E - 1)
            rl_ref[...] = records[:, F_RL]

        def combine(yr_ref, tok_r, gate_r, mult_base, pool, n_rows, x_flat,
                    p_l, n_tokens):
            """`moe_ws.layer.combine_routed` on the pool layout + shared
            experts — the gate-weighted, multiplicity-normalized scatter."""
            from repro.moe_ws.dispatch import divisor_from_tiles
            from repro.moe_ws.layer import _shared_experts

            mult_e = mult_ref[pl.ds(mult_base, pool)]
            starts = jnp.arange(pool, dtype=jnp.int32) * bt
            div = divisor_from_tiles(starts, bt, mult_e, n_rows)
            yr = yr_ref[...] / div[:, None]
            y = jnp.zeros((n_tokens, d), jnp.float32).at[tok_r[...]].add(
                gate_r[...][:, None] * yr
            )
            if cfg.n_shared_experts:
                y = y + _shared_experts(x_flat, p_l["moe"]).astype(jnp.float32)
            return y

        @pl.when(op == OP_STEP_GLUE)
        def _glue():
            phase = rec(F_PHASE)
            lyr = rec(F_LAYER)

            @pl.when(phase == GLUE_EMBED)
            def _embed_glue():
                o("h")[...] = jnp.take(
                    pv["embed"], tok_ref[...], axis=0
                ).astype(dt)
                if has_prefill:
                    o("hp")[...] = jnp.take(
                        pv["embed"], ptok_ref[...], axis=0
                    ).astype(dt)

            @pl.when(phase == GLUE_PRE)
            def _pre_glue():
                p_l = layer_params(lyr)
                # decode: qkv + rope + cache splice (attention._decode_qkv)
                h = o("h")[...]
                hn = rms_norm(h, p_l["attn_norm"], eps)
                pos_b = posr[...]
                kc_full = o("kc")[...]
                vc_full = o("vc")[...]
                cache = attn.KVCache(
                    jax.lax.dynamic_index_in_dim(kc_full, lyr, 0, keepdims=False),
                    jax.lax.dynamic_index_in_dim(vc_full, lyr, 0, keepdims=False),
                )
                q, new_cache = attn._decode_qkv(hn, p_l["attn"], cfg, cache, pos_b)
                o("kc")[...] = jax.lax.dynamic_update_slice_in_dim(
                    kc_full, new_cache.k[None].astype(kc_full.dtype), lyr, 0
                )
                o("vc")[...] = jax.lax.dynamic_update_slice_in_dim(
                    vc_full, new_cache.v[None].astype(vc_full.dtype), lyr, 0
                )
                o("qd")[...] = decode_q_block(q.reshape(B, H, hd), Hkv)
                o("ktd")[...] = _pad_to(
                    new_cache.k.transpose(0, 2, 1, 3), 2, bk_d
                )
                o("vtd")[...] = _pad_to(
                    new_cache.v.transpose(0, 2, 1, 3), 2, bk_d
                )
                if has_prefill:
                    hp = o("hp")[...]
                    hnp = rms_norm(hp, p_l["attn_norm"], eps)
                    k = jnp.einsum("bsd,dhe->bshe", hnp, p_l["attn"]["wk"])
                    v = jnp.einsum("bsd,dhe->bshe", hnp, p_l["attn"]["wv"])
                    k = apply_rope(k, pos_p, theta)
                    o("kp")[...] = jax.lax.dynamic_update_slice_in_dim(
                        o("kp")[...], _pad_seq(k.astype(dt), cap)[None], lyr, 0
                    )
                    o("vp")[...] = jax.lax.dynamic_update_slice_in_dim(
                        o("vp")[...], _pad_seq(v.astype(dt), cap)[None], lyr, 0
                    )
                    q_p = jnp.einsum("bsd,dhe->bshe", hnp, p_l["attn"]["wq"])
                    q_p = apply_rope(q_p, pos_p, theta)
                    o("qp")[...] = _pad_to(q_p.transpose(0, 2, 1, 3), 2, bq_p)
                    o("ktp")[...] = _pad_to(k.transpose(0, 2, 1, 3), 2, bk_p)
                    o("vtp")[...] = _pad_to(v.transpose(0, 2, 1, 3), 2, bk_p)

            @pl.when(phase == GLUE_POST)
            def _post_glue():
                p_l = layer_params(lyr)
                # decode: multiplicity-normalized attention combine
                # (ragged_decode_attention's divisor), wo, mlp norm
                mult_a = mult_ref[pl.ds(dec_att_base + lyr * (B * Hkv), B * Hkv)]
                div = jnp.maximum(mult_a, 1).astype(jnp.float32).reshape(B, Hkv)
                att = o("attd")[...]
                ob = decode_q_unblock(att / div[:, :, None, None], H).astype(dt)
                a = jnp.einsum(
                    "bshe,hed->bsd", ob.reshape(B, 1, H, hd), p_l["attn"]["wo"]
                )
                h2 = o("h")[...] + s * a
                hn2 = rms_norm(h2, p_l["mlp_norm"], eps)
                if is_moe:
                    x_flat = hn2.reshape(B, d)
                    o("xfd")[...] = x_flat
                    route_put(x_flat, p_l, o("tokd"), o("gated"),
                              o("ed"), o("rld"))
                    o("h")[...] = h2
                else:
                    m = swiglu(hn2, p_l["mlp"]["wg"], p_l["mlp"]["wu"],
                               p_l["mlp"]["wd"])
                    o("h")[...] = h2 + s * m
                o("attd")[...] = jnp.zeros((B, Hkv, G_pad, hd), jnp.float32)
                if has_prefill:
                    mult_f = mult_ref[
                        pl.ds(pre_att_base + lyr * n_flash_l, n_flash_l)
                    ]
                    divf = jnp.repeat(
                        jnp.maximum(mult_f, 1).astype(jnp.float32).reshape(H, nq_p),
                        bq_p, axis=1,
                    )  # [H, Lp_pad]
                    of = (
                        o("attp")[...] / divf[None, :, :, None]
                    ).transpose(0, 2, 1, 3)[:, :Lp].astype(dt)
                    ap = jnp.einsum(
                        "bshe,hed->bsd", of, p_l["attn"]["wo"],
                        preferred_element_type=attn._pet(cfg),
                    ).astype(dt)
                    hp2 = o("hp")[...] + s * ap
                    hnp2 = rms_norm(hp2, p_l["mlp_norm"], eps)
                    if is_moe:
                        xp_flat = hnp2.reshape(Lp, d)
                        o("xfp")[...] = xp_flat
                        route_put(xp_flat, p_l, o("tokp"), o("gatep"),
                                  o("ep"), o("rlp"))
                        o("hp")[...] = hp2
                    else:
                        mp = swiglu(hnp2, p_l["mlp"]["wg"], p_l["mlp"]["wu"],
                                    p_l["mlp"]["wd"])
                        o("hp")[...] = hp2 + s * mp
                    o("attp")[...] = jnp.zeros(
                        (1, H, Lp_pad, hd), jnp.float32
                    )

            if is_moe:

                @pl.when(phase == GLUE_COMB)
                def _comb_glue():
                    p_l = layer_params(lyr)
                    y = combine(
                        o("yrd"), o("tokd"), o("gated"),
                        exp_dec_base + lyr * pool_dec, pool_dec, n_rows_dec,
                        o("xfd")[...], p_l, B,
                    )
                    m = y.astype(dt).reshape(B, 1, d)
                    o("h")[...] = o("h")[...] + s * m
                    o("yrd")[...] = jnp.zeros((n_rows_dec, d), jnp.float32)
                    if has_prefill:
                        yp = combine(
                            o("yrp"), o("tokp"), o("gatep"),
                            exp_pre_base + lyr * pool_pre, pool_pre,
                            n_rows_pre, o("xfp")[...], p_l, Lp,
                        )
                        mpre = yp.astype(dt).reshape(1, Lp, d)
                        o("hp")[...] = o("hp")[...] + s * mpre
                        o("yrp")[...] = jnp.zeros(
                            (n_rows_pre, d), jnp.float32
                        )

            @pl.when(phase == GLUE_LOGITS)
            def _logits_glue():
                w_un = _unembed_matrix(pv, cfg)
                hf = rms_norm(o("h")[...], pv["final_norm"], eps)
                lg = jnp.einsum("bsd,dv->bsv", hf, w_un)[:, 0]
                o("logits")[...] = _mask_pad_vocab(lg.astype(jnp.float32), cfg)
                if has_prefill:
                    hpf = rms_norm(o("hp")[...], pv["final_norm"], eps)
                    lp = jnp.einsum("bd,dv->bv", hpf[:, -1], w_un)
                    o("logp")[...] = _mask_pad_vocab(lp.astype(jnp.float32), cfg)

    res = launch_ws_grid(
        state, execute, pure, tuple(outs),
        steal=steal, steal_policy=steal_policy, rounds=rounds,
        compress_runs=False, stage_open=stage_open,
        trace=trace, name="ws_unified",
    )
    if check:
        _check_drained(n_tasks, res)

    out = dict(zip(names, res.out))
    new_caches = Caches(kv=attn.KVCache(k=out["kc"], v=out["vc"]))
    report = UnifiedStepReport(
        res=res, state=state, stage_open=stage_open, rounds=rounds,
        n_tasks=n_tasks,
        prefill_logits=out.get("logp"),
        prefill_kv=(
            attn.KVCache(k=out["kp"], v=out["vp"]) if has_prefill else None
        ),
        tid_bases=tid_bases,
    )
    return out["logits"], new_caches, report

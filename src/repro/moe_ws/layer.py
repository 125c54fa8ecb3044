"""``moe_ffn_ws`` — dropless MoE FFN on the fence-free WS tile scheduler.

Drop-in for :func:`repro.models.moe.moe_ffn` (same signature, same
``(y, aux_loss)`` return, same router math) with the dense capacity-dropping
dispatch replaced by expert-tile tasks through the ``pallas_ws`` megakernel:

* router top-k → per-expert owner queues (``dispatch.route_to_tasks``) —
  **every** routed (token, expert) pair gets a task row; there is no
  capacity factor and nothing is dropped;
* programs Take their own expert's tiles and Steal from overloaded experts'
  stale head views (plain loads/stores, no CAS/fence) — the router's
  heavy-tailed load lands as queue skew and the thieves flatten it;
* the combine divides each routed row by its tile's execution count
  (``dispatch.row_divisor``) before the gate-weighted scatter-add, so
  duplicated tile execution under the relaxed scheduler is exactly
  normalized out — multiplicity makes the dropless dispatch *cheap*, not
  merely possible.

Queue construction has two Puts behind one kernel launch: eager callers go
through the host-side ``route_to_tasks``/``make_queue_state`` (concrete
numpy, compact padding, full telemetry), traced callers through the
jit-compatible ``route_to_tasks_jax``/``make_queue_state_jax`` (fixed
shapes at the static worst case, live masks) — so ``jit(moe_ffn_ws)`` and
``scan``-over-layers run the *same dropless dispatch*, not a dense
fallback.  The two builders are certified equivalent by
tests/test_dispatch_conformance.py.

The dispatch is **differentiable** (DESIGN.md §4.5): the routed-expert core
carries a ``jax.custom_vjp`` whose forward runs the megakernel and whose
backward is the closed-form gather–FFN–scatter transpose of
:func:`expert_ffn_nodrop_ref` — the no-drop function the scheduler provably
computes, so its VJP is *the* VJP of the dispatch regardless of which
steal/duplication schedule the forward happened to execute.  The backward
restricts the reference transpose to the routed pairs (never O(T·E)):
``grad_dispatch="dense"`` evaluates it with plain gathers/scatter-adds over
the flat ``[T·k]`` pair list, ``grad_dispatch="ws"`` re-schedules the
per-row transpose tiles through a second ``launch_ws_grid`` launch on the
same shared-pool queue layout (``run_moe_grad_schedule``).  Router gates
and the aux loss live *outside* the custom VJP, so their gradients flow
through the ordinary jnp router math unchanged.  Certified by
tests/test_moe_ws_grad.py.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.pallas_ws.queues import (
    make_pool_queue_state_jax,
    make_queue_state,
    make_queue_state_jax,
)
from repro.pallas_ws.ragged import RaggedStats as DispatchStats  # family-neutral telemetry

from .dispatch import (
    divisor_from_tiles,
    expert_queue_candidates,
    expert_rounds_bound,
    route_to_tasks,
    route_to_tasks_jax,
    route_to_tasks_pool_jax,
    row_divisor,
)
from .expert_kernel import dsilu, run_moe_grad_schedule, run_moe_schedule

SCHEDULES = ("ws", "static")
QUEUE_LAYOUTS = ("pool", "padded")
GRAD_DISPATCHES = ("dense", "ws")


def _router(x_flat, p, cfg, group_size: int):
    """The dense path's router (`models.moe.router_topk` — one
    implementation, shared, so routing/aux math cannot drift between the
    dispatches), reshaped to flat [T, ...] views."""
    from repro.models.moe import router_topk

    T, d = x_flat.shape
    g = min(group_size, T)
    G = T // g
    assert G * g == T, (T, g)
    probs, gate_vals, idx, aux = router_topk(x_flat.reshape(G, g, d), p, cfg)
    k = cfg.top_k
    return (
        probs.reshape(T, cfg.n_experts),
        gate_vals.reshape(T, k),
        idx.reshape(T, k),
        aux,
    )


def _shared_experts(x_flat, p):
    hs = jax.nn.silu(jnp.einsum("td,df->tf", x_flat, p["ws_g"]))
    hs = hs * jnp.einsum("td,df->tf", x_flat, p["ws_u"])
    return jnp.einsum("tf,fd->td", hs, p["ws_d"])


class _CoreStatic(NamedTuple):
    """Hashable launch configuration of the routed-expert core — the
    nondiff leading argument of the custom VJP (shapes/knobs only, no
    arrays)."""

    n_experts: int
    schedule: str
    steal_policy: str
    queue_layout: Optional[str]
    grad_dispatch: str
    n_programs: int
    bt: int
    steal_run_cap: int = 1


def _check_drained(state, res) -> None:
    if isinstance(res.mult, jax.core.Tracer):
        # traced launches run the static worst-case rounds bound
        # (expert_rounds_bound), which drains by construction; there is no
        # concrete mult to inspect mid-trace.
        return
    if state.pool_off is not None:
        # pool layout: live slots are exactly the pool prefix [0, Σtail)
        n_live = int(np.asarray(state.tail).sum())
    else:
        n_live = state.n_tasks
    if n_live and not (res.mult[:n_live] >= 1).all():
        missing = int((res.mult[:n_live] == 0).sum())
        raise RuntimeError(
            f"expert scheduler under-provisioned: {missing}/{n_live} "
            "tiles never executed (rounds bound too small?)"
        )


def combine_routed(routed, tasks, res, *, bt: int | None = None):
    """Multiplicity-normalized, gate-weighted combine of an expert-kernel
    run: divide each row's accumulation by its tile's execution count
    (``divisor_from_tiles``), then scatter-add ``gate * row`` back to the
    tokens.  Pad rows carry gate 0, so they vanish.  Returns
    [n_tokens, d] float32.

    ``tasks`` is the host task list; pass ``tasks=None`` with the tile
    height ``bt`` for a trace-built layout, where tile ``t`` statically owns
    rows ``[t·bt, (t+1)·bt)``.  The single combine implementation —
    `moe_ffn_ws` (both Puts), the dispatch benchmark, and the dropless
    property tests all call this.
    """
    if tasks is None:
        assert bt is not None, "traced combine needs the static tile height"
        n_tiles = res.mult.shape[0]
        starts = jnp.arange(n_tiles, dtype=jnp.int32) * bt
        div = divisor_from_tiles(starts, bt, res.mult, routed.n_rows)
    else:
        div = row_divisor(tasks, res.mult, routed.n_rows)
    yr = res.out / jnp.asarray(div)[:, None]
    return jnp.zeros((routed.n_tokens, res.out.shape[-1]), jnp.float32).at[
        jnp.asarray(routed.tok_idx)
    ].add(jnp.asarray(routed.gates)[:, None] * yr)


def expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd):
    """Raw-weight O(T·E) no-drop oracle: every expert's gated FFN applied to
    every token, combined with the routed gates.  ``x``: [T, d]; returns
    [T, d] float32."""
    xf = jnp.asarray(x).astype(jnp.float32)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, jnp.asarray(wg).astype(jnp.float32)))
    h = h * jnp.einsum("td,edf->tef", xf, jnp.asarray(wu).astype(jnp.float32))
    y_all = jnp.einsum("tef,efd->ted", h, jnp.asarray(wd).astype(jnp.float32))
    y_sel = jnp.take_along_axis(y_all, jnp.asarray(idx)[:, :, None], axis=1)
    return (jnp.asarray(gates)[:, :, None] * y_sel).sum(axis=1)


def _dispatch_and_run(static: _CoreStatic, x_flat, idx, gate_vals, wg, wu, wd,
                      trace: bool = False):
    """Put + megakernel launch + multiplicity-normalized combine — the
    routed-expert core shared by the custom VJP's primal/forward and the
    telemetry path.  Returns ``(y_routed [T, d] f32, state, res, routed,
    tasks)``; ``trace=True`` records event rings on the launch."""
    E, schedule = static.n_experts, static.schedule
    n_programs, bt = static.n_programs, static.bt
    T, k = idx.shape
    traced = any(
        isinstance(a, jax.core.Tracer) for a in (x_flat, idx, gate_vals)
    )

    # Put: routing -> expert-tile owner queues.  With stealing every expert
    # gets its own queue (the per-expert token list); the static baseline
    # needs every queue owned by a program, so experts are placed
    # round-robin over programs — classic expert parallelism.
    n_queues = E if schedule == "ws" else n_programs
    steal = schedule == "ws"
    layout = static.queue_layout
    if layout is None:
        # the host Put already lays rows out compactly, so "pool" is the
        # *traced* compact layout; eager callers keep the host arrays (full
        # task-list telemetry) unless they ask for pool explicitly
        layout = "pool" if (steal and traced) else "padded"
    if layout == "pool" and not steal:
        raise ValueError(
            "queue_layout='pool' needs per-expert queues (schedule='ws'); "
            "the static baseline regroups experts onto program queues"
        )
    if traced or layout == "pool":
        # trace-compatible Put (also exercisable eagerly for pool telemetry)
        if layout == "pool":
            records, tail, pool_off, routed = route_to_tasks_pool_jax(
                idx, gate_vals, E, bt=bt
            )
            tasks = None
            state = make_pool_queue_state_jax(
                records, tail, pool_off, routed.loads, n_programs,
                n_tasks=records.shape[0],
            )
        else:
            records, live, routed = route_to_tasks_jax(idx, gate_vals, E, bt=bt)
            cand, cand_live = expert_queue_candidates(records, live, n_queues)
            tasks = None
            state = make_queue_state_jax(
                cand, cand_live, n_programs,
                n_tasks=records.shape[0] * records.shape[1],
            )
        rounds = expert_rounds_bound(
            T * k, bt, n_queues, n_programs, steal,
            steal_run_cap=static.steal_run_cap,
        )
    else:
        idx_h = np.asarray(jax.device_get(idx))
        gates_h = np.asarray(jax.device_get(gate_vals))
        tasks, routed = route_to_tasks(idx_h, gates_h, E, bt=bt)
        state = make_queue_state(tasks, n_programs, n_queues=n_queues, partition="owner")
        rounds = None

    res = run_moe_schedule(
        state,
        x_flat.astype(jnp.float32),
        routed.tok_idx,
        wg, wu, wd,
        bt=bt,
        steal=steal,
        steal_policy=static.steal_policy,
        steal_run_cap=static.steal_run_cap if steal else 1,
        rounds=rounds,
        trace=trace,
    )

    # multiplicity-divisor normalization, then the gate-weighted combine:
    # a dropless scatter-add over every routed pair.
    y = combine_routed(routed, tasks, res, bt=bt)
    return y, state, res, routed, tasks


def _core_primal(static: _CoreStatic, x_flat, idx, gate_vals, wg, wu, wd):
    y, state, res, _, _ = _dispatch_and_run(
        static, x_flat, idx, gate_vals, wg, wu, wd
    )
    _check_drained(state, res)
    return y


def _grad_dense(x_flat, idx, gate_vals, wg, wu, wd, gy):
    """Closed-form VJP of the no-drop routed-expert function, evaluated
    directly over the flat ``[T·k]`` routed pair list with plain
    gathers/scatter-adds — the always-available transpose (no scheduler, no
    pads, no masks).  Returns ``(dx [T,d], dgates [T,k], dwg, dwu, dwd)``
    in f32."""
    T, d = x_flat.shape
    k = idx.shape[1]
    f = wg.shape[-1]
    fe = jnp.asarray(idx, jnp.int32).reshape(-1)
    ft = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    fg = jnp.asarray(gate_vals, jnp.float32).reshape(-1)
    xf = jnp.asarray(x_flat, jnp.float32)
    wg32 = jnp.asarray(wg, jnp.float32)
    wu32 = jnp.asarray(wu, jnp.float32)
    wd32 = jnp.asarray(wd, jnp.float32)

    xr = xf[ft]                                   # [Tk, d] gather
    ct = gy[ft]                                   # [Tk, d] cotangent gather
    wg_r = wg32[fe]
    wu_r = wu32[fe]
    wd_r = wd32[fe]
    u = jnp.einsum("rd,rdf->rf", xr, wg_r)
    v = jnp.einsum("rd,rdf->rf", xr, wu_r)
    sig = jax.nn.sigmoid(u)
    s = u * sig
    h = s * v
    yhat = jnp.einsum("rf,rfd->rd", h, wd_r)      # unweighted pair output
    dgates = jnp.sum(ct * yhat, axis=-1).reshape(T, k)
    dy = fg[:, None] * ct
    dh = jnp.einsum("rd,rfd->rf", dy, wd_r)
    dv = dh * s
    du = dh * v * dsilu(u, sig)
    dxr = (jnp.einsum("rf,rdf->rd", du, wg_r)
           + jnp.einsum("rf,rdf->rd", dv, wu_r))
    dx = jnp.zeros((T, d), jnp.float32).at[ft].add(dxr)
    dwg = jnp.zeros((wg.shape[0], d, f), jnp.float32).at[fe].add(
        xr[:, :, None] * du[:, None, :]
    )
    dwu = jnp.zeros((wu.shape[0], d, f), jnp.float32).at[fe].add(
        xr[:, :, None] * dv[:, None, :]
    )
    dwd = jnp.zeros((wd.shape[0], f, d), jnp.float32).at[fe].add(
        h[:, :, None] * dy[:, None, :]
    )
    return dx, dgates, dwg, dwu, dwd


def _grad_ws(static: _CoreStatic, x_flat, idx, gate_vals, wg, wu, wd, gy):
    """The same transpose with its d-gather/d-FFN tiles re-scheduled through
    a second fence-free ``launch_ws_grid`` launch (``run_moe_grad_schedule``)
    on the shared-pool queue layout — per-row outputs are disjoint across
    tiles, so backward duplication is multiplicity-normalized exactly like
    the forward, and the weight-grad segment reductions run on the
    normalized rows."""
    E, bt, P = static.n_experts, static.bt, static.n_programs
    T, d = x_flat.shape
    k = idx.shape[1]
    f = wg.shape[-1]
    Tk = T * k

    # re-derive the routing residuals (pure, certified function of the saved
    # idx/gates — cheaper than hauling the padded queue arrays through the
    # residual pytree under scan/remat)
    records, tail, pool_off, routed = route_to_tasks_pool_jax(
        idx, gate_vals, E, bt=bt
    )
    state = make_pool_queue_state_jax(
        records, tail, pool_off, routed.loads, P, n_tasks=records.shape[0],
    )
    rounds = expert_rounds_bound(
        Tk, bt, E, P, True, steal_run_cap=static.steal_run_cap
    )
    res = run_moe_grad_schedule(
        state, jnp.asarray(x_flat, jnp.float32), gy,
        routed.tok_idx, routed.gates, wg, wu, wd,
        bt=bt, steal=True, steal_policy=static.steal_policy,
        steal_run_cap=static.steal_run_cap, rounds=rounds,
    )
    # an unexecuted grad tile would contribute exactly-zero gradients (the
    # divisor clamps at 1), so under-provisioning must raise here exactly
    # as it does on the forward path
    _check_drained(state, res)
    return _assemble_row_grads(
        res, routed, idx, x_flat, gy, bt=bt, d=d, f=f, n_experts=E
    )


def _assemble_row_grads(res, routed, idx, x_flat, gy, *, bt, d, f, n_experts):
    """Normalize a grad launch's per-row output block by the tile
    multiplicity divisor, then scatter it into the core's cotangents:
    ``dx`` by routed row -> token, ``dgates`` by row -> (token, choice) via
    ``RoutedSet.row_src``, and the per-expert weight grads as outer-product
    segment sums over the rows' experts.  Split out so the multiplicity
    drills can drive it on adversarially re-executed launches."""
    T, k = idx.shape
    Tk = T * k
    n_tiles = res.mult.shape[0]
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * bt
    div = divisor_from_tiles(starts, bt, res.mult, routed.n_rows)
    G = jnp.asarray(res.out) / jnp.asarray(div)[:, None]
    dxr = G[:, :d]
    du = G[:, d: d + f]
    dv = G[:, d + f: d + 2 * f]
    h = G[:, d + 2 * f: d + 3 * f]
    dgate_rows = G[:, d + 3 * f]

    tok = jnp.asarray(routed.tok_idx)
    grow = jnp.asarray(routed.gates, jnp.float32)
    src = jnp.asarray(routed.row_src)
    live = src < Tk
    fe_all = jnp.asarray(idx, jnp.int32).reshape(-1)
    row_e = jnp.where(live, fe_all[jnp.clip(src, 0, Tk - 1)], 0)

    xr = jnp.asarray(x_flat, jnp.float32)[tok]
    dy = grow[:, None] * gy[tok]                  # 0 on pad rows (gate 0)
    dx = jnp.zeros((T, d), jnp.float32).at[tok].add(dxr)
    dwg = jnp.zeros((n_experts, d, f), jnp.float32).at[row_e].add(
        xr[:, :, None] * du[:, None, :]
    )
    dwu = jnp.zeros((n_experts, d, f), jnp.float32).at[row_e].add(
        xr[:, :, None] * dv[:, None, :]
    )
    dwd = jnp.zeros((n_experts, f, d), jnp.float32).at[row_e].add(
        h[:, :, None] * dy[:, None, :]
    )
    # pad rows scatter their (zero) gate cotangent to the sacrificial slot Tk
    dgates = (
        jnp.zeros((Tk + 1,), jnp.float32)
        .at[jnp.minimum(src, Tk)].add(dgate_rows)[:Tk]
        .reshape(T, k)
    )
    return dx, dgates, dwg, dwu, dwd


def _core_fwd(static, x_flat, idx, gate_vals, wg, wu, wd):
    y = _core_primal(static, x_flat, idx, gate_vals, wg, wu, wd)
    # residual contract (DESIGN.md §4.5): the routing is a pure certified
    # function of (idx, gates), so the residuals are exactly the core's
    # inputs — nothing scheduler-side (queue arrays, mult, schedule order)
    # may enter the backward.
    return y, (x_flat, idx, gate_vals, wg, wu, wd)


def _core_bwd(static, resids, gy):
    x_flat, idx, gate_vals, wg, wu, wd = resids
    gy = jnp.asarray(gy, jnp.float32)
    if static.grad_dispatch == "ws":
        dx, dgates, dwg, dwu, dwd = _grad_ws(
            static, x_flat, idx, gate_vals, wg, wu, wd, gy
        )
    else:
        dx, dgates, dwg, dwu, dwd = _grad_dense(
            x_flat, idx, gate_vals, wg, wu, wd, gy
        )
    d_idx = np.zeros(idx.shape, jax.dtypes.float0)  # int routing: no tangent
    return (
        dx.astype(x_flat.dtype),
        d_idx,
        dgates.astype(gate_vals.dtype),
        dwg.astype(wg.dtype),
        dwu.astype(wu.dtype),
        dwd.astype(wd.dtype),
    )


_moe_ws_core = jax.custom_vjp(_core_primal, nondiff_argnums=(0,))
_moe_ws_core.defvjp(_core_fwd, _core_bwd)


def expert_ffn_ws(
    idx,
    gates,
    x,
    wg,
    wu,
    wd,
    *,
    schedule: str = "ws",
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    queue_layout: str | None = None,
    grad_dispatch: str = "dense",
    n_programs: int = 8,
    bt: int = 8,
):
    """Router-free routed-expert core on the WS scheduler — the
    differentiable twin of :func:`expert_ffn_nodrop_ref` (same argument
    order, same [T, d] f32 return), carrying the custom VJP.  ``idx`` is
    integer routing (no tangent); ``gates``/``x``/weights differentiate
    against the no-drop reference math."""
    assert schedule in SCHEDULES, schedule
    assert queue_layout in (None,) + QUEUE_LAYOUTS, queue_layout
    assert grad_dispatch in GRAD_DISPATCHES, grad_dispatch
    static = _CoreStatic(
        n_experts=wg.shape[0], schedule=schedule, steal_policy=steal_policy,
        queue_layout=queue_layout, grad_dispatch=grad_dispatch,
        n_programs=n_programs, bt=bt,
        steal_run_cap=int(steal_run_cap),
    )
    return _moe_ws_core(
        static, jnp.asarray(x), jnp.asarray(idx, jnp.int32),
        jnp.asarray(gates, jnp.float32), wg, wu, wd,
    )


def moe_ffn_ws(
    x,
    p,
    cfg,
    group_size: int = 1024,
    *,
    schedule: str = "ws",
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    queue_layout: str | None = None,
    grad_dispatch: str = "dense",
    n_programs: int = 8,
    bt: int = 8,
    return_stats: bool = False,
    trace: bool = False,
):
    """x: [B, S, d] -> (y: [B, S, d], aux_loss scalar) — dropless WS dispatch.

    ``schedule="ws"`` steals; ``"static"`` drains owner queues only (same
    kernel and cost accounting — the makespan baseline).  ``steal_policy``
    picks the victim-selection path: ``"cost"`` (default) is the O(1)
    advisory-ranked argmax, ``"scan"`` the PR-1 full sequential scan
    (DESIGN.md §3.6).  ``steal_run_cap > 1`` (cost policy) amortizes Steal:
    one probe claims up to ``min(ceil(rem/2), cap)`` contiguous victim tiles
    (half-run rule — §3.6); the default ``1`` keeps the bit-identical
    per-tile lowering.  ``bt`` is the expert-tile row count; ``n_programs``
    the persistent program count.

    Accepts tracers: under ``jit``/``scan``/``vmap`` the queues are built by
    the traced Put and the kernel runs the static ``expert_rounds_bound`` —
    still dropless, no dense fallback anywhere.  ``queue_layout`` selects
    the traced Put's arrays: ``"pool"`` (the ws default) is the compact
    shared-pool layout (``ceil(Tk/bt) + E`` tiles total,
    ``route_to_tasks_pool_jax``), ``"padded"`` the PR-3 per-expert
    worst-case layout; the static schedule regroups experts onto program
    queues and always uses ``"padded"``.  ``return_stats`` needs concrete
    telemetry and is eager-only; ``trace=True`` (with ``return_stats``)
    additionally records per-extraction event rings and attaches the
    decoded :class:`~repro.wstrace.trace.WSTrace` to the stats.

    **Differentiable** (DESIGN.md §4.5): the routed-expert core carries a
    ``jax.custom_vjp`` whose backward is the closed-form transpose of the
    no-drop reference restricted to the routed pairs — ``grad_dispatch``
    selects its evaluation: ``"dense"`` (default) plain gathers/scatters,
    ``"ws"`` a second megakernel launch over the same tile layout.  Router
    and aux-loss gradients flow outside the VJP unchanged, so
    ``jax.grad``/``value_and_grad`` of a loss through this layer — eager,
    jitted, or scanned-over-layers — trains the dropless dispatch.
    """
    assert schedule in SCHEDULES, schedule
    assert queue_layout in (None,) + QUEUE_LAYOUTS, queue_layout
    assert grad_dispatch in GRAD_DISPATCHES, grad_dispatch
    traced = isinstance(x, jax.core.Tracer)
    if traced and return_stats:
        raise ValueError("return_stats needs concrete telemetry; call eagerly")
    if trace and not return_stats:
        raise ValueError("trace=True attaches the WSTrace to the stats; "
                         "pass return_stats=True as well")
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    probs, gate_vals, idx, aux = _router(x_flat, p, cfg, group_size)

    static = _CoreStatic(
        n_experts=cfg.n_experts, schedule=schedule, steal_policy=steal_policy,
        queue_layout=queue_layout, grad_dispatch=grad_dispatch,
        n_programs=n_programs, bt=bt,
        steal_run_cap=int(steal_run_cap),
    )
    if return_stats:
        # eager telemetry path: same impl, no VJP wrapper in the way
        y, state, res, _, _ = _dispatch_and_run(
            static, x_flat, idx, gate_vals, p["we_g"], p["we_u"], p["we_d"],
            trace=trace,
        )
        _check_drained(state, res)
    else:
        y = _moe_ws_core(
            static, x_flat, idx, gate_vals, p["we_g"], p["we_u"], p["we_d"]
        )

    if cfg.n_shared_experts:
        y = y + _shared_experts(x_flat, p).astype(jnp.float32)
    y = y.astype(x.dtype).reshape(B, S, d)
    if return_stats:
        return y, aux, DispatchStats.from_run(schedule, state, res, steal_policy)
    return y, aux


def moe_ffn_nodrop_ref(x, p, cfg, group_size: int = 1024):
    """O(T·E) dense **no-drop** oracle: every expert applied to every token,
    combined with the routed gates — the exact answer a dropless dispatch
    must reproduce (the capacity-dropping path only approximates it)."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    _, gate_vals, idx, aux = _router(x_flat, p, cfg, group_size)
    y = expert_ffn_nodrop_ref(
        idx, gate_vals, x_flat, p["we_g"], p["we_u"], p["we_d"]
    )
    if cfg.n_shared_experts:
        y = y + _shared_experts(x_flat, p).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), aux

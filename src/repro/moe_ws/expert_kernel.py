"""Expert-tile execution bodies for the persistent WS megakernel.

Forward: one task = ``row_len`` routed rows of one expert's gated FFN:

    gather   x[tok_idx[rs : rs + bt]]                  # [bt, d]
    FFN      silu(x @ wg[e]) * (x @ wu[e]) @ wd[e]     # [bt, f] -> [bt, d]
    scatter  out[rs : rs + bt] += y                    # contiguous accumulate

The scatter is *contiguous* because the routed rows are grouped by expert
(:mod:`repro.moe_ws.dispatch`): the task's output slice is disjoint from
every other task's, so duplicated execution under the relaxed scheduler adds
whole extra copies of the same rows — ``mult[tid]`` normalizes them out,
exactly as for attention q-blocks.  Dead pad rows of a ragged tail tile are
zeroed before the accumulate.

Backward (DESIGN.md §4.5): the *same* tile layout re-scheduled over the
transpose math.  A grad tile gathers its rows' activations and output
cotangents, replays the expert FFN, and emits the per-row pieces of the
no-drop reference VJP — ``d_x`` rows, the hidden-layer cotangents
``du``/``dv``, the recomputed hiddens ``h``, and the per-row gate cotangent
— packed side by side in one ``[bt, d + 3f + 1]`` block.  Everything a grad
tile writes is **per routed row**, hence disjoint across tiles, hence
idempotent-accumulable under duplication exactly like the forward; the
per-expert weight-grad reductions (outer-product segment sums over
``row_src``/experts) happen outside the kernel on the multiplicity-
normalized rows.

The Take/Steal protocol, the lockstep clocks, and the queue arrays are the
shared machinery of :mod:`repro.pallas_ws.kernel` — this module only
supplies the ``execute`` bodies and the launch wrappers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.pallas_ws.kernel import WSRunResult, launch_ws_grid
from repro.pallas_ws.queues import QueueState
from repro.pallas_ws.tasks import F_E, F_RL, F_RS


def _gather_rows(rows_ref, idx_ref, rs, n, buf):
    """Token gather of an expert tile: copy ``rows[idx[rs + i]]`` for
    ``i < n`` into ``buf[i]`` (HBM → VMEM, one row per copy) and return the
    ``[bt, d]`` float32 block, rows ``>= n`` zero.  The routed row → token
    map ``idx`` sits in SMEM; the rows stay in HBM as ``[T, 1, d]`` so one
    row is a leading-dim index (a single row of a ``[T, d]`` array is not
    a whole tile on the chip)."""
    buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def one(i, carry):
        pltpu.sync_copy(rows_ref.at[idx_ref[rs + i]], buf.at[i])
        return carry

    jax.lax.fori_loop(0, n, one, 0)
    bt, _, d = buf.shape
    return buf[...].reshape(bt, d)


def _expert_weights(wg_ref, wu_ref, wd_ref, e, wg_buf, wu_buf, wd_buf):
    """Copy expert ``e``'s whole weight blocks into VMEM, as float32."""
    pltpu.sync_copy((wg_ref.at[e], wu_ref.at[e], wd_ref.at[e]),
                    (wg_buf, wu_buf, wd_buf))
    return (wg_buf[...].astype(jnp.float32), wu_buf[...].astype(jnp.float32),
            wd_buf[...].astype(jnp.float32))


def _expert_scratch(x_ref, wg_ref, wu_ref, wd_ref, bt: int):
    """VMEM scratch of one expert tile: the gathered rows and one expert's
    weight blocks."""
    d = x_ref.shape[-1]
    f = wg_ref.shape[-1]
    return (
        pltpu.VMEM((bt, 1, d), x_ref.dtype),
        pltpu.VMEM((d, f), wg_ref.dtype),
        pltpu.VMEM((d, f), wu_ref.dtype),
        pltpu.VMEM((f, d), wd_ref.dtype),
    )


def _accumulate_rows(out_ref, rs, bt: int, block, buf):
    """Idempotent-accumulate ``block`` into the tile's disjoint routed-row
    slice ``out[rs : rs + bt]`` (copy in, add, copy back)."""
    dst = out_ref.at[pl.ds(rs, bt)]
    pltpu.sync_copy(dst, buf)
    buf[...] = buf[...] + block
    pltpu.sync_copy(buf, dst)


def _expert_execute(rec, pure, out_ref, *, bt: int):
    """Gather–FFN–scatter-accumulate for one expert tile.  ``rec(field)``
    reads one field of the claimed task record (layout-agnostic — the shell
    resolves dense vs shared-pool slot addressing).  Token activations,
    expert weights and the routed output stay in HBM; the tile copies its
    rows and its expert's weights into VMEM scratch."""
    tok_idx_ref, x_ref, wg_ref, wu_ref, wd_ref = pure
    e = rec(F_E)
    rs = pl.multiple_of(rec(F_RS), bt)
    rl = rec(F_RL)
    d = x_ref.shape[-1]

    def body(x_buf, wg_buf, wu_buf, wd_buf, y_buf):
        xt = _gather_rows(x_ref, tok_idx_ref, rs, rl, x_buf)  # [bt, d]
        wg, wu, wd = _expert_weights(wg_ref, wu_ref, wd_ref, e,
                                     wg_buf, wu_buf, wd_buf)
        h = jax.nn.silu(
            jax.lax.dot_general(xt, wg, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        ) * jax.lax.dot_general(xt, wu, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        yt = jax.lax.dot_general(h, wd, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bt, d]
        row_live = jax.lax.broadcasted_iota(jnp.int32, (bt, d), 0) < rl
        _accumulate_rows(out_ref, rs, bt, jnp.where(row_live, yt, 0.0), y_buf)

    pl.run_scoped(
        body,
        *_expert_scratch(x_ref, wg_ref, wu_ref, wd_ref, bt),
        pltpu.VMEM((bt, d), out_ref.dtype),
    )


def dsilu(u, sig):
    """d/du silu(u) given sig = sigmoid(u) — the one implementation both
    backward evaluations (the dense transpose and this tile body) share, so
    their bit-parity cannot drift."""
    return sig * (1.0 + u * (1.0 - sig))


def _expert_grad_execute(rec, pure, out_ref, *, bt: int):
    """Transpose tile: per-row VJP pieces of one expert tile's gather–FFN.

    Emits ``[dx_row | du | dv | h | dgate]`` (``grad_out_width`` columns) for the
    tile's ``bt`` routed rows — every output is per-row, so the accumulate
    slice is disjoint from every other tile's and duplicated execution is
    normalized by the same ``mult[tid]`` divisor as the forward."""
    tok_idx_ref, gate_ref, x_ref, gy_ref, wg_ref, wu_ref, wd_ref = pure
    e = rec(F_E)
    rs = pl.multiple_of(rec(F_RS), bt)
    rl = rec(F_RL)
    d = x_ref.shape[-1]
    f = wg_ref.shape[-1]
    width = grad_out_width(d, f)

    def body(x_buf, wg_buf, wu_buf, wd_buf, c_buf, dy_buf, o_buf):
        xt = _gather_rows(x_ref, tok_idx_ref, rs, rl, x_buf)  # [bt, d]
        ct = _gather_rows(gy_ref, tok_idx_ref, rs, rl, c_buf)  # [bt, d]

        def gate_row(i, carry):
            # the per-row gate rides in SMEM: scale each cotangent row
            dy_buf[i] = gate_ref[rs + i] * c_buf[i]
            return carry

        jax.lax.fori_loop(0, bt, gate_row, 0)
        dy = dy_buf[...].reshape(bt, d)                       # [bt, d]
        wg, wu, wd = _expert_weights(wg_ref, wu_ref, wd_ref, e,
                                     wg_buf, wu_buf, wd_buf)

        # replay the forward tile (remat: residuals are not hauled through HBM)
        u = jax.lax.dot_general(xt, wg, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        v = jax.lax.dot_general(xt, wu, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(u)
        s = u * sig                                           # silu(u)
        h = s * v
        yhat = jax.lax.dot_general(h, wd, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)  # [bt, d]

        # closed-form transpose of gate · (silu(x·wg) ⊙ (x·wu)) · wd
        dgate = jnp.sum(ct * yhat, axis=-1, keepdims=True)    # [bt, 1]
        dh = jax.lax.dot_general(dy, wd, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)    # [bt, f]
        dv = dh * s
        du = dh * v * dsilu(u, sig)
        dxr = jax.lax.dot_general(du, wg, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dxr = dxr + jax.lax.dot_general(dv, wu, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)

        pad = jnp.zeros((bt, width - (d + 3 * f + 1)), jnp.float32)
        block = jnp.concatenate([dxr, du, dv, h, dgate, pad], axis=1)
        row_live = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) < rl
        _accumulate_rows(out_ref, rs, bt, jnp.where(row_live, block, 0.0),
                         o_buf)

    pl.run_scoped(
        body,
        *_expert_scratch(x_ref, wg_ref, wu_ref, wd_ref, bt),
        pltpu.VMEM((bt, 1, d), gy_ref.dtype),
        pltpu.VMEM((bt, 1, d), jnp.float32),
        pltpu.VMEM((bt, width), out_ref.dtype),
    )


def _as_rows(a):
    """``[T, d]`` token rows as float32 ``[T, 1, d]`` — the layout the tile
    gather copies single rows out of (the body computes in float32)."""
    return jnp.asarray(a, jnp.float32)[:, None, :]


def grad_out_width(d: int, f: int) -> int:
    """Columns of the grad launch's per-row output block:
    ``[dx (d) | du (f) | dv (f) | h (f) | dgate (1) | 0 ...]``, zero-padded
    to whole 128-lane tiles so a tile's row block is a whole-tile copy."""
    return -(-(d + 3 * f + 1) // 128) * 128


def run_moe_grad_schedule(
    state: QueueState,
    x,
    gy,
    tok_idx,
    gate_rows,
    wg,
    wu,
    wd,
    *,
    bt: int,
    steal: bool = True,
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    rounds: Optional[int] = None,
    out: Optional[jax.Array] = None,
    mult: Optional[jax.Array] = None,
    compress_runs: Optional[bool] = None,
    trace: bool = False,
    trace_capacity: Optional[int] = None,
) -> WSRunResult:
    """Launch the transpose (backward) megakernel over a prepared
    :class:`QueueState` — the second ``launch_ws_grid`` of the custom VJP's
    ``grad_dispatch="ws"`` path.

    ``gy``: [T, d] cotangent of the combined routed output; ``gate_rows``:
    [n_padded] per-row combine gates (``RoutedSet.gates``); the rest as
    :func:`run_moe_schedule`.  ``res.out`` is the per-row VJP block
    ``[n_padded, grad_out_width(d, f)]`` (mult-weighted accumulation —
    divide by the tile divisor before use), carried over on relaunch for
    the multiplicity drills.
    """
    n_padded = tok_idx.shape[0]
    d = x.shape[-1]
    f = wg.shape[-1]
    out = (
        jnp.zeros((n_padded, grad_out_width(d, f)), jnp.float32)
        if out is None else out
    )
    execute = functools.partial(_expert_grad_execute, bt=bt)
    return launch_ws_grid(
        state, execute,
        (_as_rows(x), _as_rows(gy), wg, wu, wd), out,
        smem_pure=(tok_idx, jnp.asarray(gate_rows, jnp.float32)),
        steal=steal, steal_policy=steal_policy, steal_run_cap=steal_run_cap,
        rounds=rounds, mult=mult,
        compress_runs=compress_runs, trace=trace,
        trace_capacity=trace_capacity, name="ws_expert_grad",
    )


def run_moe_schedule(
    state: QueueState,
    x,
    tok_idx,
    wg,
    wu,
    wd,
    *,
    bt: int,
    steal: bool = True,
    steal_policy: str = "cost",
    steal_run_cap: int = 1,
    rounds: Optional[int] = None,
    out: Optional[jax.Array] = None,
    mult: Optional[jax.Array] = None,
    compress_runs: Optional[bool] = None,
    trace: bool = False,
    trace_capacity: Optional[int] = None,
    fault_plan=None,
) -> WSRunResult:
    """Launch the expert megakernel over a prepared :class:`QueueState`.

    ``x``: [T, d] token activations; ``tok_idx``: [n_padded] routed row →
    token map (``RoutedSet.tok_idx``); ``wg``/``wu``: [E, d, f]; ``wd``:
    [E, f, d].  ``out`` is the routed-row output [n_padded, d] (f32,
    mult-weighted accumulation), carried over on relaunch for the
    multiplicity drills.
    """
    n_padded = tok_idx.shape[0]
    d = x.shape[-1]
    out = jnp.zeros((n_padded, d), jnp.float32) if out is None else out
    execute = functools.partial(_expert_execute, bt=bt)
    return launch_ws_grid(
        state, execute, (_as_rows(x), wg, wu, wd), out, smem_pure=(tok_idx,),
        steal=steal, steal_policy=steal_policy, steal_run_cap=steal_run_cap,
        rounds=rounds, mult=mult,
        compress_runs=compress_runs, trace=trace,
        trace_capacity=trace_capacity, fault_plan=fault_plan, name="ws_expert",
    )

"""``moe_ffn_mesh_ws`` — cross-device expert-parallel WS dispatch.

Two-level hierarchy (arXiv:2211.00838's remote-steal shape on the paper's
fence-free substrate):

* **level 1 — intra-device**: each device Puts its local experts' pairs
  into a shared-pool queue layout and drains them through the existing
  ``launch_ws_grid`` megakernel (plain loads/stores, multiplicity absorbs
  races) for a *balanced-share* round budget ``ceil(Tk/(D·P)) + bt``;
* **level 2 — cross-device**: devices exchange one coalesced advisory
  scalar each (``advisory.py``), every device replicates the deterministic
  steal plan (``steal.py``), and phase 2 runs two more megakernel launches
  per device — continue the own pool to its donation-truncated tails, and
  execute the stolen half-run of the chosen victim's gathered pool.

Stolen contributions ride home on one ``psum`` addressed by victim id, the
multiplicity totals merge (own + stolen execution counts), and the combine
normalizes each row by its tile's total count before the gate-weighted
reduction — duplicated cross-device extraction is exact for exactly the
intra-chip reason.  The combine scatters normalized rows into per-(token,
choice) pair slots and reduces with the oracle's own expression tree, so a
clean (duplicate-free) schedule is **bit-identical** to
``expert_ffn_nodrop_ref`` — the conformance suite asserts equality, not
closeness.

``emulate_mesh_dispatch`` runs the identical protocol on one device with
collectives replaced by stacking — the adversarial conformance drills
(stale advisories, overlapping forced plans) drive it directly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.moe_ws.dispatch import divisor_from_tiles
from repro.moe_ws.expert_kernel import run_moe_schedule
from repro.pallas_ws.queues import QueueState

from .advisory import (
    apply_donation,
    donated_cost,
    reduce_advisory,
    ring_allgather,
)
from .partition import (
    _cdiv,
    expert_shard,
    local_pool_state,
    route_local_pool_jax,
)
from .steal import StealPlan, deliver_home, plan_steals, steal_queue_state

MESH_AXIS = "model"

#: telemetry row layout of one device's dispatch step ([D, len] output)
TELE_FIELDS = (
    "phase1_clock",   # local balanced-drain makespan
    "phase2_clock",   # own-continue makespan
    "steal_clock",    # stolen-segment makespan
    "advisory",       # exchanged load summary (post phase 1)
    "victim",         # chosen victim id (0 when no steal)
    "stole",          # 1 iff this device pulled a remote segment
    "take_tiles",     # tiles stolen by this device
    "mult_sum",       # Σ own-pool multiplicity (own + delivered stolen)
)


def phase_rounds(n_routed: int, bt: int, n_programs: int,
                 n_devices: int) -> tuple[int, int]:
    """Static round budgets.  Phase 1 is a deliberate *truncation* budget:
    rounds are cost-gated (a program that claims a tile of cost c stays
    busy for c rounds, and a claim in the final round overruns by up to
    ``bt`` rows), so ``r1`` rounds let each device retire about
    ``(r1 + bt) * P`` rows — subtracting the overrun tail lands the
    effective phase-1 drain at the balanced 1/D row share.  An overloaded
    device is cut off with its surplus still queued, everyone else drains
    dry, and the advisory exchange routes the idle devices to the surplus.
    Phase 2 keeps the full single-device safety bound
    (``expert_rounds_bound``'s Graham form), which drains any post-steal
    residue regardless of how phase 1 was cut."""
    r1 = max(1, _cdiv(n_routed, n_devices * n_programs) - bt + 1)
    r2 = _cdiv(n_routed, n_programs) + bt
    return r1, r2


def _pair_combine_part(routed, out_total, mult_total, *, bt: int):
    """Normalize a device's accumulated rows by total multiplicity and
    scatter them to (token, choice) pair slots ``[Tk+1, d]`` (slot Tk is
    sacrificial: pads and foreign rows land there, then get zeroed).  Each
    live pair slot is filled by exactly one device, so the cross-device sum
    of these parts is exact and the final gate-weighted reduction can reuse
    the oracle's expression tree."""
    pool_tiles = mult_total.shape[0]
    Tk = routed.n_routed
    starts = jnp.arange(pool_tiles, dtype=jnp.int32) * bt
    div = divisor_from_tiles(starts, bt, mult_total, routed.n_rows)
    yr = out_total / div[:, None]
    src = jnp.minimum(jnp.asarray(routed.row_src), Tk)
    part = jnp.zeros((Tk + 1, out_total.shape[-1]), jnp.float32).at[src].set(yr)
    return part.at[Tk].set(0.0)


def _combine_pairs(y_pairs, gates):
    """The oracle's combine: ``(gates * pairs).sum(choice)``."""
    T, k = gates.shape
    d = y_pairs.shape[-1]
    return (
        jnp.asarray(gates, jnp.float32)[:, :, None]
        * y_pairs[:T * k].reshape(T, k, d)
    ).sum(axis=1)


def mesh_dispatch_body(
    x_flat, idx, gates, wg, wu, wd, *,
    n_experts: int, n_devices: int, bt: int, n_programs: int,
    alpha: int = 1, steal: bool = True, axis: str = MESH_AXIS,
):
    """shard_map body of one mesh dispatch step (see module docstring).

    Replicated inputs: ``x_flat [T,d]``, ``idx [T,k]``, ``gates [T,k]``.
    Sharded inputs (``P(axis)`` on the expert dim): ``wg/wu [El,d,f]``,
    ``wd [El,f,d]``.  Returns the replicated combined rows ``[T,d]`` f32
    and this device's telemetry row ``[1, len(TELE_FIELDS)]``.

    ``steal=False`` is the per-device-static baseline: phase 1 runs to the
    full single-device round bound and no advisory/steal traffic happens —
    the benchmark's comparison point.
    """
    El = expert_shard(n_experts, n_devices)
    me = jax.lax.axis_index(axis)
    lo = me * El
    T, k = idx.shape
    Tk = T * k
    xf = jnp.asarray(x_flat, jnp.float32)
    r1, r2 = phase_rounds(Tk, bt, n_programs, n_devices)

    put = route_local_pool_jax(idx, gates, n_experts, lo, El, bt)
    pool_tiles = put.records.shape[0]
    state = local_pool_state(put, n_programs)

    if not steal:
        res = run_moe_schedule(
            state, xf, put.routed.tok_idx, wg, wu, wd, bt=bt, steal=True,
            steal_policy="cost", rounds=r2,
        )
        part = _pair_combine_part(put.routed, res.out, res.mult, bt=bt)
        y = _combine_pairs(jax.lax.psum(part, axis), gates)
        tele = jnp.stack([
            res.clock.max(), jnp.int32(0), jnp.int32(0),
            reduce_advisory(res.remaining), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), res.mult.sum(),
        ])
        return y, tele[None]

    # ---- phase 1: balanced local drain -----------------------------------
    res1 = run_moe_schedule(
        state, xf, put.routed.tok_idx, wg, wu, wd, bt=bt, steal=True,
        steal_policy="cost", rounds=r1,
    )

    # ---- advisory exchange + victim-context gather -----------------------
    adv_self = reduce_advisory(res1.remaining)
    adv = ring_allgather(adv_self, axis, n_devices).reshape(n_devices)
    g_rec = ring_allgather(put.records, axis, n_devices)
    g_head = ring_allgather(res1.head, axis, n_devices)
    g_tail = ring_allgather(jnp.asarray(put.tail, jnp.int32), axis, n_devices)
    g_toff = ring_allgather(put.toff[: El + 1], axis, n_devices)
    g_tok = ring_allgather(put.routed.tok_idx, axis, n_devices)
    g_wg = ring_allgather(jnp.asarray(wg, jnp.float32), axis, n_devices)
    g_wu = ring_allgather(jnp.asarray(wu, jnp.float32), axis, n_devices)
    g_wd = ring_allgather(jnp.asarray(wd, jnp.float32), axis, n_devices)

    # ---- replicated steal plan + coalesced donation advisory -------------
    plan = plan_steals(adv, g_head, g_tail, me,
                       n_devices=n_devices, bt=bt, alpha=alpha)
    rem2 = apply_donation(res1.remaining, donated_cost(put, plan.new_tail))

    # ---- phase 2a: continue own pool to the truncated tails --------------
    state2 = QueueState(
        tasks=put.records, head=res1.head, tail=plan.new_tail,
        local_head=res1.local_head, taken=res1.taken, task_list=None,
        n_tasks_hint=pool_tiles, remaining=rem2,
        pool_off=put.toff[: El + 1],
    )
    res2 = run_moe_schedule(
        state2, xf, put.routed.tok_idx, wg, wu, wd, bt=bt, steal=True,
        steal_policy="cost", rounds=r2, out=res1.out, mult=res1.mult,
    )

    # ---- phase 2b: execute the stolen remote segment ---------------------
    state_s = steal_queue_state(
        g_rec, g_toff, plan, n_programs=n_programs, pool_tiles=pool_tiles,
        bt=bt,
    )
    res_s = run_moe_schedule(
        state_s, xf, g_tok[plan.victim], g_wg[plan.victim],
        g_wu[plan.victim], g_wd[plan.victim], bt=bt, steal=True,
        steal_policy="cost", rounds=r2,
    )

    # ---- deliver stolen contributions home, merge multiplicity -----------
    out_in, mult_in = deliver_home(res_s.out, res_s.mult, plan, axis,
                                   n_devices=n_devices)
    out_total = res2.out + out_in
    mult_total = res2.mult + mult_in

    # ---- multiplicity-normalized pair combine ----------------------------
    part = _pair_combine_part(put.routed, out_total, mult_total, bt=bt)
    y = _combine_pairs(jax.lax.psum(part, axis), gates)
    tele = jnp.stack([
        res1.clock.max(), res2.clock.max(), res_s.clock.max(), adv_self,
        plan.victim, plan.stole.astype(jnp.int32), plan.take_tiles,
        mult_total.sum(),
    ])
    return y, tele[None]


def mesh_wstrace(tele, *, collective_bytes=None):
    """Lift a ``[D, len(TELE_FIELDS)]`` telemetry block into a
    :class:`~repro.wstrace.trace.WSTrace` carrying per-device *phase*
    counters (``mesh_phases``) instead of per-extraction events — the
    cross-device granularity the two-phase protocol exposes.  The Perfetto
    exporter renders one track per device with phase slices, remote-steal
    flow arrows (victim → thief), and advisory / collective-bytes counter
    tracks.  ``collective_bytes`` (per-device, e.g.
    :func:`~repro.mesh_ws.advisory.exchange_payload_bytes`) is attached to
    every device's counters when given."""
    import numpy as np

    from repro.wstrace.ring import EVENT_WIDTH
    from repro.wstrace.trace import WSTrace

    tele = np.asarray(tele)
    D = tele.shape[0]
    phases = []
    for dev in range(D):
        row = {name: int(tele[dev, i]) for i, name in enumerate(TELE_FIELDS)}
        if collective_bytes is not None:
            row["collective_bytes"] = int(collective_bytes)
        phases.append(row)
    # per-device wall: phase 1 then the longer of own-continue / steal
    span = tele[:, 0] + np.maximum(tele[:, 1], tele[:, 2])
    return WSTrace(
        events=np.zeros((0, EVENT_WIDTH), np.int32),
        n_programs=D,
        n_queues=D,
        makespan=int(span.max(initial=0)),
        dropped=np.zeros(D, np.int64),
        queue_loads=None,
        mesh_phases=phases,
    )


def expert_ffn_mesh_ws(
    idx, gates, x, wg, wu, wd, *,
    mesh, bt: int = 8, n_programs: int = 2, alpha: int = 1,
    steal: bool = True, axis: str = MESH_AXIS,
    return_telemetry: bool = False,
):
    """Router-free mesh twin of :func:`expert_ffn_nodrop_ref`: same argument
    order, same ``[T, d]`` f32 return, expert dim sharded over ``mesh``'s
    ``axis``.  The conformance suite asserts this bit-identical to the
    oracle on clean schedules."""
    n_devices = mesh.shape[axis]
    n_experts = wg.shape[0]
    body = functools.partial(
        mesh_dispatch_body, n_experts=n_experts, n_devices=n_devices,
        bt=bt, n_programs=n_programs, alpha=alpha, steal=steal,
        axis=axis,
    )
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )
    y, tele = fn(
        jnp.asarray(x), jnp.asarray(idx, jnp.int32),
        jnp.asarray(gates, jnp.float32), wg, wu, wd,
    )
    return (y, tele) if return_telemetry else y


def moe_ffn_mesh_ws(
    x, p, cfg, group_size: int = 1024, *,
    mesh=None, bt: int = 8, n_programs: int = 2, alpha: int = 1,
):
    """x: [B, S, d] -> (y, aux_loss) — `moe_ffn` drop-in with the dropless
    dispatch sharded over a device mesh (``cfg.moe_dispatch="mesh-ws"``).

    Same router, shared-expert, and aux-loss math as ``moe_ffn_ws``; the
    routed-expert core runs the two-level cross-device scheduler.  With
    ``mesh=None`` an expert mesh over the available devices is built via
    :func:`repro.launch.mesh.make_expert_mesh` (largest divisor of
    ``cfg.n_experts`` that fits the host's device count — 1 device
    degenerates to intra-chip WS with the same code path).  Forward-only:
    training keeps ``moe_dispatch="ws"`` (`launch.steps` enforces this).
    """
    from repro.moe_ws.layer import _router, _shared_experts

    if mesh is None:
        from repro.launch.mesh import make_expert_mesh

        mesh = make_expert_mesh(cfg.n_experts)
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    _, gate_vals, idx, aux = _router(x_flat, p, cfg, group_size)
    y = expert_ffn_mesh_ws(
        idx, gate_vals, x_flat, p["we_g"], p["we_u"], p["we_d"],
        mesh=mesh, bt=bt, n_programs=n_programs, alpha=alpha,
    )
    if cfg.n_shared_experts:
        y = y + _shared_experts(x_flat, p).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# single-device emulation: the identical protocol with collectives replaced
# by stacking — tier-1 conformance and the adversarial drills drive this.


class EmulatedDispatch(NamedTuple):
    y: jnp.ndarray                  # [T, d] combined rows
    plans: tuple                    # per-device StealPlan actually applied
    adv: jnp.ndarray                # [D] exchanged advisories (pre-override)
    mult_total: tuple               # per-device merged multiplicity
    clocks: tuple                   # per-device (c1, c2, cs) makespans
    tails: tuple                    # per-device live tile counts [El]


def emulate_mesh_dispatch(
    x_flat, idx, gates, wg, wu, wd, *,
    n_devices: int, bt: int = 8, n_programs: int = 2, alpha: int = 1,
    adv_override=None,
    plans_override: Optional[Sequence[StealPlan]] = None,
) -> EmulatedDispatch:
    """Run the mesh protocol on one device, devices emulated by a python
    loop and every collective replaced by the stacked equivalent.

    The numerics are the deployed path's: psum deliveries become adds over
    slots with at most one nonzero contributor per thief, so emulated and
    shard_map outputs agree bitwise.  Two adversarial hooks exercise what a
    live mesh cannot be forced into deterministically:

    * ``adv_override [D]`` replaces the exchanged advisories — arbitrarily
      stale/corrupt load summaries (claiming load where none remains, or
      hiding real load) may mis-rank victims but must not break exactness;
    * ``plans_override`` replaces the replicated plan wholesale — segments
      may overlap the victim's retained prefix or each other, forcing
      cross-device duplicate execution that only the multiplicity
      normalization can absorb.
    """
    n_experts = wg.shape[0]
    El = expert_shard(n_experts, n_devices)
    T, k = jnp.asarray(idx).shape
    Tk = T * k
    xf = jnp.asarray(x_flat, jnp.float32)
    wg = jnp.asarray(wg, jnp.float32)
    wu = jnp.asarray(wu, jnp.float32)
    wd = jnp.asarray(wd, jnp.float32)
    r1, r2 = phase_rounds(Tk, bt, n_programs, n_devices)

    puts, res1s = [], []
    for m in range(n_devices):
        put = route_local_pool_jax(idx, gates, n_experts, m * El, El, bt)
        state = local_pool_state(put, n_programs)
        sl = slice(m * El, (m + 1) * El)
        res1 = run_moe_schedule(
            state, xf, put.routed.tok_idx, wg[sl], wu[sl], wd[sl], bt=bt,
            steal=True, steal_policy="cost", rounds=r1,
        )
        puts.append(put)
        res1s.append(res1)
    pool_tiles = puts[0].records.shape[0]

    adv = jnp.stack([reduce_advisory(r.remaining) for r in res1s])
    g_head = jnp.stack([jnp.asarray(r.head, jnp.int32) for r in res1s])
    g_tail = jnp.stack([jnp.asarray(p.tail, jnp.int32) for p in puts])
    adv_eff = adv if adv_override is None else jnp.asarray(adv_override,
                                                           jnp.int32)
    if plans_override is not None:
        plans = list(plans_override)
    else:
        plans = [
            plan_steals(adv_eff, g_head, g_tail, jnp.int32(m),
                        n_devices=n_devices, bt=bt, alpha=alpha)
            for m in range(n_devices)
        ]

    out_in = [jnp.zeros_like(res1s[m].out) for m in range(n_devices)]
    mult_in = [jnp.zeros_like(res1s[m].mult) for m in range(n_devices)]
    res2s, res_ss = [], []
    for m in range(n_devices):
        put, res1, plan = puts[m], res1s[m], plans[m]
        sl = slice(m * El, (m + 1) * El)
        rem2 = apply_donation(res1.remaining,
                              donated_cost(put, plan.new_tail))
        state2 = QueueState(
            tasks=put.records, head=res1.head, tail=plan.new_tail,
            local_head=res1.local_head, taken=res1.taken, task_list=None,
            n_tasks_hint=pool_tiles, remaining=rem2,
            pool_off=put.toff[: El + 1],
        )
        res2 = run_moe_schedule(
            state2, xf, put.routed.tok_idx, wg[sl], wu[sl], wd[sl], bt=bt,
            steal=True, steal_policy="cost", rounds=r2, out=res1.out,
            mult=res1.mult,
        )
        res2s.append(res2)

        if not bool(plan.stole):
            res_ss.append(None)
            continue
        v = int(plan.victim)
        vput = puts[v]
        vsl = slice(v * El, (v + 1) * El)
        state_s = QueueState(
            tasks=vput.records, head=plan.s_head, tail=plan.s_tail,
            local_head=jnp.zeros((n_programs, El), jnp.int32),
            taken=jnp.full((pool_tiles,), -1, jnp.int32), task_list=None,
            n_tasks_hint=pool_tiles,
            remaining=(plan.s_tail - plan.s_head) * bt,
            pool_off=vput.toff[: El + 1],
        )
        res_s = run_moe_schedule(
            state_s, xf, vput.routed.tok_idx, wg[vsl], wu[vsl], wd[vsl],
            bt=bt, steal=True, steal_policy="cost", rounds=r2,
        )
        res_ss.append(res_s)
        out_in[v] = out_in[v] + res_s.out
        mult_in[v] = mult_in[v] + jnp.asarray(res_s.mult)

    pairs = jnp.zeros((Tk + 1, xf.shape[-1]), jnp.float32)
    mult_total = []
    clocks = []
    for m in range(n_devices):
        out_t = res2s[m].out + out_in[m]
        mult_t = jnp.asarray(res2s[m].mult) + mult_in[m]
        mult_total.append(mult_t)
        pairs = pairs + _pair_combine_part(puts[m].routed, out_t, mult_t,
                                           bt=bt)
        cs = 0 if res_ss[m] is None else int(jnp.asarray(res_ss[m].clock).max())
        clocks.append((int(jnp.asarray(res1s[m].clock).max()),
                       int(jnp.asarray(res2s[m].clock).max()), cs))
    y = _combine_pairs(pairs, gates)
    return EmulatedDispatch(
        y=y, plans=tuple(plans), adv=adv, mult_total=tuple(mult_total),
        clocks=tuple(clocks), tails=tuple(jnp.asarray(p.tail) for p in puts),
    )

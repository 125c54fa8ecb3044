"""MoE dispatch benchmark: dropping-dense vs ws-dropless across router skew.

Workload: top-k routing over E experts with a heavy-tailed popularity
distribution — ``skew`` is the target ratio of the hottest expert's load to
the mean load, the shape DeepSeek-V2/Kimi-K2-class routers produce.  Two
dispatches process the same routed (token, expert) pairs:

* **dropping-dense** (`models.moe.moe_ffn`): fixed per-expert capacity
  ``C = _capacity(T, k, E, cf)``; the FFN einsums are shaped [E, C]
  regardless of which slots are live, so its cost is ``E*C`` token-rows —
  balanced (capacity is uniform), but every row the router sends over C is
  **dropped** and the padded slots of cold experts are wasted work.
* **ws-dropless** (`repro.moe_ws`): one task row per routed pair, expert
  tiles through the fence-free work-stealing megakernel.  Cost is exactly
  the routed work; hot-expert queue skew is erased by thieves.  Nothing is
  dropped — the combine is exact after multiplicity normalization.

Reported per skew (units: token-rows of expert FFN, the shared cost model):

* ``dense_makespan``   — E*C/P rows (the dense grid split over P programs)
* ``ws/static makespan`` — device-measured clock of the megakernel
* ``drop_rate``        — fraction of routed pairs the dense path loses
                         (replayed with the dense cumsum slotting)
* ``max_abs_err``      — ws combine vs the dense **no-drop** oracle

Plus ``grad_rows``: jit(grad) through the dispatch's custom VJP at the
headline skew — wall clock per backward (``grad_dispatch`` dense vs ws) and
gradient parity vs ``jax.grad`` of the no-drop oracle (gated at fp32
tolerance; `benchmarks/perf_smoke.py` replays it in CI).

Writes BENCH_moe.json next to this file.  ``--dry-run`` shrinks shapes for
CI (Pallas interpret mode on CPU).  Exit status 1 when the headline claim
fails: at skew >= 4 the dense path must be dropping tokens (>0%) while the
ws makespan beats the dense makespan by >= 2x.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np


def make_skewed_routing(T: int, E: int, k: int, skew: float, seed: int = 0):
    """Sample top-k routing with hot-set popularity ``skew`` (hot/mean load).

    A hot set of ``max(1, E // 16)`` experts carries ``skew``× the mean
    per-expert load; the rest share the remainder uniformly.  Returns
    (idx [T, k], gates [T, k]) with gates normalized per token.
    """
    rng = np.random.RandomState(seed)
    h = max(1, E // 16)
    skew = min(float(skew), 0.95 * E / h)  # keep the hot weight finite
    w_hot = skew * (E - h) / max(E - skew * h, 1e-9)
    w = np.ones(E, dtype=np.float64)
    # hot experts land anywhere in [0, E): a static expert->program placement
    # cannot assume they are spread conveniently
    w[rng.choice(E, size=h, replace=False)] = w_hot
    p = w / w.sum()
    idx = np.stack(
        [rng.choice(E, size=k, replace=False, p=p) for _ in range(T)]
    ).astype(np.int32)
    gates = rng.uniform(0.2, 1.0, size=(T, k)).astype(np.float32)
    gates /= gates.sum(axis=1, keepdims=True)
    return idx, gates


def dense_drop_stats(idx, E: int, C: int):
    """Replay the dense path's capacity slotting (cumsum over the flattened
    (token, choice) axis, exactly `models.moe.moe_ffn`) and count drops."""
    T, k = idx.shape
    flat = np.zeros((T * k, E), dtype=np.int64)
    flat[np.arange(T * k), idx.reshape(-1)] = 1
    slot = np.cumsum(flat, axis=0) - flat
    in_cap = (slot[np.arange(T * k), idx.reshape(-1)] < C)
    dropped = int((~in_cap).sum())
    return dropped, dropped / float(T * k)


def run_one(T, d, f, E, k, P, bt, cf, skew, seed=0, trace=False, trace_sink=None):
    import jax
    import jax.numpy as jnp

    from repro.models.moe import _capacity
    from repro.moe_ws import (
        combine_routed,
        expert_ffn_nodrop_ref,
        route_to_tasks,
        run_moe_schedule,
    )
    from repro.pallas_ws import make_queue_state

    idx, gates = make_skewed_routing(T, E, k, skew, seed)
    loads = np.bincount(idx.reshape(-1), minlength=E)
    C = _capacity(T, k, E, cf)
    dropped, drop_rate = dense_drop_stats(idx, E, C)

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, f), jnp.float32) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (E, d, f), jnp.float32) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (E, f, d), jnp.float32) / np.sqrt(f)
    ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)

    row = dict(
        T=T, d=d, f=f, E=E, k=k, n_programs=P, bt=bt, capacity=C,
        skew=skew, routed=int(T * k),
        max_load=int(loads.max()), mean_load=float(loads.mean()),
        dense_dropped=dropped, dense_drop_rate=drop_rate,
    )
    # "ws" runs the cost-aware O(1) victim selection (the default);
    # "ws_scan" keeps the PR-1 sequential scan for comparison (§3.6)
    for name, sched, policy in (
        ("static", "static", "cost"),
        ("ws", "ws", "cost"),
        ("ws_scan", "ws", "scan"),
    ):
        tasks, routed = route_to_tasks(idx, gates, E, bt=bt)
        # ws: one queue per expert (the per-expert token list), thieves roam;
        # static: experts placed round-robin over programs (classic EP) and
        # each program drains only its own queue
        state = make_queue_state(
            tasks, P, n_queues=E if sched == "ws" else P, partition="owner"
        )
        t0 = time.perf_counter()
        res = run_moe_schedule(
            state, x, routed.tok_idx, wg, wu, wd,
            bt=bt, steal=(sched == "ws"), steal_policy=policy,
            trace=(trace and name == "ws"),
        )
        dt = time.perf_counter() - t0
        y = combine_routed(routed, tasks, res)
        err = float(jnp.abs(y - ref).max())
        assert (res.mult[: state.n_tasks] >= 1).all(), "dropless invariant"
        row[name] = dict(
            makespan=res.makespan,
            total_work=res.total_work,
            wasted_slots=res.wasted_slots,
            steals=int(res.steals.sum()),
            steal_ratio=round(res.steal_ratio, 3),
            mult_max=int(res.mult[: state.n_tasks].max()),
            slots_scanned=res.slots_scanned,
            extractions=res.extractions,
            scan_per_extraction=round(res.scan_per_extraction, 3),
            max_abs_err=err,
            wall_s=round(dt, 3),
        )
        if res.events is not None:
            from repro.wstrace import WSTrace

            tr = WSTrace.from_run(state, res)
            row[name]["trace"] = tr.summary()
            if trace_sink is not None:
                trace_sink[name] = tr
    # the dense einsums process E*C rows no matter what the router did;
    # capacity is uniform per expert, so the grid splits evenly over P
    row["dense_makespan"] = -(-E * C // P)
    row["speedup_vs_dense"] = row["dense_makespan"] / max(1, row["ws"]["makespan"])
    row["speedup_vs_static"] = row["static"]["makespan"] / max(1, row["ws"]["makespan"])
    return row


def run_grad(T, d, f, E, k, P, bt, skew, seed=0):
    """Grad-path rows (DESIGN.md §4.5): time ``jit(grad)`` through the ws
    dispatch's custom VJP — backward as the closed-form dense transpose and
    as the re-scheduled megakernel launch — and pin its parity against
    ``jax.grad`` of the no-drop oracle (``max_abs_err`` over every
    cotangent: gates, x, and all three expert weights)."""
    import jax
    import jax.numpy as jnp

    from repro.moe_ws import expert_ffn_nodrop_ref, expert_ffn_ws

    idx, gates = make_skewed_routing(T, E, k, skew, seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, f), jnp.float32) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (E, d, f), jnp.float32) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (E, f, d), jnp.float32) / np.sqrt(f)
    args = (jnp.asarray(gates), x, wg, wu, wd)

    def loss_ref(gates, x, wg, wu, wd):
        return (expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(*args)

    rows = []
    for gd in ("dense", "ws"):

        def loss_ws(gates, x, wg, wu, wd, gd=gd):
            return (expert_ffn_ws(idx, gates, x, wg, wu, wd, grad_dispatch=gd,
                                  n_programs=P, bt=bt) ** 2).sum()

        g_fn = jax.jit(jax.grad(loss_ws, argnums=(0, 1, 2, 3, 4)))
        t0 = time.perf_counter()
        g = jax.block_until_ready(g_fn(*args))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            g = jax.block_until_ready(g_fn(*args))
            best = min(best, time.perf_counter() - t0)
        err = max(
            float(jnp.abs(a - b).max()) for a, b in zip(g, g_ref)
        )
        rows.append(
            dict(
                grad_dispatch=gd, skew=skew, T=T, E=E, k=k,
                max_abs_err=err,
                wall_s=round(best, 4),
                compile_s=round(compile_s, 3),
            )
        )
    return rows


# the CI smoke cell (T, d, f, E, k, P, bt, cf) — perf_smoke.py replays it
# with tracing off and holds the makespans to exact equality with BENCH.json
DRY_SHAPES = (48, 16, 32, 32, 2, 2, 4, 1.25)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true", help="tiny shapes for CI smoke")
    ap.add_argument("--skews", default="1,2,4,8")
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--trace", default=None, metavar="TRACE_JSON",
                    help="write a Perfetto timeline of the highest-skew ws "
                         "run (load it at https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.out is None:
        # dry-run results go to a sibling file so CI smokes never clobber
        # the committed full-size benchmark
        name = "BENCH_moe.dryrun.json" if args.dry_run else "BENCH_moe.json"
        args.out = str(pathlib.Path(__file__).parent / name)

    if args.dry_run:
        T, d, f, E, k, P, bt, cf = DRY_SHAPES
    else:
        T, d, f, E, k, P, bt, cf = 96, 32, 64, 64, 2, 4, 4, 1.25

    skews = [float(s) for s in args.skews.split(",")]
    rows = []
    traces = {}
    hdr = ("skew,dense_makespan,ws_makespan,speedup_dense,static_makespan,"
           "drop_rate,steals,mult_max,max_err")
    print(hdr)
    for skew in skews:
        sink = {}
        row = run_one(T, d, f, E, k, P, bt, cf, skew, trace=True,
                      trace_sink=sink)
        if "ws" in sink:
            traces[skew] = sink["ws"]
        rows.append(row)
        print(
            f"{skew},{row['dense_makespan']},{row['ws']['makespan']},"
            f"{row['speedup_vs_dense']:.2f},{row['static']['makespan']},"
            f"{row['dense_drop_rate']:.3f},{row['ws']['steals']},"
            f"{row['ws']['mult_max']},{row['ws']['max_abs_err']:.2e}"
        )

    # grad path: jit(grad) through the custom VJP at the headline skew —
    # wall clock per backward evaluation + parity vs the no-drop oracle
    grad_rows = run_grad(T, d, f, E, k, P, bt, skew=4.0)
    print("grad_dispatch,wall_s,compile_s,max_abs_err")
    for r in grad_rows:
        print(f"{r['grad_dispatch']},{r['wall_s']},{r['compile_s']},"
              f"{r['max_abs_err']:.2e}")

    # traced-Put audit: the jit-compatible queue construction must lower to
    # plain tensor ops — 0 RMW / 0 locks / 0 fences on Put, Take AND Steal
    # (asserts internally; the rows land in the payload as the record)
    try:
        from benchmarks.zero_cost import audit_traced_put
    except ModuleNotFoundError:  # run as a bare script: python benchmarks/...
        sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
        from benchmarks.zero_cost import audit_traced_put

    payload = dict(
        bench="moe_dispatch",
        config=dict(T=T, d=d, f=f, E=E, k=k, n_programs=P, bt=bt,
                    capacity_factor=cf, dry_run=args.dry_run),
        rows=rows,
        grad_rows=grad_rows,
        traced_put_audit=audit_traced_put(),
    )
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"[moe_dispatch] wrote {args.out}")

    if args.trace and traces:
        from repro.wstrace import write_perfetto

        write_perfetto(traces[max(traces)], args.trace)
        print(f"[moe_dispatch] wrote Perfetto trace (skew={max(traces)}) to "
              f"{args.trace} — open at https://ui.perfetto.dev")

    # the headline claim this bench exists to witness: under real router
    # skew the dense path is lossy AND slower than dropless ws dispatch
    bad = [
        r for r in rows
        if r["skew"] >= 4
        and (r["speedup_vs_dense"] < 2.0 or r["dense_drop_rate"] <= 0.0)
    ]
    if bad:
        print(f"[moe_dispatch] ws-dropless claim failed at skew >= 4: {bad}")
        return 1
    # grad-path claim: both backward evaluations of the custom VJP match
    # the no-drop oracle's gradients to fp32 tolerance
    bad_grad = [r for r in grad_rows if r["max_abs_err"] > 1e-3]
    if bad_grad:
        print(f"[moe_dispatch] custom-VJP grad parity failed: {bad_grad}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

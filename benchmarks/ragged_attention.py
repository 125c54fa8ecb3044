"""Ragged-attention scheduling benchmark: static grid vs device-resident
fence-free work-stealing (repro.pallas_ws), across sequence-length skew.

Workload: B sequences where one is ``skew``× longer than the rest — the
canonical ragged batch a serving engine sees.  Tile tasks are partitioned to
owner queues by batch row, so the long sequence piles its quadratic causal
tile cost onto one queue.  We report, in kv-block *tile-slots* (the
device-measured cost counters of the megakernel, identical for both
schedules):

* ``makespan``      — completion round of the slowest program (parallel time)
* ``wasted_slots``  — P × makespan − total work (idle tile-slots)
* ``steals``        — successful cross-queue extractions
* ``max_abs_err``   — ws output vs the dense length-masked oracle

plus the analytic makespan of a *dense* static grid (padded-length tiles,
no length awareness) — what a non-persistent kernel would burn.

Writes BENCH_ragged.json next to this file.  ``--dry-run`` shrinks shapes
for CI (Pallas interpret mode on CPU).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np


def make_skewed_lengths(B: int, S: int, skew: float, seed: int = 0) -> np.ndarray:
    """One sequence at full S, the rest at S/skew (min one kv block)."""
    rng = np.random.RandomState(seed)
    short = min(S, max(8, int(round(S / skew))))
    lengths = np.full(B, short, dtype=np.int64)
    lengths[rng.randint(B)] = S
    return lengths


def dense_grid_makespan(lengths, S: int, H: int, bq: int, bk: int, P: int) -> int:
    """Tile-slots of a static *dense* grid: every padded (b, h, q-block) tile
    exists and sweeps its full causal kv range, round-robin over P programs."""
    B = len(lengths)
    costs = []
    for _ in range(B):
        for _h in range(H):
            for qi in range(-(-S // bq)):
                costs.append(max(1, -(-min(S, (qi + 1) * bq) // bk)))
    loads = np.zeros(P, dtype=np.int64)
    for i, c in enumerate(costs):
        loads[i % P] += c
    return int(loads.max())


def run_one(B, H, S, hd, bq, bk, P, skew, seed=0, trace=False, trace_sink=None):
    import jax
    import jax.numpy as jnp

    from repro.pallas_ws import ragged_attention_ref, ragged_flash_attention

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)
    lengths = make_skewed_lengths(B, S, skew, seed)

    row = dict(B=B, H=H, S=S, hd=hd, bq=bq, bk=bk, n_programs=P,
               skew=skew, lengths=lengths.tolist())
    ref = ragged_attention_ref(q, k, v, lengths)
    # "ws" is the cost-aware O(1) victim selection (the default);
    # "ws_scan" keeps the PR-1 sequential scan for apples-to-apples
    # makespan and scan-traffic comparison (DESIGN.md §3.6)
    for name, sched, policy in (
        ("static", "static", "cost"),
        ("ws", "ws", "cost"),
        ("ws_scan", "ws", "scan"),
    ):
        t0 = time.perf_counter()
        out, st = ragged_flash_attention(
            q, k, v, lengths, schedule=sched, steal_policy=policy,
            n_programs=P, bq=bq, bk=bk, return_stats=True,
            trace=(trace and name == "ws"),
        )
        dt = time.perf_counter() - t0
        err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
        row[name] = dict(
            makespan=st.makespan,
            total_work=st.total_work,
            wasted_slots=st.wasted_slots,
            steals=st.steals,
            mult_max=st.mult_max,
            slots_scanned=st.slots_scanned,
            extractions=st.extractions,
            scan_per_extraction=st.scan_per_extraction,
            queue_loads=st.queue_loads,
            max_abs_err=err,
            wall_s=round(dt, 3),
        )
        if getattr(st, "trace", None) is not None:
            row[name]["trace"] = st.trace.summary()
            if trace_sink is not None:
                trace_sink[name] = st.trace
    row["dense_grid_makespan"] = dense_grid_makespan(lengths, S, H, bq, bk, P)
    row["speedup_vs_static"] = row["static"]["makespan"] / max(1, row["ws"]["makespan"])
    row["speedup_vs_dense"] = row["dense_grid_makespan"] / max(1, row["ws"]["makespan"])
    row["scan_traffic_reduction"] = round(
        row["ws_scan"]["scan_per_extraction"]
        / max(1e-9, row["ws"]["scan_per_extraction"]), 1
    )
    return row


# the CI smoke cell (B, H, S, hd, bq, bk, P) — perf_smoke.py replays it with
# tracing off and holds the makespans to exact equality with BENCH.json
DRY_SHAPES = (4, 2, 64, 8, 8, 8, 4)


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
        name = "BENCH_ragged.dryrun.json" if args.dry_run else "BENCH_ragged.json"
        args.out = str(pathlib.Path(__file__).parent / name)

    if args.dry_run:
        B, H, S, hd, bq, bk, P = DRY_SHAPES
    else:
        B, H, S, hd, bq, bk, P = 8, 2, 256, 16, 16, 16, 4

    skews = [float(s) for s in args.skews.split(",")]
    rows = []
    traces = {}
    hdr = ("skew,static_makespan,ws_makespan,speedup,dense_makespan,steals,"
           "wasted_static,wasted_ws,scan/extr_cost,scan/extr_scan,max_err")
    print(hdr)
    for skew in skews:
        sink = {}
        row = run_one(B, H, S, hd, bq, bk, P, skew, trace=True,
                      trace_sink=sink)
        if "ws" in sink:
            traces[skew] = sink["ws"]
        rows.append(row)
        print(
            f"{skew},{row['static']['makespan']},{row['ws']['makespan']},"
            f"{row['speedup_vs_static']:.2f},{row['dense_grid_makespan']},"
            f"{row['ws']['steals']},{row['static']['wasted_slots']},"
            f"{row['ws']['wasted_slots']},{row['ws']['scan_per_extraction']},"
            f"{row['ws_scan']['scan_per_extraction']},"
            f"{row['ws']['max_abs_err']:.2e}"
        )

    payload = dict(
        bench="ragged_attention",
        config=dict(B=B, H=H, S=S, hd=hd, bq=bq, bk=bk, n_programs=P, dry_run=args.dry_run),
        rows=rows,
    )
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"[ragged_attention] wrote {args.out}")

    if args.trace and traces:
        from repro.wstrace import write_perfetto

        write_perfetto(traces[max(traces)], args.trace)
        print(f"[ragged_attention] wrote Perfetto trace (skew={max(traces)}) "
              f"to {args.trace} — open at https://ui.perfetto.dev")

    # the paper-level claim this bench exists to witness, plus the §3.6
    # policy claim: cost-aware victim selection must not cost makespan
    bad = [
        r for r in rows
        if r["skew"] >= 4
        and (r["speedup_vs_static"] <= 1.0
             or r["ws"]["makespan"] > r["ws_scan"]["makespan"] * 1.05)
    ]
    if bad:
        print(f"[ragged_attention] WS failed to beat static at skew >= 4: {bad}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

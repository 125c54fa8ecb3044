"""Benchmark orchestrator — one section per paper table/figure + the
framework-level benches.  CSV lines to stdout (tee'd to bench_output.txt).

Sections:
  [zero-cost]      paper Fig 9a/9b — put-take / put-steal µs/op + instr mix
                   (+ fence-free audit incl. the moe-ws expert dispatch)
  [spanning-tree]  paper Table 1 / Figs 10-14 — speedups per graph x algo
  [scheduler]      L1 TPU adaptation — lockstep rounds + async makespan
  [ragged]         device-resident WS tile scheduler vs static grid (pallas_ws)
  [moe]            dropless ws MoE dispatch vs capacity-dropping dense (moe_ws)
  [policy]         cost-aware O(1) victim selection vs sequential scan +
                   shared-pool vs padded traced queue layouts (§3.6)
  [mesh]           cross-device mesh-ws vs per-device-static expert
                   sharding on 8 forced host devices (§7)
  [serving]        replayed arrival traffic through the WS frontend —
                   unified one-launch engine step vs split-launch (§5)
  [chaos]          seeded fault storms (stalls, advisory corruption,
                   kill+rewind) through the relaxed-semantics SafetyChecker,
                   plus serving crash re-admission + watchdog parity
  [loader]         L2 host pipeline — work-stealing loader throughput
  [roofline]       dry-run roofline table (if results/dryrun.jsonl exists)

`python -m benchmarks.run --quick` shrinks sizes for CI.

After the scheduler-level sections run, the canonical perf trajectory is
composed into the top-level **BENCH.json** (repo root): one summary per
bench — makespan ratios, wasted tile-slots, scan traffic per extraction,
queue-array bytes, dryrun flops/bytes, fence-free audit — under a "full"
key (normal run) or a "smoke" key (``--quick``, deterministic interpret-mode
sizes).  PR-over-PR regressions diff this one file; the CI perf-smoke job
(`benchmarks/perf_smoke.py`) replays the quick grid and fails on regression
against the committed "smoke" numbers.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).parent
BENCH_JSON = BENCH_DIR.parent / "BENCH.json"


def _load(name: str, quick: bool):
    suffix = ".dryrun.json" if quick else ".json"
    path = BENCH_DIR / f"{name}{suffix}"
    if not path.exists():
        return None
    return json.loads(path.read_text())


def summarize(quick: bool) -> dict:
    """Reduce the per-bench JSON artifacts to the diffable trajectory rows:
    per bench the headline ratios at the interesting skews, scan traffic,
    queue bytes, and the dryrun cost-analysis numbers."""
    out = {}
    ragged = _load("BENCH_ragged", quick)
    if ragged:
        rows = [r for r in ragged["rows"] if r["skew"] >= 4] or ragged["rows"]
        r = rows[-1]
        out["ragged_attention"] = dict(
            skew=r["skew"],
            ws_makespan=r["ws"]["makespan"],
            static_makespan=r["static"]["makespan"],
            makespan_ratio=round(r["speedup_vs_static"], 3),
            wasted_ws=r["ws"]["wasted_slots"],
            wasted_static=r["static"]["wasted_slots"],
            scan_per_extraction_cost=r["ws"]["scan_per_extraction"],
            scan_per_extraction_scan=r["ws_scan"]["scan_per_extraction"],
            scan_traffic_reduction=r["scan_traffic_reduction"],
            max_abs_err=r["ws"]["max_abs_err"],
        )
    moe = _load("BENCH_moe", quick)
    if moe:
        rows = [r for r in moe["rows"] if r["skew"] >= 4] or moe["rows"]
        r = rows[-1]
        out["moe_dispatch"] = dict(
            skew=r["skew"],
            ws_makespan=r["ws"]["makespan"],
            dense_makespan=r["dense_makespan"],
            speedup_vs_dense=round(r["speedup_vs_dense"], 3),
            dense_drop_rate=round(r["dense_drop_rate"], 4),
            scan_per_extraction_cost=r["ws"]["scan_per_extraction"],
            scan_per_extraction_scan=r["ws_scan"]["scan_per_extraction"],
            max_abs_err=r["ws"]["max_abs_err"],
        )
        if moe.get("grad_rows"):
            # custom-VJP grad path: parity vs the no-drop oracle's grads
            # (perf_smoke gates on presence + fp32-tolerance correctness)
            out["moe_dispatch"]["grad"] = [
                {key: g[key] for key in ("grad_dispatch", "max_abs_err",
                                         "wall_s")}
                for g in moe["grad_rows"]
            ]
        if "traced_put_audit" in moe:
            out["traced_put_audit"] = [
                {k: a[k] for k in ("experiment", "algorithm", "rmws_per_op",
                                   "locks_per_op", "fences_per_op")}
                for a in moe["traced_put_audit"]
            ]
    mesh = _load("BENCH_mesh", quick)
    if mesh:
        rows = [r for r in mesh["rows"] if r["skew"] >= 4] or mesh["rows"]
        r = rows[-1]
        out["mesh_dispatch"] = dict(
            D=r["D"],
            skew=r["skew"],
            mesh_ws_makespan=r["mesh_ws"]["makespan"],
            static_makespan=r["static"]["makespan"],
            speedup_vs_static=round(r["speedup_vs_static"], 3),
            devices_stole=r["mesh_ws"]["devices_stole"],
            tiles_stolen=r["mesh_ws"]["tiles_stolen"],
            collective_bytes_measured=r["collective_bytes"]["measured_mesh_ws"],
            collective_bytes_analytic=r["collective_bytes"]["analytic_mesh_ws"],
            oracle_close=r["mesh_ws"]["oracle_close"],
        )
    serving = _load("BENCH_serving", quick)
    if serving:
        # deterministic columns only: the trace replay is seeded and the
        # engine single-threaded, so steps / utilization / counters / stream
        # parity are exact; wall-clock latencies stay in BENCH_serving.json
        out["serving"] = [
            dict(
                mode=r["mode"],
                path=r["path"],
                steps=r["steps"],
                tokens_out=r["tokens_out"],
                slot_utilization=r["slot_utilization"],
                completed=len(r["completed"]),
                rejected=len(r["rejected"]),
                stolen=r["counters"]["stolen"],
                dup_completed=r["counters"]["dup_completed"],
                streams_match=serving["streams_match"][r["mode"]],
            )
            for r in serving["rows"]
        ]
    chaos = _load("BENCH_chaos", quick)
    if chaos:
        # everything here is deterministic (seeded plans, seeded traffic,
        # greedy decode) — perf_smoke gates these columns exactly
        sched = [r for r in chaos["rows"] if r["section"] == "scheduler"]
        cells = {r["cell"]: r for r in chaos["rows"] if "cell" in r}
        out["chaos"] = dict(
            all_ok=chaos["all_ok"],
            scheduler_cells=len(sched),
            checker_clean=all(r["checker_ok"] for r in sched),
            max_mult=max((r["max_mult"] for r in sched), default=0),
            fault_off_parity=cells["fault_off_parity"]["ok"],
            replica_crash=dict(
                ok=cells["replica_crash"]["ok"],
                exactly_once=cells["replica_crash"]["exactly_once"],
                streams_match=cells["replica_crash"]["streams_match"],
                readmitted=cells["replica_crash"]["readmitted"],
                crashed=cells["replica_crash"]["crashed"],
            ),
            watchdog=dict(
                ok=cells["watchdog"]["ok"],
                streams_match=cells["watchdog"]["streams_match"],
                degradations=cells["watchdog"]["degradation_counts"],
            ),
        )
    policy = _load("BENCH_policy", quick)
    if policy:
        out["steal_policy"] = [
            dict(
                E=r["E"],
                skew=r["skew"],
                ws_cost_makespan=r["ws_cost"]["makespan"],
                ws_scan_makespan=r["ws_scan"]["makespan"],
                static_makespan=r["static"]["makespan"],
                pool_makespan=r["pool"]["makespan"],
                scan_per_extraction_cost=r["ws_cost"]["scan_per_extraction"],
                scan_per_extraction_scan=r["ws_scan"]["scan_per_extraction"],
                scan_traffic_reduction=r["traffic_reduction"],
                ws_halfrun_makespan=r.get("ws_halfrun", {}).get("makespan"),
                scan_per_extraction_halfrun=r.get("ws_halfrun", {}).get(
                    "scan_per_extraction"),
                probe_reduction_halfrun=r.get("probe_reduction_halfrun"),
                put_scatter_ops=r.get("put_scatter_ops"),
                queue_bytes=r["queue_bytes"],
                dryrun=r.get("dryrun"),
            )
            for r in policy["rows"]
        ]
    return out


def compose_bench_json(quick: bool) -> None:
    """Merge this run's summaries into the top-level BENCH.json under the
    "smoke" (--quick) or "full" key, preserving the other key so one file
    carries both the committed trajectory and its CI reference."""
    summary = summarize(quick)
    if not summary:
        return
    data = {}
    if BENCH_JSON.exists():
        data = json.loads(BENCH_JSON.read_text())
    data["smoke" if quick else "full"] = summary
    BENCH_JSON.write_text(json.dumps(data, indent=2))
    print(f"[benchmarks] composed {BENCH_JSON}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--sections",
        default="zero-cost,spanning-tree,scheduler,ragged,moe,policy,mesh,serving,chaos,loader,roofline",
    )
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sections = set(args.sections.split(","))
    t0 = time.time()

    if "zero-cost" in sections:
        print("\n== [zero-cost] put-take / put-steal (paper Fig 9) ==")
        from . import zero_cost

        zero_cost.main(n_ops=20_000 if args.quick else 100_000)

    if "spanning-tree" in sections:
        print("\n== [spanning-tree] parallel spanning tree (paper Table 1) ==")
        from . import spanning_tree

        spanning_tree.main(scale=4_000 if args.quick else 40_000)

    if "scheduler" in sections:
        print("\n== [scheduler] L1 work-stealing microbatch scheduler ==")
        from . import scheduler

        scheduler.main()

    status = 0
    if "ragged" in sections:
        print("\n== [ragged] device-resident WS tile scheduler vs static grid ==")
        from . import ragged_attention

        # nonzero when ws fails to beat static at skew >= 4 — the bench's
        # regression signal must survive the suite entry point
        status |= ragged_attention.main(["--dry-run"] if args.quick else [])

    if "moe" in sections:
        print("\n== [moe] dropless ws MoE dispatch vs dropping dense ==")
        from . import moe_dispatch

        # nonzero when ws-dropless fails to beat the dropping dense path
        # >= 2x at skew >= 4 (or dense mysteriously stops dropping)
        status |= moe_dispatch.main(["--dry-run"] if args.quick else [])

    if "policy" in sections:
        print("\n== [policy] cost-aware victim selection + queue layouts ==")
        from . import steal_policy

        # nonzero when the §3.6 claims fail at the largest expert count:
        # scan traffic not reduced >= 10x, pool bytes not reduced >= 4x,
        # or a makespan regression vs the scan policy
        status |= steal_policy.main(["--dry-run"] if args.quick else [])

    if "mesh" in sections:
        print("\n== [mesh] cross-device mesh-ws vs per-device-static ==")
        from . import mesh_dispatch

        # nonzero when mesh-ws fails to beat static sharding at skew >= 4
        # on 8 forced host devices, or any row loses bitwise oracle parity
        status |= mesh_dispatch.main(["--dry-run"] if args.quick else [])

    if "serving" in sections:
        print("\n== [serving] replayed traffic: unified vs split engine step ==")
        from . import serving_traffic

        # nonzero when any rid is lost/duplicated or the unified one-launch
        # step's token streams diverge from the split-launch oracle
        status |= serving_traffic.main(["--dry-run"] if args.quick else [])

    if "chaos" in sections:
        print("\n== [chaos] seeded fault storms through the SafetyChecker ==")
        from . import chaos_storm

        # nonzero when any cell fails the checker (lost task, multiplicity
        # bound, double claim), output parity, the fault-off bitwise gate,
        # or serving exactly-once / stream parity under crash + watchdog
        status |= chaos_storm.main(["--dry-run"] if args.quick else [])

    if any(s in sections for s in ("ragged", "moe", "policy", "mesh", "serving", "chaos")):
        compose_bench_json(quick=args.quick)

    if "loader" in sections:
        print("\n== [loader] L2 work-stealing data loader ==")
        import numpy as np

        from repro.configs import get_config
        from repro.data import WorkStealingLoader, make_batch
        from repro.models.config import SHAPES

        cfg = get_config("llama3.2-3b", smoke=True)
        n_tasks = 16 if args.quick else 48

        def prepare(tid):
            return make_batch(cfg, SHAPES["train_4k"], step=tid, n_rows=1)

        for workers in (1, 2, 4):
            t = time.time()
            loader = WorkStealingLoader(prepare, n_tasks=n_tasks, n_workers=workers).start()
            loader.batches(timeout=120)
            dt = time.time() - t
            print(
                f"loader,workers={workers},tasks={n_tasks},sec={dt:.2f},"
                f"extractions={loader.stats['extractions']},dups={loader.stats['duplicates']}"
            )

    if "roofline" in sections:
        print("\n== [roofline] dry-run roofline table ==")
        from . import roofline

        roofline.main()

    print(f"\n[benchmarks] done in {time.time() - t0:.1f}s")
    return status


if __name__ == "__main__":
    sys.exit(main())

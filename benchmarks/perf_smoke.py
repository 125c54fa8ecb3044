"""CI perf-smoke: replay the dry-run bench grid and fail on regression.

The interpret-mode schedulers are deterministic — makespans, wasted slots,
and scan-traffic counters are exact replays of the lockstep model — so a
perf regression shows up as a *number change*, not a noisy timing.  This
job re-runs the quick grid (`ragged_attention`, `moe_dispatch`,
`steal_policy`, `mesh_dispatch`, `serving_traffic`, all ``--dry-run``),
summarizes it with the same reducer
that builds BENCH.json, and compares against the committed BENCH.json
"smoke" trajectory:

* ws/static makespan ratio must not drop below committed × (1 − tol);
* scan traffic per extraction (cost policy) must not grow past
  committed × (1 + tol);
* the §3.6 scan-traffic reduction and pool queue-bytes ratio must not drop
  below committed × (1 − tol);
* the pool layout must still reproduce the host-layout ws makespan exactly;
* the mesh dispatch's speedup over per-device-static sharding must not drop
  below committed × (1 − tol), its collective bytes must not grow past
  committed × (1 + tol), and it must stay **bit-identical** to the no-drop
  oracle — an absolute gate, like the grad rows;
* the custom-VJP grad rows must be present (once committed) and match the
  no-drop oracle's gradients to fp32 tolerance — an absolute gate, since a
  wrong backward is a correctness bug, not noise;
* the serving replay (seeded trace, single-threaded — deterministic) must
  keep every unified/split cell: steps and utilization within tolerance,
  and the unified step's token streams **identical** to the split-launch
  oracle with no lost or duplicated request — absolute gates;
* a ``trace=False`` replay of the headline ragged/moe cells must reproduce
  the committed (traced) makespans **exactly** — event tracing must be free
  when off (ISSUE 7; the trace=False lowering is the pre-trace kernel);
* the chaos storm matrix (ISSUE 9; seeded fault plans, deterministic) must
  stay checker-clean with real multiplicity exercised, ``fault_plan=None``
  must remain bit-identical to the fault-free lowering, and the serving
  crash/watchdog cells must keep exactly-once completion and stream
  parity — all absolute gates.

Exit 1 on any violation (or if a bench's own headline claim already
failed).  Tolerance defaults to 10% — tight enough to catch a real
scheduler regression, loose enough to survive benign re-tuning of the
dry-run shapes (which should land together with a refreshed BENCH.json).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

if __package__ in (None, ""):  # run as a bare script: python benchmarks/...
    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

from benchmarks.run import BENCH_JSON, summarize  # noqa: E402


def _check(errs, name, ok, detail):
    if not ok:
        errs.append(f"{name}: {detail}")


def compare(fresh: dict, committed: dict, tol: float) -> list:
    errs = []
    lo, hi = 1.0 - tol, 1.0 + tol
    if not committed:
        return ["BENCH.json has no 'smoke' section: run "
                "`python -m benchmarks.run --quick` and commit BENCH.json"]
    # every committed section must actually be compared — a missing fresh
    # summary (bench not run, dryrun file absent) is a failure, never a
    # silent skip, or the gate would pass vacuously
    for section in ("ragged_attention", "moe_dispatch", "steal_policy",
                    "mesh_dispatch", "serving", "chaos"):
        if committed.get(section) and not fresh.get(section):
            errs.append(f"{section}: committed reference exists but the "
                        "fresh dry-run summary is missing — bench not run?")
    r_new, r_old = fresh.get("ragged_attention"), committed.get("ragged_attention")
    if r_new and r_old:
        _check(errs, "ragged makespan ratio",
               r_new["makespan_ratio"] >= r_old["makespan_ratio"] * lo,
               f"{r_new['makespan_ratio']} < {r_old['makespan_ratio']} * {lo}")
        _check(errs, "ragged scan traffic (cost)",
               r_new["scan_per_extraction_cost"]
               <= r_old["scan_per_extraction_cost"] * hi,
               f"{r_new['scan_per_extraction_cost']} > "
               f"{r_old['scan_per_extraction_cost']} * {hi}")
    m_new, m_old = fresh.get("moe_dispatch"), committed.get("moe_dispatch")
    if m_new and m_old:
        _check(errs, "moe speedup vs dense",
               m_new["speedup_vs_dense"] >= m_old["speedup_vs_dense"] * lo,
               f"{m_new['speedup_vs_dense']} < {m_old['speedup_vs_dense']} * {lo}")
        _check(errs, "moe scan traffic (cost)",
               m_new["scan_per_extraction_cost"]
               <= m_old["scan_per_extraction_cost"] * hi,
               f"{m_new['scan_per_extraction_cost']} > "
               f"{m_old['scan_per_extraction_cost']} * {hi}")
        # grad path (custom VJP): once committed, the rows may never vanish,
        # and parity vs the no-drop oracle's gradients is an ABSOLUTE gate —
        # a wrong backward is a correctness bug, not a perf regression
        if m_old.get("grad") and not m_new.get("grad"):
            errs.append("moe grad rows: committed reference exists but the "
                        "fresh dry-run has none — grad bench not run?")
        for g in m_new.get("grad", []):
            _check(errs, f"moe grad parity [{g['grad_dispatch']}]",
                   g["max_abs_err"] <= 1e-3,
                   f"max_abs_err {g['max_abs_err']} > 1e-3 vs the no-drop "
                   "oracle gradients")
    x_new, x_old = fresh.get("mesh_dispatch"), committed.get("mesh_dispatch")
    if x_new and x_old:
        _check(errs, "mesh speedup vs static",
               x_new["speedup_vs_static"] >= x_old["speedup_vs_static"] * lo,
               f"{x_new['speedup_vs_static']} < "
               f"{x_old['speedup_vs_static']} * {lo}")
        _check(errs, "mesh collective bytes",
               x_new["collective_bytes_measured"]
               <= x_old["collective_bytes_measured"] * hi,
               f"{x_new['collective_bytes_measured']} > "
               f"{x_old['collective_bytes_measured']} * {hi}")
        # oracle parity is an absolute gate (correctness, not perf)
        _check(errs, "mesh oracle parity", x_new["oracle_close"],
               "mesh-ws output off the no-drop oracle")
    p_new = {(r["E"], r["skew"]): r for r in fresh.get("steal_policy", [])}
    p_old = {(r["E"], r["skew"]): r for r in committed.get("steal_policy", [])}
    if p_old and not set(p_new) & set(p_old):
        errs.append(
            "steal_policy: no (E, skew) cell in common between the fresh "
            f"dry-run grid {sorted(p_new)} and the committed reference "
            f"{sorted(p_old)} — refresh BENCH.json together with the grid"
        )
    for key in sorted(set(p_new) & set(p_old)):
        n, o = p_new[key], p_old[key]
        tag = f"steal_policy E={key[0]} skew={key[1]}"
        _check(errs, f"{tag} traffic reduction",
               n["scan_traffic_reduction"] >= o["scan_traffic_reduction"] * lo,
               f"{n['scan_traffic_reduction']} < "
               f"{o['scan_traffic_reduction']} * {lo}")
        _check(errs, f"{tag} queue bytes ratio",
               n["queue_bytes"]["ratio"] >= o["queue_bytes"]["ratio"] * lo,
               f"{n['queue_bytes']['ratio']} < {o['queue_bytes']['ratio']} * {lo}")
        _check(errs, f"{tag} ws makespan",
               n["ws_cost_makespan"] <= o["ws_cost_makespan"] * hi,
               f"{n['ws_cost_makespan']} > {o['ws_cost_makespan']} * {hi}")
        _check(errs, f"{tag} pool schedule parity",
               n["pool_makespan"] == n["ws_cost_makespan"],
               f"pool {n['pool_makespan']} != ws {n['ws_cost_makespan']}")
        # amortized synchronization (batched Put + half-run Steal): the
        # batched queue build must stay scatter-free (absolute — one
        # scatter per record is the regression this PR removed), and the
        # half-run probe reduction must not collapse vs the committed
        # reference.  .get guards let a fresh gate run against a
        # pre-halfrun committed BENCH.json.
        scat = n.get("put_scatter_ops") or {}
        _check(errs, f"{tag} batched-put scatter-free",
               all(v == 0 for v in scat.values() if isinstance(v, int)),
               f"queue-build lowering emits scatters: {scat}")
        if o.get("probe_reduction_halfrun") and n.get("probe_reduction_halfrun"):
            _check(errs, f"{tag} half-run probe reduction",
                   n["probe_reduction_halfrun"]
                   >= o["probe_reduction_halfrun"] * lo,
                   f"{n['probe_reduction_halfrun']} < "
                   f"{o['probe_reduction_halfrun']} * {lo}")
    s_new = {(r["mode"], r["path"]): r for r in fresh.get("serving", [])}
    s_old = {(r["mode"], r["path"]): r for r in committed.get("serving", [])}
    if s_old and not set(s_new) & set(s_old):
        errs.append(
            "serving: no (mode, path) cell in common between the fresh "
            f"dry-run {sorted(s_new)} and the committed reference "
            f"{sorted(s_old)} — refresh BENCH.json together with the trace"
        )
    for key in sorted(set(s_new) & set(s_old)):
        n, o = s_new[key], s_old[key]
        tag = f"serving {key[0]}/{key[1]}"
        # absolute gates first: correctness, not perf
        _check(errs, f"{tag} stream parity", n["streams_match"],
               "unified token streams no longer match the split-launch oracle")
        _check(errs, f"{tag} completions",
               n["completed"] == o["completed"] and n["rejected"] == o["rejected"],
               f"completed/rejected {n['completed']}/{n['rejected']} != "
               f"committed {o['completed']}/{o['rejected']} on the same "
               "seeded trace")
        # deterministic schedule shape: the seeded replay is single-threaded,
        # so step counts and utilization are exact — tolerance only covers
        # benign re-tuning landing with a refreshed BENCH.json
        _check(errs, f"{tag} steps",
               n["steps"] <= o["steps"] * hi,
               f"{n['steps']} > {o['steps']} * {hi}")
        _check(errs, f"{tag} slot utilization",
               n["slot_utilization"] >= o["slot_utilization"] * lo,
               f"{n['slot_utilization']} < {o['slot_utilization']} * {lo}")
    c_new, c_old = fresh.get("chaos"), committed.get("chaos")
    if c_new and c_old:
        # all absolute gates: the fault plans and traffic are seeded and the
        # decode greedy, so every column is deterministic — any drift is a
        # safety regression, not noise
        _check(errs, "chaos checker", c_new["checker_clean"],
               "a fault-injected scheduler cell violated the relaxed-"
               "semantics checker (lost task / double claim / mult bound)")
        _check(errs, "chaos storm coverage",
               c_new["max_mult"] >= max(2, c_old["max_mult"]),
               f"max multiplicity {c_new['max_mult']} < committed "
               f"{c_old['max_mult']} — the storm matrix stopped exercising "
               "real duplication")
        _check(errs, "chaos fault-off parity", c_new["fault_off_parity"],
               "fault_plan=None is no longer bit-identical to the omitted "
               "kwarg — chaos injection leaks into the fault-free lowering")
        _check(errs, "chaos replica crash",
               c_new["replica_crash"]["ok"]
               and c_new["replica_crash"]["streams_match"],
               f"{c_new['replica_crash']} — crash re-admission lost, "
               "duplicated, or diverged a stream")
        _check(errs, "chaos watchdog", c_new["watchdog"]["ok"],
               f"{c_new['watchdog']} — split fallback diverged from the "
               "clean unified streams")
        _check(errs, "chaos all cells", c_new["all_ok"],
               "at least one chaos cell failed its own gate")
    return errs


def trace_off_gate(committed: dict) -> list:
    """ISSUE-7 'tracing must be free when off': replay the headline dry-run
    cell of the ragged and moe benches with ``trace=False`` and hold the
    makespans to EXACT equality with the committed BENCH.json smoke values
    (which the bench mains produce with event tracing on).  Any drift means
    the trace=False lowering is no longer the pre-trace kernel."""
    errs = []
    r_old = (committed or {}).get("ragged_attention")
    if r_old:
        from benchmarks.ragged_attention import DRY_SHAPES, run_one

        row = run_one(*DRY_SHAPES, r_old["skew"], trace=False)
        assert "trace" not in row["ws"], "trace=False run must carry no rings"
        for name, key in (("ws", "ws_makespan"), ("static", "static_makespan")):
            _check(errs, f"trace-off ragged {name} makespan",
                   row[name]["makespan"] == r_old[key],
                   f"trace=False replay gives {row[name]['makespan']}, "
                   f"committed (traced) smoke says {r_old[key]} — "
                   "tracing is no longer free when off")
    m_old = (committed or {}).get("moe_dispatch")
    if m_old:
        from benchmarks.moe_dispatch import DRY_SHAPES, run_one

        row = run_one(*DRY_SHAPES, m_old["skew"], trace=False)
        assert "trace" not in row["ws"], "trace=False run must carry no rings"
        _check(errs, "trace-off moe ws makespan",
               row["ws"]["makespan"] == m_old["ws_makespan"],
               f"trace=False replay gives {row['ws']['makespan']}, "
               f"committed (traced) smoke says {m_old['ws_makespan']} — "
               "tracing is no longer free when off")
    return errs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tolerance", type=float, default=0.10)
    ap.add_argument("--no-run", action="store_true",
                    help="compare existing *.dryrun.json instead of re-running")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    status = 0
    if not args.no_run:
        from benchmarks import (
            chaos_storm,
            mesh_dispatch,
            moe_dispatch,
            ragged_attention,
            serving_traffic,
            steal_policy,
        )

        # each main asserts its own headline claim and rewrites *.dryrun.json
        status |= ragged_attention.main(["--dry-run"])
        status |= moe_dispatch.main(["--dry-run"])
        status |= steal_policy.main(["--dry-run"])
        status |= mesh_dispatch.main(["--dry-run"])  # re-execs on 8 devices
        status |= serving_traffic.main(["--dry-run"])
        status |= chaos_storm.main(["--dry-run"])

    if not BENCH_JSON.exists():
        print(f"[perf-smoke] {BENCH_JSON} missing — commit the trajectory first")
        return 1
    committed = json.loads(BENCH_JSON.read_text()).get("smoke", {})
    fresh = summarize(quick=True)
    errs = compare(fresh, committed, args.tolerance)
    errs += trace_off_gate(committed)
    for e in errs:
        print(f"[perf-smoke] REGRESSION {e}")
    if status:
        print("[perf-smoke] a bench headline claim failed (see above)")
    if errs or status:
        return 1
    print("[perf-smoke] OK — no regression vs committed BENCH.json smoke "
          f"trajectory (tolerance {args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving-under-load benchmark: replayed arrival traffic through the
work-stealing frontend, unified megakernel step vs split-launch step.

Workload: a seeded arrival trace — ``poisson`` (geometric inter-arrival
gaps, arrivals spread round-robin over the replicas) or ``bursty`` (whole
bursts land on replica 0 at once, so the other replicas only get work by
STEALING it) — replayed step-by-step through a
:class:`repro.serving.engine.WorkStealingFrontend`.  Each engine iteration
first submits the arrivals whose timestamp has come due, then runs one
round-robin admission+step pass over the replicas.

Both decode paths run the SAME trace:

* ``split``    — the escape-hatch path: jitted ``decode_step_ws`` per step
  plus a standalone jitted prefill per admission (2 launches per admitting
  step, per replica);
* ``unified``  — ``ContinuousBatcher(unified_step=True)``: ONE mixed-mode
  ``launch_ws_grid`` launch per engine step carrying the decode tiles AND
  the folded-in admission prefill (models.unified, DESIGN.md §5).

Reported per path: p50/p99/mean per-step latency (ms), tokens/sec,
mean slot utilization, steps, and the frontend's scheduling counters
(admitted / stolen / rejected / duplicates).  The correctness claims are
absolute gates (exit 1):

* every submitted rid completes exactly once (or is surfaced as rejected —
  over-capacity prompts are part of the trace on purpose);
* the two paths produce **identical token streams** on the seeded trace —
  the unified launch is bitwise vs the jitted split oracle, so greedy
  streams may not diverge;
* counter consistency: completed + duplicates == total admissions.

Writes BENCH_serving.json next to this file (``--dry-run``:
BENCH_serving.dryrun.json, tiny trace for CI; wall-clock numbers are
recorded but only the deterministic columns — steps, utilization, counters,
stream parity — are regression-gated by perf_smoke).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

# dry-run trace shape: (slots, capacity, n_requests, max_new) — small enough
# for interpret-mode CI, big enough that bursts overflow the slots and the
# second replica must steal
DRY_SHAPES = (2, 32, 5, 3)


def make_trace(mode: str, n_requests: int, capacity: int, n_replicas: int,
               seed: int = 0, max_new: int = 3):
    """Seeded arrival trace: list of (arrival_step, replica, rid, tokens,
    max_new), sorted by arrival_step.

    ``poisson``: geometric inter-arrival gaps, round-robin replica choice.
    ``bursty``: bursts of 3 requests, all submitted to replica 0 at the
    same step — the skewed load the stealing frontend exists for.

    One request per 5 is deliberately over-capacity (prompt == capacity):
    the engine must reject it and the frontend must surface the rejection
    instead of silently dropping or corrupting a slot.
    """
    rng = np.random.default_rng(seed)
    trace = []
    step = 0
    for rid in range(n_requests):
        if mode == "poisson":
            step += int(rng.geometric(0.5))
            replica = rid % n_replicas
        elif mode == "bursty":
            if rid % 3 == 0:
                step += 4
            replica = 0
        else:
            raise ValueError(f"unknown trace mode {mode!r}")
        if rid % 5 == 3:
            length = capacity  # over-capacity: must be rejected, not admitted
        else:
            length = int(rng.integers(2, min(10, capacity - max_new)))
        tokens = rng.integers(1, 200, size=length).astype(np.int32)
        trace.append((step, replica, rid, tokens, max_new))
    return trace


def replay(fe, trace, max_iters: int = 10_000) -> dict:
    """Inject arrivals as their steps come due; drive the frontend one
    round-robin iteration at a time until the trace and all queues drain."""
    from repro.serving.engine import Request

    ti = 0
    t0 = time.perf_counter()
    iters = 0
    for it in range(max_iters):
        while ti < len(trace) and trace[ti][0] <= it:
            step, replica, rid, tokens, max_new = trace[ti]
            fe.submit(replica, Request(rid, tokens, max_new=max_new))
            ti += 1
        worked = fe.run_iteration()
        iters = it + 1
        if not worked and ti >= len(trace):
            break
    wall_s = time.perf_counter() - t0
    completed = fe.completed
    tokens_out = sum(len(r.out) for r in completed.values())
    stats = fe.stats()
    # merge the per-batcher step metrics into one path-level summary
    lat = []
    util = []
    steps = 0
    for snap in stats["batchers"]:
        if not snap:
            continue
        steps += snap["steps"]
        if snap["latency_ms"]:
            lat.append(snap["latency_ms"])
        if snap["slot_utilization"] is not None:
            util.append((snap["slot_utilization"], snap["steps"]))
    lat_all = None
    if lat:
        lat_all = {
            "p50": float(np.median([d["p50"] for d in lat])),
            "p99": float(max(d["p99"] for d in lat)),
            "mean": float(np.mean([d["mean"] for d in lat])),
        }
    util_mean = (
        sum(u * n for u, n in util) / max(1, sum(n for _, n in util))
        if util else 0.0
    )
    return dict(
        iters=iters,
        steps=steps,
        wall_s=round(wall_s, 3),
        tokens_out=tokens_out,
        tokens_per_sec=round(tokens_out / max(wall_s, 1e-9), 2),
        latency_ms=lat_all,
        slot_utilization=round(util_mean, 4),
        completed=sorted(completed.keys()),
        rejected=sorted(fe.rejected.keys()),
        streams={int(rid): list(map(int, r.out)) for rid, r in completed.items()},
        counters=stats["totals"],
        per_replica=stats["per_replica"],
    )


def run_one(slots: int, capacity: int, n_requests: int, max_new: int,
            mode: str, unified: bool, *, arch: str = "llama3.2-3b",
            n_replicas: int = 2, seed: int = 0) -> dict:
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import ContinuousBatcher, WorkStealingFrontend

    cfg = get_config(arch, smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def make_batcher():
        # split path jits the decode step so the two paths compare the
        # compiled split-launch oracle against the (inherently compiled)
        # unified megakernel, not eager-mode rounding noise
        return ContinuousBatcher(
            params, cfg, slots=slots, capacity=capacity,
            unified_step=unified, jit_ws=not unified,
        )

    fe = WorkStealingFrontend(make_batcher, n_replicas=n_replicas)
    trace = make_trace(mode, n_requests, capacity, n_replicas,
                       seed=seed, max_new=max_new)
    row = replay(fe, trace)
    row.update(mode=mode, path="unified" if unified else "split",
               launches_per_step=1 if unified else "1 + prefill per admission")
    return row


def check_claims(rows_by_mode: dict) -> int:
    """Absolute gates over a {mode: {'split': row, 'unified': row}} grid."""
    status = 0
    for mode, pair in rows_by_mode.items():
        for path, row in pair.items():
            expect = row["_expect"]
            got = set(row["completed"]) | set(row["rejected"])
            dup = set(row["completed"]) & set(row["rejected"])
            if got != expect or dup:
                print(f"[serving] FAIL {mode}/{path}: completed+rejected "
                      f"{sorted(got)} != submitted {sorted(expect)} "
                      f"(overlap {sorted(dup)})")
                status = 1
            c = row["counters"]
            admitted_net = c["admitted"] - c["dup_completed"]
            if len(row["completed"]) != admitted_net:
                print(f"[serving] FAIL {mode}/{path}: {len(row['completed'])} "
                      f"completions vs admitted {c['admitted']} - dups "
                      f"{c['dup_completed']}")
                status = 1
        if pair["split"]["streams"] != pair["unified"]["streams"]:
            print(f"[serving] FAIL {mode}: unified token streams diverge "
                  "from the split-launch oracle")
            status = 1
        else:
            print(f"[serving] {mode}: unified == split on "
                  f"{len(pair['split']['streams'])} request streams")
    return status


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true", help="tiny trace for CI")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    here = pathlib.Path(__file__).parent
    if args.out is None:
        name = ("BENCH_serving.dryrun.json" if args.dry_run
                else "BENCH_serving.json")
        args.out = here / name
    if args.dry_run:
        slots, capacity, n_requests, max_new = DRY_SHAPES
        modes = ("bursty",)
    else:
        # interpret-mode launches are seconds each — the full grid stays
        # modest (both trace modes, deeper decode) rather than realistic-scale
        slots, capacity, n_requests, max_new = 2, 48, 10, 4
        modes = ("poisson", "bursty")

    rows_by_mode = {}
    rows = []
    for mode in modes:
        pair = {}
        for unified in (False, True):
            row = run_one(slots, capacity, n_requests, max_new, mode, unified)
            row["_expect"] = set(range(n_requests))
            pair["unified" if unified else "split"] = row
            print(
                f"serving,mode={mode},path={row['path']},steps={row['steps']},"
                f"tokens_per_sec={row['tokens_per_sec']},"
                f"util={row['slot_utilization']},"
                f"p50_ms={row['latency_ms']['p50'] if row['latency_ms'] else None},"
                f"p99_ms={row['latency_ms']['p99'] if row['latency_ms'] else None},"
                f"rejected={len(row['rejected'])},stolen={row['counters']['stolen']}"
            )
        rows_by_mode[mode] = pair
        rows.extend(pair.values())

    status = check_claims(rows_by_mode)
    for row in rows:
        row.pop("_expect", None)
    payload = dict(
        config=dict(slots=slots, capacity=capacity, n_requests=n_requests,
                    max_new=max_new, n_replicas=2, seed=0,
                    dry_run=args.dry_run),
        rows=rows,
        streams_match={m: p["split"]["streams"] == p["unified"]["streams"]
                       for m, p in rows_by_mode.items()},
    )
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"[serving] wrote {args.out}")
    return status


if __name__ == "__main__":
    sys.exit(main())

"""Seeded fault-storm replay: the chaos matrix through the SafetyChecker.

Every cell drives a deterministic :class:`repro.chaos.FaultPlan` —
program stalls, advisory corruption, kill-and-relaunch, head-rewind
storms, or a whole seeded combination — through the segmented injector
(`repro.chaos.inject.run_with_faults`) over the scheduler matrix

    fault kind × steal policy {cost, scan} × queue layout {moe, attention}

plus two serving cells on the real smoke engine:

* ``replica_crash`` — a :class:`ReplicaCrashPlan` kills a replica
  mid-run; the frontend re-admits its in-flight requests idempotently and
  the greedy streams must be IDENTICAL to the fault-free run's streams;
* ``watchdog`` — an :class:`EngineFaultPlan` poisons unified-step logits;
  the batcher degrades to the split path and the streams must match the
  clean unified run bitwise.

Reported per scheduler cell: checker verdict, max multiplicity, claim
counts, ring drops, segment structure, and output parity ("bitwise" exact
float replay for the single-source moe rows, "close"-or-better normalized
parity for attention).  Per serving cell: completion/rejection sets,
re-admission + degradation counts, stream parity.  The headline claims
are absolute gates (exit 1):

* every scheduler cell is checker-clean (no lost task, per-launch
  uniqueness, the stale-republish multiplicity bound, drain) with
  acceptable output parity;
* a ``fault_off_parity`` cell proves ``fault_plan=None``, an omitted
  kwarg and a zero ``FaultPlan()`` lower to bit-identical results —
  chaos injection is free when off;
* every serving request is completed-or-rejected exactly once, with no
  duplicate token emission, and faulted streams equal fault-free streams.

Writes BENCH_chaos.json next to this file (``--dry-run``:
BENCH_chaos.dryrun.json, the smaller matrix for CI; all columns are
deterministic, so perf_smoke gates them exactly).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

# dry-run matrix: (moe tokens, fault kinds, seeds per cell)
DRY_SHAPES = (8, ("kill_storm", "combined"), 1)


def _fault_matrix():
    """Named plan constructors: seed -> FaultPlan."""
    from repro.chaos import FaultPlan

    return {
        "stalls": lambda s: FaultPlan(seed=s, stalls=(3, 0, 2, 0)),
        "advisory": lambda s: FaultPlan(seed=s, advisory="random"),
        "kill_storm": lambda s: FaultPlan(seed=s, kills=(1,), storms=1,
                                          full_first_storm=True),
        "combined": lambda s: FaultPlan.from_seed(s),
    }


# ---------------------------------------------------------------------------
# scheduler cells
# ---------------------------------------------------------------------------


def _moe_problem(seed: int, n_tokens: int, n_programs: int):
    import jax
    import jax.numpy as jnp

    from repro.moe_ws.dispatch import route_to_tasks
    from repro.pallas_ws.queues import make_queue_state

    rng = np.random.RandomState(seed % 2**31)
    E, k, bt = 4, 1, 2
    d, f = 4, 8
    idx = np.stack([rng.choice(E, k, replace=False) for _ in range(n_tokens)])
    gates = rng.uniform(0.1, 1.0, (n_tokens, k)).astype(np.float32)
    gates /= gates.sum(1, keepdims=True)
    ks = jax.random.split(jax.random.PRNGKey(seed % 997), 4)
    x = jax.random.normal(ks[0], (n_tokens, d), jnp.float32)
    w = (
        jax.random.normal(ks[1], (E, d, f), jnp.float32) / 2.0,
        jax.random.normal(ks[2], (E, d, f), jnp.float32) / 2.0,
        jax.random.normal(ks[3], (E, f, d), jnp.float32) / 2.0,
    )
    tasks, routed = route_to_tasks(idx, gates, E, bt=bt)
    state = make_queue_state(tasks, n_programs, n_queues=E, partition="owner")
    return x, w, bt, tasks, routed, state


def run_scheduler_cell(layout: str, policy: str, fault: str, seed: int,
                       *, n_tokens: int = 10) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.chaos import SafetyChecker, run_with_faults
    from repro.moe_ws.dispatch import row_divisor
    from repro.moe_ws.expert_kernel import run_moe_schedule
    from repro.pallas_ws import (
        emit_flash_tasks,
        make_queue_state,
        multiplicity_divisor,
        ragged_attention_ref,
    )
    from repro.pallas_ws.kernel import default_rounds, run_ws_schedule
    from repro.pallas_ws.queues import copy_state

    plan = _fault_matrix()[fault](seed)
    t0 = time.perf_counter()
    if layout == "moe":
        P = 3
        x, w, bt, tasks, routed, state = _moe_problem(seed, n_tokens, P)
        rounds = default_rounds(state, steal=True)
        oracle = run_moe_schedule(
            copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
            steal_policy=policy, rounds=rounds,
        )

        def launch(state, *, rounds, out, mult, fault_plan):
            return run_moe_schedule(
                state, x, routed.tok_idx, *w, bt=bt, steal=True,
                steal_policy=policy, rounds=rounds, out=out,
                mult=None if mult is None else jnp.asarray(mult),
                trace=True, fault_plan=fault_plan,
            )

        chaos = run_with_faults(state, launch, plan, rounds=rounds)
        report = SafetyChecker().check(
            chaos, n_tasks=state.n_tasks,
            oracle_accumulated=np.asarray(oracle.out),
            row_mult=row_divisor(tasks, chaos.res.mult, routed.n_rows),
        )
        parity_ok = report.normalized_parity == "bitwise"
    else:  # attention
        lengths = np.array([32, 8, 8, 16])
        H, bq, bk = 2, 8, 8
        B, S = len(lengths), int(max(lengths))
        ks = jax.random.split(jax.random.PRNGKey(seed % 997), 3)
        q = jax.random.normal(ks[0], (B, H, S, 8))
        k = jax.random.normal(ks[1], (B, H, S, 8))
        v = jax.random.normal(ks[2], (B, H, S, 8))
        tasks = emit_flash_tasks(lengths, H, bq, bk, causal=True)
        state = make_queue_state(tasks, n_programs=4)
        rounds = default_rounds(state, steal=True)

        def launch(state, *, rounds, out, mult, fault_plan):
            return run_ws_schedule(
                state, q, k, v, causal=True, bq=bq, bk=bk, steal=True,
                steal_policy=policy, rounds=rounds, out=out,
                mult=None if mult is None else jnp.asarray(mult),
                trace=True, fault_plan=fault_plan,
            )

        chaos = run_with_faults(state, launch, plan, rounds=rounds)
        div = multiplicity_divisor(tasks, chaos.res.mult, (B, H, S))
        normalized = np.asarray(chaos.res.out) / np.asarray(div)[..., None]
        report = SafetyChecker().check(
            chaos, n_tasks=state.n_tasks,
            normalized=normalized,
            oracle_normalized=np.asarray(
                ragged_attention_ref(q, k, v, lengths)),
            rtol=1e-5, atol=1e-5,
        )
        parity_ok = report.normalized_parity in ("bitwise", "close")

    return dict(
        section="scheduler",
        layout=layout, policy=policy, fault=fault, seed=seed,
        ok=bool(report.ok and parity_ok),
        checker_ok=bool(report.ok),
        max_mult=report.max_mult,
        n_claims=report.n_claims,
        n_tasks=report.n_tasks,
        dropped=report.dropped,
        parity=report.normalized_parity,
        segments=report.stats["segments"],
        violations=[str(v) for v in report.violations],
        wall_s=round(time.perf_counter() - t0, 3),
    )


def run_fault_off_parity(seed: int = 7, n_tokens: int = 10) -> dict:
    """fault_plan omitted vs None vs FaultPlan(): bitwise on every field."""
    from repro.chaos import FaultPlan
    from repro.moe_ws.expert_kernel import run_moe_schedule
    from repro.pallas_ws.kernel import default_rounds
    from repro.pallas_ws.queues import copy_state

    fields = ("out", "mult", "head", "local_head", "taken", "remaining",
              "clock", "work", "steals", "scanned")
    x, w, bt, tasks, routed, state = _moe_problem(seed, n_tokens, 3)
    rounds = default_rounds(state, steal=True)

    def run(**kw):
        return run_moe_schedule(
            copy_state(state), x, routed.tok_idx, *w, bt=bt, steal=True,
            rounds=rounds, **kw,
        )

    base = run()
    ok = True
    for res in (run(fault_plan=None), run(fault_plan=FaultPlan())):
        for f in fields:
            if not np.array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(res, f))):
                ok = False
    return dict(section="parity", cell="fault_off_parity", seed=seed,
                ok=ok, fields=list(fields))


# ---------------------------------------------------------------------------
# serving cells (real smoke engine)
# ---------------------------------------------------------------------------


def _serving_streams(completed) -> dict:
    return {int(rid): list(map(int, r.out)) for rid, r in completed.items()}


def run_replica_crash_cell(*, crash_iter: int = 1, n_requests: int = 4,
                           max_new: int = 5) -> dict:
    import jax

    from repro.chaos import ReplicaCrashPlan
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import (
        ContinuousBatcher,
        Request,
        WorkStealingFrontend,
    )

    cfg = get_config("llama3.2-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = {rid: rng.integers(1, 200, size=int(rng.integers(2, 6)))
               .astype(np.int32) for rid in range(n_requests)}

    def one_run(crash_plan):
        fe = WorkStealingFrontend(
            lambda: ContinuousBatcher(params, cfg, slots=2, capacity=16),
            n_replicas=2, crash_plan=crash_plan,
        )
        for rid, p in prompts.items():
            fe.submit(rid % 2, Request(rid, p, max_new=max_new))
        completed = fe.run(max_iters=300)
        return fe, completed

    t0 = time.perf_counter()
    fe0, clean = one_run(None)
    fe1, faulted = one_run(ReplicaCrashPlan({0: crash_iter}))
    s_clean, s_faulted = _serving_streams(clean), _serving_streams(faulted)
    exactly_once = (
        set(faulted) | set(fe1.rejected) == set(prompts)
        and not (set(faulted) & set(fe1.rejected))
    )
    # readmitted >= 1 keeps the cell honest: the crash must actually land
    # on in-flight decodes, not an already-drained replica
    return dict(
        section="serving", cell="replica_crash",
        crash_iter=crash_iter,
        ok=bool(exactly_once and s_clean == s_faulted
                and fe1.counters["crashed"] == 1
                and fe1.counters["readmitted"] >= 1
                and fe1.counters["dup_completed"] == 0),
        exactly_once=bool(exactly_once),
        streams_match=bool(s_clean == s_faulted),
        completed=sorted(faulted), rejected=sorted(fe1.rejected),
        counters=fe1.stats()["totals"],
        readmitted=fe1.counters["readmitted"],
        crashed=fe1.counters["crashed"],
        wall_s=round(time.perf_counter() - t0, 3),
    )


def run_watchdog_cell(*, poison_steps=(0, 2), max_new: int = 3) -> dict:
    import jax

    from repro.chaos import EngineFaultPlan
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import ContinuousBatcher, Request

    cfg = get_config("llama3.2-3b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.array([5, 6, 7, 8], np.int32), np.array([9, 8, 7], np.int32)]

    def one_run(fp):
        b = ContinuousBatcher(params, cfg, slots=2, capacity=32,
                              unified_step=True, fault_plan=fp)
        for rid, p in enumerate(prompts):
            assert b.admit(Request(rid, p, max_new=max_new))
        done = []
        for _ in range(24):
            done += b.step()
            if not b.n_live:
                break
        return b, {r.rid: list(map(int, r.out)) for r in done}

    t0 = time.perf_counter()
    b0, clean = one_run(None)
    b1, faulted = one_run(EngineFaultPlan(poison_steps=tuple(poison_steps)))
    degr = [d["kind"] for d in b1.degradations]
    return dict(
        section="serving", cell="watchdog",
        poison_steps=list(poison_steps),
        ok=bool(clean == faulted and degr
                and all(k == "non-finite" for k in degr)
                and not b0.degradations),
        streams_match=bool(clean == faulted),
        degradations=b1.degradations,
        degradation_counts=b1.stats()["degradations"],
        wall_s=round(time.perf_counter() - t0, 3),
    )


# ---------------------------------------------------------------------------
# gates + entry point
# ---------------------------------------------------------------------------


def check_claims(rows) -> int:
    status = 0
    for r in rows:
        if r["ok"]:
            continue
        status = 1
        tag = "/".join(str(r.get(k)) for k in ("section", "layout", "policy",
                                               "fault", "cell", "seed")
                       if r.get(k) is not None)
        print(f"[chaos] FAIL {tag}: "
              f"violations={r.get('violations')} parity={r.get('parity')} "
              f"streams_match={r.get('streams_match')}")
    sched = [r for r in rows if r["section"] == "scheduler"]
    if sched:
        mm = max(r["max_mult"] for r in sched)
        if not any(r["max_mult"] >= 2 for r in sched):
            print("[chaos] FAIL: no scheduler cell exercised multiplicity "
                  "(max_mult < 2 everywhere) — the storm matrix is vacuous")
            status = 1
        print(f"[chaos] scheduler: {len(sched)} cells checker-clean, "
              f"max multiplicity {mm}")
    return status


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="smaller matrix for CI")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    here = pathlib.Path(__file__).parent
    if args.out is None:
        name = ("BENCH_chaos.dryrun.json" if args.dry_run
                else "BENCH_chaos.json")
        args.out = here / name
    if args.dry_run:
        n_tokens, faults, n_seeds = DRY_SHAPES
        policies, layouts = ("cost",), ("moe", "attention")
    else:
        n_tokens, faults, n_seeds = 10, tuple(_fault_matrix()), 2
        policies, layouts = ("cost", "scan"), ("moe", "attention")

    rows = []
    for layout in layouts:
        for policy in policies:
            for fault in faults:
                for seed in range(n_seeds):
                    row = run_scheduler_cell(layout, policy, fault, seed,
                                             n_tokens=n_tokens)
                    rows.append(row)
                    print(
                        f"chaos,layout={layout},policy={policy},fault={fault},"
                        f"seed={seed},ok={row['ok']},max_mult={row['max_mult']},"
                        f"claims={row['n_claims']},parity={row['parity']},"
                        f"segments={len(row['segments'])}"
                    )
    rows.append(run_fault_off_parity())
    print(f"chaos,cell=fault_off_parity,ok={rows[-1]['ok']}")
    rows.append(run_replica_crash_cell())
    r = rows[-1]
    print(f"chaos,cell=replica_crash,ok={r['ok']},readmitted={r['readmitted']},"
          f"streams_match={r['streams_match']}")
    rows.append(run_watchdog_cell())
    r = rows[-1]
    print(f"chaos,cell=watchdog,ok={r['ok']},"
          f"degradations={r['degradation_counts']},"
          f"streams_match={r['streams_match']}")

    status = check_claims(rows)
    payload = dict(
        config=dict(n_tokens=n_tokens, faults=list(faults),
                    policies=list(policies), layouts=list(layouts),
                    n_seeds=n_seeds, dry_run=args.dry_run),
        rows=rows,
        all_ok=all(r["ok"] for r in rows),
    )
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"[chaos] wrote {args.out}")
    return status


if __name__ == "__main__":
    sys.exit(main())

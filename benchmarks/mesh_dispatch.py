"""Mesh dispatch benchmark: cross-device WS vs per-device-static sharding.

Workload: top-k routing over E experts sharded round-robin-free (contiguous
blocks) across D forced host devices, with the same hot-set router skew as
``moe_dispatch.py`` — hot experts concentrate on few devices, so static
expert-parallel sharding strands every other device idle while the hot
shard grinds.  Two schedules over identical routed pairs:

* **per-device-static** (``steal=False``): each device drains only its own
  expert queues (intra-device WS still on), no advisory exchange, no
  remote steals — classic expert parallelism.  Makespan = max over devices
  of the local drain clock.
* **mesh-ws** (``steal=True``): the two-level hierarchy — balanced local
  drain, coalesced advisory exchange, replicated steal plan, remote
  segment execution, psum delivery.  Makespan = max over devices of
  ``phase1 + max(phase2_own, phase2_steal)`` (the phases are separated by
  the collective barrier).

Makespans are device-clock telemetry in tile-slot units (the shared cost
model of every scheduler bench here).  Collective traffic is reported two
ways per schedule: ``measured`` — all-reduce/collective-permute bytes
counted from the compiled HLO by ``launch.hlo_analysis.analyze`` (loop trip
counts included) — and ``analytic`` — the payload accounting of
``mesh_ws.advisory.exchange_payload_bytes``.

Writes BENCH_mesh.json next to this file (``--dry-run`` →
BENCH_mesh.dryrun.json for the CI smoke; rows are deterministic, so
``perf_smoke.py`` replays them exactly).  Exit status 1 when the headline
claim fails: at skew >= 4 mesh-ws must beat the static makespan, and every
row must match the no-drop oracle to float32 accumulation order
(``repro.mesh_ws.selfcheck.ORACLE_RTOL``/``ORACLE_ATOL``).

Needs D devices.  On a CPU backend (``JAX_PLATFORMS=cpu``) it re-execs
itself with ``XLA_FLAGS=--xla_force_host_platform_device_count=D``, decided
before it touches JAX; elsewhere it runs on the process's own devices.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np


def run_one(T, d, f, E, D, k, P, bt, skew, seed=0, trace_sink=None):
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_expert_mesh
    from repro.mesh_ws import (
        exchange_payload_bytes,
        expert_ffn_mesh_ws,
        mesh_wstrace,
    )
    from repro.mesh_ws.selfcheck import ORACLE_ATOL, ORACLE_RTOL
    from repro.moe_ws.layer import expert_ffn_nodrop_ref

    from benchmarks.moe_dispatch import make_skewed_routing

    idx, gates = make_skewed_routing(T, E, k, skew, seed)
    loads = np.bincount(idx.reshape(-1), minlength=E)
    dev_loads = loads.reshape(D, E // D).sum(axis=1)

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, f), jnp.float32) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (E, d, f), jnp.float32) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (E, f, d), jnp.float32) / np.sqrt(f)
    ref = np.asarray(expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd))

    mesh = make_expert_mesh(E, D)
    row = dict(
        T=T, d=d, f=f, E=E, D=D, k=k, n_programs=P, bt=bt, skew=skew,
        routed=int(T * k), max_dev_load=int(dev_loads.max()),
        mean_dev_load=float(dev_loads.mean()),
    )
    hlo_bytes = {}
    for name, steal in (("static", False), ("mesh_ws", True)):
        fn = lambda *a: expert_ffn_mesh_ws(  # noqa: E731
            *a, mesh=mesh, bt=bt, n_programs=P, steal=steal,
            return_telemetry=True,
        )
        args = (idx, gates, x, wg, wu, wd)
        t0 = time.perf_counter()
        y, tele = fn(*args)
        y, tele = np.asarray(y), np.asarray(tele)
        dt = time.perf_counter() - t0
        if steal:
            per_dev = tele[:, 0] + np.maximum(tele[:, 1], tele[:, 2])
            tele_ws = tele
        else:
            per_dev = tele[:, 0]
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        hlo_bytes[name] = analyze(hlo)["collective_bytes"]
        row[name] = dict(
            makespan=int(per_dev.max()),
            phase1_max=int(tele[:, 0].max()),
            devices_stole=int(tele[:, 5].sum()),
            tiles_stolen=int(tele[:, 6].sum()),
            max_abs_err=float(np.abs(y - ref).max()),
            oracle_close=bool(np.allclose(y, ref, rtol=ORACLE_RTOL,
                                          atol=ORACLE_ATOL)),
            wall_s=round(dt, 3),
        )
    El = E // D
    pool_tiles = -(-T * k // bt) + El + 1
    row["collective_bytes"] = dict(
        measured_mesh_ws=hlo_bytes["mesh_ws"],
        measured_static=hlo_bytes["static"],
        analytic_mesh_ws=exchange_payload_bytes(
            n_devices=D, pool_tiles=pool_tiles, n_local=El,
            n_rows=pool_tiles * bt, n_routed=T * k, d=d, f=f,
        ),
    )
    row["speedup_vs_static"] = row["static"]["makespan"] / max(
        1, row["mesh_ws"]["makespan"]
    )
    # per-phase trace columns + the Perfetto-exportable phase timeline
    tr = mesh_wstrace(
        tele_ws,
        collective_bytes=row["collective_bytes"]["analytic_mesh_ws"],
    )
    row["mesh_ws"]["trace"] = dict(
        phase2_own_max=int(tele_ws[:, 1].max()),
        phase2_steal_max=int(tele_ws[:, 2].max()),
        advisory_total=int(tele_ws[:, 3].sum()),
        collective_bytes=row["collective_bytes"]["analytic_mesh_ws"],
    )
    if trace_sink is not None:
        trace_sink["mesh_ws"] = tr
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true", help="tiny shapes for CI smoke")
    ap.add_argument("--skews", default="1,4,16")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--trace", default=None, metavar="TRACE_JSON",
                    help="write a Perfetto phase timeline of the "
                         "highest-skew mesh-ws run")
    args = ap.parse_args(argv)
    if args.out is None:
        name = "BENCH_mesh.dryrun.json" if args.dry_run else "BENCH_mesh.json"
        args.out = str(pathlib.Path(__file__).parent / name)

    from repro.mesh_ws.selfcheck import forced_host_env, forced_host_reexec

    if forced_host_reexec(args.devices):
        # CPU backend: run on forced host devices in a child, decided
        # before this process touches JAX
        env = forced_host_env(args.devices)
        env.setdefault("PYTHONPATH", str(pathlib.Path(__file__).parent.parent / "src"))
        cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
               "--skews", args.skews, "--devices", str(args.devices),
               "--out", args.out]
        if args.trace:
            cmd += ["--trace", args.trace]
        if args.dry_run:
            cmd.append("--dry-run")
        return subprocess.run(cmd, env=env).returncode

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.dry_run:
        T, d, f, E, D, k, P, bt = 48, 8, 16, 16, args.devices, 2, 2, 4
    else:
        T, d, f, E, D, k, P, bt = 96, 16, 32, 32, args.devices, 2, 2, 4

    skews = [float(s) for s in args.skews.split(",")]
    rows = []
    traces = {}
    print("skew,static_makespan,mesh_makespan,speedup,devices_stole,"
          "tiles_stolen,collective_bytes,oracle_close")
    for skew in skews:
        sink = {}
        row = run_one(T, d, f, E, D, k, P, bt, skew, trace_sink=sink)
        if "mesh_ws" in sink:
            traces[skew] = sink["mesh_ws"]
        rows.append(row)
        print(
            f"{skew},{row['static']['makespan']},{row['mesh_ws']['makespan']},"
            f"{row['speedup_vs_static']:.2f},{row['mesh_ws']['devices_stole']},"
            f"{row['mesh_ws']['tiles_stolen']},"
            f"{row['collective_bytes']['measured_mesh_ws']},"
            f"{row['mesh_ws']['oracle_close']}"
        )

    payload = dict(
        bench="mesh_dispatch",
        config=dict(T=T, d=d, f=f, E=E, D=D, k=k, n_programs=P, bt=bt,
                    dry_run=args.dry_run),
        rows=rows,
    )
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"[mesh_dispatch] wrote {args.out}")

    if args.trace and traces:
        from repro.wstrace import write_perfetto

        write_perfetto(traces[max(traces)], args.trace)
        print(f"[mesh_dispatch] wrote Perfetto trace (skew={max(traces)}) to "
              f"{args.trace} — open at https://ui.perfetto.dev")

    # headline claims: cross-device stealing wins under skew, and the
    # dispatch matches the oracle to float32 accumulation order
    bad_exact = [
        r["skew"] for r in rows
        if not (r["mesh_ws"]["oracle_close"] and r["static"]["oracle_close"])
    ]
    if bad_exact:
        print(f"[mesh_dispatch] oracle exactness failed at skews {bad_exact}")
        return 1
    bad_speed = [
        r["skew"] for r in rows
        if r["skew"] >= 4 and r["speedup_vs_static"] <= 1.0
    ]
    if bad_speed:
        print(f"[mesh_dispatch] mesh-ws did not beat static at skews {bad_speed}")
        return 1
    return 0


if __name__ == "__main__":
    if __package__ is None:  # bare script: make `benchmarks.` importable
        sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
    sys.exit(main())

"""Self-check of the mesh dispatch: run seeded routings on N devices and
assert the shard_map output matches the single-device no-drop oracle to
float32 accumulation order (``ORACLE_RTOL``/``ORACLE_ATOL``), with
cross-device steals observed::

    python -m repro.mesh_ws.selfcheck --devices 8 --seeds 3

On a CPU backend (``JAX_PLATFORMS=cpu``) the N devices are forced host
devices: the process re-executes itself with
``--xla_force_host_platform_device_count=N`` in the child's environment,
deciding so before it touches JAX.  Anywhere else it runs in this one
process on the real devices — a parent that initialized an accelerator
would hold it, and a child could not get it.

The tier-1 conformance suite subprocess-runs this (so a 1-device pytest
session still exercises the real 8-device shard_map path), and
``examples/train_e2e.py --devices N`` reuses the routing generator for its
forward-parity demo.
"""

import argparse
import json
import os
import sys


# Mesh dispatch vs the no-drop oracle.  The oracle runs every expert's FFN
# over every token as batched einsums; the expert tiles compute each
# expert's rows with their own dot products and the mesh combine sums the
# top-k pairs itself.  The two agree to float32 accumulation order, not bit
# for bit (XLA picks the reduction order of each).
ORACLE_RTOL, ORACLE_ATOL = 1e-5, 1e-6


def forced_host_reexec(n_devices: int) -> bool:
    """Whether a main that needs ``n_devices`` must re-execute itself with
    forced host devices — decided from the environment alone, before JAX
    is touched: only on a CPU backend, and only when the forcing flag is
    not already in place (the child sees it)."""
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return False
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    return flag not in os.environ.get("XLA_FLAGS", "")


def forced_host_env(n_devices: int) -> dict:
    """The child's environment for :func:`forced_host_reexec`."""
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    return dict(os.environ, XLA_FLAGS=f"{flags} {flag}".strip())


def skewed_routing(rng, n_tokens: int, n_experts: int, top_k: int,
                   hot_frac: float = 0.75, hot_experts: int | None = None):
    """Seeded routing with a hot expert block (device 0's shard by
    default): ``hot_frac`` of tokens route entirely inside the hot block,
    the rest uniformly — the load shape cross-device stealing exists for."""
    import numpy as np

    if hot_experts is None:
        hot_experts = max(1, n_experts // 8)
    idx = np.zeros((n_tokens, top_k), np.int32)
    for t in range(n_tokens):
        pool = hot_experts if t < int(n_tokens * hot_frac) else n_experts
        idx[t] = rng.choice(pool, size=top_k, replace=False)
    gates = rng.random((n_tokens, top_k), dtype=np.float32)
    gates = gates / gates.sum(1, keepdims=True)
    return idx, gates


def run_checks(n_devices: int, seeds: int, *, n_tokens: int = 24,
               n_experts: int = 16, top_k: int = 2, d: int = 8, f: int = 16,
               bt: int = 4, n_programs: int = 2):
    import numpy as np

    from repro.launch.mesh import make_expert_mesh
    from repro.mesh_ws import expert_ffn_mesh_ws
    from repro.moe_ws.layer import expert_ffn_nodrop_ref

    mesh = make_expert_mesh(n_experts, n_devices)
    rows = []
    for seed in range(seeds):
        rng = np.random.default_rng(seed)
        idx, gates = skewed_routing(rng, n_tokens, n_experts, top_k)
        x = rng.standard_normal((n_tokens, d), dtype=np.float32)
        wg = 0.1 * rng.standard_normal((n_experts, d, f), dtype=np.float32)
        wu = 0.1 * rng.standard_normal((n_experts, d, f), dtype=np.float32)
        wd = 0.1 * rng.standard_normal((n_experts, f, d), dtype=np.float32)
        y, tele = expert_ffn_mesh_ws(
            idx, gates, x, wg, wu, wd, mesh=mesh, bt=bt,
            n_programs=n_programs, return_telemetry=True,
        )
        ref = expert_ffn_nodrop_ref(idx, gates, x, wg, wu, wd)
        y, ref, tele = np.asarray(y), np.asarray(ref), np.asarray(tele)
        rows.append({
            "seed": seed,
            "close": bool(np.allclose(y, ref, rtol=ORACLE_RTOL,
                                      atol=ORACLE_ATOL)),
            "max_abs_err": float(np.abs(y - ref).max()),
            "devices_stole": int(tele[:, 5].sum()),
            "tiles_stolen": int(tele[:, 6].sum()),
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--seeds", type=int, default=3)
    args = ap.parse_args(argv)

    if forced_host_reexec(args.devices):
        import subprocess

        return subprocess.run(
            [sys.executable, "-m", "repro.mesh_ws.selfcheck",
             "--devices", str(args.devices), "--seeds", str(args.seeds)],
            env=forced_host_env(args.devices),
        ).returncode

    import jax

    if len(jax.devices()) < args.devices:
        print(f"FAIL: {args.devices} devices needed, {len(jax.devices())} "
              "found (JAX_PLATFORMS=cpu forces host devices)", file=sys.stderr)
        return 1

    rows = run_checks(args.devices, args.seeds)
    ok = all(r["close"] for r in rows)
    stole = any(r["devices_stole"] for r in rows)
    print(json.dumps({"devices": args.devices, "ok": ok,
                      "any_steals": stole, "rows": rows}, indent=2))
    if not ok:
        print("FAIL: mesh dispatch diverged from the no-drop oracle",
              file=sys.stderr)
        return 1
    if not stole:
        print("FAIL: no seed exercised a cross-device steal", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

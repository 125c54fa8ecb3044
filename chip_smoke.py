#!/usr/bin/env python3
"""Chip check: serve llama3.2-3b at full width on one TPU through the
work-stealing decode megakernel, and check what comes out.

    python chip_smoke.py                # one chip: the serving path
    python chip_smoke.py --four-chips   # four chips: mesh-ws expert dispatch

Default run (one chip) — the main serving path, through its own entry
(``repro.launch.serve``): llama3.2-3b at its published widths (28
layers, d 3072, 24/8 heads, d_ff 8192, vocab 128256, bf16) with random
weights from ``--seed``, two WorkStealingFrontend replicas of four slots and
a 2048-token cache each, 16 requests with prompts of 128, 512 or 1024
tokens and 32 new tokens each.  Every engine step is the jitted WS decode
step, whose attention runs on the compiled megakernel.  Checks:

1. every request completes exactly once, with all 32 tokens;
2. at one early and one later engine step, the WS step's logits match the
   dense jitted ``decode_step`` on the same bf16 parameters, caches and
   tokens (that step does not go through the scheduler);
3. the decode megakernel alone, at the served shapes and ragged lengths,
   matches ``ragged_decode_ref`` computed in float32.

``--four-chips`` runs only ``expert_ffn_mesh_ws`` over a 4-device
``("model",)`` mesh with deepseek-v2-236b's routing shape (160 experts,
top-6) at the expert width the single-chip compile tests use, and compares
it with ``expert_ffn_nodrop_ref`` and with the per-device-static dispatch.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
when a check fails, or when a request is lost or duplicated.  Its last line
is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# -- serving phase shape ------------------------------------------------------
ARCH = "llama3.2-3b"
REPLICAS, SLOTS, CAPACITY = 2, 4, 2048
REQUESTS, MAX_NEW = 16, 32
PROMPT_LENS = (128, 512, 1024)
# engine decode steps (counted over both replicas) checked against the dense
# step: the second step and one late in the run
CHECK_STEPS = (1, 48)
# Logits tolerance, relative to the dense step's largest |logit|.  Both
# steps use the same bf16 weights and matmuls; they differ in the attention
# core.  The dense step rounds scores, probabilities and the attention
# output to bf16 (2^-8 relative), while the WS tile keeps them in float32
# and rounds only its output.  That difference enters all 28 residual
# layers and compounds, so a few percent of the logit scale is the honest
# bound; a wrong tile (a missed or doubled kv block, a wrong head) moves
# logits by their full scale.
LOGIT_RTOL = 0.1
# Kernel-alone tolerance, absolute, on attention outputs of unit-variance
# data.  Kernel and reference see the same bf16 values in float32; they
# differ in accumulation order (online-softmax blocks of ``decode_block``
# positions against one softmax) and in the MXU's float32 precision: at
# default precision a float32 product may be taken in one bf16 pass, 2^-8
# relative per term.
KERNEL_ATOL = 2e-2
KERNEL_LENGTHS = (2048, 1057, 129, 1)

# -- four-chip phase shape ----------------------------------------------------
MESH_DEVICES = 4
N_EXPERTS, TOP_K = 160, 6          # deepseek-v2-236b routing
D_EXPERT, F_EXPERT = 512, 1536     # d_model cut from 5120; d_ff as published
MESH_TOKENS, MESH_BT = 512, 8
# Mesh tolerance, relative to the oracle's largest |y|: the oracle runs at
# highest matmul precision; the expert tiles multiply float32 on the MXU
# (see KERNEL_ATOL) and the combine sums the top-6 pairs in its own order.
MESH_RTOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip-smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileLog:
    """Backend compile durations per program name, from JAX's monitoring
    events (``/jax/core/compile/backend_compile_duration``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = defaultdict(float)
        self.count = defaultdict(int)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            name = kw.get("fun_name", "?")
            self.seconds[name] += duration
            self.count[name] += 1

    def report(self) -> None:
        total = sum(self.count.values())
        log(f"compilations: {total}, {sum(self.seconds.values()):.1f} s")
        for name, s in sorted(self.seconds.items(), key=lambda kv: -kv[1]):
            if s >= 1.0:
                log(f"  compile {name}: {self.count[name]} x, {s:.1f} s")


def check_device(want: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} device_kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        fail(f"no TPU: JAX's default device is {d.platform!r}")
    if len(devs) < want:
        fail(f"{want} chips needed, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def serving_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve as serve_mod
    from repro.models import decode_step

    cfg = get_config(ARCH)

    def dense_decode_step(p, c, t, pos):
        return decode_step(p, cfg, c, t, pos)

    dense = jax.jit(dense_decode_step)
    steps = [0]
    checked = {}

    def check_steps(batcher):
        """Compare the batcher's WS decode step with the dense step at the
        CHECK_STEPS engine steps (counted over all replicas)."""
        ws_decode = batcher._decode

        def decode(params, caches, tokens, pos):
            k = steps[0]
            steps[0] += 1
            logits, new = ws_decode(params, caches, tokens, pos)
            if k in CHECK_STEPS:
                ref, _ = dense(params, caches, tokens, pos)
                live = np.asarray(pos) > 0
                a = np.asarray(logits)[live]
                b = np.asarray(ref)[live]
                scale = float(np.abs(b).max())
                err = float(np.abs(a - b).max())
                rms = float(np.sqrt(((a - b) ** 2).mean() / (b ** 2).mean()))
                agree = float((a.argmax(-1) == b.argmax(-1)).mean())
                checked[k] = err / scale
                log(f"step {k}: live slots {int(live.sum())}, max|ws-dense| "
                    f"{err:.4g} of max|dense| {scale:.4g} (rel {err / scale:.3g},"
                    f" rms rel {rms:.3g}, argmax agree {agree:.3f})")
            return logits, new

        batcher._decode = decode

    args = serve_mod.parse_args([
        "--arch", ARCH, "--full", "--requests", str(REQUESTS),
        "--replicas", str(REPLICAS), "--slots", str(SLOTS),
        "--capacity", str(CAPACITY),
        "--prompt-lens", ",".join(str(n) for n in PROMPT_LENS),
        "--max-new", str(MAX_NEW), "--seed", str(seed),
    ])
    fe, _ = serve_mod.build_frontend(args)
    for batcher in fe.batchers:
        check_steps(batcher)
    completed, dt = serve_mod.run_requests(fe, cfg, args)
    totals = fe.stats()["totals"]
    log(f"served {len(completed)}/{REQUESTS} requests in {dt:.1f} s "
        f"(compilation included); engine decode steps {steps[0]}; "
        f"frontend {totals}")
    if not serve_mod.served_once(fe, completed, args):
        fail(f"requests lost or duplicated: completed {sorted(completed)}, "
             f"rejected {sorted(fe.rejected)}, totals {totals}")
    missing = [k for k in CHECK_STEPS if k not in checked]
    if missing:
        fail(f"decode steps {missing} never ran ({steps[0]} steps)")
    bad = {k: v for k, v in checked.items() if not v <= LOGIT_RTOL}
    if bad:
        fail(f"WS logits off the dense step beyond {LOGIT_RTOL}: {bad}")
    log(f"logits check passed at rtol {LOGIT_RTOL}")

    # the decode megakernel alone, at the served shapes
    from repro.pallas_ws.ragged import ragged_decode_attention, ragged_decode_ref

    H, Hkv = cfg.eff_heads
    hd = cfg.hd
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(ks[0], (SLOTS, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (SLOTS, Hkv, CAPACITY, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (SLOTS, Hkv, CAPACITY, hd), jnp.bfloat16)
    q = q.astype(jnp.bfloat16).astype(jnp.float32)  # bf16 values, f32 out
    lengths = jnp.asarray(KERNEL_LENGTHS, jnp.int32)
    out = jax.jit(ragged_decode_attention)(q, k, v, lengths)
    with jax.default_matmul_precision("highest"):
        ref = ragged_decode_ref(q, k.astype(jnp.float32),
                                v.astype(jnp.float32), np.asarray(lengths))
    err = float(jnp.abs(out - ref).max())
    log(f"kernel alone: lengths {KERNEL_LENGTHS}, max|kernel-ref| {err:.3g} "
        f"(atol {KERNEL_ATOL})")
    if not err <= KERNEL_ATOL:
        fail(f"decode megakernel off ragged_decode_ref: {err}")


def mesh_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_expert_mesh
    from repro.mesh_ws import MESH_AXIS, expert_ffn_mesh_ws
    from repro.moe_ws.layer import expert_ffn_nodrop_ref

    mesh = make_expert_mesh(N_EXPERTS, n_devices=MESH_DEVICES)
    rng = np.random.RandomState(seed)
    # skewed routing: a few hot experts, so some chips run out of work
    # and steal from the loaded ones
    p = rng.zipf(1.5, size=N_EXPERTS * 8) % N_EXPERTS
    hot = np.bincount(p, minlength=N_EXPERTS).astype(np.float64) + 1.0
    hot /= hot.sum()
    idx = np.stack([rng.choice(N_EXPERTS, TOP_K, replace=False, p=hot)
                    for _ in range(MESH_TOKENS)]).astype(np.int32)
    gates = rng.uniform(0.1, 1.0, (MESH_TOKENS, TOP_K)).astype(np.float32)
    gates /= gates.sum(1, keepdims=True)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    sharded = NamedSharding(mesh, P(MESH_AXIS))
    scale = D_EXPERT ** -0.5

    def weight(key, shape):
        return jax.jit(
            lambda k: jax.random.normal(k, shape, jnp.float32) * scale,
            out_shardings=sharded)(key)

    x = jax.random.normal(ks[0], (MESH_TOKENS, D_EXPERT), jnp.float32)
    wg = weight(ks[1], (N_EXPERTS, D_EXPERT, F_EXPERT))
    wu = weight(ks[2], (N_EXPERTS, D_EXPERT, F_EXPERT))
    wd = weight(ks[3], (N_EXPERTS, F_EXPERT, D_EXPERT))
    log(f"mesh {dict(mesh.shape)}: {N_EXPERTS} experts top-{TOP_K}, "
        f"{MESH_TOKENS} tokens, expert d {D_EXPERT} f {F_EXPERT}")

    def run(steal):
        def mesh_dispatch(*a):
            return expert_ffn_mesh_ws(*a, mesh=mesh, bt=MESH_BT, steal=steal,
                                      return_telemetry=True)

        fn = jax.jit(mesh_dispatch)
        t0 = time.perf_counter()
        y, tele = fn(idx, gates, x, wg, wu, wd)
        y = np.asarray(y)
        log(f"mesh-ws steal={steal}: first call {time.perf_counter() - t0:.1f}"
            f" s (compilation included)")
        return y, np.asarray(tele)

    y_ws, tele = run(True)
    y_static, _ = run(False)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(expert_ffn_nodrop_ref)(idx, gates, x, wg, wu, wd))
    ref_scale = float(np.abs(ref).max())
    err_ws = float(np.abs(y_ws - ref).max()) / ref_scale
    err_static = float(np.abs(y_static - ref).max()) / ref_scale
    err_pair = float(np.abs(y_ws - y_static).max()) / ref_scale
    log(f"devices that stole {int(tele[:, 5].sum())}, tiles stolen "
        f"{int(tele[:, 6].sum())}")
    log(f"rel err: mesh-ws vs oracle {err_ws:.3g}, static vs oracle "
        f"{err_static:.3g}, mesh-ws vs static {err_pair:.3g} "
        f"(rtol {MESH_RTOL}, max|oracle| {ref_scale:.4g})")
    if not all(np.isfinite(a).all() for a in (y_ws, y_static)):
        fail("non-finite mesh output")
    if not max(err_ws, err_static, err_pair) <= MESH_RTOL:
        fail("mesh dispatch off the no-drop oracle")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-ws phase over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    compiles = CompileLog()
    device = check_device(MESH_DEVICES if args.four_chips else 1)
    log(f"compilation cache: {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        mesh_phase(args.seed)
    else:
        serving_phase(args.seed)
    log(f"phase wall {time.perf_counter() - t0:.1f} s")
    compiles.report()
    log(f"peak_bytes_in_use {peak_bytes()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

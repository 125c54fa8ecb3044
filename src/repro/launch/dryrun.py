import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, cell_plan, get_config
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    attach,
    batch_specs,
    cache_specs,
    opt_state_shardings,
    params_specs,
)
from repro.launch.steps import (
    make_decode_step,
    make_optimizer,
    make_prefill_step,
    make_train_step,
    train_policy,
)
from repro.models.config import SHAPES
from repro.models.sharding import use_mesh

# the dry-run lowers for pod-sized meshes on forced host devices; main()
# sets the count before JAX's CPU backend first initializes
HOST_DEVICES = 512

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
ICI_BW = 50e9  # B/s / link

def memory_report(compiled) -> dict:
    """memory_analysis() when the backend provides it; else analytic
    per-device argument/output byte totals from the compiled avals."""
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for f in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            v = getattr(ma, f, None)
            if v is not None:
                out[f] = int(v)
    return out


def _per_device_bytes(sds_tree, n_devices: int) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(sds_tree):
        n = 1
        for d in leaf.shape:
            n *= d
        nb = n * jnp.dtype(leaf.dtype).itemsize
        sh = getattr(leaf, "sharding", None)
        if sh is not None and sh.spec is not None:
            try:
                nb = sh.shard_shape(leaf.shape)
                m = 1
                for d in nb:
                    m *= d
                nb = m * jnp.dtype(leaf.dtype).itemsize
            except Exception:
                nb = n * jnp.dtype(leaf.dtype).itemsize
        total += nb
    return total


def model_flops(cfg, shape) -> float:
    """6*N_active*D for the step's token throughput (fwd+bwd for train,
    2*N*D for fwd-only serve steps)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_lowered(cfg, shape, mesh, *, ws_mode=None, chunk=1024):
    """Lower the right step for this cell; returns (lowered, extras)."""
    pol = train_policy(cfg)
    fsdp = pol["fsdp"] if shape.kind == "train" else (pol["fsdp"] or False)
    with use_mesh(mesh, fsdp=bool(fsdp)):
        p_sds, p_sh = params_specs(cfg, mesh, fsdp=fsdp)
        if shape.kind == "train":
            opt = make_optimizer(cfg)
            opt_shapes = jax.eval_shape(opt.init, p_sds)
            o_sh = opt_state_shardings(opt_shapes, p_sh, mesh)
            o_sds = attach(opt_shapes, o_sh)
            state = {"params": p_sds, "opt": o_sds}
            batch = batch_specs(cfg, shape, mesh)
            if ws_mode is not None:
                n_w = mesh.devices.size // mesh.shape["model"]
                n_tasks = 2 * n_w
                rows = max(shape.global_batch // n_tasks, 1)
                tok = batch["tokens"]
                batch = dict(batch)
                batch["tokens"] = jax.ShapeDtypeStruct(
                    (n_tasks, rows, tok.shape[1]), tok.dtype,
                    sharding=jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec(("pod", "data") if "pod" in mesh.axis_names else "data")
                    ),
                )
                batch["tails"] = jax.ShapeDtypeStruct((n_w,), jnp.int32)
                # bounded rounds: tasks_per_worker(2) + slack(2) — a fixed
                # step-time budget, comparable across scheduler modes
                step = make_train_step(
                    cfg, opt, ws_mode=ws_mode, n_workers=n_w, chunk=chunk,
                    max_rounds=4,
                )
            else:
                step = make_train_step(cfg, opt, chunk=chunk)
            state_sh = {"params": p_sh, "opt": o_sh}
            # donate the old state: params/opt are updated in place
            lowered = jax.jit(
                step, out_shardings=(state_sh, None), donate_argnums=(0,)
            ).lower(state, batch)
        elif shape.kind == "prefill":
            batch = batch_specs(cfg, shape, mesh)
            _, c_sh = cache_specs(cfg, shape, mesh)
            step = make_prefill_step(cfg, chunk=chunk)
            lowered = jax.jit(step, out_shardings=(None, c_sh)).lower(p_sds, batch)
        else:  # decode
            batch = batch_specs(cfg, shape, mesh)
            c_sds, c_sh = cache_specs(cfg, shape, mesh)
            step = make_decode_step(cfg)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            # donate the KV/SSM caches: decode updates them in place
            lowered = jax.jit(
                step, out_shardings=(None, c_sh), donate_argnums=(1,)
            ).lower(p_sds, c_sds, batch["tokens"], pos)
        extras = {
            "fsdp": str(fsdp),
            "optimizer": pol["optimizer"] if shape.kind == "train" else None,
            "params_bytes_per_device": _per_device_bytes(p_sds, mesh.devices.size),
        }
        return lowered, extras


_SMOKE_SHAPES = {
    "train_4k": ("train", 64, 8),
    "prefill_32k": ("prefill", 256, 4),
    "decode_32k": ("decode", 256, 8),
    "long_500k": ("decode", 512, 2),
}


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, *, ws_mode=None, chunk=1024,
    smoke: bool = False, pad_heads: bool = False, tag: str = "",
):
    from repro.models.config import ShapeConfig

    cfg = get_config(arch, smoke=smoke)
    if pad_heads:
        cfg = cfg.replace(pad_heads=True)
    if tag == "bf16-reduce":
        cfg = cfg.replace(bf16_reduce=True)
    if smoke:
        kind, seq, gb = _SMOKE_SHAPES[shape_name]
        shape = ShapeConfig(shape_name, kind, seq, gb)
        chunk = min(chunk, 32)
    else:
        shape = SHAPES[shape_name]
    plan = cell_plan(cfg if not smoke else get_config(arch))[shape_name]
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": ("2x2x2" if multi_pod else "2x4") if smoke else ("2x16x16" if multi_pod else "16x16"),
        "plan": plan, "ws_mode": ws_mode, "smoke": smoke,
        "tag": tag, "pad_heads": pad_heads, "chunk": chunk,
    }
    if plan != "run":
        return rec
    if smoke:
        from repro.launch.mesh import make_host_mesh

        mesh = (
            make_host_mesh((2, 2, 2), ("pod", "data", "model"))
            if multi_pod
            else make_host_mesh((2, 4), ("data", "model"))
        )
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    t0 = time.time()
    lowered, extras = build_lowered(cfg, shape, mesh, ws_mode=ws_mode, chunk=chunk)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    rec.update(extras)

    mem = memory_report(compiled)
    print(f"[{arch} x {shape_name} x {rec['mesh']}] memory_analysis: {mem}")
    rec["memory"] = mem

    # XLA's cost_analysis counts while bodies once (scan => ~n_layers
    # undercount); keep it as reference, use the trip-aware HLO walk as
    # the roofline numerator.
    ca = compiled.cost_analysis() or {}
    rec["xla_cost_flops"] = float(ca.get("flops", 0.0))
    rec["xla_cost_bytes"] = float(ca.get("bytes accessed", 0.0))
    res = analyze(compiled.as_text())
    flops = res["flops"]
    bytes_accessed = res["mem_bytes"]
    rec["hlo_flops_per_device"] = flops
    rec["hlo_bytes_per_device"] = bytes_accessed
    print(
        f"  trip-aware: flops/device={flops:.3e} bytes/device={bytes_accessed:.3e} "
        f"(xla-once-through: {rec['xla_cost_flops']:.3e} / {rec['xla_cost_bytes']:.3e})"
    )
    per_kind, coll_bytes = res["per_kind"], res["collective_bytes"]
    rec["collectives"] = {k: v for k, v in per_kind.items() if v["count"]}
    rec["collective_bytes_per_device"] = coll_bytes

    # roofline terms (seconds); flops/bytes above are per-device post-SPMD
    rec["compute_s"] = flops / PEAK_FLOPS
    rec["memory_s"] = bytes_accessed / HBM_BW
    rec["collective_s"] = coll_bytes / ICI_BW
    terms = {k: rec[k] for k in ("compute_s", "memory_s", "collective_s")}
    rec["bottleneck"] = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    rec["model_flops_global"] = mf
    rec["useful_flops_ratio"] = mf / max(flops * n_chips, 1.0)
    print(
        f"  roofline: compute={rec['compute_s']:.4f}s memory={rec['memory_s']:.4f}s "
        f"collective={rec['collective_s']:.4f}s -> {rec['bottleneck']}; "
        f"useful_ratio={rec['useful_flops_ratio']:.3f}"
    )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ws-mode", default=None)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--smoke", action="store_true", help="reduced config + 8 fake devices")
    ap.add_argument("--pad-heads", action="store_true", help="TP head padding (§Perf)")
    ap.add_argument("--tag", default="", help="label for the JSONL record")
    ap.add_argument("--out", default=None, help="append-to JSONL path")
    args = ap.parse_args(argv)
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={HOST_DEVICES}"
    )

    rec = run_cell(
        args.arch, args.shape, args.multi_pod, ws_mode=args.ws_mode,
        chunk=args.chunk, smoke=args.smoke, pad_heads=args.pad_heads, tag=args.tag,
    )
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

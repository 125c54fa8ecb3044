"""Serving driver: continuous batching behind the work-stealing frontend.

Usage:
    python -m repro.launch.serve --arch llama3.2-3b --requests 12
    python -m repro.launch.serve --full --requests 16 --replicas 2 \\
        --slots 4 --capacity 2048 --prompt-lens 128,512,1024 --max-new 32

Without ``--full`` the architecture's smoke-size config runs (seconds on a
CPU, kernels interpreted); ``--full`` serves its published widths with
random weights made from ``--seed``.  Every engine step is the jitted WS
decode step: the slots' attention tiles run on the work-stealing
megakernel.  The run exits 1 unless every request completes exactly once
with all its tokens.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import ContinuousBatcher, Request, WorkStealingFrontend


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=list(ARCH_IDS))
    ap.add_argument("--full", action="store_true",
                    help="serve the published widths (default: smoke size)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--prompt-lens", default="3,5,8",
                    help="comma-separated prompt lengths drawn per request; "
                         "each distinct length compiles one prefill")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--no-steal", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build_frontend(args: argparse.Namespace):
    """The model (random weights from ``args.seed``) and a frontend of
    ``args.replicas`` batchers.  Returns ``(frontend, cfg)``."""
    cfg = get_config(args.arch, smoke=not args.full)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    fe = WorkStealingFrontend(
        lambda: ContinuousBatcher(params, cfg, slots=args.slots,
                                  capacity=args.capacity, jit_ws=True),
        n_replicas=args.replicas,
        steal=not args.no_steal,
    )
    return fe, cfg


def run_requests(fe, cfg, args: argparse.Namespace):
    """Submit ``args.requests`` requests with a skewed arrival (most land on
    replica 0, so idle replicas steal) and run the frontend until every
    queue drains.  Returns ``(completed, seconds)``."""
    lens = [int(n) for n in str(args.prompt_lens).split(",")]
    rng = np.random.RandomState(args.seed)
    t0 = time.perf_counter()
    for rid in range(args.requests):
        rep = 0 if rng.rand() < 0.8 else rng.randint(args.replicas)
        n = lens[rng.randint(len(lens))]
        prompt = rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
        fe.submit(rep, Request(rid, prompt, max_new=args.max_new))
    completed = fe.run()
    return completed, time.perf_counter() - t0


def served_once(fe, completed, args) -> bool:
    """Every request completed exactly once, with all its tokens."""
    return (
        sorted(completed) == list(range(args.requests))
        and not fe.rejected
        and fe.counters["dup_completed"] == 0
        and all(len(r.out) == args.max_new for r in completed.values())
    )


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    fe, cfg = build_frontend(args)
    completed, dt = run_requests(fe, cfg, args)
    ok = served_once(fe, completed, args)
    print(
        f"[serve] {len(completed)}/{args.requests} completed in {dt:.1f}s "
        f"(all={ok}); stats={fe.stats()}"
    )
    for rid in sorted(completed)[:4]:
        print(f"  req {rid}: out={completed[rid].out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that set a cell's output-check limit, on the chip.

    python bench/control.py --workload <cell> --seeds 101,102,... --seconds 10

For each seed, in one process: weights from the seed, the cell's traffic
served for a short window at the cell's own sizes, then over the same
sample of finished requests the harness's own verdict twice: on the served
tokens (the program: its widest gap is the limit's lower reading) and on
the tokens a float8 forward puts first at the same positions (the
control: its widest gap is the upper reading, and its verdict has to come
out not correct).  The benchmark's own runs never run the control.  One
JSON line per seed, then a summary.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = run.registry.load_benchmark()
    wl = run.registry.find_workload(spec, args.workload)
    conf = run.registry.load_config(wl["config"])
    mix = run.registry.load_traffic(wl["traffic"])
    run.enable_cache()
    device = run.check_device(wl["chips"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, wl, conf, mix, seed, args.seconds, False, device,
                           control=True)
        c, ctl = out["checks"], out["control"]
        row = {"seed": seed, "correct": out["correct"],
               "program_gap": c["logit_gap"]["value"],
               "control_correct": ctl["correct"],
               "control_gap": ctl["checks"]["logit_gap"]["value"],
               "tokens": c["tokens_checked"]["value"], "failed": out["failed"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    print(json.dumps({"workload": args.workload,
                      "lower_reading": max(r["program_gap"] for r in rows),
                      "upper_reading": min(r["control_gap"] for r in rows),
                      "program_correct_every_seed": all(r["correct"] for r in rows),
                      "control_incorrect_every_seed": not any(r["control_correct"]
                                                              for r in rows),
                      "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

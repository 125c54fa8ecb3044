"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a data file of its
own (``configs/<config>.json``, ``traffic/<traffic>.json``), and each
per-layer metric is a reader of its own (``metrics/<metric>.py``).  Adding
a cell, a configuration, a mix or a metric means adding files and entries:
nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((bench_dir / "configs" / f"{name}.json").read_text())


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((bench_dir / "traffic" / f"{name}.json").read_text())


def load_metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<name>.py``.  It returns the
    metric's value, or None where the run gave it nothing to read."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones with
    tracing off, the per-layer ones with it on.  A metric with a
    ``workloads`` key is reported only in those cells."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, whose keys
    are those of the model's published ``config.json``."""
    from repro.models.config import ModelConfig

    if conf.get("sliding_window"):
        raise ValueError(f"{conf['name']}: a sliding window is not served by the WS decode path")
    return ModelConfig(
        name=conf["name"],
        family="dense",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim"),
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"],
    )

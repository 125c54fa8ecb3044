"""Shared pieces of the benchmark's CPU tests (run with ``pytest bench``):
the benchmark's modules on the path, and a configuration and mixes small
enough for the CPU, where the program's kernels run interpreted."""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

TINY_CONF = {
    "name": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "sliding_window": None, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "logit_gap_limit": 0.08, "slots": 2,
}

TINY_OPEN = {
    "loop": "open", "rate_per_s": 4.0,
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 24,
               "buckets": [8, 24]},
    "output": {"dist": "uniform", "min": 2, "max": 6},
    "capacity": 32,
}

TINY_CLOSED = {
    "loop": "closed", "clients": 2,
    "prompt": {"dist": "choice", "values": [8, 16], "buckets": [8, 16]},
    "output": {"dist": "uniform", "min": 3, "max": 8},
    "capacity": 32,
}

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture
def tiny_spec():
    """A BENCHMARK.json with the tiny cells and the real metric groups."""
    import registry

    spec = dict(registry.load_benchmark())
    spec["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "open", "chips": 1, "why": "test"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "closed", "chips": 1, "why": "test"},
    ]
    spec["end_to_end"] = [dict(m, workloads=["tiny.open", "tiny.closed"])
                          for m in spec["end_to_end"]]
    return spec

"""Lookup by name: a cell, configuration, mix or per-layer metric that a
later change adds as files and entries is found without editing a file
that is there."""

import json
import shutil

import pytest

import registry


@pytest.fixture
def copy(tmp_path):
    """A copy of the benchmark (BENCHMARK.json and bench/) to add files to."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out", "tests"))
    shutil.copy(registry.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_every_cell_finds_its_files():
    spec = registry.load_benchmark()
    for wl in spec["workloads"]:
        conf = registry.load_config(wl["config"])
        assert conf["name"] == wl["config"]
        assert registry.model_config(conf).n_layers == conf["num_hidden_layers"]
        assert registry.load_traffic(wl["traffic"])["capacity"] > 0
    for m in spec["per_layer"]:
        assert callable(registry.load_metric_reader(m["name"]))
    names = {c["name"] for c in spec["configs"]}
    assert {wl["config"] for wl in spec["workloads"]} == names
    for c in spec["configs"]:
        assert (registry.ROOT / c["file"]).is_file()


def test_new_files_are_found_without_edits(copy):
    bench = copy / "bench"
    conf = json.loads((bench / "configs" / "mistral-7b-v0.3-l16.json").read_text())
    conf.update(name="new-model", num_hidden_layers=3)
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = {"loop": "closed", "clients": 2, "capacity": 512,
           "prompt": {"dist": "choice", "values": [64], "buckets": [64]},
           "output": {"dist": "fixed", "value": 8}}
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new.layer_ms.py").write_text("def read(ctx):\n    return 42.0\n")
    spec = registry.load_benchmark(copy)
    spec["workloads"].append({"name": "new-model.new_mix", "config": "new-model",
                              "traffic": "new_mix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new.layer_ms", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "new", "moves": "itl_p95_ms"})
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = registry.load_benchmark(copy)
    wl = registry.find_workload(spec, "new-model.new_mix")
    assert registry.model_config(registry.load_config(wl["config"], bench)).n_layers == 3
    assert registry.load_traffic(wl["traffic"], bench) == mix
    assert registry.load_metric_reader("new.layer_ms", bench)(None) == 42.0
    names = [m["name"] for m in registry.metrics_for(spec, wl["name"], trace=True)]
    assert "new.layer_ms" in names and "engine.step_ms" in names


def test_metric_groups_follow_trace_flag():
    spec = registry.load_benchmark()
    for wl in spec["workloads"]:
        e2e = registry.metrics_for(spec, wl["name"], trace=False)
        per = registry.metrics_for(spec, wl["name"], trace=True)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert per and all("layer" in m for m in per)
        moved = {m["moves"] for m in per}
        assert moved <= {m["name"] for m in e2e}, wl["name"]


def test_unknown_names_raise():
    spec = registry.load_benchmark()
    with pytest.raises(KeyError):
        registry.find_workload(spec, "no-such.cell")
    with pytest.raises(FileNotFoundError):
        registry.load_config("no-such-config")

"""The serving entry and the switches that decide how the program meets the
device: interpret mode, the WS decode step's coverage, the compile cache's
place and the forced-host-device re-exec of the mesh mains."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.interpret import interpret_mode
from repro.launch import compile_cache
from repro.mesh_ws.selfcheck import forced_host_env, forced_host_reexec
from repro.serving import ContinuousBatcher

ROOT = Path(__file__).resolve().parents[1]


def test_cpu_backend_interprets():
    assert interpret_mode() is (jax.default_backend() == "cpu")


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "gemma3-12b",
                                  "deepseek-v2-236b", "zamba2-2.7b",
                                  "whisper-base"])
def test_ws_decode_refuses_uncovered_arch(arch):
    """An architecture decode_step_ws does not cover must ask for the dense
    step; asking for WS raises instead of turning dense."""
    cfg = get_config(arch, smoke=True)
    with pytest.raises(ValueError, match="decode_step_ws does not cover"):
        ContinuousBatcher(None, cfg, slots=2, capacity=16, use_ws=True)
    b = ContinuousBatcher(None, cfg, slots=2, capacity=16, use_ws=False)
    assert not b.use_ws


def test_serve_main_serves_every_request_once(monkeypatch, tmp_path, capsys):
    from repro.launch import serve

    # JAX read its cache settings at import: with the variable set the
    # entry point configures nothing, so this run writes no cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = serve.main(["--requests", "5", "--slots", "2", "--capacity", "32",
                     "--prompt-lens", "3,6", "--max-new", "3"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "5/5 completed" in out and "(all=True)" in out
    assert "'totals'" in out  # the stats dict, not a bound method


def test_compile_cache_defers_to_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("platforms,xla_flags,want", [
    ("cpu", "", True),
    ("cpu", "--xla_force_host_platform_device_count=8", False),
    ("cpu", "--xla_force_host_platform_device_count=4", True),
    ("", "", False),
    ("tpu", "", False),
])
def test_forced_host_reexec_only_on_cpu(monkeypatch, platforms, xla_flags,
                                        want):
    """The mesh mains re-execute on forced host devices only on a CPU
    backend: elsewhere a parent holding the chip would starve the child."""
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", xla_flags)
    assert forced_host_reexec(8) is want
    if want:
        env = forced_host_env(8)
        assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
        monkeypatch.setenv("XLA_FLAGS", env["XLA_FLAGS"])
        assert forced_host_reexec(8) is False

"""Compile the main path's megakernels for a described TPU v5e chip.

Nothing runs here: each test lowers a jitted program for one chip of a
``v5e:2x2`` topology that the installed TPU compiler describes without a
device attached, and compiles it — Mosaic refuses what interpret mode
cannot catch (unaligned slices, vector access to SMEM, more fast memory
than a kernel may use).  Shapes are the real widths: llama3.2-3b's heads
and caches (3 query heads per KV head, so a decode tile's q block carries
pad rows), the serving benchmark's Mistral-7B attention (4 per KV head),
its Mistral-NeMo attention at 6 slots of an 8192-token cache (K/V blocks of
512 positions, a 112-round grid a layer), and the expert tile at the width
the chip check names.

The code under test picks interpret mode from the backend, which is the
CPU here, so each test switches the launch to the compiled kernel itself.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

# the TPU compiler writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import repro.pallas_ws.kernel as ws_kernel  # noqa: E402
from repro.configs import get_config  # noqa: E402

# llama3.2-3b attention at full width: 8 slots of a 2048-token cache
B, H, HKV, S, HD = 8, 24, 8, 2048, 128
# Mistral-7B-v0.3 attention as the serving benchmark runs it: 4 slots
MISTRAL_B, MISTRAL_H = 4, 32
# Mistral-NeMo-12B attention as its long-document cell runs it: 6 slots of
# an 8192-token cache, 32 query heads over 8 KV heads of 128
NEMO_B, NEMO_H, NEMO_S = 6, 32, 8192
# the expert width the chip check runs (deepseek-v2-236b routing shape,
# d_model cut to 512 so one whole expert's float32 weights fit VMEM)
E, TOP_K, T, D_EXPERT, F_EXPERT, BT = 160, 6, 64, 512, 1536, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - depends on the installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Launch the megakernel compiled, as on a TPU backend, and keep the
    persistent compilation cache out of it (an entry written for a
    described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ws_kernel, "interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _compile_decode_megakernel(sharding, slots, heads, capacity=S):
    from repro.pallas_ws.ragged import ragged_decode_attention

    fn = jax.jit(lambda q, k, v, ln: ragged_decode_attention(q, k, v, ln))
    kv = _sds(sharding, (slots, HKV, capacity, HD), jnp.bfloat16)
    return fn.lower(
        _sds(sharding, (slots, heads, HD), jnp.bfloat16), kv, kv,
        _sds(sharding, (slots,), jnp.int32),
    ).compile()


def test_decode_megakernel_compiles_at_llama_width(one_chip, compiled_kernels):
    compiled = _compile_decode_megakernel(one_chip, B, H)
    assert _custom_calls(compiled) >= 1


def test_decode_megakernel_compiles_at_mistral_width(one_chip,
                                                     compiled_kernels):
    compiled = _compile_decode_megakernel(one_chip, MISTRAL_B, MISTRAL_H)
    assert _custom_calls(compiled) >= 1


def test_decode_megakernel_compiles_at_nemo_long_context(one_chip,
                                                         compiled_kernels,
                                                         monkeypatch):
    """The launch sweeps 512-position blocks in 112 rounds; the fixed
    64-position block would need 896."""
    from repro.pallas_ws import ragged

    assert ragged.decode_rounds_bound(NEMO_B, HKV, NEMO_S, 64, 8, 8, True) == 896
    seen = {}
    real = ragged.run_ws_schedule

    def spy(*args, **kw):
        seen.update(bk=kw["bk"], rounds=kw["rounds"])
        return real(*args, **kw)

    monkeypatch.setattr(ragged, "run_ws_schedule", spy)
    compiled = _compile_decode_megakernel(one_chip, NEMO_B, NEMO_H, NEMO_S)
    assert seen == {"bk": 512, "rounds": 112}
    assert _custom_calls(compiled) >= 1


def test_expert_megakernel_compiles(one_chip, compiled_kernels):
    from repro.moe_ws.layer import expert_ffn_ws

    fn = jax.jit(lambda idx, g, x, wg, wu, wd: expert_ffn_ws(
        idx, g, x, wg, wu, wd, bt=BT))
    w_in = _sds(one_chip, (E, D_EXPERT, F_EXPERT), jnp.float32)
    compiled = fn.lower(
        _sds(one_chip, (T, TOP_K), jnp.int32),
        _sds(one_chip, (T, TOP_K), jnp.float32),
        _sds(one_chip, (T, D_EXPERT), jnp.float32),
        w_in, w_in, _sds(one_chip, (E, F_EXPERT, D_EXPERT), jnp.float32),
    ).compile()
    assert _custom_calls(compiled) >= 1


def test_ws_decode_step_compiles_for_two_layer_llama(one_chip,
                                                     compiled_kernels):
    """One whole jitted WS decode step — embed, 2 layers of projections,
    cache splice and scheduled attention, logits — of llama3.2-3b at full
    width, cut to 2 layers."""
    from repro.models import init_caches, init_params
    from repro.serving.engine import jit_decode_step_ws

    cfg = dataclasses.replace(get_config("llama3.2-3b"), n_layers=2)
    slots, capacity = 4, S

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    caches = placed(jax.eval_shape(
        lambda: init_caches(cfg, slots, capacity)))
    compiled = jit_decode_step_ws(cfg).lower(
        params, caches, _sds(one_chip, (slots, 1), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32),
    ).compile()
    assert _custom_calls(compiled) >= cfg.n_layers

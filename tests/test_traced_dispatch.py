"""Jit-parity regression tests for the traced dropless dispatch.

The whole point of the traced Put is that the dropless WS path works where
training and serving live — under ``jit`` and ``scan``.  These tests pin
that contract:

* ``jit(moe_ffn_ws)`` == eager ``moe_ffn_ws`` == the no-drop oracle;
* ``jit(decode_step_ws)`` == eager ``decode_step_ws`` (logits and caches);
* ``moe_ffn_dispatch`` inside ``scan``-over-layers runs the **dropless**
  path when ``cfg.moe_dispatch == "ws"`` — with a capacity-starved config
  the dense path provably diverges, so if the deleted dense fallback ever
  silently returned under tracing, the scan output would snap to it;
* the traced ragged decode front-end matches the host-built one and the
  dense oracle, dead slots included;
* the vectorized ``row_divisor`` / ``divisor_from_tiles`` are equivalent to
  the original per-task loop (timing-insensitive: pure array comparison).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models.moe import init_moe, moe_ffn, moe_ffn_dispatch  # noqa: E402
from repro.moe_ws import (  # noqa: E402
    divisor_from_tiles,
    moe_ffn_nodrop_ref,
    moe_ffn_ws,
    route_to_tasks,
    row_divisor,
)


def _smoke_cfg(**kw):
    cfg = get_config("deepseek-v2-236b", smoke=True)
    return cfg.replace(**kw) if kw else cfg


def _moe_inputs(cfg, B=2, S=8, seed=0):
    p = init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.d_model))
    return p, x


# ---------------------------------------------------------------------------
# jit(moe_ffn_ws) parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["ws", "static"])
def test_jit_moe_ffn_ws_matches_eager_and_oracle(schedule):
    cfg = _smoke_cfg()
    p, x = _moe_inputs(cfg)
    ref, aux_ref = moe_ffn_nodrop_ref(x, p, cfg)
    y_e, aux_e = moe_ffn_ws(x, p, cfg, schedule=schedule, n_programs=4, bt=4)
    y_j, aux_j = jax.jit(
        lambda xx: moe_ffn_ws(xx, p, cfg, schedule=schedule, n_programs=4, bt=4)
    )(x)
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_e), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(aux_j - aux_ref)) < 1e-6
    assert float(jnp.abs(aux_e - aux_ref)) < 1e-6


def test_jit_moe_ffn_ws_dropless_at_router_skew():
    """Hot-expert routing under jit: the traced dispatch must still equal
    the no-drop oracle exactly where the dense capacity path loses tokens."""
    cfg = _smoke_cfg(capacity_factor=1.0, n_shared_experts=0)
    p, x = _moe_inputs(cfg, B=2, S=16, seed=7)
    p = dict(p)
    p["router"] = jnp.asarray(np.asarray(p["router"]) * 0.05)
    p["router"] = p["router"].at[:, 0].add(10.0)

    ref, _ = moe_ffn_nodrop_ref(x, p, cfg)
    y_j, _ = jax.jit(lambda xx: moe_ffn_ws(xx, p, cfg, n_programs=4, bt=4))(x)
    y_dense, _ = moe_ffn(x, p, cfg, group_size=x.shape[0] * x.shape[1])
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y_dense.astype(jnp.float32) - ref).max()) > 1e-3, (
        "the capacity path should be dropping here — skew regression"
    )


def test_autodiff_through_ws_dispatch_differentiates():
    """The ws dispatch is no longer forward-only: ``jax.grad`` through
    ``moe_ffn_dispatch`` with cfg.moe_dispatch='ws' runs the custom VJP
    (no TypeError, no deep 'JVP with aliasing' crash, and — pinned by
    tests/test_moe_ws_grad.py — never a silent dense substitution) and its
    gradients match the no-drop oracle's."""
    cfg = _smoke_cfg(moe_dispatch="ws")
    p, x = _moe_inputs(cfg, B=1, S=4, seed=9)

    def loss(xx):
        y, aux = moe_ffn_dispatch(xx, p, cfg)
        return jnp.sum(y ** 2) + aux

    def loss_ref(xx):
        y, aux = moe_ffn_nodrop_ref(xx, p, cfg)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(x)
    g_ref = jax.grad(loss_ref)(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    # the idiomatic training shape — value_and_grad inside jit — too
    v, gj = jax.jit(jax.value_and_grad(loss))(x)
    assert np.isfinite(float(v))
    np.testing.assert_allclose(np.asarray(gj), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# scan-over-layers: the dense fallback can never silently return
# ---------------------------------------------------------------------------


def test_scan_over_layers_dispatch_stays_dropless():
    """Two stacked MoE layers scanned under jit with a capacity-starved
    config: the ws dispatch must track an eager no-drop reference loop,
    and must NOT equal the dense dropping path (which is what the deleted
    tracer fallback used to return)."""
    cfg = _smoke_cfg(moe_dispatch="ws", capacity_factor=0.25, n_shared_experts=0)
    B, S = 2, 32  # T*k = 128 routed pairs over 8 experts >> dense capacity
    ps = jax.vmap(lambda k: init_moe(k, cfg, jnp.float32))(
        jax.random.split(jax.random.PRNGKey(3), 2)
    )
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model))

    def body(h, pl):
        y, aux = moe_ffn_dispatch(h, pl, cfg)
        return h + y, aux

    h_ws, aux_ws = jax.jit(lambda xx: jax.lax.scan(body, xx, ps))(x)

    h_ref = x
    for i in range(2):
        pl = jax.tree_util.tree_map(lambda a: a[i], ps)
        y, _ = moe_ffn_nodrop_ref(h_ref, pl, cfg)
        h_ref = h_ref + y
    np.testing.assert_allclose(
        np.asarray(h_ws), np.asarray(h_ref), rtol=1e-4, atol=1e-4
    )

    cfg_dense = cfg.replace(moe_dispatch="dense")
    h_dense, _ = jax.jit(
        lambda xx: jax.lax.scan(
            lambda h, pl: ((h + moe_ffn_dispatch(h, pl, cfg_dense)[0]), 0.0), xx, ps
        )
    )(x)
    assert float(jnp.abs(h_ws - h_dense).max()) > 1e-3, (
        "ws-flagged scan matched the dropping dense path — fallback returned?"
    )


def test_transformer_block_scan_runs_dropless_under_jit():
    """The full transformer stack (lm_hidden: remat + scan over stacked MoE
    layers) with cfg.moe_dispatch='ws' compiles and runs the dropless
    dispatch: hidden states diverge from the dense-flagged stack because
    the capacity-starved dense path drops tokens (aux diverges too after
    layer 1 — the routers see different hiddens — so only finiteness and
    divergence are asserted here; aux parity per layer is pinned by
    test_jit_moe_ffn_ws_matches_eager_and_oracle)."""
    from repro.models.transformer import init_params, lm_hidden

    cfg = _smoke_cfg(capacity_factor=0.25, n_shared_experts=0)
    B, S = 1, 32
    params = init_params(jax.random.PRNGKey(5), cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    cfg_ws = cfg.replace(moe_dispatch="ws")
    h_ws, aux_ws = jax.jit(
        lambda xx: lm_hidden(params, cfg_ws, xx, positions, remat=True)
    )(x)
    h_d, _ = jax.jit(
        lambda xx: lm_hidden(params, cfg, xx, positions, remat=True)
    )(x)
    assert np.isfinite(np.asarray(h_ws)).all()
    assert np.isfinite(float(aux_ws)) and float(aux_ws) > 0.0
    assert float(jnp.abs(h_ws - h_d).max()) > 1e-4, (
        "ws stack equals the capacity-starved dense stack — dropless path "
        "not taken inside the scanned transformer block"
    )


# ---------------------------------------------------------------------------
# jit(decode_step_ws) parity + traced ragged decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,overrides", [
    pytest.param("llama3.2-3b", {}, id="llama3.2-3b"),      # GQA, G = 2
    pytest.param("minicpm-2b", {}, id="minicpm-2b"),        # MHA, G = 1
    pytest.param("kimi-k2-1t-a32b", {"moe_dispatch": "ws"},  # expert tiles
                 id="kimi-k2-1t-a32b-moe-ws"),
])
def test_jit_decode_step_ws_matches_eager(arch, overrides):
    from repro.models import decode_step, decode_step_ws, prefill
    from repro.models.transformer import init_params
    from repro.serving.engine import jit_decode_step_ws

    cfg = get_config(arch, smoke=True)
    if overrides:
        cfg = cfg.replace(**overrides)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(np.array([[5, 6, 7, 8], [9, 8, 7, 6]], np.int32))}
    _, caches = prefill(params, cfg, batch, capacity=32)
    tok = jnp.asarray(np.array([[3], [4]], np.int32))
    pos = jnp.asarray(np.array([4, 2], np.int32))  # heterogeneous slots

    l_e, c_e = decode_step_ws(params, cfg, caches, tok, pos)
    step = jit_decode_step_ws(cfg)
    l_j, c_j = step(params, caches, tok, pos)
    np.testing.assert_allclose(np.asarray(l_j), np.asarray(l_e), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(c_j.kv.k), np.asarray(c_e.kv.k), rtol=1e-5, atol=1e-5
    )
    l_d, _ = decode_step(params, cfg, caches, tok, pos)
    np.testing.assert_allclose(np.asarray(l_j), np.asarray(l_d), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", [
    pytest.param(0, id="llama3.2-3b-dense"),
    pytest.param(1, id="kimi-k2-1t-a32b-moe-ws"),
])
def test_served_step_lowering_is_fence_free(case):
    """The compiled decode step the engine serves lowers with 0 RMW /
    0 locks / 0 fences (the audit asserts the scan internally)."""
    from benchmarks.zero_cost import SERVED_STEP_CASES, audit_served_step

    exp, arch, _ = SERVED_STEP_CASES[case]
    (row,) = audit_served_step(cases=(SERVED_STEP_CASES[case],))
    assert row["experiment"] == exp
    assert row["algorithm"] == f"served-step[{arch}]"
    assert row["rmws_per_op"] == row["locks_per_op"] == 0
    assert row["fences_per_op"] == 0
    assert row["hlo_bytes"] > 0


def test_traced_ragged_decode_matches_host_and_oracle():
    from repro.pallas_ws.ragged import ragged_decode_attention, ragged_decode_ref

    B, H, S, hd = 4, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    lengths = np.array([32, 0, 8, 16])  # includes a dead slot

    out_host = ragged_decode_attention(q, k, v, lengths, schedule="ws", bk=8)
    out_jit = jax.jit(
        lambda ln: ragged_decode_attention(q, k, v, ln, schedule="ws", bk=8)
    )(jnp.asarray(lengths))
    ref = ragged_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out_jit), np.asarray(out_host), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_jit), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    # dead slot stays exactly zero through the traced path
    assert float(jnp.abs(out_jit[1]).max()) == 0.0


def test_batcher_jit_ws_matches_eager_ws():
    """ContinuousBatcher(jit_ws=True): the compiled ws decode step produces
    the same greedy streams as the per-step host-built default."""
    from repro.serving.engine import ContinuousBatcher, Request

    cfg = get_config("llama3.2-3b", smoke=True)
    from repro.models.transformer import init_params

    params = init_params(jax.random.PRNGKey(0), cfg)
    outs = {}
    for jit_ws in (False, True):
        b = ContinuousBatcher(params, cfg, slots=2, capacity=32, jit_ws=jit_ws)
        assert b.use_ws
        b.admit(Request(0, np.array([5, 6, 7], np.int32), max_new=4))
        b.admit(Request(1, np.array([9, 8], np.int32), max_new=4))
        done = []
        for _ in range(10):
            done += b.step()
            if not b.n_live:
                break
        outs[jit_ws] = {r.rid: r.out for r in done}
    assert outs[False] == outs[True]


# ---------------------------------------------------------------------------
# row_divisor vectorization: equivalence with the original loop
# ---------------------------------------------------------------------------


def _loop_row_divisor(tasks, mult, n_rows):
    """The original O(n_tasks) Python-loop implementation, kept as the
    reference semantics for the vectorized np.repeat version."""
    mult = np.asarray(mult)
    div = np.ones((n_rows,), dtype=np.float32)
    for t in tasks:
        div[t.row_start: t.row_start + t.row_len] = max(1, int(mult[t.tid]))
    return div


@pytest.mark.parametrize("seed", range(6))
def test_row_divisor_vectorized_equals_loop(seed):
    rng = np.random.RandomState(seed)
    T = rng.randint(1, 40)
    E = rng.randint(1, 7)
    k = rng.randint(1, min(3, E) + 1)
    bt = int(rng.choice([2, 4, 8]))
    idx = np.stack([rng.choice(E, k, replace=False) for _ in range(T)])
    gates = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    tasks, routed = route_to_tasks(idx, gates, E, bt=bt)
    mult = rng.randint(0, 4, size=len(tasks))
    np.testing.assert_array_equal(
        row_divisor(tasks, mult, routed.n_rows),
        _loop_row_divisor(tasks, mult, routed.n_rows),
    )


def test_row_divisor_empty_tasks():
    np.testing.assert_array_equal(
        row_divisor([], np.zeros(0), 7), np.ones(7, np.float32)
    )


def test_divisor_from_tiles_traced_uniform_matches_host():
    """The traced uniform-bt branch and the host ragged branch agree on
    full tiles, eagerly and under jit."""
    rng = np.random.RandomState(0)
    n_tiles, bt = 6, 4
    starts = np.arange(n_tiles) * bt
    lens = np.full(n_tiles, bt)
    mult = rng.randint(0, 5, size=n_tiles)
    host = divisor_from_tiles(starts, lens, mult, n_tiles * bt)
    traced = jax.jit(
        lambda m: divisor_from_tiles(jnp.asarray(starts), bt, m, n_tiles * bt)
    )(jnp.asarray(mult))
    np.testing.assert_array_equal(np.asarray(traced), host)

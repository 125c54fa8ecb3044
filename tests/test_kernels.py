"""Per-kernel allclose tests: sweep shapes/dtypes in interpret mode against
the pure-jnp oracles (ref.py), forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention as decode_kernel
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan as ssd_kernel
from repro.kernels.ssd_scan.ops import ssd_scan as ssd_op
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.models.attention import flash_ref as model_flash_ref
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,hd,bq,bk,causal,window",
    [
        (1, 2, 2, 32, 8, 16, 16, True, 0),
        (2, 4, 2, 64, 16, 16, 32, True, 0),
        (2, 4, 1, 64, 16, 32, 16, False, 0),
        (1, 8, 4, 128, 32, 32, 32, True, 24),
        (1, 2, 2, 48, 8, 16, 16, True, 16),
    ],
)
def test_flash_attention_fwd(B, H, Hkv, S, hd, bq, bk, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, window=window, bq=bq, bk=bk
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )
    assert np.all(np.isfinite(np.asarray(lse)))


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 8), (False, 0)])
def test_flash_attention_grad(causal, window):
    B, H, Hkv, S, hd = 2, 4, 2, 32, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))

    def f(q, k, v):
        return (flash_attention(q, k, v, causal, window, 16, 16) ** 2).sum()

    def g(q, k, v):
        return (attention_ref(q, k, v, causal=causal, window=window) ** 2).sum()

    ga = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


def test_model_flash_ref_matches_oracle():
    """The model-side chunked jnp attention equals the kernel oracle."""
    B, H, S, hd = 2, 4, 64, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    for causal, window in [(True, 0), (True, 16)]:
        out = model_flash_ref(q, k, v, causal=causal, window=window, chunk=16)
        ref = attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=causal, window=window,
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssd scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,S,H,P,N,chunk",
    [(1, 16, 2, 4, 4, 8), (2, 32, 3, 8, 4, 8), (1, 64, 2, 16, 8, 16), (2, 24, 1, 8, 8, 8)],
)
def test_ssd_scan_fwd(b, S, H, P, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, S, N), dtype)
    C = jax.random.normal(ks[4], (b, S, N), dtype)
    y, fin = ssd_kernel(x, dt, A, B, C, chunk=chunk)
    yr, finr = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr), **_tol(dtype))


def test_ssd_grad_matches_chunked():
    b, S, H, P, N = 1, 32, 2, 8, 4
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))

    def f(x, dt, A, B, C):
        return (ssd_op(x, dt, A, B, C, 8) ** 2).sum()

    def g(x, dt, A, B, C):
        return (ssd_ref(x, dt, A, B, C)[0].astype(x.dtype) ** 2).sum()

    ga = jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    gb = jax.grad(g, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for a, b_ in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,hd,bk,pos,window",
    [
        (1, 2, 1, 32, 8, 8, 31, 0),
        (2, 4, 2, 64, 16, 16, 30, 0),
        (2, 4, 2, 64, 16, 16, 63, 16),
        (1, 8, 8, 128, 32, 32, 5, 0),
    ],
)
def test_decode_attention(B, H, Hkv, S, hd, bk, pos, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    out = decode_kernel(q, k, v, jnp.int32(pos), window=window, bk=bk)
    ref = decode_attention_ref(q, k, v, jnp.int32(pos), window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )

"""Plain float32 reference of a dense decoder, and the served-token check.

The forward follows the published Mistral/Llama equations: token
embedding; per layer RMSNorm, q/k/v projections, rotary embedding
(rotate-half pairs), causal grouped-query softmax attention, output
projection and residual; RMSNorm, SwiGLU MLP and residual; a final RMSNorm
and the output head.  It imports nothing of the program under test and
reads only the weights the benchmark made.  Matrix products run at
``highest`` precision, so float32 stays float32 on the TPU.

It runs layer by layer on a sequence padded to a fixed length (the cell's
cache capacity), so one compiled layer serves every request and only one
layer's weights are widened to float32 at a time.  Causal masking keeps the
padding from reaching the scored positions.

``precision="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 (e4m3, one scale per tensor), the
step below the configuration's bfloat16 that a later change might take.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # query rows per attention block (bounds the score matrix)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _cast(precision):
    return _fp8 if precision == "fp8" else (lambda x: x)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(jnp.float32))


def _rope(x, theta):
    """x: [P, heads, hd]; positions 0..P-1; rotate-half convention."""
    P, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(P, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@partial(jax.jit, static_argnames=("eps", "theta", "precision"))
def _layer(h, w, *, eps, theta, precision):
    c = _cast(precision)
    f32 = lambda a: c(a.astype(jnp.float32))
    P = h.shape[0]
    H, hd = w["attn"]["wq"].shape[1:]
    Hkv = w["attn"]["wk"].shape[1]
    G = H // Hkv
    x = c(_rms(h, w["attn_norm"], eps))
    q = _rope(jnp.einsum("pd,dhe->phe", x, f32(w["attn"]["wq"])), theta)
    k = _rope(jnp.einsum("pd,dhe->phe", x, f32(w["attn"]["wk"])), theta)
    v = jnp.einsum("pd,dhe->phe", x, f32(w["attn"]["wv"]))
    q, k, v = c(q), c(k), c(v)
    outs = []
    for s in range(0, P, Q_BLOCK):
        qb = q[s:s + Q_BLOCK].reshape(-1, Hkv, G, hd)
        sc = jnp.einsum("qkgd,pkd->kgqp", qb, k) * hd ** -0.5
        qpos = jnp.arange(s, s + qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(P)[None, :] <= qpos, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("kgqp,pkd->qkgd", pr, v).reshape(-1, H, hd))
    o = c(jnp.concatenate(outs, axis=0))
    h = h + jnp.einsum("phe,hed->pd", o, f32(w["attn"]["wo"]))
    x = c(_rms(h, w["mlp_norm"], eps))
    g = jnp.einsum("pd,df->pf", x, f32(w["mlp"]["wg"]))
    u = jnp.einsum("pd,df->pf", x, f32(w["mlp"]["wu"]))
    m = c(jax.nn.silu(g) * u)
    return h + jnp.einsum("pf,fd->pd", m, f32(w["mlp"]["wd"]))


@partial(jax.jit, static_argnames=("eps", "vocab", "precision"))
def _head(h, rows, gain, w_out, *, eps, vocab, precision):
    c = _cast(precision)
    x = c(_rms(h[rows], gain, eps))
    return jnp.einsum("nd,dv->nv", x, c(w_out.astype(jnp.float32)))[:, :vocab]


def logits_at(weights, conf: dict, tokens: np.ndarray, rows, pad_to: int,
              precision: str = "f32") -> np.ndarray:
    """Float32 logits [len(rows), vocab] of the next token after each
    position in ``rows`` of the sequence ``tokens``."""
    assert len(tokens) <= pad_to
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    seq = np.zeros(pad_to, np.int32)
    seq[: len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][jnp.asarray(seq)].astype(jnp.float32)
        for l in range(conf["num_hidden_layers"]):
            w = jax.tree_util.tree_map(lambda a: a[l], weights["layers"])
            h = _layer(h, w, eps=eps, theta=theta, precision=precision)
        w_out = (weights["embed"].T if conf["tie_word_embeddings"]
                 else weights["unembed"])
        out = _head(h, jnp.asarray(np.asarray(rows, np.int32)),
                    weights["final_norm"], w_out, eps=eps,
                    vocab=int(conf["vocab_size"]), precision=precision)
    return np.asarray(out)


def served_gaps(weights, conf: dict, prompt, out, pad_to: int,
                control: bool = False) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position.  With ``control``, the token at
    each position is the one the float8 forward puts first instead."""
    prompt = np.asarray(prompt, np.int32)
    out = np.asarray(out, np.int32)
    seq = np.concatenate([prompt, out[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits_at(weights, conf, seq, rows, pad_to)
    if control:
        low = logits_at(weights, conf, seq, rows, pad_to, precision="fp8")
        out = low.argmax(-1)
    return ref.max(-1) - ref[np.arange(len(rows)), out]

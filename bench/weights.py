"""Random weights for a dense decoder, made on the device from the seed.

The tree has the layout the program serves (``embed``, ``unembed``,
``final_norm`` and per-layer stacks under ``layers``), in the configuration's
dtype, built by one jitted call.  The reference reads the same arrays, so
neither side takes weights that the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def padded_vocab(conf: dict) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 256, as
    the program allocates it (the extra rows are never a served token)."""
    return -(-conf["vocab_size"] // 256) * 256


def weight_shapes(conf: dict) -> dict:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    L, V = conf["num_hidden_layers"], padded_vocab(conf)
    shapes = {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": {
            "attn_norm": (L, d),
            "attn": {"wq": (L, d, H, hd), "wk": (L, d, Hkv, hd),
                     "wv": (L, d, Hkv, hd), "wo": (L, H, hd, d)},
            "mlp_norm": (L, d),
            "mlp": {"wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)},
        },
    }
    if not conf["tie_word_embeddings"]:
        shapes["unembed"] = (d, V)
    return shapes


# contracted axes of each matrix: weights ~ N(0, 1/fan-in) keep activations
# at unit scale
_FAN_IN = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "wg": 1, "wu": 1, "wd": 1,
           "unembed": 1}
NORM_SCALE = 0.1  # norm gains are 1 + N(0, 0.1²)


def _leaf(key, name: str, shape, dtype):
    if name.endswith("norm"):
        x = NORM_SCALE * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        x = jax.random.normal(key, shape, jnp.float32)
    else:
        lead = 1 if len(shape) > 2 else 0  # stacked layer axis
        n = _FAN_IN[name]
        fan = 1
        for s in shape[lead:lead + n]:
            fan *= s
        x = jax.random.normal(key, shape, jnp.float32) * fan ** -0.5
    return x.astype(dtype)


def make_weights(conf: dict, seed: int):
    """The weight tree for ``conf`` from ``seed``, on the default device."""
    shapes = weight_shapes(conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf(k, path[-1].key, shape, dtype)
                  for k, (path, shape) in zip(keys, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the seed need not fit
    32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)

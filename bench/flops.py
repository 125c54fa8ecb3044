"""Operations and bytes the served work needs, counted from shapes and live
lengths (not from how the program implements it).
"""

from __future__ import annotations


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    return dict(d=d, H=H, Hkv=Hkv, hd=hd, f=conf["intermediate_size"],
                L=conf["num_hidden_layers"], V=conf["vocab_size"],
                itemsize=2 if conf["torch_dtype"] in ("bfloat16", "float16") else 4)


def layer_params(conf: dict) -> int:
    """Matrix parameters of one layer (projections and MLP)."""
    k = dims(conf)
    attn = k["d"] * k["hd"] * (2 * k["H"] + 2 * k["Hkv"])
    return attn + 3 * k["d"] * k["f"]


def token_flops(conf: dict) -> int:
    """Matrix FLOPs of one token outside attention: 2 per parameter of the
    layers held here, and the output head."""
    k = dims(conf)
    return 2 * (k["L"] * layer_params(conf) + k["d"] * k["V"])


def attn_flops(conf: dict, kv_len: int) -> int:
    """Attention FLOPs of one query over ``kv_len`` positions, all layers:
    q·k and p·v, 2 each per head per position per dimension."""
    k = dims(conf)
    return 4 * k["H"] * k["hd"] * kv_len * k["L"]


def decode_attn_bytes(conf: dict, kv_len: int) -> int:
    """Least bytes a decode query's attention moves, all layers: K and V of
    the live positions read once per kv head, q read and the output written
    once."""
    k = dims(conf)
    kv = 2 * k["Hkv"] * kv_len * k["hd"] * k["itemsize"]
    qo = 2 * k["H"] * k["hd"] * k["itemsize"]
    return (kv + qo) * k["L"]


def prefill_flops(conf: dict, prompt_len: int) -> int:
    """Model FLOPs of a prompt: every token's layer work, causal attention
    (token i attends over i + 1 positions), and the output head once, for
    the last token, whose logits are all a prefill needs."""
    k = dims(conf)
    causal = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * 2 * k["L"] * layer_params(conf) + 2 * k["d"] * k["V"]
            + attn_flops(conf, 1) * causal)

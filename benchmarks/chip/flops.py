"""Model FLOPs of one trained token, from a configuration file's sizes.

    6 * N_nonembedding + 6 * N_head + 6 * L * S * H * head_dim

N_nonembedding counts every parameter of the decoder layers and the final
norm; N_head is the output projection (d_model * vocab), counted whether or
not it shares the embedding's weights; the embedding lookup itself does no
arithmetic. Attention is causal: a query attends to S/2 keys on average,
2 FLOPs per multiply-add, QK^T and PV, times 3 for forward and backward.
Recomputed operations (remat) are not counted, as MFU requires.
"""
from __future__ import annotations


def layer_params(m: dict) -> int:
    d, h, k, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"], m["d_ff"])
    bias = m["use_bias"]
    n = d * h * hd + 2 * d * k * hd + h * hd * d
    if bias or m["qkv_bias"]:
        n += h * hd + 2 * k * hd
    if bias:
        n += d
    gated = m["mlp_act"] in ("swiglu", "geglu")
    n += (3 if gated else 2) * d * f
    if bias:
        n += (2 * f if gated else f) + d
    n += 2 * norm_params(m)
    return n


def norm_params(m: dict) -> int:
    return {"rmsnorm": 1, "layernorm": 2,
            "layernorm_nonparam": 0}[m["norm_type"]] * m["d_model"]


def flops_per_token(m: dict, seq_len: int) -> float:
    n_nonemb = m["num_layers"] * layer_params(m) + norm_params(m)
    n_head = m["d_model"] * m["vocab_size"]
    attn = 6 * m["num_layers"] * seq_len * m["num_heads"] * m["head_dim"]
    return 6.0 * n_nonemb + 6.0 * n_head + attn

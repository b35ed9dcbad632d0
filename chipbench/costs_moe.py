"""Operation and byte counts of a sparse-expert decoder, from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS, not
what a compiler emitted. A token runs `num_experts_per_tok` experts of
width `intermediate_size`, so only those count ("active" operations);
recomputed operations (remat) do not count; bytes are the least a
grouped matmul must move. `shape` is a configuration file's dict (HF
key names). Each function has a hand-worked case in tests/chipbench.
"""

from __future__ import annotations

from chipbench import costs


def active_matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication: per layer
    q,k,v,o, the router and its own experts' gate,up,down; plus the
    output head. The embedding is a gather and the norms elementwise."""
    d, f = shape["hidden_size"], shape["intermediate_size"]
    hd = costs.head_dim(shape)
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    attention = d * hd * (2 * h + 2 * kv)
    experts = shape["num_experts_per_tok"] * 3 * d * f
    router = d * shape["num_experts"]
    head = d * shape["vocab_size"]
    return {"attention": attention, "experts": experts, "router": router, "head": head,
            "total": shape["num_hidden_layers"] * (attention + experts + router) + head}


def train_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward + backward FLOPs a training token requires: 6 per active
    matmul parameter plus 3x the causal attention forward, averaged over
    the sequence. Recompute is not counted."""
    return (6.0 * active_matmul_params(shape)["total"]
            + 3.0 * costs.attn_flops_causal(shape, seq_len) / seq_len)


def grouped_matmul_cost(shape: dict, tokens: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over
    `tokens` tokens, forward and backward apart.

    A token makes `num_experts_per_tok` (token, expert) pairs; dropless,
    so all P of them are rows of three matmuls forward (gate and up
    [P, D] x [E, D, F], down [P, F] x [E, F, D]) and of six backward
    (each one's input gradient and weight gradient): 2 * P * D * F
    FLOPs each whatever the groups' sizes. Each must read its two
    operands and write its result once: P * D + P * F + E * D * F
    elements, the same sum for all nine.
    """
    d, f = shape["hidden_size"], shape["intermediate_size"]
    pairs = tokens * shape["num_experts_per_tok"]
    flops = 2.0 * pairs * d * f
    nbytes = float(io_bytes) * (pairs * d + pairs * f + shape["num_experts"] * d * f)
    return {"pairs": pairs, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

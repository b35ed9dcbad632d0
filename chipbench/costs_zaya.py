"""Operation and byte counts of ONE CHIP'S SHARE of a ZAYA1 decoder,
from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS of this
chip, not what a compiler emitted. `shape` is the configuration file's
dict (HF key names): `num_experts` experts are HELD here of
`published.num_experts`, `vocab_size` rows of the tied table. A token
meets the attention, the router and the head slice whole; of its
`num_experts_per_tok` experts only those held here are multiplied, so
the expert layer's count takes the share of pairs that were really
routed to held experts (`held_share`, measured: the step's
statistics), not an assumed half. Recomputed operations (remat) do not
count; bytes are the least a grouped matmul must move. Each function
has a hand-worked case in tests/chipbench.
"""

from __future__ import annotations

from chipbench import costs


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication here: per
    layer CCA's four down projections and its output projection, the
    grouped convolution (hd x hd a head and tap), the router (down
    projection, two hidden layers, the logits) and ONE expert's gate,
    up, down; plus the head over the held rows. The embedding is a
    gather; the depthwise convolution, the norms and the rotary are
    elementwise: none counts."""
    d, f, r = shape["hidden_size"], shape["moe_intermediate_size"], shape["router_hidden_size"]
    hd = costs.head_dim(shape)
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    proj = d * hd * (2 * h + 2 * kv)              # W_q, W_o; W_k, W_v1 + W_v2
    conv = shape["cca_time1"] * (h + kv) * hd * hd
    router = d * r + 2 * r * r + r * shape["published"]["num_experts"]
    return {"proj": proj, "conv": conv, "router": router, "expert": 3 * d * f,
            "head": d * shape["vocab_size"]}


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward FLOPs a training token requires OF THIS CHIP:
    6 per matmul parameter it meets (the expert's times `held_share`
    of its `num_experts_per_tok` pairs) plus 3x the causal attention
    forward over the latent's heads, averaged over the sequence."""
    p = matmul_params(shape)
    layer = (p["proj"] + p["conv"] + p["router"]
             + held_share * shape["num_experts_per_tok"] * p["expert"])
    return (6.0 * (shape["num_hidden_layers"] * layer + p["head"])
            + 3.0 * costs.attn_flops_causal(shape, seq_len) / seq_len)


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over
    the `rows` (token, expert) pairs that were routed to held experts.

    Three matmuls forward (gate and up [rows, D] x [held, D, F], down
    [rows, F] x [held, F, D]) and six backward: 2 * rows * D * F FLOPs
    each whatever the groups' sizes; each reads its two operands and
    writes its result once: rows * D + rows * F + held * D * F elements.
    Rows routed elsewhere are no row of any of them.
    """
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["num_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

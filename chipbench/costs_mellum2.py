"""Operation and byte counts of ONE CHIP'S SHARE of a Mellum2 decoder
(`model_type` mellum), from shapes.

What costs.py is for the dense decoder and costs_laguna.py for Laguna:
what the algorithm NEEDS of this chip, not what a compiler emitted.
`shape` is the configuration file's dict (HF key names): the first
`num_hidden_layers` entries of `layer_types` say each layer's mask, every
layer has `num_attention_heads` heads over `num_key_value_heads` and an
expert block; `num_experts` experts are HELD here of
`published.num_experts`, `vocab_size` rows of the embedding and columns
of the head. A token meets the attention, the router and the head slice
whole; of its `num_experts_per_tok` pairs only those whose expert is held
here are multiplied, so the routed experts' count takes the share of
pairs that were really routed to held experts (`held_share`, measured:
the step's statistics), not an assumed 1/8. A sliding layer's scores
count the (query, key) pairs INSIDE the window alone, a full layer's the
causal pairs. The norms (a head's among them), the rotary and the
embedding's gather are elementwise: none counts. Recomputed operations
(remat; the split backward's second pass over the scores) do not count;
bytes are the least a kernel must move. Each function has a hand-worked
case in tests/chipbench.
"""

from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def layers(shape: dict) -> list:
    """The type of each layer this chip runs."""
    return list(shape["layer_types"][:shape["num_hidden_layers"]])


def visible_pairs(shape: dict, kind: str, seq_len: int) -> float:
    """(query, key) pairs a head of one sequence scores: every key up to
    the row's own, or under a window the `sliding_window` up to it (the
    first rows see fewer)."""
    w = shape["sliding_window"]
    if kind == SLIDING and seq_len > w:
        return w * (w + 1) / 2 + (seq_len - w) * w
    return seq_len * (seq_len + 1) / 2


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication here: a layer's
    attention (q, k, v, o), its router, ONE routed expert, the head over
    the held columns."""
    d, hd = shape["hidden_size"], shape["head_dim"]
    heads, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    return {"attention": d * hd * (2 * heads + 2 * kv),
            "router": d * shape["published"]["num_experts"],
            "expert": 3 * d * shape["moe_intermediate_size"], "head": d * shape["vocab_size"]}


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part: 2 a matmul
    parameter; scores 4 x head_dim a visible pair and head, averaged over
    the sequence."""
    p = matmul_params(shape)
    out = {"attention": 0.0, "scores.full": 0.0, "scores.window": 0.0, "router": 0.0,
           "routed": 0.0, "head": 2.0 * p["head"]}
    for kind in layers(shape):
        out["attention"] += 2.0 * p["attention"]
        out["scores.window" if kind == SLIDING else "scores.full"] += (
            4.0 * shape["head_dim"] * shape["num_attention_heads"]
            * visible_pairs(shape, kind, seq_len) / seq_len)
        out["router"] += 2.0 * p["router"]
        out["routed"] += 2.0 * held_share * shape["num_experts_per_tok"] * p["expert"]
    return out


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward FLOPs a training token requires OF THIS CHIP:
    three times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, held_share).values())


def flash_cost(shape: dict, kind: str, batch: float, seq_len: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of the flash kernels over ALL the layers
    of one `kind` at [batch, seq_len], forward and backward apart
    (costs.flash_cost's counts): forward 2 matmuls of 2 x head_dim FLOPs a
    visible pair and head, reads Q, K, V, writes O; backward 5 such
    matmuls (a backward that runs the dq and the dk/dv kernels apart runs
    7: the two more are not required), reads Q, K, V, O, dO and writes dQ,
    dK, dV, each ONCE (k and v fetched again for every q block are not
    required either). Under a window the pairs are those INSIDE it: a
    kernel that walks whole sub-tiles cannot reach 100% of this."""
    hd, heads, kv = shape["head_dim"], shape["num_attention_heads"], shape["num_key_value_heads"]
    n = sum(1 for layer_kind in layers(shape) if layer_kind == kind)
    fwd = n * batch * heads * 4.0 * hd * visible_pairs(shape, kind, seq_len)
    q_bytes = n * batch * seq_len * heads * hd * io_bytes
    kv_bytes = n * batch * seq_len * kv * hd * io_bytes
    return {"layers": n, "fwd_flops": fwd, "bwd_flops": 2.5 * fwd,
            "fwd_bytes": 2 * q_bytes + 2 * kv_bytes,     # Q, O + K, V
            "bwd_bytes": 4 * q_bytes + 4 * kv_bytes}     # Q, O, dO, dQ + K, V, dK, dV


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over the
    `rows` (token, expert) pairs that were routed to held experts: three
    matmuls forward and six backward, 2 * rows * D * F FLOPs each (K 2304
    / N 896 and its transpose); each reads its two operands and writes its
    result once. Pairs routed elsewhere are no row of any of them."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["num_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

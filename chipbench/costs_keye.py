"""Operation and byte counts of ONE CHIP'S SHARE of the Keye-VL-2.0
language model (`model_type` KeyeVL2), from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS of this
chip, not what a compiler emitted. `shape` is the configuration file's
dict (HF key names): `num_experts` experts are HELD here of
`published.num_experts`, `vocab_size` rows of the embedding and columns
of the head. A token meets the attention's projections, the indexer's,
the router and the head slice whole; its index scores run over every
key before it; its attention over the SELECTED keys alone
(`selected_pairs`: a kernel that visits every pair under the diagonal
and masks cannot reach 100% of this); of its `num_experts_per_tok`
pairs only those whose expert is held here are multiplied
(`held_share`, measured: the step's statistics). Recomputed operations
(remat) do not count; bytes are the least a kernel must move. Each
function has a hand-worked case in tests/chipbench.
"""

from __future__ import annotations


def selected_pairs(shape: dict, seq_len: int) -> float:
    """(query, key) pairs a head of one sequence attends over: t + 1 for
    a query t before `topk`, `topk` from there on."""
    k = min(shape["sa_config"]["topk"], seq_len)
    return k * (k + 1) / 2 + (seq_len - k) * k


def causal_pairs(seq_len: int) -> float:
    return seq_len * (seq_len + 1) / 2


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication here: the
    attention (q, k, v, o), the indexer (its queries, its one key, its
    head weights), the router, ONE routed expert, the head over the held
    columns. The embedding is a gather, the norms and the rotary are
    elementwise: none counts."""
    d, hd = shape["hidden_size"], shape["head_dim"]
    sa = shape["sa_config"]
    J, c = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"attention": d * hd * (2 * shape["num_attention_heads"] + 2 * shape["num_key_value_heads"]),
            "indexer": d * (J * c + c + J),
            "router": d * shape["published"]["num_experts"],
            "expert": 3 * d * shape["moe_intermediate_size"], "head": d * shape["vocab_size"]}


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part: 2 a matmul
    parameter; the index scores 2 x J x c a (query, key) pair under the
    diagonal; the attention 4 x head_dim a SELECTED pair and head; both
    averaged over the sequence."""
    p, L = matmul_params(shape), shape["num_hidden_layers"]
    sa = shape["sa_config"]
    return {
        "attention": L * 2.0 * p["attention"],
        "indexer": L * 2.0 * p["indexer"],
        "index_scores": L * 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        * causal_pairs(seq_len) / seq_len,
        "scores.selected": L * 4.0 * shape["head_dim"] * shape["num_attention_heads"]
        * selected_pairs(shape, seq_len) / seq_len,
        "router": L * 2.0 * p["router"],
        "routed": L * 2.0 * held_share * shape["num_experts_per_tok"] * p["expert"],
        "head": 2.0 * p["head"],
    }


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward FLOPs a training token requires OF THIS CHIP:
    three times the forward's of everything that takes a gradient, once
    the indexer's (its projections and scores: the selection is discrete
    and nothing of it is differentiated). Recompute is not counted."""
    f = forward_flops_per_token(shape, seq_len, held_share)
    once = f.pop("indexer") + f.pop("index_scores")
    return 3.0 * sum(f.values()) + once


def flash_cost(shape: dict, batch: float, seq_len: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of the flash kernels of ONE layer at
    [batch, seq_len] over the SELECTED pairs, forward and backward apart
    (costs.flash_cost's counts): forward 2 matmuls of 2 x head_dim FLOPs a
    selected pair and head, reads Q, K, V and the packed selection (a bit
    a pair under or over the diagonal), writes O; backward 5 such
    matmuls, reads Q, K, V, O, dO and the selection (twice: the dq and
    the dk/dv kernels), writes dQ, dK, dV."""
    hd, heads, kv = shape["head_dim"], shape["num_attention_heads"], shape["num_key_value_heads"]
    fwd = batch * heads * 4.0 * hd * selected_pairs(shape, seq_len)
    q_bytes = batch * seq_len * heads * hd * io_bytes
    kv_bytes = batch * seq_len * kv * hd * io_bytes
    sel_bytes = batch * seq_len * seq_len / 8
    return {"fwd_flops": fwd, "bwd_flops": 2.5 * fwd,
            "fwd_bytes": 2 * q_bytes + 2 * kv_bytes + sel_bytes,
            "bwd_bytes": 4 * q_bytes + 4 * kv_bytes + 2 * sel_bytes}


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over the
    `rows` (token, expert) pairs that were routed to held experts: three
    matmuls forward and six backward, 2 * rows * D * F FLOPs each; each
    reads its two operands and writes its result once. Pairs routed
    elsewhere are no row of any of them."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["num_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

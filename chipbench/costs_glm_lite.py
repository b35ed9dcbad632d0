"""Operation and byte counts of ONE CHIP'S SHARE of a GLM-4.7-Flash
decoder (`glm4_moe_lite`), from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS of this
chip, not what a compiler emitted. `shape` is the configuration file's
dict (HF key names): `num_hidden_layers` counts the leading dense layers
(`first_k_dense_replace`) and the expert layers; the
`num_nextn_predict_layers` multi-token-prediction blocks stand beside
them, each one more expert layer, a [2 hidden, hidden] merge and a
SECOND pass of the head; `n_routed_experts` experts are HELD here of
`published.n_routed_experts`, `vocab_size` rows of the embedding and
columns of the head. A token meets the attention, the router, the shared
expert and the head slice whole; of its `num_experts_per_tok` pairs only
those whose expert is held here are multiplied, so the routed experts'
count takes the share of pairs that were really routed to held experts
(`held_share`, measured: the step's statistics), not an assumed eighth.
Recomputed operations (remat) do not count; bytes are the least a
kernel must move. Each function has a hand-worked case in
tests/chipbench.
"""

from __future__ import annotations


def blocks(shape: dict) -> dict:
    """How many blocks of each kind this chip runs a step."""
    dense = shape["first_k_dense_replace"]
    mtp = shape["num_nextn_predict_layers"]
    return {"dense": dense, "expert": shape["num_hidden_layers"] - dense + mtp, "mtp": mtp,
            "attention": shape["num_hidden_layers"] + mtp}


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication here: MLA's
    two down projections, two up projections and its output projection;
    the router; the shared expert; ONE routed expert's gate, up, down;
    a dense layer's SwiGLU; the MTP merge; the head over the held
    columns. The embedding is a gather, the norms and the rotary are
    elementwise: none counts."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    h, rq, rkv = shape["num_attention_heads"], shape["q_lora_rank"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    mla = d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d
    return {"mla": mla, "router": d * shape["published"]["n_routed_experts"],
            "shared": shape["n_shared_experts"] * 3 * d * f, "expert": 3 * d * f,
            "dense_ffn": 3 * d * shape["intermediate_size"], "merge": 2 * d * d,
            "head": d * shape["vocab_size"]}


def scores_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward FLOPs of ONE attention's causal scores for one token,
    averaged over the sequence: QK^T over the d_n + d_r channels of a
    key and PV over the d_v of a value, 2 FLOPs a channel, (S + 1) / 2
    keys a query."""
    width = shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"] + shape["v_head_dim"]
    return 2.0 * width * shape["num_attention_heads"] * (seq_len + 1) / 2


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part."""
    p, n = matmul_params(shape), blocks(shape)
    routed = held_share * shape["num_experts_per_tok"] * p["expert"]
    return {
        "mla": n["attention"] * (2.0 * p["mla"] + scores_flops_per_token(shape, seq_len)),
        "dense_ffn": n["dense"] * 2.0 * p["dense_ffn"],
        "router": n["expert"] * 2.0 * p["router"],
        "shared": n["expert"] * 2.0 * p["shared"],
        "routed": n["expert"] * 2.0 * routed,
        "merge": n["mtp"] * 2.0 * p["merge"],
        "head": (1 + n["mtp"]) * 2.0 * p["head"],
    }


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward FLOPs a training token requires OF THIS CHIP:
    three times the forward's (2 forward, 4 backward a matmul parameter;
    the causal scores likewise). Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, held_share).values())


def flash_cost(shape: dict, batch: float, seq_len: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of the flash kernel over ONE call at
    [batch, seq_len], every head with keys and values of its own,
    forward and backward apart (costs.flash_cost's counts at this
    attention's widths): forward 2 (d_n + d_r) + 2 d_v FLOPs a causal
    (q, k) pair and head, reads Q, K, V, writes O; backward 2.5 x the
    forward's, reads Q, K, V, O, dO and writes dQ, dK, dV."""
    h = shape["num_attention_heads"]
    qk, dv = shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"], shape["v_head_dim"]
    pairs = seq_len * (seq_len + 1) / 2
    fwd = batch * h * (2.0 * qk + 2.0 * dv) * pairs
    qk_bytes = batch * seq_len * h * qk * io_bytes
    v_bytes = batch * seq_len * h * dv * io_bytes
    return {"fwd_flops": fwd, "bwd_flops": 2.5 * fwd,
            "fwd_bytes": 2 * qk_bytes + 2 * v_bytes,     # Q, K + V, O
            "bwd_bytes": 4 * qk_bytes + 4 * v_bytes}     # Q, K, dQ, dK + V, O, dO, dV


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE block's grouped matmuls over the
    `rows` (token, expert) pairs that were routed to held experts: three
    matmuls forward and six backward, 2 * rows * D * F FLOPs each
    whatever the groups' sizes; each reads its two operands and writes
    its result once. Pairs routed elsewhere are no row of any of them."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["n_routed_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

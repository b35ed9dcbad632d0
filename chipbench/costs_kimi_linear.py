"""Operation and byte counts of ONE CHIP'S SHARE of a Kimi-Linear decoder
(`model_type` kimi_linear), from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS of this
chip, not what a compiler emitted. `shape` is the configuration file's
dict (HF key names): layers are numbered from 1; layer l is an MLA layer
where l is in `linear_attn_config.full_attn_layers`, else a KDA layer; the
first `first_k_dense_replace` layers are over a dense SwiGLU of
`intermediate_size`, the others over the expert layer, of which
`num_experts` experts of `published.num_experts` are HELD here, as are
`vocab_size` rows of the tables; both mixers' heads are whole. A KDA
layer's recurrence is counted in its POSITION-BY-POSITION form, the work
no implementation can avoid. Recomputed operations (remat) do not count;
bytes are the least a kernel must move. Each function has a hand-worked
case in tests/chipbench.
"""

from __future__ import annotations

KDA, MLA = "kda", "mla"


def layers(shape: dict) -> list:
    """The kind of each layer this chip runs, from layer 1."""
    full = shape["linear_attn_config"]["full_attn_layers"]
    return [MLA if l in full else KDA for l in range(1, shape["num_hidden_layers"] + 1)]


def count(shape: dict, kind: str) -> int:
    return layers(shape).count(kind)


def expert_layers(shape: dict) -> int:
    return shape["num_hidden_layers"] - shape["first_k_dense_replace"]


def state_elements(shape: dict) -> int:
    """Elements of one position's state over the heads: heads x d x d."""
    lin = shape["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication: a KDA mixer (q,
    k, v, o, the decay's and the gate's low-rank pairs, beta), the MLA mixer
    (the query's ONE matrix, the latent's down and up projections, o), a
    router, the shared expert and ONE routed expert (three matrices each),
    the dense SwiGLU, the head over the held columns. The embedding is a
    gather; the convolution's taps, the norms and the gates are
    elementwise: none counts."""
    d, lin = shape["hidden_size"], shape["linear_attn_config"]
    wide, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    h, rkv = shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    f = shape["moe_intermediate_size"]
    return {"kda": 4 * d * wide + 2 * r * (d + wide) + d * lin["num_heads"],
            "mla": d * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d,
            "router": d * shape["published"]["num_experts"],
            "shared": 3 * d * f * shape["num_shared_experts"],
            "expert": 3 * d * f,
            "dense_ffn": 3 * d * shape["intermediate_size"],
            "head": d * shape["vocab_size"]}


def num_params(shape: dict) -> int:
    """Every parameter of the tree the program holds for this shape."""
    d, lin = shape["hidden_size"], shape["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    p = matmul_params(shape)
    own = {KDA: p["kda"] + 3 * lin["short_conv_kernel_size"] * wide + lin["num_heads"]
           + 2 * wide + lin["head_dim"], MLA: p["mla"] + shape["kv_lora_rank"]}
    experts = (p["router"] + shape["published"]["num_experts"] + p["shared"]
               + shape["num_experts"] * p["expert"])
    return 2 * shape["vocab_size"] * d + d + sum(
        own[kind] + 2 * d + (p["dense_ffn"] if l < shape["first_k_dense_replace"] else experts)
        for l, kind in enumerate(layers(shape)))


def scores_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward FLOPs of ONE MLA layer's causal scores for one token, averaged
    over the sequence: QK^T over the d_n + d_r channels of a key and PV over
    the d_v of a value, 2 FLOPs a channel, (S + 1) / 2 keys a query."""
    width = shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"] + shape["v_head_dim"]
    return 2.0 * width * shape["num_attention_heads"] * (seq_len + 1) / 2


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part: 2 a matmul
    parameter; an MLA layer's scores; a KDA layer's recurrence 7 an element
    of the heads' state (the decay 1; k^T S, the write k u^T and q^T S 2
    each); the routed experts `num_experts_per_token` x `held_share` (the
    share of a step's pairs routed to held experts) experts a token."""
    p = matmul_params(shape)
    n = {kind: count(shape, kind) for kind in (KDA, MLA)}
    sparse = expert_layers(shape)
    return {"kda.proj": n[KDA] * 2.0 * p["kda"],
            "kda.scan": n[KDA] * 7.0 * state_elements(shape),
            "mla.proj": n[MLA] * 2.0 * p["mla"],
            "mla.scores": n[MLA] * scores_flops_per_token(shape, seq_len),
            "dense_ffn": shape["first_k_dense_replace"] * 2.0 * p["dense_ffn"],
            "experts.router": sparse * 2.0 * p["router"],
            "experts.shared": sparse * 2.0 * p["shared"],
            "experts.routed": sparse * 2.0 * p["expert"] * shape["num_experts_per_token"]
            * held_share,
            "head": 2.0 * p["head"]}


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward: three times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, held_share).values())


def scan_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """Operations and least bytes of the KDA rule over ALL the KDA layers at
    [batch, seq_len], forward and backward apart, in the
    position-by-position form (chipbench/costs_solar_open2.py::scan_cost has
    the derivation; the same counts, this file's own copy): forward 7 an
    element of the state a head and position, reads q, k, v [d] in bf16 and
    g [d] and beta in float32, writes o [d] in bf16; backward 14, reads
    those and dO and writes dq, dk, dv, dg, dbeta."""
    n = count(shape, KDA)
    lin = shape["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    positions = n * batch * seq_len
    inputs = positions * h * (3 * d * 2 + d * 4 + 4)
    o = positions * h * d * 2
    elements = positions * state_elements(shape)
    return {"layers": n, "fwd_flops": 7.0 * elements, "bwd_flops": 14.0 * elements,
            "fwd_bytes": inputs + o, "bwd_bytes": 2 * inputs + o}


def flash_cost(shape: dict, batch: float, seq_len: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of attention over ALL the MLA layers at
    [batch, seq_len], every head with keys and values of its own, forward
    and backward apart: forward 2 (d_n + d_r) + 2 d_v FLOPs a causal (q, k)
    pair and head, reads Q, K (d_n + d_r wide) and V, writes O (d_v wide);
    backward 2.5 x the forward's, reads Q, K, V, O, dO and writes dQ, dK,
    dV: the same work whatever implements it (one kernel over keys of 192,
    or the scores as two products)."""
    n, h = count(shape, MLA), shape["num_attention_heads"]
    qk, dv = shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"], shape["v_head_dim"]
    pairs = seq_len * (seq_len + 1) / 2
    fwd = n * batch * h * (2.0 * qk + 2.0 * dv) * pairs
    qk_bytes = n * batch * seq_len * h * qk * io_bytes
    v_bytes = n * batch * seq_len * h * dv * io_bytes
    return {"layers": n, "fwd_flops": fwd, "bwd_flops": 2.5 * fwd,
            "fwd_bytes": 2 * qk_bytes + 2 * v_bytes,     # Q, K + V, O
            "bwd_bytes": 4 * qk_bytes + 4 * v_bytes}     # Q, K, dQ, dK + V, O, dO, dV


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over the
    `rows` (token, expert) pairs that were routed to held experts: three
    matmuls forward (gate, up, down) and six backward, 2 * rows * D * F
    FLOPs each; each reads its two operands and writes its result once.
    Pairs routed elsewhere are no row of any of them."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["num_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

"""Operation and byte counts of a Solar-Open2 decoder (`model_type`
solar_open2), from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS, not
what a compiler emitted. `shape` is the configuration file's dict (HF key
names): layer l is a GQA layer where l is in `gqa_layers`, else a KDA
layer; the head counts (`num_attention_heads`, `num_key_value_heads`,
`linear_attn_config.num_heads`), `n_routed_experts` experts of
`published.n_routed_experts` and `vocab_size` rows of the tables are what
this chip HOLDS. A KDA layer's recurrence is counted in its
POSITION-BY-POSITION form, the work no implementation can avoid: a
chunked form multiplies more (its intra-chunk products, its solve) and
none of that is required. Recomputed operations (remat) do not count;
bytes are the least a kernel must move. Each function has a hand-worked
case in tests/chipbench.
"""

from __future__ import annotations

from chipbench import costs

GQA, KDA = "gqa", "kda"


def layers(shape: dict) -> list:
    """The kind of each layer this chip runs."""
    return [GQA if l in shape["gqa_layers"] else KDA for l in range(shape["num_hidden_layers"])]


def count(shape: dict, kind: str) -> int:
    return layers(shape).count(kind)


def state_elements(shape: dict) -> int:
    """Elements of one position's state over the held heads: heads x d x d."""
    lin = shape["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication: a KDA mixer
    (q, k, v, o over the held heads, the decay's and the gate's low-rank
    pairs, beta), the GQA mixer (q, its gate, k, v, o), a router, the
    shared expert and ONE routed expert (three matrices each), the head
    over the held columns. The embedding is a gather; the convolution's
    taps, the norms and the gates are elementwise: none counts."""
    d, lin = shape["hidden_size"], shape["linear_attn_config"]
    wide, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    hd, h, kv = shape["head_dim"], shape["num_attention_heads"], shape["num_key_value_heads"]
    f = shape["moe_intermediate_size"]
    return {"kda": 4 * d * wide + 2 * r * (d + wide) + d * lin["num_heads"],
            "gqa": d * hd * (3 * h + 2 * kv),
            "router": d * shape["published"]["n_routed_experts"],
            "shared": 3 * d * f * shape["n_shared_experts"],
            "expert": 3 * d * f,
            "head": d * shape["vocab_size"]}


def num_params(shape: dict) -> int:
    """Every parameter of the tree the program holds for this shape."""
    d, lin = shape["hidden_size"], shape["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    p = matmul_params(shape)
    own = {KDA: p["kda"] + 3 * lin["short_conv_kernel_size"] * wide + lin["num_heads"]
           + 2 * wide + lin["head_dim"], GQA: p["gqa"]}
    experts = (p["router"] + shape["published"]["n_routed_experts"] + p["shared"]
               + shape["n_routed_experts"] * p["expert"])
    return 2 * shape["vocab_size"] * d + d + sum(own[kind] + experts + 2 * d
                                                 for kind in layers(shape))


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part: 2 a matmul
    parameter; the GQA layer's scores 4 x head_dim a visible pair and held
    head, averaged over the sequence; a KDA layer's recurrence 7 an
    element of the held heads' state (the decay 1; k^T S, the write k u^T
    and q^T S 2 each); the routed experts `num_experts_per_tok` x
    `held_share` (the share of a step's pairs routed to held experts)
    experts a token."""
    p = matmul_params(shape)
    n = {kind: count(shape, kind) for kind in (GQA, KDA)}
    every = n[GQA] + n[KDA]
    return {"kda.proj": n[KDA] * 2.0 * p["kda"],
            "kda.scan": n[KDA] * 7.0 * state_elements(shape),
            "gqa.proj": n[GQA] * 2.0 * p["gqa"],
            "gqa.scores": n[GQA] * costs.attn_flops_causal(
                {**shape, "num_hidden_layers": 1}, seq_len) / seq_len,
            "experts.router": every * 2.0 * p["router"],
            "experts.shared": every * 2.0 * p["shared"],
            "experts.routed": every * 2.0 * p["expert"] * shape["num_experts_per_tok"]
            * held_share,
            "head": 2.0 * p["head"]}


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward: three times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, held_share).values())


def scan_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """Operations and least bytes of the KDA rule over ALL the KDA layers
    at [batch, seq_len], forward and backward apart, in the
    position-by-position form S_t = (I - beta k k^T) Diag(e^g) S_{t-1} +
    beta k v^T, o_t = S_t^T q_t.

    FORWARD, an element of the state [d, d] a held head and position: the
    decay (1), k^T S (2), the write k u^T (2), q^T S (2): 7. It reads q,
    k, v [d] in bf16 and the decay g [d] and beta in float32 a head and
    position (g is as wide as k: what a per-channel decay costs in bytes)
    and writes o [d] in bf16 (the least a kernel that takes the
    convolution's output must move).
    BACKWARD, reverse mode through the same recurrence: two gradient
    products for each of the forward's three (12) and the decay's two
    (dS <- e^g dS 1, dg = <dS, S> 1): 14 (the states made again do not
    count: recompute). It reads q, k, v, g, beta and dO and writes dq, dk,
    dv, dg, dbeta."""
    n = count(shape, KDA)
    lin = shape["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    positions = n * batch * seq_len
    inputs = positions * h * (3 * d * 2 + d * 4 + 4)
    o = positions * h * d * 2
    elements = positions * state_elements(shape)
    return {"layers": n, "fwd_flops": 7.0 * elements, "bwd_flops": 14.0 * elements,
            "fwd_bytes": inputs + o, "bwd_bytes": 2 * inputs + o}


def flash_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """costs.flash_cost (one layer at the held `num_attention_heads` /
    `num_key_value_heads` heads of `head_dim`, causal) times the GQA
    layers this chip runs."""
    n = count(shape, GQA)
    return {"layers": n, **{k: n * v for k, v in costs.flash_cost(shape, batch, seq_len).items()}}


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over the
    `rows` (token, expert) pairs that were routed to held experts: three
    matmuls forward (gate, up, down) and six backward, 2 * rows * D * F
    FLOPs each; each reads its two operands and writes its result once.
    Pairs routed elsewhere are no row of any of them."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["n_routed_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}

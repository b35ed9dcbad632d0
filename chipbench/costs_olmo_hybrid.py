"""Operation and byte counts of an Olmo-Hybrid decoder (`model_type`
olmo_hybrid), from shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS, not
what a compiler emitted. `shape` is the configuration file's dict (HF
key names): the first `num_hidden_layers` entries of `layer_types` say
each layer's mixer; `vocab_size` rows of the embedding and columns of
the head are held. A linear layer's recurrence is counted in its
POSITION-BY-POSITION form, the work no implementation can avoid: a
chunked form multiplies more (its intra-chunk products and its solve)
and none of that is required. Recomputed operations (remat) do not
count; bytes are the least a kernel must move. Each function has a
hand-worked case in tests/chipbench.
"""

from __future__ import annotations

from chipbench import costs

FULL, LINEAR = "full_attention", "linear_attention"


def layers(shape: dict) -> list:
    """The kind of each layer this chip runs."""
    return list(shape["layer_types"][:shape["num_hidden_layers"]])


def state_elements(shape: dict) -> int:
    """Elements of one position's state over the heads: heads x dk x dv."""
    return (shape["linear_num_value_heads"] * shape["linear_key_head_dim"]
            * shape["linear_value_head_dim"])


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication: a linear
    mixer (q, k, v, the output gate, the decay's and beta's projections,
    o), a full mixer (q, k, v, o), the SwiGLU, the head over the held
    columns. The embedding is a gather; the convolution's taps, the norms
    and the gates are elementwise: none counts."""
    d, h = shape["hidden_size"], shape["linear_num_value_heads"]
    dk, dv = shape["linear_key_head_dim"], shape["linear_value_head_dim"]
    return {"linear": d * h * (2 * dk + 3 * dv + 2), "full": 4 * d * d,
            "ffn": 3 * d * shape["intermediate_size"], "head": d * shape["vocab_size"]}


def num_params(shape: dict) -> int:
    """Every parameter of the tree the program holds for this shape."""
    d, h, K = shape["hidden_size"], shape["linear_num_value_heads"], shape["linear_conv_kernel_dim"]
    dk, dv = shape["linear_key_head_dim"], shape["linear_value_head_dim"]
    p = matmul_params(shape)
    total = 2 * shape["vocab_size"] * d + d
    for kind in layers(shape):
        total += p["ffn"] + 2 * d
        if kind == LINEAR:
            total += p["linear"] + K * h * (2 * dk + dv) + 2 * h + dv
        else:
            total += p["full"] + 2 * d
    return total


def forward_flops_per_token(shape: dict, seq_len: int) -> dict:
    """Forward FLOPs a token requires, by part: 2 a matmul parameter; a
    full layer's scores 4 x head_dim a visible pair and head, averaged
    over the sequence; a linear layer's recurrence 6 an element of the
    state (k^T S, the write k u^T, q^T S: 2 each)."""
    p = matmul_params(shape)
    out = {"linear.proj": 0.0, "linear.scan": 0.0, "full.proj": 0.0, "full.scores": 0.0,
           "ffn": 0.0, "head": 2.0 * p["head"]}
    for kind in layers(shape):
        out["ffn"] += 2.0 * p["ffn"]
        if kind == LINEAR:
            out["linear.proj"] += 2.0 * p["linear"]
            out["linear.scan"] += 6.0 * state_elements(shape)
        else:
            out["full.proj"] += 2.0 * p["full"]
            out["full.scores"] += costs.attn_flops_causal(
                {**shape, "num_hidden_layers": 1}, seq_len) / seq_len
    return out


def train_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len).values())


def scan_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """Operations and least bytes of the gated delta rule over ALL the
    linear layers at [batch, seq_len], forward and backward apart, in the
    position-by-position form: forward 6 FLOPs an element of the state
    and position; it reads q, k [dk], v [dv] in bf16 and g, beta in
    float32 a head and position and writes o [dv] in bf16. Backward: two
    gradient products for each of the forward's three (12 an element); it
    reads q, k, v, g, beta and dO and writes dq, dk, dv, dg, dbeta."""
    n = sum(1 for kind in layers(shape) if kind == LINEAR)
    h = shape["linear_num_value_heads"]
    dk, dv = shape["linear_key_head_dim"], shape["linear_value_head_dim"]
    positions = n * batch * seq_len
    inputs = positions * h * ((2 * dk + dv) * 2 + 2 * 4)
    o = positions * h * dv * 2
    fwd = 6.0 * positions * state_elements(shape)
    return {"layers": n, "fwd_flops": fwd, "bwd_flops": 2.0 * fwd,
            "fwd_bytes": inputs + o, "bwd_bytes": 2 * inputs + o}


def flash_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """costs.flash_cost (one layer at `num_attention_heads` /
    `num_key_value_heads` heads of hidden_size / heads, causal) times the
    full layers this chip runs."""
    n = sum(1 for kind in layers(shape) if kind == FULL)
    return {"layers": n, **{k: n * v for k, v in costs.flash_cost(shape, batch, seq_len).items()}}

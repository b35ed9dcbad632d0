"""Operation and byte counts of a Nemotron-H decoder (`model_type`
nemotron_h: the causal tower of Nemotron-Labs-TwoTower-30B-A3B), from
shapes.

What costs.py is for the dense decoder: what the algorithm NEEDS, not
what a compiler emitted. `shape` is the configuration file's dict (HF
key names): the first `num_hidden_layers` characters of
`hybrid_override_pattern` say each layer's ONE sublayer (M a Mamba-2
mixer, E an expert layer, * an attention layer); `n_routed_experts`
experts of `published.n_routed_experts` and `vocab_size` rows of the
embedding and columns of the head are held. A Mamba layer's scan is
counted in its POSITION-BY-POSITION form, the work no implementation can
avoid: a chunked form multiplies more (its intra-chunk products) and
none of that is required. Recomputed operations (remat) do not count;
bytes are the least a kernel must move. Each function has a hand-worked
case in tests/chipbench.
"""

from __future__ import annotations

from chipbench import costs

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def layers(shape: dict) -> str:
    """The kind of each layer this chip runs."""
    return shape["hybrid_override_pattern"][:shape["num_hidden_layers"]]


def count(shape: dict, kind: str) -> int:
    return layers(shape).count(kind)


def state_elements(shape: dict) -> int:
    """Elements of one position's state over the heads: heads x P x N."""
    return shape["mamba_num_heads"] * shape["mamba_head_dim"] * shape["ssm_state_size"]


def conv_channels(shape: dict) -> int:
    return (shape["mamba_num_heads"] * shape["mamba_head_dim"]
            + 2 * shape["n_groups"] * shape["ssm_state_size"])


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication: a Mamba mixer
    (the one input projection to z, xBC and dt, the output projection),
    an attention mixer (q, k, v, o), an expert layer's router, its shared
    expert and ONE routed expert (two matrices each: relu^2 has no
    gate), the head over the held columns. The embedding is a gather; the
    convolution's taps, the norms and the gates are elementwise: none
    counts."""
    d = shape["hidden_size"]
    inner = shape["mamba_num_heads"] * shape["mamba_head_dim"]
    hd, h, kv = shape["head_dim"], shape["num_attention_heads"], shape["num_key_value_heads"]
    return {"mamba": d * (inner + conv_channels(shape) + shape["mamba_num_heads"]) + inner * d,
            "attention": 2 * d * hd * (h + kv),
            "router": d * shape["published"]["n_routed_experts"],
            "shared": 2 * d * shape["moe_shared_expert_intermediate_size"],
            "expert": 2 * d * shape["moe_intermediate_size"],
            "head": d * shape["vocab_size"]}


def num_params(shape: dict) -> int:
    """Every parameter of the tree the program holds for this shape."""
    d, heads = shape["hidden_size"], shape["mamba_num_heads"]
    p = matmul_params(shape)
    own = {MAMBA: p["mamba"] + (shape["conv_kernel"] + 1) * conv_channels(shape) + 3 * heads
           + heads * shape["mamba_head_dim"],
           ATTENTION: p["attention"],
           EXPERTS: p["router"] + shape["published"]["n_routed_experts"] + p["shared"]
           + shape["n_routed_experts"] * p["expert"]}
    return 2 * shape["vocab_size"] * d + d + sum(own[kind] + d for kind in layers(shape))


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part: 2 a matmul
    parameter; an attention layer's scores 4 x head_dim a visible pair
    and head, averaged over the sequence; a Mamba layer's scan 5 an
    element of the state (the decay 1, the write x B^T and its add 2, the
    read-out H C 2); the routed experts `num_experts_per_tok` x
    `held_share` (the share of a step's pairs routed to held experts)
    experts a token."""
    p = matmul_params(shape)
    n = {kind: count(shape, kind) for kind in (MAMBA, EXPERTS, ATTENTION)}
    return {"mamba.proj": n[MAMBA] * 2.0 * p["mamba"],
            "mamba.scan": n[MAMBA] * 5.0 * state_elements(shape),
            "attention.proj": n[ATTENTION] * 2.0 * p["attention"],
            "attention.scores": n[ATTENTION] * costs.attn_flops_causal(
                {**shape, "num_hidden_layers": 1}, seq_len) / seq_len,
            "experts.router": n[EXPERTS] * 2.0 * p["router"],
            "experts.shared": n[EXPERTS] * 2.0 * p["shared"],
            "experts.routed": n[EXPERTS] * 2.0 * p["expert"] * shape["num_experts_per_tok"]
            * held_share,
            "head": 2.0 * p["head"]}


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward: three times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, held_share).values())


def scan_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """Operations and least bytes of the selective scan over ALL the Mamba
    layers at [batch, seq_len], forward and backward apart, in the
    position-by-position form H_t = a_t H_{t-1} + (dt x)_t B_t^T, y_t =
    H_t C_t (+ D x_t, elementwise: not counted).

    FORWARD, an element of the state [P, N] a head and position: the decay
    a_t H (1), the write's product and its add (2), the read-out's product
    and its add (2): 5. It reads x [P] a head and B, C [N] a GROUP in
    bf16 and dt in float32, and writes y [P] in bf16 (the least a kernel
    that takes the projection's bf16 output must move).
    BACKWARD, reverse mode through the same recurrence with dH the state's
    cotangent: dH += dy_t C_t^T (2), dC_t = H_t^T dy_t (2), d(dt x)_t =
    dH B_t (2), dB_t = dH^T (dt x)_t (2), da_t = <dH, H_{t-1}> (2), dH <-
    a_t dH (1): 11 (the states H made again do not count: recompute). It
    reads x, B, C, dt and dy and writes dx, dB, dC, ddt."""
    n = count(shape, MAMBA)
    heads, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    gn = shape["n_groups"] * shape["ssm_state_size"]
    positions = n * batch * seq_len
    inputs = positions * ((heads * p + 2 * gn) * 2 + heads * 4)
    y = positions * heads * p * 2
    elements = positions * state_elements(shape)
    return {"layers": n, "fwd_flops": 5.0 * elements, "bwd_flops": 11.0 * elements,
            "fwd_bytes": inputs + y, "bwd_bytes": 2 * inputs + y}


def flash_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """costs.flash_cost (one layer at `num_attention_heads` /
    `num_key_value_heads` heads of `head_dim`, causal) times the attention
    layers this chip runs."""
    n = count(shape, ATTENTION)
    return {"layers": n, **{k: n * v for k, v in costs.flash_cost(shape, batch, seq_len).items()}}


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over the
    `rows` (token, expert) pairs that were routed to held experts: TWO
    matmuls forward (up, down: relu^2 has no gate) and four backward, 2 *
    rows * D * F FLOPs each; each reads its two operands and writes its
    result once. Pairs routed elsewhere are no row of any of them."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["n_routed_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 2 * flops, "bwd_flops": 4 * flops,
            "fwd_bytes": 2 * nbytes, "bwd_bytes": 4 * nbytes}

"""Operation and byte counts of a Granite 4.0-H decoder (`model_type`
granitemoehybrid: Mamba-2 mixers in `mamba_n_groups` groups and GQA
layers without a rotary by `layer_types`, each over a dense SwiGLU, a
tied table) trained on PACKED DOCUMENTS, from shapes and from the
batch's own boundaries.

What costs.py is for the dense decoder: what the algorithm NEEDS, not
what a compiler emitted. `shape` is the configuration file's dict (HF
key names): the first `num_hidden_layers` entries of `layer_types` say
each layer's mixer; `vocab_size` rows of the tied table are held. A
Mamba layer's scan is counted in its POSITION-BY-POSITION form, the work
no implementation can avoid, documents or not: a reset saves no product
of the recurrence (a position still decays, writes and reads its state
once), and a chunked form's intra-chunk products and masks are none of
it. Attention is counted over the VISIBLE (query, key) pairs, which
packing cuts: a query sees the keys of its own document that are not
after it, sum over the documents of n (n + 1) / 2, read from the batch
(`run["packed"]`, runners/train_reference_granite_hybrid.py); a kernel
that walks key blocks of other documents does work that is not required.
Recomputed operations (the block's forward made again under remat
"full") do not count; bytes are the least a kernel must move. Each
function has a hand-worked case in tests/chipbench.
"""

from __future__ import annotations

MAMBA, ATTENTION = "mamba", "attention"


def layers(shape: dict) -> list:
    """The kind of each layer this chip runs."""
    return shape["layer_types"][:shape["num_hidden_layers"]]


def count(shape: dict, kind: str) -> int:
    return layers(shape).count(kind)


def head_dim(shape: dict) -> int:
    return shape["hidden_size"] // shape["num_attention_heads"]


def state_elements(shape: dict) -> int:
    """Elements of one position's state over the heads: heads x P x N."""
    return shape["mamba_n_heads"] * shape["mamba_d_head"] * shape["mamba_d_state"]


def conv_channels(shape: dict) -> int:
    return (shape["mamba_n_heads"] * shape["mamba_d_head"]
            + 2 * shape["mamba_n_groups"] * shape["mamba_d_state"])


def matmul_params(shape: dict) -> dict:
    """Parameters a token meets in a matrix multiplication: a Mamba mixer
    (the one input projection to z, xBC and dt, the output projection), an
    attention mixer (q, k, v, o), a layer's SwiGLU (three matrices), the
    head over the held rows of the tied table. The embedding is a gather;
    the convolution's taps, the norms, the gates and the four multipliers
    are elementwise: none counts."""
    d = shape["hidden_size"]
    inner = shape["mamba_n_heads"] * shape["mamba_d_head"]
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    return {"mamba": d * (inner + conv_channels(shape) + shape["mamba_n_heads"]) + inner * d,
            "attention": 2 * d * head_dim(shape) * (h + kv),
            "swiglu": 3 * d * shape["shared_intermediate_size"],
            "head": d * shape["vocab_size"]}


def num_params(shape: dict) -> int:
    """Every parameter of the tree the program holds for this shape (the
    table ONCE: it is tied)."""
    d, heads = shape["hidden_size"], shape["mamba_n_heads"]
    p = matmul_params(shape)
    own = {MAMBA: p["mamba"] + (shape["mamba_d_conv"] + 1) * conv_channels(shape) + 3 * heads
           + heads * shape["mamba_d_head"],
           ATTENTION: p["attention"]}
    return shape["vocab_size"] * d + d + sum(own[kind] + p["swiglu"] + 2 * d
                                             for kind in layers(shape))


def forward_flops_per_token(shape: dict, seq_len: int, visible_pairs: float) -> dict:
    """Forward FLOPs a token requires OF THIS CHIP, by part: 2 a matmul
    parameter; an attention layer's scores 4 x head_dim a VISIBLE pair
    and head (`visible_pairs` of one sequence of `seq_len`), averaged over
    the sequence; a Mamba layer's scan 5 an element of the state (the
    decay 1, the write x B^T and its add 2, the read-out H C 2)."""
    p = matmul_params(shape)
    n = {kind: count(shape, kind) for kind in (MAMBA, ATTENTION)}
    return {"mamba.proj": n[MAMBA] * 2.0 * p["mamba"],
            "mamba.scan": n[MAMBA] * 5.0 * state_elements(shape),
            "attention.proj": n[ATTENTION] * 2.0 * p["attention"],
            "attention.scores": n[ATTENTION] * shape["num_attention_heads"] * 4.0
            * head_dim(shape) * visible_pairs / seq_len,
            "swiglu": (n[MAMBA] + n[ATTENTION]) * 2.0 * p["swiglu"],
            "head": 2.0 * p["head"]}


def train_flops_per_token(shape: dict, seq_len: int, visible_pairs: float) -> float:
    """Forward + backward: three times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, visible_pairs).values())


def scan_cost(shape: dict, batch: float, seq_len: int) -> dict:
    """Operations and least bytes of the selective scan over ALL the Mamba
    layers at [batch, seq_len], forward and backward apart, in the
    position-by-position form H_t = a_t H_{t-1} + (dt x)_t B_t^T, y_t =
    H_t C_t (+ D x_t, elementwise: not counted), with or without resets
    (the module's docstring): costs_nemotron_h.scan_cost's counts at this
    family's key names. FORWARD 5 an element of the state a position;
    reads x [P] a head and B, C [N] a GROUP in bf16 and dt in float32,
    writes y in bf16. BACKWARD 11; reads x, B, C, dt and dy and writes dx,
    dB, dC, ddt."""
    n = count(shape, MAMBA)
    heads, p = shape["mamba_n_heads"], shape["mamba_d_head"]
    gn = shape["mamba_n_groups"] * shape["mamba_d_state"]
    positions = n * batch * seq_len
    inputs = positions * ((heads * p + 2 * gn) * 2 + heads * 4)
    y = positions * heads * p * 2
    elements = positions * state_elements(shape)
    return {"layers": n, "fwd_flops": 5.0 * elements, "bwd_flops": 11.0 * elements,
            "fwd_bytes": inputs + y, "bwd_bytes": 2 * inputs + y}


def flash_cost(shape: dict, batch: float, seq_len: int, visible_pairs: float,
               io_bytes: int = 2) -> dict:
    """costs.flash_cost's counts over the pairs a packed batch leaves
    VISIBLE (`visible_pairs` a sequence), times the attention layers this
    chip runs: forward 4 x head_dim FLOPs a visible pair and head, reads
    Q, K, V and writes O; backward 2.5 times the operations, reads Q, K,
    V, O, dO and writes dQ, dK, dV. The bytes are the whole sequence's
    whatever the documents: every row and key is read once."""
    n, hd = count(shape, ATTENTION), head_dim(shape)
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    fwd = n * batch * h * 4.0 * hd * visible_pairs
    q_bytes = n * batch * seq_len * h * hd * io_bytes
    kv_bytes = n * batch * seq_len * kv * hd * io_bytes
    return {"layers": n, "fwd_flops": fwd, "bwd_flops": 2.5 * fwd,
            "fwd_bytes": 2 * q_bytes + 2 * kv_bytes, "bwd_bytes": 4 * q_bytes + 4 * kv_bytes}

"""Operation and byte counts of ONE CHIP'S SHARE of an SDAR decoder
(`model_type` sdar_moe) TRAINED BY BLOCK DIFFUSION, from shapes.

What costs_mellum2.py is for Mellum2: what the algorithm NEEDS of this
chip, not what a compiler emitted nor which tiles a kernel visits. `shape`
is the configuration file's dict (HF key names). A step of L DATA tokens
runs 2L rows, the clean copy and the noised copy, through every layer:
both copies' projections, router and experts count, because the method
requires them; the scores count the L (L + beta) (row, key) pairs a head
that the block-diffusion mask leaves visible (clean -> clean L (L + beta)
/ 2, noised -> clean L (L - beta) / 2, noised -> own block L beta), and the
head the L noised rows. Per DATA token: `train_tok_s` counts the L tokens
of a step, so the second copy shows as operations a token, and a later
change that made it cheaper shows as less time for the same count.
`num_experts` experts are HELD here of `published.num_experts`; of a row's
`num_experts_per_tok` pairs only those whose expert is held are
multiplied (`held_share`, measured: the step's statistics). The norms, the
rotary, the corruption, the merge and the embedding's gather are
elementwise: none counts. Recomputed operations do not count; bytes are
the least a kernel must move. Each function has a hand-worked case in
tests/chipbench."""

from __future__ import annotations


def block_length(shape: dict) -> int:
    return shape["block_diffusion"]["block_length"]


def visible_pairs(shape: dict, seq_len: int) -> float:
    """(row, key) pairs a head of one sequence of `seq_len` DATA tokens
    scores under the mask: L (L + beta)."""
    return float(seq_len) * (seq_len + block_length(shape))


def matmul_params(shape: dict) -> dict:
    """Parameters a row meets in a matrix multiplication here: a layer's
    attention (q, k, v, o), its router, ONE routed expert, the head over
    the held columns."""
    d, hd = shape["hidden_size"], shape["head_dim"]
    heads, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    return {"attention": d * hd * (2 * heads + 2 * kv),
            "router": d * shape["published"]["num_experts"],
            "expert": 3 * d * shape["moe_intermediate_size"], "head": d * shape["vocab_size"]}


def forward_flops_per_token(shape: dict, seq_len: int, held_share: float) -> dict:
    """Forward FLOPs a DATA token requires OF THIS CHIP, by part: 2 a
    matmul parameter, twice over for the parts both copies run; scores 4 x
    head_dim a visible pair and head, over the sequence's tokens; the head
    once (the noised rows)."""
    p, n = matmul_params(shape), shape["num_hidden_layers"]
    return {"attention": n * 2 * 2.0 * p["attention"],
            "scores": n * 4.0 * shape["head_dim"] * shape["num_attention_heads"]
            * visible_pairs(shape, seq_len) / seq_len,
            "router": n * 2 * 2.0 * p["router"],
            "routed": n * 2 * 2.0 * held_share * shape["num_experts_per_tok"] * p["expert"],
            "head": 2.0 * p["head"]}


def train_flops_per_token(shape: dict, seq_len: int, held_share: float) -> float:
    """Forward + backward FLOPs a DATA token requires OF THIS CHIP: three
    times the forward's. Recompute is not counted."""
    return 3.0 * sum(forward_flops_per_token(shape, seq_len, held_share).values())


def flash_cost(shape: dict, batch: float, seq_len: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of the masked attention over ALL the
    layers at [batch, 2 x seq_len rows], forward and backward apart
    (costs.flash_cost's counts): forward 2 matmuls of 2 x head_dim FLOPs a
    VISIBLE pair and head, reads Q (2L rows), K, V (2L keys), writes O;
    backward 5 such matmuls, reads Q, K, V, O, dO and writes dQ, dK, dV,
    each once. The count does not depend on which tiles a program visits:
    a kernel that walks whole sub-tiles cannot reach 100% of it."""
    hd, heads, kv = shape["head_dim"], shape["num_attention_heads"], shape["num_key_value_heads"]
    n, rows = shape["num_hidden_layers"], 2 * seq_len
    fwd = n * batch * heads * 4.0 * hd * visible_pairs(shape, seq_len)
    q_bytes = n * batch * rows * heads * hd * io_bytes
    kv_bytes = n * batch * rows * kv * hd * io_bytes
    return {"layers": n, "fwd_flops": fwd, "bwd_flops": 2.5 * fwd,
            "fwd_bytes": 2 * q_bytes + 2 * kv_bytes,     # Q, O + K, V
            "bwd_bytes": 4 * q_bytes + 4 * kv_bytes}     # Q, O, dO, dQ + K, V, dK, dV


def grouped_matmul_cost(shape: dict, rows: float, io_bytes: int = 2) -> dict:
    """Operations and least bytes of ONE layer's grouped matmuls over the
    `rows` (row, expert) pairs that were routed to held experts: three
    matmuls forward and six backward, 2 * rows * D * F FLOPs each (K 2048
    / N 768 and its transpose); each reads its two operands and writes its
    result once."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    flops = 2.0 * rows * d * f
    nbytes = float(io_bytes) * (rows * d + rows * f + shape["num_experts"] * d * f)
    return {"rows": rows, "fwd_flops": 3 * flops, "bwd_flops": 6 * flops,
            "fwd_bytes": 3 * nbytes, "bwd_bytes": 6 * nbytes}


def tiles_floor_pct(seq_len: int, tile: int = 512) -> float:
    """The least share of a causal walk's tiles over 2L rows and keys that
    ONE walk over them must visit under the mask, at square tiles that
    whole blocks divide: (n^2 + 2n) / (2n^2 + n), n = L / tile."""
    n = seq_len / tile
    return 100.0 * (n * n + 2 * n) / (2 * n * n + n)

"""The plain reference: SDAR's decoder (`model_type` sdar_moe: the
Qwen3-MoE block in every layer, full attention at ONE head count with an
RMSNorm a head on q and k, the plain rotary, softmax top-k routed experts
chosen with a selection bias, no shared expert, untied head) TRAINED BY
BLOCK DIFFUSION, in straightforward jax.numpy.

float32 throughout (`F32`: the one-thing-wrong tool puts bfloat16 there,
and then EVERYTHING is bfloat16: the stream, the norms, both softmaxes,
the router's probabilities and its choice, the logits; only the rotary's
tables and the corruption are made in float32),
`jax.default_matmul_precision("highest")`, no kernel, no sort and no
grouped matmul: every held expert is applied to every row and the result
masked by the routing; attention is an explicit masked softmax over ALL
2L keys under the dense boolean mask of the four rules below, a BLOCK of
`QUERY_BLOCK` queries and one head at a time, so that at 16,384 rows one
[1024, 16384] score matrix is alive; the head's logits and the loss go a
block of rows at a time. It takes the program's parameter tree and a
configuration file's sizes (HF key names). It imports nothing from
ray_tpu. `grads` is reverse mode through the same functions; the
`jax.checkpoint`s change no number.

THE TREE is models/laguna.py's with a period of ONE layer: `embed` [V, D];
`lm_head` [D, V]; `final_norm`; `layers`: `router_bias` [layers, E],
`period` {"0": the block, leaves stacked over the layers}. A block's
leaves: ln1, wq [D, H hd], wk, wv [D, KV hd], q_norm, k_norm [hd], wo
[H hd, D], ln2, router [D, E], w_gate / w_up [held, D, F], w_down [held,
F, D].

THE SHARE. `num_experts` in the file is how many experts are HELD here
(`deployment.first_expert_held` is the first of them); the router has
`published.num_experts` outputs and routes over all of them. `vocab_size`
rows of the embedding and columns of the head are held: ids, logits and
the loss are over that slice.

THE EQUATIONS (JetLM/SDAR-30B-A3B-Chat config.json; what it leaves open is
ASSUMED, the same in the program: the configuration file's `assumed`).
Data: a sequence x of L ids, blocks of beta = `block_diffusion.block_length`
positions, blk(i) = floor(i / beta).

  corruption  from a key made of the step count (0: the first step) and
              of the batch's own ids (`step_key`: key 0 folded with the
              count, then with the sum of id x (2 x index + 1) modulo
              2^32): one key for the levels, one for the draws
              (`jax.random.split`); a level t_b ~ U(0, 1) a block, p_b =
              (1 - eps) t_b + eps; position i of block b is masked when
              its own uniform draw is below p_b; a masked id becomes MASK
              = `vocab_size` - 1 (`corrupt`).
  input       2L rows: x, then the noised copy; row r is position r mod L
              (`positions`).
  visibility  between a row r and a key c, the same in every layer
              (`visible`): clean -> clean iff blk(c) <= blk(r); clean ->
              noised never; noised -> clean iff blk(c) < blk(r); noised ->
              noised iff blk(c) = blk(r).
  attention   x = RMSNorm(h); q = x Wq, k = x Wk, v = x Wv, no bias; q_h <-
              RMSNorm(q_h) w_q, k_h <- RMSNorm(k_h) w_k over each head's
              channels (`head_norm`); the rotary at `rope_theta` on every
              channel, channel i paired with i + hd / 2; scores q k^T /
              sqrt(hd), query head n on key head floor(n / (H / KV)); o =
              softmax over the visible keys; h += concat(o) Wo.
  router      p = softmax(x W_r) over all E in float32; the
              `num_experts_per_tok` largest of p + b; weights p[chosen] /
              sum of p[chosen] (`norm_topk_prob`). No scaling, no shared
              expert, no auxiliary loss.
  expert      W_down(silu(x W_gate) * (x W_up)).
  loss        the final RMSNorm and the untied head on the L NOISED rows;
              the row of position i predicts x_i ITSELF (`target_of`);
              l = (1 / L) sum_b w_b sum_{i in b, counted} -log
              softmax(z_i)[x_i] in float32, w_b = 1 / p_b (`block_weight`),
              counted = masked (`counted`).

`first_attention` is the masked attention ALONE, for the runner's reading
of it where the rules weigh most: layer 0's q, k, v of one sequence, the
attention of a set of rows under the dense mask and a cotangent of it
pulled back (`qkv` and `attend` are what `attention` itself is made of).

chipbench/tools/sdar_wrong.py patches the small functions named above to
make the reference wrong in one thing at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 1024   # queries a block of the attention
ROW_BLOCK = 2048     # rows a block of the head's logits


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def head_norm(x, scale, eps):
    """x [S, heads, hd]: an RMSNorm over each head's channels, one learned [hd]."""
    return _rms_norm(x, scale, eps)


# -- the objective's own parts ------------------------------------------------------


def step_key(tokens, step: int = 0):
    """The key of the corruption of the batch `tokens` [B, L] in step `step`."""
    ids = tokens.reshape(-1).astype(jnp.uint32)
    checksum = jnp.sum(ids * (2 * jnp.arange(ids.size, dtype=jnp.uint32) + 1), dtype=jnp.uint32)
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0), step), checksum)


def corrupt(tokens, shape: dict, step: int = 0) -> dict:
    """tokens [B, L] -> {"noised" [B, L], "masked" bool [B, L], "p" float32
    [B, L / beta]}: the corruption of the batch in step `step`."""
    d = shape["block_diffusion"]
    beta, eps = d["block_length"], d["eps"]
    b, n = tokens.shape
    if n % beta:
        raise ValueError(f"{n} positions are not whole blocks of {beta}")
    k_level, k_mask = jax.random.split(step_key(tokens, step))
    p = (1.0 - eps) * jax.random.uniform(k_level, (b, n // beta)) + eps
    masked = jax.random.uniform(k_mask, (b, n)) < jnp.repeat(p, beta, axis=1)
    return {"noised": jnp.where(masked, jnp.int32(shape["vocab_size"] - 1), tokens),
            "masked": masked, "p": p}


def positions(n: int):
    """The position of each of the 2n rows: both copies of position i carry i."""
    return jnp.concatenate([jnp.arange(n), jnp.arange(n)])


def visible(row_noised, row_at, key_noised, key_at, beta: int):
    """Whether a row sees a key, from which copy each is of (`*_noised`)
    and its position in the sequence (`*_at`): the four rules."""
    row_block, key_block = row_at // beta, key_at // beta
    return jnp.where(key_noised, row_noised & (key_block == row_block),
                     jnp.where(row_noised, key_block < row_block, key_block <= row_block))


def block_weight(p):
    """The weight of a block's masked positions in the loss."""
    return 1.0 / p


def counted(masked):
    """The noised rows that enter the loss."""
    return masked


def target_of(tokens, targets):
    """What the noised row of position i predicts: x_i itself, not x_{i+1}."""
    return tokens


# -- the network --------------------------------------------------------------------


def rope_tables(theta: float, head_dim: int, at):
    """(cos, sin) [rows, hd / 2] at the rows' positions, always made in float32."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    ang = at.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, tables):
    cos, sin = (t[:, None, :].astype(x.dtype) for t in tables)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def qkv(h, lp, shape: dict):
    """h [2L, D] -> (q [2L, H, hd], k, v [2L, KV, hd]) of a layer: the norm,
    the three projections, the norm a head on q and k, the rotary at the
    rows' positions."""
    s = h.shape[0]
    heads, kv, hd = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    eps = shape["rms_norm_eps"]
    x = _rms_norm(h, lp["ln1"], eps)
    tables = rope_tables(shape["rope_theta"], hd, positions(s // 2))
    q = _rope(head_norm((x @ lp["wq"]).reshape(s, heads, hd), lp["q_norm"], eps), tables)
    k = _rope(head_norm((x @ lp["wk"]).reshape(s, kv, hd), lp["k_norm"], eps), tables)
    return q, k, (x @ lp["wv"]).reshape(s, kv, hd)


def attend(rows, q_rows, k, v, m: int, beta: int):
    """Head `m`'s attention of the rows numbered `rows` [Q] (q_rows [Q, H,
    hd]: theirs) over ALL 2L keys k, v [2L, KV, hd] under the dense boolean
    mask of the four rules -> [Q, hd]."""
    s, hd = k.shape[0], k.shape[-1]
    n, g = s // 2, m // (q_rows.shape[1] // k.shape[1])
    at = jnp.arange(s)
    scores = (q_rows[:, m] @ k[:, g].T).astype(F32) / jnp.sqrt(F32(hd))
    allowed = visible((rows >= n)[:, None], (rows % n)[:, None], (at >= n)[None, :],
                      (at % n)[None, :], beta)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return probs.astype(v.dtype) @ v[:, g]


def attention(h, lp, shape: dict):
    """The attention half of a layer on the 2L rows h [2L, D] -> h + attention."""
    s = h.shape[0]
    beta, heads, hd = (shape["block_diffusion"]["block_length"], shape["num_attention_heads"],
                       shape["head_dim"])
    q, k, v = qkv(h, lp, shape)
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} rows are not whole blocks of {block} queries")

    def one(i):
        b, m = i // heads, i % heads
        return attend(b * block + jnp.arange(block), jax.lax.dynamic_slice_in_dim(q, b * block, block),
                      k, v, m, beta)

    o = jax.lax.map(jax.checkpoint(one), jnp.arange((s // block) * heads))            # [B H, Q, hd]
    o = jnp.swapaxes(o.reshape(s // block, heads, block, hd), 1, 2).reshape(s, heads * hd)
    return h + o @ lp["wo"]


def first_attention(params, tokens, noised, rows, w, shape: dict, round_to):
    """The masked attention ALONE, where its rules weigh most: layer 0's
    q, k, v of one sequence ([L] ids and their noised copy), rounded to
    `round_to` (the dtype a program is handed them in), the attention of
    the rows numbered `rows` [Q] under the dense mask, and the cotangent w
    [Q, H, hd] of it pulled back -> ((q, k, v), {"o" [Q, H, hd], "dq" [Q,
    H, hd], "dk", "dv" [2L, KV, hd]})."""
    beta, heads = shape["block_diffusion"]["block_length"], shape["num_attention_heads"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[jnp.concatenate([tokens, noised])]
        q, k, v = qkv(h, blocks_of(params, shape)[0], shape)
        q, k, v = (x.astype(round_to).astype(x.dtype) for x in (q, k, v))

        def of_rows(q_rows, k, v):
            one = lambda m: attend(rows, q_rows, k, v, m, beta)  # noqa: E731
            return jnp.swapaxes(jax.lax.map(jax.checkpoint(one), jnp.arange(heads)), 0, 1)

        o, pull = jax.vjp(of_rows, q[rows], k, v)
        return (q, k, v), dict(zip(("o", "dq", "dk", "dv"), (o,) + pull(w.astype(o.dtype))))


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, lp, shape: dict):
    """x [S, D] (already normed) -> weights [S, E]: a chosen expert's
    renormalised probability, zero elsewhere."""
    probs = jax.nn.softmax((x @ lp["router"]).astype(F32), axis=-1)
    biased = probs + lp["router_bias"].astype(F32)
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    w = jnp.where(biased >= kth, probs, 0.0)
    return w / w.sum(axis=-1, keepdims=True) if shape["norm_topk_prob"] else w


def experts(h, lp, shape: dict):
    """The expert half of a layer on h [S, D] -> (h + the held experts'
    part of the routed sum, chosen [S, E] bool)."""
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    weights = route(x, lp, shape)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["num_experts"]].astype(x.dtype)

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                                   # w [S]
        return acc + w[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    return h + out, weights > 0


def blocks_of(params, shape: dict) -> list:
    """[a layer's params] in layer order, each with its row of the selection biases."""
    cast = lambda tree: jax.tree.map(lambda w: w.astype(F32), tree)  # noqa: E731
    layers, bias = params["layers"], params["layers"]["router_bias"].astype(F32)
    if set(layers) != {"router_bias", "period"} or set(layers["period"]) != {"0"}:
        raise ValueError("a stack of layers all alike: a period of one")
    stacked = layers["period"]["0"]
    n = jax.tree.leaves(stacked)[0].shape[0]
    if n != shape["num_hidden_layers"] or bias.shape[0] != n:
        raise ValueError("the parameter tree's depth is not the configuration's")
    return [{**cast(jax.tree.map(lambda w: w[l], stacked)), "router_bias": bias[l]}
            for l in range(n)]


def layer(h, lp, shape: dict):
    return experts(attention(h, lp, shape), lp, shape)


def head_loss(h, final_norm, lm_head, targets, weights, eps):
    """h [S, D] -> the weighted sum of the rows' cross-entropy (nats,
    float32) over the held slice of the vocabulary, a block of rows at a time."""
    s = h.shape[0]
    block = min(ROW_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} rows are not whole blocks of {block}")

    def rows(xs):
        hb, yb, wb = xs
        lg = (_rms_norm(hb, final_norm, eps) @ lm_head).astype(F32)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0].astype(jnp.float32)
        return (wb * nll).sum()

    parts = (h.reshape(s // block, block, -1), targets.reshape(s // block, block),
             weights.reshape(s // block, block))
    return jax.lax.map(jax.checkpoint(rows), parts).sum()


def sequence(params, tokens, targets, noised, masked, p, shape: dict):
    """One sequence [L] with its corruption -> (the weighted summed
    cross-entropy of its noised rows, tokens per expert [layers, E] over
    both copies)."""
    n = tokens.shape[0]
    if 2 * n > shape["max_position_embeddings"]:
        raise ValueError(f"{2 * n} rows: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"]:
        raise ValueError("an untied head, as published")
    beta = shape["block_diffusion"]["block_length"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[jnp.concatenate([tokens, noised])]
        counts = []
        for lp in blocks_of(params, shape):
            h, chosen = jax.checkpoint(lambda h, lp: layer(h, lp, shape))(h, lp)
            counts.append(chosen.sum(0))
        weights = counted(masked) * jnp.repeat(block_weight(p), beta)
        nll = head_loss(h[n:], params["final_norm"].astype(F32), params["lm_head"].astype(F32),
                        target_of(tokens, targets), weights.astype(jnp.float32),
                        shape["rms_norm_eps"])
        return nll, jnp.stack(counts)


def loss_parts(params, tokens, targets, shape: dict, step: int = 0) -> dict:
    """tokens/targets [B, L] -> {"loss", "tokens_per_expert" [layers, E],
    "masked": how many positions were masked}, sequence by sequence."""
    drawn = corrupt(tokens, shape, step)
    one = jax.jit(lambda p, *a: sequence(p, *a, shape))
    parts = [one(params, tokens[b], targets[b], drawn["noised"][b], drawn["masked"][b],
                 drawn["p"][b]) for b in range(tokens.shape[0])]
    return {"loss": sum(p[0] for p in parts) / tokens.size,
            "tokens_per_expert": sum(p[1] for p in parts),
            "masked": drawn["masked"].sum()}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, L] in its first step."""
    return loss_parts(params, tokens, targets, shape)["loss"]


def grads(params, tokens, targets, shape: dict, step: int = 0):
    """The gradient of `loss` by every leaf of the parameter tree, float32:
    reverse mode through the equations above, sequence by sequence."""
    drawn = corrupt(tokens, shape, step)
    one = jax.jit(jax.grad(lambda p, *a: sequence(p, *a, shape)[0]))
    total = None
    for b in range(tokens.shape[0]):
        g = one(params, tokens[b], targets[b], drawn["noised"][b], drawn["masked"][b],
                drawn["p"][b])
        total = g if total is None else jax.tree.map(jnp.add, total, g)
    return jax.tree.map(lambda g: (g / tokens.size).astype(jnp.float32), total)

"""The plain reference: the language model of Keye-VL-2.0 (`model_type`
KeyeVL2: grouped-query attention with an RMSNorm a head on q and k, over
the keys a learned indexer selects for each query; softmax top-k routed
experts chosen with a selection bias and renormalised; untied head) in
straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel, no bisection, no packed mask, no grouped matmul: the selection
is a stable sort of a query's index scores, every held expert is applied
to every token and the result masked by the routing, and attention is one
[queries, S] score matrix a head over a BLOCK of queries at a time (so
it fits beside the step on the chip at 8192 tokens). It takes the
program's parameter tree and a configuration file's sizes (HF key
names). It imports nothing from ray_tpu.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `layers`,
leaves stacked over the layers: ln1, wq [D, H hd], wk, wv [D, G hd],
wo [H hd, D], q_norm, k_norm [hd], idx_wq [D, J c], idx_wk [D, c],
idx_ww [D, J], idx_norm_w, idx_norm_b [c], ln2, router [D, E],
router_bias [E], w_gate / w_up [held, D, F], w_down [held, F, D].

THE SHARE. `num_experts` in the file is how many experts are HELD here
(`deployment.first_expert_held` is the first of them); the router has
`published.num_experts` outputs and routes over all of them. A (token,
expert) pair whose expert is not held gets nothing from this chip, and
that partial result goes on to the next layer. `vocab_size` rows of the
embedding and columns of the head are held: ids, logits and the loss
are over that slice.

The equations (Kwai-Keye/Keye-VL-2.0-30B-A3B config.json with its
`sa_config`; what it leaves open is ASSUMED, the same in the program:
the configuration file's `assumed`); x = RMSNorm(h), eps `rms_norm_eps`,
t a query and s <= t a key, H = `num_attention_heads` over G =
`num_key_value_heads` heads of `head_dim`, J = `indexer_num_heads` of c =
`indexer_head_dim`, k = `topk`:

  main       q_t,h = rope(norm_q((x_t Wq)_h)), k_s,g = rope(norm_k((x_s Wk)_g)),
             v_s,g = (x_s Wv)_g; norm an RMSNorm over a head's channels
             with one learned weight [hd] (ASSUMED: the Qwen3-MoE
             lineage); rotary at `rope_theta` on the whole head, channel i
             paired with i + hd / 2 (text: `mrope_section`'s three streams
             are equal and the sectioned rotary is this one);
  indexer    qI_t,j = rope((x_t W_Iq)_j), kI_s = rope(LayerNorm(x_s W_Ik))
             (weight and bias, eps 1e-6), rotary at `rope_theta` on all c
             channels, half-split (ASSUMED: DeepSeek-V3.2's public code);
             w_t = x_t W_Iw; I_t,s = sum_j w_t,j ReLU(qI_t,j . kI_s) / sqrt(J c);
  selection  S_t = every s <= t where t < k, else the k keys s <= t of
             largest I_t,s, equal scores to the lower s (per TOKEN:
             `q_chunk_size` / `kv_chunk_size` change no result);
  attend     o_t,h = sum_{s in S_t} softmax_{s in S_t}(q_t,h . k_s,g / sqrt(hd)) v_s,g;
             h += concat(o) Wo. No bias.
  router     p = softmax(x W_r) over all E; the `num_experts_per_tok`
             largest of p + b are chosen (b a selection bias that takes no
             gradient); weights p[chosen] / sum of p[chosen]
             (`norm_topk_prob`). No auxiliary loss, no shared expert.
  expert     W_down(silu(x W_gate) * (x W_up)).
  loss       final RMSNorm, the untied head, mean cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 1024  # queries a block of the index scores and of the attention


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, weight, bias, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _rope(x, theta: float):
    """x [S, heads, d]: every channel rotated by the row's position,
    channel i paired with i + d / 2."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(s, dtype=F32)[:, None] * inv[None, :])[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def index_scores(q_i, k_i, w_i):
    """q_i [Q, J, c], k_i [S, c], w_i [Q, J] -> I [Q, S]."""
    J, c = q_i.shape[1], q_i.shape[2]
    dots = jnp.einsum("qjc,sc->qjs", q_i, k_i)
    return jnp.einsum("qjs,qj->qs", jax.nn.relu(dots), w_i) / jnp.sqrt(F32(J * c)).astype(dots.dtype)


def select(index, first_row: int, topk: int):
    """index [Q, S], the block's first row -> selected [Q, S] bool: a row's
    visible keys (s <= t) where it has at most `topk`, else the `topk` of
    largest score, equal scores to the lower s (a stable sort)."""
    q, s = index.shape
    visible = jnp.arange(s)[None, :] <= first_row + jnp.arange(q)[:, None]
    order = jnp.argsort(jnp.where(visible, -index.astype(F32), jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)  # a key's place in its row's order
    return visible & (rank < topk)


def attention(h, lp, shape: dict):
    """The attention half of a layer on h [S, D] -> (h + attention over
    the selected keys, selected pairs)."""
    s = h.shape[0]
    hd, heads, kv = shape["head_dim"], shape["num_attention_heads"], shape["num_key_value_heads"]
    sa, theta, eps = shape["sa_config"], shape["rope_theta"], shape["rms_norm_eps"]
    J, c = sa["indexer_num_heads"], sa["indexer_head_dim"]
    x = _rms_norm(h, lp["ln1"], eps)
    q = _rope(_rms_norm((x @ lp["wq"]).reshape(s, heads, hd), lp["q_norm"], eps), theta)
    k = _rope(_rms_norm((x @ lp["wk"]).reshape(s, kv, hd), lp["k_norm"], eps), theta)
    v = (x @ lp["wv"]).reshape(s, kv, hd)
    q_i = _rope((x @ lp["idx_wq"]).reshape(s, J, c), theta)
    k_i = _rope(_layer_norm(x @ lp["idx_wk"], lp["idx_norm_w"], lp["idx_norm_b"])[:, None], theta)[:, 0]
    w_i = x @ lp["idx_ww"]
    group = heads // kv
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} tokens are not whole blocks of {block} queries")

    def one_block(b):
        rows = jax.lax.dynamic_slice_in_dim
        lo = b * block
        chosen = select(index_scores(rows(q_i, lo, block), k_i, rows(w_i, lo, block)),
                        lo, sa["topk"])

        def one_head(n):
            scores = (rows(q, lo, block)[:, n] @ k[:, n // group].T) / jnp.sqrt(F32(hd)).astype(q.dtype)
            probs = jax.nn.softmax(jnp.where(chosen, scores.astype(F32), -jnp.inf), axis=-1)
            return probs.astype(v.dtype) @ v[:, n // group]

        # head by head, so that only one [block, S] score matrix is alive at a time
        return jnp.swapaxes(jax.lax.map(one_head, jnp.arange(heads)), 0, 1), chosen.sum()

    o, pairs = jax.lax.map(one_block, jnp.arange(s // block))          # [blocks, block, H, hd]
    return h + o.reshape(s, heads * hd) @ lp["wo"], pairs.sum()


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, lp, shape: dict):
    """x [S, D] (already normed) -> weights [S, E]: a chosen expert's
    renormalised probability, zero elsewhere."""
    probs = jax.nn.softmax((x @ lp["router"]).astype(F32), axis=-1)
    biased = probs + lp["router_bias"].astype(F32)
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    w = jnp.where(biased >= kth, probs, 0.0)
    if shape["norm_topk_prob"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return w.astype(x.dtype)


def experts(h, lp, shape: dict):
    """The expert half of a layer on h [S, D] -> (h + the held experts'
    part of the routed sum, chosen [S, E] bool)."""
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    weights = route(x, lp, shape)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["num_experts"]]

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                                   # w [S]
        return acc + w[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    return h + out, weights > 0


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> (summed cross-entropy (nats) over the held slice
    of the vocabulary, tokens per expert [layers, E], selected pairs [layers])."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"] or shape["mlp_only_layers"] or shape["decoder_sparse_step"] != 1:
        raise ValueError("an untied head and every layer an expert layer, as published")
    layers = jax.tree.map(lambda w: w.astype(F32), params["layers"])
    if jax.tree.leaves(layers)[0].shape[0] != shape["num_hidden_layers"]:
        raise ValueError("the parameter tree's depth is not the configuration's")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        counts, pairs = [], []
        for l in range(shape["num_hidden_layers"]):
            lp = jax.tree.map(lambda w: w[l], layers)
            h, n = attention(h, lp, shape)
            h, chosen = experts(h, lp, shape)
            counts.append(chosen.sum(0))
            pairs.append(n)
        lg = (_rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
              @ params["lm_head"].astype(F32)).astype(jnp.float32)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return nll.sum(), jnp.stack(counts), jnp.stack(pairs)


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "tokens_per_expert" [layers, E],
    "selected_pairs" [layers]}, sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    parts = [one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])]
    return {"loss": sum(p[0] for p in parts) / tokens.size,
            "tokens_per_expert": sum(p[1] for p in parts),
            "selected_pairs": sum(p[2] for p in parts)}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: the head's mean cross-entropy
    (the configuration has no auxiliary loss)."""
    return loss_parts(params, tokens, targets, shape)["loss"]

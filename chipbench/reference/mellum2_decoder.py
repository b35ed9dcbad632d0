"""The plain reference: Mellum2's decoder (`model_type` mellum: three
sliding-window layers to one full-attention layer at ONE head count, an
RMSNorm a head on q and k, a rotary by layer type with YaRN over the whole
head of the full layers, softmax top-k routed experts chosen with a
selection bias in EVERY layer, no gate, no shared expert, no dense layer,
untied head) in straightforward jax.numpy.

float32 throughout (`F32`: the one-thing-wrong tool puts bfloat16 there,
and then EVERYTHING is bfloat16: the stream, the norms, both softmaxes,
the router's probabilities and its choice, the logits; only the rotary's
tables are made in float32, as positions past 256 have no bfloat16),
`jax.default_matmul_precision("highest")`, no kernel,
no sort and no grouped matmul: every held expert is applied to every
token and the result masked by the routing; attention is an explicit
masked softmax over ALL the keys, a BLOCK of `QUERY_BLOCK` queries and
one head at a time, so that at 16,384 positions one [1024, 16384] score
matrix is alive and 16,384 x 16,384 x 32 never stands whole; the head's
logits and the loss go a block of rows at a time for the same reason. It
takes the program's parameter tree and a configuration file's sizes (HF
key names). It imports nothing from ray_tpu. `grads` is reverse mode
through the same functions; the `jax.checkpoint`s (a layer, a (block,
head) of scores, an expert, a block of the head's rows) change no number
and are there so that it fits beside the program's gradient on the chip.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `layers`:
`router_bias` [layers, E] (every block's selection bias, in layer order),
`period` {"0": .., "1": ..} (the blocks of one period of layer kinds by
position, leaves stacked over the periods: layer l is position l mod P of
period l div P), `tail` {"0": ..} (unstacked: the layers after the last
whole period; may be absent). A block's leaves: ln1, wq [D, H hd], wk, wv
[D, KV hd], q_norm, k_norm [hd], wo [H hd, D], ln2, router [D, E], w_gate
/ w_up [held, D, F], w_down [held, F, D].

THE SHARE. `num_experts` in the file is how many experts are HELD here
(`deployment.first_expert_held` is the first of them); the router has
`published.num_experts` outputs and routes over all of them. A (token,
expert) pair whose expert is not held gets nothing from this chip, and
that partial result goes on to the next layer. `vocab_size` rows of the
embedding and columns of the head are held: ids, logits and the loss are
over that slice.

The equations (JetBrains/Mellum2-12B-A2.5B-Instruct config.json; what it
leaves open is ASSUMED, the same in the program: the configuration file's
`assumed`); x = RMSNorm(h), eps `rms_norm_eps`; H = `num_attention_heads`
heads of `head_dim` over KV = `num_key_value_heads`:

  attention  q = x Wq, k = x Wk, v = x Wv, no bias; q_h <- RMSNorm(q_h)
             w_q, k_h <- RMSNorm(k_h) w_k over the channels of each head,
             one learned [hd] each, before the rotary (ASSUMED: the config
             has no key for it; `head_norm`); rotary by `layer_types[l]`
             (`rope_parameters`) on every channel, channel i paired with
             i + hd / 2: sliding_attention inv_freq theta^(-2i / hd);
             full_attention inv_freq by YaRN as HF's
             `_compute_yarn_parameters` computes it, cos and sin times
             `attention_factor`; scores q k^T / sqrt(hd), query head n on
             key head floor(n / (H / KV)) (`key_head`); key j visible to query i
             when j <= i, and in a sliding layer also i - j <
             `sliding_window` (`visible`); o = softmax(scores) v;
             h += concat(o) Wo.
  router     p = softmax(x W_r) over all E in float32; the
             `num_experts_per_tok` largest of p + b are chosen (b a
             selection bias that takes no gradient, zero as published);
             weights p[chosen] / sum of p[chosen] (`norm_topk_prob`;
             `renormalise`). No scaling, no auxiliary loss.
  expert     W_down(silu(x W_gate) * (x W_up)), width
             `moe_intermediate_size`.
  layer      h += attention; x = RMSNorm(h); h += sum_e w_e Expert_e(x).
  loss       final RMSNorm, the untied head, mean cross-entropy in float32.

NOT here, as not in the program: a multi-token-prediction head (a reader's
summary of the family names one; the config has no key, size or equation
of it); `intermediate_size` (no layer is dense).

chipbench/tools/mellum2_wrong.py patches the small functions below
(`head_norm`, `visible`, `rope_group`, `key_head`, `renormalise`, `F32`)
to make the reference wrong in one thing at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 1024   # queries a block of the attention
ROW_BLOCK = 2048     # rows a block of the head's logits


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def head_norm(x, scale, eps):
    """x [S, heads, hd]: an RMSNorm over each head's channels, one learned [hd]."""
    return _rms_norm(x, scale, eps)


def yarn_parameters(dim: int, base: float, factor: float, original_max: int,
                    beta_fast: float, beta_slow: float) -> np.ndarray:
    """inv_freq [dim / 2]: transformers' `_compute_yarn_parameters`
    (modeling_rope_utils.py), line for line, `truncate` true."""

    def find_correction_dim(num_rotations, dim, base, max_position_embeddings):
        return (dim * math.log(max_position_embeddings / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def find_correction_range(low_rot, high_rot, dim, base, max_position_embeddings):
        low = math.floor(find_correction_dim(low_rot, dim, base, max_position_embeddings))
        high = math.ceil(find_correction_dim(high_rot, dim, base, max_position_embeddings))
        return max(low, 0), min(high, dim - 1)

    def linear_ramp_factor(lo, hi, dim):
        if lo == hi:
            hi += 0.001
        return np.clip((np.arange(dim, dtype=np.float32) - lo) / (hi - lo), 0, 1)

    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    low, high = find_correction_range(beta_fast, beta_slow, dim, base, original_max)
    inv_freq_extrapolation_factor = 1 - linear_ramp_factor(low, high, dim // 2)
    return (inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
            + inv_freq_extrapolation * inv_freq_extrapolation_factor).astype(np.float32)


def rope_group(shape: dict, kind: str) -> dict:
    """The rotary's parameters of one layer type (`rope_parameters[kind]`)."""
    return shape["rope_parameters"][kind]


def rope_tables(group: dict, head_dim: int, seq_len: int):
    """One layer type's (cos, sin) [S, hd / 2], always made in float32."""
    if group.get("rope_type", "default") == "yarn":
        inv = yarn_parameters(head_dim, group["rope_theta"], group["factor"],
                              group["original_max_position_embeddings"],
                              group["beta_fast"], group["beta_slow"])
        scale = group["attention_factor"]
    else:
        inv = 1.0 / group["rope_theta"] ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
        scale = 1.0
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, tables):
    """x [S, heads, hd]: every channel rotated by the row's position, channel i
    paired with i + hd / 2."""
    cos, sin = (t[:, None, :].astype(x.dtype) for t in tables)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def visible(i, j, kind: str, shape: dict):
    """Whether query position i [Q, 1] sees key position j [1, S]."""
    allowed = j <= i
    if kind == SLIDING:
        allowed = allowed & (i - j < shape["sliding_window"])
    return allowed


def key_head(n, heads: int, kv: int):
    """The key-value head query head n reads: `heads` / `kv` neighbours share one."""
    return n // (heads // kv)


def attention(h, lp, shape: dict, kind: str):
    """The attention half of a layer on h [S, D] -> h + attention."""
    s = h.shape[0]
    heads, kv, hd = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    eps = shape["rms_norm_eps"]
    x = _rms_norm(h, lp["ln1"], eps)
    tables = rope_tables(rope_group(shape, kind), hd, s)
    q = _rope(head_norm((x @ lp["wq"]).reshape(s, heads, hd), lp["q_norm"], eps), tables)
    k = _rope(head_norm((x @ lp["wk"]).reshape(s, kv, hd), lp["k_norm"], eps), tables)
    v = (x @ lp["wv"]).reshape(s, kv, hd)
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} tokens are not whole blocks of {block} queries")
    keys = jnp.arange(s)[None, :]

    def one(at):
        b, n = at // heads, at % heads
        g = key_head(n, heads, kv)
        rows = jax.lax.dynamic_slice_in_dim(q, b * block, block)[:, n]                  # [Q, hd]
        scores = (rows @ k[:, g].T).astype(F32) / jnp.sqrt(F32(hd))
        allowed = visible(b * block + jnp.arange(block)[:, None], keys, kind, shape)
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return probs.astype(v.dtype) @ v[:, g]

    # a block of queries and one head at a time, so that only one [Q, S] score matrix
    # is alive (the gradient makes it again, for the same reason)
    o = jax.lax.map(jax.checkpoint(one), jnp.arange((s // block) * heads))            # [B H, Q, hd]
    o = jnp.swapaxes(o.reshape(s // block, heads, block, hd), 1, 2).reshape(s, heads * hd)
    return h + o @ lp["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def renormalise(w, shape: dict):
    return w / w.sum(axis=-1, keepdims=True) if shape["norm_topk_prob"] else w


def route(x, lp, shape: dict):
    """x [S, D] (already normed) -> weights [S, E]: a chosen expert's
    renormalised probability, zero elsewhere."""
    probs = jax.nn.softmax((x @ lp["router"]).astype(F32), axis=-1)
    biased = probs + lp["router_bias"].astype(F32)
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    return renormalise(jnp.where(biased >= kth, probs, 0.0), shape)


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
    """[a layer's params] in layer order, from the tree's own layout: the
    periods' blocks, then the tail; each with its row of the selection biases."""
    cast = lambda tree: jax.tree.map(lambda w: w.astype(F32), tree)  # noqa: E731
    if "dense" in shape["mlp_layer_types"][:shape["num_hidden_layers"]] or "dense_layers" in params:
        raise ValueError("every layer sparse, as published")
    layers, bias = params["layers"], params["layers"]["router_bias"].astype(F32)
    period = [layers["period"][str(j)] for j in range(len(layers["period"]))]
    n_periods = jax.tree.leaves(period[0])[0].shape[0]
    blocks = [jax.tree.map(lambda w: w[p], block) for p in range(n_periods) for block in period]
    tail = layers.get("tail", {})
    blocks += [tail[str(j)] for j in range(len(tail))]
    if len(blocks) != shape["num_hidden_layers"] or bias.shape[0] != len(blocks):
        raise ValueError("the parameter tree's depth is not the configuration's")
    return [{**cast(lp), "router_bias": bias[l]} for l, lp in enumerate(blocks)]


def layer(h, lp, kind: str, shape: dict):
    return experts(attention(h, lp, shape, kind), lp, shape)


def head_loss(h, final_norm, lm_head, targets, eps):
    """h [S, D] -> the summed cross-entropy (nats, float32) over the held
    slice of the vocabulary, a block of rows at a time."""
    s = h.shape[0]
    block = min(ROW_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} tokens are not whole blocks of {block} rows")

    def rows(xs):
        hb, yb = xs
        lg = (_rms_norm(hb, final_norm, eps) @ lm_head).astype(F32)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0].astype(jnp.float32).sum()

    return jax.lax.map(jax.checkpoint(rows), (h.reshape(s // block, block, -1),
                                              targets.reshape(s // block, block))).sum()


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> (summed cross-entropy (nats) over the held slice
    of the vocabulary, tokens per expert [layers, E])."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"]:
        raise ValueError("an untied head, as published")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        counts = []
        for l, lp in enumerate(blocks_of(params, shape)):
            # the gradient keeps a layer's input and runs the layer again
            h, chosen = jax.checkpoint(
                lambda h, lp, kind=shape["layer_types"][l]: layer(h, lp, kind, shape))(h, lp)
            counts.append(chosen.sum(0))
        nll = head_loss(h, params["final_norm"].astype(F32), params["lm_head"].astype(F32),
                        targets, shape["rms_norm_eps"])
        return nll, jnp.stack(counts)


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "tokens_per_expert" [layers, E]},
    sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    parts = [one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])]
    return {"loss": sum(p[0] for p in parts) / tokens.size,
            "tokens_per_expert": sum(p[1] for p in parts)}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: the head's mean cross-entropy
    (the configuration has no auxiliary loss)."""
    return loss_parts(params, tokens, targets, shape)["loss"]


def grads(params, tokens, targets, shape: dict):
    """The gradient of `loss` by every leaf of the parameter tree, float32:
    reverse mode through the equations above, sequence by sequence (the
    selection bias takes none: it reads zero)."""
    one = jax.jit(jax.grad(lambda p, t, y: sequence(p, t, y, shape)[0]))
    total = one(params, tokens[0], targets[0])
    for b in range(1, tokens.shape[0]):
        total = jax.tree.map(jnp.add, total, one(params, tokens[b], targets[b]))
    return jax.tree.map(lambda g: (g / tokens.size).astype(jnp.float32), total)

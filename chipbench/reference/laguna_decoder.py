"""The plain reference: Laguna's decoder (`model_type` laguna: sliding-
window and full attention layers at their own head counts, a gate a head
on the attention's output, a rotary by layer type with YaRN on part of a
head, a leading dense layer, softmax top-k routed experts chosen with a
selection bias beside a shared expert, untied head) in straightforward
jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel, no sort and no grouped matmul: every held expert is applied to
every token and the result masked by the routing, attention is one
[S, S] score matrix a head, one head and one sequence at a time (so it
fits beside the step on the chip). It takes the program's parameter
tree and a configuration file's sizes (HF key names). It imports nothing
from ray_tpu.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `dense_layers`
(leaves stacked over the leading dense layers); `layers`:
`router_bias` [expert layers, E] (every expert block's selection bias,
in layer order), `period` {"0": .., "1": ..} (the blocks of one period of
layer kinds by position, leaves stacked over the periods: expert layer e
is position e mod P of period e div P), `tail` {"0": ..} (unstacked: the
expert layers after the last whole period; may be absent). A block's
leaves: ln1, wq [D, H_l hd], wk, wv [D, KV hd], wg [D, H_l], wo
[H_l hd, D], ln2, then w_gate / w_up [D, F_dense], w_down (dense) or
router [D, E], shared_gate / shared_up [D, F_s], shared_down, w_gate /
w_up [held, D, F], w_down [held, F, D] (expert).

THE SHARE. `num_experts` in the file is how many experts are HELD here
(`deployment.first_expert_held` is the first of them); the router has
`published.num_experts` outputs and routes over all of them. A (token,
expert) pair whose expert is not held gets nothing from this chip, and
that partial result goes on to the next layer. The shared expert is
whole on every chip. `vocab_size` rows of the embedding and columns of
the head are held: ids, logits and the loss are over that slice.

The equations (poolside/Laguna-S-2.1 config.json; what it leaves open is
ASSUMED, the same in the program: the configuration file's `assumed`);
x = RMSNorm(h), eps `rms_norm_eps`, layer l with H_l =
`num_attention_heads_per_layer[l]` heads of `head_dim`:

  attention  q = x Wq, k = x Wk, v = x Wv; rotary by `layer_types[l]`
             (`rope_parameters`): sliding_attention every channel,
             inv_freq theta^(-2i / hd); full_attention the first
             `partial_rotary_factor` x hd channels, the rest pass, inv_freq
             by YaRN as HF's `_compute_yarn_parameters` computes it, cos and
             sin times `attention_factor`; channel i pairs with i + half
             (ASSUMED); scores q k^T / sqrt(hd); key j visible to query i
             when j <= i, and in a sliding layer also i - j <
             `sliding_window`; o = softmax(scores) v; g = sigmoid(x Wg)
             [H_l], o_h <- g_h o_h (ASSUMED form of "per-head" gating:
             arXiv:2505.06708's headwise gate); h += concat(o) Wo. No
             bias, no q/k norm.
  router     p = softmax(x W_r) over all E; the `num_experts_per_tok`
             largest of p + b are chosen (b a selection bias that takes
             no gradient); weights `moe_routed_scaling_factor` x
             p[chosen] / sum of p[chosen] (`norm_topk_prob`). No
             auxiliary loss.
  expert     W_down(silu(x W_gate) * (x W_up)), routed, shared and dense.
  layer      h += attention; x = RMSNorm(h); a `dense` layer
             (`mlp_layer_types`): h += SwiGLU_{intermediate_size}(x); a
             `sparse` one: h += sum_e w_e Expert_e(x) + Shared(x).
  loss       final RMSNorm, the untied head, mean cross-entropy.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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


def rope_tables(group: dict, head_dim: int, seq_len: int):
    """One layer type's (cos, sin) [S, rot / 2] and rot, the channels that turn."""
    rot = int(head_dim * group.get("partial_rotary_factor", 1))
    if group.get("rope_type", "default") == "yarn":
        inv = yarn_parameters(rot, group["rope_theta"], group["factor"],
                              group["original_max_position_embeddings"],
                              group["beta_fast"], group["beta_slow"])
        scale = group["attention_factor"]
    else:
        inv, scale = 1.0 / group["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float32) / rot), 1.0
    ang = jnp.arange(seq_len, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale, rot


def _rope(x, tables):
    """x [S, heads, hd]: the first `rot` channels of every head rotated by
    position, half-split pairing within them; the rest pass through."""
    cos, sin, rot = tables
    cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(h, lp, shape: dict, kind: str, heads: int):
    """The attention half of a layer on h [S, D] -> h + gated attention."""
    s = h.shape[0]
    hd, kv = shape["head_dim"], shape["num_key_value_heads"]
    x = _rms_norm(h, lp["ln1"], shape["rms_norm_eps"])
    tables = rope_tables(shape["rope_parameters"][kind], hd, s)
    q = _rope((x @ lp["wq"]).reshape(s, heads, hd), tables)
    k = _rope((x @ lp["wk"]).reshape(s, kv, hd), tables)
    v = (x @ lp["wv"]).reshape(s, kv, hd)
    gate = jax.nn.sigmoid(x @ lp["wg"])                                # [S, heads]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = j <= i
    if kind == SLIDING:
        allowed = allowed & (i - j < shape["sliding_window"])
    group = heads // kv

    def one_head(n):
        qh, kh, vh = q[:, n], k[:, n // group], v[:, n // group]
        scores = (qh @ kh.T) / jnp.sqrt(F32(hd))
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ vh

    # head by head, so that only one [S, S] score matrix is alive at a time
    o = jax.lax.map(one_head, jnp.arange(heads))                       # [heads, S, hd]
    o = jnp.swapaxes(o, 0, 1) * gate[:, :, None]
    return h + o.reshape(s, heads * hd) @ lp["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, lp, shape: dict):
    """x [S, D] (already normed) -> weights [S, E]: a chosen expert's
    scaled, renormalised probability, zero elsewhere."""
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)
    biased = probs + lp["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    w = jnp.where(biased >= kth, probs, 0.0)
    if shape["norm_topk_prob"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return w * shape["moe_routed_scaling_factor"]


def experts(h, lp, shape: dict):
    """The expert half of a layer on h [S, D] -> (h + the held experts'
    part of the routed sum + the shared expert, chosen [S, E] bool)."""
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    weights = route(x, lp, shape)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["num_experts"]]

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                                   # w [S]
        return acc + w[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    shared = _swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return h + out + shared, weights > 0


def blocks_of(params, shape: dict) -> list:
    """[(layer's params, dense?)] in layer order, from the tree's own
    layout: the dense layers, the periods' blocks, the tail."""
    f32 = lambda tree: jax.tree.map(lambda w: w.astype(F32), tree)  # noqa: E731
    n_dense = sum(1 for t in shape["mlp_layer_types"] if t == "dense")
    out = [(f32(jax.tree.map(lambda w: w[i], params["dense_layers"])), True)
           for i in range(n_dense)]
    layers, bias = params["layers"], params["layers"]["router_bias"].astype(F32)
    period = [layers["period"][str(j)] for j in range(len(layers["period"]))]
    n_periods = jax.tree.leaves(period[0])[0].shape[0]
    expert = [jax.tree.map(lambda w: w[p], block) for p in range(n_periods) for block in period]
    tail = layers.get("tail", {})
    expert += [tail[str(j)] for j in range(len(tail))]
    if len(out) + len(expert) != shape["num_hidden_layers"] or bias.shape[0] != len(expert):
        raise ValueError("the parameter tree's depth is not the configuration's")
    return out + [({**f32(lp), "router_bias": bias[e]}, False) for e, lp in enumerate(expert)]


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> (summed cross-entropy (nats) over the held slice
    of the vocabulary, tokens per expert [expert layers, E])."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"]:
        raise ValueError("an untied head, as published")
    eps = shape["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        counts = []
        for l, (lp, dense) in enumerate(blocks_of(params, shape)):
            h = attention(h, lp, shape, shape["layer_types"][l],
                          shape["num_attention_heads_per_layer"][l])
            if dense:
                x = _rms_norm(h, lp["ln2"], eps)
                h = h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                h, chosen = experts(h, lp, shape)
                counts.append(chosen.sum(0))
        lg = _rms_norm(h, params["final_norm"].astype(F32), eps) @ params["lm_head"].astype(F32)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return nll.sum(), jnp.stack(counts)


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "tokens_per_expert" [expert
    layers, E]}, sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    parts = [one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])]
    return {"loss": sum(p[0] for p in parts) / tokens.size,
            "tokens_per_expert": sum(p[1] for p in parts)}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: the head's mean cross-entropy
    (the configuration has no auxiliary loss)."""
    return loss_parts(params, tokens, targets, shape)["loss"]

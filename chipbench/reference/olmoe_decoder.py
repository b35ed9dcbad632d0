"""The plain reference: a pre-norm decoder with QK-norm attention and a
dropless top-k expert layer (OLMoE-1B-7B) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel, no cache, no sort and no grouped matmul: every expert is
applied to every token and the result masked by the routing, one
sequence at a time. It takes the program's parameter tree (`embed`
[V, D], `layers` with every leaf stacked over a leading layer axis:
ln1, wq, wk, wv, wo, q_norm, k_norm, ln2, router [D, E], w_gate / w_up
[E, D, F], w_down [E, F, D]; then `final_norm`, `lm_head` [D, V]) and a
configuration file's sizes (HF key names). It imports nothing from
ray_tpu.

Follows the published architecture (allenai/OLMoE-1B-7B-0125-Instruct
config.json; arXiv:2409.02060; the HF `olmoe` model code): RMSNorm
before attention and before the experts; q and k normalised by an
RMSNorm with a learned scale over the WHOLE projected width, before the
head split and the rotary embedding (half-split pairing); causal
multi-head attention; the router a softmax over all experts' logits,
the `num_experts_per_tok` largest chosen and their probabilities used
as they are (`norm_topk_prob` false) or renormalised to sum to 1; each
expert a SwiGLU MLP; untied head. The training loss is the next-token
cross-entropy plus, per layer and averaged over layers,
`router_aux_loss_coef` x the load-balancing loss
E * sum_e f_e * P_e (f_e the share of tokens that chose expert e among
their choices, so sum_e f_e = num_experts_per_tok; P_e the mean router
probability of e) and `router_z_loss_coef` x mean(logsumexp(logits)^2).
The router's means are over ALL tokens of a batch, as the program takes
them: `loss_parts` sums each sequence's counts and probabilities per
layer before it forms the two losses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, H, hd] -> rotated by position; half-split pairing."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lp, shape: dict):
    """The attention half of a layer on h [S, D] -> h + attn."""
    s = h.shape[0]
    nh, nkv = shape["num_attention_heads"], shape["num_key_value_heads"]
    hd = shape.get("head_dim") or shape["hidden_size"] // nh
    eps = shape["rms_norm_eps"]
    x = _rms_norm(h, lp["ln1"], eps)
    q = _rms_norm(x @ lp["wq"], lp["q_norm"], eps).reshape(s, nh, hd)
    k = _rms_norm(x @ lp["wk"], lp["k_norm"], eps).reshape(s, nkv, hd)
    q, k = _rope(q, shape["rope_theta"]), _rope(k, shape["rope_theta"])
    v = (x @ lp["wv"]).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv                                            # [S, hd] each
        scores = (qh @ kh.T) / jnp.sqrt(F32(hd))
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1) @ vh

    # head by head, so that only one [S, S] score matrix is alive at a time
    heads = jax.lax.map(one_head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return h + jnp.swapaxes(heads, 0, 1).reshape(s, nh * hd) @ lp["wo"]


def route(x, router, shape: dict):
    """x [S, D] (already normed) -> (weights [S, E], zero for an expert
    the token did not choose; probabilities [S, E]; logsumexp [S])."""
    k = shape["num_experts_per_tok"]
    logits = x @ router
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    kth = jnp.sort(probs, axis=-1)[:, -k][:, None]
    chosen = probs >= kth
    weights = jnp.where(chosen, probs, 0.0)
    if shape["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, probs, lse


def experts(h, lp, shape: dict):
    """The expert half of a layer on h [S, D] -> (h + sum of the chosen
    experts' outputs, chosen [S, E] bool, probabilities [S, E],
    logsumexp [S]). Every expert runs on every token, one expert at a
    time; the routing weight (0 where not chosen) masks the rest."""
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    weights, probs, lse = route(x, lp["router"], shape)

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                                # w [S]
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return h + out, weights > 0, probs, lse


def layer(h, lp, shape: dict):
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    return experts(attention(h, lp, shape), lp, shape)


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> (summed next-token cross-entropy (nats), and
    per layer [L, ...]: tokens per expert [E], summed router
    probabilities [E], summed logsumexp^2)."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if jax.tree.leaves(params["layers"])[0].shape[0] != shape["num_hidden_layers"]:
        raise ValueError("the parameter tree's depth is not the configuration's")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]

        def one_layer(h, lp):
            h, chosen, probs, lse = layer(h, lp, shape)
            return h, (chosen.sum(0), probs.sum(0), jnp.sum(lse * lse))

        h, router = jax.lax.scan(one_layer, h, params["layers"])
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        lg = h @ params["lm_head"].astype(F32)
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum(), router


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"ce", "balance" [L], "z" [L],
    "tokens_per_expert" [L, E], "loss"}: the training loss and what it
    is made of, sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    parts = [one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])]
    n = tokens.shape[0] * tokens.shape[1]
    ce = sum(p[0] for p in parts) / n
    counts, prob_sums, lse2 = (sum(p[1][i] for p in parts) for i in range(3))
    balance = shape["num_experts"] * jnp.sum((counts / n) * (prob_sums / n), axis=-1)
    z = lse2 / n
    total = (ce + shape["router_aux_loss_coef"] * balance.mean()
             + shape["router_z_loss_coef"] * z.mean())
    return {"ce": ce, "balance": balance, "z": z, "tokens_per_expert": counts, "loss": total}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: mean cross-entropy plus the
    two router losses at the configuration's coefficients."""
    return loss_parts(params, tokens, targets, shape)["loss"]

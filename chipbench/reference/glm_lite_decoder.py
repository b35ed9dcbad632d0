"""The plain reference: GLM-4.7-Flash's decoder (`glm4_moe_lite`: latent
attention with a decoupled rotary part, a leading dense layer, sigmoid
top-k routed experts chosen with a selection bias beside a shared
expert, a multi-token-prediction block, untied head) in straightforward
jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel, no cache, no sort and no grouped matmul: every held expert is
applied to every token and the result masked by the routing, attention
is one [S, S] score matrix a head, one sequence at a time. It takes the
program's parameter tree and a configuration file's sizes (HF key
names). It imports nothing from ray_tpu.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `dense_layers`
(leaves stacked over `first_k_dense_replace` layers): ln1, the
attention's leaves, ln2, w_gate / w_up [D, F_dense], w_down; `layers`
(stacked over the expert layers, `num_hidden_layers` less the dense
ones): ln1, the attention's leaves, ln2, router [D, E], shared_gate /
shared_up [D, F_s], shared_down, w_gate / w_up [held, D, F], w_down
[held, F, D]; `mtp`: enorm, hnorm, eh_proj [2 D, D], `block` (one expert
layer's leaves, unstacked), final_norm. The attention's leaves: wq_a
[D, r_q], q_a_norm, wq_b [r_q, H (d_n + d_r)], wkv_a [D, r_kv + d_r],
kv_a_norm, wkv_b [r_kv, H (d_n + d_v)], wo [H d_v, D]. The selection
biases of every expert block are ONE table, `layers.router_bias`
[expert layers + 1, E], the MTP block's row last.

THE SHARE. `n_routed_experts` in the file is how many experts are HELD
here (`deployment.first_expert_held` is the first of them); the router
has `published.n_routed_experts` outputs and routes over all of them. A
(token, expert) pair whose expert is not held gets nothing from this
chip (another chip of the deployment computes it), and that partial
result is what goes on to the next layer. The shared expert is whole on
every chip. `vocab_size` rows of the embedding and columns of the head
are held: ids, logits and both losses are over that slice.

The equations, from DeepSeek-V2 (arXiv:2405.04434: MLA), DeepSeek-V3
(arXiv:2412.19437: sigmoid routing with a selection bias, MTP) and
zai-org/GLM-4.7-Flash's config.json; x = RMSNorm(hidden), eps
`rms_norm_eps`:

  MLA     c_q = RMSNorm(x W_qa); [q_nope ; q_rot] = c_q W_qb, H heads of
          d_n + d_r; [c_kv ; k_rot] = x W_kva; c_kv <- RMSNorm(c_kv);
          [k_nope ; v] = c_kv W_kvb, H heads of d_n + d_v; rotary (theta
          `rope_theta`, every one of the d_r channels, ASSUMED half-split
          pairing: channel i with i + d_r / 2) on each head's q_rot and
          on the ONE k_rot, shared by all heads; q = [q_nope ; q_rot],
          k = [k_nope ; k_rot]; causal softmax attention, scale
          1 / sqrt(d_n + d_r); hidden += concat(o) W_o. No bias
          (`attention_bias` false); `rope_scaling` null.
  router  s = sigmoid(x W_r) [E]; the `num_experts_per_tok` largest of
          s + b are chosen (b a selection bias that takes no gradient;
          `n_group` = `topk_group` = 1: no group limit); the weights are
          `routed_scaling_factor` x s[chosen] / (sum of s[chosen] + 1e-20)
          (`norm_topk_prob`). No auxiliary loss.
  expert  W_down(silu(x W_gate) * (x W_up)), routed and shared alike.
  layer   h += MLA(RMSNorm(h)); h += sum_e w_e Expert_e(x) + Shared(x)
          with x = RMSNorm(h); a DENSE layer has one SwiGLU of width
          `intermediate_size` in the experts' place.
  MTP     m_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] (ASSUMED
          order: the embedding's half first), h_i the last layer's output
          BEFORE the final norm; one more expert layer; the module's own
          final RMSNorm; the SAME head; logits at i predict t_{i+2}; the
          last position has no target. loss = mean CE of the head +
          `mtp_loss_weight` (ASSUMED 0.3: the config has no key for it) x
          mean CE of the MTP head over its S - 1 positions.

DEPARTURE, in the program and here alike: the update rule that moves the
selection bias between steps is a training recipe's (its speed is no
key of the config) and is not implemented; b is a parameter that no
step moves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, heads, d_r]: every channel rotated by position 0 .. S - 1,
    half-split pairing."""
    rot = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(h, lp, shape: dict):
    """The attention half of a layer on h [S, D] -> h + MLA(RMSNorm(h))."""
    s = h.shape[0]
    nh, eps, rkv = shape["num_attention_heads"], shape["rms_norm_eps"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    x = _rms_norm(h, lp["ln1"], eps)
    c_q = _rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps)
    q = (c_q @ lp["wq_b"]).reshape(s, nh, dn + dr)
    kv_a = x @ lp["wkv_a"]
    c_kv = _rms_norm(kv_a[:, :rkv], lp["kv_a_norm"], eps)
    kv = (c_kv @ lp["wkv_b"]).reshape(s, nh, dn + dv)
    theta = shape["rope_theta"]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k_rot = _rope(kv_a[:, None, rkv:], theta)                       # [S, 1, d_r]: one a token
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rot, (s, nh, dr))], axis=-1)
    v = kv[..., dn:]
    allowed = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = (qh @ kh.T) / jnp.sqrt(F32(dn + dr))
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ vh

    # head by head, so that only one [S, S] score matrix is alive at a time
    heads = jax.lax.map(one_head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return h + jnp.swapaxes(heads, 0, 1).reshape(s, nh * dv) @ lp["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(x, lp, shape: dict):
    """x [S, D] (already normed) -> weights [S, E]: a chosen expert's
    scaled, renormalised score, zero elsewhere."""
    scores = jax.nn.sigmoid(x @ lp["router"])
    biased = scores + lp["router_bias"]
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    w = jnp.where(biased >= kth, scores, 0.0)
    if shape["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * shape["routed_scaling_factor"]


def experts(h, lp, shape: dict):
    """The expert half of a layer on h [S, D] -> (h + the held experts'
    part of the routed sum + the shared expert, chosen [S, E] bool).
    Every held expert runs on every token, one at a time; the routing
    weight (0 where not chosen) masks the rest."""
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    weights = route(x, lp, shape)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["n_routed_experts"]]

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                                # w [S]
        return acc + w[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    shared = _swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return h + out + shared, weights > 0


def expert_layer(h, lp, shape: dict):
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    return experts(mla(h, lp, shape), lp, shape)


def dense_layer(h, lp, shape: dict):
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    h = mla(h, lp, shape)
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _nll(h, norm, head, targets, eps):
    """Per-position next-token negative log-likelihood of the head over
    RMSNorm(h)."""
    lg = _rms_norm(h, norm.astype(F32), eps) @ head
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> (summed cross-entropy of the head (nats) over
    the held slice of the vocabulary, the same of the MTP head over its
    S - 1 positions, tokens per expert [expert layers + 1, E])."""
    n_dense = shape["first_k_dense_replace"]
    n_expert = shape["num_hidden_layers"] - n_dense
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if jax.tree.leaves(params["layers"]["router"])[0].shape[0] != n_expert:
        raise ValueError("the parameter tree's depth is not the configuration's")
    if shape["num_nextn_predict_layers"] != 1 or shape["tie_word_embeddings"]:
        raise ValueError("one multi-token-prediction block and an untied head, as published")
    eps = shape["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        table, head = params["embed"].astype(F32), params["lm_head"].astype(F32)
        bias = params["layers"]["router_bias"].astype(F32)
        h = table[tokens]
        for i in range(n_dense):
            h = dense_layer(h, jax.tree.map(lambda w: w[i], params["dense_layers"]), shape)

        def one_layer(h, lp):
            h, chosen = expert_layer(h, lp, shape)
            return h, chosen.sum(0)

        h, counts = jax.lax.scan(
            one_layer, h, {**params["layers"], "router_bias": bias[:n_expert]})
        main = _nll(h, params["final_norm"], head, targets, eps).sum()
        # the second head: the next token's embedding beside this position's last hidden state
        mp = jax.tree.map(lambda w: w.astype(F32), params["mtp"])
        merged = jnp.concatenate([_rms_norm(table[targets], mp["enorm"], eps),
                                  _rms_norm(h, mp["hnorm"], eps)], axis=-1) @ mp["eh_proj"]
        m, chosen = expert_layer(merged, {**mp["block"], "router_bias": bias[n_expert]}, shape)
        ahead = _nll(m, mp["final_norm"], head, jnp.roll(targets, -1), eps)[:-1].sum()
        return main, ahead, jnp.concatenate([counts, chosen.sum(0)[None]])


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "loss_main", "loss_mtp",
    "tokens_per_expert" [expert layers + 1, E]}, sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    parts = [one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])]
    b, s = tokens.shape
    main = sum(p[0] for p in parts) / (b * s)
    ahead = sum(p[1] for p in parts) / (b * (s - 1))
    return {"loss": main + shape["mtp_loss_weight"] * ahead, "loss_main": main,
            "loss_mtp": ahead, "tokens_per_expert": sum(p[2] for p in parts)}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: the head's mean cross-entropy
    plus the weighted MTP head's (the configuration has no auxiliary loss)."""
    return loss_parts(params, tokens, targets, shape)["loss"]

"""The plain reference: ZAYA1's decoder (compressed convolutional
attention, an MLP router whose state is carried from layer to layer,
top-1 of the experts, tied head) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel, no cache, no sort and no grouped matmul: a convolution is a sum
of shifted products, every held expert is applied to every token and
the result masked by the routing, one sequence at a time. It takes the
program's parameter tree (`embed` [V, D]; `layers` with every leaf
stacked over a leading layer axis: ln1, wq [D, H hd], wk [D, G hd], wv1,
wv2 [D, G hd / 2], conv0 [k0, (H + G) hd], conv1 [k1, H + G, hd, hd],
temp [G], wo [H hd, D], ln2, router_down [D, R], router_gamma [R],
router_norm [R], router_w1, router_w2 [R, R], router_w3 [R, E],
router_bias [E], w_gate / w_up [held, D, F], w_down [held, F, D]; then
`final_norm`) and a configuration file's sizes (HF key names). It
imports nothing from ray_tpu.

THE SHARE. `num_experts` in the file is how many experts are HELD here
(`deployment.first_expert_held` is the first of them); the router has
`published.num_experts` outputs and routes over all of them. A token
routed to an expert that is not held gets nothing from the expert layer
(another chip of the deployment computes it), and that partial result
is what goes on to the next layer. `vocab_size` rows of the tied table
are held: the logits and the loss are over that slice.

The equations, from the CCA paper (arXiv:2510.04476), the ZAYA1 report
(arXiv:2511.17127) and Zyphra/ZAYA1-8B's config.json; with x =
RMSNorm(hidden), H query heads, G key-value heads of hd channels:

  CCA     q~ = x W_q, k~ = x W_k; v_t = [x_t W_v1 ; x_{t-1} W_v2] (ASSUMED:
          key-value head 0 is the current token's, head 1 the previous
          token's; no projection has a bias: `attention_bias` false);
          u = [q~ ; k~] as H + G heads; u0 = conv0(u), depthwise, causal,
          `cca_time0` taps; u1 = conv1(u0), a full hd x hd mix inside each
          head, causal, `cca_time1` taps (ASSUMED: no convolution bias;
          taps in time order, the last on the current token);
          m = (q~ + k~ of the head's group) / 2; q = u1[:H] + m,
          k = u1[H:] + mean of m over the group's query heads (ASSUMED
          form of the q-k mean); q <- sqrt(hd) q / |q|_2,
          k <- temp_g sqrt(hd) k / |k|_2, a learned temperature a
          key-value head (ASSUMED: x * rsqrt(mean(x^2) + rms_norm_eps));
          rotary (half-split pairing, theta of `rope_parameters.hybrid`)
          on the first `partial_rotary_factor` of each head; causal
          softmax attention, scale 1 / sqrt(hd); hidden += o W_o.
  router  r = x W_down + gamma * r_prev (r_prev: the previous layer's r,
          zero before the first layer); z = W_3 gelu(W_2 gelu(W_1
          RMSNorm(r))), exact gelu; p = softmax(z); the
          `num_experts_per_tok` largest of p + b are chosen (b a
          selection bias that takes no gradient) and weighted by p as it
          is: renormalised, a top-1 weight would be 1 and the router
          would learn nothing. No auxiliary loss.
  expert  W_down(silu(x W_gate) * (x W_up)).
  layer   h += CCA(RMSNorm(h)); h += experts(RMSNorm(h)); then the final
          RMSNorm and logits h E^T with the tied table.

DEPARTURES from the report, in the program and here alike: the update
rule of the selection bias b is not implemented (b is a parameter; zero
from the seed, it stays zero), and the learned residual scaling, which
has no key in the config, is left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _previous(x, n, segments):
    """x [S, ...] -> x at token t - n; zero where there is no such token
    or it lies in another document (`segments` [S] or None)."""
    if n == 0:
        return x
    y = jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], axis=0)
    if segments is None:
        return y
    same = jnp.concatenate([jnp.zeros((n,), bool), segments[n:] == segments[:-n]])
    return jnp.where(same.reshape((-1,) + (1,) * (x.ndim - 1)), y, 0.0)


def _rope(x, positions, rot, theta):
    """x [S, heads, hd]: the first `rot` channels of each head rotated
    by position, half-split pairing; the rest pass."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _positions(s, segments):
    idx = jnp.arange(s)
    if segments is None:
        return idx
    starts = jnp.concatenate([jnp.ones((1,), bool), segments[1:] != segments[:-1]])
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0))


def cca(h, lp, shape: dict, segments=None):
    """The attention half of a layer on h [S, D] -> h + CCA(RMSNorm(h))."""
    s = h.shape[0]
    nh, nkv, hd = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
    rep, eps = nh // nkv, shape["rms_norm_eps"]
    x = _rms_norm(h, lp["ln1"], eps)
    q_lat = (x @ lp["wq"]).reshape(s, nh, hd)
    k_lat = (x @ lp["wk"]).reshape(s, nkv, hd)
    v = jnp.concatenate([x @ lp["wv1"], _previous(x, 1, segments) @ lp["wv2"]], axis=-1)
    v = v.reshape(s, nkv, hd)
    u = jnp.concatenate([q_lat, k_lat], axis=1)                     # [S, H + G, hd]
    k0, k1 = shape["cca_time0"], shape["cca_time1"]
    taps0 = lp["conv0"].reshape(k0, nh + nkv, hd)
    u0 = sum(taps0[j] * _previous(u, k0 - 1 - j, segments) for j in range(k0))
    u1 = sum(jnp.einsum("snc,ncd->snd", _previous(u0, k1 - 1 - j, segments), lp["conv1"][j])
             for j in range(k1))
    m = 0.5 * (q_lat + jnp.repeat(k_lat, rep, axis=1))              # [S, H, hd]
    q = u1[:, :nh] + m
    k = u1[:, nh:] + m.reshape(s, nkv, rep, hd).mean(axis=2)

    def unit(y):  # sqrt(hd) y / |y|_2
        return y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)

    q, k = unit(q), unit(k) * lp["temp"][None, :, None]
    rot = int(hd * shape["partial_rotary_factor"])
    theta = shape["rope_parameters"]["hybrid"]["rope_theta"]
    pos = _positions(s, segments)
    q, k = _rope(q, pos, rot, theta), _rope(k, pos, rot, theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    allowed = jnp.tril(jnp.ones((s, s), bool))
    if segments is not None:
        allowed = allowed & (segments[:, None] == segments[None, :])

    def one_head(qkv):
        qh, kh, vh = qkv                                            # [S, hd] each
        scores = (qh @ kh.T) / jnp.sqrt(F32(hd))
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ vh

    # head by head, so that only one [S, S] score matrix is alive at a time
    heads = jax.lax.map(one_head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return h + jnp.swapaxes(heads, 0, 1).reshape(s, nh * hd) @ lp["wo"]


def route(x, lp, shape: dict, r_prev):
    """x [S, D] (already normed), r_prev [S, R] -> (weights [S, E]: a
    chosen expert's probability, zero elsewhere; probabilities [S, E];
    this layer's router state r [S, R])."""
    eps = shape["rms_norm_eps"]
    r = x @ lp["router_down"] + lp["router_gamma"] * r_prev
    y = _rms_norm(r, lp["router_norm"], eps)
    y = jax.nn.gelu(y @ lp["router_w1"], approximate=False)
    y = jax.nn.gelu(y @ lp["router_w2"], approximate=False)
    probs = jax.nn.softmax(y @ lp["router_w3"], axis=-1)
    score = probs + lp["router_bias"]
    kth = jnp.sort(score, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    return jnp.where(score >= kth, probs, 0.0), probs, r


def experts(h, lp, shape: dict, r_prev):
    """The expert half of a layer on h [S, D] -> (h + the held experts'
    part of the chosen experts' outputs, chosen [S, E] bool, r [S, R]).
    Every held expert runs on every token, one at a time; the routing
    weight (0 where not chosen) masks the rest."""
    x = _rms_norm(h, lp["ln2"], shape["rms_norm_eps"])
    weights, _, r = route(x, lp, shape, r_prev)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["num_experts"]]

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                                # w [S]
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    return h + out, weights > 0, r


def layer(h, r_prev, lp, shape: dict, segments=None):
    """(hidden [S, D], previous router state [S, R]) -> (hidden, this
    layer's router state, chosen [S, E])."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    h, chosen, r = experts(cca(h, lp, shape, segments), lp, shape, r_prev)
    return h, r, chosen


def sequence(params, tokens, targets, shape: dict, segments=None):
    """One sequence [S] -> (summed next-token cross-entropy (nats) over
    the held slice of the vocabulary, tokens per expert [L, E])."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if jax.tree.leaves(params["layers"])[0].shape[0] != shape["num_hidden_layers"]:
        raise ValueError("the parameter tree's depth is not the configuration's")
    if not shape["tie_word_embeddings"]:
        raise ValueError("ZAYA1's head is the embedding table")
    with jax.default_matmul_precision("highest"):
        table = params["embed"].astype(F32)
        h = table[tokens]
        r = jnp.zeros((tokens.shape[0], shape["router_hidden_size"]), F32)

        def one_layer(carry, lp):
            h, r, chosen = layer(*carry, lp, shape, segments)
            return (h, r), chosen.sum(0)

        (h, _), counts = jax.lax.scan(one_layer, (h, r), params["layers"])
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        lg = h @ table.T
        logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum(), counts


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "tokens_per_expert" [L, E]},
    sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    parts = [one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])]
    n = tokens.shape[0] * tokens.shape[1]
    return {"loss": sum(p[0] for p in parts) / n,
            "tokens_per_expert": sum(p[1] for p in parts)}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: the mean cross-entropy (the
    configuration has no auxiliary loss)."""
    return loss_parts(params, tokens, targets, shape)["loss"]

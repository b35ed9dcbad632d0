"""The plain reference: Olmo-Hybrid's decoder (`model_type` olmo_hybrid:
gated-delta-rule linear-attention layers and full-attention layers
without a rotary in one stack, every layer over a dense SwiGLU, each
sublayer's output normed, untied head) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel and no chunked form: the linear layers' recurrence runs POSITION
BY POSITION, as its equation is written (a `lax.scan` over t that
carries the state of every head), so that it is independent of the
program's chunked algebra; full attention is one [S, S] score matrix a
head, one head and one sequence at a time (so it fits beside the step on
the chip). It takes the program's parameter tree and a configuration
file's sizes (HF key names). It imports nothing from ray_tpu. `grads` is
reverse mode through the same functions; the three `jax.checkpoint`s (a
block, a head's scores, a segment of 64 positions of the recurrence)
change no number and are there so that it fits at 4,096 positions.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `layers`:
`period` {"0": .., "3": ..} (the blocks of one period of layer kinds by
position, leaves stacked over the periods: layer l is position l mod P
of period l div P). A linear block's leaves: wq, wk [D, H dk], wv, wg
[D, H dv], wa, wb [D, H], conv_q, conv_k [K, H dk], conv_v [K, H dv],
A_log, dt_bias [H], o_norm [dv], wo [H dv, D]; a full block's: wq, wk,
wv, wo [D, D], q_norm, k_norm [D]; both: ln1, ln2 [D], w_gate, w_up
[D, F], w_down [F, D].

THE SHARE. `vocab_size` rows of the embedding and columns of the head
are held: ids, logits and the loss are over that slice. Nothing else of
a layer is divided.

The equations (allenai/Olmo-Hybrid-7B config.json names the sizes; what
it leaves open is ASSUMED, the same in the program: the configuration
file's `assumed`). u is a sublayer's input, eps `rms_norm_eps`, H =
`linear_num_value_heads` = `linear_num_key_heads`, dk =
`linear_key_head_dim`, dv = `linear_value_head_dim`, K =
`linear_conv_kernel_dim`:

  linear     q~ = u Wq, k~ = u Wk, v~ = u Wv; on every channel of each a
             causal depthwise convolution over time, y_t = sum_j
             taps[j] x_{t-j}, j < K, zeros before the sequence, no bias;
             SiLU; a head: q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(dk), k =
             k~ / sqrt(|k~|^2 + 1e-6), v = v~; beta = sigmoid(u Wb),
             doubled under `linear_allow_neg_eigval`; g = -exp(A_log)
             softplus(u Wa + dt_bias); S_{-1} = 0, S_t = exp(g_t) (I -
             beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T, o_t = S_t^T
             q_t; y = RMSNorm_dv(o; o_norm) SiLU(u Wg); out = concat(y) Wo.
  full       q = RMSNorm_D(u Wq; q_norm), k = RMSNorm_D(u Wk; k_norm)
             over the WHOLE projected width, v = u Wv; `num_attention_heads`
             heads of D / heads; NO rotary (`rope_parameters.rope_theta`
             null); scores q k^T / sqrt(hd), key j visible to query i
             when j <= i; out = concat(softmax(scores) v) Wo.
  layer      h += RMSNorm(mixer(h); ln1); h += RMSNorm(SwiGLU(h); ln2):
             the OLMo-2 lineage's reordered norm in both kinds of layer.
  head       logits = RMSNorm(h; final_norm) lm_head over the held slice;
             the loss is the mean cross-entropy (no auxiliary loss).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STATE = jnp.float32  # the carried state's dtype
FULL, LINEAR = "full_attention", "linear_attention"
L2_EPS = 1e-6
SEGMENT = 64  # positions whose states the gradient makes again at a time (no result reads it)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def conv(x, taps):
    """x [S, C], taps [K, C] -> y_t = sum_j taps[j] x_{t-j}: nothing ahead of t."""
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[taps.shape[0] - 1 - j:][:s] for j in range(taps.shape[0]))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def beta_of(u, lp, shape: dict):
    beta = jax.nn.sigmoid(u @ lp["wb"])
    return 2.0 * beta if shape["linear_allow_neg_eigval"] else beta


def decay_of(u, lp):
    """g [S, H] <= 0: the log of the factor the state decays by at each position."""
    return -jnp.exp(lp["A_log"]) * jax.nn.softplus(u @ lp["wa"] + lp["dt_bias"])


def recurrence(q, k, v, g, beta):
    """q, k [S, H, dk], v [S, H, dv], g, beta [S, H] -> o [S, H, dv]: the
    gated delta rule, one position at a time. The positions are walked in
    segments (an outer scan over an inner one, the same steps in the same
    order) only so that the gradient fits at 4,096 positions: reverse mode
    keeps the state each SEGMENT started from and makes a segment's own
    states again (`jax.checkpoint`), where one flat scan would keep all
    S of them, 2.2 MB each at the published sizes."""

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S.astype(F32) * jnp.exp(g_t)[:, None, None]
        S = S - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum("hk,hkv->hv", k_t, S)[:, None, :]
        S = S + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S.astype(STATE), jnp.einsum("hkv,hk->hv", S, q_t)

    s, h = q.shape[:2]
    seg = max(n for n in range(1, SEGMENT + 1) if s % n == 0)
    xs = tuple(a.reshape(s // seg, seg, *a.shape[1:]) for a in (q, k, v, g, beta))
    segment = jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs))
    _, o = jax.lax.scan(segment, jnp.zeros((h, q.shape[2], v.shape[2]), STATE), xs)
    return o.reshape(s, *o.shape[2:])


def output_gate(o, gate, w, eps):
    return _rms_norm(o, w, eps) * jax.nn.silu(gate)


def rule_inputs(u, lp, shape: dict):
    """u [S, D] -> what the recurrence reads: q, k [S, H, dk], v [S, H, dv], g, beta [S, H]."""
    s = u.shape[0]
    heads, dk, dv = (shape["linear_num_value_heads"], shape["linear_key_head_dim"],
                     shape["linear_value_head_dim"])
    q = jax.nn.silu(conv(u @ lp["wq"], lp["conv_q"])).reshape(s, heads, dk)
    k = jax.nn.silu(conv(u @ lp["wk"], lp["conv_k"])).reshape(s, heads, dk)
    v = jax.nn.silu(conv(u @ lp["wv"], lp["conv_v"])).reshape(s, heads, dv)
    return l2norm(q) / jnp.sqrt(F32(dk)), l2norm(k), v, decay_of(u, lp), beta_of(u, lp, shape)


def linear_mixer(u, lp, shape: dict):
    s = u.shape[0]
    heads, dv = shape["linear_num_value_heads"], shape["linear_value_head_dim"]
    o = recurrence(*rule_inputs(u, lp, shape))
    y = output_gate(o, (u @ lp["wg"]).reshape(s, heads, dv), lp["o_norm"], shape["rms_norm_eps"])
    return y.reshape(s, heads * dv) @ lp["wo"]


def rotary(q, k, shape: dict):
    """q, k [S, heads, hd] as the scores read them: unchanged (no rotary)."""
    return q, k


def full_mixer(u, lp, shape: dict):
    s, heads, eps = u.shape[0], shape["num_attention_heads"], shape["rms_norm_eps"]
    hd = shape["hidden_size"] // heads
    q = _rms_norm(u @ lp["wq"], lp["q_norm"], eps).reshape(s, heads, hd)
    k = _rms_norm(u @ lp["wk"], lp["k_norm"], eps).reshape(s, heads, hd)
    v = (u @ lp["wv"]).reshape(s, heads, hd)
    q, k = rotary(q, k, shape)
    allowed = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def one_head(i):
        scores = (q[:, i] @ k[:, i].T) / jnp.sqrt(F32(hd))
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ v[:, i]

    # head by head, so that only one [S, S] score matrix is alive at a time
    # (the gradient makes a head's scores again, for the same reason)
    o = jax.lax.map(jax.checkpoint(one_head), jnp.arange(heads))                       # [heads, S, hd]
    return jnp.swapaxes(o, 0, 1).reshape(s, heads * hd) @ lp["wo"]


def block(h, lp, kind: str, shape: dict):
    eps = shape["rms_norm_eps"]
    mixer = linear_mixer if kind == LINEAR else full_mixer
    h = h + _rms_norm(mixer(h, lp, shape), lp["ln1"], eps)
    return h + _rms_norm(_swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), lp["ln2"], eps)


def blocks_of(params, shape: dict) -> list:
    """[(layer's params, kind)] in layer order, from the tree's own layout."""
    period = [params["layers"]["period"][str(j)] for j in range(len(params["layers"]["period"]))]
    n_periods = jax.tree.leaves(period[0])[0].shape[0]
    out = [jax.tree.map(lambda w: w[p].astype(F32), blk) for p in range(n_periods)
           for blk in period]
    if len(out) != shape["num_hidden_layers"]:
        raise ValueError("the parameter tree's depth is not the configuration's")
    kinds = shape["layer_types"][:len(out)]
    for lp, kind in zip(out, kinds):
        if ("A_log" in lp) != (kind == LINEAR):
            raise ValueError("the parameter tree's kinds are not the configuration's layer_types")
    return list(zip(out, kinds))


def logits(params, tokens, shape: dict):
    """One sequence [S] -> logits [S, V] over the held slice."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"]:
        raise ValueError("an untied head, as published")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        for lp, kind in blocks_of(params, shape):
            # the gradient keeps a block's input and runs the block again
            h = jax.checkpoint(lambda h, lp, kind=kind: block(h, lp, kind, shape))(h, lp)
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        return (h @ params["lm_head"].astype(F32)).astype(jnp.float32)


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> summed cross-entropy (nats) over the held slice."""
    lg = logits(params, tokens, shape)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].sum()


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss"}, sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence(p, t, y, shape))
    return {"loss": sum(one(params, tokens[b], targets[b])
                        for b in range(tokens.shape[0])) / tokens.size}


def loss(params, tokens, targets, shape: dict):
    """The training loss of a batch [B, S]: the head's mean cross-entropy
    (the configuration has no auxiliary loss)."""
    return loss_parts(params, tokens, targets, shape)["loss"]


def grads(params, tokens, targets, shape: dict):
    """The gradient of `loss` by every leaf of the parameter tree, float32:
    reverse mode through the equations above, sequence by sequence."""
    one = jax.jit(jax.grad(lambda p, t, y: sequence(p, t, y, shape)))
    total = one(params, tokens[0], targets[0])
    for b in range(1, tokens.shape[0]):
        total = jax.tree.map(jnp.add, total, one(params, tokens[b], targets[b]))
    return jax.tree.map(lambda g: g / tokens.size, total)


def first_rule(params, tokens, shape: dict, w):
    """Layer 0's recurrence ALONE, on what that layer hands it for one
    sequence [S] (the embedded tokens through the projections, the
    convolution, the norms and the gates): ((q, k, v, g, beta), (o, dq, dk,
    dv, dg, dbeta)), the last five the cotangent w [S, H, dv] of o pulled
    back through the position-by-position rule. What a run holds the
    program's rule to on the SAME inputs, where nothing else's rounding
    stands between the two."""
    def both(params, tokens, w):
        with jax.default_matmul_precision("highest"):
            lp, kind = blocks_of(params, shape)[0]
            if kind != LINEAR:
                raise ValueError("layer 0 is no linear-attention layer")
            args = rule_inputs(params["embed"].astype(F32)[tokens], lp, shape)
            o, pull = jax.vjp(recurrence, *args)
            return args, (o,) + pull(w.astype(o.dtype))

    return jax.jit(both)(params, tokens, w)


"""The plain reference: Solar-Open2-250B (`model_type` solar_open2:
Kimi-Delta-Attention layers and gated GQA layers without a rotary, three
to one, every layer over sigmoid-routed experts and a shared one, untied
head) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no kernel,
no chunked form, no grouped matmul: the KDA recurrence runs POSITION BY
POSITION, as its equation is written (a `lax.scan` over t that carries the
state of every head), so that it is independent of the program's chunked
algebra; every held expert is applied to every token and the result masked
by the routing; attention is a masked softmax over one block of 1,024
queries and one head at a time (so it fits beside the step on the chip).
It takes the program's parameter tree and a configuration file's sizes (HF
key names). It imports nothing from ray_tpu. `grads` is reverse mode
through the same functions; the `jax.checkpoint`s (a block, a block of
queries, an expert, a segment of 64 positions of the recurrence) change no
number and are there so that it fits at 8,192 positions.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `layers`:
`router_bias` [layers, E] and `period`: {"0": .., "3": ..}, a period's
blocks by position, leaves stacked over the periods: layer l is position
l mod 4 of period l div 4 (position 0 the GQA layer). A KDA block: wq, wk,
wv [D, H d], conv_q, conv_k, conv_v [K, H d], wf1, wg1 [D, r], wf2, wg2
[r, H d], wb [D, H], A_log [H], dt_bias, g_bias [H d], o_norm [d], wo
[H d, D]; a GQA block: wq, wg [D, heads 128], wk, wv [D, kv 128], wo; both
ln1, ln2, router [D, E], shared_gate, shared_up, shared_down, w_gate, w_up
[held, D, F], w_down [held, F, D].

THE SHARE. `num_attention_heads`, `num_key_value_heads`,
`linear_attn_config.num_heads` and `n_routed_experts` in the file are what
is HELD here (`deployment.first_expert_held` the first expert); the router
has `published.n_routed_experts` outputs and routes over all of them. A
mixer's output is its held heads' rows of `wo`: a partial sum; a (token,
expert) pair whose expert is not held gets nothing from this chip; both
partial results go on to the next layer, in the program and here alike.
`vocab_size` rows of the embedding and columns of the head are held: ids,
logits and the loss are over that slice.

The equations (the published config.json names the sizes; what it leaves
open is ASSUMED, the same in the program: the configuration file's
`assumed`). u = RMSNorm(h) at `rms_norm_eps`; a layer is h += mixer(u),
then h += experts(RMSNorm(h)).

  KDA   q~, k~, v~ = u Wq, u Wk, u Wv; y_t = sum_j taps[j] x_{t-j}, j < K,
        zeros before the sequence, then SiLU; q = l2norm(q~) / sqrt(d),
        k = l2norm(k~) a head, v = v~; beta = sigmoid(u Wb) (x 2 under
        `kda_allow_neg_eigval`); g = -exp(A_log[h]) softplus((u Wf1) Wf2 +
        dt_bias) [H, d]; S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
        S_{t-1} + beta_t k_t v_t^T from 0, o_t = S_t^T q_t; y =
        RMSNorm_d(o; o_norm) . sigmoid((u Wg1) Wg2 + g_bias); out = y Wo.
  GQA   q, gate [heads x 128], k, v [kv x 128]; NO rotary; scores q k^T /
        sqrt(128), key j visible to query i when j <= i; o <- o .
        sigmoid(gate); out = o Wo.
  experts  s = sigmoid(u W_r) over all E; the `num_experts_per_tok`
        largest of s + b chosen (b a selection bias that takes no
        gradient); weights s[chosen] / (their sum + 1e-20) x
        `routed_scaling_factor`; an expert is W_down (silu(W_gate x) .
        W_up x); plus the shared expert on every token. No auxiliary loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STATE = jnp.float32  # the carried state's dtype
GQA, KDA = "gqa", "kda"
L2_EPS = 1e-6
SEGMENT = 64  # positions whose states the gradient makes again at a time (no result reads it)
QUERIES = 1024  # queries whose scores are alive at a time (no result reads it)


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
    return 2.0 * beta if shape["kda_allow_neg_eigval"] else beta


def decay_of(u, lp, heads: int):
    """g [S, H, d] <= 0: the log of the factor each CHANNEL of the key
    decays by at each position."""
    f = ((u @ lp["wf1"]) @ lp["wf2"] + lp["dt_bias"]).reshape(u.shape[0], heads, -1)
    return -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(f)


def recurrence(q, k, v, g, beta):
    """q, k, g [S, H, d], v [S, H, dv], beta [S, H] -> o [S, H, dv]: the
    KDA rule, one position at a time. The positions are walked in segments
    (an outer scan over an inner one, the same steps in the same order)
    only so that the gradient fits at 8,192 positions."""

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S.astype(F32) * jnp.exp(g_t)[:, :, None]
        S = S - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum("hk,hkv->hv", k_t, S)[:, None, :]
        S = S + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S.astype(STATE), jnp.einsum("hkv,hk->hv", S, q_t)

    s, h = q.shape[:2]
    seg = max(n for n in range(1, SEGMENT + 1) if s % n == 0)
    xs = tuple(a.reshape(s // seg, seg, *a.shape[1:]) for a in (q, k, v, g, beta))
    segment = jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs))
    _, o = jax.lax.scan(segment, jnp.zeros((h, q.shape[2], v.shape[2]), STATE), xs)
    return o.reshape(s, *o.shape[2:])


def gate_act(x):
    """The KDA output gate's activation: sigmoid (fla's FusedRMSNormGated, activation sigmoid)."""
    return jax.nn.sigmoid(x)


def output_gate(o, gate, w, eps):
    return _rms_norm(o, w, eps) * gate_act(gate)


def short_conv(x, taps):
    """The projection's output through its convolution and SiLU."""
    return jax.nn.silu(conv(x, taps))


def rule_inputs(u, lp, shape: dict):
    """u [S, D] -> what the recurrence reads: q, k, v, g [S, H, d], beta [S, H]."""
    s, lin = u.shape[0], shape["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    q = short_conv(u @ lp["wq"], lp["conv_q"]).reshape(s, heads, d)
    k = short_conv(u @ lp["wk"], lp["conv_k"]).reshape(s, heads, d)
    v = short_conv(u @ lp["wv"], lp["conv_v"]).reshape(s, heads, d)
    return (l2norm(q) / jnp.sqrt(F32(d)), l2norm(k), v, decay_of(u, lp, heads),
            beta_of(u, lp, shape))


def kda_mixer(u, lp, shape: dict):
    s, heads = u.shape[0], shape["linear_attn_config"]["num_heads"]
    o = recurrence(*rule_inputs(u, lp, shape))
    gate = ((u @ lp["wg1"]) @ lp["wg2"] + lp["g_bias"]).reshape(s, heads, -1)
    return output_gate(o, gate, lp["o_norm"], shape["rms_norm_eps"]).reshape(s, -1) @ lp["wo"]


def gqa_gate(o, u, lp, shape: dict):
    """o [S, heads x hd] through the elementwise output gate."""
    return o * jax.nn.sigmoid(u @ lp["wg"]) if shape["use_gqa_gate"] else o


def gqa_mixer(u, lp, shape: dict):
    s, heads, kv, hd = (u.shape[0], shape["num_attention_heads"], shape["num_key_value_heads"],
                        shape["head_dim"])
    if shape["use_rope"]:
        raise ValueError("no rotary (`use_rope` false), as published")
    q = (u @ lp["wq"]).reshape(s, heads, hd)
    k = (u @ lp["wk"]).reshape(s, kv, hd)
    v = (u @ lp["wv"]).reshape(s, kv, hd)
    rows = max(n for n in range(1, QUERIES + 1) if s % n == 0)

    def one_block(hb):
        """One head's `rows` queries against every key: [rows, hd]."""
        i, b = hb
        at = b * rows + jnp.arange(rows)
        scores = (jax.lax.dynamic_slice_in_dim(q[:, i], b * rows, rows) @ k[:, i // (heads // kv)].T
                  ) / jnp.sqrt(F32(hd))
        allowed = jnp.arange(s)[None, :] <= at[:, None]
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ v[:, i // (heads // kv)]

    pairs = jnp.stack(jnp.meshgrid(jnp.arange(heads), jnp.arange(s // rows), indexing="ij"),
                      -1).reshape(-1, 2)
    o = jax.lax.map(jax.checkpoint(one_block), (pairs[:, 0], pairs[:, 1]))   # [heads x blocks, rows, hd]
    o = jnp.swapaxes(o.reshape(heads, s, hd), 0, 1).reshape(s, heads * hd)
    return gqa_gate(o, u, lp, shape) @ lp["wo"]


def score(logits):
    """An expert's score from its logit: the sigmoid, each by itself."""
    return jax.nn.sigmoid(logits)


def route(u, lp, shape: dict, chosen=None):
    """u [S, D] (already normed) -> weights [S, E]: a chosen expert's
    renormalised, scaled score, zero elsewhere. `chosen` [S, E] bool, where
    given, is the choice (another computation's: the scores, their
    renormalisation and every gradient stay this function's own)."""
    scores = score((u @ lp["router"]).astype(F32))
    if chosen is None:
        biased = scores + lp["router_bias"].astype(F32)
        kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
        chosen = biased >= kth
    w = jnp.where(chosen, scores, 0.0)
    if shape["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return (w * shape["routed_scaling_factor"]).astype(u.dtype)


def experts_mixer(u, lp, shape: dict, chosen=None):
    """-> (the held experts' part of the routed sum + the shared expert, chosen [S, E] bool)."""
    weights = route(u, lp, shape, chosen)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["n_routed_experts"]]

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                           # w [S]
        return acc + w[:, None] * _swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    if shape["n_shared_experts"] != 1:
        raise ValueError("one shared expert, as published")
    return out + _swiglu(u, lp["shared_gate"], lp["shared_up"], lp["shared_down"]), weights > 0


def kind_of(layer: int, shape: dict) -> str:
    return GQA if layer in shape["gqa_layers"] else KDA


def blocks_of(params, shape: dict) -> list:
    """[(layer's params, kind)] in layer order, from the tree's own layout."""
    period = params["layers"]["period"]
    per, n = len(period), shape["num_hidden_layers"]
    if jax.tree.leaves(period)[0].shape[0] * per != n:
        raise ValueError("the parameter tree's depth is not the configuration's")
    out = []
    for l in range(n):
        lp = jax.tree.map(lambda w: w[l // per].astype(F32), period[str(l % per)])
        lp["router_bias"] = params["layers"]["router_bias"][l].astype(F32)
        if ("wb" in lp) != (kind_of(l, shape) == KDA):
            raise ValueError(f"layer {l} of the tree is not the kind `gqa_layers` says")
        out.append((lp, kind_of(l, shape)))
    return out


def block(h, lp, kind: str, shape: dict, chosen=None):
    """-> (the layer's output, chosen [S, E])."""
    eps = shape["rms_norm_eps"]
    h = h + (kda_mixer if kind == KDA else gqa_mixer)(_rms_norm(h, lp["ln1"], eps), lp, shape)
    y, chosen = experts_mixer(_rms_norm(h, lp["ln2"], eps), lp, shape, chosen)
    return h + y, chosen


def forward(params, tokens, shape: dict, chosen=None):
    """One sequence [S] -> (logits [S, V] over the held slice, tokens per
    expert [layers, E]). `chosen` [layers, S, E] bool, where given, is every
    layer's choice of experts in place of the router's own (`route`)."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"] or shape["first_k_dense_replace"]:
        raise ValueError("an untied head and no dense layer, as published")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        counts = []
        for l, (lp, kind) in enumerate(blocks_of(params, shape)):
            # the gradient keeps a block's input and runs the block again
            h, took = jax.checkpoint(lambda h, lp, given, kind=kind: block(h, lp, kind, shape, given))(
                h, lp, None if chosen is None else chosen[l])
            counts.append(took.sum(0))
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        return (h @ params["lm_head"].astype(F32)).astype(jnp.float32), jnp.stack(counts)


def logits(params, tokens, shape: dict):
    return forward(params, tokens, shape)[0]


def sequence(params, tokens, targets, shape: dict, chosen=None):
    """One sequence [S] -> (summed cross-entropy (nats) over the held slice,
    tokens per expert [layers, E])."""
    lg, counts = forward(params, tokens, shape, chosen)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].sum(), counts


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


def grads(params, tokens, targets, shape: dict, chosen=None):
    """The gradient of `loss` by every leaf of the parameter tree, float32:
    reverse mode through the equations above, sequence by sequence (the
    selection bias takes none: it reads zero). `chosen` [B, layers, S, E]
    bool, where given, is the choice of experts the gradient is taken under
    (`forward`): a choice is piecewise constant and takes no gradient, so
    this is the gradient of the same loss at the same weights along ANOTHER
    computation's pieces, which is what tells a wrong gradient from a pair
    that a rounding moved to another expert."""
    one = jax.jit(jax.grad(lambda p, t, y, c: sequence(p, t, y, shape, c)[0]))
    given = [None if chosen is None else chosen[b] for b in range(tokens.shape[0])]
    total = one(params, tokens[0], targets[0], given[0])
    for b in range(1, tokens.shape[0]):
        total = jax.tree.map(jnp.add, total, one(params, tokens[b], targets[b], given[b]))
    return jax.tree.map(lambda g: g / tokens.size, total)


def first_rule(params, tokens, shape: dict, w):
    """The FIRST KDA layer's recurrence ALONE (layer 1: layer 0 is the GQA
    layer), on what that layer hands it for one sequence [S] (the embedded
    tokens through layer 0, then layer 1's norm, projections, convolution,
    norms and gates): ((q, k, v, g, beta), (o, dq, dk, dv, dg, dbeta)), the
    last five the cotangent w [S, H, d] of o pulled back through the
    position-by-position rule. What a run holds the program's rule to on
    the SAME inputs, where nothing else's rounding stands between the two."""
    def both(params, tokens, w):
        with jax.default_matmul_precision("highest"):
            h = params["embed"].astype(F32)[tokens]
            for lp, kind in blocks_of(params, shape):
                if kind == KDA:
                    break
                h, _ = block(h, lp, kind, shape)
            else:
                raise ValueError("no KDA layer")
            args = rule_inputs(_rms_norm(h, lp["ln1"], shape["rms_norm_eps"]), lp, shape)
            o, pull = jax.vjp(recurrence, *args)
            return args, (o,) + pull(w.astype(o.dtype))

    return jax.jit(both)(params, tokens, w)

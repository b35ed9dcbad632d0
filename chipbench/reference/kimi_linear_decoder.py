"""The plain reference: Kimi-Linear-48B-A3B (`model_type` kimi_linear:
Kimi-Delta-Attention layers three to one latent-attention (MLA) layer
without a rotary, a leading dense layer, then sigmoid-routed experts and a
shared one, untied head) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no kernel,
no chunked form, no grouped matmul: the KDA recurrence runs POSITION BY
POSITION, as its equation is written (a `lax.scan` over t that carries the
state of every head), so that it is independent of the program's chunked
algebra; every held expert is applied to every token and the result masked
by the routing; attention is a masked softmax over one block of 1,024
queries and one head at a time (so it fits beside the step on the chip).
It takes the program's parameter tree and a configuration file's sizes (HF
key names). It imports nothing from ray_tpu (the KDA functions are this
file's own copy, as chipbench/reference/solar_open2_decoder.py has its).
`grads` is reverse mode through the same functions; the `jax.checkpoint`s
(a block, a block of queries, an expert, a segment of 64 positions of the
recurrence) change no number and are there so that it fits at 8,192
positions.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `dense_layers`
(leaves stacked over the `first_k_dense_replace` leading layers: ln1, the
mixer's leaves, ln2, w_gate, w_up [D, F_dense], w_down); `layers`:
`router_bias` [expert layers, E], `period`: {"0": ..} (a period's blocks by
position, leaves stacked over the periods) and `tail`: {"0": ..}
(unstacked, the layers after the last whole period; absent without one).
Layers are numbered from 1 as `linear_attn_config` numbers them: layer l is
an MLA layer where l is in `full_attn_layers`, else a KDA layer; expert
layer i (from 0, layer `first_k_dense_replace` + 1 + i) is position i mod P
of period i div P while whole periods last, then the tail's. A KDA block:
wq, wk, wv [D, H d], conv_q, conv_k, conv_v [K, H d], wf1, wg1 [D, r], wf2,
wg2 [r, H d], wb [D, H], A_log [H], dt_bias, g_bias [H d], o_norm [d], wo
[H d, D]; an MLA block: wq [D, H (d_n + d_r)], wkv_a [D, r_kv + d_r],
kv_a_norm [r_kv], wkv_b [r_kv, H (d_n + d_v)], wo [H d_v, D]; both ln1,
ln2 and either the dense SwiGLU's three or router [D, E], shared_gate,
shared_up, shared_down, w_gate, w_up [held, D, F], w_down [held, F, D].

THE SHARE. `num_experts` in the file is what is HELD here
(`deployment.first_expert_held` the first expert); the router has
`published.num_experts` outputs and routes over all of them; a (token,
expert) pair whose expert is not held gets nothing from this chip.
`vocab_size` rows of the embedding and columns of the head are held: ids,
logits and the loss are over that slice. Both mixers' heads are whole.

The equations (the published config.json names the sizes; what it leaves
open is ASSUMED, the same in the program: the configuration file's
`assumed`). u = RMSNorm(h) at `rms_norm_eps`; a layer is h += mixer(u),
then h += ffn(RMSNorm(h)).

  KDA   q~, k~, v~ = u Wq, u Wk, u Wv; y_t = sum_j taps[j] x_{t-j}, j < K,
        zeros before the sequence, then SiLU; q = l2norm(q~) / sqrt(d),
        k = l2norm(k~) a head, v = v~; beta = sigmoid(u Wb), NOT doubled;
        g = -exp(A_log[h]) softplus((u Wf1) Wf2 + dt_bias) [H, d]; S_t =
        (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        from 0, o_t = S_t^T q_t; y = RMSNorm_d(o; o_norm) . sigmoid((u
        Wg1) Wg2 + g_bias); out = y Wo.
  MLA   q = u Wq, H heads of d_n + d_r (no latent, no norm); [c_kv ; k_r]
        = u W_kva; c_kv <- RMSNorm(c_kv); [k_n ; v] = c_kv W_kvb, H heads
        of d_n + d_v; k = [k_n ; k_r], the ONE k_r shared by all heads; NO
        rotary; scores q k^T / sqrt(d_n + d_r), key j visible to query i
        when j <= i; out = concat(o) Wo.
  experts  s = sigmoid(u W_r) over all E; the `num_experts_per_token`
        largest of s + b chosen (b a selection bias that takes no
        gradient; one group); weights s[chosen] / (their sum + 1e-20) x
        `routed_scaling_factor`; an expert is W_down (silu(W_gate x) .
        W_up x); plus the shared expert on every token. No auxiliary loss.
  dense    W_down (silu(W_gate x) . W_up x) at `intermediate_size`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STATE = jnp.float32  # the carried state's dtype
KDA, MLA = "kda", "mla"
L2_EPS = 1e-6
SEGMENT = 64  # positions whose states the gradient makes again at a time (no result reads it)
QUERIES = 1024  # queries whose scores are alive at a time (no result reads it)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


# -- KDA ----------------------------------------------------------------------------


def conv(x, taps):
    """x [S, C], taps [K, C] -> y_t = sum_j taps[j] x_{t-j}: nothing ahead of t."""
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[taps.shape[0] - 1 - j:][:s] for j in range(taps.shape[0]))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def beta_of(u, lp):
    """One number a head and position in (0, 1): NOT doubled."""
    return jax.nn.sigmoid(u @ lp["wb"])


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


def rule_inputs(u, lp, shape: dict):
    """u [S, D] -> what the recurrence reads: q, k, v, g [S, H, d], beta [S, H]."""
    s, lin = u.shape[0], shape["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    q, k, v = (jax.nn.silu(conv(u @ lp[w], lp[taps])).reshape(s, heads, d)
               for w, taps in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    return (l2norm(q) / jnp.sqrt(F32(d)), l2norm(k), v, decay_of(u, lp, heads), beta_of(u, lp))


def kda_mixer(u, lp, shape: dict):
    s, heads = u.shape[0], shape["linear_attn_config"]["num_heads"]
    o = recurrence(*rule_inputs(u, lp, shape))
    gate = ((u @ lp["wg1"]) @ lp["wg2"] + lp["g_bias"]).reshape(s, heads, -1)
    y = _rms_norm(o, lp["o_norm"], shape["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return y.reshape(s, -1) @ lp["wo"]


# -- MLA ----------------------------------------------------------------------------


def shared_key(kv_a, shape: dict):
    """kv_a [S, r_kv + d_r] -> the ONE key part all heads share, [S, d_r]: the
    last channels as they are projected (no rotary: `mla_use_nope`)."""
    return kv_a[:, shape["kv_lora_rank"]:]


def rotary(x, shape: dict):
    """x [S, heads, d_r], the channels of a query or of the shared key that a
    rotary would turn: as they are projected (`mla_use_nope`: NO rotary)."""
    return x


def latent_norm(c_kv, lp, shape: dict):
    return _rms_norm(c_kv, lp["kv_a_norm"], shape["rms_norm_eps"])


def attention_inputs(u, lp, shape: dict):
    """u [S, D] -> q, k [S, H, d_n + d_r], v [S, H, d_v] of an MLA layer."""
    s, nh, rkv = u.shape[0], shape["num_attention_heads"], shape["kv_lora_rank"]
    dn, dr, dv = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"]
    if not shape["mla_use_nope"] or shape["q_lora_rank"] is not None:
        raise ValueError("no rotary and no query latent, as published")
    q = (u @ lp["wq"]).reshape(s, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], shape)], axis=-1)
    kv_a = u @ lp["wkv_a"]
    kv = (latent_norm(kv_a[:, :rkv], lp, shape) @ lp["wkv_b"]).reshape(s, nh, dn + dv)
    k_r = jnp.broadcast_to(rotary(shared_key(kv_a, shape)[:, None, :], shape), (s, nh, dr))
    return q, jnp.concatenate([kv[..., :dn], k_r], axis=-1), kv[..., dn:]


def softmax_scale(shape: dict):
    return 1.0 / jnp.sqrt(F32(shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"]))


def attend(q, k, v, scale):
    """q, k [S, H, dk], v [S, H, dv] -> causal softmax attention [S, H, dv],
    a block of `QUERIES` queries and one head at a time."""
    s, heads = q.shape[:2]
    rows = max(n for n in range(1, QUERIES + 1) if s % n == 0)

    def one_block(hb):
        i, b = hb
        at = b * rows + jnp.arange(rows)
        scores = (jax.lax.dynamic_slice_in_dim(q[:, i], b * rows, rows) @ k[:, i].T) * scale
        allowed = jnp.arange(s)[None, :] <= at[:, None]
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ v[:, i]

    pairs = jnp.stack(jnp.meshgrid(jnp.arange(heads), jnp.arange(s // rows), indexing="ij"),
                      -1).reshape(-1, 2)
    o = jax.lax.map(jax.checkpoint(one_block), (pairs[:, 0], pairs[:, 1]))  # [H x blocks, rows, dv]
    return jnp.swapaxes(o.reshape(heads, s, v.shape[2]), 0, 1)


def mla_mixer(u, lp, shape: dict):
    q, k, v = attention_inputs(u, lp, shape)
    return attend(q, k, v, softmax_scale(shape)).reshape(u.shape[0], -1) @ lp["wo"]


# -- the feed-forward -----------------------------------------------------------------


def score(logits):
    """An expert's score from its logit: the sigmoid, each by itself."""
    return jax.nn.sigmoid(logits)


def routed_scaling(shape: dict):
    return shape["routed_scaling_factor"]


def route(u, lp, shape: dict, chosen=None):
    """u [S, D] (already normed) -> weights [S, E]: a chosen expert's
    renormalised, scaled score, zero elsewhere. `chosen` [S, E] bool, where
    given, is the choice (another computation's: the scores, their
    renormalisation and every gradient stay this function's own)."""
    if shape["num_expert_group"] != 1 or shape["topk_group"] != 1:
        raise ValueError("one group of experts, as published")
    scores = score((u @ lp["router"]).astype(F32))
    if chosen is None:
        biased = scores + lp["router_bias"].astype(F32)
        kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_token"]][:, None]
        chosen = biased >= kth
    w = jnp.where(chosen, scores, 0.0)
    if shape["moe_renormalize"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return (w * routed_scaling(shape)).astype(u.dtype)


def shared_expert(u, lp):
    return _swiglu(u, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def experts_mixer(u, lp, shape: dict, chosen=None):
    """-> (the held experts' part of the routed sum + the shared expert, chosen [S, E] bool)."""
    weights = route(u, lp, shape, chosen)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["num_experts"]]

    def one_expert(acc, ew):
        w_gate, w_up, w_down, w = ew                           # w [S]
        return acc + w[:, None] * _swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], held.T))
    if shape["num_shared_experts"] != 1:
        raise ValueError("one shared expert, as published")
    return out + shared_expert(u, lp), weights > 0


# -- the stack ------------------------------------------------------------------------


def kind_of(layer: int, shape: dict) -> str:
    """Of layer `layer`, numbered from 1."""
    lin = shape["linear_attn_config"]
    if (layer in lin["full_attn_layers"]) == (layer in lin["kda_layers"]):
        raise ValueError(f"layer {layer} is in both or neither of kda_layers and full_attn_layers")
    return MLA if layer in lin["full_attn_layers"] else KDA


def blocks_of(params, shape: dict) -> list:
    """[(layer's params in float32, kind, dense)] in layer order, from the
    tree's own layout; an expert block's params carry its `router_bias`."""
    n, n_dense = shape["num_hidden_layers"], shape["first_k_dense_replace"]
    period, tail = params["layers"]["period"], params["layers"].get("tail", {})
    per = len(period)
    periods = jax.tree.leaves(period)[0].shape[0]
    if n_dense + periods * per + len(tail) != n:
        raise ValueError("the parameter tree's depth is not the configuration's")
    out = []
    for l in range(n):
        if l < n_dense:
            lp = jax.tree.map(lambda w: w[l].astype(F32), params["dense_layers"])
        else:
            i = l - n_dense
            lp = (jax.tree.map(lambda w: w[i // per].astype(F32), period[str(i % per)])
                  if i < periods * per else
                  jax.tree.map(lambda w: w.astype(F32), tail[str(i - periods * per)]))
            lp["router_bias"] = params["layers"]["router_bias"][i].astype(F32)
        kind = kind_of(l + 1, shape)
        if ("wb" in lp) != (kind == KDA) or ("router" in lp) == (l < n_dense):
            raise ValueError(f"layer {l + 1} of the tree is not the kind the configuration says")
        out.append((lp, kind, l < n_dense))
    return out


def block(h, lp, kind: str, dense: bool, shape: dict, chosen=None):
    """-> (the layer's output, chosen [S, E]; None for a dense layer)."""
    eps = shape["rms_norm_eps"]
    h = h + (kda_mixer if kind == KDA else mla_mixer)(_rms_norm(h, lp["ln1"], eps), lp, shape)
    u = _rms_norm(h, lp["ln2"], eps)
    if dense:
        return h + _swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    y, chosen = experts_mixer(u, lp, shape, chosen)
    return h + y, chosen


def forward(params, tokens, shape: dict, chosen=None):
    """One sequence [S] -> (logits [S, V] over the held slice, tokens per
    expert [expert layers, E]). `chosen` [expert layers, S, E] bool, where
    given, is every expert layer's choice in place of the router's own."""
    if tokens.shape[0] > shape["model_max_length"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['model_max_length']} positions")
    if shape["tie_word_embeddings"] or shape["num_nextn_predict_layers"] or shape["moe_layer_freq"] != 1:
        raise ValueError("an untied head, no MTP block, experts in every layer after the dense "
                         "ones, as published")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        counts = []
        for lp, kind, dense in blocks_of(params, shape):
            # the gradient keeps a block's input and runs the block again
            given = None if chosen is None or dense else chosen[len(counts)]
            h, took = jax.checkpoint(
                lambda h, lp, given, kind=kind, dense=dense: block(h, lp, kind, dense, shape, given)
            )(h, lp, given)
            if not dense:
                counts.append(took.sum(0))
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        return (h @ params["lm_head"].astype(F32)).astype(jnp.float32), jnp.stack(counts)


def logits(params, tokens, shape: dict):
    return forward(params, tokens, shape)[0]


def sequence(params, tokens, targets, shape: dict, chosen=None):
    """One sequence [S] -> (summed cross-entropy (nats) over the held slice,
    tokens per expert [expert layers, E])."""
    lg, counts = forward(params, tokens, shape, chosen)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].sum(), counts


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "tokens_per_expert" [expert layers,
    E]}, sequence by sequence."""
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
    selection bias takes none: it reads zero). `chosen` [B, expert layers,
    S, E] bool, where given, is the choice of experts the gradient is taken
    under (`forward`)."""
    one = jax.jit(jax.grad(lambda p, t, y, c: sequence(p, t, y, shape, c)[0]))
    given = [None if chosen is None else chosen[b] for b in range(tokens.shape[0])]
    total = one(params, tokens[0], targets[0], given[0])
    for b in range(1, tokens.shape[0]):
        total = jax.tree.map(jnp.add, total, one(params, tokens[b], targets[b], given[b]))
    return jax.tree.map(lambda g: g / tokens.size, total)


def _input_of_first(kind: str, params, tokens, shape: dict):
    """(the normed input u [S, D] of the first layer of `kind`, its params):
    the embedded tokens through the layers before it, then its first norm."""
    h = params["embed"].astype(F32)[tokens]
    for lp, k, dense in blocks_of(params, shape):
        if k == kind:
            return _rms_norm(h, lp["ln1"], shape["rms_norm_eps"]), lp
        h, _ = block(h, lp, k, dense, shape)
    raise ValueError(f"no {kind} layer")


def first_rule(params, tokens, shape: dict, w):
    """The FIRST KDA layer's recurrence ALONE (layer 1, the dense layer's
    mixer), on what that layer hands it for one sequence [S]: ((q, k, v, g,
    beta), (o, dq, dk, dv, dg, dbeta)), the last five the cotangent w [S, H,
    d] of o pulled back through the position-by-position rule. What a run
    holds the program's rule to on the SAME inputs."""
    def both(params, tokens, w):
        with jax.default_matmul_precision("highest"):
            args = rule_inputs(*_input_of_first(KDA, params, tokens, shape), shape)
            o, pull = jax.vjp(recurrence, *args)
            return args, (o,) + pull(w.astype(o.dtype))

    return jax.jit(both)(params, tokens, w)


def first_attention(params, tokens, shape: dict, w, dtype=jnp.bfloat16):
    """The FIRST MLA layer's attention ALONE, on what that layer hands it for
    one sequence [S] (the embedded tokens through the layers before it, then
    its norm and projections): ((q, k, v), (o, dq, dk, dv)), q and k [S, H,
    d_n + d_r], v [S, H, d_v] ROUNDED to `dtype` (the type the program's
    kernel is handed them in; the softmax over them float32 at `highest`, a
    block of queries at a time), the last three the cotangent w [S, H, d_v]
    of o pulled back. What a run holds the program's kernels to on the SAME
    inputs, where nothing else's rounding stands between the two."""
    def both(params, tokens, w):
        with jax.default_matmul_precision("highest"):
            args = attention_inputs(*_input_of_first(MLA, params, tokens, shape), shape)
            args = tuple(a.astype(dtype).astype(F32) for a in args)
            o, pull = jax.vjp(lambda q, k, v: attend(q, k, v, softmax_scale(shape)), *args)
            return args, (o,) + pull(w.astype(o.dtype))

    return jax.jit(both)(params, tokens, w)

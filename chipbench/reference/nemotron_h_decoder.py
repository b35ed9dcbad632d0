"""The plain reference: the CAUSAL tower of Nemotron-Labs-TwoTower-30B-A3B
(`model_type` nemotron_h: Mamba-2 state-space mixers, expert layers of
relu^2 experts and attention layers without a rotary in one stack, ONE
sublayer a layer, untied head) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no kernel,
no chunked form, no grouped matmul: the state-space scan runs POSITION BY
POSITION, as its equation is written (a `lax.scan` over t that carries the
state of every head), so that it is independent of the program's chunked
algebra; every held expert is applied to every token and the result
masked by the routing; attention is one [S, S] score matrix a head, one
head at a time (so it fits beside the step on the chip). It takes the
program's parameter tree and a configuration file's sizes (HF key names).
It imports nothing from ray_tpu. `grads` is reverse mode through the same
functions; the `jax.checkpoint`s (a block, a head's scores, an expert, a
segment of 64 positions of the scan) change no number and are there so
that it fits at 8,192 positions.

The published model's SECOND tower (a denoiser conditioned on this one)
and its diffusion objective are NOT here, as they are not in the program:
the published config gives none of their sizes or equations.

THE TREE. `embed` [V, D]; `lm_head` [D, V]; `final_norm`; `layers`: the
layers of a KIND stacked in their order: `mamba` (ln, w_in [D, 2 x inner
+ 2 G N + H], conv [K, inner + 2 G N], conv_bias, dt_bias, A_log, D [H],
norm [inner], w_out [inner, D]), `attention` (ln, wq, wk, wv, wo),
`experts` (ln, router [D, E], shared_up, shared_down, w_up [held, D, F],
w_down [held, F, D]), `router_bias` [expert layers, E]. Layer l is the
next unused layer of the kind `hybrid_override_pattern`[l] names.

THE SHARE. `n_routed_experts` in the file is how many experts are HELD
here (`deployment.first_expert_held` is the first of them); the router
has `published.n_routed_experts` outputs and routes over all of them. A
(token, expert) pair whose expert is not held gets nothing from this
chip, and that partial result goes on to the next layer. `vocab_size`
rows of the embedding and columns of the head are held: ids, logits and
the loss are over that slice.

The equations (the published config.json names the sizes; what it leaves
open is ASSUMED, the same in the program: the configuration file's
`assumed`). u = RMSNorm(h; ln) at `layer_norm_epsilon`; every layer is
h += f(u). H = `mamba_num_heads` of P = `mamba_head_dim`, G = `n_groups`,
N = `ssm_state_size`, K = `conv_kernel`:

  M          [z | xBC | dt] = u W_in (widths H P | H P + 2 G N | H);
             xBC = SiLU(conv(xBC) + b), y_t = sum_j taps[j] x_{t-j}, j < K,
             zeros before the sequence; x [H, P], B, C [G, N], head h reads
             group h // (H / G); dt = softplus(dt + dt_bias); A = -exp(A_log);
             H_{-1} = 0, H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T, y_t =
             H_t C_t + D x_t; y = GroupRMSNorm(y SiLU(z); norm) over G
             groups of H P / G channels; out = y W_out.
  E          s = sigmoid(u W_r) in float32 over all E; the
             `num_experts_per_tok` largest of s + b are chosen (b a
             selection bias that takes no gradient); weights s[chosen] /
             (sum of s[chosen] + 1e-20) x `routed_scaling_factor`; an expert
             is W_down relu(W_up x)^2; plus the shared expert, the same
             form, on every token. No auxiliary loss.
  *          q [`num_attention_heads` x `head_dim`], k, v
             [`num_key_value_heads` x `head_dim`] = u Wq, u Wk, u Wv; NO
             rotary; scores q k^T / sqrt(hd), key j visible to query i when
             j <= i; query head i reads key-value head i // (heads / kv);
             out = concat(softmax(scores) v) Wo.
  head       logits = RMSNorm(h; final_norm) lm_head over the held slice;
             the loss is the mean cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STATE = jnp.float32  # the carried state's dtype
SEGMENT = 64  # positions whose states the gradient makes again at a time (no result reads it)
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
GROUP = {MAMBA: "mamba", EXPERTS: "experts", ATTENTION: "attention"}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def conv(x, taps, bias):
    """x [S, C], taps [K, C], bias [C] -> y_t = sum_j taps[j] x_{t-j} + b: nothing ahead of t."""
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[taps.shape[0] - 1 - j:][:s] for j in range(taps.shape[0])) + bias


def step_of(dt, lp):
    """dt [S, H] -> the step softplus(dt + dt_bias) > 0."""
    return jax.nn.softplus(dt + lp["dt_bias"])


def skip_of(lp):
    """D [H]: the skip's weight a head."""
    return lp["D"]


def group_of_head(heads: int, groups: int):
    """[H]: the group of B and C that head h reads."""
    return jnp.arange(heads) // (heads // groups)


def recurrence(x, dt, A, B, C, D):
    """x [S, H, P], dt [S, H] (> 0), A [H] (< 0), B, C [S, G, N], D [H] ->
    y [S, H, P]: the selective scan, one position at a time. The positions
    are walked in segments (an outer scan over an inner one, the same steps
    in the same order) only so that the gradient fits at 8,192 positions:
    reverse mode keeps the state each SEGMENT started from and makes a
    segment's own states again (`jax.checkpoint`), where one flat scan
    would keep all S of them, 2 MB each at the published sizes."""
    s, heads = x.shape[:2]
    of = group_of_head(heads, B.shape[1])

    def step(H, xs):
        x_t, dt_t, B_t, C_t = xs
        H = H.astype(F32) * jnp.exp(dt_t * A)[:, None, None]
        H = H + (dt_t[:, None] * x_t)[:, :, None] * B_t[of][:, None, :]
        return H.astype(STATE), jnp.einsum("hpn,hn->hp", H, C_t[of]) + D[:, None] * x_t

    seg = max(n for n in range(1, SEGMENT + 1) if s % n == 0)
    xs = tuple(a.reshape(s // seg, seg, *a.shape[1:]) for a in (x, dt, B, C))
    segment = jax.checkpoint(lambda H, xs: jax.lax.scan(step, H, xs))
    _, y = jax.lax.scan(segment, jnp.zeros((heads, x.shape[2], B.shape[2]), STATE), xs)
    return y.reshape(s, *y.shape[2:])


def gated_norm(y, z, w, groups: int, eps):
    """y, z [S, inner] -> GroupRMSNorm(y SiLU(z)): the gate BEFORE the norm."""
    s, inner = y.shape
    g = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, inner) * w


def scan_inputs(u, lp, shape: dict):
    """u [S, D] -> (z [S, inner], what the scan reads: x [S, H, P], dt [S, H],
    A [H], B, C [S, G, N], D [H])."""
    s = u.shape[0]
    heads, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    g, n = shape["n_groups"], shape["ssm_state_size"]
    inner = heads * p
    if shape["mamba_proj_bias"] or not shape["use_conv_bias"]:
        raise ValueError("no bias on the projections and one on the convolution, as published")
    zxbcdt = u @ lp["w_in"]
    z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    xBC = jax.nn.silu(conv(xBC, lp["conv"], lp["conv_bias"]))
    x, B, C = jnp.split(xBC, [inner, inner + g * n], axis=-1)
    return z, (x.reshape(s, heads, p), step_of(dt, lp), -jnp.exp(lp["A_log"]),
               B.reshape(s, g, n), C.reshape(s, g, n), skip_of(lp))


def mamba_mixer(u, lp, shape: dict):
    z, args = scan_inputs(u, lp, shape)
    y = recurrence(*args).reshape(u.shape[0], -1)
    return gated_norm(y, z, lp["norm"], shape["n_groups"], shape["layer_norm_epsilon"]) @ lp["w_out"]


def rotary(q, k, shape: dict):
    """q, k [S, heads, hd] as the scores read them: unchanged (no rotary)."""
    return q, k


def attention_mixer(u, lp, shape: dict):
    s, heads, kv, hd = (u.shape[0], shape["num_attention_heads"], shape["num_key_value_heads"],
                        shape["head_dim"])
    q = (u @ lp["wq"]).reshape(s, heads, hd)
    k = (u @ lp["wk"]).reshape(s, kv, hd)
    v = (u @ lp["wv"]).reshape(s, kv, hd)
    q, k = rotary(q, k, shape)
    allowed = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def one_head(i):
        scores = (q[:, i] @ k[:, i // (heads // kv)].T) / jnp.sqrt(F32(hd))
        return jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1) @ v[:, i // (heads // kv)]

    # head by head, so that only one [S, S] score matrix is alive at a time
    # (the gradient makes a head's scores again, for the same reason)
    o = jax.lax.map(jax.checkpoint(one_head), jnp.arange(heads))                       # [heads, S, hd]
    return jnp.swapaxes(o, 0, 1).reshape(s, heads * hd) @ lp["wo"]


def activation(x):
    """relu^2 (`mlp_hidden_act` relu2)."""
    return jnp.square(jax.nn.relu(x))


def expert(x, w_up, w_down):
    return activation(x @ w_up) @ w_down


def route(u, lp, shape: dict):
    """u [S, D] (already normed) -> weights [S, E]: a chosen expert's
    renormalised, scaled score, zero elsewhere."""
    if shape["n_group"] != 1 or shape["topk_group"] != 1:
        raise ValueError("no groups of experts (`n_group` 1), as published")
    scores = jax.nn.sigmoid((u @ lp["router"]).astype(F32))
    biased = scores + lp["router_bias"].astype(F32)
    kth = jnp.sort(biased, axis=-1)[:, -shape["num_experts_per_tok"]][:, None]
    w = jnp.where(biased >= kth, scores, 0.0)
    return scale(renormalise(w, shape), shape).astype(u.dtype)


def renormalise(w, shape: dict):
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) if shape["norm_topk_prob"] else w


def scale(w, shape: dict):
    return w * shape["routed_scaling_factor"]


def experts_mixer(u, lp, shape: dict):
    """-> (the held experts' part of the routed sum + the shared expert, chosen [S, E] bool)."""
    weights = route(u, lp, shape)
    first = shape.get("deployment", {}).get("first_expert_held", 0)
    held = weights[:, first:first + shape["n_routed_experts"]]

    def one_expert(acc, ew):
        w_up, w_down, w = ew                                   # w [S]
        return acc + w[:, None] * expert(u, w_up, w_down), None

    out, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                          (lp["w_up"], lp["w_down"], held.T))
    if shape["n_shared_experts"] != 1:
        raise ValueError("one shared expert, as published")
    return out + expert(u, lp["shared_up"], lp["shared_down"]), weights > 0


def blocks_of(params, shape: dict) -> list:
    """[(layer's params, kind)] in layer order, from the tree's own layout."""
    kinds = shape["hybrid_override_pattern"][:shape["num_hidden_layers"]]
    layers, used, out = params["layers"], dict.fromkeys(GROUP, 0), []
    for kind in kinds:
        i = used[kind]
        lp = jax.tree.map(lambda w: w[i].astype(F32), layers[GROUP[kind]])
        if kind == EXPERTS:
            lp["router_bias"] = layers["router_bias"][i].astype(F32)
        used[kind] += 1
        out.append((lp, kind))
    for kind, n in used.items():
        if n != (jax.tree.leaves(layers[GROUP[kind]])[0].shape[0] if GROUP[kind] in layers else 0):
            raise ValueError("the parameter tree's depth is not the configuration's")
    return out


def block(h, lp, kind: str, shape: dict):
    """-> (h + f(RMSNorm(h)), chosen [S, E] for an expert layer else None)."""
    u = _rms_norm(h, lp["ln"], shape["layer_norm_epsilon"])
    if kind == EXPERTS:
        y, chosen = experts_mixer(u, lp, shape)
        return h + y, chosen
    return h + (mamba_mixer if kind == MAMBA else attention_mixer)(u, lp, shape), None


def forward(params, tokens, shape: dict):
    """One sequence [S] -> (logits [S, V] over the held slice, tokens per
    expert [expert layers, E])."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if shape["tie_word_embeddings"]:
        raise ValueError("an untied head, as published")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        counts = []
        for lp, kind in blocks_of(params, shape):
            # the gradient keeps a block's input and runs the block again
            h, chosen = jax.checkpoint(lambda h, lp, kind=kind: block(h, lp, kind, shape))(h, lp)
            if chosen is not None:
                counts.append(chosen.sum(0))
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["layer_norm_epsilon"])
        return (h @ params["lm_head"].astype(F32)).astype(jnp.float32), jnp.stack(counts)


def logits(params, tokens, shape: dict):
    return forward(params, tokens, shape)[0]


def sequence(params, tokens, targets, shape: dict):
    """One sequence [S] -> (summed cross-entropy (nats) over the held slice,
    tokens per expert [expert layers, E])."""
    lg, counts = forward(params, tokens, shape)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0].sum(), counts


def loss_parts(params, tokens, targets, shape: dict) -> dict:
    """tokens/targets [B, S] -> {"loss", "tokens_per_expert" [expert layers, E]},
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
    return jax.tree.map(lambda g: g / tokens.size, total)


def first_scan(params, tokens, shape: dict, w):
    """Layer 0's scan ALONE, on what that layer hands it for one sequence
    [S] (the embedded tokens through the norm, the projection, the
    convolution and the step): ((x, dt, A, B, C, D), (y, dx, ddt, dB, dC)),
    the last four the cotangent w [S, H, P] of y pulled back through the
    position-by-position scan. What a run holds the program's scan to on
    the SAME inputs, where nothing else's rounding stands between the two."""
    def both(params, tokens, w):
        with jax.default_matmul_precision("highest"):
            lp, kind = blocks_of(params, shape)[0]
            if kind != MAMBA:
                raise ValueError("layer 0 is no Mamba layer")
            h = params["embed"].astype(F32)[tokens]
            _, args = scan_inputs(_rms_norm(h, lp["ln"], shape["layer_norm_epsilon"]), lp, shape)
            x, dt, A, B, C, D = args
            y, pull = jax.vjp(lambda x, dt, B, C: recurrence(x, dt, A, B, C, D), x, dt, B, C)
            return args, (y,) + pull(w.astype(y.dtype))

    return jax.jit(both)(params, tokens, w)

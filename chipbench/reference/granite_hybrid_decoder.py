"""The plain reference: granite-4.0-h-micro (`model_type` granitemoehybrid:
Mamba-2 mixers in ONE group and GQA layers without a rotary, each over a
dense SwiGLU, four muP multipliers, a tied table) trained on PACKED
DOCUMENTS, in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no kernel,
no chunked form: the state-space scan runs POSITION BY POSITION, as its
equation is written (a `lax.scan` over t that carries the state of every
head and takes it as ZERO where the document changes), so that it is
independent of the program's chunked algebra and of its masks; the
convolution is a sum of four masked shifts; attention is a masked softmax
a block of query rows and a head at a time (so it fits beside the step on
the chip). It takes the program's parameter tree and a configuration
file's sizes (HF key names). It imports nothing from ray_tpu. `grads` is
reverse mode through the same functions; the `jax.checkpoint`s (a block, a
head's rows, a segment of 64 positions of the scan) change no number and
are there so that it fits at 8,192 positions.

THE TREE. `embed` [V, D] (tied: no `lm_head`); `final_norm`; `layers`:
groups "0", "1", ... of consecutive layers, each the group's layers of a
KIND stacked in their order: `mamba` (ln, w_in [D, 2 x inner
+ 2 G N + H], conv [K, inner + 2 G N], conv_bias, dt_bias, A_log, D [H],
norm [inner], w_out [inner, D], ln2, w_gate, w_up [D, F], w_down [F, D])
and `attention` (ln, wq, wk, wv, wo, ln2 and the same three). Layer l is
the next unused layer, in its group, of the kind `layer_types`[l] names.

THE SHARE. `vocab_size` rows of the table are held: ids, logits and the
loss are over that slice.

The equations (the published config.json names the sizes; what it leaves
open is ASSUMED, the same in the program: the configuration file's
`assumed`). d(t) is the document of position t (None: one document).
H = `mamba_n_heads` of P = `mamba_d_head`, G = `mamba_n_groups`, N =
`mamba_d_state`, K = `mamba_d_conv`:

  h_0 = `embedding_multiplier` x Emb(t)
  layer:     h += `residual_multiplier` x mixer(RMSNorm(h; ln));
             h += `residual_multiplier` x W_down (silu(u W_gate) . u W_up),
             u = RMSNorm(h; ln2); RMSNorm at `rms_norm_eps`.
  mamba      [z | xBC | dt] = u W_in (widths H P | H P + 2 G N | H);
             xBC = SiLU(conv(xBC) + b), conv_t = sum_j taps[j] x_{t-j} over
             the j < K with d(t - j) = d(t), nothing before the sequence;
             x [H, P], B, C [G, N], head h reads group h // (H / G); dt =
             softplus(dt + dt_bias); A = -exp(A_log); H_t = exp(dt_t A)
             H_{t-1} + dt_t x_t B_t^T with H_{t-1} taken as 0 where d(t) !=
             d(t - 1) and at t = 0, y_t = H_t C_t + D x_t; y = RMSNorm(y
             SiLU(z); norm) over G groups of H P / G channels; out = y W_out.
  attention  q [`num_attention_heads` x hd], k, v [`num_key_value_heads` x
             hd] = u Wq, u Wk, u Wv, hd = hidden / heads; NO rotary; scores
             q k^T x `attention_multiplier`, key j visible to query i when
             j <= i and d(j) = d(i); query head i reads key-value head i //
             (heads / kv); out = concat(softmax(scores) v) Wo.
  head       logits = RMSNorm(h; final_norm) Emb^T / `logits_scaling` over
             the held slice; the loss is the mean cross-entropy over the
             targets the batch's `mask` keeps (all of them without one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STATE = jnp.float32  # the carried state's dtype
SEGMENT = 64  # positions whose states the gradient makes again at a time (no result reads it)
ROWS = 1024   # query rows whose scores are alive at a time


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def documents(segment_ids, positions: int):
    """ids [S] or None -> d [S] int32: the document a position lies in (ONE without ids)."""
    return jnp.zeros((positions,), jnp.int32) if segment_ids is None else segment_ids


def reads_back(d, j: int):
    """[S] bool: whether position t may read position t - j: it exists and
    lies in t's document."""
    if j == 0:
        return jnp.ones_like(d, bool)
    return jnp.concatenate([jnp.zeros((j,), bool), d[j:] == d[:-j]])


def starts(d):
    """[S] bool: whether position t takes the state before it as zero: the
    sequence's first, and every position whose document is not the one before's."""
    return ~reads_back(d, 1)


def conv(x, taps, bias, d):
    """x [S, C], taps [K, C], bias [C] -> sum_j taps[j] x_{t-j} + b over the
    j that `reads_back` allows: a sum of K masked shifts, nothing ahead of t."""
    s = x.shape[0]
    shifted = lambda j: jnp.concatenate([jnp.zeros((j, x.shape[1]), x.dtype), x])[:s]  # noqa: E731
    return sum(taps[j] * jnp.where(reads_back(d, j)[:, None], shifted(j), 0)
               for j in range(taps.shape[0])) + bias


def step_of(dt, lp):
    """dt [S, H] -> the step softplus(dt + dt_bias) > 0."""
    return jax.nn.softplus(dt + lp["dt_bias"])


def group_of_head(heads: int, groups: int):
    """[H]: the group of B and C that head h reads."""
    return jnp.arange(heads) // (heads // groups)


def recurrence(x, dt, A, B, C, D, d):
    """x [S, H, P], dt [S, H] (> 0), A [H] (< 0), B, C [S, G, N], D [H], d
    [S] -> y [S, H, P]: the selective scan, one position at a time, the
    state taken as zero where `starts(d)`. The positions are walked in
    segments (an outer scan over an inner one, the same steps in the same
    order) only so that the gradient fits at 8,192 positions."""
    s, heads = x.shape[:2]
    of = group_of_head(heads, B.shape[1])

    def step(H, xs):
        x_t, dt_t, B_t, C_t, new = xs
        H = jnp.where(new, 0.0, H.astype(F32)) * jnp.exp(dt_t * A)[:, None, None]
        H = H + (dt_t[:, None] * x_t)[:, :, None] * B_t[of][:, None, :]
        return H.astype(STATE), jnp.einsum("hpn,hn->hp", H, C_t[of]) + D[:, None] * x_t

    seg = max(n for n in range(1, SEGMENT + 1) if s % n == 0)
    xs = tuple(a.reshape(s // seg, seg, *a.shape[1:]) for a in (x, dt, B, C, starts(d)))
    segment = jax.checkpoint(lambda H, xs: jax.lax.scan(step, H, xs))
    _, y = jax.lax.scan(segment, jnp.zeros((heads, x.shape[2], B.shape[2]), STATE), xs)
    return y.reshape(s, *y.shape[2:])


def gated_norm(y, z, w, groups: int, eps):
    """y, z [S, inner] -> GroupRMSNorm(y SiLU(z)): the gate BEFORE the norm."""
    s, inner = y.shape
    g = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, inner) * w


def ssm_groups(shape: dict) -> int:
    """G: how many groups of heads share a B and a C."""
    return shape["mamba_n_groups"]


def scan_inputs(u, lp, shape: dict, d):
    """u [S, D] -> (z [S, inner], what the scan reads: x [S, H, P], dt [S, H],
    A [H], B, C [S, G, N], D [H])."""
    s = u.shape[0]
    heads, p = shape["mamba_n_heads"], shape["mamba_d_head"]
    g, n = ssm_groups(shape), shape["mamba_d_state"]
    inner = heads * p
    if shape["mamba_proj_bias"] or not shape["mamba_conv_bias"]:
        raise ValueError("no bias on the projections and one on the convolution, as published")
    if (inner != shape["mamba_expand"] * shape["hidden_size"]
            or lp["conv"].shape[0] != shape["mamba_d_conv"]):
        raise ValueError("the mixer's widths are not the configuration's")
    zxbcdt = u @ lp["w_in"]
    z, xBC, dt = jnp.split(zxbcdt, [inner, zxbcdt.shape[1] - heads], axis=-1)
    if xBC.shape[1] != inner + 2 * shape["mamba_n_groups"] * n:
        raise ValueError("the convolution's channels are not x, B and C at the file's sizes")
    xBC = jax.nn.silu(conv(xBC, lp["conv"], lp["conv_bias"], d))
    x, B, C = jnp.split(xBC, [inner, inner + (xBC.shape[1] - inner) // 2], axis=-1)
    # B's and C's channels as `ssm_groups` groups (the published: ONE, of the whole state)
    return z, (x.reshape(s, heads, p), step_of(dt, lp), -jnp.exp(lp["A_log"]),
               B.reshape(s, g, -1), C.reshape(s, g, -1), lp["D"])


def mamba_mixer(u, lp, shape: dict, d):
    z, args = scan_inputs(u, lp, shape, d)
    y = recurrence(*args, d).reshape(u.shape[0], -1)
    y = gated_norm(y, z, lp["norm"], shape["mamba_n_groups"], shape["rms_norm_eps"])
    return y @ lp["w_out"]


def rotary(q, k, shape: dict):
    """q, k [S, heads, hd] as the scores read them: unchanged (`position_embedding_type` nope)."""
    return q, k


def softmax_scale(shape: dict):
    """What the scores are multiplied by: `attention_multiplier`, NOT hd ** -0.5."""
    return shape["attention_multiplier"]


def visible(d, rows):
    """[len(rows), S] bool: key j visible to query i: not after it, and in its document."""
    at = jnp.arange(d.shape[0])
    return (at[None, :] <= rows[:, None]) & (d[None, :] == d[rows][:, None])


def attention_mixer(u, lp, shape: dict, d):
    s, heads, kv = u.shape[0], shape["num_attention_heads"], shape["num_key_value_heads"]
    hd = shape["hidden_size"] // heads
    if shape["position_embedding_type"] != "nope" or shape["attention_bias"]:
        raise ValueError("no rotary and no bias, as published")
    q = (u @ lp["wq"]).reshape(s, heads, hd)
    k = (u @ lp["wk"]).reshape(s, kv, hd)
    v = (u @ lp["wv"]).reshape(s, kv, hd)
    q, k = rotary(q, k, shape)
    block = max(n for n in range(1, min(ROWS, s) + 1) if s % n == 0)

    def rows_of_a_head(at):
        i, first = at
        rows = first + jnp.arange(block)
        scores = (q[rows, i] @ k[:, i // (heads // kv)].T) * softmax_scale(shape)
        return jax.nn.softmax(jnp.where(visible(d, rows), scores, -jnp.inf), axis=-1) \
            @ v[:, i // (heads // kv)]

    # a block of rows of a head at a time, so that only [block, S] scores are alive
    # (the gradient makes them again, for the same reason)
    grid = jnp.stack(jnp.meshgrid(jnp.arange(heads), jnp.arange(0, s, block), indexing="ij"),
                     axis=-1).reshape(-1, 2)
    o = jax.lax.map(jax.checkpoint(rows_of_a_head), (grid[:, 0], grid[:, 1]))
    o = o.reshape(heads, s, hd)
    return jnp.swapaxes(o, 0, 1).reshape(s, heads * hd) @ lp["wo"]


def swiglu(u, lp, shape: dict):
    if shape["num_local_experts"] or lp["w_gate"].shape[1] != shape["shared_intermediate_size"]:
        raise ValueError("a dense SwiGLU of `shared_intermediate_size`, as published")
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]


def blocks_of(params, shape: dict) -> list:
    """[(layer's params, kind)] in layer order, from the tree's own layout:
    the groups "0", "1", ... hold the next layers of the stack, each kind's
    stacked in their order."""
    kinds = shape["layer_types"][:shape["num_hidden_layers"]]
    out = []
    for key in sorted(params["layers"], key=int):
        group = params["layers"][key]
        held = {kind: jax.tree.leaves(group[kind])[0].shape[0] for kind in group}
        used = dict.fromkeys(group, 0)
        for kind in kinds[len(out):len(out) + sum(held.values())]:
            i = used[kind]
            out.append((jax.tree.map(lambda w: w[i].astype(F32), group[kind]), kind))
            used[kind] += 1
        if used != held:
            raise ValueError("the parameter tree's groups are not the configuration's layers")
    if len(out) != len(kinds):
        raise ValueError("the parameter tree's depth is not the configuration's")
    return out


def block(h, lp, kind: str, shape: dict, d):
    """One layer: the mixer of its kind, then the SwiGLU, each on the normed
    stream and added at `residual_multiplier`."""
    eps, r = shape["rms_norm_eps"], shape["residual_multiplier"]
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    h = h + r * mixer(_rms_norm(h, lp["ln"], eps), lp, shape, d)
    return h + r * swiglu(_rms_norm(h, lp["ln2"], eps), lp, shape)


def embedded(params, tokens, shape: dict):
    return shape["embedding_multiplier"] * params["embed"].astype(F32)[tokens]


def logits(params, tokens, shape: dict, segment_ids=None):
    """One sequence [S] (its documents `segment_ids` [S] or None) -> logits
    [S, V] over the held slice."""
    if tokens.shape[0] > shape["max_position_embeddings"]:
        raise ValueError(f"{tokens.shape[0]} tokens: over the published "
                         f"{shape['max_position_embeddings']} positions")
    if not shape["tie_word_embeddings"]:
        raise ValueError("a tied table, as published")
    d = documents(segment_ids, tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        h = embedded(params, tokens, shape)
        for lp, kind in blocks_of(params, shape):
            # the gradient keeps a block's input and runs the block again
            h = jax.checkpoint(lambda h, lp, kind=kind: block(h, lp, kind, shape, d))(h, lp)
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        return ((h @ params["embed"].astype(F32).T) / shape["logits_scaling"]).astype(jnp.float32)


def kept(mask, targets):
    """The targets the loss keeps, [S] float32: the batch's mask (None: all)."""
    return jnp.ones(targets.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)


def sequence(params, tokens, targets, shape: dict, segment_ids=None, mask=None):
    """One sequence [S] -> (the kept targets' cross-entropies [S] (nats), over
    the held slice; zero where the mask drops a target)."""
    lg = logits(params, tokens, shape, segment_ids)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0] * kept(mask, targets)


def _rows(batch_array, b):
    return None if batch_array is None else batch_array[b]


def loss(params, tokens, targets, shape: dict, segment_ids=None, mask=None):
    """The training loss of a batch [B, S]: the mean cross-entropy over the
    targets the mask keeps, sequence by sequence."""
    one = jax.jit(lambda p, t, y, s, m: sequence(p, t, y, shape, s, m).sum())
    total = sum(one(params, tokens[b], targets[b], _rows(segment_ids, b), _rows(mask, b))
                for b in range(tokens.shape[0]))
    return total / jnp.maximum(kept(mask, targets).sum(), 1.0)


def grads(params, tokens, targets, shape: dict, segment_ids=None, mask=None):
    """The gradient of `loss` by every leaf of the parameter tree, float32:
    reverse mode through the equations above, sequence by sequence (the
    table's is the sum of its two uses)."""
    one = jax.jit(jax.grad(lambda p, t, y, s, m: sequence(p, t, y, shape, s, m).sum()))
    total = None
    for b in range(tokens.shape[0]):
        g = one(params, tokens[b], targets[b], _rows(segment_ids, b), _rows(mask, b))
        total = g if total is None else jax.tree.map(jnp.add, total, g)
    weight = jnp.maximum(kept(mask, targets).sum(), 1.0)
    return jax.tree.map(lambda g: g / weight, total)


def first_scan(params, tokens, shape: dict, w, segment_ids=None):
    """Layer 0's scan ALONE, on what that layer hands it for one sequence
    [S] WITH its documents (the embedded tokens through the norm, the
    projection, the convolution and the step): ((x, dt, A, B, C, D), (y, dx,
    ddt, dB, dC)), the last four the cotangent w [S, H, P] of y pulled back
    through the position-by-position scan (dB and dC [S, G N]). What a run holds the program's
    scan to on the SAME inputs and ids, where nothing else's rounding
    stands between the two."""
    def both(params, tokens, w, segment_ids):
        d = documents(segment_ids, tokens.shape[0])
        with jax.default_matmul_precision("highest"):
            lp, kind = blocks_of(params, shape)[0]
            if kind != "mamba":
                raise ValueError("layer 0 is no Mamba layer")
            u = _rms_norm(embedded(params, tokens, shape), lp["ln"], shape["rms_norm_eps"])
            _, args = scan_inputs(u, lp, shape, d)
            x, dt, A, B, C, D = args
            y, pull = jax.vjp(lambda x, dt, B, C: recurrence(x, dt, A, B, C, D, d), x, dt, B, C)
            dx, ddt, dB, dC = pull(w.astype(y.dtype))
            # B's and C's cotangents a position's channels in their order, whatever the groups
            return args, (y, dx, ddt, dB.reshape(dB.shape[0], -1), dC.reshape(dC.shape[0], -1))

    return jax.jit(both)(params, tokens, w, segment_ids)

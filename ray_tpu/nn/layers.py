"""Functional NN building blocks (pure jax, pytree params).

The framework's model layer is deliberately functional: params are plain
pytrees built next to a parallel pytree of logical-axis annotations
(see ray_tpu.parallel.sharding). No module objects, no tracing magic —
everything stays jit/scan/shard_map-friendly.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.parallel.context import current_mesh


def head_major(x: jax.Array) -> jax.Array:
    """x [B, heads, S, hd], held in memory in that order: the tile is
    (S, hd), the tokens and a head's channels, and always full. On a TPU
    an array's last two dimensions are its tile, 8 or 16 rows of 128
    lanes: with [B, S, heads, hd] the heads are the tile's rows (8
    key-value heads half padding in bfloat16) and every kernel that
    wants [B, H, S, hd] is met by a transpose. The attention sublayers
    (models/llama.py, cca.py, mla.py) keep q, k and v in this order from
    where the projections write them to where `wo` contracts them; the
    first two pin it here where a matmul writes: left to itself the
    compiler lays such an array out by what the next operation would
    like (the tokens in the lanes for a shift, the channels in the
    sublanes for the rotary's slices) and copies between the two.

    Under a mesh of several devices the layout is the compiler's: the
    constraint has no partitioning rule, and the partitioner gathers the
    whole array onto every device to apply it (26 all-gathers of arrays
    the size of q in the fsdp 2 x tp 2 step; rehearsal, PR 38)."""
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return x
    return with_layout_constraint(x, Layout(major_to_minor=(0, 1, 2, 3)))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in fp32 regardless of input dtype (numerics on the VPU are cheap)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0) -> tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables [max_seq, head_dim//2] in fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotate pairs of features. x: [B, S, H, D]; positions: [B, S] or [S]."""
    c = cos[positions]  # [..., S, D/2]
    s = sin[positions]
    if c.ndim == 2:  # positions was [S]
        c = c[None, :, None, :]
        s = s[None, :, None, :]
    else:  # [B, S, D/2]
        c = c[:, :, None, :]
        s = s[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _swap_halves(x: jax.Array, rot: int | None = None) -> jax.Array:
    """[x2, x1] of x = [x1, x2] along the last axis of x [B, H, S, D], as
    a product with the permutation matrix: exact (one term a sum; at the
    highest precision, so for float32 operands too), and on the MXU,
    where a slice or a concatenate that starts inside the 128
    lanes is a pass of its own over half-empty tiles (PERF.md section 6,
    PR 33 and PR 38). The batch is spelled as a BATCH dimension of the
    product, as models/mla.py::_up does: the "dots" remat policy saves
    every product without one, and this one is cheaper made again.
    `rot` < D (a rotary on part of a head): the halves of the FIRST
    `rot` channels change places and the rest stay: the same product
    with a block of the permutation, still nothing cut inside the lanes."""
    D = x.shape[-1]
    if rot is None or rot == D:
        swap = jnp.roll(jnp.eye(D, dtype=x.dtype), D // 2, axis=1)
    else:
        swap = jnp.eye(D, dtype=x.dtype).at[:rot, :rot].set(
            jnp.roll(jnp.eye(rot, dtype=x.dtype), rot // 2, axis=1))
    return jax.lax.dot_general(
        x, jnp.broadcast_to(swap, (x.shape[0], D, D)), (((3,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(x: jax.Array, cc: jax.Array, ss: jax.Array, rot: int | None = None) -> jax.Array:
    """x * [c, c] + [x2, x1] * [-s, s] in float32: `apply_rope`'s
    x1 c - x2 s and x2 c + x1 s, product for product. With `rot` < D the
    tables carry 1 and 0 over the channels that pass through."""
    return (x.astype(jnp.float32) * cc
            + _swap_halves(x, rot).astype(jnp.float32) * ss).astype(x.dtype)


def _rotate_fwd(x, cc, ss, rot):
    return _rotate(x, cc, ss, rot), (cc, ss)


def _rotate_bwd(rot, tables, g):
    # the rotation by the opposite angle, the swap made BEFORE the products as in
    # the forward: what differentiating `apply_rope` gives, g1 c + g2 s and g2 c -
    # g1 s rounded once (differentiating `_rotate` would round the swapped half to
    # g's dtype on its way through the product)
    cc, ss = tables
    return _rotate(g, cc, -ss, rot), jnp.zeros_like(cc), jnp.zeros_like(ss)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def apply_rope_head_major(x: jax.Array, cos: jax.Array, sin: jax.Array,
                          positions: jax.Array) -> jax.Array:
    """`apply_rope` for x [B, H, S, D], the heads a major axis: the same
    function, value for value and gradient for gradient. The tables
    broadcast over a major axis and every operand is a full (S, D) tile:
    nothing is cut at lane D / 2, the halves change places on the MXU
    (`_swap_halves`), so one fused pass reads x and writes the result."""
    c = cos[positions]  # [..., S, D/2]
    s = sin[positions]
    if c.ndim == 2:  # positions was [S]
        c, s = c[None], s[None]
    cc = jnp.concatenate([c, c], axis=-1)[:, None]  # [1 or B, 1, S, D]
    ss = jnp.concatenate([-s, s], axis=-1)[:, None]
    return _rotate(x, cc, ss)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's blended rotary frequencies [dim / 2] (arXiv:2309.00071, as
    HF's `_compute_yarn_parameters` computes them, truncating): channel
    pair i turns at 1 / theta^(2i / dim) where it makes more than
    `beta_fast` turns over `original_max` positions (extrapolated, kept),
    at that / `factor` where it makes fewer than `beta_slow`
    (interpolated), and on a linear ramp between the two, whose ends are
    the floor and the ceiling of the pairs that make exactly those
    turns, clamped to [0, dim - 1]. The attention factor that goes with
    it multiplies the tables (`rope_tables`), not the frequencies."""
    def pair_of(turns):  # the (fractional) pair that makes `turns` turns over the original context
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(pair_of(beta_fast)), 0), min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extrapolated = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0.0, 1.0)
    return (extrapolated / factor * ramp + extrapolated * (1.0 - ramp)).astype(np.float32)


def rope_tables(positions: jax.Array, inv_freq, scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """cos and sin of positions x inv_freq, times `scale` (YaRN's
    attention factor), float32 [1 or B, S, rot / 2]: made from the
    step's own positions ([S] or [B, S]), so no table of `max_seq` rows
    (a million-position model's would be 0.5 GiB) is ever built."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    if ang.ndim == 2:
        ang = ang[None]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate_head_major(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """The rotary of `rope_tables`' cos and sin [1 or B, S, rot / 2] on the
    FIRST rot channels of every head of x [B, H, S, D], half-split
    pairing within them (channel i with i + rot / 2); the other D - rot
    pass through. `apply_rope_head_major`'s one fused pass: the tables
    are padded with cos 1 and sin 0 over the channels that pass and the
    swap is a block of the permutation (`_swap_halves`), so a rotary on
    half a head cuts nothing at lane 64."""
    D, rot = x.shape[-1], 2 * cos.shape[-1]
    keep = cos.shape[:-1] + (D - rot,)
    cc = jnp.concatenate([cos, cos, jnp.ones(keep, cos.dtype)], axis=-1)[:, None]
    ss = jnp.concatenate([-sin, sin, jnp.zeros(keep, sin.dtype)], axis=-1)[:, None]
    return _rotate(x, cc, ss, rot)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    gate = jnp.einsum("bsd,df->bsf", x, w_gate.astype(x.dtype))
    up = jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, w_down.astype(x.dtype))


def init_dense(key: jax.Array, shape: tuple[int, ...], dtype, scale: float | None = None) -> jax.Array:
    """Truncated-normal fan-in init."""
    fan_in = shape[0]
    std = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std).astype(dtype)


def cross_entropy_loss(
    logits: jax.Array,  # [B, S, V] (any float dtype; upcast internally)
    targets: jax.Array,  # [B, S] int32
    mask: jax.Array | None = None,  # [B, S] 0/1
) -> tuple[jax.Array, jax.Array]:
    """Returns (mean_nll, total_weight). fp32 log-softmax for stability."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / total, total


# ---------------------------------------------------------------------------
# fused lm-head + cross entropy
# ---------------------------------------------------------------------------
#
# The naive path (forward() -> [T, V] logits -> cross_entropy_loss) is
# HBM-bound, not MXU-bound: XLA materializes the fp32 logits, the
# logsumexp intermediates, the take_along_axis gather, and the softmax
# in the backward. This custom-VJP version:
#   * forward: ONE [T, V] fp32 materialization (the matmul output),
#     read twice (lse, gold-via-iota-compare); no gather;
#   * backward: forms d_logits = (softmax - onehot) * coef in bf16 as the
#     producer of the two grad matmuls' operand, never in HBM;
#   * residuals are h, w, targets, lse. The backward WRITES the logits as a
#     second matmul, but in a compiled step XLA merges it with the
#     forward's: one fp32 [T, V] buffer (4.6 GiB in `olmoe-train`, 1.5 in
#     `m7b-train`: what caps their batches) is held from forward to
#     backward and read by the sum pass and by both gradients.
# The weight gradient stays XLA's: it fuses that matmul with the
# optimizer's update of the head into one operation of three [D, V]
# results and never writes the gradient to HBM (a separate kernel's
# float32 [D, V] result lives through the whole backward pass). How fast
# that operation runs hangs on the VMEM the step's compile scopes to one
# operation (train/step.py; PERF.md, PR 29).
# The reference delegates this to torch CE inside vLLM/torch workers;
# the TPU design needs it fused for the same reason flash attention
# does (HBM bandwidth is the ceiling, SURVEY §5.7).


@jax.custom_vjp
def _fused_nll(h, w, targets):
    """Per-token negative log-likelihood of a linear head.

    h: [T, D] (bf16 typical), w: [D, V], targets: [T] int32 -> [T] f32.
    """
    nll, _ = _fused_nll_fwd(h, w, targets)
    return nll


def _logits_f32(h, w):
    return jax.lax.dot_general(
        h, w.astype(h.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [T, V] fp32 accumulation off bf16 operands (full-rate MXU)


def _fused_nll_fwd(h, w, targets):
    logits = _logits_f32(h, w)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    # gold logit via iota-compare reduction: a [T, V] compare+select
    # feeding a row sum fuses into one pass; take_along_axis lowers to
    # a slow TPU gather (and a scatter in the backward)
    V = w.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1)
    gold = jnp.sum(
        jnp.where(iota == targets[:, None], logits, 0.0), axis=-1
    )
    return lse - gold, (h, w, targets, lse)


def _fused_nll_bwd(res, g):  # g: [T] f32 cotangent of nll
    h, w, targets, lse = res
    logits = _logits_f32(h, w)  # the forward's buffer again, once compiled
    V = w.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1)
    p = jnp.exp(logits - lse[:, None])
    onehot = (iota == targets[:, None]).astype(jnp.float32)
    dl = ((p - onehot) * g[:, None]).astype(h.dtype)  # [T, V] bf16
    dh = jax.lax.dot_general(
        dl, w.astype(h.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(h.dtype)
    dw = jax.lax.dot_general(
        h, dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(w.dtype)
    return dh, dw, None


_fused_nll.defvjp(_fused_nll_fwd, _fused_nll_bwd)


def fused_cross_entropy_loss(
    h: jax.Array,        # [B, S, D] final hidden states (pre lm-head)
    w: jax.Array,        # [D, V] lm-head weight
    targets: jax.Array,  # [B, S] int32
    mask: jax.Array | None = None,  # [B, S] 0/1
) -> tuple[jax.Array, jax.Array]:
    """(mean_nll, total_weight) without materializing fp32 softmax state."""
    B, S, D = h.shape
    nll = _fused_nll(h.reshape(B * S, D), w, targets.reshape(B * S))
    nll = nll.reshape(B, S)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / total, total


Initializer = Callable[[jax.Array, tuple[int, ...]], jax.Array]

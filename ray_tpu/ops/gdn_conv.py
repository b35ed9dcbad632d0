"""What stands between a linear-attention layer's projections and its
recurrence, as one Pallas kernel forward and one backward.

A gated-delta-rule mixer (models/olmo_hybrid.py) takes each of its q, k
and v projections x [B, H, T, d] (the matmul's bfloat16 output) through

    float32 -> a causal depthwise convolution of K taps over time
    (pre_t = sum_j taps[j] x_{t-j}, zeros before the sequence; no bias
    there: a Mamba-2 mixer, models/nemotron_h.py, hands one a channel,
    pre_t + b, through the same two kernels)
    -> SiLU -> for q and k an L2 norm over the head's d channels and a
    scale (s / sqrt(sum s^2 + eps) x scale),

elementwise work over 47 M elements a layer that XLA made about twenty
fusions of, forward and transpose, each a pass over float32 in HBM: the
shift of 1-3 rows along a tile's sublanes is no free index remap there
(29.1 ms of a 229 ms step of `olmo-hybrid-train`; PERF.md section 6,
PR 48). `gdn_conv` is the same chain in ONE pass a tensor forward and
ONE backward: the arithmetic, its order of casts and its constants are
the jax.numpy chain's (float32 throughout after the one cast), which
tests/test_gdn_conv.py keeps as the reference.

THE FORWARD KERNEL has a grid of (batch x heads, blocks of `_ROWS`
positions). A step reads its block of x where it stands (the index map
finds the head: no reshape or copy stands between the projection and the
kernel, or between the kernel and ops/gated_delta.py's, which read its
float32 output where it is written) and, by a second spec, the 16 rows
before the block (zeros at the sequence's start), and stages both as
float32 into a VMEM scratch. It then walks the block a tile of 64-128
rows at a time, the walk unrolled, so that a tile's whole chain stays in
registers: a load a tap, j rows back (a static offset along the
sublanes, which the load unit takes where XLA's pad-and-slice was a pass
over HBM), the multiply-adds, SiLU, the norm's lane reduction, one store.

THE BACKWARD KERNEL walks the blocks from the sequence's end. It makes
the pre-activation again from x (seven operations an element: nothing of
the forward is saved but x, which the "dots" remat policy holds
already), takes dy through the norm and the SiLU to dpre, and writes the
reverse convolution dx_t = sum_j taps[j] dpre_{t+j} in x's dtype: the 8
rows of dpre after a block are carried in VMEM from the step before.
The taps' gradients sum_t dpre_t x_{t-j} are accumulated in float32 in
the output block, which stays in VMEM for a head's walk, 8 sublanes
apart, and are summed over the sublanes and the batch outside.

Under the block's `jax.checkpoint` the forward kernel runs again in the
backward, as the fusions it replaced did: float32 q, k, v of three layers
held across the backward would be 567 MB.

A BIAS rides as one more row of a head's taps, [H, K + 1, d]: the forward
adds it to the pre-activation, the backward sums dpre alone into that
row's gradient where a tap's sums dpre x_{t-j} (its x is 1). Without a
bias both kernels are traced as they were (the lowered text of a model
that has none is unchanged).

PACKED DOCUMENTS (`segment_ids`; a Mamba-2 mixer trained on documents
packed into one sequence, models/granite_hybrid.py): tap j of position t
reads position t - j only where both lie in one document. What a row
needs is ONE number, its distance from its document's first position
(`_since`, no more than K - 1), which both kernels read as a third
array [B, T, d] in x's dtype, a sequence's heads sharing it (in bfloat16 a
quarter of what a step writes): the forward zeroes a tap's load where
the distance is under the tap's number; the backward does so making the
pre-activation again, and gives position t's dx tap j's term only
where t + j is that far inside its document (the distances of the rows
after a block are carried in VMEM beside dpre's). Without `segment_ids`
both kernels are traced as they were.

ONE path, no option: off the TPU the same kernels run under the Pallas
interpreter, as ops/gated_delta.py's do. Any d, any number of taps up to
9 (the rows kept beside a block are 8), any T (a sequence that is no
whole number of blocks is padded with zero rows, which write nothing
that is kept).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs

L2_EPS = 1e-6  # fla's l2norm: x / sqrt(sum x^2 + eps)
# positions a grid step holds: 240 steps a tensor at 30 heads of 4,096 positions
_ROWS = 512
# registers an array of a tile of the walk inside a step fills (8 rows x 128 lanes each): 128
# rows at keys of 96, 64 at values of 192, so that the chain from the loads to the store lives
# in the 64 registers (32 a tile are 7-30% slower at 192). The walk is unrolled: as a
# `fori_loop` the same kernels took 2.8 x the time forward and 2 x backward, and a static offset
# is what lets a tap be a load; fewer, larger tiles are less to trace (PERF.md section 6, PR 48)
_TILE = 16
# rows kept beside a block: one float32 sublane tile, of which the taps read K - 1
_HALO = 8
# rows the second spec reads before a block: one bfloat16 sublane tile
_BEFORE = 16
_LANES = 128
_F32 = jnp.float32


def _tile_rows(d: int) -> int:
    """Rows of a tile of the walk at heads of d: `_TILE` registers an array, 8 at least."""
    return max(1, _TILE // -(-d // _LANES)) * _HALO


def _stage(x_scr, x_ref, before_ref, at_start):
    """The block and the `_HALO` rows before it, float32: rows `_HALO`..
    of the scratch the block, rows 0.. what precedes it, zeros where the
    block is the sequence's first."""
    x_scr[_HALO:, :] = x_ref[...].astype(_F32)
    before = before_ref[...].astype(_F32)[_BEFORE - _HALO:]
    x_scr[:_HALO, :] = jnp.where(at_start, 0.0, before)


def _taps_back(x_scr, at, K, sub, since=None):
    """[x_{t-j} for each tap j], each [sub, d], of the `sub` rows at block
    row `at`: a load a tap, j rows back; with `since` [sub, d] (a row's
    positions since its document's first), zeros where t - j lies in the
    document before."""
    back = [x_scr[at + _HALO - j:at + _HALO - j + sub, :] for j in range(K)]
    if since is None:
        return back
    return back[:1] + [jnp.where(since >= j, back[j], 0.0) for j in range(1, K)]


def _weighted(shifted, taps):
    """sum_j taps[j] x shifted[j], in the reference's order."""
    acc = shifted[0] * taps[0:1]
    for j in range(1, len(shifted)):
        acc = acc + shifted[j] * taps[j:j + 1]
    return acc


def _split_bias(taps, bias):
    """A head's rows [K (+ 1), d] -> (its K taps, its bias [1, d] or None)."""
    return (taps[:-1], taps[-1:]) if bias else (taps, None)


def _fwd_kernel(x_ref, before_ref, taps_ref, *rest, scale, sub, bias=False, docs=False):
    since_ref, y_ref, x_scr = rest if docs else (None, *rest)
    rows = y_ref.shape[0]
    _stage(x_scr, x_ref, before_ref, pl.program_id(1) == 0)
    taps, b = _split_bias(taps_ref[...], bias)
    for at in range(0, rows, sub):
        since = since_ref[at:at + sub, :].astype(_F32) if docs else None
        pre = _weighted(_taps_back(x_scr, at, taps.shape[0], sub, since), taps)
        if bias:
            pre = pre + b
        s = pre * jax.nn.sigmoid(pre)
        if scale is not None:
            s = s * jax.lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + L2_EPS) * scale
        y_ref[at:at + sub, :] = s


def _bwd_kernel(x_ref, before_ref, taps_ref, dy_ref, *rest, scale, sub, bias=False, docs=False):
    since_ref, dx_ref, dtaps_ref, x_scr, d_scr, s_scr = rest if docs else (None, *rest, None)
    rows = dy_ref.shape[0]
    i, blocks = pl.program_id(1), pl.num_programs(1)     # step i holds block blocks - 1 - i
    _stage(x_scr, x_ref, before_ref, i == blocks - 1)
    # the rows of dpre after this block: the first rows of the block the step before held
    d_scr[rows:, :] = jnp.where(i == 0, 0.0, d_scr[:_HALO, :])
    if docs:   # and those rows' positions since their document's first, carried likewise
        s_scr[rows:, :] = jnp.where(i == 0, 0.0, s_scr[:_HALO, :])
        s_scr[:rows, :] = since_ref[...].astype(_F32)
    taps, b = _split_bias(taps_ref[...], bias)
    K = taps.shape[0]
    sums = [jnp.zeros(dtaps_ref.shape[1:], _F32)] * (K + bias)
    for at in reversed(range(0, rows, sub)):
        shifted = _taps_back(x_scr, at, K, sub, s_scr[at:at + sub, :] if docs else None)
        pre = _weighted(shifted, taps)
        if bias:
            pre = pre + b
        sig = jax.nn.sigmoid(pre)
        ds = dy_ref[at:at + sub, :]
        if scale is not None:
            s = pre * sig
            r = jax.lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + L2_EPS)
            ds = (ds - s * (r * r * jnp.sum(ds * s, axis=-1, keepdims=True))) * (r * scale)
        dpre = ds * (sig * (1.0 + pre * (1.0 - sig)))
        d_scr[at:at + sub, :] = dpre
        ahead = [d_scr[at + j:at + j + sub, :] for j in range(K)]     # dpre_{t+j}
        if docs:   # position t + j read tap j of t only from inside its own document
            ahead = ahead[:1] + [jnp.where(s_scr[at + j:at + j + sub, :] >= j, ahead[j], 0.0)
                                 for j in range(1, K)]
        dx_ref[at:at + sub, :] = _weighted(ahead, taps).astype(dx_ref.dtype)
        # a tap's gradient, 8 sublanes apart: adds of whole registers, no reduction in the walk
        # (the bias's row, the last, sums dpre alone)
        sums = [acc + (dpre if back is None else dpre * back).reshape(
                    sub // _HALO, _HALO, -1).sum(axis=0)
                for acc, back in zip(sums, shifted + [None] * bias)]

    @pl.when(i == 0)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for j in range(K + bias):
        dtaps_ref[j] += sums[j]


def _specs(rows, H, d, K, blocks, reverse):
    """Block specs of (x or y, the rows before a block, a head's taps) on
    the grid (batch x heads, blocks of rows), the blocks walked from the
    end if `reverse`. x is read where it stands, [B, H, T, d]: a step sees
    its head's rows as [rows, d]."""
    step = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    return (pl.BlockSpec((None, None, rows, d), lambda bh, i: (bh // H, bh % H, step(i), 0)),
            pl.BlockSpec((None, None, _BEFORE, d), lambda bh, i: (
                bh // H, bh % H, jnp.maximum(step(i) * (rows // _BEFORE) - 1, 0), 0)),
            pl.BlockSpec((None, K, d), lambda bh, i: (bh % H, 0, 0)))


def _scratch(rows, d):
    return pltpu.VMEM((rows + _HALO, d), _F32)


# the backward carries dpre's rows and the taps' sums along a head's blocks
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# a jitted function of its own, forward and backward each: a model's layers share ONE trace of
# a kernel's body a shape, and the compiled step names the kernels after these functions
# (ops/gated_delta.py has what tracing a body a layer and pass cost a start-up)
def _with_documents(kernel, since, rows, H, d, blocks, reverse):
    """(the kernel told whether it has the ref, the ref's spec, its operand): with `since`
    None both kernels are traced as they were, operand for operand. `since` [B, T, d] is
    shared by a sequence's heads: a step sees its block's rows."""
    if since is None:
        return kernel, [], ()
    step = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    spec = pl.BlockSpec((None, rows, d), lambda bh, i: (bh // H, step(i), 0))
    return functools.partial(kernel, docs=True), [spec], (since,)


@functools.partial(jax.jit, static_argnames=("scale", "rows", "interpret", "bias"))
def gdn_conv_fwd(x, taps, scale, rows, interpret, bias=False, since=None):
    """x [B, H, T, d], T whole blocks of `rows`; taps [H, K, d] float32
    ([H, K + 1, d] with `bias`: the last row); since None or [B, T, d]
    (`_since`) -> [B, H, T, d] float32."""
    B, H, T, d = x.shape
    block, before, head_taps = _specs(rows, H, d, taps.shape[1], T // rows, reverse=False)
    kernel, spec, since = _with_documents(
        functools.partial(_fwd_kernel, scale=scale, sub=min(_tile_rows(d), rows), bias=bias),
        since, rows, H, d, T // rows, reverse=False)
    return pl.pallas_call(
        kernel,
        grid=(B * H, T // rows),
        in_specs=[block, before, head_taps] + spec,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, _F32),
        scratch_shapes=[_scratch(rows, d)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(x, x, taps, *since)


@functools.partial(jax.jit, static_argnames=("scale", "rows", "interpret", "bias"))
def gdn_conv_bwd(x, taps, dy, scale, rows, interpret, bias=False, since=None):
    """-> (dx as x, the gradients of the taps' rows [B x H, K (+ 1), 8, d]
    float32: to be summed over the batch and the 8)."""
    B, H, T, d = x.shape
    K = taps.shape[1]
    block, before, head_taps = _specs(rows, H, d, K, T // rows, reverse=True)
    kernel, spec, since = _with_documents(
        functools.partial(_bwd_kernel, scale=scale, sub=min(_tile_rows(d), rows), bias=bias),
        since, rows, H, d, T // rows, reverse=True)
    return pl.pallas_call(
        kernel,
        grid=(B * H, T // rows),
        in_specs=[block, before, head_taps, block] + spec,
        out_specs=[block, pl.BlockSpec((None, K, _HALO, d), lambda bh, i: (bh, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B * H, K, _HALO, d), _F32)],
        scratch_shapes=[_scratch(rows, d)] * (2 + len(since)),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(x, x, taps, dy, *since)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _chain(scale, rows, interpret, bias, x, taps, since):
    """`taps` [H, K, d], or with `bias` [H, K + 1, d]: the bias the last of a head's rows."""
    return gdn_conv_fwd(x, taps, scale, rows, interpret, bias=bias, since=since)


def _chain_fwd(scale, rows, interpret, bias, x, taps, since):
    return gdn_conv_fwd(x, taps, scale, rows, interpret, bias=bias, since=since), (x, taps, since)


def _chain_bwd(scale, rows, interpret, bias, residuals, dy):
    x, taps, since = residuals
    dx, dtaps = gdn_conv_bwd(x, taps, dy, scale, rows, interpret, bias=bias, since=since)
    H, K, d = taps.shape
    # the documents are no argument to differentiate by: None or zeros
    return (dx, dtaps.reshape(-1, H, K, _HALO, d).sum(axis=(0, 3)),
            None if since is None else jnp.zeros_like(since))


_chain.defvjp(_chain_fwd, _chain_bwd)


def _since(segment_ids: jax.Array, K: int, short: int, d: int, dtype) -> jax.Array:
    """segment_ids [B, T] -> [B, T + short, d] in `dtype`: a position's
    distance from its document's first position (the first of the sequence,
    or one whose id differs from the position before it), no more than K -
    1 (whole numbers under 9: exact in any float dtype), down a head's d
    lanes as the kernels compare it with a tap's number."""
    B, T = segment_ids.shape
    at = jnp.arange(T, dtype=jnp.int32)
    starts = jnp.pad(segment_ids[:, 1:] != segment_ids[:, :-1], ((0, 0), (1, 0)))
    since = jnp.minimum(at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1), K - 1)
    since = jnp.pad(since, ((0, 0), (0, short)))
    return jnp.broadcast_to(since[:, :, None], (B, T + short, d)).astype(dtype)


def gdn_conv(x: jax.Array, taps: jax.Array, scale: Optional[float] = None,
             bias: Optional[jax.Array] = None,
             segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """x [B, H, T, d] (any float dtype), taps [K, H x d] (tap j on position
    t - j), `bias` [H x d] or None, `segment_ids` [B, T] or None -> SiLU
    of the causal depthwise convolution (+ the bias), [B, H, T, d] float32;
    with `scale` (q: d ** -0.5, k: 1.0), its L2 norm over d times `scale`;
    with `segment_ids` (packed documents) tap j of position t reads
    position t - j only where both lie in one document. The module's
    docstring has the kernels. One layer span a call site WHILE TRACING
    (`gdn_conv.kernel`) counts the sites."""
    B, H, T, d = x.shape
    K = taps.shape[0]
    if K - 1 > _HALO:
        raise NotImplementedError(f"{K} taps: the kernels carry {_HALO} rows beside a block")
    if bias is not None and scale is not None:
        raise NotImplementedError("a bias under the L2 norm: no mixer has both")
    tile = _tile_rows(d)
    rows = min(_ROWS, -(-T // tile) * tile)
    short = -(-T // rows) * rows - T
    if short:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, short), (0, 0)))
    head_taps = taps.astype(_F32).reshape(K, H, d).swapaxes(0, 1)
    if bias is not None:   # one more row of a head's taps
        head_taps = jnp.concatenate([head_taps, bias.astype(_F32).reshape(H, 1, d)], axis=1)
    since = None if segment_ids is None else _since(segment_ids, K, short, d, x.dtype)
    with obs.layer_span("gdn_conv.kernel"):
        y = _chain(scale, rows, jax.default_backend() != "tpu", bias is not None, x, head_taps,
                   since)
    return y[:, :, :T] if short else y

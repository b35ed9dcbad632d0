"""A Mamba-2 mixer's gated group RMS norm as one Pallas kernel forward and
one backward.

Between the selective scan and the output projection a Mamba-2 mixer
(models/nemotron_h.py) takes the scan's y [B, T, W] (float32) and the
gate z [B, T, W] (the input projection's bfloat16 output) through

    u   = y * SiLU(float32(z))                    the gate BEFORE the norm
    n   = u * rsqrt(mean_g(u^2) + eps)            mean_g: over the channels of u's GROUP,
                                                  group = channel // (W / groups)
    out = (n * weight) in z's dtype               one learned weight a channel

everything float32 after the one cast of z, `eps` inside the root. XLA
made of these five lines a reshape of the lanes to (groups, W / groups)
and answered it with relayout copies of y, the rsqrt's broadcast as an
array in HBM and a backward fusion a pass, forward, again under the
block's `jax.checkpoint`, and transposed: 23.4 ms of a 233 ms step of
`twotower-train-8k`, where the bytes of the twelve passes want 4.9
(PERF.md section 6, PR 52). `gated_norm` is the same function in ONE pass
forward and ONE backward; the jax.numpy lines are tests/test_gated_norm.py's
reference.

THE FORWARD KERNEL (`gated_norm_fwd`) has a grid of (batch, blocks of
`_ROWS` positions) over the arrays as they stand (token-major: how
ops/ssd.py's forward kernel writes y and how z leaves the projection's
matmul; no reshape, to [B x T, W] or to (groups, W / groups), exists
outside the kernel or in it: in the step compiled for a described v5e
even the free one made XLA write the saved y twice). A step reads its
rows of y and z as they stand and walks them a tile of `_TILE` rows and a
group at a time: a loop over the tiles, the groups unrolled in its body,
each a static slice of whole lane tiles, so that a tile's chain from the
two loads to the store lives in registers, the mean one reduction over
the lanes. (The tiles unrolled too ran no faster on the chip, 0.386 and
0.704 ms a call either way, and took four times as long to trace and six
to compile, which a start-up pays: PERF.md section 6, PR 52.)

THE BACKWARD KERNEL (`gated_norm_bwd`) reads y, z, the weight and the
cotangent of out; nothing of the forward is saved but its INPUTS (y is
what ops/ssd.py names `ssd_out`, z a matmul's output: a remat policy that
saves those holds nothing new across the backward). It makes u, the root
r = rsqrt(mean_g(u^2) + eps) and n again in registers, then

    dn = dout * weight
    du = r * (dn - n * mean_g(dn * n))
    dy = du * SiLU(z)                  in y's dtype: float32, token-major, what
                                       ops/ssd.py's backward kernel reads as it stands
    dz = du * y * SiLU'(z)             in z's dtype
    dweight = sum over rows of dout * n

the weight's gradient accumulated in float32 in an output block that stays
in VMEM for the whole grid, 8 sublanes apart (adds of whole registers, no
reduction over the rows in the walk), and summed over the 8 outside.

Under the block's `jax.checkpoint` the forward kernel runs again in the
backward, as the fusions it replaced did (the normed bfloat16 is the output
projection's operand: 64 MiB a layer at 8,192 tokens of 4,096 channels).

ONE path, no option: off the TPU the same kernels run under the Pallas
interpreter, as ops/ssd.py's do. Shapes: any number of positions (a last
block that is not full is padded with zero rows, which write nothing that
is kept), y and z in any float dtype, W whole groups, a group whole lane
tiles of 128 or, narrower, one that divides a lane tile (the tiny
configurations of the tests); anything else is refused by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs

# rows a grid step holds, and the elements of a block at most (fewer rows where the channels are
# more than 4,096): y 2 MiB + z 1 + out 1 forward, y 2 + z 1 + dout 1 + dy 2 + dz 1 backward,
# 14 MiB double-buffered; the calls state what they hold (`_params`)
_ROWS = 128
_BLOCK = _ROWS * 4096
# rows of a tile of the walk inside a step: one bfloat16 sublane tile, two float32 ones, so
# that a tile of a group of 512 channels is 8 registers an array; on the chip tiles of 8, 16
# and 32 rows in blocks of 64 to 512 run alike, at the bytes' pace (PERF.md section 6, PR 52)
_TILE = 16
_SUBLANES = 8
_LANES = 128
_F32 = jnp.float32


def _gate(y_ref, z_ref, at, lanes):
    """A tile's (y, z, sigmoid(z), SiLU(z)), float32."""
    y, z = y_ref[at, lanes].astype(_F32), z_ref[at, lanes].astype(_F32)
    sig = jax.nn.sigmoid(z)
    return y, z, sig, z * sig


def _root(u, eps):
    return jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)


def _walk(rows, W, group, tile_of_groups):
    """`tile_of_groups(at, [a group's lanes, ...])` on every tile of `_TILE` rows of a block:
    a loop over the tiles, the groups unrolled in its body (static slices of the lanes), so
    that a body is traced a group and not a group and tile."""
    tile = min(_TILE, rows)
    groups = [slice(g, g + group) for g in range(0, W, group)]

    def body(i, carry):
        tile_of_groups(pl.ds(pl.multiple_of(i * tile, tile), tile), groups)
        return carry

    jax.lax.fori_loop(0, rows // tile, body, None)


def _fwd_kernel(y_ref, z_ref, w_ref, out_ref, *, group, eps):
    def tile_of_groups(at, groups):
        for lanes in groups:
            y, _, _, s = _gate(y_ref, z_ref, at, lanes)
            u = y * s
            out_ref[at, lanes] = (u * _root(u, eps) * w_ref[:, lanes]).astype(out_ref.dtype)

    _walk(*out_ref.shape, group, tile_of_groups)


def _bwd_kernel(y_ref, z_ref, w_ref, dout_ref, dy_ref, dz_ref, dw_ref, *, group, eps):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def tile_of_groups(at, groups):
        for lanes in groups:
            y, z, sig, s = _gate(y_ref, z_ref, at, lanes)
            u = y * s
            r = _root(u, eps)
            n = u * r
            dout = dout_ref[at, lanes].astype(_F32)
            dn = dout * w_ref[:, lanes]
            du = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
            dy_ref[at, lanes] = (du * s).astype(dy_ref.dtype)
            dz_ref[at, lanes] = (du * y * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)
            # 8 sublanes apart: adds of whole registers, no reduction over the rows in the walk
            dw_ref[:, lanes] += (dout * n).reshape(-1, _SUBLANES, group).sum(axis=0)

    _walk(*dout_ref.shape, group, tile_of_groups)


def _specs(rows, W):
    """Block specs of (a block of a [B, T, W] array's positions, the weight's one row) on
    the grid (batch, blocks of positions): a step sees its rows as [rows, W]."""
    return (pl.BlockSpec((None, rows, W), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, W), lambda b, i: (0, 0)))


def _params(semantics, rows, W, *blocks):
    """Both of the grid's axes as `semantics`, and the VMEM a step may hold, said here: the
    row blocks (of `blocks`' dtypes) double-buffered, and 8 MiB for the weight's row, its
    gradient's 8 and the walk's own (1.2 MB of them used at the cell's 4,096 channels, 5 at
    16,384: compiled for a described v5e, not run)."""
    held = 2 * rows * W * sum(jnp.dtype(v.dtype).itemsize for v in blocks)
    return pltpu.CompilerParams(dimension_semantics=(semantics,) * 2,
                                vmem_limit_bytes=held + 8 * 2 ** 20)


# a jitted function of its own, forward and backward each: a model's layers share ONE trace of
# a kernel's body a shape, and the compiled step names the kernels after these functions
# (ops/gated_delta.py has what tracing a body a layer and pass cost a start-up)
@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows", "interpret"))
def gated_norm_fwd(y, z, weight, groups, eps, rows, interpret):
    """y, z [B, T, W], T whole blocks of `rows`; weight [1, W] float32 -> [B, T, W] in z's
    dtype."""
    B, T, W = z.shape
    block, row = _specs(rows, W)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, group=W // groups, eps=eps),
        grid=(B, T // rows),
        in_specs=[block, block, row],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        compiler_params=_params("parallel", rows, W, y, z, z),
        interpret=interpret,
    )(y, z, weight)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows", "interpret"))
def gated_norm_bwd(y, z, weight, dout, groups, eps, rows, interpret):
    """-> (dy as y, dz as z, the weight's gradient [8, W] float32: to be summed over the 8)."""
    B, T, W = z.shape
    block, row = _specs(rows, W)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, group=W // groups, eps=eps),
        grid=(B, T // rows),
        in_specs=[block, block, row, block],
        out_specs=[block, block, pl.BlockSpec((_SUBLANES, W), lambda b, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype), jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((_SUBLANES, W), _F32)],
        # the weight's gradient is carried along every block
        compiler_params=_params("arbitrary", rows, W, y, z, dout, y, z),
        interpret=interpret,
    )(y, z, weight, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _norm(groups, eps, rows, interpret, y, z, weight):
    return gated_norm_fwd(y, z, weight, groups, eps, rows, interpret)


def _norm_fwd(groups, eps, rows, interpret, y, z, weight):
    return gated_norm_fwd(y, z, weight, groups, eps, rows, interpret), (y, z, weight)


def _norm_bwd(groups, eps, rows, interpret, residuals, dout):
    dy, dz, dw = gated_norm_bwd(*residuals, dout, groups, eps, rows, interpret)
    return dy, dz, dw.sum(axis=0, keepdims=True)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_norm(y: jax.Array, z: jax.Array, weight: jax.Array, *, groups: int,
               eps: float) -> jax.Array:
    """y, z [B, T, W] (any float dtypes), weight [W] -> RMSNorm over each
    of the `groups` groups of W / groups channels of y * SiLU(z), times the
    weight, [B, T, W] in z's dtype (the module's docstring has the
    equations and the kernels). One layer span a call site WHILE TRACING
    (`gated_norm.kernel`) counts the sites."""
    if y.shape != z.shape or z.ndim != 3 or weight.shape != z.shape[2:]:
        raise ValueError(f"y {y.shape}, z {z.shape}, weight {weight.shape}: y and z alike "
                         "[B, T, W], a weight a channel")
    _, T, W = z.shape
    if groups < 1 or W % groups:
        raise ValueError(f"{W} channels in {groups} groups: a width of whole groups")
    group = W // groups
    if group % _LANES and _LANES % group:
        raise NotImplementedError(
            f"groups of {group} channels: a group is whole lane tiles of {_LANES} (or, "
            "narrower, divides one)")
    # whole tiles: `_ROWS`, fewer at a wide array or a short sequence
    rows = min(_ROWS, max(_BLOCK // W // _TILE, 1) * _TILE, -(-T // _TILE) * _TILE)
    short = -T % rows
    if short:
        y, z = (jnp.pad(v, ((0, 0), (0, short), (0, 0))) for v in (y, z))
    with obs.layer_span("gated_norm.kernel"):
        out = _norm(groups, float(eps), rows, jax.default_backend() != "tpu", y, z,
                    weight.astype(_F32).reshape(1, W))
    return out[:, :T] if short else out

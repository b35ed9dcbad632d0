"""Grouped matmul of the expert layer: a Pallas kernel on a TPU,
`jax.lax.ragged_dot` everywhere else.

`grouped_matmul(lhs [P, K], rhs [E, K, N], group_sizes [E]) -> [P, N]`:
rows `sum(group_sizes[:e]) .. sum(group_sizes[:e + 1])` of `lhs` times
`rhs[e]`; `group_sizes` sums to P (dropless: every row has a group), or,
with `tail=True`, to less: the rows past the last group belong to no
group here (a chip that holds a share of the experts: the pairs routed
to the others, models/moe.py). No tile of any of the three kernels
visits them; their output is zero and so are their gradients, as
`ragged_dot` gives them.

XLA lowers `jax.lax.ragged_dot` on a TPU to a Mosaic kernel of its own,
tiled 512 x 512 x 512, whatever the shapes: at the expert layer's
shapes that re-reads a 512 x 512 weight tile for every 512 rows and
runs at 55-60% of the MXU's peak (PERF.md, PR 26). The kernels here
walk the schedule of JAX's megablox kernels (row tiles in group order, a
tile that straddles a boundary visited once per group) with tiles chosen
from the shapes (`pick_tiles`): the whole contraction in one block, so
an expert's weights stay in VMEM while its rows stream past, and row
tiles cut into sub-blocks, so a straddling tile multiplies only the
sub-blocks that hold rows of the group.

Three kernels, one custom VJP (residuals: lhs, rhs, group_sizes):

  forward          `ragged-dot-tiled`        lhs [P, K] x rhs [E, K, N]
  input gradient   `ragged-dot-tiled-dgrad`  the same kernel on the
                   cotangent, `rhs` read [N, K]-wise by the index map
  weight gradient  `ragged-dot-tiled-wgrad`  lhs^T [K, P] x g [P, N]
                   -> [E, K, N], accumulated over a group's row tiles

The names are the jitted wrappers' and become the custom-calls' names
in the compiled step, which is how a profile's reader tells a grouped
matmul from the flash kernels. bf16 or float32 operands, float32
accumulation, results in the dtypes `ragged_dot` and its transposes
give.

Which path runs is decided by what can be observed (`_kernel_tiles`):
the kernel when the backend is a TPU, no multi-device mesh is ambient
(a Mosaic kernel has no partitioning rule; under a mesh the partitioner
places `ragged_dot`) and `pick_tiles` accepts the shapes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs
from ray_tpu.parallel.context import current_mesh

_F32 = jnp.float32


class Tiles(NamedTuple):
    """Block sizes of one grouped matmul over [P, K] x [E, K, N]: `tm`
    rows of P, `tk` of K, `tn` of N."""

    tm: int
    tk: int
    tn: int


# The height at which a row tile that straddles a group boundary is cut:
# the MXU's own. It divides every `tm` (PERF.md, PR 27: 128 read 2 points
# above 256 and 5-6 above no cut).
_CUT = 128


# What a kernel may hold in VMEM: operand and result blocks double-buffered,
# the float32 accumulator, and what Mosaic keeps beside them on its stack
# (the float32 product of a block, a transposed copy of the operand that is
# read transposed). A v5e core has 128 MiB; Mosaic's default scoped limit
# (16 MiB) is raised to what the chosen tiles need.
_VMEM_BUDGET = 40 << 20
_VMEM_HEADROOM = 8 << 20


def _vmem_bytes(t: Tiles, itemsize: int, *, wgrad: bool) -> int:
    blocks = t.tm * t.tk + t.tk * t.tn + t.tm * t.tn
    if wgrad:  # lhs [tm, tk] read transposed, g [tm, tn] -> out [tk, tn]
        result, transposed = t.tk * t.tn, t.tm * t.tk
    else:  # lhs [tm, tk], rhs [tk, tn] (the input gradient reads it transposed) -> out [tm, tn]
        result, transposed = t.tm * t.tn, t.tk * t.tn
    return 2 * itemsize * blocks + 2 * 4 * result + itemsize * transposed


def _blocks(width: int) -> list:
    """The blocks a dimension of `width` may be cut into: the multiples of a
    lane tile (128) that divide it; a width that none divides goes WHOLE
    where it is a multiple of half a lane tile (an expert of 1,856 = 14.5
    tiles, Nemotron-H's: a block that is an array's whole dimension needs no
    alignment, and Mosaic pads the last half tile); else none."""
    if width % 128:
        return [width] if width % 64 == 0 else []
    return [t for t in range(128, width + 1, 128) if width % t == 0]


def pick_tiles(P: int, K: int, N: int, dtype, *, wgrad: bool = False) -> Optional[Tiles]:
    """Tiles for `[P, K] x [E, K, N]` (or, `wgrad`, `[K, P] x [P, N]`),
    or None where no tile divides the shapes.

    Row tiles of 512 (256 or 128 where 512 does not divide P), cut into
    sub-blocks of `_CUT` rows at group edges. Of the
    (tk, tn) that divide K and N in multiples of a lane tile (`_blocks`:
    or take a width of whole half tiles whole) and fit
    the VMEM budget, the pair that moves the fewest elements through
    HBM, the walk's re-reads counted: `lhs` once per N block; forward,
    a group's weights once if the contraction is ONE block (they stay
    in VMEM while the group's rows stream past) and once per row tile
    if it is not; the weight gradient, `g` once per K block. At the
    expert layer's shapes that is the whole of K and N (PERF.md, PR 27:
    83-88% of the MXU's peak against 52-58% at 512 x 512 x 512).
    """
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    itemsize = jnp.dtype(dtype).itemsize
    tm = next((t for t in (512, 256, 128) if P % t == 0), None)
    if tm is None or not (_blocks(K) and _blocks(N)):
        return None

    def moved(t: Tiles) -> int:
        tiles_k, tiles_n = K // t.tk, N // t.tn
        if wgrad:
            return P * K * tiles_n + P * N * tiles_k
        return P * K * tiles_n + (0 if tiles_k == 1 else (P // tm) * K * N)

    fitting = [t for tk in _blocks(K) for tn in _blocks(N) for t in [Tiles(tm, tk, tn)]
               if _vmem_bytes(t, itemsize, wgrad=wgrad) <= _VMEM_BUDGET]
    return min(fitting, key=lambda t: (moved(t), -t.tk * t.tn), default=None)


# -- the schedule ------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2))  # traced once a step program, not once a call
def _schedule(group_sizes: jax.Array, P: int, tm: int):
    """The order in which a kernel visits row tiles: (group_offsets
    [E + 1], group_ids [T], m_tile_ids [T]), number of visits.

    Groups in order, and for each the row tiles that hold its rows; a
    tile that straddles a boundary is visited once for each group in it,
    consecutively, so a block of the result stays in VMEM between them.
    An empty group gets one visit (the weight gradient writes its
    zeros), so one schedule serves the three kernels of a layer: their
    calls are the same expression and the compiler keeps one. T is the
    most visits there can be, P / tm + E - 1; a kernel's grid is the number
    there are, so the slots past it are never read.
    """
    E, tiles_m = group_sizes.shape[0], P // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = jnp.minimum(offsets[:-1] // tm, tiles_m - 1)
    last = jnp.where(group_sizes > 0, (ends - 1) // tm, first)
    visits = last - first + 1
    visit_ends = jnp.cumsum(visits)
    # slot t belongs to the group whose visits [start, end) hold it: sums over
    # a [T, E] comparison, which the compiler fuses (a gather it unrolls)
    t = jnp.arange(tiles_m + E - 1, dtype=jnp.int32)[:, None]
    group_ids = jnp.minimum(jnp.sum(t >= visit_ends[None, :], axis=1, dtype=jnp.int32), E - 1)
    inside = jnp.logical_and(t >= (visit_ends - visits)[None, :], t < visit_ends[None, :])
    first_tile_less_first_visit = (first - (visit_ends - visits))[None, :]
    m_tile_ids = jnp.minimum(
        t[:, 0] + jnp.sum(jnp.where(inside, first_tile_less_first_visit, 0), axis=1), tiles_m - 1)
    return (offsets, group_ids, m_tile_ids), visit_ends[-1]


def _rows_of_group(offsets, group_ids, m_tile_ids, t, tm):
    """(first row of tile t, start and end row of the group it is visited for)."""
    g = group_ids[t]
    return m_tile_ids[t] * tm, offsets[g], offsets[g + 1]


def _row_mask(row0, start, end, shape):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(rows >= start, rows < end)


# -- forward and input gradient ------------------------------------------------


def _for_sub_blocks(tm: int, row0, start, end, body) -> None:
    """body(rows, first row) for each `_CUT`-row block of the tile that
    holds a row of [start, end): a loop, so ONE copy of the body in the
    kernel's code (Mosaic unrolls a matmul; the four copies of an
    unrolled loop were 0.5 s of every start-up's load)."""
    def block(i, carry):
        lo = row0 + i * _CUT

        @pl.when(jnp.logical_and(start < lo + _CUT, end > lo))
        def _():
            body(pl.ds(pl.multiple_of(i * _CUT, _CUT), _CUT), lo)

        return carry

    jax.lax.fori_loop(0, tm // _CUT, block, None)


def _gmm_kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, *acc,
                tiles: Tiles, tiles_k: int, transpose_rhs: bool):
    t, k = pl.program_id(1), pl.program_id(2)
    row0, start, end = _rows_of_group(offsets, group_ids, m_tile_ids, t, tiles.tm)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def visit(rows, store):
        """Add this k block's product of `rows`; `store` the sum after the last."""
        product = jax.lax.dot_general(lhs[rows, :], rhs[...], dims, preferred_element_type=_F32)
        if tiles_k == 1:
            store(product)
            return
        (acc_ref,) = acc

        @pl.when(k == 0)
        def _():
            acc_ref[rows, :] = product

        @pl.when(k > 0)
        def _():
            acc_ref[rows, :] += product

        @pl.when(k == tiles_k - 1)
        def _():
            store(acc_ref[rows, :])

    whole = jnp.logical_and(start <= row0, end >= row0 + tiles.tm)

    @pl.when(whole)
    def _():
        def store(total):
            out[...] = total.astype(out.dtype)

        visit(slice(None), store)

    # a tile that straddles a group's edge: only the sub-blocks that hold
    # rows of this group are multiplied, and only its rows are written
    @pl.when(jnp.logical_not(whole))
    def _():
        def sub_block(rows, lo):
            def store(total):
                mask = _row_mask(lo, start, end, total.shape)
                out[rows, :] = jnp.where(
                    mask, total, out[rows, :].astype(_F32)).astype(out.dtype)

            visit(rows, store)

        _for_sub_blocks(tiles.tm, row0, start, end, sub_block)


def _gmm_call(lhs, rhs, schedule, num_tiles, tiles: Tiles, *, transpose_rhs: bool,
              interpret: bool):
    """lhs [P, K] x rhs [E, K, N] -> [P, N]; with `transpose_rhs`, rhs is
    [E, N, K] and read transposed, block by block."""
    P, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiles
    tiles_k, tiles_n = K // tk, N // tn
    out_dtype = jnp.result_type(lhs.dtype, rhs.dtype)

    def rhs_index(n, t, k, offsets, group_ids, m_tile_ids):
        return (group_ids[t], n, k) if transpose_rhs else (group_ids[t], k, n)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tiles=tiles, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((P, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n, t, k, o, g, m: (m[t], k)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, k, o, g, m: (m[t], n)),
            grid=(tiles_n, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), _F32)] if tiles_k > 1 else [],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tiles, lhs.dtype.itemsize, wgrad=False)
            + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=2 * P * K * N, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (P * K * tiles_n + rhs.size + P * N)),
        interpret=interpret,
    )(*schedule, lhs, rhs)


# -- weight gradient -------------------------------------------------------------


def _tgmm_kernel(offsets, group_ids, m_tile_ids, lhs, g, out, acc, *, tiles: Tiles):
    t, last_t = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ids[t]
    row0, start, end = _rows_of_group(offsets, group_ids, m_tile_ids, t, tiles.tm)
    dims = (((0,), (0,)), ((), ()))  # contract the rows: lhs^T x g

    @pl.when(jnp.logical_or(t == 0, group_ids[jnp.maximum(t - 1, 0)] != group))
    def _():
        acc[...] = jnp.zeros_like(acc)

    whole = jnp.logical_and(start <= row0, end >= row0 + tiles.tm)

    @pl.when(whole)
    def _():
        acc[...] += jax.lax.dot_general(lhs[...], g[...], dims, preferred_element_type=_F32)

    @pl.when(jnp.logical_not(whole))
    def _():
        def sub_block(rows, lo):
            # rows of other groups out of ONE operand (the narrower) is enough
            a, b = lhs[rows, :], g[rows, :]
            if a.shape[1] <= b.shape[1]:
                a = jnp.where(_row_mask(lo, start, end, a.shape), a.astype(_F32), 0).astype(a.dtype)
            else:
                b = jnp.where(_row_mask(lo, start, end, b.shape), b.astype(_F32), 0).astype(b.dtype)
            acc[...] += jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)

        _for_sub_blocks(tiles.tm, row0, start, end, sub_block)

    @pl.when(jnp.logical_or(t == last_t, group_ids[jnp.minimum(t + 1, last_t)] != group))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _tgmm_call(lhs, g, schedule, num_tiles, tiles: Tiles, num_groups: int, *,
               interpret: bool):
    """lhs [P, K], g [P, N] -> [E, K, N]: per group, lhs^T x g over its rows."""
    P, K = lhs.shape
    N = g.shape[1]
    tm, tk, tn = tiles
    tiles_k, tiles_n = K // tk, N // tn
    out_dtype = jnp.result_type(lhs.dtype, g.dtype)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tiles=tiles),
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n, k, t, o, gi, m: (m[t], k)),
                pl.BlockSpec((tm, tn), lambda n, k, t, o, gi, m: (m[t], n)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda n, k, t, o, gi, m: (gi[t], k, n)),
            grid=(tiles_n, tiles_k, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tiles, lhs.dtype.itemsize, wgrad=True)
            + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=2 * P * K * N, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize
            * (P * K * tiles_n + P * N * tiles_k + num_groups * K * N)),
        interpret=interpret,
    )(*schedule, lhs, g)


# -- named programs: a custom-call takes the name of the jit around it ------------


def _named(name, fn, static):
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, static_argnames=static)


_forward = _named(
    "ragged-dot-tiled",
    lambda lhs, rhs, schedule, num_tiles, *, tiles, interpret: _gmm_call(
        lhs, rhs, schedule, num_tiles, tiles, transpose_rhs=False, interpret=interpret),
    ("tiles", "interpret"))
_input_grad = _named(
    "ragged-dot-tiled-dgrad",
    lambda g, rhs, schedule, num_tiles, *, tiles, interpret: _gmm_call(
        g, rhs, schedule, num_tiles, tiles, transpose_rhs=True, interpret=interpret),
    ("tiles", "interpret"))
_weight_grad = _named(
    "ragged-dot-tiled-wgrad",
    lambda lhs, g, schedule, num_tiles, *, tiles, num_groups, interpret: _tgmm_call(
        lhs, g, schedule, num_tiles, tiles, num_groups, interpret=interpret),
    ("tiles", "num_groups", "interpret"))


# -- the differentiable op ---------------------------------------------------------


def _zero_tail(out, group_sizes):
    """Rows past the last group were written by no tile: zero, not what
    the buffer held. A select over all P rows of `out`, forward and on
    the input gradient: P is what the caller hands the grouped matmul,
    N * top_k where the expert layer runs over all pair rows and the
    bound on the held rows where it runs compact (models/moe.py)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < group_sizes.sum(), out, jnp.zeros((), out.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul_pallas(lhs, rhs, group_sizes, interpret, tail):
    P, K = lhs.shape
    tiles = _require_tiles(P, K, rhs.shape[2], lhs.dtype)
    out = _forward(lhs, rhs, *_schedule(group_sizes, P, tiles.tm),
                   tiles=tiles, interpret=interpret)
    return _zero_tail(out, group_sizes) if tail else out


def _pallas_fwd(lhs, rhs, group_sizes, interpret, tail):
    out = _grouped_matmul_pallas(lhs, rhs, group_sizes, interpret, tail)
    return out, (lhs, rhs, group_sizes)


def _pallas_bwd(interpret, tail, res, g):
    lhs, rhs, group_sizes = res
    (P, K), (E, _, N) = lhs.shape, rhs.shape
    g = g.astype(lhs.dtype)
    # d lhs [P, K] = g [P, N] x rhs^T: the contraction is N
    tiles = _require_tiles(P, N, K, lhs.dtype)
    schedule = _schedule(group_sizes, P, tiles.tm)  # `tm` follows from P alone
    d_lhs = _input_grad(g, rhs, *schedule, tiles=tiles, interpret=interpret)
    if tail:
        d_lhs = _zero_tail(d_lhs, group_sizes)
    tiles = _require_tiles(P, K, N, lhs.dtype, wgrad=True)
    d_rhs = _weight_grad(lhs, g, *schedule, tiles=tiles, num_groups=E, interpret=interpret)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


_grouped_matmul_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def _require_tiles(P, K, N, dtype, *, wgrad=False) -> Tiles:
    tiles = pick_tiles(P, K, N, dtype, wgrad=wgrad)
    if tiles is None:
        raise ValueError(f"no tile divides a grouped matmul of [{P}, {K}] x [E, {K}, {N}] "
                         f"in {jnp.dtype(dtype).name}")
    return tiles


def grouped_matmul_pallas(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                          interpret: bool = False, tail: bool = False) -> jax.Array:
    """The kernel path, whatever the backend (`interpret` for the CPU's
    tests). Raises where `pick_tiles` refuses the shapes."""
    return _grouped_matmul_pallas(lhs, rhs, group_sizes.astype(jnp.int32), interpret, tail)


def _kernel_serves(lhs: jax.Array, rhs: jax.Array) -> bool:
    if jax.default_backend() != "tpu" or lhs.dtype != rhs.dtype:
        return False
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return False
    (P, K), N = lhs.shape, rhs.shape[2]
    return all(pick_tiles(*shape, lhs.dtype, wgrad=wgrad) is not None
               for shape, wgrad in (((P, K, N), False), ((P, N, K), False), ((P, K, N), True)))


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   tail: bool = False) -> jax.Array:
    """lhs [P, K] x rhs [E, K, N] over the row groups `group_sizes` [E]
    -> [P, N], differentiable in lhs and rhs; `tail`: the groups may
    end before row P, and the rows after them come out zero. One layer
    span per call site WHILE TRACING says which path it took."""
    if _kernel_serves(lhs, rhs):
        with obs.layer_span("grouped_matmul.kernel"):
            return grouped_matmul_pallas(lhs, rhs, group_sizes, tail=tail)
    with obs.layer_span("grouped_matmul.ragged_dot"):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)

"""The selective state-space scan of a Mamba-2 mixer in its chunked dual
form (state-space duality, arXiv:2405.21060): two Pallas kernels that walk
the chunks with a group's states in VMEM.

A head h of P channels carries a state H [P, N]; B and C [N] are shared
by the heads of a GROUP (head h reads group h // (heads / groups)); the
decay is one number a head and position:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        (H = 0 before the sequence)
    y_t = H_t C_t + D x_t

`ssd_scan` computes exactly this, position for position the same sums in
another order, over chunks of `chunk` positions (128: the published
`chunk_size`). With a_t = dt_t A (<= 0), cum its running sum inside a
chunk and total its sum over the chunk, a chunk of a head is

    scores = C B^T                                   [L, L], ONCE a group
    W      = scores . [m <= l] exp(cum_l - cum_m) dt_m          (a head's)
    y      = W x + exp(cum) . (C H_start^T) + D x
    H     <- exp(total) H_start + (x . exp(total - cum) dt)^T B.

THE LAYOUT the kernels read and write is the one ops/gdn_conv.py leaves
the mixer's convolution in: ONE array `xbc` [b, heads P / N + 2 groups,
T, N] of "lane blocks" N channels wide: first x, a block N / P heads
side by side (group g's are blocks g nx .. (g + 1) nx - 1, nx = per P /
N), then B, a block a group, then C. An index map finds a group's blocks
where they stand, and the backward writes dx, dB and dC into ONE array
of the same layout (its own DMAs: three places of one output a step),
so that no slice, transpose or concatenate of a [T, channels] array
stands between the convolution's kernels and these, forward or
backward. y goes out, and its cotangent comes in, TOKEN-MAJOR [b, T,
heads P], a step its group's per P channels of the chunk's rows: what
the mixer's gated norm reads and writes with no transpose. A state's
row is a channel of the group (head x P + p), so a lane block of x goes
with rows j N .. (j + 1) N - 1 of the group's states [per P, N], and
the heads of a block stand side by side in a
product's lanes: W x of a block is [W_a | W_b] [L, 2 L] times x's
lanes of head a over x's lanes of head b [2 L, N]; the read-out C H^T
and the write (x . w)^T B are ONE [L, N] x [N, N] product a block, B or
C shared by its heads.

THE FORWARD KERNEL (`ssd_scan_fwd`) has a grid of (batch x groups,
chunks), the second axis sequential, and the group's states [per P, N]
float32 in a VMEM scratch for the whole walk. A step reads its chunk's
x, B, C, dt and a and makes cum (a product with a triangle of ones),
the scores, each head's mask and every product above in VMEM: nothing
of a chunk's algebra is an array in HBM (the jax.numpy form this
replaced wrote the [heads, chunks, L, L] float32 masks, 256 MiB each,
and the chunks' own states there three times a layer: 79.9 ms of a 281
ms step of `twotower-train-8k`; PERF.md section 6, PR 50). What a head
needs along the ROWS of a tile (exp(cum_l), the write's weights) is
made on [heads, L] rows, a register, and turned by one transpose of the
rows repeated. It writes y and the state every chunk STARTED from.

THE BACKWARD KERNEL (`ssd_scan_bwd`) walks the chunks in reverse
carrying dH in VMEM, makes each chunk's masks again from x, dt, a, B, C
and the chunk's starting state, and writes dx, dB, dC (summed over the
group's heads in the kernel), the gradients of dt and of a a head and
position, and D's summed over the walk. The decay's cotangent is formed
inside the chunk, all float32: d cum is what the masks' rows give less
what their columns give, UNDER the diagonal only (on it the span is 0
whatever cum is, and its large terms would cancel only to their
rounding), plus the read-out's term; d a is its reverse running sum (a
product with the triangle), plus the write's weights' term summed over
the positions BEFORE t (not the total less a running sum: the last
position's weight is dt itself), plus <dH, H_start> exp(total).
What is a column of a tile there is gathered a lane a vector and turned
to rows by ONE transpose a step.

What the forward writes is named (`ssd_out`, `ssd_states`) and
models/llama.py::_remat's policy saves it, so under a block's
`jax.checkpoint` the scan runs twice a layer, forward and backward, and
no forward a second time (128 + 128 MiB a layer at 8,192 tokens of 64
heads of 64 with a state of 128); under a policy that saves none of it
the forward kernel runs once more in the backward.

Everything is float32 with every product at `highest` (operands are cast
in VMEM from whatever dtype arrives; a float32 matmul is one bfloat16
pass on the chip otherwise, which the benchmark's check of the scan
alone refuses); the decay is applied position by position, never at a
chunk's granularity: exp only of spans <= 0, so a decay whose
exp(total) underflows writes zeros and no inf. Nothing is T x T and no
loop runs over positions. A sequence that is no whole number of chunks
is padded with positions of dt = 0, which decay nothing and write
nothing.

ONE path, no option: off the TPU the same kernels run under the Pallas
interpreter, as ops/gated_delta.py's do. TWO names over the one core:
`ssd_scan_lanes` takes `xbc` as above (models/nemotron_h.py's sublayer
hands it the convolution's output as it stands), `ssd_scan` the plain
[b, heads, T, P] arrays (the name the benchmark's runner holds to the
position-by-position recurrence; it builds `xbc` and calls the other).
Shapes: N a whole number of heads (N % P == 0), a group's channels a
whole number of lane blocks (per P % N == 0), chunks of whole sublanes
holding 2 per <= chunk vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs

CHUNK = 128
_SUBLANES = 8
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, contract):
    """a x b in float32 at `highest`; `_NT`: a b^T, `_TN`: a^T b."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _down(rows, reps):
    """Rows [1, L] each -> [L, len(rows) x reps]: row i down the columns
    i reps .. (i + 1) reps - 1 (the rows repeated along the sublanes,
    then ONE transpose, exact)."""
    L = rows[0].shape[1]
    return jnp.concatenate([jnp.broadcast_to(r, (reps, L)) for r in rows], axis=0).T


def _stack(*parts):
    return jnp.concatenate(parts, axis=0)


class _Chunk:
    """What of a group's chunk reads no state, as values in VMEM (the
    module docstring's names). [per, L] rows, a head a row: dt, cum, e =
    exp(cum), to_end = exp(total - cum), w = to_end dt; gamma [per, 1] =
    exp(total). tri [L, L]: m <= l; scores [L, L] = C B^T under it."""

    def __init__(self, da_ref, B, C, P):
        self.per, self.L = da_ref.shape[0] // 2, da_ref.shape[1]
        self.P, self.hp = P, B.shape[1] // P
        self.dt, a = da_ref[:self.per, :], da_ref[self.per:, :]
        self.tri = _iota((self.L, self.L), 1) <= _iota((self.L, self.L), 0)
        self.ones = jnp.where(self.tri, 1.0, 0.0)
        self.cum = _dot(a, self.ones, _NT)               # cum[h, l] = sum_{m <= l} a[h, m]
        total = jnp.sum(a, axis=1, keepdims=True)
        self.e, self.to_end = jnp.exp(self.cum), jnp.exp(total - self.cum)
        self.gamma = jnp.exp(total)
        self.w = self.to_end * self.dt
        self.scores = jnp.where(self.tri, _dot(C, B, _NT), 0.0)

    def heads(self, j):
        return range(j * self.hp, (j + 1) * self.hp)

    def lanes(self, rows, j):
        """[per, L] rows -> [L, N]: lane block j's heads' rows down their own P lanes."""
        return _down([rows[h:h + 1, :] for h in self.heads(j)], self.P)

    def state_rows(self, j):
        """gamma down the rows of lane block j's states, [N, 1]."""
        return _stack(*(jnp.broadcast_to(self.gamma[h:h + 1, :], (self.P, 1))
                        for h in self.heads(j)))

    def decay(self, h):
        """Head h's exp(cum_l - cum_m) [L, L], 1 above the diagonal: exp only of spans <= 0
        (above it the span is positive and may overflow; the scores are zero there)."""
        span = _down([self.cum[h:h + 1, :]], self.L) - self.cum[h:h + 1, :]
        return jnp.exp(jnp.minimum(span, 0.0))

    def of_heads(self, v, j):
        """v [L, N] of lane block j -> its heads' parts one over the other [hp L, N], each
        in its own lanes and zeros in the others'."""
        if self.hp == 1:
            return v
        head = _iota(v.shape, 1) // self.P
        return _stack(*(jnp.where(head == k, v, 0.0) for k in range(self.hp)))


def _fwd_kernel(x_ref, b_ref, c_ref, da_ref, d_ref, y_ref, states_ref, h_scr, *, P):
    nx, L, N = x_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    B, C = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    m = _Chunk(da_ref, B, C, P)
    states_ref[...] = h_scr[...]
    for j in range(nx):
        rows = pl.ds(j * N, N)
        x, H = x_ref[j].astype(_F32), h_scr[rows, :]
        W = jnp.concatenate([m.scores * m.decay(h) * m.dt[h:h + 1, :] for h in m.heads(j)], axis=1)
        y_ref[:, j * N:(j + 1) * N] = (_dot(W, m.of_heads(x, j), _NN)
                                       + m.lanes(m.e, j) * _dot(C, H, _NT) + d_ref[j:j + 1, :] * x)
        h_scr[rows, :] = m.state_rows(j) * H + _dot(x * m.lanes(m.w, j), B, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, da_ref, d_ref, states_ref, dy_ref,
                dxbc_ref, dda_ref, dd_ref, dx_scr, db_scr, dc_scr, dh_scr, sem, *, P, G):
    nx, L, N = x_ref.shape
    bg, i = pl.program_id(0), pl.program_id(1)      # step i holds chunk n - 1 - i
    chunk = pl.num_programs(1) - 1 - i

    @pl.when(i == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    B, C = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    m = _Chunk(da_ref, B, C, P)
    per = m.per
    head_row, row, lane = _iota((per, 1), 0), _iota((L, L), 0), _iota((L, L), 1)
    strict = lane < row
    on_diagonal = jnp.where(lane == row, m.scores, 0.0)
    dS = dB = dC = 0.0
    # the masks' columns' sums a head, [per, L] rows: under the diagonal, and on it
    under, on = jnp.zeros((per, L), _F32), jnp.zeros((per, L), _F32)
    along = jnp.zeros((per, 1), _F32)      # <dH, H_start> a head
    cols = jnp.zeros((L, L), _F32)         # column h: the write's dw; column per + h: d cum's rows
    for j in range(nx):
        rows = pl.ds(j * N, N)
        x, dy = x_ref[j].astype(_F32), dy_ref[:, j * N:(j + 1) * N].astype(_F32)
        H, dH = states_ref[rows, :], dh_scr[rows, :]
        w = m.lanes(m.w, j)
        dyE, xw = dy * m.lanes(m.e, j), x * w
        BdH = _dot(B, dH, _NT)
        write = x * BdH                                    # dw of a head: its lanes' sum
        read = dyE * _dot(C, H, _NT)                       # d cum through exp(cum) likewise
        dC = dC + _dot(dyE, H, _NN)
        dB = dB + _dot(xw, dH, _NN)
        dh_scr[rows, :] = m.state_rows(j) * dH + _dot(dyE, C, _TN)
        HdH = jnp.sum(H * dH, axis=1, keepdims=True)
        dys = m.of_heads(dy, j)
        dWs = _dot(dys, x, _NT)                            # [hp L, L]: a head's dy x^T
        head = _iota((L, N), 1) // P
        Ws = []
        for k, h in enumerate(m.heads(j)):
            dt_m = m.dt[h:h + 1, :]
            decay, dW = m.decay(h), dWs[k * L:(k + 1) * L]
            SG = m.scores * decay
            Ws.append(SG * dt_m)
            dS = dS + dW * decay * dt_m
            # the decay's cotangent reads the mask UNDER the diagonal only: on it the span is 0
            # whatever cum is, and its large terms would cancel between rows and columns only
            # up to their rounding
            Q = jnp.where(strict, dW * SG, 0.0)
            under = jnp.where(head_row == h, jnp.sum(Q, axis=0, keepdims=True), under)
            on = jnp.where(head_row == h, jnp.sum(dW * on_diagonal, axis=0, keepdims=True), on)
            along = jnp.where(head_row == h, jnp.sum(HdH[k * P:(k + 1) * P], axis=0, keepdims=True),
                              along)
            own = head == k
            cols = jnp.where(lane == h, jnp.sum(jnp.where(own, write, 0.0), axis=1, keepdims=True),
                             cols)
            cols = jnp.where(lane == per + h,
                             jnp.sum(Q * dt_m, axis=1, keepdims=True)
                             + jnp.sum(jnp.where(own, read, 0.0), axis=1, keepdims=True), cols)
        dx_scr[j] = (_dot(_stack(*Ws), dys, _TN) + d_ref[j:j + 1, :] * dy
                     + w * BdH).astype(dx_scr.dtype)
        dd_ref[j] += (dy * x).reshape(L // _SUBLANES, _SUBLANES, N).sum(axis=0)
    dS = jnp.where(m.tri, dS, 0.0)
    db_scr[...] = (dB + _dot(dS, C, _TN)).astype(db_scr.dtype)
    dc_scr[...] = (dC + _dot(dS, B, _NN)).astype(dc_scr.dtype)
    # three places of ONE output: where the convolution's backward reads x's, B's and C's
    b, g, at = bg // G, bg % G, pl.ds(chunk * L, L)
    copies = [pltpu.make_async_copy(dx_scr, dxbc_ref.at[b, pl.ds(g * nx, nx), at, :], sem.at[0]),
              pltpu.make_async_copy(db_scr, dxbc_ref.at[b, G * nx + g, at, :], sem.at[1]),
              pltpu.make_async_copy(dc_scr, dxbc_ref.at[b, G * (nx + 1) + g, at, :], sem.at[2])]
    for copy in copies:
        copy.start()
    # what stood down the columns, as rows; then the decay's cotangent, all on [per, L] rows
    turned = cols.T
    dw, rows_of = turned[:per], turned[per:2 * per]
    dda_ref[:per, :] = under + on + dw * m.to_end
    # d a_t: what cum_l gives at every l >= t (a mask's row, the read-out's exp(cum)) less what
    # the masks' columns m >= t take; the write's weights exp(total - cum_m) give sum_{m < t}
    # (summed so, not as the total less a running sum: the last position's weight is dt
    # itself and large); exp(total) <dH, H_start> at every t
    dda_ref[per:, :] = (_dot(rows_of - under * m.dt, m.ones, _NN)
                        + _dot(dw * m.w, jnp.where(strict, 1.0, 0.0), _NT) + m.gamma * along)
    for copy in copies:
        copy.wait()


def _specs(G, nx, per, P, N, L, n, reverse):
    """Block specs of (x, B, C, dt over a, D a lane, the chunks' states, y)
    on the grid (batch x groups, chunks), the chunks walked from the end
    if `reverse`: a group's lane blocks of x and its block of B and of C
    are read where they stand in `xbc`; y [b, T, channels] takes the
    group's channels of the chunk's rows."""
    step = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    return (pl.BlockSpec((None, nx, L, N), lambda bg, c: (bg // G, bg % G, step(c), 0)),
            pl.BlockSpec((None, None, L, N), lambda bg, c: (bg // G, G * nx + bg % G, step(c), 0)),
            pl.BlockSpec((None, None, L, N),
                         lambda bg, c: (bg // G, G * (nx + 1) + bg % G, step(c), 0)),
            pl.BlockSpec((None, 2 * per, L), lambda bg, c: (bg, 0, step(c))),
            pl.BlockSpec((None, nx, N), lambda bg, c: (bg % G, 0, 0)),
            pl.BlockSpec((None, None, per * P, N), lambda bg, c: (bg, step(c), 0, 0)),
            pl.BlockSpec((None, L, per * P), lambda bg, c: (bg // G, step(c), bg % G)))


# the states carry along a group's chunks (and the backward's DMAs are waited for in their step)
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# a jitted function of its own, forward and backward each: the model's layers share ONE trace
# of a kernel's body, and the compiled step names the kernels after these functions
# (ops/gated_delta.py has what tracing a body a layer and pass cost a start-up)
@functools.partial(jax.jit, static_argnames=("head_dim", "chunk", "interpret"))
def ssd_scan_fwd(xbc, da, d_lanes, head_dim, chunk, interpret):
    """xbc [b, G nx + 2 G, T, N] (the module's docstring), T whole chunks;
    da [b x G, 2 per, T] float32 (a group's dt over its a = dt A); d_lanes
    [G, nx, N] (D a channel) -> y [b, T, G per P] float32 (token-major,
    the channels in their order), the chunks' starting states [b x G, T /
    chunk, per P, N]."""
    b, _, T, N = xbc.shape
    G, nx = d_lanes.shape[:2]
    per, n = da.shape[1] // 2, T // chunk
    x, B, C, gates, D, states, y = _specs(G, nx, per, head_dim, N, chunk, n, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=head_dim),
        grid=(b * G, n),
        in_specs=[x, B, C, gates, D],
        out_specs=[y, states],
        out_shape=[jax.ShapeDtypeStruct((b, T, G * nx * N), _F32),
                   jax.ShapeDtypeStruct((b * G, n, per * head_dim, N), _F32)],
        scratch_shapes=[pltpu.VMEM((per * head_dim, N), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(xbc, xbc, xbc, da, d_lanes)


@functools.partial(jax.jit, static_argnames=("head_dim", "chunk", "interpret"))
def ssd_scan_bwd(xbc, da, d_lanes, states, dy, head_dim, chunk, interpret):
    """-> (d xbc as xbc, d da as da, D's gradient [b x G, nx, 8, N]: to be
    summed over the batch and the 8)."""
    b, _, T, N = xbc.shape
    G, nx = d_lanes.shape[:2]
    per, n = da.shape[1] // 2, T // chunk
    x, B, C, gates, D, st, y = _specs(G, nx, per, head_dim, N, chunk, n, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=head_dim, G=G),
        grid=(b * G, n),
        in_specs=[x, B, C, gates, D, st, y],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), gates,
                   pl.BlockSpec((None, nx, _SUBLANES, N), lambda bg, c: (bg, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(xbc.shape, xbc.dtype), jax.ShapeDtypeStruct(da.shape, _F32),
                   jax.ShapeDtypeStruct((b * G, nx, _SUBLANES, N), _F32)],
        scratch_shapes=[pltpu.VMEM((nx, chunk, N), xbc.dtype), pltpu.VMEM((chunk, N), xbc.dtype),
                        pltpu.VMEM((chunk, N), xbc.dtype), pltpu.VMEM((per * head_dim, N), _F32),
                        pltpu.SemaphoreType.DMA((3,))],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(xbc, xbc, xbc, da, d_lanes, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _scan(head_dim, chunk, interpret, xbc, da, d_lanes):
    return ssd_scan_fwd(xbc, da, d_lanes, head_dim, chunk, interpret)[0]


def _scan_fwd(head_dim, chunk, interpret, xbc, da, d_lanes):
    y, states = ssd_scan_fwd(xbc, da, d_lanes, head_dim, chunk, interpret)
    # named, so that a remat policy can SAVE them (models/llama.py::_remat lists the names):
    # with both kept the backward needs no second forward; the "dots" policy alone sees no
    # dot_general in a pallas_call
    return checkpoint_name(y, "ssd_out"), (xbc, da, d_lanes, checkpoint_name(states, "ssd_states"))


def _scan_bwd(head_dim, chunk, interpret, residuals, dy):
    xbc, da, d_lanes, states = residuals
    dxbc, dda, dd = ssd_scan_bwd(xbc, da, d_lanes, states, dy, head_dim, chunk, interpret)
    return dxbc, dda, dd.reshape((-1,) + d_lanes.shape[:2] + dd.shape[2:]).sum(axis=(0, 3))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_lanes(xbc: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array, *, head_dim: int,
                   chunk: int = CHUNK) -> jax.Array:
    """xbc [b, heads P / N + 2 groups, T, N] (x's lane blocks, then B's,
    then C's: the module's docstring; any float dtype), dt [b, heads, T]
    (after its softplus: >= 0), A [heads] (< 0), D [heads] -> y [b, T,
    heads P] float32, token-major (what the mixer's gated norm reads as it
    stands). One layer span a call site WHILE TRACING (`ssd_scan.kernel`)
    counts the sites."""
    b, blocks, T, N = xbc.shape
    heads, P = dt.shape[1], head_dim
    if N % P or (heads * P) % N or (blocks - heads * P // N) % 2:
        raise NotImplementedError(
            f"{heads} heads of {P} beside B and C as {blocks} lane blocks of {N}: a block is "
            "whole heads, x whole blocks, B and C a block a group each")
    groups = (blocks - heads * P // N) // 2
    if groups < 1 or heads % groups:
        raise ValueError(f"{heads} heads in {groups} groups")
    per = heads // groups
    if (per * P) % N or chunk % _SUBLANES or 2 * per > chunk:
        raise NotImplementedError(
            f"groups of {per} heads of {P} at a state of {N} in chunks of {chunk}: a group's "
            "channels are whole lane blocks, a chunk whole sublanes that hold 2 vectors a head")
    dt = dt.astype(_F32)
    a = dt * A.astype(_F32)[:, None]
    short = -T % chunk
    if short:  # dt = 0: no decay, nothing written; the rows are dropped below
        xbc = jnp.pad(xbc, ((0, 0), (0, 0), (0, short), (0, 0)))
        dt, a = (jnp.pad(v, ((0, 0), (0, 0), (0, short))) for v in (dt, a))
    da = jnp.concatenate([v.reshape(b, groups, per, T + short) for v in (dt, a)], axis=2)
    d_lanes = jnp.repeat(D.astype(_F32), P).reshape(groups, per * P // N, N)
    with obs.layer_span("ssd_scan.kernel"):
        y = _scan(P, chunk, jax.default_backend() != "tpu", xbc,
                  da.reshape(b * groups, 2 * per, T + short), d_lanes)
    return y[:, :T] if short else y


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
             D: jax.Array, *, chunk: int = CHUNK) -> jax.Array:
    """x [b, heads, T, P], dt [b, heads, T] (after its softplus: >= 0), A
    [heads] (< 0), B and C [b, groups, T, N], D [heads] -> y [b, heads, T,
    P] float32 (the module's docstring has the equations): `ssd_scan_lanes`
    on x's heads side by side in lane blocks of N."""
    b, heads, T, P = x.shape
    groups, N = B.shape[1], B.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads in {groups} groups")
    if N % P or (heads * P) % N:
        raise NotImplementedError(f"heads of {P} at a state of {N}: a lane block is whole heads")
    hp = N // P

    def blocks(v):   # [b, heads, T, P] -> [b, heads / hp, T, hp P]
        return v.reshape(b, heads // hp, hp, T, P).swapaxes(2, 3).reshape(b, heads // hp, T, N)

    xbc = jnp.concatenate([blocks(x.astype(_F32)), B.astype(_F32), C.astype(_F32)], axis=1)
    y = ssd_scan_lanes(xbc, dt, A, D, head_dim=P, chunk=chunk)
    return y.reshape(b, T, heads, P).swapaxes(1, 2)

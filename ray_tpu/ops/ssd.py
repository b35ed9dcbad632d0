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

PACKED DOCUMENTS (`segment_ids` [b, T]; PR 66, for a Mamba-2 hybrid
trained on documents packed into one sequence,
models/granite_hybrid.py): the state a position reads holds nothing of
an earlier document, i.e. H_{t-1} is taken as 0 where the document
changes, inside a chunk and across chunks, forward and backward. Both
kernels take one more small array, `doc` [b, 8, T] float32
(`_documents`: a position's document counted along the sequence, the
document of the last position BEFORE its chunk, the document of its
chunk's last position), and `_Chunk` makes from a chunk's rows of it
three 0 / 1 factors IN VMEM: the [L, L] mask also holds document(l) ==
document(m) (ONCE a group: it multiplies the scores all heads share);
exp(cum) is zero at the positions the carried state does not reach;
exp(total - cum), and so the write's weight, is zero at the positions
whose write does not reach the chunk's end; exp(total) is zero when the
chunk ends in another document than it was entered in. Every product of
both kernels, the cotangents of dt and of a among them, is formed from
those fields, so the backward is the forward's transpose with no term
of its own. The reset is NOT a large negative number added to `a`: cum
is a float32 running sum, and every span after such a term would be a
difference of two numbers of its size and lose its digits. Without
`segment_ids` both kernels are traced as they were, operand for operand
(tests/test_ssd_documents.py holds the jaxpr's hash).

A GROUP'S HEADS AND THE VMEM. The walk over a group's `per` heads is
unrolled in both kernels, and what a step holds grows with `per`: the
group's states [per P, N] float32 (scratch, and a block of the chunks'
starting states), x's and y's blocks [L, per P], the heads' [L, L]
masks on the compiler's stack. 8 groups of 8 heads of 64 at a state of
128 (`twotower-train-8k`) fit what a kernel is given unasked (16 MiB).
ONE group of 64 heads (`per` = 64, granite-4.0-h-micro: states of 2
MiB a copy, 64 masks a chunk, the two vectors a head that are turned
from columns to rows filling a chunk of 128 exactly, `2 per <= chunk`
with equality) does not: the backward kernel's blocks and stack are
36.8 MiB by the compiler's own count. `_compiler_params` states the
need by `per` and asks for it (31 MiB forward, 41 backward, of the
chip's 128); the group is walked whole, its B and C read once a chunk
and dB and dC summed in the kernel as for 8 heads (no blocks of heads,
no partial sums through HBM).

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
    exp(total). tri [L, L]: m <= l; mask [L, L]: the pairs a position
    reads, tri itself without documents; scores [L, L] = C B^T under it.

    With `doc_ref` (packed documents: the module's docstring) three 0 / 1
    factors, made here from the chunk's rows of `doc` and multiplied in
    ONCE, carry the reset through everything both kernels compute from
    these fields, the backward's cotangents of dt and a among them: the
    mask also holds document(l) == document(m); e is zero at the
    positions the chunk's starting state does not reach (another
    document than the last position before the chunk); to_end, and so
    w, is zero at the positions whose write does not reach the chunk's
    end; gamma is zero when the chunk's end is in another document than
    the one the starting state belongs to. The decay itself is untouched:
    no large negative number is added to `a`, whose running sum the spans
    after a boundary are differences of."""

    def __init__(self, da_ref, B, C, P, doc_ref=None):
        self.per, self.L = da_ref.shape[0] // 2, da_ref.shape[1]
        self.P, self.hp = P, B.shape[1] // P
        self.dt, a = da_ref[:self.per, :], da_ref[self.per:, :]
        self.tri = self.mask = _iota((self.L, self.L), 1) <= _iota((self.L, self.L), 0)
        self.ones = jnp.where(self.tri, 1.0, 0.0)
        self.cum = _dot(a, self.ones, _NT)               # cum[h, l] = sum_{m <= l} a[h, m]
        total = jnp.sum(a, axis=1, keepdims=True)
        self.e, self.to_end = jnp.exp(self.cum), jnp.exp(total - self.cum)
        self.gamma = jnp.exp(total)
        if doc_ref is not None:
            doc, before, last = doc_ref[0:1, :], doc_ref[1:2, :], doc_ref[2:3, :]
            self.mask = self.tri & (_down([doc], self.L) == doc)
            self.e = self.e * jnp.where(doc == before, 1.0, 0.0)
            self.to_end = self.to_end * jnp.where(doc == last, 1.0, 0.0)
            self.gamma = self.gamma * jnp.where(before[:, :1] == last[:, :1], 1.0, 0.0)
        self.w = self.to_end * self.dt
        self.scores = jnp.where(self.mask, _dot(C, B, _NT), 0.0)

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


def _fwd_kernel(x_ref, b_ref, c_ref, da_ref, d_ref, *rest, P, docs=False):
    doc_ref, y_ref, states_ref, h_scr = rest if docs else (None, *rest)
    nx, L, N = x_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    B, C = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    m = _Chunk(da_ref, B, C, P, doc_ref)
    states_ref[...] = h_scr[...]
    for j in range(nx):
        rows = pl.ds(j * N, N)
        x, H = x_ref[j].astype(_F32), h_scr[rows, :]
        W = jnp.concatenate([m.scores * m.decay(h) * m.dt[h:h + 1, :] for h in m.heads(j)], axis=1)
        y_ref[:, j * N:(j + 1) * N] = (_dot(W, m.of_heads(x, j), _NN)
                                       + m.lanes(m.e, j) * _dot(C, H, _NT) + d_ref[j:j + 1, :] * x)
        h_scr[rows, :] = m.state_rows(j) * H + _dot(x * m.lanes(m.w, j), B, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, da_ref, d_ref, states_ref, dy_ref, *rest, P, G, docs=False):
    doc_ref, dxbc_ref, dda_ref, dd_ref, dx_scr, db_scr, dc_scr, dh_scr, sem = (
        rest if docs else (None, *rest))
    nx, L, N = x_ref.shape
    bg, i = pl.program_id(0), pl.program_id(1)      # step i holds chunk n - 1 - i
    chunk = pl.num_programs(1) - 1 - i

    @pl.when(i == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    B, C = b_ref[...].astype(_F32), c_ref[...].astype(_F32)
    m = _Chunk(da_ref, B, C, P, doc_ref)
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
    dS = jnp.where(m.mask, dS, 0.0)
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
            pl.BlockSpec((None, L, per * P), lambda bg, c: (bg // G, step(c), bg % G)),
            # `doc` [b, 8, T]: the chunk's rows of a sequence's documents
            pl.BlockSpec((None, _SUBLANES, L), lambda bg, c: (bg // G, 0, step(c))))


# the states carry along a group's chunks (and the backward's DMAs are waited for in their step)
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
_MIB = 1 << 20
_SCOPED_VMEM = 16 * _MIB   # what a kernel is given when it asks for nothing


def _compiler_params(nx, per, P, N, L, itemsize, backward):
    """`_SEQUENTIAL`, with the VMEM a group of `per` heads needs where that
    is over what a kernel is given unasked. Counted by `per`: the blocks
    the pipeline holds TWICE (x [nx, L, N] and y or dy [L, per P], which
    at whole lane blocks are the same bytes in float32; the chunk's
    starting states [per P, N] float32, written forward and read backward;
    B, C; dt over a [2 per, L]), the scratch ONCE (the carried states [per
    P, N]; backward also dx's [nx, L, N]), and for a step's VALUES, which
    the compiler keeps on a stack in VMEM where its registers end, 8 MiB
    and two [L, L] float32 tiles a head forward, four backward (a head's
    mask, its decay and their cotangents: the walk over the heads is
    unrolled). 8 groups of 8 heads (`twotower-train-8k`): 2.2 + 8 + 1 MiB
    forward, 3.1 + 8 + 2 backward: no limit asked. ONE group of 64 heads
    of 64 at a state of 128 (`granite-h-micro-train-packed`): the states
    alone are 2 MiB a copy; 14.4 + 8 + 8 = 31 MiB forward and 16.5 + 8 +
    16 = 41 backward are asked for, of the chip's 128 (the compiler's own
    count of the backward, blocks and stack: 36.8 MiB)."""
    state, x = per * P * N * 4, nx * L * N * itemsize
    small = 2 * L * N * itemsize + 2 * per * L * 4
    twice = x + L * per * P * 4 + state + small
    once = state + (x + small if backward else 0)
    need = 2 * twice + once + 8 * _MIB + (4 if backward else 2) * per * L * L * 4
    if need <= _SCOPED_VMEM:
        return _SEQUENTIAL
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=-(-need // _MIB) * _MIB)


# a jitted function of its own, forward and backward each: the model's layers share ONE trace
# of a kernel's body, and the compiled step names the kernels after these functions
# (ops/gated_delta.py has what tracing a body a layer and pass cost a start-up)
def _with_documents(kernel, doc):
    """(the kernel told whether it has the ref, the input's operands): with `doc` None both
    kernels are traced as they were, operand for operand."""
    return (kernel, ()) if doc is None else (functools.partial(kernel, docs=True), (doc,))


@functools.partial(jax.jit, static_argnames=("head_dim", "chunk", "interpret"))
def ssd_scan_fwd(xbc, da, d_lanes, head_dim, chunk, interpret, doc=None):
    """xbc [b, G nx + 2 G, T, N] (the module's docstring), T whole chunks;
    da [b x G, 2 per, T] float32 (a group's dt over its a = dt A); d_lanes
    [G, nx, N] (D a channel); doc None or [b, 8, T] float32 (`_documents`)
    -> y [b, T, G per P] float32 (token-major, the channels in their
    order), the chunks' starting states [b x G, T / chunk, per P, N]."""
    b, _, T, N = xbc.shape
    G, nx = d_lanes.shape[:2]
    per, n = da.shape[1] // 2, T // chunk
    x, B, C, gates, D, states, y, docs = _specs(G, nx, per, head_dim, N, chunk, n, reverse=False)
    kernel, doc = _with_documents(functools.partial(_fwd_kernel, P=head_dim), doc)
    return pl.pallas_call(
        kernel,
        grid=(b * G, n),
        in_specs=[x, B, C, gates, D] + [docs] * len(doc),
        out_specs=[y, states],
        out_shape=[jax.ShapeDtypeStruct((b, T, G * nx * N), _F32),
                   jax.ShapeDtypeStruct((b * G, n, per * head_dim, N), _F32)],
        scratch_shapes=[pltpu.VMEM((per * head_dim, N), _F32)],
        compiler_params=_compiler_params(nx, per, head_dim, N, chunk, xbc.dtype.itemsize, False),
        interpret=interpret,
    )(xbc, xbc, xbc, da, d_lanes, *doc)


@functools.partial(jax.jit, static_argnames=("head_dim", "chunk", "interpret"))
def ssd_scan_bwd(xbc, da, d_lanes, states, dy, head_dim, chunk, interpret, doc=None):
    """-> (d xbc as xbc, d da as da, D's gradient [b x G, nx, 8, N]: to be
    summed over the batch and the 8)."""
    b, _, T, N = xbc.shape
    G, nx = d_lanes.shape[:2]
    per, n = da.shape[1] // 2, T // chunk
    x, B, C, gates, D, st, y, docs = _specs(G, nx, per, head_dim, N, chunk, n, reverse=True)
    kernel, doc = _with_documents(functools.partial(_bwd_kernel, P=head_dim, G=G), doc)
    return pl.pallas_call(
        kernel,
        grid=(b * G, n),
        in_specs=[x, B, C, gates, D, st, y] + [docs] * len(doc),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), gates,
                   pl.BlockSpec((None, nx, _SUBLANES, N), lambda bg, c: (bg, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(xbc.shape, xbc.dtype), jax.ShapeDtypeStruct(da.shape, _F32),
                   jax.ShapeDtypeStruct((b * G, nx, _SUBLANES, N), _F32)],
        scratch_shapes=[pltpu.VMEM((nx, chunk, N), xbc.dtype), pltpu.VMEM((chunk, N), xbc.dtype),
                        pltpu.VMEM((chunk, N), xbc.dtype), pltpu.VMEM((per * head_dim, N), _F32),
                        pltpu.SemaphoreType.DMA((3,))],
        compiler_params=_compiler_params(nx, per, head_dim, N, chunk, xbc.dtype.itemsize, True),
        interpret=interpret,
    )(xbc, xbc, xbc, da, d_lanes, states, dy, *doc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _scan(head_dim, chunk, interpret, xbc, da, d_lanes, doc):
    return ssd_scan_fwd(xbc, da, d_lanes, head_dim, chunk, interpret, doc)[0]


def _scan_fwd(head_dim, chunk, interpret, xbc, da, d_lanes, doc):
    y, states = ssd_scan_fwd(xbc, da, d_lanes, head_dim, chunk, interpret, doc)
    # named, so that a remat policy can SAVE them (models/llama.py::_remat lists the names):
    # with both kept the backward needs no second forward; the "dots" policy alone sees no
    # dot_general in a pallas_call
    return checkpoint_name(y, "ssd_out"), (xbc, da, d_lanes,
                                           checkpoint_name(states, "ssd_states"), doc)


def _scan_bwd(head_dim, chunk, interpret, residuals, dy):
    xbc, da, d_lanes, states, doc = residuals
    dxbc, dda, dd = ssd_scan_bwd(xbc, da, d_lanes, states, dy, head_dim, chunk, interpret, doc)
    # the documents are no function's argument to differentiate by: None or zeros
    return (dxbc, dda, dd.reshape((-1,) + d_lanes.shape[:2] + dd.shape[2:]).sum(axis=(0, 3)),
            None if doc is None else jnp.zeros_like(doc))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _documents(segment_ids: jax.Array, chunk: int, short: int) -> jax.Array:
    """segment_ids [b, T] -> the kernels' `doc` [b, 8, T + short] float32
    (whole numbers under 2^24, exact): row 0 a position's DOCUMENT, counted
    along the sequence (a position whose id differs from the one before it
    starts the next: equal ids apart from each other are two documents, as
    the recurrence's reset reads them); row 1 the document of the last
    position BEFORE the position's chunk (-1 before the first: nothing is
    carried into it); row 2 that of the chunk's last position; rows 3-7
    fill the sublane tile. The padding continues the last document (its
    dt = 0 writes nothing)."""
    b, T = segment_ids.shape
    starts = jnp.pad(segment_ids[:, 1:] != segment_ids[:, :-1], ((0, 0), (1, 0)))
    doc = jnp.pad(jnp.cumsum(starts, axis=1, dtype=jnp.int32), ((0, 0), (0, short)), mode="edge")
    last = doc.reshape(b, -1, chunk)[:, :, -1:]
    before = jnp.pad(last[:, :-1], ((0, 0), (1, 0), (0, 0)), constant_values=-1)
    rows = [doc] + [jnp.broadcast_to(v, (b, v.shape[1], chunk)).reshape(b, -1)
                    for v in (before, last)]
    return jnp.pad(jnp.stack(rows, axis=1).astype(_F32), ((0, 0), (0, _SUBLANES - 3), (0, 0)))


def ssd_scan_lanes(xbc: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array, *, head_dim: int,
                   chunk: int = CHUNK, segment_ids: jax.Array | None = None) -> jax.Array:
    """xbc [b, heads P / N + 2 groups, T, N] (x's lane blocks, then B's,
    then C's: the module's docstring; any float dtype), dt [b, heads, T]
    (after its softplus: >= 0), A [heads] (< 0), D [heads], `segment_ids`
    None or [b, T] (packed documents: the state a position reads holds
    nothing of another document) -> y [b, T, heads P] float32, token-major
    (what the mixer's gated norm reads as it stands). One layer span a call
    site WHILE TRACING (`ssd_scan.kernel`) counts the sites."""
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
    doc = None if segment_ids is None else _documents(segment_ids, chunk, short)
    with obs.layer_span("ssd_scan.kernel"):
        y = _scan(P, chunk, jax.default_backend() != "tpu", xbc,
                  da.reshape(b * groups, 2 * per, T + short), d_lanes, doc)
    return y[:, :T] if short else y


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
             D: jax.Array, *, chunk: int = CHUNK, segment_ids: jax.Array | None = None) -> jax.Array:
    """x [b, heads, T, P], dt [b, heads, T] (after its softplus: >= 0), A
    [heads] (< 0), B and C [b, groups, T, N], D [heads], `segment_ids` None
    or [b, T] -> y [b, heads, T, P] float32 (the module's docstring has the
    equations): `ssd_scan_lanes` on x's heads side by side in lane blocks
    of N."""
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
    y = ssd_scan_lanes(xbc, dt, A, D, head_dim=P, chunk=chunk, segment_ids=segment_ids)
    return y.reshape(b, T, heads, P).swapaxes(1, 2)

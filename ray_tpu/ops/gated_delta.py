"""The gated delta rule over a sequence: Pallas kernels that walk the chunks
with the state in VMEM.

A linear-attention layer (Gated DeltaNet, arXiv:2412.06464) carries a
matrix a head along the sequence instead of keys and values. With q_t,
k_t in R^dk, v_t in R^dv, a write strength beta_t and a log decay
g_t <= 0 (alpha_t = exp(g_t)), the state S in R^{dk x dv} starts at 0 and

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t.

Position by position that is 4,096 dependent steps (the form the tests
hold this module to: tests/test_gated_delta.py has it, and
chipbench/reference/olmo_hybrid_decoder.py's `recurrence` is the
benchmark's). `gated_delta_rule` is the same function in work a chip can
do: the sequence in chunks of `CHUNK` positions (the WY form of
arXiv:2406.06484 with the decay of arXiv:2412.06464). Inside a chunk,
with c_i the decay summed from the chunk's start to i, G_ij =
exp(c_i - c_j) for i >= j and S the state the chunk starts from, the
"pseudo-values" u_j = beta_j (v_j - (alpha_j S_{j-1})^T k_j) solve ONE unit
lower-triangular system, and the chunk is

    A   = tril(diag(beta) (K K^T . G), -1),   T = (I + A)^-1
    U   = T diag(beta) (V - diag(e^c) K S)
    O   = (Q . e^c) S + tril(Q K^T . G) U
    S  <- e^{c_C} S + (K . e^{c_C - c})^T U.

THE KERNELS work on PAIRS of chunks: everything of a chunk that reads no
state (K K^T, Q K^T, G, A and its inverse) is, for two chunks side by
side, ONE 128 x 128 block-diagonal matrix, which fills the MXU's tile
where a chunk alone fills a quarter of it; what reads the state runs a
chunk at a time, on the rows of its half. T is made by block-recursive
inversion, all on the MXU: the inverse of the 2 x 2 diagonal blocks is
I - A there, and a level that doubles the blocks is T <- T - T A_off T
with A_off the part of A that joins two neighbouring blocks (five levels
to 64), the levels of a step's pairs interleaved. A_off has rows in the
blocks' later halves alone, and so have both products, so from blocks of
8 positions up they run over those 64 rows (`_later`, `_spread`: seven
tiles of 128^3 a pair where all rows made ten; `gated_delta_fwd` 4.02 ->
3.29 ms a layer at [1, 30, 4096, 96 / 192], the same bits out; my chip
run, PR 65, call 2). ops/kda.py imports the inverse and the two helpers.

THE FORWARD KERNEL has a grid of (batch x heads, blocks of `_BLOCK`
pairs), the second axis sequential, and S [dk, dv] float32 in a VMEM
scratch for a whole head's walk. A step reads its positions' q, k, v, g
and beta and makes everything above in VMEM: nothing of a chunk's algebra
is an array in HBM (the jax.numpy form this replaced wrote W, U0, Aqk, Qg,
Kg, G, the system and its right-hand sides there as float32
[T / CHUNK, B, H, CHUNK, ...] arrays, three times a layer: 93.6 ms of a
303 ms step of `olmo-hybrid-train`; PERF.md section 6, PR 47). It writes o,
the state every chunk STARTED from ([T / CHUNK, dk, dv] a head) and the
pairs' inverses T ([T / 128, 64, 128] a head: a pair's two diagonal
blocks side by side, the zeros off the diagonal not kept), which is all
the backward needs beside the inputs.

THE BACKWARD KERNEL walks the blocks in reverse carrying dS in VMEM,
makes each chunk's intermediates again from q, k, v, g, beta, the
chunk's starting state and its pair's T (the inverse is a third of a
forward's products and is not made twice), and writes dq, dk, dv, dg,
dbeta. What the forward writes is named (`gdn_out`; `gdn_states` the
states and the inverses) and models/llama.py::_remat's policy saves it,
so under the block's `jax.checkpoint` the rule runs twice a layer,
forward and backward, and no forward a second time (94 + 135 + 30 MiB a
layer at 4,096 tokens of 30 heads of 96 x 192, where the chunked arrays of
the jax.numpy form were over 0.5 GiB of one layer's temporaries); under a
policy that saves none of it, the forward kernel runs once more in the
backward.

Everything is float32 with the matmuls at `highest` precision: the
decay, the solve and the carried state never see bfloat16, and the decay
is applied position by position (G), never at the chunk's granularity.
No array is [T, T]. tests/test_gated_delta.py holds forward and every
gradient to the position-by-position rule's.

ONE path, no option: off the TPU the same kernels run under the Pallas
interpreter, as ops/flash.py's do. The kernels read q, k, v and write o
and the gradients where they stand, [B, H, T, d] (a grid step's index
map finds its head: no reshape or copy stands between the caller's
operations and the kernels; in models/olmo_hybrid.py those are
ops/gdn_conv.py's kernels, which write float32 q, k, v and read dq, dk,
dv in the same place). A step stages its blocks into VMEM scratch
as wide as whole lanes (keys of 96 -> 128, values of 192 -> 256: zero
columns of k and v leave zero rows and columns of S), and a sequence that
is no multiple of a block of pairs is padded with positions that write
nothing (k = v = 0, beta = 0, g = 0) and read nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs

# fla's: 64 positions. 4,096 tokens are 64 chunks; a chunk's solve is 64 x 64
CHUNK = 64
# what a kernel's step works on: two chunks side by side, so that everything of a chunk that
# reads no state (K K^T, Q K^T, G, the inverse) is ONE 128 x 128 block-diagonal matrix a pair
# and fills the MXU's tile, where a chunk alone fills a quarter of it
_PAIR = 2 * CHUNK
# pairs a grid step walks: the compiler sees that many pairs' state-free algebra beside the
# one chain through S, and a head's walk is fewer steps. 2, not 4: a kernel's body is unrolled
# over its pairs, so 4 is twice the program to trace and compile (the step's start-up is held
# to 10%, and the bodies' tracing already cost it 5.7 s once) and pads a short sequence to 512
# positions, for 1.3% of the rule alone (5.87 / 12.56 ms a layer against 5.94 / 12.73 at
# [1, 30, 4096, 96 / 192]; 1 pair a step is 15% slower; PERF.md section 6, PR 47, call 3)
_BLOCK = 2
_LANES, _SUBLANES = 128, 8
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_LOG_CHUNK = CHUNK.bit_length() - 1


def _dot(a, b, contract):
    """a x b in float32 at `highest`; `_NT`: a b^T, `_TN`: a^T b."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _col(row, eye):
    """[1, n] -> [n, 1], exactly: one term of each sum is not zero."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _stack(*parts):
    return jnp.concatenate(parts, axis=0)


def _indices(n):
    """Row and column index of every element of an [n, n] matrix."""
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _later(x, h):
    """The rows of the later halves of the blocks of 2h, [_PAIR, n] ->
    [_PAIR / 2, n]: the only rows a level that joins halves of h positions
    has (its mask keeps i in the later half, j in the earlier), and a
    product costs the MXU by its rows. Whole sublane tiles move (h >= 8);
    under that the rows pass whole."""
    if h < _SUBLANES:
        return x
    return jnp.concatenate([x[s:s + h] for s in range(h, x.shape[0], 2 * h)], axis=0)


def _spread(y, h):
    """`_later`'s rows back where they stood, zeros in the earlier halves."""
    if h < _SUBLANES:
        return y
    zeros = jnp.zeros((h, y.shape[1]), y.dtype)
    return jnp.concatenate([part for s in range(0, y.shape[0], h)
                            for part in (zeros, y[s:s + h])], axis=0)


def _inverses(systems):
    """(I + A)^-1 of each strictly lower-triangular, block-diagonal A
    [n, n] (blocks of CHUNK): the inverses of the 2 x 2 diagonal blocks
    are I - A there, and a level doubles the blocks,
    [[T1, 0], [-T2 A21 T1, T2]] with every block of a level at once:
    T <- T - T A_off T, A_off what of A joins two neighbours of h
    positions. A_off has rows in the later halves alone and T is
    block-diagonal at that level, so A_off T and T (A_off T) have them
    there too: both products run over `_later`'s rows (half a tile each
    from h = 8 up; seven tiles a matrix where all rows made ten). The
    levels are the outer loop: the matrices' chains of products stand
    interleaved in the program."""
    r, c = _indices(_PAIR)
    inverses = [jnp.where(r == c, 1.0, 0.0) - jnp.where((r >> 1) == (c >> 1), A, 0.0)
                for A in systems]
    for level in range(1, _LOG_CHUNK):
        h = 1 << level
        joins = ((r >> (level + 1)) == (c >> (level + 1))) & ((r >> level) != (c >> level))
        joined = [_spread(_dot(_later(jnp.where(joins, A, 0.0), h), T, _NN), h)
                  for A, T in zip(systems, inverses)]
        inverses = [T - _spread(_dot(_later(T, h), X, _NN), h) for T, X in zip(inverses, joined)]
    return inverses


class _Pair:
    """What of a pair of chunks reads no state, as values in VMEM (the
    module docstring's names): the masks `lower`, `strict` (within a
    chunk, on and under the diagonal) and `eye`; b [n, 1] beta; e, d
    [n, 1] the decay from a position's chunk's start and to its end, gamma
    [n, 1] its whole chunk's; G, PG = K K^T . G, A, B = Q K^T . G [n, n],
    block-diagonal; T is set by `_pairs`."""

    def __init__(self, q, k, g_row, b_row):
        r, c = _indices(_PAIR)
        same = (r >> _LOG_CHUNK) == (c >> _LOG_CHUNK)
        self.eye = r == c
        self.lower, self.strict = same & (r >= c), same & (r > c)
        c_col = jnp.sum(jnp.where(self.lower, g_row, 0.0), axis=1, keepdims=True)
        c_row, self.b = _row(c_col, self.eye), _col(b_row, self.eye)
        ends = same & ((c & (CHUNK - 1)) == CHUNK - 1)
        c_last = jnp.sum(jnp.where(ends, c_row, 0.0), axis=1, keepdims=True)
        # exp only of what the mask keeps: above the diagonal c_i - c_j > 0 may overflow
        self.G = jnp.where(self.lower, jnp.exp(jnp.where(self.lower, c_col - c_row, 0.0)), 0.0)
        self.e, self.d, self.gamma = jnp.exp(c_col), jnp.exp(c_last - c_col), jnp.exp(c_last)
        self.PG = _dot(k, k, _NT) * self.G
        self.A = jnp.where(self.strict, self.b * self.PG, 0.0)
        self.B = _dot(q, k, _NT) * self.G


def _packed(T):
    """A pair's inverse [_PAIR, _PAIR] -> its two diagonal blocks side by side
    [CHUNK, _PAIR], exactly: the blocks off the diagonal are zeros."""
    return T[:CHUNK] + T[CHUNK:]


def _unpacked(W):
    """`_packed`'s [CHUNK, _PAIR] -> the pair's block-diagonal [_PAIR, _PAIR], by two selects."""
    left = jax.lax.broadcasted_iota(jnp.int32, W.shape, 1) < CHUNK
    return _stack(jnp.where(left, W, 0.0), jnp.where(left, 0.0, W))


def _pairs(q_ref, k_ref, gb_ref, block, solves_ref=None):
    """The block's pairs, their inverses made level by level together, or
    read where the forward wrote them."""
    pairs = [_Pair(q_ref[pl.ds(p * _PAIR, _PAIR), :], k_ref[pl.ds(p * _PAIR, _PAIR), :],
                   gb_ref[p, 0:1, :], gb_ref[p, 1:2, :]) for p in range(block)]
    if solves_ref is None:
        solves = _inverses([m.A for m in pairs])
    else:
        solves = [_unpacked(solves_ref[p]) for p in range(block)]
    for m, T in zip(pairs, solves):
        m.T = T
    return pairs


def _half(p, half):
    """(Where chunk `half` of the block's pair p stands in the block, its rows in the pair)."""
    return pl.ds(p * _PAIR + half * CHUNK, CHUNK), slice(half * CHUNK, (half + 1) * CHUNK)


def _in_pair(x, half):
    """x [CHUNK, n] as the rows of its half of a pair [_PAIR, n], zeros in the other."""
    zeros = jnp.zeros_like(x)
    return _stack(zeros, x) if half else _stack(x, zeros)


def _staged(first, *moves):
    """Each (scratch, ref) of `moves`: the ref's block into the scratch's
    first lanes. The scratch is as wide as whole lanes and its other
    lanes stay zero: they, and what else `first` names, are zeroed at a
    head's first step."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        for scr in (*first, *(scr for scr, _ in moves)):
            scr[...] = jnp.zeros_like(scr)

    for scr, ref in moves:
        scr[:, :ref.shape[-1]] = ref[...]


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, states_ref, solves_ref,
                q_scr, k_scr, v_scr, s_scr, *, block):
    dk, dv = states_ref.shape[-2:]
    _staged((s_scr,), (q_scr, q_ref), (k_scr, k_ref), (v_scr, v_ref))
    pairs = _pairs(q_scr, k_scr, gb_ref, block)
    BTs = [_dot(m.B, m.T, _NN) for m in pairs]
    for p, (m, BT) in enumerate(zip(pairs, BTs)):
        solves_ref[p] = _packed(m.T)
        for half in range(2):
            at, rows = _half(p, half)
            q, k, v = q_scr[at, :], k_scr[at, :], v_scr[at, :]
            e, S = m.e[rows], s_scr[...]
            states_ref[2 * p + half] = S[:dk, :dv]
            KS_QS = _dot(_stack(k, q * e), S, _NN)
            R = m.b[rows] * (v - e * KS_QS[:CHUNK])
            # U = T R and B U = (B T) R in one product: the rows of the half, all of its columns
            U_BU = _dot(_stack(m.T[rows], BT[rows]), _in_pair(R, half), _NN)
            o_ref[at, :] = (KS_QS[CHUNK:] + U_BU[CHUNK:])[:, :dv]
            s_scr[...] = m.gamma[rows][:1] * S + _dot(k * m.d[rows], U_BU[:CHUNK], _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, states_ref, solves_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgb_ref,
                q_scr, k_scr, v_scr, do_scr, s_scr, ds_scr, *, block):
    dk, dv = states_ref.shape[-2:]
    _staged((s_scr, ds_scr), (q_scr, q_ref), (k_scr, k_ref), (v_scr, v_ref), (do_scr, do_ref))
    pairs = _pairs(q_scr, k_scr, gb_ref, block, solves_ref)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0) == CHUNK - 1
    for p in reversed(range(block)):
        m, both = pairs[p], pl.ds(p * _PAIR, _PAIR)
        BdO = _dot(m.B, do_scr[both, :], _TN)              # B^T dO reads no state
        Tt = m.T.T
        halves, dc_taken = {}, 0.0
        for half in reversed(range(2)):
            at, rows = _half(p, half)
            q, k, v, dO = q_scr[at, :], k_scr[at, :], v_scr[at, :], do_scr[at, :]
            s_scr[:dk, :dv] = states_ref[2 * p + half]
            S, dS = s_scr[...], ds_scr[...]
            b, e, d, gamma = m.b[rows], m.e[rows], m.d[rows], m.gamma[rows][:1]
            KS = _dot(k, S, _NN)
            Z = v - e * KS
            U = _dot(m.T[rows], _in_pair(b * Z, half), _NN)
            dU = BdO[rows] + _dot(k * d, dS, _NN)
            dR = _dot(Tt[rows], _in_pair(dU, half), _NN)   # U = T R: dR = T^T dU, dA = -dR U^T
            dB_dA = _dot(_stack(dO, dR), _in_pair(U, half), _NT)        # [dO; dR] U^T
            dB = jnp.where(m.lower[rows], dB_dA[:CHUNK], 0.0)
            dA = jnp.where(m.strict[rows], -dB_dA[CHUNK:], 0.0)
            dRb = b * dR                                   # d(V - e K S), and dv
            dKS = -e * dRb
            dOS_dKSS = _dot(_stack(dO, dKS), S, _NT)       # [C, dk] each: d(e q), and of dk
            dOS, UdS = dOS_dKSS[:CHUNK], _dot(U, dS, _NT)  # UdS: d(d k)
            dv_ref[at, :] = dRb[:, :dv]
            ds_scr[...] = gamma * dS + _dot(_stack(k, q * e), _stack(dKS, dO), _TN)
            # the decay: c enters through G (rows add, columns take away), e, d and gamma
            X = dB * m.B[rows] + dA * m.A[rows]
            de = jnp.sum(q * dOS, axis=1, keepdims=True) - jnp.sum(dRb * KS, axis=1, keepdims=True)
            dd = jnp.sum(k * UdS, axis=1, keepdims=True) * d
            dc_last = jnp.sum(dd, axis=0, keepdims=True) + gamma * jnp.sum(
                jnp.sum(S * dS, axis=1, keepdims=True), axis=0, keepdims=True)
            halves[half] = dict(
                dQK=dB * m.G[rows], dP=dA * b * m.G[rows], dq=e * dOS,
                dk=dOS_dKSS[CHUNK:] + d * UdS,
                dbeta=(jnp.sum(dR * Z, axis=1, keepdims=True)
                       + jnp.sum(dA * m.PG[rows], axis=1, keepdims=True)),
                dc=(jnp.sum(X, axis=1, keepdims=True) + de * e - dd
                    + jnp.where(at_end, dc_last, 0.0)))
            dc_taken = dc_taken + jnp.sum(X, axis=0, keepdims=True)
        whole = {name: _stack(halves[0][name], halves[1][name]) for name in halves[0]}
        # what is left reads no state: Q K^T and K K^T's cotangents onto q and k, a pair at once
        q, k = q_scr[both, :], k_scr[both, :]
        dQK_dP = _stack(whole["dQK"], whole["dP"])
        onto = _dot(dQK_dP, k, _NN)                        # [dQK k; dP k]
        dq_ref[both, :] = (whole["dq"] + onto[:_PAIR])[:, :dk]
        dk_ref[both, :] = (whole["dk"] + onto[_PAIR:]
                           + _dot(dQK_dP, _stack(q, k), _TN))[:, :dk]
        dc = whole["dc"] - _col(dc_taken, m.eye)
        # c is g summed from the chunk's start: dg_j sums dc from j to the chunk's end
        dgb_ref[p, 0:1, :] = jnp.sum(jnp.where(m.lower, dc, 0.0), axis=0, keepdims=True)
        dgb_ref[p, 1:2, :] = _row(whole["dbeta"], m.eye)


def _specs(block, H, dk, dv, blocks, reverse):
    """Block specs of (q or k, v, g and beta, the states, the inverses) on
    the grid (batch x heads, blocks of pairs), the blocks walked from the
    end if `reverse`. q, k and v are read where they stand, [B, H, T, d]:
    a step sees its head's rows as [rows, d]."""
    step = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    rows = block * _PAIR
    at_head = lambda bh, i: (bh // H, bh % H, step(i), 0)  # noqa: E731
    at = lambda bh, i: (bh, step(i), 0, 0)  # noqa: E731
    return (pl.BlockSpec((None, None, rows, dk), at_head),
            pl.BlockSpec((None, None, rows, dv), at_head),
            pl.BlockSpec((None, block, 2, _PAIR), at),
            pl.BlockSpec((None, 2 * block, dk, dv), at),
            pl.BlockSpec((None, block, CHUNK, _PAIR), at))


def _scratch(block, dk, dv, values, states):
    """VMEM scratch of a kernel, as wide as whole lanes: q and k and
    `values` blocks as wide as v, then `states` matrices [dk, dv]."""
    dk, dv = (-(-d // _LANES) * _LANES for d in (dk, dv))
    rows = block * _PAIR
    return ([pltpu.VMEM((rows, dk), _F32)] * 2 + [pltpu.VMEM((rows, dv), _F32)] * values
            + [pltpu.VMEM((dk, dv), _F32)] * states)


_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# a jitted function of its own, forward and backward each: the model's layers share ONE trace
# of a kernel's body (tracing the unrolled bodies a layer and pass was 5.7 s of a warm start
# on the chip's host), and the compiled step names the kernels after these functions
@functools.partial(jax.jit, static_argnames="interpret")
def gated_delta_fwd(q, k, v, gb, interpret):
    """q, k [B, H, T, dk], v [B, H, T, dv], gb [B x H, P, 2, _PAIR] (g over
    beta, a row a pair), float32, T whole blocks of pairs -> o
    [B, H, T, dv], the chunks' starting states [B x H, 2 P, dk, dv], the
    pairs' inverses [B x H, P, CHUNK, _PAIR] (a pair's two diagonal blocks
    side by side: the other half of the pair's matrix is zeros)."""
    B, H, T, dk = q.shape
    dv, P = v.shape[-1], gb.shape[1]
    block = min(_BLOCK, P)
    qk, vo, gates, states, solves = _specs(block, H, dk, dv, P // block, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block),
        grid=(B * H, P // block),
        in_specs=[qk, qk, vo, gates],
        out_specs=[vo, states, solves],
        out_shape=[jax.ShapeDtypeStruct(v.shape, _F32),
                   jax.ShapeDtypeStruct((B * H, 2 * P, dk, dv), _F32),
                   jax.ShapeDtypeStruct((B * H, P, CHUNK, _PAIR), _F32)],
        scratch_shapes=_scratch(block, dk, dv, values=1, states=1),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(q, k, v, gb)


@functools.partial(jax.jit, static_argnames="interpret")
def gated_delta_bwd(q, k, v, gb, states, solves, do, interpret):
    B, H, T, dk = q.shape
    dv, P = v.shape[-1], gb.shape[1]
    block = min(_BLOCK, P)
    qk, vo, gates, st, sv = _specs(block, H, dk, dv, P // block, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block),
        grid=(B * H, P // block),
        in_specs=[qk, qk, vo, gates, st, sv, vo],
        out_specs=[qk, qk, vo, gates],
        out_shape=[jax.ShapeDtypeStruct(q.shape, _F32), jax.ShapeDtypeStruct(k.shape, _F32),
                   jax.ShapeDtypeStruct(v.shape, _F32), jax.ShapeDtypeStruct(gb.shape, _F32)],
        scratch_shapes=_scratch(block, dk, dv, values=2, states=2),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(q, k, v, gb, states, solves, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(interpret, q, k, v, gb):
    return gated_delta_fwd(q, k, v, gb, interpret)[0]


def _rule_fwd(interpret, q, k, v, gb):
    o, states, solves = gated_delta_fwd(q, k, v, gb, interpret)
    # named, so that a remat policy can SAVE them (models/llama.py::_remat lists the names):
    # with all kept the backward needs no second forward; the "dots" policy alone sees no
    # dot_general in a pallas_call
    o = checkpoint_name(o, "gdn_out")
    states, solves = (checkpoint_name(a, "gdn_states") for a in (states, solves))
    return o, (q, k, v, gb, states, solves)


def _rule_bwd(interpret, residuals, do):
    return tuple(gated_delta_bwd(*residuals, do, interpret))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array) -> jax.Array:
    """q, k [B, H, T, dk], v [B, H, T, dv] (any float dtype), g [B, H, T]
    the log decay (<= 0) and beta [B, H, T] the write strength -> o
    [B, H, T, dv] float32, the state starting at 0. The module's
    docstring has the algebra. One layer span a call site WHILE TRACING
    (`gated_delta.kernel`) counts the sites."""
    B, H, T, dk = q.shape
    P = -(-T // _PAIR)
    P = -(-P // min(_BLOCK, P)) * min(_BLOCK, P)
    short = P * _PAIR - T

    def whole(a):
        """float32, zeros beyond T up to whole blocks of pairs (nothing where T is whole)."""
        a = a.astype(_F32)
        return jnp.pad(a, ((0, 0), (0, 0), (0, short)) + ((0, 0),) * (a.ndim - 3)) if short else a

    gb = jnp.stack([whole(a).reshape(B * H, P, _PAIR) for a in (g, beta)], axis=2)
    with obs.layer_span("gated_delta.kernel"):
        o = _rule(jax.default_backend() != "tpu", whole(q), whole(k), whole(v), gb)
    return o[:, :, :T] if short else o

"""Kimi Delta Attention's rule over a sequence: a delta rule whose decay is a
VECTOR over the key's channels, as two Pallas kernels that walk the chunks
with the state in VMEM.

A KDA layer (arXiv:2510.26692; fla's `KimiDeltaAttention`) carries a matrix
a head along the sequence, as the gated delta rule of ops/gated_delta.py
does, but forgets each of the key's channels at a rate of its own. With
q_t, k_t in R^dk, v_t in R^dv, a write strength beta_t and a log decay
g_t in R^dk, g_t <= 0, the state S in R^{dk x dv} starts at 0 and

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t.

Position by position that is 8,192 dependent steps (the form the tests
hold this module to: tests/test_kda.py has it, and
chipbench/reference/solar_open2_decoder.py's `recurrence` is the
benchmark's). `kda_rule` is the same function in chunks of `CHUNK`
positions. With c_i [dk] the decay summed from the chunk's start to i
(inclusive), S the state the chunk starts from and

    M(A)_ij = sum_d a_id k_jd exp(c_id - c_jd)      (i >= j),

the "pseudo-values" u_j = beta_j (v_j - (Diag(e^{g_j}) S_{j-1})^T k_j) solve
ONE unit lower-triangular system a chunk:

    A   = tril(diag(beta) M(K), -1),   T = (I + A)^-1
    U   = T diag(beta) (V - (K . e^c) S)
    O   = (Q . e^c) S + tril(M(Q)) U
    S  <- Diag(e^{c_C}) S + (K . e^{c_C - c})^T U.

WHY THIS IS NOT ops/gated_delta.py WITH A WIDER g. With ONE decay a head
the decay factors out of the products: M(A) = (A K^T) . G with G_ij =
exp(c_i - c_j) a [64, 64] mask, which is what that module's kernels build
in VMEM. With a decay a channel it does not: M is a sum over channels of
products that each carry their own exponential, and the obvious split
(a . e^c)(k . e^-c)^T overflows float32 inside ONE chunk as soon as a
channel forgets fast (e^{-c} after 64 positions at g = -30 is e^1920).
NO EXPONENT OF A POSITIVE NUMBER IS FORMED HERE. fla's scheme takes the
decays between two sub-blocks of 16 positions relative to the later one's
first row and, inside a diagonal sub-block, forms the [16, 16, dk]
differences themselves, which on this chip is a reduction across the
lanes for each of a sub-block's 256 pairs. The kernels take the first
idea ALL THE WAY DOWN instead, so that every product is the MXU's: a chunk
is halved six times (`_HALVES`: 32, 16, .. 1 positions), and at the level
of halves of h positions every block of 2h has ONE reference row r, the
first of its later half:

    M_ij = (a_i . e^{c_i - c_r}) (k_j . e^{c_r - c_j})^T,
                                     i in the later half, j in the earlier,

where c_i - c_r = g_{r+1} + .. + g_i <= 0 and c_r - c_j = g_{j+1} + .. +
g_r <= 0: both are SUMS of a few g (never a difference of two cumulative
sums), and ONE array a level, X_h = exp of them, decays the later half's
rows and the earlier half's columns alike, for M(K . beta) and M(Q) both
(their rows are stacked into one product a level). Every pair i > j of a
chunk parts at exactly one level; i = j needs no decay. All the sums of g a
pair of chunks needs (c, c_C - c and the six levels') are ONE product of
the constant 0 / 1 matrix `_SUMS` with g, and the backward's dg ONE product
with its transpose. A factor that underflows to 0 stands for a product that
is smaller still. That algebra shares nothing with the scalar rule's
kernels but the chunk size, the pairs and the inverse (imported from
ops/gated_delta.py), so it is a module of its own; with g constant over a
head's channels it IS the scalar rule, and tests/test_kda.py holds the two
to each other.

A PRODUCT COSTS THE MXU BY ITS ROWS AND ITS bf16 PASSES, so no row and no
pass is multiplied that the trace knows to be zero (PR 65). (1) `_SUMS` is
handed to the MXU as bfloat16, which holds 0 and 1 exactly, and the other
operand as its three bfloat16 terms (`_sum_dot`): three passes accumulated
in float32, which are `highest`'s six less the three that multiply the
zero terms of a matrix that has ONE, so the same sums to the order of an
addition. No product of two float32 arrays drops a pass. (2) A level's
mask keeps the rows of its blocks' later halves alone, so its products run
over those 64 rows (`_later`) and are put back (`_spread`): whole sublane
tiles from 8 positions up, and under that through a VMEM scratch by
strided loads and stores along the sublanes (`_short_rows`). (3) The
inverse's levels from 8 positions up run over the same rows
(ops/gated_delta.py::`_inverses`). A pair's products, in tiles of 128^3
at six passes (tests/test_kda.py counts them off the kernels' jaxprs):

                                              kda_fwd      kda_bwd
    `_decays`: `_SUMS` x g at three passes       4            4
    dg: `_SUMS`^T x the cotangents at three      -            4
    the six levels of `_Pair`                    6            3
    the levels' cotangents (drows, dcols)        -           12
    `_inverses`: five levels                     7      read from `solves`
    B T / B^T dO                                 1            1
    what reads the state, both halves            5           11
    a pair                                  23 (was 33)  35 (was 50.5)

THE KERNELS work on PAIRS of chunks, as ops/gated_delta.py's do: what of a
chunk reads no state (the sums, the levels' products, A and its inverse by
block doubling on the MXU) is ONE 128 x 128 block-diagonal matrix a pair.
The forward has a grid of (batch x heads, blocks of `_BLOCK` pairs), the
second axis sequential, and the state TRANSPOSED, S^T [dv, dk] float32, in
a VMEM scratch for a whole head's walk (the channels' decay e^{c_C} is then
a row over the lanes). A step reads its positions' q, k, v, g [.., d] and
beta WHERE THEY STAND, [B, H, T, d] (an index map finds the head: no pad,
reshape or transpose stands between models/solar_open2.py's convolution
and gates and the kernel), and nothing of a chunk is an array in HBM. It
writes o, the state every chunk STARTED from ([T / 64, dv, dk] a head) and
the pairs' inverses ([T / 128, 64, 128] a head: a pair's two diagonal
blocks side by side, no zeros kept).

THE BACKWARD walks the blocks in reverse carrying dS^T in VMEM, makes each
chunk's intermediates again from the inputs, the chunk's starting state and
its pair's inverse, and writes dq, dk, dv, dg [.., d] and dbeta in place.
What the forward writes is named (`kda_out`; `kda_states` the states and
the inverses) and models/solar_open2.py lists both in `REMAT_SAVES`, so
under the block's `jax.checkpoint` the rule runs twice a layer, forward and
backward, and no forward a second time: 32 + 64 + 16 MiB a layer at
[1, 8, 8192, 128], where the jax.numpy form this replaced wrote the
columns' four references alone as 128 MiB a layer, three times
(tests/test_solar_open2_step_compile.py holds the whole step's
`memory_analysis()` under the chip's 15.75 GiB with them kept: the memory
decides, and it fits). Under a policy that saves none of it the forward
kernel runs once more in the backward.

Everything is float32 and every product of two float32 arrays is at
`highest` precision: the decay, the solve and the carried state never see
bfloat16 (the three bfloat16 terms of the sums' operand are all 24 bits of
the float32 they split), and the decay is applied position by position and
channel by channel. No array is [T, T].

ONE path, no option: off the TPU the same kernels run under the Pallas
interpreter. Head sizes that fill no lane tile (the tiny preset's 16, the
tests' 12 / 24) are staged into lane-wide VMEM scratch with zero columns,
chosen from the shape (128 needs none), and a sequence that is no multiple
of a block of pairs is padded with positions that write nothing (k = v =
0, beta = 0, g = 0) and read nothing.

AGAINST THE SCALAR RULE'S KERNELS (ROADMAP D14; my chip runs, profiler
traces, the rule alone under `jax.checkpoint` with the names saved). The
kernels are MXU-bound: a pair's forward was 33 products of 128^3 at six
bf16 passes, 3.18 ms a layer at [1, 8, 8192, 128] and 4.00 backward (PR 61,
calls 1 and 3; the jax.numpy form took 22.6 for forward, forward again and
transpose), and is 23 since PR 65: 2.37 + 3.01 at 8 heads, 9.49 + 12.04 at
[1, 32, 8192, 128] where 12.71 + 16.00 stood (PR 65, calls 1 and 2). At
`olmo-hybrid-train`'s shape (30 heads of 96 x 192 over 4,096 positions, g
broadcast over the channels) PR 61 read 6.36 + 8.90 = 15.3 ms a layer
where `gated_delta_fwd` / `_bwd` took 4.02 + 4.42 = 8.4 (3.29 + 4.42 since
PR 65): 1.8 x, + 20 ms of that cell's 210 ms step, ten times its bound.
One decay a head factors out of ONE product as a mask; six levels of
products a pair are what a decay a channel costs, so the scalar module
stays and nothing routes `olmo-hybrid-train` through here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu import obs
from ray_tpu.ops.gated_delta import (_NN, _NT, _TN, _PAIR, _SEQUENTIAL, _SUBLANES, _col, _dot,
                                     _half, _in_pair, _indices, _inverses, _packed, _row, _stack,
                                     _unpacked)
from ray_tpu.ops.gated_delta import _later as _later_tiles, _spread as _spread_tiles

# fla's: 64 positions. 8,192 tokens are 128 chunks, 64 pairs; a chunk's solve is 64 x 64
CHUNK = 64
# the halves a chunk is cut into, level by level: at the level of h, a block of 2h positions'
# later half against its earlier half, the decays relative to the later half's first row
_HALVES = (32, 16, 8, 4, 2, 1)
# pairs a grid step walks: 2, as ops/gated_delta.py's `_BLOCK` (which has what a longer unrolled
# body costs): forward + backward 2.37 + 3.01 ms a layer at [1, 8, 8192, 128] against 3.10 + 3.17
# at 1 pair a step; 4 pairs read 2.17 + 2.98 for three times the seconds to compile a kernel
# (my chip run, PR 65, call 3, profiler trace)
_BLOCK = 2
_LANES = 128
_F32, _BF16 = jnp.float32, jnp.bfloat16
_LOG_CHUNK = CHUNK.bit_length() - 1


def _sum_matrices() -> np.ndarray:
    """`_SUMS` [(2 + 6) x _PAIR, _PAIR] of 0 / 1: times a pair's g [_PAIR,
    dk] it is, stacked, c (g summed from the chunk's start to i, i
    included), c_C - c (from after i to the chunk's end) and, a level of
    `_HALVES`, |c_i - c_r| with r the first row of the later half of i's
    block of 2h: g summed over (r, i] for i in the later half and over
    (i, r] in the earlier. bfloat16 holds 0 and 1 exactly."""
    i, t = np.indices((_PAIR, _PAIR))
    same = (i // CHUNK) == (t // CHUNK)
    mats = [same & (t <= i), same & (t > i)]
    for h in _HALVES:
        r = i // (2 * h) * (2 * h) + h
        mats.append(np.where(i >= r, (t > r) & (t <= i), (t > i) & (t <= r)))
    return np.concatenate(mats).astype(_BF16)


_SUMS = _sum_matrices()
_N_SUMS = _SUMS.shape[0] // _PAIR


def _sum_dot(sums, x):
    """sums x with sums of 0 / 1 in bfloat16 and x float32, to float32's
    last bit: x is the sum of three bfloat16 terms (8 bits each, all 24 of
    float32's), sums has ONE term, so three passes of the MXU accumulated
    in float32 are the six that `highest` makes of two float32 arrays, less
    the three that multiply zeros."""
    total = None
    for _ in range(3):
        term = x.astype(_BF16)
        x = x - term.astype(_F32)
        part = jax.lax.dot_general(sums, term, (_NN, ((), ())), preferred_element_type=_F32)
        total = part if total is None else total + part
    return total


def _decays(g, sums):
    """Every exponential of the kernels. g [_PAIR, dk] (<= 0) and `_SUMS` ->
    (e^c, e^{c_C - c}, [X_h for h in `_HALVES`]), each [_PAIR, dk] in
    (0, 1]: an exponent is a sum of g, clamped at 0 against the products'
    rounding."""
    exponents = _sum_dot(sums, g)
    parts = [jnp.exp(jnp.minimum(exponents[n * _PAIR:(n + 1) * _PAIR], 0.0))
             for n in range(_N_SUMS)]
    return parts[0], parts[1], parts[2:]


def _level_mask(r, c, h):
    """[_PAIR, _PAIR]: i in the later half and j in the earlier half of the same block of 2h."""
    lh = h.bit_length() - 1
    return ((r >> lh) == (c >> lh) + 1) & (((r >> lh) & 1) == 1)


def _short_rows(h):
    """The later halves' rows of a level under a sublane tile's 8
    positions, as h strided slices of a [_PAIR, n] ref: slice j holds row
    h + j of every block of 2h."""
    return [pl.ds(h + j, _PAIR // (2 * h), stride=2 * h) for j in range(h)]


def _later(x, h, scr):
    """The rows of a level's later halves, [_PAIR, n] -> [_PAIR / 2, n]:
    the only rows of the level's products that its mask keeps, and a
    product costs the MXU by its rows. From 8 positions up whole sublane
    tiles move (ops/gated_delta.py's); under that x goes through the VMEM
    scratch `scr` and comes back by strided loads along the sublanes, slice
    by slice (`_short_rows`: another order of the same rows, which
    `_spread` undoes)."""
    if h >= _SUBLANES:
        return _later_tiles(x, h)
    scr[:, :x.shape[1]] = x
    return jnp.concatenate([scr[rows, :x.shape[1]] for rows in _short_rows(h)], axis=0)


def _spread(y, h, scr):
    """`_later`'s rows back where they stood. From 8 positions up the
    earlier halves' rows are zeros; under that they are whatever `scr`
    held, for the level's mask to drop (`_later_rows`)."""
    if h >= _SUBLANES:
        return _spread_tiles(y, h)
    n = y.shape[0] // h
    for j, rows in enumerate(_short_rows(h)):
        scr[rows, :y.shape[1]] = y[j * n:(j + 1) * n]
    return scr[:, :y.shape[1]]


def _later_rows(h):
    """[_PAIR, 1]: i in the later half of its block of 2h."""
    i = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, 1), 0)
    return ((i >> (h.bit_length() - 1)) & 1) == 1


class _Pair:
    """What of a pair of chunks reads no state, as values in VMEM (the
    module docstring's names): the masks `lower`, `strict` (within a
    chunk, on and under the diagonal), `eye` and one a level; b [n, 1]
    beta; e, d [n, dk] the decays from a position's chunk's start and to
    its end; X the levels' decays; kb = beta k; B = tril(M(Q)) and, if
    `system`, A [n, n], block-diagonal; T is set by `_pairs`."""

    def __init__(self, q, k, g, b_row, sums, system, short):
        r, c = _indices(_PAIR)
        same = (r >> _LOG_CHUNK) == (c >> _LOG_CHUNK)
        self.eye = r == c
        self.lower, self.strict = same & (r >= c), same & (r > c)
        self.masks = [_level_mask(r, c, h) for h in _HALVES]
        self.b = _col(b_row, self.eye)
        self.e, self.d, self.X = _decays(g, sums)
        self.kb = self.b * k
        # i = j: no decay
        self.B = jnp.where(self.eye, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
        self.A = jnp.zeros_like(self.B) if system else None
        for h, mask, X in zip(_HALVES, self.masks, self.X):
            rows = _later(q * X, h, short[0])
            n = rows.shape[0]
            M = _dot(_stack(rows, _later(self.kb * X, h, short[1])) if system else rows, k * X, _NT)
            self.B = jnp.where(mask, _spread(M[:n], h, short[2]), self.B)
            if system:
                self.A = jnp.where(mask, _spread(M[n:], h, short[3]), self.A)


def _pairs(q_ref, k_ref, g_ref, b_ref, sums_ref, block, short, solves_ref=None):
    """The block's pairs, their inverses made level by level together, or
    read where the forward wrote them."""
    at = lambda p: pl.ds(p * _PAIR, _PAIR)  # noqa: E731
    pairs = [_Pair(q_ref[at(p), :], k_ref[at(p), :], g_ref[at(p), :], b_ref[p], sums_ref[...],
                   system=solves_ref is None, short=short) for p in range(block)]
    if solves_ref is None:
        solves = _inverses([m.A for m in pairs])
    else:
        solves = [_unpacked(solves_ref[p]) for p in range(block)]
    for m, T in zip(pairs, solves):
        m.T = T
    return pairs


def _staged(refs, scratch, first=()):
    """Each ref [rows, d] as the kernel reads it: itself where d is whole
    lanes, else its block copied into the first lanes of the next scratch
    of `scratch` (an iterator over `_scratch`'s blocks, which stand in the
    refs' order), whose other lanes stay zero: they, and what `first`
    names, are zeroed at a head's first step."""
    scrs = [next(scratch) if ref.shape[-1] % _LANES else None for ref in refs]

    @pl.when(pl.program_id(1) == 0)
    def _():
        for scr in (*first, *(scr for scr in scrs if scr is not None)):
            scr[...] = jnp.zeros_like(scr)

    for scr, ref in zip(scrs, refs):
        if scr is not None:
            scr[:, :ref.shape[-1]] = ref[...]
    return [ref if scr is None else scr for scr, ref in zip(scrs, refs)]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sums_ref, o_ref, states_ref, solves_ref,
                s_scr, *scratch, block):
    dv, dk = states_ref.shape[-2:]
    short, scratch = scratch[:_SHORT_FWD], scratch[_SHORT_FWD:]
    q_src, k_src, g_src, v_src = _staged((q_ref, k_ref, g_ref, v_ref), iter(scratch), (s_scr,))
    pairs = _pairs(q_src, k_src, g_src, b_ref, sums_ref, block, short)
    BTs = [_dot(m.B, m.T, _NN) for m in pairs]
    for p, (m, BT) in enumerate(zip(pairs, BTs)):
        solves_ref[p] = _packed(m.T)
        for half in range(2):
            at, rows = _half(p, half)
            q, k, v = q_src[at, :], k_src[at, :], v_src[at, :]
            e, S = m.e[rows], s_scr[...]                    # S is S^T [dv, dk]
            states_ref[2 * p + half] = S[:dv, :dk]
            KS_QS = _dot(_stack(k * e, q * e), S, _NT)
            R = m.b[rows] * (v - KS_QS[:CHUNK])
            # U = T R and B U = (B T) R in one product: the rows of the half, all of its columns
            U_BU = _dot(_stack(m.T[rows], BT[rows]), _in_pair(R, half), _NN)
            o_ref[at, :] = (KS_QS[CHUNK:] + U_BU[CHUNK:])[:, :dv]
            # e's last row is e^{c_C}, a factor a channel: a row over S^T's lanes
            s_scr[...] = e[CHUNK - 1:] * S + _dot(U_BU[:CHUNK], k * m.d[rows], _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sums_ref, sums_t_ref, states_ref, solves_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, s_scr, ds_scr, *scratch, block):
    dv, dk = states_ref.shape[-2:]
    short, scratch = scratch[:_SHORT_BWD], scratch[_SHORT_BWD:]
    q_src, k_src, g_src, v_src, do_src = _staged((q_ref, k_ref, g_ref, v_ref, do_ref),
                                                 iter(scratch), (s_scr, ds_scr))
    pairs = _pairs(q_src, k_src, g_src, b_ref, sums_ref, block, short, solves_ref)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0) == CHUNK - 1
    for p in reversed(range(block)):
        m, both = pairs[p], pl.ds(p * _PAIR, _PAIR)
        BdO = _dot(m.B, do_src[both, :], _TN)              # B^T dO reads no state
        Tt = m.T.T
        halves = {}
        for half in reversed(range(2)):
            at, rows = _half(p, half)
            q, k, v, dO = q_src[at, :], k_src[at, :], v_src[at, :], do_src[at, :]
            s_scr[:dv, :dk] = states_ref[2 * p + half]
            S, dS = s_scr[...], ds_scr[...]                # S^T, dS^T [dv, dk]
            b, e, d = m.b[rows], m.e[rows], m.d[rows]
            gamma, ke, qe, kd = e[CHUNK - 1:], k * e, q * e, k * d
            Z = v - _dot(ke, S, _NT)
            U = _dot(m.T[rows], _in_pair(b * Z, half), _NN)
            dU = BdO[rows] + _dot(kd, dS, _NT)
            dR = _dot(Tt[rows], _in_pair(dU, half), _NN)   # U = T R: dR = T^T dU, dA = -dR U^T
            dB_dA = _dot(_stack(dO, dR), _in_pair(U, half), _NT)        # [dO; dR] U^T
            dRb = b * dR                                   # d(V - (K . e^c) S), and dv
            dQe_dKe = _dot(_stack(dO, -dRb), S, _NN)       # [C, dk] each: d(q . e^c), d(k . e^c)
            dQe, dKe, dKd = dQe_dKe[:CHUNK], dQe_dKe[CHUNK:], _dot(U, dS, _NN)
            dv_ref[at, :] = dRb[:, :dv]
            ds_scr[...] = gamma * dS + _dot(_stack(-dRb, dO), _stack(ke, qe), _TN)
            dgamma = jnp.sum(S * dS, axis=0, keepdims=True)             # [1, dk]
            halves[half] = dict(
                dB=jnp.where(m.lower[rows], dB_dA[:CHUNK], 0.0),
                dA=jnp.where(m.strict[rows], -dB_dA[CHUNK:], 0.0),
                dq=e * dQe, dk=e * dKe + d * dKd,
                dbeta=jnp.sum(dR * Z, axis=1, keepdims=True),
                # the sums of g enter through e (c), d (c_C - c) and e^{c_C} (c's last row)
                dc=e * (q * dQe + k * dKe) + jnp.where(at_end, gamma * dgamma, 0.0),
                ds=kd * dKd)
        whole = {name: _stack(halves[0][name], halves[1][name]) for name in halves[0]}
        # what is left reads no state: the levels' products' cotangents onto q, k, beta and g
        q, k = q_src[both, :], k_src[both, :]
        on_eye = jnp.sum(jnp.where(m.eye, whole["dB"], 0.0), axis=1, keepdims=True)
        dq, dkb, dk_ = whole["dq"] + on_eye * k, 0.0, whole["dk"] + on_eye * q
        dsums = [whole["dc"], whole["ds"]]
        for h, mask, X in zip(_HALVES, m.masks, m.X):
            qx, kbx, kx = q * X, m.kb * X, k * X
            dM = _stack(_later(jnp.where(mask, whole["dB"], 0.0), h, short[0]),
                        _later(jnp.where(mask, whole["dA"], 0.0), h, short[1]))
            n = dM.shape[0] // 2
            rows = _stack(_later(qx, h, short[2]), _later(kbx, h, short[3]))
            drows, dcols = _dot(dM, kx, _NN), _dot(dM, rows, _TN)
            dqx, dkbx = _spread(drows[:n], h, short[4]), _spread(drows[n:], h, short[5])
            if h < _SUBLANES:   # what the scratch held in the earlier halves' rows
                dqx, dkbx = (jnp.where(_later_rows(h), d, 0.0) for d in (dqx, dkbx))
            dq, dkb, dk_ = dq + dqx * X, dkb + dkbx * X, dk_ + dcols * X
            dsums.append(dqx * qx + dkbx * kbx + dcols * kx)
        dq_ref[both, :] = dq[:, :dk]
        dk_ref[both, :] = (dk_ + m.b * dkb)[:, :dk]
        dg_ref[both, :] = _sum_dot(sums_t_ref[...], _stack(*dsums))[:, :dk]
        db_ref[p] = _row(whole["dbeta"] + jnp.sum(dkb * k, axis=1, keepdims=True), m.eye)


def _specs(block, H, dk, dv, blocks, reverse):
    """Block specs of (q, k or g, v, beta, `_SUMS`, the states, the
    inverses) on the grid (batch x heads, blocks of pairs), the blocks
    walked from the end if `reverse`. q, k, v and g are read where they
    stand, [B, H, T, d]: a step sees its head's rows as [rows, d]."""
    step = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    rows = block * _PAIR
    at_head = lambda bh, i: (bh // H, bh % H, step(i), 0)  # noqa: E731
    at = lambda bh, i: (bh, step(i), 0, 0)  # noqa: E731
    return (pl.BlockSpec((None, None, rows, dk), at_head),
            pl.BlockSpec((None, None, rows, dv), at_head),
            pl.BlockSpec((None, block, 1, _PAIR), at),
            pl.BlockSpec(_SUMS.shape, lambda bh, i: (0, 0)),
            pl.BlockSpec(_SUMS.shape[::-1], lambda bh, i: (0, 0)),
            pl.BlockSpec((None, 2 * block, dv, dk), at),
            pl.BlockSpec((None, block, CHUNK, _PAIR), at))


# the scratch the levels under 8 positions move their rows through (`_later`, `_spread`): the
# forward's two stacked operands and its two products, the backward's four and two
_SHORT_FWD, _SHORT_BWD = 4, 6


def _scratch(block, dk, dv, keys, values, states, short):
    """VMEM scratch of a kernel: `states` matrices [dv, dk] as wide as
    whole lanes, `short` pairs' worth of rows for the levels under a
    sublane tile, then a block as wide as whole lanes for each of `keys`
    arrays as wide as k and `values` as wide as v, where the head size
    fills no lane tile (`_staged`)."""
    wide = lambda d: -(-d // _LANES) * _LANES  # noqa: E731
    rows = block * _PAIR
    return ([pltpu.VMEM((wide(dv), wide(dk)), _F32)] * states
            + [pltpu.VMEM((_PAIR, max(wide(dk), _PAIR)), _F32)] * short
            + [pltpu.VMEM((rows, wide(dk)), _F32)] * (keys if dk % _LANES else 0)
            + [pltpu.VMEM((rows, wide(dv)), _F32)] * (values if dv % _LANES else 0))


# a jitted function of its own, forward and backward each: the model's layers share ONE trace
# of a kernel's body, and the compiled step names the kernels after these functions
@functools.partial(jax.jit, static_argnames="interpret")
def kda_fwd(q, k, v, g, b, interpret):
    """q, k, g [B, H, T, dk], v [B, H, T, dv], b [B x H, P, 1, _PAIR]
    (beta, a row a pair), float32, T whole blocks of pairs -> o
    [B, H, T, dv], the chunks' starting states TRANSPOSED [B x H, 2 P, dv,
    dk], the pairs' inverses [B x H, P, CHUNK, _PAIR] (a pair's two
    diagonal blocks side by side: the other half of the pair's matrix is
    zeros)."""
    B, H, T, dk = q.shape
    dv, P = v.shape[-1], b.shape[1]
    block = min(_BLOCK, P)
    qk, vo, beta, sums, _, states, solves = _specs(block, H, dk, dv, P // block, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block),
        grid=(B * H, P // block),
        in_specs=[qk, qk, vo, qk, beta, sums],
        out_specs=[vo, states, solves],
        out_shape=[jax.ShapeDtypeStruct(v.shape, _F32),
                   jax.ShapeDtypeStruct((B * H, 2 * P, dv, dk), _F32),
                   jax.ShapeDtypeStruct((B * H, P, CHUNK, _PAIR), _F32)],
        scratch_shapes=_scratch(block, dk, dv, keys=3, values=1, states=1, short=_SHORT_FWD),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(q, k, v, g, b, _SUMS)


@functools.partial(jax.jit, static_argnames="interpret")
def kda_bwd(q, k, v, g, b, states, solves, do, interpret):
    B, H, T, dk = q.shape
    dv, P = v.shape[-1], b.shape[1]
    block = min(_BLOCK, P)
    qk, vo, beta, sums, sums_t, st, sv = _specs(block, H, dk, dv, P // block, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block),
        grid=(B * H, P // block),
        in_specs=[qk, qk, vo, qk, beta, sums, sums_t, st, sv, vo],
        out_specs=[qk, qk, vo, qk, beta],
        out_shape=[jax.ShapeDtypeStruct(a.shape, _F32) for a in (q, k, v, g, b)],
        scratch_shapes=_scratch(block, dk, dv, keys=3, values=2, states=2, short=_SHORT_BWD),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(q, k, v, g, b, _SUMS, _SUMS.T, states, solves, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(interpret, q, k, v, g, b):
    return kda_fwd(q, k, v, g, b, interpret)[0]


def _rule_fwd(interpret, q, k, v, g, b):
    o, states, solves = kda_fwd(q, k, v, g, b, interpret)
    # named, so that a remat policy can SAVE them (models/solar_open2.py::REMAT_SAVES lists the
    # names): with all kept the backward needs no second forward; the "dots" policy alone sees
    # no dot_general in a pallas_call
    o = checkpoint_name(o, "kda_out")
    states, solves = (checkpoint_name(a, "kda_states") for a in (states, solves))
    return o, (q, k, v, g, b, states, solves)


def _rule_bwd(interpret, residuals, do):
    return tuple(kda_bwd(*residuals, do, interpret))


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array) -> jax.Array:
    """q, k [B, H, T, dk], v [B, H, T, dv] (any float dtype), g [B, H, T,
    dk] the log decay a channel (<= 0) and beta [B, H, T] the write
    strength -> o [B, H, T, dv] float32, the state starting at 0. The
    module's docstring has the algebra. Two layer spans a call site WHILE
    TRACING count the sites: `kda.rule`, and `kda.kernel` that the kernels
    are what runs it."""
    B, H, T, dk = q.shape
    P = -(-T // _PAIR)
    P = -(-P // min(_BLOCK, P)) * min(_BLOCK, P)
    short = P * _PAIR - T

    def whole(a):
        """float32, zeros beyond T up to whole blocks of pairs (nothing where T is whole)."""
        a = a.astype(_F32)
        return jnp.pad(a, ((0, 0), (0, 0), (0, short)) + ((0, 0),) * (a.ndim - 3)) if short else a

    with obs.layer_span("kda.rule"), obs.layer_span("kda.kernel"):
        o = _rule(jax.default_backend() != "tpu", whole(q), whole(k), whole(v), whole(g),
                  whole(beta).reshape(B * H, P, 1, _PAIR))
    return o[:, :, :T] if short else o

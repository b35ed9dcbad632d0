"""Kimi Delta Attention's rule over a sequence, in chunks: a delta rule whose
decay is a VECTOR over the key's channels.

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

    A   = tril(diag(beta) M(K), -1)
    U   = (I + A)^-1 diag(beta) (V - (K . e^c) S)  =  U0 - W S
    O   = (Q . e^c) S + tril(M(Q)) U
    S  <- Diag(e^{c_C}) S + (K . e^{c_C - c})^T U.

W and U0, two right-hand sides of the same solve, M(Q) and the decayed
copies of q and k read no state and are made for every chunk at once;
what runs ALONG the sequence is a `lax.scan` over the chunks that carries
S alone (three products and an add a step).

WHY THIS IS NOT ops/gated_delta.py WITH A WIDER g. With ONE decay a head
the decay factors out of the products: M(A) = (A K^T) . G with G_ij =
exp(c_i - c_j) a [64, 64] mask, which is what that module's kernels build
in VMEM. With a decay a channel it does not: M is a sum over channels of
products that each carry their own exponential, and the obvious split
(a . e^c)(k . e^-c)^T overflows float32 inside ONE chunk as soon as a
channel forgets fast (e^{-c} after 64 positions at g = -30 is e^1920).
NO EXPONENT OF A POSITIVE NUMBER IS FORMED HERE (fla's scheme): a chunk
is cut into sub-blocks of `SUB` = 16 positions;

  * between two sub-blocks I > J the decays are taken relative to r, the
    FIRST row of the later one: (a_i . e^{c_i - c_r}) (k_j . e^{c_r - c_j})^T,
    where c_i - c_r <= 0 for i in I and c_r - c_j <= 0 for j before r: one
    [16, dk] x [dk, 64] product a sub-block of rows, whose columns at or
    after r are masked away (their exponents are clamped at 0 first);
  * inside a diagonal sub-block the [16, 16, dk] differences c_i - c_j
    themselves are formed, clamped at 0 above the diagonal, and summed
    over the channels (an elementwise pass the compiler fuses into its
    reduction: no [16, 16, dk] array is written).

A factor that underflows to 0 stands for a product that is smaller still
(both exponents are <= 0 and add up to c_i - c_j). That algebra is one
module's worth and shares nothing with the scalar rule's Pallas kernels
but the chunk size, so it is a module of its own (`olmo-hybrid-train`'s
lowered step is untouched); with g constant over a head's channels it IS
the scalar rule, and tests/test_kda.py holds the two to each other.

Everything is float32 with the matmuls at `highest` precision: the decay,
the solve and the carried state never see bfloat16. No array is [T, T];
the largest written is [T / 64, 4, 64, dk] a head (the columns' four
references).

THE BACKWARD is `jax.grad` of this: the parts outside the scan are plain
batched algebra, and the scan's transpose walks the chunks in reverse
carrying dS. Nothing here is a `dot_general` without batch dimensions and
nothing is named, so under `jax.checkpoint` (models/llama.py::_remat,
"dots" or "full" alike) NOTHING of a chunk's arrays is saved for the
backward: the forward runs once more there, a layer at a time.

ONE path, jax.numpy, one chunk size: there is no kernel behind it yet
(PERF.md section 7: the next `perf_opt`, which `kda_scan_roofline`
sizes) and no option. A sequence that is no multiple of CHUNK is padded
with positions that write nothing (k = v = 0, beta = 0, g = 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ray_tpu import obs

# fla's: 64 positions. 8,192 tokens are 128 steps of the scan; a chunk's solve is 64 x 64
CHUNK = 64
# positions of a sub-block: inside one the [SUB, SUB, dk] differences are formed, between two
# the decays are taken relative to the later one's first row
SUB = 16
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _decayed_products(a: jax.Array, k: jax.Array, c: jax.Array) -> jax.Array:
    """a, k, c [..., CHUNK, dk] -> M [..., CHUNK, CHUNK], M_ij = sum_d
    a_id k_jd exp(c_id - c_jd) for i >= j and 0 above the diagonal, with no
    exponent of a positive number formed (the module's docstring)."""
    lead, (C, dk) = a.shape[:-2], a.shape[-2:]
    n = C // SUB
    a4, k4, c4 = (x.reshape(*lead, n, SUB, dk) for x in (a, k, c))
    first = c4[..., 0, :]                                             # [..., n, dk]: c_r
    rows = a4 * jnp.exp(c4 - first[..., None, :])                     # i in I: c_i - c_r <= 0
    # every column against every sub-block's r; at or after r the exponent is clamped
    # (those columns are masked below)
    cols = k[..., None, :, :] * jnp.exp(
        jnp.minimum(first[..., :, None, :] - c[..., None, :, :], 0.0))  # [..., n, C, dk]
    between = jnp.einsum("...id,...jd->...ij", rows, cols, precision=_HI)   # [..., n, SUB, C]
    block = jnp.arange(C) // SUB
    between = jnp.where(block[:, None] > block[None, :], between.reshape(*lead, C, C), 0.0)
    # the diagonal sub-blocks: the differences themselves, one fused pass
    d = jnp.minimum(c4[..., :, None, :] - c4[..., None, :, :], 0.0)   # [..., n, SUB, SUB, dk]
    inside = jnp.sum(a4[..., :, None, :] * k4[..., None, :, :] * jnp.exp(d), axis=-1)
    inside = jnp.where(jnp.tril(jnp.ones((SUB, SUB), bool)), inside, 0.0)
    eye = jnp.eye(n, dtype=_F32)
    inside = (inside[..., :, :, None, :] * eye[:, None, :, None]).reshape(*lead, C, C)
    return between + inside


def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array) -> jax.Array:
    """q, k [B, H, T, dk], v [B, H, T, dv] (any float dtype), g [B, H, T,
    dk] the log decay a channel (<= 0) and beta [B, H, T] the write
    strength -> o [B, H, T, dv] float32, the state starting at 0. The
    module's docstring has the algebra. One layer span a call site WHILE
    TRACING (`kda.rule`) counts the sites."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    C = CHUNK
    N = -(-T // C)

    def chunks(a):
        """[B, H, T, ...] -> float32 [N, B, H, C, ...], zeros after T."""
        a = jnp.pad(a.astype(_F32), [(0, 0), (0, 0), (0, N * C - T)] + [(0, 0)] * (a.ndim - 3))
        return jnp.moveaxis(a.reshape(B, H, N, C, *a.shape[3:]), 2, 0)

    with obs.layer_span("kda.rule"):
        q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
        c = jnp.cumsum(g, axis=-2)                                    # [N, B, H, C, dk]
        kb = k * beta[..., None]
        system = jnp.tril(_decayed_products(kb, k, c), -1) + jnp.eye(C, dtype=_F32)
        rhs = jnp.concatenate([kb * jnp.exp(c), v * beta[..., None]], axis=-1)
        solved = solve_triangular(system, rhs, lower=True, unit_diagonal=True)
        W, U0 = solved[..., :dk], solved[..., dk:]
        Mq = _decayed_products(q, k, c)
        Qc = q * jnp.exp(c)
        last = c[..., -1:, :]                                         # [N, B, H, 1, dk]: c_C
        Kd = k * jnp.exp(last - c)
        decay = jnp.exp(last[..., 0, :])                              # [N, B, H, dk]

        def chunk(S, xs):
            W, U0, Mq, Qc, Kd, decay = xs
            U = U0 - jnp.einsum("bhck,bhkv->bhcv", W, S, precision=_HI)
            O = (jnp.einsum("bhck,bhkv->bhcv", Qc, S, precision=_HI)
                 + jnp.einsum("bhij,bhjv->bhiv", Mq, U, precision=_HI))
            S = decay[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", Kd, U, precision=_HI)
            return S, O

        _, O = jax.lax.scan(chunk, jnp.zeros((B, H, dk, dv), _F32), (W, U0, Mq, Qc, Kd, decay))
    return jnp.moveaxis(O, 0, 2).reshape(B, H, N * C, dv)[:, :, :T]

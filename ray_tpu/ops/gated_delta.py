"""The gated delta rule over a sequence, in chunks.

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
do: the sequence in chunks of `CHUNK` positions (the
WY form of arXiv:2406.06484 with the decay of arXiv:2412.06464). Inside
a chunk, with c_i the decay summed from the chunk's start to i,
G_ij = exp(c_i - c_j) for i >= j and S_0 the state the chunk starts from,
the "pseudo-values" u_j = beta_j (v_j - (alpha_j S_{j-1})^T k_j) solve ONE
unit lower-triangular system,

    (I + tril(diag(beta) (K K^T . G), -1)) U = diag(beta) V - diag(beta e^c) K S_0,

so U = U0 - W S_0 with U0 and W two right-hand sides of the same solve,
made for every chunk at once (nothing in them reads a state). What runs
ALONG the sequence is a scan over the chunks that carries S alone:

    U   = U0 - W S
    O   = (Q . e^c) S + tril(Q K^T . G) U
    S  <- e^{c_C} S + (K . e^{c_C - c})^T U.

(The other form was built and measured: S <- M S + N with M = e^{c_C} I
- (K . e^{c_C - c})^T W and N made for every chunk at once, the loop one
product and an add, U and O batched over all chunks after it. It read
9.69 / 21.45 ms a layer, forward / rematerialised forward + backward,
where this one reads 8.82 / 19.69 (my chip runs, PR 46, calls 3 and 5):
the loop's steps are not what costs, the stacked states' bytes are.)

Everything is float32 with the matmuls at `highest` precision: the
decay, the solve and the carried state never see bfloat16, and the decay
is applied position by position (G), never at the chunk's granularity.
No array is [T, T]; the largest is [T / CHUNK, CHUNK, CHUNK] a head.

THE BACKWARD is `jax.grad` of this: the parts outside the scan are
plain batched algebra, and the scan's transpose walks the chunks in
reverse carrying dS. Under `jax.checkpoint` (models/llama.py::_remat)
the forward scan runs once more in the backward, to hand the transpose
the state each chunk STARTED from ([T / CHUNK, heads, dk, dv] float32:
135 MiB a layer at 4,096 tokens of 30 heads of 96 x 192, alive for one
layer at a time): three passes a layer and step, each linear in the
tokens. tests/test_gated_delta.py holds forward and every gradient
to the position-by-position rule's.

ONE path, jax.numpy: there is no kernel behind it and no option. A
sequence that is no multiple of CHUNK is padded with positions that
write nothing (k = v = 0, beta = 0, g = 0) and read nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

# fla's: 64 positions. 4,096 tokens are 64 steps of the scan; a chunk's solve is 64 x 64
CHUNK = 64
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array) -> jax.Array:
    """q, k [B, H, T, dk], v [B, H, T, dv] (any float dtype), g [B, H, T]
    the log decay (<= 0) and beta [B, H, T] the write strength -> o
    [B, H, T, dv] float32, the state starting at 0. The module's
    docstring has the algebra."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    C = CHUNK
    N = -(-T // C)

    def chunks(a):
        """[B, H, T, ...] -> float32 [N, B, H, C, ...], zeros after T."""
        a = jnp.pad(a.astype(_F32), [(0, 0), (0, 0), (0, N * C - T)] + [(0, 0)] * (a.ndim - 3))
        return jnp.moveaxis(a.reshape(B, H, N, C, *a.shape[3:]), 2, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    c = jnp.cumsum(g, axis=-1)                                        # [N, B, H, C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # exp only of what the mask keeps: above the diagonal c_i - c_j > 0 may overflow
    G = jnp.where(lower, jnp.exp(jnp.where(lower, c[..., :, None] - c[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    A = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI) * G
    system = jnp.tril(A, -1) + jnp.eye(C, dtype=_F32)
    rhs = jnp.concatenate([kb * jnp.exp(c)[..., None], v * beta[..., None]], axis=-1)
    solved = solve_triangular(system, rhs, lower=True, unit_diagonal=True)
    W, U0 = solved[..., :dk], solved[..., dk:]
    Aqk = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HI) * G
    Qg = q * jnp.exp(c)[..., None]
    Kg = k * jnp.exp(c[..., -1:] - c)[..., None]
    decay = jnp.exp(c[..., -1])                                       # [N, B, H]

    def chunk(S, xs):
        W, U0, Aqk, Qg, Kg, decay = xs
        U = U0 - jnp.einsum("bhck,bhkv->bhcv", W, S, precision=_HI)
        O = (jnp.einsum("bhck,bhkv->bhcv", Qg, S, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", Aqk, U, precision=_HI))
        S = decay[..., None, None] * S + jnp.einsum("bhck,bhcv->bhkv", Kg, U, precision=_HI)
        return S, O

    _, O = jax.lax.scan(chunk, jnp.zeros((B, H, dk, dv), _F32), (W, U0, Aqk, Qg, Kg, decay))
    return jnp.moveaxis(O, 0, 2).reshape(B, H, N * C, dv)[:, :, :T]

"""The selective state-space scan of a Mamba-2 mixer in its chunked dual
form (state-space duality, arXiv:2405.21060), jax.numpy.

A head h of P channels carries a state H [P, N]; B and C [N] are shared
by the heads of a GROUP (head h reads group h // (heads / groups)); the
decay is one number a head and position:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        (H = 0 before the sequence)
    y_t = H_t C_t + D x_t

`ssd_scan` computes exactly this, position for position the same sums in
another order, over chunks of `chunk` positions (128: the published
`chunk_size`). With a_t = dt_t A (<= 0) and cum its running sum inside a
chunk:

  * INSIDE a chunk y_t = sum_{s <= t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s:
    the scores C B^T once a GROUP [L, L], masked and decayed a HEAD, times
    the chunk's x: two matmuls, nothing longer than a chunk on a side;
  * a chunk's OWN contribution to the state at its end,
    sum_s exp(cum_L - cum_s) dt_s x_s B_s^T, for all chunks at once;
  * a `lax.scan` over the chunks carries H [B, heads, P, N]:
    H <- exp(cum_L) H + the chunk's own, and hands out each chunk's
    STARTING state;
  * the read-out of that state, exp(cum_t) (H_start C_t), added to y.

Everything is float32 and every product runs at `highest` (a float32
matmul is one bfloat16 pass on the chip otherwise: the scan would then
carry bfloat16 operands, which the benchmark's check of the scan alone
refuses). B and C are never broadcast to the heads: they enter each
product as the group's [.., N] operand. Nothing is T x T and no loop
runs over positions. A sequence that is no whole number of chunks is
padded with positions of dt = 0, which decay nothing and write nothing.

The backward is JAX's own transpose of these products; under a block's
`jax.checkpoint` (models/llama.py's "dots" policy keeps no product that
has a batch dimension, which all of these have) the scan is computed
again in the backward pass and nothing of it is held between the two.

ONE path, ONE public name: models/nemotron_h.py binds `ssd_scan`, and
the benchmark's runner holds the function bound to that name alone to
the position-by-position recurrence (a kernel that replaces this body
is held to the same yardstick).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128
_F32 = jnp.float32


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
             D: jax.Array, *, chunk: int = CHUNK) -> jax.Array:
    """x [b, heads, T, P], dt [b, heads, T] (after its softplus: >= 0), A
    [heads] (< 0), B and C [b, groups, T, N], D [heads] -> y [b, heads, T,
    P] float32 (the module's docstring has the equations)."""
    b, heads, T, P = x.shape
    groups, N = B.shape[1], B.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads in {groups} groups")
    per = heads // groups
    x, dt, B, C = (v.astype(_F32) for v in (x, dt, B, C))
    short = -T % chunk
    if short:  # dt = 0: no decay, nothing written; the rows are dropped below
        x, B, C = (jnp.pad(v, ((0, 0), (0, 0), (0, short), (0, 0))) for v in (x, B, C))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, short)))
    n = (T + short) // chunk
    einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    # g a group, h a head of it, c a chunk, l / m positions of it, p a channel, n the state
    xs = x.reshape(b, groups, per, n, chunk, P)
    dts = dt.reshape(b, groups, per, n, chunk)
    Bs, Cs = B.reshape(b, groups, n, chunk, N), C.reshape(b, groups, n, chunk, N)
    cum = jnp.cumsum(dts * A.astype(_F32).reshape(groups, per, 1, 1), axis=-1)
    total = cum[..., -1]

    # inside a chunk
    later = jnp.tril(jnp.ones((chunk, chunk), bool))         # [l, m]: m <= l
    span = cum[..., :, None] - cum[..., None, :]
    # (the inner select: exp of a masked-out positive span would be inf, and 0 x inf in a gradient)
    decay = jnp.where(later, jnp.exp(jnp.where(later, span, 0.0)), 0.0)
    scores = einsum("bgcln,bgcmn->bgclm", Cs, Bs)
    y = einsum("bghclm,bghcmp->bghclp", scores[:, :, None] * decay * dts[..., None, :], xs)

    # each chunk's own contribution to the state at its end, then the carry over the chunks
    own = einsum("bgcmn,bghcmp->bghcpn", Bs,
                 xs * (jnp.exp(total[..., None] - cum) * dts)[..., None])

    def carry(H, chunk_of):
        kept, add = chunk_of
        return H * kept[..., None, None] + add, H

    _, starts = jax.lax.scan(
        carry, jnp.zeros((b, groups, per, P, N), _F32),
        (jnp.moveaxis(jnp.exp(total), 3, 0), jnp.moveaxis(own, 3, 0)))
    starts = jnp.moveaxis(starts, 0, 3)                       # [b, g, h, c, p, n]

    # the carried state read out
    y = y + einsum("bgcln,bghcpn->bghclp", Cs, starts) * jnp.exp(cum)[..., None]
    y = y + xs * D.astype(_F32).reshape(groups, per, 1, 1, 1)
    y = y.reshape(b, heads, T + short, P)
    return y[:, :, :T] if short else y

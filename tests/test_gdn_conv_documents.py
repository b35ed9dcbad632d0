"""ops/gdn_conv.py under PACKED DOCUMENTS (`segment_ids`): the one forward
and the one backward kernel (under the interpreter here) against a sum of
four masked shifts (chipbench/reference/granite_hybrid_decoder.py's
`conv`, which imports nothing of the program): tap j of position t reads
position t - j only where both lie in one document; the output and every
gradient (x, the taps, the bias), across the blocks' edges, in bfloat16 and
float32, with the L2 norm; a sequence of one document is the convolution
without ids, to the bit; WITHOUT ids the traced program is the parent's."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import granite_hybrid_decoder as reference
from ray_tpu.ops import gdn_conv as gc
from ray_tpu.ops.gdn_conv import gdn_conv

K = 4
# a block is 512 rows, a tile of the walk 128: boundaries at both kinds of edge, one position
# either side of them, in consecutive positions and at position 1
STARTS = (1, 2, 5, 17, 18, 19, 127, 128, 129, 511, 512, 513, 515, 1024, 1025)


def ids_of(T, B=2):
    doc = np.zeros(T, np.int64)
    for s in STARTS:
        doc[s:] += s < T
    ids = np.where(doc % 2 == 0, 7, 3)          # ids recur: a document is a run
    return jnp.asarray(np.stack([ids, np.roll(ids, 2)][:B]), jnp.int32)


def four_masked_shifts(x, taps, bias, ids):
    """The reference's `conv` and SiLU a head and sequence; x [B, H, T, d]."""
    B, H, T, d = x.shape
    runs = jnp.cumsum(jnp.pad(ids[:, 1:] != ids[:, :-1], ((0, 0), (1, 0))), axis=1)
    rows = x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B, T, H * d)
    b = jnp.zeros((H * d,)) if bias is None else bias
    pre = jnp.stack([reference.conv(rows[i], taps, b, runs[i]) for i in range(B)])
    return jax.nn.silu(pre).reshape(B, T, H, d).transpose(0, 2, 1, 3)


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("T,dtype,d", [(50, jnp.float32, 128), (1100, jnp.bfloat16, 128),
                                       (600, jnp.float32, 64), (530, jnp.float32, 192)],
                         ids=["one_block", "three_blocks_bf16", "heads_of_64", "heads_of_192"])
def test_the_convolution_under_documents_is_four_masked_shifts(T, dtype, d):
    B, H = 2, 3
    ks = jax.random.split(jax.random.key(T), 4)
    x = jax.random.normal(ks[0], (B, H, T, d)).astype(dtype)
    taps, bias = jax.random.normal(ks[1], (K, H * d)), jax.random.normal(ks[2], (H * d,))
    w, ids = jax.random.normal(ks[3], (B, H, T, d)), ids_of(T)
    y, pull = jax.vjp(lambda x, t, b: gdn_conv(x, t, bias=b, segment_ids=ids), x, taps, bias)
    want, pull_ref = jax.vjp(lambda x, t, b: four_masked_shifts(x, t, b, ids), x, taps, bias)
    assert rel(y, want) < 1e-6
    for g, r, tol in zip(pull(w), pull_ref(w), (2e-5 if dtype == jnp.bfloat16 else 1e-6, 2e-6, 2e-6)):
        assert g.dtype == r.dtype and rel(g, r) < tol
    assert rel(gdn_conv(x, taps, bias=bias), want) > 1e-2    # not the convolution without them


def test_with_the_l2_norm_and_no_bias():
    B, H, T, d = 1, 2, 140, 128
    ks = jax.random.split(jax.random.key(1), 3)
    x, taps = jax.random.normal(ks[0], (B, H, T, d)), jax.random.normal(ks[1], (K, H * d))
    ids = ids_of(T, B)
    want = four_masked_shifts(x, taps, None, ids)
    want = want * jax.lax.rsqrt(jnp.sum(want * want, -1, keepdims=True) + gc.L2_EPS) * 0.5
    assert rel(gdn_conv(x, taps, scale=0.5, segment_ids=ids), want) < 1e-6


def test_one_document_is_the_convolution_without_ids_to_the_bit():
    B, H, T, d = 2, 2, 700, 128
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (B, H, T, d)).astype(jnp.bfloat16)
    taps, bias = jax.random.normal(ks[1], (K, H * d)), jax.random.normal(ks[2], (H * d,))
    f = lambda ids: jax.value_and_grad(  # noqa: E731
        lambda x, t, b: jnp.sum(gdn_conv(x, t, bias=b, segment_ids=ids) ** 2), (0, 1, 2))(x, taps, bias)
    (y, grads), (y0, grads0) = f(jnp.full((B, T), 3, jnp.int32)), f(None)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    for g, g0 in zip(grads, grads0):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(g0, np.float32))


def test_the_kernels_distances():
    """`_since`: a position's distance from its document's first, at most K - 1,
    down the head's lanes in x's dtype; the padding reads 0."""
    ids = jnp.asarray([[4, 4, 4, 4, 4, 9, 4, 4]], jnp.int32)
    since = np.asarray(gc._since(ids, 4, short=2, d=8, dtype=jnp.bfloat16).astype(jnp.float32))
    assert since.shape == (1, 10, 8) and (since == since[:, :, :1]).all()
    np.testing.assert_array_equal(since[0, :, 0], [0, 1, 2, 3, 3, 0, 0, 1, 0, 0])


# sha256 of the jaxpr of `gdn_conv` WITHOUT ids, forward and backward, as the parent of PR 66
# traced it (commit a9a0c77): a Mamba mixer's call (a bias, 48 heads of 128, bfloat16) and a
# delta-rule mixer's (the L2 norm, no bias)
_NO_DOCUMENTS = {
    "bias": "963632552cc03854f408cd0151adccc41d810e07676e8e538ccfaa6eebce8023",
    "norm": "dd5fd38a146162b01961a156d015e8ff5236d80ca36331889e3492e620b26192",
}


def _jaxpr(kind: str, with_ids: bool = False) -> str:
    S = jax.ShapeDtypeStruct
    x, taps = S((1, 48, 1024, 128), jnp.bfloat16), S((4, 48 * 128), jnp.float32)
    ids = jnp.zeros((1, 1024), jnp.int32) if with_ids else None
    if kind == "bias":
        f = lambda x, t, b: gdn_conv(x, t, bias=b, segment_ids=ids).sum()  # noqa: E731
        return str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(
            x, taps, S((48 * 128,), jnp.float32)))
    f = lambda x, t: gdn_conv(x, t, scale=0.125, segment_ids=ids).sum()  # noqa: E731
    return str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1)))(x, taps))


@pytest.mark.parametrize("kind", sorted(_NO_DOCUMENTS))
def test_without_ids_the_traced_kernels_are_the_parents(kind):
    assert hashlib.sha256(_jaxpr(kind).encode()).hexdigest() == _NO_DOCUMENTS[kind]
    assert _jaxpr(kind, with_ids=True) != _jaxpr(kind)

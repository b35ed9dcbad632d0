"""The flash kernels under a selection of keys (PR 42; ops/flash.py,
`selection=`), in interpret mode on the CPU against `xla_attention`
under the same mask: value and all three gradients with the whole kv
sequence in one block (the fused backward), a kv block of TWO selection
blocks (PR 43: the cell's 8192 keys at heads of 128 in bf16 take this
path, fused), over two kv blocks (the dq and dk/dv kernels apart: a
sequence over the budget of bytes), with folded heads, padded rows and
keys, segment ids and a window beside it; the packed layout; and
`selection=None` tracing to the kernels the parent traced."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash
from ray_tpu.ops.attention import attention_head_major, xla_attention
from ray_tpu.ops.flash import flash_attention, pack_selection, unpack_selection


def _mask(b, s, sk, density=0.4, seed=5):
    """A random selection in which every row sees its own key."""
    m = jax.random.bernoulli(jax.random.key(seed), density, (b, s, sk))
    return m | (jnp.arange(sk)[None, :] == (jnp.arange(s)[:, None] + sk - s))[None]


def _against_xla(shape, *, seg=False, window=None, d_tol=2e-3, **kw):
    b, s, h, kvh, d = shape
    q = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32) * 0.5
    k, v = (jax.random.normal(jax.random.key(i), (b, s, kvh, d), jnp.float32) * 0.5 for i in (2, 3))
    probe = jax.random.normal(jax.random.key(4), (b, s, h, d), jnp.float32)
    sel = pack_selection(_mask(b, s, s))
    segs = None
    if seg:
        segs = jnp.broadcast_to((jnp.arange(s) >= s // 3).astype(jnp.int32), (b, s))
    # one program each: taken bare, every operation of the composite's value and gradient is
    # compiled alone for this case's shapes
    got = jax.jit(jax.value_and_grad(lambda *a: (flash_attention(
        *a, selection=sel, segment_ids=segs, window=window, **kw) * probe).sum(), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(lambda *a: (xla_attention(
        *a, selection=sel, segment_ids=segs, window=window) * probe).sum(), (0, 1, 2)))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-3)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=d_tol, atol=2e-4)


# float32 at heads of 32 is 4 MiB a k block of 8192 keys as VMEM holds it (the 128 lanes):
# the module's own budget since PR 56, so 4224 keys are ONE kv block of two selection blocks
# and the backward fused; under the 2 MiB it was before, they are two kv blocks of 4096 and
# the kernels apart (the form a selection over more keys than the budget admits still takes)
@pytest.mark.parametrize("case", [
    dict(shape=(1, 1024, 4, 1, 64), block_q=128),             # two sub-tiles of 512, heads folded
    dict(shape=(2, 300, 3, 1, 64)),                           # padded rows and keys, one ragged tile
    dict(shape=(1, 640, 2, 2, 64), block_q=128, seg=True),    # segments beside the selection
    dict(shape=(1, 1024, 2, 1, 64), block_q=256, window=300),  # a window beside it
    dict(shape=(1, 4224, 2, 1, 32), block_q=256, budget=2 << 20, took="split"),  # two kv blocks
    dict(shape=(1, 4224, 2, 1, 32), block_q=256),  # one kv block, two selection blocks
    dict(shape=(1, 4224, 2, 1, 32), block_q=256, window=700, seg=True),
], ids=["fused_folded", "padded", "segments", "window", "two_kv_blocks",
        "two_selection_blocks_fused", "two_selection_blocks_window_segments"])
def test_selections_against_xla_attention(case, monkeypatch, backwards_traced):
    shape, took = case.pop("shape"), case.pop("took", "fused")
    if "budget" in case:
        monkeypatch.setattr(flash, "KV_BLOCK_BYTES", case.pop("budget"))
    assert backwards_traced(lambda: _against_xla(shape, **case)) == (took == "fused", took == "split")


def test_head_major_entry_takes_the_selection_for_both_impls():
    b, h, kvh, s, d = 1, 4, 2, 96, 32
    q = jax.random.normal(jax.random.key(1), (b, h, s, d), jnp.float32)
    k, v = (jax.random.normal(jax.random.key(i), (b, kvh, s, d), jnp.float32) for i in (2, 3))
    sel = pack_selection(_mask(b, s, s) & (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])[None])
    a = attention_head_major(q, k, v, causal=True, impl="flash", selection=sel)
    x = attention_head_major(q, k, v, causal=True, impl="xla", selection=sel)
    np.testing.assert_allclose(np.asarray(a), np.asarray(x), rtol=2e-3, atol=2e-4)
    with pytest.raises(ValueError, match="no selection"):
        attention_head_major(q, k, v, impl="ring", selection=sel)


def test_the_packed_layout_is_a_bit_a_pair_in_the_order_the_kernels_read():
    """Word (q, kv block j, lane) holds at bit b the key j x 4096 + 128 b +
    lane: 8 MiB at 8192 x 8192, a sub-tile of 512 keys four shifts of one
    [rows, 128] tile."""
    for s, sk in ((8, 200), (4, 4096), (2, 8192), (3, 5000)):
        m = _mask(1, s, sk, density=0.3, seed=s)
        packed = pack_selection(m)
        blocks = -(-sk // flash.selection_block(sk))
        assert packed.shape == (1, s, 128 * blocks) and packed.dtype == jnp.int32
        assert (np.asarray(unpack_selection(packed, sk)) == np.asarray(m)).all()
    one = jnp.zeros((1, 1, 8192), bool).at[0, 0, 4096 + 128 * 31 + 5].set(True).at[0, 0, 130].set(True)
    words = np.asarray(pack_selection(one)).view(np.uint32)[0, 0]
    assert words[128 + 5] == 1 << 31 and words[2] == 1 << 1 and words.astype(bool).sum() == 2
    assert pack_selection(jnp.ones((1, 8192, 8192), bool)).nbytes == 8 << 20
    q = jnp.zeros((1, 16, 1, 8))
    with pytest.raises(ValueError, match="pack_selection"):
        flash_attention(q, q, q, selection=jnp.zeros((1, 16, 16), jnp.int32))
    with pytest.raises(ValueError, match="pack_selection"):
        flash_attention(q, q, q, selection=pack_selection(jnp.ones((1, 16, 16), bool)), block_k=8)


# sha256 of the jaxpr of the flash kernels with no selection, forward and backward, as the
# parent of PR 39 traced them (commit 53acc07; tests/test_flash_window.py holds the same two
# for `window=None`, and PR 40's tree, the parent of this PR, traces to them too)
_NO_SELECTION = {
    "fused": "667f19c6f8580a5f8ae7637c95559c3091c29dcf87040a7869c449dd00313be3",
    "kv_blocks": "bff9a11cf7109d17fa1b44360dba0e885a0f3d6144c10649032dabd9c4b086b1",
}


def _kernels_jaxpr(selected=False, **kw) -> str:
    shape = jax.ShapeDtypeStruct((1, 2048, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
    sel = (jax.ShapeDtypeStruct((1, 2048, 128), jnp.int32),) if selected else ()

    def both(q, k, v, seg, *sel):
        return jax.value_and_grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, selection=sel[0] if sel else None, **kw,
            **({"segment_ids": seg} if "block_k" in kw else {})).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    return str(jax.make_jaxpr(both)(shape, kv, kv, seg, *sel))


@pytest.mark.parametrize("name,kw", [("fused", {}), ("kv_blocks", {"block_k": 1024})])
def test_without_a_selection_the_kernels_are_the_parents(name, kw):
    """`selection=None` traces to the kernels the parent traced, text for
    text: no operand, no ref, no term of the mask (the jaxpr carries the
    kernels' bodies and no source location)."""
    assert hashlib.sha256(_kernels_jaxpr(**kw).encode()).hexdigest() == _NO_SELECTION[name]
    if name == "fused":
        assert _kernels_jaxpr(selected=True) != _kernels_jaxpr()

"""The flash kernels at a value width of their own (PR 64; ops/flash.py:
`v`, `o` and `dv` at `Dv`, `q`, `k`, `dq` and `dk` at `D`), in interpret mode
on the CPU against `xla_attention` under the same mask: value and all three
gradients, for every kind of mask the kernels take (causal and not, a sliding
window, segment ids, padded rows and keys, a packed selection), with folded
heads, over one kv block (the fused backward) and over several (the dq and
dk/dv kernels apart); the VMEM the kernels state at keys of 192 beside values
of 128; and block diffusion at unlike widths refused by name. Where the two
widths are one the kernels trace to the parent's jaxprs:
tests/test_flash_selection.py's hashes hold that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash
from ray_tpu.ops.attention import attention_head_major, xla_attention
from ray_tpu.ops.flash import flash_attention, pack_selection


def _against_xla(shape, *, seg=False, selected=False, causal=True, window=None, tol=2e-3, **kw):
    b, s, h, kvh, d, dv = shape
    q = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.key(2), (b, s, kvh, d), jnp.float32) * 0.5
    v = jax.random.normal(jax.random.key(3), (b, s, kvh, dv), jnp.float32) * 0.5
    probe = jax.random.normal(jax.random.key(4), (b, s, h, dv), jnp.float32)
    segs = jnp.broadcast_to((jnp.arange(s) >= s // 3).astype(jnp.int32), (b, s)) if seg else None
    sel = None
    if selected:  # a random selection in which every row sees its own key
        mask = jax.random.bernoulli(jax.random.key(5), 0.4, (b, s, s)) | jnp.eye(s, dtype=bool)[None]
        sel = pack_selection(mask)
    masks = dict(causal=causal, segment_ids=segs, window=window, selection=sel)
    # one program each: taken bare, every operation of the composite is compiled alone
    got = jax.jit(jax.value_and_grad(lambda *a: (flash_attention(
        *a, **masks, **kw) * probe).sum(), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(lambda *a: (xla_attention(
        *a, **masks) * probe).sum(), (0, 1, 2)))(q, k, v)
    assert got[1][0].shape == q.shape and got[1][1].shape == k.shape and got[1][2].shape == v.shape
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-3)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol, atol=2e-4)


@pytest.mark.parametrize("case", [
    dict(shape=(1, 1024, 4, 1, 48, 32), block_q=128),             # two sub-tiles, heads folded
    dict(shape=(2, 300, 3, 1, 48, 32)),                           # padded rows and keys
    dict(shape=(1, 640, 2, 2, 24, 16), causal=False),             # no diagonal
    dict(shape=(1, 640, 2, 2, 48, 32), block_q=128, seg=True),    # segment ids
    dict(shape=(1, 1024, 2, 1, 48, 32), block_q=256, window=300),  # a sliding window
    dict(shape=(1, 640, 2, 1, 48, 32), block_q=128, selected=True),  # a packed selection
    dict(shape=(1, 1024, 4, 2, 48, 32), block_q=128, block_k=256, took="split"),  # four kv blocks
    dict(shape=(1, 1024, 2, 1, 48, 32), block_q=256, block_k=256, window=300, seg=True,
         took="split"),
    dict(shape=(1, 512, 2, 2, 16, 48), block_q=128),              # values WIDER than keys
    dict(shape=(1, 512, 4, 4, 192, 128), block_q=128),            # the cell's widths, short
], ids=["fused_folded", "padded", "not_causal", "segments", "window", "selection",
        "kv_blocks_split", "kv_blocks_window_segments", "values_wider", "keys_192_values_128"])
def test_values_of_their_own_width_against_xla_attention(case, backwards_traced):
    shape, took = case.pop("shape"), case.pop("took", "fused")
    assert backwards_traced(lambda: _against_xla(shape, **case)) == (took == "fused", took == "split")


def test_head_major_entry_takes_unlike_widths_for_both_impls():
    """`attention_head_major` (what models/mla.py calls): o is as wide as v,
    the scale that of the KEYS' width, through the kernels and through XLA."""
    q = jax.random.normal(jax.random.key(1), (1, 4, 200, 24), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (1, 2, 200, 24), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (1, 2, 200, 8), jnp.float32)
    got = attention_head_major(q, k, v, impl="flash")
    want = attention_head_major(q, k, v, impl="xla")
    by_hand = jnp.swapaxes(xla_attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                                         softmax_scale=24 ** -0.5), 1, 2)
    assert got.shape == want.shape == (1, 4, 200, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(want), np.asarray(by_hand), rtol=1e-6, atol=1e-6)


def test_the_kernels_state_their_vmem_by_both_widths():
    """Keys of 192 are 256 lanes in VMEM, values of 128 are 128: 8,192 keys in
    bf16 stay ONE kv block (a k block 4 MiB, the budget), the forward fits
    Mosaic's default (13.5 MiB of blocks), the fused backward states 45.75 MiB
    (k, dk 16 + v, dv 8 double-buffered, dk and dv again in float32 12, the
    rows 1.75, 8 spare). At one width the numbers are what they were."""
    assert flash.default_block_k(8192, 192, 2) == 8192
    assert flash._fwd_params(512, 8192, 192, 1, 2, 128) is None
    stated = flash._fused_bwd_params(512, 8192, 192, 1, 2, 128).vmem_limit_bytes
    assert stated == int(45.75 * 2 ** 20)
    padded = flash._fused_bwd_params(512, 8192, 192, 1, 2).vmem_limit_bytes  # v at 192 too
    assert padded == flash._fused_bwd_params(512, 8192, 256, 1, 2).vmem_limit_bytes > stated
    for d in (64, 128, 256):
        assert repr(flash._fused_bwd_params(512, 8192, d, 1, 2)) == repr(
            flash._fused_bwd_params(512, 8192, d, 1, 2, d))
        assert repr(flash._fwd_params(512, 16384, d, 1, 2)) == repr(
            flash._fwd_params(512, 16384, d, 1, 2, d))


def test_block_diffusion_at_unlike_widths_is_refused_by_name():
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match="values of a width of their own .8 beside keys of 16."):
        flash.flash_attention_head_major(q, q, jnp.zeros((1, 2, 64, 8)), blockdiff=(32, 4))

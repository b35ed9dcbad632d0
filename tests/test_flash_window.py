"""The flash kernels under a sliding window (PR 39; ops/flash.py), in
interpret mode on the CPU against `xla_attention` with the same mask:
value and all three gradients at the cell's own walk (4096 keys, a
window of 512, groups of 9 and 6), a window no sub-tile divides,
`q_offset`, segment ids, several kv blocks; the range walk's arithmetic;
and `window=None` tracing to the kernels the parent traced."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash
from ray_tpu.ops.attention import attention_head_major, xla_attention
from ray_tpu.ops.flash import flash_attention


def _against_xla(shape, *, window, seg=False, sk=None, **kw):
    b, s, h, kvh, d = shape
    sk = sk or s
    q = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32) * 0.5
    k, v = (jax.random.normal(jax.random.key(i), (b, sk, kvh, d), jnp.float32) * 0.5
            for i in (2, 3))
    probe = jax.random.normal(jax.random.key(4), (b, s, h, d), jnp.float32)
    segs = None
    if seg:
        segs = jnp.broadcast_to((jnp.arange(s) >= s // 3).astype(jnp.int32)
                                + (jnp.arange(s) >= 2 * s // 3), (b, s))
    xla = {key: kw[key] for key in ("q_offset",) if key in kw}
    # one program each: taken bare, every operation of the composite's value and gradient is
    # compiled alone for this case's shapes
    got = jax.jit(jax.value_and_grad(lambda *a: (flash_attention(
        *a, window=window, segment_ids=segs, **kw) * probe).sum(), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(lambda *a: (xla_attention(
        *a, window=window, segment_ids=segs, **xla) * probe).sum(), (0, 1, 2)))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-3)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("heads", [9, 6], ids=["group_of_9", "group_of_6"])
def test_window_of_512_at_4096_keys_is_xla_attention(heads):
    """The cell's own walk: 4096 keys in one kv block, q blocks and
    sub-tiles of 512, a window of 512: value and all three gradients,
    at the groups of 9 and 6 query heads a key-value head."""
    _against_xla((1, 4096, heads, 1, 64), window=512)


@pytest.mark.parametrize("case", [
    dict(shape=(1, 1024, 2, 1, 64), window=200, block_q=128),                  # no sub-tile divides it
    dict(shape=(1, 1024, 4, 2, 64), window=130, block_q=128, block_k=256, seg=True),
    dict(shape=(1, 512, 2, 2, 64), window=100, block_q=128, block_k=256, q_offset=512, sk=1024),
    dict(shape=(1, 512, 2, 2, 64), window=700, block_q=128, q_offset=512, sk=1024),
    dict(shape=(2, 300, 3, 1, 64), window=64),                                  # padded rows and keys
    dict(shape=(1, 256, 2, 2, 64), window=1, block_q=128),                      # a row sees itself alone
    # PR 53, `mellum2-train-16k`'s walk made small: FOUR kv blocks (keys 1,024, blocks of 256, as
    # 16,384 keys make four of 4,096), the dq and dk/dv kernels apart, groups of 8 query heads a
    # key head, a window inside one kv block's width (192) and one that spans two and three (320)
    dict(shape=(1, 1024, 8, 1, 64), window=192, block_q=128, block_k=256),
    dict(shape=(1, 1024, 8, 1, 64), window=320, block_q=128, block_k=256),
], ids=["undivided", "segments_kv_blocks", "q_offset_kv_blocks", "q_offset_fused", "padded",
        "window_of_one", "four_kv_blocks_group_of_8_window_192",
        "four_kv_blocks_group_of_8_window_320"])
def test_windows_against_xla_attention(case, backwards_traced):
    case = dict(case)
    shape = case.pop("shape")
    fused, split = backwards_traced(lambda: _against_xla(shape, **case))
    # several kv blocks take the dq and the dk/dv kernels apart; one takes the fused kernel
    several = case.get("block_k", 1 << 30) < case.get("sk", shape[1])
    assert (fused, split) == ((0, 1) if several else (1, 0))


def test_the_range_walk_visits_15_of_the_causal_walks_36_sub_tiles():
    first, count, causal = [], [], []
    for i in range(8):
        f, n = flash._tiles_to_run(i, 0, 512, 4096, 512, causal=True, q_offset=0, window=512)
        first.append(int(f)), count.append(int(n))
        causal.append(int(flash._tiles_to_run(i, 0, 512, 4096, 512, causal=True, q_offset=0)[1]))
    assert first == [0, 0, 1, 2, 3, 4, 5, 6] and count == [1, 2, 2, 2, 2, 2, 2, 2]
    assert sum(count) == 15 and sum(causal) == 36
    # a kv block wholly outside the window is not fetched: the step names a resident one
    fetched = [int(flash._kv_block_of(6, j, 512, 512, 8, q_offset=0, window=512)) for j in range(8)]
    assert fetched == [5, 5, 5, 5, 5, 5, 6, 6]
    rows = [int(flash._q_block_of(i, 2, 512, 512, 8, q_offset=0, window=512)) for i in range(8)]
    assert rows == [2, 2, 2, 3, 3, 3, 3, 3]
    with pytest.raises(ValueError, match="causal"):
        flash_attention(*(jnp.zeros((1, 16, 1, 8)),) * 3, causal=False, window=4)
    with pytest.raises(ValueError, match="no sliding window"):
        attention_head_major(*(jnp.zeros((1, 1, 16, 8)),) * 3, impl="ring", window=4)


# sha256 of the jaxpr of the flash kernels without a window, forward and backward, as the
# parent of PR 39 (commit 53acc07, which knew no window) traces them: [1, 2048, 4 -> 2, 128]
# bf16, causal, interpret mode off, and the same with segment ids over two kv blocks
_NO_WINDOW = {
    "fused": "667f19c6f8580a5f8ae7637c95559c3091c29dcf87040a7869c449dd00313be3",
    "kv_blocks": "bff9a11cf7109d17fa1b44360dba0e885a0f3d6144c10649032dabd9c4b086b1",
}


def _kernels_jaxpr(**kw) -> str:
    shape = jax.ShapeDtypeStruct((1, 2048, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((1, 2048), jnp.int32)

    def both(q, k, v, seg):
        return jax.value_and_grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, **kw,
            **({"segment_ids": seg} if "block_k" in kw else {})).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)

    return str(jax.make_jaxpr(both)(shape, kv, kv, seg))


@pytest.mark.parametrize("name,kw", [("fused", {}), ("kv_blocks", {"block_k": 1024})])
def test_without_a_window_the_kernels_are_the_parents(name, kw):
    """`window=None` traces to the kernels the parent traced: the prefix
    walk, the mask, the index maps, unchanged (the jaxpr carries the
    kernels' bodies and no source location)."""
    assert hashlib.sha256(_kernels_jaxpr(**kw).encode()).hexdigest() == _NO_WINDOW[name]
    assert _kernels_jaxpr(window=512, **kw) != _kernels_jaxpr(**kw)

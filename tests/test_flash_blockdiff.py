"""The flash kernels under the block-diffusion mask (PR 55; ops/flash.py
`blockdiff`), in interpret mode on the CPU in float32 against the DENSE
boolean mask of the four rules (`ops/attention.py::block_diffusion_mask`
through `xla_attention`): value and all three gradients, with the
backward as ONE fused kernel (the cell's form: the L clean keys in one kv
block) and as the dq and dk/dv kernels apart (several kv blocks), at L
that no tile divides, at blocks of 4 and of larger lengths; the mask
itself pair by pair; the walk's arithmetic (`_visible_end`,
`blockdiff_tiles`) against a count over the dense mask; what the entry
refuses; and `blockdiff=None` tracing to the kernels the parent traced."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash
from ray_tpu.ops.attention import attention_head_major, block_diffusion_mask, xla_attention

# (L, beta, block_q, block_k): block_k None = the whole padded sequence = the fused backward
CASES = {
    "fused_L96_beta4": (96, 4, 32, None),            # three q blocks a copy
    "fused_L72_beta4_ragged": (72, 4, 32, None),     # the copies meet INSIDE a q block; padded
    "fused_L96_beta8": (96, 8, 32, None),
    "fused_L640_beta16": (640, 16, 64, None),        # 640 keys: one sub-tile of the whole block
    "fused_L1024_beta4_two_subtiles": (1024, 4, 128, None),   # sub-tiles of 512: the prefix walk
    "split_L96_beta4_three_kv": (96, 4, 32, 32),
    "split_L72_beta4_ragged": (72, 4, 32, 48),
    "split_L96_beta8_two_kv": (96, 8, 32, 48),
    "split_L256_beta32": (256, 32, 64, 128),
}


def _inputs(L, heads=4, kv=2, d=32):
    q, k, v, probe = (jax.random.normal(jax.random.key(i), (1, h, 2 * L, d), jnp.float32) * 0.5
                      for i, h in ((1, heads), (2, kv), (3, kv), (4, heads)))
    return q, k, v, probe


@pytest.mark.parametrize("case", list(CASES))
def test_blockdiff_kernels_are_the_dense_mask_in_value_and_gradients(case):
    L, beta, bq, bk = CASES[case]
    q, k, v, probe = _inputs(L)

    def ours(q, k, v):
        return (flash._block_diffusion(q, k, v, (L, beta), block_q=bq, block_k=bk) * probe).sum()

    def dense(q, k, v):
        o = xla_attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), blockdiff=(L, beta))
        return (jnp.swapaxes(o, 1, 2) * probe).sum()

    split_before = flash.obs.layer_counters().get("flash.bwd_split", {"count": 0})["count"]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(ours, (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(dense, (0, 1, 2)))(q, k, v)
    took_split = flash.obs.layer_counters().get("flash.bwd_split", {"count": 0})["count"] > split_before
    assert took_split == case.startswith("split")
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5, abs=1e-4)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("L,beta", [(8, 4), (12, 2), (16, 16), (6, 1)])
def test_the_mask_is_the_four_rules_pair_by_pair(L, beta):
    mask = np.asarray(block_diffusion_mask(L, beta))
    for r in range(2 * L):
        for c in range(2 * L):
            rb, cb = (r % L) // beta, (c % L) // beta
            if r < L:
                want = c < L and cb <= rb          # clean -> clean, its own block whole; never noised
            else:
                want = cb < rb if c < L else cb == rb
            assert mask[r, c] == want, (r, c)
    assert mask.sum() == L * (L + beta)            # the visible pairs a head
    # not causal inside a clean block, and bidirectional inside a noised one
    if beta > 1:
        assert mask[0, beta - 1] and mask[L, L + beta - 1] and mask[L + beta - 1, L]


@pytest.mark.parametrize("L,beta,bq", [(8192, 4, 512), (1024, 16, 128), (96, 4, 32), (72, 4, 32),
                                      (640, 32, 64)])
def test_the_walk_visits_the_tiles_that_hold_a_visible_clean_key_and_no_other(L, beta, bq):
    """`_visible_end` (what every kernel's trip count, the dq kernel's skip
    and the kv fetch's clamp are made of) against the dense mask's own
    clean columns, q block by q block; and `blockdiff_tiles`' counts."""
    mask = np.asarray(block_diffusion_mask(L, beta))[:, :L]
    tk = flash._sub_k(flash.default_block_k(L, 128, 2))
    rows = -(-2 * L // bq) * bq
    padded = np.zeros((rows, L), bool)
    padded[:2 * L] = mask
    visited = 0
    for i in range(rows // bq):
        seen = np.flatnonzero(padded[i * bq:(i + 1) * bq].any(0))
        end = int(flash._visible_end(i, bq, (L, beta)))
        # the walk may run past the last visible key of PADDED rows only
        assert end >= (seen.max() + 1 if seen.size else 0)
        if (i + 1) * bq <= 2 * L:
            assert end == (seen.max() + 1 if seen.size else 0), i
        visited += -(-end // tk)
    tiles = flash.blockdiff_tiles(L, beta, bq)
    assert tiles["visited"] == visited and tiles["visible_pairs"] == L * (L + beta)
    assert tiles["causal"] == sum(min(-(-(i + 1) * bq // tk), -(-2 * L // tk))
                                  for i in range(rows // bq))


def test_the_cells_walk_is_272_of_a_causal_walks_528_tiles():
    tiles = flash.blockdiff_tiles(8192, 4)
    assert tiles == {"visited": 272, "causal": 528, "visible_pairs": 67_141_632}
    # ONE walk over 2L x 2L could not go under (n^2 + 2n) / (2n^2 + n), n = 16: 54.5%
    assert 100 * 272 / 528 == pytest.approx(51.5, abs=0.05) and 100 * 288 / 528 < 54.6
    assert flash.default_block_k(8192, 128, 2) == 8192        # one kv block: the fused backward


@pytest.mark.parametrize("kw,names", [
    (dict(blockdiff=(64, 4), window=8), "stands alone"),
    (dict(blockdiff=(64, 4), selection=jnp.zeros((1, 128, 128), jnp.int32)), "stands alone"),
    (dict(blockdiff=(64, 4), segment_ids=jnp.zeros((1, 128), jnp.int32)), "stands alone"),
    (dict(blockdiff=(60, 4)), "2L rows and 2L keys"),
    (dict(blockdiff=(64, 5)), "whole blocks"),
], ids=["window", "selection", "segments", "rows", "blocks"])
def test_the_entry_refuses_what_the_mask_does_not_stand_with(kw, names):
    q, k, v, _ = _inputs(64)
    with pytest.raises(ValueError, match=names):
        flash.flash_attention_head_major(q, k, v, **kw)


def test_both_paths_of_the_dispatch_take_the_mask():
    """`attention_head_major`: the flash kernels and the XLA composite agree
    under `blockdiff`, and `impl` ring has no such mask."""
    L, beta = 48, 4
    q, k, v, _ = _inputs(L)
    with jax.default_matmul_precision("highest"):
        a = attention_head_major(q, k, v, impl="flash", blockdiff=(L, beta))
        b = attention_head_major(q, k, v, impl="xla", blockdiff=(L, beta))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="block-diffusion mask"):
        attention_head_major(q, k, v, impl="ring", blockdiff=(L, beta))


def test_a_noised_row_of_block_0_sees_its_own_block_alone():
    """Rows L .. L + beta - 1 see no clean key: the kernels hand them a
    log-sum-exp that weighs nothing, and the merge leaves the softmax over
    the block's own noised keys."""
    L, beta = 32, 4
    q, k, v, _ = _inputs(L, heads=2, kv=2, d=16)
    with jax.default_matmul_precision("highest"):
        o = flash._block_diffusion(q, k, v, (L, beta))
    s = jnp.einsum("hid,hjd->hij", q[0, :, L:L + beta], k[0, :, L:L + beta]) / 4.0
    want = jnp.einsum("hij,hjd->hid", jax.nn.softmax(s, -1), v[0, :, L:L + beta])
    np.testing.assert_allclose(np.asarray(o[0, :, L:L + beta]), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert np.isfinite(np.asarray(o)).all()


def test_without_the_mask_the_kernels_trace_to_what_they_were():
    """`blockdiff=None` adds a static argument and no operation: the causal
    call's jaxpr names the same primitives in the same order as a call that
    never heard of the mask (the keyword left out)."""
    q, k, v, _ = _inputs(64)
    named = jax.make_jaxpr(lambda *a: flash.flash_attention_head_major(*a, blockdiff=None))(q, k, v)
    plain = jax.make_jaxpr(lambda *a: flash.flash_attention_head_major(*a))(q, k, v)
    digest = lambda j: hashlib.sha256(str(j).encode()).hexdigest()  # noqa: E731
    assert digest(named) == digest(plain)

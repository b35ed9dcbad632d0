"""The flash kernels under the block-diffusion mask (PR 55; ops/flash.py
`blockdiff`), in interpret mode on the CPU in float32 against the DENSE
boolean mask of the four rules (`ops/attention.py::block_diffusion_mask`
through `xla_attention`): value and all three gradients, with the
backward as ONE fused kernel (the cell's form: the L clean keys in one kv
block) and as the dq and dk/dv kernels apart (several kv blocks), at L
that no tile divides, at blocks of 4 and of larger lengths; the mask
itself pair by pair; the walk's arithmetic (`_visible_end`,
`blockdiff_tiles`) against a count over the dense mask; what the entry
refuses; and `blockdiff=None` tracing to the kernels the parent traced.
The noised rows' own blocks are merged OUTSIDE the kernels by one of two
texts a shape (PR 67; `flash._blockdiff_merge`): blocks that are no whole
sublane tiles (beta 1, 2, 4: the cell's) on the arrays as they lie
(`_own_rows`, `_merge_own_blocks`, a backward written out), blocks of 8, 16
and 32 rows on the `[.., beta, D]` view; the cases name which they take."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash
from ray_tpu.ops.attention import attention_head_major, block_diffusion_mask, xla_attention

# (L, beta, block_q, block_k[, heads, kv heads, head width]): block_k None = the whole padded
# sequence = the fused backward; the heads default to `_inputs`' 4 / 2 at a width of 32
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
    # PR 67, the shapes the merge's text is chosen by. TILED (`beta % 8`): the cell's head layout,
    # GQA 32 / 4 at a width of 128, 128 tiles of 8 rows a copy; L that no tile of 128 rows
    # divides (72, 96) and that no tile of 8 does (20, 12: a block ends where a tile does not,
    # and every second block of 4 STARTS in the middle of one, so both edges of a tile fall
    # inside and between blocks); blocks of 1 (a row sees its own key alone) and 2
    "tiled_cell_heads_L1024_beta4": (1024, 4, 512, None, 32, 4, 128),
    "tiled_L272_beta4_four_tiles_of_68": (272, 4, 64, None),      # `_own_rows_tile`: no 128
    "tiled_L72_beta4_group4": (72, 4, 32, None, 8, 2, 128),
    "tiled_L96_beta4_one_kv_head": (96, 4, 32, None, 4, 1, 64),
    "tiled_L20_beta4_half_tiles": (20, 4, 16, None),
    "tiled_L12_beta2_half_tiles": (12, 2, 16, None),
    "tiled_L64_beta1": (64, 1, 32, None),
    "tiled_L96_beta2": (96, 2, 32, None),
    "tiled_L96_beta2_split": (96, 2, 32, 48),
    # the VIEW (`beta` whole tiles of 8 rows), at a group of 4
    "view_L128_beta8_group4": (128, 8, 64, None, 8, 2, 32),
    "view_L128_beta16_group4": (128, 16, 64, None, 8, 2, 32),
    "view_L128_beta32_group4": (128, 32, 64, None, 8, 2, 32),
}
_TILED = ("flash.blockdiff_merge_tiled", "flash.blockdiff_merge_view")


def _counted(*names):
    return tuple(flash.obs.layer_counters().get(n, {"count": 0})["count"] for n in names)


def _inputs(L, heads=4, kv=2, d=32):
    q, k, v, probe = (jax.random.normal(jax.random.key(i), (1, h, 2 * L, d), jnp.float32) * 0.5
                      for i, h in ((1, heads), (2, kv), (3, kv), (4, heads)))
    return q, k, v, probe


@pytest.mark.parametrize("case", list(CASES))
def test_blockdiff_kernels_are_the_dense_mask_in_value_and_gradients(case):
    L, beta, bq, bk, *heads = CASES[case]
    q, k, v, probe = _inputs(L, *heads)

    def ours(q, k, v):
        return (flash._block_diffusion(q, k, v, (L, beta), block_q=bq, block_k=bk) * probe).sum()

    def dense(q, k, v):
        o = xla_attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), blockdiff=(L, beta))
        return (jnp.swapaxes(o, 1, 2) * probe).sum()

    split_before, merged_before = _counted("flash.bwd_split"), _counted(*_TILED)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(ours, (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(dense, (0, 1, 2)))(q, k, v)
    took_split = _counted("flash.bwd_split") > split_before
    assert took_split == ("split" in case)
    # ONE text of the merge a shape, chosen by `beta` against the tile of 8 rows, and counted
    tiled, view = (now - was for now, was in zip(_counted(*_TILED), merged_before))
    assert (tiled, view) == ((1, 0) if beta % 8 else (0, 1))
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


@pytest.mark.parametrize("L,beta", [(16, 4), (20, 4), (12, 2), (7, 1), (24, 3), (136, 4), (8192, 4)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_own_rows_hands_each_row_its_blocks_rows_exactly(L, beta, dtype):
    """`_own_rows`: array j holds, at row t, row beta (t // beta) + j of its
    input, bit for bit, in float32: a 0/1 matrix over tiles of T rows picks
    them (`_own_rows_tile`: 128 at the cell's L, the whole of a short
    sequence, 68 where 128 does not divide L), and a product of 0/1 with one
    value and zeros added is that value."""
    x = jax.random.normal(jax.random.key(5), (2, 3, L, 8), jnp.float32).astype(dtype)
    rows = flash._own_rows(x, beta)
    assert len(rows) == beta
    t = np.arange(L)
    for j, got in enumerate(rows):
        assert got.dtype == jnp.float32 and got.shape == x.shape
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(x.astype(jnp.float32))[:, :, beta * (t // beta) + j])


@pytest.mark.parametrize("L,beta,T", [(8192, 4, 128), (1024, 4, 128), (72, 4, 72), (96, 2, 96),
                                      (20, 4, 20), (64, 1, 64), (136, 4, 68), (8200, 4, 100),
                                      (131, 1, 1)])
def test_own_rows_tile_is_whole_blocks_that_divide_the_sequence(L, beta, T):
    """A rule from the shapes, as `default_block_k` is one: the most rows
    that are whole blocks, divide L and are at most the MXU's 128."""
    assert flash._own_rows_tile(L, beta) == T
    assert T % beta == 0 and L % T == 0 and T <= 128


def _merge_alone_lowered(L, beta, dtype=jnp.bfloat16, heads=32, kv=4, d=128):
    """The merge ALONE (no kernel), value and gradients, lowered at the cell's shapes."""
    shapes = [((1, heads, 2 * L, d), dtype), ((1, kv, 2 * L, d), dtype), ((1, kv, 2 * L, d), dtype),
              ((1, heads, 2 * L, d), dtype), ((1, heads, 2 * L, 1), jnp.float32)]

    def loss(q, k, v, o1, lse1):
        return flash._blockdiff_merge(q, k, v, o1, lse1, (L, beta)).astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).lower(*args).as_text()


def _second_minor_dims(text):
    """The second-minor dimension of every array type of rank >= 2 in a lowered text."""
    return {int(dims.split("x")[-2]) for dims in re.findall(r"tensor<((?:\d+x)+\d+)x[a-z]\w*>", text)}


def test_the_cells_merge_holds_no_array_whose_rows_are_half_a_tile():
    """At the cell's shape (GQA 32 / 4, heads of 128, L = 8,192, blocks of
    4, bfloat16) the merge's lowered text, forward and backward, holds NO
    array whose second-minor dimension is 4: the `[.., 2048, 4, 128]` views
    the parent computed on (each a padded layout and a copy into and out of
    it) are gone by the text, not by a timing. The text the parent ran still
    stands for blocks of whole tiles, and there its view is in the text."""
    text = _merge_alone_lowered(8192, 4)
    assert 4 not in _second_minor_dims(text)
    assert {8192, 16384} <= _second_minor_dims(text)       # the arrays as they lie
    assert 8 in _second_minor_dims(_merge_alone_lowered(64, 8, heads=4, kv=2, d=32))


def _dots(text):
    """(the operand and result types, whether HIGHEST) of every dot_general of a lowered text."""
    return [(re.findall(r"tensor<[^>]*x(bf16|f32)>", line.split(" : ", 1)[1]), "HIGHEST" in line)
            for line in text.splitlines() if "dot_general" in line and " : " in line]


def test_the_tiled_merges_only_products_pick_rows_and_take_one_pass_where_that_is_exact():
    """The tiled text's scores and weighted values are multiplies and
    reductions in float32 on the VPU; its ONLY products on the MXU pick the
    own rows by a 0/1 matrix (`_own_rows`): forward, `beta` arrays of k and
    of v; backward, their transposes. Where k and v arrive bfloat16 (the cell) the
    forward's operands are both bfloat16 and the float32 sum adds zeros to
    one value: ONE pass is exact, and allowed. A float32 operand is no
    bfloat16 value: float32 k and v (the tests') and the cotangents on the
    way back run at `HIGHEST`. Each carries a batch dimension, so the
    layers' remat policy "dots" saves none of them."""
    text = _merge_alone_lowered(256, 4, jnp.bfloat16, heads=8, kv=2)
    dots = _dots(text)
    # beta = 4 arrays each of k and of v forward, and as many transposes back
    assert sorted(dots) == sorted([(["bf16", "bf16", "f32"], False)] * 8
                                  + [(["f32", "f32", "f32"], True)] * 8)
    assert _dots(_merge_alone_lowered(256, 4, jnp.float32, heads=8, kv=2)) \
        == [(["f32", "f32", "f32"], True)] * 16
    batched = [line for line in text.splitlines() if "dot_general" in line]
    assert all("batching_dims = [0, 1, 2] x [0, 1, 2]" in line for line in batched), batched[0]


def test_the_view_merges_products_run_at_highest():
    """The view's two einsums and their four transposes multiply p, dp and
    ds, which are no bfloat16 values: each is at `HIGHEST`."""
    dots = _dots(_merge_alone_lowered(64, 8, jnp.bfloat16, heads=4, kv=2, d=32))
    assert len(dots) == 6 and all(highest for _, highest in dots)

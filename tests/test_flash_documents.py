"""The flash kernels' walk under PACKED DOCUMENTS (PR 69; interpreter mode
on the CPU): where a call carries segment ids and its kv sequence is ONE
block, a q block's walk starts at the first sub-tile that may hold a key of
its rows' documents (`flash._doc_first_tiles`, a table made in XLA and read
from SMEM) and ends where it ended, every sub-tile that runs masked as it
was. Value and all three gradients against `xla_attention` at heads of 64,
2,048 keys = four sub-tiles of 512 and eight q blocks of 256 rows; the
table and `segment_tiles` against a brute-force count of the sub-tiles that
hold a visible pair. tests/test_flash.py's own cases under segment ids run
the same walk at other shapes; tests/test_tpu_compile.py compiles it for
the described v5e at the cell's shape."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import obs
from ray_tpu.ops import attention as _attention
from ray_tpu.ops import flash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, H, KVH, D, BQ, TK = 2048, 2, 1, 64, 256, 512


def _laid_end_to_end(lengths) -> np.ndarray:
    """Documents of `lengths` laid end to end from position 0, cut at S: ids [S]."""
    return np.searchsorted(np.cumsum(lengths), np.arange(S), side="right").astype(np.int32)


# name -> ids [S]: where the boundaries lie against sub-tiles of 512 keys and q blocks of 256 rows
DOCS = {
    "inside_a_sub_tile": _laid_end_to_end([700, S]),
    "on_a_sub_tiles_edge": _laid_end_to_end([1024, S]),
    "on_a_q_blocks_edge": _laid_end_to_end([768, S]),       # 3 x 256, inside the second sub-tile
    "one_document": np.zeros(S, np.int32),                   # the range is the causal prefix
    "twenty_short": _laid_end_to_end(np.random.default_rng(0).integers(40, 165, 20).tolist() + [S]),
    # ids no packer emits: the range is conservative, the result still the reference's
    "decreasing": (5 - np.arange(S) // 400).astype(np.int32),
    "repeating": (np.arange(S) // 300 % 2).astype(np.int32),  # a later document sees an earlier one's keys
    "shuffled": np.random.default_rng(1).integers(0, 4, S).astype(np.int32),
}
SORTED = [name for name, ids in DOCS.items() if (np.diff(ids) >= 0).all()]


@functools.cache
def _inputs():
    kq, kk, kv, kp = jax.random.split(jax.random.key(69), 4)
    return (jax.random.normal(kq, (1, S, H, D)) * 0.5, jax.random.normal(kk, (1, S, KVH, D)),
            jax.random.normal(kv, (1, S, KVH, D)), jax.random.normal(kp, (1, S, H, D)))


# ONE function object a side: the ids are an argument, so the cases of one (window, block_k)
# share a program
@functools.partial(jax.jit, static_argnames=("window",))
def _xla_value_and_grads(q, k, v, probe, ids, window):
    return jax.value_and_grad(lambda *a: (_attention.xla_attention(
        *a, causal=True, window=window, segment_ids=ids) * probe).sum(), (0, 1, 2))(q, k, v)


@functools.partial(jax.jit, static_argnames=("window", "block_k"))
def _flash_value_and_grads(q, k, v, probe, ids, window, block_k):
    return jax.value_and_grad(lambda *a: (flash.flash_attention(
        *a, causal=True, window=window, segment_ids=ids, block_q=BQ, block_k=block_k) * probe).sum(),
        (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("docs,window,block_k", [
    *[(name, None, None) for name in DOCS],
    # under a window the walk starts at the LATER of the two firsts: the documents' where the
    # window reaches back over a boundary, the window's deep inside a long document
    ("on_a_sub_tiles_edge", 700, None),
    ("twenty_short", 700, None),
    # several kv blocks (the dq and dk/dv kernels apart): the walk is positional there
    ("twenty_short", None, 512),
    ("decreasing", None, 1024),
    ("inside_a_sub_tile", 700, 1024),
], ids=lambda x: str(x))
def test_value_and_gradients_under_documents_are_the_composites(docs, window, block_k):
    q, k, v, probe = _inputs()
    ids = jnp.asarray(DOCS[docs])[None]
    want = _xla_value_and_grads(q, k, v, probe, ids, window)
    got = _flash_value_and_grads(q, k, v, probe, ids, window, block_k)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-3)
    for g, w, name in zip(got[1], want[1], "qkv"):
        assert np.asarray(w).any()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name}")


def _counted(name: str) -> int:
    return obs.layer_counters().get(name, {"count": 0})["count"]


@pytest.mark.parametrize("segments,block_k,want", [
    (True, None, 2), (True, 1024, 0), (False, None, 0)], ids=["one_kv_block", "two_kv_blocks", "no_ids"])
def test_a_call_built_with_the_documents_range_is_counted_while_tracing(segments, block_k, want):
    """`flash.doc_walk`: one layer span a kernel call built with the table,
    the forward's and the fused backward's, WHILE TRACING (the compile
    tests read it through `Step.engaged`); over several kv blocks and
    without ids nothing is built with it and nothing counted."""
    q, k, v, _ = _inputs()
    ids = jnp.asarray(DOCS["twenty_short"])[None] if segments else None
    grad = jax.jit(jax.grad(lambda q, k, v: flash.flash_attention(
        q, k, v, segment_ids=ids, block_q=BQ, block_k=block_k).sum(), (0, 1, 2)))
    before = _counted("flash.doc_walk")
    compiled = grad.lower(q, k, v).compile()
    assert _counted("flash.doc_walk") - before == want
    jax.block_until_ready(compiled(q, k, v))
    assert _counted("flash.doc_walk") - before == want


def _dense(q, k, v, qseg, kseg, q_offset, causal):
    """Attention under the two sides' ids by the definition: float32, every pair."""
    G = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, G, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = (qseg[:, :, None] == kseg[:, None, :])[:, None]
    if causal:
        mask = mask & (jnp.arange(q.shape[1])[:, None] + q_offset >= jnp.arange(k.shape[1])[None, :])
    s = jnp.where(mask, s, -1e30)
    p = jnp.where(mask, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)  # a row that sees nothing: zeros
    return jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30), v)


@pytest.mark.parametrize("rows,q_offset,causal,q_ids,kv_ids", [
    # a shard's rows against the whole sequence's keys, the diagonal at the shard's offset
    (slice(1536, 2048), 1536, True, "twenty_short", "twenty_short"),
    (slice(600, 1112), 600, True, "inside_a_sub_tile", "inside_a_sub_tile"),  # off every grid
    # ring attention's block from BEHIND: no diagonal, the rows' ids a later stretch of the
    # numbering than most of the keys'; and two sides that share ids by chance alone
    (slice(1536, 2048), 0, False, "twenty_short", "twenty_short"),
    (slice(0, 512), 0, False, "twenty_short", "decreasing"),
], ids=["shard_causal", "offset_off_grid", "block_from_behind", "unlike_ids_no_diagonal"])
def test_kv_segment_ids_with_a_q_offset_keep_their_results(rows, q_offset, causal, q_ids, kv_ids):
    """The table reads the two sides' ids and never a position, so q may
    stand at any offset against the keys (ring attention's rotating kv
    shards: `kv_segment_ids` beside the rows' own `segment_ids`): value and
    gradients are the definition's."""
    q, k, v, probe = _inputs()
    q, probe = q[:, rows], probe[:, rows]
    kseg = jnp.asarray(DOCS[kv_ids])[None]
    qseg = jnp.asarray(DOCS[q_ids])[None, rows]

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) * probe).sum()

    got = jax.jit(jax.value_and_grad(loss(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, segment_ids=qseg, kv_segment_ids=kseg,
        block_q=BQ)), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(loss(lambda q, k, v: _dense(
        q, k, v, qseg, kseg, q_offset, causal)), (0, 1, 2)))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-3)
    for g, w, name in zip(got[1], want[1], "qkv"):
        assert np.asarray(w).any()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name}")


def _live(qseg, kseg, bq, tk, q_offset=None) -> np.ndarray:
    """bool [nq, nt]: the (q block, sub-tile) pairs that hold a visible pair
    under the ids (and the diagonal at `q_offset`, None: none), by brute force."""
    see = qseg[:, None] == kseg[None, :]
    if q_offset is not None:
        see &= np.arange(len(qseg))[:, None] + q_offset >= np.arange(len(kseg))[None, :]
    return see.reshape(len(qseg) // bq, bq, len(kseg) // tk, tk).any(axis=(1, 3))


@pytest.mark.parametrize("docs", list(DOCS))
def test_segment_tiles_counts_the_sub_tiles_that_hold_a_visible_pair(docs):
    """`segment_tiles` counts as the kernels count: for ids that do not
    decrease it is the brute-force count of the (q block, sub-tile) pairs
    with a visible pair (every sub-tile between a q block's first document
    and the diagonal holds one), for any other ids never under it; one
    document is the causal walk; and the table itself lies at or before
    every live sub-tile."""
    ids = DOCS[docs]
    live = _live(ids, ids, BQ, TK, q_offset=0)
    tiles = flash.segment_tiles(ids[None], block_q=BQ, head_dim=D, itemsize=4)
    causal = int(_live(np.zeros_like(ids), np.zeros_like(ids), BQ, TK, q_offset=0).sum())
    assert tiles["causal"] == causal == 20 and tiles["visited"] <= causal
    if docs in SORTED:
        assert tiles["visited"] == int(live.sum())
    else:
        assert tiles["visited"] >= int(live.sum())
    if docs == "one_document":
        assert tiles["visited"] == causal
    first = np.asarray(flash._doc_first_tiles(ids[None], ids[None], BQ, TK, S, S))
    assert all(first[i] <= np.flatnonzero(live[i])[0] for i in range(S // BQ))
    # two rows of a batch are counted apart and summed
    both = flash.segment_tiles(np.stack([ids, DOCS["one_document"]]), block_q=BQ, head_dim=D, itemsize=4)
    assert both == {"visited": tiles["visited"] + causal, "causal": 2 * causal}


@pytest.mark.parametrize("q_ids,k_ids,sq_valid,sk_valid", [
    ("twenty_short", "twenty_short", S, S),
    ("twenty_short", "twenty_short", S - 200, S - 300),   # padding is in neither side's range
    ("shuffled", "repeating", S, S),
    ("decreasing", "twenty_short", S, S),
    ("one_document", "decreasing", S, S - 512),            # a sub-tile of padding alone
], ids=lambda x: str(x))
def test_the_table_is_at_or_before_every_live_sub_tile_whatever_the_two_sides_ids(
        q_ids, k_ids, sq_valid, sk_valid):
    """`_doc_first_tiles` on two sides that need share nothing: no sub-tile
    before a q block's entry holds a key that a row of the block sees
    (padding, which the kernels' mask cuts, counted on neither side); a q
    block that meets no key at all reads one past the last sub-tile; and
    sorted ids shared by both sides give the first live sub-tile exactly."""
    qseg = np.where(np.arange(S) < sq_valid, DOCS[q_ids], -1).astype(np.int32)
    kseg = np.where(np.arange(S) < sk_valid, DOCS[k_ids], -2).astype(np.int32)
    first = np.asarray(flash._doc_first_tiles(qseg[None], kseg[None], BQ, TK, sq_valid, sk_valid))
    live = _live(qseg, kseg, BQ, TK)
    assert first.shape == (S // BQ,) and first.dtype == np.int32
    for i, row in enumerate(live):
        assert first[i] <= (np.flatnonzero(row)[0] if row.any() else S // TK), i
        if q_ids == k_ids and q_ids in SORTED and row.any():
            assert first[i] == np.flatnonzero(row)[0], i


def test_the_generators_documents_visit_under_half_of_the_causal_walk():
    """`packed_zipf_docs` as `granite-h-micro-train-packed` draws them
    (log-normal lengths, median 600, sigma 1.2, clipped to 16..8,192), 256
    sequences of 8,192 at q blocks and sub-tiles of 512: the walk visits
    0.40-0.50 of the causal walk's 136 sub-tiles a head and sequence
    (ISSUE 69 reckoned 0.448 over 400), every sequence within it."""
    from chipbench.generators import packed_zipf_docs

    with open(os.path.join(REPO, "chipbench", "traffic", "packed_zipf_docs.json")) as f:
        params = json.load(f)
    lengths = packed_zipf_docs.lengths(jax.random.key(69), 256, params)
    ids = np.asarray(packed_zipf_docs.documents_of(lengths, params["seq_len"]))
    tiles = flash.segment_tiles(ids, head_dim=64)
    assert tiles["causal"] == 256 * 136
    assert 0.40 < tiles["visited"] / tiles["causal"] < 0.50
    each = [flash.segment_tiles(row[None], head_dim=64)["visited"] for row in ids[:32]]
    assert 16 <= min(each) and max(each) <= 136

"""ops/ssd.py under PACKED DOCUMENTS (`segment_ids`): the chunked scan's two
Pallas kernels (under the interpreter here) against the
position-by-position recurrence whose state is taken as zero where the
document changes (chipbench/reference/granite_hybrid_decoder.py's, which
imports nothing of the program): the output and EVERY gradient, at ONE
group and at 8, with boundaries on a chunk's edge, inside a chunk, at
position 0 and in consecutive positions, at lengths that are and are not
whole chunks; ids that recur name two documents; a sequence of one
document is the scan without ids (to a rounding: its factors are ones); WITHOUT ids the traced
program is the parent's, text for text; the VMEM a group of 64 heads asks
for and that a group of 8 asks for none."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import granite_hybrid_decoder as reference
from ray_tpu.ops import ssd
from ray_tpu.ops.ssd import ssd_scan, ssd_scan_lanes

P, N, CHUNK = 16, 32, 16
# where a new document starts, by case (T = 72: four chunks of 16 and 8 positions more)
BOUNDARIES = {
    "on_a_chunks_edge": (16, 48),
    "inside_a_chunk": (5, 27, 40),
    "at_position_0": (1, 2),                 # position 0 is a document of its own, so is 1
    "consecutive_positions": (30, 31, 32, 33, 34),
    "every_kind_at_once": (1, 16, 17, 29, 32, 63, 64, 71),
}
T = 72


def inputs(groups, seed=0, b=2, T=T):
    # ONE group of 8 heads (2 x 8 vectors fill a chunk of 16, as the cell's 2 x 64 fill 128);
    # 8 groups of 2 heads
    heads = 8 if groups == 1 else 16
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (b, heads, T, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, heads, T)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (b, groups, T, N))
    Cm = jax.random.normal(ks[4], (b, groups, T, N))
    D = jax.random.normal(ks[5], (heads,))
    return x, dt, A, Bm, Cm, D


def ids_of(starts, b=2, T=T):
    """[b, T] ids that change at `starts` and RECUR (7, 3, 7, ...): a document is a run."""
    doc = np.zeros(T, np.int64)
    for s in starts:
        doc[s:] += 1
    ids = np.where(doc % 2 == 0, 7, 3)
    # the second sequence's boundaries a position later, so that the batch's rows differ
    return jnp.asarray(np.stack([ids, np.roll(ids, 1)][:b]), jnp.int32)


@jax.jit
def by_position(x, dt, A, Bm, Cm, D, ids):
    """The reference's recurrence on the program's layout, a sequence at a time; its
    documents are the runs of equal ids."""
    with jax.default_matmul_precision("highest"):
        rows = lambda a: jnp.moveaxis(a, 0, 1)  # noqa: E731
        runs = jnp.cumsum(jnp.pad(ids[:, 1:] != ids[:, :-1], ((0, 0), (1, 0))), axis=1)
        return jnp.stack([rows(reference.recurrence(rows(x[b]), rows(dt[b]), A, rows(Bm[b]),
                                                    rows(Cm[b]), D, runs[b]))
                          for b in range(x.shape[0])])


def close(got, want, tol=3e-5):
    err = float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))
    assert err < tol, err


@pytest.mark.parametrize("groups", [1, 8], ids=["one_group", "eight_groups"])
@pytest.mark.parametrize("case", sorted(BOUNDARIES))
def test_the_scan_under_documents_is_the_recurrence_with_its_resets(case, groups):
    """Forward and all six gradients (x, dt, A, B, C, D: the five cotangents
    the benchmark's runner reads are among them)."""
    args, ids = inputs(groups, seed=len(case)), ids_of(BOUNDARIES[case])
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    got_y, pull = jax.vjp(jax.jit(lambda *a: ssd_scan(*a, chunk=CHUNK, segment_ids=ids)), *args)
    want_y, pull_ref = jax.vjp(lambda *a: by_position(*a, ids), *args)
    close(got_y, want_y)
    for g, r, name in zip(pull(w), pull_ref(w), ("dx", "ddt", "dA", "dB", "dC", "dD")):
        assert g.shape == r.shape, name
        close(g, r, 6e-5)
    # and it is not the scan without them
    assert float(jnp.linalg.norm(ssd_scan(*args, chunk=CHUNK) - want_y)
                 / jnp.linalg.norm(want_y)) > 1e-2


@pytest.mark.parametrize("T", [64, 50, 7])
def test_lengths_that_are_and_are_not_whole_chunks(T):
    args = inputs(1, seed=T, T=T)
    ids = ids_of((3, 5, 17, 33, 48), T=T)
    close(ssd_scan(*args, chunk=CHUNK, segment_ids=ids), by_position(*args, ids))


def test_the_cells_block_one_group_of_heads_of_64_at_a_state_of_128():
    """Heads of 64 at a state of 128 in chunks of 128, two heads a lane block, ONE
    group (the cell's, at 4 heads for 64), two chunks less a few positions."""
    T = 250
    ks = jax.random.split(jax.random.key(4), 6)
    args = (jax.random.normal(ks[0], (1, 4, T, 64)),
            jax.nn.softplus(jax.random.normal(ks[1], (1, 4, T)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (4,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (1, 1, T, 128)), jax.random.normal(ks[4], (1, 1, T, 128)),
            jax.random.normal(ks[5], (4,)))
    ids = ids_of((5, 128, 129, 190), b=1, T=T)
    w = jax.random.normal(jax.random.key(5), (1, 4, T, 64))
    got_y, pull = jax.vjp(jax.jit(lambda *a: ssd_scan(*a, segment_ids=ids)), *args)
    want_y, pull_ref = jax.vjp(lambda *a: by_position(*a, ids), *args)
    close(got_y, want_y)
    for g, r in zip(pull(w), pull_ref(w)):
        close(g, r, 6e-5)


def test_one_document_is_the_scan_without_ids_and_under_checkpoint():
    """Every factor the ids bring is 1.0 then; the compiled programs differ, so a last digit may."""
    args = inputs(1, seed=3)
    ids = jnp.full((2, T), 4, jnp.int32)
    f = lambda ids: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *a: jnp.sum(jax.checkpoint(lambda *a: ssd_scan(*a, chunk=CHUNK, segment_ids=ids))(*a)
                           ** 2), argnums=range(6)))(*args)
    (y, grads), (y0, grads0) = f(ids), f(None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=2e-6)
    for g, g0 in zip(grads, grads0):
        close(g, g0, 1e-6)


def test_the_kernels_rows_of_documents():
    """`_documents`: a position's document counted along the sequence, the
    document before its chunk (-1 before the first) and its chunk's last; the
    padding continues the last document."""
    ids = jnp.asarray([[5, 5, 9, 9, 9, 5, 5, 2, 2, 2]], jnp.int32)
    doc = np.asarray(ssd._documents(ids, chunk=4, short=2))
    assert doc.shape == (1, 8, 12) and doc.dtype == np.float32 and not doc[0, 3:].any()
    np.testing.assert_array_equal(doc[0, 0], [0, 0, 1, 1, 1, 2, 2, 3, 3, 3, 3, 3])
    np.testing.assert_array_equal(doc[0, 1], [-1] * 4 + [1] * 4 + [3] * 4)
    np.testing.assert_array_equal(doc[0, 2], [1] * 4 + [3] * 4 + [3] * 4)


# sha256 of the jaxpr of `ssd_scan_lanes` WITHOUT ids, forward and backward, at the block of
# `twotower-train-8k` (64 heads of 64 in 8 groups, a state of 128, two chunks), as the parent
# of PR 66 traced it (commit a9a0c77)
_NO_DOCUMENTS = "2363dbacb1e577a60367dbdee55eb74aa0ec5b40843cfc4e183f3d3cbec1dfcb"


def _jaxpr(with_ids: bool) -> str:
    S = jax.ShapeDtypeStruct
    xbc, dt, heads = S((1, 48, 256, 128), jnp.float32), S((1, 64, 256), jnp.float32), S((64,), jnp.float32)
    ids = jnp.zeros((1, 256), jnp.int32) if with_ids else None

    def f(xbc, dt, A, D):
        return ssd_scan_lanes(xbc, dt, A, D, head_dim=64, chunk=128, segment_ids=ids).sum()

    return str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2, 3)))(xbc, dt, heads, heads))


def test_without_ids_the_traced_kernels_are_the_parents():
    """`segment_ids=None` traces to what the parent traced, text for text: no
    operand, no ref, no mask, no VMEM asked for (the jaxpr carries the
    kernels' bodies and their compiler parameters, and no source location)."""
    assert hashlib.sha256(_jaxpr(False).encode()).hexdigest() == _NO_DOCUMENTS
    assert _jaxpr(True) != _jaxpr(False)


def test_the_vmem_is_stated_by_the_heads_of_a_group():
    """8 heads a group (twotower): nothing asked, the parameters the parent's
    own object. 64 heads a group (granite-4.0-h-micro): 31 MiB forward and 41
    backward, over the compiler's own count of the backward (36.8 MiB), far
    under the chip's 128."""
    assert ssd._compiler_params(4, 8, 64, 128, 128, 4, False) is ssd._SEQUENTIAL
    assert ssd._compiler_params(4, 8, 64, 128, 128, 4, True) is ssd._SEQUENTIAL
    fwd = ssd._compiler_params(32, 64, 64, 128, 128, 4, False)
    bwd = ssd._compiler_params(32, 64, 64, 128, 128, 4, True)
    assert fwd.vmem_limit_bytes == 31 << 20 and bwd.vmem_limit_bytes == 41 << 20
    assert bwd.vmem_limit_bytes > 36.8 * 2 ** 20
    assert fwd.dimension_semantics == bwd.dimension_semantics == ("parallel", "arbitrary")

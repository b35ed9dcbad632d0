"""ops/gdn_conv.py on the CPU: the kernels under the Pallas interpreter
against the jax.numpy chain they replaced in models/olmo_hybrid.py (kept
HERE as the reference: `causal_conv`, `chain`), forward and every
gradient (the input's, the taps'), at the 7B's head sizes with and
without the norm, at sequences of whole blocks, of a last block that is
not full and of less than one; that nothing is read ahead of t across a
tile's and a block's boundary; that a block's first rows read the rows
before it and the first block zeros, forward, and a block's last rows the
gradients after it, backward; that it is kernels all the way and that a
call site counts itself."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import obs
from ray_tpu.ops import gdn_conv as gc

F32 = jnp.float32
H, K = 2, 4


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """x [B, heads, S, d] float32, taps [K, heads x d] -> sum_j taps[j] x
    x[t - j], zeros before the sequence: K shifted multiply-adds, nothing
    ahead of t."""
    B, H, S, d = x.shape
    taps = taps.astype(F32).reshape(-1, H, 1, d)
    y = x * taps[0]
    for j in range(1, taps.shape[0]):
        y = y + jnp.pad(x, ((0, 0), (0, 0), (j, 0), (0, 0)))[:, :, :S] * taps[j]
    return y


@functools.partial(jax.jit, static_argnums=2)
def chain(x: jax.Array, taps: jax.Array, scale=None) -> jax.Array:
    """What `gdn_sublayer` did with a projection until PR 48, operation for
    operation: float32, the convolution, SiLU, and for q and k the L2 norm
    over a head's channels and the scale. One program a shape and scale:
    taken bare, each of its operations is compiled alone."""
    s = jax.nn.silu(causal_conv(x.astype(F32), taps))
    if scale is not None:
        s = s * jax.lax.rsqrt(jnp.sum(s * s, -1, keepdims=True) + gc.L2_EPS) * scale
    return s


def inputs(T, d, dtype=F32, B=1, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (B, H, T, d), dtype),
            jax.random.normal(ks[1], (K, H * d)) * 0.5,
            jax.random.normal(ks[2], (B, H, T, d)))


def grads_of(fn, x, taps, w, scale):
    return jax.jit(jax.grad(lambda x, t: (fn(x, t, scale) * w).sum(), argnums=(0, 1)))(x, taps)


# keys of 96 with the norm (q's scale, k's), values of 192 without
HEADS = pytest.mark.parametrize("d,scale", [(96, 96 ** -0.5), (96, 1.0), (192, None)],
                                ids=["q_96_normed_and_scaled", "k_96_normed", "v_192_plain"])
# blocks are of 512 rows, walked in tiles of 128 (d = 96) or 64 (d = 192): 1024 = two whole
# blocks; 1100 = two and 76 rows, padded to a third; 150 = less than one (one block of 256 or
# 192); 40 = less than one tile
LENGTHS = pytest.mark.parametrize("T", [1024, 1100, 150, 40])


@HEADS
@LENGTHS
def test_forward_is_the_chains(T, d, scale):
    x, taps, _ = inputs(T, d)
    got = gc.gdn_conv(x, taps, scale)
    assert got.shape == x.shape and got.dtype == F32
    # float32 both ways, the same operations in the same order but for the sum over the lanes
    np.testing.assert_allclose(np.asarray(got), np.asarray(chain(x, taps, scale)),
                               rtol=1e-5, atol=1e-6)


@HEADS
@LENGTHS
def test_both_gradients_are_jax_grad_of_the_chain(T, d, scale):
    """The input's gradient (the reverse convolution through SiLU's and the
    norm's derivatives) and the taps' (a sum over every position of a
    head), against reverse mode through the jax.numpy chain."""
    x, taps, w = inputs(T, d, B=2)
    for name, g, r in zip(("dx", "dtaps"), grads_of(gc.gdn_conv, x, taps, w, scale),
                          grads_of(chain, x, taps, w, scale)):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(r).max()), err_msg=name)


@HEADS
def test_a_bfloat16_projection_is_cast_once_and_its_gradient_is_bfloat16(d, scale):
    """As the model calls it: x the matmul's bfloat16 output, the taps
    float32 parameters. Forward float32 of the cast input; dx rounded to
    bfloat16 once, as the cast's transpose rounds the chain's."""
    x, taps, w = inputs(600, d, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(gc.gdn_conv(x, taps, scale)),
                               np.asarray(chain(x, taps, scale)), rtol=1e-5, atol=1e-6)
    (dx, dtaps), (rx, rtaps) = grads_of(gc.gdn_conv, x, taps, w, scale), grads_of(chain, x, taps, w, scale)
    assert dx.dtype == jnp.bfloat16 and dtaps.dtype == F32
    # one bfloat16 rounding of float32 values that differ in their last bits: an ulp at most
    np.testing.assert_allclose(np.asarray(dx, np.float32), np.asarray(rx, np.float32),
                               rtol=2 ** -7, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dtaps), np.asarray(rtaps), rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(rtaps).max()))


@pytest.mark.parametrize("cut", [70, 128, 512, 515, 1023],
                         ids=["inside_a_tile", "at_a_tiles_first_row", "at_a_blocks_first_row",
                              "in_a_blocks_halo", "the_last_row"])
@pytest.mark.parametrize("d,scale", [(96, 1.0), (192, None)], ids=["normed", "plain"])
def test_a_position_reads_nothing_after_it(cut, d, scale):
    """x changed from `cut` on: every output before it bit for bit what it
    was, the output AT it not."""
    x, taps, _ = inputs(1024, d)
    base, again = gc.gdn_conv(x, taps, scale), gc.gdn_conv(x.at[:, :, cut:].add(1.0), taps, scale)
    np.testing.assert_array_equal(np.asarray(again[:, :, :cut]), np.asarray(base[:, :, :cut]))
    assert float(jnp.abs(again[:, :, cut] - base[:, :, cut]).max()) > 0.0


def test_a_blocks_first_rows_read_the_rows_before_it_and_the_first_block_zeros():
    """x is nonzero only in the last three rows of the first block (509-511)
    and in row 0: rows 512-514, the second block's first, see them through
    taps 1-3 (row 512 all three, row 514 the last alone, row 515 nothing);
    row 0 sees zeros before the sequence, tap 0 alone."""
    d = 96
    _, taps, _ = inputs(1024, d)
    seen = jax.random.normal(jax.random.key(5), (1, H, 3, d))
    x = jnp.zeros((1, H, 1024, d)).at[:, :, 509:512].set(seen).at[:, :, 0].set(1.0)
    got = gc.gdn_conv(x, taps)
    t = taps.reshape(K, H, d)
    pre = {512: seen[0, :, 2] * t[1] + seen[0, :, 1] * t[2] + seen[0, :, 0] * t[3],
           513: seen[0, :, 2] * t[2] + seen[0, :, 1] * t[3],
           514: seen[0, :, 2] * t[3],
           515: jnp.zeros((H, d)),
           0: t[0]}
    for row, want in pre.items():
        np.testing.assert_allclose(np.asarray(got[0, :, row]), np.asarray(jax.nn.silu(want)),
                                   rtol=1e-6, atol=1e-7, err_msg=str(row))
    assert float(jnp.abs(got[0, :, 512]).max()) > 0.0


def test_a_blocks_last_rows_take_the_gradients_of_the_rows_after_it():
    """The cotangent is nonzero only in the second block's first row (512):
    the input's gradient stands in rows 509-512 (the reverse convolution
    crosses the boundary backward) and nowhere else, and is the chain's."""
    x, taps, _ = inputs(1024, 192)
    w = jnp.zeros_like(x, F32).at[:, :, 512].set(1.0)
    dx, dtaps = grads_of(gc.gdn_conv, x, taps, w, None)
    rx, rtaps = grads_of(chain, x, taps, w, None)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dtaps), np.asarray(rtaps), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(dx[:, :, 509:513]).min()) > 0.0
    assert float(jnp.abs(dx[:, :, :509]).max()) == 0.0 == float(jnp.abs(dx[:, :, 513:]).max())


@pytest.mark.parametrize("d,scale", [(96, 96 ** -0.5), (192, None)], ids=["normed", "plain"])
def test_the_rematerialised_gradient_is_the_plain_one(d, scale):
    """Under the block's `jax.checkpoint` nothing of the chain is saved but
    its input: the forward kernel runs again, and both gradients are bit
    for bit the plain ones."""
    x, taps, w = inputs(1100, d)
    remat = jax.checkpoint(lambda x, t, s: gc.gdn_conv(x, t, s), static_argnums=2)
    for a, b in zip(grads_of(remat, x, taps, w, scale), grads_of(gc.gdn_conv, x, taps, w, scale)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def kernels_of(jaxpr):
    """`pallas_call`s of a jaxpr, every sub-jaxpr but the kernels' own walked."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
            continue
        assert eqn.primitive.name not in ("scan", "while"), eqn.primitive.name
        n += sum(kernels_of(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


def test_it_is_one_kernel_forward_and_one_backward_on_the_callers_own_array():
    """A forward is ONE `pallas_call` and a gradient two (no loop outside
    them), and at whole blocks the kernel reads the caller's array itself:
    no pad, reshape or copy of x stands before it."""
    x, taps, w = inputs(1024, 96, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda x, t: gc.gdn_conv(x, t, 1.0))(x, taps).jaxpr
    assert kernels_of(jaxpr) == 1
    call = jaxpr.eqns[-1]
    assert call.primitive.name == "custom_vjp_call" and call.outvars == jaxpr.outvars
    assert call.invars[0] == jaxpr.invars[0]
    grad = jax.make_jaxpr(jax.grad(lambda x, t: (gc.gdn_conv(x, t, 1.0) * w).sum(),
                                   argnums=(0, 1)))(x, taps)
    assert kernels_of(grad.jaxpr) == 2


def test_a_call_site_counts_itself_while_tracing_and_too_many_taps_are_refused():
    x, taps, _ = inputs(64, 24)
    before = obs.layer_counters().get("gdn_conv.kernel", {}).get("count", 0)
    jax.make_jaxpr(gc.gdn_conv)(x, taps)
    assert obs.layer_counters()["gdn_conv.kernel"]["count"] == before + 1
    with pytest.raises(NotImplementedError, match="10 taps"):
        gc.gdn_conv(x, jnp.zeros((10, H * 24)))


# -- a bias a channel (a Mamba-2 mixer's convolution, models/nemotron_h.py) ---------------


def chain_biased(x, taps, bias):
    """SiLU(conv(x) + b): the chain with the bias [H x d] on the pre-activation."""
    B, heads, S, d = x.shape
    return jax.nn.silu(causal_conv(x.astype(F32), taps) + bias.astype(F32).reshape(heads, 1, d))


def biased_grads(fn, x, taps, bias, w):
    return jax.jit(jax.grad(lambda x, t, b: (fn(x, t, b) * w).sum(), argnums=(0, 1, 2)))(
        x, taps, bias)


@pytest.mark.parametrize("T", [1024, 1100, 40])
@pytest.mark.parametrize("d", [128, 96], ids=["heads_of_128", "heads_of_96"])
def test_a_bias_is_added_before_silu_and_takes_its_gradient(T, d):
    """Forward, and all THREE gradients (the input's, the taps', the
    bias's: the sum of dpre over every position of a channel), against
    reverse mode through the jax.numpy chain; batch 2."""
    x, taps, w = inputs(T, d, B=2)
    bias = jax.random.uniform(jax.random.key(4), (H * d,), minval=-0.5, maxval=0.5)
    got = gc.gdn_conv(x, taps, bias=bias)
    assert got.shape == x.shape and got.dtype == F32
    np.testing.assert_allclose(np.asarray(got), np.asarray(chain_biased(x, taps, bias)),
                               rtol=1e-5, atol=1e-6)
    fn = lambda x, t, b: gc.gdn_conv(x, t, bias=b)  # noqa: E731
    for name, g, r in zip(("dx", "dtaps", "dbias"), biased_grads(fn, x, taps, bias, w),
                          biased_grads(chain_biased, x, taps, bias, w)):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(r).max()), err_msg=name)


def test_a_bias_under_checkpoint_a_bfloat16_input_and_the_refusal_under_a_norm():
    x, taps, w = inputs(600, 128, jnp.bfloat16)
    bias = jax.random.uniform(jax.random.key(4), (H * 128,), minval=-0.5, maxval=0.5)
    fn = lambda x, t, b: gc.gdn_conv(x, t, bias=b)  # noqa: E731
    plain = biased_grads(fn, x, taps, bias, w)
    for a, b in zip(biased_grads(jax.checkpoint(fn), x, taps, bias, w), plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plain[0].dtype == jnp.bfloat16 and plain[2].dtype == bias.dtype
    np.testing.assert_allclose(np.asarray(plain[2]),
                               np.asarray(biased_grads(chain_biased, x, taps, bias, w)[2]),
                               rtol=2e-4, atol=2e-5 * float(jnp.abs(plain[2]).max()))
    with pytest.raises(NotImplementedError, match="a bias under the L2 norm"):
        gc.gdn_conv(x, taps, 1.0, bias=bias)


def test_a_zero_bias_is_no_bias_and_without_one_the_program_is_the_parents():
    """A zero bias changes no output; a call without a bias traces the
    kernels with the arguments they always had (no `bias` in their
    parameters): the lowered text of a model that has none is unchanged."""
    x, taps, _ = inputs(1024, 128)
    np.testing.assert_array_equal(np.asarray(gc.gdn_conv(x, taps, bias=jnp.zeros(H * 128))),
                                  np.asarray(gc.gdn_conv(x, taps)))
    text = jax.jit(lambda x, t: gc.gdn_conv(x, t)).lower(x, taps).as_text()
    assert "gdn_conv_fwd" in text
    assert kernels_of(jax.make_jaxpr(lambda x, t, b: gc.gdn_conv(x, t, bias=b))(
        x, taps, jnp.zeros(H * 128)).jaxpr) == 1

"""ops/gated_norm.py on the CPU: the kernels under the Pallas interpreter
against the five jax.numpy lines they replaced in
models/nemotron_h.py::mamba_sublayer (kept HERE as the reference:
`five_lines`), forward and every gradient (y's, z's, the weight's), all
float32, at the cell's width of 8 groups of 512 and at one group, at rows
of whole blocks, of a last block that is not full and of less than a tile,
z float32 and bfloat16; that the readings NOT taken (the norm before the
gate, one norm over all the channels) and a bfloat16 computation stand a
hundred tolerances away, so the tolerance is what holds the function and
its precision (the benchmark's four checks cannot see a norm computed in
bfloat16: PERF.md section 7 item 8); that nothing of the forward is saved
but its inputs; that it is kernels all the way, a call site counts itself
and the shapes not taken are refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import obs
from ray_tpu.ops import gated_norm as gn
from test_gdn_conv import kernels_of

F32, BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-5
TOL = 1e-5   # float32 both ways, the same operations but for the order of the sums over the lanes


def five_lines(y, z, weight, groups, eps=EPS):
    """What `mamba_sublayer` did under `ssm.norm` until PR 52, operation
    for operation: y [..., W] float32 x SiLU(float32(z)) -> RMS norm over
    each group of W / groups channels -> x weight -> z's dtype."""
    lead, W = y.shape[:-1], y.shape[-1]
    y = y.reshape(lead + (groups, W // groups)) \
        * jax.nn.silu(z.astype(F32)).reshape(lead + (groups, W // groups))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return (y.reshape(lead + (W,)) * weight.astype(F32)).astype(z.dtype)


def group_rms(u, groups, eps):
    """u [..., W] / the root mean square of its group, in u's dtype."""
    g = u.reshape(u.shape[:-1] + (groups, -1))
    return (g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)).reshape(u.shape)


def norm_before_the_gate(y, z, weight, groups, eps=EPS):
    """The reading not taken (`norm_before_gate` of the family's kernels):
    RMSNorm(y) x weight, THEN x SiLU(z)."""
    return (group_rms(y, groups, eps) * weight * jax.nn.silu(z.astype(F32))).astype(z.dtype)


def one_norm_over_all_channels(y, z, weight, groups, eps=EPS):
    return five_lines(y, z, weight, 1, eps)


def bfloat16_inside(y, z, weight, groups, eps=EPS):
    """The same operations on bfloat16 values: what the precision rule forbids."""
    u = y.astype(BF16) * jax.nn.silu(z.astype(BF16))
    return (group_rms(u, groups, eps) * weight.astype(BF16)).astype(z.dtype)


def inputs(lead, W, z_dtype=F32, seed=0):
    """(y, z, weight, a cotangent of the output): y of the scan's scale, a weight near 1."""
    ks = jax.random.split(jax.random.key(seed), 4)
    return (2.0 * jax.random.normal(ks[0], lead + (W,), F32),
            jax.random.normal(ks[1], lead + (W,), F32).astype(z_dtype),
            1.0 + 0.1 * jax.random.normal(ks[2], (W,), F32),
            jax.random.normal(ks[3], lead + (W,), F32))


def grads_of(fn, y, z, weight, ct, groups):
    return jax.jit(jax.grad(lambda y, z, w: (fn(y, z, w, groups).astype(F32) * ct).sum(),
                            argnums=(0, 1, 2)))(y, z, weight)


def kernel(y, z, weight, groups, eps=EPS):
    return gn.gated_norm(y, z, weight, groups=groups, eps=eps)


def close(got, want, name, ulp=False):
    """To `TOL` of the largest value; a bfloat16 result to one rounding of
    float32 values that differ in their last bits: an ulp at most."""
    assert got.shape == want.shape and got.dtype == want.dtype, name
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if ulp:
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6, err_msg=name)
    else:
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL * float(np.abs(want).max()),
                                   err_msg=name)


# blocks are of 128 rows, walked in tiles of 16: the cell's width at 48 rows (one block of three
# tiles); 136 rows = one block and 8 rows, padded to a second; 2 x 256 = a batch of two, two
# whole blocks each, of ONE group of 512 (four lane tiles, the cell's); 5 rows = less than a
# tile, twice; groups of 64, narrower
# than a lane tile (the tests' tiny configurations)
SHAPES = pytest.mark.parametrize("lead,W,groups", [
    ((1, 48), 4096, 8), ((1, 136), 256, 2), ((2, 256), 512, 1), ((2, 5), 256, 2), ((3, 16), 128, 2)],
    ids=["the_cells_8_groups_of_512", "a_last_block_not_full", "two_whole_blocks_of_one_group",
         "less_than_a_tile", "groups_of_half_a_lane_tile"])
Z = pytest.mark.parametrize("z_dtype", [F32, BF16], ids=["z_float32", "z_bfloat16"])


@SHAPES
@Z
def test_forward_is_the_five_lines(lead, W, groups, z_dtype):
    y, z, weight, _ = inputs(lead, W, z_dtype)
    got = kernel(y, z, weight, groups)
    assert got.shape == z.shape and got.dtype == z_dtype
    close(got, five_lines(y, z, weight, groups), "out", ulp=z_dtype == BF16)


@SHAPES
@Z
def test_every_gradient_is_jax_grad_of_the_five_lines(lead, W, groups, z_dtype):
    """dy (float32, what the scan's backward kernel reads), dz (in z's
    dtype: rounded once, as the cast's transpose rounds the lines') and the
    weight's (a sum over every row, across the grid's steps), against
    reverse mode through the jax.numpy lines."""
    y, z, weight, ct = inputs(lead, W, z_dtype)
    got, want = grads_of(kernel, y, z, weight, ct, groups), grads_of(five_lines, y, z, weight,
                                                                     ct, groups)
    assert got[0].dtype == F32 and got[1].dtype == z_dtype and got[2].dtype == F32
    for name, g, r in zip(("dy", "dz", "dweight"), got, want):
        close(g, r, name, ulp=name == "dz" and z_dtype == BF16)


@pytest.mark.parametrize("wrong", [norm_before_the_gate, one_norm_over_all_channels,
                                   bfloat16_inside], ids=lambda f: f.__name__)
def test_the_readings_not_taken_stand_a_hundred_tolerances_away(wrong):
    """Forward and in every gradient the kernel is inside `TOL` of the five
    lines where each wrong reading is at least 100 x `TOL` outside, as a
    share of the largest value: the tolerance sees the order of gate and
    norm, the group and the precision."""
    groups = 4
    y, z, weight, ct = inputs((1, 32), 512)

    def distances(fn):
        outs = (fn(y, z, weight, groups),) + grads_of(fn, y, z, weight, ct, groups)
        refs = (five_lines(y, z, weight, groups),) + grads_of(five_lines, y, z, weight, ct, groups)
        return [float(jnp.abs(o - r).max() / jnp.abs(r).max()) for o, r in zip(outs, refs)]

    assert max(distances(kernel)) <= TOL
    assert min(distances(wrong)) >= 100 * TOL, distances(wrong)


def test_it_is_one_kernel_forward_and_one_backward_on_the_callers_own_arrays():
    """A forward is ONE `pallas_call` and a gradient two (no loop outside
    them); at whole blocks the kernel reads the caller's y and z
    themselves: no pad, reshape (to rows or to (groups, channels)) or copy
    stands before it, and only the weight's one row is made."""
    y, z, weight, ct = inputs((1, 256), 1024, BF16)
    jaxpr = jax.make_jaxpr(lambda *a: kernel(*a, 8))(y, z, weight).jaxpr
    assert kernels_of(jaxpr) == 1
    call = jaxpr.eqns[-1]
    assert call.primitive.name == "custom_vjp_call" and call.outvars == jaxpr.outvars
    assert list(call.invars[:2]) == list(jaxpr.invars[:2])
    assert {e.primitive.name for e in jaxpr.eqns[:-1]} <= {"reshape", "convert_element_type"}
    grad = jax.make_jaxpr(jax.grad(lambda *a: (kernel(*a, 8).astype(F32) * ct).sum(),
                                   argnums=(0, 1, 2)))(y, z, weight)
    assert kernels_of(grad.jaxpr) == 2


def test_nothing_is_saved_but_the_inputs_and_the_rematerialised_gradient_is_the_plain_one():
    """The residuals are y, z and the weight, no array of the forward's
    making: a block that READS the output (as the output projection's
    weight gradient does), under a `jax.checkpoint` that saves nothing,
    runs the forward kernel once more and the backward kernel once, three
    in all, and every gradient is bit for bit the plain one."""
    y, z, weight, ct = inputs((1, 136), 256, BF16)

    def block(y, z, weight, groups):
        return jnp.square(kernel(y, z, weight, groups).astype(F32))

    remat = jax.checkpoint(block, static_argnums=3)
    for a, b in zip(grads_of(remat, y, z, weight, ct, 2), grads_of(block, y, z, weight, ct, 2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    grad = jax.make_jaxpr(jax.grad(lambda *a: (remat(*a, 2) * ct).sum(), argnums=(0, 1, 2)))(
        y, z, weight)
    assert kernels_of(grad.jaxpr) == 3
    _, residuals = gn._norm_fwd(2, EPS, 128, True, y[:, :128], z[:, :128], weight[None])
    assert [(r.shape, r.dtype) for r in residuals] == [
        ((1, 128, 256), F32), ((1, 128, 256), BF16), ((1, 256), F32)]


def test_a_call_site_counts_itself_while_tracing():
    y, z, weight, _ = inputs((1, 16), 256)
    before = obs.layer_counters().get("gated_norm.kernel", {}).get("count", 0)
    jax.make_jaxpr(lambda *a: kernel(*a, 2))(y, z, weight)
    assert obs.layer_counters()["gated_norm.kernel"]["count"] == before + 1


@pytest.mark.parametrize("W,groups,error,named", [
    (1024, 3, ValueError, "1024 channels in 3 groups: a width of whole groups"),
    (1024, 0, ValueError, "1024 channels in 0 groups"),
    (384, 2, NotImplementedError, "groups of 192 channels: a group is whole lane tiles of 128"),
    (960, 20, NotImplementedError, "groups of 48 channels"),
], ids=["a_width_of_no_whole_groups", "no_group", "a_group_of_a_tile_and_a_half",
        "a_group_that_divides_no_tile"])
def test_the_shapes_not_taken_are_refused_by_name(W, groups, error, named):
    y, z, weight, _ = inputs((1, 16), W)
    with pytest.raises(error, match=named):
        kernel(y, z, weight, groups)


def test_unlike_y_and_z_and_a_weight_of_another_width_are_refused_by_name():
    y, z, weight, _ = inputs((1, 16), 256)
    for unlike in ((y[:, :8], z, weight), (y[0], z[0], weight), (y, z, weight[:128])):
        with pytest.raises(ValueError, match="y and z alike .B, T, W., a weight a channel"):
            kernel(*unlike, 2)

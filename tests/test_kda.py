"""ops/kda.py on the CPU: the chunked rule of Kimi Delta Attention (a delta
rule whose decay is a vector over the key's channels), its two Pallas
kernels under the interpreter, against the position-by-position rule,
forward and every gradient (`jax.grad` of the plain recurrence) at 1e-5
relative in float32, at sequences of several chunks, of several grid steps
and at ones that are no multiple of a block; under decays so strong that
the naive split (k . e^c)(k . e^-c)^T overflows within ONE chunk; what it
reduces to when the decay is the same on every channel
(ops/gated_delta.py's rule); what the forward hands the backward and that
the remat policy's names save it; and that it is kernels all the way:
one `pallas_call` forward, two for a gradient, no loop over chunks or
positions outside them and nothing that grows with T x T."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Literal

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import kda
from test_gated_delta import kernels_and_loops   # (`pallas_call`s, loops outside them)

B, H, DK, DV = 2, 3, 12, 24   # neither head size fills a tile
HI = jax.lax.Precision.HIGHEST
NAMES = "q k v g beta".split()


@jax.jit
def recurrent_kda_rule(q, k, v, g, beta):
    """The rule as written, one position at a time (a `lax.scan` over T):
    q, k, g [B, H, T, dk], v [B, H, T, dv], beta [B, H, T] -> o [B, H, T,
    dv] float32. The plain form the chunked one is held to."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                      # [B, H, d] / [B, H]
        S = S * jnp.exp(g_t)[..., None]                   # a decay a channel of the key
        kS = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=HI)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - kS), precision=HI)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=HI)

    B, H, _, dk = q.shape
    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2)


def inputs(T, beta_max=2.0, seed=0, shape=(B, H, DK, DV), decay=0.3):
    """Unit keys and queries as the sublayer makes them, a log decay of a
    few percent a position and CHANNEL, beta in (0, beta_max)."""
    B, H, DK, DV = shape
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(kk, (B, H, T, DK)) for kk in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / DK ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, H, T, DV))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, H, T, DK)))
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, T)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, H, T, DV))


@functools.lru_cache(maxsize=None)
def _grads_program(rule):
    return jax.jit(jax.grad(lambda w, *a: (rule(*a) * w).sum(), argnums=(1, 2, 3, 4, 5)))


def grads_of(rule, args, w):
    """Every gradient of sum(rule(*args) * w), from ONE program a rule and shape."""
    return _grads_program(rule)(w, *args)


def assert_close(got, want, name="", rel=1e-5):
    """Every element within `rel` of the array's largest magnitude."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), name
    worst = float(np.abs(got - want).max()) / float(np.abs(want).max())
    assert worst <= rel, (name, worst)


RULE = jax.jit(kda.kda_rule)
CHECKPOINTED = jax.checkpoint(kda.kda_rule)   # one function: one program a shape

# 192 = three whole chunks of 64; 150 = two and 22 positions; 40 = less than one; 330 = five
# and 10 positions, three grid steps of the kernels: the state is carried from step to step
SHAPES = pytest.mark.parametrize("T", [192, 150, 40, 330])


@SHAPES
def test_chunked_forward_is_the_position_by_position_rule(T):
    args, _ = inputs(T)
    got, want = RULE(*args), recurrent_kda_rule(*args)
    assert got.shape == (B, H, T, DV) and got.dtype == jnp.float32
    assert_close(got, want)


@SHAPES
def test_chunked_backward_is_jax_grad_of_the_plain_recurrence(T):
    """q, k, v, g and beta each: the backward kernel against reverse-mode
    through the scan over positions, with and without the block's
    `jax.checkpoint` around the rule (which runs the forward kernel again)."""
    args, w = inputs(T)
    want = grads_of(recurrent_kda_rule, args, w)
    for rule in (kda.kda_rule, CHECKPOINTED):
        for name, got, ref in zip(NAMES, grads_of(rule, args, w), want):
            assert ref.shape == got.shape
            assert_close(got, ref, name)


def test_at_the_published_head_size():
    """Heads of 128 x 128 (Solar-Open2's), a T that is no multiple of 64."""
    args, w = inputs(150, seed=3, shape=(1, 2, 128, 128))
    assert_close(RULE(*args), recurrent_kda_rule(*args))
    want = grads_of(recurrent_kda_rule, args, w)
    for name, got, ref in zip(NAMES, grads_of(kda.kda_rule, args, w), want):
        assert_close(got, ref, name)


def strong(args):
    """The same inputs with a third of the channels at g = -30 A POSITION:
    over a chunk of 64 that channel's c reaches -1920, and exp(-c) is far
    beyond float32 (e^88); two positions apart such a channel is gone."""
    q, k, v, g, beta = args
    fast = (jnp.arange(g.shape[-1]) % 3 == 0)
    return q, k, v, jnp.where(fast, -30.0, g), beta


def test_the_naive_split_overflows_where_this_does_not():
    """What the sub-blocks are for: (k . e^c)(k . e^-c)^T, the scalar
    rule's factorisation taken channel by channel, is not finite under a
    decay of -30 a position; the chunked rule is, and equals the
    position-by-position rule within the same limit, forward and every
    gradient."""
    args, w = inputs(192, seed=1)
    args = strong(args)
    q, k, v, g, beta = args
    c = jnp.cumsum(g[:, :, :64], axis=2)
    naive = jnp.einsum("bhid,bhjd->bhij", k[:, :, :64] * jnp.exp(c), k[:, :, :64] * jnp.exp(-c))
    assert not np.isfinite(np.asarray(naive)).all()
    assert_close(RULE(*args), recurrent_kda_rule(*args))
    want = grads_of(recurrent_kda_rule, args, w)
    for name, got, ref in zip(NAMES, grads_of(kda.kda_rule, args, w), want):
        assert_close(got, ref, name)


def _largest_exp_operands(jaxpr, consts, args, found):
    """Evaluate `jaxpr` equation by equation (into a `pjit`'s own, which
    is where jax.numpy's helpers stand) -> its outputs; the largest value
    of every `exp`'s operand is appended to `found`."""
    env = dict(zip(jaxpr.constvars, consts)) | dict(zip(jaxpr.invars, args))
    for eqn in jaxpr.eqns:
        vals = [v.val if isinstance(v, Literal) else env[v] for v in eqn.invars]
        if eqn.primitive.name == "exp":
            found.append(float(jnp.max(vals[0])))
        if eqn.primitive.name == "pjit":
            inner = eqn.params["jaxpr"]
            out = _largest_exp_operands(inner.jaxpr, inner.consts, vals, found)
        else:
            assert " exp " not in str(eqn.params.get("jaxpr", "")), eqn.primitive
            out = eqn.primitive.bind(*vals, **eqn.params)
            out = out if eqn.primitive.multiple_results else [out]
        env.update(zip(eqn.outvars, out))
    return [v.val if isinstance(v, Literal) else env[v] for v in jaxpr.outvars]


def _exps_in(jaxpr):
    """How many `exp` equations a jaxpr holds, every sub-jaxpr walked (a
    `pallas_call`'s kernel and its `pl.when` branches among them)."""
    return sum((eqn.primitive.name == "exp")
               + sum(_exps_in(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def test_every_exponent_formed_is_of_a_number_that_is_not_positive():
    """Every `exp` of the kernels is `kda._decays`'s, which the kernels' own
    jaxprs show (walked into the `pallas_call`s: forward and backward hold
    exactly as many `exp` as that function forms for each of a grid step's
    pairs, and the rule holds none outside them), and that function takes
    values, not refs: read off ITS jaxpr, each `exp` is evaluated on the
    strong decays' every pair of chunks, and its operand's largest value
    is <= 0. Not by the clamp alone: the sums of g themselves are."""
    args, w = inputs(192, seed=1)
    args = strong(args)
    g = jnp.pad(args[3], ((0, 0), (0, 0), (0, 64), (0, 128 - DK)))       # two pairs, lane-wide
    pairs = g.reshape(-1, 128, 128)
    closed = jax.make_jaxpr(kda._decays)(pairs[0], kda._SUMS)
    formed = _exps_in(closed.jaxpr)
    assert formed == 2 + len(kda._HALVES)
    for pair in pairs:
        found = []
        out = _largest_exp_operands(closed.jaxpr, closed.consts, [pair, jnp.asarray(kda._SUMS)],
                                    found)
        assert len(found) == formed and max(found) <= 0.0, found
        assert all(float(x.min()) >= 0.0 and float(x.max()) <= 1.0 for x in out)
        sums = np.asarray(kda._SUMS, np.float64) @ np.asarray(pair, np.float64)
        assert sums.max() <= 0.0 and sums.min() < -88.0    # unclamped; e^88 is float32's largest
    block = min(kda._BLOCK, 2)    # 192 positions are two pairs: one grid step or two
    forward = jax.make_jaxpr(kda.kda_rule)(*args).jaxpr
    assert _exps_in(forward) == block * formed
    gradient = jax.make_jaxpr(jax.grad(lambda *a: (kda.kda_rule(*a) * w).sum(),
                                       argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    assert _exps_in(gradient) == 2 * block * formed


def _products(jaxpr, found):
    """Every `dot_general` of a jaxpr, sub-jaxprs walked (a `pallas_call`'s
    kernel among them), as (rows, contraction, columns, bf16 passes): six
    for two float32 arrays at `highest`, one for two bfloat16 arrays."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            a, b = (v.aval for v in eqn.invars)
            (contract, _), _ = eqn.params["dimension_numbers"]
            K = int(np.prod([a.shape[i] for i in contract]))
            kinds = {(jnp.dtype(jnp.float32), HI): 6, (jnp.dtype(jnp.bfloat16), None): 1}
            precision = eqn.params["precision"]
            precision = precision[0] if isinstance(precision, tuple) else precision
            assert a.dtype == b.dtype and (a.dtype, precision) in kinds, (a, b, precision)
            found.append((a.size // K, K, b.size // K, kinds[a.dtype, precision]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _products(sub, found)
    return found


def _kernel_jaxpr(kernel):
    """The jaxpr of `kda_fwd` / `kda_bwd` at heads of 128 over ONE grid step (`_BLOCK` pairs)."""
    T = kda._BLOCK * 128
    x = jnp.zeros((1, 1, T, 128))
    b = jnp.zeros((1, kda._BLOCK, 1, 128))
    if kernel == "kda_fwd":
        return jax.make_jaxpr(functools.partial(kda.kda_fwd, interpret=True))(x, x, x, x, b)
    states, solves = jnp.zeros((1, 2 * kda._BLOCK, 128, 128)), jnp.zeros((1, kda._BLOCK, 64, 128))
    return jax.make_jaxpr(functools.partial(kda.kda_bwd, interpret=True))(
        x, x, x, x, b, states, solves, x)


@pytest.mark.parametrize("kernel,limit", [("kda_fwd", 144), ("kda_bwd", 216)])
def test_a_pairs_products_multiply_no_row_and_no_pass_known_to_be_zero(kernel, limit):
    """What a pair of chunks costs the MXU, read off the kernel's own jaxpr:
    every `dot_general`'s rows x contraction x columns in tiles of 128^3,
    times its bfloat16 passes. PR 65 took the forward from 198 passes to
    138 and the backward from 303 to 210 (the 0 / 1 sums at three passes
    where `highest` spends six, the inverse's levels from 8 positions up
    and every level of halves over the later halves' rows alone); a change
    that puts rows or passes back fails here with the table."""
    found = _products(_kernel_jaxpr(kernel).jaxpr, [])
    table = {}
    for product in found:
        table[product] = table.get(product, 0) + 1
    passes = sum(m * k * n * p for m, k, n, p in found) / 128 ** 3 / kda._BLOCK
    lines = "\n".join(f"{count:3d} x [{m}, {k}] x [{k}, {n}] at {p} passes"
                      for (m, k, n, p), count in sorted(table.items()))
    assert passes <= limit, f"{kernel}: {passes} bf16 passes a pair over {limit}\n{lines}"
    # three of a sum's products are one float32 product of the 0 / 1 matrix: never at `highest`
    assert not any(m == kda._SUMS.shape[0] and p == 6 for m, k, n, p in found), lines


def test_the_sums_three_bfloat16_terms_are_highests_exponents():
    """`_sum_dot`: `_SUMS` in bfloat16 (0 and 1 are exact) times g as its
    three bfloat16 terms, which ARE g (hi + mid + lo bit for bit),
    accumulated in float32. On a pair with g = -30 on every third channel
    the exponents are within 1 ulp of the float64 sums everywhere (a whole
    multiple of 30, exactly, on the fast channels) and of what `highest`
    makes of two float32 arrays wherever that is itself within 1 ulp of
    them (a float32 sum of 64 terms rounds 63 times; three sums of 8-bit
    terms round twice), and never further from the float64 sums than it."""
    (_, _, _, g, _), _ = inputs(128, seed=5, shape=(1, 1, 128, 128))
    g = strong((0, 0, 0, g, 0))[3][0, 0]
    hi = g.astype(jnp.bfloat16)
    mid = (g - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    lo = (g - hi.astype(jnp.float32) - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    np.testing.assert_array_equal((f32(hi) + f32(mid)) + f32(lo), np.asarray(g))
    assert kda._SUMS.dtype == jnp.bfloat16
    sums = np.asarray(kda._SUMS, np.float64)
    assert set(np.unique(sums)) == {0.0, 1.0}
    got = np.asarray(jax.jit(kda._sum_dot)(jnp.asarray(kda._SUMS), g), np.float64)
    highest = np.asarray(jax.lax.dot_general(
        jnp.asarray(kda._SUMS, jnp.float32), g, (((1,), (0,)), ((), ())), precision=HI), np.float64)
    exact = sums @ np.asarray(g, np.float64)
    ulp = np.spacing(np.abs(exact).astype(np.float32)).astype(np.float64)
    assert (np.abs(got - exact) <= ulp).all()
    fast = np.arange(128) % 3 == 0
    np.testing.assert_array_equal(got[:, fast], exact[:, fast])
    np.testing.assert_array_equal(got[:, fast], highest[:, fast])
    near = np.abs(highest - exact) <= ulp
    assert near.mean() > 0.9 and (np.abs(got - highest)[near] <= 2 * ulp[near]).all()
    assert np.abs(got - exact).max() <= np.abs(highest - exact).max()


@pytest.mark.parametrize("h", [4, 2, 1])
def test_a_short_levels_strided_rows_and_their_write_are_each_others_inverse(h):
    """Under a sublane tile's 8 positions `_later` reads the later halves'
    rows of every block of 2h through a VMEM scratch by h strided loads
    (`_short_rows`: slice j is row h + j of every block) and `_spread`
    writes them back through the same slices: under the interpreter the 64
    rows are exactly the later halves', each once, and the way back puts
    each where it stood and touches no earlier half's row."""
    x = jnp.arange(128 * 128, dtype=jnp.float32).reshape(128, 128) + 1.0

    def kernel(x_ref, rows_ref, back_ref, scr, out_scr):
        out_scr[...] = jnp.full_like(out_scr, -1.0)
        rows = kda._later(x_ref[...], h, scr)
        rows_ref[...] = rows
        back_ref[...] = kda._spread(rows, h, out_scr)

    rows, back = pl.pallas_call(
        kernel, out_shape=[jax.ShapeDtypeStruct((64, 128), jnp.float32),
                           jax.ShapeDtypeStruct((128, 128), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)] * 2, interpret=True)(x)
    i = np.arange(128)
    later = (i // h) % 2 == 1
    assert sorted(np.asarray(rows)[:, 0]) == sorted(np.asarray(x)[later, 0])
    np.testing.assert_array_equal(np.asarray(rows)[:128 // (2 * h)], np.asarray(x)[h::2 * h])
    np.testing.assert_array_equal(np.asarray(back), np.where(later[:, None], np.asarray(x), -1.0))
    np.testing.assert_array_equal(np.asarray(kda._later_rows(h))[:, 0], later)


def test_with_one_decay_a_head_it_is_the_gated_delta_rule():
    """g constant over a head's channels: ops/gated_delta.py's rule (its
    Pallas kernels, interpreted) on the same inputs, forward and the
    gradients (g's summed over the channels it was broadcast to)."""
    (q, k, v, g, beta), w = inputs(150, seed=2)
    g1 = g[..., 0]
    wide = jnp.broadcast_to(g1[..., None], g.shape)
    assert_close(RULE(q, k, v, wide, beta), gd.gated_delta_rule(q, k, v, g1, beta))
    got = grads_of(kda.kda_rule, (q, k, v, wide, beta), w)
    want = grads_of(gd.gated_delta_rule, (q, k, v, g1, beta), w)
    for name, a, b in zip(NAMES, got, want):
        assert_close(a.sum(-1) if name == "g" else a, b, name)


def test_the_mean_decay_is_another_function():
    """The scalar rule with the MEAN of the channels' decays (the one
    mechanism the benchmark's table holds most against) is far from the
    rule: the tests above are not passing by a tolerance that would let
    it through."""
    (q, k, v, g, beta), _ = inputs(192)
    want = np.asarray(recurrent_kda_rule(q, k, v, g, beta))
    mean = np.asarray(gd.gated_delta_rule(q, k, v, g.mean(-1), beta))
    assert np.abs(mean - want).max() > 100 * 1e-5 * np.abs(want).max()


def test_the_remat_policys_names_save_what_the_backward_reads():
    """models/solar_open2.py lists `kda_out` and `kda_states` in
    `REMAT_SAVES`, which llama._remat saves by name: with them kept the
    rematerialised backward runs no second forward kernel (one `pallas_call`
    in the backward's jaxpr, the backward kernel), without them two; the
    gradients are the same either way."""
    from ray_tpu.models import solar_open2

    args, w = inputs(150)
    saved = jax.checkpoint(kda.kda_rule, policy=jax.checkpoint_policies.save_only_these_names(
        *solar_open2.REMAT_SAVES))
    for a, b in zip(grads_of(saved, args, w), grads_of(CHECKPOINTED, args, w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def backward_kernels(rule):
        _, pull = jax.vjp(rule, *args)
        return kernels_and_loops(jax.make_jaxpr(pull)(w).jaxpr)[0]

    assert backward_kernels(saved) == 1 and backward_kernels(CHECKPOINTED) == 2


def test_what_the_forward_hands_the_backward_is_the_states_and_the_inverses_without_their_zeros():
    """The forward kernel reads q, k, v and g where they stand ([B, H, T, d]:
    no reshape of them stands before it) and writes, beside o, the state each
    chunk started from, TRANSPOSED [dv, dk] (the position-by-position rule's
    state after the positions before the chunk), and each pair's inverse as
    its two diagonal blocks side by side, [B x H, T / 128, 64, 128]: chunk
    c's (I + A)^-1 is half c mod 2 of pair c div 2, and the zeros off a
    pair's diagonal are not kept."""
    T = 512   # whole grid steps of the kernels: the wrapper's padding is not this test's
    (q, k, v, g, beta), _ = inputs(T)
    o, states, solves = kda.kda_fwd(q, k, v, g, beta.reshape(B * H, T // 128, 1, 128),
                                    interpret=True)
    assert o.shape == v.shape and states.shape == (B * H, T // 64, DV, DK)
    assert solves.shape == (B * H, T // 128, 64, 128)
    np.testing.assert_array_equal(np.asarray(states[:, 0]), 0.0)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    for b, h, chunk in ((0, 0, 0), (1, 2, 3), (0, 1, 5)):
        at = slice(chunk * 64, (chunk + 1) * 64)
        Kc, bc, c = f64(k)[b, h, at], f64(beta)[b, h, at], np.cumsum(f64(g)[b, h, at], 0)
        M = np.einsum("id,jd,ijd->ij", Kc, Kc, np.exp(np.minimum(c[:, None] - c[None], 0.0)))
        half = solves[b * H + h, chunk // 2, :, (chunk % 2) * 64:(chunk % 2 + 1) * 64]
        np.testing.assert_allclose(np.asarray(half), np.linalg.inv(
            np.eye(64) + np.tril(bc[:, None] * M, -1)), atol=2e-5)
        S = np.zeros((DK, DV))
        for t in range(chunk * 64):
            S = np.exp(f64(g)[b, h, t])[:, None] * S
            k_t, v_t, b_t = f64(k)[b, h, t], f64(v)[b, h, t], f64(beta)[b, h, t]
            S = S + np.outer(k_t, b_t * (v_t - k_t @ S))
        np.testing.assert_allclose(np.asarray(states[b * H + h, chunk]), S.T, atol=2e-5)
    jaxpr = jax.make_jaxpr(kda.kda_rule)(q, k, v, g, beta).jaxpr
    call = jaxpr.eqns[-1]
    assert call.primitive.name == "custom_vjp_call" and call.outvars == jaxpr.outvars
    assert call.invars[:4] == jaxpr.invars[:4]                  # the caller's arrays themselves


def test_no_write_is_pure_decay_a_channel():
    """beta = 0: the state only decays, and from zero it stays zero; with
    one write at position 0 and none after, o_t = sum over the channels of
    q_td exp(g_1d + .. + g_td) k_0d x what position 0 stored: each channel
    of the key forgets at its own rate."""
    (q, k, v, g, _), _ = inputs(150)
    assert float(jnp.abs(RULE(q, k, v, g, jnp.zeros((B, H, 150)))).max()) == 0.0
    beta = jnp.zeros((B, H, 150)).at[:, :, 0].set(1.0)
    got = RULE(q, k, v, g, beta)
    decay = jnp.exp(jnp.cumsum(g.at[:, :, 0].set(0.0), axis=2))                 # [B, H, T, dk]
    stored = jnp.einsum("bhtk,bhtk,bhk->bht", q, decay, k[:, :, 0])[..., None] * v[:, :, :1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(stored), rtol=1e-4, atol=1e-6)


def test_a_position_reads_nothing_after_it():
    args, _ = inputs(150)
    base = RULE(*args)
    moved = [a.at[:, :, 100:].add(-0.5 if name == "g" else 1.0) for name, a in zip(NAMES, args)]
    again = RULE(*moved)
    assert float(jnp.abs(again[:, :, :100] - base[:, :, :100]).max()) == 0.0
    assert float(jnp.abs(again[:, :, 100:] - base[:, :, 100:]).max()) > 1e-3


def test_nothing_grows_with_the_square_of_the_sequence():
    """At 1,024 positions a forward is ONE `pallas_call` and a gradient two
    (the forward that hands out the chunks' states, the backward), no `scan`
    or `while` stands outside them (the walk over the chunks is the kernels'
    grid), and the largest array of forward and backward is the states the
    chunks start from, [T / 64, dv, dk] a head, or the constant matrix of
    the sums: nothing T x T."""
    T = 1024
    args, w = inputs(T, shape=(1, 1, DK, DV))
    assert kernels_and_loops(jax.make_jaxpr(kda.kda_rule)(*args).jaxpr) == (1, [])
    sizes = []
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: (kda.kda_rule(*a) * w).sum(),
                                    argnums=(0, 1, 2, 3, 4)))(*args)
    assert kernels_and_loops(jaxpr.jaxpr, sizes) == (2, [])
    assert max(sizes) <= max(T * max(DK, DV, 64), kda._SUMS.size) < T * T

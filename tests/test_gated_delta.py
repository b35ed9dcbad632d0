"""ops/gated_delta.py on the CPU: the chunked gated delta rule, its Pallas
kernels under the interpreter, against the position-by-position rule,
forward and every gradient (`jax.grad` of the plain recurrence), at
sequences of several chunks, of several grid steps and at ones that are no
multiple of the chunk, at head sizes that fill no tile and at the 7B's;
the write strength up to 1 and up to 2 (`linear_allow_neg_eigval`); what
the rule reduces to when a gate is switched off; and that it is kernels
all the way: one `pallas_call` forward, two for a gradient, no loop over
chunks or positions outside them and nothing that grows with T x T."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd

B, H, DK, DV = 2, 3, 12, 24   # neither head size fills a tile
HI = jax.lax.Precision.HIGHEST


@jax.jit
def recurrent_gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                               beta: jax.Array) -> jax.Array:
    """The rule as written, one position at a time (a `lax.scan` over T):
    q, k [B, H, T, dk], v [B, H, T, dv], g and beta [B, H, T] -> o
    [B, H, T, dv] float32. The plain form the chunked one is held to."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                      # [B, H, d] / [B, H]
        S = S * jnp.exp(g_t)[..., None, None]
        kS = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=HI)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - kS), precision=HI)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=HI)

    B, H, _, dk = q.shape
    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2)


def inputs(T, beta_max, seed=0, shape=(B, H, DK, DV)):
    """Unit keys and queries as the sublayer makes them, a log decay of a
    few percent a position, beta in (0, beta_max)."""
    B, H, DK, DV = shape
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(kk, (B, H, T, DK)) for kk in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / DK ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, H, T, DV))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (B, H, T)))
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, T)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, H, T, DV))


@functools.lru_cache(maxsize=None)
def _grads_program(rule):
    return jax.jit(jax.grad(lambda w, *a: (rule(*a) * w).sum(), argnums=(1, 2, 3, 4, 5)))


def grads_of(rule, args, w):
    """Every gradient of sum(rule(*args) * w), from ONE program a rule and
    shape: `w` is an argument, so the cases that differ in their values
    alone (the two write strengths) compile it once."""
    return _grads_program(rule)(w, *args)


RULE = jax.jit(gd.gated_delta_rule)
CHECKPOINTED = jax.checkpoint(gd.gated_delta_rule)   # one function: one program a shape


# 192 = three whole chunks of 64; 150 = two and 22 positions; 40 = less than one; 600 = nine
# and 24 positions, three grid steps of the kernels: the state is carried from step to step
SHAPES = pytest.mark.parametrize("T", [192, 150, 40, 600])
EIGVAL = pytest.mark.parametrize("beta_max", [1.0, 2.0], ids=["beta_to_1", "neg_eigval_beta_to_2"])


@SHAPES
@EIGVAL
def test_chunked_forward_is_the_position_by_position_rule(T, beta_max):
    args, _ = inputs(T, beta_max)
    got, want = RULE(*args), recurrent_gated_delta_rule(*args)
    assert got.shape == (B, H, T, DV) and got.dtype == jnp.float32
    # float32 both ways; the orders of summation differ
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


@SHAPES
@EIGVAL
def test_chunked_backward_is_jax_grad_of_the_plain_recurrence(T, beta_max):
    """q, k, v, g and beta each: the transpose of the scan over chunks
    against reverse-mode through the scan over positions, with and
    without the block's `jax.checkpoint` around the rule."""
    args, w = inputs(T, beta_max)
    want = grads_of(recurrent_gated_delta_rule, args, w)
    for rule in (gd.gated_delta_rule, CHECKPOINTED):
        for name, g, r in zip("q k v g beta".split(), grads_of(rule, args, w), want):
            scale = float(jnp.abs(r).max())
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-5 * scale,
                                       err_msg=name)


@pytest.mark.parametrize("shape,T", [((1, 2, 96, 192), 330), ((1, 1, 128, 256), 130)],
                         ids=["keys_96_values_192_padded_to_the_lanes", "whole_lanes"])
def test_forward_and_backward_at_head_sizes_of_a_tile_and_more(shape, T):
    """The 7B's heads (96 / 192: the kernels stage them into 128 / 256
    lanes, and the state is two lane tiles wide) and heads that need no
    padding, at a T that is no multiple of 64 and more than one grid step."""
    args, w = inputs(T, 2.0, seed=3, shape=shape)
    np.testing.assert_allclose(np.asarray(gd.gated_delta_rule(*args)),
                               np.asarray(recurrent_gated_delta_rule(*args)), rtol=2e-5, atol=2e-6)
    want = grads_of(recurrent_gated_delta_rule, args, w)
    for name, g, r in zip("q k v g beta".split(), grads_of(gd.gated_delta_rule, args, w), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(r).max()), err_msg=name)


def test_the_remat_policys_names_save_what_the_backward_reads():
    """models/llama.py::_remat saves `gdn_out` and `gdn_states` by name: with
    them kept the rematerialised backward runs no second forward kernel (one
    `pallas_call` in the backward's jaxpr, the backward kernel), without them
    two; the gradients are the same either way."""
    args, w = inputs(150, 2.0)
    saved = jax.checkpoint(gd.gated_delta_rule, policy=jax.checkpoint_policies.save_only_these_names(
        "gdn_out", "gdn_states"))
    nothing = jax.checkpoint(gd.gated_delta_rule)
    for a, b in zip(grads_of(saved, args, w), grads_of(nothing, args, w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def backward_kernels(rule):
        _, pull = jax.vjp(rule, *args)
        return kernels_and_loops(jax.make_jaxpr(pull)(w).jaxpr)[0]

    assert backward_kernels(saved) == 1 and backward_kernels(nothing) == 2


def test_what_the_forward_hands_the_backward_is_the_states_and_the_inverses_without_their_zeros():
    """The forward kernel reads q, k, v where they stand ([B, H, T, d]: no
    reshape of them stands before it) and writes, beside o, the state each
    chunk started from and each pair's inverse as its two diagonal blocks
    side by side, [B x H, T / 128, 64, 128]: chunk c's (I + A)^-1 is half
    c mod 2 of pair c div 2, and the zeros off a pair's diagonal are not
    kept."""
    T = 512   # whole grid steps of the kernels: the wrapper's padding is not this test's
    (q, k, v, g, beta), _ = inputs(T, 2.0)
    gb = jnp.stack([a.reshape(B * H, T // 128, 128) for a in (g, beta)], axis=2)
    o, states, solves = gd.gated_delta_fwd(q, k, v, gb, interpret=True)
    assert o.shape == v.shape and states.shape == (B * H, T // 64, DK, DV)
    assert solves.shape == (B * H, T // 128, 64, 128)
    np.testing.assert_array_equal(np.asarray(states[:, 0]), 0.0)
    K, c = np.asarray(k, np.float64), np.cumsum(np.asarray(g, np.float64).reshape(B, H, -1, 64), -1)
    for b, h, chunk in ((0, 0, 0), (1, 2, 3), (0, 1, 5)):
        Kc, cc = K[b, h, chunk * 64:(chunk + 1) * 64], c[b, h, chunk]
        A = np.tril(np.asarray(beta, np.float64)[b, h, chunk * 64:(chunk + 1) * 64, None]
                    * (Kc @ Kc.T) * np.exp(cc[:, None] - cc[None, :]), -1)
        half = solves[b * H + h, chunk // 2, :, (chunk % 2) * 64:(chunk % 2 + 1) * 64]
        np.testing.assert_allclose(np.asarray(half), np.linalg.inv(np.eye(64) + A), atol=2e-5)
    jaxpr = jax.make_jaxpr(gd.gated_delta_rule)(q, k, v, g, beta).jaxpr
    call = jaxpr.eqns[-1]
    assert call.primitive.name == "custom_vjp_call" and call.outvars == jaxpr.outvars
    assert call.invars[:3] == jaxpr.invars[:3]                  # the caller's arrays themselves


def _inverses_over_all_rows(A):
    """The block doubling as it stood before PR 65: T <- T - T (A_off T), every row multiplied."""
    r, c = gd._indices(gd._PAIR)
    T = jnp.where(r == c, 1.0, 0.0) - jnp.where((r >> 1) == (c >> 1), A, 0.0)
    for level in range(1, gd._LOG_CHUNK):
        joins = ((r >> (level + 1)) == (c >> (level + 1))) & ((r >> level) != (c >> level))
        T = T - gd._dot(T, gd._dot(jnp.where(joins, A, 0.0), T, gd._NN), gd._NN)
    return T


@pytest.mark.parametrize("scale", [0.05, 0.5], ids=["weak", "strong"])
def test_the_inverse_over_the_later_halves_rows_is_the_inverse(scale):
    """`_inverses` multiplies, at the levels of 8, 16 and 32 positions, the
    later halves' rows alone (`_later` / `_spread`): of a random strictly
    lower-triangular A, block-diagonal in chunks of 64, it gives
    (I + A)^-1 as close to numpy's float64 inverse as the form over all
    rows does, and the same matrix as that form to the rounding of a sum,
    with exact zeros off the diagonal blocks."""
    n = gd._PAIR
    r, c = np.indices((n, n))
    keep = (r // gd.CHUNK == c // gd.CHUNK) & (r > c)
    systems = [jnp.asarray(np.where(keep, scale * np.random.default_rng(seed).standard_normal(
        (n, n)), 0.0), jnp.float32) for seed in (0, 1)]
    got = jax.jit(gd._inverses)(systems)
    for A, T in zip(systems, got):
        want = np.linalg.inv(np.eye(n) + np.asarray(A, np.float64))
        before = np.asarray(jax.jit(_inverses_over_all_rows)(A), np.float64)
        T = np.asarray(T, np.float64)
        size = np.abs(want).max()
        assert np.abs(T - want).max() <= max(1.5 * np.abs(before - want).max(), 1e-6 * size)
        assert np.abs(T - before).max() <= 1e-5 * size
        np.testing.assert_array_equal(T[r // gd.CHUNK != c // gd.CHUNK], 0.0)
        np.testing.assert_array_equal(T[r < c], 0.0)


def test_later_and_spread_are_each_others_inverse_on_the_later_halves():
    """From 8 positions up `_later` takes the later half of every block of
    2h, in order, and `_spread` puts the rows back with zeros between;
    under a sublane tile both pass their argument whole."""
    x = jnp.arange(gd._PAIR * 3, dtype=jnp.float32).reshape(gd._PAIR, 3)
    i = np.arange(gd._PAIR)
    for h in (32, 16, 8):
        later = (i // h) % 2 == 1
        np.testing.assert_array_equal(np.asarray(gd._later(x, h)), np.asarray(x)[later])
        np.testing.assert_array_equal(np.asarray(gd._spread(gd._later(x, h), h)),
                                      np.where(later[:, None], np.asarray(x), 0.0))
    for h in (4, 2, 1):
        assert gd._later(x, h) is x and gd._spread(x, h) is x


def test_no_decay_and_full_writes_are_the_plain_delta_rule():
    """g = 0 and beta = 1: S_t = S_{t-1} + k_t (v_t - S_{t-1}^T k_t)^T, which
    with unit keys stores v_t exactly under k_t."""
    (q, k, v, _, _), _ = inputs(150, 1.0)
    zeros, ones = jnp.zeros((B, H, 150)), jnp.ones((B, H, 150))

    def delta_rule(q, k, v):
        def step(S, xs):
            q_t, k_t, v_t = xs
            S = S + jnp.einsum("bhk,bhv->bhkv", k_t, v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
            return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)
        xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v))
        return jnp.moveaxis(jax.lax.scan(step, jnp.zeros((B, H, DK, DV)), xs)[1], 0, 2)

    got = gd.gated_delta_rule(q, k, v, zeros, ones)
    np.testing.assert_allclose(np.asarray(got), np.asarray(delta_rule(q, k, v)), rtol=2e-5, atol=2e-6)
    # read back with the key just written: the value just written
    back = gd.gated_delta_rule(k, k, v, zeros, ones)
    np.testing.assert_allclose(np.asarray(back), np.asarray(v), rtol=1e-4, atol=1e-5)


def test_no_write_is_pure_decay():
    """beta = 0: the state only decays, and from zero it stays zero; with
    one write at position 0 and none after, o_t = exp(g_1 + .. + g_t) x
    what position 0 stored."""
    (q, k, v, g, _), _ = inputs(150, 1.0)
    assert float(jnp.abs(gd.gated_delta_rule(q, k, v, g, jnp.zeros((B, H, 150)))).max()) == 0.0
    beta = jnp.zeros((B, H, 150)).at[:, :, 0].set(1.0)
    got = gd.gated_delta_rule(q, k, v, g, beta)
    decay = jnp.exp(jnp.cumsum(g.at[:, :, 0].set(0.0), axis=-1))               # [B, H, T]
    stored = jnp.einsum("bhtk,bhk->bht", q, k[:, :, 0])[..., None] * v[:, :, :1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(decay[..., None] * stored),
                               rtol=1e-4, atol=1e-6)


def test_a_position_reads_nothing_after_it():
    args, _ = inputs(150, 2.0)
    base = gd.gated_delta_rule(*args)
    moved = [a.at[:, :, 100:].add(1.0) if a.ndim == 4 else a.at[:, :, 100:].add(-0.5) for a in args]
    again = gd.gated_delta_rule(*moved)
    assert float(jnp.abs(again[:, :, :100] - base[:, :, :100]).max()) == 0.0
    assert float(jnp.abs(again[:, :, 100:] - base[:, :, 100:]).max()) > 1e-3


def kernels_and_loops(jaxpr, sizes=None):
    """(`pallas_call`s, loops outside them) of a jaxpr, every sub-jaxpr but the
    kernels' own walked; `sizes` collects the sizes of what the equations make."""
    kernels, loops = 0, []
    for eqn in jaxpr.eqns:
        if sizes is not None:
            sizes.extend(v.aval.size for v in eqn.outvars if hasattr(v.aval, "size"))
        if eqn.primitive.name == "pallas_call":
            kernels += 1
            continue
        if eqn.primitive.name in ("scan", "while"):
            loops.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            k, l = kernels_and_loops(sub, sizes)
            kernels, loops = kernels + k, loops + l
    return kernels, loops


def test_nothing_it_builds_is_t_by_t_and_the_chunks_are_walked_inside_the_kernels():
    """At 1,024 positions a forward is ONE `pallas_call` and a gradient two
    (the forward that hands out the chunks' states, the backward), no
    `scan` or `while` stands outside them (the walk over the chunks is the
    kernels' grid), and the largest array of forward and backward is a
    small multiple of the inputs (the states the chunks start from:
    T / 64 x dk x dv a head), nothing T x T."""
    T = 1024
    args, w = inputs(T, 2.0)
    assert kernels_and_loops(jax.make_jaxpr(gd.gated_delta_rule)(*args).jaxpr) == (1, [])
    sizes = []
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: (gd.gated_delta_rule(*a) * w).sum(),
                                    argnums=(0, 1, 2, 3, 4)))(*args)
    assert kernels_and_loops(jaxpr.jaxpr, sizes) == (2, [])
    assert max(sizes) <= B * H * T * max(gd.CHUNK, DK + DV) * 2 < B * H * T * T

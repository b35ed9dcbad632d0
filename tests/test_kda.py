"""ops/kda.py on the CPU: the chunked rule of Kimi Delta Attention (a delta
rule whose decay is a vector over the key's channels) against the
position-by-position rule, forward and every gradient (`jax.grad` of the
plain recurrence) at 1e-5 relative in float32, at sequences of several
chunks and at ones that are no multiple of the chunk; under decays so
strong that the naive split (k . e^c)(k . e^-c)^T overflows within ONE
chunk; what it reduces to when the decay is the same on every channel
(ops/gated_delta.py's rule); and that nothing in it grows with T x T."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import kda

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

# 192 = three whole chunks of 64; 150 = two and 22 positions; 40 = less than one
SHAPES = pytest.mark.parametrize("T", [192, 150, 40])


@SHAPES
def test_chunked_forward_is_the_position_by_position_rule(T):
    args, _ = inputs(T)
    got, want = RULE(*args), recurrent_kda_rule(*args)
    assert got.shape == (B, H, T, DV) and got.dtype == jnp.float32
    assert_close(got, want)


@SHAPES
def test_chunked_backward_is_jax_grad_of_the_plain_recurrence(T):
    """q, k, v, g and beta each: the transpose of the scan over chunks
    against reverse-mode through the scan over positions, with and
    without the block's `jax.checkpoint` around the rule."""
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
            assert " exp " not in str(eqn.params.get("jaxpr", "")), eqn.primitive  # the scan
            out = eqn.primitive.bind(*vals, **eqn.params)
            out = out if eqn.primitive.multiple_results else [out]
        env.update(zip(eqn.outvars, out))
    return [v.val if isinstance(v, Literal) else env[v] for v in jaxpr.outvars]


def test_every_exponent_formed_is_of_a_number_that_is_not_positive():
    """Read off the jaxpr: each `exp` of the chunked rule is evaluated on
    the strong decays, and its operand's largest value is <= 0 (the
    scan's body forms none: all of them stand before it)."""
    args, _ = inputs(192, seed=1)
    args = strong(args)
    closed = jax.make_jaxpr(kda.kda_rule)(*args)
    found = []
    out, = _largest_exp_operands(closed.jaxpr, closed.consts, list(args), found)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(kda.kda_rule(*args)))
    assert len(found) >= 6 and max(found) <= 0.0, found


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


def test_nothing_grows_with_the_square_of_the_sequence():
    """No array of the lowered rule has T x T elements: the largest is the
    columns' four references, [T / 64, 4, 64, dk] a head."""
    T = 1024
    args, _ = inputs(T, shape=(1, 1, DK, DV))
    sizes = [np.prod(v.aval.shape) for eqn in jax.make_jaxpr(kda.kda_rule)(*args).jaxpr.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes) <= T * 4 * max(DK, DV, 64) < T * T

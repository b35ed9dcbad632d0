"""The routers' pick of the chosen experts' scores
(`models/moe.py::_of_chosen`): `take_along_axis` value for value and
gradient for gradient, with no gather forward and no scatter backward;
and the routers' product, float32 at `highest` whatever the stream's
dtype. Oracle: `jnp.take_along_axis` itself."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cca, mla, moe

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
# (experts, chosen a token): ZAYA1 (one a token: the gather itself), GLM, Mellum2, Keye and
# SDAR, Laguna
SHAPES = [(16, 1), (64, 4), (64, 8), (128, 8), (256, 10)]


def _gather(values, chosen):
    return jnp.take_along_axis(values, chosen, axis=-1)


def _scores(experts, top_k, rows=96, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    probs = jax.nn.softmax(3.0 * jax.random.normal(k[0], (rows, experts), F32), axis=-1)
    bias = 0.01 * jax.random.normal(k[1], (experts,), F32)
    chosen = jax.lax.top_k(probs + bias, top_k)[1]
    return probs, chosen, jax.random.normal(k[2], (rows, top_k), F32)


def _same(a, b):
    return (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("experts,top_k", SHAPES)
def test_of_chosen_is_take_along_axis_value_for_value(experts, top_k):
    probs, chosen, _ = _scores(experts, top_k)
    ours = jax.jit(moe._of_chosen)(probs, chosen)
    assert ours.shape == (96, top_k) and ours.dtype == F32
    assert _same(ours, _gather(probs, chosen))
    # a score that is no finite number is selected like any other, and spoils no neighbour
    odd = probs.at[:, 0].set(jnp.inf).at[:, 1].set(-1e30)
    assert _same(moe._of_chosen(odd, chosen), _gather(odd, chosen))


@pytest.mark.parametrize("experts,top_k", SHAPES)
def test_of_chosen_is_take_along_axis_gradient_for_gradient(experts, top_k):
    probs, chosen, g = _scores(experts, top_k, seed=1)
    ours = jax.jit(lambda v: jax.vjp(lambda v: moe._of_chosen(v, chosen), v)[1](g)[0])(probs)
    theirs = jax.vjp(lambda v: _gather(v, chosen), probs)[1](g)[0]
    assert _same(ours, theirs)


def test_a_pair_chosen_twice_takes_both_cotangents_as_a_scatter_add_would():
    probs, _, g = _scores(8, 3, rows=4, seed=2)
    chosen = jnp.asarray([[1, 1, 2], [0, 5, 0], [7, 7, 7], [3, 4, 5]], jnp.int32)
    ours = jax.vjp(lambda v: moe._of_chosen(v, chosen), probs)[1](g)[0]
    theirs = jax.vjp(lambda v: _gather(v, chosen), probs)[1](g)[0]
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=1e-6, atol=0)
    assert _same(moe._of_chosen(probs, chosen), _gather(probs, chosen))


def _primitives(jaxpr, found=None):
    """{primitive: [([operand (shape, dtype)], its precision)]}, through every sub-jaxpr."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        operands = [(v.aval.shape, v.aval.dtype) for v in eqn.invars if hasattr(v.aval, "dtype")]
        found.setdefault(eqn.primitive.name, []).append((operands, eqn.params.get("precision")))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def test_one_expert_a_token_keeps_the_gather_and_two_do_not():
    probs, chosen, _ = _scores(16, 2, seed=3)
    names = lambda c: set(_primitives(jax.make_jaxpr(moe._of_chosen)(probs, c).jaxpr))  # noqa: E731
    assert "gather" in names(chosen[:, :1]) and "gather" not in names(chosen)


# a router of each kind that weighs by a score it did NOT choose by (score + bias chose)
ROUTERS = {
    "softmax_bias": dataclasses.replace(moe.MOE_TINY, n_experts=8, top_k=3, n_layers=1,
                                        selection_bias=True),
    "sigmoid_bias": dataclasses.replace(mla.GLM_LITE_TINY, n_layers=2),
    # (two a token: with ZAYA1's own ONE the pick stays a gather, `_of_chosen`'s docstring)
    "mlp": dataclasses.replace(cca.ZAYA_TINY, n_layers=1, top_k=2),
}


def _layer(cfg):
    lp = jax.tree.map(lambda leaf: leaf[-1], moe.expert_params(cfg, jax.random.key(5)))
    lp["router_bias"] = 0.01 * jax.random.normal(jax.random.key(6), lp["router_bias"].shape)
    return lp


@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_a_router_gathers_no_score_forward_and_scatters_none_backward(router, monkeypatch):
    cfg = ROUTERS[router]
    lp, x = _layer(cfg), jnp.zeros((2, 16, cfg.d_model), BF16)
    scores = ((32, cfg.n_experts), F32)

    def traced():
        # the chosen experts' weights reach the output alone: the layer's output, differentiated
        out = lambda lp, x: jnp.sum(moe.moe_ffn(x, lp, cfg)[0].astype(F32))  # noqa: E731
        prims = _primitives(jax.make_jaxpr(jax.grad(out, argnums=(0, 1)))(lp, x).jaxpr)
        assert "top_k" in prims
        gathered = [operands[0] for operands, _ in prims.get("gather", [])]
        scattered = [name for name in prims if name.startswith("scatter")]
        return scores in gathered, scattered

    # (the dispatch's and the combine's gathers stay, and are gathers in both directions)
    assert traced() == (False, [])
    monkeypatch.setattr(moe, "_of_chosen", _gather)   # the check sees the difference
    gathers_scores, scattered = traced()
    assert gathers_scores and scattered


@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_moe_ffn_is_what_it_was_with_take_along_axis(router, monkeypatch):
    cfg = ROUTERS[router]
    lp = _layer(cfg)
    x = jax.random.normal(jax.random.key(8), (2, 32, cfg.d_model), F32).astype(cfg.dtype)

    def run():   # ONE program a side: taken bare, every operation of the layer is a compile
        def loss(lp, x):
            out, stats, _ = moe.moe_ffn(x, lp, cfg)
            return jnp.sum(jnp.square(out.astype(F32))) + stats["balance_loss"], stats
        (value, stats), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(lp, x)
        return value, stats["tokens_per_expert"], grads

    ours = run()
    monkeypatch.setattr(moe, "_of_chosen", _gather)
    theirs = run()
    assert _same(ours[1], theirs[1])
    for a, b in zip(jax.tree.leaves((ours[0], ours[2])), jax.tree.leaves((theirs[0], theirs[2]))):
        a, b = np.asarray(a.astype(F32), np.float64), np.asarray(b.astype(F32), np.float64)
        assert np.abs(a - b).max() <= 1e-6 * max(np.abs(b).max(), 1e-30)


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bfloat16_stream", "float32_stream"])
def test_the_routers_product_is_float32_at_highest_whatever_the_stream(dtype):
    """PR 59 measured it on the chip: at `highest` the compiler multiplies bfloat16 rows AS
    THEY STAND by the float32 weight's three terms (0.084 ms at [16384, 2304] x [2304, 64]
    where one bfloat16 pass takes 0.028), so the cast costs nothing and there is nothing to
    spell by hand; a weight rounded to bfloat16 is another result (0.007 off at logits of 5)."""
    cfg = ROUTERS["softmax_bias"]
    lp, x = _layer(cfg), jnp.zeros((1, 16, cfg.d_model), dtype)
    # the statistics alone: the router, the counts and nothing of an expert
    router = jax.make_jaxpr(lambda x: moe.moe_ffn(x, lp, cfg)[1]["balance_loss"])(x)
    dots = _primitives(router.jaxpr)["dot_general"]
    assert [p for operands, p in dots if [dt for _, dt in operands] == [F32, F32]
            and p in (HIGHEST, (HIGHEST, HIGHEST))], dots
    assert all(dt != BF16 for operands, _ in dots for _, dt in operands)

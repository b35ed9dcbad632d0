"""A small share of the experts: everything after the sort over a static
bound C on the HELD rows (`moe.held_rows_bound`), and the same block over
all N * top_k rows when a step's routing puts more than C pairs on the
held experts. Oracle: the same `moe_ffn` with no compact path built (the
bound taken away), which is the program every share ran before; the two
differ by the order of a float32 sum of at most top_k terms.

The sum of the held rows into their tokens has TWO forms, chosen from
(N, C, D) alone (`moe._sum_is_linear`, PR 44): every case of the compact
path runs over both, the form forced through that private rule, and the
linear form is held to the one-hot product directly."""

import contextlib
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import obs
from ray_tpu.models import moe

D, F = 64, 128
BASE = dataclasses.replace(moe.MOE_TINY, dtype=jnp.float32, d_model=D, d_ff=F, n_layers=1)
# (experts, held, top_k, tokens): Laguna's, GLM-4.7-Flash's and ZAYA1's shares
SHARES = {"laguna": (256, 8, 10, 256), "glm": (64, 8, 4, 512), "zaya": (16, 8, 1, 1024)}
ROUTERS = {
    "linear": dict(),
    "sigmoid": dict(router_score="sigmoid", routed_scaling=1.8, shared_d_ff=F),
    "biased_softmax": dict(routed_scaling=2.5, shared_d_ff=F),
}
STATS = ("tokens_per_expert", "dropped_pairs", "imbalance", "balance_loss", "z_loss",
         "pairs_elsewhere")


def _config(share, router="linear", **more):
    E, held, K, _ = SHARES[share]
    return dataclasses.replace(BASE, n_experts=E, top_k=K, experts_held=held,
                               first_expert_held=held, **ROUTERS[router], **more)


def _layer(cfg, router, seed=0):
    lp = jax.tree.map(lambda a: a[0], moe.expert_params(cfg, jax.random.key(seed)))
    if router != "linear":  # a selection bias that moves choices, as a balanced one does
        lp["router_bias"] = 0.02 * jax.random.normal(jax.random.key(seed + 1), (cfg.n_experts,))
    return lp


def _tokens(share, seed=2):
    return jax.random.normal(jax.random.key(seed), (2, SHARES[share][3] // 2, D))


def _value_stats_grads(cfg, x, lp):
    """(out, statistics, gradients of a scalar of `out` in x and in every
    float leaf of the layer's parameters)."""
    weight = jax.random.normal(jax.random.key(9), x.shape)

    def f(x, lp):
        out, stats, _ = moe.moe_ffn(x, lp, cfg)
        return (out.astype(jnp.float32) * weight).sum(), (out, stats)

    (_, (out, stats)), grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(x, lp)
    return out, stats, grads


def _without_the_compact_path():
    return mock.patch.object(moe, "held_rows_bound", lambda *a: None)


FORMS = {"product": False, "linear": True}


def _sites(name):
    return obs.layer_counters().get(name, {"count": 0})["count"]


@contextlib.contextmanager
def _built_with(form):
    """Every site traced inside sums the held rows in ONE form, whatever the
    rule says of its shape; the blocks' inner jit keeps one trace a shape
    of the function it wraps, so it wraps a fresh one here. Yields
    `only_that_form_was_built()`."""
    before = {name: _sites("moe.sum." + name) for name in FORMS}

    def only_that_form_was_built():
        built = {name: _sites("moe.sum." + name) - before[name] for name in FORMS}
        return built.pop(form) > 0 and not any(built.values())

    with mock.patch.object(moe, "_sum_is_linear", lambda *shape: FORMS[form]), \
            mock.patch.object(moe, "_held_or_all_once",
                              jax.jit(lambda *a: moe._held_or_all(*a), static_argnums=(0,))):
        yield only_that_form_was_built


@pytest.fixture(params=sorted(FORMS))
def form(request):
    with _built_with(request.param) as only_that_form_was_built:
        yield only_that_form_was_built


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= rtol * max(np.abs(want).max(), 1e-30)


def test_the_bound_follows_from_the_shapes():
    # the benchmark's share cells: 2,560 of 40,960 and 8,192 of 32,768 rows
    assert moe.held_rows_bound(4096 * 10, 8, 256) == 2560
    assert moe.held_rows_bound(8192 * 4, 8, 64) == 8192
    # a share under a thirty-second (`solar-open2-train-8k`'s fortieth) takes 4 x the uniform
    # 1,639 rows, where one repeated token's 1,190 rows are most of a share
    assert moe.held_rows_bound(8192 * 8, 8, 320) == 6656
    # a half share would not halve the rows, every expert held has no rows elsewhere
    assert moe.held_rows_bound(8192, 8, 16) is None
    assert moe.held_rows_bound(6 * 4096 * 8, 64, 64) is None
    # a multiple of the kernels' row tile, never more than half the pairs
    for pairs, held, experts in [(1280, 8, 256), (2048, 8, 64), (1024, 1, 64), (512, 1, 64)]:
        bound = moe.held_rows_bound(pairs, held, experts)
        assert bound is None or (bound % 512 == 0 and 2 * bound <= pairs
                                 and bound >= 2 * pairs * held / experts)
    assert moe.held_rows_bound(512, 1, 64) is None


@pytest.mark.parametrize("shape,linear", [
    ((4096, 10, 2560, 3072), True), ((8192, 4, 8192, 2048), True),
    ((8192, 8, 16384, 2048), True), ((16384, 8, 32768, 2304), True),
    ((8192, 8, 6656, 4096), True), ((512, 4, 512, 64), False), ((1024, 4, 1024, 64), False)],
    ids=["laguna-train", "glm47f-train", "keye-train-8k", "mellum2-train-16k",
         "solar-open2-train-8k", "tiny", "this_file_s_band"])
def test_the_form_of_the_sum_follows_from_the_shapes(shape, linear):
    """(N, top_k, C, D) of the benchmark's small shares in bf16, each as the two forms
    were measured alone on the chip (PERF.md, PR 63: with a window of 256 C / N rows the
    band is 2.2 times the product's speed in `laguna-train`, whose one window of 256 x
    top_k rows was all of C and lost to it by 0.353 to 0.406 ms in PR 44, and 4.6 to 7.8
    times in the other six), and nothing but the shapes: the rule takes no configuration.
    The product where the whole [N, C] matrix is a few MXU passes."""
    assert moe._sum_is_linear(*shape, itemsize=2) is linear


def _rows_of_a_share(routing, dtype, C=1536, N=576, most=4, seed=3):
    """y [C, D] in expert order and the token of each row: tokens with 0, 1,
    .. `most` held rows, then (past the held pairs) rows of pairs routed
    elsewhere, which `_zero_tail` made zero; N is no multiple of the band's
    block of tokens and the last window is pushed back inside y."""
    rng = np.random.default_rng(seed)
    held = {"half_of_C": C // 2, "exactly_C": C, "none_held": 0}[routing]
    per_token = np.zeros(N, np.int64)
    per_token[: held // most] = most             # `most` rows each ...
    per_token[held // most: held // most + held % most] = 1   # ... and one each for the rest
    per_token = rng.permutation(per_token)       # the other tokens have none
    tok = rng.permutation(np.repeat(np.arange(N), per_token))
    elsewhere = rng.permutation(np.repeat(np.arange(N), most - per_token))[: C - held]
    tok = np.concatenate([tok, elsewhere]).astype(np.int32)   # a token has `most` pairs in all
    y = rng.standard_normal((C, D)).astype(np.float32) * np.exp(rng.uniform(-3, 3, (C, 1)))
    y[held:] = 0
    return jnp.asarray(y, dtype), jnp.asarray(tok), per_token, (N, most)


def _summed(direction, y, tok, pairs):
    if direction == "from_held_rows_forward":
        return moe._from_held_rows(y, tok, pairs)
    xt = jnp.zeros((pairs[0], D), y.dtype)
    (d_xt,) = jax.vjp(lambda xt: moe._to_held_rows(xt, tok, pairs), xt)[1](y)
    return d_xt


@pytest.mark.parametrize("routing", ["half_of_C", "exactly_C", "none_held"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("direction", ["from_held_rows_forward", "to_held_rows_backward"])
def test_linear_sum_of_the_held_rows_is_the_one_hot_product(direction, dtype, routing):
    """Both directions of the pair (`moe.combine`'s forward, `moe.dispatch`'s
    backward): the linear form sums the same rows in float32 and rounds once,
    so it differs from the product by the order of at most `most` float32
    additions; a token with no held row reads exact zero, a token with one
    reads that row, rows past the held pairs add nothing."""
    y, tok, per_token, pairs = _rows_of_a_share(routing, dtype)
    N = pairs[0]
    got, sums = {}, {}
    for name in FORMS:
        with _built_with(name) as only_that_form_was_built:
            got[name] = np.asarray(_summed(direction, y, tok, pairs), np.float32)
            assert only_that_form_was_built()
            # the same rows' float32 sum, the form's own order, before the one rounding
            sums[name] = _summed(direction, y.astype(jnp.float32), tok, pairs)
    lin, prod = got["linear"], got["product"]
    assert lin.shape == (N, D) and not lin[per_token == 0].any()
    rows = np.asarray(y, np.float32)[: int(per_token.sum())]
    toks = np.asarray(tok)[: len(rows)]
    for t in np.flatnonzero(per_token == 1)[:8]:
        np.testing.assert_array_equal(lin[t], rows[toks == t][0])
    magnitude = np.zeros((N, D))
    np.add.at(magnitude, toks, np.abs(rows, dtype=np.float64))
    reordering = 8 * np.finfo(np.float32).eps * magnitude   # of at most eight additions
    f32 = {name: np.asarray(s, np.float32) for name, s in sums.items()}
    assert (np.abs(f32["linear"].astype(np.float64) - f32["product"]) <= reordering).all()
    if dtype == "float32":
        np.testing.assert_array_equal(lin, f32["linear"])
        assert routing == "none_held" or np.abs(lin).max() > 1
    else:   # ONE rounding of the float32 sum: no bfloat16 accumulation
        np.testing.assert_array_equal(lin, np.asarray(sums["linear"].astype(jnp.bfloat16),
                                                      np.float32))
        same = f32["linear"] == f32["product"]
        assert same.mean() > 0.9
        np.testing.assert_array_equal(lin[same], prod[same])


# -- the band's window follows a block's run (PR 63) --------------------------------------
#
# 1,024 tokens = four blocks of 256, top-4, C = 1,024 rows: W = 256 x C / N = 256 rows, a
# quarter of the 256 x top_k rows a block owns at worst.
BAND = dict(N=1024, K=4, C=1024, W=256)
ROUTINGS = ("uniform", "zipf", "adversarial", "run_ends_at_C", "empty_block",
            "past_the_held_pairs")


def _routed(routing, dtype, seed=11):
    """tok [C] as `moe._held_rows` hands it (the rows in EXPERT order, so in no order of
    tokens; N, no token, past the held pairs), y [C, D] with zeros there, and the held rows
    of each block of 256 tokens."""
    N, K, C = BAND["N"], BAND["K"], BAND["C"]
    rng = np.random.default_rng(seed)
    blocks = N // 256

    def drawn(n, p=None, tokens=N):   # n of the tokens' K pairs each, no pair twice
        p = None if p is None else np.repeat(p, K) / (K * p.sum())
        return rng.choice(tokens * K, size=n, replace=False, p=p) // K

    if routing in ("uniform", "past_the_held_pairs"):
        tok = drawn(C // 2 if routing == "uniform" else 300)
    elif routing == "zipf":   # the early tokens take most pairs: block 0 holds several windows
        tok = drawn(C // 2, 1.0 / np.arange(1, N + 1) ** 1.1)
    elif routing == "adversarial":   # EVERY pair of block 1 held, 256 x top_k rows, and no other
        tok = np.repeat(np.arange(256, 512), K)
    elif routing == "run_ends_at_C":   # every row held; the last block's run is the last 724
        tok = np.concatenate([drawn(300, tokens=N - 256), N - 256 + drawn(C - 300, tokens=256)])
    elif routing == "empty_block":
        tok = drawn(C // 2)
        tok = tok[(tok < 512) | (tok >= 768)]
    tok = rng.permutation(tok)
    held = len(tok)
    runs = np.bincount(tok // 256, minlength=blocks)
    tok = np.concatenate([tok, np.full(C - held, N)]).astype(np.int32)
    y = rng.standard_normal((C, D)).astype(np.float32) * np.exp(rng.uniform(-3, 3, (C, 1)))
    y[held:] = 0
    return jnp.asarray(y, dtype), jnp.asarray(tok), runs


@functools.cache
def _jitted_sum(direction, form):
    """One trace a (direction, form) and dtype for every routing: the shapes are the same."""
    return jax.jit(lambda y, tok: _summed(direction, y, tok, (BAND["N"], BAND["K"])))


def _sum_in(form, direction, y, tok):
    with mock.patch.object(moe, "_sum_is_linear", lambda *shape: FORMS[form]):
        return _jitted_sum(direction, form)(y, tok)


@pytest.mark.parametrize("direction", ["from_held_rows_forward", "to_held_rows_backward",
                                       "the_gathers"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_band_follows_a_blocks_run(routing, dtype, direction):
    """The band whose window is the slack times a block's AVERAGE run, a block whose run is
    longer taking more windows, against the one-hot product: whatever the routing the same
    rows are summed, each once (a window pushed back inside y, as the one window of 256 x
    top_k rows was, would sum the rows of `run_ends_at_C`'s last block twice), in float32
    and rounded once; the statistic says how many windows the longest run took."""
    N, K, C, W = BAND["N"], BAND["K"], BAND["C"], BAND["W"]
    assert moe._band(N, K, C) == (256, W)
    y, tok, runs = _routed(routing, dtype)
    trips = int(moe._band_trips(tok, (N, K), C))
    assert trips == max(1, -(-runs.max() // W))
    assert trips == {"uniform": 1, "adversarial": K * 256 // W, "empty_block": 1,
                     "past_the_held_pairs": 1}.get(routing, trips)
    assert routing not in ("zipf", "run_ends_at_C") or trips > 1
    assert (runs.min() == 0) == (routing in ("adversarial", "empty_block"))
    if routing == "run_ends_at_C":   # the last run ends where y ends, and passes two windows
        assert runs.sum() == C and runs[-1] > 2 * W
    if direction == "the_gathers":   # a row that names no token reads the last token's
        clipped = np.minimum(np.asarray(tok), N - 1)
        xt = jax.random.normal(jax.random.key(3), (N, D)).astype(y.dtype)
        rows = moe._to_held_rows(xt, tok, (N, K))
        np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                      np.asarray(xt, np.float32)[clipped])
        (d_y,) = jax.vjp(lambda y: moe._from_held_rows(y, tok, (N, K)), y)[1](xt)
        np.testing.assert_array_equal(np.asarray(d_y, np.float32),
                                      np.asarray(xt, np.float32)[clipped])
        return
    band = np.asarray(_sum_in("linear", direction, y, tok), np.float32)
    prod = np.asarray(_sum_in("product", direction, y, tok), np.float32)
    held = int(runs.sum())
    rows, toks = np.asarray(y, np.float32)[:held], np.asarray(tok)[:held]
    want, magnitude = np.zeros((N, D)), np.zeros((N, D))
    np.add.at(want, toks, rows.astype(np.float64))
    np.add.at(magnitude, toks, np.abs(rows, dtype=np.float64))
    assert band.shape == (N, D) and not band[magnitude.sum(axis=1) == 0].any()
    if dtype == "float32":   # the order of at most top_k float32 additions
        assert (np.abs(band - want) <= 8 * np.finfo(np.float32).eps * magnitude).all()
        assert (np.abs(band - prod) <= 8 * np.finfo(np.float32).eps * magnitude).all()
        assert held == 0 or np.abs(band).max() > 1
    else:   # ONE rounding of a float32 sum: a bfloat16 accumulation would read 2^-8 a row
        assert (np.abs(band - want) <= 2.0 ** -8 * np.abs(want) + 1e-30).all()
        assert (band == prod).mean() > 0.99


CELLS = {   # (N, top_k, held, experts, D) -> W
    "mellum2-train-16k": ((16384, 8, 8, 64, 2304), 512),
    "sdar-train-8k": ((16384, 8, 16, 128, 2048), 512),
    "keye-train-8k": ((8192, 8, 16, 128, 2048), 512),
    "solar-open2-train-8k": ((8192, 8, 8, 320, 4096), 256),
    "twotower-train-8k": ((8192, 6, 8, 128, 2688), 256),
    "glm47f-train": ((8192, 4, 8, 64, 2048), 256),
    "laguna-train": ((4096, 10, 8, 256, 3072), 256),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_window_follows_from_the_shapes(cell):
    """W = 256 x C / N, the slack times the rows a block of 256 tokens owns on average,
    rounded up to 128: a function of N, top_k and C and of nothing else; an eighth to a
    quarter of the 256 x top_k rows (or C) the window was."""
    (N, K, held, E, _), W = CELLS[cell]
    C = moe.held_rows_bound(N * K, held, E)
    assert moe._band(N, K, C) == (256, W)
    slack = 2 if 32 * held >= E else 4
    assert W % 128 == 0 and W - 128 < slack * 256 * K * held / E <= W
    assert W <= min(C, 256 * K) // 4


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("share", sorted(SHARES))
def test_compact_block_is_the_block_over_all_rows(share, router, form):
    """Output, every statistic and every gradient (x, the three expert
    weights, the router, the shared expert's) are the full path's, with
    either form of the sum of the held rows."""
    cfg = _config(share, router)
    lp, x = _layer(cfg, router), _tokens(share)
    before = _sites("moe.compact")
    out, stats, grads = _value_stats_grads(cfg, x, lp)
    built = _sites("moe.compact") - before
    with _without_the_compact_path():
        want_out, want_stats, want_grads = _value_stats_grads(cfg, x, lp)
    E, held, K, N = SHARES[share]
    if share == "zaya":
        assert not built and "compact" not in stats
    else:
        assert built and int(stats["compact"]) == 1 and form()
        assert 1 <= int(stats["band_trips"]) <= K   # no block needs more than 256 x top_k rows
        assert 0 < N * K - int(stats["pairs_elsewhere"]) <= moe.held_rows_bound(N * K, held, E)
    assert "compact" not in want_stats
    for key in STATS:
        np.testing.assert_array_equal(np.asarray(stats[key]), np.asarray(want_stats[key]), key)
    assert int(stats["dropped_pairs"]) == 0 and int(stats["tokens_per_expert"].sum()) == N * K
    _close(out, want_out, 1e-6)
    _close(grads[0], want_grads[0], 1e-5)
    assert set(grads[1]) == set(want_grads[1]) >= {"w_gate", "w_up", "w_down", "router"}
    for name in grads[1]:
        _close(grads[1][name], want_grads[1][name], 1e-5)
    for name in ("w_gate", "w_up", "w_down", "router"):
        assert np.abs(np.asarray(grads[1][name])).max() > 0, name


def test_compact_block_in_bfloat16_rounds_a_float32_sum_as_the_full_one_does(form):
    cfg = _config("glm", "sigmoid", dtype=jnp.bfloat16)
    lp, x = _layer(cfg, "sigmoid"), _tokens("glm").astype(jnp.bfloat16)
    out, stats, grads = _value_stats_grads(cfg, x, lp)
    with _without_the_compact_path():
        want_out, _, want_grads = _value_stats_grads(cfg, x, lp)
    assert int(stats["compact"]) == 1 and out.dtype == jnp.bfloat16 and form()
    _close(out, want_out, 2 ** -7)  # one bfloat16 rounding of a sum taken in another order
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        _close(got, want, 2 ** -6)


def _two_kinds_of_token(cfg, n_held_tokens, n_tokens):
    """A layer and tokens whose routing is known: the first `n_held_tokens`
    tokens choose the top_k FIRST held experts, every other token top_k
    experts that are not held."""
    K, first = cfg.top_k, cfg.first_expert_held
    lp = _layer(cfg, "linear")
    v = jax.random.normal(jax.random.key(5), (D,))
    v = v / jnp.linalg.norm(v)
    router = 0.01 * np.asarray(lp["router"])
    router[:, first:first + K] += 8.0 * np.asarray(v)[:, None]
    elsewhere = (first + cfg.n_held) % cfg.n_experts
    router[:, elsewhere:elsewhere + K] -= 8.0 * np.asarray(v)[:, None]
    lp["router"] = jnp.asarray(router)
    sign = jnp.where(jnp.arange(n_tokens) < n_held_tokens, 1.0, -1.0)
    noise = 0.1 * jax.random.normal(jax.random.key(6), (n_tokens, D))
    x = (sign[:, None] * v[None, :] + noise - (noise @ v)[:, None] * v[None, :])
    return lp, x.reshape(2, n_tokens // 2, D)


@pytest.mark.parametrize("held_tokens,compact", [(128, 1), (129, 0), (512, 0), (0, 1)],
                         ids=["exactly_C", "C_plus_one_token", "every_pair_held", "none_held"])
def test_a_routing_past_the_bound_runs_over_all_rows_and_loses_no_pair(held_tokens, compact, form):
    """GLM's share (top-4, 8 of 64 held) over 512 tokens: C = 512 rows of
    2,048. `held_tokens` tokens put all four pairs on held experts."""
    cfg = _config("glm")
    E, held, K, N = SHARES["glm"]
    bound = moe.held_rows_bound(N * K, held, E)
    assert bound == 512
    lp, x = _two_kinds_of_token(cfg, held_tokens, N)
    out, stats, grads = _value_stats_grads(cfg, x, lp)
    assert N * K - int(stats["pairs_elsewhere"]) == held_tokens * K
    assert int(stats["compact"]) == compact == int(held_tokens * K <= bound)
    # W = 256 of C = 512 rows: the 128 held tokens are ONE block's, its run all 512 rows
    assert int(stats["band_trips"]) == {128: 2, 0: 1}.get(held_tokens, 0)
    assert int(stats["dropped_pairs"]) == 0 and form()
    with _without_the_compact_path():
        want_out, want_stats, want_grads = _value_stats_grads(cfg, x, lp)
    for key in STATS:
        np.testing.assert_array_equal(np.asarray(stats[key]), np.asarray(want_stats[key]), key)
    _close(out, want_out, 1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        _close(got, want, 1e-5)
    if held_tokens:  # the held tokens' rows are the experts' outputs, not zeros
        assert np.abs(np.asarray(out).reshape(N, D)[:held_tokens]).min(axis=0).max() > 0
    rest = np.asarray(out).reshape(N, D)[held_tokens:]
    assert not rest.any()  # pairs elsewhere contribute zero


@pytest.mark.parametrize("share,held,branches", [
    ("laguna", 8, True), ("glm", 8, True), ("zaya", 8, False), ("glm", None, False)],
    ids=["laguna", "glm", "half_share", "every_expert_held"])
def test_only_a_small_share_traces_a_branch(share, held, branches):
    """`experts_held is None` and a half share keep the program they had:
    no `cond` anywhere in the layer, forward or backward, and no
    `compact` statistic."""
    cfg = dataclasses.replace(_config(share), experts_held=held,
                              first_expert_held=0 if held is None else held)
    lp, x = _layer(cfg, "linear"), _tokens(share)

    def loss(x, lp):
        out, stats, _ = moe.moe_ffn(x, lp, cfg)
        return out.sum(), stats

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(x, lp))
    assert ("cond[" in text) == branches
    stats = jax.eval_shape(loss, x, lp)[1]
    assert ("compact" in stats) == ("band_trips" in stats) == branches
    if branches:
        assert stats["compact"].shape == () and stats["compact"].dtype == jnp.int32


def test_compact_path_keeps_no_array_of_all_pairs_between_forward_and_backward(form):
    """What the forward hands the backward under the decoder's "dots"
    policy: the held rows' gate and up [C, d_ff] by name and nothing of
    N * top_k rows and model or expert width (a `cond` differentiated as
    it stands would keep both branches' residuals, zeros in the one that
    did not run)."""
    from ray_tpu.models import llama

    cfg = dataclasses.replace(_config("glm"), remat=True, remat_policy="dots")
    E, held, K, N = SHARES["glm"]
    bound = moe.held_rows_bound(N * K, held, E)
    lp, x = _layer(cfg, "linear"), _tokens("glm")
    block = llama._remat(lambda x, lp: moe.moe_ffn(x, lp, cfg)[0], cfg)
    _, vjp = jax.vjp(block, x, lp)
    kept = [leaf.shape for leaf in jax.tree.leaves(vjp) if hasattr(leaf, "shape")]
    assert kept.count((bound, F)) == 2 and form(), kept
    wide = [s for s in kept if len(s) >= 2 and s[0] in (N * K,) and s[-1] in (D, F)]
    assert not wide and (N, K, D) not in kept, kept


def test_compact_path_lowers_under_a_mesh(form):
    """Under a mesh the grouped matmuls are `jax.lax.ragged_dot` and the
    partitioner's, as is either form of the sum of the held rows: a train
    step of a small share over dp 2 x ep 2 x tp 2 meets the loss of the
    unsharded one and runs every block compact."""
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import default_rules, tree_shardings
    from ray_tpu.train.step import TrainState, init_sharded_params, make_train_step

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = dataclasses.replace(moe.MOE_TINY, dtype=jnp.float32, n_experts=64, top_k=4,
                              experts_held=8, first_expert_held=16)
    mesh = make_mesh(MeshSpec(dp=2, ep=2, tp=2), devices=jax.devices()[:8])
    rules = default_rules()
    init = lambda: llama.init_params(cfg, jax.random.key(0))  # noqa: E731
    params = init_sharded_params(init, llama.logical_axes(cfg), mesh, rules)
    assert params["layers"]["w_gate"].shape[1] == 8 and "ep" in str(
        params["layers"]["w_gate"].sharding.spec)
    opt = optax.adamw(1e-3)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt,
                           mesh=mesh, rules=rules)
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, size=(8, 65)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]), "targets": jnp.asarray(toks[:, 1:])}
    unsharded = float(llama.loss_fn(init(), batch, cfg))
    batch = jax.device_put(
        batch, tree_shardings(mesh, rules, jax.tree.map(lambda x: ("batch", "seq"), batch)))
    _, metrics = step(TrainState.create(params, opt), batch)
    assert abs(float(metrics["loss"]) - unsharded) < 1e-4 * unsharded
    stats = metrics["stats"]
    assert np.asarray(stats["compact"]).tolist() == [1] * cfg.n_layers and form()
    assert not np.asarray(stats["dropped_pairs"]).any()

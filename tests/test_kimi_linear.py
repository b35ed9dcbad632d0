"""models/kimi_linear.py (Kimi-Linear-48B-A3B: Kimi-Delta-Attention layers
three to one NoPE latent-attention layer at keys wider than values, a
leading dense layer, then sigmoid-routed experts and a shared one) against
the plain reference (chipbench/reference/kimi_linear_decoder.py, which
imports nothing of the program and runs the recurrence position by
position): the stack's plan (a dense layer, whole periods and a TAIL that is
no prefix of the period), the counts by hand, each kind of block (KDA-dense,
KDA-sparse, MLA-sparse), the shares of the experts that add up to the uncut
layer, and the refusals by name. (Logits, loss and every gradient of the one
train path at the tiny preset, each reading of the equations NOT taken told
from the one taken, remat and bf16: tests/test_contract_kimi_linear.py; the
flash kernels at a value width of their own: tests/test_flash_dv.py; the rule
alone: tests/test_kda.py; what every model holds alike and compiles nothing:
tests/test_model_contract.py, a row of model_cases.MODELS.)"""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import kimi_linear_decoder as ref
from model_cases import KIMI_LINEAR, seeded_params
from ray_tpu.models import kimi_linear as kl, llama, mla, moe, solar_open2 as so
from ray_tpu.models.registry import get_model_config

FP32 = KIMI_LINEAR.fp32
FULL = kl.KIMI_LINEAR_48B_A3B
HIGHEST = jax.default_matmul_precision("highest")
SHAPE = KIMI_LINEAR.shape_of(FP32)
K, M = kl.KDA, kl.MLA


def blocks(params):
    """[(a layer's leaves as its block reads them, kind, dense)] of the tiny
    preset, in layer order: the reference's own walk of the tree."""
    return ref.blocks_of(params, SHAPE)


def stream(seed=3, seq=150):
    return 0.5 * jax.random.normal(jax.random.key(seed), (2, seq, FP32.d_model))


def test_the_stack_is_a_dense_layer_whole_periods_and_a_tail_that_ends_inside_one():
    """The published 27: the dense KDA layer, six periods (KDA, KDA, MLA,
    KDA) and the tail (KDA, MLA), which is no prefix of the period; the
    benchmark's five: the dense layer and ONE period; the whole model builds
    abstractly (tree, axes, loss) and is the 48B it is published as."""
    assert FULL.layer_types.count(M) == 7 and FULL.layer_types.count(K) == 20
    assert [l + 1 for l, t in enumerate(FULL.layer_types) if t == M] == [4, 8, 12, 16, 20, 24, 27]
    p = kl.plan(FULL)
    assert p == {"dense": [K], "period": [K, K, M, K], "periods": 6, "tail": [K, M]}
    cell = dataclasses.replace(FULL, n_layers=5)
    assert kl.plan(cell) == {"dense": [K], "period": [K, K, M, K], "periods": 1, "tail": []}
    # a cut inside a period: the whole periods it reaches, then a prefix
    assert kl.plan(dataclasses.replace(FULL, n_layers=11))["tail"] == [K, K]
    assert kl.plan(FP32) == {"dense": [K], "period": [K, M], "periods": 2, "tail": [M]}
    with pytest.raises(ValueError, match="no expert layer"):
        kl.plan(dataclasses.replace(FULL, n_layers=1))
    assert 49.0e9 < FULL.num_params() < 49.3e9
    small = dataclasses.replace(FULL, vocab_size=1024, experts_held=1, max_seq=64)
    params = jax.eval_shape(lambda: llama.init_params(small, jax.random.key(0)))
    period, tail = params["layers"]["period"], params["layers"]["tail"]
    assert period["2"]["wq"].shape == (6, 2304, 32 * 192) and "wq_a" not in period["2"]
    assert period["2"]["wkv_a"].shape == (6, 2304, 576) and period["2"]["kv_a_norm"].shape == (6, 512)
    assert period["2"]["wkv_b"].shape == (6, 512, 32 * 256) and period["2"]["wo"].shape == (6, 4096, 2304)
    assert period["0"]["wf1"].shape == (6, 2304, 128) and period["3"]["dt_bias"].shape == (6, 4096)
    assert tail["0"]["wb"].shape == (2304, 32) and tail["1"]["wq"].shape == (2304, 32 * 192)
    assert params["dense_layers"]["w_up"].shape == (1, 2304, 9216) and "wb" in params["dense_layers"]
    assert params["layers"]["router_bias"].shape == (26, 256)
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    loss, _, stats = jax.eval_shape(lambda p, t: llama.loss_and_weight_fn(
        p, {"tokens": t, "targets": t}, small), params, tokens)
    assert loss.shape == () and stats["tokens_per_expert"].shape == (26, 256)
    axes = llama.logical_axes(small)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_counts_of_parameters_and_operations_are_the_trees_and_the_issues():
    """By hand (ISSUE 64's table): a KDA mixer 39.52M, the MLA mixer 29.11M,
    an expert layer at 8 held 64.30M, the dense SwiGLU 63.70M, the tables at
    20,480 rows 94.37M: 602M at the cell's sizes x 12 B = 6.73 GiB;
    `num_params` is the tree's own count."""
    cell = dataclasses.replace(FULL, n_layers=5, experts_held=8, vocab_size=20480)
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 4 * 4096
           + 32 + 2 * 4096 + 128)
    attn = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304 + 512
    experts = 2304 * 256 + 256 + 3 * 2304 * 1024 * (8 + 1)
    dense, tables = 3 * 2304 * 9216, 2 * 20480 * 2304 + 2304
    assert kda == 39_518_368 and attn == 29_114_880 and dense == 63_700_992
    by_hand = 4 * kda + attn + 4 * experts + dense + 5 * 2 * 2304 + tables
    tree = jax.eval_shape(lambda: llama.init_params(cell, jax.random.key(0)))
    assert by_hand == cell.num_params() == sum(x.size for x in jax.tree.leaves(tree)) == 602_450_816
    assert 6.72 < by_hand * 12 / 2 ** 30 < 6.74
    # operations a token, every expert somewhere: 2 a matmul parameter it meets, the MLA
    # layer's scores over 4096.5 keys at 192 + 128 channels, 7 an element of a KDA head's state
    matmul = (4 * (kda - 3 * 4 * 4096 - 32 - 2 * 4096 - 128) + (attn - 512) + dense
              + 4 * (2304 * 256 + 3 * 2304 * 1024 * 9) + 20480 * 2304)
    assert cell.flops_per_token(8192) == pytest.approx(
        2 * matmul + 4 * 7 * 32 * 128 * 128 + 2 * (192 + 128) * 32 * 4096.5)
    # of the work HELD here (a thirty-second of the routed pairs): ISSUE 64's "about 770 MFLOP",
    # the mixers 61%, the head 12%
    held = cell.flops_per_token(8192) - 4 * 2 * 3 * 2304 * 1024 * 8 * (1 - 8 / 256)
    mixers = 4 * (2 * (kda - 57_504) + 7 * 32 * 128 * 128) + 2 * (attn - 512) + 640 * 32 * 4096.5
    assert 765e6 < held < 775e6 and 0.60 < mixers / held < 0.62
    assert 0.12 < 2 * 20480 * 2304 / held < 0.13


def test_kda_sublayer_with_beta_not_doubled_is_the_references():
    """The dense layer's KDA mixer (layer 1) through models/solar_open2.py's
    sublayer with `kda_neg_eigval` false; doubled it is another function."""
    (lp, kind, dense), x = blocks(seeded_params(KIMI_LINEAR, FP32))[0], stream()
    assert (kind, dense) == (K, True)
    run = jax.jit(lambda x, c: so.kda_sublayer(x, lp, c, segment_ids=None), static_argnums=1)
    with HIGHEST:
        got = run(x, FP32)
        want = jnp.stack([jax.jit(lambda u: ref.kda_mixer(u, lp, SHAPE))(x[b]) for b in range(2)])
        doubled = run(x, dataclasses.replace(FP32, kda_neg_eigval=True))
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(doubled - want).max()) > 0.05 * float(jnp.abs(want).max())


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_mla_sublayer_without_a_query_latent_or_a_rotary_is_the_references(impl):
    """Layer 3 of the tiny preset: 4 heads, keys of 12 + 4 beside values of
    8, the ONE shared key; through both implementations (the flash kernels
    interpreted, at a value width of their own). Shifting every position
    changes nothing but the causal mask's reach: no rotary. With a rotary
    (`mla_rope`) it is another function, and the leaves say there is no
    query latent."""
    cfg = dataclasses.replace(FP32, attention_impl=impl)
    (lp, kind, dense), x = blocks(seeded_params(KIMI_LINEAR, FP32))[2], stream(seq=64)
    assert (kind, dense) == (M, False) and "wq" in lp and "wq_a" not in lp
    positions = jnp.arange(64)
    run = jax.jit(lambda x, c: mla.mla_sublayer(x, lp, c, positions=positions, segment_ids=None),
                  static_argnums=1)
    with HIGHEST:
        got = run(x, cfg)
        want = jnp.stack([jax.jit(lambda u: ref.mla_mixer(u, lp, SHAPE))(x[b]) for b in range(2)])
        rotated = run(x, dataclasses.replace(cfg, mla_rope=True))
    assert got.shape == x.shape
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert float(jnp.abs(rotated - want).max()) > 0.01 * float(jnp.abs(want).max())
    with HIGHEST:
        moved = mla.mla_sublayer(jnp.roll(x, 7, axis=1)[:, 7:], lp, FP32,
                                 positions=positions[:57], segment_ids=None)
        alone = mla.mla_sublayer(x[:, :57], lp, FP32, positions=positions[:57], segment_ids=None)
    assert float(jnp.abs(moved - alone).max()) < 1e-5


@pytest.mark.parametrize("layer,kind,dense", [(0, K, True), (1, K, False), (2, M, False),
                                              (5, M, False)],
                         ids=["kda_dense", "kda_sparse", "mla_sparse", "mla_sparse_tail"])
def test_each_kind_of_block_is_the_references(layer, kind, dense):
    """A whole block (mixer, residual, norm, feed-forward, residual) of each
    kind the stack holds, the tail's among them, against the reference's
    `block`, with a random selection bias."""
    lp, got_kind, got_dense = blocks(seeded_params(KIMI_LINEAR, FP32, bias=0.1))[layer]
    assert (got_kind, got_dense) == (kind, dense)
    x = stream()
    run = jax.jit(lambda x: kl._block(x, lp, c=FP32, kind=kind, dense=dense,
                                      positions=jnp.arange(150), segment_ids=None))
    with HIGHEST:
        got, stats = run(x)
        want = [jax.jit(lambda h: ref.block(h, lp, kind, dense, SHAPE))(x[b]) for b in range(2)]
    theirs = jnp.stack([w[0] for w in want])
    assert float(jnp.abs(got - theirs).max()) < 2e-5 * float(jnp.abs(theirs).max())
    if dense:
        assert stats is None and want[0][1] is None
    else:
        np.testing.assert_array_equal(np.asarray(stats["tokens_per_expert"]),
                                      np.asarray(sum(w[1].sum(0) for w in want)))
        assert int(stats["dropped_pairs"]) == 0


@pytest.mark.parametrize("held", [1, 2, 3, 4, 6, 12])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(held):
    """The cell's deployment, small: every group of held experts that
    divides the tiny preset's 12. The 12 / `held` shares of an expert layer
    (top-4 of 12 sigmoid scores x 2.446 with a random selection bias), the
    shared expert counted ONCE, sum to what the uncut reference gives for the
    whole layer; every pair is held by exactly one share."""
    params = seeded_params(KIMI_LINEAR, FP32, bias=0.1)
    whole, x = blocks(params)[1][0], stream()
    with HIGHEST:
        want = jnp.stack([jax.jit(lambda u: ref.experts_mixer(u, whole, SHAPE)[0])(x[b])
                          for b in range(2)])
        shared = jnp.stack([ref.shared_expert(x[b], whole) for b in range(2)])
        run = jax.jit(lambda x, lp, cfg: moe.moe_ffn(x, lp, cfg)[:2], static_argnums=2)
        routed, held_pairs = [], 0
        for first in range(0, FP32.n_experts, held):
            cfg = dataclasses.replace(FP32, experts_held=held, first_expert_held=first)
            lp = {**whole, **{n: whole[n][first:first + held] for n in ("w_gate", "w_up", "w_down")}}
            out, stats = run(x, lp, cfg)
            routed.append(out - shared)
            pairs = x.shape[0] * x.shape[1] * FP32.top_k
            held_pairs += pairs - (int(stats["pairs_elsewhere"]) if held < FP32.n_experts else 0)
    assert held_pairs == x.shape[0] * x.shape[1] * FP32.top_k
    assert float(jnp.abs(sum(routed) + shared - want).max()) < 2e-5 * float(jnp.abs(want).max())
    if held < FP32.n_experts:
        assert float(jnp.abs(routed[0] + shared - want).max()) > 0.01 * float(jnp.abs(want).max())


def test_the_train_step_learns_a_batch_by_the_registrys_name():
    import optax

    from ray_tpu.train.step import TrainState, make_train_step

    # the dense layer and one period: the program is compiled once
    cfg = dataclasses.replace(get_model_config("kimi-linear-tiny"), remat=True, n_layers=3)
    assert cfg == dataclasses.replace(kl.KIMI_LINEAR_TINY, remat=True, n_layers=3)
    assert llama.remat_saves(cfg) == llama.remat_saves(llama.LLAMA_TINY) | {"kda_out", "kda_states"}
    opt = optax.adamw(3e-3)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    batch = KIMI_LINEAR.batch_of(cfg)
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < 0.85 * losses[0] and metrics["stats"]["tokens_per_expert"].shape == (2, 12)


def test_what_is_not_implemented_is_refused_by_name():
    params, x = seeded_params(KIMI_LINEAR, FP32), stream()
    with pytest.raises(NotImplementedError, match="packed documents.*KDA"):
        llama.loss_and_weight_fn(params, {**KIMI_LINEAR.batch_of(FP32), "segment_ids": jnp.zeros(
            (2, 150), jnp.int32)}, FP32)
    with pytest.raises(ValueError, match="unlike kinds"):
        llama.init_params(dataclasses.replace(FP32, first_dense_layers=3), jax.random.key(0))
    del x


def test_no_other_configuration_loads_the_module():
    """The registry names the presets without importing models/kimi_linear.py
    (or ops/kda.py); GLM-4.7-Flash, whose MLA sublayer it shares, never loads it."""
    code = ("import sys; from ray_tpu.models import registry, llama; "
            "registry.get_model_config('glm-lite-tiny'); registry.get_model_config('laguna-tiny'); "
            "assert 'kimi-linear-tiny' in registry.list_models(); "
            "assert 'ray_tpu.models.kimi_linear' not in sys.modules and "
            "'ray_tpu.ops.kda' not in sys.modules; "
            "registry.get_model_config('kimi-linear-tiny'); "
            "assert 'ray_tpu.ops.kda' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, env={
        **__import__("os").environ, "JAX_PLATFORMS": "cpu"})

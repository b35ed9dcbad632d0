"""models/nemotron_h.py (the causal tower of Nemotron-Labs-TwoTower-30B-A3B)
against the plain reference (chipbench/reference/nemotron_h_decoder.py,
which imports nothing of the program and runs the scan position by
position): the stack's plan from the pattern string, the counts by hand,
the family's initialisation, each sublayer, the sixteen shares that add
up to the uncut layer, and the refusals by name. (Logits, loss and every
gradient of the one train path at the tiny preset with all three kinds
of layer, each reading of the equations NOT taken told from the one
taken, remat and bf16: tests/test_contract_nemotron_h.py; what every model
holds alike and compiles nothing: tests/test_model_contract.py, a row of
model_cases.MODELS.)"""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h_decoder as ref
from model_cases import NEMOTRON_H, seeded_params
from ray_tpu.models import llama, moe, nemotron_h as nh
from ray_tpu.models.registry import get_model_config

FP32 = NEMOTRON_H.fp32
FULL = nh.NEMOTRON_TWOTOWER_30B_A3B
HIGHEST = jax.default_matmul_precision("highest")


def layer_of(params, kind, i=0):
    lp = jax.tree.map(lambda w: w[i], params["layers"][nh.GROUP[kind]])
    if kind == nh.EXPERTS:
        lp["router_bias"] = params["layers"]["router_bias"][i]
    return lp


def stream(seed=3, seq=40):
    return 0.5 * jax.random.normal(jax.random.key(seed), (2, seq, FP32.d_model))


def test_the_stack_is_cut_from_the_pattern_string_and_the_whole_52_build():
    """The published pattern is one scan over five `MEMEM*E`, one over three
    `ME`, and what repeats nowhere unrolled; every cut keeps the order; the
    whole tower builds abstractly (its tree and its loss) at 23 / 23 / 6
    layers of a kind, and the benchmark's nine are 4 / 4 / 1."""
    assert nh.segments(tuple(FULL.pattern)) == [
        ("MEMEM*E", 5), ("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ("E", 1)]
    for pattern in (FULL.pattern, "MEMEM*EME", "M", "EEE*", FP32.pattern):
        cut = nh.segments(tuple(pattern))
        assert "".join(unit * n for unit, n in cut) == pattern
    assert [FULL.count(k) for k in "ME*"] == [23, 23, 6] and len(FULL.pattern) == 52
    nine = dataclasses.replace(FULL, n_layers=9)
    assert "".join(nine.layer_types) == "MEMEM*EME" and [nine.count(k) for k in "ME*"] == [4, 4, 1]
    small = dataclasses.replace(FULL, vocab_size=1024, experts_held=1, max_seq=64)
    params = jax.eval_shape(lambda: llama.init_params(small, jax.random.key(0)))
    assert params["layers"]["mamba"]["w_in"].shape == (23, 2688, 4096 + 6144 + 64)
    assert params["layers"]["experts"]["w_up"].shape == (23, 1, 2688, 1856)
    assert params["layers"]["attention"]["wk"].shape == (6, 2688, 256)
    assert params["layers"]["router_bias"].shape == (23, 128)
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    loss, _, stats = jax.eval_shape(lambda p, t: llama.loss_and_weight_fn(
        p, {"tokens": t, "targets": t}, small), params, tokens)
    assert loss.shape == () and stats["tokens_per_expert"].shape == (23, 128)
    axes = llama.logical_axes(small)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_counts_of_parameters_and_operations_are_the_trees_and_the_issues():
    """By hand (ISSUE 49): a Mamba layer 38,744,896, the attention layer
    23,399,040, an expert layer with 8 of 128 held 100,125,440, embedding +
    head + final norm at 16,384 rows 88,083,072: 666,963,456 at the cell's
    sizes; the whole tower 31.58e9. `flops_per_token` counts every expert
    somewhere (top-6 whole): the Mamba layers 80.0 MFLOP each, the
    attention layer 113.9 at 8,192 keys."""
    cell = dataclasses.replace(FULL, n_layers=9, experts_held=8, vocab_size=16384)
    mamba = 2688 * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * 2688 + 2688
    attention = 2 * 2688 * 128 * (32 + 2) + 2688
    experts = 2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856 + 2688
    assert (mamba, attention, experts) == (38_744_896, 23_399_040, 100_125_440)
    assert cell.num_params() == 4 * mamba + attention + 4 * experts + 88_083_072 == 666_963_456
    assert round(FULL.num_params() / 1e9, 2) == 31.58
    tiny = jax.eval_shape(lambda: llama.init_params(FP32, jax.random.key(0)))
    assert FP32.num_params() == sum(a.size for a in jax.tree.leaves(tiny))
    one = lambda pattern: dataclasses.replace(cell, pattern=pattern, n_layers=1)  # noqa: E731
    head = 2.0 * 2688 * 16384
    assert one("M").flops_per_token(8192) - head == 2.0 * (2688 * 10304 + 4096 * 2688) + 5.0 * 64 * 64 * 128
    assert one("*").flops_per_token(8192) - head == (2.0 * 2 * 2688 * 128 * 34
                                                     + 4.0 * 128 * 32 * 8193 / 2)
    assert one("E").flops_per_token(8192) - head == 2.0 * (2688 * 128 + 2 * 2688 * (6 * 1856 + 3712))


def test_the_mixer_starts_as_the_family_does():
    """A in (1, 16), dt in (1e-3, 1e-1) through the inverse of softplus,
    D = 1, the convolution's bias within +-1/2, the output projection
    scaled down by the root of the PUBLISHED depth."""
    c = dataclasses.replace(FP32, published_layers=52)
    p = nh.mamba_params(c, jax.random.key(0), n=4)
    A, dt = np.exp(np.asarray(p["A_log"])), np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1.0 <= A.min() and A.max() <= 16.0 and A.std() > 1.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.all(np.asarray(p["D"]) == 1.0) and np.all(np.asarray(p["norm"]) == 1.0)
    assert 0.4 < np.abs(np.asarray(p["conv_bias"])).max() <= 0.5
    std = float(np.asarray(p["w_out"]).std())
    assert std == pytest.approx(0.987 / np.sqrt(c.mamba_inner * 52), rel=0.05)   # truncated at 3


def test_mamba_sublayer_is_the_references_and_reads_nothing_ahead():
    params = seeded_params(NEMOTRON_H, FP32)
    lp, x = layer_of(params, nh.MAMBA, 1), stream()
    with HIGHEST:
        got = jax.jit(lambda x: nh.mamba_sublayer(x, lp, FP32, segment_ids=None))(x)
        want = jnp.stack([ref.mamba_mixer(x[b], lp, NEMOTRON_H.shape_of(FP32)) for b in range(2)])
        later = jax.jit(lambda x: nh.mamba_sublayer(x, lp, FP32, segment_ids=None))(
            x.at[:, 23:].add(1.0))
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    np.testing.assert_array_equal(np.asarray(later[:, :23]), np.asarray(got[:, :23]))
    assert float(jnp.abs(later[:, 23] - got[:, 23]).max()) > 0


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_attention_sublayer_is_the_references_and_has_no_rotary(impl):
    """GQA 4 / 2 at heads of 16; shifting every position by one changes
    nothing but the causal mask's reach: no rotary."""
    cfg = dataclasses.replace(FP32, attention_impl=impl)
    lp, x = layer_of(seeded_params(NEMOTRON_H, FP32), nh.ATTENTION), stream(seq=64)
    with HIGHEST:
        got = jax.jit(lambda x: nh.attention_sublayer(x, lp, cfg, segment_ids=None))(x)
        want = jnp.stack([ref.attention_mixer(x[b], lp, NEMOTRON_H.shape_of(FP32))
                          for b in range(2)])
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())
    # the last row of a sequence read alone, wherever it stands: the same output
    with HIGHEST:
        moved = nh.attention_sublayer(jnp.roll(x, 7, axis=1)[:, 7:], lp, FP32, segment_ids=None)
        alone = nh.attention_sublayer(x[:, :57], lp, FP32, segment_ids=None)
    assert float(jnp.abs(moved - alone).max()) < 1e-5


def test_expert_sublayer_is_the_references_with_a_random_selection_bias():
    params = seeded_params(NEMOTRON_H, FP32, bias=0.1)
    lp, x = layer_of(params, nh.EXPERTS, 2), stream()
    with HIGHEST:
        got, stats, _ = jax.jit(lambda x: moe.moe_ffn(x, lp, FP32))(x)
        want = [ref.experts_mixer(x[b], lp, NEMOTRON_H.shape_of(FP32)) for b in range(2)]
    assert float(jnp.abs(got - jnp.stack([w[0] for w in want])).max()) < 1e-5
    np.testing.assert_array_equal(np.asarray(stats["tokens_per_expert"]),
                                  np.asarray(sum(w[1].sum(0) for w in want)))


def test_sixteen_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The cell's deployment, small: 16 chips each hold one of the 16
    routed experts of a layer; the shares' routed parts, with the shared
    expert (which every chip computes alike) counted ONCE, sum to what the
    uncut reference gives for the whole layer; each share's own output is
    the reference's for that share; the pairs elsewhere add up too."""
    params = seeded_params(NEMOTRON_H, FP32, bias=0.1)
    whole, x = layer_of(params, nh.EXPERTS, 0), stream()
    shape = NEMOTRON_H.shape_of(FP32)
    with HIGHEST:
        want = jnp.stack([ref.experts_mixer(x[b], whole, shape)[0] for b in range(2)])
        shared = jnp.stack([ref.expert(x[b], whole["shared_up"], whole["shared_down"])
                            for b in range(2)])
        routed, held_pairs = [], 0
        for first in range(FP32.n_experts):
            cfg = dataclasses.replace(FP32, experts_held=1, first_expert_held=first)
            lp = {**whole, "w_up": whole["w_up"][first:first + 1],
                  "w_down": whole["w_down"][first:first + 1]}
            out, stats, _ = jax.jit(lambda x, lp, cfg=cfg: moe.moe_ffn(x, lp, cfg))(x, lp)
            share = jnp.stack([ref.experts_mixer(x[b], lp, NEMOTRON_H.shape_of(cfg))[0]
                               for b in range(2)])
            assert float(jnp.abs(out - share).max()) < 1e-5
            routed.append(out - shared)
            held_pairs += x.shape[0] * x.shape[1] * FP32.top_k - int(stats["pairs_elsewhere"])
    assert held_pairs == x.shape[0] * x.shape[1] * FP32.top_k
    assert float(jnp.abs(sum(routed) + shared - want).max()) < 2e-5 * float(jnp.abs(want).max())


def test_the_train_step_learns_a_batch_by_the_registrys_name():
    import optax

    from ray_tpu.train.step import TrainState, make_train_step

    cfg = dataclasses.replace(get_model_config("nemotron-h-tiny"), remat=True)
    assert cfg == dataclasses.replace(nh.NEMOTRON_H_TINY, remat=True)
    opt = optax.adamw(3e-3)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    batch = NEMOTRON_H.batch_of(cfg)
    losses = []
    for _ in range(12):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < 0.8 * losses[0] and metrics["stats"]["tokens_per_expert"].shape == (3, 16)


def test_what_is_not_implemented_is_refused_by_name():
    params, x = seeded_params(NEMOTRON_H, FP32), stream()
    # packed documents under a Mamba layer were refused here until PR 66 built them
    # (tests/test_ssd_documents.py, tests/test_gdn_conv_documents.py, tests/test_granite_hybrid.py):
    # ONE document is the sublayer without ids, two are not
    lp, ids = layer_of(params, nh.MAMBA), jnp.zeros(x.shape[:2], jnp.int32)
    plain = nh.mamba_sublayer(x, lp, FP32, segment_ids=None)
    np.testing.assert_allclose(nh.mamba_sublayer(x, lp, FP32, segment_ids=ids), plain, atol=1e-5)
    two = nh.mamba_sublayer(x, lp, FP32, segment_ids=ids.at[:, 17:].set(1))
    np.testing.assert_allclose(two[:, :17], plain[:, :17], atol=1e-5)
    assert float(jnp.abs(two[:, 17:] - plain[:, 17:]).max()) > 1e-3
    with pytest.raises(NotImplementedError, match=r"dense MLP layer \(-\) is not"):
        dataclasses.replace(FP32, pattern="M-E*M-E*").layer_types
    with pytest.raises(ValueError, match="the pattern names 12"):
        dataclasses.replace(FP32, n_layers=13).layer_types
    with pytest.raises(NotImplementedError, match="whole heads of 128"):
        odd = dataclasses.replace(FP32, ssm_state=24)
        nh.mamba_sublayer(x, jax.tree.map(lambda w: w[0], nh.mamba_params(odd, jax.random.key(0))),
                          odd, segment_ids=None)
    # the second tower and the diffusion objective: said, not built
    assert "SECOND tower" in nh.__doc__ and "diffusion objective" in nh.__doc__


def test_no_other_configuration_loads_the_module():
    """The registry names the presets without importing models/nemotron_h.py
    (or ops/ssd.py); a dense or another expert model never loads them."""
    code = ("import sys; from ray_tpu.models import registry, llama; "
            "registry.get_model_config('olmoe-1b-7b'); registry.get_model_config('laguna-tiny'); "
            "assert 'nemotron-h-tiny' in registry.list_models(); "
            "assert 'ray_tpu.models.nemotron_h' not in sys.modules and "
            "'ray_tpu.ops.ssd' not in sys.modules; "
            "registry.get_model_config('nemotron-h-tiny'); "
            "assert 'ray_tpu.ops.ssd' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, env={
        **__import__("os").environ, "JAX_PLATFORMS": "cpu"})

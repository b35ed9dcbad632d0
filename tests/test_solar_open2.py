"""models/solar_open2.py (Solar-Open2-250B: Kimi-Delta-Attention layers three
to one gated NoPE GQA layer, over sigmoid-routed experts and a shared one)
against the plain reference (chipbench/reference/solar_open2_decoder.py,
which imports nothing of the program and runs the recurrence position by
position): the stack's plan, the counts by hand, fla's initialisation, each
sublayer, the shares of the HEADS and of the experts that add up to the
uncut layer, and the refusals by name. (Logits, loss and every gradient of
the one train path at the tiny preset, each reading of the equations NOT
taken told from the one taken, remat and bf16:
tests/test_contract_solar_open2.py; the rule alone: tests/test_kda.py; what
every model holds alike and compiles nothing: tests/test_model_contract.py,
a row of model_cases.MODELS.)"""

import dataclasses
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import solar_open2_decoder as ref
from model_cases import SOLAR_OPEN2, seeded_params
from ray_tpu.models import laguna, llama, moe, solar_open2 as so
from ray_tpu.models.registry import get_model_config

FP32 = SOLAR_OPEN2.fp32
FULL = so.SOLAR_OPEN2_250B
HIGHEST = jax.default_matmul_precision("highest")
SHAPE = SOLAR_OPEN2.shape_of(FP32)


def layer_of(params, layer):
    """Layer `layer`'s leaves as a block reads them (position `layer` mod 4
    of period `layer` div 4), its row of the selection biases among them."""
    lp = jax.tree.map(lambda w: w[layer // 4], params["layers"]["period"][str(layer % 4)])
    lp["router_bias"] = params["layers"]["router_bias"][layer]
    return lp


def stream(seed=3, seq=150):
    return 0.5 * jax.random.normal(jax.random.key(seed), (2, seq, FP32.d_model))


def test_the_stack_is_whole_periods_of_a_gqa_layer_and_three_kda_layers():
    """The published 48 are twelve periods of (GQA, KDA, KDA, KDA); the
    benchmark's four are one; the whole model builds abstractly (its tree,
    its axes and its loss) and is the 250B it is published as."""
    assert FULL.layer_types[:8] == (so.GQA, so.KDA, so.KDA, so.KDA) * 2
    assert FULL.layer_types.count(so.GQA) == 12 and len(FULL.layer_types) == 48
    p = laguna.plan(FULL)
    assert p["periods"] == 12 and p["period"] == [(so.GQA, 64)] + [(so.KDA, 64)] * 3
    assert not p["tail"] and p["dense"] is None
    one = laguna.plan(dataclasses.replace(FULL, n_layers=4, n_heads=8, n_kv_heads=1, kda_heads=8))
    assert one["periods"] == 1 and one["period"] == [(so.GQA, 8)] + [(so.KDA, 8)] * 3
    assert 249e9 < FULL.num_params() < 252e9
    small = dataclasses.replace(FULL, vocab_size=1024, experts_held=1, max_seq=64)
    params = jax.eval_shape(lambda: llama.init_params(small, jax.random.key(0)))
    period = params["layers"]["period"]
    assert period["0"]["wq"].shape == period["0"]["wg"].shape == (12, 4096, 64 * 128)
    assert period["0"]["wk"].shape == (12, 4096, 8 * 128)
    assert period["2"]["wf1"].shape == (12, 4096, 128) and period["2"]["wf2"].shape == (12, 128, 8192)
    assert period["3"]["dt_bias"].shape == (12, 8192) and period["3"]["A_log"].shape == (12, 64)
    assert period["1"]["w_up"].shape == (12, 1, 4096, 1280)
    assert params["layers"]["router_bias"].shape == (48, 320)
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    loss, _, stats = jax.eval_shape(lambda p, t: llama.loss_and_weight_fn(
        p, {"tokens": t, "targets": t}, small), params, tokens)
    assert loss.shape == () and stats["tokens_per_expert"].shape == (48, 320)
    axes = llama.logical_axes(small)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_counts_of_parameters_and_operations_are_the_trees_and_the_issues():
    """By hand (ISSUE 60's table): a KDA mixer of 8 held heads 18.13M, the
    GQA mixer at 8 / 1 heads 13.63M, the shared expert 15.73M, the router
    1.31M, 8 routed experts 125.8M, the tables at 24,576 rows 201.3M:
    840M at the cell's sizes; `num_params` is the tree's own count."""
    cell = dataclasses.replace(FULL, n_layers=4, experts_held=8, vocab_size=24576, n_heads=8,
                               n_kv_heads=1, kda_heads=8)
    kda = (4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8 + 3 * 4 * 1024
           + 8 + 2 * 1024 + 128)
    gqa = 4096 * 128 * (8 + 8 + 1 + 1 + 8)
    experts = 4096 * 320 + 320 + 3 * 4096 * 1280 * (8 + 1)
    tables = 2 * 24576 * 4096 + 4096
    assert kda == 18_135_176 and gqa == 13_631_488
    by_hand = 3 * kda + gqa + 4 * (experts + 2 * 4096) + tables
    tree = jax.eval_shape(lambda: llama.init_params(cell, jax.random.key(0)))
    assert by_hand == cell.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    assert 839e6 < by_hand < 842e6
    # operations a token: 2 a matmul parameter it meets (8 of the experts), the GQA
    # layer's scores over 4096.5 keys, 7 an element of a KDA head's state
    flops = cell.flops_per_token(8192)
    matmul = (3 * (kda - 3 * 4 * 1024 - 8 - 2 * 1024 - 128) + gqa
              + 4 * (4096 * 320 + 3 * 4096 * 1280 * 9) + 24576 * 4096)
    assert flops == pytest.approx(2 * matmul + 3 * 7 * 8 * 128 * 128 + 4 * 128 * 8 * 4096.5)
    # of the work HELD here (a fortieth of the routed pairs) the head is two fifths (ISSUE 60:
    # "about 39%"; 5% at depth): what the cell's `why` says
    held = flops - 4 * 2 * 3 * 4096 * 1280 * 8 * (1 - 8 / 320)
    assert 0.38 < 2 * 24576 * 4096 / held < 0.40 and 12.5e12 < 3 * 8192 * held < 13.0e12


def test_the_decay_starts_as_flas_does():
    """A in (1, 16) a head, dt in (1e-3, 1e-1) a CHANNEL through the inverse
    of softplus, the gate's bias 0, the norm's weight 1."""
    p = so.attention_params(FP32, jax.random.key(0), so.KDA, n=4)
    A, dt = np.exp(np.asarray(p["A_log"])), np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert A.shape == (4, 8) and dt.shape == (4, 8 * 16)
    assert 1.0 <= A.min() and A.max() <= 16.0 and A.std() > 1.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001 and dt.std() > 0.01
    assert not np.asarray(p["g_bias"]).any() and np.all(np.asarray(p["o_norm"]) == 1.0)


def test_kda_sublayer_is_the_references_and_reads_nothing_ahead():
    lp, x = layer_of(seeded_params(SOLAR_OPEN2, FP32), 5), stream()
    run = jax.jit(lambda x: so.kda_sublayer(x, lp, FP32, segment_ids=None))
    with HIGHEST:
        got = run(x)
        want = jnp.stack([jax.jit(lambda u: ref.kda_mixer(u, lp, SHAPE))(x[b]) for b in range(2)])
        later = run(x.at[:, 83:].add(1.0))
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    np.testing.assert_array_equal(np.asarray(later[:, :83]), np.asarray(got[:, :83]))
    assert float(jnp.abs(later[:, 83] - got[:, 83]).max()) > 0


def test_the_decay_is_a_vector_over_the_keys_channels():
    """What tells KDA from the gated delta rule: the log decay the
    sublayer hands its rule differs from channel to channel of one head
    and position, by about as much as from head to head."""
    lp, x = layer_of(seeded_params(SOLAR_OPEN2, FP32), 1), stream()
    with HIGHEST:
        _, _, _, g, _ = ref.rule_inputs(x[0], jax.tree.map(lambda w: w.astype(jnp.float32), lp), SHAPE)
    g = np.asarray(g)
    assert g.shape == (150, 8, 16) and (g < 0).all()
    assert g.std(axis=-1).mean() > 0.2 * g.std(axis=1).mean()


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_gqa_sublayer_is_the_references_has_a_gate_and_no_rotary(impl):
    """GQA 8 / 2 at heads of 16 with the elementwise gate; shifting every
    position changes nothing but the causal mask's reach: no rotary."""
    cfg = dataclasses.replace(FP32, attention_impl=impl)
    lp, x = layer_of(seeded_params(SOLAR_OPEN2, FP32), 4), stream(seq=64)
    with HIGHEST:
        got = jax.jit(lambda x: so.gqa_sublayer(x, lp, cfg, segment_ids=None))(x)
        want = jnp.stack([ref.gqa_mixer(x[b], lp, SHAPE) for b in range(2)])
        ungated = jnp.stack([ref.gqa_mixer(x[b], lp, {**SHAPE, "use_gqa_gate": False})
                             for b in range(2)])
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert float(jnp.abs(got - ungated).max()) > 0.1 * float(jnp.abs(want).max())
    with HIGHEST:
        moved = so.gqa_sublayer(jnp.roll(x, 7, axis=1)[:, 7:], lp, FP32, segment_ids=None)
        alone = so.gqa_sublayer(x[:, :57], lp, FP32, segment_ids=None)
    assert float(jnp.abs(moved - alone).max()) < 1e-5


def test_expert_sublayer_is_the_references_at_a_width_that_is_no_power_of_two():
    """Top-4 of 40 sigmoid scores chosen with a random selection bias,
    renormalised, plus the shared expert: `_of_chosen`'s compare-and-sum
    and the dispatch over 40 columns."""
    params = seeded_params(SOLAR_OPEN2, FP32, bias=0.1)
    lp, x = layer_of(params, 2), stream()
    with HIGHEST:
        got, stats, _ = jax.jit(lambda x: moe.moe_ffn(x, lp, FP32))(x)
        want = [ref.experts_mixer(x[b], lp, SHAPE) for b in range(2)]
    assert float(jnp.abs(got - jnp.stack([w[0] for w in want])).max()) < 1e-5
    np.testing.assert_array_equal(np.asarray(stats["tokens_per_expert"]),
                                  np.asarray(sum(w[1].sum(0) for w in want)))
    assert stats["tokens_per_expert"].shape == (40,) and int(stats["dropped_pairs"]) == 0


def head_share(lp, kind, first, n, per_kv=None):
    """The leaves of heads first .. first + n of a mixer (`per_kv`: query
    heads a key-value head, a GQA layer's), everything else whole."""
    hd = FP32.kda_head_dim if kind == so.KDA else FP32.head_dim
    cols = slice(first * hd, (first + n) * hd)
    if kind == so.GQA:
        kv = slice(first // per_kv * hd, (first + n) // per_kv * hd)
        return {**lp, "wq": lp["wq"][:, cols], "wg": lp["wg"][:, cols], "wk": lp["wk"][:, kv],
                "wv": lp["wv"][:, kv], "wo": lp["wo"][cols]}
    out = {**lp, "wo": lp["wo"][cols], "wb": lp["wb"][:, first:first + n],
           "A_log": lp["A_log"][first:first + n]}
    for name in ("wq", "wk", "wv", "wf2", "wg2", "conv_q", "conv_k", "conv_v"):
        out[name] = lp[name][:, cols]
    for name in ("dt_bias", "g_bias"):
        out[name] = lp[name][cols]
    return out


def test_the_shares_of_the_heads_and_of_the_experts_add_up_to_the_uncut_layer():
    """The cell's deployment, small. A KDA mixer's eight head-shares (one
    head each: `wo` over that head's rows, the low-rank pairs' first
    factors whole), the GQA mixer's (two shares of four query heads and one
    key-value head each; eight at the published 64 / 8) and the expert
    layer's forty (one routed expert each, the shared expert counted ONCE)
    sum to what the uncut reference gives for the whole layer, and each
    share is the reference's for that share."""
    params = seeded_params(SOLAR_OPEN2, FP32, bias=0.1)
    x = stream()
    ref_of = lambda f, lp, shape: jnp.stack([jax.jit(  # noqa: E731
        lambda u: f(u, lp, shape))(x[b]) for b in range(2)])
    with HIGHEST:
        # KDA: layer 1
        whole = layer_of(params, 1)
        want = ref_of(ref.kda_mixer, whole, SHAPE)
        one = dataclasses.replace(FP32, kda_heads=1)
        shape = {**SHAPE, "linear_attn_config": {**SHAPE["linear_attn_config"], "num_heads": 1}}
        run = jax.jit(lambda x, lp: so.kda_sublayer(x, lp, one, segment_ids=None))
        shares = [run(x, head_share(whole, so.KDA, h, 1)) for h in range(FP32.kda_heads)]
        theirs = ref_of(ref.kda_mixer, head_share(whole, so.KDA, 3, 1), shape)
        assert float(jnp.abs(shares[3] - theirs).max()) < 2e-5 * float(jnp.abs(theirs).max())
        assert float(jnp.abs(sum(shares) - want).max()) < 2e-5 * float(jnp.abs(want).max())
        assert float(jnp.abs(shares[0] - want).max()) > 0.1 * float(jnp.abs(want).max())
        # GQA: layer 0
        whole = layer_of(params, 0)
        want = ref_of(ref.gqa_mixer, whole, SHAPE)
        part = dataclasses.replace(FP32, n_heads=4, n_kv_heads=1)
        shape = {**SHAPE, "num_attention_heads": 4, "num_key_value_heads": 1}
        run = jax.jit(lambda x, lp: so.gqa_sublayer(x, lp, part, segment_ids=None))
        shares = [run(x, head_share(whole, so.GQA, h, 4, per_kv=4)) for h in (0, 4)]
        theirs = ref_of(ref.gqa_mixer, head_share(whole, so.GQA, 4, 4, per_kv=4), shape)
        assert float(jnp.abs(shares[1] - theirs).max()) < 1e-4 * float(jnp.abs(theirs).max())
        assert float(jnp.abs(sum(shares) - want).max()) < 1e-4 * float(jnp.abs(want).max())
        # the experts: layer 2
        whole = layer_of(params, 2)
        want = ref_of(lambda u, lp, s: ref.experts_mixer(u, lp, s)[0], whole, SHAPE)
        shared = ref_of(lambda u, lp, s: ref._swiglu(u, lp["shared_gate"], lp["shared_up"],
                                                     lp["shared_down"]), whole, SHAPE)
        routed, held_pairs = [], 0
        run = jax.jit(lambda x, lp, cfg: moe.moe_ffn(x, lp, cfg)[:2], static_argnums=2)
        for first in range(FP32.n_experts):
            cfg = dataclasses.replace(FP32, experts_held=1, first_expert_held=first)
            lp = {**whole, **{n: whole[n][first:first + 1] for n in ("w_gate", "w_up", "w_down")}}
            out, stats = run(x, lp, cfg)
            routed.append(out - shared)
            held_pairs += x.shape[0] * x.shape[1] * FP32.top_k - int(stats["pairs_elsewhere"])
        assert held_pairs == x.shape[0] * x.shape[1] * FP32.top_k
        assert float(jnp.abs(sum(routed) + shared - want).max()) < 2e-5 * float(jnp.abs(want).max())


def test_the_train_step_learns_a_batch_by_the_registrys_name():
    import optax

    from ray_tpu.train.step import TrainState, make_train_step

    # one period: the program is compiled once, and two periods are twice the seconds
    cfg = dataclasses.replace(get_model_config("solar-open2-tiny"), remat=True, n_layers=4)
    assert cfg == dataclasses.replace(so.SOLAR_OPEN2_TINY, remat=True, n_layers=4)
    opt = optax.adamw(3e-3)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    batch = SOLAR_OPEN2.batch_of(cfg)
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < 0.85 * losses[0] and metrics["stats"]["tokens_per_expert"].shape == (4, 40)


def test_the_dots_policy_keeps_the_flash_output_and_what_the_rules_forward_kernel_writes():
    """`llama.remat_saves` gains the two names of ops/kda.py's forward kernel
    by this stack (`kda_out`; `kda_states`: the chunks' starting states and the
    pairs' inverses), so under a block's `jax.checkpoint` the lowered gradient
    calls `kda_fwd` once a KDA layer and not again in the backward, `kda_bwd`
    once; the rule's operations stand under `kda.scan`, and no triangular
    solve or loop over the chunks is left of the jax.numpy form."""
    assert so.REMAT_SAVES == ("kda_out", "kda_states")
    assert llama.remat_saves(FP32) == llama.remat_saves(llama.LLAMA_TINY) | set(so.REMAT_SAVES)
    cfg = dataclasses.replace(FP32, remat=True, n_layers=4)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    batch = jax.eval_shape(lambda: SOLAR_OPEN2.batch_of(cfg))
    text = jax.jit(jax.grad(lambda p, b: llama.loss_and_weight_fn(p, b, cfg)[0])).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("kda.proj", "kda.conv", "kda.gates", "kda.scan", "kda.norm", "kda.out",
                  "attn.qkv", "attn.attend", "attn.gate", "attn.out", "moe.router", "shared.ffn"):
        assert f"/{scope}/" in text, scope
    assert "triangular_solve" not in text and "triangular-solve" not in text
    # with arguments: the constant 0 / 1 matrix of the kernels' sums is hoisted into a function
    # of no argument that takes the jitted function's name
    calls = re.findall(r"call @(kda_fwd|kda_bwd)\w*\(%", text)
    assert sorted(calls) == ["kda_bwd"] * 3 + ["kda_fwd"] * 3, calls


def test_what_is_not_implemented_is_refused_by_name():
    params, x = seeded_params(SOLAR_OPEN2, FP32), stream()
    with pytest.raises(NotImplementedError, match="packed documents.*KDA"):
        so.kda_sublayer(x, layer_of(params, 1), FP32,
                        segment_ids=jnp.zeros(x.shape[:2], jnp.int32))
    with pytest.raises(NotImplementedError, match="packed documents.*KDA"):
        llama.loss_and_weight_fn(params, {**SOLAR_OPEN2.batch_of(FP32), "segment_ids": jnp.zeros(
            (2, 150), jnp.int32)}, FP32)
    with pytest.raises(ValueError, match="does not end on a whole period"):
        llama.logical_axes(dataclasses.replace(FP32, n_layers=6))


def test_no_other_configuration_loads_the_module():
    """The registry names the presets without importing models/solar_open2.py
    (or ops/kda.py); a dense or another expert model never loads them."""
    code = ("import sys; from ray_tpu.models import registry, llama; "
            "registry.get_model_config('olmoe-1b-7b'); registry.get_model_config('laguna-tiny'); "
            "registry.get_model_config('olmo-hybrid-tiny'); "
            "assert 'solar-open2-tiny' in registry.list_models(); "
            "assert 'ray_tpu.models.solar_open2' not in sys.modules and "
            "'ray_tpu.ops.kda' not in sys.modules; "
            "registry.get_model_config('solar-open2-tiny'); "
            "assert 'ray_tpu.ops.kda' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, env={
        **__import__("os").environ, "JAX_PLATFORMS": "cpu"})

"""SDAR's whole train path (models/laguna.py's full layers under
models/block_diffusion.py's objective) at a small size on the CPU, seeded
weights, against its plain reference (chipbench/reference/sdar_decoder.py):
loss, tokens per expert and every gradient, whole and as a share; the
corruption bit for bit; the objective's parts one at a time (the key folds
the step count and the batch's own ids, every microbatch of an accumulated
step draws noise of its own, the head runs on the noised rows, the weight
is 1 / p_b, `targets` is not read); the masked attention alone through the
runner's own comparison; the cell's one-thing-wrong table at the tiny size;
the eight shares of the reference's expert layer adding up to the uncut
one; and the contract's two compiled cases (tests/model_cases.py), which
read the same memos. The kernels under the mask: tests/test_flash_blockdiff.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.reference import sdar_decoder
from chipbench.tools import sdar_wrong
from model_cases import (SDAR, contract_cases, reference_path, seeded_params, train_path,
                         worst_leaf)
from ray_tpu.models import block_diffusion, llama
from ray_tpu.train.step import TrainState, make_train_step

FP32, B, S = SDAR.fp32, SDAR.batch, SDAR.seq


@pytest.mark.parametrize("held,bias", [(None, 0.0), (None, 0.05), ((4, 8), 0.0), ((4, 8), 0.05)],
                         ids=["all_experts-zero_bias", "all_experts-random_bias",
                              "a_share-zero_bias", "a_share-random_bias"])
def test_train_path_meets_the_reference_in_loss_and_gradients(held, bias):
    """llama.loss_and_weight_fn (the one train path) on four layers against
    the plain reference: the loss, the tokens per expert of every layer
    over BOTH copies' rows, every gradient by its worst leaf, and the
    step's own report of its corruption and its mask."""
    cfg = FP32 if held is None else dataclasses.replace(
        FP32, experts_held=held[0], first_expert_held=held[1])
    ours, theirs = train_path(SDAR, cfg, bias), reference_path(SDAR, cfg, bias)
    stats, ref = ours.stats, theirs.parts
    assert float(ours.weight) == B * S                       # the DATA tokens, not the rows
    assert float(ours.loss) == pytest.approx(float(ref["loss"]), rel=2e-6)
    assert stats["tokens_per_expert"].shape == (4, cfg.n_experts)
    assert stats["tokens_per_expert"].tolist() == ref["tokens_per_expert"].tolist()
    assert stats["tokens_per_expert"].sum(-1).tolist() == [2 * cfg.top_k * B * S] * 4
    assert int(stats["dropped_pairs"].sum()) == 0
    assert int(stats["diff_masked"]) == int(ref["masked"]) > 0
    assert int(stats["diff_visible_pairs"]) == S * (S + 4)
    worst = worst_leaf(ours.grads, theirs.grads)
    assert len(worst) == len(jax.tree.leaves(ours.params)) - 1
    assert max(worst.values()) < 2e-4, sorted(worst.items(), key=lambda kv: -kv[1])[:3]


@pytest.mark.parametrize("step", [0, 1, 7])
def test_the_corruption_is_the_references_bit_for_bit(step):
    """The same key (the step count folded with a checksum of the batch's
    ids, each side by its own code), the same two draws, the same masked
    positions, noised ids and levels: integers and a key, no tolerance.
    Both under jit, as the step and the runner's check run them."""
    cfg = FP32
    tokens = SDAR.batch_of(cfg)["tokens"]
    got = jax.jit(lambda t: block_diffusion.corrupt(
        t, block_diffusion.step_key({"tokens": t, "step": step}), block=cfg.diffusion_block,
        mask_id=cfg.vocab_size - 1))(tokens)
    want = jax.jit(lambda t: sdar_decoder.corrupt(t, SDAR.shape_of(cfg), step))(tokens)
    for name in ("masked", "noised", "p"):
        assert (np.asarray(got[name]) == np.asarray(want[name])).all(), name
    masked, p = np.asarray(got["masked"]), np.asarray(got["p"])
    assert 0 < masked.sum() < masked.size and (p >= 1e-3).all() and (p <= 1).all()
    assert p.shape == (B, S // 4) and len(np.unique(p)) == p.size     # a level a BLOCK
    assert (np.asarray(got["noised"])[masked] == cfg.vocab_size - 1).all()
    assert (np.asarray(got["noised"])[~masked] == np.asarray(tokens)[~masked]).all()
    assert block_diffusion.EPS == SDAR.shape_of(cfg)["block_diffusion"]["eps"] == 1e-3


def test_the_key_is_made_of_the_count_and_the_ids_and_of_nothing_a_caller_sets():
    """No seed anywhere: the configuration has one field of the objective
    (`diffusion_block`); another step, another batch, or one id moved or
    exchanged with its neighbour is another key; the same batch at the
    same count the same key, whatever else the batch carries."""
    fields = {f.name for f in dataclasses.fields(FP32) if f.name.startswith("diffusion")}
    assert fields == {"diffusion_block"}
    tokens = SDAR.batch_of(FP32)["tokens"]
    data = lambda key: jax.random.key_data(key).tolist()  # noqa: E731
    base = data(block_diffusion.step_key({"tokens": tokens}))
    assert base == data(block_diffusion.step_key({"tokens": tokens, "step": jnp.int32(0),
                                                  "targets": tokens * 0}))
    assert base == data(sdar_decoder.step_key(tokens, 0))
    swapped = tokens.at[0, 0].set(tokens[0, 1]).at[0, 1].set(tokens[0, 0])
    others = [{"tokens": tokens, "step": 1}, {"tokens": tokens.at[1, 7].add(1)},
              {"tokens": swapped}, {"tokens": tokens[::-1]}]
    assert int(tokens[0, 0]) != int(tokens[0, 1])
    keys = [data(block_diffusion.step_key(b)) for b in others]
    assert base not in keys and len({tuple(k) for k in keys}) == len(keys)
    assert keys[0] == data(sdar_decoder.step_key(tokens, 1))


def test_the_objective_reads_tokens_and_the_step_count_alone():
    """`targets` is not read (the row of position i predicts x_i itself);
    the key folds the step count the train step hands in, so step 0 of
    `make_train_step` is `loss_and_weight_fn` without a step, step 1
    another draw."""
    params, batch = seeded_params(SDAR, FP32), SDAR.batch_of(FP32)
    loss = jax.jit(lambda p, b: llama.loss_and_weight_fn(p, b, FP32)[0])
    plain = float(loss(params, batch))
    assert float(loss(params, {**batch, "targets": batch["targets"] * 0})) == plain
    assert float(loss(params, {**batch, "step": jnp.int32(0)})) == plain
    assert abs(float(loss(params, {**batch, "step": jnp.int32(1)})) - plain) > 1e-3
    opt = optax.adamw(1e-3)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, FP32), opt)
    # the step donates its state: a copy, or the memo's arrays are gone for the next case
    fresh = jax.tree.map(lambda w: jnp.array(w, copy=True), seeded_params(SDAR, FP32))
    state, first = step(TrainState.create(fresh, opt), batch)
    assert float(first["loss"]) == pytest.approx(plain, rel=1e-6)
    assert int(first["stats"]["diff_masked"]) == int(
        sdar_decoder.corrupt(batch["tokens"], SDAR.shape_of(FP32), 0)["masked"].sum())
    _, second = step(state, batch)
    assert int(second["stats"]["diff_masked"]) == int(
        sdar_decoder.corrupt(batch["tokens"], SDAR.shape_of(FP32), 1)["masked"].sum())


def test_every_microbatch_of_an_accumulated_step_draws_noise_of_its_own():
    """`grad_accum` 2 over a batch whose two halves are the SAME sequence:
    the loss function is handed the count of its evaluations (the
    optimizer's step x 2 + the microbatch's index), so the halves are
    masked differently in the first step and again in the second, each as
    the reference masks that sequence at that count."""
    one = SDAR.batch_of(FP32)["tokens"][:1]
    batch = {"tokens": jnp.concatenate([one, one]), "targets": jnp.concatenate([one, one])}
    opt = optax.adamw(1e-3)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, FP32), opt, grad_accum=2)
    fresh = jax.tree.map(lambda w: jnp.array(w, copy=True), seeded_params(SDAR, FP32))
    state, first = step(TrainState.create(fresh, opt), batch)
    _, second = step(state, batch)
    got = [int(n) for m in (first, second) for n in m["stats"]["diff_masked"]]
    want = [int(sdar_decoder.corrupt(one, SDAR.shape_of(FP32), k)["masked"].sum())
            for k in range(4)]
    assert got == want and len(set(got)) > 1
    at = [int(n) for m in (first, second) for n in m["stats"]["diff_masked_at"]]
    assert len(set(at)) == 4


def test_the_attention_alone_is_the_references_on_the_edge_rows_and_a_wrong_rule_is_not():
    """The runner's `first_attention_is_the_reference`, small and in float32:
    layer 0's q, k, v through the program's own call under the mask
    (kernels and merge) against the dense mask on the first and last rows
    of both copies, forward and a cotangent pulled back; then the two rules
    no other limit of the cell sees, which this reading refuses."""
    from chipbench import manifest as mf

    runner = mf.load_plugin(mf.ROOT, "runners", "train_reference_sdar")
    cfg = dataclasses.replace(FP32, attention_impl="flash")
    params, shape = seeded_params(SDAR, FP32), SDAR.shape_of(FP32)
    tokens = SDAR.batch_of(FP32)["tokens"]
    noised = sdar_decoder.corrupt(tokens, shape)["noised"]
    rows = runner.edge_rows(S)
    assert len(rows) == 4 * min(runner.EDGE, S // 2) and rows.max() == 2 * S - 1
    w = jax.random.normal(jax.random.key(3), (len(rows), cfg.n_heads, cfg.head_dim))

    def reads():
        return jax.jit(lambda p, t, n: sdar_decoder.first_attention(
            p, t, n, rows, w, shape, round_to=cfg.dtype))(params, tokens[0], noised[0])

    qkv, want = reads()
    with jax.default_matmul_precision("highest"):
        errors = runner.attention_errors(runner.program_attention(cfg, qkv, rows, w), want)
    assert set(errors) == {"o", "dq", "dk", "dv"} and max(errors.values()) < 2e-5, errors
    for wrong in ("causal inside the clean copy's block", "noised -> clean with <= in place of <"):
        with sdar_wrong.VARIANTS[wrong]():
            off = runner.attention_errors(reads()[1], want)
        assert off["o"] > 0.1 and off["dq"] > 0.1, (wrong, off)


@pytest.mark.parametrize("wrong", list(sdar_wrong.VARIANTS))
def test_one_thing_wrong_moves_the_tiny_loss_or_gradient(wrong):
    """The cell's one-thing-wrong table (chipbench/tools/sdar_wrong.py: the
    same patches of the reference), at the tiny size in float32: each row
    moves the loss, some leaf's gradient or the masked positions beyond
    the train path's own tolerances (2e-6 and 2e-4, which the program
    meets), so a program that computed so would fail here."""
    sound = reference_path(SDAR, FP32)
    params, batch, shape = seeded_params(SDAR, FP32), SDAR.batch_of(FP32), SDAR.shape_of(FP32)

    def f(p):   # a function a case: `jax.jit` keeps one trace a function, patched or not
        return sdar_decoder.loss_parts(p, batch["tokens"], batch["targets"], shape)["loss"]

    with sdar_wrong.VARIANTS[wrong](), SDAR.reference_set_up(), \
            jax.default_matmul_precision("highest"):
        moved = abs(float(jax.jit(f)(params)) / float(sound.parts["loss"]) - 1)
        if wrong not in sdar_wrong.PRECISION_ONLY:
            assert moved > 2e-4, moved
            return
        grads = jax.jit(jax.grad(f))(params)
    worst = max(worst_leaf(jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                           sound.grads).values())
    assert moved > 2e-6 or worst > 2e-4, (moved, worst)


def test_the_eight_shares_of_the_references_layer_add_up_to_the_uncut_layer():
    """The cell's deployment, small: 128 experts, top-8 softmax with a
    bias, weights renormalised, no shared expert, over 8 chips of 16
    experts. Each share computes its own experts' part of the routed sum
    over the same routing; the parts add up to the uncut layer's, and
    every share chooses what the uncut layer chooses."""
    d, f, rows = 64, 32, 2 * S
    keys = iter(jax.random.split(jax.random.key(2), 8))
    lp = {"ln2": 1 + 0.2 * jax.random.normal(next(keys), (d,)),
          "router": jax.random.normal(next(keys), (d, 128)) / 8,
          "router_bias": 0.01 * jax.random.normal(next(keys), (128,)),
          "w_gate": jax.random.normal(next(keys), (128, d, f)) / 8,
          "w_up": jax.random.normal(next(keys), (128, d, f)) / 8,
          "w_down": jax.random.normal(next(keys), (128, f, d)) / 6}
    h = jax.random.normal(next(keys), (rows, d))
    shape = {"rms_norm_eps": 1e-6, "num_experts_per_tok": 8, "norm_topk_prob": True,
             "num_experts": 128, "deployment": {"first_expert_held": 0}}

    def share(first):
        held = {**lp, **{k: lp[k][first:first + 16] for k in ("w_gate", "w_up", "w_down")}}
        return sdar_decoder.experts(h, held, {**shape, "num_experts": 16,
                                              "deployment": {"first_expert_held": first}})

    with jax.default_matmul_precision("highest"):
        whole, chosen = sdar_decoder.experts(h, lp, shape)
        parts = [share(first) for first in range(0, 128, 16)]
    assert chosen.sum(-1).tolist() == [8] * rows
    np.testing.assert_allclose(sum(np.asarray(out - h) for out, _ in parts),
                               np.asarray(whole - h), rtol=2e-5, atol=2e-5)
    for _, theirs in parts:
        assert (np.asarray(theirs) == np.asarray(chosen)).all()


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    SDAR)

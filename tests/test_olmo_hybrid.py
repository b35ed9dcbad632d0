"""Olmo-Hybrid through the one decoder (PR 46), at a small size on the
CPU (two periods of three linear layers and a full one, 150 positions:
two chunks of 64 and 22 more), seeded weights, against the plain
reference (chipbench/reference/olmo_hybrid_decoder.py, imported, which
runs the recurrence position by position): the causal convolution, both
sublayers, the eight shares of the tables, the refusals. (The chunked rule
against the plain recurrence: tests/test_gated_delta.py; the whole train
path in logits, loss and every gradient, the readings the configuration
file's `assumed` did NOT take each told from the one it took, remat and
bf16: tests/test_contract_olmo_hybrid.py; `config_from_hf` and the engine's
refusal: tests/test_model_contract.py.)"""

import dataclasses
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.reference import olmo_hybrid_decoder as ref
from chipbench.tools.olmo_hybrid_wrong import olmo3_rotary
from model_cases import OLMO_HYBRID, seeded_params
from ray_tpu.models import llama, olmo_hybrid as oh
from ray_tpu.models.registry import get_model_config, list_models
# the jax.numpy convolution the model ran until PR 48: the reference of ops/gdn_conv.py's kernels
from test_gdn_conv import causal_conv

FP32, B, S = OLMO_HYBRID.fp32, OLMO_HYBRID.batch, OLMO_HYBRID.seq
SHAPE = OLMO_HYBRID.shape_of(FP32)


def block_of(params, position, period=0):
    return jax.tree.map(lambda w: w[period], params["layers"]["period"][str(position)])


def stream(seed=3):
    return jax.random.normal(jax.random.key(seed), (B, S, FP32.d_model))


# -- the tree ---------------------------------------------------------------------------


def test_the_stack_is_whole_periods_of_three_linear_layers_and_a_full_one():
    full = oh._plan(oh.OLMO_HYBRID_7B)
    assert full["periods"] == 8 and full["dense"] is None and not full["tail"]
    assert full["period"] == [(oh.LINEAR, 30)] * 3 + [(oh.FULL, 30)]
    assert oh._plan(FP32)["periods"] == 2
    with pytest.raises(ValueError, match="does not end on a whole period"):
        oh._plan(dataclasses.replace(FP32, n_layers=6))
    params = llama.init_params(FP32, jax.random.key(0))
    axes = jax.tree.map(lambda a: 0, llama.logical_axes(FP32), is_leaf=lambda x: isinstance(x, tuple))
    assert jax.tree.structure(params) == jax.tree.structure(axes)
    for (path, leaf), ax in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree.leaves(llama.logical_axes(FP32),
                                                is_leaf=lambda x: isinstance(x, tuple))):
        assert leaf.ndim == len(ax), jax.tree_util.keystr(path)


def test_counts_of_parameters_and_operations_are_the_trees_and_the_issues():
    assert FP32.num_params() == sum(x.size for x in jax.tree.leaves(
        llama.init_params(FP32, jax.random.key(0))))
    full = get_model_config("olmo-hybrid-7b")
    cell = dataclasses.replace(full, n_layers=4, vocab_size=12544)
    assert round(full.num_params() / 1e9, 2) == 7.43
    assert round(cell.num_params() / 1e6, 1) == 928.9              # ISSUE 46: 928.8M + the norms
    # ISSUE 46, step 5: 1,810 MFLOP a token forward with the recurrence in its chunked count
    # (about 6 a mixer); in the position-by-position count (3.3) it is 1,802
    assert round(cell.flops_per_token(4096) / 1e6) == 1802
    linear = 2 * cell._mixer_matmul_params(oh.LINEAR) + 6 * 30 * 96 * 192
    assert round(linear / 1e6, 1) == 180.7 and round(3 * linear / cell.flops_per_token(4096), 3) == 0.301


def test_the_decay_starts_as_flas_does():
    """A uniform in (0, 16) and dt log-uniform in (1e-3, 1e-1): a fresh
    layer forgets between a thousandth and 1.6 of its state's log a position."""
    lp = oh.attention_params(FP32, jax.random.key(1), oh.LINEAR, n=64)
    A, dt = np.exp(np.asarray(lp["A_log"])), np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 0 < A.min() and A.max() <= 16 and 0.9e-3 <= dt.min() and dt.max() <= 0.11


# -- the sublayers -----------------------------------------------------------------------


def test_the_causal_convolution_sees_nothing_ahead_of_t_and_is_the_references():
    x = jax.random.normal(jax.random.key(0), (B, 3, S, 12))
    taps = jax.random.normal(jax.random.key(1), (4, 36))
    got = causal_conv(x, taps)
    for b in range(B):
        flat = jnp.swapaxes(x[b], 0, 1).reshape(S, 36)                  # [S, heads x d]
        want = ref.conv(flat, taps).reshape(S, 3, 12)
        np.testing.assert_allclose(np.asarray(jnp.swapaxes(got[b], 0, 1)), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    again = causal_conv(x.at[:, :, 70:].add(1.0), taps)
    assert float(jnp.abs(again[:, :, :70] - got[:, :, :70]).max()) == 0.0
    assert float(jnp.abs(again[:, :, 70] - got[:, :, 70]).max()) > 0.0
    # the first position sees zeros before the sequence: tap 0 alone
    np.testing.assert_allclose(np.asarray(got[:, :, 0]),
                               np.asarray(x[:, :, 0] * taps[0].reshape(3, 12)), rtol=1e-6)


@pytest.mark.parametrize("neg_eigval", [True, False], ids=["beta_to_2", "beta_to_1"])
def test_linear_sublayer_is_the_references(neg_eigval):
    cfg = dataclasses.replace(FP32, allow_neg_eigval=neg_eigval)
    shape = {**SHAPE, "linear_allow_neg_eigval": neg_eigval}
    lp, x = block_of(seeded_params(OLMO_HYBRID, FP32), 1), stream()
    with jax.default_matmul_precision("highest"):
        got = oh.gdn_sublayer(x, lp, cfg, positions=jnp.arange(S), segment_ids=None)
        want = jnp.stack([ref.linear_mixer(x[b], lp, shape) for b in range(B)])
        other = jnp.stack([ref.linear_mixer(x[b], lp, {**shape, "linear_allow_neg_eigval":
                                                       not neg_eigval}) for b in range(B)])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-5 * scale
    assert float(jnp.abs(got - other).max()) > 1e-2 * scale   # the doubling is seen


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_full_sublayer_is_the_references_and_has_no_rotary(impl):
    cfg = dataclasses.replace(FP32, attention_impl=impl)
    lp, x = block_of(seeded_params(OLMO_HYBRID, FP32), 3), stream()
    with jax.default_matmul_precision("highest"):
        got = oh.full_sublayer(x, lp, cfg, positions=jnp.arange(S), segment_ids=None)
        want = jnp.stack([ref.full_mixer(x[b], lp, SHAPE) for b in range(B)])
        with mock.patch.object(ref, "rotary", olmo3_rotary):
            turned = jnp.stack([ref.full_mixer(x[b], lp, SHAPE) for b in range(B)])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-5 * scale
    assert float(jnp.abs(got - turned).max()) > 1e-2 * scale  # the other reading is another layer


# -- the model -----------------------------------------------------------------------------


def test_eight_row_slices_of_the_tables_give_the_eight_column_blocks_of_the_logits():
    """The cell's deployment, small: the embedding and the head divided by
    rows over 8 chips. A chip that holds rows [a, b) computes, for tokens
    drawn from its slice, exactly the columns [a, b) of the uncut model's
    logits: the layers are whole on every chip and nothing else of the
    tree is cut."""
    params = seeded_params(OLMO_HYBRID, FP32)
    V, n = FP32.vocab_size, 8
    rows = V // n
    with jax.default_matmul_precision("highest"):
        forward = jax.jit(lambda p, t, cfg: llama.forward(p, t, cfg), static_argnums=2)
        for chip in range(n):
            a = chip * rows
            tokens = a + jax.random.randint(jax.random.key(chip), (1, 70), 0, rows)
            whole = forward(params, tokens, FP32)
            share = {**params, "embed": params["embed"][a:a + rows],
                     "lm_head": params["lm_head"][:, a:a + rows]}
            cut = forward(share, tokens - a, dataclasses.replace(FP32, vocab_size=rows))
            np.testing.assert_allclose(np.asarray(cut), np.asarray(whole[..., a:a + rows]),
                                       rtol=1e-5, atol=1e-5)
    # and the reference is given the same rows: its loss over the slice is the program's
    cfg = dataclasses.replace(FP32, vocab_size=rows)
    share = {**params, "embed": params["embed"][:rows], "lm_head": params["lm_head"][:, :rows]}
    batch = OLMO_HYBRID.batch_of(cfg)
    with jax.default_matmul_precision("highest"):
        loss = llama.loss_fn(share, batch, cfg)
    want = ref.loss(share, batch["tokens"], batch["targets"], OLMO_HYBRID.shape_of(cfg))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)


def test_the_train_step_learns_a_batch_by_the_registrys_name():
    from ray_tpu.train.step import TrainState, make_train_step

    cfg = get_model_config("olmo-hybrid-tiny")
    assert isinstance(cfg, oh.OlmoHybridConfig) and "olmo-hybrid-7b" in list_models()
    opt = optax.adamw(3e-3)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    batch = OLMO_HYBRID.batch_of(cfg)
    losses = []
    for _ in range(12):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.6 * losses[0], losses
    assert "stats" not in m


def test_the_dots_policy_keeps_the_flash_output_and_runs_the_rule_under_its_scope():
    cfg = dataclasses.replace(FP32, remat=True, remat_policy="dots", n_layers=4)
    params, batch = seeded_params(OLMO_HYBRID, cfg), OLMO_HYBRID.batch_of(cfg)
    grad = jax.jit(jax.grad(lambda p: llama.loss_fn(p, batch, cfg)))
    text = str(jax.make_jaxpr(grad)(params))
    assert "attn_out" in text
    import re

    names = set(re.findall(r'op_name="([^"]*)"', grad.lower(params).compile().as_text()))
    for scope in ("gdn.proj", "gdn.conv", "gdn.gates", "gdn.scan", "gdn.norm", "gdn.out",
                  "attn.qkv", "attn.rope", "attn.attend", "attn.out", "dense.ffn", "block.norm"):
        assert any(scope in n for n in names), scope


# -- the refusals ---------------------------------------------------------------------------


def test_packed_sequences_are_refused_by_name_under_a_linear_layer():
    params, batch = seeded_params(OLMO_HYBRID, FP32), OLMO_HYBRID.batch_of(FP32)
    with pytest.raises(NotImplementedError, match="segment_ids .packed documents. under a linear"):
        llama.loss_and_weight_fn(params, {**batch, "segment_ids": jnp.zeros((B, S), jnp.int32)},
                                 FP32)


def test_no_other_configuration_loads_the_module():
    """`ray_tpu.models.registry` knows the names and loads
    models/olmo_hybrid.py (and with it ops/gated_delta.py) only when one
    is asked for; Laguna, whose stack goes through the same seam of
    models/llama.py, does not."""
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.models import llama, registry; "
            "c = registry.get_model_config('laguna-tiny'); llama.logical_axes(c); "
            "llama.logical_axes(registry.get_model_config('mistral-7b')); "
            "assert 'ray_tpu.models.olmo_hybrid' not in sys.modules; "
            "assert 'ray_tpu.ops.gated_delta' not in sys.modules; "
            "c = registry.get_model_config('olmo-hybrid-tiny'); llama.logical_axes(c); "
            "assert 'ray_tpu.ops.gated_delta' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})

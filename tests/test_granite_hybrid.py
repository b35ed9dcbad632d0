"""models/granite_hybrid.py (granite-4.0-h-micro: Mamba-2 mixers in ONE
group and NoPE GQA layers, each over a SwiGLU, four muP multipliers, a
tied table) against the plain reference
(chipbench/reference/granite_hybrid_decoder.py, which imports nothing of
the program and runs the scan position by position, its state taken as
zero where the document changes), on PACKED DOCUMENTS and without them:
the loss, every gradient and the logits; packing tied to the model (a
packed sequence's outputs and per-document losses are each document's run
alone); each reading of the equations NOT taken, and each way of getting a
boundary wrong, told from the one taken; the scan alone as the benchmark's
runner holds it; the stack's plan, the counts by hand and the refusals by
name."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest as mf
from chipbench.reference import granite_hybrid_decoder as ref
from chipbench.tools.granite_hybrid_wrong import PRECISION_ONLY, VARIANTS
from ray_tpu.models import granite_hybrid as gh, llama, nemotron_h as nh
from ray_tpu.models.registry import config_from_hf, get_model_config

FP32 = dataclasses.replace(gh.GRANITE_HYBRID_TINY, dtype=jnp.float32)
FULL = gh.GRANITE_4_H_MICRO
SEQ = 88   # five chunks of 16 and 8 positions more
# documents of 1, 15, 16 (to a chunk's edge), 2, 1 (consecutive boundaries), 30 and 23 positions:
# boundaries at position 1, inside a chunk, on a chunk's edge and in consecutive positions
LENGTHS = (1, 15, 16, 2, 1, 30, 23)


def shape_of(cfg) -> dict:
    """A GraniteHybridConfig as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "shared_intermediate_size": cfg.d_ff,
        "num_local_experts": 0, "layer_types": list(cfg.published_types),
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.rms_eps,
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_n_groups": cfg.ssm_groups, "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": cfg.conv_kernel, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "mamba_expand": cfg.mamba_inner // cfg.d_model, "attention_bias": False,
        "position_embedding_type": "nope", "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier, "logits_scaling": cfg.logits_scaling,
        "max_position_embeddings": cfg.max_seq, "tie_word_embeddings": cfg.tie_embeddings,
        "vocab_size": cfg.vocab_size,
    }


SHAPE = shape_of(FP32)


@functools.lru_cache(maxsize=None)
def seeded(seed=0):
    """init_params with the norms, D and the biases moved off the one or zero they start at."""
    params = llama.init_params(FP32, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 64))
    for group in params["layers"].values():
        for kind, leaves in group.items():
            moved = {"ln": 0.2, "ln2": 0.2, **({"norm": 0.2, "D": 0.3} if kind == "mamba" else {})}
            for name, scale in moved.items():
                leaves[name] = leaves[name] + scale * jax.random.normal(next(keys), leaves[name].shape)
    params["final_norm"] = params["final_norm"] + 0.2 * jax.random.normal(next(keys), (FP32.d_model,))
    return params


def packed_batch(batch=2, seed=1) -> dict:
    """Two sequences of SEQ tokens: the first LENGTHS' documents, the second the same
    lengths in reverse; ids that are NOT monotone (a document is a run of equal ids)."""
    tok = jax.random.randint(jax.random.key(seed), (batch, SEQ + 1), 0, FP32.vocab_size)
    rows = [np.repeat(np.arange(len(LENGTHS)), order) for order in (LENGTHS, LENGTHS[::-1])][:batch]
    doc = np.stack([np.concatenate([r, r[-1:]]) for r in rows])          # SEQ + 1 positions
    ids = (doc * 5 + 3) % 7                                               # equal ids apart recur
    assert all((np.diff(ids[b]) != 0).sum() == len(LENGTHS) - 1 for b in range(batch))
    return {"tokens": tok[:, :-1], "targets": tok[:, 1:],
            "segment_ids": jnp.asarray(ids[:, :-1], jnp.int32),
            "mask": jnp.asarray(doc[:, 1:] == doc[:, :-1], jnp.int32)}


def plain_batch() -> dict:
    batch = packed_batch()
    return {k: batch[k] for k in ("tokens", "targets")}


HIGHEST = jax.default_matmul_precision("highest")


@functools.lru_cache(maxsize=None)
def train_path(packed: bool):
    """(loss, every gradient) of llama.loss_and_weight_fn, one jitted program."""
    batch = packed_batch() if packed else plain_batch()
    with HIGHEST:
        return jax.jit(jax.value_and_grad(
            lambda p: llama.loss_and_weight_fn(p, batch, FP32)[0]))(seeded())


def reference_of(batch):
    return (batch["tokens"], batch["targets"], SHAPE, batch.get("segment_ids"), batch.get("mask"))


def worst(got, want) -> float:
    return max(float(jnp.abs(g - w).max() / jnp.maximum(jnp.abs(w).max(), 1e-12))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "one_document"])
def test_train_path_meets_the_reference_in_loss_and_every_gradient(packed):
    batch = packed_batch() if packed else plain_batch()
    loss, grads = train_path(packed)
    assert float(loss) == pytest.approx(float(ref.loss(seeded(), *reference_of(batch))), rel=3e-6)
    want = ref.grads(seeded(), *reference_of(batch))
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    assert worst(grads, want) < 3e-4
    assert float(train_path(True)[0]) != pytest.approx(float(train_path(False)[0]), rel=1e-4)


def test_logits_are_the_references_under_the_documents():
    batch = packed_batch()
    with HIGHEST:
        got = jax.jit(lambda p, t, s: llama.forward(p, t, FP32, segment_ids=s))(
            seeded(), batch["tokens"], batch["segment_ids"])
    theirs = jax.jit(lambda p, t, s: ref.logits(p, t, SHAPE, s))
    want = jnp.stack([theirs(seeded(), batch["tokens"][b], batch["segment_ids"][b])
                      for b in range(2)])
    assert float(jnp.abs(got - want).max()) < 3e-5 * float(jnp.abs(want).max())


def test_packing_ties_to_the_model_each_document_is_what_it_is_alone():
    """The packed sequence's logits at a document's positions, and that
    document's summed loss under the batch's mask, are the document run
    ALONE as a sequence of its own (no ids): through the program's kernels."""
    batch = packed_batch(batch=1)
    forward = jax.jit(lambda p, t, s: llama.forward(p, t, FP32, segment_ids=s))
    with HIGHEST:
        together = forward(seeded(), batch["tokens"], batch["segment_ids"])[0]
    logp = jax.nn.log_softmax(together, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][0][:, None], axis=-1)[:, 0]
    start = 0
    for n in LENGTHS:
        rows = slice(start, start + n)
        alone_batch = {"tokens": batch["tokens"][:, rows], "targets": batch["targets"][:, rows],
                       "mask": batch["mask"][:, rows]}
        with HIGHEST:
            alone = llama.forward(seeded(), alone_batch["tokens"], FP32)[0]
            loss, weight = llama.loss_and_weight_fn(seeded(), alone_batch, FP32)[:2]
        scale = float(jnp.abs(alone).max())
        assert float(jnp.abs(together[rows] - alone).max()) < 5e-5 * scale, (start, n)
        kept = batch["mask"][0, rows].astype(jnp.float32)
        if float(kept.sum()):
            assert float(loss * weight) == pytest.approx(float((nll[rows] * kept).sum()), rel=2e-5)
        start += n
    assert start == SEQ


@functools.lru_cache(maxsize=None)
def program_losses():
    """The program's cross-entropy a position of packed sequence 0, the masked ones zero."""
    batch = packed_batch(batch=1)
    with HIGHEST:
        logits = jax.jit(lambda p, t, s: llama.forward(p, t, FP32, segment_ids=s))(
            seeded(), batch["tokens"], batch["segment_ids"])[0]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               batch["targets"][0][:, None], axis=-1)[:, 0]
    return nll * batch["mask"][0]


def reference_losses(shape=SHAPE):
    batch = packed_batch(batch=1)
    return jax.jit(lambda p, t, y, s, m: ref.sequence(p, t, y, shape, s, m))(
        seeded(), *(batch[k][0] for k in ("tokens", "targets", "segment_ids", "mask")))


def test_the_programs_losses_a_position_are_the_sound_references():
    assert float(jnp.abs(reference_losses() - program_losses()).max()) < 3e-5


@pytest.mark.parametrize("name", [n for n in VARIANTS if n not in PRECISION_ONLY],
                         ids=lambda n: n.replace(" ", "_").replace("/", "over"))
def test_each_reading_not_taken_is_told_from_the_one_taken(name):
    """The program's cross-entropy a position on packed documents against
    the reference changed in ONE thing (the rows of the cell's
    one-thing-wrong table, chipbench/tools/granite_hybrid_wrong.py): thirty
    times and more outside what the sound comparison leaves (3e-5)."""
    wrong, changed = VARIANTS[name]
    with wrong():
        got = reference_losses({**SHAPE, **changed})
    assert float(jnp.abs(got - program_losses()).max()) > 1e-3, name


def test_the_scan_alone_as_the_runner_holds_it_with_the_batchs_documents():
    """runners/train_reference_granite_hybrid.py's `program_scan` (the name
    `ssd_scan` of the model's module, given the ids) against
    `reference.first_scan`, forward and the four cotangents; and without
    the ids it is another function."""
    runner = mf.load_plugin(mf.ROOT, "runners", "train_reference_granite_hybrid")
    batch = packed_batch(batch=1)
    config = {**SHAPE, "assumed_sizes": {"chunk_size": FP32.chunk_size}}
    w = runner.scan_cotangent(batch["tokens"], config, seed=5)
    assert w.shape == (SEQ, FP32.mamba_heads, FP32.mamba_head_dim)
    args, outputs = ref.first_scan(seeded(), batch["tokens"][0], SHAPE, w, batch["segment_ids"][0])
    got = runner.program_scan(FP32.stack_module, FP32.chunk_size, args, w, batch["segment_ids"][0])
    for name, want in zip(runner.SCAN_OUTPUTS, outputs):
        err = float(jnp.linalg.norm(got[name] - want) / jnp.linalg.norm(want))
        assert err < 2e-5, (name, err)
    one = runner.program_scan(FP32.stack_module, FP32.chunk_size, args, w,
                              jnp.zeros_like(batch["segment_ids"][0]))
    assert float(jnp.linalg.norm(one["y"] - outputs[0]) / jnp.linalg.norm(outputs[0])) > 1e-2


def test_the_stack_is_cut_from_layer_types_and_the_whole_40_build():
    """The published 40 are four periods of ten (ONE scan of four); the
    benchmark's ten are (Mamba x 5), attention, (Mamba x 4); the tree holds a
    group a segment, no slice of a kind's stack; the whole model builds
    abstractly at 36 / 4 layers of a kind."""
    assert nh.segments(FULL.layer_types) == [("MMMMM*MMMM", 4)]
    ten = dataclasses.replace(FULL, n_layers=10, vocab_size=12544)
    assert nh.segments(ten.layer_types) == [("M", 5), ("*", 1), ("M", 4)]
    assert [FULL.count(k) for k in "M*"] == [36, 4]
    params = jax.eval_shape(lambda: llama.init_params(ten, jax.random.key(0)))
    assert sorted(params["layers"]) == ["0", "1", "2"] and "lm_head" not in params
    assert params["layers"]["0"]["mamba"]["w_in"].shape == (5, 2048, 4096 + 4352 + 64)
    assert params["layers"]["1"]["attention"]["wk"].shape == (1, 2048, 512)
    assert params["layers"]["2"]["mamba"]["w_gate"].shape == (4, 2048, 8192)
    whole = jax.eval_shape(lambda: llama.init_params(
        dataclasses.replace(FULL, vocab_size=1024), jax.random.key(0)))
    assert whole["layers"]["0"]["mamba"]["conv"].shape == (36, 4, 4352)
    assert whole["layers"]["0"]["attention"]["wo"].shape == (4, 2048, 2048)
    axes = llama.logical_axes(ten)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_counts_of_parameters_and_operations_are_the_trees_and_the_issues():
    """By hand (ISSUE 66): a Mamba mixer 25,847,232, its layer 76,182,976, the
    attention layer 60,821,504, the eighth of the tied table 25,690,112:
    772,160,448 at the cell's sizes; the tiny tree is its count."""
    cell = dataclasses.replace(FULL, n_layers=10, vocab_size=12544)
    mixer = 2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048
    swiglu = 3 * 2048 * 8192
    attention = 2 * 2048 * 64 * (32 + 8)
    assert (mixer, swiglu, attention) == (25_847_232, 50_331_648, 10_485_760)
    assert mixer + swiglu + 2 * 2048 == 76_182_976 and attention + swiglu + 2 * 2048 == 60_821_504
    assert cell.num_params() == 9 * 76_182_976 + 60_821_504 + 25_690_112 + 2048 == 772_160_448
    tiny = jax.eval_shape(lambda: llama.init_params(FP32, jax.random.key(0)))
    assert FP32.num_params() == sum(a.size for a in jax.tree.leaves(tiny))
    one = lambda kind: dataclasses.replace(cell, published_types=(kind,), n_layers=1)  # noqa: E731
    head = 2.0 * 2048 * 12544
    assert one("mamba").flops_per_token(8192) - head == (
        2.0 * (2048 * 8512 + 4096 * 2048 + swiglu) + 5.0 * 64 * 64 * 128)
    assert one("attention").flops_per_token(8192) - head == (
        2.0 * (attention + swiglu) + 4.0 * 64 * 32 * 8193 / 2)


def test_the_first_loss_of_random_weights_is_near_ln_of_the_vocabulary():
    """The tied table at hidden ** -0.5: under x 12 and / 8 the loss of the
    seeded tiny model is ln(512) to a twentieth."""
    loss = llama.loss_fn(llama.init_params(FP32, jax.random.key(3)), plain_batch(), FP32)
    assert float(loss) == pytest.approx(np.log(FP32.vocab_size), abs=0.05)
    table = llama.init_params(FP32, jax.random.key(3))["embed"]
    assert float(table.std()) == pytest.approx(0.987 * FP32.d_model ** -0.5, rel=0.05)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published() -> dict:
    import json
    import os

    if os.path.exists(CATALOG):
        for line in open(CATALOG):
            row = json.loads(line)
            if row["name"] == "granite-4.0-h-micro":
                return row["config"]
    file = mf.read_json(mf.ROOT, "chipbench/configs/granite-4.0-h-micro-train.json")
    return {**file, **file["published"]}


def test_config_from_hf_maps_the_published_config_onto_the_preset():
    cfg = config_from_hf(published())
    assert cfg == get_model_config("granite-4.0-h-micro") and type(cfg) is gh.GraniteHybridConfig
    assert (cfg.ssm_groups, cfg.mamba_heads, cfg.head_dim, cfg.d_ff) == (1, 64, 64, 8192)
    assert cfg.chunk_size == 128 and cfg.tie_embeddings and cfg.logits_scaling == 8.0
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier) == (
        12.0, 0.22, 0.015625)
    assert get_model_config("granite-hybrid-tiny") == gh.GRANITE_HYBRID_TINY


@pytest.mark.parametrize("key,value,names", [
    ("num_local_experts", 72, "num_local_experts 72 .routed experts."),
    ("num_experts_per_tok", 10, "routed experts"),
    ("position_embedding_type", "rope", "position_embedding_type 'rope' .a rotary."),
    ("mamba_n_groups", 3, "mamba_n_groups 3, which does not divide the 64 heads"),
    ("attention_bias", True, "a bias"),
    ("mamba_proj_bias", True, "a bias"),
    ("mamba_conv_bias", False, "a convolution without a bias"),
    ("layer_types", ["mamba", "sliding_attention"] * 20, "layer types other than mamba and attention"),
    ("hidden_act", "gelu", "hidden_act 'gelu'"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("mamba_expand", 3, "mamba_expand 3"),
    ("num_hidden_layers", 48, "layer_types shorter than num_hidden_layers"),
])
def test_config_from_hf_refuses_by_name_what_is_not_implemented(key, value, names):
    with pytest.raises(ValueError, match="granitemoehybrid config with .*" + names):
        config_from_hf({**published(), key: value})


def test_engine_refuses_the_model_by_name():
    from ray_tpu.llm.engine import EngineConfig

    with pytest.raises(ValueError, match="Granite 4.0-H.*training-only"):
        EngineConfig(model="granite-hybrid-tiny")
    with pytest.raises(ValueError, match="Nemotron-H"):   # as it was
        EngineConfig(model="nemotron-h-tiny")


@pytest.mark.parametrize("remat_policy", ["dots", "full"])
def test_remat_gives_the_same_loss_and_gradients_on_packed_documents(remat_policy):
    """The cell trains under remat "full": the rematerialised train path's loss
    and every gradient are the plain one's (the scan's and the convolution's
    kernels run again in the backward, with the same documents)."""
    cfg = dataclasses.replace(FP32, remat=True, remat_policy=remat_policy)
    batch = packed_batch()
    with HIGHEST:
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_and_weight_fn(p, batch, cfg)[0]))(seeded())
    want_loss, want = train_path(True)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert worst(grads, want) < 2e-5


def test_bf16_compute_through_the_flash_kernels_stays_near_the_reference():
    """The cell's own path: bfloat16 compute, the flash kernels (interpreted)
    under the documents' mask, against the plain reference on the same
    bfloat16-rounded parameters."""
    cfg = dataclasses.replace(FP32, dtype=jnp.bfloat16, attention_impl="flash")
    batch = packed_batch()
    loss = jax.jit(lambda p: llama.loss_and_weight_fn(p, batch, cfg)[0])(seeded())
    assert float(loss) == pytest.approx(float(ref.loss(seeded(), *reference_of(batch))), rel=0.02)
    plain = jax.jit(lambda p: llama.loss_and_weight_fn(p, plain_batch(), cfg)[0])(seeded())
    assert float(plain) != pytest.approx(float(loss), rel=1e-4)

"""The granite-h-micro-train-packed cell's files (PR 66): the manifest with
the cell (for however many cells there are), the configuration file against
the catalog's row, the model builder, the second generator (packed
documents), the runner that composes the runners there were and hands the
reference the batch's documents, the cost functions by hand-worked cases,
each new reader on a hand-built step table, and the rows of the
one-thing-wrong tool. It also carries, for this cell, every assertion but
one of tests/chipbench/test_chipbench_step.py's
`test_training_cell_is_well_formed_and_reports_what_it_did_and_the_new_metrics`,
which holds every training cell to the ONE generator there was (that case
is skipped from tests/conftest.py: the file may not be edited)."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_granite_hybrid as cg, manifest as mf, readers_granite_hybrid
from chipbench.tools import granite_hybrid_wrong as wrong_tool

M = mf.load_manifest()
CELL, CONFIG, TRAFFIC = ("granite-h-micro-train-packed", "granite-4.0-h-micro-train",
                         "packed_zipf_docs")
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
TRAFFIC_FILE = mf.read_json(mf.ROOT, f"chipbench/traffic/{TRAFFIC}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "vocab_size"]
NEW_METRICS = ("ssm_share_pct.g1", "ssm_scan_pct.g1", "ssm_glue_pct.g1", "ssd_scan_roofline.g1",
               "flash_roofline.nope64", "train_mfu_pct.granite_hybrid")
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
TIMELINE = ("dispatch_ms.train", "step_stalls.train", "stall_loss_pct.train", "gc_pause_ms.train",
            "report_max_ms.train", "host_other_cpu_pct.train", "step_gap_ms.train",
            "step_gap_program_pct.train")
# what every training cell reports, the attention and the dense families, and this cell with them
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "head_share_pct", "optim_share_pct",
          "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct", "fallback_sites.train",
          "attn_share_pct", "ffn_share_pct") + SETUP + TIMELINE
PEAKS = costs.load_peaks("TPU v5 lite")
generator = mf.load_plugin(mf.ROOT, "generators", "packed_zipf_docs")


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "packed_zipf_docs"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["zipf_s"]) == (8192, 1.1)
    assert (cell["traffic"]["median_len"], cell["traffic"]["sigma"], cell["traffic"]["min_len"]) == (
        600, 1.2, 16)
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert len(cell["cell"]["why"]) <= 200 and cell["cell"]["why"] == M["workloads"][-1]["why"]
    assert CELLS[-1] == CELL and M["configs"][-1]["name"] == CONFIG
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "train", "check",
                "reference"):
        assert SHAPE[key], key
    assert "TO FILL" not in json.dumps(SHAPE) and "TO BE FILLED" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED)
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    assert [w["chips"] for w in M["workloads"]].count(4) == 1
    assert [w["name"] for w in M["workloads"] if w["traffic"] == TRAFFIC] == [CELL]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] == ("state-space mixer" if name.startswith("ssm_") else
                          "train step" if "mfu" in name else "kernels")
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
    assert m["source"] == ("host_clock" if "mfu" in name else "device_trace")
    assert reader(name).__doc__
    # a program without the scopes or the model (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert reader(name).read({"shape": SHAPE, "trace": None}) is None
    names = [e["name"] for e in M["per_layer"]]
    assert names[-6:] == list(NEW_METRICS) and len(set(names)) == len(names)


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_gains_the_cell_at_its_lists_end(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]   # the manifest's order
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name not in ("attn_share_pct", "ffn_share_pct", "fallback_sites.train"):
        assert m["workloads"] == TRAINING_CELLS
    if name == "ffn_share_pct":   # the cells with a dense SwiGLU under `dense.ffn`
        assert {"m7b-train", "olmo-hybrid-train", "kimi-linear-train-8k"} <= set(m["workloads"])


def test_the_accepted_ssm_metrics_stay_twotowers_alone():
    for name in ("ssm_share_pct", "ssm_scan_pct", "ssm_glue_pct", "ssd_scan_roofline"):
        assert mf.by_name(M["per_layer"], name, "metric")["workloads"] == ["twotower-train-8k"]


def test_step_scopes_name_what_the_cells_readers_sum_and_add_no_scope():
    from chipbench import readers_step

    own = mf.read_json(mf.ROOT, "chipbench/step_scopes/granite_hybrid.json")
    assert set(own) == {"comment", "families"}
    merged = readers_step.vocabulary()["families"]
    for family, scopes in own["families"].items():
        assert merged[family][:0] == [] and set(scopes) <= set(merged[family])
    nemotron = mf.read_json(mf.ROOT, "chipbench/step_scopes/nemotron_h.json")
    assert {f: own["families"][f] for f in nemotron["families"]} == nemotron["families"]
    assert readers_granite_hybrid.FAMILIES == tuple(nemotron["families"])
    assert set(SHAPE["check"]["scopes"]) <= {s for f in merged.values() for s in f}


# -- the configuration file against the catalog -------------------------------------


def catalog_row():
    import os

    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this installation")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "granite-4.0-h-micro":
            return row
    raise AssertionError("the catalog has no granite-4.0-h-micro")


def test_every_published_key_is_the_catalogs_but_the_two_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v]
    assert differs == REDUCED
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    assert SHAPE["layer_types"] == row["config"]["layer_types"] and len(SHAPE["layer_types"]) == 40


def test_every_width_the_issue_names_is_as_published_and_no_cut_names_a_width():
    widths = {"hidden_size": 2048, "shared_intermediate_size": 8192, "intermediate_size": 8192,
              "num_attention_heads": 32, "num_key_value_heads": 8, "mamba_n_heads": 64,
              "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
              "mamba_expand": 2, "mamba_chunk_size": 256, "embedding_multiplier": 12,
              "residual_multiplier": 0.22, "attention_multiplier": 0.015625, "logits_scaling": 8}
    assert {k: SHAPE[k] for k in widths} == widths
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    assert SHAPE["num_hidden_layers"] == 10 and SHAPE["layer_types"][:10] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)      # one whole period
    assert SHAPE["vocab_size"] == 12544 == 100352 // 8 and SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["assumed_sizes"]["chunk_size"] == 128      # what ops/ssd.py walks, and why
    assert "256" in SHAPE["assumed"]["iii_chunk"] and "128" in SHAPE["assumed"]["iii_chunk"]
    assert cg.num_params(SHAPE) == 772_160_448
    for stated in ("772,160,448", "76,182,976", "60,821,504"):
        assert stated in SHAPE["memory"] or stated in json.dumps(SHAPE["deployment"]), stated


def test_the_file_states_the_rehearsals_three_readings_and_the_policy_taken():
    told = SHAPE["reduced"]["num_hidden_layers"]
    for reading in ("(a)", "(b)", "(c)", "19.58", "14.28"):
        assert reading in told, reading
    assert SHAPE["train"]["remat_policy"] == "full" and SHAPE["train"]["remat_policy_why"]
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["lr"] == 3e-4


# -- the builder ---------------------------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.vocab_size, cfg.d_model, cfg.d_ff) == (10, 12544, 2048, 8192)
    assert (cfg.mamba_heads, cfg.ssm_groups, cfg.ssm_state, cfg.chunk_size) == (64, 1, 128, 128)
    assert cfg.remat and cfg.remat_policy == "full" and cfg.attention_impl == "flash"
    assert cfg.num_params() == cg.num_params(SHAPE) == 772_160_448
    params = jax.eval_shape(init, jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == 772_160_448
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 1024), ("mamba_n_groups", 8), ("attention_multiplier", 0.125),
    ("logits_scaling", 1), ("residual_multiplier", 1.0), ("embedding_multiplier", 1),
    ("position_embedding_type", "rope"), ("num_local_experts", 8), ("mamba_conv_bias", False),
    ("shared_intermediate_size", 4096),
])
def test_builder_refuses_a_changed_width_multiplier_or_form(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="not at the file's sizes"):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_changed_published_counts_or_another_chunk():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="not at the file's sizes"):
        builder.build({**SHAPE, "published": {**SHAPE["published"], "num_hidden_layers": 48}})
    with pytest.raises(RuntimeError, match="not at the file's sizes"):
        builder.build({**SHAPE, "assumed_sizes": {**SHAPE["assumed_sizes"], "chunk_size": 256}})
    with pytest.raises(RuntimeError, match="not at the file's sizes"):
        builder.build({**SHAPE, "layer_types": ["mamba"] * 40})


# -- the generator ----------------------------------------------------------------------------

SMALL = {**TRAFFIC_FILE, "seq_len": 512, "max_context": 512, "median_len": 40}


@pytest.fixture(scope="module")
def batches():
    make = generator.batch_fn(SMALL, 1000, 3, seed=2 ** 31 + 11)
    return make, [jax.device_get(make(i)) for i in range(6)]


def test_a_batch_is_documents_end_to_end_with_no_padding(batches):
    _, made = batches
    for b in made:
        assert set(b) == {"tokens", "targets", "segment_ids", "mask"}
        assert all(v.shape == (3, 512) and v.dtype == np.int32 for v in b.values())
        for row in b["segment_ids"]:
            assert row[0] == 0 and set(np.diff(row)) <= {0, 1}    # end to end, counted from 0
            lengths = np.bincount(row)
            assert (lengths[:-1] >= 16).all() and lengths.sum() == 512   # every position a token
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])
        assert 0 <= b["tokens"].min() and b["tokens"].max() < 1000


def test_the_mask_is_zero_at_a_documents_last_position_and_nowhere_else(batches):
    _, made = batches
    for b in made:
        ids, mask = b["segment_ids"], b["mask"]
        np.testing.assert_array_equal(mask[:, :-1], (ids[:, 1:] == ids[:, :-1]).astype(np.int32))
        assert set(np.unique(mask)) <= {0, 1}
        # the masked targets are the documents that END inside the sequence
        assert ((mask == 0).sum(axis=1) >= ids[:, -1]).all()
        assert ((mask == 0).sum(axis=1) <= ids[:, -1] + 1).all()


def test_lengths_keep_their_bounds_and_their_median():
    lens = np.asarray(generator.lengths(jax.random.key(0), 64, TRAFFIC_FILE))
    assert lens.shape == (64, 512) and lens.min() >= 16 and lens.max() <= 8192
    assert 520 < np.median(lens) < 690 and 1050 < lens.mean() < 1400
    assert 0.03 < (lens > 4096).mean() < 0.07
    docs = np.asarray(generator.documents_of(jnp.asarray([[3, 2, 50]]), 8))
    np.testing.assert_array_equal(docs, [[0, 0, 0, 1, 1, 2, 2, 2]])


def test_one_program_for_every_seed_and_another_batch_a_step(batches):
    make, made = batches
    other = generator.batch_fn(SMALL, 1000, 3, seed=12)
    other(0)
    assert make.func is not other.func and make.func._cache_size() == 1
    assert other.func._cache_size() == 1          # the seed is an argument: one program each
    assert not np.array_equal(made[0]["tokens"], made[1]["tokens"])
    assert not np.array_equal(made[0]["segment_ids"], made[1]["segment_ids"])
    np.testing.assert_array_equal(jax.device_get(make(0))["segment_ids"], made[0]["segment_ids"])
    assert not np.array_equal(jax.device_get(other(0))["tokens"], made[0]["tokens"])
    expected = generator.expected(TRAFFIC_FILE, 12544, 7)
    assert expected["ln_vocab"] == pytest.approx(np.log(12544))
    assert expected["mean_document_unclipped"] == pytest.approx(1232.66, rel=1e-4)
    with pytest.raises(ValueError, match="over 8192"):
        generator.batch_fn({**TRAFFIC_FILE, "seq_len": 16384}, 1000, 1, 0)


# -- the runner's own pieces -----------------------------------------------------------------

runner = mf.load_plugin(mf.ROOT, "runners", "train_reference_granite_hybrid")


def test_the_reference_is_handed_batch_0s_documents_and_refuses_another_batch():
    seen = {}

    def loss(params, tokens, targets, config, segment_ids, mask):
        seen.update(segment_ids=segment_ids, mask=mask)
        return 1.5

    batch = {"tokens": np.arange(6).reshape(1, 6), "targets": np.arange(1, 7).reshape(1, 6),
             "segment_ids": np.array([[0, 0, 1, 1, 1, 2]]), "mask": np.array([[1, 0, 1, 1, 0, 1]])}
    on = runner.OnBatch(types.SimpleNamespace(loss=loss), lambda: batch)
    assert on.loss(None, batch["tokens"], batch["targets"], {}) == 1.5
    assert seen["segment_ids"] is batch["segment_ids"] and seen["mask"] is batch["mask"]
    with pytest.raises(RuntimeError, match="another batch"):
        on.loss(None, batch["tokens"] + 1, batch["targets"], {})


def test_what_the_windows_batches_held_is_read_from_the_generator_again():
    make = generator.batch_fn(SMALL, 1000, 1, seed=5)
    steps = [{"step": i} for i in (4, 5, 6, 7)]
    packed = runner.packed(make, steps, steps[1:3])
    for i, at in enumerate((4, 5, 6, 7)):
        ids = np.asarray(make(at)["segment_ids"])[0]
        lengths = np.bincount(ids)
        assert packed["documents"][i] == len(lengths) and packed["longest"][i] == lengths.max()
        assert packed["visible_pairs"][i] == sum(int(n) * (int(n) + 1) // 2 for n in lengths)
        assert packed["masked_targets"][i] == int((np.asarray(make(at)["mask"]) == 0).sum())
    assert packed["visible_pairs_traced"] == pytest.approx(np.mean(packed["visible_pairs"][1:3]))
    assert runner.packed(make, steps, [])["visible_pairs_traced"] is None
    # one document: every causal pair; the fewest pairs: every document at the shortest length
    assert 16 * 17 // 2 * 32 <= min(packed["visible_pairs"]) <= max(packed["visible_pairs"]) <= (
        512 * 513 // 2)


# -- the costs by hand ----------------------------------------------------------------------


def test_required_operations_are_issue_66s_count():
    p = cg.matmul_params(SHAPE)
    assert p == {"mamba": 2048 * 8512 + 4096 * 2048, "attention": 10_485_760,
                 "swiglu": 50_331_648, "head": 25_690_112}
    assert cg.count(SHAPE, "mamba") == 9 and cg.count(SHAPE, "attention") == 1
    assert cg.state_elements(SHAPE) == 64 * 64 * 128 and cg.conv_channels(SHAPE) == 4352
    causal = 8192 * 8193 / 2
    f = cg.forward_flops_per_token(SHAPE, 8192, causal)
    assert f == {"mamba.proj": 9 * 2.0 * 25_821_184, "mamba.scan": 9 * 5.0 * 524_288,
                 "attention.proj": 2.0 * 10_485_760,
                 "attention.scores": 32 * 4.0 * 64 * 8193 / 2,
                 "swiglu": 10 * 2.0 * 50_331_648, "head": 2.0 * 25_690_112}
    assert round(sum(f.values()) / 1e6) == 1601           # MFLOP a token forward, one document
    assert cg.train_flops_per_token(SHAPE, 8192, causal) == 3.0 * sum(f.values())
    # seven documents of 1,170 tokens: a seventh of the pairs, the scores 33.6 -> 4.8 MFLOP
    packed = cg.forward_flops_per_token(SHAPE, 8192, 7 * 1170 * 1171 / 2)
    assert packed["attention.scores"] == pytest.approx(f["attention.scores"] / 7, rel=0.01)
    assert {k: v for k, v in packed.items() if k != "attention.scores"} == {
        k: v for k, v in f.items() if k != "attention.scores"}


def test_scan_and_flash_costs_by_hand():
    c = cg.scan_cost(SHAPE, 1, 8192)
    positions = 9 * 8192
    assert c["layers"] == 9 and c["fwd_flops"] == 5.0 * positions * 524_288
    assert c["bwd_flops"] == 11.0 * positions * 524_288
    inputs = positions * (4352 * 2 + 64 * 4)
    assert c["fwd_bytes"] == inputs + positions * 8192 and c["bwd_bytes"] == 2 * inputs + positions * 8192
    pairs = 3 * 2000 * 2001 / 2 + 2192 * 2193 / 2
    f = cg.flash_cost(SHAPE, 1, 8192, pairs)
    assert f["layers"] == 1 and f["fwd_flops"] == 32 * 4.0 * 64 * pairs and f["bwd_flops"] == 2.5 * f["fwd_flops"]
    q, kv = 8192 * 32 * 64 * 2, 8192 * 8 * 64 * 2
    assert f["fwd_bytes"] == 2 * q + 2 * kv and f["bwd_bytes"] == 4 * q + 4 * kv
    assert cg.flash_cost(SHAPE, 1, 8192, 8192 * 8193 / 2)["fwd_flops"] == costs.flash_cost(
        {**SHAPE, "head_dim": 64}, 1, 8192)["fwd_flops"]


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 1.8, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "ssm.scan": {"seconds": 0.30, "ops": {"kernel:ssd_scan_fwd.1": 0.2, "kernel:ssd_scan_bwd.1": 0.1}},
        "ssm.proj": {"seconds": 0.3, "ops": {"fusion.1": 0.3}},
        "ssm.out": {"seconds": 0.1, "ops": {"fusion.2": 0.1}},
        "ssm.conv": {"seconds": 0.05, "ops": {"kernel:gdn_conv_fwd.27": 0.05}},
        "ssm.gates": {"seconds": 0.001, "ops": {"fusion.4": 0.001}},
        "ssm.norm": {"seconds": 0.049, "ops": {"fusion.5": 0.049}},
        "attn.attend": {"seconds": 0.012, "ops": {"kernel:attn.attend.3": 0.006,
                                                  "kernel:attn.attend.2": 0.004, "fusion.6": 0.002}},
        "dense.ffn": {"seconds": 0.7, "ops": {"fusion.7": 0.7}}}}
    packed = {"visible_pairs": [5.0e6, 7.0e6, 9.0e6, 6.0e6], "visible_pairs_traced": 8.0e6}
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 8192}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 1.8, "window_s": 1.81},
            "trace": object(), "values": {"train_tok_s": 14000.0}, "packed": packed, **extra}


def test_readers_sum_the_families_the_scan_and_the_kernels():
    run = _run()
    assert reader("ssm_share_pct.g1").read(run) == pytest.approx(100 * 0.8 / 1.8)
    assert reader("ssm_scan_pct.g1").read(run) == pytest.approx(100 * 0.30 / 1.8)
    assert reader("ssm_glue_pct.g1").read(run) == pytest.approx(100 * 0.1 / 1.8)
    c = cg.scan_cost(SHAPE, 1, 8192)
    least, bound = costs.roofline_seconds(3 * (c["fwd_flops"] + c["bwd_flops"]),
                                          3 * (c["fwd_bytes"] + c["bwd_bytes"]), PEAKS)
    assert reader("ssd_scan_roofline.g1").read(run) == pytest.approx(100 * least / 0.30)
    assert 0 < reader("ssd_scan_roofline.g1").read(run) < 100
    f = cg.flash_cost(SHAPE, 1, 8192, 8.0e6)
    least, _ = costs.roofline_seconds(3 * 3.5 * f["fwd_flops"],
                                      3 * (f["fwd_bytes"] + f["bwd_bytes"]), PEAKS)
    assert reader("flash_roofline.nope64").read(run) == pytest.approx(100 * least / 0.010)
    assert 0 < reader("flash_roofline.nope64").read(run) < 100
    per_token = cg.train_flops_per_token(SHAPE, 8192, 6.75e6)
    assert reader("train_mfu_pct.granite_hybrid").read(run) == pytest.approx(
        100 * 14000.0 * per_token / PEAKS["bf16_flops_per_s"])
    assert 0 < reader("train_mfu_pct.granite_hybrid").read(run) < 100


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    other = mf.read_json(mf.ROOT, "chipbench/configs/nemotron-twotower-30b-a3b-train.json")
    for name in NEW_METRICS:
        assert reader(name).read(_run(shape=other)) is None, name
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"dense.ffn": {
        "seconds": 0.7, "ops": {"fusion.7": 0.7}}}}
    for name in NEW_METRICS[:5]:
        assert reader(name).read(bare) is None, name
    assert reader("train_mfu_pct.granite_hybrid").read(_run(values={})) is None
    # a runner that left no record of the documents (the parent's): nothing, no error
    assert reader("flash_roofline.nope64").read(_run(packed=None)) is None
    assert reader("train_mfu_pct.granite_hybrid").read(_run(packed=None)) is None
    # and the accepted readers of the sibling cell read nothing of this one
    for name in ("ssm_share_pct", "ssd_scan_roofline", "train_mfu_pct.nemotron_h"):
        assert reader(name).read(_run()) is None or name == "ssm_share_pct"


# -- the one-thing-wrong tool's rows ------------------------------------------------------


def test_the_wrong_table_has_every_row_the_issue_names():
    rows = list(wrong_tool.VARIANTS)
    for said in ("no state reset", "a reset one position late",
                 "the convolution reading across a boundary", "attention across a boundary",
                 "the mask left off the loss", "embedding_multiplier at 1",
                 "residual_multiplier at 1", "attention_multiplier at 1", "logits_scaling at 1",
                 "scale 1 / 8 where 1 / 64", "a rotary (theta 10000, whole head) put in",
                 "the gate after the norm", "B and C read as 8 groups", "the state in bfloat16",
                 "the reference in bfloat16 throughout"):
        assert said in rows, said
    assert rows[-2:] == list(wrong_tool.PRECISION_ONLY)
    from chipbench.reference import granite_hybrid_decoder as ref

    before = {k: getattr(ref, k) for k in ("starts", "conv", "visible", "kept", "rotary",
                                           "gated_norm", "ssm_groups", "STATE", "F32")}
    for name, (wrong, changed) in wrong_tool.VARIANTS.items():
        with wrong():
            moved = [k for k, v in before.items() if getattr(ref, k) is not v]
        assert bool(moved) != bool(changed), name       # ONE thing: a patch or a key
        assert len(changed) <= 1 and all(k in SHAPE for k in changed), name
    assert all(getattr(ref, k) is v for k, v in before.items())     # and it is undone


def test_the_reference_imports_nothing_of_the_programs_models():
    import re

    source = open(f"{mf.ROOT}/chipbench/reference/granite_hybrid_decoder.py").read()
    assert not re.search(r"^\s*(from|import) ray_tpu", source, re.M)
    d = jnp.asarray([0, 0, 1, 1, 1, 2])
    from chipbench.reference import granite_hybrid_decoder as ref

    np.testing.assert_array_equal(ref.starts(d), [True, False, True, False, False, True])
    np.testing.assert_array_equal(ref.reads_back(d, 2), [False, False, False, False, True, False])
    np.testing.assert_array_equal(np.asarray(wrong_tool.reset_one_late(d)),
                                  [True, False, False, True, False, False])
    x = jnp.arange(12.0).reshape(6, 2)
    taps = jnp.ones((4, 2))
    got = ref.conv(x, taps, jnp.zeros(2), d)
    np.testing.assert_array_equal(got[:, 0], [0, 0 + 2, 4, 4 + 6, 4 + 6 + 8, 10])

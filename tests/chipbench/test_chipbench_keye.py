"""The keye-train-8k cell's files (PR 42): the manifest with the cell (for
however many cells there are), the configuration file against the
catalog's row, the model builder and its balancing rule, the runner's
loop at a tiny size, the cost functions by hand-worked cases, each new
reader on a hand-built step table."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_keye, manifest as mf, readers_keye, readers_step
from chipbench.reference import keye_decoder

M = mf.load_manifest()
CELL, CONFIG, TRAFFIC = "keye-train-8k", "keye-vl-2.0-30b-a3b-train", "zipf_tokens_8k"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ("dsa_share_pct", "dsa_index_pct", "dsa_select_pct", "flash_roofline.selected",
               "expert_matmul_roofline.held8", "train_mfu_pct.keye")
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
# what every share cell reports, and this cell with them (ISSUE 42, step 6)
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "moe_compact_pct", "expert_imbalance", "experts_elsewhere_pct", "head_share_pct",
          "optim_share_pct", "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct",
          "fallback_sites.train") + SETUP
PEAKS = costs.load_peaks("TPU v5 lite")


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["max_context"], cell["traffic"]["zipf_s"]) == (
        8192, 8192, 1.1)
    assert cell["cell"]["traffic"] == TRAFFIC
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assumed = SHAPE["assumed"]
    assert {"i_selection", "ii_indexer", "iii_qk_norm", "iv_mrope", "v_indexer_training",
            "vi_router", "vii_dtypes_and_weights"} <= set(assumed)
    assert "NOT IMPLEMENTED" in assumed["v_indexer_training"] and "BALANCED" in assumed["vi_router"]
    assert "TO FILL" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    # their cost functions read every causal pair, another head shape or another share's keys
    assert not reported & {"flash_roofline", "flash_roofline.mla", "flash_roofline.window",
                           "flash_roofline.full48", "expert_matmul_roofline", "train_mfu_pct",
                           "train_mfu_pct.moe", "expert_matmul_roofline.held", "attn_share_pct",
                           "expert_matmul_roofline.held4", "expert_matmul_roofline.held10",
                           "train_mfu_pct.zaya", "train_mfu_pct.glm", "train_mfu_pct.laguna"}
    # the cells, however many: one of four chips, this one where it entered
    assert len(CELLS) >= 7 and [w["chips"] for w in M["workloads"]].count(4) == 1
    assert CELLS[6] == CELL and M["configs"][6]["name"] == CONFIG
    assert CELLS == TRAINING_CELLS
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "512 rows" in why and "4,096" in why and "16,384" in why
    assert why == mf.by_name(M["workloads"], CELL, "workload")["why"]
    assert f"{SHAPE['num_hidden_layers']} of 48 layers" in why and "8192" in why
    # one cell of this configuration, and no other cell on this traffic
    assert [w["name"] for w in M["workloads"] if CONFIG == w["config"] or TRAFFIC == w["traffic"]] == [CELL]


def test_new_metrics_are_appended_after_everything_older():
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert tuple(names[at:at + len(NEW_METRICS)]) == NEW_METRICS
    assert names[at - 1] == "moe_compact_pct"  # PR 40's, the last before this PR
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] in {e["layer"] for e in M["per_layer"] if e["name"] not in NEW_METRICS}
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
    assert m["source"] == ("host_clock" if "mfu" in name else "device_trace")
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes or the statistic (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert reader(name).read({"shape": SHAPE, "trace": None}) is None


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_its_cells_in_their_order(name):
    """An accepted metric that this cell joins is what it was, with the
    cell appended to its list."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert CELL in m["workloads"]
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    # the cells that were there stand before it, whatever a later PR appends after it
    parent = [c for c in m["workloads"] if CELLS.index(c) < CELLS.index(CELL)]
    assert parent == m["workloads"][:len(parent)] and parent
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name in ("moe_share_pct", "moe_dispatch_pct", "expert_imbalance"):
        assert m["workloads"][:4] == ["olmoe-train", "zaya1-train", "glm47f-train", "laguna-train"]
    if name == "moe_compact_pct":   # the small shares
        assert m["workloads"] == ["glm47f-train", "laguna-train", CELL]


def test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were():
    """For any number of cells (what the tests that spell out six cells or a
    cell's exact set hold, carried here): every training cell reports
    `train_tok_s` and `setup_s`, every start-up metric and the metrics every
    training cell has; a metric that is one cell's alone stays that
    cell's; bounds and the window are untouched; one cell of four chips."""
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert e2e["train_tok_s"]["workloads"] == CELLS and e2e["train_tok_s"]["bound"] == 0.01
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    assert M["run_seconds"] == 10 and [w["chips"] for w in M["workloads"]].count(4) == 1
    everywhere = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
                  "hbm_step_gib.train", "report_ms.train", "head_share_pct", "optim_share_pct",
                  "wgrad_optim_fused_pct", "step_unscoped_pct", "block_share_pct") + SETUP
    for cell in TRAINING_CELLS:
        reported = {m["name"] for m in mf.metrics_of(M, "per_layer", cell)}
        assert set(everywhere) <= reported, cell
    for name, cells in (("expert_matmul_roofline", ["olmoe-train"]),
                        ("train_mfu_pct.moe", ["olmoe-train"]),
                        ("expert_matmul_roofline.held", ["zaya1-train"]),
                        ("expert_matmul_roofline.held4", ["glm47f-train"]),
                        ("flash_roofline.mla", ["glm47f-train"]), ("mla_share_pct", ["glm47f-train"]),
                        ("cca_share_pct", ["zaya1-train"]), ("swa_share_pct", ["laguna-train"]),
                        ("flash_roofline.window", ["laguna-train"]),
                        ("train_mfu_pct.laguna", ["laguna-train"]),
                        ("coll_exposed_pct", ["m7b-train-4chip"]),
                        ("train_mfu_pct", ["m7b-train", "m7b-train-4chip"]),
                        ("flash_roofline", ["m7b-train", "m7b-train-4chip", "olmoe-train",
                                            "zaya1-train"]),
                        ("attn_share_pct", ["m7b-train", "m7b-train-4chip", "olmoe-train",
                                            "laguna-train"])):
        assert mf.by_name(M["per_layer"], name, "metric")["workloads"] == cells, name
    # the tail of `per_layer`: every PR's block in its order, this PR's six last
    names = [m["name"] for m in M["per_layer"]]
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)


LISTED = [m["name"] for m in M["end_to_end"] + M["per_layer"] if "workloads" in m]
LAGUNAS = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
           "hbm_step_gib.train", "report_ms.train", "head_share_pct", "optim_share_pct",
           "wgrad_optim_fused_pct", "step_unscoped_pct", "block_share_pct", "fallback_sites.train",
           "attn_share_pct", "ffn_share_pct", "moe_share_pct", "moe_dispatch_pct",
           "expert_imbalance", "experts_elsewhere_pct") + SETUP


@pytest.mark.parametrize("name", LISTED)
def test_a_metrics_cells_stand_in_the_manifests_order_whoever_joined_later(name):
    """What tests/chipbench/test_chipbench_laguna.py::test_joined_metric_...
    holds of a list that ends with laguna-train (skipped from
    tests/conftest.py: a later cell is appended after it), for every
    metric with a list and any number of cells: the cells are the
    manifest's, once each, in the manifest's order, so a cell that joins
    stands after the ones that were there; a start-up metric lists every
    training cell and moves `setup_s`, every other moves `train_tok_s`."""
    m = next(e for e in M["end_to_end"] + M["per_layer"] if e["name"] == name)
    assert m["workloads"] and m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    elif name != "train_tok_s":
        assert m["moves"] == "train_tok_s"
    if name in ("moe_share_pct", "moe_dispatch_pct", "expert_imbalance"):
        assert m["workloads"][:3] == ["olmoe-train", "zaya1-train", "glm47f-train"]
    if name == "attn_share_pct":   # the cells whose blocks have `attn.*` scopes
        assert m["workloads"][:4] == ["m7b-train", "m7b-train-4chip", "olmoe-train", "laguna-train"]


@pytest.mark.parametrize("name", LAGUNAS)
def test_laguna_train_keeps_every_metric_it_joined(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert "laguna-train" in m["workloads"]
    before = [c for c in m["workloads"] if CELLS.index(c) < CELLS.index("laguna-train")]
    assert before == m["workloads"][:len(before)] and before


def test_the_compact_metric_is_the_small_shares_and_follows_pr_39s_six():
    """tests/chipbench/test_chipbench_compact.py's test of the entry
    (skipped from tests/conftest.py: it spells out PR 40's two cells), for
    any number of small shares: the entry as PR 40 wrote it, its first
    cells those two, every cell of it one that reports a share held, and
    `zaya1-train`, which holds half, not among them."""
    names = [m["name"] for m in M["per_layer"]]
    assert names[names.index("train_mfu_pct.laguna") + 1] == "moe_compact_pct"
    m = mf.by_name(M["per_layer"], "moe_compact_pct", "metric")
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": "moe_compact_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "expert layer", "moves": "train_tok_s"}
    assert m["layer"] == mf.by_name(M["per_layer"], "moe_dispatch_pct", "metric")["layer"]
    assert m["workloads"][:2] == ["glm47f-train", "laguna-train"]
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    elsewhere = mf.by_name(M["per_layer"], "experts_elsewhere_pct", "metric")["workloads"]
    assert set(m["workloads"]) == set(elsewhere) - {"zaya1-train"}
    for cell in CELLS:
        reported = {e["name"] for e in mf.metrics_of(M, "per_layer", cell)}
        assert ("moe_compact_pct" in reported) == (cell in m["workloads"])


def test_every_traffic_file_names_a_generator_and_stays_inside_the_window_of_its_models():
    """tests/chipbench/test_chipbench_traffic.py's test (skipped from
    tests/conftest.py: it holds every file to 4096 tokens), for any
    number of files: a generator that loads, a sequence inside the
    file's own `max_context`, and that inside the published window of
    every configuration a cell runs it on; no file without a cell."""
    used = {}
    for w in M["workloads"]:
        used.setdefault(w["traffic"], []).append(mf.load_cell(mf.ROOT, M, w["name"])["config"])
    d = os.path.join(mf.ROOT, "chipbench", "traffic")
    for fn in sorted(os.listdir(d)):
        t = mf.read_json(mf.ROOT, f"chipbench/traffic/{fn}")
        assert mf.load_plugin(mf.ROOT, "generators", t["generator"])
        assert t["seq_len"] <= t["max_context"] <= 8192
        assert used[fn[:-len(".json")]]
        for config in used[fn[:-len(".json")]]:
            assert t["max_context"] <= config["max_position_embeddings"]
    assert mf.read_json(mf.ROOT, "chipbench/traffic/zipf_tokens.json")["max_context"] == 4096


def test_step_scopes_gain_three_families_and_keep_the_rest():
    vocabulary = readers_step.vocabulary()
    assert vocabulary["families"]["dsa"] == ["dsa.qkv", "dsa.norm", "dsa.rope", "dsa.attend", "dsa.out"]
    assert vocabulary["families"]["dsa_index"] == ["dsa.index.proj", "dsa.index.scores"]
    assert vocabulary["families"]["dsa_select"] == ["dsa.select"]
    assert vocabulary["families"]["attn"] == ["attn.qkv", "attn.rope", "attn.attend", "attn.out"]
    assert vocabulary["families"]["swa"] == ["swa.qkv", "swa.rope", "swa.attend", "swa.out"]
    assert readers_step.scope_of_path(
        "jit(step)/jvp(block.stack)/while/body/checkpoint/dsa.index.scores/dot_general") == "dsa.index.scores"
    assert readers_step.scope_of_path(
        "jit(step)/transpose(jvp(block.stack))/while/body/checkpoint/dsa.attend/pallas_call") == "dsa.attend"
    assert readers_step.family("dsa.select") == "dsa_select" and readers_step.family("dsa.norm") == "dsa"
    assert set(SHAPE["check"]["scopes"]) == {s for f in ("dsa", "dsa_index", "dsa_select", "moe")
                                             for s in vocabulary["families"][f]}


# -- the configuration file against the catalog --------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this installation")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Keye-VL-2.0-30B-A3B":
            return row
    raise AssertionError("the catalog has no such row")


def test_every_published_key_is_the_catalogs_but_the_three_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert SHAPE["published"][key] == value and SHAPE[key] < value, key
        else:
            assert SHAPE[key] == value, key
    assert set(SHAPE["published"]) == set(REDUCED)


def test_every_width_the_issue_names_is_as_published():
    assert (SHAPE["hidden_size"], SHAPE["head_dim"], SHAPE["num_attention_heads"],
            SHAPE["num_key_value_heads"], SHAPE["moe_intermediate_size"],
            SHAPE["num_experts_per_tok"], SHAPE["num_local_experts"]) == (2048, 128, 32, 4, 768, 8, 128)
    assert SHAPE["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                  "q_chunk_size": 512, "topk": 2048}
    assert SHAPE["intermediate_size"] == 6144 and SHAPE["mlp_only_layers"] == []
    assert SHAPE["rope_theta"] == 10000000 and SHAPE["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert SHAPE["norm_topk_prob"] is True and SHAPE["tie_word_embeddings"] is False


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    assert SHAPE["num_hidden_layers"] >= 4 and SHAPE["num_experts"] == 16 >= 8
    assert SHAPE["vocab_size"] == 19072 == 149 * 128 >= SHAPE["published"]["vocab_size"] / 8
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 8
    assert SHAPE["deployment"]["first_expert_held"] == 0
    assert SHAPE["num_experts"] * 8 == SHAPE["published"]["num_experts"]
    for key in REDUCED:
        assert not mf.WIDTH_KEYS.search(key)
        assert str(SHAPE["published"][key]) in SHAPE["reduced"][key].replace(",", "")
    assert SHAPE["train"]["global_batch"] in (1, 2) and SHAPE["train"]["optimizer"] == "adamw"
    assert SHAPE["train"]["lr"] in (3e-4, 3e-5, 3e-6, 2.5e-7) and SHAPE["train"]["lr_why"]
    for key in ("loss_tol", "routing_tol"):
        assert SHAPE["check"][key] > 0 and len(SHAPE["check"][f"{key}_why"]) > 200


def test_builder_builds_the_share_at_the_files_sizes():
    from ray_tpu.models import dsa

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert isinstance(cfg, dsa.KeyeConfig) and cfg.attention_impl == "flash"
    assert (cfg.n_layers, cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.vocab_size) == (
        SHAPE["num_hidden_layers"], 128, 16, 0, 19072)
    shapes = jax.eval_shape(init, jax.random.key(0))
    L = cfg.n_layers
    assert shapes["layers"]["w_gate"].shape == (L, 16, 2048, 768)
    assert shapes["layers"]["router"].shape == (L, 2048, 128)
    assert shapes["layers"]["router_bias"].shape == (L, 128)
    assert shapes["layers"]["idx_wq"].shape == (L, 2048, 1024)
    assert shapes["layers"]["idx_wk"].shape == (L, 2048, 64) and shapes["layers"]["idx_ww"].shape == (L, 2048, 16)
    assert shapes["layers"]["q_norm"].shape == (L, 128) and shapes["layers"]["wq"].shape == (L, 2048, 4096)
    assert shapes["embed"].shape == (19072, 2048) and shapes["lm_head"].shape == (2048, 19072)
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == 78_120_960 + L * 96_899_584
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) == jax.tree.structure(shapes)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 1024), ("head_dim", 64), ("moe_intermediate_size", 512),
    ("num_experts_per_tok", 4), ("num_key_value_heads", 8), ("num_local_experts", 64),
    ("norm_topk_prob", False), ("rope_theta", 10000),
])
def test_builder_refuses_a_changed_width(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match=key):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_a_changed_indexer_counts_and_what_is_not_run():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    for key, value in (("topk", 1024), ("indexer_num_heads", 8), ("indexer_head_dim", 128)):
        with pytest.raises(RuntimeError, match=f"sa_config.{key}"):
            builder.build({**SHAPE, "sa_config": {**SHAPE["sa_config"], key: value}})
    with pytest.raises(RuntimeError, match="num_experts"):
        builder.build({**SHAPE, "published": {**SHAPE["published"], "num_experts": 64}})
    with pytest.raises(RuntimeError, match="use_sliding_window"):
        builder.build({**SHAPE, "use_sliding_window": True})
    with pytest.raises(RuntimeError):
        builder.build({**SHAPE, "sa_config": {**SHAPE["sa_config"], "indexer_num_kv_heads": 2}})


def _tiny_shape():
    """A configuration file's keys at `keye-tiny`'s sizes: 4 of 8 experts held."""
    from ray_tpu.models import dsa

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    t = dsa.KEYE_TINY
    shape = {k: getattr(t, a) for k, a in {**builder.WIDTHS, **builder.COUNTS}.items()}
    sa = {k: getattr(t, a) for k, a in builder.INDEXER.items()}
    return {**shape, "registry_model": "keye-tiny", "sa_config": {**sa, "indexer_num_kv_heads": 1},
            "attention_bias": False, "use_sliding_window": False, "sliding_window": None,
            "mlp_only_layers": [], "decoder_sparse_step": 1,
            "published": {k: shape[k] for k in builder.COUNTS}, "num_experts": 4,
            "deployment": {"first_expert_held": 0},
            "train": {"attention_impl": "xla", "global_batch": 4}}


TINY_TRAFFIC = {"generator": "zipf_tokens", "seq_len": 64, "max_context": 128, "zipf_s": 1.1}


def test_balanced_bias_evens_the_experts_of_every_layer_on_the_runs_own_tokens():
    """One fixed rule, no option: from the weights and the batches alone, a
    table [layers, experts] under which every layer's experts see nearer
    equal numbers of the pairs of FRESH batches than under b = 0."""
    from ray_tpu.models import llama

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    cfg, init, _ = builder.build(_tiny_shape(), attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    make = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)
    assert (builder.PASSES, builder.AVERAGED) == (48, 16)
    assert builder.STEP_LAST < builder.STEP_FIRST <= 0.01
    table = builder.balanced_bias(cfg, params, make)
    assert table.shape == (cfg.n_layers, 8) == (2, 8) and table.dtype == np.float32
    assert not np.asarray(params["layers"]["router_bias"]).any()  # the weights are not touched
    loss = jax.jit(lambda p, b: llama.loss_and_weight_fn(p, b, cfg)[2]["tokens_per_expert"])

    def spread(bias):
        layers = {**params["layers"], "router_bias": jnp.asarray(bias)}
        seen = sum(np.asarray(loss({**params, "layers": layers}, make(i))) for i in range(8))
        return seen.max(-1) / seen.mean(-1)

    imb0, imb1 = spread(np.zeros_like(table)), spread(table)
    assert imb1.mean() < imb0.mean() and imb1.max() < imb0.max()


def test_runners_loop_starts_the_step_from_the_balanced_table_and_meets_the_reference(
        monkeypatch, tmp_path):
    """The loop itself on the CPU at keye-tiny's sizes: every step reports
    both layers' rows, top-2 pairs a token in each; the table the loop
    started from is kept for the reference, which reads it under the same
    key and meets the step's first loss and routing."""
    import gc

    from ray_tpu.train import session

    assert SHAPE["runner"] == "train_reference_from_config"
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    base = mf.load_plugin(mf.ROOT, "runners", "train_reference")
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    reports = []
    monkeypatch.setattr(session, "report", reports.append)
    monkeypatch.setattr(runner, "_BASE", base)
    monkeypatch.setattr(keye_decoder, "QUERY_BLOCK", 32)
    config = {**_tiny_shape(), "model_builder": SHAPE["model_builder"]}
    config["train"] = {**config["train"], "lr": 1e-6}
    runner.train_loop({"root": mf.ROOT, "config": config, "traffic": TINY_TRAFFIC, "seed": 5,
                       "seconds": 0.2, "trace": 0, "out_dir": str(tmp_path)})
    gc.unfreeze()
    assert [r["phase"] for r in reports[:4]] == ["warm"] * 4 and reports[-1]["phase"] == "done"
    table = runner._BIAS[0]
    assert table.shape == (2, 8) and (np.abs(table).max(-1) > 1e-4).all()
    first = np.asarray(reports[-1]["first_counts"])
    pairs = 2 * 4 * 64                                            # top-2 of a batch of 4 x 64
    assert first.shape == (2, 8) and first.sum(-1).tolist() == [pairs] * 2
    for r in reports[:-1]:
        assert r["router"]["dropped_pairs"] == 0 and r["router"]["pairs"] == [pairs] * 2
    cfg, init, _ = builder.build(config, attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    params["layers"]["router_bias"] = jnp.asarray(table)
    batch = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)(0)
    shape = {**config, "head_dim": 16, "rms_norm_eps": 1e-6, "tie_word_embeddings": False}
    ref = keye_decoder.loss_parts(params, batch["tokens"], batch["targets"], shape)
    assert reports[0]["loss"] == pytest.approx(float(ref["loss"]), rel=0.02)
    moved = np.abs(first - np.asarray(ref["tokens_per_expert"])).sum() // 2
    assert moved <= 0.08 * 2 * pairs


# -- the cost functions, by hand ----------------------------------------------------


@pytest.mark.parametrize("seq,pairs,mean", [
    (2048, 2048 * 2049 / 2, 1024.5),                                 # the selection bites nowhere
    (4096, 2048 * 2049 / 2 + 2048 * 2048, 1536.25),                  # half the queries past the cut
    (8192, 2048 * 2049 / 2 + 6144 * 2048, 1792.125),                 # three in four: the cell's
])
def test_selected_pairs_by_hand(seq, pairs, mean):
    assert costs_keye.selected_pairs(SHAPE, seq) == pairs == mean * seq
    assert costs_keye.causal_pairs(seq) == seq * (seq + 1) / 2
    # 75% of the causal pairs at 4096, 43.7% at 8192 (ISSUE 42)
    share = {2048: 1.0, 4096: 0.75, 8192: 0.4375}[seq]
    assert pairs / costs_keye.causal_pairs(seq) == pytest.approx(share, abs=2e-4)


def test_required_operations_are_issue_42s_count():
    """A layer forward, MFLOP a token at 8192: projections 37.7, the
    indexer's projections 4.5 and scores 8.4, attention over the selected
    keys 29.4, the held experts 9.4 at an eighth of the pairs, the router
    0.5: 90.0; the head 78.1."""
    L = SHAPE["num_hidden_layers"]
    f = costs_keye.forward_flops_per_token(SHAPE, 8192, 1 / 8)
    per_layer = {k: v / L / 1e6 for k, v in f.items() if k != "head"}
    assert per_layer["attention"] == pytest.approx(37.75, abs=0.01)
    assert per_layer["indexer"] == pytest.approx(4.52, abs=0.01)
    assert per_layer["index_scores"] == pytest.approx(8.39, abs=0.01)
    assert per_layer["scores.selected"] == pytest.approx(29.36, abs=0.01)
    assert per_layer["routed"] == pytest.approx(9.44, abs=0.01)
    assert per_layer["router"] == pytest.approx(0.52, abs=0.01)
    assert sum(per_layer.values()) == pytest.approx(90.0, abs=0.05)
    assert f["head"] / 1e6 == pytest.approx(78.1, abs=0.05)
    # trained: three times what takes a gradient, once the indexer's
    once = f["indexer"] + f["index_scores"]
    assert costs_keye.train_flops_per_token(SHAPE, 8192, 1 / 8) == pytest.approx(
        3 * (sum(f.values()) - once) + once)
    p = costs_keye.matmul_params(SHAPE)
    assert p["attention"] == 18_874_368 and p["indexer"] == 2048 * (1024 + 64 + 16)
    assert p["expert"] == 3 * 2048 * 768 and p["router"] == 2048 * 128


def test_flash_and_grouped_matmul_costs_by_hand():
    c = costs_keye.flash_cost(SHAPE, 1, 8192)
    pairs = 2048 * 2049 / 2 + 6144 * 2048
    assert c["fwd_flops"] == 32 * 4 * 128 * pairs and c["bwd_flops"] == 2.5 * c["fwd_flops"]
    q, kv, sel = 8192 * 32 * 128 * 2, 8192 * 4 * 128 * 2, 8192 * 8192 / 8
    assert c["fwd_bytes"] == 2 * q + 2 * kv + sel and c["bwd_bytes"] == 4 * q + 4 * kv + 2 * sel
    g = costs_keye.grouped_matmul_cost(SHAPE, 8192)
    assert g["fwd_flops"] == 3 * 2 * 8192 * 2048 * 768 and g["bwd_flops"] == 2 * g["fwd_flops"]
    assert g["fwd_bytes"] == 3 * 2 * (8192 * 2048 + 8192 * 768 + 16 * 2048 * 768)


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    L = SHAPE["num_hidden_layers"]
    table = {"busy_s": 1.0, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "dsa.attend": {"seconds": 0.30, "ops": {"kernel:dsa.attend.30": 0.08,
                                                "kernel:dsa.attend.31": 0.20, "fusion.9": 0.02}},
        "dsa.qkv": {"seconds": 0.06, "ops": {"fusion.1": 0.06}},
        "dsa.norm": {"seconds": 0.01, "ops": {"fusion.2": 0.01}},
        "dsa.index.proj": {"seconds": 0.02, "ops": {"fusion.3": 0.02}},
        "dsa.index.scores": {"seconds": 0.07, "ops": {"fusion.5": 0.07}},
        "dsa.select": {"seconds": 0.05, "ops": {"fusion.6": 0.05}},
        "moe.experts": {"seconds": 0.1, "ops": {"fusion.4": 0.1}}}}
    steps = [{"router": {"pairs": [65536] * L, "pairs_elsewhere": [57344] * L}}] * 3
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 8192}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 1.0, "window_s": 1.0},
            "trace": object(), "traced_window_steps": steps,
            "ops": {"expert_matmul": {"seconds": 0.04}}, "values": {"train_tok_s": 23000.0},
            **extra}


def test_readers_sum_the_families_and_the_kernels():
    run, L = _run(), SHAPE["num_hidden_layers"]
    assert reader("dsa_share_pct").read(run) == pytest.approx(100 * 0.51)
    assert reader("dsa_index_pct").read(run) == pytest.approx(100 * 0.09)
    assert reader("dsa_select_pct").read(run) == pytest.approx(100 * 0.05)
    c = costs_keye.flash_cost(SHAPE, 1, 8192)
    least = L * 3 * 3.5 * c["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    got = reader("flash_roofline.selected").read(run)
    assert got == pytest.approx(100 * least / 0.28) and got < 100
    g = costs_keye.grouped_matmul_cost(SHAPE, 8192)
    least, _ = costs.roofline_seconds(L * 3 * (g["fwd_flops"] + g["bwd_flops"]),
                                      L * 3 * (g["fwd_bytes"] + g["bwd_bytes"]), PEAKS)
    assert reader("expert_matmul_roofline.held8").read(run) == pytest.approx(100 * least / 0.04)
    per_token = costs_keye.train_flops_per_token(SHAPE, 8192, 1 / 8)
    assert reader("train_mfu_pct.keye").read(run) == pytest.approx(
        100 * 23000.0 * per_token / PEAKS["bf16_flops_per_s"])


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    other = mf.read_json(mf.ROOT, "chipbench/configs/glm-4.7-flash-train.json")
    for name in NEW_METRICS[3:]:
        assert reader(name).read(_run(shape=other)) is None, name
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"moe.experts": {
        "seconds": 0.1, "ops": {"fusion.4": 0.1}}}}
    for name in NEW_METRICS[:4]:
        assert reader(name).read(bare) is None, name
    no_share = _run(traced_window_steps=[{"router": {"pairs": [65536] * 6}}] * 3)
    assert reader("expert_matmul_roofline.held8").read(no_share) is None
    assert reader("train_mfu_pct.keye").read(no_share) is None
    assert readers_keye.is_keye({"shape": SHAPE}) and not readers_keye.is_keye({"shape": other})

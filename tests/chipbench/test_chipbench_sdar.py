"""The sdar-train-8k cell's files (PR 55): the manifest with the cell, the
configuration file against the catalog's row, the model builder, the
runner that composes the runners there were (the pair count of TWO copies,
the loss's two readings in the form a heavy-tailed weight allows, the
corruption exactly, the masked attention alone), the cost functions by
hand-worked cases and each new reader on a hand-built step table.

Every case reads `M`, THE MANIFEST AS THIS PR LEFT IT: today's less what
later PRs appended after this cell, its configuration and its metrics
(`as_this_pr_left_it`). A later PR that appends cells, metrics or list
members therefore changes no case here and skips none; what holds of the
manifest of its own day is its own test file's to hold. It also carries
what five tests of tests/chipbench/test_chipbench_mellum2.py held while
the benchmark had ten cells and `per_layer` ended with that PR's five:
that file is the accepted benchmark's and may not be edited, so they are
skipped from tests/conftest.py by name and run here, every assertion, on
the manifest less what this PR appended."""

import copy
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_sdar, manifest as mf, readers_sdar, readers_step
from chipbench.reference import sdar_decoder

CELL, CONFIG, TRAFFIC = "sdar-train-8k", "sdar-30b-a3b-train", "zipf_tokens_8k"
NEW_METRICS = ("flash_roofline.blockdiff", "expert_matmul_roofline.sdar", "train_mfu_pct.sdar",
               "diff_share_pct", "blockdiff_tiles_pct", "blockdiff_merge_pct", "qk_norm_pct.sdar")


def as_this_pr_left_it(manifest: dict) -> dict:
    """`manifest` less what later PRs appended: the cells and configurations
    after this PR's, the metrics after this PR's last, and those cells off
    every list."""
    was = copy.deepcopy(manifest)
    cells = [w["name"] for w in was["workloads"]]
    later = set(cells[cells.index(CELL) + 1:])
    was["workloads"] = [w for w in was["workloads"] if w["name"] not in later]
    configs = [c["name"] for c in was["configs"]]
    was["configs"] = was["configs"][:configs.index(CONFIG) + 1]
    names = [m["name"] for m in was["per_layer"]]
    was["per_layer"] = was["per_layer"][:names.index(NEW_METRICS[-1]) + 1]
    for m in was["per_layer"] + was["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w not in later]
    return was


M = as_this_pr_left_it(mf.load_manifest())
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
TIMELINE = ("dispatch_ms.train", "step_stalls.train", "stall_loss_pct.train", "gc_pause_ms.train",
            "report_max_ms.train", "host_other_cpu_pct.train", "step_gap_ms.train",
            "step_gap_program_pct.train")
# what every share cell reports, the full layers' attention family, and this cell
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "moe_compact_pct", "expert_imbalance", "experts_elsewhere_pct", "head_share_pct",
          "optim_share_pct", "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct",
          "fallback_sites.train", "attn_share_pct") + SETUP + TIMELINE
PEAKS = costs.load_peaks("TPU v5 lite")
HERE = os.path.dirname(__file__)
MELLUM2 = "test_chipbench_mellum2.py"


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


def before_this_pr(manifest=M):
    """The manifest as the parent had it: this PR's cell, configuration and
    seven metrics taken off, and the cell off every list it joined."""
    was = copy.deepcopy(manifest)
    was["configs"] = [c for c in was["configs"] if c["name"] != CONFIG]
    was["workloads"] = [w for w in was["workloads"] if w["name"] != CELL]
    was["per_layer"] = [m for m in was["per_layer"] if m["name"] not in NEW_METRICS]
    for m in was["per_layer"] + was["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    return was


def mellum2s(manifest):
    """tests/chipbench/test_chipbench_mellum2.py as a module of its own that
    reads `manifest` (and takes ITS PR's entries off that one)."""
    spec = importlib.util.spec_from_file_location("carried_mellum2", os.path.join(HERE, MELLUM2))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.M = manifest
    module.CELLS = module.TRAINING_CELLS = [w["name"] for w in manifest["workloads"]]
    module.before_this_pr.__defaults__ = (manifest,)
    return module


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["zipf_s"]) == (8192, 1.1)
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train", "block_diffusion"):
        assert SHAPE[key], key
    assumed = SHAPE["assumed"]
    assert {"block_length", "noise_schedule", "mask_token", "corruption_key", "two_copies",
            "visibility", "qk_norm", "loss", "router", "router_bias_update", "param_dtype",
            "weights", "generation"} <= set(assumed)
    # each reading taken and the one not taken; what is said and not built
    for item in ("block_length", "noise_schedule", "two_copies", "qk_norm", "loss"):
        assert "NOT taken" in assumed[item] or "not taken" in assumed[item], item
    assert "NOT built" in assumed["generation"] and "BALANCED" in assumed["router_bias_update"]
    assert "TBD" not in json.dumps(SHAPE) and "PROVISIONAL" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    assert CELLS[10] == CELL and M["configs"][10]["name"] == CONFIG and CELLS == TRAINING_CELLS
    assert len(CELLS) >= 11 and [w["chips"] for w in M["workloads"]].count(4) == 1
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "16,384 rows" in why and "1 x 8192" in why and "blocks of 4" in why
    assert "16 held" in why and "1,024 rows an expert" in why
    assert why == mf.by_name(M["workloads"], CELL, "workload")["why"]
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] == [CELL]
    assert CELL in [w["name"] for w in M["workloads"] if w["traffic"] == TRAFFIC]


def test_nothing_the_parent_had_is_changed_but_by_the_cell_appended():
    """Added files and appended list members only: with this PR's entries
    taken off, every entry of the manifest is an entry the ten-cell
    benchmark had, in its place, with its bound; `run_seconds`, `command`
    and `paths` as they were."""
    was = before_this_pr()
    assert [w["name"] for w in was["workloads"]] == CELLS[:10] and len(was["configs"]) == 10
    assert [m["name"] for m in was["per_layer"]] == [m["name"] for m in M["per_layer"]][:-7]
    assert [m["name"] for m in M["per_layer"]][-7:] == list(NEW_METRICS)
    assert M["configs"][-1]["name"] == CONFIG and M["workloads"][-1]["name"] == CELL
    for m in M["per_layer"] + M["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
    assert (M["run_seconds"], M["command"], M["paths"]) == (
        10, ["python3", "-m", "chipbench.run"], ["chipbench", "tests/chipbench"])
    assert [(m["name"], m["bound"]) for m in M["end_to_end"]] == [("train_tok_s", 0.01),
                                                                 ("setup_s", 0.1)]
    assert mf.problems(was) == []


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] == {"train_mfu_pct.sdar": "train step", "diff_share_pct": "train step",
                          "blockdiff_merge_pct": "attention",
                          "qk_norm_pct.sdar": "attention"}.get(name, "kernels")
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
    assert m["source"] == {"train_mfu_pct.sdar": "host_clock",
                           "blockdiff_tiles_pct": "program_counter"}.get(name, "device_trace")
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes or the report (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert reader(name).read({"shape": SHAPE, "trace": None}) is None


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_its_cells_in_their_order(name):
    """An accepted metric that this cell joins is what it was, with the
    cell appended to its list."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"][-1] == CELL or CELLS.index(m["workloads"][-1]) > CELLS.index(CELL)
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    parent = [c for c in m["workloads"] if CELLS.index(c) < CELLS.index(CELL)]
    assert parent == m["workloads"][:len(parent)] and parent
    was = mf.by_name(before_this_pr()["per_layer"], name, "metric")
    assert parent == was["workloads"]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        k: v for k, v in was.items() if k != "workloads"}
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name in TIMELINE:
        assert m["workloads"] == TRAINING_CELLS
    if name == "attn_share_pct":
        assert parent[-2:] == ["twotower-train-8k", "mellum2-train-16k"]
    if name == "moe_compact_pct":   # the small shares, in the order they entered
        assert parent == ["glm47f-train", "laguna-train", "keye-train-8k", "twotower-train-8k",
                          "mellum2-train-16k"]


# -- what tests/chipbench/test_chipbench_mellum2.py held of the ten-cell benchmark -------


def _mellum2_cases():
    module = mellum2s(before_this_pr())
    cases = [("test_nothing_the_parent_had_is_changed_but_by_the_cell_appended", None),
             ("test_the_attention_familys_list_keeps_twotower_and_gains_this_cell", None),
             ("test_the_host_timelines_eight_are_reported_by_every_training_cell", None)]
    cases += [("test_joined_metric_keeps_its_entry_and_its_cells_in_their_order", name)
              for name in module.JOINED]
    cases += [("test_twotowers_new_metric_is_as_it_entered_on_the_manifest_less_what_came_later",
               name) for name in ("ssm_share_pct", "ssm_scan_pct", "ssm_glue_pct",
                                  "ssd_scan_roofline", "flash_roofline.full32",
                                  "expert_matmul_roofline.held6", "train_mfu_pct.nemotron_h")]
    return cases


@pytest.mark.parametrize("test,case", _mellum2_cases(),
                         ids=lambda v: v if v is None or "." in v or "_pct" in v or "_" in v else v)
def test_mellum2s_test_holds_on_the_manifest_less_what_pr_55_appended(test, case):
    """Each case of tests/chipbench/test_chipbench_mellum2.py that spells
    out ten cells or `per_layer` ending with PR 53's five (skipped from
    tests/conftest.py): the test as PR 53 wrote it, every assertion, on
    the manifest less what this PR appended."""
    module = mellum2s(before_this_pr())
    getattr(module, test)(*(() if case is None else (case,)))


def test_the_manifest_of_a_later_day_reads_as_this_pr_left_it():
    """What keeps a later PR from having to skip a case of this file: cells,
    a configuration, metrics and list members appended after this PR's are
    taken off before any case reads the manifest."""
    later = copy.deepcopy(M)
    later["configs"].append({**M["configs"][-1], "name": "a-later-config"})
    later["workloads"].append({**M["workloads"][-1], "name": "a-later-cell",
                               "config": "a-later-config"})
    later["per_layer"].append({**M["per_layer"][-1], "name": "a_later_metric",
                               "workloads": ["a-later-cell"]})
    for m in later["per_layer"][:-1] + later["end_to_end"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("a-later-cell")
    assert later != M and as_this_pr_left_it(later) == M == as_this_pr_left_it(M)


def test_step_scopes_gain_two_families_and_keep_the_rest():
    own = mf.read_json(mf.ROOT, "chipbench/step_scopes/sdar.json")
    assert own["families"] == {"diff": ["diff.corrupt", "diff.loss"],
                               "blockdiff_merge": ["flash.blockdiff_merge"]}
    assert set(own) == {"comment", "families"}
    vocabulary = readers_step.vocabulary()
    assert vocabulary["families"]["attn"] == ["attn.qkv", "attn.rope", "attn.attend", "attn.out"]
    assert vocabulary["families"]["qk_norm"] == ["attn.norm", "swa.norm"]
    # the masked kernels' own scope is NOT listed: they are booked to the scope around them
    assert readers_step.scope_of_path(
        "jit(step)/transpose(jvp(block.stack))/while/body/checkpoint/attn.attend/"
        "flash.blockdiff/pallas_call") == "attn.attend"
    # the merge beside them IS listed, a family of its own, forward and backward
    assert readers_step.scope_of_path(
        "jit(step)/transpose(jvp(block.stack))/while/body/checkpoint/attn.attend/"
        "flash.blockdiff_merge/exp") == "flash.blockdiff_merge"
    assert readers_step.family("flash.blockdiff_merge") == "blockdiff_merge"
    # the weighted cross-entropy stands inside `head`: booked to the innermost
    assert readers_step.scope_of_path("jit(step)/jvp(head)/diff.loss/mul") == "diff.loss"
    assert readers_step.scope_of_path(
        "jit(step)/transpose(jvp(head))/diff.loss/dot_general") == "diff.loss"
    assert readers_step.family("diff.corrupt") == readers_step.family("diff.loss") == "diff"
    assert set(SHAPE["check"]["scopes"]) == (
        {s for f in ("attn", "moe", "diff", "blockdiff_merge") for s in vocabulary["families"][f]}
        | {"attn.norm"})


# -- the configuration file against the catalog -------------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "SDAR-30B-A3B-Chat":
            return row
    raise AssertionError("the catalog has no such row")


def test_every_published_key_is_the_catalogs_but_the_three_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v}
    assert changed == set(REDUCED)
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    assert set(row["not_given"]) == {"block length", "noise schedule"}   # set, and `assumed`


def test_every_width_the_issue_names_is_as_published():
    want = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "moe_intermediate_size": 768, "num_experts_per_tok": 8,
            "norm_topk_prob": True, "intermediate_size": 6144, "rope_theta": 1000000,
            "max_position_embeddings": 32768, "rms_norm_eps": 1e-6, "model_type": "sdar_moe"}
    assert {k: SHAPE[k] for k in want} == want
    assert SHAPE["published"]["num_experts"] == 128   # the router's outputs
    assert SHAPE["block_diffusion"] == {"block_length": 4, "eps": 0.001}   # no seed: the step's own
    assert mf.read_json(mf.ROOT, f"chipbench/traffic/{TRAFFIC}.json")["seq_len"] == 8192


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    assert SHAPE["num_hidden_layers"] == 4 and SHAPE["num_experts"] == 16
    assert SHAPE["vocab_size"] == 19072 and SHAPE["vocab_size"] % 128 == 0
    assert abs(SHAPE["vocab_size"] * 8 - SHAPE["published"]["vocab_size"]) < 8 * 128
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 8
    assert 8 * SHAPE["num_experts"] == SHAPE["published"]["num_experts"]
    assert SHAPE["deployment"]["first_expert_held"] == 0
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    rungs = SHAPE["reduced"]["num_experts"]
    assert "rung (a)" in rungs and "Rung (b)" in rungs and "5.10 + 11.45" in rungs
    assert "3.42 + 9.55" in rungs and "NO `*.remat`" in rungs
    check = SHAPE["check"]
    assert set(check) == {"scopes", "loss_tol_rows", "loss_tol_rows_why", "routing_tol",
                          "routing_tol_why", "grad_tol", "grad_tol_why", "mask_tol",
                          "mask_tol_why", "corruption", "corruption_why"}
    # the limit on the loss is one on the ROWS: x r (0.02-0.07) it is the base runner's
    assert 0 < check["loss_tol_rows"] <= 0.1 and 0 < check["routing_tol"] < 0.05
    assert 0 < check["grad_tol"] < 1 and check["corruption"] == "exact"
    assert 0 < check["mask_tol"] <= 0.2
    for why in ("loss_tol_rows_why", "routing_tol_why", "grad_tol_why", "mask_tol_why",
                "corruption_why"):
        assert "my chip runs, PR 55" in check[why], why
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["attention_impl"] == "flash"
    assert "my chip runs, PR 55" in SHAPE["train"]["lr_why"]
    assert "GiB" in SHAPE["memory"] and "456,674,816" in SHAPE["memory"]


# -- the model builder -----------------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (4, 2048, 768, 0, 32, 4, 128)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (128, 16, 0, 8)
    assert cfg.vocab_size == 19072 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.attn_gate == "none" and cfg.qk_head_norm and cfg.first_dense_layers == 0
    assert cfg.remat and cfg.remat_policy == "dots"
    assert cfg.diffusion_block == 4 and not hasattr(cfg, "diffusion_seed")
    assert cfg.stack_module == "ray_tpu.models.laguna"   # ONE module of the typed stack
    assert cfg.kinds() == [("full_attention", 32)] * 4
    shapes = jax.eval_shape(init, jax.random.key(0))
    layers = shapes["layers"]
    assert set(layers) == {"router_bias", "period"} and set(layers["period"]) == {"0"}
    block = layers["period"]["0"]
    assert block["wq"].shape == (4, 2048, 4096) and block["wk"].shape == (4, 2048, 512)
    assert block["q_norm"].shape == block["k_norm"].shape == (4, 128) and "wg" not in block
    assert block["w_gate"].shape == (4, 16, 2048, 768)
    assert block["router"].shape == (4, 2048, 128) and layers["router_bias"].shape == (4, 128)
    assert shapes["embed"].shape == (19072, 2048) and shapes["lm_head"].shape == (2048, 19072)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == 456_674_816   # ISSUE 55's 456.6M + the norms and the 4 x 128 biases
    assert (builder.STEP_FIRST, builder.STEP_LAST, builder.PASSES, builder.AVERAGED) == (
        3.5e-3, 1e-4, 48, 16)   # Keye's: the same router


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2304), ("moe_intermediate_size", 896), ("num_key_value_heads", 8),
    ("num_attention_heads", 16), ("head_dim", 64), ("num_experts_per_tok", 6),
    ("norm_topk_prob", False), ("rope_theta", 500000), ("attention_bias", True),
    ("use_sliding_window", True), ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("hidden_act", "gelu"), ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("block_diffusion", {"block_length": 8, "eps": 0.001}),
    ("block_diffusion", {"block_length": 4, "eps": 0.01}),
    ("block_diffusion", {"block_length": 4, "eps": 0.001, "seed": 45}),
    ("published", {"num_hidden_layers": 48, "num_experts": 64, "vocab_size": 151936}),
], ids=lambda v: None if isinstance(v, (dict, list)) else str(v))
def test_builder_refuses_a_changed_width_form_objective_or_published_count(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="not at the file's sizes"):
        builder.build({**SHAPE, key: value})


# -- the runner ------------------------------------------------------------------------


@pytest.mark.parametrize("what,correct", [
    ("the_references", True), ("a_leaf_off_by_twice_the_limit", False),
    ("one_masked_position_moved", False), ("a_pair_uncounted", False),
    ("the_attention_off_by_twice_the_limit", False), ("a_loss_that_rose_under_a_lucky_draw", False),
    ("a_loss_that_fell_under_an_unlucky_draw", True)])
def test_the_runner_composes_the_runners_and_holds_what_the_objective_adds(
        monkeypatch, what, correct):
    """No copy of the loop: `run` loads runners/train_reference_from_config.py
    and runs it with `loss_tol` = `loss_tol_rows` x r of the run's own first
    corruption, takes `every_pair_counted` again at TWO rows a data token
    and `loss_fell` again on loss / W over the same steps, then holds the
    first step's gradient through runners/train_reference_checked.py's
    `errors_by_leaf` / `verdict`, the masked attention alone and the
    corruption EXACTLY against the reference's own."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    source = open(os.path.join(mf.ROOT, "chipbench", "runners", SHAPE["runner"] + ".py")).read()
    assert "while " not in source and "session.report" not in source   # no loop of its own
    assert runner.ROWS_PER_TOKEN == 2 and runner.EDGE == 64
    shape = {**SHAPE, "vocab_size": 64}
    batches = [(jnp.arange(40, dtype=jnp.int32).reshape(1, 40) * (k + 1)) % 7 for k in range(12)]
    tokens = batches[0]
    want = sdar_decoder.corrupt(tokens, shape)
    masked = np.asarray(want["masked"])
    carried = runner.weights(sdar_decoder, shape, batches)
    w0 = np.asarray(want["masked"] / jnp.repeat(want["p"], 4, axis=1))
    assert carried[0] == pytest.approx((w0.sum() / 40, np.sqrt((w0 ** 2).sum()) / w0.sum()))
    assert len({W for W, _ in carried}) == 12          # a draw a step
    # the weighted mean cross-entropy falls from 9.4 by 0.01 a step; what is reported carries W
    mean_ce = [9.4 - 0.01 * k for k in range(12)]
    if what == "a_loss_that_rose_under_a_lucky_draw":
        mean_ce = mean_ce[::-1]
    losses = [ce * W for ce, (W, _) in zip(mean_ce, carried)]
    raw_fell = sum(losses[-10:]) / 10 < losses[0]
    pairs = 2 * 8 * 40
    stats = {"diff_masked": np.int32(masked.sum()),
             "diff_masked_at": np.int32((masked * np.arange(1, 41)).sum()),
             "diff_tiles_visited": np.int32(1), "diff_tiles_causal": np.int32(2),
             "diff_visible_pairs": np.int32(40 * 44),
             "tokens_per_expert": np.full((4, 128), pairs // 128)}
    if what == "one_masked_position_moved":
        stats["diff_masked_at"] += 1
    tree = {"layers": {"period": {"0": {"wq": jnp.arange(1.0, 7.0)}}}, "embed": jnp.ones((3, 2))}
    factor = 1.0 + 2 * SHAPE["check"]["grad_tol"] if what.startswith("a_leaf") else 1.0
    off = {"layers": {"period": {"0": {"wq": factor * tree["layers"]["period"]["0"]["wq"]}}},
           "embed": tree["embed"]}
    counted = pairs - (1 if what == "a_pair_uncounted" else 0)
    handed = []

    def from_config_run(ctx):
        handed.append(ctx["config"]["check"])
        return {"correct": False, "losses": losses, "tokens_per_step": 40,
                "checks": {"every_pair_counted": False, "loss_fell": raw_fell},
                "shape": ctx["config"],
                "steps": [{"router": {"pairs": [pairs, counted, pairs, pairs]}}]}

    from_config = types.SimpleNamespace(_BIAS=["the bias"], run=from_config_run)
    composed = types.SimpleNamespace(with_bias=lambda params, bias: params)
    plugins = {"train_reference_from_config": from_config, "train_reference_checked": checked,
               "train_reference_nemotron_h": composed}
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[name])
    monkeypatch.setattr(runner, "tokens_maker", lambda ctx, chk: lambda i: batches[i])
    monkeypatch.setattr(runner, "program_first_step", lambda ctx, chk, comp, seed, bias: (
        "params", {"tokens": tokens, "targets": tokens}, off, 9.4, stats))
    rows = {"o": jnp.ones((8, 2, 4)), "dq": jnp.ones((8, 2, 4)), "dk": jnp.ones((6, 4)),
            "dv": jnp.ones((6, 4))}
    far = 1.0 + 2 * SHAPE["check"]["mask_tol"] if what.startswith("the_attention") else 1.0
    monkeypatch.setattr(runner, "first_attention", lambda ctx, chk, ref, params, t, seed: (
        {**rows, "dq": rows["dq"].at[5, 1].multiply(far)}, rows))
    cfg = types.SimpleNamespace(diffusion_block=4, vocab_size=64)
    monkeypatch.setattr(checked, "built", lambda ctx: (cfg, None, None))
    monkeypatch.setattr(sdar_decoder, "grads", lambda params, t, y, config: tree)
    logged = []
    got = runner.run({"root": mf.ROOT, "config": shape, "traffic": {},
                      "args": types.SimpleNamespace(seed=5),
                      "log": lambda **kw: logged.append(kw)})
    # the base runner was handed the rows' limit x r of THIS run's first step, and nothing else
    assert handed[0]["loss_tol"] == pytest.approx(SHAPE["check"]["loss_tol_rows"] * carried[0][1])
    assert {k: v for k, v in handed[0].items() if k != "loss_tol"} == shape["check"]
    assert got["shape"] == shape
    assert got["checks"]["loss_fell"] is (what != "a_loss_that_rose_under_a_lucky_draw")
    if what == "a_loss_that_fell_under_an_unlucky_draw":
        assert isinstance(raw_fell, bool)   # whatever the raw losses say, the mean decides
    assert got["checks"]["every_pair_counted"] is (what != "a_pair_uncounted")
    assert got["checks"]["first_gradient_is_the_reference"] is (not what.startswith("a_leaf"))
    assert got["checks"]["first_attention_is_the_reference"] is (
        not what.startswith("the_attention"))
    assert got["checks"]["first_corruption_is_the_reference"] is (
        what != "one_masked_position_moved")
    assert got["correct"] is correct
    assert got["diffusion"]["diff_tiles_visited"] == 1 and got["diffusion"]["diff_tiles_causal"] == 2
    assert [e["event"] for e in logged] == ["correct_loss_fell", "correct_gradient",
                                            "correct_attention", "correct_corruption"]
    assert logged[0]["first_r"] == pytest.approx(carried[0][1])
    assert logged[2]["leaves"] == 4 and logged[2]["rows"] == 256
    if what.startswith("the_attention"):
        assert logged[2]["worst"] == "dq" and logged[2]["err"] > SHAPE["check"]["mask_tol"]
    assert logged[3]["differences"]["masked"] == 0 and logged[3]["differences"]["p"] == 0


def test_the_raw_losses_of_a_falling_mean_can_rise_and_the_runner_reads_the_mean():
    """Why `loss_fell` is taken again: W of a step spreads by percents, so
    over seeds the first raw loss lies under the last ten's mean about as
    often as not when the mean cross-entropy falls by less than that."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    shape = {**SHAPE, "vocab_size": 64}
    rose = 0
    for seed in range(8):
        batches = [(jnp.arange(64, dtype=jnp.int32).reshape(1, 64) * (k + seed + 1)) % 11
                   for k in range(11)]
        W = [w for w, _ in runner.weights(sdar_decoder, shape, batches)]
        losses = [(9.4 - 0.001 * k) * w for k, w in enumerate(W)]
        rose += sum(losses[-10:]) / 10 >= losses[0]
        mean = [loss / w for loss, w in zip(losses, W)]
        assert sum(mean[-10:]) / 10 < mean[0]
    assert 0 < rose < 8


def test_attention_errors_are_a_rows_own_groups_share_and_a_whole_arrays():
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    want = {"o": jnp.ones((8, 2, 4)).at[:2].multiply(100.0), "dq": jnp.ones((8, 2, 4)),
            "dk": jnp.ones((6, 4)), "dv": 2 * jnp.ones((6, 4))}
    same = runner.attention_errors(want, want)
    assert same == {"o": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
    # one channel of a row of the LAST group off by 1: |1| of that group's norm 2, not of the
    # first group's 200; a whole array off by a tenth
    got = {**want, "o": want["o"].at[7, 0, 0].add(1.0), "dv": 1.1 * want["dv"]}
    off = runner.attention_errors(got, want)
    assert off["o"] == pytest.approx(0.5) and off["dv"] == pytest.approx(0.1)
    assert (runner.edge_rows(8192) == np.concatenate([
        np.arange(64), np.arange(8128, 8192), np.arange(8192, 8256),
        np.arange(16320, 16384)])).all()


# -- the costs, by hand ----------------------------------------------------------------


def test_required_operations_are_issue_55s_count():
    """A layer's forward at the cell's sizes: projections 0.62, visible
    scores 1.10, held experts 0.16 TFLOP (ISSUE 55), per STEP = per data
    token x 8,192."""
    parts = costs_sdar.forward_flops_per_token(SHAPE, 8192, 1 / 8)
    step = {k: v * 8192 / 4 / 1e12 for k, v in parts.items()}   # a layer, TFLOP
    assert step["attention"] == pytest.approx(0.618, abs=2e-3)   # both copies' q, k, v, o
    assert step["scores"] == pytest.approx(1.100, abs=1e-3)      # 4 x 128 x 32 x L (L + 4)
    assert step["routed"] == pytest.approx(0.155, abs=2e-3)      # 16,384 rows x 8 x 1/8 held
    assert costs_sdar.visible_pairs(SHAPE, 8192) == 67_141_632
    p = costs_sdar.matmul_params(SHAPE)
    assert p == {"attention": 2048 * 128 * 72, "router": 2048 * 128, "expert": 3 * 2048 * 768,
                 "head": 2048 * 19072}
    total = costs_sdar.train_flops_per_token(SHAPE, 8192, 1 / 8)
    assert total == pytest.approx(3 * sum(parts.values()))
    assert parts["head"] == 2.0 * 2048 * 19072                   # the L noised rows alone
    layer = sum(v for k, v in parts.items() if k != "head")
    assert parts["scores"] / layer == pytest.approx(0.58, abs=0.01)   # the mechanism does most


def test_flash_and_grouped_matmul_costs_by_hand():
    c = costs_sdar.flash_cost(SHAPE, 1, 8192)
    assert c["layers"] == 4
    assert c["fwd_flops"] == 4 * 32 * 4.0 * 128 * 8192 * 8196 and c["bwd_flops"] == 2.5 * c["fwd_flops"]
    q, kv = 4 * 16384 * 32 * 128 * 2, 4 * 16384 * 4 * 128 * 2
    assert c["fwd_bytes"] == 2 * q + 2 * kv and c["bwd_bytes"] == 4 * q + 4 * kv
    g = costs_sdar.grouped_matmul_cost(SHAPE, 16384)
    assert g["fwd_flops"] == 3 * 2.0 * 16384 * 2048 * 768 and g["bwd_flops"] == 2 * g["fwd_flops"]
    assert g["fwd_bytes"] == 3 * 2.0 * (16384 * 2048 + 16384 * 768 + 16 * 2048 * 768)
    assert costs_sdar.tiles_floor_pct(8192) == pytest.approx(54.545, abs=1e-3)
    assert costs_sdar.tiles_floor_pct(8192, tile=8) == pytest.approx(50.0, abs=0.1)


# -- the readers -----------------------------------------------------------------------


def _run(**extra):
    scopes = {"attn.attend": {"seconds": 0.30, "ops": {"kernel:flash.blockdiff.20": 0.08,
                                                       "kernel:flash.blockdiff.21": 0.19,
                                                       "fusion.7": 0.03}},
              "diff.corrupt": {"seconds": 0.001, "ops": {"fusion.1": 0.001}},
              "diff.loss": {"seconds": 0.002, "ops": {"fusion.2": 0.002}},
              "head": {"seconds": 0.04, "ops": {"fusion.3": 0.04}}}
    steps = [{"router": {"pairs": [131072] * 4, "pairs_elsewhere": [114688] * 4}}] * 3
    return {"shape": SHAPE, "traffic": {"seq_len": 8192}, "chips": 1, "peaks": PEAKS,
            "traced_steps": 3, "traced_window_steps": steps, "kind": "train",
            "values": {"train_tok_s": 22500.0},
            "ops": {"expert_matmul": {"seconds": 0.06}},
            "diffusion": {"diff_tiles_visited": 272, "diff_tiles_causal": 528},
            "step_table": {"busy_s": 1.0, "scopes": scopes, "fused_with_optim_s": 0.0,
                           "unknown": {}}, **extra}


def test_readers_read_the_kernels_the_objective_and_the_steps_report():
    run = _run()
    c = costs_sdar.flash_cost(SHAPE, 1, 8192)
    least = 3 * (c["fwd_flops"] + c["bwd_flops"]) / PEAKS["bf16_flops_per_s"]
    assert readers_sdar.flash_roofline(run) == pytest.approx(100 * least / 0.27)   # kernels alone
    assert reader("flash_roofline.blockdiff").read(run) == readers_sdar.flash_roofline(run)
    assert 0 < readers_sdar.flash_roofline(run) < 100
    g = costs_sdar.grouped_matmul_cost(SHAPE, 16384)
    want = 100 * 4 * 3 * (g["fwd_flops"] + g["bwd_flops"]) / PEAKS["bf16_flops_per_s"] / 0.06
    assert reader("expert_matmul_roofline.sdar").read(run) == pytest.approx(want)
    per_token = costs_sdar.train_flops_per_token(SHAPE, 8192, 1 / 8)
    assert reader("train_mfu_pct.sdar").read(run) == pytest.approx(
        100 * 22500.0 * per_token / PEAKS["bf16_flops_per_s"])
    assert reader("diff_share_pct").read(run) == pytest.approx(0.3)
    assert reader("blockdiff_tiles_pct").read(run) == pytest.approx(100 * 272 / 528)


def test_readers_find_nothing_in_another_cells_run_or_without_the_report():
    other = _run(shape={**SHAPE, "model_type": "mellum"})
    for name in NEW_METRICS:
        assert reader(name).read(other) is None, name
    assert reader("blockdiff_tiles_pct").read(_run(diffusion=None)) is None
    assert reader("flash_roofline.blockdiff").read(_run(step_table={
        "busy_s": 1.0, "scopes": {}, "fused_with_optim_s": 0.0, "unknown": {}})) is None
    # the record there and every operation of the objective fused into a neighbour's pass: 0.0
    fused = _run()
    del fused["step_table"]["scopes"]["diff.corrupt"], fused["step_table"]["scopes"]["diff.loss"]
    assert reader("diff_share_pct").read(fused) == 0.0

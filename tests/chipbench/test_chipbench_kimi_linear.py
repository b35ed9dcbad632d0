"""The kimi-linear-train-8k cell's files (PR 64): the manifest with the cell
(read AS THIS PR LEFT IT: what a later PR appends is taken off before any
case reads it, tests/chipbench/test_chipbench_sdar.py's rule), the
configuration file against the catalog's row, its `memory` against the
tree's own count, the model builder, the runner that composes the runners
there were, the cost functions by hand-worked cases, each new reader on a
hand-built step table, and the one-thing-wrong tool at a tiny size."""

import copy
import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_kimi_linear, manifest as mf, readers_kimi_linear
from chipbench.reference import kimi_linear_decoder

CELL, CONFIG, TRAFFIC = "kimi-linear-train-8k", "kimi-linear-48b-a3b-train", "zipf_tokens_8k"
NEW_METRICS = ("train_mfu_pct.kimi_linear", "flash_roofline.mla192", "kda_scan_roofline.h32",
               "expert_matmul_roofline.kimi_linear")


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


def less_this_pr(manifest: dict) -> dict:
    """`manifest` as the PARENT had it: this PR's cell, configuration and four
    metrics taken off, and the cell off every list it joined."""
    was = as_this_pr_left_it(manifest)
    was["workloads"], was["configs"] = was["workloads"][:-1], was["configs"][:-1]
    was["per_layer"] = was["per_layer"][:-len(NEW_METRICS)]
    for m in was["per_layer"] + was["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    return was


M = as_this_pr_left_it(mf.load_manifest())
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CELLS = [w["name"] for w in M["workloads"]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
TIMELINE = ("dispatch_ms.train", "step_stalls.train", "stall_loss_pct.train", "gc_pause_ms.train",
            "report_max_ms.train", "host_other_cpu_pct.train", "step_gap_ms.train",
            "step_gap_program_pct.train")
# what every share cell reports, the KDA families, the dense SwiGLU, and this cell.
# (`mla_share_pct` and `mla_glue_pct` READ in this cell, 9.7 and 0.29 in a traced run, and are
# not joined: tests/chipbench/test_chipbench_glm_lite.py, which this PR may not edit, holds
# their lists to `glm47f-train` alone; PERF.md section 7 asks a `benchmark` PR for it.)
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "moe_compact_pct", "expert_imbalance", "experts_elsewhere_pct", "head_share_pct",
          "optim_share_pct", "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct",
          "fallback_sites.train", "ffn_share_pct", "kda_share_pct", "kda_scan_pct",
          "kda_glue_pct") + SETUP + TIMELINE
PEAKS = costs.load_peaks("TPU v5 lite")
TOKENS = 4 * 8192   # the KDA layers' positions a step


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(mf.load_manifest()) == [] and mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["zipf_s"]) == (8192, 1.1)
    assert cell["cell"]["traffic"] == TRAFFIC
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assumed = SHAPE["assumed"]
    assert {"0_sources", "i_block", "ii_kda", "iii_beta", "iv_mla", "v_router", "vi_dtypes",
            "vii_weights", "viii_sequence_length", "ix_packed_documents"} <= set(assumed)
    # each reading NOT taken is named beside the one taken
    for key in ("iii_beta", "iv_mla", "v_router"):
        assert "NOT taken" in assumed[key], key
    assert "NOT doubled" in assumed["iii_beta"] and "192^-1/2" in assumed["iv_mla"]
    assert "BALANCED" in assumed["v_router"] and "NOT IMPLEMENTED" in assumed["ix_packed_documents"]
    assert "TO BE" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    assert CELLS[-1] == CELL and M["configs"][-1]["name"] == CONFIG and len(CELLS) == 13
    # thirteen cells allow three four-chip cells (25%, rounded down); one is built
    assert [w["chips"] for w in M["workloads"]].count(4) == 1 and max(1, len(CELLS) // 4) == 3
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "256 rows" in why and "ALL 32 heads" in why and "1 x 8192" in why
    assert "top-8 of 256" in why and "32x" in why and "192/128" in why


def test_nothing_the_parent_had_is_changed_but_by_the_cell_appended():
    """Against the parent commit's BENCHMARK.json where git has it, else
    against the file less this PR's entries: every entry that stood stands,
    in its place, but for the `workloads` lists the cell joined at their end."""
    import subprocess

    was = less_this_pr(mf.load_manifest())
    try:
        text = subprocess.run(["git", "show", "9b9aa72d0c8bd80d35b5140b4fbd618e742f4620:BENCHMARK.json"],
                              cwd=mf.ROOT, capture_output=True, text=True, check=True).stdout
        assert json.loads(text) == was
    except (subprocess.CalledProcessError, FileNotFoundError):
        pass   # a checkout without the parent's history: the lists' tails are held below
    for key in ("command", "paths", "run_seconds"):
        assert M[key] == was[key], key
    assert len(M["end_to_end"]) == len(was["end_to_end"])
    assert len(M["per_layer"]) == len(was["per_layer"]) + len(NEW_METRICS)
    for now, then in zip(M["per_layer"][:len(was["per_layer"])] + M["end_to_end"],
                         was["per_layer"] + was["end_to_end"]):
        joined = now.get("workloads", [])[-1:] == [CELL]
        assert now == ({**then, "workloads": then["workloads"] + [CELL]} if joined else then)
        assert joined == (now["name"] in JOINED + ("train_tok_s",)), now["name"]
    assert M["configs"][:-1] == was["configs"] and M["workloads"][:-1] == was["workloads"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] == ("train step" if "mfu" in name else "kernels")
    assert m["better"] == "higher"
    assert m["source"] == ("host_clock" if "mfu" in name else "device_trace")
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes or the statistic (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert tuple(e["name"] for e in M["per_layer"][-len(NEW_METRICS):]) == NEW_METRICS


def test_the_manifest_of_a_later_day_reads_as_this_pr_left_it():
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


def test_step_scopes_gain_no_family_and_every_scope_of_the_stack_is_listed():
    from chipbench import readers_step

    own = mf.read_json(mf.ROOT, "chipbench/step_scopes/kimi_linear.json")
    assert own["families"] == {} and set(own) == {"comment", "families"}
    families = readers_step.VOCABULARY["families"]
    assert families["kda_scan"] == ["kda.scan"] and families["ffn"] == ["dense.ffn"]
    assert families["mla"] == ["mla.down", "mla.up", "mla.glue", "mla.attend", "mla.out"]
    listed = {s for scopes in families.values() for s in scopes}
    assert set(SHAPE["check"]["scopes"]) <= listed
    assert readers_step.scope_of_path("jit(step)/jvp(mla.attend)/pallas_call") == "mla.attend"


# -- the configuration file against the catalog -------------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Kimi-Linear-48B-A3B-Instruct":
            return row
    raise AssertionError("the catalog has no such row")


def test_every_published_key_is_the_catalogs_but_the_three_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v}
    assert changed == set(REDUCED)
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    # the nested group WHOLE: `kda_layers` and `full_attn_layers` as published
    assert SHAPE["linear_attn_config"] == row["config"]["linear_attn_config"]
    assert SHAPE["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert SHAPE["num_hidden_layers"] == 5


def test_every_width_the_issue_names_is_as_published():
    want = {"hidden_size": 2304, "intermediate_size": 9216, "moe_intermediate_size": 1024,
            "num_attention_heads": 32, "kv_lora_rank": 512, "q_lora_rank": None,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
            "num_experts_per_token": 8, "num_shared_experts": 1, "routed_scaling_factor": 2.446,
            "rms_norm_eps": 1e-5, "mla_use_nope": True, "first_k_dense_replace": 1,
            "moe_renormalize": True, "moe_router_activation_func": "sigmoid"}
    assert {k: SHAPE[k] for k in want} == want
    lin = SHAPE["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert SHAPE["published"]["num_experts"] == 256   # the router's outputs
    # two keys more than the published config.json, the same numbers under the harness's spelling
    assert SHAPE["num_experts_per_tok"] == SHAPE["num_experts_per_token"] == 8
    assert SHAPE["max_position_embeddings"] == SHAPE["model_max_length"] == 1048576
    assert "harness_keys" in SHAPE["assumed"]


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    published = SHAPE["published"]
    assert SHAPE["num_hidden_layers"] == 5 and SHAPE["num_experts"] >= 8
    # a whole period and four layers after the leading dense one
    assert costs_kimi_linear.layers(SHAPE) == ["kda", "kda", "kda", "mla", "kda"]
    assert SHAPE["vocab_size"] * 8 == published["vocab_size"] and SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 32
    assert 32 * SHAPE["num_experts"] == published["num_experts"]
    assert SHAPE["deployment"]["first_expert_held"] == 0
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    for key in REDUCED:
        assert "of" in SHAPE["reduced"][key], key
    rehearsal = SHAPE["reduced"]["num_hidden_layers"]
    assert "TAKEN" in rehearsal and "15.75" in rehearsal and "rehearsal, PR 64" in rehearsal
    assert "16 experts" in rehearsal   # the issue's other branch, and why it was not taken
    check = SHAPE["check"]
    assert check["scopes"] == ["moe.router", "moe.dispatch", "moe.experts", "moe.combine",
                               "kda.proj", "kda.conv", "kda.gates", "kda.scan", "kda.norm",
                               "kda.out", "mla.down", "mla.up", "mla.glue", "mla.attend",
                               "mla.out", "dense.ffn"]
    assert set(check) == {"scopes", "loss_tol", "loss_tol_why", "routing_tol", "routing_tol_why",
                          "grad_tol", "grad_tol_why", "routed_grad_tol", "routed_grad_tol_why",
                          "rule_tol", "rule_tol_why", "attention_tol", "attention_tol_why"}
    assert 0 < check["loss_tol"] <= 5e-4 and 0 < check["routing_tol"] < 0.05
    # a leaf left unchanged, or a gradient of zero, reads 1: over every limit
    assert 0 < check["rule_tol"] < check["attention_tol"] < check["grad_tol"]
    assert check["grad_tol"] <= check["routed_grad_tol"] < 1
    for why in ("loss_tol_why", "routing_tol_why", "grad_tol_why", "routed_grad_tol_why",
                "rule_tol_why", "attention_tol_why"):
        assert "my chip runs, PR 64" in check[why], why
    assert "bfloat16" in check["rule_tol_why"] and "mean" in check["rule_tol_why"].lower()
    assert "128^-1/2" in check["attention_tol_why"] and "rotary" in check["attention_tol_why"]
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["lr"] == 2.5e-7
    assert SHAPE["train"]["attention_impl"] == "flash" and "BALANCED" in SHAPE["train"]["lr_why"]


def test_memory_is_the_trees_own_count():
    """`memory` states the parameters the program's tree holds for this file
    (602M, ISSUE 64's table), its 12 B a parameter as arguments, and what the
    chip's allocator read."""
    n = costs_kimi_linear.num_params(SHAPE)
    assert n == 602_450_816 and f"{n:,}" in SHAPE["memory"]
    assert f"{n * 12 / 2 ** 30:.2f} GiB" in SHAPE["memory"] and "memory_peak_bytes" in SHAPE["memory"]
    assert "39,518,368" in SHAPE["memory"] and "29,114,880" in SHAPE["memory"]


# -- the model builder -----------------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.dense_d_ff, cfg.n_heads,
            cfg.head_dim, cfg.v_head_dim) == (5, 2304, 1024, 1024, 9216, 32, 192, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.conv_kernel) == (32, 128, 128, 4)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (256, 8, 0, 8)
    assert cfg.vocab_size == 20480 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.router_score == "sigmoid" and cfg.remat and cfg.remat_policy == "dots"
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda") and len(cfg.mla_layers) == 7
    assert cfg.routed_scaling == 2.446 and not cfg.kda_neg_eigval and not cfg.mla_rope
    assert cfg.q_lora_rank == 0 and cfg.first_dense_layers == 1
    shapes = jax.eval_shape(init, jax.random.key(0))
    period, dense = shapes["layers"]["period"], shapes["dense_layers"]
    assert set(period) == {"0", "1", "2", "3"} and "tail" not in shapes["layers"]
    assert dense["wq"].shape == (1, 2304, 4096) and dense["w_gate"].shape == (1, 2304, 9216)
    assert period["2"]["wq"].shape == (1, 2304, 32 * 192)
    assert period["2"]["wkv_b"].shape == (1, 512, 32 * 256) and period["2"]["wo"].shape == (1, 4096, 2304)
    for j in "013":
        assert period[j]["wq"].shape == (1, 2304, 4096) and period[j]["conv_k"].shape == (1, 4, 4096)
        assert period[j]["wf1"].shape == (1, 2304, 128) and period[j]["wf2"].shape == (1, 128, 4096)
        assert period[j]["A_log"].shape == (1, 32) and period[j]["dt_bias"].shape == (1, 4096)
    for j in "0123":
        assert period[j]["w_up"].shape == (1, 8, 2304, 1024)
        assert period[j]["shared_down"].shape == (1, 1024, 2304)
        assert period[j]["router"].shape == (1, 2304, 256)
    assert shapes["layers"]["router_bias"].shape == (4, 256)
    assert shapes["embed"].shape == (20480, 2304) and shapes["lm_head"].shape == (2304, 20480)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == costs_kimi_linear.num_params(SHAPE) == 602_450_816


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("moe_intermediate_size", 768), ("intermediate_size", 8192),
    ("kv_lora_rank", 256), ("qk_nope_head_dim", 192), ("qk_rope_head_dim", 32),
    ("v_head_dim", 192), ("num_attention_heads", 16), ("num_experts_per_token", 6),
    ("routed_scaling_factor", 1.0), ("rms_norm_eps", 1e-6), ("mla_use_nope", False),
    ("q_lora_rank", 768), ("rope_scaling", {"type": "yarn"}), ("num_expert_group", 8),
    ("num_nextn_predict_layers", 1), ("first_k_dense_replace", 0), ("num_shared_experts", 2),
    ("moe_router_activation_func", "softmax"),
    ("linear_attn_config", {**SHAPE["linear_attn_config"], "head_dim": 64}),
    ("linear_attn_config", {**SHAPE["linear_attn_config"], "num_heads": 8}),
    ("linear_attn_config", {**SHAPE["linear_attn_config"], "short_conv_kernel_size": 2}),
    ("linear_attn_config", {**SHAPE["linear_attn_config"], "full_attn_layers": [4, 8]})])
def test_builder_refuses_a_changed_width_or_form(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="sizes"):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_changed_published_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    for key, value in (("vocab_size", 65536), ("num_experts", 64), ("num_hidden_layers", 32)):
        with pytest.raises(RuntimeError, match="sizes"):
            builder.build({**SHAPE, "published": {**SHAPE["published"], key: value}})


# -- the runner: the runners there were, composed ----------------------------------------


def _ctx(logged, config=SHAPE):
    return {"root": mf.ROOT, "config": config, "traffic": {}, "args": types.SimpleNamespace(seed=5),
            "log": lambda **kw: logged.append(kw)}


@pytest.mark.parametrize("factor,routed_factor,rule_factor,attention_factor,correct", [
    (1.0, 1.0, 1.0, 1.0, True), (1.0 + 2 * SHAPE["check"]["grad_tol"], 1.0, 1.0, 1.0, False),
    (1.0, 1.0 + 2 * SHAPE["check"]["routed_grad_tol"], 1.0, 1.0, False),
    (1.0, 1.0, 1.0 + 2 * SHAPE["check"]["rule_tol"], 1.0, False),
    (1.0, 1.0, 1.0, 1.0 + 2 * SHAPE["check"]["attention_tol"], False)],
    ids=["the_references", "a_leaf_off_by_twice_the_limit", "a_routed_leaf_off_by_twice_its_limit",
         "the_rule_off_by_twice_its_limit", "the_attention_off_by_twice_its_limit"])
def test_the_runner_runs_the_share_runner_then_holds_gradient_rule_and_attention(
        monkeypatch, factor, routed_factor, rule_factor, attention_factor, correct):
    """No further copy of the loop: `run` loads runners/train_reference_from_config.py
    and runs it on the file's configuration with the harness's spelling of the
    pairs' key beside it, takes the bias the loop started from, and adds three
    readings through the runners that stand; `correct` is all of them."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    solar = mf.load_plugin(mf.ROOT, "runners", "train_reference_solar_open2")
    seen, logged = {}, []
    from_config = types.SimpleNamespace(
        _BIAS=["the bias"], run=lambda ctx: seen.update(ran=ctx["config"] is SHAPE) or {
            "correct": True, "checks": {"first_routing_is_the_reference": True}, "losses": [10.5]})
    layer = {"wf2": jnp.arange(1.0, 7.0), "w_up": jnp.arange(2.0, 5.0)}
    tree = {"layers": {"period": {"1": layer}}, "embed": jnp.ones((3, 2))}
    off = {"layers": {"period": {"1": {"wf2": factor * layer["wf2"],
                                       "w_up": routed_factor * layer["w_up"]}}},
           "embed": tree["embed"]}
    shared = types.SimpleNamespace(program_gradient=lambda ctx, chk, seed, bias: (
        seen.update(seed=seed, bias=bias, checked=chk is checked) or "params",
        {"tokens": ["t0"], "targets": "y"}, off, 10.5))
    six = tuple(jnp.full((2,), float(i + 1)) for i in range(6))
    four = six[:4]
    solar = types.SimpleNamespace(
        RULE_OUTPUTS=solar.RULE_OUTPUTS, rule_cotangent=lambda tokens, config, seed: "w",
        program_rule=lambda module, args, w: (
            seen.update(program_rule=(module, args, w))
            or dict(zip(solar.RULE_OUTPUTS, (rule_factor * a for a in six)))))
    plugins = {"train_reference_from_config": from_config, "train_reference_checked": checked,
               "train_reference_nemotron_h": shared, "train_reference_solar_open2": solar}
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[name])
    monkeypatch.setattr(kimi_linear_decoder, "grads", lambda params, t, y, config: (
        seen.update(reference=(params, t, y)) or tree))
    monkeypatch.setattr(kimi_linear_decoder, "first_rule", lambda params, tokens, config, w: (
        seen.update(rule=(params, tokens, w)) or ("args", six)))
    monkeypatch.setattr(kimi_linear_decoder, "first_attention", lambda params, tokens, config, w: (
        seen.update(attention=(params, tokens, w)) or ("qkv", four)))
    monkeypatch.setattr(runner, "attention_cotangent", lambda tokens, config, seed: "wa")
    monkeypatch.setattr(runner, "program_attention", lambda args, w: (
        seen.update(program_attention=(args, w))
        or dict(zip(runner.ATTENTION_OUTPUTS, (attention_factor * a for a in four)))))
    monkeypatch.setattr(checked, "built", lambda ctx: (types.SimpleNamespace(stack_module="m"),))
    got = runner.run(_ctx(logged))
    assert seen["ran"] and seen["bias"] == "the bias" and seen["seed"] == 5 and seen["checked"]
    assert seen["reference"] == ("params", ["t0"], "y") and seen["rule"] == ("params", "t0", "w")
    assert seen["attention"] == ("params", "t0", "wa")
    assert seen["program_rule"] == ("m", "args", "w") and seen["program_attention"] == ("qkv", "wa")
    assert got["checks"] == {"first_routing_is_the_reference": True,
                             "first_gradient_is_the_reference": factor == routed_factor == 1.0,
                             "first_rule_is_the_reference": rule_factor == 1.0,
                             "first_attention_is_the_reference": attention_factor == 1.0}
    assert got["correct"] is correct
    events = {e["event"]: e for e in logged}
    assert events["correct_gradient"]["leaves"] == 2 and events["correct_rule"]["leaves"] == 6
    assert events["correct_attention"]["leaves"] == 4
    assert events["correct_gradient"]["routed_leaves"] == 1
    assert events["correct_gradient"]["routed_tolerance"] == SHAPE["check"]["routed_grad_tol"]
    assert events["correct_attention"]["tolerance"] == SHAPE["check"]["attention_tol"]
    assert events["correct_gradient"]["first_loss"] == 10.5


def test_the_gradients_limits_hold_every_leaf_the_routed_ones_to_their_own():
    """`gradient_verdict`: the worst of the leaves that are no expert layer's
    `router`, `w_gate`, `w_up` or `w_down` against `grad_tol` (the DENSE
    layer's SwiGLU among them: no routing decision multiplies it); the routed
    leaves of every expert layer against `routed_grad_tol`; none is left out."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    period, check = "['layers']['period']", {"grad_tol": 0.2, "routed_grad_tol": 0.4}
    errors = {f"{period}['0']['router']": 0.35, f"{period}['2']['w_down']": 0.3,
              f"{period}['1']['A_log']": 0.04, f"{period}['1']['shared_up']": 0.02,
              "['dense_layers']['w_up']": 0.03, "['embed']": 0.01}
    got = runner.gradient_verdict(checked, errors, check)
    assert got["ok"] and got["worst"] == f"{period}['1']['A_log']" and got["err"] == 0.04
    assert (got["leaves"], got["routed_leaves"]) == (4, 2)
    assert got["routed_worst"] == f"{period}['0']['router']" and got["routed_err"] == 0.35
    bad = lambda **kw: not runner.gradient_verdict(checked, {**errors, **kw}, check)["ok"]  # noqa: E731
    assert bad(**{f"{period}['1']['A_log']": 0.21}) and bad(**{"['embed']": float("nan")})
    assert bad(**{f"{period}['2']['w_down']": 0.41}) and bad(**{f"{period}['0']['router']": 1.0})
    assert bad(**{f"{period}['0']['router']": float("nan")})
    # the dense SwiGLU's and the shared expert's leaves are no routed ones
    assert bad(**{"['dense_layers']['w_up']": 0.3}) and bad(**{f"{period}['1']['shared_up']": 0.3})
    tree = jax.eval_shape(mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"]).build(
        SHAPE)[1], jax.random.key(0))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]
    routed = [p for p in paths if p.endswith(runner.ROUTED) and "dense_layers" not in p]
    assert len(routed) == 16 and len(paths) == 114


def _tiny(dtype=jnp.float32):
    from model_cases import kimi_linear_shape
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    # the dense layer and one period (KDA, MLA)
    cfg = dataclasses.replace(get_model_config("kimi-linear-tiny"), dtype=dtype, n_layers=3)
    return cfg, {**kimi_linear_shape(cfg), "train": {"lr": 2.5e-7, "global_batch": 2}}, llama


def test_the_program_gradient_rule_and_attention_are_the_programs_own_and_meet_the_references(
        monkeypatch):
    """At the tiny preset (the dense layer and one period) in float32 on the
    CPU: `program_gradient` runs the program's `make_train_step` with AdamW
    from the bias it is given, every leaf meets `reference.grads`; layer 1's
    rule through the program's `kda_rule` BY NAME meets the
    position-by-position rule; layer 3's attention through the kernels
    (interpreted) at keys of 16 and values of 8 meets the float32 softmax,
    forward and the cotangent pulled back; a scale of the un-rotated width
    alone, the shared key left out and a bfloat16 state are each seen."""
    from chipbench.tools import kimi_linear_wrong as wrong

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    solar = mf.load_plugin(mf.ROOT, "runners", "train_reference_solar_open2")
    shared = mf.load_plugin(mf.ROOT, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    cfg, shape, llama = _tiny()
    tok = jax.random.randint(jax.random.key(1), (2, 81), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    init = lambda key: llama.init_params(cfg, key)   # noqa: E731
    monkeypatch.setattr(checked, "built", lambda ctx: (cfg, init, lambda seed: batch))
    bias = 0.05 * np.random.default_rng(0).standard_normal((2, 12)).astype(np.float32)
    ctx = {"config": shape}
    with jax.default_matmul_precision("highest"):
        params, got_batch, grads, loss, counts = shared.program_gradient(
            ctx, checked, 7, bias, with_counts=True)
    assert got_batch is batch and counts.shape == (2, 12) and counts.sum() == 2 * 160 * cfg.top_k
    parts = kimi_linear_decoder.loss_parts(params, batch["tokens"], batch["targets"], shape)
    assert abs(loss - float(parts["loss"])) < 1e-5 * loss
    assert shared.moved_share(counts, parts["tokens_per_expert"]) == 0.0
    reference = kimi_linear_decoder.grads(params, batch["tokens"], batch["targets"], shape)
    errors = checked.errors_by_leaf(grads, reference)
    assert len(errors) == len(jax.tree.leaves(params)) and max(errors.values()) < 5e-4, max(
        errors, key=errors.get)
    assert runner.gradient_verdict(checked, errors, SHAPE["check"])["routed_leaves"] == 8
    w = solar.rule_cotangent(batch["tokens"], shape, 7)
    assert w.shape == (80, cfg.kda_heads, cfg.kda_head_dim)
    args, outputs = kimi_linear_decoder.first_rule(params, batch["tokens"][0], shape, w)
    assert [a.shape for a in args] == [(80, 4, 16)] * 4 + [(80, 4)]
    with jax.default_matmul_precision("highest"):
        mine = solar.program_rule(cfg.stack_module, args, w)
    names = dict(zip(solar.RULE_OUTPUTS, outputs))
    rule = checked.errors_by_leaf(mine, names)
    assert set(rule) == {f"['{n}']" for n in solar.RULE_OUTPUTS} and max(rule.values()) < 2e-5, rule
    for name in ("the decay's mean over a head's channels", "the state in bfloat16", "beta doubled"):
        with wrong.VARIANTS[name]():
            _, other = kimi_linear_decoder.first_rule(params, batch["tokens"][0], shape, w)
        seen = checked.errors_by_leaf(dict(zip(solar.RULE_OUTPUTS, other)), names)
        assert max(seen.values()) > 20 * max(rule.values()), (name, seen, rule)
    # the attention alone, the inputs in float32 here (the cell rounds them to bfloat16)
    w = runner.attention_cotangent(batch["tokens"], shape, 7)
    assert w.shape == (80, cfg.n_heads, cfg.v_head_dim)
    args, outputs = kimi_linear_decoder.first_attention(params, batch["tokens"][0], shape, w,
                                                        dtype=jnp.float32)
    assert [a.shape for a in args] == [(80, 4, 16)] * 2 + [(80, 4, 8)]
    mine = runner.program_attention(args, w, dtype="float32")
    names = dict(zip(runner.ATTENTION_OUTPUTS, outputs))
    attention = checked.errors_by_leaf(mine, names)
    assert set(attention) == {f"['{n}']" for n in runner.ATTENTION_OUTPUTS}
    assert max(attention.values()) < 2e-5, attention
    for name in ("scale 128^-1/2", "k_r left out of the scores", "a rotary on the 64 channels",
                 "c_kv's norm left out"):
        with wrong.VARIANTS[name]():
            _, other = kimi_linear_decoder.first_attention(params, batch["tokens"][0], shape, w,
                                                           dtype=jnp.float32)
        seen = checked.errors_by_leaf(dict(zip(runner.ATTENTION_OUTPUTS, other)), names)
        assert max(seen.values()) > 20 * max(attention.values()), (name, seen, attention)


def test_each_change_of_the_wrong_table_moves_the_reference_and_is_undone():
    """chipbench/tools/kimi_linear_wrong.py patches the reference's small
    functions one at a time; here, at the tiny preset on the CPU, every change
    moves the reference's loss, and the reference is the plain one again after
    it."""
    from chipbench.tools import kimi_linear_wrong as wrong

    assert list(wrong.VARIANTS) == [
        "a rotary on the 64 channels", "scale 128^-1/2", "beta doubled", "c_kv's norm left out",
        "k_r left out of the scores", "softmax scores in the router", "scaling 1 for 2.446",
        "the shared expert left out", "the decay's mean over a head's channels",
        "the state in bfloat16", "the reference in bfloat16 throughout"]
    assert wrong.PRECISION_ONLY == tuple(wrong.VARIANTS)[-2:]
    cfg, shape, llama = _tiny()
    params = llama.init_params(cfg, jax.random.key(3))
    # a norm at 1 would hide its absence
    params["layers"]["period"]["1"]["kv_a_norm"] = params["layers"]["period"]["1"]["kv_a_norm"] * 0.3
    tok = jax.random.randint(jax.random.key(2), (1, 81), 0, cfg.vocab_size)
    tokens, targets = tok[:, :-1], tok[:, 1:]
    sound = float(kimi_linear_decoder.loss(params, tokens, targets, shape))
    plain = dict(vars(kimi_linear_decoder))
    for name, change in wrong.VARIANTS.items():
        with change():
            moved = float(kimi_linear_decoder.loss(params, tokens, targets, shape))
        assert abs(moved - sound) > 1e-5 * sound, name
        assert dict(vars(kimi_linear_decoder)) == plain, name   # every patch taken off again
    assert float(kimi_linear_decoder.loss(params, tokens, targets, shape)) == sound


# -- the cost functions, by hand ----------------------------------------------------


def test_required_operations_are_issue_64s_count():
    """Per token forward on this share, MFLOP (ISSUE 64's table): four KDA
    mixers 330 (the rule itself 3.7 a mixer), the MLA layer 58 + 84 of scores
    at 8,192, the dense SwiGLU 127, the head 94, the expert layers' held part
    76: about 770, a step 18.9 TFLOP of required work, 96 ms at the peak."""
    f = costs_kimi_linear.forward_flops_per_token(SHAPE, 8192, 1 / 32)
    kda = 4 * 2304 * 4096 + 2 * 128 * (2304 + 4096) + 2304 * 32
    assert f["kda.proj"] == 4 * 2 * kda and f["kda.scan"] == 4 * 7 * 32 * 128 * 128
    assert round((f["kda.proj"] + f["kda.scan"]) / 1e6) == 330
    assert f["mla.proj"] == 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304)
    assert f["mla.scores"] == 2 * (192 + 128) * 32 * 8193 / 2
    assert round(f["mla.proj"] / 1e6) == 58 and round(f["mla.scores"] / 1e6) == 84
    assert f["dense_ffn"] == 2 * 3 * 2304 * 9216 and round(f["dense_ffn"] / 1e6) == 127
    assert f["experts.router"] == 4 * 2 * 2304 * 256 and f["experts.shared"] == 4 * 2 * 3 * 2304 * 1024
    assert f["experts.routed"] == 4 * 2 * 3 * 2304 * 1024 * 8 / 32
    assert 75 <= sum(v for k, v in f.items() if k.startswith("experts")) / 1e6 <= 76
    assert f["head"] == 2 * 2304 * 20480 and round(f["head"] / 1e6) == 94
    assert 768 <= round(sum(f.values()) / 1e6) <= 771
    assert costs_kimi_linear.train_flops_per_token(SHAPE, 8192, 1 / 32) == 3 * sum(f.values())
    step = 8192 * 3 * sum(f.values())
    assert 18.8e12 < step < 19.0e12 and 0.095 < step / PEAKS["bf16_flops_per_s"] < 0.097
    mixers = sum(v for k, v in f.items() if k.startswith(("kda", "mla")))
    assert 0.60 < mixers / sum(f.values()) < 0.62 and 0.12 < f["head"] / sum(f.values()) < 0.13
    # the same count at the tiny size against the program's own method, every expert somewhere
    cfg, shape, _ = _tiny()
    mine = sum(costs_kimi_linear.forward_flops_per_token(shape, 80, 1.0).values())
    assert mine == pytest.approx(cfg.flops_per_token(80))
    assert costs_kimi_linear.num_params(shape) == cfg.num_params()
    assert costs_kimi_linear.expert_layers(SHAPE) == 4 and costs_kimi_linear.expert_layers(shape) == 2


def test_scan_flash_and_grouped_matmul_costs_by_hand():
    c = costs_kimi_linear.scan_cost(SHAPE, 1, 8192)
    elements = 32 * 128 * 128
    assert c["layers"] == 4 and c["fwd_flops"] == 7 * TOKENS * elements
    assert c["bwd_flops"] == 14 * TOKENS * elements
    # q, k, v in bf16, the decay [128] and beta in float32 a head and position; o in bf16
    inputs = TOKENS * 32 * (3 * 128 * 2 + 128 * 4 + 4)
    assert c["fwd_bytes"] == inputs + TOKENS * 32 * 128 * 2
    assert c["bwd_bytes"] == 2 * inputs + TOKENS * 32 * 128 * 2
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    # 16 / 3 x Solar-Open2's 1.05 ms: four layers for three, 32 heads for 8
    assert bound == "memory" and 5.5e-3 < least < 5.7e-3
    f = costs_kimi_linear.flash_cost(SHAPE, 1, 8192)
    assert f["layers"] == 1 and f["fwd_flops"] == 32 * 2 * (192 + 128) * 8192 * 8193 / 2
    assert f["bwd_flops"] == 2.5 * f["fwd_flops"]
    assert f["fwd_bytes"] == 2 * 8192 * 32 * 192 * 2 + 2 * 8192 * 32 * 128 * 2
    assert f["bwd_bytes"] == 2 * f["fwd_bytes"]
    least, bound = costs.roofline_seconds(3.5 * f["fwd_flops"], 3 * f["fwd_bytes"], PEAKS)
    assert bound == "compute" and 12.1e-3 < least < 12.3e-3   # 12.2 ms a step at the MXU's peak
    _, shape, _ = _tiny()
    t = costs_kimi_linear.flash_cost(shape, 2, 80)
    assert t["layers"] == 1 and t["fwd_flops"] == 2 * 4 * 2 * (16 + 8) * 80 * 81 / 2
    g = costs_kimi_linear.grouped_matmul_cost(SHAPE, 2048.0)
    assert g["fwd_flops"] == 3 * 2 * 2048 * 2304 * 1024 and g["bwd_flops"] == 2 * g["fwd_flops"]
    each = 2 * (2048 * 2304 + 2048 * 1024 + 8 * 2304 * 1024)
    assert g["fwd_bytes"] == 3 * each and g["bwd_bytes"] == 6 * each


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 0.96, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "kda.scan": {"seconds": 0.30, "ops": {"kernel:kda_fwd.1": 0.1, "kernel:kda_bwd.1": 0.2}},
        "kda.proj": {"seconds": 0.2, "ops": {"fusion.1": 0.2}},
        "mla.attend": {"seconds": 0.062, "ops": {"kernel:mla.attend.9": 0.04,
                                                 "kernel:mla.attend.8": 0.02, "fusion.6": 0.002}},
        "moe.experts": {"seconds": 0.03, "ops": {"fusion.7": 0.03}}}}
    router = {"pairs": [65536] * 4, "pairs_elsewhere": [63488] * 4}
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 8192}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 0.96, "window_s": 0.97},
            "trace": object(), "values": {"train_tok_s": 25000.0},
            "ops": {"expert_matmul": {"seconds": 0.02}},
            "traced_window_steps": [{"router": router}] * 3, **extra}


def test_readers_read_the_rule_the_kernels_and_the_step():
    run = _run()
    c = costs_kimi_linear.scan_cost(SHAPE, 1, 8192)
    least = 3 * (c["fwd_bytes"] + c["bwd_bytes"]) / PEAKS["hbm_bytes_per_s"]
    assert reader("kda_scan_roofline.h32").read(run) == pytest.approx(100 * least / 0.30)
    assert 0 < reader("kda_scan_roofline.h32").read(run) < 100
    f = costs_kimi_linear.flash_cost(SHAPE, 1, 8192)
    least = 3 * 3.5 * f["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.mla192").read(run) == pytest.approx(100 * least / 0.06)
    assert 0 < reader("flash_roofline.mla192").read(run) < 100
    g = costs_kimi_linear.grouped_matmul_cost(SHAPE, 2048.0)
    least, _ = costs.roofline_seconds(12 * (g["fwd_flops"] + g["bwd_flops"]),
                                      12 * (g["fwd_bytes"] + g["bwd_bytes"]), PEAKS)
    assert reader("expert_matmul_roofline.kimi_linear").read(run) == pytest.approx(100 * least / 0.02)
    assert 0 < reader("expert_matmul_roofline.kimi_linear").read(run) < 100
    per_token = costs_kimi_linear.train_flops_per_token(SHAPE, 8192, 2048 / 65536)
    assert reader("train_mfu_pct.kimi_linear").read(run) == pytest.approx(
        100 * 25000.0 * per_token / PEAKS["bf16_flops_per_s"])
    assert 0 < reader("train_mfu_pct.kimi_linear").read(run) < 100


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    for other in ("solar-open2-250b-train", "glm-4.7-flash-train"):
        shape = mf.read_json(mf.ROOT, f"chipbench/configs/{other}.json")
        for name in NEW_METRICS:
            assert reader(name).read(_run(shape=shape)) is None, (other, name)
        assert not readers_kimi_linear.is_kimi_linear({"shape": shape})
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"moe.experts": {
        "seconds": 0.03, "ops": {"fusion.7": 0.03}}}}
    for name in ("flash_roofline.mla192", "kda_scan_roofline.h32"):
        assert reader(name).read(bare) is None, name
    assert reader("train_mfu_pct.kimi_linear").read(_run(values={})) is None
    assert reader("expert_matmul_roofline.kimi_linear").read(_run(traced_window_steps=[])) is None
    assert reader("train_mfu_pct.kimi_linear").read(_run(traced_window_steps=[])) is None
    assert readers_kimi_linear.is_kimi_linear({"shape": SHAPE})
    # Solar-Open2's readers, which share the KDA scopes, stay silent in this cell
    from chipbench import readers_solar_open2
    assert not readers_solar_open2.is_solar_open2({"shape": SHAPE})
    assert reader("kda_scan_roofline").read(_run()) is None

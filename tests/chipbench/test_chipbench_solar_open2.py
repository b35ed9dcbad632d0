"""The solar-open2-train-8k cell's files (PR 60): the manifest with the cell
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

from chipbench import costs, costs_solar_open2, manifest as mf, readers_solar_open2
from chipbench.reference import solar_open2_decoder

CELL, CONFIG, TRAFFIC = "solar-open2-train-8k", "solar-open2-250b-train", "zipf_tokens_8k"
NEW_METRICS = ("kda_share_pct", "kda_scan_pct", "kda_glue_pct", "kda_scan_roofline",
               "flash_roofline.nope8", "expert_matmul_roofline.solar_open2",
               "train_mfu_pct.solar_open2")


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
    """`manifest` as the PARENT had it: this PR's cell, configuration and
    seven metrics taken off, and the cell off every list it joined."""
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
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size", "num_attention_heads",
           "num_key_value_heads", "linear_attn_config"]
CELLS = [w["name"] for w in M["workloads"]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
TIMELINE = ("dispatch_ms.train", "step_stalls.train", "stall_loss_pct.train", "gc_pause_ms.train",
            "report_max_ms.train", "host_other_cpu_pct.train", "step_gap_ms.train",
            "step_gap_program_pct.train")
# what every share cell reports, the GQA layer's attention family, and this cell
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "moe_compact_pct", "expert_imbalance", "experts_elsewhere_pct", "head_share_pct",
          "optim_share_pct", "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct",
          "fallback_sites.train", "attn_share_pct") + SETUP + TIMELINE
PEAKS = costs.load_peaks("TPU v5 lite")
TOKENS = 3 * 8192   # the KDA layers' positions a step


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
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assumed = SHAPE["assumed"]
    assert {"0_sources", "i_kda_projections", "ii_beta", "iii_decay", "iv_rule", "v_output",
            "vi_gqa", "vii_block", "viii_router", "ix_dtypes", "x_weights", "xi_chunk",
            "xii_sequence_length", "xiii_packed_documents"} <= set(assumed)
    # each reading NOT taken is named beside the one taken
    for key in ("i_kda_projections", "ii_beta", "iii_decay", "v_output", "vi_gqa", "viii_router"):
        assert "NOT taken" in assumed[key], key
    assert "MEAN" in assumed["iii_decay"] and "Qwen3-Next" in assumed["vi_gqa"]
    assert "BALANCED" in assumed["viii_router"] and "NOT IMPLEMENTED" in assumed["xiii_packed_documents"]
    assert "TO BE" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    assert CELLS[-1] == CELL and M["configs"][-1]["name"] == CONFIG and len(CELLS) == 12
    # twelve cells allow a third four-chip cell (25%, rounded down); one is built
    assert [w["chips"] for w in M["workloads"]].count(4) == 1
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "205 rows" in why and "8 of 64 heads" in why and "1 x 8192" in why
    assert "top-8 of 320" in why and "40x" in why


def test_nothing_the_parent_had_is_changed_but_by_the_cell_appended():
    """Against the parent commit's BENCHMARK.json where git has it, else
    against the file less this PR's entries: every entry that stood stands,
    in its place, but for the `workloads` lists the cell joined at their end."""
    import subprocess

    was = less_this_pr(mf.load_manifest())
    try:
        text = subprocess.run(["git", "show", "d4c839ad074a69300fa372c04b319be8d905d648:BENCHMARK.json"],
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
    assert m["layer"] in {e["layer"] for e in M["per_layer"] if e["name"] not in NEW_METRICS}
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
    assert m["source"] == ("host_clock" if "mfu" in name else "device_trace")
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes or the statistic (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert tuple(e["name"] for e in M["per_layer"][-len(NEW_METRICS):]) == NEW_METRICS


def test_the_gqa_layers_gate_joins_no_metric_of_its_own():
    """The GQA layer's elementwise gate stands under `attn.gate` in the
    program, where it fuses into `attn.out`'s product and reads 0 on the
    chip: `attn_gate_pct` stays laguna-train's alone, and the gate's time is
    `attn.out`'s (PERF.md section 5)."""
    assert mf.by_name(M["per_layer"], "attn_gate_pct", "metric")["workloads"] == ["laguna-train"]
    assert "attn.gate" in SHAPE["check"]["scopes"] and "attn.out" in SHAPE["check"]["scopes"]


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


def test_step_scopes_gain_three_families_and_keep_the_rest():
    from chipbench import readers_step

    own = mf.read_json(mf.ROOT, "chipbench/step_scopes/solar_open2.json")
    assert own["families"] == {"kda_proj": ["kda.proj", "kda.out"], "kda_scan": ["kda.scan"],
                               "kda_glue": ["kda.conv", "kda.gates", "kda.norm"]}
    assert set(own) == {"comment", "families"}
    for fam, scopes in own["families"].items():
        assert readers_step.VOCABULARY["families"][fam] == scopes
        assert all(readers_step.FAMILY_OF[s] == fam for s in scopes)
    # the families that stood, scope for scope
    assert readers_step.VOCABULARY["families"]["gdn_scan"] == ["gdn.scan"]
    assert readers_step.VOCABULARY["families"]["gate"] == ["attn.gate", "swa.gate"]
    assert readers_step.scope_of_path("jit(step)/jvp(kda.scan)/while/body/dot_general") == "kda.scan"


# -- the configuration file against the catalog -------------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Solar-Open2-250B":
            return row
    raise AssertionError("the catalog has no such row")


def test_every_published_key_is_the_catalogs_but_the_six_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if SHAPE.get(k) != v}
    assert changed == set(REDUCED)
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    # inside the one nested group only the head COUNT changes
    group, was = SHAPE["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k for k in was if group[k] != was[k]} == {"num_heads"} and set(group) == set(was)
    # `gqa_layers` stays WHOLE: the layers below `num_hidden_layers` are run
    assert SHAPE["gqa_layers"] == list(range(0, 48, 4)) and SHAPE["num_hidden_layers"] == 4


def test_every_width_the_issue_names_is_as_published():
    want = {"hidden_size": 4096, "head_dim": 128, "moe_intermediate_size": 1280,
            "num_experts_per_tok": 8, "intermediate_size": 10240, "n_shared_experts": 1,
            "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "use_rope": False,
            "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True}
    assert {k: SHAPE[k] for k in want} == want
    assert SHAPE["linear_attn_config"]["head_dim"] == 128
    assert SHAPE["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert SHAPE["published"]["n_routed_experts"] == 320   # the router's outputs


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    published = SHAPE["published"]
    assert SHAPE["num_hidden_layers"] == 4 and SHAPE["n_routed_experts"] >= 8
    assert SHAPE["vocab_size"] * 8 == published["vocab_size"] and SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 40
    assert 40 * SHAPE["n_routed_experts"] == published["n_routed_experts"]
    assert SHAPE["deployment"]["first_expert_held"] == 0
    # ONE share of the heads, 8 ways, for all three counts
    assert 8 * SHAPE["num_attention_heads"] == published["num_attention_heads"]
    assert 8 * SHAPE["num_key_value_heads"] == published["num_key_value_heads"]
    assert 8 * SHAPE["linear_attn_config"]["num_heads"] == published["linear_attn_config"]["num_heads"]
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    assert not mf.WIDTH_KEYS.search("num_heads")   # a head COUNT is no width
    for key in REDUCED:
        assert "of" in SHAPE["reduced"][key], key
    rehearsal = SHAPE["reduced"]["num_hidden_layers"]
    assert "(a)" in rehearsal and "TAKEN" in rehearsal and "(b)" in rehearsal and "(c)" in rehearsal
    assert "15.75" in rehearsal and "rehearsal, PR 60" in rehearsal
    check = SHAPE["check"]
    assert check["scopes"] == ["moe.router", "moe.dispatch", "moe.experts", "moe.combine",
                               "kda.proj", "kda.conv", "kda.gates", "kda.scan", "kda.norm",
                               "kda.out", "attn.qkv", "attn.attend", "attn.gate", "attn.out"]
    assert set(check) == {"scopes", "loss_tol", "loss_tol_why", "routing_tol", "routing_tol_why",
                          "grad_tol", "grad_tol_why", "routed_grad_tol", "routed_grad_tol_why",
                          "rule_tol", "rule_tol_why"}
    assert 0 < check["loss_tol"] <= 5e-4 and 0 < check["routing_tol"] < 0.05
    # a leaf left unchanged, or a gradient of zero, reads 1: over every limit
    assert 0 < check["rule_tol"] < check["grad_tol"] < check["routed_grad_tol"] < 1
    for why in ("loss_tol_why", "routing_tol_why", "grad_tol_why", "routed_grad_tol_why",
                "rule_tol_why"):
        assert "my chip runs, PR 60" in check[why], why
    assert "bfloat16" in check["rule_tol_why"] and "mean" in check["rule_tol_why"].lower()
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["lr"] == 2.5e-7
    assert SHAPE["train"]["attention_impl"] == "flash" and "BALANCED" in SHAPE["train"]["lr_why"]


def test_memory_is_the_trees_own_count():
    """`memory` states the parameters the program's tree holds for this
    file (840M, ISSUE 60's table corrected by the tree), its 12 B a
    parameter as arguments, and what the chip's allocator read."""
    n = costs_solar_open2.num_params(SHAPE)
    assert n == 840_875_672 and f"{n:,}" in SHAPE["memory"]
    assert f"{n * 12 / 2 ** 30:.2f} GiB" in SHAPE["memory"] and "memory_peak_bytes" in SHAPE["memory"]
    assert "18,135,176" in SHAPE["memory"] and "13,631,488" in SHAPE["memory"]


# -- the model builder -----------------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (4, 4096, 1280, 1280, 8, 1, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.conv_kernel) == (8, 128, 128, 4)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (320, 8, 0, 8)
    assert cfg.vocab_size == 24576 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.router_score == "sigmoid" and cfg.remat and cfg.remat_policy == "dots"
    assert cfg.layer_types == ("gqa", "kda", "kda", "kda") and len(cfg.gqa_layers) == 12
    assert cfg.routed_scaling == 1.0
    shapes = jax.eval_shape(init, jax.random.key(0))
    period = shapes["layers"]["period"]
    assert period["0"]["wq"].shape == period["0"]["wg"].shape == (1, 4096, 1024)
    assert period["0"]["wk"].shape == (1, 4096, 128) and period["0"]["wo"].shape == (1, 1024, 4096)
    for j in "123":
        assert period[j]["wq"].shape == (1, 4096, 1024) and period[j]["conv_k"].shape == (1, 4, 1024)
        assert period[j]["wf1"].shape == (1, 4096, 128) and period[j]["wf2"].shape == (1, 128, 1024)
        assert period[j]["A_log"].shape == (1, 8) and period[j]["dt_bias"].shape == (1, 1024)
        assert period[j]["w_up"].shape == (1, 8, 4096, 1280)
        assert period[j]["shared_down"].shape == (1, 1280, 4096)
        assert period[j]["router"].shape == (1, 4096, 320)
    assert shapes["layers"]["router_bias"].shape == (4, 320)
    assert shapes["embed"].shape == (24576, 4096) and shapes["lm_head"].shape == (4096, 24576)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == costs_solar_open2.num_params(SHAPE) == 840_875_672


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("moe_intermediate_size", 1024), ("head_dim", 64),
    ("num_experts_per_tok", 6), ("routed_scaling_factor", 2.5), ("rms_norm_eps", 1e-6),
    ("use_gqa_gate", False), ("kda_allow_neg_eigval", False), ("use_rope", True),
    ("kda_use_full_proj", True), ("first_k_dense_replace", 1), ("n_shared_experts", 2),
    ("gqa_layers", [0, 3, 6]), ("num_key_value_heads", 2),
    ("linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 64, "num_heads": 8,
                            "num_kv_heads": None}),
    ("linear_attn_config", {"short_conv_kernel_size": 2, "head_dim": 128, "num_heads": 8,
                            "num_kv_heads": None}),
    ("linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 16,
                            "num_kv_heads": None})])
def test_builder_refuses_a_changed_width_form_or_an_uneven_share(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="sizes"):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_changed_published_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    for key, value in (("vocab_size", 65536), ("n_routed_experts", 64), ("num_hidden_layers", 32),
                       ("num_attention_heads", 32)):
        with pytest.raises(RuntimeError, match="sizes"):
            builder.build({**SHAPE, "published": {**SHAPE["published"], key: value}})


# -- the runner: the runners there were, composed ----------------------------------------


def _ctx(logged, config=SHAPE):
    return {"root": mf.ROOT, "config": config, "traffic": {}, "args": types.SimpleNamespace(seed=5),
            "log": lambda **kw: logged.append(kw)}


@pytest.mark.parametrize("factor,routed_factor,rule_factor,correct", [
    (1.0, 1.0, 1.0, True), (1.0 + 2 * SHAPE["check"]["grad_tol"], 1.0, 1.0, False),
    (1.0, 1.0 + 2 * SHAPE["check"]["routed_grad_tol"], 1.0, False),
    (1.0, 1.0, 1.0 + 2 * SHAPE["check"]["rule_tol"], False)],
    ids=["the_references", "a_leaf_off_by_twice_the_limit", "a_routed_leaf_off_by_twice_its_limit",
         "the_rule_off_by_twice_its_limit"])
def test_the_runner_runs_the_share_runner_then_holds_gradient_and_rule(monkeypatch, factor,
                                                                      routed_factor, rule_factor,
                                                                      correct):
    """No further copy of the loop: `run` loads runners/train_reference_from_config.py
    and runs it (the balanced bias, loss, routing, dropless counts are that
    runner's), takes the bias the loop started from, and adds two readings
    through runners/train_reference_nemotron_h.py's `program_gradient` and
    runners/train_reference_checked.py's `errors_by_leaf` and `verdict`;
    `correct` is all of them."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
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
    plugins = {"train_reference_from_config": from_config, "train_reference_checked": checked,
               "train_reference_nemotron_h": shared}
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[name])
    monkeypatch.setattr(solar_open2_decoder, "grads", lambda params, t, y, config: (
        seen.update(reference=(params, t, y, config is SHAPE)) or tree))
    six = tuple(jnp.full((2,), float(i + 1)) for i in range(6))
    monkeypatch.setattr(runner, "rule_cotangent", lambda tokens, config, seed: "w")
    monkeypatch.setattr(solar_open2_decoder, "first_rule", lambda params, tokens, config, w: (
        seen.update(rule=(params, tokens, w)) or ("args", six)))
    monkeypatch.setattr(checked, "built", lambda ctx: (types.SimpleNamespace(stack_module="m"),))
    monkeypatch.setattr(runner, "program_rule", lambda module, args, w: (
        seen.update(program_rule=(module, args, w))
        or dict(zip(runner.RULE_OUTPUTS, (rule_factor * a for a in six)))))
    got = runner.run(_ctx(logged))
    assert seen["ran"] and seen["bias"] == "the bias" and seen["seed"] == 5 and seen["checked"]
    assert seen["reference"] == ("params", ["t0"], "y", True) and seen["rule"] == ("params", "t0", "w")
    assert seen["program_rule"] == ("m", "args", "w")
    assert got["checks"] == {"first_routing_is_the_reference": True,
                             "first_gradient_is_the_reference": correct or rule_factor != 1.0,
                             "first_rule_is_the_reference": rule_factor == 1.0}
    assert got["correct"] is correct
    events = {e["event"]: e for e in logged}
    assert events["correct_gradient"]["leaves"] == 2 and events["correct_rule"]["leaves"] == 6
    assert events["correct_gradient"]["routed_leaves"] == 1
    assert events["correct_gradient"]["routed_tolerance"] == SHAPE["check"]["routed_grad_tol"]
    assert events["correct_gradient"]["tolerance"] == SHAPE["check"]["grad_tol"]
    assert events["correct_rule"]["tolerance"] == SHAPE["check"]["rule_tol"]
    assert events["correct_gradient"]["first_loss"] == 10.5


def test_the_gradients_limits_hold_89_leaves_and_log_the_first_layers_routed_four():
    """`gradient_verdict`: the worst of the leaves that are no layer's
    `router`, `w_gate`, `w_up` or `w_down` against `grad_tol`; those leaves
    of the layers after the first against `routed_grad_tol`; the first
    layer's four read and reported beside them, must be numbers, and hold no
    limit (`check.routed_grad_tol_why` has why: a sound step reads up to
    0.90 on them)."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    period, check = "['layers']['period']", {"grad_tol": 0.2, "routed_grad_tol": 0.4}
    errors = {f"{period}['0']['router']": 0.9, f"{period}['0']['w_up']": 0.45,
              f"{period}['2']['w_down']": 0.3, f"{period}['3']['router']": 0.28,
              f"{period}['1']['A_log']": 0.04, f"{period}['1']['shared_up']": 0.02,
              "['embed']": 0.01}
    got = runner.gradient_verdict(checked, errors, check)
    assert got["ok"] and got["worst"] == f"{period}['1']['A_log']" and got["err"] == 0.04
    assert (got["leaves"], got["routed_leaves"], got["first_layer_leaves"]) == (3, 2, 2)
    assert got["tolerance"] == 0.2 and got["routed_tolerance"] == 0.4
    assert got["routed_worst"] == f"{period}['2']['w_down']" and got["routed_err"] == 0.3
    assert got["first_layer_worst"] == f"{period}['0']['router']" and got["first_layer_err"] == 0.9
    assert got["first_layer_tolerance"] is None
    bad = lambda **kw: not runner.gradient_verdict(checked, {**errors, **kw}, check)["ok"]  # noqa: E731
    assert bad(**{f"{period}['1']['A_log']": 0.21}) and bad(**{"['embed']": float("nan")})
    # a routed leaf after the first layer over ITS limit, or left unchanged (it reads 1)
    assert bad(**{f"{period}['2']['w_down']": 0.41}) and bad(**{f"{period}['3']['router']": 1.0})
    # a first-layer routed leaf that is no number fails the run; one that is merely large does not
    assert bad(**{f"{period}['0']['router']": float("nan")})
    assert bad(**{f"{period}['0']['w_up']": float("inf")})
    assert not bad(**{f"{period}['0']['router']": 30.0})
    # the shared expert's leaves are no routed ones: every token runs it
    assert bad(**{f"{period}['1']['shared_up']": 0.3})
    tree = jax.eval_shape(mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"]).build(
        SHAPE)[1], jax.random.key(0))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]
    routed = [p for p in paths if p.endswith(runner.ROUTED)]
    assert len(paths) == 93 and len(routed) == 16
    assert sum(runner.FIRST_LAYER in p for p in routed) == 4
    assert (SHAPE["check"]["grad_tol"], SHAPE["check"]["routed_grad_tol"]) == (0.2, 0.4)
    assert "NO limit" in SHAPE["check"]["routed_grad_tol_why"]


def test_a_choice_handed_to_the_reference_is_the_choice_and_the_scores_stay_its_own():
    """The reference's `route` under a choice handed to it (the tool's row of
    the moved pairs alone): the weights are its own scores renormalised over
    THAT choice (the selection bias is not read), and without one it chooses
    the `top_k` largest of score + bias."""
    rng = np.random.default_rng(0)
    shape = {"num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1}
    u = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
    lp = {"router": jnp.asarray(rng.standard_normal((8, 6)), jnp.float32),
          "router_bias": jnp.asarray(rng.standard_normal(6), jnp.float32)}
    scores = np.asarray(jax.nn.sigmoid(u @ lp["router"]))
    own = np.asarray(solar_open2_decoder.route(u, lp, shape))
    top = np.argsort(scores + np.asarray(lp["router_bias"]), axis=-1)[:, -2:]
    assert ((own > 0) == (np.arange(6) == top[:, :1]) | (np.arange(6) == top[:, 1:])).all()
    chosen = np.roll(own > 0, 1, axis=-1)   # another computation's choice
    given = np.asarray(solar_open2_decoder.route(u, lp, shape, jnp.asarray(chosen)))
    np.testing.assert_allclose(given, np.where(chosen, scores, 0) / (scores * chosen).sum(
        -1, keepdims=True), rtol=1e-6)


def _tiny(dtype=jnp.float32):
    from model_cases import solar_open2_shape
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    cfg = dataclasses.replace(get_model_config("solar-open2-tiny"), dtype=dtype, n_layers=4)
    return cfg, {**solar_open2_shape(cfg), "train": {"lr": 2.5e-7, "global_batch": 2}}, llama


def test_the_program_gradient_and_rule_are_the_programs_own_and_meet_the_references(monkeypatch):
    """At the tiny preset (one period) in float32 on the CPU:
    `program_gradient` runs the program's `make_train_step` with AdamW from
    the bias it is given, every leaf meets `reference.grads`; layer 1's
    rule through the program's `kda_rule` BY NAME meets the
    position-by-position rule, forward and the cotangent pulled back; the
    decay's mean over the channels and a bfloat16 state are both seen."""
    from chipbench.tools import solar_open2_wrong as wrong

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    shared = mf.load_plugin(mf.ROOT, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    cfg, shape, llama = _tiny()
    tok = jax.random.randint(jax.random.key(1), (2, 81), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    init = lambda key: llama.init_params(cfg, key)   # noqa: E731
    monkeypatch.setattr(checked, "built", lambda ctx: (cfg, init, lambda seed: batch))
    bias = 0.05 * np.random.default_rng(0).standard_normal((4, 40)).astype(np.float32)
    ctx = {"config": shape}
    with jax.default_matmul_precision("highest"):
        params, got_batch, grads, loss, counts = shared.program_gradient(
            ctx, checked, 7, bias, with_counts=True)
    assert got_batch is batch and counts.shape == (4, 40) and counts.sum() == 4 * 160 * cfg.top_k
    parts = solar_open2_decoder.loss_parts(params, batch["tokens"], batch["targets"], shape)
    assert abs(loss - float(parts["loss"])) < 1e-5 * loss
    assert shared.moved_share(counts, parts["tokens_per_expert"]) == 0.0
    # the tool's `stream_choice`: in float32 the program's sublayers, run layer by layer, choose
    # as the reference does and as the step reported
    chosen = wrong.stream_choice(cfg, params, batch["tokens"])
    assert chosen.shape == (2, 4, 80, 40) and shared.moved_share(chosen.sum((0, 2)), counts) == 0.0
    reference = solar_open2_decoder.grads(params, batch["tokens"], batch["targets"], shape, chosen)
    errors = checked.errors_by_leaf(grads, reference)
    assert len(errors) == len(jax.tree.leaves(params)) and max(errors.values()) < 5e-4, max(
        errors, key=errors.get)
    w = runner.rule_cotangent(batch["tokens"], shape, 7)
    assert w.shape == (80, cfg.kda_heads, cfg.kda_head_dim)
    args, outputs = solar_open2_decoder.first_rule(params, batch["tokens"][0], shape, w)
    assert [a.shape for a in args] == [(80, 8, 16)] * 4 + [(80, 8)]
    with jax.default_matmul_precision("highest"):
        mine = runner.program_rule(cfg.stack_module, args, w)
    names = dict(zip(runner.RULE_OUTPUTS, outputs))
    rule = checked.errors_by_leaf(mine, names)
    assert set(rule) == {f"['{n}']" for n in runner.RULE_OUTPUTS} and max(rule.values()) < 2e-5, rule
    for name in ("the decay's mean over a head's channels", "the state in bfloat16"):
        with wrong.VARIANTS[name]():
            _, other = solar_open2_decoder.first_rule(params, batch["tokens"][0], shape, w)
        seen = checked.errors_by_leaf(dict(zip(runner.RULE_OUTPUTS, other)), names)
        assert max(seen.values()) > 20 * max(rule.values()), (name, seen, rule)


def test_each_change_of_the_wrong_table_moves_the_reference_and_is_undone():
    """chipbench/tools/solar_open2_wrong.py patches the reference's small
    functions one at a time; here, at the tiny preset on the CPU, every
    change moves the reference's loss (or, a precision alone, its rule),
    and the reference is the plain one again after it."""
    from chipbench.tools import solar_open2_wrong as wrong

    assert list(wrong.VARIANTS) == [
        "the decay's mean over a head's channels", "beta not doubled", "the convolution left out",
        "the KDA output gate left out", "SiLU where sigmoid in the KDA gate",
        "the GQA output gate left out", "softmax scores in the router", "the state in bfloat16",
        "the reference in bfloat16 throughout"]
    assert wrong.PRECISION_ONLY == tuple(wrong.VARIANTS)[-2:]
    cfg, shape, llama = _tiny()
    params = llama.init_params(cfg, jax.random.key(3))
    tok = jax.random.randint(jax.random.key(2), (1, 81), 0, cfg.vocab_size)
    tokens, targets = tok[:, :-1], tok[:, 1:]
    sound = float(solar_open2_decoder.loss(params, tokens, targets, shape))
    plain = dict(vars(solar_open2_decoder))
    for name, change in wrong.VARIANTS.items():
        with change():
            moved = float(solar_open2_decoder.loss(params, tokens, targets, shape))
        assert abs(moved - sound) > 1e-5 * sound, name
        assert dict(vars(solar_open2_decoder)) == plain, name   # every patch taken off again
    assert float(solar_open2_decoder.loss(params, tokens, targets, shape)) == sound


# -- the cost functions, by hand ----------------------------------------------------


def test_required_operations_are_issue_60s_count():
    """Per token forward on this share, MFLOP (ISSUE 60's table): the three
    KDA mixers 111 (the rule itself 0.9 a mixer), the GQA layer 44, the four
    expert layers 161 at a fortieth of the pairs held, the head 201: about
    518, a step 12.7 TFLOP of required work, 65 ms at the peak."""
    f = costs_solar_open2.forward_flops_per_token(SHAPE, 8192, 1 / 40)
    kda = 4 * 4096 * 1024 + 2 * 128 * (4096 + 1024) + 4096 * 8
    assert f["kda.proj"] == 3 * 2 * kda and f["kda.scan"] == 3 * 7 * 8 * 128 * 128
    assert round((f["kda.proj"] + f["kda.scan"]) / 1e6) == 111
    assert f["gqa.proj"] == 2 * 4096 * 128 * (8 + 8 + 1 + 1 + 8)
    assert f["gqa.scores"] == 4 * 128 * 8 * 8193 / 2
    assert round((f["gqa.proj"] + f["gqa.scores"]) / 1e6) == 44
    assert f["experts.router"] == 4 * 2 * 4096 * 320 and f["experts.shared"] == 4 * 2 * 3 * 4096 * 1280
    assert f["experts.routed"] == 4 * 2 * 3 * 4096 * 1280 * 8 / 40
    assert round(sum(v for k, v in f.items() if k.startswith("experts")) / 1e6) == 161
    assert f["head"] == 2 * 4096 * 24576 and round(sum(f.values()) / 1e6) == 518
    assert costs_solar_open2.train_flops_per_token(SHAPE, 8192, 1 / 40) == 3 * sum(f.values())
    step = 8192 * 3 * sum(f.values())
    assert 12.5e12 < step < 13.0e12 and 0.063 < step / PEAKS["bf16_flops_per_s"] < 0.066
    assert 0.38 < f["head"] / sum(f.values()) < 0.40   # the cell's `why`: 39% of the operations
    assert costs_solar_open2.layers(SHAPE) == ["gqa", "kda", "kda", "kda"]
    # the same count at the tiny size against the program's own method, every expert somewhere
    cfg, shape, _ = _tiny()
    mine = sum(costs_solar_open2.forward_flops_per_token(shape, 80, 1.0).values())
    assert mine == pytest.approx(cfg.flops_per_token(80))
    assert costs_solar_open2.num_params(shape) == cfg.num_params()


def test_scan_flash_and_grouped_matmul_costs_by_hand():
    c = costs_solar_open2.scan_cost(SHAPE, 1, 8192)
    elements = 8 * 128 * 128
    assert c["layers"] == 3 and c["fwd_flops"] == 7 * TOKENS * elements
    assert c["bwd_flops"] == 14 * TOKENS * elements
    # q, k, v in bf16, the decay [128] and beta in float32 a head and position; o in bf16
    inputs = TOKENS * 8 * (3 * 128 * 2 + 128 * 4 + 4)
    assert c["fwd_bytes"] == inputs + TOKENS * 8 * 128 * 2
    assert c["bwd_bytes"] == 2 * inputs + TOKENS * 8 * 128 * 2
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    assert bound == "memory" and 1.0e-3 < least < 1.1e-3   # 1.05 ms a step for the three layers
    # at the tiny size: one KDA layer of 8 heads of 16 over 2 x 80 positions
    _, shape, _ = _tiny()
    t = costs_solar_open2.scan_cost({**shape, "num_hidden_layers": 2}, 2, 80)
    assert t["layers"] == 1 and t["fwd_flops"] == 7 * 160 * 8 * 16 * 16
    assert t["fwd_bytes"] == 160 * 8 * (3 * 16 * 2 + 16 * 4 + 4) + 160 * 8 * 16 * 2
    f = costs_solar_open2.flash_cost(SHAPE, 1, 8192)
    assert f["layers"] == 1 and f["fwd_flops"] == 8 * 4 * 128 * 8192 * 8193 / 2
    assert f["fwd_bytes"] == 2 * 8192 * 8 * 128 * 2 + 2 * 8192 * 1 * 128 * 2
    g = costs_solar_open2.grouped_matmul_cost(SHAPE, 1638.0)
    assert g["fwd_flops"] == 3 * 2 * 1638 * 4096 * 1280 and g["bwd_flops"] == 2 * g["fwd_flops"]
    each = 2 * (1638 * 4096 + 1638 * 1280 + 8 * 4096 * 1280)
    assert g["fwd_bytes"] == 3 * each and g["bwd_bytes"] == 6 * each


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 0.75, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "kda.scan": {"seconds": 0.21, "ops": {"fusion.810": 0.21}},
        "kda.proj": {"seconds": 0.05, "ops": {"fusion.1": 0.05}},
        "kda.out": {"seconds": 0.02, "ops": {"fusion.2": 0.02}},
        "kda.conv": {"seconds": 0.008, "ops": {"kernel:gdn_conv_fwd.27": 0.008}},
        "kda.gates": {"seconds": 0.001, "ops": {"fusion.4": 0.001}},
        "kda.norm": {"seconds": 0.003, "ops": {"fusion.5": 0.003}},
        "attn.attend": {"seconds": 0.0112, "ops": {"kernel:attn.attend.9": 0.0065,
                                                   "kernel:attn.attend.8": 0.0035,
                                                   "fusion.6": 0.0012}},
        "moe.experts": {"seconds": 0.03, "ops": {"fusion.7": 0.03}}}}
    router = {"pairs": [65536] * 4, "pairs_elsewhere": [63898] * 4}
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 8192}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 0.75, "window_s": 0.76},
            "trace": object(), "values": {"train_tok_s": 32800.0},
            "ops": {"expert_matmul": {"seconds": 0.0176}},
            "traced_window_steps": [{"router": router}] * 3, **extra}


def test_readers_sum_the_families_the_rule_and_the_kernels():
    run = _run()
    assert reader("kda_share_pct").read(run) == pytest.approx(100 * 0.292 / 0.75)
    assert reader("kda_scan_pct").read(run) == pytest.approx(100 * 0.21 / 0.75)
    assert reader("kda_glue_pct").read(run) == pytest.approx(100 * 0.012 / 0.75)
    c = costs_solar_open2.scan_cost(SHAPE, 1, 8192)
    least = 3 * (c["fwd_bytes"] + c["bwd_bytes"]) / PEAKS["hbm_bytes_per_s"]
    assert reader("kda_scan_roofline").read(run) == pytest.approx(100 * least / 0.21)
    assert 0 < reader("kda_scan_roofline").read(run) < 100
    f = costs_solar_open2.flash_cost(SHAPE, 1, 8192)
    least = 3 * 3.5 * f["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.nope8").read(run) == pytest.approx(100 * least / 0.0100)
    assert 0 < reader("flash_roofline.nope8").read(run) < 100
    g = costs_solar_open2.grouped_matmul_cost(SHAPE, 1638.0)
    least, _ = costs.roofline_seconds(12 * (g["fwd_flops"] + g["bwd_flops"]),
                                      12 * (g["fwd_bytes"] + g["bwd_bytes"]), PEAKS)
    assert reader("expert_matmul_roofline.solar_open2").read(run) == pytest.approx(
        100 * least / 0.0176)
    assert 0 < reader("expert_matmul_roofline.solar_open2").read(run) < 100
    per_token = costs_solar_open2.train_flops_per_token(SHAPE, 8192, 1638 / 65536)
    assert reader("train_mfu_pct.solar_open2").read(run) == pytest.approx(
        100 * 32800.0 * per_token / PEAKS["bf16_flops_per_s"])
    assert 0 < reader("train_mfu_pct.solar_open2").read(run) < 100


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    other = mf.read_json(mf.ROOT, "chipbench/configs/olmo-hybrid-7b-train.json")
    for name in NEW_METRICS[3:]:
        assert reader(name).read(_run(shape=other)) is None, name
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"moe.experts": {
        "seconds": 0.03, "ops": {"fusion.7": 0.03}}}}
    for name in NEW_METRICS[:5]:
        assert reader(name).read(bare) is None, name
    assert reader("train_mfu_pct.solar_open2").read(_run(values={})) is None
    assert reader("expert_matmul_roofline.solar_open2").read(_run(traced_window_steps=[])) is None
    assert reader("train_mfu_pct.solar_open2").read(_run(traced_window_steps=[])) is None
    assert readers_solar_open2.is_solar_open2({"shape": SHAPE})
    assert not readers_solar_open2.is_solar_open2({"shape": other})

"""The olmo-hybrid-train cell's files (PR 46): the manifest with the cell
(for however many cells there are), the configuration file against the
catalog's row, the model builder, the runner that reads its limits from
the file, the cost functions by hand-worked cases, each new reader on a
hand-built step table, and the one-thing-wrong tool's changes at a tiny
size."""

import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_olmo_hybrid, manifest as mf, readers_olmo_hybrid, readers_step
from chipbench.reference import olmo_hybrid_decoder

M = mf.load_manifest()
CELL, CONFIG = "olmo-hybrid-train", "olmo-hybrid-7b-train"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "vocab_size"]
NEW_METRICS = ("gdn_share_pct", "gdn_scan_pct", "gdn_glue_pct", "gdn_scan_roofline",
               "flash_roofline.full30", "train_mfu_pct.olmo_hybrid")
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
# what every one-chip dense cell reports, and the families of blocks this cell has
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "head_share_pct", "optim_share_pct",
          "wgrad_optim_fused_pct", "step_unscoped_pct", "block_share_pct", "fallback_sites.train",
          "attn_share_pct", "ffn_share_pct") + SETUP
PEAKS = costs.load_peaks("TPU v5 lite")


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["zipf_s"]) == (4096, 1.1)
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assert {"i_mixer", "ii_no_rotary", "iii_norms", "iv_dtypes_and_weights",
            "v_packed_documents"} <= set(SHAPE["assumed"])
    assert "TBD" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    # their cost functions read another model's keys, or count no recurrence: wrong here
    assert not reported & {"flash_roofline", "train_mfu_pct", "moe_share_pct", "expert_imbalance",
                           "flash_roofline.full48", "train_mfu_pct.laguna", "coll_exposed_pct"}
    assert CELLS[7] == CELL and M["configs"][7]["name"] == CONFIG and CELLS == TRAINING_CELLS
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "1 x 4096" in why and "96 x 192" in why
    assert why == mf.by_name(M["workloads"], CELL, "workload")["why"]
    # one four-chip cell still: what this row adds exists on one chip
    assert [w["chips"] for w in M["workloads"]].count(4) == 1


def test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were():
    """What tests/chipbench/test_chipbench_keye.py's test of this name holds
    for seven cells and a tail that ends with PR 42's six (skipped from
    tests/conftest.py: this PR appends a cell and six metrics), for any
    number of cells and any tail: every training cell reports `train_tok_s`,
    `setup_s`, every start-up metric and the metrics every training cell
    has; a metric that was one cell's alone, or some cells', KEEPS those
    cells as the head of its list, in the manifest's order; bounds and the
    window are untouched; one cell of four chips; each PR's block of
    per-layer metrics stands in its order, this PR's six last."""
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
    alone = (("expert_matmul_roofline", ["olmoe-train"]), ("train_mfu_pct.moe", ["olmoe-train"]),
             ("expert_matmul_roofline.held", ["zaya1-train"]),
             ("expert_matmul_roofline.held4", ["glm47f-train"]),
             ("flash_roofline.mla", ["glm47f-train"]), ("mla_share_pct", ["glm47f-train"]),
             ("cca_share_pct", ["zaya1-train"]), ("swa_share_pct", ["laguna-train"]),
             ("flash_roofline.window", ["laguna-train"]), ("train_mfu_pct.laguna", ["laguna-train"]),
             ("dsa_share_pct", ["keye-train-8k"]), ("train_mfu_pct.keye", ["keye-train-8k"]),
             ("coll_exposed_pct", ["m7b-train-4chip"]),
             ("train_mfu_pct", ["m7b-train", "m7b-train-4chip"]),
             ("flash_roofline", ["m7b-train", "m7b-train-4chip", "olmoe-train", "zaya1-train"]))
    for name, cells in alone:
        assert mf.by_name(M["per_layer"], name, "metric")["workloads"] == cells, name
    # a list a later cell may join keeps its first cells, in the manifest's order
    attn = mf.by_name(M["per_layer"], "attn_share_pct", "metric")["workloads"]
    assert attn[:4] == ["m7b-train", "m7b-train-4chip", "olmoe-train", "laguna-train"]
    assert attn == [c for c in CELLS if c in attn]
    names = [m["name"] for m in M["per_layer"]]
    keyes = ["dsa_share_pct", "dsa_index_pct", "dsa_select_pct", "flash_roofline.selected",
             "expert_matmul_roofline.held8", "train_mfu_pct.keye"]
    at = names.index(keyes[0])
    assert names[at:at + 6] == keyes and names[at + 6:at + 12] == list(NEW_METRICS)
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
    # a program without the scopes (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert reader(name).read({"shape": SHAPE, "trace": None}) is None


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_its_cells_in_their_order(name):
    """An accepted metric that this cell joins is what it was, with the
    cell appended to its list."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert CELL in m["workloads"]
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    before = m["workloads"][:m["workloads"].index(CELL)]
    assert before and set(before) <= set(CELLS[:7])
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name in ("attn_share_pct", "ffn_share_pct"):   # the cells whose blocks have such scopes
        assert before[:2] == ["m7b-train", "m7b-train-4chip"] and "laguna-train" in before


def test_step_scopes_gain_three_families_and_keep_the_rest():
    vocabulary = readers_step.vocabulary()
    assert vocabulary["families"]["gdn_proj"] == ["gdn.proj", "gdn.out"]
    assert vocabulary["families"]["gdn_scan"] == ["gdn.scan"]
    assert vocabulary["families"]["gdn_glue"] == ["gdn.conv", "gdn.gates", "gdn.norm"]
    assert vocabulary["families"]["attn"] == ["attn.qkv", "attn.rope", "attn.attend", "attn.out"]
    assert vocabulary["families"]["swa"] == ["swa.qkv", "swa.rope", "swa.attend", "swa.out"]
    assert set(vocabulary["engage_counters"]) == {"tp_overlap", "grouped_matmul", "flash_bwd"}
    # the loops over the chunks stand under the rule's scope with everything in their bodies
    inside = ("jit(step)/transpose(jvp(block.stack))/while/body/closed_call/checkpoint/"
              "rematted_computation/gdn.scan/while/body/dot_general")
    assert readers_step.scope_of_path(inside) == "gdn.scan"
    assert readers_step.family("gdn.scan") == "gdn_scan" and readers_step.family("gdn.out") == "gdn_proj"
    assert readers_step.scope_of_path("jit(step)/block.stack/while/body/squeeze") == "block.stack"


# -- the configuration file against the catalog --------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        return None
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next((r for r in rows if r["name"] == "Olmo-Hybrid-7B"), None)


def test_every_published_key_is_the_catalogs_but_the_two_cuts():
    row = catalog_row()
    if row is None:
        pytest.skip("no catalog row of Olmo-Hybrid-7B in this installation")
    assert SHAPE["source"] == row["source_url"]
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    differ = {k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v}
    assert differ == set(REDUCED)
    assert (SHAPE["num_hidden_layers"], SHAPE["vocab_size"]) == (4, 12544)


def test_every_width_the_issue_names_is_as_published():
    assert (SHAPE["hidden_size"], SHAPE["num_attention_heads"], SHAPE["num_key_value_heads"],
            SHAPE["intermediate_size"]) == (3840, 30, 30, 11008)
    assert (SHAPE["linear_num_key_heads"], SHAPE["linear_num_value_heads"],
            SHAPE["linear_key_head_dim"], SHAPE["linear_value_head_dim"],
            SHAPE["linear_conv_kernel_dim"], SHAPE["linear_allow_neg_eigval"]) == (
        30, 30, 96, 192, 4, True)
    assert SHAPE["layer_types"][:4] == ["linear_attention"] * 3 + ["full_attention"]
    assert SHAPE["layer_types"] == SHAPE["layer_types"][:4] * 8          # whole, as published
    assert SHAPE["rope_parameters"] == {"rope_theta": None} and SHAPE["rms_norm_eps"] == 1e-6
    assert SHAPE["model_type"] == "olmo_hybrid" and not SHAPE["tie_word_embeddings"]


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    period = SHAPE["layer_types"][:SHAPE["num_hidden_layers"]]
    assert period == ["linear_attention"] * 3 + ["full_attention"]        # one whole period
    assert SHAPE["vocab_size"] * 8 == SHAPE["published"]["vocab_size"]
    assert SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 1
    assert SHAPE["deployment"]["pipeline_stages"] * SHAPE["num_hidden_layers"] == 32
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    assert SHAPE["check"]["scopes"] == ["gdn.proj", "gdn.conv", "gdn.gates", "gdn.scan",
                                        "gdn.norm", "gdn.out"]
    assert 0 < SHAPE["check"]["loss_tol"] <= 5e-4 and set(SHAPE["check"]) == {
        "scopes", "loss_tol", "loss_tol_why", "grad_tol", "grad_tol_why", "rule_tol", "rule_tol_why"}
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["lr"] == 3e-4
    assert SHAPE["train"]["attention_impl"] == "flash"


# -- the model builder and the runner ------------------------------------------


def test_builder_builds_the_stage_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.head_dim) == (
        4, 3840, 11008, 30, 128)
    assert (cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.conv_kernel) == (
        30, 96, 192, 4)
    assert cfg.vocab_size == 12544 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.allow_neg_eigval and cfg.remat and cfg.remat_policy == "dots"
    shapes = jax.eval_shape(init, jax.random.key(0))
    period = shapes["layers"]["period"]
    assert sorted(period) == ["0", "1", "2", "3"]
    assert period["0"]["wq"].shape == (1, 3840, 2880) and period["0"]["wv"].shape == (1, 3840, 5760)
    assert period["1"]["conv_v"].shape == (1, 4, 5760) and period["2"]["A_log"].shape == (1, 30)
    assert period["2"]["o_norm"].shape == (1, 192) and period["0"]["wo"].shape == (1, 5760, 3840)
    assert period["3"]["wq"].shape == (1, 3840, 3840) and period["3"]["q_norm"].shape == (1, 3840)
    assert "A_log" not in period["3"] and "q_norm" not in period["0"]
    assert period["3"]["w_gate"].shape == (1, 3840, 11008)
    assert shapes["embed"].shape == (12544, 3840) and shapes["lm_head"].shape == (3840, 12544)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # 928.9M parameters: ISSUE 46's 928.8M and the norms; the cost file counts the same
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == costs_olmo_hybrid.num_params(SHAPE) == 928_862_196


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("intermediate_size", 8192), ("num_attention_heads", 15),
    ("linear_num_value_heads", 15), ("linear_key_head_dim", 64), ("linear_value_head_dim", 128),
    ("linear_conv_kernel_dim", 2), ("linear_allow_neg_eigval", False), ("rms_norm_eps", 1e-5)])
def test_builder_refuses_a_changed_width(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match=key[:8]):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_changed_lists_rotary_and_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="vocab_size"):
        builder.build({**SHAPE, "published": {**SHAPE["published"], "vocab_size": 50176}})
    with pytest.raises(RuntimeError, match="layer_types"):
        builder.build({**SHAPE, "layer_types": ["full_attention"] * 32})
    with pytest.raises(RuntimeError, match="sizes"):
        builder.build({**SHAPE, "rope_parameters": {"rope_theta": 500000}})


def _mocked_base(monkeypatch, warm_phase="warm"):
    """runners/train_reference.py with its loop and `run` replaced by what
    they report and return, in their order; -> (runner, base, seen, order)."""
    import gc

    from ray_tpu.train import session

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    base = mf.load_plugin(mf.ROOT, "runners", "train_reference")
    seen, order = {}, []
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: base)
    monkeypatch.setattr(gc, "freeze", lambda: order.append("freeze"))
    monkeypatch.setattr(session, "report", lambda metrics: order.append(
        (metrics["phase"], metrics["step"])))

    def loop(c):   # what the base loop reports, in its order
        seen.update(looped=c)
        for i in range(1 + base.WARM_STEPS):
            session.report({"phase": warm_phase, "step": i})
        session.report({"phase": "window", "step": i + 1})

    def run(ctx):
        seen.update(tol=base.LOSS_TOL, scopes=base.SCOPES)
        base.train_loop({"the": "config"})   # what the trainer's worker calls
        return {"correct": True, "checks": {"first_loss_is_the_reference": True}, "losses": [9.4]}

    monkeypatch.setattr(base, "train_loop", loop)
    monkeypatch.setattr(base, "run", run)
    return runner, base, seen, order


def _ctx(logged):
    import types

    return {"root": mf.ROOT, "config": SHAPE, "traffic": {}, "args": types.SimpleNamespace(seed=5),
            "log": lambda **kw: logged.append(kw)}


@pytest.mark.parametrize("factor, correct", [(1.0, True), (1.0 + 2 * SHAPE["check"]["grad_tol"], False)],
                         ids=["the_references_gradient", "a_leaf_off_by_twice_the_limit"])
def test_the_runner_sets_the_base_runners_limits_from_the_file_runs_it_and_adds_the_gradient(
        monkeypatch, factor, correct):
    """No fourth copy of the loop: the wrapper loads runners/train_reference.py,
    sets its two constants from `check`, and calls its `run`; around that
    runner's own loop it freezes the collector's objects once, behind the
    LAST warm step's report, and puts `session.report` back. Then it holds
    the program's first gradient to the reference's, and `correct` is that
    runner's checks AND this one."""
    from ray_tpu.train import session

    runner, base, seen, order = _mocked_base(monkeypatch)
    reporting, logged = session.report, []
    tree = {"layers": {"period": {"0": {"wq": jnp.arange(1.0, 7.0)}}}, "embed": jnp.ones((3, 2))}
    off = {"layers": {"period": {"0": {"wq": factor * tree["layers"]["period"]["0"]["wq"]}}},
           "embed": tree["embed"]}
    monkeypatch.setattr(runner, "program_gradient", lambda ctx, seed: (
        seen.update(seed=seed) or "params", {"tokens": ["t0"], "targets": "y"}, off, 9.4))
    monkeypatch.setattr(olmo_hybrid_decoder, "grads", lambda params, t, y, config: (
        seen.update(reference=(params, t, y, config is SHAPE)) or tree))
    six = tuple(jnp.full((2,), float(i + 1)) for i in range(6))
    monkeypatch.setattr(runner, "rule_cotangent", lambda tokens, config, seed: "w")
    monkeypatch.setattr(olmo_hybrid_decoder, "first_rule", lambda params, tokens, config, w: (
        seen.update(rule=(params, tokens, w)) or ("args", six)))
    monkeypatch.setattr(runner, "program_rule", lambda ctx, args, w: (
        seen.update(program_rule=(args, w)) or dict(zip(runner.RULE_OUTPUTS, six))))
    out = runner.run(_ctx(logged))
    assert out["correct"] is correct and out["checks"] == {
        "first_loss_is_the_reference": True, "first_gradient_is_the_reference": correct,
        "first_rule_is_the_reference": True}
    assert seen == {"tol": SHAPE["check"]["loss_tol"], "scopes": tuple(SHAPE["check"]["scopes"]),
                    "looped": {"the": "config"}, "seed": 5, "reference": ("params", ["t0"], "y", True),
                    "rule": ("params", "t0", "w"), "program_rule": ("args", "w")}
    assert order == [("warm", 0), ("warm", 1), ("warm", 2), ("warm", 3), "freeze", ("window", 4)]
    assert session.report is reporting
    assert base.train_loop is runner.train_loop and runner.train_loop.__module__ == runner.__name__
    assert [e["event"] for e in logged] == ["steady", "correct_gradient", "correct_rule"]
    assert logged[1]["ok"] is correct and (correct or logged[1]["worst"].endswith("['wq']"))
    assert set(logged[1]["errors"]) == {"['embed']", "['layers']['period']['0']['wq']"}
    assert logged[2]["ok"] and set(logged[2]["errors"]) == {f"['{n}']" for n in runner.RULE_OUTPUTS}
    assert logged[2]["tolerance"] == SHAPE["check"]["rule_tol"]
    src = open(os.path.join(mf.ROOT, "chipbench", "runners", SHAPE["runner"] + ".py")).read()
    assert "JaxTrainer" not in src and "while clock" not in src and "session.report(" not in src


def test_the_runner_fails_a_run_whose_collector_was_never_rested(monkeypatch):
    """The freeze hangs on the base loop's last warm report; a loop that
    reports under other names would be timed with the collector running:
    that run raises, it does not return a number."""
    runner, _, _, order = _mocked_base(monkeypatch, warm_phase="warmup")
    with pytest.raises(RuntimeError, match="never rested before the window"):
        runner.run(_ctx([]))
    assert "freeze" not in order


def test_errors_by_leaf_and_verdict_by_hand():
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    want = {"a": jnp.array([3.0, 4.0]), "b": {"c": jnp.zeros(2), "d": jnp.array([1.0, 0.0])}}
    got = {"a": jnp.array([3.0, 4.5]), "b": {"c": jnp.array([0.0, 0.25]), "d": jnp.array([1.0, jnp.nan])}}
    errors = runner.errors_by_leaf(got, want)
    assert set(errors) == {"['a']", "['b']['c']", "['b']['d']"}
    assert errors["['a']"] == pytest.approx(0.1) and errors["['b']['c']"] == 0.25   # |got| where want is 0
    assert errors["['b']['d']"] != errors["['b']['d']"]
    finite = {k: e for k, e in errors.items() if e == e}
    assert runner.verdict(finite, 0.3) == {"ok": True, "worst": "['b']['c']", "err": 0.25,
                                           "tolerance": 0.3, "leaves": 2}
    assert not runner.verdict(finite, 0.2)["ok"]
    nan = runner.verdict(errors, 10.0)     # not a number is not within any limit
    assert not nan["ok"] and nan["worst"] == "['b']['d']" and nan["leaves"] == 3
    check = SHAPE["check"]
    assert 0 < check["rule_tol"] < check["grad_tol"] < 1 and check["grad_tol_why"] and check["rule_tol_why"]


def _tiny():
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    cfg = dataclasses.replace(get_model_config("olmo-hybrid-tiny"), n_layers=4, dtype=jnp.float32)
    shape = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
             "num_hidden_layers": 4, "layer_types": list(cfg.layer_types[:4]),
             "linear_num_value_heads": cfg.linear_heads, "linear_key_head_dim": cfg.linear_key_dim,
             "linear_value_head_dim": cfg.linear_value_dim, "linear_allow_neg_eigval": True,
             "rms_norm_eps": cfg.rms_eps, "max_position_embeddings": cfg.max_seq,
             "tie_word_embeddings": False, "vocab_size": cfg.vocab_size}
    return cfg, shape, llama


def test_the_program_gradient_is_the_train_steps_own_and_meets_the_references(monkeypatch):
    """At the tiny preset in float32 on the CPU: `program_gradient` runs the
    program's `make_train_step` with AdamW as the loop does (the gradient
    read back through the first moment is what `jax.grad` of the step's
    loss function gives, the parameters handed back are fresh ones), and
    every leaf meets `reference.grads`, which is `jax.grad` of the reference's loss
    with nothing rematerialised (150 positions: three segments of 50)."""
    import types

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    cfg, shape, llama = _tiny()
    tok = jax.random.randint(jax.random.key(1), (2, 151), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    init = lambda key: llama.init_params(cfg, key)   # noqa: E731
    plugins = {"model_builders": types.SimpleNamespace(build=lambda config, **kw: (cfg, init, None)),
               "generators": types.SimpleNamespace(
                   batch_fn=lambda traffic, vocab, b, seed: lambda i: batch)}
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[kind])
    ctx = {"root": mf.ROOT, "traffic": {"generator": "g"},
           "config": {"model_builder": "b",
                      "train": {"attention_impl": "xla", "global_batch": 2, "lr": 3e-4}}}
    import optax

    from ray_tpu import obs
    from ray_tpu.train.step import TrainState, make_train_step

    with jax.default_matmul_precision("highest"):
        # the timed step as the loop builds it, run once: what a traced run's readers ask about
        opt = optax.adamw(3e-4)
        timed = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
        timed(TrainState.create(init(jax.random.key(7)), opt), batch)
        described = obs.op_names()
        params, got_batch, grads, loss = runner.program_gradient(ctx, 7)
        want = jax.jit(jax.grad(lambda p: llama.loss_and_weight_fn(p, batch, cfg)[0]))(params)
        # the step run for its gradient IS that program: the record still describes the timed step
        assert described and obs.op_names() == described
    assert got_batch is batch
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, init(jax.random.key(7)))
    assert all(jax.tree.leaves(same))
    assert max(runner.errors_by_leaf(grads, want).values()) < 1e-5
    reference = olmo_hybrid_decoder.grads(params, batch["tokens"], batch["targets"], shape)
    flat = jax.jit(jax.grad(lambda p: sum(olmo_hybrid_decoder.sequence(
        p, batch["tokens"][b], batch["targets"][b], shape) for b in range(2)) / 300))(params)
    assert max(runner.errors_by_leaf(reference, flat).values()) < 2e-4
    assert abs(loss - float(olmo_hybrid_decoder.loss(params, batch["tokens"], batch["targets"],
                                                     shape))) < 1e-4 * loss
    errors = runner.errors_by_leaf(grads, reference)
    assert len(errors) == 68 and max(errors.values()) < 5e-4, max(errors, key=errors.get)
    # layer 0's rule alone, on the reference's own arrays: the function the sublayer calls
    # against the position-by-position rule, forward and the cotangent pulled back
    w = runner.rule_cotangent(batch["tokens"], shape, 7)
    assert w.shape == (150, cfg.linear_heads, cfg.linear_value_dim)
    args, outputs = olmo_hybrid_decoder.first_rule(params, batch["tokens"][0], shape, w)
    assert [a.shape for a in args] == [(150, 3, 12), (150, 3, 12), (150, 3, 24), (150, 3), (150, 3)]
    with jax.default_matmul_precision("highest"):
        mine = runner.program_rule(ctx, args, w)
    rule = runner.errors_by_leaf(mine, dict(zip(runner.RULE_OUTPUTS, outputs)))
    assert set(rule) == {f"['{n}']" for n in runner.RULE_OUTPUTS} and max(rule.values()) < 1e-4, rule
    with mock.patch.object(olmo_hybrid_decoder, "STATE", jnp.bfloat16):   # a bfloat16 state is seen
        _, rounded = olmo_hybrid_decoder.first_rule(params, batch["tokens"][0], shape, w)
    seen = runner.errors_by_leaf(dict(zip(runner.RULE_OUTPUTS, rounded)), dict(zip(runner.RULE_OUTPUTS, outputs)))
    assert min(seen.values()) > 20 * max(rule.values()), (seen, rule)


# -- the cost functions, by hand ----------------------------------------------------


def test_required_operations_are_issue_46s_count():
    """ISSUE 46, step 5, a token forward: a linear mixer's projections 177.4
    MFLOP + the recurrence 3.3 in its position-by-position form; the full
    mixer 118.0 + its scores 31.5; a SwiGLU 253.6; the head 96.3: 1,802
    (1,810 with the recurrence at the chunked form's 6), the three linear
    mixers 30% of it."""
    f = costs_olmo_hybrid.forward_flops_per_token(SHAPE, 4096)
    d = 3840
    assert f["linear.proj"] == 3 * 2 * d * 30 * (2 * 96 + 3 * 192 + 2)
    assert f["linear.proj"] / 3 == pytest.approx(177.4e6, rel=1e-3)
    assert f["linear.scan"] == 3 * 6 * 30 * 96 * 192 and f["linear.scan"] / 3 == pytest.approx(3.3e6, rel=6e-3)
    assert f["full.proj"] == 2 * 4 * d * d and f["full.proj"] == pytest.approx(118.0e6, rel=1e-3)
    assert f["full.scores"] == pytest.approx(4 * 128 * 30 * 4097 / 2) == pytest.approx(31.5e6, rel=2e-3)
    assert f["ffn"] == 4 * 2 * 3 * d * 11008 and f["ffn"] / 4 == pytest.approx(253.6e6, rel=1e-3)
    assert f["head"] == 2 * d * 12544 and f["head"] == pytest.approx(96.3e6, rel=1e-3)
    total = sum(f.values())
    assert total == pytest.approx(1802.4e6, rel=1e-4)
    assert (f["linear.proj"] + f["linear.scan"]) / total == pytest.approx(0.301, abs=2e-3)
    assert costs_olmo_hybrid.train_flops_per_token(SHAPE, 4096) == 3 * total
    # the program's own count is the same function of the same sizes
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    assert builder.build(SHAPE)[0].flops_per_token(4096) == pytest.approx(total)
    assert costs_olmo_hybrid.layers(SHAPE) == ["linear_attention"] * 3 + ["full_attention"]


def test_scan_and_flash_costs_by_hand():
    c = costs_olmo_hybrid.scan_cost(SHAPE, 1, 4096)
    assert c["layers"] == 3 and costs_olmo_hybrid.state_elements(SHAPE) == 30 * 96 * 192
    assert c["fwd_flops"] == 3 * 4096 * 6 * 30 * 96 * 192 and c["bwd_flops"] == 2 * c["fwd_flops"]
    inputs = 3 * 4096 * 30 * ((96 + 96 + 192) * 2 + 2 * 4)
    o = 3 * 4096 * 30 * 192 * 2
    assert c["fwd_bytes"] == inputs + o and c["bwd_bytes"] == 2 * inputs + o
    # the rule itself is bound by its bytes on a v5e: 4.7 operations a byte
    assert costs.roofline_seconds(c["fwd_flops"], c["fwd_bytes"], PEAKS)[1] == "memory"
    f = costs_olmo_hybrid.flash_cost(SHAPE, 1, 4096)
    assert f["layers"] == 1 and f["fwd_flops"] == 30 * 4 * 128 * (4096 * 4097 / 2)
    q = 4096 * 30 * 128 * 2
    assert f["fwd_bytes"] == 4 * q and f["bwd_bytes"] == 8 * q and f["bwd_flops"] == 2.5 * f["fwd_flops"]
    assert costs.roofline_seconds(f["fwd_flops"], f["fwd_bytes"], PEAKS)[1] == "compute"


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 0.9, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "gdn.scan": {"seconds": 0.27, "ops": {"custom-call.19": 0.02, "fusion.2627": 0.25}},
        "gdn.proj": {"seconds": 0.12, "ops": {"fusion.1": 0.12}},
        "gdn.out": {"seconds": 0.045, "ops": {"fusion.2": 0.045}},
        "gdn.conv": {"seconds": 0.09, "ops": {"fusion.3": 0.09}},
        "gdn.gates": {"seconds": 0.001, "ops": {"fusion.4": 0.001}},
        "gdn.norm": {"seconds": 0.003, "ops": {"fusion.5": 0.003}},
        "attn.attend": {"seconds": 0.0108, "ops": {"kernel:attn.attend.9": 0.0068,
                                                   "kernel:attn.attend.10": 0.0029,
                                                   "fusion.6": 0.0011}},
        "dense.ffn": {"seconds": 0.24, "ops": {"fusion.7": 0.24}}}}
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 4096}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 0.9, "window_s": 0.91},
            "trace": object(), "values": {"train_tok_s": 13000.0}, **extra}


def test_readers_sum_the_families_the_scan_and_the_full_layers_kernels():
    run = _run()
    assert reader("gdn_share_pct").read(run) == pytest.approx(100 * 0.529 / 0.9)
    assert reader("gdn_scan_pct").read(run) == pytest.approx(100 * 0.27 / 0.9)
    assert reader("gdn_glue_pct").read(run) == pytest.approx(100 * 0.094 / 0.9)
    c = costs_olmo_hybrid.scan_cost(SHAPE, 1, 4096)
    least = 3 * (c["fwd_bytes"] + c["bwd_bytes"]) / PEAKS["hbm_bytes_per_s"]
    assert reader("gdn_scan_roofline").read(run) == pytest.approx(100 * least / 0.27)
    assert 0 < reader("gdn_scan_roofline").read(run) < 100
    f = costs_olmo_hybrid.flash_cost(SHAPE, 1, 4096)
    least = 3 * 3.5 * f["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.full30").read(run) == pytest.approx(100 * least / 0.0097)
    per_token = costs_olmo_hybrid.train_flops_per_token(SHAPE, 4096)
    assert reader("train_mfu_pct.olmo_hybrid").read(run) == pytest.approx(
        100 * 13000.0 * per_token / PEAKS["bf16_flops_per_s"])


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    other = mf.read_json(mf.ROOT, "chipbench/configs/laguna-s-2.1-train.json")
    for name in NEW_METRICS[3:]:
        assert reader(name).read(_run(shape=other)) is None, name
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"dense.ffn": {
        "seconds": 0.24, "ops": {"fusion.7": 0.24}}}}
    for name in NEW_METRICS[:5]:
        assert reader(name).read(bare) is None, name
    assert reader("train_mfu_pct.olmo_hybrid").read(_run(values={})) is None


# -- the one-thing-wrong tool's changes, at a tiny size ------------------------------


def test_each_change_of_the_wrong_table_moves_the_references_loss():
    """chipbench/tools/olmo_hybrid_wrong.py patches the reference's small
    functions one at a time; here, at the tiny preset on the CPU, every
    patch runs and gives another loss than the sound reference (or none:
    without the L2 norms the rule diverges)."""
    from chipbench.tools import olmo_hybrid_wrong
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    cfg = dataclasses.replace(get_model_config("olmo-hybrid-tiny"), n_layers=4)
    shape = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
             "num_hidden_layers": 4, "layer_types": list(cfg.layer_types[:4]),
             "linear_num_value_heads": cfg.linear_heads, "linear_key_head_dim": cfg.linear_key_dim,
             "linear_value_head_dim": cfg.linear_value_dim, "linear_allow_neg_eigval": True,
             "rms_norm_eps": cfg.rms_eps, "max_position_embeddings": cfg.max_seq,
             "tie_word_embeddings": False, "vocab_size": cfg.vocab_size}
    params = llama.init_params(cfg, jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (1, 81), 0, cfg.vocab_size)
    sound = float(olmo_hybrid_decoder.loss(params, tok[:, :-1], tok[:, 1:], shape))
    changes = olmo_hybrid_wrong.VARIANTS
    assert len(changes) == 9 and set(olmo_hybrid_wrong.PRECISION_ONLY) < set(changes)
    for name, wrong in changes.items():
        with wrong():
            loss = float(olmo_hybrid_decoder.loss(params, tok[:, :-1], tok[:, 1:], shape))
        assert not abs(loss - sound) <= 1e-6 * sound, name
    # and the patches are gone afterwards
    assert float(olmo_hybrid_decoder.loss(params, tok[:, :-1], tok[:, 1:], shape)) == sound
    assert olmo_hybrid_decoder.F32 == jnp.float32 and olmo_hybrid_decoder.STATE == jnp.float32


def test_the_wrong_table_puts_each_row_through_the_runners_own_comparison(monkeypatch, tmp_path, capsys):
    """The tool's `main` at the tiny preset (float32, CPU): the program's
    row and a wrong reference's row go through the runner's
    `gradient_errors` / `gradient_verdict` and the file's two limits; the
    program comes out correct, the reference without its decay does not."""
    import types

    import chipbench.run
    from chipbench.tools import olmo_hybrid_wrong

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    cfg, shape, llama = _tiny()
    tok = jax.random.randint(jax.random.key(1), (1, 101), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}

    def program_gradient(ctx, seed):
        params = llama.init_params(cfg, jax.random.key(seed))
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(lambda p: llama.loss_and_weight_fn(p, batch, cfg)[0])(params)
        return params, batch, grads, float(loss)

    config = {**shape, "runner": SHAPE["runner"],
              "check": {"loss_tol": 1e-4, "grad_tol": 1e-3, "rule_tol": 1e-4}}
    monkeypatch.setattr(mf, "ROOT", str(tmp_path))
    monkeypatch.setattr(mf, "load_manifest", lambda root: {})
    monkeypatch.setattr(mf, "load_cell", lambda root, m, name: {"chips": 1, "config": config, "traffic": {}})
    def program_rule(ctx, args, w):
        monkeypatch.setattr(runner, "built", lambda ctx: (cfg, None, None))
        with jax.default_matmul_precision("highest"):
            return runner.program_rule(ctx, args, w)

    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: types.SimpleNamespace(
        errors_by_leaf=runner.errors_by_leaf, verdict=runner.verdict, RULE_OUTPUTS=runner.RULE_OUTPUTS,
        rule_cotangent=runner.rule_cotangent, program_gradient=program_gradient,
        program_rule=program_rule))
    monkeypatch.setattr(chipbench.run, "open_chip", lambda chips, name: (None, None, "cpu"))
    monkeypatch.setattr("sys.argv", ["olmo_hybrid_wrong", "--seeds", "3", "--only", "the decay left out"])
    assert olmo_hybrid_wrong.main() == 0
    out = json.load(open(tmp_path / "chiprun_out" / "chipbench" / "wrong-olmo-hybrid-train.json"))
    program, wrong = out["rows"]
    assert program["what"].startswith("the program") and program["correct"] and program["grad_err"] < 1e-3
    assert program["rule_err"] < 1e-4 < wrong["rule_err"]
    assert wrong["what"] == "the decay left out" and not wrong["correct"] and wrong["grad_err"] > 1e-2
    assert len(program["errors"]["gradient"]) == 68 and len(program["errors"]["rule"]) == 6
    assert wrong["whole_tree_err"] > program["whole_tree_err"]
    assert out["summary"]["the decay left out"]["correct_on"] == 0
    assert '"grad_tol": 0.001' in capsys.readouterr().out


"""The mellum2-train-16k cell's files (PR 53): the manifest with the cell
(for however many cells there are), the configuration file against the
catalog's row, the model builder, the runner that composes the runners
there were, the cost functions by hand-worked cases, each new reader on a
hand-built step table, and the one-thing-wrong tool at a tiny size. It
also carries, for any number of cells, what seven tests of this directory
held while the benchmark had nine cells, two sliding-window-free `swa`
lists and traffic of at most 8,192 tokens (each skipped from
tests/conftest.py, which names the test that carries it here)."""

import copy
import dataclasses
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_mellum2, manifest as mf, readers_mellum2, readers_step
from chipbench.reference import mellum2_decoder

M = mf.load_manifest()
CELL, CONFIG, TRAFFIC = "mellum2-train-16k", "mellum2-12b-a2.5b-train", "zipf_tokens_16k"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ("train_mfu_pct.mellum2", "flash_roofline.window1k", "flash_roofline.full16k",
               "expert_matmul_roofline.mellum2", "qk_norm_pct")
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
TIMELINE = ("dispatch_ms.train", "step_stalls.train", "stall_loss_pct.train", "gc_pause_ms.train",
            "report_max_ms.train", "host_other_cpu_pct.train", "step_gap_ms.train",
            "step_gap_program_pct.train")
# what every share cell reports, both attention families of the typed stack, and this cell
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "moe_compact_pct", "expert_imbalance", "experts_elsewhere_pct", "head_share_pct",
          "optim_share_pct", "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct",
          "fallback_sites.train", "attn_share_pct", "swa_share_pct") + SETUP + TIMELINE
PEAKS = costs.load_peaks("TPU v5 lite")
HERE = os.path.dirname(__file__)


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


def earlier(file, manifest=None):
    """Another test file of this directory as a module of its own; with
    `manifest`, reading that one (and the lists of cells it makes of it)."""
    spec = importlib.util.spec_from_file_location("carried_" + file[:-3], os.path.join(HERE, file))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if manifest is not None:
        module.M = manifest
        for name in ("CELLS", "TRAINING_CELLS", "TRAINING"):
            if hasattr(module, name):
                setattr(module, name, [w["name"] for w in manifest["workloads"]])
    return module


def before_this_pr(manifest=M):
    """The manifest as the parent had it: this PR's cell, configuration and
    five metrics taken off, and the cell off every list it joined."""
    was = copy.deepcopy(manifest)
    was["configs"] = [c for c in was["configs"] if c["name"] != CONFIG]
    was["workloads"] = [w for w in was["workloads"] if w["name"] != CELL]
    was["per_layer"] = [m for m in was["per_layer"] if m["name"] not in NEW_METRICS]
    for m in was["per_layer"] + was["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    return was


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["max_context"],
            cell["traffic"]["zipf_s"]) == (16384, 16384, 1.1)
    assert cell["cell"]["traffic"] == TRAFFIC
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assumed = SHAPE["assumed"]
    assert {"qk_norm", "mtp_head", "intermediate_size", "rotary_pairing", "yarn", "window",
            "router", "router_bias_update", "param_dtype", "weights"} <= set(assumed)
    # each reading taken and the one not taken; what is said and not built
    assert "PRESENT" in assumed["qk_norm"] and "NOT taken" in assumed["qk_norm"]
    assert "NOT built" in assumed["mtp_head"] and "BALANCED" in assumed["router_bias_update"]
    assert "PROVISIONAL" not in json.dumps(SHAPE) and "TO FILL" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported >= set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    # what the cell may never report: another model's cost functions, a gate, a dense layer
    assert not reported & {"attn_gate_pct", "ffn_share_pct", "flash_roofline",
                           "flash_roofline.window", "flash_roofline.full48",
                           "expert_matmul_roofline", "expert_matmul_roofline.held10",
                           "train_mfu_pct.laguna", "train_mfu_pct.moe", "train_mfu_pct"}
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    assert CELLS[9] == CELL and M["configs"][9]["name"] == CONFIG and CELLS == TRAINING_CELLS
    assert len(CELLS) >= 10 and [w["chips"] for w in M["workloads"]].count(4) == 1
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "2,048 rows" in why and "16,384" in why and "1 x 16384" in why
    assert "window-1024" in why and "8 held" in why and "attention 8x" in why
    assert why == mf.by_name(M["workloads"], CELL, "workload")["why"]
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] == [CELL]
    assert [w["name"] for w in M["workloads"] if w["traffic"] == TRAFFIC][:1] == [CELL]


def test_nothing_the_parent_had_is_changed_but_by_the_cell_appended():
    """Added files and appended list members only: with this PR's entries
    taken off, every entry of the manifest is an entry the nine-cell
    benchmark had, in its place, with its bound; `run_seconds`, `command`
    and `paths` as they were."""
    was = before_this_pr()
    assert [w["name"] for w in was["workloads"]] == CELLS[:9] and len(was["configs"]) == 9
    assert [m["name"] for m in was["per_layer"]] == [m["name"] for m in M["per_layer"]][:-5]
    assert [m["name"] for m in M["per_layer"]][-len(NEW_METRICS):] == list(NEW_METRICS)
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
    assert m["workloads"][0] == CELL and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] == ("attention" if name == "qk_norm_pct" else
                          "train step" if "mfu" in name else "kernels")
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
    assert m["source"] == ("host_clock" if "mfu" in name else "device_trace")
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert reader(name).read({"shape": SHAPE, "trace": None}) is None
    names = [e["name"] for e in M["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 5] == list(NEW_METRICS) and len(set(names)) == len(names)


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_its_cells_in_their_order(name):
    """An accepted metric that this cell joins is what it was, with the
    cell appended to its list (and what the nemotron file's case
    `attn_share_pct` held while that list ENDED with twotower-train-8k,
    skipped from tests/conftest.py)."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"][-1] == CELL or CELLS.index(m["workloads"][-1]) > CELLS.index(CELL)
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    parent = [c for c in m["workloads"] if CELLS.index(c) < CELLS.index(CELL)]
    assert parent == m["workloads"][:len(parent)] and parent
    assert parent == mf.by_name(before_this_pr()["per_layer"], name, "metric")["workloads"]
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name in TIMELINE:
        assert m["workloads"] == TRAINING_CELLS
    if name == "attn_share_pct":
        assert parent[-2:] == ["olmo-hybrid-train", "twotower-train-8k"]
    if name == "swa_share_pct":
        assert parent == ["laguna-train"]
    if name == "moe_compact_pct":   # the small shares, in the order they entered
        assert parent == ["glm47f-train", "laguna-train", "keye-train-8k", "twotower-train-8k"]


def test_step_scopes_gain_one_family_and_keep_the_rest():
    own = mf.read_json(mf.ROOT, "chipbench/step_scopes/mellum2.json")
    assert own["families"] == {"qk_norm": ["attn.norm", "swa.norm"]} and set(own) == {"comment",
                                                                                       "families"}
    vocabulary = readers_step.vocabulary()
    assert vocabulary["families"]["attn"] == ["attn.qkv", "attn.rope", "attn.attend", "attn.out"]
    assert vocabulary["families"]["swa"] == ["swa.qkv", "swa.rope", "swa.attend", "swa.out"]
    assert vocabulary["families"]["gate"] == ["attn.gate", "swa.gate"]
    assert readers_step.scope_of_path(
        "jit(step)/transpose(jvp(block.stack))/while/body/checkpoint/swa.norm/mul") == "swa.norm"
    assert readers_step.family("attn.norm") == readers_step.family("swa.norm") == "qk_norm"
    assert set(SHAPE["check"]["scopes"]) == (
        {s for f in ("attn", "swa", "qk_norm", "moe") for s in vocabulary["families"][f]})


# -- the configuration file against the catalog -------------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Mellum2-12B-A2.5B-Instruct":
            return row
    raise AssertionError("the catalog has no such row")


def test_every_published_key_is_the_catalogs_but_the_three_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if SHAPE.get(k) != v}
    assert changed == set(REDUCED)
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    # the lists stay WHOLE: their first `num_hidden_layers` entries are run
    assert len(SHAPE["layer_types"]) == len(SHAPE["mlp_layer_types"]) == 28
    assert SHAPE["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert set(SHAPE["mlp_layer_types"]) == {"sparse"}


def test_every_width_the_issue_names_is_as_published():
    want = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "moe_intermediate_size": 896, "num_experts_per_tok": 8,
            "sliding_window": 1024, "norm_topk_prob": True, "intermediate_size": 7168,
            "max_position_embeddings": 131072, "rms_norm_eps": 1e-6}
    assert {k: SHAPE[k] for k in want} == want
    assert SHAPE["published"]["num_experts"] == 64   # the router's outputs
    assert SHAPE["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
    assert SHAPE["rope_parameters"]["sliding_attention"] == {"rope_type": "default",
                                                             "rope_theta": 500000}
    assert mf.read_json(mf.ROOT, f"chipbench/traffic/{TRAFFIC}.json")["seq_len"] == 16384


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    assert SHAPE["num_hidden_layers"] == 4 and SHAPE["num_experts"] >= 8
    assert SHAPE["vocab_size"] * 8 == SHAPE["published"]["vocab_size"]
    assert SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 8
    assert 8 * SHAPE["num_experts"] == SHAPE["published"]["num_experts"]
    assert SHAPE["deployment"]["first_expert_held"] == 0
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    # the rung taken and the rehearsal that refused the other, both in `reduced`
    rungs = SHAPE["reduced"]["num_experts"]
    assert "rung (a)" in rungs and "rung (b)" in rungs and "SEVEN" in rungs
    assert "6.65 + 8.05" in rungs and "3.80 + 6.86" in rungs
    check = SHAPE["check"]
    assert set(check) == {"scopes", "loss_tol", "loss_tol_why", "routing_tol", "routing_tol_why",
                          "grad_tol", "grad_tol_why"}
    assert 0 < check["loss_tol"] <= 5e-4 and 0 < check["routing_tol"] < 0.05
    assert 0 < check["grad_tol"] < 1
    for why in ("loss_tol_why", "routing_tol_why", "grad_tol_why"):
        assert "my chip runs, PR 53" in check[why], why
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["attention_impl"] == "flash"
    assert "my chip runs, PR 53" in SHAPE["train"]["lr_why"]
    assert "GiB" in SHAPE["memory"] and "340,350,464" in SHAPE["memory"]


# -- the model builder -----------------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (4, 2304, 896, 0, 32, 4, 128)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (64, 8, 0, 8)
    assert cfg.vocab_size == 12288 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.attn_gate == "none" and cfg.qk_head_norm and cfg.first_dense_layers == 0
    assert cfg.remat and cfg.remat_policy == "dots" and cfg.sliding_window == 1024
    assert cfg.stack_module == "ray_tpu.models.laguna"   # ONE module of the typed stack
    assert cfg.kinds() == [("sliding_attention", 32)] * 3 + [("full_attention", 32)]
    shapes = jax.eval_shape(init, jax.random.key(0))
    layers = shapes["layers"]
    assert set(layers) == {"router_bias", "period"} and "dense_layers" not in shapes
    block = layers["period"]["3"]
    assert block["wq"].shape == (1, 2304, 4096) and block["wk"].shape == (1, 2304, 512)
    assert block["q_norm"].shape == block["k_norm"].shape == (1, 128) and "wg" not in block
    assert block["w_gate"].shape == (1, 8, 2304, 896) and block["w_down"].shape == (1, 8, 896, 2304)
    assert block["router"].shape == (1, 2304, 64) and layers["router_bias"].shape == (4, 64)
    assert shapes["embed"].shape == (12288, 2304) and shapes["lm_head"].shape == (2304, 12288)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == 340_350_464   # ISSUE 53's 340,350,208 + the 4 x 64 biases
    assert (builder.STEP_FIRST, builder.STEP_LAST, builder.PASSES, builder.AVERAGED) == (
        5.7e-3, 1.7e-4, 48, 16)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("moe_intermediate_size", 1024), ("num_key_value_heads", 8),
    ("num_attention_heads", 16), ("head_dim", 64), ("num_experts_per_tok", 6),
    ("sliding_window", 512), ("norm_topk_prob", False), ("rms_norm_eps", 1e-5),
    ("layer_types", ["full_attention"] * 28), ("attention_bias", True),
    ("use_sliding_window", False), ("hidden_act", "gelu"),
    ("mlp_layer_types", ["dense"] + ["sparse"] * 27)])
def test_builder_refuses_a_changed_width_or_form(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="sizes"):
        builder.build({**SHAPE, key: value})


@pytest.mark.parametrize("kind,key,value", [
    ("full_attention", "factor", 128), ("full_attention", "attention_factor", 1.0),
    ("full_attention", "rope_type", "default"), ("sliding_attention", "rope_theta", 10000),
    ("full_attention", "original_max_position_embeddings", 4096)])
def test_builder_refuses_a_changed_rotary(kind, key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    rope = {**SHAPE["rope_parameters"], kind: {**SHAPE["rope_parameters"][kind], key: value}}
    with pytest.raises(RuntimeError, match=f"{kind}.{key}"):
        builder.build({**SHAPE, "rope_parameters": rope})


def test_builder_refuses_changed_published_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    for key, value in (("vocab_size", 65536), ("num_experts", 128), ("num_hidden_layers", 48)):
        with pytest.raises(RuntimeError, match="sizes"):
            builder.build({**SHAPE, "published": {**SHAPE["published"], key: value}})


# -- the runner: the runners there were, composed ----------------------------------------


@pytest.mark.parametrize("factor,correct", [(1.0, True), (1.0 + 2 * SHAPE["check"]["grad_tol"], False)],
                         ids=["the_references", "a_leaf_off_by_twice_the_limit"])
def test_the_runner_runs_the_share_runner_then_holds_the_gradient(monkeypatch, factor, correct):
    """No fourth copy of the loop: `run` loads
    runners/train_reference_from_config.py and runs it (the balanced bias,
    loss, routing, dropless counts are that runner's), takes the bias the
    loop started from, and adds the gradient's reading through
    runners/train_reference_nemotron_h.py's `program_gradient` and
    runners/train_reference_checked.py's `errors_by_leaf` and `verdict`;
    `correct` is all of them."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    source = open(os.path.join(mf.ROOT, "chipbench", "runners", SHAPE["runner"] + ".py")).read()
    assert "while " not in source and "session.report" not in source   # no loop of its own
    seen, logged = {}, []
    tree = {"layers": {"period": {"0": {"wq": jnp.arange(1.0, 7.0)}}}, "embed": jnp.ones((3, 2))}
    off = {"layers": {"period": {"0": {"wq": factor * tree["layers"]["period"]["0"]["wq"]}}},
           "embed": tree["embed"]}
    from_config = types.SimpleNamespace(
        _BIAS=["the bias"], run=lambda ctx: seen.update(ran=ctx["config"] is SHAPE) or {
            "correct": True, "checks": {"first_routing_is_the_reference": True}, "losses": [9.4]})
    composed = types.SimpleNamespace(program_gradient=lambda ctx, chk, seed, bias: (
        seen.update(seed=seed, bias=bias, checked=chk is checked) or "params",
        {"tokens": "t", "targets": "y"}, off, 9.4))
    plugins = {"train_reference_from_config": from_config, "train_reference_checked": checked,
               "train_reference_nemotron_h": composed}
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[name])
    monkeypatch.setattr(mellum2_decoder, "grads", lambda params, t, y, config: (
        seen.update(reference=(params, t, y, config is SHAPE)) or tree))
    got = runner.run({"root": mf.ROOT, "config": SHAPE, "traffic": {},
                      "args": types.SimpleNamespace(seed=5),
                      "log": lambda **kw: logged.append(kw)})
    assert seen["ran"] and seen["bias"] == "the bias" and seen["seed"] == 5 and seen["checked"]
    assert seen["reference"] == ("params", "t", "y", True)
    assert got["checks"] == {"first_routing_is_the_reference": True,
                             "first_gradient_is_the_reference": correct}
    assert got["correct"] is correct
    event, = logged
    assert event["event"] == "correct_gradient" and event["leaves"] == 2
    assert event["tolerance"] == SHAPE["check"]["grad_tol"] and event["first_loss"] == 9.4


def _tiny():
    from model_cases import MELLUM2
    from ray_tpu.models import llama

    cfg = dataclasses.replace(MELLUM2.fp32, n_layers=4)
    shape = {**MELLUM2.shape_of(cfg), "train": {"lr": 2.5e-7, "global_batch": 1}}
    return MELLUM2, cfg, shape, llama


def test_the_wrong_table_puts_each_row_through_the_runners_own_comparisons(monkeypatch, tmp_path,
                                                                           capsys):
    """The tool's `main` at the tiny preset (float32, CPU, one period): the
    program's row (the program's own `make_train_step` from the bias it is
    given) and a wrong reference's go through `moved_share` and
    train_reference_checked.py's `errors_by_leaf` / `verdict` at the
    file's three limits; the program comes out correct, the reference
    whose key head is h mod KV does not, and says by which limit."""
    import chipbench.run
    from chipbench.tools import mellum2_wrong as tool

    composed = mf.load_plugin(mf.ROOT, "runners", "train_reference_nemotron_h")
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    model, cfg, shape, llama = _tiny()
    batch = {k: v[:1] for k, v in model.batch_of(cfg).items()}
    init = lambda key: llama.init_params(cfg, key)   # noqa: E731
    config = {**shape, "runner": SHAPE["runner"], "model_builder": "b",
              "check": {"loss_tol": 1e-4, "routing_tol": 1e-3, "grad_tol": 1e-3}}
    monkeypatch.setattr(checked, "built", lambda ctx: (cfg, init, lambda seed: batch))
    real = composed.program_gradient

    def at_highest(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return real(*a, **kw)

    monkeypatch.setattr(composed, "program_gradient", at_highest)
    plugins = {"runners": {"train_reference_nemotron_h": composed,
                           "train_reference_checked": checked},
               "model_builders": {"b": types.SimpleNamespace(
                   balanced_bias=lambda cfg, params, make: np.zeros((4, 16), np.float32))},
               "generators": {"g": types.SimpleNamespace(
                   batch_fn=lambda traffic, vocab, b, seed: lambda i: batch)}}
    monkeypatch.setattr(mf, "ROOT", str(tmp_path))
    monkeypatch.setattr(mf, "load_manifest", lambda root: {})
    monkeypatch.setattr(mf, "load_cell", lambda root, m, name: {
        "chips": 1, "config": config, "traffic": {"generator": "g"}})
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[kind][name])
    monkeypatch.setattr(chipbench.run, "open_chip", lambda chips, name: (None, None, "cpu"))
    assert len(tool.VARIANTS) == 11 and set(tool.PRECISION_ONLY) < set(tool.VARIANTS)
    wrong_head = "key head h mod KV for floor(h / group)"
    with model.reference_set_up():
        assert tool.main(["--seeds", "3", "--only", wrong_head]) == 0
    out = json.load(open(tmp_path / "chiprun_out" / "chipbench" / f"wrong-{CELL}.json"))
    program, wrong = out["rows"]
    assert program["what"].startswith("the program") and program["correct"]
    assert program["grad_err"] < 1e-3 and program["moved_share"] == 0.0 and program["rel_err"] < 1e-5
    assert wrong["what"] == wrong_head and not wrong["correct"] and wrong["grad_err"] > 1e-2
    assert "grad_tol" in wrong["refused_by"] and not program["refused_by"]
    assert len(program["errors"]) == len(jax.tree.leaves(init(jax.random.key(0))))
    assert out["summary"][wrong_head]["correct_on"] == 0
    assert "grad_tol" in out["summary"][wrong_head]["refused_by_on_every_seed"]
    assert '"grad_tol": 0.001' in capsys.readouterr().out
    # the patches are gone afterwards
    assert mellum2_decoder.F32 == jnp.float32 and mellum2_decoder.key_head(9, 32, 4) == 1


# -- the cost functions, by hand ----------------------------------------------------


def test_required_operations_are_issue_53s_count():
    """A step's operations on this share (ISSUE 53, 4.5 forwards where a
    required step is 3): the full layer's scores 4 x 128 x 32 x 8,192.5 a
    token, a sliding layer's 4 x 128 x 32 x about 992, a layer's
    projections 2 x 21,233,664, the held experts at an eighth of the
    pairs."""
    f = costs_mellum2.forward_flops_per_token(SHAPE, 16384, 1 / 8)
    assert f["attention"] == 4 * 2 * 2304 * 128 * (2 * 32 + 2 * 4) == 4 * 2 * 21_233_664
    assert f["scores.full"] == 4 * 128 * 32 * 16385 / 2
    window = (1024 * 1025 / 2 + (16384 - 1024) * 1024) / 16384
    assert f["scores.window"] == 3 * 4 * 128 * 32 * window and 991 < window < 993
    assert f["router"] == 4 * 2 * 2304 * 64 and f["head"] == 2 * 2304 * 12288
    assert f["routed"] == 4 * 2 * 3 * 2304 * 896 * 8 / 8
    total = 3 * sum(f.values()) * 16384
    assert costs_mellum2.train_flops_per_token(SHAPE, 16384, 1 / 8) * 16384 == total
    # 6.6 T the full layer's scores, 2.4 T the three windows', 8.4 T the projections,
    # 2.4 T the held experts, 2.8 T the head: 22.6 T required a step
    assert round(3 * f["scores.full"] * 16384 / 1e12, 1) == 6.6
    assert round(3 * f["scores.window"] * 16384 / 1e12, 1) == 2.4
    assert round(3 * f["routed"] * 16384 / 1e12, 1) == 2.4 and round(total / 1e12, 1) == 22.6
    assert costs_mellum2.layers(SHAPE) == ["sliding_attention"] * 3 + ["full_attention"]


def test_flash_and_grouped_matmul_costs_by_hand():
    full = costs_mellum2.flash_cost(SHAPE, "full_attention", 1, 16384)
    assert full["layers"] == 1 and full["fwd_flops"] == 32 * 4 * 128 * 16384 * 16385 / 2
    assert full["bwd_flops"] == 2.5 * full["fwd_flops"]
    assert full["fwd_bytes"] == 2 * 16384 * 32 * 128 * 2 + 2 * 16384 * 4 * 128 * 2
    assert full["bwd_bytes"] == 2 * full["fwd_bytes"]
    least, bound = costs.roofline_seconds(full["fwd_flops"] + full["bwd_flops"],
                                          full["fwd_bytes"] + full["bwd_bytes"], PEAKS)
    assert bound == "compute" and 0.038 < least < 0.040   # 39 ms a step for the full layer
    window = costs_mellum2.flash_cost(SHAPE, "sliding_attention", 1, 16384)
    pairs = 1024 * 1025 / 2 + (16384 - 1024) * 1024
    assert window["layers"] == 3 and window["fwd_flops"] == 3 * 32 * 4 * 128 * pairs
    assert window["fwd_bytes"] == 3 * full["fwd_bytes"]
    assert 8.0 < full["fwd_flops"] / (window["fwd_flops"] / 3) < 8.5   # a window sees an eighth
    g = costs_mellum2.grouped_matmul_cost(SHAPE, 16384.0)   # 8 experts x 2,048 rows
    assert g["fwd_flops"] == 3 * 2 * 16384 * 2304 * 896 and g["bwd_flops"] == 2 * g["fwd_flops"]
    each = 2 * (16384 * 2304 + 16384 * 896 + 8 * 2304 * 896)
    assert g["fwd_bytes"] == 3 * each and g["bwd_bytes"] == 6 * each


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 1.2, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "attn.attend": {"seconds": 0.33, "ops": {"kernel:attn.attend.3": 0.06,
                                                 "kernel:attn.attend.4": 0.12,
                                                 "kernel:attn.attend.5": 0.12, "fusion.6": 0.03}},
        "swa.attend": {"seconds": 0.21, "ops": {"kernel:swa.attend.7": 0.05,
                                                "kernel:swa.attend.8": 0.15, "fusion.9": 0.01}},
        "swa.norm": {"seconds": 0.009, "ops": {"fusion.10": 0.009}},
        "attn.norm": {"seconds": 0.003, "ops": {"fusion.11": 0.003}},
        "swa.rope": {"seconds": 0.02, "ops": {"fusion.12": 0.02}},
        "moe.experts": {"seconds": 0.12, "ops": {"fusion.7": 0.12}}}}
    router = {"pairs": [131072] * 4, "pairs_elsewhere": [114688] * 4}
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 16384}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 1.2, "window_s": 1.21},
            "trace": object(), "values": {"train_tok_s": 40000.0},
            "ops": {"expert_matmul": {"seconds": 0.09}},
            "traced_window_steps": [{"router": router}] * 3, **extra}


def test_readers_sum_the_norms_and_the_kernels_by_their_scope():
    run = _run()
    assert reader("qk_norm_pct").read(run) == pytest.approx(100 * 0.012 / 1.2)
    f = costs_mellum2.flash_cost(SHAPE, "full_attention", 1, 16384)
    least = 3 * 3.5 * f["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.full16k").read(run) == pytest.approx(100 * least / 0.30)
    w = costs_mellum2.flash_cost(SHAPE, "sliding_attention", 1, 16384)
    least = 3 * 3.5 * w["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.window1k").read(run) == pytest.approx(100 * least / 0.20)
    for name in ("flash_roofline.full16k", "flash_roofline.window1k"):
        assert 0 < reader(name).read(run) < 100
    g = costs_mellum2.grouped_matmul_cost(SHAPE, 16384.0)
    least, _ = costs.roofline_seconds(12 * (g["fwd_flops"] + g["bwd_flops"]),
                                      12 * (g["fwd_bytes"] + g["bwd_bytes"]), PEAKS)
    assert reader("expert_matmul_roofline.mellum2").read(run) == pytest.approx(100 * least / 0.09)
    assert 0 < reader("expert_matmul_roofline.mellum2").read(run) < 100
    per_token = costs_mellum2.train_flops_per_token(SHAPE, 16384, 1 / 8)
    assert reader("train_mfu_pct.mellum2").read(run) == pytest.approx(
        100 * 40000.0 * per_token / PEAKS["bf16_flops_per_s"])
    # the shared readers read the stack's families as they read Laguna's
    assert reader("swa_share_pct").read(run) == pytest.approx(100 * 0.23 / 1.2)
    assert reader("attn_share_pct").read(run) == pytest.approx(100 * 0.33 / 1.2)
    assert reader("experts_elsewhere_pct").read(run) == pytest.approx(87.5)


def test_readers_find_nothing_in_another_cells_run_and_zero_where_the_norm_is_fused_away():
    other = mf.read_json(mf.ROOT, "chipbench/configs/laguna-s-2.1-train.json")
    for name in NEW_METRICS:
        assert reader(name).read(_run(shape=other)) is None, name
    # Laguna's own readers leave this cell alone too: its costs spell out head counts by layer
    for name in ("flash_roofline.window", "flash_roofline.full48", "train_mfu_pct.laguna",
                 "expert_matmul_roofline.held10"):
        assert name not in {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"moe.experts": {
        "seconds": 0.12, "ops": {"fusion.7": 0.12}}}}
    assert reader("qk_norm_pct").read(bare) == 0.0
    for name in ("flash_roofline.window1k", "flash_roofline.full16k"):
        assert reader(name).read(bare) is None, name
    assert reader("train_mfu_pct.mellum2").read(_run(values={})) is None
    assert reader("expert_matmul_roofline.mellum2").read(_run(traced_window_steps=[])) is None
    assert readers_mellum2.is_mellum2(_run()) and not readers_mellum2.is_mellum2({})


# -- carried: what tests of nine cells held, for any number ------------------------------


def test_every_traffic_file_names_a_generator_and_stays_inside_the_window_of_its_models():
    """tests/chipbench/test_chipbench_keye.py's test of this name (skipped
    from tests/conftest.py: it holds every file to 8,192 tokens), for any
    number of files and any length: a generator that loads, a sequence
    inside the file's own `max_context`, and that inside the published
    window of every configuration a cell runs it on; no file without a
    cell; the files there were as they were."""
    used = {}
    for w in M["workloads"]:
        used.setdefault(w["traffic"], []).append(mf.load_cell(mf.ROOT, M, w["name"])["config"])
    d = os.path.join(mf.ROOT, "chipbench", "traffic")
    for fn in sorted(os.listdir(d)):
        t = mf.read_json(mf.ROOT, f"chipbench/traffic/{fn}")
        assert mf.load_plugin(mf.ROOT, "generators", t["generator"])
        assert t["seq_len"] <= t["max_context"]
        assert used[fn[:-len(".json")]]
        for config in used[fn[:-len(".json")]]:
            assert t["max_context"] <= config["max_position_embeddings"]
    for name, length in (("zipf_tokens", 4096), ("zipf_tokens_8k", 8192), (TRAFFIC, 16384)):
        t = mf.read_json(mf.ROOT, f"chipbench/traffic/{name}.json")
        assert (t["max_context"], t["seq_len"], t["zipf_s"], t["generator"]) == (
            length, length, 1.1, "zipf_tokens")


def test_lagunas_window_metric_is_as_it_entered_with_a_second_cell_after_it():
    """tests/chipbench/test_chipbench_laguna.py's
    `test_new_metric_is_this_cells_alone_and_moves_train_tok_s[swa_share_pct]`
    (skipped from tests/conftest.py: it holds the list to laguna-train
    ALONE): the test as PR 39 wrote it, every assertion, on the manifest
    less what this PR appended; and the list today."""
    module = earlier("test_chipbench_laguna.py", before_this_pr())
    module.test_new_metric_is_this_cells_alone_and_moves_train_tok_s("swa_share_pct")
    assert mf.by_name(M["per_layer"], "swa_share_pct", "metric")["workloads"] == ["laguna-train",
                                                                                 CELL]


def test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were():
    """tests/chipbench/test_chipbench_olmo_hybrid.py's test of this name
    (skipped from tests/conftest.py: it spells out `swa_share_pct` as
    laguna-train's alone), every assertion, on the manifest less what this
    PR appended."""
    module = earlier("test_chipbench_olmo_hybrid.py", before_this_pr())
    module.test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were()
    assert mf.by_name(M["end_to_end"], "train_tok_s", "metric")["workloads"] == CELLS


def test_the_host_timelines_eight_are_reported_by_every_training_cell():
    """tests/chipbench/test_chipbench_step_timeline.py's
    `test_the_eight_are_appended_for_all_nine_cells_and_the_manifest_has_no_problems`
    (skipped from tests/conftest.py: nine cells, and the eight as the END
    of the list): the test as PR 51 wrote it on the manifest less what
    this PR appended; today the eight stand together, in their order,
    before this PR's five, and list every training cell."""
    module = earlier("test_chipbench_step_timeline.py", before_this_pr())
    module.test_the_eight_are_appended_for_all_nine_cells_and_the_manifest_has_no_problems()
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(TIMELINE[0])
    assert set(names[at:at + 8]) == set(TIMELINE) and names[at + 8:] == list(NEW_METRICS)
    for name in TIMELINE:
        assert mf.by_name(M["per_layer"], name, "metric")["workloads"] == TRAINING_CELLS
    assert mf.problems(M) == []


@pytest.mark.parametrize("name", ("ssm_share_pct", "ssm_scan_pct", "ssm_glue_pct",
                                  "ssd_scan_roofline", "flash_roofline.full32",
                                  "expert_matmul_roofline.held6", "train_mfu_pct.nemotron_h"))
def test_twotowers_new_metric_is_as_it_entered_on_the_manifest_less_what_came_later(name):
    """tests/chipbench/test_chipbench_step_timeline.py's
    `test_an_earlier_cells_test_holds_on_the_manifest_less_what_pr_51_appended`
    for twotower-train-8k's seven (skipped from tests/conftest.py: it takes
    PR 51's eight off the list and finds this PR's five at its end): the
    nemotron file's test as PR 49 wrote it, every assertion, with BOTH
    taken off."""
    was = before_this_pr()
    was["per_layer"] = [m for m in was["per_layer"] if m["name"] not in TIMELINE]
    assert len(was["per_layer"]) == len(M["per_layer"]) - 8 - 5
    module = earlier("test_chipbench_nemotron_h.py", was)
    module.test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name)


def test_the_attention_familys_list_keeps_twotower_and_gains_this_cell():
    """tests/chipbench/test_chipbench_nemotron_h.py's
    `test_joined_metric_keeps_its_entry_and_its_cells_in_their_order[attn_share_pct]`
    (skipped from tests/conftest.py: it holds the list to END with
    twotower-train-8k), on the manifest less what this PR appended."""
    module = earlier("test_chipbench_nemotron_h.py", before_this_pr())
    module.test_joined_metric_keeps_its_entry_and_its_cells_in_their_order("attn_share_pct")
    attn = mf.by_name(M["per_layer"], "attn_share_pct", "metric")["workloads"]
    assert attn[-3:] == ["olmo-hybrid-train", "twotower-train-8k", CELL]

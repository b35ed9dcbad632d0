"""The twotower-train-8k cell's files (PR 49): the manifest with the cell
(for however many cells there are), the configuration file against the
catalog's row, the model builder, the runner that composes the two
runners there were, the cost functions by hand-worked cases, each new
reader on a hand-built step table, and the one-thing-wrong tool at a tiny
size. It also carries, for any number of cells, what two cases of
tests/chipbench/test_chipbench_keye.py held while `keye-train-8k` was the
one cell on the 8k traffic and the last of the small shares (skipped from
tests/conftest.py)."""

import dataclasses
import json
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_nemotron_h, manifest as mf, readers_nemotron_h
from chipbench.reference import nemotron_h_decoder

M = mf.load_manifest()
CELL, CONFIG, TRAFFIC = "twotower-train-8k", "nemotron-twotower-30b-a3b-train", "zipf_tokens_8k"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ("ssm_share_pct", "ssm_scan_pct", "ssm_glue_pct", "ssd_scan_roofline",
               "flash_roofline.full32", "expert_matmul_roofline.held6", "train_mfu_pct.nemotron_h")
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
# what every share cell reports, the attention layer's family, and this cell with them
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "moe_compact_pct", "expert_imbalance", "experts_elsewhere_pct", "head_share_pct",
          "optim_share_pct", "wgrad_optim_fused_pct", "block_share_pct", "step_unscoped_pct",
          "fallback_sites.train", "attn_share_pct") + SETUP
PEAKS = costs.load_peaks("TPU v5 lite")


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
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
    assert {"0_what_is_trained", "i_mamba_widths", "ii_groups", "iii_step_and_decay",
            "iv_gated_norm", "v_attention", "vi_router", "vii_dtypes", "viii_weights",
            "ix_sequence_length", "x_packed_documents"} <= set(assumed)
    # the second tower and the diffusion objective: said, not built
    assert "NOT IMPLEMENTED" in assumed["0_what_is_trained"]
    assert "SECOND tower" in assumed["0_what_is_trained"] and "diffusion" in assumed["0_what_is_trained"]
    assert "BALANCED" in assumed["vi_router"] and "NOT IMPLEMENTED" in assumed["x_packed_documents"]
    assert "TO BE FILLED" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    assert CELLS[8] == CELL and M["configs"][8]["name"] == CONFIG and CELLS == TRAINING_CELLS
    assert len(CELLS) >= 9 and [w["chips"] for w in M["workloads"]].count(4) == 1
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "CAUSAL".lower() in why.lower() and "384 rows" in why
    assert "9 of 52 layers" in why and "1 x 8192" in why and "6,144" in why
    assert why == mf.by_name(M["workloads"], CELL, "workload")["why"]
    # one cell of this configuration; the 8k traffic's cells are Keye's and this one
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] == [CELL]
    assert [w["name"] for w in M["workloads"] if w["traffic"] == TRAFFIC][:2] == [
        "keye-train-8k", CELL]


def test_keyes_cell_is_as_it_entered_but_no_longer_alone_on_its_traffic():
    """tests/chipbench/test_chipbench_keye.py's
    `test_manifest_is_well_formed_with_the_cell` (skipped from
    tests/conftest.py: its last line spells out keye-train-8k as the ONE
    cell on zipf_tokens_8k), every other assertion of it."""
    keye, kconfig = "keye-train-8k", "keye-vl-2.0-30b-a3b-train"
    shape = mf.read_json(mf.ROOT, f"chipbench/configs/{kconfig}.json")
    cell = mf.load_cell(mf.ROOT, M, keye)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    assert (cell["traffic"]["seq_len"], cell["traffic"]["max_context"],
            cell["traffic"]["zipf_s"]) == (8192, 8192, 1.1)
    assert cell["cell"]["traffic"] == TRAFFIC
    entry = mf.by_name(M["configs"], kconfig, "config")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] == list(
        shape["reduced"])
    assert entry["source"] == shape["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert shape[key], key
    assumed = shape["assumed"]
    assert {"i_selection", "ii_indexer", "iii_qk_norm", "iv_mrope", "v_indexer_training",
            "vi_router", "vii_dtypes_and_weights"} <= set(assumed)
    assert "NOT IMPLEMENTED" in assumed["v_indexer_training"] and "BALANCED" in assumed["vi_router"]
    assert "TO FILL" not in json.dumps(shape)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", keye)}
    keyes = {"dsa_share_pct", "dsa_index_pct", "dsa_select_pct", "flash_roofline.selected",
             "expert_matmul_roofline.held8", "train_mfu_pct.keye"}
    assert reported >= keyes | (set(JOINED) - {"attn_share_pct"})
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", keye)} == {"train_tok_s", "setup_s"}
    assert not reported & {"flash_roofline", "flash_roofline.mla", "flash_roofline.window",
                           "flash_roofline.full48", "expert_matmul_roofline", "train_mfu_pct",
                           "train_mfu_pct.moe", "expert_matmul_roofline.held", "attn_share_pct",
                           "expert_matmul_roofline.held4", "expert_matmul_roofline.held10",
                           "train_mfu_pct.zaya", "train_mfu_pct.glm", "train_mfu_pct.laguna"}
    assert CELLS[6] == keye and M["configs"][6]["name"] == kconfig
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "512 rows" in why and "4,096" in why and "16,384" in why
    assert f"{shape['num_hidden_layers']} of 48 layers" in why and "8192" in why
    assert [w["name"] for w in M["workloads"] if w["config"] == kconfig] == [keye]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] == ("state-space mixer" if name.startswith("ssm_") else
                          "train step" if "mfu" in name else "kernels")
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
    assert m["source"] == ("host_clock" if "mfu" in name else "device_trace")
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None
    assert reader(name).read({"shape": SHAPE, "trace": None}) is None
    names = [e["name"] for e in M["per_layer"]]
    assert names[-7:] == list(NEW_METRICS) and len(set(names)) == len(names)


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_its_cells_in_their_order(name):
    """An accepted metric that this cell joins is what it was, with the
    cell appended to its list (and, for `moe_compact_pct`, what the keye
    file's case of this name held while that list ENDED with keye-train-8k,
    skipped from tests/conftest.py: the small shares in the order they
    entered)."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"][-1] == CELL or CELLS.index(m["workloads"][-1]) > CELLS.index(CELL)
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    parent = [c for c in m["workloads"] if CELLS.index(c) < CELLS.index(CELL)]
    assert parent == m["workloads"][:len(parent)] and parent
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name in ("moe_share_pct", "moe_dispatch_pct", "expert_imbalance"):
        assert m["workloads"][:5] == ["olmoe-train", "zaya1-train", "glm47f-train",
                                      "laguna-train", "keye-train-8k"]
    if name == "moe_compact_pct":   # the small shares
        assert m["workloads"][:4] == ["glm47f-train", "laguna-train", "keye-train-8k", CELL]
    if name == "attn_share_pct":
        assert m["workloads"][-2:] == ["olmo-hybrid-train", CELL]


def test_step_scopes_gain_three_families_and_keep_the_rest():
    from chipbench import readers_step

    names = readers_step.scope_names(mf.ROOT) if hasattr(readers_step, "scope_names") else None
    own = mf.read_json(mf.ROOT, "chipbench/step_scopes/nemotron_h.json")
    assert own["families"] == {"ssm_proj": ["ssm.proj", "ssm.out"], "ssm_scan": ["ssm.scan"],
                               "ssm_glue": ["ssm.conv", "ssm.gates", "ssm.norm"]}
    assert set(own) == {"comment", "families"}
    base = mf.read_json(mf.ROOT, "chipbench/step_scopes/base.json")
    assert not set(own["families"]) & set(base["families"])
    if names is not None:
        assert {"ssm.scan", "gdn.scan", "attn.attend", "moe.experts"} <= set(names)
    assert readers_nemotron_h.FAMILIES == tuple(own["families"])


# -- the configuration file against the catalog -------------------------------------


def catalog_row():
    import os

    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16":
            return row
    raise AssertionError("the catalog has no such row")


def test_every_published_key_is_the_catalogs_but_the_three_cuts():
    row = catalog_row()
    assert SHAPE["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if SHAPE.get(k) != v}
    assert changed == set(REDUCED)
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    # the pattern string stays WHOLE: the first `num_hidden_layers` characters are run
    assert len(SHAPE["hybrid_override_pattern"]) == 52 and SHAPE["num_hidden_layers"] == 9
    assert SHAPE["hybrid_override_pattern"][:9] == "MEMEM*EME"


def test_every_width_the_issue_names_is_as_published():
    want = {"hidden_size": 2688, "moe_intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712, "mamba_num_heads": 64,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128, "num_experts_per_tok": 6, "chunk_size": 128,
            "conv_kernel": 4, "routed_scaling_factor": 2.5, "expand": 2}
    assert {k: SHAPE[k] for k in want} == want
    assert SHAPE["published"]["n_routed_experts"] == 128   # the router's outputs


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    assert SHAPE["num_hidden_layers"] >= 7 + 2 and SHAPE["n_routed_experts"] >= 8
    assert SHAPE["vocab_size"] * 8 == SHAPE["published"]["vocab_size"]
    assert SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["deployment"]["chips_that_share_a_layer"] == 16
    assert 16 * SHAPE["n_routed_experts"] == SHAPE["published"]["n_routed_experts"]
    assert SHAPE["deployment"]["first_expert_held"] == 0
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    check = SHAPE["check"]
    assert check["scopes"] == ["moe.router", "moe.dispatch", "moe.experts", "moe.combine",
                               "ssm.proj", "ssm.conv", "ssm.gates", "ssm.scan", "ssm.norm",
                               "ssm.out", "attn.qkv", "attn.attend", "attn.out"]
    assert set(check) == {"scopes", "loss_tol", "loss_tol_why", "routing_tol", "routing_tol_why",
                          "grad_tol", "grad_tol_why", "scan_tol", "scan_tol_why"}
    assert 0 < check["loss_tol"] <= 5e-4 and 0 < check["routing_tol"] < 0.05
    assert 0 < check["scan_tol"] < check["grad_tol"] < 1
    for why in ("loss_tol_why", "routing_tol_why", "grad_tol_why", "scan_tol_why"):
        assert "my chip runs, PR 49" in check[why], why
    assert "bfloat16" in check["scan_tol_why"]
    assert SHAPE["train"]["global_batch"] == 1 and SHAPE["train"]["lr"] == 2.5e-7
    assert SHAPE["train"]["attention_impl"] == "flash" and "BALANCED" in SHAPE["train"]["lr_why"]
    assert "GiB" in SHAPE["memory"] and "666,963,456" in SHAPE["memory"]


# -- the model builder -----------------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (9, 2688, 1856, 3712, 32, 2, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.conv_kernel,
            cfg.chunk_size) == (64, 64, 8, 128, 4, 128)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (128, 8, 0, 6)
    assert cfg.vocab_size == 16384 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.expert_act == "relu2" and cfg.router_score == "sigmoid" and cfg.remat
    assert cfg.remat_policy == "dots" and "".join(cfg.layer_types) == "MEMEM*EME"
    assert cfg.published_layers == 52 and len(cfg.pattern) == 52
    shapes = jax.eval_shape(init, jax.random.key(0))
    layers = shapes["layers"]
    assert layers["mamba"]["w_in"].shape == (4, 2688, 10304)
    assert layers["mamba"]["conv"].shape == (4, 4, 6144) and layers["mamba"]["conv_bias"].shape == (4, 6144)
    assert layers["mamba"]["norm"].shape == (4, 4096) and layers["mamba"]["w_out"].shape == (4, 4096, 2688)
    assert layers["attention"]["wq"].shape == (1, 2688, 4096) and layers["attention"]["wv"].shape == (1, 2688, 256)
    assert layers["experts"]["w_up"].shape == (4, 8, 2688, 1856) and "w_gate" not in layers["experts"]
    assert layers["experts"]["shared_down"].shape == (4, 3712, 2688) and layers["router_bias"].shape == (4, 128)
    assert layers["experts"]["router"].shape == (4, 2688, 128)
    assert shapes["embed"].shape == (16384, 2688) and shapes["lm_head"].shape == (2688, 16384)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() == costs_nemotron_h.num_params(SHAPE) == 666_963_456


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("moe_intermediate_size", 1024), ("num_key_value_heads", 8),
    ("moe_shared_expert_intermediate_size", 1856), ("mamba_num_heads", 32), ("mamba_head_dim", 128),
    ("n_groups", 1), ("ssm_state_size", 64), ("conv_kernel", 2), ("chunk_size", 256),
    ("num_experts_per_tok", 8), ("routed_scaling_factor", 1.0), ("layer_norm_epsilon", 1e-6),
    ("hybrid_override_pattern", "MEMEM*EME"), ("mlp_hidden_act", "silu"), ("use_conv_bias", False),
    ("n_group", 8), ("time_step_limit", [0, 0.5])])
def test_builder_refuses_a_changed_width_or_form(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match=key[:8]):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_changed_published_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    for key, value in (("vocab_size", 65536), ("n_routed_experts", 64), ("num_hidden_layers", 48)):
        with pytest.raises(RuntimeError, match="sizes"):
            builder.build({**SHAPE, "published": {**SHAPE["published"], key: value}})


# -- the runner: the two runners there were, composed ------------------------------------


def _ctx(logged, config=SHAPE):
    return {"root": mf.ROOT, "config": config, "traffic": {}, "args": types.SimpleNamespace(seed=5),
            "log": lambda **kw: logged.append(kw)}


@pytest.mark.parametrize("factor,scan_factor,correct", [
    (1.0, 1.0, True), (1.0 + 2 * SHAPE["check"]["grad_tol"], 1.0, False),
    (1.0, 1.0 + 2 * SHAPE["check"]["scan_tol"], False)],
    ids=["the_references", "a_leaf_off_by_twice_the_limit", "the_scan_off_by_twice_its_limit"])
def test_the_runner_runs_the_share_runner_then_holds_gradient_and_scan(monkeypatch, factor,
                                                                      scan_factor, correct):
    """No fourth copy of the loop: `run` loads runners/train_reference_from_config.py
    and runs it (the balanced bias, loss, routing, dropless counts are that
    runner's), takes the bias the loop started from, and adds the two
    readings of runners/train_reference_checked.py through ITS
    `errors_by_leaf` and `verdict`; `correct` is all of them."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    seen, logged = {}, []
    from_config = types.SimpleNamespace(
        _BIAS=["the bias"], run=lambda ctx: seen.update(ran=ctx["config"] is SHAPE) or {
            "correct": True, "checks": {"first_routing_is_the_reference": True}, "losses": [9.7]})
    plugins = {"train_reference_from_config": from_config, "train_reference_checked": checked}
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[name])
    tree = {"layers": {"mamba": {"w_in": jnp.arange(1.0, 7.0)}}, "embed": jnp.ones((3, 2))}
    off = {"layers": {"mamba": {"w_in": factor * tree["layers"]["mamba"]["w_in"]}},
           "embed": tree["embed"]}
    monkeypatch.setattr(runner, "program_gradient", lambda ctx, chk, seed, bias: (
        seen.update(seed=seed, bias=bias, checked=chk is checked) or "params",
        {"tokens": ["t0"], "targets": "y"}, off, 9.7))
    monkeypatch.setattr(nemotron_h_decoder, "grads", lambda params, t, y, config: (
        seen.update(reference=(params, t, y, config is SHAPE)) or tree))
    five = tuple(jnp.full((2,), float(i + 1)) for i in range(5))
    monkeypatch.setattr(runner, "scan_cotangent", lambda tokens, config, seed: "w")
    monkeypatch.setattr(nemotron_h_decoder, "first_scan", lambda params, tokens, config, w: (
        seen.update(scan=(params, tokens, w)) or ("args", five)))
    monkeypatch.setattr(checked, "built", lambda ctx: (types.SimpleNamespace(stack_module="m"),))
    monkeypatch.setattr(runner, "program_scan", lambda module, chunk, args, w: (
        seen.update(program_scan=(module, chunk, args, w))
        or dict(zip(runner.SCAN_OUTPUTS, (scan_factor * a for a in five)))))
    got = runner.run(_ctx(logged))
    assert seen["ran"] and seen["bias"] == "the bias" and seen["seed"] == 5 and seen["checked"]
    assert seen["reference"] == ("params", ["t0"], "y", True) and seen["scan"] == ("params", "t0", "w")
    assert seen["program_scan"] == ("m", 128, "args", "w")
    assert got["checks"] == {"first_routing_is_the_reference": True,
                             "first_gradient_is_the_reference": correct or scan_factor != 1.0,
                             "first_scan_is_the_reference": correct or factor != 1.0}
    assert got["correct"] is correct
    events = {e["event"]: e for e in logged}
    assert events["correct_gradient"]["leaves"] == 2 and events["correct_scan"]["leaves"] == 5
    assert events["correct_gradient"]["tolerance"] == SHAPE["check"]["grad_tol"]
    assert events["correct_scan"]["tolerance"] == SHAPE["check"]["scan_tol"]
    assert events["correct_gradient"]["first_loss"] == 9.7


def test_moved_share_and_with_bias_by_hand():
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    assert runner.moved_share([[3, 1, 2], [2, 2, 2]], [[2, 2, 2], [2, 2, 2]]) == 1 / 12
    assert runner.moved_share([[2, 2]], [[2, 2]]) == 0.0
    params = {"embed": 1, "layers": {"router_bias": jnp.zeros((2, 3), jnp.float32), "mamba": 2}}
    out = runner.with_bias(params, np.ones((2, 3)))
    assert out["embed"] == 1 and out["layers"]["mamba"] == 2
    assert float(out["layers"]["router_bias"].sum()) == 6.0
    assert float(params["layers"]["router_bias"].sum()) == 0.0


def _tiny(dtype=jnp.float32):
    from model_cases import nemotron_h_shape
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    cfg = dataclasses.replace(get_model_config("nemotron-h-tiny"), dtype=dtype)
    return cfg, {**nemotron_h_shape(cfg), "train": {"lr": 2.5e-7, "global_batch": 2}}, llama


def test_the_program_gradient_and_scan_are_the_programs_own_and_meet_the_references(monkeypatch):
    """At the tiny preset in float32 on the CPU: `program_gradient` runs the
    program's `make_train_step` with AdamW from the bias it is given (the
    gradient read back through the first moment is `jax.grad` of the
    step's loss, the parameters handed back are fresh ones with that
    bias), every leaf meets `reference.grads`; layer 0's scan through the
    program's `ssd_scan` BY NAME meets the position-by-position scan,
    forward and the cotangent pulled back; a bfloat16 state is seen."""
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    cfg, shape, llama = _tiny()
    tok = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    init = lambda key: llama.init_params(cfg, key)   # noqa: E731
    monkeypatch.setattr(checked, "built", lambda ctx: (cfg, init, lambda seed: batch))
    bias = 0.05 * np.random.default_rng(0).standard_normal((3, 16)).astype(np.float32)
    ctx = {"config": shape}
    with jax.default_matmul_precision("highest"):
        params, got_batch, grads, loss, counts = runner.program_gradient(
            ctx, checked, 7, bias, with_counts=True)
        want = jax.jit(jax.grad(lambda p: llama.loss_and_weight_fn(p, batch, cfg)[0]))(params)
    assert got_batch is batch and counts.shape == (3, 16) and counts.sum() == 3 * 80 * cfg.top_k
    fresh = runner.with_bias(jax.jit(init)(jax.random.key(7)), bias)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), params, fresh)))
    assert max(checked.errors_by_leaf(grads, want).values()) < 1e-5
    parts = nemotron_h_decoder.loss_parts(params, batch["tokens"], batch["targets"], shape)
    assert abs(loss - float(parts["loss"])) < 1e-5 * loss
    assert runner.moved_share(counts, parts["tokens_per_expert"]) == 0.0
    reference = nemotron_h_decoder.grads(params, batch["tokens"], batch["targets"], shape)
    errors = checked.errors_by_leaf(grads, reference)
    assert len(errors) == 24 and max(errors.values()) < 5e-4, max(errors, key=errors.get)
    w = runner.scan_cotangent(batch["tokens"], shape, 7)
    assert w.shape == (40, cfg.mamba_heads, cfg.mamba_head_dim)
    args, outputs = nemotron_h_decoder.first_scan(params, batch["tokens"][0], shape, w)
    assert [a.shape for a in args] == [(40, 8, 16), (40, 8), (8,), (40, 2, 64), (40, 2, 64), (8,)]
    with jax.default_matmul_precision("highest"):
        mine = runner.program_scan(cfg.stack_module, cfg.chunk_size, args, w)
    names = dict(zip(runner.SCAN_OUTPUTS, outputs))
    scan = checked.errors_by_leaf(mine, names)
    assert set(scan) == {f"['{n}']" for n in runner.SCAN_OUTPUTS} and max(scan.values()) < 1e-5, scan
    with mock.patch.object(nemotron_h_decoder, "STATE", jnp.bfloat16):
        _, rounded = nemotron_h_decoder.first_scan(params, batch["tokens"][0], shape, w)
    seen = checked.errors_by_leaf(dict(zip(runner.SCAN_OUTPUTS, rounded)), names)
    assert min(seen.values()) > 20 * max(scan.values()), (seen, scan)


# -- the cost functions, by hand ----------------------------------------------------


def test_required_operations_are_issue_49s_count():
    """Per token forward on this share, MFLOP (ISSUE 49): the four Mamba
    mixers 320 (the scan itself 2.6 a mixer), the attention layer 114, the
    four expert layers 192 at a sixteenth of the pairs held, the head 88:
    about 714."""
    f = costs_nemotron_h.forward_flops_per_token(SHAPE, 8192, 1 / 16)
    mamba = 2 * (2688 * 10304 + 4096 * 2688)
    assert f["mamba.proj"] == 4 * mamba and f["mamba.scan"] == 4 * 5 * 64 * 64 * 128
    assert round((f["mamba.proj"] + f["mamba.scan"]) / 1e6) == 320
    assert f["attention.proj"] == 2 * 2 * 2688 * 128 * 34
    assert f["attention.scores"] == 4 * 128 * 32 * 8193 / 2
    assert round((f["attention.proj"] + f["attention.scores"]) / 1e6) == 114
    assert f["experts.router"] == 4 * 2 * 2688 * 128 and f["experts.shared"] == 4 * 2 * 2 * 2688 * 3712
    assert f["experts.routed"] == 4 * 2 * 2 * 2688 * 1856 * 6 / 16
    assert round(sum(v for k, v in f.items() if k.startswith("experts")) / 1e6) == 192
    assert f["head"] == 2 * 2688 * 16384 and round(sum(f.values()) / 1e6) == 714
    assert costs_nemotron_h.train_flops_per_token(SHAPE, 8192, 1 / 16) == 3 * sum(f.values())
    assert costs_nemotron_h.layers(SHAPE) == "MEMEM*EME"
    assert [costs_nemotron_h.count(SHAPE, k) for k in "ME*"] == [4, 4, 1]


def test_scan_flash_and_grouped_matmul_costs_by_hand():
    c = costs_nemotron_h.scan_cost(SHAPE, 1, 8192)
    positions, elements = 4 * 8192, 64 * 64 * 128
    assert c["layers"] == 4 and c["fwd_flops"] == 5 * positions * elements
    assert c["bwd_flops"] == 11 * positions * elements
    inputs = positions * ((4096 + 2 * 1024) * 2 + 64 * 4)
    assert c["fwd_bytes"] == inputs + positions * 4096 * 2
    assert c["bwd_bytes"] == 2 * inputs + positions * 4096 * 2
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    assert bound == "memory" and 2.0e-3 < least < 2.3e-3   # 2.16 ms a step for the four layers
    f = costs_nemotron_h.flash_cost(SHAPE, 1, 8192)
    assert f["layers"] == 1 and f["fwd_flops"] == 32 * 4 * 128 * 8192 * 8193 / 2
    assert f["fwd_bytes"] == 2 * 8192 * 32 * 128 * 2 + 2 * 8192 * 2 * 128 * 2
    g = costs_nemotron_h.grouped_matmul_cost(SHAPE, 3072.0)
    assert g["fwd_flops"] == 2 * 2 * 3072 * 2688 * 1856 and g["bwd_flops"] == 2 * g["fwd_flops"]
    each = 2 * (3072 * 2688 + 3072 * 1856 + 8 * 2688 * 1856)
    assert g["fwd_bytes"] == 2 * each and g["bwd_bytes"] == 4 * each


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 0.84, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "ssm.scan": {"seconds": 0.24, "ops": {"fusion.1750": 0.24}},
        "ssm.proj": {"seconds": 0.1, "ops": {"fusion.1": 0.1}},
        "ssm.out": {"seconds": 0.04, "ops": {"fusion.2": 0.04}},
        "ssm.conv": {"seconds": 0.0225, "ops": {"kernel:gdn_conv_fwd.27": 0.0225}},
        "ssm.gates": {"seconds": 0.0001, "ops": {"fusion.4": 0.0001}},
        "ssm.norm": {"seconds": 0.0414, "ops": {"fusion.5": 0.0414}},
        "attn.attend": {"seconds": 0.0392, "ops": {"kernel:attn.attend.3": 0.0259,
                                                   "kernel:attn.attend.2": 0.0108,
                                                   "fusion.6": 0.0025}},
        "moe.experts": {"seconds": 0.03, "ops": {"fusion.7": 0.03}}}}
    router = {"pairs": [49152] * 4, "pairs_elsewhere": [46080] * 4}
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 8192}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 0.84, "window_s": 0.85},
            "trace": object(), "values": {"train_tok_s": 28800.0},
            "ops": {"expert_matmul": {"seconds": 0.0176}},
            "traced_window_steps": [{"router": router}] * 3, **extra}


def test_readers_sum_the_families_the_scan_and_the_kernels():
    run = _run()
    assert reader("ssm_share_pct").read(run) == pytest.approx(100 * 0.444 / 0.84)
    assert reader("ssm_scan_pct").read(run) == pytest.approx(100 * 0.24 / 0.84)
    assert reader("ssm_glue_pct").read(run) == pytest.approx(100 * 0.064 / 0.84)
    c = costs_nemotron_h.scan_cost(SHAPE, 1, 8192)
    least = 3 * (c["fwd_bytes"] + c["bwd_bytes"]) / PEAKS["hbm_bytes_per_s"]
    assert reader("ssd_scan_roofline").read(run) == pytest.approx(100 * least / 0.24)
    assert 0 < reader("ssd_scan_roofline").read(run) < 100
    f = costs_nemotron_h.flash_cost(SHAPE, 1, 8192)
    least = 3 * 3.5 * f["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.full32").read(run) == pytest.approx(100 * least / 0.0367)
    g = costs_nemotron_h.grouped_matmul_cost(SHAPE, 3072.0)
    least, _ = costs.roofline_seconds(12 * (g["fwd_flops"] + g["bwd_flops"]),
                                      12 * (g["fwd_bytes"] + g["bwd_bytes"]), PEAKS)
    assert reader("expert_matmul_roofline.held6").read(run) == pytest.approx(100 * least / 0.0176)
    assert 0 < reader("expert_matmul_roofline.held6").read(run) < 100
    per_token = costs_nemotron_h.train_flops_per_token(SHAPE, 8192, 1 / 16)
    assert reader("train_mfu_pct.nemotron_h").read(run) == pytest.approx(
        100 * 28800.0 * per_token / PEAKS["bf16_flops_per_s"])


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    other = mf.read_json(mf.ROOT, "chipbench/configs/keye-vl-2.0-30b-a3b-train.json")
    for name in NEW_METRICS[3:]:
        assert reader(name).read(_run(shape=other)) is None, name
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"moe.experts": {
        "seconds": 0.03, "ops": {"fusion.7": 0.03}}}}
    for name in NEW_METRICS[:5]:
        assert reader(name).read(bare) is None, name
    assert reader("train_mfu_pct.nemotron_h").read(_run(values={})) is None
    assert reader("expert_matmul_roofline.held6").read(_run(traced_window_steps=[])) is None
    assert reader("train_mfu_pct.nemotron_h").read(_run(traced_window_steps=[])) is None


# -- the one-thing-wrong tool, at a tiny size ------------------------------------------


def test_each_change_of_the_wrong_table_moves_the_reference_and_is_undone():
    """chipbench/tools/nemotron_h_wrong.py patches the reference's small
    functions one at a time; here, at the tiny preset on the CPU, every
    patch runs; each change of the mathematics gives another loss than the
    sound reference; both precisions move the scan alone; the patches are
    gone afterwards."""
    from chipbench.tools import nemotron_h_wrong as tool

    cfg, shape, llama = _tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    params["layers"]["mamba"]["D"] = params["layers"]["mamba"]["D"] * 0.7
    tok = jax.random.randint(jax.random.key(1), (1, 49), 0, cfg.vocab_size)
    sound = float(nemotron_h_decoder.loss(params, tok[:, :-1], tok[:, 1:], shape))
    w = jnp.ones((48, cfg.mamba_heads, cfg.mamba_head_dim))
    sound_scan = nemotron_h_decoder.first_scan(params, tok[0, :-1], shape, w)[1]
    assert len(tool.VARIANTS) == 12 and set(tool.PRECISION_ONLY) < set(tool.VARIANTS)
    for name, wrong in tool.VARIANTS.items():
        with wrong():
            if name in tool.PRECISION_ONLY:
                scan = nemotron_h_decoder.first_scan(params, tok[0, :-1], shape, w)[1]
                assert float(jnp.abs(scan[0].astype(jnp.float32) - sound_scan[0]).max()) > 1e-4, name
            else:
                loss = float(nemotron_h_decoder.loss(params, tok[:, :-1], tok[:, 1:], shape))
                assert not abs(loss - sound) <= 1e-6 * sound, name
    assert float(nemotron_h_decoder.loss(params, tok[:, :-1], tok[:, 1:], shape)) == sound
    assert nemotron_h_decoder.F32 == jnp.float32 and nemotron_h_decoder.STATE == jnp.float32


def test_the_wrong_table_puts_each_row_through_the_runners_own_comparisons(monkeypatch, tmp_path,
                                                                           capsys):
    """The tool's `main` at the tiny preset (float32, CPU): the program's
    row and a wrong reference's go through the runner's `moved_share` and
    train_reference_checked.py's `errors_by_leaf` / `verdict` at the
    file's four limits; the program comes out correct, the reference
    without its D does not."""
    import chipbench.run
    from chipbench.tools import nemotron_h_wrong as tool

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    checked = mf.load_plugin(mf.ROOT, "runners", "train_reference_checked")
    cfg, shape, llama = _tiny()
    tok = jax.random.randint(jax.random.key(1), (1, 49), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    init = lambda key: llama.init_params(cfg, key)   # noqa: E731
    config = {**shape, "runner": SHAPE["runner"], "model_builder": "b",
              "check": {"loss_tol": 1e-4, "routing_tol": 1e-3, "grad_tol": 1e-3, "scan_tol": 1e-4}}
    monkeypatch.setattr(checked, "built", lambda ctx: (cfg, init, lambda seed: batch))
    real_gradient, real_scan = runner.program_gradient, runner.program_scan

    def at_highest(f):
        def g(*a, **kw):
            with jax.default_matmul_precision("highest"):
                return f(*a, **kw)
        return g

    monkeypatch.setattr(runner, "program_gradient", at_highest(real_gradient))
    monkeypatch.setattr(runner, "program_scan", at_highest(real_scan))
    plugins = {"runners": {SHAPE["runner"]: runner, "train_reference_checked": checked},
               "model_builders": {"b": types.SimpleNamespace(
                   balanced_bias=lambda cfg, params, make: np.zeros((3, 16), np.float32))},
               "generators": {"g": types.SimpleNamespace(
                   batch_fn=lambda traffic, vocab, b, seed: lambda i: batch)}}
    monkeypatch.setattr(mf, "ROOT", str(tmp_path))
    monkeypatch.setattr(mf, "load_manifest", lambda root: {})
    monkeypatch.setattr(mf, "load_cell", lambda root, m, name: {
        "chips": 1, "config": config, "traffic": {"generator": "g"}})
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: plugins[kind][name])
    monkeypatch.setattr(chipbench.run, "open_chip", lambda chips, name: (None, None, "cpu"))
    assert tool.main(["--seeds", "3", "--only", "D left out"]) == 0
    out = json.load(open(tmp_path / "chiprun_out" / "chipbench" / "wrong-twotower-train-8k.json"))
    program, wrong = out["rows"]
    assert program["what"].startswith("the program") and program["correct"]
    assert program["grad_err"] < 1e-3 and program["moved_share"] == 0.0 and program["rel_err"] < 1e-5
    assert program["scan_err"] < 1e-4 < wrong["scan_err"]
    assert wrong["what"] == "D left out" and not wrong["correct"] and wrong["grad_err"] > 1e-2
    assert len(program["errors"]["gradient"]) == 24 and len(program["errors"]["scan"]) == 5
    assert out["summary"]["D left out"]["correct_on"] == 0
    assert '"scan_tol": 0.0001' in capsys.readouterr().out

"""The laguna-train cell's files (PR 39): the manifest with the cell (for
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

from chipbench import costs, costs_laguna, manifest as mf, readers_laguna, readers_step
from chipbench.reference import laguna_decoder

M = mf.load_manifest()
CELL, CONFIG = "laguna-train", "laguna-s-2.1-train"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ("swa_share_pct", "attn_gate_pct", "flash_roofline.window", "flash_roofline.full48",
               "expert_matmul_roofline.held10", "train_mfu_pct.laguna")
CELLS = [w["name"] for w in M["workloads"]]
TRAINING_CELLS = [w for w in CELLS
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w)]]
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
# what holds for every training cell, and the families of blocks this cell has
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "hbm_step_gib.train", "report_ms.train", "head_share_pct", "optim_share_pct",
          "wgrad_optim_fused_pct", "step_unscoped_pct", "block_share_pct", "fallback_sites.train",
          "attn_share_pct", "ffn_share_pct", "moe_share_pct", "moe_dispatch_pct",
          "expert_imbalance", "experts_elsewhere_pct") + SETUP
PEAKS = costs.load_peaks("TPU v5 lite")
PR_37 = ("head_share_pct", "optim_share_pct", "wgrad_optim_fused_pct", "attn_share_pct",
         "ffn_share_pct", "step_unscoped_pct", "hbm_step_gib.train", "fallback_sites.train",
         "block_share_pct")


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    entry = mf.by_name(M["configs"], CONFIG, "config")
    assert entry["reduced"] == REDUCED == list(SHAPE["reduced"])
    assert entry["source"] == SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assert {"gate", "qk_norm", "rotary_pairing", "yarn", "router", "router_bias_update",
            "shared_expert", "param_dtype", "weights"} <= set(SHAPE["assumed"])
    assert "TO FILL" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    # their cost functions read one head count, every pair, or another share's keys: wrong here
    assert not reported & {"flash_roofline", "flash_roofline.mla", "expert_matmul_roofline",
                           "train_mfu_pct.moe", "train_mfu_pct", "expert_matmul_roofline.held",
                           "expert_matmul_roofline.held4", "train_mfu_pct.zaya",
                           "train_mfu_pct.glm"}
    # the cells, however many: one of four chips, this one where it entered
    assert len(CELLS) >= 6 and [w["chips"] for w in M["workloads"]].count(4) == 1
    assert CELLS[5] == CELL and M["configs"][5]["name"] == CONFIG
    assert CELLS == TRAINING_CELLS
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "160 rows" in why and "5,120" in why and "40,960" in why
    assert why == mf.by_name(M["workloads"], CELL, "workload")["why"]


def test_per_layer_keeps_pr_37s_metrics_in_their_order_before_what_came_later():
    """tests/chipbench/test_chipbench_step.py held PR 37's metrics to the
    END of the list (skipped from tests/conftest.py: a later PR appends).
    The same, for any tail: they stand together, in their order, after
    everything older, and what follows them is this PR's six."""
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(PR_37[0])
    assert tuple(names[at:at + len(PR_37)]) == PR_37
    assert tuple(names[at + len(PR_37):][:len(NEW_METRICS)]) == NEW_METRICS
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] in {e["layer"] for e in M["per_layer"] if e["name"] not in NEW_METRICS}
    assert m["better"] == ("higher" if "roofline" in name or "mfu" in name else "lower")
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
    parent = [c for c in m["workloads"] if c != CELL]
    assert parent == m["workloads"][:len(parent)] and parent
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS
    else:
        assert m["moves"] == "train_tok_s"
    if name in ("moe_share_pct", "moe_dispatch_pct", "expert_imbalance"):
        assert m["workloads"][:3] == ["olmoe-train", "zaya1-train", "glm47f-train"]
    if name == "attn_share_pct":   # the cells whose blocks have `attn.*` scopes
        assert m["workloads"] == ["m7b-train", "m7b-train-4chip", "olmoe-train", CELL]


def test_metrics_of_the_other_cells_stay_theirs():
    for name, cells in (("expert_matmul_roofline", ["olmoe-train"]),
                        ("train_mfu_pct.moe", ["olmoe-train"]),
                        ("expert_matmul_roofline.held", ["zaya1-train"]),
                        ("expert_matmul_roofline.held4", ["glm47f-train"]),
                        ("flash_roofline.mla", ["glm47f-train"]),
                        ("mla_share_pct", ["glm47f-train"]), ("cca_share_pct", ["zaya1-train"])):
        assert mf.by_name(M["per_layer"], name, "metric")["workloads"] == cells
    assert CELL not in mf.by_name(M["per_layer"], "flash_roofline", "metric")["workloads"]
    assert mf.by_name(M["end_to_end"], "train_tok_s", "metric")["workloads"] == CELLS
    assert mf.by_name(M["end_to_end"], "train_tok_s", "metric")["bound"] == 0.01
    assert M["run_seconds"] == 10


def test_step_scopes_gain_two_families_and_keep_the_rest():
    vocabulary = readers_step.vocabulary()
    assert vocabulary["families"]["swa"] == ["swa.qkv", "swa.rope", "swa.attend", "swa.out"]
    assert vocabulary["families"]["gate"] == ["attn.gate", "swa.gate"]
    assert vocabulary["families"]["attn"] == ["attn.qkv", "attn.rope", "attn.attend", "attn.out"]
    assert readers_step.scope_of_path(
        "jit(step)/transpose(jvp(block.stack))/while/body/checkpoint/swa.gate/mul") == "swa.gate"
    assert readers_step.scope_of_path("jit(step)/block.stack/while/body/squeeze") == "block.stack"
    assert readers_step.scope_of_path("jit(step)/block.stack/while/body/checkpoint/add") is None
    assert readers_step.family("swa.attend") == "swa" and readers_step.family("attn.gate") == "gate"


# -- the configuration file against the catalog --------------------------------


def catalog_row():
    if not os.path.exists(CATALOG):
        return None
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next((r for r in rows if r["name"] == "Laguna-S-2.1"), None)


def test_every_published_key_is_the_catalogs_but_the_three_cuts():
    row = catalog_row()
    if row is None:
        pytest.skip("no catalog row of Laguna-S-2.1 in this installation")
    assert SHAPE["source"] == row["source_url"]
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    differ = {k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v}
    assert differ == set(REDUCED)
    assert (SHAPE["num_hidden_layers"], SHAPE["num_experts"], SHAPE["vocab_size"]) == (5, 8, 12544)


def test_every_width_the_issue_names_is_as_published():
    assert (SHAPE["hidden_size"], SHAPE["head_dim"], SHAPE["num_key_value_heads"]) == (3072, 128, 8)
    assert SHAPE["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert SHAPE["layer_types"][:5] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert SHAPE["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert len(SHAPE["layer_types"]) == len(SHAPE["num_attention_heads_per_layer"]) == 48
    assert SHAPE["sliding_window"] == 512 and SHAPE["gating"] == "per-head"
    full, sliding = (SHAPE["rope_parameters"][k] for k in ("full_attention", "sliding_attention"))
    assert (full["rope_type"], full["rope_theta"], full["factor"], full["partial_rotary_factor"],
            full["attention_factor"]) == ("yarn", 500000, 128, 0.5, 1.4852030263919618)
    assert (sliding["rope_type"], sliding["rope_theta"], sliding["partial_rotary_factor"]) == (
        "default", 10000, 1)
    assert (SHAPE["intermediate_size"], SHAPE["moe_intermediate_size"],
            SHAPE["shared_expert_intermediate_size"]) == (12288, 1024, 1024)
    assert (SHAPE["num_experts_per_tok"], SHAPE["published"]["num_experts"],
            SHAPE["moe_routed_scaling_factor"], SHAPE["norm_topk_prob"]) == (10, 256, 2.5, True)


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    dense = SHAPE["mlp_layer_types"][:SHAPE["num_hidden_layers"]].count("dense")
    assert dense == 1 and SHAPE["num_hidden_layers"] - dense == 4      # one whole period
    assert 8 <= SHAPE["num_experts"] < SHAPE["published"]["num_experts"]
    assert SHAPE["vocab_size"] * 8 >= SHAPE["published"]["vocab_size"]
    assert SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["num_experts"] * SHAPE["deployment"]["chips_that_share_a_layer"] == 256
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    assert SHAPE["check"]["scopes"][:4] == ["moe.router", "moe.dispatch", "moe.experts",
                                            "moe.combine"]
    assert {"attn.qkv", "attn.rope", "attn.attend", "attn.gate", "attn.out", "swa.qkv", "swa.rope",
            "swa.attend", "swa.gate", "swa.out", "shared.ffn", "dense.ffn"} == set(
        SHAPE["check"]["scopes"][4:])
    assert 0 < SHAPE["check"]["loss_tol"] < 0.01 and 0 < SHAPE["check"]["routing_tol"] < 0.1
    assert SHAPE["train"]["global_batch"] in (1, 2) and SHAPE["train"]["lr"] == 2.5e-07


# -- the model builder ---------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.n_expert_layers, cfg.first_dense_layers) == (5, 4, 1)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (256, 8, 0, 10)
    assert (cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.dense_d_ff) == (3072, 1024, 1024, 12288)
    assert (cfg.head_dim, cfg.n_kv_heads, cfg.sliding_window) == (128, 8, 512)
    assert cfg.vocab_size == 12544 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.router_score == "softmax" and cfg.routed_scaling == 2.5
    shapes = jax.eval_shape(init, jax.random.key(0))
    period = shapes["layers"]["period"]
    assert period["0"]["wq"].shape == (1, 3072, 72 * 128) and period["3"]["wq"].shape == (
        1, 3072, 48 * 128)
    assert period["0"]["wg"].shape == (1, 3072, 72) and period["0"]["wk"].shape == (1, 3072, 1024)
    assert period["1"]["w_gate"].shape == (1, 8, 3072, 1024)
    assert period["2"]["router"].shape == (1, 3072, 256)
    assert shapes["layers"]["router_bias"].shape == (4, 256) and "tail" not in shapes["layers"]
    assert shapes["dense_layers"]["w_gate"].shape == (1, 3072, 12288)
    assert shapes["dense_layers"]["wq"].shape == (1, 3072, 48 * 128)
    assert shapes["embed"].shape == (12544, 3072) and shapes["lm_head"].shape == (3072, 12544)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # 811.0M parameters: ISSUE 39's table and the file's `memory` line
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() and n == pytest.approx(811.0e6, rel=1e-4)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("head_dim", 64), ("num_key_value_heads", 4),
    ("intermediate_size", 8192), ("moe_intermediate_size", 512),
    ("shared_expert_intermediate_size", 512), ("num_experts_per_tok", 8),
    ("moe_routed_scaling_factor", 1.0), ("sliding_window", 1024), ("gating", True),
    ("norm_topk_prob", False)])
def test_builder_refuses_a_changed_width(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match=key[:8]):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_changed_lists_rotary_and_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="num_experts"):
        builder.build({**SHAPE, "published": {**SHAPE["published"], "num_experts": 128}})
    with pytest.raises(RuntimeError, match="heads_per_layer"):
        builder.build({**SHAPE, "num_attention_heads_per_layer": [48] * 48})
    with pytest.raises(RuntimeError, match="layer_types"):
        builder.build({**SHAPE, "layer_types": ["full_attention"] * 48})
    full = {**SHAPE["rope_parameters"]["full_attention"], "attention_factor": 1.0}
    with pytest.raises(RuntimeError, match="attention_factor"):
        builder.build({**SHAPE, "rope_parameters": {**SHAPE["rope_parameters"],
                                                    "full_attention": full}})
    with pytest.raises(RuntimeError, match="sizes"):
        builder.build({**SHAPE, "moe_router_logit_softcapping": 30})


def _tiny_shape():
    """A configuration file's keys at `laguna-tiny`'s sizes: 4 of 16 experts held."""
    from ray_tpu.models import laguna

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    t = laguna.LAGUNA_TINY
    shape = {k: getattr(t, a) for k, a in {**builder.WIDTHS, **builder.COUNTS}.items()}
    rope = {kind: {k: getattr(r, a) for k, a in builder.ROTARY.items()}
            for kind, r in (("full_attention", t.rope_full), ("sliding_attention", t.rope_sliding))}
    n = t.n_layers
    return {**shape, "registry_model": "laguna-tiny", "rope_parameters": rope,
            "layer_types": list(t.layer_types[:n]),
            "num_attention_heads_per_layer": list(t.heads_per_layer[:n]),
            "mlp_layer_types": ["dense"] + ["sparse"] * (n - 1),
            "moe_router_logit_softcapping": 0, "moe_apply_router_weight_on_input": False,
            "published": {k: shape[k] for k in builder.COUNTS}, "num_experts": 4,
            "deployment": {"first_expert_held": 0},
            "train": {"attention_impl": "xla", "global_batch": 4}}


TINY_TRAFFIC = {"generator": "zipf_tokens", "seq_len": 64, "max_context": 128, "zipf_s": 1.1}


def test_balanced_bias_evens_the_experts_of_every_block_on_the_runs_own_tokens():
    """One fixed rule, no option: from the weights and the batches alone, a
    table [expert layers, experts] under which every block's experts see
    nearer equal numbers of the pairs of FRESH batches than under b = 0."""
    from ray_tpu.models import llama

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    cfg, init, _ = builder.build(_tiny_shape(), attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    make = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)
    assert (builder.PASSES, builder.AVERAGED) == (48, 16)
    assert builder.STEP_LAST < builder.STEP_FIRST <= 0.01
    table = builder.balanced_bias(cfg, params, make)
    assert table.shape == (cfg.n_expert_layers, 16) == (8, 16) and table.dtype == np.float32
    assert not np.asarray(params["layers"]["router_bias"]).any()  # the weights are not touched
    loss = jax.jit(lambda p, b: llama.loss_and_weight_fn(p, b, cfg)[2]["tokens_per_expert"])

    def spread(bias):
        layers = {**params["layers"], "router_bias": jnp.asarray(bias)}
        seen = sum(np.asarray(loss({**params, "layers": layers}, make(i))) for i in range(8))
        return seen.max(-1) / seen.mean(-1)

    imb0, imb1 = spread(np.zeros_like(table)), spread(table)
    assert imb1.mean() < imb0.mean() and imb1.max() < imb0.max()


def test_the_runner_that_carries_the_cell_writes_the_table_where_the_model_reads_it():
    import inspect

    assert SHAPE["runner"] == "train_reference_from_config"
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    src = inspect.getsource(runner)
    assert 'params["layers"]["router_bias"] = ' in src and "builder.balanced_bias(" in src
    assert '"router_bias": np.asarray(_BIAS[0])' in src
    assert '["router_bias"].astype(F32)' in inspect.getsource(laguna_decoder.blocks_of)


def test_runners_loop_starts_the_step_from_the_balanced_table_of_every_block(monkeypatch, tmp_path):
    """The loop itself on the CPU at laguna-tiny's sizes (a dense layer and
    two periods): every step reports the eight expert blocks' rows, top-3
    pairs a token in each, the table the loop started from is kept for the
    reference, which reads it under the same key and meets the step's
    first loss and routing."""
    import gc

    from ray_tpu.train import session

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    base = mf.load_plugin(mf.ROOT, "runners", "train_reference")
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    reports = []
    monkeypatch.setattr(session, "report", reports.append)
    monkeypatch.setattr(runner, "_BASE", base)
    config = {**_tiny_shape(), "model_builder": SHAPE["model_builder"]}
    config["train"] = {**config["train"], "lr": 1e-6}
    runner.train_loop({"root": mf.ROOT, "config": config, "traffic": TINY_TRAFFIC, "seed": 5,
                       "seconds": 0.2, "trace": 0, "out_dir": str(tmp_path)})
    gc.unfreeze()
    assert [r["phase"] for r in reports[:4]] == ["warm"] * 4 and reports[-1]["phase"] == "done"
    table = runner._BIAS[0]
    assert table.shape == (8, 16) and (np.abs(table).max(-1) > 1e-4).all()
    first = np.asarray(reports[-1]["first_counts"])
    pairs = 3 * 4 * 64                                            # top-3 of a batch of 4 x 64
    assert first.shape == (8, 16) and first.sum(-1).tolist() == [pairs] * 8
    for r in reports[:-1]:
        assert r["router"]["dropped_pairs"] == 0 and r["router"]["pairs"] == [pairs] * 8
    cfg, init, _ = builder.build(config, attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    params["layers"]["router_bias"] = jnp.asarray(table)
    batch = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)(0)
    ref = laguna_decoder.loss_parts(params, batch["tokens"], batch["targets"], config)
    assert reports[0]["loss"] == pytest.approx(float(ref["loss"]), rel=0.02)
    moved = np.abs(first - np.asarray(ref["tokens_per_expert"])).sum() // 2
    assert moved <= 0.08 * 8 * pairs


# -- the cost functions, by hand ----------------------------------------------------


def test_required_operations_are_issue_39s_count():
    """1,118 MFLOP a token forward at 1/32 of the pairs held: layer 0 365,
    a sliding layer 170 of which scores 17.7, layer 4 165, the head 77."""
    f = costs_laguna.forward_flops_per_token(SHAPE, 4096, 1 / 32)
    assert sum(f.values()) == pytest.approx(1118.3e6, rel=1e-4)
    d = 3072
    full_attn = 2 * (d * 128 * (2 * 48 + 16) + d * 48)
    swa_attn = 2 * (d * 128 * (2 * 72 + 16) + d * 72)
    assert f["attention"] == 2 * full_attn + 3 * swa_attn
    window_pairs = 512 * 513 / 2 + (4096 - 512) * 512
    assert costs_laguna.visible_pairs(SHAPE, "sliding_attention", 4096) == window_pairs == 1966336
    assert costs_laguna.visible_pairs(SHAPE, "full_attention", 4096) == 4096 * 4097 / 2
    assert costs_laguna.visible_pairs(SHAPE, "sliding_attention", 300) == 300 * 301 / 2
    assert f["scores.window"] == pytest.approx(3 * 4 * 128 * 72 * window_pairs / 4096)
    assert f["scores.window"] / 3 == pytest.approx(17.7e6, rel=2e-3)
    assert f["scores.full"] == pytest.approx(2 * 4 * 128 * 48 * 4097 / 2)
    assert f["dense_ffn"] == 2 * 3 * d * 12288 and f["head"] == 2 * d * 12544
    assert f["router"] == 4 * 2 * d * 256 and f["shared"] == 4 * 2 * 3 * d * 1024
    assert f["routed"] == pytest.approx(4 * 2 * (10 / 32) * 3 * d * 1024)
    assert costs_laguna.train_flops_per_token(SHAPE, 4096, 1 / 32) == 3 * sum(f.values())
    # every pair held: the routed experts at ten a token
    assert costs_laguna.forward_flops_per_token(SHAPE, 4096, 1.0)["routed"] == 4 * 2 * 30 * d * 1024
    assert [k for k, *_ in costs_laguna.layers(SHAPE)].count("sliding_attention") == 3


def test_flash_and_grouped_matmul_costs_by_hand():
    swa = costs_laguna.flash_cost(SHAPE, "sliding_attention", 1, 4096)
    assert swa["layers"] == 3
    assert swa["fwd_flops"] == 3 * 72 * 4 * 128 * 1966336 and swa["bwd_flops"] == 2.5 * swa["fwd_flops"]
    q, kv = 4096 * 72 * 128 * 2, 4096 * 8 * 128 * 2
    assert swa["fwd_bytes"] == 3 * (2 * q + 2 * kv) and swa["bwd_bytes"] == 3 * (4 * q + 4 * kv)
    full = costs_laguna.flash_cost(SHAPE, "full_attention", 1, 4096)
    assert full["layers"] == 2 and full["fwd_flops"] == 2 * 48 * 4 * 128 * (4096 * 4097 / 2)
    # compute-bound either way on a v5e
    for c in (swa, full):
        assert costs.roofline_seconds(c["fwd_flops"], c["fwd_bytes"], PEAKS)[1] == "compute"
    g = costs_laguna.grouped_matmul_cost(SHAPE, 1280)
    assert g["fwd_flops"] == 3 * 2 * 1280 * 3072 * 1024 and g["bwd_flops"] == 2 * g["fwd_flops"]
    assert g["fwd_bytes"] == 3 * 2 * (1280 * 3072 + 1280 * 1024 + 8 * 3072 * 1024)
    # 160 rows an expert: the weights' bytes bound it, not the operations
    assert costs.roofline_seconds(g["fwd_flops"], g["fwd_bytes"], PEAKS)[1] == "memory"


# -- the readers on a hand-built step table ------------------------------------------


def _run(**extra):
    table = {"busy_s": 0.5, "fused_with_optim_s": 0.0, "unknown": {}, "scopes": {
        "swa.attend": {"seconds": 0.060, "ops": {"kernel:swa.attend.30": 0.02,
                                                 "kernel:swa.attend.31": 0.035, "fusion.9": 0.005}},
        "attn.attend": {"seconds": 0.05, "ops": {"kernel:attn.attend.12": 0.05}},
        "swa.qkv": {"seconds": 0.04, "ops": {"fusion.1": 0.04}},
        "swa.gate": {"seconds": 0.01, "ops": {"fusion.2": 0.01}},
        "attn.gate": {"seconds": 0.005, "ops": {"fusion.3": 0.005}},
        "moe.experts": {"seconds": 0.1, "ops": {"fusion.4": 0.1}}}}
    steps = [{"router": {"pairs": [40960] * 4, "pairs_elsewhere": [39680] * 4}}] * 3
    return {"step_table": table, "shape": SHAPE, "traffic": {"seq_len": 4096}, "chips": 1,
            "traced_steps": 3, "peaks": PEAKS, "busy": {"busy_s": 0.5, "window_s": 0.5},
            "trace": object(), "traced_window_steps": steps,
            "ops": {"expert_matmul": {"seconds": 0.02}}, "values": {"train_tok_s": 25000.0},
            **extra}


def test_readers_sum_the_families_and_the_kernels_of_each_kind():
    run = _run()
    assert reader("swa_share_pct").read(run) == pytest.approx(100 * 0.10 / 0.5)
    assert reader("attn_gate_pct").read(run) == pytest.approx(100 * 0.015 / 0.5)
    assert readers_laguna.kernel_seconds(run, "swa.attend") == pytest.approx(0.055)
    swa = costs_laguna.flash_cost(SHAPE, "sliding_attention", 1, 4096)
    least = 3 * 3.5 * swa["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.window").read(run) == pytest.approx(100 * least / 0.055)
    full = costs_laguna.flash_cost(SHAPE, "full_attention", 1, 4096)
    least = 3 * 3.5 * full["fwd_flops"] / PEAKS["bf16_flops_per_s"]
    assert reader("flash_roofline.full48").read(run) == pytest.approx(100 * least / 0.05)
    g = costs_laguna.grouped_matmul_cost(SHAPE, 1280)
    least = 4 * 3 * (g["fwd_bytes"] + g["bwd_bytes"]) / PEAKS["hbm_bytes_per_s"]
    assert reader("expert_matmul_roofline.held10").read(run) == pytest.approx(100 * least / 0.02)
    per_token = costs_laguna.train_flops_per_token(SHAPE, 4096, 1 / 32)
    assert reader("train_mfu_pct.laguna").read(run) == pytest.approx(
        100 * 25000.0 * per_token / PEAKS["bf16_flops_per_s"])


def test_readers_find_nothing_in_another_cells_run_or_a_program_without_the_scopes():
    other = mf.read_json(mf.ROOT, "chipbench/configs/glm-4.7-flash-train.json")
    for name in NEW_METRICS[2:]:
        assert reader(name).read(_run(shape=other)) is None, name
    bare = _run()
    bare["step_table"] = {**bare["step_table"], "scopes": {"moe.experts": {
        "seconds": 0.1, "ops": {"fusion.4": 0.1}}}}
    for name in ("swa_share_pct", "attn_gate_pct", "flash_roofline.window",
                 "flash_roofline.full48"):
        assert reader(name).read(bare) is None, name
    no_share = _run(traced_window_steps=[{"router": {"pairs": [40960] * 4}}] * 3)
    assert reader("expert_matmul_roofline.held10").read(no_share) is None
    assert reader("train_mfu_pct.laguna").read(no_share) is None

"""The glm47f-train cell's files (PR 34): the manifest with the cell (for
however many cells there are), the configuration file against the
catalog's row, the model builder and its balancing rule, the cost
functions by hand-worked cases, each new reader on a hand-built trace
and HLO text, and the reference against per-token loops."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_glm_lite, hlo_scopes, manifest as mf, trace_reduce as tr
from chipbench.reference import glm_lite_decoder

M = mf.load_manifest()
CELL, CONFIG = "glm47f-train", "glm-4.7-flash-train"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ("mla_share_pct", "mla_glue_pct", "flash_roofline.mla",
               "expert_matmul_roofline.held4", "mtp_share_pct", "train_mfu_pct.glm")
SETUP = tuple(m["name"] for m in M["per_layer"] if m["name"].startswith("setup_"))
JOINED = ("compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
          "report_ms.train", "moe_share_pct", "moe_dispatch_pct", "expert_imbalance",
          "experts_elsewhere_pct") + SETUP
PEAKS = costs.load_peaks("TPU v5 lite")
TRAINING_CELLS = [w["name"] for w in M["workloads"]
                  if "train_tok_s" in [e["name"] for e in mf.metrics_of(M, "end_to_end", w["name"])]]


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
    # the driver holds a configuration's `why` to 200 characters; `problems` checks the cells' only
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert SHAPE[key], key
    assert {"param_dtype", "weights", "rotary_pairing", "mtp_merge_order", "mtp_loss_weight",
            "router_bias_update"} <= set(SHAPE["assumed"])
    assert "TO FILL" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED) and len(SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    # their cost functions read `hidden_size / heads`, `num_experts` or every pair: wrong here
    assert not reported & {"flash_roofline", "expert_matmul_roofline", "train_mfu_pct.moe",
                           "train_mfu_pct", "expert_matmul_roofline.held", "train_mfu_pct.zaya"}
    assert len(M["workloads"]) >= 5 and [w["chips"] for w in M["workloads"]].count(4) == 1
    assert M["workloads"][4]["name"] == CELL
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "T/16" in why and "T/2" in why


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] in {e["layer"] for e in M["per_layer"] if e["name"] not in NEW_METRICS}
    assert reader(name).read.__module__ and reader(name).__doc__
    # a program without the scopes or the statistic (the parent): nothing to read, no error
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None


@pytest.mark.parametrize("name", JOINED)
def test_joined_metric_keeps_its_entry_and_its_cells_in_their_order(name):
    """An accepted metric that this cell joins is what it was, with the
    cell appended to its list: however many cells have joined since, the
    list is the manifest's cells in the manifest's order."""
    m = mf.by_name(M["per_layer"], name, "metric")
    cells = [w["name"] for w in M["workloads"]]
    assert CELL in m["workloads"]
    assert m["workloads"] == [c for c in cells if c in m["workloads"]]
    if name in SETUP:
        assert m["moves"] == "setup_s" and m["workloads"] == TRAINING_CELLS == cells
    else:
        assert m["moves"] == "train_tok_s"
    if name in ("moe_share_pct", "moe_dispatch_pct", "expert_imbalance"):
        assert m["workloads"][:2] == ["olmoe-train", "zaya1-train"] and m["layer"] == "expert layer"
    if name == "experts_elsewhere_pct":
        assert m["workloads"][0] == "zaya1-train"


def test_metrics_of_the_other_share_and_of_the_whole_expert_model_stay_theirs():
    for name, cells in (("expert_matmul_roofline", ["olmoe-train"]),
                        ("train_mfu_pct.moe", ["olmoe-train"]),
                        ("expert_matmul_roofline.held", ["zaya1-train"]),
                        ("train_mfu_pct.zaya", ["zaya1-train"]),
                        ("cca_share_pct", ["zaya1-train"]), ("cca_mix_pct", ["zaya1-train"])):
        assert mf.by_name(M["per_layer"], name, "metric")["workloads"] == cells
    assert CELL not in mf.by_name(M["per_layer"], "flash_roofline", "metric")["workloads"]


# -- the configuration file against the catalog --------------------------------

# huggingface.co/zai-org/GLM-4.7-Flash config.json, as the catalog's row gave it when PR 34 read it
ROW_OF_PR34 = {
    "name": "GLM-4.7-Flash",
    "source_url": "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json",
    "config": {"attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 10240, "max_position_embeddings": 202752,
               "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
               "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20,
               "n_group": 1, "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
               "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
               "first_k_dense_replace": 1, "num_hidden_layers": 47, "num_key_value_heads": 20,
               "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
               "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
               "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}}


def catalog_row(path, name):
    """The catalog's row of that name, or None: the catalog lies outside
    the repo and changes under it, and an installation may have none."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next((r for r in rows if r["name"] == name), None)


def keys_that_differ(row):
    """Keys of the configuration file that are not the row's (the
    catalog's where it has one, else the copy above), key by key."""
    row = row or ROW_OF_PR34
    assert SHAPE["source"] == row["source_url"]
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    return {k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v}


@pytest.mark.parametrize("catalog", ["installed", "without_the_row", "with_the_row"])
def test_every_published_key_is_the_catalogs_but_the_three_cuts(catalog, tmp_path):
    path = CATALOG
    if catalog != "installed":
        path = str(tmp_path / "architectures.jsonl")
        rows = [{"name": "another-model", "source_url": "https://example.org", "config": {}}]
        rows += [ROW_OF_PR34] if catalog == "with_the_row" else []
        with open(path, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))
    assert keys_that_differ(catalog_row(path, ROW_OF_PR34["name"])) == set(REDUCED)
    assert (SHAPE["num_hidden_layers"], SHAPE["n_routed_experts"], SHAPE["vocab_size"]) == (
        5, 8, 19456)
    # a row that moves a width is seen, so the comparison is one
    moved = {**ROW_OF_PR34, "config": {**ROW_OF_PR34["config"], "kv_lora_rank": 256}}
    assert keys_that_differ(moved) == set(REDUCED) | {"kv_lora_rank"}


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    dense = SHAPE["first_k_dense_replace"]
    assert dense == 1 and 4 <= SHAPE["num_hidden_layers"] - dense
    assert SHAPE["num_hidden_layers"] < SHAPE["published"]["num_hidden_layers"]
    assert 8 <= SHAPE["n_routed_experts"] < SHAPE["published"]["n_routed_experts"]
    assert SHAPE["vocab_size"] * 8 >= SHAPE["published"]["vocab_size"]
    assert SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["n_routed_experts"] * SHAPE["deployment"]["chips_that_share_a_layer"] == 64
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    assert SHAPE["num_experts_per_tok"] == 4 and SHAPE["routed_scaling_factor"] == 1.8
    assert SHAPE["check"]["scopes"][:4] == ["moe.router", "moe.dispatch", "moe.experts",
                                            "moe.combine"]
    assert {"mla.down", "mla.up", "mla.glue", "mla.attend", "mla.out", "shared.ffn", "mtp.merge",
            "mtp.block", "mtp.head"} == set(SHAPE["check"]["scopes"][4:])
    assert 0 < SHAPE["check"]["loss_tol"] < 0.01 and 0 < SHAPE["check"]["routing_tol"] < 0.1


# -- the model builder ---------------------------------------------------------


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.n_expert_layers, cfg.first_dense_layers, cfg.mtp_layers) == (5, 4, 1, 1)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (64, 8, 0, 4)
    assert (cfg.d_model, cfg.d_ff, cfg.shared_d_ff, cfg.dense_d_ff) == (2048, 1536, 1536, 10240)
    assert (cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (20, 256, 256)
    assert cfg.vocab_size == 19456 and not cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.router_score == "sigmoid" and cfg.mtp_loss_weight == SHAPE["mtp_loss_weight"] == 0.3
    shapes = jax.eval_shape(init, jax.random.key(0))
    assert shapes["layers"]["w_gate"].shape == (4, 8, 2048, 1536)
    assert shapes["layers"]["router"].shape == (4, 2048, 64)
    assert shapes["layers"]["router_bias"].shape == (5, 64)      # the MTP block's row last
    assert shapes["layers"]["wkv_b"].shape == (4, 512, 20 * (192 + 256))
    assert shapes["dense_layers"]["w_gate"].shape == (1, 2048, 10240)
    assert shapes["mtp"]["eh_proj"].shape == (4096, 2048)
    assert shapes["mtp"]["block"]["w_down"].shape == (8, 1536, 2048)
    assert shapes["embed"].shape == (19456, 2048) and shapes["lm_head"].shape == (2048, 19456)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # 706.9M parameters: what the file's `memory` line says
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() and n == pytest.approx(706.9e6, rel=1e-3)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 4096), ("moe_intermediate_size", 1024), ("intermediate_size", 8192),
    ("num_attention_heads", 16), ("q_lora_rank", 1536), ("kv_lora_rank", 256),
    ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 32), ("v_head_dim", 128),
    ("num_experts_per_tok", 8), ("routed_scaling_factor", 2.5), ("n_shared_experts", 2),
    ("first_k_dense_replace", 3), ("num_nextn_predict_layers", 0), ("rope_theta", 10000)])
def test_builder_refuses_a_changed_width(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="shared_width" if key == "n_shared_experts" else key[:8]):
        builder.build({**SHAPE, key: value})


def test_builder_refuses_a_registry_entry_that_is_not_at_the_published_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="n_routed_experts"):
        builder.build({**SHAPE, "published": {**SHAPE["published"], "n_routed_experts": 128}})
    with pytest.raises(RuntimeError, match="sizes"):
        builder.build({**SHAPE, "n_group": 8})


def _tiny_shape():
    """A configuration file's keys at `glm-lite-tiny`'s sizes: 4 of 8 experts held."""
    from ray_tpu.models import mla

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    t = mla.GLM_LITE_TINY
    shape = {k: getattr(t, a) for k, a in {**builder.WIDTHS, **builder.COUNTS}.items()}
    return {**shape, "registry_model": "glm-lite-tiny", "n_shared_experts": 1, "n_group": 1,
            "mtp_loss_weight": 0.3, "published": {k: shape[k] for k in builder.COUNTS},
            "n_routed_experts": 4, "deployment": {"first_expert_held": 0},
            "train": {"attention_impl": "xla", "global_batch": 4}}


TINY_TRAFFIC = {"generator": "zipf_tokens", "seq_len": 64, "max_context": 128, "zipf_s": 1.1}


def test_balanced_bias_evens_the_experts_of_every_block_on_the_runs_own_tokens():
    """One fixed rule, no option: from the weights and the batches alone,
    a table [expert layers + 1, experts], the MTP block's row last, under
    which every block's experts see nearer equal numbers of the pairs of
    FRESH batches of that traffic than under b = 0, and the held half
    nearer half."""
    from ray_tpu.models import llama

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    cfg, init, _ = builder.build(_tiny_shape(), attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    make = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)
    assert (builder.PASSES, builder.AVERAGED) == (48, 16)
    assert builder.STEP_LAST < builder.STEP_FIRST <= 0.1
    table = builder.balanced_bias(cfg, params, make)
    assert table.shape == (cfg.n_expert_layers + 1, 8) == (3, 8) and table.dtype == np.float32
    assert not np.asarray(params["layers"]["router_bias"]).any()  # the weights are not touched

    def spread(bias):
        layers = {**params["layers"], "router_bias": jnp.asarray(bias)}
        seen = sum(np.asarray(llama.loss_and_weight_fn(
            {**params, "layers": layers}, make(i), cfg)[2]["tokens_per_expert"]) for i in range(8))
        return seen.max(-1) / seen.mean(-1), seen[:, :4].sum(-1) / seen.sum(-1)

    (imb0, held0), (imb1, held1) = spread(np.zeros_like(table)), spread(table)
    assert (imb1 < imb0).all() and imb1.max() < 1.25 < imb0.max()
    assert np.abs(held1 - 0.5).max() < 0.05


def test_the_runner_that_carries_the_cell_writes_the_table_where_the_model_reads_it():
    """The cell runs through runners/train_reference_from_config.py as it
    stands: its seams are the builder's `balanced_bias`, the parameter
    `layers.router_bias` and the file's `check`; the reference reads the
    same table under the same key, the MTP block's row with it."""
    import inspect

    assert SHAPE["runner"] == "train_reference_from_config"
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    src = inspect.getsource(runner)
    assert 'params["layers"]["router_bias"] = ' in src and "builder.balanced_bias(" in src
    assert '"router_bias": np.asarray(_BIAS[0])' in src
    assert "router_bias\"].astype(F32)" in inspect.getsource(glm_lite_decoder.sequence)


def test_runners_loop_starts_the_step_from_the_balanced_table_of_every_block(monkeypatch, tmp_path):
    """The loop itself on the CPU at glm-lite-tiny's sizes: the first
    step's routing is the balanced one in the two expert layers AND in the
    MTP block, every step reports the three rows, top-2 pairs a token in
    each, and the table the loop started from is kept for the reference,
    which reads it under the same key and meets the step's first loss and
    routing."""
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
    assert table.shape == (3, 8) and (np.abs(table).max(-1) > 1e-3).all()
    first = np.asarray(reports[-1]["first_counts"])
    assert first.shape == (3, 8) and (first.max(-1) / first.mean(-1)).max() < 1.5
    pairs = 2 * 4 * 64                                            # top-2 of a batch of 4 x 64
    for r in reports[:-1]:
        assert r["router"]["dropped_pairs"] == 0 and r["router"]["pairs"] == [pairs] * 3
        assert len(r["router"]["pairs_elsewhere"] if "pairs_elsewhere" in r["router"] else [0] * 3) == 3
    # the reference, given the table as the runner gives it, is the step's first loss and routing
    cfg, init, _ = builder.build(config, attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    params["layers"]["router_bias"] = jnp.asarray(table)
    batch = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)(0)
    ref = glm_lite_decoder.loss_parts(params, batch["tokens"], batch["targets"], config)
    assert reports[0]["loss"] == pytest.approx(float(ref["loss"]), rel=0.02)
    moved = np.abs(first - np.asarray(ref["tokens_per_expert"])).sum() // 2
    assert moved <= 0.05 * 3 * pairs


# -- cost functions, by hand ---------------------------------------------------


def test_matmul_params_by_hand():
    p = costs_glm_lite.matmul_params(SHAPE)
    # q down 1.573M, q up 3.932M, kv down 1.180M, kv up 4.588M, out 10.486M
    assert p["mla"] == (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
                        + 5120 * 2048) == 21_757_952
    assert p["router"] == 2048 * 64 and p["shared"] == p["expert"] == 3 * 2048 * 1536
    assert p["dense_ffn"] == 3 * 2048 * 10240 and p["merge"] == 4096 * 2048
    assert p["head"] == 2048 * 19456
    assert costs_glm_lite.blocks(SHAPE) == {"dense": 1, "expert": 5, "mtp": 1, "attention": 6}


def test_train_flops_per_token_by_hand():
    # forward, MFLOP a token: MLA's projections 43.5 + causal scores 20 x 4 x 256 x 4097 / 2 =
    # 41.96 in each of six blocks; the dense SwiGLU 125.8; in each of five expert blocks the
    # router 0.26, the shared expert 18.87 and four experts of 18.87 x the share held; the
    # merge 16.8; the head 79.7, twice
    parts = costs_glm_lite.forward_flops_per_token(SHAPE, 4096, 0.125)
    assert parts["mla"] == pytest.approx(6 * (2 * 21_757_952 + 20 * 4 * 256 * 4097 / 2))
    assert parts["dense_ffn"] == 2 * 3 * 2048 * 10240
    assert parts["router"] == 5 * 2 * 2048 * 64 and parts["shared"] == 5 * 2 * 9_437_184
    assert parts["routed"] == pytest.approx(5 * 2 * 0.125 * 4 * 9_437_184)
    assert parts["merge"] == 2 * 4096 * 2048 and parts["head"] == 2 * 2 * 2048 * 19456
    forward = sum(parts.values())
    assert forward == pytest.approx(957e6, rel=2e-3)             # ISSUE 34: 957 MFLOP a token
    assert costs_glm_lite.train_flops_per_token(SHAPE, 4096, 0.125) == pytest.approx(3 * forward)
    assert parts["mla"] / forward == pytest.approx(0.54, abs=0.01)    # MLA: 54% of it
    assert parts["head"] / forward == pytest.approx(0.17, abs=0.01)   # the heads: 17%
    mtp = (parts["mla"] / 6 + (parts["router"] + parts["shared"] + parts["routed"]) / 5
           + parts["merge"] + parts["head"] / 2)
    assert mtp / forward == pytest.approx(0.22, abs=0.01)             # MTP: 22%
    # with every pair held the routed experts count whole
    assert costs_glm_lite.forward_flops_per_token(SHAPE, 4096, 1.0)["routed"] == 5 * 2 * 4 * 9_437_184


def test_flash_cost_by_hand():
    c = costs_glm_lite.flash_cost(SHAPE, 2, 4096)
    pairs = 4096 * 4097 / 2
    assert c["fwd_flops"] == 2 * 20 * 4 * 256 * pairs and c["bwd_flops"] == 2.5 * c["fwd_flops"]
    one = 2 * 4096 * 20 * 256 * 2                                 # one of Q, K, V, O: 84 MB
    assert c["fwd_bytes"] == 4 * one and c["bwd_bytes"] == 8 * one
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    assert bound == "compute" and least == pytest.approx(3.5 * c["fwd_flops"] / 197e12)
    # costs.flash_cost would read heads of 2048 / 20 = 102 here: why the cell is not on its list
    assert costs.head_dim(SHAPE) == 102


def test_grouped_matmul_cost_by_hand():
    c = costs_glm_lite.grouped_matmul_cost(SHAPE, 4096)
    one = 2 * 4096 * 2048 * 1536                                  # 25.8 GFLOP a matmul
    assert c["fwd_flops"] == 3 * one and c["bwd_flops"] == 6 * one
    moved = 2 * (4096 * 2048 + 4096 * 1536 + 8 * 2048 * 1536)
    assert c["fwd_bytes"] == 3 * moved and c["bwd_bytes"] == 6 * moved
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    assert bound == "compute" and least == pytest.approx(9 * one / 197e12)
    assert costs_glm_lite.grouped_matmul_cost(SHAPE, 0)["fwd_flops"] == 0


# -- the readers, on a hand-built trace and HLO text ---------------------------

HLO = """
HloModule jit_step

ENTRY %main {
  %fusion.1 = bf16[8,4]{1,0} fusion(%x, %w), kind=kOutput, calls=%d0, metadata={op_name="jit(step)/jvp(mla.down)/bsd,dr->bsr/dot_general"}
  %fusion.2 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/mla.glue/concatenate" stack_frame_id=3}
  %fusion.3 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mla.glue/mul"}
  %fusion.4 = bf16[8,16]{1,0} fusion(%x, %w), kind=kOutput, calls=%d, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/mla.up/bsr,rhk->bhsk/dot_general"}
  %mla.attend.30 = bf16[8,4]{1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/mla.attend/pallas_call"}
  %mla.attend.26 = bf16[8,4]{1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mtp.block)/mla.attend/pallas_call"}
  %fusion.5 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/transpose(jvp(mtp.block))/jvp(mtp.block)/checkpoint/mla.glue/add_any"}
  %ragged-dot-tiled.39 = bf16[16,4]{1,0} custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mtp.block)/moe.experts/ragged-dot-tiled"}
  %ragged-dot-tiled.40 = bf16[16,4]{1,0} custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.experts/ragged-dot-tiled"}
  %fusion.6 = bf16[8,4]{1,0} fusion(%e, %h), kind=kOutput, calls=%f4, metadata={op_name="jit(step)/jvp(mtp.merge)/bsd,de->bse/dot_general"}
  %fusion.7 = f32[8,19456]{1,0} fusion(%h, %w2), kind=kOutput, calls=%e2, metadata={op_name="jit(step)/jvp(mtp.head)/dot_general"}
  %fusion.8 = bf16[8,4]{1,0} fusion(%x, %w), kind=kOutput, calls=%s, metadata={op_name="jit(step)/jvp()/while/body/closed_call/shared.ffn/bsd,df->bsf/dot_general"}
  %sort.2 = (s32[16], s32[16]) sort(%k, %v), metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/moe.dispatch/sort"}
  ROOT %fusion.166 = f32[8,19456]{1,0} fusion(%h, %w2), kind=kOutput, calls=%e, metadata={op_name="jit(step)/jvp()/dot_general"}
}
"""


def _traced_run(with_scopes=True, elsewhere=True):
    """Three steps of one second on one device. A step: 0.02 down, 0.01 +
    0.02 glue, 0.03 up and a flash kernel of 0.05 in the layers before
    the MTP block; in that block a flash kernel of 0.04, 0.01 of glue and
    a grouped matmul of 0.03; the merge 0.02 and the second head pass
    0.1; the shared expert 0.02, a sort 0.01 and a grouped matmul of 0.06
    in the expert layers; 0.3 of the head."""
    ops, host = [], [["main", "chipbench.window", 0.0, 3.0]]
    for s in (0.0, 1.0, 2.0):
        ops += [["fusion.1", s, 0.02], ["fusion.2", s + 0.02, 0.01], ["fusion.3", s + 0.03, 0.02],
                ["fusion.4", s + 0.05, 0.03], ["kernel:mla.attend.30", s + 0.08, 0.05],
                ["kernel:mla.attend.26", s + 0.13, 0.04], ["fusion.5", s + 0.17, 0.01],
                ["kernel:ragged-dot-tiled.39", s + 0.18, 0.03], ["fusion.6", s + 0.21, 0.02],
                ["fusion.7", s + 0.23, 0.1], ["fusion.8", s + 0.33, 0.02],
                ["sort.2", s + 0.35, 0.01], ["kernel:ragged-dot-tiled.40", s + 0.36, 0.06],
                ["fusion.166", s + 0.5, 0.3]]
    trace = tr.from_dict({"device_ops": {"/device:TPU:0": ops}, "host": host})
    win, rules = tr.window(trace), mf.trace_names(mf.ROOT)["rules"]
    scopes = hlo_scopes.scopes_of(HLO, tuple(SHAPE["check"]["scopes"]))
    # five expert blocks a step: 32768 pairs a block, of which this chip holds about an eighth
    steps = [{"router": {"imbalance": [x] * 5, "pairs": [32768] * 5,
                         **({"pairs_elsewhere": [32768 - held] * 5} if elsewhere else {})}}
             for x, held in ((3.0, 4200), (1.5, 4000), (2.5, 4096))]
    return {
        "trace": trace, "win": win, "rules": rules, "busy": tr.busy(trace, win),
        "ops": tr.class_time(trace.device_ops, rules, win),
        "scopes": {k: v for k, v in scopes.items() if v.startswith("moe.")} if with_scopes else None,
        "cca_scopes": ({k: v for k, v in scopes.items() if not v.startswith("moe.")}
                       if with_scopes else None),
        "shape": SHAPE, "traffic": {"seq_len": 4096}, "peaks": PEAKS, "chips": 1,
        "traced_steps": 3, "tokens_per_step": 8192, "traced_window_steps": steps,
        "values": {"train_tok_s": 28000.0},
    }


def test_an_instruction_reads_the_outermost_listed_scope_of_its_path():
    scopes = hlo_scopes.scopes_of(HLO, tuple(SHAPE["check"]["scopes"]))
    assert scopes["mla.attend.30"] == "mla.attend" and scopes["mla.attend.26"] == "mtp.block"
    assert scopes["fusion.5"] == "mtp.block" and scopes["ragged-dot-tiled.39"] == "mtp.block"
    assert scopes["fusion.1"] == "mla.down" and scopes["fusion.8"] == "shared.ffn"
    assert "fusion.166" not in scopes


def test_scope_readers_count_kernels_with_the_scope_they_were_called_in():
    run = _traced_run()
    busy = run["busy"]["busy_s"]
    assert busy == pytest.approx(3 * 0.72)
    got = __import__("chipbench.readers_glm_lite", fromlist=["x"]).seconds_by_scope(run)
    assert got["mla.attend"] == pytest.approx(3 * 0.05) and got["mtp.block"] == pytest.approx(3 * 0.08)
    mla = 3 * (0.02 + 0.01 + 0.02 + 0.03 + 0.05)
    assert reader("mla_share_pct").read(run) == pytest.approx(100 * mla / busy)
    assert reader("mla_glue_pct").read(run) == pytest.approx(100 * 3 * 0.03 / busy)
    mtp = 3 * (0.04 + 0.01 + 0.03 + 0.02 + 0.1)
    assert reader("mtp_share_pct").read(run) == pytest.approx(100 * mtp / busy)
    # the expert layer's readers, as they stand: the scoped ops of the layers before the MTP
    # block (the sort) and every grouped-matmul kernel of the step
    assert reader("moe_dispatch_pct").read(run) == pytest.approx(100 * 3 * 0.01 / busy)
    assert reader("moe_share_pct").read(run) == pytest.approx(100 * 3 * (0.01 + 0.09) / busy)
    for name in ("mla_share_pct", "mla_glue_pct", "mtp_share_pct"):
        assert reader(name).read(_traced_run(with_scopes=False)) is None


def test_kernel_rooflines_and_the_shares_mfu():
    run = _traced_run()
    assert run["ops"]["flash"]["seconds"] == pytest.approx(3 * 0.09)
    assert run["ops"]["expert_matmul"]["seconds"] == pytest.approx(3 * 0.09)
    c = costs_glm_lite.flash_cost(SHAPE, 2, 4096)
    least = 6 * 3 * (c["fwd_flops"] + c["bwd_flops"]) / 197e12          # six calls a step
    assert reader("flash_roofline.mla").read(run) == pytest.approx(100 * least / 0.27)
    # medians of the traced steps, summed over the five blocks: 5 x 4096 of 5 x 32768
    assert reader("experts_elsewhere_pct").read(run) == pytest.approx(87.5)
    least = 3 * 5 * 9 * 2 * 4096 * 2048 * 1536 / 197e12                  # compute-bound
    assert reader("expert_matmul_roofline.held4").read(run) == pytest.approx(100 * least / 0.27)
    want = 100 * 28000.0 * costs_glm_lite.train_flops_per_token(SHAPE, 4096, 0.125) / 197e12
    assert reader("train_mfu_pct.glm").read(run) == pytest.approx(want) and 30 < want < 50
    assert reader("expert_imbalance").read(run) == pytest.approx(2.5)
    # a program with no such statistic (the parent), or another model's file: nothing to read
    old = _traced_run(elsewhere=False)
    for name in ("expert_matmul_roofline.held4", "train_mfu_pct.glm"):
        assert reader(name).read(old) is None
    other = {**run, "shape": {"hidden_size": 2048}}
    for name in ("flash_roofline.mla", "expert_matmul_roofline.held4", "train_mfu_pct.glm"):
        assert reader(name).read(other) is None


# -- the reference against per-token loops -------------------------------------


def _tiny_layer(seed=0, d=16, f=12, e=6, held=3, h=2, rq=6, rkv=8, dn=6, dr=2, dv=8):
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[0]), jnp.float32)  # noqa: E731
    lp = {"ln1": 1 + 0.1 * w(d), "ln2": 1 + 0.1 * w(d), "wq_a": w(d, rq), "q_a_norm": 1 + 0.1 * w(rq),
          "wq_b": w(rq, h * (dn + dr)), "wkv_a": w(d, rkv + dr), "kv_a_norm": 1 + 0.1 * w(rkv),
          "wkv_b": w(rkv, h * (dn + dv)), "wo": w(h * dv, d), "router": w(d, e),
          "router_bias": 0.2 * w(e), "shared_gate": w(d, f), "shared_up": w(d, f),
          "shared_down": w(f, d), "w_gate": w(held, d, f), "w_up": w(held, d, f),
          "w_down": w(held, f, d)}
    shape = {"num_attention_heads": h, "rms_norm_eps": 1e-5, "kv_lora_rank": rkv,
             "qk_nope_head_dim": dn, "qk_rope_head_dim": dr, "v_head_dim": dv, "rope_theta": 100.0,
             "num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1.8,
             "n_routed_experts": held, "published": {"n_routed_experts": e},
             "deployment": {"first_expert_held": 1}}
    return lp, shape


def test_reference_mla_equals_a_per_token_loop():
    """Query t against keys 0 .. t, one head at a time, the ONE rotary key
    a token rotated by its position and read by every head, the rotary
    pairing channel i with i + d_r / 2."""
    lp, shape = _tiny_layer()
    s, d = 7, 16
    h = jnp.asarray(np.random.default_rng(1).normal(size=(s, d)), jnp.float32)
    got = np.asarray(glm_lite_decoder.mla(h, lp, shape), np.float64)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    nh, rkv, dn, dr, dv = 2, 8, 6, 2, 8

    def rms(x, g):
        return x / np.sqrt((x * x).mean() + 1e-5) * f(g)

    def rope(x, t):  # x [d_r]
        half = dr // 2
        ang = t / (100.0 ** (np.arange(0, dr, 2) / dr))
        return np.concatenate([x[:half] * np.cos(ang) - x[half:] * np.sin(ang),
                               x[half:] * np.cos(ang) + x[:half] * np.sin(ang)])

    q, k, v = np.zeros((s, nh, dn + dr)), np.zeros((s, nh, dn + dr)), np.zeros((s, nh, dv))
    for t in range(s):
        x = rms(f(h[t]), lp["ln1"])
        qt = (rms(x @ f(lp["wq_a"]), lp["q_a_norm"]) @ f(lp["wq_b"])).reshape(nh, dn + dr)
        a = x @ f(lp["wkv_a"])
        kv = (rms(a[:rkv], lp["kv_a_norm"]) @ f(lp["wkv_b"])).reshape(nh, dn + dv)
        for j in range(nh):
            q[t, j] = np.concatenate([qt[j, :dn], rope(qt[j, dn:], t)])
            k[t, j] = np.concatenate([kv[j, :dn], rope(a[rkv:], t)])
            v[t, j] = kv[j, dn:]
    want = f(h).copy()
    for t in range(s):
        heads = []
        for j in range(nh):
            sc = np.array([q[t, j] @ k[u, j] for u in range(t + 1)]) / np.sqrt(dn + dr)
            p = np.exp(sc - sc.max())
            heads.append((p / p.sum()) @ v[:t + 1, j])
        want[t] += np.concatenate(heads) @ f(lp["wo"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_reference_router_and_experts_equal_a_per_token_loop():
    """Sigmoid scores, the top-2 of score + bias, the chosen scores
    renormalised and scaled; experts 1-3 of 6 held: a pair on another
    expert adds nothing; the shared expert for every token."""
    lp, shape = _tiny_layer()
    s, d = 9, 16
    h = jnp.asarray(np.random.default_rng(2).normal(size=(s, d)), jnp.float32)
    out, chosen = glm_lite_decoder.experts(h, lp, shape)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    want, picked = f(h).copy(), np.zeros((s, 6), bool)
    for t in range(s):
        x = f(h[t]) / np.sqrt((f(h[t]) ** 2).mean() + 1e-5) * f(lp["ln2"])
        score = 1 / (1 + np.exp(-(x @ f(lp["router"]))))
        top = np.argsort(-(score + f(lp["router_bias"])))[:2]
        picked[t, top] = True
        for e in top:
            if 1 <= e < 4:
                w = 1.8 * score[e] / score[top].sum()
                want[t] += w * ((silu(x @ f(lp["w_gate"][e - 1])) * (x @ f(lp["w_up"][e - 1])))
                                @ f(lp["w_down"][e - 1]))
        want[t] += (silu(x @ f(lp["shared_gate"])) * (x @ f(lp["shared_up"]))) @ f(lp["shared_down"])
    np.testing.assert_allclose(np.asarray(out, np.float64), want, rtol=2e-4, atol=2e-5)
    assert np.asarray(chosen).tolist() == picked.tolist() and picked.sum() == 2 * s
    assert (picked[:, [0, 4, 5]]).any()  # some pairs were routed elsewhere


def test_reference_imports_nothing_from_the_program():
    import chipbench.reference.glm_lite_decoder as mod

    src = open(mod.__file__).read()
    code = src.split('"""', 2)[2]  # past the module's docstring
    assert "ray_tpu" not in code and "import chipbench" not in code
    assert 'default_matmul_precision("highest")' in code

"""The zaya1-train cell's files (PR 32): the manifest with the cell, the
configuration file against the catalog's row, the model builder, the
cost functions by hand-worked cases, each new reader on a hand-built
trace and HLO text, the runner that reads its constants from the
configuration file, the reference against per-token loops, and run.py
without a chip."""

import json
import math
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_zaya, hlo_scopes, manifest as mf, trace_reduce as tr
from chipbench.reference import zaya_decoder

M = mf.load_manifest()
CELL, CONFIG = "zaya1-train", "zaya1-8b-train"
SHAPE = mf.read_json(mf.ROOT, f"chipbench/configs/{CONFIG}.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ("cca_share_pct", "cca_mix_pct", "expert_matmul_roofline.held",
               "train_mfu_pct.zaya", "experts_elsewhere_pct")
STARTUP = ("setup_interp_s.train", "setup_import_s.train", "setup_import_program_s.train",
           "setup_backend_s.train", "setup_init_params_s.train", "setup_first_step_s.train",
           "setup_warm_steps_s.train", "setup_unnamed_s.train")
JOINED = ("compiles_in_window.train", "flash_roofline", "device_idle_pct.train",
          "hbm_peak_gib.train", "setup_compile_s.train", "setup_cache_misses.train",
          "setup_runtime_s.train", "report_ms.train", "moe_share_pct", "moe_dispatch_pct",
          "expert_imbalance") + STARTUP
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
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference", "check"):
        assert SHAPE[key], key
    assert {"param_dtype", "weights"} <= set(SHAPE["assumed"])
    assert "TO FILL" not in json.dumps(SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED)
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}
    # what counts every pair and `intermediate_size` would read this cell at twice the truth
    assert not reported & {"expert_matmul_roofline", "train_mfu_pct.moe", "train_mfu_pct"}
    assert len(M["workloads"]) == 4 and [w["chips"] for w in M["workloads"]].count(4) == 1
    assert "T/16" in cell["cell"]["why"] and "twice its share" in cell["cell"]["why"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s" and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] in {e["layer"] for e in M["per_layer"] if e["name"] not in NEW_METRICS} \
        or m["layer"] == "attention"
    assert reader(name).read.__module__ and reader(name).__doc__


@pytest.mark.parametrize("name", ["moe_share_pct", "moe_dispatch_pct", "expert_imbalance"])
def test_expert_layer_metric_is_reported_by_both_expert_cells(name):
    """What test_chipbench_olmoe.py holds for `olmoe-train` alone (skipped
    by conftest.py, which says why): the entry is what it was, with this
    cell appended to its list."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == ["olmoe-train", CELL] and m["moves"] == "train_tok_s"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["layer"] == "expert layer" and reader(name).__doc__
    for other in ("expert_matmul_roofline", "train_mfu_pct.moe"):
        assert mf.by_name(M["per_layer"], other, "metric")["workloads"] == ["olmoe-train"]


@pytest.mark.parametrize("name", STARTUP)
def test_startup_phase_metric_lists_every_training_cell(name):
    """What test_chipbench_manifest.py holds for the three cells of PR 31
    (skipped by conftest.py, which says why), for however many there are."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert (m["moves"], m["unit"], m["better"], m["source"]) == (
        "setup_s", "s", "lower", "host_clock")
    assert m["workloads"] == TRAINING_CELLS
    assert TRAINING_CELLS == ["m7b-train", "m7b-train-4chip", "olmoe-train", CELL]


# huggingface.co/Zyphra/ZAYA1-8B config.json, as the catalog's row gave it when PR 32 read it
ROW_OF_PR32 = {
    "name": "ZAYA1-8B",
    "source_url": "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json",
    "config": {"attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
               "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
               "lm_head_bias": False, "max_position_embeddings": 131072, "model_type": "zaya",
               "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts": 16,
               "num_experts_per_tok": 1, "num_hidden_layers": 40, "num_key_value_heads": 2,
               "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
               "rope_parameters": {
                   "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                              "rope_type": "default"},
                   "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                                      "rope_type": "default"},
                   "rope_type": "default"},
               "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True,
               "vocab_size": 262272}}


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
    row = row or ROW_OF_PR32
    assert SHAPE["source"] == row["source_url"]
    assert SHAPE["published"] == {k: row["config"][k] for k in REDUCED}
    return {k for k, v in row["config"].items() if SHAPE.get(k, "absent") != v}


@pytest.mark.parametrize("catalog", ["installed", "without_the_row", "with_the_row"])
def test_every_published_key_is_the_catalogs_but_the_three_cuts(catalog, tmp_path):
    path = CATALOG
    if catalog != "installed":
        path = str(tmp_path / "architectures.jsonl")
        rows = [{"name": "another-model", "source_url": "https://example.org", "config": {}}]
        rows += [ROW_OF_PR32] if catalog == "with_the_row" else []
        with open(path, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))
    assert keys_that_differ(catalog_row(path, ROW_OF_PR32["name"])) == set(REDUCED)
    assert (SHAPE["num_hidden_layers"], SHAPE["num_experts"], SHAPE["vocab_size"]) == (6, 8, 32896)
    # a row that moves a width is seen, so the comparison is one
    moved = {**ROW_OF_PR32, "config": {**ROW_OF_PR32["config"], "router_hidden_size": 512}}
    assert keys_that_differ(moved) == set(REDUCED) | {"router_hidden_size"}


def test_the_cuts_keep_to_the_guides_floors_and_name_no_width():
    assert 4 <= SHAPE["num_hidden_layers"] < SHAPE["published"]["num_hidden_layers"]
    assert 8 <= SHAPE["num_experts"] < SHAPE["published"]["num_experts"]
    assert SHAPE["vocab_size"] * 8 >= SHAPE["published"]["vocab_size"]
    assert SHAPE["vocab_size"] % 128 == 0
    assert SHAPE["num_experts"] * SHAPE["deployment"]["chips_that_share_a_layer"] == 16
    assert not [k for k in REDUCED if mf.WIDTH_KEYS.search(k)]
    assert SHAPE["num_experts_per_tok"] == 1 and SHAPE["router_hidden_size"] == 256


def test_builder_builds_the_share_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.n_experts, cfg.n_held, cfg.first_expert_held, cfg.top_k) == (
        6, 16, 8, 0, 1)
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 2048, 8, 2, 128)
    assert cfg.vocab_size == 32896 and cfg.tie_embeddings and cfg.attention_impl == "flash"
    assert cfg.router_kind == "mlp" and cfg.router_hidden == 256 and cfg.conv_kernels == (2, 2)
    shapes = jax.eval_shape(init, jax.random.key(0))
    assert shapes["layers"]["w_gate"].shape == (6, 8, 2048, 2048)
    assert shapes["layers"]["router_w3"].shape == (6, 256, 16)
    assert shapes["layers"]["conv1"].shape == (6, 2, 10, 128, 128)
    assert shapes["embed"].shape == (32896, 2048) and "lm_head" not in shapes
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # 708.8M parameters: what the file's `memory` line says
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg.num_params() and n == pytest.approx(708.8e6, rel=1e-3)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 4096), ("moe_intermediate_size", 1024), ("head_dim", 64),
    ("num_attention_heads", 16), ("num_key_value_heads", 8), ("router_hidden_size", 128),
    ("num_experts_per_tok", 2), ("cca_time1", 3), ("partial_rotary_factor", 1.0)])
def test_builder_refuses_a_changed_width(key, value):
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match=key[:8]):
        builder.build({**SHAPE, key: value})


def test_builder_hands_out_the_programs_own_init_and_a_zero_bias():
    """The builder's init is llama.init_params of the share and takes
    nothing but a key (so its program is one for every seed); nothing of
    the traffic or of a balanced bias reaches it through the file."""
    import dataclasses

    from ray_tpu.models import cca, llama

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    assert not [k for k in SHAPE if "bias" in k and k not in ("attention_bias", "lm_head_bias")]
    cfg, init, _ = builder.build(SHAPE)
    assert init.__code__.co_argcount == 1
    tiny = dataclasses.replace(cca.ZAYA_TINY, experts_held=2)
    params = llama.init_params(tiny, jax.random.key(7))
    assert not np.asarray(params["layers"]["router_bias"]).any()
    assert jax.eval_shape(init, jax.random.key(7))["layers"]["router_bias"].shape == (6, 16)


def _tiny_shape():
    """A configuration file's keys at `zaya-tiny`'s sizes: 2 of 4 experts held."""
    from ray_tpu.models import cca

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    t = cca.ZAYA_TINY
    shape = {k: getattr(t, a) for k, a in {**builder.WIDTHS, **builder.COUNTS}.items()}
    return {**shape, "registry_model": "zaya-tiny", "cca_time0": 2, "cca_time1": 2,
            "rope_parameters": {"hybrid": {"rope_theta": t.rope_theta}},
            "published": {k: shape[k] for k in builder.COUNTS}, "num_experts": 2,
            "deployment": {"first_expert_held": 0},
            "train": {"attention_impl": "xla", "global_batch": 4}}


TINY_TRAFFIC = {"generator": "zipf_tokens", "seq_len": 64, "max_context": 128, "zipf_s": 1.1}


def test_balanced_bias_evens_the_experts_on_the_runs_own_tokens():
    """One fixed rule, no option: from the weights and the batches alone,
    a table [layers, experts] under which every layer's experts see
    nearer equal numbers of FRESH batches of that traffic than under
    b = 0, and the held half nearer half."""
    from ray_tpu.models import llama

    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    gen = mf.load_plugin(mf.ROOT, "generators", "zipf_tokens")
    cfg, init, _ = builder.build(_tiny_shape(), attention_impl="xla")
    params = jax.jit(init)(jax.random.key(5))
    make = gen.batch_fn(TINY_TRAFFIC, cfg.vocab_size, 4, 5)
    assert (builder.PASSES, builder.AVERAGED) == (48, 16)
    assert builder.STEP_LAST < builder.STEP_FIRST <= 1 / 16
    table = builder.balanced_bias(cfg, params, make)
    assert table.shape == (3, 4) and table.dtype == np.float32
    assert not np.asarray(params["layers"]["router_bias"]).any()  # the weights are not touched

    def spread(bias):
        layers = {**params["layers"], "router_bias": jnp.asarray(bias)}
        seen = sum(np.asarray(llama.loss_and_weight_fn(
            {**params, "layers": layers}, make(i), cfg)[2]["tokens_per_expert"]) for i in range(8))
        return seen.max(-1) / seen.mean(-1), seen[:, :2].sum(-1) / seen.sum(-1)

    (imb0, held0), (imb1, held1) = spread(np.zeros_like(table)), spread(table)
    assert (imb1 < imb0).all() and imb1.max() < 1.25 < imb0.max()
    assert np.abs(held1 - 0.5).max() < 0.05 < np.abs(held0 - 0.5).max()


def test_runners_loop_is_the_base_runners_with_the_balancing_added():
    """The copy stays a copy: the loop of runners/train_reference.py,
    line for line, plus the lines that set the balanced bias and the
    two that rest the garbage collector before the window."""
    def loop(name):
        src = open(os.path.join(mf.ROOT, f"chipbench/runners/{name}.py")).read()
        return src[src.index("def train_loop("):src.index("def run(ctx")].rstrip().split("\n")

    base, mine = loop("train_reference"), loop(SHAPE["runner"])
    added = [line for line in mine if line.endswith("# balanced")]
    steady = [line.strip() for line in mine if line.endswith("# steady")]
    assert len(added) == 3 and "balanced_bias(cfg, params" in added[1]
    assert steady == ["gc.collect()  # steady", "gc.freeze()  # steady"]
    body = mine[mine.index("    WARM_STEPS, TRACED_STEPS = _BASE.WARM_STEPS, _BASE.TRACED_STEPS") + 1:]
    assert [line for line in body if not line.endswith(("# balanced", "# steady"))] == base[1:]
    # the collector is put to rest where the warm steps end, inside set-up's clock
    assert mine[mine.index("        gc.freeze()  # steady") + 1] == "    # ---- the window " + "-" * 55
    assert mine.index(added[0]) == mine.index("            params = jax.jit(init)(key)") + 1
    assert mine[mine.index(added[2]) + 1] == "        state = TrainState.create(params, opt)"


def test_runners_loop_starts_the_step_from_the_balanced_bias(monkeypatch, tmp_path):
    """The loop itself on the CPU at zaya-tiny's sizes: the first step's
    routing is the balanced one, every step reports, and the table the
    loop started from is kept for the reference."""
    from ray_tpu.train import session

    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    base = mf.load_plugin(mf.ROOT, "runners", "train_reference")
    reports = []
    monkeypatch.setattr(session, "report", reports.append)
    monkeypatch.setattr(runner, "_BASE", base)
    config = {**_tiny_shape(), "model_builder": SHAPE["model_builder"]}
    config["train"] = {**config["train"], "lr": 1e-6}
    runner.train_loop({"root": mf.ROOT, "config": config, "traffic": TINY_TRAFFIC, "seed": 5,
                       "seconds": 0.2, "trace": 0, "out_dir": str(tmp_path)})
    assert [r["phase"] for r in reports[:4]] == ["warm"] * 4 and reports[-1]["phase"] == "done"
    assert any(r["phase"] == "window" for r in reports)
    table = runner._BIAS[0]
    assert table.shape == (3, 4) and np.abs(table).max() > 1e-3
    first = np.asarray(reports[-1]["first_counts"])
    assert (first.max(-1) / first.mean(-1)).max() < 1.5  # b = 0 reads 2 and more here
    assert all(r["router"]["dropped_pairs"] == 0 for r in reports[:-1])
    import gc

    assert gc.get_freeze_count() > 0
    gc.unfreeze()


def test_builder_refuses_a_registry_entry_that_is_not_at_the_published_counts():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    with pytest.raises(RuntimeError, match="num_experts"):
        builder.build({**SHAPE, "published": {**SHAPE["published"], "num_experts": 24}})


# -- cost functions, by hand ---------------------------------------------------


def test_matmul_params_by_hand():
    p = costs_zaya.matmul_params(SHAPE)
    assert p["proj"] == 2 * 2048 * 1024 + 2 * 2048 * 256        # W_q, W_o; W_k, W_v1 + W_v2
    assert p["conv"] == 2 * 10 * 128 * 128                      # two taps, ten heads
    assert p["router"] == 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert p["expert"] == 3 * 2048 * 2048
    assert p["head"] == 2048 * 32896


def test_train_flops_per_token_by_hand():
    # forward, MFLOP a token and layer: projections 10.49, grouped convolution 0.66,
    # router 1.32, scores 4 * 128 * 8 * 4097 / 2 = 8.39, one expert 25.17 x the share held
    layer = 2 * (5_242_880 + 327_680 + 659_456) + 4 * 128 * 8 * 4097 / 2
    head = 2 * 2048 * 32896
    for share in (0.0, 0.5, 1.0):
        forward = 6 * (layer + share * 2 * 12_582_912) + head
        assert costs_zaya.train_flops_per_token(SHAPE, 4096, share) == pytest.approx(3 * forward)
    half = costs_zaya.train_flops_per_token(SHAPE, 4096, 0.5)
    assert half == pytest.approx(1.006e9, rel=2e-3)              # "about 1.0 GFLOP a token"
    assert 3 * head / half == pytest.approx(0.40, abs=0.01)      # the head: 40% at 6 layers
    whole = {**SHAPE, "num_hidden_layers": 40, "vocab_size": 262272}
    whole_head = 3 * 2 * 2048 * 262272
    assert whole_head / costs_zaya.train_flops_per_token(whole, 4096, 1.0) == pytest.approx(
        0.368, abs=0.005)                                        # 36.8% in the whole model


def test_grouped_matmul_cost_by_hand():
    c = costs_zaya.grouped_matmul_cost(SHAPE, 8192)
    one = 2 * 8192 * 2048 * 2048                                  # 68.7 GFLOP a matmul
    assert c["fwd_flops"] == 3 * one and c["bwd_flops"] == 6 * one
    moved = 2 * (8192 * 2048 + 8192 * 2048 + 8 * 2048 * 2048)
    assert c["fwd_bytes"] == 3 * moved and c["bwd_bytes"] == 6 * moved
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    assert bound == "compute" and least == pytest.approx(9 * one / 197e12)
    # no rows, no operations: the weights' bytes stay
    assert costs_zaya.grouped_matmul_cost(SHAPE, 0)["fwd_flops"] == 0


# -- the readers, on a hand-built trace and HLO text ---------------------------

HLO = """
HloModule jit_step

ENTRY %main {
  %fusion.7 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/cca.mix/mul" stack_frame_id=3}
  %fusion.8 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/cca.mix/add"}
  %fusion.9 = bf16[8,16]{1,0} fusion(%x, %w), kind=kOutput, calls=%d, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/cca.proj/bsd,dh->bsh/dot_general"}
  %fusion.10 = bf16[8,4]{1,0} fusion(%o, %w), kind=kOutput, calls=%d2, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/cca.out/bsh,hd->bsd/dot_general"}
  %cca.attend.3 = bf16[8,4]{1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/cca.attend/pallas_call"}
  %sort.2 = (s32[16], s32[16]) sort(%k, %v), metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/moe.dispatch/sort"}
  %select.4 = bf16[16,4]{1,0} fusion(%y), kind=kLoop, calls=%z, metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/moe.experts/select_n"}
  %ragged-dot-tiled.3 = bf16[16,4]{1,0} custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/checkpoint/moe.experts/ragged-dot-tiled"}
  ROOT %fusion.166 = f32[8,32896]{1,0} fusion(%h, %w2), kind=kOutput, calls=%e, metadata={op_name="jit(step)/jvp()/dot_general"}
}
"""


def _traced_run(with_scopes=True, elsewhere=True):
    """Three steps of one second on one device: per step a 0.2 s while
    that holds the scoped ops (cca: 0.03 projections, 0.02 + 0.01 mix,
    0.02 output; moe: 0.01 sort, 0.02 select) and nothing else, the two
    flash kernels (0.06, named after the scope they sit in), two
    grouped-matmul kernels of 0.1 s, 0.3 s of the head."""
    ops, host = [], [["main", "chipbench.window", 0.0, 3.0]]
    for s in (0.0, 1.0, 2.0):
        ops += [["while.1", s, 0.2], ["fusion.9", s, 0.03], ["fusion.7", s + 0.03, 0.02],
                ["fusion.8", s + 0.05, 0.01], ["fusion.10", s + 0.06, 0.02],
                ["sort.2", s + 0.08, 0.01], ["select.4", s + 0.09, 0.02],
                ["kernel:cca.attend.3", s + 0.2, 0.02], ["kernel:cca.attend.4", s + 0.22, 0.04],
                ["kernel:ragged-dot-tiled.3", s + 0.3, 0.1],
                ["kernel:ragged-dot-tiled-wgrad.1", s + 0.4, 0.1],
                ["fusion.166", s + 0.6, 0.3]]
    trace = tr.from_dict({"device_ops": {"/device:TPU:0": ops}, "host": host})
    win, rules = tr.window(trace), mf.trace_names(mf.ROOT)["rules"]
    scopes = hlo_scopes.scopes_of(HLO, tuple(SHAPE["check"]["scopes"]))
    # six layers a step: 16384 pairs a layer, of which this chip holds 8000 / 8192 / 8400
    steps = [{"router": {"imbalance": [x] * 6, "pairs": [16384] * 6,
                         **({"pairs_elsewhere": [16384 - held] * 6} if elsewhere else {})}}
             for x, held in ((3.0, 8400), (1.5, 8000), (2.5, 8192))]
    return {
        "trace": trace, "win": win, "rules": rules, "busy": tr.busy(trace, win),
        "ops": tr.class_time(trace.device_ops, rules, win),
        "scopes": {k: v for k, v in scopes.items() if v.startswith("moe.")} if with_scopes else None,
        "cca_scopes": ({k: v for k, v in scopes.items() if v.startswith("cca.")}
                       if with_scopes else None),
        "shape": SHAPE, "traffic": {"seq_len": 4096}, "peaks": PEAKS, "chips": 1,
        "traced_steps": 3, "tokens_per_step": 16384, "traced_window_steps": steps,
        "values": {"train_tok_s": 85000.0},
    }


def test_flash_kernels_under_the_cca_scope_are_still_classed_flash():
    """The kernels take the name of the scope they are called in
    (`cca.attend.N`, not closed_call / checkpoint): base.json's last
    pattern classes them `flash`, which flash_roofline sums with the two
    named classes; the grouped matmuls keep their own class."""
    run = _traced_run()
    assert run["ops"]["flash"]["seconds"] == pytest.approx(0.18)
    assert run["ops"]["expert_matmul"]["seconds"] == pytest.approx(0.6)
    c = costs.flash_cost(SHAPE, SHAPE["train"]["global_batch"], 4096)
    least = 6 * 3 * (c["fwd_flops"] + c["bwd_flops"]) / 197e12
    assert reader("flash_roofline").read(run) == pytest.approx(100 * least / 0.18)
    # the latent's own heads are the file's keys: 8 query heads of 128 over 2
    assert c["fwd_flops"] == SHAPE["train"]["global_batch"] * 8 * 4 * 128 * 4096 * 4097 / 2


def test_cca_share_and_mix_readers():
    run = _traced_run()
    busy = run["busy"]["busy_s"]
    assert busy == pytest.approx(3 * (0.2 + 0.06 + 0.2 + 0.3))
    scoped = 3 * (0.03 + 0.02 + 0.01 + 0.02)
    assert reader("cca_share_pct").read(run) == pytest.approx(100 * (scoped + 0.18) / busy)
    assert reader("cca_mix_pct").read(run) == pytest.approx(100 * 3 * 0.03 / busy)
    # the expert layer's readers see their own scopes only, not CCA's
    assert reader("moe_dispatch_pct").read(run) == pytest.approx(100 * 3 * 0.03 / busy)
    assert reader("moe_share_pct").read(run) == pytest.approx(100 * (3 * 0.03 + 0.6) / busy)
    for name in ("cca_share_pct", "cca_mix_pct"):
        assert reader(name).read(_traced_run(with_scopes=False)) is None
        assert reader(name).read({"busy": None}) is None


def test_readers_of_the_held_pairs():
    run = _traced_run()
    # medians of the traced steps, summed over the six layers: 6 x 8192 of 6 x 16384
    assert reader("experts_elsewhere_pct").read(run) == pytest.approx(50.0)
    least = 3 * 6 * 9 * 2 * 8192 * 2048 * 2048 / 197e12          # compute-bound
    assert reader("expert_matmul_roofline.held").read(run) == pytest.approx(100 * least / 0.6)
    want = 100 * 85000.0 * costs_zaya.train_flops_per_token(SHAPE, 4096, 0.5) / 197e12
    assert reader("train_mfu_pct.zaya").read(run) == pytest.approx(want) and 0 < want < 100
    # counting every pair, as costs_moe does, would read twice that: the reason this
    # cell is not on expert_matmul_roofline's list
    assert reader("expert_matmul_roofline").read(
        {**run, "shape": {**SHAPE, "intermediate_size": 2048}}) == pytest.approx(
            2 * reader("expert_matmul_roofline.held").read(run) * 16384 / 16384, rel=1e-6)
    # a program with no such statistic (the parent): nothing to read, no error
    old = _traced_run(elsewhere=False)
    for name in ("experts_elsewhere_pct", "expert_matmul_roofline.held", "train_mfu_pct.zaya"):
        assert reader(name).read(old) is None
        assert reader(name).read({}) is None


# -- the runner that reads its constants from the configuration file -----------


@pytest.mark.parametrize("first_counts,ok", [([[50, 50], [49, 51]], True), ([[50, 50], [40, 60]], False)])
def test_runner_sets_the_tolerances_and_the_scopes_and_holds_the_first_routing(
        monkeypatch, first_counts, ok):
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    seen = {}
    plain_loss = zaya_decoder.loss
    def loss_parts(params, *rest):
        seen.update(reference_bias=params["layers"]["router_bias"], reference_rest=rest,
                    reference_other=params["layers"]["w"])
        return {"loss": 5.0, "tokens_per_expert": np.array([[50, 50], [50, 50]])}

    monkeypatch.setattr(zaya_decoder, "loss_parts", loss_parts)

    def fake_run(ctx):
        # as the real runner goes: every step's summary in order, then the reference
        stats = {"pairs_elsewhere": np.array([3, 4]), "tokens_per_expert": np.array(first_counts)}
        seen.update(tol=base.LOSS_TOL, scopes=base.SCOPES, summary=base.router_summary(stats),
                    later=base.router_summary({"tokens_per_expert": np.array([[100, 0], [100, 0]])}),
                    none=base.router_summary(None),
                    reference=zaya_decoder.loss({"layers": {"router_bias": 0, "w": 3}},
                                                "tokens", "targets", ctx["config"]))
        return {"scopes": {"fusion.7": "cca.mix", "sort.2": "moe.dispatch"}, "busy": None,
                "checks": {"losses_finite": True}, "correct": True}

    base = types.SimpleNamespace(
        LOSS_TOL=6e-5, SCOPES=("moe.router",), run=fake_run,
        router_summary=lambda stats: None if stats is None else {"pairs": [7, 7]})
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: base)
    logged = []
    ctx = {"root": mf.ROOT, "config": dict(SHAPE), "traffic": {"generator": "zipf_tokens"},
           "args": types.SimpleNamespace(seed=41), "log": lambda **kw: logged.append(kw)}
    monkeypatch.setattr(runner, "_BIAS", [np.array([[0.25, -0.25], [0.0, 0.5]], np.float32)])
    got = runner.run(ctx)
    assert ctx["config"] == SHAPE  # the builder is handed the file and nothing else
    assert base.train_loop is runner.train_loop  # the loop with the balancing, in that runner's place
    # the reference is given the selection bias the step started from, and nothing else is changed
    assert seen["reference_bias"].tolist() == [[0.25, -0.25], [0.0, 0.5]]
    assert seen["tol"] == SHAPE["check"]["loss_tol"] and 0 < seen["tol"] < 1e-2
    assert seen["scopes"] == tuple(SHAPE["check"]["scopes"])
    assert seen["summary"] == {"pairs": [7, 7], "pairs_elsewhere": [3, 4]} and seen["none"] is None
    assert seen["reference"] == 5.0 and zaya_decoder.loss is plain_loss
    assert seen["reference_rest"] == ("tokens", "targets", SHAPE) and seen["reference_other"] == 3
    assert got["checks"] == {"losses_finite": True, "first_routing_is_the_reference": ok}
    assert got["correct"] is ok and 0.0024 < SHAPE["check"]["routing_tol"] < 0.24
    moved = 1 if ok else 10  # of 200 pairs: half the sum of the tables' differences
    assert logged[-2]["event"] == "correct.routing" and logged[-2]["ok"] is ok
    assert (logged[-2]["moved"], logged[-2]["pairs"]) == (moved, 200)
    assert logged[-1]["event"] == "routing" and logged[-1]["elsewhere_share_mean"] == 0.5
    assert logged[-1]["router_bias"] == [[0.25, -0.25], [0.0, 0.5]]
    assert got["scopes"] == {"sort.2": "moe.dispatch"}
    assert got["cca_scopes"] == {"fusion.7": "cca.mix"}


def test_runner_leaves_both_scope_tables_empty_where_the_step_names_no_scope(monkeypatch):
    runner = mf.load_plugin(mf.ROOT, "runners", SHAPE["runner"])
    table = np.array([[8, 8]])
    monkeypatch.setattr(zaya_decoder, "loss_parts",
                        lambda *a: {"loss": 5.0, "tokens_per_expert": table})

    def fake_run(ctx):
        base.router_summary({"tokens_per_expert": table})
        zaya_decoder.loss({"layers": {}})
        return {"scopes": None, "checks": {}, "correct": True}

    base = types.SimpleNamespace(LOSS_TOL=0.0, SCOPES=(), run=fake_run,
                                 router_summary=lambda stats: {})
    monkeypatch.setattr(mf, "load_plugin", lambda root, kind, name: base)
    monkeypatch.setattr(runner, "_BIAS", [])
    got = runner.run({"root": mf.ROOT, "config": dict(SHAPE), "log": lambda **kw: None})
    assert got["scopes"] is None and got["cca_scopes"] is None and got["correct"] is True


def test_the_real_runner_has_what_the_thin_one_sets():
    base = mf.load_plugin(mf.ROOT, "runners", "train_reference")
    assert isinstance(base.LOSS_TOL, float) and isinstance(base.SCOPES, tuple)
    assert callable(base.router_summary) and callable(base.run) and callable(base.train_loop)
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    assert callable(builder.build) and callable(builder.balanced_bias)
    src = open(os.path.join(mf.ROOT, "chipbench/runners/train_reference.py")).read()
    assert "router_summary(stats)" in src and "LOSS_TOL" in src and "SCOPES)" in src


# -- the reference against hand-written per-token loops --------------------------

TINY = {"hidden_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rope_parameters": {"hybrid": {"rope_theta": 10000.0}}, "rms_norm_eps": 1e-5,
        "router_hidden_size": 8, "num_experts": 3, "published": {"num_experts": 6},
        "deployment": {"first_expert_held": 2}, "num_experts_per_tok": 1,
        "max_position_embeddings": 32, "num_hidden_layers": 2, "tie_word_embeddings": True}


def _tiny_layer(seed=0, d=16, f=12, r=8, e=6, held=3, h=4, g=2, hd=4):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2] if len(s) > 1 else 1),  # noqa: E731
                               jnp.float32)
    return {"ln1": 1 + 0.1 * n(d), "ln2": 1 + 0.1 * n(d), "wq": n(d, h * hd), "wk": n(d, g * hd),
            "wv1": n(d, g * hd // 2), "wv2": n(d, g * hd // 2), "wo": n(h * hd, d),
            "conv0": n(2, (h + g) * hd), "conv1": n(2, h + g, hd, hd), "temp": 1 + 0.2 * n(g),
            "router_down": n(d, r), "router_gamma": 1 + 0.3 * n(r), "router_norm": 1 + 0.1 * n(r),
            "router_w1": n(r, r), "router_w2": n(r, r), "router_w3": n(r, e),
            "router_bias": 0.05 * n(e),
            "w_gate": n(held, d, f), "w_up": n(held, d, f), "w_down": n(held, f, d)}


def test_reference_cca_equals_a_per_token_loop():
    lp = _tiny_layer()
    s, H, G, hd = 9, 4, 2, 4
    h = jnp.asarray(np.random.default_rng(1).normal(size=(s, 16)), jnp.float32)
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    hn = np.asarray(h, np.float64)
    x = hn / np.sqrt((hn ** 2).mean(-1, keepdims=True) + 1e-5) * p["ln1"]
    q_lat, k_lat = x @ p["wq"], x @ p["wk"]
    u = np.concatenate([q_lat, k_lat], -1)                       # [S, 24]: six heads of 4
    taps1 = p["conv1"]
    u0, u1 = np.zeros_like(u), np.zeros_like(u)
    for t in range(s):
        u0[t] = p["conv0"][1] * u[t] + (p["conv0"][0] * u[t - 1] if t else 0)
    for t in range(s):
        for n in range(H + G):
            cur = u0[t, n * hd:(n + 1) * hd] @ taps1[1, n]
            prev = u0[t - 1, n * hd:(n + 1) * hd] @ taps1[0, n] if t else 0
            u1[t, n * hd:(n + 1) * hd] = cur + prev
    out = np.zeros((s, H * hd))
    inv = 1.0 / 10000.0 ** (np.arange(0, 2, 2) / 2)             # rot = 2: one pair a head

    def rope(vec, t):
        a = t * inv[0]
        return np.r_[vec[0] * math.cos(a) - vec[1] * math.sin(a),
                     vec[1] * math.cos(a) + vec[0] * math.sin(a), vec[2:]]

    qs, ks, vs = np.zeros((s, H, hd)), np.zeros((s, G, hd)), np.zeros((s, G, hd))
    for t in range(s):
        vs[t, 0] = x[t] @ p["wv1"]
        vs[t, 1] = x[t - 1] @ p["wv2"] if t else 0
        m = [(q_lat[t, i * hd:(i + 1) * hd] + k_lat[t, (i // 2) * hd:(i // 2 + 1) * hd]) / 2
             for i in range(H)]
        for i in range(H):
            q = u1[t, i * hd:(i + 1) * hd] + m[i]
            qs[t, i] = rope(q / np.sqrt((q ** 2).mean() + 1e-5), t)
        for g in range(G):
            k = u1[t, (H + g) * hd:(H + g + 1) * hd] + (m[2 * g] + m[2 * g + 1]) / 2
            ks[t, g] = rope(p["temp"][g] * k / np.sqrt((k ** 2).mean() + 1e-5), t)
    for t in range(s):
        for i in range(H):
            sc = np.array([qs[t, i] @ ks[j, i // 2] / 2.0 for j in range(t + 1)])
            w = np.exp(sc - sc.max())
            out[t, i * hd:(i + 1) * hd] = (w / w.sum()) @ vs[:t + 1, i // 2]
    want = hn + out @ p["wo"]
    with jax.default_matmul_precision("highest"):
        got = zaya_decoder.cca(h, lp, TINY)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_reference_router_and_experts_equal_a_per_token_loop():
    lp = _tiny_layer()
    s = 7
    h = jnp.asarray(np.random.default_rng(2).normal(size=(s, 16)), jnp.float32)
    r_prev = jnp.asarray(np.random.default_rng(3).normal(size=(s, 8)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, chosen, r = zaya_decoder.experts(h, lp, TINY, r_prev)
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    hn, want = np.asarray(h, np.float64), np.array(h, np.float64)
    gelu = np.vectorize(lambda v: 0.5 * v * (1 + math.erf(v / math.sqrt(2))))
    held_tokens = 0
    for t in range(s):
        x = hn[t] / np.sqrt((hn[t] ** 2).mean() + 1e-5) * p["ln2"]
        rt = x @ p["router_down"] + p["router_gamma"] * np.asarray(r_prev[t], np.float64)
        np.testing.assert_allclose(np.asarray(r[t]), rt, rtol=1e-5, atol=1e-5)
        y = rt / np.sqrt((rt ** 2).mean() + 1e-5) * p["router_norm"]
        z = gelu(gelu(y @ p["router_w1"]) @ p["router_w2"]) @ p["router_w3"]
        prob = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        e = int(np.argmax(prob + p["router_bias"]))
        assert np.flatnonzero(np.asarray(chosen[t])).tolist() == [e]
        if 2 <= e < 5:                                           # experts 2, 3, 4 are held
            gate = x @ p["w_gate"][e - 2]
            want[t] += prob[e] * ((gate / (1 + np.exp(-gate)) * (x @ p["w_up"][e - 2]))
                                  @ p["w_down"][e - 2])
            held_tokens += 1
    assert 0 < held_tokens < s                                   # both kinds of token are here
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


def test_reference_loss_is_the_cross_entropy_over_the_slice_and_carries_the_router_state():
    rng = np.random.default_rng(4)
    layers = jax.tree.map(lambda a, b: jnp.stack([a, b]), _tiny_layer(0), _tiny_layer(1))
    params = {"embed": jnp.asarray(rng.normal(size=(40, 16)), jnp.float32), "layers": layers,
              "final_norm": jnp.ones((16,))}
    toks = jnp.asarray(rng.integers(0, 40, size=(3, 9)), jnp.int32)
    parts = zaya_decoder.loss_parts(params, toks[:, :-1], toks[:, 1:], TINY)
    assert parts["tokens_per_expert"].shape == (2, 6)
    assert parts["tokens_per_expert"].sum(-1).tolist() == [24, 24]
    # by hand from the layer functions: the second layer is handed the first one's r
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for row in toks:
            h, r = params["embed"][row[:-1]], jnp.zeros((8, 8))
            for i in range(2):
                h, r, _ = zaya_decoder.layer(h, r, jax.tree.map(lambda x: x[i], layers), TINY)
            h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
            logp = jax.nn.log_softmax(h @ params["embed"].T, axis=-1)
            total -= float(jnp.take_along_axis(logp, row[1:, None], axis=-1).sum())
    assert float(parts["loss"]) == pytest.approx(total / 24, rel=1e-5)
    assert float(zaya_decoder.loss(params, toks[:, :-1], toks[:, 1:], TINY)) == float(parts["loss"])
    # without the carried state the loss is another
    no_carry = jax.tree.map(lambda x: x, params)
    no_carry["layers"] = {**layers, "router_gamma": jnp.zeros_like(layers["router_gamma"])}
    other = zaya_decoder.loss(no_carry, toks[:, :-1], toks[:, 1:], TINY)
    assert abs(float(other) - float(parts["loss"])) > 1e-6
    long = jnp.zeros((33,), jnp.int32)
    with pytest.raises(ValueError, match="positions"):
        zaya_decoder.sequence(params, long, long, TINY)


def test_reference_imports_nothing_from_the_program():
    src = open(os.path.join(mf.ROOT, "chipbench/reference/zaya_decoder.py")).read()
    imports = [ln for ln in src.splitlines() if ln.startswith(("import ", "from "))]
    assert imports and not [ln for ln in imports if "ray_tpu" in ln or "chipbench" in ln]
    assert 'default_matmul_precision("highest")' in src


# -- run.py without a chip -----------------------------------------------------


def test_run_exits_non_zero_without_a_tpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": mf.ROOT}
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode not in (0, None), r.stderr[-2000:]
    assert "TPU chip(s)" in r.stderr
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith('{"correct"')]

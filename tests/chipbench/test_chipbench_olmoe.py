"""The olmoe-train cell's files (PR 26): the manifest with the cell, the
cost functions by hand-worked cases, each new reader on a hand-built
trace and HLO text, the reference against a per-token loop, the model
builder, and run.py without a chip."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs, costs_moe, hlo_scopes, manifest as mf, trace_reduce as tr
from chipbench.reference import olmoe_decoder

M = mf.load_manifest()
CELL = "olmoe-train"
SHAPE = mf.read_json(mf.ROOT, "chipbench/configs/olmoe-1b-7b-train.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("train_mfu_pct.moe", "moe_share_pct", "moe_dispatch_pct",
               "expert_matmul_roofline", "expert_imbalance")
JOINED = ("compiles_in_window.train", "flash_roofline", "device_idle_pct.train",
          "hbm_peak_gib.train", "setup_compile_s.train", "setup_cache_misses.train",
          "setup_runtime_s.train", "report_ms.train",
          # PR 31: the start-up by phase
          "setup_interp_s.train", "setup_import_s.train", "setup_import_program_s.train",
          "setup_backend_s.train", "setup_init_params_s.train", "setup_first_step_s.train",
          "setup_warm_steps_s.train", "setup_unnamed_s.train")
PEAKS = costs.load_peaks("TPU v5 lite")


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the cell ------------------------------------------------


def test_manifest_is_well_formed_with_the_cell():
    assert mf.problems(M) == []
    cell = mf.load_cell(mf.ROOT, M, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    entry = mf.by_name(M["configs"], "olmoe-1b-7b-train", "config")
    assert entry["reduced"] == ["num_hidden_layers"] and list(SHAPE["reduced"]) == entry["reduced"]
    for key in ("assumed", "stands_for", "memory", "reference"):
        assert SHAPE[key]
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(JOINED)
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", CELL)} == {"train_tok_s", "setup_s"}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_alone_and_moves_train_tok_s(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert reader(name).read.__module__ and reader(name).__doc__


# huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct config.json, as the catalog's row gave it
# when PR 26 read it (the catalog has since dropped the row: 88 rows, none of that name)
ROW_OF_PR26 = {
    "name": "OLMoE-1B-7B-0125-Instruct",
    "source_url": "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json",
    "config": {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False, "num_attention_heads": 16,
               "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
               "router_aux_loss_coef": 0.01}}


def catalog_row(path, name):
    """The catalog's row of that name, or None: the catalog lies outside the repo and
    changes under it, and an installation may have none."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next((r for r in rows if r["name"] == name), None)


def keys_that_differ(row):
    """Keys of the configuration file that are not what it is held to: the catalog's row
    where there is one, key by key; without it the file's own `source` (the manifest's)
    and `published` block."""
    entry = mf.by_name(M["configs"], "olmoe-1b-7b-train", "config")
    assert SHAPE["source"] == entry["source"] == (row or ROW_OF_PR26)["source_url"]
    published = row["config"] if row is not None else {**SHAPE, **SHAPE["published"]}
    assert SHAPE["published"]["num_hidden_layers"] == published["num_hidden_layers"]
    return {k for k, v in published.items() if SHAPE.get(k, "absent") != v}


@pytest.mark.parametrize("catalog", ["installed", "without_the_row", "with_the_row"])
def test_every_published_width_is_the_catalogs(catalog, tmp_path):
    """It neither raises nor skips, with and without the row in the catalog."""
    path = CATALOG
    if catalog != "installed":
        path = str(tmp_path / "architectures.jsonl")
        rows = [{"name": "another-model", "source_url": "https://example.org", "config": {}}]
        rows += [ROW_OF_PR26] if catalog == "with_the_row" else []
        with open(path, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))
    row = catalog_row(path, ROW_OF_PR26["name"])
    assert (row is not None) == (catalog == "with_the_row") or catalog == "installed"
    assert keys_that_differ(row) == {"num_hidden_layers"}
    assert list(SHAPE["reduced"]) == ["num_hidden_layers"] and SHAPE["num_hidden_layers"] == 1
    # a row that moves a width is seen, so the comparison is one
    moved = {**ROW_OF_PR26, "config": {**ROW_OF_PR26["config"], "hidden_size": 4096}}
    assert keys_that_differ(moved) == {"num_hidden_layers", "hidden_size"}


def test_builder_builds_the_registry_model_at_the_files_sizes():
    builder = mf.load_plugin(mf.ROOT, "model_builders", SHAPE["model_builder"])
    cfg, init, axes = builder.build(SHAPE, attention_impl="flash")
    assert (cfg.n_layers, cfg.n_experts, cfg.top_k, cfg.d_ff) == (1, 64, 8, 1024)
    assert cfg.qk_norm and not cfg.norm_topk_prob and cfg.attention_impl == "flash"
    shapes = jax.eval_shape(init, jax.random.key(0))
    assert shapes["layers"]["w_gate"].shape == (1, 64, 2048, 1024)
    assert shapes["layers"]["q_norm"].shape == (1, 2048)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    # 625.7M parameters: what the file's `memory` line says
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == pytest.approx(
        625.7e6, rel=1e-3)
    with pytest.raises(RuntimeError, match="num_experts_per_tok"):
        builder.build({**SHAPE, "num_experts_per_tok": 2})
    with pytest.raises(RuntimeError, match="intermediate_size"):
        builder.build({**SHAPE, "intermediate_size": 2048})


# -- cost functions, by hand ---------------------------------------------------


def test_active_matmul_params_by_hand():
    p = costs_moe.active_matmul_params(SHAPE)
    assert p["attention"] == 4 * 2048 * 2048                 # q, k, v, o: 16 full heads of 128
    assert p["experts"] == 8 * 3 * 2048 * 1024               # a token's own 8 experts
    assert p["router"] == 2048 * 64
    assert p["head"] == 2048 * 50304
    assert p["total"] == 16_777_216 + 50_331_648 + 131_072 + 103_022_592


def test_train_flops_per_token_by_hand():
    # forward, MFLOP a token: head 206.0, experts 100.7, projections 33.6, router 0.26,
    # scores 4 * 128 * 16 * 4097 / 2 = 16.8; three times that for a training token
    forward = 2 * 170_262_528 + 16 * 4 * 128 * 4097 / 2
    assert costs_moe.train_flops_per_token(SHAPE, 4096) == pytest.approx(3 * forward)
    assert costs_moe.train_flops_per_token(SHAPE, 4096) == pytest.approx(1.072e9, rel=1e-3)
    full = {**SHAPE, "num_hidden_layers": 16}
    head = 6 * 2048 * 50304
    assert head / costs_moe.train_flops_per_token(SHAPE, 4096) == pytest.approx(0.58, abs=0.01)
    assert head / costs_moe.train_flops_per_token(full, 4096) == pytest.approx(0.08, abs=0.005)


def test_grouped_matmul_cost_by_hand():
    c = costs_moe.grouped_matmul_cost(SHAPE, 16384)
    assert c["pairs"] == 131072
    one = 2 * 131072 * 2048 * 1024                            # 0.55 TFLOP a matmul
    assert c["fwd_flops"] == 3 * one and c["bwd_flops"] == 6 * one
    moved = 2 * (131072 * 2048 + 131072 * 1024 + 64 * 2048 * 1024)
    assert c["fwd_bytes"] == 3 * moved and c["bwd_bytes"] == 6 * moved
    least, bound = costs.roofline_seconds(c["fwd_flops"] + c["bwd_flops"],
                                          c["fwd_bytes"] + c["bwd_bytes"], PEAKS)
    assert bound == "compute" and least == pytest.approx(9 * one / 197e12)


# -- the readers, on a hand-built trace and HLO text ---------------------------

HLO = """
HloModule jit_step

%fused_computation.1 (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  ROOT %mul.9 = bf16[8,4]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.experts/mul"}
}

ENTRY %main {
  %fusion.7 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.experts/mul" stack_frame_id=3}
  %sort.2 = (s32[16], s32[16]) sort(%k, %v), metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.dispatch/sort"}
  %gather_fusion = bf16[16,4]{1,0} fusion(%x, %i), kind=kCustom, calls=%g, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/moe.combine/gather"}
  %fusion.9 = f32[8,64]{1,0} fusion(%x, %r), kind=kOutput, calls=%d, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.router/nd,de->ne/dot_general"}
  %fusion.11 = f32[8,4]{1,0} fusion(%x), kind=kLoop, calls=%n, metadata={op_name="jit(step)/jvp()/while/body/closed_call/moe.routerish/mul"}
  %ragged-dot-none.3 = bf16[16,4]{1,0} custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %fusion.166 = f32[8,50304]{1,0} fusion(%h, %w2), kind=kOutput, calls=%e, metadata={op_name="jit(step)/jvp()/dot_general"}
}
"""
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine")


def test_scopes_of_reads_each_instructions_named_scope():
    got = hlo_scopes.scopes_of(HLO, SCOPES)
    assert got == {"mul.9": "moe.experts", "fusion.7": "moe.experts", "sort.2": "moe.dispatch",
                   "gather_fusion": "moe.combine", "fusion.9": "moe.router"}
    assert hlo_scopes.scopes_of(HLO, ("tp_overlap.ag_matmul",)) == {}


def _traced_run(with_scopes=True):
    """Three steps of one second on one device: per step a 0.1 s while that
    holds the scoped ops (0.02 router, 0.01 sort, 0.03 elementwise, 0.02 gather)
    and nothing else, two grouped-matmul kernels of 0.2 s, 0.3 s of the head."""
    ops, host = [], [["main", "chipbench.window", 0.0, 3.0]]
    for s in (0.0, 1.0, 2.0):
        ops += [["while.1", s, 0.1], ["fusion.9", s, 0.02], ["sort.2", s + 0.02, 0.01],
                ["fusion.7", s + 0.03, 0.03], ["gather_fusion", s + 0.06, 0.02],
                ["fusion.11", s + 0.08, 0.02],
                ["kernel:ragged-dot-none.3", s + 0.1, 0.2],
                ["kernel:ragged-dot-none.1", s + 0.3, 0.2],
                ["kernel:ragged-dot-metadata.2", s + 0.5, 0.001],
                ["kernel:closed_call.5", s + 0.55, 0.05],
                ["fusion.166", s + 0.6, 0.3]]
    trace = tr.from_dict({"device_ops": {"/device:TPU:0": ops}, "host": host})
    win, rules = tr.window(trace), mf.trace_names(mf.ROOT)["rules"]
    steps = [{"router": {"imbalance": [x]}} for x in (3.0, 1.5, 2.5)]
    return {
        "trace": trace, "win": win, "rules": rules, "busy": tr.busy(trace, win),
        "ops": tr.class_time(trace.device_ops, rules, win),
        "scopes": hlo_scopes.scopes_of(HLO, SCOPES) if with_scopes else None,
        "shape": SHAPE, "traffic": {"seq_len": 4096}, "peaks": PEAKS, "chips": 1,
        "traced_steps": 3, "tokens_per_step": 16384, "traced_window_steps": steps,
        "values": {"train_tok_s": 80000.0},
    }


def test_grouped_matmul_kernels_are_not_classed_flash():
    """trace_names: the file that names them is read before base.json,
    whose last pattern classes every unknown Pallas kernel as flash."""
    run = _traced_run()
    assert run["ops"]["expert_matmul"]["seconds"] == pytest.approx(1.2)
    assert run["ops"]["expert_matmul_schedule"]["seconds"] == pytest.approx(0.003)
    assert run["ops"]["flash_fwd"]["seconds"] == pytest.approx(0.15) and "flash" not in run["ops"]


def test_moe_share_and_dispatch_readers():
    run = _traced_run()
    busy = run["busy"]["busy_s"]
    assert busy == pytest.approx(3 * (0.1 + 0.4 + 0.001 + 0.05 + 0.3))
    scoped = 3 * (0.02 + 0.01 + 0.03 + 0.02)          # fusion.11 is under no moe scope
    assert reader("moe_dispatch_pct").read(run) == pytest.approx(100 * scoped / busy)
    assert reader("moe_share_pct").read(run) == pytest.approx(100 * (scoped + 1.203) / busy)
    for name in ("moe_share_pct", "moe_dispatch_pct"):
        assert reader(name).read(_traced_run(with_scopes=False)) is None
        assert reader(name).read({"busy": None}) is None


def test_expert_matmul_roofline_reader():
    run = _traced_run()
    least = 3 * 9 * 2 * 131072 * 2048 * 1024 / 197e12   # 3 steps x 9 matmuls, compute-bound
    assert reader("expert_matmul_roofline").read(run) == pytest.approx(100 * least / 1.2)
    assert reader("expert_matmul_roofline").read({"ops": {}}) is None


def test_expert_imbalance_reader_takes_the_median_of_the_traced_steps():
    assert reader("expert_imbalance").read(_traced_run()) == 2.5
    assert reader("expert_imbalance").read({"traced_window_steps": [{"loss": 1.0}]}) is None
    assert reader("expert_imbalance").read({}) is None


def test_train_mfu_moe_reader():
    run = _traced_run()
    want = 100 * 80000.0 * costs_moe.train_flops_per_token(SHAPE, 4096) / 197e12
    assert reader("train_mfu_pct.moe").read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("train_mfu_pct.moe").read({**run, "values": {"train_tok_s": None}}) is None


# -- the reference against a hand-written per-token loop ----------------------

TINY = {"hidden_size": 16, "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 8,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "max_position_embeddings": 32,
        "num_hidden_layers": 1, "num_experts": 6, "num_experts_per_tok": 2,
        "norm_topk_prob": False, "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}


def _tiny_layer(seed=0, d=16, f=12, e=6):
    r = np.random.default_rng(seed)
    g = lambda *s: jnp.asarray(r.normal(size=s) / np.sqrt(s[-2] if len(s) > 1 else 1), jnp.float32)
    return {"ln1": 1 + 0.1 * g(d), "ln2": 1 + 0.1 * g(d), "q_norm": 1 + 0.1 * g(d),
            "k_norm": 1 + 0.1 * g(d), "wq": g(d, d), "wk": g(d, d), "wv": g(d, d), "wo": g(d, d),
            "router": g(d, e), "w_gate": g(e, d, f), "w_up": g(e, d, f), "w_down": g(e, f, d)}


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_reference_experts_equal_a_per_token_loop(norm_topk_prob):
    shape = {**TINY, "norm_topk_prob": norm_topk_prob}
    lp = _tiny_layer()
    h = jnp.asarray(np.random.default_rng(1).normal(size=(7, 16)), jnp.float32)
    out, chosen, probs, lse = olmoe_decoder.experts(h, lp, shape)
    hn, want = np.asarray(h, np.float64), np.array(h, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    for t in range(7):
        x = hn[t] / np.sqrt((hn[t] ** 2).mean() + 1e-5) * p["ln2"]
        logits = x @ p["router"]
        prob = np.exp(logits) / np.exp(logits).sum()
        top = np.argsort(prob)[::-1][:2]
        w = prob[top] / (prob[top].sum() if norm_topk_prob else 1.0)
        for e, we in zip(top, w):
            gate = x @ p["w_gate"][e]
            want[t] += we * ((gate / (1 + np.exp(-gate)) * (x @ p["w_up"][e])) @ p["w_down"][e])
        assert sorted(np.flatnonzero(np.asarray(chosen[t]))) == sorted(top)
        assert float(lse[t]) == pytest.approx(np.log(np.exp(logits).sum()), rel=1e-5)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)


def test_reference_loss_is_cross_entropy_plus_both_router_losses():
    r = np.random.default_rng(2)
    params = {"embed": jnp.asarray(r.normal(size=(40, 16)), jnp.float32),
              "layers": jax.tree.map(lambda x: x[None], _tiny_layer()),
              "final_norm": jnp.ones((16,)),
              "lm_head": jnp.asarray(r.normal(size=(16, 40)) / 4, jnp.float32)}
    toks = jnp.asarray(r.integers(0, 40, size=(3, 9)), jnp.int32)
    parts = olmoe_decoder.loss_parts(params, toks[:, :-1], toks[:, 1:], TINY)
    counts = np.asarray(parts["tokens_per_expert"])
    assert counts.shape == (1, 6) and counts.sum() == 3 * 8 * 2
    # the router's means by hand, over all 24 tokens of the batch
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    with jax.default_matmul_precision("highest"):
        seen = [olmoe_decoder.experts(
            olmoe_decoder.attention(params["embed"][t], lp, TINY), lp, TINY) for t in toks[:, :-1]]
    chosen, probs, lse = (np.concatenate([np.asarray(s[i]) for s in seen]) for i in (1, 2, 3))
    assert (counts[0] == chosen.sum(0)).all()
    # f_e sums to 2, so an even split gives exactly 2
    balance = 6 * ((chosen.sum(0) / 24) * probs.mean(0)).sum()
    assert float(parts["balance"][0]) == pytest.approx(balance, rel=1e-5) and balance >= 2.0
    assert float(parts["z"][0]) == pytest.approx((lse ** 2).mean(), rel=1e-5)
    want = float(parts["ce"]) + 0.01 * float(parts["balance"][0]) + 0.001 * float(parts["z"][0])
    assert float(parts["loss"]) == pytest.approx(want, rel=1e-6)
    whole = olmoe_decoder.loss(params, toks[:, :-1], toks[:, 1:], TINY)
    assert float(whole) == float(parts["loss"])
    long = jnp.zeros((33,), jnp.int32)
    with pytest.raises(ValueError, match="positions"):
        olmoe_decoder.sequence(params, long, long, TINY)


def test_reference_imports_nothing_from_the_program():
    src = open(os.path.join(mf.ROOT, "chipbench/reference/olmoe_decoder.py")).read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


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

"""A later PR adds a cell, a configuration, a generator, a per-layer
metric and name patterns as NEW files and NEW entries, and edits no
file that is there; and run.py starts nothing without the chip."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import manifest as mf


def _tree_hash(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        if "__pycache__" in d:
            continue
        for fn in files:
            p = os.path.join(d, fn)
            out[os.path.relpath(p, root)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


@pytest.fixture()
def copy(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(mf.ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    return root


def test_new_files_are_picked_up_with_no_edit(copy):
    before = _tree_hash(copy)
    w = lambda rel, text: open(os.path.join(copy, rel), "w").write(text)
    w("chipbench/generators/uniform_tokens.py",
      "def batch_fn(params, vocab_size, batch, seed, sharding=None):\n"
      "    return lambda step: {'tokens': [[step % vocab_size] * params['seq_len']] * batch}\n")
    w("chipbench/traffic/uniform_2k.json", json.dumps(
        {"generator": "uniform_tokens", "seq_len": 2048, "max_context": 4096}))
    cfg = mf.read_json(copy, "chipbench/configs/mistral-7b-train.json")
    cfg["train"]["global_batch"] = 2
    w("chipbench/configs/mistral-7b-train-b2.json", json.dumps(cfg))
    w("chipbench/workloads/m7b-train-2k.json", json.dumps(
        {"config": "mistral-7b-train-b2", "chips": 1, "traffic": "uniform_2k",
         "why": "shorter sequences: less of the step in attention"}))
    w("chipbench/layer_metrics/step_jitter_pct.py", "def read(run):\n    return run.get('jitter')\n")
    w("chipbench/trace_names/zz_more.json", json.dumps({"patterns": {"^jit_burst": "decode"}}))
    m = mf.load_manifest(copy)
    m["configs"].append({"name": "mistral-7b-train-b2", "source": cfg["source"],
                         "file": "chipbench/configs/mistral-7b-train-b2.json",
                         "reduced": ["num_hidden_layers"], "why": "a smaller batch"})
    m["workloads"].append({"name": "m7b-train-2k", "config": "mistral-7b-train-b2",
                           "traffic": "uniform_2k", "chips": 1, "why": "shorter sequences"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "m7b-train" in e.get("workloads", ()):
            e["workloads"].append("m7b-train-2k")
    m["per_layer"].append({"name": "step_jitter_pct", "unit": "%", "better": "lower",
                           "source": "host_clock", "layer": "train step",
                           "moves": "train_tok_s", "workloads": ["m7b-train-2k"]})
    json.dump(m, open(os.path.join(copy, "BENCHMARK.json"), "w"))

    m = mf.load_manifest(copy)
    assert mf.problems(m, copy) == []
    cell = mf.load_cell(copy, m, "m7b-train-2k")
    assert cell["config"]["train"]["global_batch"] == 2
    gen = mf.load_plugin(copy, "generators", cell["traffic"]["generator"])
    assert len(gen.batch_fn(cell["traffic"], 32000, 2, 0)(5)["tokens"][0]) == 2048
    names = [x["name"] for x in mf.metrics_of(m, "per_layer", "m7b-train-2k")]
    assert "step_jitter_pct" in names and "train_mfu_pct" in names
    assert "coll_exposed_pct" not in names
    assert mf.load_plugin(copy, "layer_metrics", "step_jitter_pct").read({"jitter": 3}) == 3
    assert any(cls == "decode" and rx.search("jit_burst") for rx, cls in mf.trace_names(copy)["rules"])
    after = _tree_hash(copy)
    assert {k: v for k, v in after.items() if k in before} == before  # nothing there was edited


def test_no_python_file_of_the_harness_names_a_cell():
    cells = [w["name"] for w in mf.load_manifest()["workloads"]]
    for rel in ("chipbench/run.py", "chipbench/manifest.py",
                "chipbench/runners/train.py", "chipbench/readers.py", "chipbench/tracing.py"):
        src = open(os.path.join(mf.ROOT, rel)).read()
        assert not [c for c in cells if f'"{c}"' in src or f"'{c}'" in src], rel


def _run(root, cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root}
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("cell", ["m7b-train", "m7b-train-4chip"])
def test_run_exits_non_zero_without_a_tpu_and_prints_no_result(cell):
    r = _run(mf.ROOT, cell)
    assert r.returncode not in (0, None), r.stderr[-2000:]
    assert "TPU chip(s)" in r.stderr
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith('{"correct"')]


def test_run_exits_non_zero_where_only_the_benchmark_is(copy):
    """A directory with only BENCHMARK.json and the files under paths."""
    r = _run(copy, "m7b-train")
    assert r.returncode not in (0, None)
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith('{"correct"')]


def test_unknown_cell_is_refused():
    r = _run(mf.ROOT, "m7b-nothing")
    assert r.returncode not in (0, None) and "m7b-nothing" in r.stderr

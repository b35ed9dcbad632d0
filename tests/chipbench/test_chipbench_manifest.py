"""BENCHMARK.json and the files it names are well-formed (tier-1, CPU)."""

import copy
import json
import os

import pytest

from chipbench import manifest as mf

M = mf.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
CONFIGS = [c["name"] for c in M["configs"]]
CONFIG_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(mf.ROOT, "chipbench", "configs")))
E2E = [m["name"] for m in M["end_to_end"]]
LAYER = [m["name"] for m in M["per_layer"]]
READERS = sorted(f[:-3] for f in os.listdir(os.path.join(mf.ROOT, "chipbench", "layer_metrics"))
                 if f.endswith(".py"))
CELL_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(mf.ROOT, "chipbench", "workloads")))


def test_manifest_has_no_problems():
    assert mf.problems(M) == []


def test_manifest_has_exactly_the_contract_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("name", E2E + LAYER)
def test_metric_name_unit_and_keys(name):
    m = mf.by_name(M["end_to_end"] + M["per_layer"], name, "metric")
    assert mf.NAME_RE.match(m["name"]) and mf.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in mf.SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if name in E2E else {"layer", "moves"}
    assert set(m) <= allowed


@pytest.mark.parametrize("name", LAYER)
def test_layer_metric_moves_a_metric_its_cells_report(name):
    m = mf.by_name(M["per_layer"], name, "metric")
    target = mf.by_name(M["end_to_end"], m["moves"], "metric")
    assert set(m.get("workloads", CELLS)) <= set(target.get("workloads", CELLS))


STARTUP = ["setup_interp_s.train", "setup_import_s.train", "setup_import_program_s.train",
           "setup_backend_s.train", "setup_init_params_s.train", "setup_first_step_s.train",
           "setup_warm_steps_s.train", "setup_unnamed_s.train"]


@pytest.mark.parametrize("name", STARTUP)
def test_startup_phase_metric_moves_setup_s_in_every_training_cell(name):
    """PR 31: every second of setup_s has a name, in each of the cells."""
    m = mf.by_name(M["per_layer"], name, "metric")
    assert (m["moves"], m["unit"], m["better"], m["source"]) == (
        "setup_s", "s", "lower", "host_clock")
    assert m["workloads"] == [c for c in CELLS if "train_tok_s" in
                              [e["name"] for e in mf.metrics_of(M, "end_to_end", c)]]
    assert m["workloads"] == ["m7b-train", "m7b-train-4chip", "olmoe-train"]


def test_every_file_on_disk_is_listed_and_every_listed_name_has_its_file():
    """No reader, cell or configuration waits outside BENCHMARK.json."""
    assert set(LAYER) == set(READERS) and set(CELLS) == set(CELL_FILES)
    assert set(CONFIGS) == set(CONFIG_FILES)


@pytest.mark.parametrize("name", READERS)
def test_layer_metric_has_a_reader_file(name):
    mod = mf.load_plugin(mf.ROOT, "layer_metrics", name)
    assert callable(mod.read) and mod.__doc__


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_when_there_is_nothing_to_read(name):
    """A reader meets a run that carries none of what it reads."""
    empty = {"values": {"train_tok_s": None}, "compiles_in_window": None}
    assert mf.load_plugin(mf.ROOT, "layer_metrics", name).read(empty) is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    got = mf.load_cell(mf.ROOT, M, cell)
    assert got["cell"]["why"] and len(got["cell"]["why"]) <= 200
    for kind, key in (("generators", got["traffic"]["generator"]),
                      ("runners", got["config"]["runner"]),
                      ("model_builders", got["config"]["model_builder"])):
        assert mf.load_plugin(mf.ROOT, kind, key)
    e2e = [m["name"] for m in mf.metrics_of(M, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert mf.metrics_of(M, "per_layer", cell)


@pytest.mark.parametrize("cell", CELL_FILES)
def test_cell_and_config_files_set_sizes_and_traffic_only(cell):
    """No engine path option in a cell or configuration file."""
    raw = mf.read_json(mf.ROOT, f"chipbench/workloads/{cell}.json")
    got = {"cell": raw, "config": mf.read_json(mf.ROOT, f"chipbench/configs/{raw['config']}.json")}
    assert mf.read_json(mf.ROOT, f"chipbench/traffic/{raw['traffic']}.json")["generator"]
    path_options = {"pipeline_decode", "mixed_batch", "attn_impl", "decode_chunk",
                    "spec", "kvtier", "mixed_prefill_chunk", "enable_prefix_caching"}
    assert not path_options & set(got["config"].get("engine", {}))
    assert not path_options & set(got["cell"])


# what each source publishes, by the file's own `source`: a configuration from another
# source brings its entry here (test_chipbench_olmoe.py holds OLMoE to the catalog's row too)
MISTRAL = "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json"
OLMOE = "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json"
PUBLISHED = {
    MISTRAL: {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
              "num_key_value_heads": 8, "vocab_size": 32000, "rope_theta": 10000.0,
              "rms_norm_eps": 1e-5, "sliding_window": 4096, "max_position_embeddings": 32768,
              "hidden_act": "silu", "tie_word_embeddings": False, "num_hidden_layers": 32},
    OLMOE: {"hidden_size": 2048, "intermediate_size": 1024, "num_attention_heads": 16,
            "num_key_value_heads": 16, "vocab_size": 50304, "rope_theta": 10000,
            "rms_norm_eps": 1e-5, "max_position_embeddings": 4096, "hidden_act": "silu",
            "tie_word_embeddings": False, "num_experts": 64, "num_experts_per_tok": 8,
            "norm_topk_prob": False, "num_hidden_layers": 16},
}


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_config_keeps_every_published_width(name):
    cfg = mf.read_json(mf.ROOT, f"chipbench/configs/{name}.json")
    published = dict(PUBLISHED[cfg["source"]])
    depth = published.pop("num_hidden_layers")
    assert {k: cfg[k] for k in published} == published
    assert ["num_hidden_layers"] == list(cfg["reduced"])
    assert cfg["published"]["num_hidden_layers"] == depth > cfg["num_hidden_layers"]
    assert len(cfg["source"]) <= 200
    assert {"param_dtype", "weights"} <= set(cfg["assumed"])
    assert ("sliding_window" in cfg["assumed"]) == ("sliding_window" in published)


@pytest.mark.parametrize("name", CONFIGS)
def test_manifest_entry_agrees_with_the_configuration_file(name):
    entry = mf.by_name(M["configs"], name, "config")
    cfg = mf.read_json(mf.ROOT, entry["file"])
    assert entry["file"] == f"chipbench/configs/{name}.json"
    assert entry["reduced"] == list(cfg["reduced"]) and entry["source"] == cfg["source"]


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_model_builder_refuses_a_changed_width(name):
    cfg = mf.read_json(mf.ROOT, f"chipbench/configs/{name}.json")
    builder = mf.load_plugin(mf.ROOT, "model_builders", cfg["model_builder"])
    model, _, _ = builder.build(cfg)
    assert (model.d_model, model.d_ff, model.n_layers) == (
        cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"])
    assert cfg["intermediate_size"] == PUBLISHED[cfg["source"]]["intermediate_size"] != 11008
    with pytest.raises(RuntimeError):
        builder.build({**cfg, "intermediate_size": 11008})


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)


def test_command_and_paths():
    assert M["command"][:3] == ["python3", "-m", "chipbench.run"]
    assert M["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51


def _broken(edit):
    m = copy.deepcopy(M)
    edit(m)
    return mf.problems(m)


BREAKS = {
    "space in a name": lambda m: m["per_layer"][0].update(name="gen late"),
    "unit too long": lambda m: m["end_to_end"][0].update(unit="milliseconds_of_wait"),
    "moves a metric its cell lacks": lambda m: m["end_to_end"][0].update(
        workloads=[m["workloads"][0]["name"]]),
    "an end-to-end metric read from the program": lambda m: m["end_to_end"][0].update(
        source="program_counter"),
    "moves no metric": lambda m: m["per_layer"][0].update(moves="nothing"),
    "bound over a tenth": lambda m: m["end_to_end"][0].update(bound=0.2),
    "two four-chip cells of two": lambda m: m["workloads"][0].update(chips=4),
    "a width in reduced": lambda m: m["configs"][0].update(reduced=["hidden_size"]),
    "a cell with no file": lambda m: m["workloads"].append(
        {**m["workloads"][0], "name": "m7b-ghost", "traffic": "ghost"}),
    "no setup_s": lambda m: m["end_to_end"].pop(),
    "run_seconds too long": lambda m: m.update(run_seconds=52),
}


@pytest.mark.parametrize("case", sorted(BREAKS))
def test_problems_catches(case):
    assert _broken(BREAKS[case]) != []

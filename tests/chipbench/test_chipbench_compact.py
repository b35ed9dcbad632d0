"""`moe_compact_pct` (PR 40): the entry, appended, for the two cells whose
step is built with the compact path; its reader on a hand-built trace and
record; and the assertions of test_chipbench_laguna.py's
`test_manifest_is_well_formed_with_the_cell`, which holds the cell's
reported metrics to exactly the set it entered with and is skipped from
tests/conftest.py: the same, with "what it entered with, then what later
PRs appended" in the set's place."""

import json

import pytest

from chipbench import manifest as mf, trace_reduce as tr

M = mf.load_manifest()
NAME = "moe_compact_pct"
CELLS = [w["name"] for w in M["workloads"]]
SHARE_CELLS = ["glm47f-train", "laguna-train"]  # 8 of 64 and 8 of 256 experts held
PR_39 = ("swa_share_pct", "attn_gate_pct", "flash_roofline.window", "flash_roofline.full48",
         "expert_matmul_roofline.held10", "train_mfu_pct.laguna")


def reader():
    return mf.load_plugin(mf.ROOT, "layer_metrics", NAME)


def test_metric_is_appended_for_the_two_small_shares():
    assert mf.problems(M) == []
    names = [m["name"] for m in M["per_layer"]]
    after = names[names.index(PR_39[-1]) + 1:]
    assert after[0] == NAME and len(set(names)) == len(names)
    m = mf.by_name(M["per_layer"], NAME, "metric")
    assert m == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                 "layer": "expert layer", "moves": "train_tok_s", "workloads": SHARE_CELLS}
    assert m["layer"] == mf.by_name(M["per_layer"], "moe_dispatch_pct", "metric")["layer"]
    assert m["workloads"] == [c for c in CELLS if c in SHARE_CELLS]
    # the cells that hold every expert or half of them build no compact path: not listed
    elsewhere = mf.by_name(M["per_layer"], "experts_elsewhere_pct", "metric")["workloads"]
    assert set(m["workloads"]) == set(elsewhere) - {"zaya1-train"}
    for cell in CELLS:
        reported = {e["name"] for e in mf.metrics_of(M, "per_layer", cell)}
        assert (NAME in reported) == (cell in SHARE_CELLS)


def test_laguna_cell_reports_what_it_entered_with_and_what_was_appended_since():
    import test_chipbench_laguna as laguna

    cell = mf.load_cell(mf.ROOT, M, laguna.CELL)
    assert cell["chips"] == 1 and cell["traffic"]["generator"] == "zipf_tokens"
    entry = mf.by_name(M["configs"], laguna.CONFIG, "config")
    assert entry["reduced"] == laguna.REDUCED == list(laguna.SHAPE["reduced"])
    assert entry["source"] == laguna.SHAPE["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    for key in ("assumed", "published", "deployment", "stands_for", "memory", "reference",
                "check", "train"):
        assert laguna.SHAPE[key], key
    assert {"gate", "qk_norm", "rotary_pairing", "yarn", "router", "router_bias_update",
            "shared_expert", "param_dtype", "weights"} <= set(laguna.SHAPE["assumed"])
    assert "TO FILL" not in json.dumps(laguna.SHAPE)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", laguna.CELL)}
    entered = set(laguna.NEW_METRICS) | set(laguna.JOINED)
    names = [m["name"] for m in M["per_layer"]]
    appended_since = set(names[names.index(PR_39[-1]) + 1:])
    assert entered <= reported and reported - entered <= appended_since
    assert NAME in reported and len(laguna.SETUP) == 11
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", laguna.CELL)} == {
        "train_tok_s", "setup_s"}
    assert not reported & {"flash_roofline", "flash_roofline.mla", "expert_matmul_roofline",
                           "train_mfu_pct.moe", "train_mfu_pct", "expert_matmul_roofline.held",
                           "expert_matmul_roofline.held4", "train_mfu_pct.zaya",
                           "train_mfu_pct.glm"}
    assert len(CELLS) >= 6 and [w["chips"] for w in M["workloads"]].count(4) == 1
    assert CELLS[5] == laguna.CELL and M["configs"][5]["name"] == laguna.CONFIG
    assert CELLS == laguna.TRAINING_CELLS
    why = cell["cell"]["why"]
    assert len(why) <= 200 and "160 rows" in why and "5,120" in why and "40,960" in why
    assert why == mf.by_name(M["workloads"], laguna.CELL, "workload")["why"]


# -- the reader, on a hand-built trace and record --------------------------------

HELD = "jit(step)/transpose(jvp(block.stack))/while/body/checkpoint/moe.experts/cond/" \
       "branch_1_fun/transpose(jvp(moe.experts))/moe.held/jit(ragged-dot-tiled-wgrad)/pallas_call"
ALL = HELD.replace("branch_1_fun", "branch_0_fun").replace("moe.held", "moe.all")
RECORD = {
    "ragged-dot-tiled-wgrad.27": [("custom-call", HELD, ())],
    "ragged-dot-tiled-wgrad.28": [("custom-call", HELD, ())],
    "ragged-dot-tiled-wgrad.29": [("custom-call", HELD, ())],
    "ragged-dot-tiled-wgrad.24": [("custom-call", ALL, ())],
    "ragged-dot-tiled-wgrad.25": [("custom-call", ALL, ())],
    "ragged-dot-tiled-wgrad.26": [("custom-call", ALL, ())],
    # not counted: another kernel of the branch, and an instruction of a block with no branch
    "ragged-dot-tiled-dgrad.27": [("custom-call", HELD.replace("wgrad", "dgrad"), ())],
    "ragged-dot-tiled-wgrad.3": [("custom-call", "jit(step)/moe.experts/pallas_call", ())],
    "fusion.11": [("fusion", HELD.replace("jit(ragged-dot-tiled-wgrad)/pallas_call", "mul"), ())],
}


def _run(block_branches, window=(1.0, 2.0)):
    """A traced window of `block_branches` expert blocks: 27 / 28 / 29 ran
    where a block took the held rows, 24 / 25 / 26 where it took all."""
    events, t = [], window[0]
    for held in block_branches:
        for n in ((27, 28, 29) if held else (24, 25, 26)):
            events += [(f"kernel:ragged-dot-tiled-wgrad.{n}", t, 1e-4),
                       ("kernel:ragged-dot-tiled-dgrad.27", t + 2e-4, 1e-4), ("fusion.11", t, 1e-5)]
            t += 1e-3
    events += [("kernel:ragged-dot-tiled-wgrad.3", t, 1e-4),
               ("kernel:ragged-dot-tiled-wgrad.24", window[1] + 0.5, 1e-4)]  # after the window
    return {"trace": tr.Trace({"/device:TPU:0": events}, {}, []), "win": window}


@pytest.fixture
def program(monkeypatch):
    """The program's surfaces the reader asks: the engage counters and the
    record of the compiled step."""
    from ray_tpu import obs

    state = {"counters": {"moe.compact": {"count": 4, "busy_s": 0.1}}, "record": RECORD}
    monkeypatch.setattr(obs, "layer_counters", lambda: state["counters"])
    monkeypatch.setattr(obs, "op_names", lambda: state["record"])
    return state


def test_reader_counts_blocks_by_the_branch_their_kernels_stand_in(program):
    read = reader().read
    assert reader().__doc__ and "moe.held" in reader().__doc__
    assert read(_run([True] * 12)) == 100.0
    assert read(_run([True, True, False, True])) == 75.0
    assert read(_run([False] * 3)) == 0.0


def test_reader_reads_nothing_where_there_is_nothing_to_read(program, monkeypatch):
    from ray_tpu import obs

    read = reader().read
    # no trace: an untraced run, the parent's line
    assert read({}) is None and read({"trace": None, "win": (0, 1)}) is None
    # no kernel of either branch in the window (under a mesh: `ragged_dot`)
    assert read(_run([])) is None
    # no site built with the compact path: olmoe-train, zaya1-train
    program["counters"] = {"moe.full": {"count": 6, "busy_s": 0.1}}
    assert read(_run([True] * 3)) is None
    program["counters"] = {"moe.compact": {"count": 4, "busy_s": 0.1}}
    program["record"] = None  # the step was never called: no record
    assert read(_run([True] * 3)) is None
    # a program without the counters or the record (the parent of the PRs that added them)
    monkeypatch.delattr(obs, "op_names")
    assert read(_run([True] * 3)) is None

"""The step's table (PR 37): the one attribution of chipbench/readers_step.py on a
hand-built trace and a hand-written compiled module, the readers that share it, the
tool that prints it, and the manifest with the new metrics in it."""

import json

import pytest

from chipbench import manifest as mf, readers_step as rs, trace_reduce as tr
from chipbench.tools import step_table as tool
from ray_tpu import obs
from ray_tpu.obs.programs import parse_op_names

M = mf.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
# the cells that train, by the manifest: whichever cell joins later is held as these are
TRAINING = [c for c in CELLS
            if "train_tok_s" in {m["name"] for m in mf.metrics_of(M, "end_to_end", c)}]
# name -> (unit, source, layer, the cells that read it when PR 37 wrote it, or None: every
# training cell). A later cell that has such a block joins the list; none leaves it.
NEW = {
    "head_share_pct": ("%", "device_trace", "model programs", None),
    "optim_share_pct": ("%", "device_trace", "train step", None),
    "wgrad_optim_fused_pct": ("%", "device_trace", "train step", None),
    "attn_share_pct": ("%", "device_trace", "attention",
                       ["m7b-train", "m7b-train-4chip", "olmoe-train"]),
    "ffn_share_pct": ("%", "device_trace", "model programs",
                      ["m7b-train", "m7b-train-4chip", "glm47f-train"]),
    "step_unscoped_pct": ("%", "device_trace", "train step", None),
    "hbm_step_gib.train": ("GiB", "program_counter", "device", None),
    # the cells whose step has an overlapped ring or a grouped matmul to fall back from
    "fallback_sites.train": ("count", "program_counter", "kernels",
                             ["m7b-train-4chip", "olmoe-train", "zaya1-train", "glm47f-train"]),
    "block_share_pct": ("%", "device_trace", "model programs", None),
}
SHARES = [n for n in NEW if n.endswith("_pct")]


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


# -- the manifest with the new metrics ------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_has_its_entry_its_cells_and_its_reader(name):
    unit, source, layer, cells = NEW[name]
    m = mf.by_name(M["per_layer"], name, "metric")
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source, "layer": layer,
        "moves": "train_tok_s"}
    assert set(m["workloads"]) <= set(TRAINING)
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]  # the manifest's order
    if cells is None:
        assert m["workloads"] == TRAINING
    else:
        assert set(cells) <= set(m["workloads"])
    assert layer in {e["layer"] for e in M["per_layer"] if e["name"] not in NEW}
    assert callable(reader(name).read) and reader(name).__doc__
    # nothing to read: a run that trained nothing, a run without a trace
    assert reader(name).read({}) is None and reader(name).read({"busy": None}) is None


def test_new_metrics_are_appended_and_the_manifest_is_well_formed():
    assert mf.problems(M) == []
    assert [m["name"] for m in M["per_layer"]][-len(NEW):] == list(NEW)
    assert [w["chips"] for w in M["workloads"]].count(4) == 1
    assert len(M["workloads"]) >= 5


# What test_chipbench_olmoe.py and test_chipbench_glm_lite.py hold of their cell in
# `test_manifest_is_well_formed_with_the_cell` (skipped from tests/conftest.py since this PR:
# each also holds the cell's reported metrics to exactly the set it entered with, and a PR
# that is no `benchmark` PR edits no file here). Every other assertion of the two is carried
# here, for EVERY training cell where the manifest can say it, and for the two cells what
# only they have. The lists are the manifest's, so the next metric that joins a cell skips nothing.
SETUP = [m["name"] for m in M["per_layer"] if m["name"].startswith("setup_")]
EVERY_CELL = ["compiles_in_window.train", "device_idle_pct.train", "hbm_peak_gib.train",
              "report_ms.train"] + SETUP
EXPERT = ["moe_share_pct", "moe_dispatch_pct", "expert_imbalance"]
# cell -> what its own test held of it alone
OF_THE_CELL = {
    "olmoe-train": dict(
        reduced=["num_hidden_layers"], shape=("assumed", "stands_for", "memory", "reference"),
        reports=["train_mfu_pct.moe", "expert_matmul_roofline", "flash_roofline"] + EXPERT),
    "glm47f-train": dict(
        reduced=["num_hidden_layers", "n_routed_experts", "vocab_size"],
        shape=("assumed", "published", "deployment", "stands_for", "memory", "reference",
               "check", "train"),
        assumed={"param_dtype", "weights", "rotary_pairing", "mtp_merge_order",
                 "mtp_loss_weight", "router_bias_update"},
        reports=["mla_share_pct", "mla_glue_pct", "flash_roofline.mla",
                 "expert_matmul_roofline.held4", "mtp_share_pct", "train_mfu_pct.glm",
                 "experts_elsewhere_pct"] + EXPERT,
        # their cost functions read `hidden_size / heads`, `num_experts` or every pair: wrong here
        never={"flash_roofline", "expert_matmul_roofline", "train_mfu_pct.moe", "train_mfu_pct",
               "expert_matmul_roofline.held", "train_mfu_pct.zaya"},
        at=4, why=("T/16", "T/2")),
}


@pytest.mark.parametrize("name", TRAINING)
def test_training_cell_is_well_formed_and_reports_what_it_did_and_the_new_metrics(name):
    cell = mf.load_cell(mf.ROOT, M, name)
    entry = mf.by_name(M["configs"], cell["config_name"], "config")
    shape = mf.read_json(mf.ROOT, entry["file"])
    assert cell["traffic"]["generator"] == "zipf_tokens"
    # one cell asks for four chips, and says so in its name
    assert cell["chips"] == (4 if name.endswith("-4chip") else 1)
    assert entry["reduced"] == list(shape["reduced"])
    assert entry["source"] == shape["source"] and len(entry["source"]) <= 200
    # the driver holds a configuration's `why` to 200 characters; `problems` checks the cells' only
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert len(cell["cell"]["why"]) <= 200
    for key in ("assumed", "published", "stands_for", "memory", "train"):
        assert shape[key], key
    assert "TO FILL" not in json.dumps(shape)
    reported = {m["name"] for m in mf.metrics_of(M, "per_layer", name)}
    assert len(SETUP) == 11 and set(EVERY_CELL) <= reported
    assert {n for n, (_, _, _, cells) in NEW.items() if cells is None or name in cells} <= reported
    assert {m["name"] for m in mf.metrics_of(M, "end_to_end", name)} == {"train_tok_s", "setup_s"}
    held = OF_THE_CELL.get(name)
    if held is None:
        return
    assert entry["reduced"] == held["reduced"]
    for key in held["shape"]:
        assert shape[key], key
    assert held.get("assumed", set()) <= set(shape["assumed"])
    assert set(held["reports"]) <= reported and not reported & held.get("never", set())
    if "at" in held:
        assert M["workloads"][held["at"]]["name"] == name
    assert all(part in cell["cell"]["why"] for part in held.get("why", ()))


# -- the attribution, on a hand-built trace and a hand-written compiled module ----

HLO = """
HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_head_wgrad (p0: bf16[8,4], p1: f32[4,16]) -> (f32[4,16], f32[4,16]) {
  %p0 = bf16[8,4]{1,0} parameter(0)
  %p1 = f32[4,16]{1,0:T(8,128)} parameter(1)
  %convolution.1 = f32[4,16]{1,0} convolution(%p0, %p0), window={size=1}, dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(head))/dot_general"}
  %multiply.1 = f32[4,16]{1,0} multiply(%convolution.1, %p1), metadata={op_name="jit(step)/optim/mul"}
  ROOT %tuple.1 = (f32[4,16]{1,0}, f32[4,16]{1,0}) tuple(%multiply.1, %p1), metadata={op_name="jit(step)/optim/add"}
}

%fused_adam (p0: f32[4,16]) -> f32[4,16] {
  %p0.1 = f32[4,16]{1,0} parameter(0)
  ROOT %sqrt.1 = f32[4,16]{1,0} sqrt(%p0.1), metadata={op_name="jit(step)/optim/sqrt"}
}

%fused_norm_into_qkv (p0: bf16[8,4]) -> bf16[8,4] {
  %p0.2 = bf16[8,4]{1,0} parameter(0)
  %multiply.2 = bf16[8,4]{1,0} multiply(%p0.2, %p0.2), metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/block.norm/mul"}
  ROOT %dot.2 = bf16[8,4]{1,0} dot(%multiply.2, %p0.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/attn.qkv/bsd,dh->bsh/dot_general"}
}

%fused_stack_write (p0: bf16[8,4]) -> bf16[2,8,4] {
  %p0.3 = bf16[8,4]{1,0} parameter(0)
  %add.3 = bf16[8,4]{1,0} add(%p0.3, %p0.3), metadata={op_name="jit(step)/jvp(mtp.block)/mla.up/add"}
  %negate.3 = bf16[8,4]{1,0} negate(%add.3), metadata={op_name="jit(step)/jvp(mtp.block)/mla.up/neg"}
  ROOT %dynamic-update-slice.3 = bf16[2,8,4]{2,1,0} dynamic-update-slice(%negate.3), metadata={op_name="jit(step)/jvp()/while/body/dynamic_update_slice"}
}

%fused_experts (p0: bf16[8,4], p1: bf16[1,4,16]) -> bf16[8,16] {
  %p0.4 = bf16[8,4]{1,0} parameter(0)
  %p1.4 = bf16[1,4,16]{2,1,0} parameter(1)
  ROOT %dot.4 = bf16[8,16]{1,0} dot(%p0.4, %p1.4), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/moe.experts/dot_general"}
}

%body (arg: (s32[], bf16[8,4], bf16[2,4,16])) -> (s32[], bf16[8,4]) {
  %arg = (s32[], bf16[8,4]{1,0}, bf16[2,4,16]{2,1,0}) parameter(0)
  %get-tuple-element.7 = bf16[2,4,16]{2,1,0} get-tuple-element(%arg), index=2
  %dynamic-slice.8 = bf16[1,4,16]{2,1,0} dynamic-slice(%get-tuple-element.7, %i), dynamic_slice_sizes={1,4,16}, metadata={op_name="jit(step)/jvp(block.stack)/while/body/dynamic_slice"}
  %fusion.21 = bf16[8,16]{1,0} fusion(%fusion.20, %dynamic-slice.8), kind=kOutput, calls=%fused_experts, metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/moe.experts/mul"}
  %add.6 = bf16[8,4]{1,0} add(%fusion.20, %fusion.20), metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/add"}
  %fusion.20 = bf16[8,4]{1,0} fusion(%x), kind=kOutput, calls=%fused_norm_into_qkv, metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/attn.qkv/bsd,dh->bsh/dot_general"}
  %attn.attend.7 = bf16[8,4]{1,0} custom-call(%fusion.20), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(block.stack)/while/body/closed_call/attn.attend/pallas_call"}
  %copy.5 = bf16[8,4]{0,1} copy(%attn.attend.7), metadata={op_name="jit(step)/jvp(block.stack)/while/body/squeeze"}
  ROOT %tuple.9 = (s32[], bf16[8,4]{1,0}) tuple(%i, %copy.5)
}

ENTRY %main.1 () -> f32[] {
  %convert.41 = bf16[2,4,16]{2,1,0} convert(%state_params__layers____w_up__)
  %tuple.8 = (s32[], bf16[8,4]{1,0}, bf16[2,4,16]{2,1,0}) tuple(%zero, %h0, %convert.41)
  %while.3 = (s32[], bf16[8,4]{1,0}) while(%tuple.8), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(block.stack)/while"}
  %fusion.30 = bf16[2,8,4]{2,1,0} fusion(%y), kind=kLoop, calls=%fused_stack_write, metadata={op_name="jit(step)/jvp()/while/body/dynamic_update_slice"}
  %fusion.10 = (f32[4,16]{1,0}, f32[4,16]{1,0}) fusion(%h, %w), kind=kOutput, calls=%fused_head_wgrad, metadata={op_name="jit(step)/optim/add"}
  %fusion.11 = f32[4,16]{1,0} fusion(%v), kind=kLoop, calls=%fused_adam, metadata={op_name="jit(step)/optim/sqrt"}
  %convert.40 = bf16[4,16]{1,0} convert(%state_params__lm_head__)
  %tuple.99 = (bf16[4,16]{1,0}) tuple(%convert.40)
  %broadcast.60 = bf16[2,8,4]{2,1,0} broadcast(%zero), dimensions={}, metadata={op_name="broadcast.95"}
  %fusion.31 = bf16[2,8,4]{2,1,0} fusion(%broadcast.60), kind=kLoop, calls=%fused_stack_write, metadata={op_name="jit(step)/jvp(block.stack)/while/body/dynamic_update_slice"}
  ROOT %reduce.50 = f32[] reduce(%z, %zero), dimensions={0}, to_apply=%sum, metadata={op_name="jit(step)/jvp(mtp.block)/mla.glue/reduce_sum"}
}
"""
NAMES = parse_op_names(HLO)


def _trace(extra=()):
    # one device, a window of 1.0 s; the while spans its body's three operations
    # and 0.01 s of the while is covered by none of them
    ops = [("while.3", 0.0, 0.45), ("fusion.20", 0.0, 0.2), ("kernel:attn.attend.7", 0.2, 0.19),
           ("copy.5", 0.4, 0.05), ("fusion.30", 0.45, 0.05), ("fusion.10", 0.5, 0.25),
           ("fusion.11", 0.75, 0.1), ("convert.40", 0.85, 0.02), ("reduce.50", 0.87, 0.03),
           *extra]
    host = [("python", "chipbench.window", 0.0, 1.0)]
    programs = [("jit_step(123)", 0.0, 0.92), ("jit_make(77)", 0.92, 0.08)]
    return tr.from_dict({"device_ops": {"/device:TPU:0": ops}, "host": host,
                         "device_programs": {"/device:TPU:0": programs}})


RULES = mf.trace_names(mf.ROOT)["rules"]


def _table(extra=()):
    trace = _trace(extra)
    win = tr.window(trace)
    return rs.table_of(trace, win, tr.busy(trace, win)["busy_s"], NAMES, RULES)


def test_op_names_holds_every_computations_instructions_and_a_fusions_own():
    assert NAMES["fusion.10"][0] == ("fusion", "jit(step)/optim/add", ("h", "w"))
    assert [e[0] for e in NAMES["fusion.10"][1:]] == [
        "parameter", "parameter", "convolution", "multiply", "tuple"]
    assert NAMES["attn.attend.7"] == [
        ("custom-call", "jit(step)/jvp(block.stack)/while/body/closed_call/attn.attend/pallas_call",
         ("fusion.20",))]
    assert NAMES["while.3"][0][0] == "while" and NAMES["convert.40"][0][:2] == ("convert", "")
    # the compiler's own carries no path, and the record gives it none: what it is for is
    # the reader's to say, from the operands
    assert NAMES["convert.41"] == [("convert", "", ("state_params__layers____w_up__",))]
    assert NAMES["copy.5"][0][0] == "copy" and "multiply.2" in NAMES  # a body's, a fused one's
    # seen through the loop's tuple: the body's parameter reads the tuple the loop was given,
    # and the element taken from it reads what stands at its index there
    assert NAMES["arg"][0] == ("parameter", "", ("tuple.8",))
    assert NAMES["get-tuple-element.7"][0] == ("get-tuple-element", "", ("convert.41",))
    assert NAMES["dot.2"][0][2] == ("multiply.2", "p0.2")


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jvp(mtp.block)/mla.up/add", "mla.up"),                      # innermost
    ("jit(step)/transpose(jvp(mtp.block))/checkpoint/moe.experts/mul", "moe.experts"),
    ("jit(step)/jvp(block.stack)/while/body/closed_call/attn.qkv/tp_overlap.ag_matmul/dot_general",
     "attn.qkv"),                                                           # a ring is no scope
    ("jit(step)/transpose(jvp(head))/dot_general", "head"),
    ("jit(step)/jvp(mtp.head)/reduce_max", "mtp.head"),
    ("jit(step)/jvp()/while/body/dynamic_update_slice", None),
    # a shallow scope holds the scan's own and no more: a block's operation without a name
    # of its own is NOT the stack's
    ("jit(step)/jvp(block.stack)/while", "block.stack"),
    ("jit(step)/transpose(jvp(block.stack))/while/body/dynamic_update_slice", "block.stack"),
    ("jit(step)/jvp(block.stack)/broadcast_in_dim", "block.stack"),
    ("jit(step)/jvp(block.stack)/while/body/closed_call/add", None),
    ("jit(step)/transpose(jvp(block.stack))/while/body/closed_call/checkpoint/remat2", None),
    ("jit(step)/jvp(block.stack)/while/body/closed_call/block.norm/mul", "block.norm"),
    ("jit(step)/jvp(header)/moe.experts2/embedding/add", None),            # whole components only
    ("", None),
])
def test_a_paths_scope_is_the_innermost_listed_one(path, scope):
    assert rs.scope_of_path(path) == scope


def test_fusions_are_booked_by_the_matmul_they_hold_then_the_root_then_the_majority():
    # the norm fused into the projection reads the projection's scope
    assert rs.book(NAMES["fusion.20"]) == ("attn.qkv", False)
    # the head's weight gradient fused with AdamW: the head's, and counted apart
    assert rs.book(NAMES["fusion.10"]) == ("head", True)
    assert rs.book(NAMES["fusion.11"]) == ("optim", False)
    # a root under no listed scope: what most of its instructions carry
    assert rs.book(NAMES["fusion.30"]) == ("mla.up", False)
    # the scan's own slice is the stack's; the block's add that no scope holds is nobody's
    assert rs.book(NAMES["copy.5"]) == ("block.stack", False)
    assert rs.book(NAMES["add.6"]) == (None, False)
    assert rs.book(NAMES["convert.40"]) == (None, False)
    # a matmul of the update alone (none today) is the update's
    assert rs.book([("fusion", "", ()), ("dot", "jit(step)/optim/dot_general", ())]) \
        == ("optim", False)


def test_the_compilers_own_instruction_is_booked_to_what_its_result_is_for():
    users = rs.users_of(NAMES)
    # the stack's weights converted before the scan: through the loop's tuple and the scan's
    # slice (shallow) to the expert matmul that multiplies by them
    assert rs.inherited("convert.41", NAMES, users) == "moe.experts"
    # a zero fill (its path a bare name) that only the stack's write reads: the stack's
    assert rs.inherited("broadcast.60", NAMES, users) == "block.stack"
    # nothing the program named reads it
    assert rs.inherited("convert.40", NAMES, users) is None
    # too far to be called its consumer
    assert rs.inherited("convert.41", NAMES, users, reach=2) == "block.stack"
    table = _table(extra=[("convert.41", 0.90, 0.005), ("broadcast.60", 0.905, 0.005),
                          ("add.6", 0.91, 0.005)])
    assert table["scopes"]["moe.experts"]["ops"] == {"convert.41": pytest.approx(0.005)}
    assert "broadcast.60" in table["scopes"]["block.stack"]["ops"]
    # a path of the program's own under no listed scope is never inherited away
    assert "add.6" in table["scopes"][rs.UNSCOPED]["ops"] and "add.6" not in table["unknown"]


def test_vocabulary_is_merged_from_the_files_of_step_scopes(tmp_path):
    d = tmp_path / "chipbench" / "step_scopes"
    d.mkdir(parents=True)
    (d / "base.json").write_text(json.dumps(mf.read_json(mf.ROOT, "chipbench/step_scopes/base.json")))
    (d / "ssm.json").write_text(json.dumps(
        {"families": {"ssm": ["ssm.scan", "ssm.proj"], "attn": ["attn.gate"]}}))
    got = rs.vocabulary(str(tmp_path))
    assert got["families"]["ssm"] == ["ssm.scan", "ssm.proj"]
    assert got["families"]["attn"] == rs.VOCABULARY["families"]["attn"] + ["attn.gate"]
    assert got["program"] == rs.STEP_PROGRAM and got["shallow"] == rs.VOCABULARY["shallow"]
    # every scope is in one family, and every family of a share's reader is there
    assert len(rs.SCOPES) == len(set(rs.SCOPES)) == sum(map(len, rs.VOCABULARY["families"].values()))
    assert {"head", "optim", "attn", "ffn", "block"} <= set(rs.VOCABULARY["families"])
    assert set(rs.SHALLOW) <= set(rs.SCOPES)


def test_table_books_every_leaf_once_and_the_families_sum_to_the_busy_time():
    # fusion.999: no such instruction; fusion.11 AGAIN, inside the batch maker's program
    table = _table(extra=[("fusion.999", 0.9, 0.02), ("fusion.11", 0.93, 0.03)])
    assert table["busy_s"] == pytest.approx(0.95)
    seconds = {scope: row["seconds"] for scope, row in table["scopes"].items()}
    assert seconds == pytest.approx({
        "attn.qkv": 0.2, "attn.attend": 0.19, "block.stack": 0.06, "mla.up": 0.05, "head": 0.25,
        "optim": 0.1, "mla.glue": 0.03, rs.UNSCOPED: 0.07})
    # the while is booked what its body's operations leave uncovered of it, no more
    assert table["scopes"]["block.stack"]["ops"] == pytest.approx({"copy.5": 0.05, "while.3": 0.01})
    # the kernel is found under its instruction's name
    assert table["scopes"]["attn.attend"]["ops"] == {"kernel:attn.attend.7": pytest.approx(0.19)}
    # an operation the compiled text does not know, or one of another program whatever
    # its name, is unscoped, and said to be unknown
    assert table["unknown"] == pytest.approx({"fusion.999": 0.02, "fusion.11": 0.03})
    assert table["scopes"][rs.UNSCOPED]["ops"].keys() == {"convert.40", "fusion.999", "fusion.11"}
    assert table["scopes"]["optim"]["ops"] == {"fusion.11": pytest.approx(0.1)}
    assert table["fused_with_optim_s"] == pytest.approx(0.25)
    families = rs.family_seconds(table)
    assert families == pytest.approx({"attn": 0.39, "block": 0.06, "mla": 0.08, "head": 0.25,
                                      "optim": 0.1, rs.UNSCOPED: 0.07})
    assert sum(families.values()) == pytest.approx(table["busy_s"])


def test_seconds_are_clipped_to_the_window_and_averaged_over_devices():
    ops = [("fusion.11", -0.5, 1.0), ("fusion.10", 0.5, 1.0)]
    trace = tr.from_dict({"device_ops": {"/device:TPU:0": ops, "/device:TPU:1": ops[:1]},
                          "host": [("python", "chipbench.window", 0.0, 1.0)]})
    table = rs.table_of(trace, (0.0, 1.0), 0.75, NAMES)
    assert table["scopes"]["optim"]["seconds"] == pytest.approx(0.5)   # 0.5 on each of two
    assert table["scopes"]["head"]["seconds"] == pytest.approx(0.25)   # 0.5 on one of two


GIB = 2 ** 30
MEMORY_ANALYSIS = {"argument_size_in_bytes": 8 * GIB, "output_size_in_bytes": 8 * GIB,
                   "temp_size_in_bytes": 3 * GIB, "alias_size_in_bytes": 8 * GIB}
COUNTERS = {"tp_overlap.ag_matmul": {"count": 4, "busy_s": 0.1},
            "tp_overlap.plain": {"count": 2, "busy_s": 0.0},
            "grouped_matmul.kernel": {"count": 3, "busy_s": 0.0},
            "grouped_matmul.ragged_dot": {"count": 1, "busy_s": 0.0}}


def _run(monkeypatch, record=True):
    trace = _trace()
    win = tr.window(trace)
    if record:
        monkeypatch.setattr(obs, "op_names", lambda: NAMES, raising=False)
    else:  # the parent's program: no record to ask
        monkeypatch.delattr(obs, "op_names")
    return {"kind": "train", "trace": trace, "win": win, "busy": tr.busy(trace, win),
            "rules": RULES, "memory_analysis": MEMORY_ANALYSIS}


def test_readers_read_the_table_the_runs_memory_and_the_programs_counters(monkeypatch):
    run = _run(monkeypatch)
    monkeypatch.setattr(obs, "layer_counters", lambda: COUNTERS)
    got = {name: reader(name).read(run) for name in NEW}
    busy = 0.9
    assert got == pytest.approx({
        "head_share_pct": 100 * 0.25 / busy, "optim_share_pct": 100 * 0.1 / busy,
        "wgrad_optim_fused_pct": 100 * 0.25 / busy, "attn_share_pct": 100 * 0.39 / busy,
        "ffn_share_pct": 0.0, "step_unscoped_pct": 100 * 0.02 / busy,
        "block_share_pct": 100 * 0.06 / busy,
        "hbm_step_gib.train": 11.0, "fallback_sites.train": 3.0})
    monkeypatch.setattr(obs, "op_names", lambda: pytest.fail("the table is made once a run"))
    assert reader("head_share_pct").read(run) == got["head_share_pct"]


def test_readers_return_nothing_on_a_program_without_the_record(monkeypatch):
    run = _run(monkeypatch, record=False)
    assert all(reader(name).read(run) is None for name in SHARES)
    run = _run(monkeypatch)
    monkeypatch.setattr(obs, "op_names", lambda: None)  # noted, and never run
    assert all(reader(name).read(dict(run)) is None for name in SHARES)
    # the compiler's account is the traced run's: a run that could not take it says nothing
    read = reader("hbm_step_gib.train").read
    assert read({"kind": "train", "memory_analysis": None}) is None
    assert read({"kind": "train", "memory_analysis": {"error": "RuntimeError()"}}) is None


@pytest.mark.parametrize("counters,want", [
    (COUNTERS, 3.0),
    ({k: v for k, v in COUNTERS.items() if "plain" not in k and "ragged" not in k}, 0.0),
    # one kind of site alone (a one-chip expert cell: no ring to ask for)
    ({"grouped_matmul.kernel": {"count": 9, "busy_s": 0.0}}, 0.0),
    ({"grouped_matmul.ragged_dot": {"count": 9, "busy_s": 0.0}}, 9.0),
    # no site of either kind counted on either path: not "all engaged"
    ({"train.report": {"count": 9, "busy_s": 0.0}}, None),
    ({}, None),
], ids=["both_fall_back", "all_engaged", "one_kind", "none_engaged", "no_site", "no_counters"])
def test_fallback_sites_reads_nothing_where_no_site_was_counted(monkeypatch, counters, want):
    monkeypatch.setattr(obs, "layer_counters", lambda: counters)
    assert reader("fallback_sites.train").read({"kind": "train"}) == want
    assert reader("fallback_sites.train").read({"kind": "serve"}) is None


# -- the tool that prints the table ----------------------------------------------


def test_step_table_tool_prints_a_row_a_scope_the_unscoped_operations_and_the_memory():
    table = _table(extra=[("fusion.999", 0.9, 0.02), ("fusion.11", 0.93, 0.03)])
    memory = tool.memory_of({"memory_analysis": MEMORY_ANALYSIS})
    assert tool.memory_of({"memory_analysis": {"error": "x"}}) is None
    text = tool.render(table, NAMES, memory, steps=1, title="m7b-train seed 7",
                       counters=COUNTERS)
    lines = text.splitlines()
    head = next(line for line in lines if line.startswith("head "))
    assert "250.000" in head and "26.32" in head and "fusion.10 bwd 250.000" in head
    assert next(i for i, line in enumerate(lines) if line.startswith("attn.qkv")) \
        < next(i for i, line in enumerate(lines) if line.startswith("mla.glue"))  # by seconds
    assert any(line.startswith("family attn") and "41.05" in line for line in lines)
    assert any("fusion.999" in line and "not the step's" in line for line in lines)
    assert any("while.3 fwd 10.000" in line for line in lines)
    assert any("convert.40" in line for line in lines)
    assert any("fused with optim" in line and "26.32" in line for line in lines)
    assert any("11.000 GiB" in line for line in lines)
    assert "sites tp_overlap: engaged 4, fallback 2" in lines
    assert "sites grouped_matmul: engaged 3, fallback 1" in lines
    assert json.loads(json.dumps(tool.as_json(table, memory, steps=1)))["families"]["head"] \
        == pytest.approx(250.0)

"""The eight readers of the step's host timeline (PR 51) on hand-built rows and traces, and
the tool that prints them: the value by hand, the profiler's two pauses of a traced run
taken off, the window's last step blanked, and None where the program keeps no timeline
(the parent of PR 51) or the run has no trace."""

import copy
import importlib.util
import json
import os

import pytest

from chipbench import manifest as mf, readers_timeline as rt, trace_reduce as tr
from chipbench.tools import step_timeline as tool

M = mf.load_manifest()
NEW = {
    "dispatch_ms.train": ("ms", "program_span", "train step"),
    "step_stalls.train": ("count", "program_span", "trainer"),
    "stall_loss_pct.train": ("%", "program_span", "trainer"),
    "gc_pause_ms.train": ("ms", "program_counter", "runtime and trainer"),
    "report_max_ms.train": ("ms", "program_span", "trainer"),
    "host_other_cpu_pct.train": ("%", "program_counter", "runtime and trainer"),
    "step_gap_ms.train": ("ms", "device_trace", "device"),
    "step_gap_program_pct.train": ("%", "program_span", "trainer"),
}
FROM_THE_TIMELINE = [n for n in NEW if not n.startswith("step_gap")]
W0 = 1000.0
RULES = mf.trace_names(mf.ROOT)["rules"]


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


def row(k, period=0.1, **over):
    r = {"start": W0 + 0.001 + 0.1 * k, "dispatch_s": 0.0005, "wait_s": period - 0.002,
         "report_s": 0.0003, "between_s": 0.0012, "period_s": period, "thread_cpu_s": 0.003,
         "other_cpu_s": 0.002, "nivcsw": 0, "gc_s": 0.0, "gc_generation": None,
         "compile_s": 0.0}
    r.update(over)
    return r


def a_run(rows, monkeypatch, traced=False, paused=None):
    """A runner's `run` whose program answers `obs.step_timeline` with `rows`; the runner's
    own clock (`steps[k]["start"]`) has `paused[k]` seconds taken off BEFORE step k."""
    from ray_tpu import obs

    starts, at, off = [], W0, 0.0
    for k, r in enumerate(rows):
        r["start"] = at + 0.001
        off += (paused or {}).get(k, 0.0)
        starts.append({"start": at - W0 - off})
        at += r["period_s"] if r["period_s"] is not None else 0.1
    monkeypatch.setattr(obs, "step_timeline",
                        lambda since, until: [dict(r) for r in rows
                                              if since <= r["start"] <= until], raising=False)
    run = {"kind": "train", "window_wall": (W0, at + 0.0005), "steps": starts,
           "values": {"train_tok_s": 1.0, "setup_s": 2.0}}
    if traced:
        run["traced_steps"] = 3
    return run


def test_the_eight_are_appended_for_all_nine_cells_and_the_manifest_has_no_problems():
    cells = [w["name"] for w in M["workloads"]]
    tail = M["per_layer"][-8:]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        assert (m["unit"], m["source"], m["layer"]) == NEW[m["name"]]
        assert m["better"] == "lower" and m["moves"] == "train_tok_s"
        assert m["workloads"] == cells and len(cells) == 9
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert mf.problems(M) == []


# the cases tests/conftest.py skips: they hold a cell's reported set, or the end of
# `per_layer`, to what it was when the cell entered
CARRIED = [("test_chipbench_nemotron_h.py", "test_manifest_is_well_formed_with_the_cell", ()),
           ("test_chipbench_olmo_hybrid.py", "test_manifest_is_well_formed_with_the_cell", ())]
CARRIED += [("test_chipbench_nemotron_h.py",
             "test_new_metric_is_this_cells_alone_and_moves_train_tok_s", (name,))
            for name in ("ssm_share_pct", "ssm_scan_pct", "ssm_glue_pct", "ssd_scan_roofline",
                         "flash_roofline.full32", "expert_matmul_roofline.held6",
                         "train_mfu_pct.nemotron_h")]


@pytest.mark.parametrize("file,test,case", CARRIED, ids=lambda v: "-".join(v) if
                         isinstance(v, tuple) else v)
def test_an_earlier_cells_test_holds_on_the_manifest_less_what_pr_51_appended(
        file, test, case, monkeypatch):
    """The test as its PR wrote it, every assertion, with PR 51's eight entries (the END of
    the list, held above) taken off the manifest it reads."""
    path = os.path.join(os.path.dirname(__file__), file)
    spec = importlib.util.spec_from_file_location("carried_" + file[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    before = copy.deepcopy(M)
    before["per_layer"] = [m for m in before["per_layer"] if m["name"] not in NEW]
    assert len(before["per_layer"]) == len(M["per_layer"]) - 8
    monkeypatch.setattr(module, "M", before)
    getattr(module, test)(*case)


def test_readers_of_an_untraced_window_by_hand(monkeypatch):
    rows = [row(k) for k in range(10)]
    rows[4].update(period_s=0.16, wait_s=0.158, gc_s=0.004, report_s=0.0021)
    rows[7].update(dispatch_s=0.0009, other_cpu_s=0.012)
    rows[9].update(period_s=None, between_s=None, thread_cpu_s=None, other_cpu_s=None,
                   nivcsw=None, gc_s=None)  # the last step kept: nothing called the step again
    run = a_run(rows, monkeypatch)
    assert reader("dispatch_ms.train").read(run) == pytest.approx(0.5)
    assert reader("step_stalls.train").read(run) == 1
    seconds = 8 * 0.1 + 0.16
    assert reader("stall_loss_pct.train").read(run) == pytest.approx(100 * 0.06 / seconds)
    assert reader("gc_pause_ms.train").read(run) == pytest.approx(4.0)
    assert reader("report_max_ms.train").read(run) == pytest.approx(2.1)
    assert reader("host_other_cpu_pct.train").read(run) == pytest.approx(
        100 * (8 * 0.002 + 0.012) / seconds)
    slow, = rt.stalls(run)
    assert slow["cause"] == "wait" and slow["start"] == rows[4]["start"]
    assert run["timeline_rows"] is rt.window_rows(run)  # made once a run


def test_a_steady_window_reads_zero_not_none(monkeypatch):
    run = a_run([row(k) for k in range(6)], monkeypatch)
    assert reader("step_stalls.train").read(run) == 0
    assert reader("stall_loss_pct.train").read(run) == 0.0
    assert reader("gc_pause_ms.train").read(run) == 0.0


def test_a_traced_runs_two_profiler_pauses_are_not_stalls(monkeypatch):
    def rows():
        out = [row(k) for k in range(9)]
        # the runner started the profiler before step 3 and stopped it before step 6
        out[2].update(period_s=1.6, between_s=1.5012, other_cpu_s=0.9)
        out[5].update(period_s=3.1, between_s=3.0012, other_cpu_s=2.0)
        return out

    paused = {3: 1.5, 6: 3.0}
    traced = a_run(rows(), monkeypatch, traced=True, paused=paused)
    assert reader("step_stalls.train").read(traced) == 0
    kept = rt.window_rows(traced)
    assert kept[2]["paused_s"] == pytest.approx(1.5) and kept[5]["paused_s"] == pytest.approx(3.0)
    assert kept[2]["period_s"] == pytest.approx(0.1) and kept[5]["between_s"] == pytest.approx(0.0012)
    # the profiler's own CPU is no part of the other threads' share
    assert reader("host_other_cpu_pct.train").read(traced) == pytest.approx(2.0)
    # a real stall beside the pauses is still one
    stalled = rows()
    stalled[7].update(period_s=0.2, wait_s=0.198)
    assert reader("step_stalls.train").read(a_run(stalled, monkeypatch, True, paused)) == 1
    # the same rows of an UNTRACED run: nothing paused the runner's clock, both are stalls
    assert reader("step_stalls.train").read(a_run(rows(), monkeypatch)) == 2


def test_the_windows_last_step_has_no_period(monkeypatch):
    rows = [row(k) for k in range(5)]
    run = a_run(rows, monkeypatch)
    # what calls the step next is a check after the window, a minute later
    rows[4].update(period_s=60.0, between_s=59.9)
    kept = rt.window_rows(run)
    assert len(kept) == 5 and kept[4]["period_s"] is None and kept[4]["other_cpu_s"] is None
    assert reader("step_stalls.train").read(run) == 0
    assert rt.window_seconds(kept) == pytest.approx(0.4)


@pytest.mark.parametrize("name", FROM_THE_TIMELINE)
def test_none_where_the_program_keeps_no_timeline(name, monkeypatch):
    from ray_tpu import obs

    run = a_run([row(k) for k in range(4)], monkeypatch)
    monkeypatch.delattr(obs, "step_timeline")  # the parent of PR 51
    assert reader(name).read(run) is None
    monkeypatch.setattr(obs, "step_timeline", lambda since, until: [], raising=False)
    assert reader(name).read({**run, "timeline_rows": None}) is None
    run.pop("timeline_rows")
    assert reader(name).read(run) is None  # a timeline with no step in the window
    assert reader(name).read({"kind": "serve", "window_wall": (W0, W0 + 10)}) is None


def a_trace(host):
    """Three train-step programs on device 0, 100 ms apart, the first operation 1 ms into
    each and the last ending at 98 ms; the batch maker's program inside each gap. From the
    last operation of one step to the first of the next: 3 ms."""
    ops, programs = [], []
    for k in range(3):
        a = 5.0 + 0.1 * k
        programs.append([f"jit_step({k})", a, 0.0982])
        ops += [["fusion.1", a + 0.001, 0.05], ["fusion.2", a + 0.051, 0.047]]
        programs.append(["jit_make(9)", a + 0.0985, 0.0002])
        ops.append(["fusion.7", a + 0.0985, 0.0002])
    return tr.from_dict({
        "device_ops": {"/device:TPU:0": ops, "/device:TPU:1": [["fusion.1", 5.0, 0.3]]},
        "device_programs": {"/device:TPU:0": programs},
        "host": host,
    })


def test_a_gap_the_programs_events_half_cover_reads_fifty():
    # gaps [5.098, 5.101) and [5.198, 5.201): the batch maker's 0.2 ms runs inside each and
    # is no part of the step's program, so the gap is 3 ms
    host = [["w", "train.report", 5.0981, 0.0005], ["w", "train.step", 5.1, 0.001],
            ["w", "train.report", 5.1985, 0.001], ["w", "train.step", 5.2005, 0.0005],
            ["w", "chipbench.loss_sync", 5.0, 0.098], ["w", "train.step", 5.0, 0.0005]]
    run = {"trace": a_trace(host), "rules": RULES}
    assert rt.step_gaps(run) == [pytest.approx((5.098, 5.101)), pytest.approx((5.198, 5.201))]
    assert reader("step_gap_ms.train").read(run) == pytest.approx(3.0)
    # 0.5 + 1.0 ms of the first gap, 1.0 + 0.5 of the second: 3 of 6 ms
    assert reader("step_gap_program_pct.train").read(run) == pytest.approx(50.0)
    # the parent of PR 51 writes `train.report` alone: the gap is read, the share is not
    parent = {"trace": a_trace([e for e in host if e[1] != "train.step"]), "rules": RULES}
    assert reader("step_gap_ms.train").read(parent) == pytest.approx(3.0)
    assert reader("step_gap_program_pct.train").read(parent) is None


@pytest.mark.parametrize("name", ["step_gap_ms.train", "step_gap_program_pct.train"])
def test_gap_readers_without_a_trace_or_without_two_steps(name):
    with open(os.path.join(os.path.dirname(__file__), "trace_fixture.json")) as f:
        fixture = tr.from_dict(json.load(f))  # a serving trace: no train step in it
    assert reader(name).read({"trace": fixture, "rules": RULES}) is None
    assert reader(name).read({"trace": None}) is None and reader(name).read({}) is None
    one = a_trace([["w", "train.step", 5.0, 0.001]])
    one.device_programs["/device:TPU:0"] = one.device_programs["/device:TPU:0"][:2]
    assert reader(name).read({"trace": one, "rules": RULES}) is None


def test_tool_arguments():
    args = tool.parse(["--workload", "zaya1-train", "--seed", "2147483653"])
    assert (args.seconds, args.trace, args.runs, args.seed) == (10.0, 0, 1, 2147483653)
    args = tool.parse(["--workload", "m7b-train", "--seed", "7", "--seconds", "5",
                       "--trace", "1", "--runs", "12"])
    assert (args.seconds, args.trace, args.runs) == (5.0, 1, 12)
    for bad in (["--seed", "7"], ["--workload", "m7b-train", "--seed", "7", "--runs", "0"],
                ["--workload", "m7b-train", "--seed", "7", "--trace", "2"]):
        with pytest.raises(SystemExit):
            tool.parse(bad)
    assert tool.out_path("m7b-train", 7, 0).endswith(
        "chiprun_out/chipbench/step_timeline-m7b-train-s7-t0.json")
    assert tool.out_path("m7b-train", 7, 1, runs=12).endswith("step_timeline-m7b-train-s7-t1-x12.json")


def test_tool_table_of_a_stored_run(monkeypatch):
    rows = [row(k) for k in range(10)]
    rows[4].update(period_s=0.16, wait_s=0.1, between_s=0.0592, gc_s=0.05, gc_generation=2)
    run = a_run(rows, monkeypatch)
    window = tool.summary(run, "zaya1-train", 11, 0)
    assert window["steps"] == 10 and window["step_stalls"] == 1
    assert window["median_period_ms"] == pytest.approx(100.0)
    assert len(window["periods_ms"]) == 10 and window["periods_ms"][-1] is None
    assert len(window["steps_ms"]) == 10 and window["steps_ms"][4][:4] == pytest.approx(
        [0.5, 100.0, 0.3, 59.2])
    # 60 ms over the median in 960 ms of window: the one stall is all of it here
    assert window["over_median_pct"] == pytest.approx(100 * 0.06 / 0.96)
    assert window["over_median_pct"] == pytest.approx(window["stall_loss_pct"])
    slow, = window["slow"]
    assert (slow["step"], slow["cause"], slow["segment"]) == (4, "gc", "between_s")
    assert slow["excess_ms"] == pytest.approx(60.0) and slow["gc_generation"] == 2
    assert "device" not in window
    stored = json.loads(json.dumps(window))  # what the tool writes and reads back
    quiet = tool.summary(a_run([row(k) for k in range(6)], monkeypatch), "zaya1-train", 12, 0)
    text = tool.render([stored, quiet, {"workload": "zaya1-train", "seed": 13, "trace": 0,
                                        "error": "the run exited 1 and left no timeline"}])
    assert "3 window(s), 1 held a slow step, 1 slow step(s)" in text
    assert "slow zaya1-train seed 11 step 4" in text and "cause gc" in text
    assert "causes: gc 1" in text and "left no timeline" in text
    from ray_tpu import obs

    monkeypatch.delattr(obs, "step_timeline")
    assert "error" in tool.summary({"kind": "train", "window_wall": (W0, W0 + 1)}, "c", 1, 0)


def test_tool_adds_the_devices_side_of_a_traced_run(monkeypatch):
    run = a_run([row(k) for k in range(6)], monkeypatch, traced=True)
    host = [["w", "train.step", 5.1, 0.001], ["w", "train.step", 5.2, 0.001]]
    run.update(trace=a_trace(host), rules=RULES, busy={"busy_s": 0.291, "window_s": 0.3})
    device = tool.summary(run, "m7b-train", 7, 1)["device"]
    assert device["busy_ms_a_step"] == pytest.approx(97.0)
    assert device["idle_pct"] == pytest.approx(3.0)
    assert device["step_gap_ms"] == pytest.approx(3.0)
    assert device["step_gap_program_pct"] == pytest.approx(100 * 2 / 6)
    assert "step gap 3.000 ms" in tool.render([tool.summary(run, "m7b-train", 7, 1)])

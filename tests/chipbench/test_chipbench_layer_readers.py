"""The four set-up and trainer readers of PR 24, each on a hand-built
trace and a stubbed compile log: the value, and None where the program
has no such span, counter or log (as the parent of PR 24 has not). And
the eight start-up phase readers of PR 31 on a hand-built `done` report:
the phase's seconds, None where the run carries no table of phases (as
the parent of PR 31 does not), and the unnamed remainder by hand."""

import pytest

from chipbench import manifest as mf, trace_reduce as tr

WINDOW = (1000.0, 1010.0)
LOG = [
    (990.0, "jit(init)", 2.0, "compiled"),
    (995.0, "jit(step)", 5.5, "loaded"),
    (999.5, "jit(make_batch)", 0.25, "loaded"),
    (1003.0, "jit(late)", 9.0, "compiled"),  # inside the window: not set-up
]


# a `done` report's table (chipbench/phases.py) beside its setup_s
PHASES = {"interp": 0.04, "import": 6.5, "import_program": 2.25, "backend": 5.0,
          "init_params": 4.5, "first_step": 2.0, "warm_steps": 1.125}
# setup_s leaves the machine's phases out: 23.0 of age - (0.04 + 6.5 + 5.0)
DONE = {"values": {"setup_s": 11.46, "train_tok_s": 35000.0}, "setup_phases": PHASES}
RUNTIME = {"runtime.init": {"count": 1, "busy_s": 0.25},
           "train.worker_start": {"count": 1, "busy_s": 0.5}}


def reader(name):
    return mf.load_plugin(mf.ROOT, "layer_metrics", name)


@pytest.fixture
def obs_stub(monkeypatch):
    from ray_tpu import obs

    def stub(log=None, counters=None):
        for name, value in (("compile_log", log), ("layer_counters", counters)):
            if value is None:
                monkeypatch.delattr(obs, name, raising=False)
            else:
                monkeypatch.setattr(obs, name, lambda v=value: v, raising=False)

    return stub


@pytest.mark.parametrize("name,want", [
    ("setup_compile_s.train", 7.75), ("setup_cache_misses.train", 1)])
def test_compile_log_readers(obs_stub, name, want):
    obs_stub(log=LOG)
    assert reader(name).read({"window_wall": WINDOW}) == want


@pytest.mark.parametrize("name", ["setup_compile_s.train", "setup_cache_misses.train"])
@pytest.mark.parametrize("log", [None, []])
def test_compile_log_readers_without_a_log(obs_stub, name, log):
    obs_stub(log=log)
    assert reader(name).read({"window_wall": WINDOW}) is None


def test_setup_runtime_reader(obs_stub):
    obs_stub(counters={"runtime.init": {"count": 1, "busy_s": 0.25},
                       "train.worker_start": {"count": 1, "busy_s": 0.5},
                       "train.report": {"count": 31, "busy_s": 0.01}})
    assert reader("setup_runtime_s.train").read({}) == 0.75
    obs_stub(counters={"train.report": {"count": 31, "busy_s": 0.01}})
    assert reader("setup_runtime_s.train").read({}) is None
    obs_stub(counters=None)
    assert reader("setup_runtime_s.train").read({}) is None


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_reader_reads_its_own_phase(phase):
    read = reader(f"setup_{phase}_s.train").read
    assert read(DONE) == PHASES[phase]
    assert read({**DONE, "setup_phases": {k: v for k, v in PHASES.items() if k != phase}}) is None


@pytest.mark.parametrize("phase", sorted(PHASES) + ["unnamed"])
@pytest.mark.parametrize("run", [{"values": {"setup_s": 23.0}},
                                 {"values": {"setup_s": 23.0}, "setup_phases": None}])
def test_phase_readers_without_the_table(obs_stub, phase, run):
    """The parent of PR 31: a `done` report with setup_s and no phases."""
    obs_stub(counters=RUNTIME)
    assert reader(f"setup_{phase}_s.train").read(run) is None


def test_unnamed_reader_is_setup_less_the_phases_less_the_runtime(obs_stub):
    read = reader("setup_unnamed_s.train").read
    obs_stub(counters=RUNTIME)
    # 11.46 - (2.25 + 4.5 + 2.0 + 1.125) - (0.25 + 0.5)
    assert read(DONE) == pytest.approx(0.835)
    obs_stub(counters=None)  # a program without layer counters: nothing more to take off
    assert read(DONE) == pytest.approx(1.585)
    # the phases inside the clock, the runtime's spans and the remainder add up to
    # setup_s; with the machine's three, to the process's age
    from chipbench import phases

    obs_stub(counters=RUNTIME)
    inside = [reader(f"setup_{p}_s.train").read(DONE) for p in phases.PROGRAM]
    inside += [reader("setup_runtime_s.train").read(DONE), read(DONE)]
    assert sum(inside) == pytest.approx(DONE["values"]["setup_s"])
    machine = [reader(f"setup_{p}_s.train").read(DONE) for p in phases.MACHINE]
    assert sum(inside) + sum(machine) == pytest.approx(23.0)


def test_phases_add_up_on_the_clock_of_setup_s():
    """chipbench/phases.py: stretches of one name add up, a copy is handed out, and the
    clock is the process's age."""
    from chipbench import phases

    before = phases.seconds()
    age = phases.process_age_s()
    with phases.phase("test_phase"):
        pass
    with phases.phase("test_phase"):
        pass
    after = phases.seconds()
    assert 0 < age <= phases.process_age_s()
    # setup_s: the age less what the machine's phases hold so far
    assert phases.setup_s() == pytest.approx(
        phases.process_age_s() - sum(after.get(n, 0.0) for n in phases.MACHINE), abs=0.05)
    assert phases.MACHINE == ("interp", "import", "backend")
    assert 0.0 <= after["test_phase"] < 0.5 and "test_phase" not in before
    after["test_phase"] = 99.0
    assert phases.seconds()["test_phase"] < 0.5
    assert set(PHASES) == set(phases.MACHINE + phases.PROGRAM) and len(PHASES) == 7


def test_report_reader_takes_the_programs_span_from_the_trace():
    trace = tr.from_dict({
        "device_ops": {"/device:TPU:0": [["fusion.1", 0.0, 0.3]]},
        "host": [["worker", "train.report", 0.31, 0.0004],
                 ["worker", "chipbench.report", 0.3099, 0.0006],
                 ["worker", "train.report", 0.71, 0.0002],
                 ["worker", "train.report", 1.11, 0.0009]],
    })
    assert reader("report_ms.train").read({"trace": trace}) == pytest.approx(0.4)
    assert reader("report_ms.train").read({"trace": tr.from_dict(
        {"device_ops": {}, "host": [["worker", "chipbench.report", 0.3, 0.001]]})}) is None
    assert reader("report_ms.train").read({}) is None


def test_manifest_is_still_well_formed_and_the_new_metrics_move_setup():
    manifest = mf.load_manifest()
    assert mf.problems(manifest) == []
    moves = {m["name"]: m["moves"] for m in manifest["per_layer"]}
    assert [moves[n] for n in ("setup_compile_s.train", "setup_cache_misses.train",
                               "setup_runtime_s.train", "report_ms.train")] \
        == ["setup_s", "setup_s", "setup_s", "train_tok_s"]
    assert {m["source"] for m in manifest["per_layer"]} >= {"program_span", "program_counter"}


# -- the A/A tool's arithmetic (chipbench/tools/aa.py), by hand -----------------


def test_aa_compare_by_hand():
    from chipbench.tools import aa

    a = [20.0, 22.0, 24.0, 20.0]
    b = [21.0, 22.0, 23.0, 26.0]
    got = aa.compare(a, b, ["A", "B", "A", "B"])
    assert got["A"]["median"] == 21.0 and got["B"]["median"] == 22.5
    assert got["b_minus_a_over_a"] == pytest.approx(1.5 / 21.0)
    assert (got["b_above_a"], got["b_below_a"]) == (2, 1)  # 1, 0, -1, 6
    assert got["by_first"]["A_first"] == {"n": 2, "median_b_minus_a": 0.0, "b_above_a": 1}
    assert got["by_first"]["B_first"] == {"n": 2, "median_b_minus_a": 3.0, "b_above_a": 1}
    # quartiles as statistics.quantiles(n=4) gives them: 20.0 and 23.5 of a
    assert got["A"]["quartile_distance"] == pytest.approx(3.5)
    # six draws of two pairs; the worst is pairs (0, 3): (23.5 - 20) / 20
    draws = got["two_pair_draws"]
    assert draws["n"] == 6 and draws["worst"] == pytest.approx(0.175)
    assert draws["p95_abs"] == pytest.approx(0.175) and draws["b_worse_by_over_10pct"] == 3  # pairs (0, 3), (1, 3), (2, 3)
    assert aa.quartile_distance([1.0]) is None


def test_aa_unpack_keeps_the_compile_cache_and_nothing_else(tmp_path):
    import os
    import tarfile

    from chipbench.tools import aa

    src = tmp_path / "src"
    (src / "pkg").mkdir(parents=True)
    (src / "pkg" / "m.py").write_text("x = 1\n")
    tar = str(tmp_path / "tree.tar")
    with tarfile.open(tar, "w") as t:
        t.add(str(src / "pkg"), arcname="pkg")
    where = str(tmp_path / "B")
    aa.unpack(tar, where)
    os.makedirs(os.path.join(where, ".jax_cache"))
    open(os.path.join(where, ".jax_cache", "step-cache"), "w").write("compiled")
    os.makedirs(os.path.join(where, "pkg", "__pycache__"))
    aa.unpack(tar, where, out_of_page_cache=True)
    assert open(os.path.join(where, "pkg", "m.py")).read() == "x = 1\n"
    assert not os.path.exists(os.path.join(where, "pkg", "__pycache__"))
    assert open(os.path.join(where, ".jax_cache", "step-cache")).read() == "compiled"
    env = aa.side_env(str(tmp_path), "B")
    assert env["HOME"].endswith("home_B") and os.path.isdir(env["TMPDIR"])
    assert "JAX_COMPILATION_CACHE_DIR" not in env and "BENCH_RUN" not in env


def test_aa_one_reads_the_result_and_the_phases_of_a_run(tmp_path):
    """A stand-in for the benchmark's command that prints what run.py prints."""
    import json
    import os
    import sys

    from chipbench.tools import aa

    lines = [{"event": "setup", "setup_s": 11.46, "phases": PHASES, "runtime_s": 0.75,
              "programs": [["jit(step)", 0.6, "loaded"]]},
             {"event": "train", "step_s": [0.35, 1.9, 0.35]},
             {"event": "window done", "compiles_in_window": 0},
             {"correct": True, "attempted": 29, "failed": 0,
              "metrics": {"setup_s": {"value": 11.46, "unit": "s"},
                          "train_tok_s": {"value": 35000.0, "unit": "tokens/s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                         "memory_peak_bytes": 8431637504}}]
    script = "import json\nfor x in %r:\n    print(json.dumps(x))\n" % (lines,)
    got = aa.one([sys.executable, "-c", script], str(tmp_path), dict(os.environ),
                 "any-cell", 7, 1)
    assert got["rc"] == 0 and got["correct"] is True and got["programs"] == [["jit(step)", 0.6, "loaded"]]
    v = got["values"]
    assert v["setup_s"] == 11.46 and v["phase.backend"] == 5.0
    assert v["phase.machine"] == pytest.approx(11.54) and v["process_age_s"] == pytest.approx(23.0)
    assert v["phase.unnamed"] == pytest.approx(0.835)
    assert got["slowest_step_s"] == 1.9 and got["compiles_in_window"] == 0
    failed = aa.one([sys.executable, "-c", "import sys; sys.exit(3)"], str(tmp_path),
                    dict(os.environ), "any-cell", 7, 1)
    assert failed["rc"] == 3 and "values" not in failed

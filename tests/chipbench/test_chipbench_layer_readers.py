"""The four set-up and trainer readers of PR 24, each on a hand-built
trace and a stubbed compile log: the value, and None where the program
has no such span, counter or log (as the parent of PR 24 has not)."""

import pytest

from chipbench import manifest as mf, trace_reduce as tr

WINDOW = (1000.0, 1010.0)
LOG = [
    (990.0, "jit(init)", 2.0, "compiled"),
    (995.0, "jit(step)", 5.5, "loaded"),
    (999.5, "jit(make_batch)", 0.25, "loaded"),
    (1003.0, "jit(late)", 9.0, "compiled"),  # inside the window: not set-up
]


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

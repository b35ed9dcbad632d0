"""The trace reduction against a small hand-built trace kept beside this
test, so every PR computes the same numbers the same way."""

import json
import os
import re

import pytest

from chipbench import manifest as mf, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "trace_fixture.json")) as f:
    T = tr.from_dict(json.load(f))
RULES = [(re.compile(r"^jit_decode"), "decode"), (re.compile(r"^jit_prefill"), "prefill"),
         (re.compile(r"^all-reduce"), "collective")]
WIN = tr.window(T)


def test_window_is_the_harness_annotation():
    assert WIN == (0.0, 6.0)
    no_marker = tr.Trace(T.device_ops, T.device_programs, [])
    assert tr.window(no_marker) == (0.0, 5.5)


INTERVALS = [
    ([(0, 1), (1, 2), (3, 4)], [(0, 2), (3, 4)]),
    ([(0, 5), (1, 2)], [(0, 5)]),
    ([(2, 3), (0, 1)], [(0, 1), (2, 3)]),
    ([(1, 1)], []),
]


@pytest.mark.parametrize("given,want", INTERVALS)
def test_union(given, want):
    assert tr.union(given) == want


def test_subtract_and_clip():
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


def test_busy_is_the_union_averaged_over_devices():
    b = tr.busy(T, WIN)
    # device 0: [0,2) + [3,5.5) = 4.5 s; device 1: 4 s
    assert b["per_device"] == {"/device:TPU:0": 4.5, "/device:TPU:1": 4.0}
    assert b["busy_s"] == pytest.approx(4.25) and b["window_s"] == 6.0


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    gaps = tr.idle_gaps(T, WIN)
    # the harness's own annotation wins over a longer foreign event
    assert gaps[0] == ["chipbench.request", 1.0]
    assert gaps[1] == ["something else", 0.5]
    assert len(gaps) == 2


def test_class_time_of_programs_and_ops():
    assert tr.leaves([("a", 0.0, 0.3), ("b", 0.3 - 1e-12, 0.1)]) == [("a", 0.0, 0.3), ("b", 0.3 - 1e-12, 0.1)]
    progs = tr.class_time(T.device_programs, RULES, WIN)
    assert progs["decode"]["seconds"] == pytest.approx((2.0 + 4.0) / 2)
    assert progs["prefill"] == {"seconds": pytest.approx(2.5 / 2), "count": 0.5}
    ops = tr.class_time(T.device_ops, RULES, WIN)
    assert ops == {"collective": {"seconds": pytest.approx(0.25), "count": 0.5}}


def test_a_while_is_not_counted_beside_its_body():
    names = [e[0] for e in tr.leaves(T.device_ops["/device:TPU:0"])]
    assert "while.3" not in names and names.count("fusion.1") == 2
    assert tr.leaves([("a", 0, 10), ("b", 1, 2), ("c", 1.5, 1), ("d", 5, 1), ("e", 10, 1)]) == [
        ("c", 1.5, 1), ("d", 5, 1), ("e", 10, 1)]


def test_exposed_collective_time():
    # the all-reduce is in flight over [3.8,5) (its async span); fusion.1 covers [3,4)
    # and fusion.9 [4.5,5.5): only [4,4.5) is hidden by nothing
    assert tr.exposed(T, RULES, "collective", WIN) == pytest.approx(0.5 / 2)


def test_top_ops_and_a_window_that_cuts():
    top = tr.top_ops(T, WIN, n=2)
    assert top[0] == ["fusion.1", pytest.approx((2.0 + 4.0) / 2)]  # not while.3
    assert tr.busy(T, (1.0, 3.5))["per_device"]["/device:TPU:0"] == pytest.approx(1.5)


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.busy(tr.Trace({}, {}, []), (0.0, 1.0))


def test_name_patterns_are_data_read_as_a_merge(tmp_path):
    names = mf.trace_names(mf.ROOT)
    assert names["lines"]["ops"] and re.compile(names["lines"]["device_plane"]).search("/device:TPU:0")
    d = tmp_path / "chipbench" / "trace_names"
    d.mkdir(parents=True)
    (d / "a.json").write_text(json.dumps({"lines": {"ops": ["X"]}, "patterns": {"^foo": "decode"}}))
    (d / "b.json").write_text(json.dumps({"patterns": {"^foo": "prefill", "^bar": "collective"}}))
    merged = mf.trace_names(str(tmp_path))
    assert merged["lines"] == {"ops": ["X"]}
    assert tr.classify("foo.1", merged["rules"]) == "prefill"  # the later file wins
    assert tr.classify("bar", merged["rules"]) == "collective"
    assert tr.classify("baz", merged["rules"]) is None


def test_reads_a_real_xplane_file(tmp_path):
    """from_xplane on a trace JAX writes here (the CPU has no device
    plane: the reduction must say so, not invent one)."""
    import jax
    import jax.numpy as jnp

    from chipbench import tracing

    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    tracing.stop()
    logged = []
    out = tracing.reduce(str(tmp_path), mf.trace_names(mf.ROOT), lambda **f: logged.append(f))
    assert out == {"busy": None} and "no device operation" in logged[0]["error"]


def test_op_events_keep_their_own_name_only():
    long = "%while.51 = (s32[]{:T(128)}, bf16[4,1,4096]{2,0,1}) while(%tuple.121), condition=%c, body=%b"
    assert tr.short_name(long) == "while.51"
    assert tr.short_name("fusion.7") == "fusion.7"
    kernel = '%closed_call.7 = (bf16[3,32,4096,128]) custom-call(%a), custom_call_target="tpu_custom_call"'
    assert tr.short_name(kernel, "tpu_custom_call") == "kernel:closed_call.7"
    assert tr.classify("kernel:closed_call.7", mf.trace_names(mf.ROOT)["rules"]) == "flash_fwd"
    assert tr.classify("kernel:checkpoint.12", mf.trace_names(mf.ROOT)["rules"]) == "flash_bwd"

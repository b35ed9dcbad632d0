"""Layer spans (ray_tpu.obs.layer_span), the compile log, the serving
runner's and the engine's spans and counters, engine.warmup()."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu import obs
from ray_tpu.chaos import harness as chaos
from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.models import llama
from ray_tpu.obs.recorder import SpanRecorder, layer_record, layer_span

GREEDY = dict(temperature=0.0, ignore_eos=True)


def _engine(**kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_num_seqs", 4)
    return LLMEngine(EngineConfig(model=llama.LLAMA_TINY, **kw))


# -- the primitive ------------------------------------------------------------


def test_layer_span_outside_a_capture_leaves_only_counters():
    rec = SpanRecorder()
    with layer_span("t.layer", recorder=rec):
        time.sleep(0.002)
    with layer_span("t.layer", recorder=rec):
        pass
    got = rec.layer_counters()["t.layer"]
    assert got["count"] == 2 and got["busy_s"] >= 0.002
    assert rec.layer_spans() == [] and len(rec) == 0


def test_layer_span_inside_a_capture_is_a_span_with_the_right_parent():
    rec = SpanRecorder()
    request = obs.new_context()
    with rec.capture() as spans:
        with layer_span("t.outer", recorder=rec) as outer:
            with layer_span("t.inner", recorder=rec):
                pass
            with layer_span("t.for_request", ctx=request, recorder=rec):
                pass
            outer.attrs["rows"] = 3  # known only at the end
    with layer_span("t.after", recorder=rec):
        pass
    by = {s.name: s for s in spans}
    assert set(by) == {"t.outer", "t.inner", "t.for_request"}
    assert by["t.outer"].parent_id is None and by["t.outer"].attrs == {"rows": 3}
    assert by["t.inner"].parent_id == by["t.outer"].span_id
    assert by["t.inner"].trace_id == by["t.outer"].trace_id
    # work done for one request hangs under that request's context
    assert by["t.for_request"].trace_id == request.trace_id
    assert by["t.for_request"].parent_id == request.span_id
    assert by["t.outer"].start <= by["t.inner"].start <= by["t.inner"].end <= by["t.outer"].end
    assert rec.layer_counters()["t.after"]["count"] == 1


def test_layer_spans_never_evict_a_request_trace():
    rec = SpanRecorder(max_traces=2, max_layer_spans=8)
    rec.record("llm.request", 1.0, 2.0, ctx=obs.new_context(),
               attrs={"request_id": "r1"})
    with rec.capture() as spans:
        for _ in range(50):
            with layer_span("t.flood", recorder=rec):
                pass
    assert rec.find_by_request("r1") is not None and len(rec) == 1
    assert rec.num_dropped_traces == 0
    # its own ring is bounded, and says what it dropped
    assert len(spans) == 8 and rec.num_dropped_layer_spans == 42
    assert rec.layer_counters()["t.flood"]["count"] == 50


def test_layer_record_counts_a_span_started_elsewhere():
    rec = SpanRecorder()
    with rec.capture() as spans:
        layer_record("t.handoff", time.time() - 0.5, attrs={"rank": 0}, recorder=rec)
    assert 0.5 <= rec.layer_counters()["t.handoff"]["busy_s"] < 5
    assert [(s.name, s.attrs) for s in spans] == [("t.handoff", {"rank": 0})]


def test_recorder_resize_and_since():
    rec = SpanRecorder(max_traces=2)
    for i, end in enumerate((10.0, 20.0, 30.0)):
        rec.record("llm.request", end - 1, end, ctx=obs.new_context(),
                   attrs={"request_id": f"r{i}"})
    assert len(rec) == 2 and rec.num_dropped_traces == 1
    rec.resize(max_traces=8)
    rec.record("llm.request", 39.0, 40.0, ctx=obs.new_context(),
               attrs={"request_id": "r3"})
    assert len(rec) == 3
    window = rec.since(25.0)  # one window's requests, taken at its end
    assert [t[0].attrs["request_id"] for t in window.values()] == ["r2", "r3"]
    rec.resize(max_traces=1)
    assert len(rec) == 1 and rec.find_by_request("r3") is not None


def test_layer_counters_reach_the_metrics_registry():
    from ray_tpu.util.metrics import prometheus_text

    with obs.layer_span("t.exported"):
        pass
    text = prometheus_text()
    assert 'ray_tpu_obs_layer_spans_total{name="t.exported"}' in text
    assert 'ray_tpu_obs_layer_busy_seconds_total{name="t.exported"}' in text


def test_concurrent_layer_spans_lose_no_count():
    import sys

    rec = SpanRecorder()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(2000):
                with layer_span("t.shared", recorder=rec):
                    pass

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.layer_counters()["t.shared"]["count"] == 16000


def test_layer_span_is_a_host_event_in_a_real_profiler_trace(tmp_path):
    """The span is in the .xplane.pb under its own name, and the clock
    markers place the recorder's times on the profiler's clock."""
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.capture() as spans:
            with obs.layer_span("t.traced"):
                f(jnp.ones(8)).block_until_ready()
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    host = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for plane in jax.profiler.ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events]
    seen = [(s, d) for name, s, d in host if name == "t.traced"]
    assert len(seen) == 1
    offset = obs.clock_offset((n, s) for n, s, _ in host)
    assert offset is not None
    span, = [s for s in spans if s.name == "t.traced"]
    assert abs(span.start + offset - seen[0][0]) < 1e-3
    assert abs(span.duration_s - seen[0][1]) < 1e-3


# -- the compile log ------------------------------------------------------------


def test_compile_log_tells_a_compile_from_a_cache_load(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from ray_tpu.utils.compile_cache import start_compile_log

    start_compile_log()
    start_compile_log()  # once a process, however often it is asked
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        def compile_log_probe(x):
            return jnp.cos(x) * 3 + 2

        t0 = time.time()
        jax.jit(compile_log_probe)(jnp.ones(7)).block_until_ready()
        jax.clear_caches()
        jax.jit(compile_log_probe)(jnp.ones(7)).block_until_ready()
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    mine = [e for e in obs.compile_log(since=t0) if "compile_log_probe" in e[1]]
    assert [e[3] for e in mine] == ["compiled", "loaded"]
    assert all(e[2] > 0 and e[0] >= t0 for e in mine)


# -- the engine -------------------------------------------------------------------


def test_every_engine_program_is_lowered_under_its_class_name():
    """No jit__lambda: the device's line of a trace and the compile log
    name the class of every program the engine builds."""
    from ray_tpu.llm.spec import SpecConfig

    t0 = time.time()
    from ray_tpu.utils.compile_cache import start_compile_log

    start_compile_log()
    sp = SamplingParams(max_tokens=6, **GREEDY)
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    _engine().generate(prompts, sp)                  # prefill, pipe chunk
    _engine(mixed_batch=True).generate(prompts, sp)  # mixed
    # a spec engine's own tables: verify, and the unpipelined decode programs
    report = _engine(spec=SpecConfig(method="prompt_lookup", num_draft_tokens=2),
                     max_num_seqs=1, max_prefill_len=16, decode_chunk=4).warmup()
    assert set(report) == {"prefill", "verify", "decode", "decode_chunk"}
    eng = _engine()
    eng._kv_import_fn(16)
    names = {e[1] for e in obs.compile_log(since=t0)}
    for cls in ("llm_prefill", "llm_pipe_chunk_n", "llm_decode)", "llm_decode_chunk_n",
                "llm_mixed", "llm_verify"):
        assert any(f"jit({cls}" in n for n in names), (cls, sorted(names))
    lowered = eng._kv_import_fn(16).lower(
        eng.cache, *(jax.ShapeDtypeStruct(
            eng.cache["k"].shape[:2] + (16,) + eng.cache["k"].shape[3:],
            eng.cache["k"].dtype),) * 2,
        jax.ShapeDtypeStruct((16,), jnp.int32))
    assert "jit_llm_kv_scatter" in lowered.as_text()[:200]
    assert not any("lambda" in n for n in names if "llm" in n)
    import inspect

    from ray_tpu.llm import engine as engine_mod

    src = inspect.getsource(engine_mod)
    assert "jax.jit(lambda" not in src and "jax.jit(\n            lambda" not in src


def test_engine_counters_match_a_hand_counted_run():
    eng = _engine(max_num_seqs=4)
    sp = SamplingParams(max_tokens=5, **GREEDY)
    before = obs.layer_counters()
    eng.generate([[1, 2, 3, 4], [9, 8, 7]], sp)
    n = eng.counters()
    # 2 requests x 5 tokens: the first of each comes from its prefill
    assert n["prefill_tokens"] == 7 and n["prefill_cached_tokens"] == 0
    assert n["decode_tokens"] == 8 and n["decode_row_steps"] == 8
    # both rows decode together: 4 steps with 2 live rows of 4 slots
    assert n["decode_steps"] == 4
    assert n["decode_occupancy"] == pytest.approx(8 / (4 * 4))
    assert n["dispatches"]["prefill"] == 2 and n["first_calls"]["prefill"] == 1
    assert n["preemptions"] == 0 and n["max_num_seqs"] == 4
    after = obs.layer_counters()
    for name in ("engine.step", "engine.schedule", "engine.prefill_dispatch",
                 "engine.decode_dispatch", "engine.sync", "engine.append"):
        assert after[name]["count"] > before.get(name, {"count": 0})["count"], name
    # the same prompt again is served from the prefix cache, by count
    eng.generate([[1, 2, 3, 4] * 5], sp)
    eng.generate([[1, 2, 3, 4] * 5], sp)
    assert eng.counters()["prefill_cached_tokens"] == 16


@pytest.mark.slow
def test_engine_counts_decode_chunks_without_a_switch():
    eng = LLMEngine(
        EngineConfig(model=llama.LLAMA_TINY, num_blocks=64, decode_chunk=4)
    )
    steps_before = obs.layer_counters().get("engine.step", {"count": 0})["count"]
    out = eng.generate(
        [[1, 2, 3, 4]], SamplingParams(max_tokens=6, ignore_eos=True)
    )
    assert len(out[0]) == 6
    n = eng.counters()
    assert n["dispatches"]["pipe_chunk"] >= 1, "no decode chunk was counted"
    assert n["decode_tokens"] == 5 and n["decode_row_steps"] == 5
    assert obs.layer_counters()["engine.step"]["count"] > steps_before


def test_engine_step_span_names_what_the_step_did():
    eng = _engine()
    eng.add_request([1, 2, 3, 4], SamplingParams(max_tokens=4, **GREEDY))
    with obs.capture() as spans:
        while eng.has_unfinished():
            eng.step()
    steps = [s for s in spans if s.name == "engine.step"]
    assert steps[0].attrs == {"rows": 0, "waiting": 1, "kind": "prefill"}
    assert {s.attrs["kind"] for s in steps[1:]} == {"decode"}
    ids = {s.span_id: s.name for s in spans}
    children = {s.name for s in spans if ids.get(s.parent_id) == "engine.step"}
    assert children == {"engine.schedule", "engine.prefill_dispatch",
                        "engine.decode_dispatch", "engine.sync", "engine.append"}


def test_warmup_leaves_nothing_to_compile_under_traffic():
    from ray_tpu.utils.compile_cache import start_compile_log

    start_compile_log()
    eng = _engine(max_num_seqs=2, max_prefill_len=32, decode_chunk=4)
    before = obs.layer_counters().get("engine.warmup.prefill", {"count": 0})["count"]
    report = eng.warmup()
    c = eng.config
    assert report["prefill"]["programs"] == len(c.prefill_buckets()) * len(c.bt_widths())
    assert report["pipe_chunk"]["programs"] == len(c.decode_buckets()) * len(c.bt_widths()) * 7
    for row in report.values():
        assert row["compiled"] + row["loaded"] == row["programs"] and row["seconds"] > 0
    assert obs.layer_counters()["engine.warmup.prefill"]["count"] == before + 1
    warmed = eng.counters()["first_calls"]
    t0 = time.time()
    out = eng.generate([[1, 2, 3, 4], [5, 6, 7]], SamplingParams(max_tokens=9, **GREEDY))
    assert [len(o) for o in out] == [9, 9]
    assert eng.counters()["first_calls"] == warmed
    assert [e for e in obs.compile_log(since=t0) if "llm_" in e[1]] == []
    # the warm-up wrote the trash page only: the same tokens as a cold engine
    assert out == _engine(max_num_seqs=2, max_prefill_len=32, decode_chunk=4).generate(
        [[1, 2, 3, 4], [5, 6, 7]], SamplingParams(max_tokens=9, **GREEDY))


# -- the serving runner -------------------------------------------------------------


@pytest.fixture
def runner():
    from ray_tpu.llm.openai_api import _EngineRunner

    r = _EngineRunner(_engine())
    yield r
    r.shutdown()
    chaos.uninstall()


def _drain(q):
    while True:
        out = q.get(timeout=120)
        assert not isinstance(out, BaseException), out
        if out is None or out.finished:
            return


def test_runner_submit_reports_the_lock_wait_a_held_step_cost(runner):
    """A second request arrives while the loop holds the lock across a
    slowed engine step: its runner.submit span carries that wait, the
    engine's own queue_wait cannot see it, and llm.request hands it on
    as pre_engine_wait_s. No wall-clock race: the second submit starts
    only once a step is known to hold the lock."""
    delay = 0.3
    sp = SamplingParams(max_tokens=3, **GREEDY)
    _drain(runner.submit([1, 2, 3], sp)[1])  # compile outside the slowed part
    chaos.install(chaos.FaultSchedule(5, [
        chaos.FaultSpec(chaos.DELAY_RPC, site="llm.engine.step", delay_s=delay)]))
    ctx = obs.new_context()
    with obs.capture() as spans:
        _, q1 = runner.submit([4, 5, 6], SamplingParams(max_tokens=8, **GREEDY))
        # wait until the loop thread is INSIDE a step, holding the lock
        deadline = time.time() + 30
        while not (runner.lock._lk.locked() and runner.lock._depth == 1):
            assert time.time() < deadline
            time.sleep(0.001)
        time.sleep(0.01)
        rid2, q2 = runner.submit([7, 8, 9], sp, trace=ctx)
        chaos.uninstall()
        _drain(q1)
        _drain(q2)
    second = [s for s in spans if s.name == "runner.submit"][-1]
    # it waited out (at least) the rest of the slowed step
    assert second.attrs["lock_wait_ms"] >= 1e3 * (delay - 0.05)
    assert second.duration_s * 1e3 >= second.attrs["lock_wait_ms"]
    assert second.trace_id == ctx.trace_id and second.parent_id == ctx.span_id
    request, = [s for s in obs.get_recorder().get(ctx.trace_id) if s.name == "llm.request"]
    assert request.attrs["pre_engine_wait_s"] >= delay - 0.05
    assert request.attrs["queue_wait_s"] < request.attrs["pre_engine_wait_s"]
    totals = runner.lock.totals()
    assert totals["wait_s"] >= delay - 0.05 and totals["hold_s"] >= delay
    turn = {s.name for s in spans if s.name.startswith("runner.")}
    assert turn == {"runner.submit", "runner.step", "runner.lock_wait", "runner.deliver"}


def test_counters_and_served_stats_return_while_the_runner_lock_is_held():
    from ray_tpu.llm.openai_api import LLMConfig, LLMServer

    server = LLMServer(LLMConfig(model_id="tiny-stats", engine=EngineConfig(
        model=llama.LLAMA_TINY, num_blocks=64, max_num_seqs=4)))
    try:
        _drain(server.runner.submit([1, 2, 3], SamplingParams(max_tokens=3, **GREEDY))[1])
        got = {}
        with server.runner.lock:  # what a long engine step looks like to a reader

            def read():
                got["counters"] = server.engine.counters()
                got["stats"] = server.stats()

            t = threading.Thread(target=read)
            t.start()
            t.join(timeout=20)
            assert not t.is_alive(), "stats() waited for the runner's lock"
        assert got["counters"]["decode_tokens"] == 2
        stats = got["stats"]
        assert stats["counters"] == got["counters"] and stats["num_preemptions"] == 0
        assert stats["trace"]["engine.step"]["count"] >= 2
        assert stats["trace"]["runner.submit"]["busy_s"] > 0
        assert stats["runner_lock"]["acquires"] >= 3
    finally:
        server.shutdown()


# -- the trainer's start-up (what setup_runtime_s.train reads) ------------------------


def test_fit_counts_one_worker_start_per_worker_attempt(tmp_path):
    import ray_tpu
    from ray_tpu.core import runtime as rt
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig, session

    def loop():
        # the first attempt's rank 1 dies: the whole gang is started again
        if session.get_world_rank() == 1 and not (tmp_path / "died").exists():
            (tmp_path / "died").write_text("x")
            raise RuntimeError("injected worker failure")
        session.report({"ok": 1})

    if rt.is_initialized():
        rt.shutdown_runtime()
    ray_tpu.init(num_cpus=4)
    try:
        before = obs.layer_counters().get("train.worker_start", {"count": 0, "busy_s": 0.0})
        with obs.capture() as spans:
            result = JaxTrainer(
                loop, scaling_config=ScalingConfig(num_workers=2),
                run_config=RunConfig(name="spans", storage_path=str(tmp_path),
                                     failure_config=FailureConfig(max_failures=1)),
            ).fit()
        assert result.error is None and result.metrics == {"ok": 1}
        after = obs.layer_counters()["train.worker_start"]
        assert after["count"] == before["count"] + 4  # 2 workers x 2 attempts
        assert after["busy_s"] > before["busy_s"]
        starts = [s for s in spans if s.name == "train.worker_start"]
        assert sorted(s.attrs["rank"] for s in starts) == [0, 0, 1, 1]
        # an attempt's span starts when the attempt does, not when fit() did
        assert len({round(s.start, 6) for s in starts}) == 2
    finally:
        rt.shutdown_runtime()


def test_init_sharded_params_is_one_span_and_every_leaf_is_born_sharded(cpu_devices):
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import default_rules, tree_shardings
    from ray_tpu.train.step import init_sharded_params

    cfg = llama.LLAMA_TINY
    mesh, rules = make_mesh(MeshSpec(fsdp=4, tp=2)), default_rules()
    before = obs.layer_counters().get("train.init_params", {"count": 0})["count"]
    params = init_sharded_params(
        lambda: llama.init_params(cfg, jax.random.key(0)), llama.logical_axes(cfg), mesh, rules)
    assert obs.layer_counters()["train.init_params"]["count"] == before + 1
    want = tree_shardings(mesh, rules, llama.logical_axes(cfg))
    leaves, wanted = jax.tree.leaves(params), jax.tree.leaves(want)
    assert len(leaves) == len(wanted) > 0
    for leaf, sharding in zip(leaves, wanted):
        assert leaf.committed and leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
    # sharded for real, not replicated eight times
    wq = params["layers"]["wq"]
    assert wq.addressable_shards[0].data.size * 8 == wq.size


# -- the step's names and the program's record of its compiled step (PR 37) ------------

# every scope a reader may book an operation to, and what each kind of block runs of them
_EVERY_KIND = ("embed", "block.stack", "block.norm", "head", "optim")
_GQA = ("attn.qkv", "attn.rope", "attn.attend", "attn.out")
_MOE = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine")
_CCA = ("cca.proj", "cca.mix", "cca.attend", "cca.out")
_MLA = ("mla.down", "mla.up", "mla.glue", "mla.attend", "mla.out")
_MTP = ("mtp.merge", "mtp.block", "mtp.head")
_LISTED = _EVERY_KIND + _GQA + _MOE + _CCA + _MLA + _MTP + ("dense.ffn", "shared.ffn")
SCOPES_OF = {
    "llama-tiny": _EVERY_KIND + _GQA + ("dense.ffn",),
    "moe-tiny": _EVERY_KIND + _GQA + _MOE,
    "zaya-tiny": _EVERY_KIND + _CCA + _MOE,
    "glm-lite-tiny": _EVERY_KIND + _MLA + _MOE + _MTP + ("dense.ffn", "shared.ffn"),
}


def _tiny_step(model="llama-tiny", batch=2):
    import optax

    from ray_tpu.models.registry import get_model_config
    from ray_tpu.train.step import TrainState, make_train_step

    cfg, opt = get_model_config(model), optax.adamw(1e-3)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    tokens = jnp.zeros((batch, 32), jnp.int32)
    return step, state, {"tokens": tokens, "targets": tokens}


def _under(path, scope):
    """`scope` as a whole component of an op_name path, bare or wrapped."""
    import re

    return re.search(r"(?<![^/(])" + re.escape(scope) + r"(?![^/)])", path) is not None


@pytest.mark.parametrize("model", sorted(SCOPES_OF))
def test_every_part_of_the_train_step_runs_under_a_name_of_the_models_own(model):
    """Forward AND backward of each scope the kind of block runs, and no
    matmul of the compiled step under none of them."""
    from ray_tpu.obs.programs import parse_op_names

    step, state, batch = _tiny_step(model)
    names = parse_op_names(step.lower(state, batch).compile().as_text())
    paths = {path for entries in names.values() for _, path, _ in entries if path}
    for scope in SCOPES_OF[model]:
        mine = [p for p in paths if _under(p, scope)]
        assert any("transpose(" not in p for p in mine), f"{scope}: no forward op"
        if scope != "optim":  # nothing differentiates the update
            assert any("transpose(jvp(" in p for p in mine), f"{scope}: no backward op"
    for scope in set(_LISTED) - set(SCOPES_OF[model]):
        assert not any(_under(p, scope) for p in paths), f"{scope} in a {model} step"
    matmuls = [(name, path) for name, entries in names.items()
               for op, path, _ in entries[:1] if op in ("dot", "convolution")]
    assert matmuls
    # the stack's name is around every block: it is no name for a matmul inside one
    sublayers = [s for s in _LISTED if s != "block.stack"]
    for name, path in matmuls:
        assert any(_under(path, s) for s in sublayers), f"{name} under no scope: {path!r}"


def test_train_step_record_notes_abstract_values_at_the_first_call_and_lowers_when_asked():
    from unittest import mock

    step, state, batch = _tiny_step()
    # noted when it is made; nothing to answer from before it has run
    assert step._abstract is None and obs.op_names() is None
    assert "stablehlo" in step.lower(state, batch).as_text()  # the jitted step's own
    real = step._jitted
    step._jitted = spy = mock.Mock(wraps=real)
    state, _ = step(state, batch)
    noted = step._abstract
    leaves = jax.tree.leaves(noted)
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)  # no array held
    assert [x.shape for x in jax.tree.leaves(noted[1])] == [(2, 32), (2, 32)]
    state, _ = step(state, batch)
    assert step._abstract is noted and spy.call_count == 2 and spy.lower.call_count == 0
    names = obs.op_names()
    assert obs.op_names() == names
    assert spy.lower.call_count == 1  # two requests, one lowering and compile
    ran = real.lower(state, batch).compile().as_text()
    assert set(names) == set(obs.programs.parse_op_names(ran))  # the step that ran
    # a fusion carries the paths of its fused computation's instructions after its own
    fusions = [e for e in names.values() if e[0][0] == "fusion"]
    assert fusions and all(len(e) > 1 for e in fusions)
    assert any(_under(path, "optim") for e in fusions for _, path, _ in e[1:])
    # what an instruction reads, seen through a loop's tuple: an element the layer scan's
    # body takes from its parameter reads what the loop was given, not the parameter
    body = [e[0] for e in names.values() if e[0][0] == "get-tuple-element" and e[0][2]]
    assert body and all(names[read][0][0] != "parameter" or not names[read][0][2]
                        for _, _, (read,) in body)


def test_a_second_train_step_replaces_the_first_record():
    first, state, batch = _tiny_step()
    first(state, batch)
    had = obs.op_names()
    assert had and any(_under(p, "dense.ffn") for e in had.values() for _, p, _ in e)
    second, state, batch = _tiny_step("moe-tiny", batch=1)
    assert obs.op_names() is None  # the newest step has not run yet
    second(state, batch)
    paths = [p for e in obs.op_names().values() for _, p, _ in e]
    assert any(_under(p, "moe.experts") for p in paths)
    assert not any(_under(p, "dense.ffn") for p in paths)
    assert first.compiled() is not None  # the first keeps its own


def test_a_step_called_inside_another_program_notes_shapes_without_a_placement():
    step, state, batch = _tiny_step()
    out = jax.eval_shape(lambda s, b: step(s, b)[1]["loss"], state, batch)
    assert out.shape == () and step._abstract is not None
    assert all(x.sharding is None for x in jax.tree.leaves(step._abstract))


def test_obs_exports_the_record_and_no_clock_marker():
    assert {"note_program", "op_names"} <= set(obs.__all__) and not hasattr(obs, "memory")
    assert "clock_marker" not in obs.__all__ and not hasattr(obs, "clock_marker")

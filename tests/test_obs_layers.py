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
    markers place the recorder's times on the profiler's clock. The
    trace holds a handful of markers, so ONE of them stamped late (the
    thread switched out between the two clocks' readings: 12 ms in a
    whole lane of PR 54's, beside five other workers) moves their
    median: the times are held to a millisecond in one trace of three."""
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    noted = obs.programs.NotedProgram(f, "train.step")  # as make_train_step's step is
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    def off_by(directory) -> list:
        """One trace: the span's start and length and the step's start, each less the profiler's."""
        jax.profiler.start_trace(str(directory), profiler_options=opts)
        try:
            with obs.capture() as spans:
                with obs.layer_span("t.traced"):
                    f(jnp.ones(8)).block_until_ready()
                    time.sleep(0.01)
                noted(jnp.ones(8)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
        host = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for plane in jax.profiler.ProfileData.from_file(path).planes
                for line in plane.lines for e in line.events]
        seen, = [(s, d) for name, s, d in host if name == "t.traced"]
        offset = obs.clock_offset((n, s) for n, s, _ in host)
        assert offset is not None
        span, = [s for s in spans if s.name == "t.traced"]
        # the step's call is a host event of the program's own, on the same clock
        called, = [(s, d) for name, s, d in host if name == "train.step"]
        step, = [s for s in spans if s.name == "train.step"]
        return [span.start + offset - seen[0], span.duration_s - seen[1],
                step.start + offset - called[0]]

    traces = []
    while len(traces) < 3 and not (traces and max(map(abs, traces[-1])) < 1e-3):
        traces.append(off_by(tmp_path / str(len(traces))))
    assert max(map(abs, traces[-1])) < 1e-3, traces


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
    began, calls = time.time(), obs.layer_counters().get("train.step", {"count": 0})["count"]
    state, _ = step(state, batch)
    noted = step._abstract
    leaves = jax.tree.leaves(noted)
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)  # no array held
    assert [x.shape for x in jax.tree.leaves(noted[1])] == [(2, 32), (2, 32)]
    state, _ = step(state, batch)
    assert step._abstract is noted and spy.call_count == 2 and spy.lower.call_count == 0
    # each call ran under the layer span of the noted name, and the timeline kept both
    assert obs.layer_counters()["train.step"]["count"] == calls + 2
    kept = obs.layer_timeline("train.step", since=began)
    assert len(kept) == 2 and all(end >= start and len(clocks) == 5
                                  for start, end, clocks in kept)
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
    assert {"layer_timeline", "step_timeline", "slow_steps", "watch_gc", "unwatch_gc"} \
        <= set(obs.__all__)
    assert "clock_marker" not in obs.__all__ and not hasattr(obs, "clock_marker")


# -- the step's host timeline (PR 51) -----------------------------------------------


def test_timeline_keeps_registered_names_outside_a_capture_and_only_those():
    rec = SpanRecorder()
    rec.keep_timeline("t.kept")
    before = time.time()
    for name in ("t.kept", "t.other", "t.kept"):
        with layer_span(name, recorder=rec):
            pass
    kept = rec.layer_timeline("t.kept")
    assert len(kept) == 2 and all(before <= a <= b and extra is None for a, b, extra in kept)
    assert rec.layer_timeline("t.other") == [] and rec.layer_spans() == []
    assert rec.layer_timeline("t.kept", since=kept[1][0]) == kept[1:]
    # the process's recorder keeps the step's call, its report and the collector's pauses
    assert set(obs.get_recorder()._timelines) == {"train.step", "train.report", "host.gc"}


def test_a_clocked_timeline_name_takes_the_hosts_clocks_at_entry():
    rec = SpanRecorder()
    rec.keep_timeline("t.clocked", clocks=True)
    for _ in range(2):
        with layer_span("t.clocked", recorder=rec):
            sum(i * i for i in range(20000))  # the thread runs: its CPU clock moves
    (_, _, first), (_, _, second) = rec.layer_timeline("t.clocked")
    assert first[0] == second[0] == threading.get_ident()
    assert second[1] > first[1] and second[2] >= first[2] + (second[1] - first[1]) - 1e-3
    assert second[3] is None or second[3] >= first[3]
    assert second[4] >= first[4] >= 0.0


def test_layer_counters_carry_the_longest_use():
    rec = SpanRecorder()
    for seconds in (0.001, 0.02, 0.002):
        with layer_span("t.tail", recorder=rec):
            time.sleep(seconds)
    got = rec.layer_counters()["t.tail"]
    assert got["count"] == 3 and 0.02 <= got["max_s"] < got["busy_s"]
    layer_record("t.elsewhere", time.time() - 0.5, recorder=rec)
    assert rec.layer_counters()["t.elsewhere"]["max_s"] >= 0.5


def test_the_longest_use_reaches_the_metrics_registry():
    from ray_tpu.util.metrics import prometheus_text

    with obs.layer_span("t.exported_tail"):
        pass
    assert 'ray_tpu_obs_layer_max_seconds{name="t.exported_tail"}' in prometheus_text()


def test_timeline_ring_drops_the_oldest_and_counts_the_drops():
    rec = SpanRecorder()
    rec.keep_timeline("t.ring", size=4)
    for i in range(7):
        with layer_span("t.ring", recorder=rec) as sp:
            sp.attrs["i"] = i
    kept = rec.layer_timeline("t.ring")
    assert len(kept) == 4 and rec.num_dropped_timeline_events["t.ring"] == 3
    assert [a for a, _, _ in kept] == sorted(a for a, _, _ in kept)
    assert rec.layer_counters()["t.ring"]["count"] == 7  # a drop loses no count
    rec.clear()
    assert rec.layer_timeline("t.ring") == [] and rec.num_dropped_timeline_events["t.ring"] == 0


def _script(steps, disturbed=None, where=None, disturb=None, report=True):
    """A loop as a user's: `steps` steps of call (2 ms under `train.step`), wait (3 ms),
    report, between (1 ms); `disturb()` runs once, in step `disturbed`, at `where`.
    -> (when the loop began, the start of the disturbed step's call)."""
    from ray_tpu.train import session

    began, marked = time.time(), None
    for i in range(steps):
        hit = disturb if i == disturbed else None
        with obs.layer_span("train.step") as sp:
            time.sleep(0.002)
            if hit and where == "dispatch":
                hit()
        if i == disturbed:
            marked = sp.start
        time.sleep(0.003)
        if hit and where == "wait":
            hit()
        if report:
            session.report({"step": i, "slow": bool(hit and where == "report")})
        time.sleep(0.001)
        if hit and where == "between":
            hit()  # before the NEXT step's call: the input side of this step's period
    return began, marked


class _Mailbox:
    """A report queue whose `put` holds a report marked slow for 60 ms."""

    def put(self, rep):
        if rep["metrics"]["slow"]:
            time.sleep(0.06)


@pytest.fixture
def scripted_session():
    from ray_tpu.train import session

    session._set_session(session.TrainContext(0, 1, "", _Mailbox()))
    yield
    session._clear_session()


def test_step_timeline_under_a_real_trainer_has_four_segments_that_sum_to_the_period(tmp_path):
    import ray_tpu
    from ray_tpu.core import runtime as rt
    from ray_tpu.train import JaxTrainer, RunConfig, session

    began = []

    def loop():
        began.append(time.time())
        for i in range(5):
            with obs.layer_span("train.step"):
                time.sleep(0.01)
            time.sleep(0.005)
            session.report({"step": i})
            time.sleep(0.002)

    if rt.is_initialized():
        rt.shutdown_runtime()
    ray_tpu.init(num_cpus=2)
    try:
        result = JaxTrainer(loop, run_config=RunConfig(name="timeline",
                                                       storage_path=str(tmp_path))).fit()
        assert result.error is None
    finally:
        ray_tpu.shutdown()
    rows = obs.step_timeline(since=began[0])  # read after shutdown(), as the benchmark does
    assert len(rows) == 5
    for r in rows[:-1]:
        assert r["dispatch_s"] >= 0.01 and r["wait_s"] >= 0.005 and r["between_s"] >= 0.002
        assert r["report_s"] > 0
        total = r["dispatch_s"] + r["wait_s"] + r["report_s"] + r["between_s"]
        assert abs(total - r["period_s"]) < 1e-6
        assert 0 <= r["thread_cpu_s"] < r["period_s"] and r["other_cpu_s"] >= 0
        assert r["gc_s"] >= 0 and r["compile_s"] == 0
    last = rows[-1]
    assert last["period_s"] is None and last["between_s"] is None and last["report_s"] > 0
    assert obs.step_timeline(since=began[0], until=rows[2]["start"]) == rows[:3]


def _big_graph():
    return [[i] for i in range(400_000)]


def _new_shape():
    jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((7, 13))).block_until_ready()


@pytest.mark.parametrize("cause,where,how", [
    ("gc", "dispatch", "collect"),
    ("report", "report", None),
    ("between", "between", "sleep"),
    ("dispatch", "dispatch", "sleep"),
    ("compile", "dispatch", "compile"),
    ("wait", "wait", "sleep"),
])
def test_slow_steps_names_one_cause(cause, where, how, scripted_session, monkeypatch):
    import gc

    from ray_tpu.obs import recorder
    from ray_tpu.utils.compile_cache import start_compile_log

    # the kernel's count of preemptions is this machine's weather: without it a sleep in
    # the caller's wait can only read `wait`
    monkeypatch.setattr(recorder, "_RUSAGE_THREAD", None)
    start_compile_log()
    held = _big_graph() if how == "collect" else None
    disturb = {"collect": gc.collect, "sleep": lambda: time.sleep(0.06),
               "compile": _new_shape, None: lambda: None}[how]
    obs.watch_gc()
    try:
        began, marked = _script(9, disturbed=5, where=where, disturb=disturb)
    finally:
        obs.unwatch_gc()
    del held
    rows = obs.step_timeline(since=began)
    assert len(rows) == 9
    slow = {s["start"]: s for s in obs.slow_steps(since=began)}
    assert marked in slow, [r["period_s"] for r in rows]
    found = slow[marked]
    assert found["cause"] == cause and found["excess_s"] > 0.01
    assert found["period_s"] > 1.2 * found["median_s"]
    if cause == "gc":
        assert found["gc_s"] >= 0.5 * found["excess_s"] and found["gc_generation"] == 2
    elif cause == "compile":
        assert found["compile_s"] > 0
    else:
        assert found["segment"] == where + "_s"
    # the same rows handed in read the same
    assert [s["start"] for s in obs.slow_steps(rows=rows)] == sorted(slow)


def _rows(periods, **disturbed):
    """Hand-built rows of a steady loop: 100 ms periods, 1 / 97 / 1 / 1 ms segments."""
    rows = [{"start": 10.0 + 0.1 * i, "dispatch_s": 0.001, "wait_s": p - 0.003,
             "report_s": 0.001, "between_s": 0.001, "period_s": p, "thread_cpu_s": 0.004,
             "other_cpu_s": 0.002, "nivcsw": 0, "gc_s": 0.0, "gc_generation": None,
             "compile_s": 0.0} for i, p in enumerate(periods)]
    rows[3].update(disturbed)
    return rows


@pytest.mark.parametrize("clocks,cause", [
    (dict(nivcsw=3), "preempted"),                          # off a core, and it ran no longer
    (dict(nivcsw=3, thread_cpu_s=0.05), "wait"),            # it ran: nothing took it off
    (dict(other_cpu_s=0.09), "other_threads"),              # the other threads burned the excess
    (dict(other_cpu_s=0.05), "wait"),                       # not enough to explain it
    (dict(nivcsw=None, thread_cpu_s=None, other_cpu_s=None, gc_s=None), "wait"),
    (dict(gc_s=0.03), "wait"),                              # a third of the excess: not the cause
    (dict(gc_s=0.05, gc_generation=2), "gc"),
    (dict(compile_s=0.2, gc_s=0.08), "compile"),            # the stated order: compile first
])
def test_slow_steps_reads_the_clocks_in_its_stated_order(clocks, cause):
    rows = _rows([0.1, 0.1, 0.1, 0.18, 0.1, 0.1], **clocks)
    slow, = obs.slow_steps(rows=rows)
    assert slow["start"] == rows[3]["start"] and slow["segment"] == "wait_s"
    assert slow["cause"] == cause and abs(slow["excess_s"] - 0.08) < 1e-9
    assert obs.slow_steps(rows=_rows([0.1] * 6)) == []
    assert obs.slow_steps(rows=rows, factor=2.0) == []
    # a run whose EVERY step is slow is a median, not a stall
    assert obs.slow_steps(rows=_rows([0.18] * 6)) == []


def test_a_step_with_no_report_before_the_next_is_still_a_row():
    began, _ = _script(4, report=False)
    rows = obs.step_timeline(since=began)
    assert len(rows) == 4
    for r in rows[:-1]:
        assert r["wait_s"] is None and r["report_s"] is None
        assert abs(r["dispatch_s"] + r["between_s"] - r["period_s"]) < 1e-9
    # and its slow step is named by the segment that is left
    began, marked = _script(8, disturbed=4, where="wait", disturb=lambda: time.sleep(0.06),
                            report=False)
    slow = {s["start"]: s for s in obs.slow_steps(since=began)}
    assert slow[marked]["cause"] == "between"


def test_the_collectors_hook_is_installed_once_and_gone_after_shutdown():
    import gc

    import ray_tpu
    from ray_tpu.core import runtime as rt
    from ray_tpu.obs.recorder import _on_gc

    if rt.is_initialized():
        rt.shutdown_runtime()
    ray_tpu.init(num_cpus=1)
    try:
        ray_tpu.init(num_cpus=1, ignore_reinit_error=True)
        obs.watch_gc()  # a train worker entering its loop
        assert gc.callbacks.count(_on_gc) == 1
        before = dict(obs.layer_counters()["host.gc"])
        began = time.time()
        held = _big_graph()
        gc.collect()
        del held
        gc.collect(0)
        after = obs.layer_counters()["host.gc"]
        assert after["count"] >= before["count"] + 2 and after["busy_s"] > before["busy_s"]
        assert after["max_s"] >= 0.001
        kept = obs.layer_timeline("host.gc", since=began)
        assert any(extra == {"generation": 2} and b - a >= 0.001 for a, b, extra in kept)
        # a quick collection of the youngest generation is counted and not kept
        assert not any(extra["generation"] == 0 and b - a <= 0.001 for a, b, extra in kept)
    finally:
        ray_tpu.shutdown()
    assert _on_gc not in gc.callbacks
    count = obs.layer_counters()["host.gc"]["count"]  # the counter outlives the runtime
    gc.collect()
    assert obs.layer_counters()["host.gc"]["count"] == count


def test_a_checkpoint_save_and_the_wait_for_it_are_layer_spans(tmp_path):
    """`train.checkpoint.save` / `.wait`: what a save costs the loop's thread, and with
    `max_s` its worst (the operator's surface: `obs.layer_counters()`, `/metrics`)."""
    import numpy as np

    from ray_tpu.train import checkpoint

    class Writer:  # an async save still in flight for 20 ms
        def wait_until_finished(self):
            time.sleep(0.02)

    def counts():
        got = obs.layer_counters()
        return [got.get(n, {"count": 0})["count"]
                for n in ("train.checkpoint.save", "train.checkpoint.wait")]

    before = counts()
    staged = tmp_path / "sharded.tmp"
    staged.mkdir()
    with obs.capture() as spans:
        checkpoint.Checkpoint.from_state({"w": np.arange(3.0)}, str(tmp_path / "plain"))
        checkpoint._PendingSave(Writer(), str(staged), str(tmp_path / "sharded")) \
            .wait_until_finished()
    assert [a - b for a, b in zip(counts(), before)] == [1, 1]
    assert [s.attrs for s in spans if s.name == "train.checkpoint.save"] == [{"sharded": False}]
    assert obs.layer_counters()["train.checkpoint.wait"]["max_s"] >= 0.02
    assert os.path.isdir(tmp_path / "plain") and os.path.isdir(tmp_path / "sharded")

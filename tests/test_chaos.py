"""ray_tpu.chaos: deterministic fault injection + the serving paths that
survive it (replica failover, engine preemption recovery, admission
control, graceful drain) — host-mode, CPU backend.

Cluster-mode chaos (node kills, heartbeat partitions, drains) lives in
test_chaos_cluster.py.
"""

import concurrent.futures
import dataclasses
import time

import pytest

import ray_tpu
from ray_tpu import chaos, obs, serve

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _chaos_clean():
    yield
    chaos.uninstall()


# ---------------------------------------------------------------------------
# schedule determinism + disabled-path inertness
# ---------------------------------------------------------------------------


def _mixed_schedule(seed):
    return chaos.FaultSchedule(seed, [
        chaos.FaultSpec(chaos.DROP_RPC, site="rpc.call",
                        match={"method": "push_*"}, p=0.4),
        chaos.FaultSpec(chaos.DELAY_RPC, site="rpc.call", every_n=7,
                        start_after=3, max_fires=4),
        chaos.FaultSpec(chaos.KILL_REPLICA, site="serve.replica", p=0.25),
    ])


def _drive(sched):
    for i in range(80):
        sched.fire("rpc.call", method="push_task" if i % 2 else "heartbeat")
        sched.fire("serve.replica", deployment="d", app="a")
    return sched.decisions()


def test_schedule_same_seed_reproduces_same_fault_sequence():
    d1 = _drive(_mixed_schedule(42))
    d2 = _drive(_mixed_schedule(42))
    assert d1 == d2 and len(d1) > 0
    # a different seed decorrelates the probabilistic specs
    assert _drive(_mixed_schedule(43)) != d1
    # and the wire form (env propagation) round-trips the whole contract
    sched = _mixed_schedule(42)
    clone = chaos.FaultSchedule.from_wire(sched.to_wire())
    assert _drive(sched) == _drive(clone)


def test_schedule_match_and_bounds():
    sched = chaos.FaultSchedule(7, [
        chaos.FaultSpec(chaos.DROP_RPC, site="rpc.call",
                        match={"method": "push_task"}, start_after=2,
                        max_fires=2),
    ])
    hits = []
    for _ in range(10):
        hits.append(bool(sched.fire("rpc.call", method="push_task")))
        assert not sched.fire("rpc.call", method="heartbeat")
        assert not sched.fire("other.site", method="push_task")
    # first 2 eligible calls skipped, then exactly max_fires=2 fire
    assert hits == [False, False, True, True] + [False] * 6
    with pytest.raises(ValueError):
        chaos.FaultSpec("no_such_kind")
    # at_s routes to ChaosRunner, which can't execute in-process kinds —
    # such a spec would silently fire nowhere, so it's rejected up front
    with pytest.raises(ValueError, match="at_s"):
        chaos.FaultSpec(chaos.DROP_RPC, site="rpc.call", at_s=2.0)
    chaos.FaultSpec(chaos.KILL_REPLICA, at_s=2.0)  # runner kind: fine


def test_disabled_harness_is_inert():
    assert chaos.harness.ACTIVE is None
    assert chaos.fire("rpc.call", method="x") == []
    assert chaos.fault_log() == []
    sched = chaos.install(chaos.FaultSchedule(1, []))
    assert chaos.active() is sched
    chaos.uninstall()
    assert chaos.active() is None
    import os

    assert chaos.ENV_VAR not in os.environ


def test_backoff_growth_cap_jitter_and_determinism():
    import random

    from ray_tpu.util.backoff import ExponentialBackoff

    b = ExponentialBackoff(base=0.1, cap=1.0, multiplier=2.0, jitter=0.0)
    assert [round(b.next_delay(), 3) for i in range(6)] == [
        0.1, 0.2, 0.4, 0.8, 1.0, 1.0
    ]
    b.reset()
    assert b.next_delay() == pytest.approx(0.1)
    # jittered delays stay inside [(1-j)*ladder, ladder]
    j = ExponentialBackoff(base=0.1, cap=1.0, jitter=0.5,
                           rng=random.Random(5))
    ladder = [0.1, 0.2, 0.4, 0.8, 1.0]
    for expect in ladder:
        d = j.next_delay()
        assert expect * 0.5 <= d <= expect
    # seeded rng => reproducible jitter
    a = ExponentialBackoff(base=0.1, cap=1.0, rng=random.Random(9))
    b2 = ExponentialBackoff(base=0.1, cap=1.0, rng=random.Random(9))
    assert [a.next_delay() for _ in range(8)] == [
        b2.next_delay() for _ in range(8)
    ]
    with pytest.raises(ValueError):
        ExponentialBackoff(base=0.0)


# ---------------------------------------------------------------------------
# serve-layer failover
# ---------------------------------------------------------------------------


@pytest.fixture
def serve_instance():
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=32)
    yield
    serve.shutdown()


def test_replica_failover_and_controller_replacement(serve_instance):
    @serve.deployment(num_replicas=2)
    class Sq:
        def __call__(self, x):
            return x * x

    handle = serve.run(Sq.bind(), name="chaos_failover", route_prefix=None)
    sched = chaos.install(chaos.FaultSchedule(3, [
        chaos.FaultSpec(chaos.KILL_REPLICA, site="serve.replica",
                        every_n=5, start_after=2, max_fires=3),
    ]))
    # ACCEPTANCE: every request completes despite 3 injected replica
    # crashes — failover re-dispatches onto a healthy replica
    outs = [handle.remote(i).result(timeout_s=60) for i in range(14)]
    chaos.uninstall()
    assert outs == [i * i for i in range(14)]
    assert [f.kind for f in sched.log].count(chaos.KILL_REPLICA) == 3
    # post-mortem trail: the fault AND the failover landed in obs traces
    rec = obs.get_recorder()
    names = {
        s.name for m in rec.traces(limit=300) for s in rec.get(m["trace_id"])
    }
    assert "chaos.kill_replica" in names and "serve.failover" in names

    # orchestrated kill: the actor actually dies; requests keep completing
    # and the controller replaces the corpse
    from ray_tpu.serve.api import _get_controller_handle

    ctrl = _get_controller_handle()
    killed = ray_tpu.get(ctrl.kill_replica.remote("chaos_failover", None))
    assert killed
    assert [handle.remote(i).result(timeout_s=60) for i in range(10)] == [
        i * i for i in range(10)
    ]
    # replacement: the corpse leaves the routing set (health sweep) and a
    # fresh replica brings the deployment back to 2 RUNNING
    deadline = time.time() + 30
    ids = []
    while time.time() < deadline:
        info = ray_tpu.get(
            ctrl.get_running_replicas.remote("chaos_failover", "Sq")
        )
        ids = [x[0] for x in info["replicas"]]
        if killed not in ids and len(ids) >= 2:
            break
        time.sleep(0.2)
    assert killed not in ids and len(ids) >= 2, ids

    # opt-out: a non-idempotent endpoint with system_retries=0 surfaces
    # the crash instead of silently re-executing
    sched2 = chaos.install(chaos.FaultSchedule(5, [
        chaos.FaultSpec(chaos.KILL_REPLICA, site="serve.replica", max_fires=1),
    ]))
    from ray_tpu.serve.handle import _is_replica_failure

    with pytest.raises(Exception) as ei:
        handle.options(system_retries=0).remote(3).result(timeout_s=60)
    assert _is_replica_failure(ei.value), repr(ei.value)
    assert sched2.fired_kinds() == [chaos.KILL_REPLICA]


def test_failover_budget_is_attempts_not_unique_replicas(serve_instance):
    """A replica that crashes EVERY request must exhaust the retry budget
    and raise — counting unique failed replica ids instead of attempts
    would re-dispatch onto the same sole replica forever."""
    @serve.deployment(num_replicas=1)
    def echo(x):
        return x

    handle = serve.run(echo.bind(), name="chaos_budget", route_prefix=None)
    assert handle.remote(1).result(timeout_s=60) == 1
    sched = chaos.install(chaos.FaultSchedule(9, [
        chaos.FaultSpec(chaos.KILL_REPLICA, site="serve.replica"),  # always
    ]))
    t0 = time.time()
    with pytest.raises(Exception) as ei:
        handle.remote(2).result(timeout_s=60)
    chaos.uninstall()
    assert "ReplicaCrashed" in repr(ei.value)
    assert time.time() - t0 < 30, "retry loop did not terminate promptly"
    # default budget: 1 original + 2 retries = 3 crashes
    assert sched.fired_kinds().count(chaos.KILL_REPLICA) == 3


# ---------------------------------------------------------------------------
# LLM engine: preemption recovery + idempotent completions
# ---------------------------------------------------------------------------


def _tiny_engine_config(**over):
    import jax.numpy as jnp

    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models import llama

    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    kw = dict(model=cfg, num_blocks=64, block_size=8, max_num_seqs=4,
              max_prefill_len=32, decode_chunk=2)
    kw.update(over)
    return EngineConfig(**kw)


def test_engine_recover_preserves_finished_prefix():
    """Finished-prefix safety of recover(): outputs generated before the
    crash survive verbatim (soft AND rebuilt-KV recovery), nothing is
    lost, nothing re-emitted."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    eng = LLMEngine(_tiny_engine_config())
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    rids = [eng.add_request([1, 2, 3, i + 4], sp) for i in range(3)]
    eng.step()
    eng.step()
    before = {r: list(eng.requests[r].output_token_ids) for r in rids}
    assert all(before.values())
    moved = eng.recover(rebuild_kv=False)
    assert set(moved) == set(rids)
    # mid-flight hard crash too: run a step, then lose the whole KV cache
    eng.step()
    eng.recover(rebuild_kv=True)
    outs = {}
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished:
                outs[o.request_id] = o.output_token_ids
    assert set(outs) == set(rids)
    for r in rids:
        assert len(outs[r]) == 12
        assert outs[r][: len(before[r])] == before[r], "prefix changed"
    # recovery left its trail in the flight recorder
    rec_names = set()
    for m in obs.get_recorder().traces(limit=100):
        for s in obs.get_recorder().get(m["trace_id"]):
            rec_names.add(s.name)
    assert "engine.recover" in rec_names


def test_engine_preemption_no_lost_no_duplicated_completions(serve_instance):
    """ACCEPTANCE: under an injected engine preemption, a serving
    workload of N requests completes all N with no lost and no duplicated
    completion ids."""
    from ray_tpu.llm.openai_api import LLMConfig, build_openai_app

    llm = LLMConfig(model_id="tiny-chaos-preempt",
                    engine=_tiny_engine_config())
    handle = build_openai_app(llm, name="chaos_llm", route_prefix=None)
    sched = chaos.install(chaos.FaultSchedule(11, [
        chaos.FaultSpec(chaos.PREEMPT_ENGINE, site="llm.engine.step",
                        start_after=3, max_fires=1),
    ]))

    def one(i):
        return handle.options(method_name="completions").remote(
            {"prompt": f"hello {i}", "max_tokens": 10, "temperature": 0.0,
             "seed": i}
        ).result(timeout_s=180)

    n = 6
    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        outs = list(ex.map(one, range(n)))
    chaos.uninstall()
    assert chaos.PREEMPT_ENGINE in sched.fired_kinds()
    ids = [o["id"] for o in outs]
    assert len(ids) == n and len(set(ids)) == n  # all N, no dup ids
    for o in outs:
        assert "error" not in o, o
        assert o["choices"][0]["finish_reason"] in ("stop", "length")
        assert 0 < o["usage"]["completion_tokens"] <= 10
    st = handle.options(method_name="stats").remote().result(timeout_s=30)
    assert st["engine_recoveries"] >= 1
    # the recovery event is in the flight recorder for the post-mortem
    rec = obs.get_recorder()
    names = {
        s.name for m in rec.traces(limit=300) for s in rec.get(m["trace_id"])
    }
    assert "chaos.preempt_engine" in names
    assert "engine.runner_recover" in names or "engine.recover" in names


# ---------------------------------------------------------------------------
# admission control + graceful drain
# ---------------------------------------------------------------------------


def test_overload_sheds_429_with_retry_after_then_drains_503(serve_instance):
    """ACCEPTANCE: under injected overload the app sheds load with 429 +
    Retry-After while accepted requests keep bounded queue_wait (checked
    against the ray_tpu.obs SLO histogram); drain turns new requests into
    503s while in-flight work finishes."""
    from ray_tpu.llm.admission import AdmissionConfig
    from ray_tpu.llm.openai_api import LLMConfig, build_openai_app
    from ray_tpu.obs import slo

    model_id = "tiny-chaos-overload"
    llm = LLMConfig(
        model_id=model_id,
        # one token a round, the plain decode: on the engine's default, pipelined path
        # this flood cannot be made to fill the queue from a test. A round sleeps holding
        # the runner's lock, which the admission check takes too, and the loop takes the
        # lock back as soon as it has let it go, so arrivals are admitted about as fast as
        # requests END: with chunks of up to 64 tokens, of one token (CHUNK_BUCKETS patched
        # to (1,)), with requests of 24, 48 or 384 tokens and rounds of 0.1 or 0.2 s the
        # first 429 came after 6.6-111 s and in three of eight runs not in 120 s (PR 54's
        # runs; ROADMAP D8 has what the server needs for it). A token a round and a
        # delivery a round the checks come in about one a round, against requests of 24
        # rounds: the queue is full within seconds
        engine=_tiny_engine_config(max_num_seqs=2, pipeline_decode=False, decode_chunk=1),
        admission=AdmissionConfig(max_queue_depth=3),
    )
    handle = build_openai_app(llm, name="chaos_overload", route_prefix=None)
    # slow each engine round so that an accepted request holds its slot for
    # seconds (24 tokens at 0.2 s a round and a token a round), and flood
    # until a request HAS been shed (the event this test means) rather than
    # with one burst that has to win a race: a round sleeps holding the
    # runner's lock, which the admission check takes too, so the checks
    # come in about one a round, and with chunks of 64 tokens a round the
    # queue never reached max_queue_depth=3: "overload never shed"
    # (ROADMAP D8's flaky list)
    chaos.install(chaos.FaultSchedule(5, [
        chaos.FaultSpec(chaos.DELAY_RPC, site="llm.engine.step",
                        delay_s=0.2),
    ]))

    # all submitters arrive TOGETHER: without the barrier, thread-start
    # stagger under full-suite GIL load can spread the burst enough that
    # the queue never crosses max_queue_depth and nothing sheds
    import threading as _threading

    start_gate = _threading.Barrier(24, timeout=60)
    shed = _threading.Event()
    deadline = time.monotonic() + 120

    def one(i):
        return handle.options(method_name="completions").remote(
            {"prompt": f"p{i}", "max_tokens": 24 if i < 24 else 4,
             "temperature": 0.0}
        ).result(timeout_s=180)

    def flood(i):
        """Submit again and again until SOME submitter has been shed: 24
        submitters against two slots and a queue of three fill the queue
        however the arrivals are staggered, and an accepted request holds
        its submitter until it is served."""
        start_gate.wait()
        mine = []
        while not shed.is_set() and time.monotonic() < deadline:
            mine.append(one(i))
            if mine[-1].get("error", {}).get("code") == 429:
                shed.set()
        return mine

    with concurrent.futures.ThreadPoolExecutor(24) as ex:
        outs = [o for mine in ex.map(flood, range(24)) for o in mine]
    chaos.uninstall()
    assert shed.is_set(), "overload never shed in 120 s of 24 submitters"
    accepted = [o for o in outs if "choices" in o]
    rejected = [o for o in outs if o.get("error", {}).get("code") == 429]
    assert accepted, "everything shed"
    for o in rejected:
        assert o["error"]["type"] == "rate_limit_error"
        assert o["error"]["retry_after"] >= 0.1  # the Retry-After hint
    # accepted requests kept bounded queue_wait per the SLO histogram
    data = slo.queue_wait_histogram().hist_data()
    buckets, total_s, count = data[(model_id,)]
    assert count == len(accepted)
    # bound scaled to the slowed engine: worst accepted waiter ~= 3 queue
    # positions x ~5s service / 2 slots; shedding keeps the mean well under
    assert total_s / count < 8.0, f"mean queue_wait {total_s/count:.3f}s"
    st = handle.options(method_name="stats").remote().result(timeout_s=30)
    assert st["admission"]["rejected_429"] == len(rejected)

    # Retry-After surfaces as an HTTP header through the proxy mapping
    from ray_tpu.llm.admission import retry_after_header

    assert retry_after_header(rejected[0]) is not None
    assert int(retry_after_header(rejected[0])) >= 1

    # graceful drain: in-flight finishes, new arrivals get 503
    d = handle.options(method_name="drain").remote(30.0).result(timeout_s=60)
    assert d["drained"] is True and d["inflight"] == 0
    out = one(99)
    assert out["error"]["code"] == 503
    assert out["error"]["type"] == "service_unavailable_error"
    assert out["error"]["retry_after"] > 0
    st = handle.options(method_name="stats").remote().result(timeout_s=30)
    assert st["admission"]["draining"] is True
    assert st["admission"]["rejected_503"] >= 1


# ---------------------------------------------------------------------------
# process-pool fault injection (crash-isolated worker_mode="process")
# ---------------------------------------------------------------------------


def test_process_pool_chaos_kill_retries_to_success():
    from ray_tpu.core import runtime as rt

    if rt.is_initialized():
        rt.shutdown_runtime()
    ray_tpu.init(num_cpus=4, worker_mode="process")
    try:
        sched = chaos.install(chaos.FaultSchedule(17, [
            chaos.FaultSpec(chaos.KILL_WORKER, site="process_pool.task",
                            max_fires=1),
        ]))

        @ray_tpu.remote(max_retries=2)
        def work(x):
            return x + 1

        # first attempt's worker is killed mid-task; the retry completes
        assert ray_tpu.get(work.remote(41), timeout=60) == 42
        assert sched.fired_kinds() == [chaos.KILL_WORKER]
    finally:
        chaos.uninstall()
        rt.shutdown_runtime()


# ---------------------------------------------------------------------------
# CORRUPT_FRAME on the raw RPC plane (the one kind no test referenced —
# found by scripts/check_chaos_hooks.py, which now gates this coverage)
# ---------------------------------------------------------------------------


def test_corrupt_frame_fails_decode_then_redial_recovers():
    """A CORRUPT_FRAME-mangled frame keeps its length prefix, so the peer
    reads a full frame, fails to deserialize it, and drops the connection
    (the realistic torn-wire mode). The caller must see a typed RpcError
    — never a hang, never a half-applied stream — and a redial client
    absorbs the fault transparently on the next attempt."""
    from ray_tpu.cluster.rpc import (
        ReconnectingRpcClient,
        RpcClient,
        RpcError,
        RpcServer,
    )

    srv = RpcServer()
    srv.route("echo", lambda payload, peer: {"v": payload["v"]})
    addr = srv.start()
    try:
        # raw client: the corrupted call fails with a typed error
        sched = chaos.install(chaos.FaultSchedule(11, [
            chaos.FaultSpec(chaos.CORRUPT_FRAME, site="rpc.frame",
                            max_fires=1),
        ]))
        c = RpcClient(*addr, timeout=5.0).connect()
        with pytest.raises(RpcError):
            c.call("echo", {"v": 1}, timeout=5.0)
        c.close()
        assert sched.fired_kinds() == [chaos.CORRUPT_FRAME]
        chaos.uninstall()

        # redial client: one corruption costs a reconnect, not the request
        chaos.install(chaos.FaultSchedule(12, [
            chaos.FaultSpec(chaos.CORRUPT_FRAME, site="rpc.frame",
                            max_fires=1),
        ]))
        rc = ReconnectingRpcClient(*addr, timeout=5.0, retries=2)
        assert rc.call("echo", {"v": 2}, timeout=5.0) == {"v": 2}
        rc.close()
    finally:
        chaos.uninstall()
        srv.stop()


def test_admission_reservation_never_leaks():
    """Regression (code-review catch on the admission-TOCTOU fix): the
    reservation counted by _admission_check must be handed over to the
    real queue entry on submit — a leak would permanently shrink the
    effective queue depth until the server 429s ALL traffic. Drive the
    success, invalid-request, and rejected paths and assert the counter
    returns to zero."""
    import asyncio

    from ray_tpu.llm.admission import AdmissionConfig
    from ray_tpu.llm.openai_api import LLMConfig, LLMServer

    server = LLMServer(LLMConfig(
        model_id="tiny-admit-leak",
        engine=_tiny_engine_config(max_num_seqs=2),
        admission=AdmissionConfig(max_queue_depth=3),
    ))
    try:
        for i in range(5):  # > max_queue_depth: a leak would start 429ing
            out = asyncio.run(server.completions(
                {"prompt": f"p{i}", "max_tokens": 4, "temperature": 0.0}
            ))
            assert "choices" in out, out
            assert server._admit_reserved == 0
        # invalid request after admission: reservation released, not leaked
        bad = asyncio.run(server.completions(
            {"prompt": "p", "max_tokens": 4, "temperature": "NaNsense"}
        ))
        assert bad["error"]["code"] == 400
        assert server._admit_reserved == 0
        # chat path too
        out = asyncio.run(server.chat_completions(
            {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 4}
        ))
        assert "choices" in out
        assert server._admit_reserved == 0
    finally:
        server.shutdown()

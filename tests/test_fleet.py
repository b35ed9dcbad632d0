"""Multi-tenant model fleet (ray_tpu.fleet, r21).

What must hold:

* **spec/QoS units** — model refs parse, weighted-fair queue shares
  price per tenant, a batch tenant's flood exhausts ITS OWN share while
  the paying tenant stays admittable;
* **adapter residency** — slot exhaustion is a typed error, LRU evict
  frees idle adapters (never in-flight ones), and an adapter swap drops
  exactly the swapped adapter's prefix chains (the co-resident
  adapter's cached prefixes survive, bitwise);
* **tenant isolation end-to-end** — under a batch-tenant flood, the
  paying tenant's request priority-preempts into the batch and its
  queue-wait SLO grades GREEN;
* **canary ladder** — one replica takes the new version, grading sees
  only post-canary traffic, promote fans out bitwise-identically,
  rollback restores the retained weights bitwise; a seeded
  PREEMPT_ENGINE mid-canary loses zero requests;
* **capture gates** — the checked-in FLEET_serving_r21.json holds the
  acceptance numbers (paying tenant green with isolation vs red
  without; fleet goodput >= static partitioning; canary
  promote+rollback bitwise with zero lost requests).
"""

import concurrent.futures
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.fleet import (
    AdapterSpec,
    FleetAdmissionRejected,
    FleetManager,
    FleetSpec,
    ModelSpec,
    TenantSpec,
    UnknownModelError,
    UnknownTenantError,
    bitwise_equal,
    local_slo_histograms,
)
from ray_tpu.fleet.qos import TenantQoSController
from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.engine import AdapterSlotsExhausted
from ray_tpu.models import llama
from ray_tpu.obs.telemetry import SLOThresholds, evaluate_slo

pytestmark = pytest.mark.fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPT = [5, 9, 17, 3]
GREEDY = SamplingParams(max_tokens=6, temperature=0.0)
# generous grading thresholds: CPU cold-compile TTFT must not fail
# functional tests (the bench grades with real ones)
LOOSE = SLOThresholds(ttft_p_s=120, tpot_p_s=120, queue_wait_p_s=120)


def _cfg(**kw):
    kw.setdefault("model", llama.LLAMA_TINY)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_loras", 2)
    kw.setdefault("lora_rank", 4)
    return EngineConfig(**kw)


def _adapters(seed, scale=0.5, rank=4):
    m = llama.LLAMA_TINY
    rng = np.random.RandomState(seed)
    mk = lambda *shape: (rng.randn(*shape) * scale).astype(np.float32)
    return {
        "wq": (mk(m.n_layers, m.d_model, rank),
               mk(m.n_layers, rank, m.n_heads * m.head_dim)),
        "wv": (mk(m.n_layers, m.d_model, rank),
               mk(m.n_layers, rank, m.n_kv_heads * m.head_dim)),
    }


def _spec(**kw):
    kw.setdefault("models", (ModelSpec(
        "tiny", replicas=1, adapters=(AdapterSpec("styleA", rank=4),)
    ),))
    kw.setdefault("tenants", (
        TenantSpec("gold", priority=2, weight=3.0),
        TenantSpec("batch", priority=0, weight=1.0),
    ))
    return FleetSpec(**kw)


# ---------------------------------------------------------------------------
# spec + QoS units (no engines)
# ---------------------------------------------------------------------------


def test_spec_parse_shares_and_lookups():
    spec = _spec(total_queue_budget=8)
    assert FleetSpec.parse_model_ref("tiny") == ("tiny", None)
    assert FleetSpec.parse_model_ref("tiny:styleA") == ("tiny", "styleA")
    # weighted-fair shares: 3:1 over budget 8 -> 6 and 2
    assert spec.queue_depth_for(spec.tenant("gold")) == 6
    assert spec.queue_depth_for(spec.tenant("batch")) == 2
    with pytest.raises(UnknownTenantError):
        spec.tenant("nobody")
    with pytest.raises(UnknownModelError):
        spec.model("other")
    lax = _spec(allow_unknown_tenants=True)
    assert lax.tenant("nobody").priority == 0
    assert lax.tenant("").tenant_id == "anon"  # anonymous pools under one id
    with pytest.raises(ValueError, match="':'-free"):
        AdapterSpec("a:b")


def test_qos_flood_exhausts_own_share_only():
    """The isolation invariant at the admission layer: the batch
    tenant's flood fills the batch share and sheds; the paying tenant's
    share stays open throughout."""
    spec = _spec(total_queue_budget=8)
    qos = TenantQoSController(spec)
    batch, gold = spec.tenant("batch"), spec.tenant("gold")
    admitted, rejections = 0, []
    for _ in range(10):
        rej = qos.admit(batch)
        if rej is None:
            admitted += 1
        else:
            rejections.append(rej)
    assert admitted == 2 and len(rejections) == 8  # batch share = 2
    assert rejections[0]["error"]["code"] in (429, 503)
    # the paying tenant admits straight through its own 6-slot share
    for _ in range(6):
        assert qos.admit(gold) is None
    assert qos.waiting_by_tenant() == {"batch": 2, "gold": 6}
    # releases reopen the batch share
    qos.release("batch")
    assert qos.admit(batch) is None


# ---------------------------------------------------------------------------
# adapter residency: typed exhaustion, LRU evict, scoped invalidation
# ---------------------------------------------------------------------------


def test_adapter_slots_exhausted_typed_and_lru_evict():
    eng = LLMEngine(_cfg(), seed=7)
    eng.add_lora("a", _adapters(1))
    eng.add_lora("b", _adapters(2))
    with pytest.raises(AdapterSlotsExhausted, match="slots in use"):
        eng.add_lora("c", _adapters(3))
    assert isinstance(AdapterSlotsExhausted("x"), ValueError)  # old catches
    # touch "a" (most recently used) -> LRU victim is "b"
    rid = eng.add_request(PROMPT, GREEDY, lora_id="a")
    while eng.has_unfinished():
        eng.step()
    eng.abort_request(rid)
    eng.add_lora("c", _adapters(3), evict=True)
    assert set(eng._lora_slots) == {"a", "c"}


def test_lru_evict_refuses_inflight_adapter():
    eng = LLMEngine(_cfg(max_loras=1), seed=7)
    eng.add_lora("a", _adapters(1))
    eng.add_request(PROMPT, SamplingParams(max_tokens=32), lora_id="a")
    eng.step()  # "a" now has an in-flight sequence
    assert eng.evict_lru_lora() is None  # pinned, not evictable
    with pytest.raises(AdapterSlotsExhausted):
        eng.add_lora("b", _adapters(2), evict=True)


def test_adapter_swap_scoped_prefix_invalidation():
    """remove_lora drops exactly the removed adapter's salt: the
    co-resident adapter's cached prefix chains survive and still hit."""
    eng = LLMEngine(_cfg(enable_prefix_caching=True, block_size=4), seed=7)
    eng.add_lora("a", _adapters(1))
    eng.add_lora("b", _adapters(2))
    prompt = list(range(3, 19))  # 16 tokens = 4 full blocks
    for lid in ("a", "b"):
        eng.add_request(prompt, GREEDY, lora_id=lid)
        while eng.has_unfinished():
            eng.step()
    slot_a = eng._lora_slots["a"]
    slot_b = eng._lora_slots["b"]
    assert eng.allocator.probe_prefix(prompt, slot_a) > 0
    assert eng.allocator.probe_prefix(prompt, slot_b) > 0
    eng.remove_lora("a")
    # a's chains are gone, b's survive untouched
    assert eng.allocator.probe_prefix(prompt, slot_a) == 0
    assert eng.allocator.probe_prefix(prompt, slot_b) > 0
    # reload "a" (new weights): fresh salt serves fresh chains
    eng.add_lora("a", _adapters(9))
    new_slot = eng._lora_slots["a"]
    assert eng.allocator.probe_prefix(prompt, new_slot) == 0


# ---------------------------------------------------------------------------
# fleet routing + end-to-end isolation
# ---------------------------------------------------------------------------


def test_fleet_routes_and_serves_adapter_refs():
    mgr = FleetManager(_spec(models=(ModelSpec("tiny", replicas=2),)),
                       engine_config=_cfg(), seed=7, thresholds=LOOSE)
    try:
        mgr.register_adapter("tiny", "styleA", _adapters(1))
        base = mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY),
                           timeout_s=120)
        tuned = mgr.collect(mgr.submit("gold", "tiny:styleA", PROMPT, GREEDY),
                            timeout_s=120)
        assert base.output_token_ids != tuned.output_token_ids
        # adapter residency is dynamic: at least one replica loaded it
        resident = [
            r.tag for r in mgr.replicas("tiny")
            if "styleA" in r.engine._lora_slots
        ]
        assert resident
        # an unregistered adapter is a typed error, not a hang
        with pytest.raises(Exception, match="not registered"):
            mgr.submit("gold", "tiny:ghost", PROMPT, GREEDY)
        # routing spreads equal load round-robin (the canary replica
        # must see traffic)
        tags = {mgr.route("tiny", None, PROMPT).tag for _ in range(4)}
        assert len(tags) == 2
    finally:
        mgr.close()


def test_noisy_neighbor_paying_tenant_green():
    """ACCEPTANCE (functional half): a batch tenant floods the fleet;
    the paying tenant's request preempts into the batch, its queue-wait
    grades GREEN, and the preemption is attributed to the batch tenant
    by the {model,tenant,reason} counter."""
    from ray_tpu.llm.engine import preemption_counter

    spec = _spec(total_queue_budget=8)
    mgr = FleetManager(
        spec, engine_config=_cfg(max_num_seqs=2), seed=7, thresholds=LOOSE
    )
    try:
        # warm the engine (compile) so grading sees steady-state numbers
        mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY), timeout_s=120)
        baseline = local_slo_histograms()

        stop = threading.Event()
        shed = [0]

        def flood():
            while not stop.is_set():
                try:
                    # long enough that no single decode chunk (up to 64
                    # steps once the adaptive controller has ratcheted) can
                    # finish a batch row: at 24 tokens with EOS allowed, the
                    # flush that precedes a priority preemption finished the
                    # whole batch on a fast idle host and nothing was left
                    # to preempt (the test then failed without any load)
                    t = mgr.submit("batch", "tiny", PROMPT,
                                   SamplingParams(max_tokens=100, ignore_eos=True))
                except FleetAdmissionRejected:
                    shed[0] += 1
                    time.sleep(0.005)
                    continue
                try:
                    mgr.collect(t, timeout_s=120)
                except Exception:
                    pass

        threads = [threading.Thread(target=flood) for _ in range(4)]
        for th in threads:
            th.start()
        engine = mgr.replicas("tiny")[0].engine

        def saturated():
            """The flood holds both of max_num_seqs=2 rows. Waited for,
            not slept for: under a loaded host the four threads need
            more than half a second to start and be scheduled, and all
            three paying requests then ran through an idle engine with
            nothing to preempt."""
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if sum(r.tenant == "batch" for r in list(engine.running)) >= 2:
                    return True
                time.sleep(0.002)
            return False

        def preempted_batch():
            return any(k[1] == "batch" and k[2] == "priority"
                       for k in preemption_counter().series())

        try:
            # at least three paying requests, and on until one of them HAS
            # preempted a batch row (the event this test means), under a
            # deadline: between `saturated()` and the paying request's
            # admission a decode chunk can finish both batch rows, and then
            # that request preempts nothing however the host is loaded
            deadline = time.monotonic() + 120
            served = 0
            while served < 3 or not preempted_batch():
                assert time.monotonic() < deadline, "no batch row preempted in 120 s"
                assert saturated()
                out = mgr.collect(
                    mgr.submit("gold", "tiny", PROMPT, GREEDY), timeout_s=120
                )
                assert out.finished
                served += 1
        finally:
            stop.set()
            for th in threads:
                th.join(timeout=120)
        # the paying tenant's own SLO series (post-warmup only) is green
        grades = evaluate_slo(
            local_slo_histograms(baseline=baseline),
            SLOThresholds(ttft_p_s=60, tpot_p_s=60, queue_wait_p_s=60),
        )["model_tags"]
        assert grades["tenant:gold"]["grade"] == "green", grades
        # priority preemption fired and was attributed to the batch tenant
        pre = {
            k: v for k, v in preemption_counter().series().items()
            if k[2] == "priority"
        }
        assert pre and any(k[1] == "batch" for k in pre), pre
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# canary ladder
# ---------------------------------------------------------------------------


def _perturbed(params, factor=1.01):
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x) * np.asarray(factor, np.asarray(x).dtype),
        params,
    )


def test_canary_base_promote_bitwise():
    mgr = FleetManager(_spec(models=(ModelSpec("tiny", replicas=3),)),
                       engine_config=_cfg(), seed=7, thresholds=LOOSE)
    try:
        reps = mgr.replicas("tiny")
        new = _perturbed(reps[0].engine.params)
        info = mgr.weights.begin_canary("tiny", params=new)
        canary = next(r for r in reps if r.tag == info["replica"])
        others = [r for r in reps if r.tag != info["replica"]]
        # exactly one replica serves the candidate
        assert bitwise_equal(canary.engine.params, new)
        assert all(not bitwise_equal(r.engine.params, new) for r in others)
        # round-robin routing lands traffic on the canary tag
        for _ in range(6):
            mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY),
                        timeout_s=120)
        g = mgr.weights.canary_grade()
        assert g["grade"] == "green", g
        rep = mgr.weights.decide(g["grade"])
        assert rep["outcome"] == "promoted"
        # bitwise identity across the whole pool after promote
        assert all(bitwise_equal(r.engine.params, new) for r in reps)
        assert mgr.weights.versions[("tiny", None)] == info["version"]
    finally:
        mgr.close()


def test_canary_red_rolls_back_bitwise():
    """Red canary: impossible thresholds force a red grade; decide()
    rolls back and the canary replica serves the retained pre-canary
    weights bitwise (greedy tokens prove it end-to-end)."""
    mgr = FleetManager(
        _spec(models=(ModelSpec("tiny", replicas=2),)),
        engine_config=_cfg(), seed=7,
        thresholds=SLOThresholds(ttft_p_s=1e-9, tpot_p_s=1e-9,
                                 queue_wait_p_s=1e-9, yellow_factor=1.0),
    )
    try:
        reps = mgr.replicas("tiny")
        old = jax.tree_util.tree_map(np.asarray, reps[0].engine.params)
        ref = mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY),
                          timeout_s=120).output_token_ids
        mgr.weights.begin_canary("tiny", params=_perturbed(old, 1.5))
        for _ in range(4):
            mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY),
                        timeout_s=120)
        rep = mgr.weights.decide()
        assert rep["outcome"] == "rolled_back"
        assert all(bitwise_equal(r.engine.params, old) for r in reps)
        # and the fleet serves the pre-canary continuation again
        outs = {
            tuple(mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY),
                              timeout_s=120).output_token_ids)
            for _ in range(4)
        }
        assert outs == {tuple(ref)}
    finally:
        mgr.close()


def test_canary_adapter_rollback_scoped_drop():
    """Adapter canary + rollback: only the swapped adapter's prefix
    chains drop (the base salt's cache survives), and rollback restores
    the v1 adapter bytes (greedy continuation proves it)."""
    mgr = FleetManager(_spec(), engine_config=_cfg(
        enable_prefix_caching=True, block_size=4), seed=7, thresholds=LOOSE)
    try:
        mgr.register_adapter("tiny", "styleA", _adapters(1))
        prompt = list(range(3, 19))
        base_out = mgr.collect(mgr.submit("gold", "tiny", prompt, GREEDY),
                               timeout_s=120).output_token_ids
        v1_out = mgr.collect(
            mgr.submit("gold", "tiny:styleA", prompt, GREEDY),
            timeout_s=120).output_token_ids
        eng = mgr.replicas("tiny")[0].engine
        assert eng.allocator.probe_prefix(prompt, 0) > 0  # base chains hot
        mgr.weights.begin_canary("tiny", adapter_id="styleA",
                                 payload=_adapters(2))
        # the swap dropped ONLY styleA's salt: base chains still resident
        assert eng.allocator.probe_prefix(prompt, 0) > 0
        v2_out = mgr.collect(
            mgr.submit("gold", "tiny:styleA", prompt, GREEDY),
            timeout_s=120).output_token_ids
        assert v2_out != v1_out  # canary actually serves the new adapter
        rb = mgr.weights.rollback()
        assert rb["outcome"] == "rolled_back"
        assert eng.allocator.probe_prefix(prompt, 0) > 0
        back = mgr.collect(
            mgr.submit("gold", "tiny:styleA", prompt, GREEDY),
            timeout_s=120).output_token_ids
        assert back == v1_out  # bitwise-restored weights, same greedy path
        assert base_out == mgr.collect(
            mgr.submit("gold", "tiny", prompt, GREEDY),
            timeout_s=120).output_token_ids
    finally:
        mgr.close()


@pytest.mark.chaos
def test_preempt_engine_mid_canary_zero_lost():
    """ACCEPTANCE: seeded PREEMPT_ENGINE fires mid-canary; every
    in-flight request completes (the runner's recover ladder re-enqueues
    them on the rebuilt/recovered engine) and the promote still lands
    bitwise-identically."""
    from ray_tpu.chaos import harness as chaos
    from ray_tpu.chaos.schedule import FaultSchedule, FaultSpec

    mgr = FleetManager(_spec(models=(ModelSpec("tiny", replicas=2),)),
                       engine_config=_cfg(), seed=7, thresholds=LOOSE)
    sched = chaos.install(FaultSchedule(13, [
        FaultSpec(chaos.PREEMPT_ENGINE, site="llm.engine.step",
                  start_after=6, every_n=25, max_fires=2),
    ]))
    try:
        new = _perturbed(mgr.replicas("tiny")[0].engine.params)
        mgr.weights.begin_canary("tiny", params=new)

        def one(i):
            t = mgr.submit("gold", "tiny", PROMPT + [i],
                           SamplingParams(max_tokens=8, temperature=0.0))
            return mgr.collect(t, timeout_s=180)

        n = 8
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            outs = list(ex.map(one, range(n)))
        assert chaos.PREEMPT_ENGINE in sched.fired_kinds()
        assert len(outs) == n  # zero lost
        assert all(o.finished and len(o.output_token_ids) > 0 for o in outs)
        assert sum(r.runner.num_recoveries
                   for r in mgr.replicas("tiny")) >= 1
        rep = mgr.weights.promote()
        assert rep["outcome"] == "promoted"
        assert all(bitwise_equal(r.engine.params, new)
                   for r in mgr.replicas("tiny"))
    finally:
        chaos.uninstall()
        mgr.close()


# ---------------------------------------------------------------------------
# pool targets (the autoscale surface)
# ---------------------------------------------------------------------------


def test_set_pool_target_and_actuator():
    from ray_tpu.autoscale import FleetPoolActuator
    from ray_tpu.autoscale.policy import Decision

    mgr = FleetManager(_spec(models=(ModelSpec("tiny", replicas=1),)),
                       engine_config=_cfg(), seed=7, thresholds=LOOSE)
    try:
        act = FleetPoolActuator(mgr)
        assert act.pool_state()["tiny"]["replicas_running"] == 1
        act.apply(Decision(pool="tiny", action="scale_up", target=3,
                           reason="test"))
        assert len(mgr.replicas("tiny")) == 3
        # scale-up replicas joined the weight plane: a base publish
        # reaches all three and a late publish_base converges them
        new = _perturbed(mgr.replicas("tiny")[0].engine.params)
        mgr.weights.publish_base("tiny", new)
        assert all(bitwise_equal(r.engine.params, new)
                   for r in mgr.replicas("tiny"))
        act.apply(Decision(pool="tiny", action="scale_down", target=1,
                           reason="test"))
        assert len(mgr.replicas("tiny")) == 1
        # the survivor still serves
        out = mgr.collect(mgr.submit("gold", "tiny", PROMPT, GREEDY),
                          timeout_s=120)
        assert out.finished
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# capture gates (tier-1): the checked-in r21 benchmark results
# ---------------------------------------------------------------------------


def _load_capture(name):
    path = os.path.join(REPO, "benchmarks", name)
    assert os.path.exists(path), f"{name} capture missing"
    with open(path) as f:
        return json.load(f)


def test_fleet_capture_gate_isolation():
    """ACCEPTANCE: under the same batch-tenant flood, the paying tenant
    grades GREEN with QoS isolation and RED without it."""
    cap = _load_capture("FLEET_serving_r21.json")
    assert cap["bench"] == "fleet_serving"
    nn = cap["noisy_neighbor"]
    assert nn["isolated"]["paying_grade"] == "green", nn
    assert nn["no_isolation"]["paying_grade"] == "red", nn
    assert nn["isolated"]["batch_shed"] >= 1
    assert nn["isolated"]["priority_preemptions"] >= 1


def test_fleet_capture_gate_goodput():
    """ACCEPTANCE: multiplexed fleet goodput >= static partitioning on
    the same skewed two-adapter workload."""
    cap = _load_capture("FLEET_serving_r21.json")
    gp = cap["goodput"]
    assert gp["fleet_completed"] >= gp["static_completed"], gp
    assert gp["fleet_goodput_rps"] >= gp["static_goodput_rps"], gp


def test_fleet_capture_gate_canary():
    """ACCEPTANCE: the canary rollout promoted bitwise-identically, the
    red canary rolled back bitwise-identically, and the seeded
    mid-canary engine preemption lost zero requests."""
    cap = _load_capture("FLEET_serving_r21.json")
    can = cap["canary"]
    assert can["promote"]["grade"] == "green"
    assert can["promote"]["bitwise_identical"] is True
    assert can["rollback"]["grade"] == "red"
    assert can["rollback"]["bitwise_identical"] is True
    assert can["requests_lost"] == 0
    assert can["preemptions_fired"] >= 1
    assert len(can["timeline"]) >= 4

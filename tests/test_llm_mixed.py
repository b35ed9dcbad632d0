"""Mixed ragged batching: ops/ragged kernel tier + llm/mixed planner +
the engine's unified prefill+decode dispatch (EngineConfig.mixed_batch).

The correctness contract everywhere is BITWISE token identity vs the
split engine (the split path is the oracle and stays in the tree);
kernel numerics are checked against a dense per-row reference, with the
Pallas kernel exercised under interpret on CPU.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.llm.mixed import MixedBatchPlan, token_bucket
from ray_tpu.llm.sampling import SamplingParams
from ray_tpu.models import llama

FP32_TINY = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.mixed


# ---------------------------------------------------------------------------
# ragged kernel numerics
# ---------------------------------------------------------------------------


def _dense_ragged_ref(q, k_cache, v_cache, bt, cu, ctx, bs):
    """Per-row dense oracle: row t of sequence b sits at absolute
    position ctx[b] - q_len_b + (t - cu[b]) and attends kv positions
    <= its own AND < ctx[b]."""
    T, H, D = q.shape
    KVH = k_cache.shape[0]
    G = H // KVH
    B = len(ctx)
    out = np.zeros((T, H, D), np.float32)
    for b in range(B):
        q_len = int(cu[b + 1] - cu[b])
        for i in range(q_len):
            t = int(cu[b]) + i
            q_pos = int(ctx[b]) - q_len + i
            n = q_pos + 1
            slots = [
                int(bt[b, p // bs]) * bs + p % bs for p in range(n)
            ]
            k = np.asarray(k_cache)[:, slots]
            v = np.asarray(v_cache)[:, slots]
            for h in range(H):
                kvh = h // G
                s = (np.asarray(q)[t, h] @ k[kvh].T) / np.sqrt(D)
                p_ = np.exp(s - s.max())
                p_ /= p_.sum()
                out[t, h] = p_ @ v[kvh]
    return out


def _ragged_case(rng, q_lens, ctx_lens, bs=4, MB=8):
    H, KVH, D = 8, 2, 16
    B = len(q_lens)
    T = sum(q_lens)
    num_slots = 64 * bs
    q = jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
    k_cache = jnp.asarray(rng.normal(size=(KVH, num_slots, D)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(KVH, num_slots, D)), jnp.float32)
    bt = jnp.asarray(
        rng.choice(64, size=(B, MB), replace=False), jnp.int32
    )
    cu = np.zeros(B + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    return q, k_cache, v_cache, bt, jnp.asarray(cu), jnp.asarray(
        np.asarray(ctx_lens, np.int32))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_ragged_attention_matches_dense(impl):
    """Packed variable-length rows (a prefill chunk, decode rows, a
    mid-prompt chunk) against the dense per-row oracle."""
    from ray_tpu.ops.ragged import ragged_attention

    rng = np.random.default_rng(0)
    q_lens = [5, 1, 1, 3]
    ctx_lens = [5, 20, 13, 9]  # row 3: chunk ending mid-prompt history
    q, kc, vc, bt, cu, ctx = _ragged_case(rng, q_lens, ctx_lens)
    ref = _dense_ragged_ref(q, kc, vc, bt, np.asarray(cu),
                            np.asarray(ctx), 4)
    got = np.asarray(ragged_attention(
        q, kc, vc, bt, cu, ctx, block_size=4, max_q_len=8, impl=impl
    ))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_ragged_decode_only_degenerate_matches_paged(impl):
    """All q_len = 1 is the decode batch: ragged must agree with the
    rectangular paged_attention kernel on the same cache."""
    from ray_tpu.ops.paged_attention import paged_attention
    from ray_tpu.ops.ragged import ragged_attention

    rng = np.random.default_rng(1)
    q_lens = [1, 1, 1]
    ctx_lens = [7, 20, 13]
    q, kc, vc, bt, cu, ctx = _ragged_case(rng, q_lens, ctx_lens)
    got = np.asarray(ragged_attention(
        q, kc, vc, bt, cu, ctx, block_size=4, max_q_len=4, impl=impl
    ))
    ref = np.asarray(paged_attention(
        q, kc, vc, bt, ctx, block_size=4, impl="xla"
    ))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_ragged_pallas_interpret_matches_xla_packed():
    """The two impls on an identical packed mixed batch — the CPU
    stand-in for the TPU kernel's parity gate."""
    from ray_tpu.ops.ragged import ragged_attention

    rng = np.random.default_rng(2)
    q_lens = [6, 1, 4, 1, 1]
    ctx_lens = [6, 17, 11, 9, 25]
    q, kc, vc, bt, cu, ctx = _ragged_case(rng, q_lens, ctx_lens)
    a = np.asarray(ragged_attention(
        q, kc, vc, bt, cu, ctx, block_size=4, max_q_len=8, impl="xla"
    ))
    b = np.asarray(ragged_attention(
        q, kc, vc, bt, cu, ctx, block_size=4, max_q_len=8,
        impl="pallas_interpret"
    ))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_token_bucket_shapes():
    assert token_bucket(1) == 16
    assert token_bucket(16) == 16
    assert token_bucket(17) == 32
    assert token_bucket(100) == 128


# ---------------------------------------------------------------------------
# engine: split-vs-mixed bitwise identity
# ---------------------------------------------------------------------------


def _engine(mixed, chunk=8, **kw):
    cfg = EngineConfig(
        model=FP32_TINY, num_blocks=128, block_size=4, max_num_seqs=8,
        max_prefill_len=64, mixed_batch=mixed, mixed_prefill_chunk=chunk,
        **kw,
    )
    return LLMEngine(cfg, seed=0)


def _prompts():
    rng = np.random.default_rng(7)
    return [
        rng.integers(3, 500, size=int(n)).tolist()
        for n in [5, 37, 9, 52, 14, 23]
    ]


@pytest.mark.parametrize("attn_impl", ["auto", "pallas_interpret"])
def test_mixed_greedy_token_identical(attn_impl):
    """Chunked long prompts + short prompts through the ragged dispatch
    must be BITWISE identical to the split engine — also with the Pallas
    ragged kernel inside the engine's mixed step (pad sequences of
    q_len 0 included, which no standalone kernel test packs)."""
    prompts = _prompts()
    sp = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    ref = _engine(False).generate(prompts, sp)
    eng = _engine(True, attn_impl=attn_impl)
    assert eng.generate(prompts, sp) == ref
    st = eng.stats()["mixed"]
    assert st["dispatches"] > 0 and st["prefill_tokens"] > 0
    assert st["decode_tokens"] > 0  # decode rows rode prefill dispatches
    assert eng.allocator.num_free == 128  # KV fully returned


def test_mixed_seeded_sampling_token_identical():
    """Sampled streams key on fold_in(request key, output index), so
    scheduling differences (split vs packed) must not shift them."""
    prompts = _prompts()
    sp = SamplingParams(max_tokens=12, temperature=0.9, top_k=5, seed=42,
                        ignore_eos=True)
    assert _engine(True).generate(prompts, sp) == \
        _engine(False).generate(prompts, sp)


def test_mixed_stop_mid_chunk_identical():
    """Requests stopping (stop-token / max_tokens) while another prompt
    is mid-chunk: membership churn inside the mixed window."""
    prompts = _prompts()
    ref_eng, mix_eng = _engine(False), _engine(True, chunk=6)
    outs = {}
    for eng in (ref_eng, mix_eng):
        for i, p in enumerate(prompts):
            sp = SamplingParams(
                max_tokens=4 + 3 * i, temperature=0.0,
                stop_token_ids=(17,), ignore_eos=False,
            )
            eng.add_request(p, sp, request_id=f"s{i}")
        got = {}
        while eng.has_unfinished():
            for o in eng.step():
                if o.finished:
                    got[o.request_id] = list(o.output_token_ids)
        outs[eng is mix_eng] = got
    assert outs[True] == outs[False]


def test_mixed_lora_rows_identical():
    """Per-token adapter ids through the packed dispatch: mixed-adapter
    batches must match the split engine's per-sequence selection."""

    def mk(seed):
        m = FP32_TINY
        rng = np.random.RandomState(seed)
        r = 4
        return {
            "wq": ((rng.randn(m.n_layers, m.d_model, r) * 0.5).astype(
                np.float32),
                (rng.randn(m.n_layers, r, m.n_heads * m.head_dim) * 0.5
                 ).astype(np.float32)),
        }

    prompts = _prompts()[:4]
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    outs = {}
    for mixed in (False, True):
        eng = _engine(mixed, max_loras=2, lora_rank=4)
        eng.add_lora("A", mk(1))
        eng.add_lora("B", mk(2))
        for i, p in enumerate(prompts):
            eng.add_request(p, sp, request_id=f"l{i}",
                            lora_id=[None, "A", "B", "A"][i])
        got = {}
        while eng.has_unfinished():
            for o in eng.step():
                if o.finished:
                    got[o.request_id] = list(o.output_token_ids)
        outs[mixed] = got
    assert outs[True] == outs[False]


def test_mixed_spec_decode_identical():
    """verify_tokens through the ragged packed verifier (no trash-slot
    pad-column buckets) must keep spec decode token-identical and the
    acceptance stats live."""
    from ray_tpu.llm.spec import Drafter, SpecConfig

    class _Oracle(Drafter):
        """Proposes the true continuation — maximal acceptance, so the
        ragged verifier's accept path is exercised, not just rollback."""

        def __init__(self, table):
            self.table = {tuple(p): list(o) for p, o in table}

        def propose(self, request_id, tokens, k):
            for p, o in self.table.items():
                n = len(p)
                if tuple(tokens[:n]) == p:
                    done = len(tokens) - n
                    return o[done:done + k]
            return []

    rng = np.random.default_rng(3)
    pat = rng.integers(3, 200, size=5).tolist()
    prompts = [pat * 4, rng.integers(3, 500, size=9).tolist(), pat * 3]
    sp = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
    ref = _engine(False).generate(prompts, sp)
    eng = _engine(True, spec=SpecConfig(num_draft_tokens=4))
    eng.drafter = _Oracle(list(zip(prompts, ref)))
    assert eng.generate(prompts, sp) == ref
    st = eng.stats()["spec"]
    assert st["accepted_tokens"] > 0 and st["acceptance_rate"] > 0.9


# ---------------------------------------------------------------------------
# engine: dispatch structure
# ---------------------------------------------------------------------------


def test_one_dispatch_serves_prefills_and_decode_rows():
    """ACCEPTANCE: >= 2 in-flight prefills and >= 4 decode rows advance
    in ONE ragged dispatch."""
    eng = _engine(True, chunk=4)
    rng = np.random.default_rng(11)
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    for i in range(4):
        eng.add_request(rng.integers(3, 500, size=5).tolist(), sp,
                        request_id=f"d{i}")
    eng.step()  # admission happens inside step()
    while eng._mixed_prefills:
        eng.step()
    assert len(eng.running) == 4  # the decode batch
    before = {r.request_id: len(r.output_token_ids) for r in eng.running}
    d0 = eng.stats()["mixed"]["dispatches"]
    for j in range(2):
        eng.add_request(rng.integers(3, 500, size=16).tolist(), sp,
                        request_id=f"p{j}")
    eng.step()
    # both prompts were admitted mid-prefill (chunk 4 < 16) into the
    # SAME dispatch, and every decode row advanced one token in it
    assert len(eng._mixed_prefills) == 2
    assert eng.stats()["mixed"]["dispatches"] == d0 + 1
    for r in eng.running:
        if r.request_id in before:
            assert len(r.output_token_ids) == before[r.request_id] + 1


def test_chunked_prefill_never_starves_decode():
    """While a long prompt streams through chunked mixed dispatches,
    every decode row gains exactly one token per engine step."""
    eng = _engine(True, chunk=4)
    rng = np.random.default_rng(12)
    sp = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True)
    for i in range(3):
        eng.add_request(rng.integers(3, 500, size=4).tolist(), sp,
                        request_id=f"d{i}")
    eng.step()  # admission happens inside step()
    while eng._mixed_prefills:
        eng.step()
    eng.add_request(rng.integers(3, 500, size=40).tolist(), sp,
                    request_id="long")
    saw_mid_prefill_steps = 0
    while True:
        before = {r.request_id: len(r.output_token_ids)
                  for r in eng.running if r.request_id != "long"}
        eng.step()
        if not eng._mixed_prefills:
            break
        saw_mid_prefill_steps += 1
        for r in eng.running:
            if r.request_id in before:
                assert len(r.output_token_ids) == \
                    before[r.request_id] + 1, "decode starved by prefill"
    # chunk=4 over a 40-token prompt: the window is real, not one step
    assert saw_mid_prefill_steps >= 5


def test_decode_only_routes_to_existing_ladder():
    """With no prefill cursors, mixed mode is the degenerate case and
    must not pay ragged dispatches for pure decode."""
    eng = _engine(True)
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    eng.add_request([5, 9, 17, 3], sp, request_id="a")
    eng.step()  # admission + whole-prompt chunk
    assert not eng._mixed_prefills
    d0 = eng.stats()["mixed"]["dispatches"]
    while eng.has_unfinished():
        eng.step()
    assert eng.stats()["mixed"]["dispatches"] == d0


def test_mixed_plan_shapes_and_trash_slots():
    """Planner invariants: cu monotone, pad tokens target the trash
    slot, T_pad a token_bucket, per-row chunks bounded by the budget."""
    eng = _engine(True, chunk=4)
    rng = np.random.default_rng(13)
    sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    for i in range(2):
        eng.add_request(rng.integers(3, 500, size=5).tolist(), sp,
                        request_id=f"d{i}")
    eng.step()  # admission happens inside step()
    while eng._mixed_prefills:
        eng.step()
    eng.add_request(rng.integers(3, 500, size=11).tolist(), sp,
                    request_id="p0")
    eng._mixed_admit()  # pull the long prompt in without dispatching
    plan = MixedBatchPlan.build(eng)
    assert plan.T == sum(plan.chunk_lens)
    assert len(plan.tokens) == token_bucket(plan.T)
    assert all(cl <= 4 for k, cl in zip(plan.kinds, plan.chunk_lens)
               if k == "prefill")
    cu = np.asarray(plan.cu_q_lens)
    assert (np.diff(cu) >= 0).all() and cu[-1] == plan.T
    trash = eng.config.num_blocks * eng.config.block_size
    assert (np.asarray(plan.slots)[plan.T:] == trash).all()


# ---------------------------------------------------------------------------
# engine: faults, recovery, disagg
# ---------------------------------------------------------------------------


def test_preempt_mid_mixed_batch_recovers_identical():
    """PREEMPT_ENGINE fired mid-mixed-window (chaos harness), recover(),
    finish — token streams must match a clean split run."""
    from ray_tpu import chaos

    prompts = _prompts()
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    ref = _engine(False).generate(prompts, sp)

    eng = _engine(True, chunk=6)
    sched = chaos.install(chaos.FaultSchedule(5, [
        chaos.FaultSpec(chaos.PREEMPT_ENGINE, site="llm.engine.step",
                        start_after=2, max_fires=1),
    ]))
    try:
        for i, p in enumerate(prompts):
            eng.add_request(p, sp, request_id=f"c{i}")
        got = {}
        while eng.has_unfinished():
            try:
                outs = eng.step()
            except chaos.EnginePreempted:
                eng.recover()
                assert not eng._mixed_prefills  # cursors died with batch
                continue
            for o in outs:
                if o.finished:
                    got[o.request_id] = list(o.output_token_ids)
    finally:
        chaos.uninstall()
    assert chaos.PREEMPT_ENGINE in sched.fired_kinds()
    assert [got[f"c{i}"] for i in range(len(prompts))] == ref


def test_export_mid_mixed_prefill_raises():
    """A request whose prompt is still streaming through mixed chunks
    has no complete KV to hand off."""
    eng = _engine(True, chunk=4)
    sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    eng.add_request(list(range(3, 23)), sp, request_id="x")
    eng.step()
    assert "x" in eng._mixed_prefills
    with pytest.raises(ValueError, match="mid-prefill"):
        eng.export_request("x")


def test_import_handoff_joins_live_mixed_batch():
    """A disagg handoff imported while a mixed window is in flight joins
    the decode rows of subsequent dispatches; its stream matches the
    colocated split engine."""
    prompts = _prompts()
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    ref = _engine(False).generate([prompts[0]], sp)[0]

    pre = _engine(False)
    pre.add_request(prompts[0], sp, request_id="h")
    pre.step()
    h = pre.export_request("h")

    dec = _engine(True, chunk=4)
    dec.add_request(prompts[3], sp, request_id="bg")  # 52 tokens, chunk 4
    dec.step()
    assert dec._mixed_prefills  # a live mixed window
    rid = dec.import_handoff(h)
    got = {}
    while dec.has_unfinished():
        for o in dec.step():
            if o.finished:
                got[o.request_id] = list(o.output_token_ids)
    assert got[rid] == ref


# ---------------------------------------------------------------------------
# checked-in capture gate
# ---------------------------------------------------------------------------


def test_checked_in_mixed_capture_gate():
    """Tier-1 gate on the checked-in A/B capture: mixed dispatch must
    not lose throughput vs the split baseline (median of interleaved
    trials) and token identity must hold in the capture. Regenerate
    with `python benchmarks/llm_serving_bench.py --mixed`."""
    path = os.path.join(REPO, "benchmarks", "MIXED_serving_r24.json")
    assert os.path.exists(path), "missing checked-in MIXED_serving_r24.json"
    doc = json.loads(open(path).read())
    assert doc["token_identical"] is True
    assert doc["value"] >= 1.0, (
        "mixed dispatch lost throughput vs split in the checked-in "
        f"capture: {doc['value']} < 1.0"
    )
    assert doc["mixed_stats"]["dispatches"] > 0
    assert doc["mixed_stats"]["decode_tokens"] > 0
    assert 0.0 <= doc["padding_waste_ratio"] <= 1.0

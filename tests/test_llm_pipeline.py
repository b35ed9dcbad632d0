"""Pipelined decode tests (ray_tpu.llm.pipeline).

Contracts under test:
 * TOKEN IDENTITY: the pipelined path (device-resident state, on-device
   stop masks, double-buffered dispatch, adaptive chunks) produces
   bitwise-identical token streams to the sync path — greedy and seeded
   sampling, mixed per-row knobs, stop tokens firing mid-chunk, LoRA
   rows, preemption under cache pressure, crash recovery mid-pipeline,
   and a disagg import_handoff joining a live pipelined batch;
 * the all-done early-out: a batch that fully finishes at step 1 of a
   16-step chunk does not pay the other 15 device steps;
 * the adaptive ChunkController is deterministic under a fixed gap
   trace and only ever emits bounded CHUNK_BUCKETS values (the
   (n_steps, mode) jit cache assert enforces the same bound);
 * observability: host-prep/sync-wait histograms record, engine stats
   expose the `pipeline` row, and the checked-in bench capture keeps
   pipelined tok/s >= sync.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.llm.sampling import SamplingParams
from ray_tpu.models import llama

pytestmark = pytest.mark.pipeline

FP32_TINY = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(pipelined: bool, *, num_blocks=64, seed=0, **kw):
    kw.setdefault("model", FP32_TINY)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_prefill_len", 64)
    cfg = EngineConfig(num_blocks=num_blocks, pipeline_decode=pipelined, **kw)
    return LLMEngine(cfg, seed=seed)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(3, 500, size=n))) for n in (7, 12, 5)]


# ---------------------------------------------------------------------------
# bitwise token identity vs the sync path
# ---------------------------------------------------------------------------


def test_pipelined_greedy_identity(prompts):
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    sync = _engine(False).generate(prompts, sp)
    eng = _engine(True)
    pipe = eng.generate(prompts, sp)
    assert pipe == sync
    # all KV blocks drain back after the pipelined run too
    assert eng.allocator.num_free == eng.config.num_blocks
    assert eng.stats()["pipeline"]["dispatches"] > 0


def test_pipelined_seeded_mixed_knobs_identity(prompts):
    """Per-row knobs (seeded temperature / top-k / top-p / greedy) in
    ONE batch: every row's stream must be chunk-partitioning invariant
    and batch-mate independent, pipelined or not."""
    sps = [
        SamplingParams(max_tokens=15, temperature=1.0, seed=7, ignore_eos=True),
        SamplingParams(max_tokens=9, temperature=0.8, top_k=5, seed=3,
                       ignore_eos=True),
        SamplingParams(max_tokens=12, temperature=1.2, top_p=0.9, seed=11,
                       ignore_eos=True),
    ]
    assert _engine(True).generate(prompts, sps) == \
        _engine(False).generate(prompts, sps)
    # and against a different starting chunk length
    assert _engine(True, decode_chunk=2).generate(prompts, sps) == \
        _engine(False, decode_chunk=1).generate(prompts, sps)


def test_pipelined_stop_token_mid_chunk():
    """A stop id firing mid-chunk truncates at exactly the same token
    the sync path's host ladder keeps (the on-device mask fires, the
    per-row n_emitted caps the host walk)."""
    p = [5, 6, 7]
    sp = SamplingParams(max_tokens=30, temperature=1.0, seed=42, ignore_eos=True)
    ref = _engine(False).generate([p], sp)[0]
    stop_tok = ref[3]
    sp_stop = SamplingParams(
        max_tokens=30, temperature=1.0, seed=42, ignore_eos=True,
        stop_token_ids=(stop_tok,),
    )
    got = _engine(True).generate([p], sp_stop)[0]
    assert got == ref[:4] and got[-1] == stop_tok


def test_a_requests_key_is_the_same_in_every_process():
    """A seeded request samples from its seed and its id, and the id's
    share is a digest that does not differ by process: `hash(rid)` does
    (PYTHONHASHSEED), which made the reference of the case above another
    sequence in every run, one that sometimes held the stop token before
    its fourth place (ROADMAP D8's flaky list, PR 54)."""
    import zlib

    eng = _engine(False)
    rid = eng.add_request([5, 6, 7], SamplingParams(max_tokens=1, seed=42), request_id="req-a")
    want = jax.random.fold_in(jax.random.key(42), zlib.crc32(b"req-a") & 0x7FFFFFFF)
    assert (jax.random.key_data(eng.requests[rid]._key) == jax.random.key_data(want)).all()


def test_pipelined_eos_and_max_tokens_terminations(prompts):
    """Natural EOS stops (ignore_eos=False) and max_tokens walls land
    identically; finish_reason survives the pipelined bookkeeping."""
    sp = SamplingParams(max_tokens=40, temperature=1.0, seed=5)
    assert _engine(True).generate(prompts, sp) == \
        _engine(False).generate(prompts, sp)

    def reasons(pipelined):
        eng = _engine(pipelined)
        rids = [eng.add_request(p, sp) for p in prompts]
        out = {}
        while eng.has_unfinished():
            for o in eng.step():
                if o.finished:
                    out[o.request_id] = o.finish_reason
        return [out[r] for r in rids]

    assert reasons(True) == reasons(False)


def test_pipelined_wide_stop_set_falls_back_to_sync(prompts):
    """A request with more stop ids than the padded on-device matrix
    holds must still serve (sync fallback), with identical tokens."""
    from ray_tpu.llm.pipeline import STOP_WIDTH_CAP

    sp = SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True,
        stop_token_ids=tuple(range(1000, 1000 + STOP_WIDTH_CAP + 3)),
    )
    eng = _engine(True)
    assert eng.generate(prompts, sp) == _engine(False).generate(prompts, sp)
    stats = eng.stats().get("pipeline")
    assert stats is None or stats["sync_fallbacks"] > 0 or \
        stats["dispatches"] == 0


def test_pipelined_lora_rows_identity():
    """Mixed-adapter batches (per-row LoRA ids ride the device state)
    decode identically pipelined vs sync."""
    def cfg(pipelined):
        return EngineConfig(
            model=FP32_TINY, num_blocks=64, max_num_seqs=4,
            max_loras=2, lora_rank=4, pipeline_decode=pipelined,
        )

    m = FP32_TINY
    rng = np.random.RandomState(3)
    mk = lambda *s: (rng.randn(*s) * 0.5).astype(np.float32)  # noqa: E731
    adapters = {
        "wq": (mk(m.n_layers, m.d_model, 4),
               mk(m.n_layers, 4, m.n_heads * m.head_dim)),
        "wv": (mk(m.n_layers, m.d_model, 4),
               mk(m.n_layers, 4, m.n_kv_heads * m.head_dim)),
    }
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)

    def run(pipelined):
        eng = LLMEngine(cfg(pipelined), seed=7)
        eng.add_lora("styleA", {k: (np.array(a), np.array(b))
                                for k, (a, b) in adapters.items()})
        rids = [
            eng.add_request([5, 9, 17, 3], sp, lora_id=lid)
            for lid in (None, "styleA", None)
        ]
        out = {}
        while eng.has_unfinished():
            for o in eng.step():
                if o.finished:
                    out[o.request_id] = tuple(o.output_token_ids)
        return [out[r] for r in rids]

    got = run(True)
    assert got == run(False)
    assert got[0] != got[1]  # the adapter actually changed row 1


def test_pipelined_preemption_identity():
    """Cache pressure mid-pipeline: the flush-then-preempt ladder keeps
    greedy determinism (preemption-by-recompute contract)."""
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(3, 500, size=10))) for _ in range(3)]
    sp = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
    small = _engine(True, num_blocks=10)
    outs = small.generate(prompts, sp)
    assert small.num_preemptions > 0
    assert small.allocator.num_free == 10
    assert outs == _engine(False, num_blocks=64).generate(prompts, sp)


def test_pipelined_recover_mid_pipeline(prompts):
    """recover() while a chunk is in flight: the un-synced chunk is
    dropped (its tokens were never booked), re-admission recomputes the
    delivered prefix, and the final streams still match sync."""
    sp = SamplingParams(max_tokens=14, temperature=0.0, ignore_eos=True)
    eng = _engine(True)
    rids = [eng.add_request(p, sp) for p in prompts]
    for _ in range(3):  # admission + cold-start dispatch (+ one sync)
        eng.step()
    assert eng._pipe_inflight is not None
    moved = eng.recover()
    assert eng._pipe_inflight is None and eng._pipe_state is None
    assert set(moved) == set(rids)
    out = {}
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished:
                out[o.request_id] = o.output_token_ids
    ref = _engine(False).generate(prompts, sp)
    assert [out[r] for r in rids] == ref


def test_import_handoff_joins_live_pipelined_batch():
    """Disagg: a handoff imported while the decode engine has a live
    pipelined batch in flight — the import flushes the chunk, joins the
    batch, and both the resident rows and the import decode exactly
    their sync-path streams."""
    params = llama.init_params(FP32_TINY, jax.random.key(0))
    rng = np.random.default_rng(4)
    p_res = list(map(int, rng.integers(3, 120, size=9)))
    p_hand = list(map(int, rng.integers(3, 120, size=13)))
    sp = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)

    def run(pipelined):
        cfgkw = dict(model=FP32_TINY, num_blocks=64, block_size=8,
                     max_num_seqs=4, max_prefill_len=64)
        dec = LLMEngine(EngineConfig(pipeline_decode=pipelined, **cfgkw),
                        params=params, seed=0)
        pre = LLMEngine(EngineConfig(pipeline_decode=pipelined, **cfgkw),
                        params=params, seed=0)
        out = {}

        def drain(outputs):
            for o in outputs:
                if o.finished:
                    out[o.request_id] = o.output_token_ids

        rid_res = dec.add_request(p_res, sp)
        for _ in range(4):  # prefill + a few pipelined decode rounds
            drain(dec.step())
        pre.add_request(p_hand, sp, request_id="hand-1")
        pre.step()
        h = pre.export_request("hand-1")
        rid_h = dec.import_handoff(h)
        while dec.has_unfinished():
            drain(dec.step())
        assert dec.num_prefill_batches <= 1  # the import never re-prefilled
        return out[rid_res], out[rid_h]

    assert run(True) == run(False)


def test_admission_precheck_honors_live_shared_prefix():
    """The admission precheck must discount LIVE-shared prefix-cache
    blocks (adopted by refcount, zero free-pool cost): a waiting
    request sharing a running request's sealed prefix admits even when
    the free pool can't cover its whole prompt."""
    from ray_tpu.llm.kv_cache import BlockAllocator

    # allocator-level: live-shared matches cost nothing, zero-ref
    # cached matches still consume a free slot
    a = BlockAllocator(num_blocks=8, block_size=2)
    blocks = a.allocate(2)
    h1 = a.chain_hash(0, (10, 11))
    h2 = a.chain_hash(h1, (12, 13))
    a.register_full_block(blocks[0], h1)
    a.register_full_block(blocks[1], h2)
    toks = [10, 11, 12, 13, 14]  # 3 blocks total, 2 cached
    assert a.probe_admission_need(toks) == 1   # live-shared: refs held
    a.free(blocks)                             # now zero-ref cached
    assert a.probe_admission_need(toks) == 3   # resurrection costs slots
    assert a.probe_admission_need([99, 98, 97]) == 2  # no match

    # engine-level: A runs a long generation holding the shared prefix;
    # B (same prefix + suffix) must admit although
    # blocks_needed(B) > num_free
    shared = list(range(100, 116))  # 16 tokens = 4 blocks at bs=4
    eng = _engine(True, num_blocks=9)
    sp_a = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    rid_a = eng.add_request(shared, sp_a)
    eng.step()  # admit A (prefill seals the shared blocks, refs held)
    eng.step()  # first decode round reserves A's chunk blocks
    rid_b = eng.add_request(
        shared + [7, 8], SamplingParams(max_tokens=2, temperature=0.0,
                                        ignore_eos=True))
    assert eng.allocator.blocks_needed(len(shared) + 2) > \
        eng.allocator.num_free  # a cache-blind precheck would starve B
    b_admitted_while_a_live = False
    for _ in range(30):
        outs = eng.step()
        if any(o.request_id == rid_b and o.new_token_ids for o in outs):
            b_admitted_while_a_live = rid_a in eng.requests
            break
    assert b_admitted_while_a_live, (
        "prefix-sharing request starved at admission until its "
        "prefix-holder finished"
    )
    eng.abort_request(rid_a)


def test_abort_flush_cannot_strand_batchmate_finish():
    """abort_request's internal flush may finish a BATCH-MATE and empty
    the running set; its finish event rides _pending_outputs, and
    has_unfinished() must stay true until a step() delivers it —
    otherwise every driver loop (they all gate step() on the predicate)
    strands the completed request's final tokens forever."""
    sp_a = SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True)
    sp_b = SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True)
    eng = _engine(True, decode_chunk=2)
    rid_a = eng.add_request([5, 6, 7], sp_a)
    rid_b = eng.add_request([9, 10, 11], sp_b)
    # admit + dispatch until a chunk is in flight, stopping before B's
    # tiny budget has been DELIVERED (it may already be done on device)
    while eng._pipe_inflight is None and eng.has_unfinished():
        eng.step()
    eng.abort_request(rid_a)
    if eng._pending_outputs:
        assert eng.has_unfinished(), (
            "pending flush outputs but has_unfinished() is False: "
            "drivers would never call step() again"
        )
    seen = {}
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished:
                seen[o.request_id] = o.output_token_ids
    if rid_b in seen:  # B finished (not aborted mid-flight): full budget
        assert len(seen[rid_b]) == 3
    assert not eng._pending_outputs


# ---------------------------------------------------------------------------
# early exit + bounded jit cache + controller determinism
# ---------------------------------------------------------------------------


def test_all_done_early_exit_skips_device_steps():
    """A batch that fully finishes at step 1 of a 16-step chunk must
    not pay the other 15: the while_loop's measured steps_run is the
    proof (steps_saved_by_early_exit in the stats row).

    Stop TOKENS (not max_tokens) force the early finish so the
    remaining-token budget can't quantize the chunk down first: every
    row keeps a 20-token budget, a 16-step chunk dispatches, and each
    row's first decoded token is its stop id."""
    prompts = [[5, 6, 7], [9, 10, 11]]
    ref = _engine(False).generate(
        prompts, SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    )
    sps = [
        SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True,
                       stop_token_ids=(ref[i][1],))
        for i in range(2)
    ]
    eng = _engine(True, decode_chunk=16)
    outs = eng.generate(prompts, sps)
    assert [len(o) for o in outs] == [2, 2]  # stopped at decode step 1
    st = eng.stats()["pipeline"]
    assert st["steps_dispatched"] >= 16  # a full-size chunk was dispatched
    # the whole run decodes 1 kept token per row: the while_loop must
    # have exited almost immediately, never paying the 15 masked steps
    assert st["steps_executed"] <= 4, st
    assert st["steps_saved_by_early_exit"] >= 12, st


def test_jit_cache_bounded_to_chunk_buckets():
    from ray_tpu.llm.pipeline import CHUNK_BUCKETS

    eng = _engine(True)
    with pytest.raises(AssertionError, match="bucket"):
        eng._decode_chunk_fn(3, "greedy")
    with pytest.raises(AssertionError, match="bucket"):
        eng._pipe_chunk_fn(CHUNK_BUCKETS[-1] * 2, "greedy", 1)
    with pytest.raises(AssertionError, match="stop width"):
        eng._pipe_chunk_fn(8, "greedy", 3)
    # config-level clamp: an oversized decode_chunk lands on a bucket
    cfg = EngineConfig(model=FP32_TINY, decode_chunk=4096)
    assert cfg.decode_chunk == CHUNK_BUCKETS[-1]


def test_chunk_controller_deterministic_and_bounded():
    from ray_tpu.llm.pipeline import CHUNK_BUCKETS, ChunkController

    def replay(trace):
        ctl = ChunkController(initial=8)
        picks = []
        for gap, sync, chunk_ms, steps_run in trace:
            n = ctl.next_steps()
            ctl.note_overhead(gap + sync)
            ctl.note_chunk(chunk_ms, n, steps_run)
            picks.append(n)
        return picks

    # a host-bound trace: huge host overhead, cheap chunks
    # -> the controller ratchets UP (and deterministically)
    trace_up = [(70.0, 30.0, 40.0, 8)] * 6
    picks = replay(trace_up)
    assert picks == replay(trace_up)  # fixed trace => fixed decisions
    assert all(p in CHUNK_BUCKETS for p in picks)
    assert picks[-1] > picks[0]

    # device-bound trace with systematic early exit -> ratchets DOWN
    ctl = ChunkController(initial=16)
    downs = []
    for _ in range(6):
        n = ctl.next_steps()
        ctl.note_overhead(0.1)
        ctl.note_chunk(50.0, n, steps_run=2)
        downs.append(n)
    assert downs[-1] < downs[0]
    assert all(p in CHUNK_BUCKETS for p in downs)

    # the remaining-budget cap quantizes, never exceeds a bucket
    ctl2 = ChunkController(initial=64)
    assert ctl2.next_steps(cap=3) == 4
    assert ctl2.next_steps(cap=200) == 64


# ---------------------------------------------------------------------------
# observability + the checked-in capture gate
# ---------------------------------------------------------------------------


def test_host_split_layer_spans_and_stats_row():
    """The host/device split of a pipelined round, always on: the
    engine.decode_dispatch and engine.sync layer spans (what the
    llm_decode_host_prep_ms / llm_decode_sync_wait_ms histograms timed
    behind EngineConfig.profile), the engine's counters, the stats row."""
    from ray_tpu import obs

    before = obs.layer_counters()
    eng = _engine(True, decode_chunk=4)
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    eng.generate([[1, 2, 3, 4]], sp)
    after = obs.layer_counters()
    n = eng.counters()
    for name in ("engine.decode_dispatch", "engine.sync"):
        rounds = after[name]["count"] - before.get(name, {"count": 0})["count"]
        assert rounds >= n["dispatches"]["pipe_chunk"] >= 2, name
        assert after[name]["busy_s"] > before.get(name, {"busy_s": 0.0})["busy_s"]
    assert n["decode_tokens"] == 7 and n["decode_steps"] >= 7
    row = eng.stats()["pipeline"]
    assert row["dispatches"] == n["dispatches"]["pipe_chunk"]
    assert {"chunks_by_steps", "overlap_ratio", "host_prep_ms",
            "sync_wait_ms", "steps_saved_by_early_exit"} <= set(row)
    assert 0.0 <= row["overlap_ratio"] <= 1.0


def test_layer_span_export_is_metrics_instrumented():
    """The layer counters that took over from the pipeline's histograms
    are under the live-registry lint."""
    from ray_tpu.analysis.metrics_registry import INSTRUMENTED

    assert ("ray_tpu.obs.recorder", "register_metrics") in INSTRUMENTED
    assert ("ray_tpu.llm.pipeline", "register_metrics") not in INSTRUMENTED


def test_checked_in_pipeline_capture_gate():
    """Tier-1 gate on the checked-in A/B capture: the pipelined path
    must not lose throughput vs sync on the CPU capture, and the
    correctness contract (token identity) must hold in the capture.
    Regenerate with `python benchmarks/llm_serving_bench.py --pipeline`."""
    path = os.path.join(REPO, "benchmarks", "PIPELINE_decode_r16.json")
    assert os.path.exists(path), "missing checked-in PIPELINE_decode_r16.json"
    doc = json.loads(open(path).read())
    assert doc["token_identical"] is True
    assert doc["pipelined"]["tok_s"] >= doc["sync"]["tok_s"], (
        "pipelined decode lost throughput vs sync in the checked-in "
        f"capture: {doc['pipelined']['tok_s']} < {doc['sync']['tok_s']}"
    )
    assert doc["pipeline"]["dispatches"] > 0
    assert 0.0 <= doc["pipeline"]["overlap_ratio"] <= 1.0

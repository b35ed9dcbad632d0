"""Continuous-batching LLM engine.

The reference's engine is vLLM behind a Ray actor
(python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py);
this one is native and TPU-shaped:

 * static-shape buckets everywhere (prefill lengths, decode batch
   sizes) so XLA compiles a handful of programs once and the MXU sees
   fixed tiles — the TPU analog of CUDA-graph capture;
 * paged KV cache (llm/kv_cache.py) with prefix reuse;
 * scheduler: admit-prefill-then-decode with preemption by recompute,
   the vLLM v0 policy shape, host-side and O(batch);
 * sampling as one jitted vectorized program (llm/sampling.py).

Engine API mirrors vLLM's LLMEngine (add_request / step / generate) so
the serving layer (llm/openai_api.py) and batch processor sit on top
unchanged.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
import zlib
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu import obs
from ray_tpu.chaos import harness as _chaos
from ray_tpu.llm.kv_cache import (
    BlockAllocator,
    NoFreeBlocksError,
    SequenceBlocks,
)
from ray_tpu.llm.sampling import SamplingParams, sample_tokens
from ray_tpu.models import llama
from ray_tpu.models.llama_decode import decode_step, init_cache, prefill
from ray_tpu.obs import context as trace_context
from ray_tpu.obs import recorder as trace_recorder
from ray_tpu.utils.logging import get_logger

logger = get_logger("ray_tpu.llm.engine")


def _named(name: str, fn):
    """``fn`` under a stable name: jax.jit names the compiled module
    after the function (``jit_llm_prefill``), which is what the
    device's line of a profiler trace and the compile log show — a
    lambda would read ``jit__lambda`` for every class of program."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def prefix_cache_hit_counter():
    """Prompt tokens served from the prefix cache instead of recomputed,
    split by the TIER that held them (hbm = resident paged cache,
    host / object = resurrected by llm/kvtier with zero recompute).
    Alongside the lookup counter it gives the fleet-level hit rate the
    disaggregated decode pick consumes (llm/disagg/orchestrator.py);
    the tier label is the `== kv tiers ==` mix `ray_tpu status` shows."""
    from ray_tpu.util.metrics import Counter

    return Counter(
        "llm_prefix_cache_hit_tokens_total",
        description="prompt tokens whose KV was reused from the prefix "
        "cache at prefill admission (no recompute), by serving tier "
        "(hbm/host/object)",
        tag_keys=("model", "tier"),
    )


def prefix_cache_lookup_counter():
    from ray_tpu.util.metrics import Counter

    return Counter(
        "llm_prefix_cache_lookup_tokens_total",
        description="prompt tokens considered for prefix-cache reuse at "
        "prefill admission (hit_tokens / lookup_tokens = hit rate)",
        tag_keys=("model",),
    )


def preemption_counter():
    """Requests kicked out of a running batch, attributable per tenant:
    `reason` separates KV-pressure preemptions (pressure), priority
    preemptions where a paying tenant displaced a batch tenant
    (priority), and crash-recovery re-enqueues (recover). The tenant
    label is what makes a fleet's noisy-neighbor story auditable — the
    batch tenant's preempt rate should rise while the paying tenant's
    stays flat."""
    from ray_tpu.obs.telemetry import cluster_counter

    return cluster_counter(
        "llm_preemptions_total",
        description="requests preempted out of the decode batch, by "
        "model, tenant, and reason (pressure/priority/recover)",
        tag_keys=("model", "tenant", "reason"),
    )


def utilization_gauges() -> dict:
    """Per-engine utilization gauges for the cluster telemetry plane
    (obs/telemetry.py): the fleet view the SLO-driven autoscaler sizes
    pools from. All aggregate by SUM across engines/replicas."""
    from ray_tpu.obs.telemetry import cluster_gauge

    return {
        "kv_pages_used": cluster_gauge(
            "llm_kv_pages_used",
            description="paged-KV blocks currently allocated in this "
            "engine (used = total - free)",
            tag_keys=("model",),
        ),
        "kv_pages_total": cluster_gauge(
            "llm_kv_pages_total",
            description="paged-KV blocks this engine was configured with",
            tag_keys=("model",),
        ),
        "kv_hbm_bytes": cluster_gauge(
            "llm_kv_hbm_bytes",
            description="bytes of accelerator memory held by this "
            "engine's paged KV cache (static allocation)",
            tag_keys=("model",),
        ),
        "queue_depth": cluster_gauge(
            "llm_queue_depth",
            description="requests waiting for prefill admission in this "
            "engine",
            tag_keys=("model",),
        ),
        "running": cluster_gauge(
            "llm_running_requests",
            description="requests in this engine's decode batch",
            tag_keys=("model",),
        ),
    }


def register_metrics() -> None:
    """scripts/check_metrics.py hook: force lazy metrics to register."""
    prefix_cache_hit_counter()
    prefix_cache_lookup_counter()
    preemption_counter()
    utilization_gauges()


class AdapterSlotsExhausted(ValueError):
    """Every LoRA adapter slot is loaded and none can be evicted (all
    referenced by in-flight requests, or eviction was not requested).
    Subclasses ValueError so pre-r21 callers matching on the generic
    add_lora failure keep working; fleet routing catches THIS type to
    fall back to another replica instead of treating it as a bad
    request."""


@dataclasses.dataclass
class EngineConfig:
    model: llama.LlamaConfig = dataclasses.field(default_factory=lambda: llama.LLAMA_TINY)
    num_blocks: int = 512
    block_size: int = 16
    max_num_seqs: int = 16          # decode batch ceiling
    max_prefill_len: int = 1024     # longest admitted prompt suffix
    attn_impl: str = "auto"
    cache_dtype: Any = None          # default: model dtype
    enable_prefix_caching: bool = True
    eos_token_id: int = 2
    # tensor-parallel serving: a MeshSpec (e.g. MeshSpec(tp=2)) shards
    # weights Megatron-style and the paged KV cache across its kv-head
    # dim; XLA inserts the TP collectives (reference: vLLM TP degree ->
    # placement group, vllm_models.py:117-131 — here it's one SPMD
    # program over the mesh, no worker gang)
    mesh_spec: Any = None
    # LoRA multiplexing: serve up to max_loras adapters from ONE engine
    # with mixed-adapter continuous batching — every sequence in a decode
    # batch may use a different adapter (reference: per-replica adapter
    # load/unload, llm/_internal/serve/deployments/llm/multiplex/)
    max_loras: int = 0
    lora_rank: int = 8
    lora_targets: tuple = ("wq", "wv")
    # multi-step decode: run up to this many decode+sample iterations ON
    # DEVICE per host round-trip (llm/decode_loop.py). 1 = classic
    # one-sync-per-token stepping. Chunks shrink automatically near a
    # request's max_tokens/max_seq; EOS overshoot is discarded host-side.
    # With pipeline_decode this is only the adaptive controller's
    # STARTING chunk; measured host-gap/device-step times take over.
    decode_chunk: int = 8
    # pipelined decode (llm/pipeline.py): batch state lives on device
    # across chunks, stop conditions evaluate in-graph (finished rows
    # freeze + all-done early-out), and chunk N+1 dispatches before
    # chunk N's tokens are synced so host bookkeeping overlaps device
    # compute; chunk length adapts to the measured host gap. Token
    # streams are bitwise-identical to the sync path. False keeps the
    # classic sync path (also taken automatically for batches with
    # > pipeline.STOP_WIDTH_CAP stop ids, and by spec decoding, which
    # has its own round structure).
    pipeline_decode: bool = True
    # speculative decoding (llm/spec/): a SpecConfig turns each decode
    # round into draft -> one batched verify pass (k+1 tokens per row
    # through the paged prefill path) -> distribution-preserving
    # accept/resample. Rows whose drafter proposes nothing degenerate to
    # a plain decode step inside the same program; if NO row has a
    # draft, the round falls back to the classic decode/chunk path.
    spec: Any = None
    # tiered prefix cache (llm/kvtier): sealed full blocks evicted from
    # the HBM allocator spill to a host-DRAM LRU and then the object
    # store instead of being discarded, and prefill admission resurrects
    # them with a verified scatter (zero recompute). True / a dict / a
    # KVTierConfig enables it; None keeps the HBM-only cache.
    kvtier: Any = None
    # mixed ragged batching (llm/mixed.py over ops/ragged.py): pack
    # in-flight prefill chunks AND the running decode batch into ONE
    # ragged dispatch per step instead of separate prefill/decode
    # programs — prompts stream mixed_prefill_chunk tokens/step so a
    # long prefill never stalls decode rows. Token streams stay bitwise
    # identical to the split path (retained as the identity oracle);
    # spec verify also routes through the packed ragged program,
    # deleting the rectangular verify's per-row pad-column waste.
    mixed_batch: bool = False
    mixed_prefill_chunk: int = 256

    def __post_init__(self):
        if isinstance(self.model, str):
            # registry name ("llama3-8b", "mistral-7b", ...) — the vLLM
            # model-id ergonomics (models/registry.py)
            from ray_tpu.models.registry import get_model_config

            self.model = get_model_config(self.model)
        from ray_tpu.models.moe import MoEConfig

        if hasattr(self.model, "residual_multiplier") and hasattr(self.model, "mamba_heads"):
            raise ValueError(
                "LLMEngine serves llama-family models with a key-value cache; Granite 4.0-H's "
                "Mamba-2 layers carry a state-space state and a convolution's last taps a "
                "sequence (models/granite_hybrid.py), a second kind of state beside the pages, "
                "which no cache manager here holds (continuous batching over that state is "
                "not built): it is training-only")
        if hasattr(self.model, "mamba_heads"):
            raise ValueError(
                "LLMEngine serves llama-family models with a key-value cache; Nemotron-H's "
                "Mamba-2 layers carry a state-space state and a convolution's last taps a "
                "sequence (models/nemotron_h.py), a second kind of state beside the pages, "
                "which no cache manager here holds, and its experts are training-only: it is "
                "training-only (and the published model's block-diffusion decode is not built)")
        if hasattr(self.model, "kda_heads") and hasattr(self.model, "kv_lora_rank"):
            raise ValueError(
                "LLMEngine serves llama-family models with a key-value cache; Kimi-Linear's "
                "Kimi-Delta-Attention layers carry a recurrent state a head and a convolution's "
                "last taps a sequence and its latent-attention layers a latent cache "
                "(models/kimi_linear.py): two kinds of state in one manager, which is not built, "
                "and its experts are training-only: it is training-only")
        if hasattr(self.model, "kda_heads"):
            raise ValueError(
                "LLMEngine serves llama-family models with a key-value cache; Solar-Open2's "
                "Kimi-Delta-Attention layers carry a recurrent state a head and a convolution's "
                "last taps a sequence (models/solar_open2.py), a second kind of state beside "
                "the pages, which no cache manager here holds, and its experts are "
                "training-only: it is training-only")
        if isinstance(self.model, MoEConfig):
            # the serving decoder is the dense llama path; accepting a
            # MoEConfig (a LlamaConfig subclass) would silently serve a
            # dense model with the experts' hyperparameters
            raise ValueError(
                "LLMEngine serves dense llama-family models; MoE serving "
                "is not implemented (training-side MoE lives in models/moe.py; "
                "Mixtral, OLMoE, ZAYA1, whose compressed convolutional attention "
                "has no cache here either, GLM-4.7-Flash, whose latent attention "
                "would be served in its absorbed form, Laguna and Mellum2, whose sliding-window "
                "layers want a cache sized by layer type, and Keye, whose indexer wants a "
                "cache of its own keys and a selection in the ragged kernel, and SDAR, which "
                "decodes a block of positions in several denoising steps and no token at a "
                "time, are training-only)"
            )
        if hasattr(self.model, "linear_key_dim"):
            raise ValueError(
                "LLMEngine serves llama-family models with a key-value cache; Olmo-Hybrid's "
                "linear-attention layers carry a recurrent state a head (models/olmo_hybrid.py), "
                "a second kind of state beside the pages, which no cache manager here holds: "
                "it is training-only")
        # a prefill bucket longer than the context window can never be
        # used; clamping keeps bucket compilation bounded by the model
        self.max_prefill_len = min(self.max_prefill_len, self.model.max_seq)
        # chunk lengths compile per value: clamp to the bounded bucket
        # set so the jit cache can never grow past it
        from ray_tpu.llm.pipeline import CHUNK_BUCKETS

        self.decode_chunk = min(self.decode_chunk, CHUNK_BUCKETS[-1])
        # the ragged kernel's static max_q_len compiles per value: one
        # clamped budget keeps the mixed program count at exactly one
        self.mixed_prefill_chunk = max(
            1, min(self.mixed_prefill_chunk, self.max_prefill_len)
        )
        if self.spec is not None:
            from ray_tpu.llm.spec import SpecConfig

            if isinstance(self.spec, dict):
                self.spec = SpecConfig(**self.spec)
            if not isinstance(self.spec, SpecConfig):
                raise ValueError(
                    f"EngineConfig.spec must be a SpecConfig, got {type(self.spec)}"
                )
        if self.kvtier is not None:
            from ray_tpu.llm.kvtier import KVTierConfig

            if self.kvtier is True:
                self.kvtier = KVTierConfig()
            elif isinstance(self.kvtier, dict):
                self.kvtier = KVTierConfig(**self.kvtier)
            if not isinstance(self.kvtier, KVTierConfig):
                raise ValueError(
                    f"EngineConfig.kvtier must be a KVTierConfig, True, or a "
                    f"dict, got {type(self.kvtier)}"
                )

    def prefill_buckets(self) -> list[int]:
        out, b = [], 16
        while b < self.max_prefill_len:
            out.append(b)
            b *= 2
        out.append(self.max_prefill_len)
        return out

    def decode_buckets(self) -> list[int]:
        out, b = [], 1
        while b < self.max_num_seqs:
            out.append(b)
            b *= 2
        out.append(self.max_num_seqs)
        return out

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.model.max_seq // self.block_size)

    def bt_widths(self) -> list[int]:
        """Every block-table width LLMEngine._bt_width can return: powers
        of two from the floor (16, or the model's maximum if that is
        smaller) up to the maximum itself."""
        top = self.max_blocks_per_seq
        out, w = [], min(16, top)
        while w < top:
            out.append(w)
            w *= 2
        return out + [top]


class RequestStatus:
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"
    ABORTED = "aborted"
    # exported to another engine via a KV handoff (disaggregated
    # prefill/decode); this engine no longer owns the request
    MIGRATED = "migrated"


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_token_ids: list
    sampling_params: SamplingParams
    output_token_ids: list = dataclasses.field(default_factory=list)
    status: str = RequestStatus.WAITING
    seq: Optional[SequenceBlocks] = None
    arrival: float = dataclasses.field(default_factory=time.time)
    finish_reason: Optional[str] = None
    num_preemptions: int = 0
    cumulative_logprob: float = 0.0
    token_logprobs: list = dataclasses.field(default_factory=list)
    lora_slot: int = 0
    # multi-tenant QoS (ray_tpu.fleet): higher priority admits first and
    # may preempt lower-priority running requests; tenant labels the
    # preempt/shed counters; slo_tag (when set) records this request's
    # SLO observations under an EXTRA series beyond the engine's
    # model_tag — the fleet grades canary replicas and tenants from it
    priority: int = 0
    tenant: str = ""
    slo_tag: Optional[str] = None
    _key: Any = None
    # request tracing (ray_tpu.obs): the submitter's TraceContext; every
    # lifecycle span below records as its child. Timestamps: queue_start
    # resets on preemption (each wait is its own queue_wait span);
    # first_prefill/first_token survive preemption (they ARE the SLOs);
    # span_cursor tiles decode-round spans so per-request phase spans
    # cover arrival -> finish without gaps (scheduler gaps land inside a
    # round span and are priced by its sched_gap_ms attr, not hidden)
    trace: Any = None
    t_queue_start: float = 0.0
    t_first_prefill: Optional[float] = None
    t_prefill_start: Optional[float] = None
    t_first_token: Optional[float] = None
    t_span_cursor: Optional[float] = None
    _prefill_cached: int = 0
    # seconds the request spent in front of the engine before
    # add_request (the serving runner's lock): `arrival` starts the
    # engine's clock, this is what a client's TTFT has on top of it
    pre_engine_wait_s: float = 0.0

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    new_token_ids: list
    output_token_ids: list
    finished: bool
    finish_reason: Optional[str] = None
    num_cached_tokens: int = 0


class LLMEngine:
    def __init__(
        self,
        config: EngineConfig,
        params: Optional[llama.Params] = None,
        seed: int = 0,
    ):
        self.config = config
        c = config
        self.allocator = BlockAllocator(c.num_blocks, c.block_size)
        self.mesh = None
        param_shardings = None
        if c.mesh_spec is not None:
            from ray_tpu.parallel.mesh import make_mesh
            from ray_tpu.parallel.sharding import default_rules, tree_shardings

            self.mesh = make_mesh(c.mesh_spec)
            tp = self.mesh.shape["tp"]
            if c.model.n_kv_heads % max(tp, 1) != 0:
                raise ValueError(
                    f"n_kv_heads={c.model.n_kv_heads} not divisible by tp={tp}"
                )
            if c.attn_impl == "pallas" and self.mesh.size > 1:
                # the TPU compiler refuses to partition a Mosaic kernel,
                # and the paged/ragged kernels have no shard_map wrapper
                # (ops/attention.py has one for flash): fail here, not
                # at the first decode step's compile
                raise ValueError(
                    "attn_impl='pallas' cannot run under a multi-device "
                    "mesh_spec yet; serve tensor-parallel with 'auto'/'xla'"
                )
            param_shardings = tree_shardings(
                self.mesh, default_rules(), llama.logical_axes(c.model)
            )
        if params is None:
            def llm_init_params():
                return llama.init_params(c.model, jax.random.key(seed))

            # under a mesh the tree is born sharded (the pattern of
            # train/step.init_sharded_params): a model that only fits
            # spread over the chips must never exist whole on one first
            params = (
                llm_init_params() if param_shardings is None
                else jax.jit(llm_init_params, out_shardings=param_shardings)()
            )
        elif param_shardings is not None:
            params = jax.device_put(params, param_shardings)
        self.params = params
        self.cache = self._init_kv_cache()
        # static KV allocation size for the llm_kv_hbm_bytes gauge
        # (nbytes is array metadata; no device sync)
        self._kv_cache_nbytes = int(sum(
            getattr(x, "nbytes", 0) for x in jax.tree.leaves(self.cache)
        ))
        self._telemetry_next = 0.0  # gauge-refresh throttle
        self.waiting: deque[Request] = deque()
        self.running: list[Request] = []
        self.requests: dict[str, Request] = {}  # unfinished only
        self.num_preemptions = 0
        self._counter = itertools.count()
        self._root_key = jax.random.key(seed ^ 0x5EED)
        # serving SLO label (llm_ttft_seconds{model=...}); the OpenAI app
        # stamps its model_id here after construction
        self.model_tag = "engine"
        # weight-sync plane (train/weight_sync.py): the version of the
        # last applied publish — 0 until a subscriber swaps params.
        # Surfaced via stats()/GET /v1/stats so actor/learner skew in an
        # RL post-training deployment is observable from one RPC.
        self.weight_version = 0

        # LoRA adapter stacks: slot 0 is the zero adapter ("no lora");
        # per-target A [L, n_slots, d_in, r], B [L, n_slots, r, d_out]
        self._lora_slots: dict[str, int] = {}
        # lora_id -> last time a request selected it (monotonic): the
        # LRU order evict_lru_lora / add_lora(evict=True) walk when the
        # slot budget is exhausted
        self._lora_last_used: dict[str, float] = {}
        self._lora = None
        if c.max_loras > 0:
            m = c.model
            n = c.max_loras + 1
            out_dims = {
                "wq": m.n_heads * m.head_dim,
                "wk": m.n_kv_heads * m.head_dim,
                "wv": m.n_kv_heads * m.head_dim,
            }
            stacks = {}
            for t in c.lora_targets:
                stacks[f"{t}_A"] = jnp.zeros(
                    (m.n_layers, n, m.d_model, c.lora_rank), m.dtype
                )
                stacks[f"{t}_B"] = jnp.zeros(
                    (m.n_layers, n, c.lora_rank, out_dims[t]), m.dtype
                )
            self._lora = stacks

        # jitted entry points; cache buffers are donated so XLA updates pages
        # in place instead of copying the whole cache every step
        def llm_prefill(params, t, p, sl, sm, bt, cl, cache, lora):
            return prefill(
                params, t, p, sl, sm, bt, cl, cache, c.model,
                block_size=c.block_size, lora=lora,
            )

        def llm_decode(params, t, p, sm, bt, cl, cache, lora):
            return decode_step(
                params, t, p, sm, bt, cl, cache, c.model,
                block_size=c.block_size, attn_impl=c.attn_impl, lora=lora,
            )

        self._prefill = jax.jit(llm_prefill, donate_argnums=(7,))
        self._decode = jax.jit(llm_decode, donate_argnums=(6,))
        # always-on counters (counters()): plain ints written by the
        # engine's own thread and read by anyone without a lock.
        # _seen_programs: (class, static arguments and padded shapes)
        # of every program dispatched so far — a new one is a compile
        # or a cache load
        self._n = {
            "decode_steps": 0, "decode_row_steps": 0, "decode_tokens": 0,
            "prefill_tokens": 0, "prefill_cached_tokens": 0,
            "dispatches": {}, "first_calls": {},
        }
        self._seen_programs: set = set()
        self._step_kind = "idle"
        self._decode_chunks: dict[tuple, Any] = {}  # (n_steps, mode) -> jitted
        # disaggregated serving: jitted KV-page scatter per padded width
        # (import_handoff), and prefix-cache accounting for stats()/the
        # decode-replica pick (hit/lookup in TOKENS, not blocks)
        self._kv_imports: dict[int, Any] = {}
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        # hit tokens split by serving tier (hbm resident vs host/object
        # resurrected) — the per-tier view stats()/metrics expose
        self.tier_hit_tokens: dict[str, int] = {}
        self.num_prefill_batches = 0
        self.num_kv_imports = 0

        # tiered prefix cache (llm/kvtier): listens to the allocator's
        # seal/evict/drop events, owns the host-DRAM + object-store
        # tiers, and publishes this engine's resident chains to the
        # cluster prefix index when one is attached
        self.kvtier = None
        self.kvfetch = None
        if c.kvtier is not None:
            from ray_tpu.llm.kvtier import KVTierManager

            self.kvtier = KVTierManager(self, c.kvtier)
            # prefetch-at-admission + cross-engine pulls (llm/kvfetch):
            # the worker verifies/deserializes/fetches a queued
            # request's prefix while it waits; step()'s tick scatters
            # it into HBM before the request reaches the queue head
            from ray_tpu.llm.kvfetch import KVFetchManager

            self.kvfetch = KVFetchManager(self)

        # pipelined decode (llm/pipeline.py): device-resident batch
        # state, the in-flight double-buffered chunk, the adaptive chunk
        # controller, and outputs produced by internal flushes (returned
        # by the next step() so no token/finish event is ever dropped)
        self._pipe_state = None
        self._pipe_inflight = None
        self._pipe_ctl = None
        self._pipe_stats = None
        self._pipe_last_sync_t = None
        self._pending_outputs: list[RequestOutput] = []

        # speculative decoding: drafter + verify program cache + stats
        self.drafter = None
        self.spec_stats = None
        self._verify_fns: dict[int, Any] = {}  # suffix width K+1 -> jitted
        if c.spec is not None:
            from ray_tpu.llm.spec.stats import SpecStats

            self.drafter = c.spec.build_drafter(c.model)
            self.spec_stats = SpecStats()

        # mixed ragged batching (llm/mixed.py): prefill cursors
        # (request_id -> next un-prefilled absolute token index — a
        # request in here is RUNNING but mid-prompt), the ONE jitted
        # ragged dispatch, the lazily-built ragged spec verifier, and
        # padding-waste stats. The cursor dict exists unconditionally so
        # the preempt/abort/recover hooks never need a mode check.
        self._mixed_prefills: dict[str, int] = {}
        self._mixed_fn = None
        self._mixed_stats = None
        self._verify_ragged = None
        if c.mixed_batch:
            from ray_tpu.llm.mixed import MixedStats
            from ray_tpu.models.llama_decode import mixed_step

            maxq = c.mixed_prefill_chunk

            def llm_mixed(params, t, p, sl, bt, cu, cl, cache, lora):
                return mixed_step(
                    params, t, p, sl, bt, cu, cl, cache, c.model,
                    block_size=c.block_size, max_q_len=maxq,
                    attn_impl=c.attn_impl, lora=lora,
                )

            self._mixed_fn = jax.jit(llm_mixed, donate_argnums=(7,))
            self._mixed_stats = MixedStats()

    def _init_kv_cache(self):
        """Fresh paged KV cache with the engine's sharding (also the
        crash-recovery rebuild path: recover(rebuild_kv=True))."""
        c = self.config

        def llm_init_kv():
            return init_cache(
                c.model, c.num_blocks * c.block_size, dtype=c.cache_dtype,
                trash_slots=c.block_size,
            )

        if self.mesh is None:
            return llm_init_kv()
        from jax.sharding import NamedSharding, PartitionSpec as P

        # cache [L, kv_heads, slots, hd]: heads across tp, allocated
        # under jit so each chip only ever holds its own heads
        kv_sharding = NamedSharding(self.mesh, P(None, "tp", None, None))
        return jax.jit(llm_init_kv, out_shardings=kv_sharding)()

    @staticmethod
    def _assert_chunk_bucket(n_steps: int) -> None:
        """The (n_steps, mode) jit caches are bounded BY CONSTRUCTION to
        the adaptive bucket set — a novel n_steps would silently compile
        (and retain) a new program forever."""
        from ray_tpu.llm.pipeline import CHUNK_BUCKETS

        assert n_steps in CHUNK_BUCKETS, (
            f"decode chunk n_steps={n_steps} outside the bounded bucket "
            f"set {CHUNK_BUCKETS}; quantize via pipeline.chunk_bucket"
        )

    def _decode_chunk_fn(self, n_steps: int, sample_mode: str = "full"):
        c = self.config
        self._assert_chunk_bucket(n_steps)
        fn = self._decode_chunks.get((n_steps, sample_mode))
        if fn is None:
            from ray_tpu.llm.decode_loop import decode_chunk

            def chunk(params, t, p, bt, cl, cache, temps, tks, tps, keys,
                      starts, remaining, lora):
                return decode_chunk(
                    params, t, p, bt, cl, cache, temps, tks, tps, keys,
                    starts, remaining,
                    c.model, n_steps=n_steps, block_size=c.block_size,
                    trash_slot=c.num_blocks * c.block_size,
                    attn_impl=c.attn_impl, sample_mode=sample_mode, lora=lora,
                )

            fn = jax.jit(
                _named(f"llm_decode_chunk_n{n_steps}_{sample_mode}", chunk),
                donate_argnums=(5,),
            )
            self._decode_chunks[(n_steps, sample_mode)] = fn
        return fn

    def _pipe_chunk_fn(self, n_steps: int, sample_mode: str, stop_w: int):
        """Jitted masked/early-exiting chunk (llm/pipeline.py) for the
        pipelined path; cache keyed (and bounded) by the chunk-bucket +
        stop-width sets."""
        c = self.config
        self._assert_chunk_bucket(n_steps)
        from ray_tpu.llm.pipeline import STOP_WIDTHS, decode_chunk_masked

        assert stop_w in STOP_WIDTHS, (
            f"stop width {stop_w} outside the bounded set {STOP_WIDTHS}"
        )
        key = (n_steps, sample_mode, "masked", stop_w)
        fn = self._decode_chunks.get(key)
        if fn is None:
            def chunk(params, t, p, bt, cl, cache, temps, tks, tps, keys,
                      starts, max_toks, done, stop_ids, stop_on_eos, lora):
                return decode_chunk_masked(
                    params, t, p, bt, cl, cache, temps, tks, tps, keys,
                    starts, max_toks, done, stop_ids, stop_on_eos,
                    c.model, n_steps=n_steps, block_size=c.block_size,
                    trash_slot=c.num_blocks * c.block_size,
                    eos_id=c.eos_token_id, attn_impl=c.attn_impl,
                    sample_mode=sample_mode, lora=lora,
                )

            fn = jax.jit(
                _named(f"llm_pipe_chunk_n{n_steps}_w{stop_w}_{sample_mode}", chunk),
                donate_argnums=(5,),
            )
            self._decode_chunks[key] = fn
        return fn

    def _verify_fn(self, width: int):
        """Jitted spec verifier for a [B_pad, width] suffix (width = k+1,
        a compile-time bucket like decode_buckets)."""
        c = self.config
        fn = self._verify_fns.get(width)
        if fn is None:
            from ray_tpu.models.llama_decode import verify_tokens

            def verify(params, t, p, sm, bt, cl, cache, lora):
                return verify_tokens(
                    params, t, p, sm, bt, cl, cache, c.model,
                    block_size=c.block_size, lora=lora,
                )

            fn = jax.jit(_named(f"llm_verify_w{width}", verify),
                         donate_argnums=(6,))
            self._verify_fns[width] = fn
        return fn

    def _verify_ragged_fn(self):
        """Jitted PACKED spec verifier (llama_decode.verify_tokens_ragged):
        rows carry exactly 1 + draft_len tokens instead of a [B, K+1]
        rectangle — jax.jit re-specializes per packed-token bucket, so
        one entry covers every (T_pad, B_pad) shape."""
        if self._verify_ragged is None:
            c = self.config
            from ray_tpu.models.llama_decode import verify_tokens_ragged

            maxq = c.spec.num_draft_tokens + 1

            def llm_verify_ragged(params, t, p, sl, bt, cu, cl, gi, cache, lora):
                return verify_tokens_ragged(
                    params, t, p, sl, bt, cu, cl, gi, cache, c.model,
                    block_size=c.block_size, max_q_len=maxq,
                    attn_impl=c.attn_impl, lora=lora,
                )

            self._verify_ragged = jax.jit(llm_verify_ragged, donate_argnums=(8,))
        return self._verify_ragged

    @staticmethod
    def _sample_mode(batch) -> str:
        """STATIC sampler fast path for this batch (llm.sampling): the
        full top-k/top-p machinery costs a per-step lax.top_k; greedy
        and plain-temperature batches skip it entirely. A request with
        top_k > TOP_CAP forces the exact full-vocab sort — the capped
        path would silently clamp it (ADVICE r05).

        Per-row greedy short-circuit: top-k/top-p cannot change an
        argmax (the most-likely token always survives both filters), so
        a greedy request's knobs are IGNORED when deriving the mode —
        clients routinely send temperature=0 together with top_k/top_p,
        and before this, one such request dragged the whole batch onto a
        sort path nobody sampled from."""
        sampled = [r for r in batch if not r.sampling_params.greedy]
        if not sampled:
            return "greedy"
        if all(
            r.sampling_params.top_k <= 0 and r.sampling_params.top_p >= 1.0
            for r in sampled
        ):
            return "categorical"
        if any(r.sampling_params.needs_full_sort for r in sampled):
            return "full_sort"
        return "full"

    # -- LoRA multiplexing ----------------------------------------------------

    def add_lora(self, lora_id: str, adapters: dict,
                 evict: bool = False) -> None:
        """Register an adapter: {"wq": (A [L,d,r], B [L,r,out]), ...} for
        the configured lora_targets. Requests select it by lora_id.

        With ``evict`` a full slot budget evicts the least-recently-used
        resident adapter first (refusing any with in-flight requests);
        without it — or when nothing is evictable — raises
        :class:`AdapterSlotsExhausted`."""
        c = self.config
        if c.max_loras <= 0:
            raise ValueError("EngineConfig.max_loras is 0: LoRA disabled")
        if lora_id in self._lora_slots:
            raise ValueError(f"lora {lora_id!r} already loaded")
        if len(self._lora_slots) >= c.max_loras:
            if not evict or not self.evict_lru_lora():
                raise AdapterSlotsExhausted(
                    f"all {c.max_loras} adapter slots in use"
                )
        # validate EVERYTHING before mutating: a partial write would leave
        # stale weights in a slot still marked free
        for t, (A, B) in adapters.items():
            if t not in c.lora_targets:
                raise ValueError(
                    f"adapter target {t!r} not in lora_targets={c.lora_targets}"
                )
            want_a = self._lora[f"{t}_A"].shape[0:1] + self._lora[f"{t}_A"].shape[2:]
            want_b = self._lora[f"{t}_B"].shape[0:1] + self._lora[f"{t}_B"].shape[2:]
            if tuple(np.shape(A)) != want_a or tuple(np.shape(B)) != want_b:
                raise ValueError(
                    f"adapter {t!r} shapes {np.shape(A)}/{np.shape(B)} != "
                    f"expected {want_a}/{want_b}"
                )
        used = set(self._lora_slots.values())
        slot = next(i for i in range(1, c.max_loras + 1) if i not in used)
        for t, (A, B) in adapters.items():
            self._lora[f"{t}_A"] = self._lora[f"{t}_A"].at[:, slot].set(
                jnp.asarray(A, self.config.model.dtype)
            )
            self._lora[f"{t}_B"] = self._lora[f"{t}_B"].at[:, slot].set(
                jnp.asarray(B, self.config.model.dtype)
            )
        self._lora_slots[lora_id] = slot
        self._lora_last_used[lora_id] = time.monotonic()

    def remove_lora(self, lora_id: str) -> None:
        slot = self._lora_slots.get(lora_id)
        if slot is None:
            raise ValueError(f"unknown lora {lora_id!r}")
        in_flight = [
            r.request_id for r in list(self.waiting) + self.running
            if r.lora_slot == slot
        ]
        if in_flight:
            # zeroing the slot mid-generation would silently switch those
            # sequences to the base model
            raise ValueError(
                f"lora {lora_id!r} is in use by requests {in_flight[:4]}; "
                "abort or drain them first"
            )
        self._lora_slots.pop(lora_id)
        self._lora_last_used.pop(lora_id, None)
        for k in list(self._lora):
            self._lora[k] = self._lora[k].at[:, slot].set(0.0)
        # cached prefixes salted with this slot would serve the NEXT
        # adapter assigned to it stale K/V — but only THIS slot's chains:
        # other adapters' cached prefixes (and their deep-tier copies)
        # are still correct and survive the swap
        self.allocator.drop_prefix_cache(salt=slot)

    def evict_lru_lora(self) -> Optional[str]:
        """Evict the least-recently-used resident adapter that has no
        in-flight requests referencing its slot. Returns the evicted
        lora_id, or None when every resident adapter is pinned by
        in-flight work (the caller decides whether that is
        AdapterSlotsExhausted or a retry)."""
        busy = {r.lora_slot for r in list(self.waiting) + self.running}
        candidates = sorted(
            (lid for lid, slot in self._lora_slots.items()
             if slot not in busy),
            key=lambda lid: self._lora_last_used.get(lid, 0.0),
        )
        if not candidates:
            return None
        victim = candidates[0]
        self.remove_lora(victim)
        logger.info("evicted LRU adapter %r", victim)
        return victim

    def _lora_slot(self, lora_id) -> int:
        if lora_id is None:
            return 0
        try:
            return self._lora_slots[lora_id]
        except KeyError:
            raise ValueError(f"unknown lora {lora_id!r}; add_lora first") from None

    def _lora_arg(self, ids: "np.ndarray") -> "dict | None":
        if self._lora is None:
            return None
        # stacks are [L, n_slots, ...]; the scan consumes the layer dim
        return {"ids": jnp.asarray(ids, jnp.int32), **self._lora}

    # -- public API -----------------------------------------------------------

    def add_request(
        self,
        prompt_token_ids: list,
        sampling_params: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        lora_id: Optional[str] = None,
        trace: Optional[trace_context.TraceContext] = None,
        priority: int = 0,
        tenant: str = "",
        slo_tag: Optional[str] = None,
    ) -> str:
        sp = sampling_params or SamplingParams()
        rid = request_id or f"req-{next(self._counter)}"
        lora_slot = self._lora_slot(lora_id)
        if lora_id is not None:
            self._lora_last_used[lora_id] = time.monotonic()
        if len(prompt_token_ids) > self.config.max_prefill_len:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} exceeds "
                f"max_prefill_len={self.config.max_prefill_len}"
            )
        # must leave room for >=1 generated token: a prompt of max_seq or
        # longer would overflow the block table (sized for max_seq) during
        # prefill and push RoPE positions past the table
        if len(prompt_token_ids) >= self.config.model.max_seq:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} >= model max_seq="
                f"{self.config.model.max_seq}; prompts must be shorter than "
                "the model context window"
            )
        # a prompt the cache can NEVER hold would wedge the queue head:
        # _prefill_one would return None forever while the engine spins
        need = self.allocator.blocks_needed(len(prompt_token_ids) + 1)
        if need > self.config.num_blocks:
            raise ValueError(
                f"prompt needs {need} KV blocks but the cache has only "
                f"{self.config.num_blocks}; raise num_blocks or shorten it"
            )
        req = Request(rid, list(map(int, prompt_token_ids)), sp)
        req.lora_slot = lora_slot
        req.priority = int(priority)
        req.tenant = tenant
        req.slo_tag = slo_tag
        # every request is traced: explicit ctx from the serving layer, the
        # ambient contextvar (submitter thread), or a fresh root — the
        # flight recorder is bounded, so always-on costs a dict per request
        req.trace = trace or trace_context.current() or trace_context.new_context()
        req.t_queue_start = req.arrival
        key = self._root_key if sp.seed is None else jax.random.key(sp.seed)
        req._key = jax.random.fold_in(key, zlib.crc32(str(rid).encode()) & 0x7FFFFFFF)
        self.requests[rid] = req
        self.waiting.append(req)
        if self.kvfetch is not None:
            # kick the prefix prefetch while the request waits in the
            # queue (deep-tier verify/deserialize + any remote fetch
            # happen on the worker, off the admission path)
            self.kvfetch.request_admitted(req)
        return rid

    def abort_request(self, request_id: str) -> None:
        req = self.requests.get(request_id)
        if req is None or req.status in (RequestStatus.FINISHED, RequestStatus.ABORTED):
            return
        if self.kvfetch is not None:
            # cancel/flush discipline: an abort mid-prefetch releases
            # the request's reservation refs and staged chain NOW — an
            # abort storm must leak zero blocks and zero endpoint slots
            self.kvfetch.cancel(request_id)
        if req in self.running:
            # removing a decode-batch row is a membership change: land
            # the in-flight pipelined chunk first (its outputs are
            # delivered by the next step()); the flush may finish this
            # request normally, in which case there is nothing to abort
            self._pipe_flush(deliver=True)
            if req.status in (RequestStatus.FINISHED, RequestStatus.ABORTED):
                return
        if req in self.running:
            self.running.remove(req)
        if req in self.waiting:
            self.waiting.remove(req)
        self._mixed_prefills.pop(request_id, None)
        if req.seq is not None:
            req.seq.release()
        req.status = RequestStatus.ABORTED
        req.finish_reason = "abort"
        now = time.time()
        self._obs_span(
            req, "llm.request", req.arrival, now,
            {"request_id": req.request_id, "finish_reason": "abort",
             "prompt_tokens": len(req.prompt_token_ids),
             "output_tokens": len(req.output_token_ids),
             "e2e_s": round(max(0.0, now - req.arrival), 6)},
        )
        try:
            from ray_tpu.obs import slo

            slo.record_request_slo(
                self.model_tag, ttft_s=None, tpot_s=None, queue_wait_s=None,
                e2e_s=max(0.0, now - req.arrival), finish_reason="abort",
            )
        except Exception:  # noqa: BLE001
            pass
        self.requests.pop(request_id, None)
        if self.drafter is not None:
            self.drafter.release(request_id)

    def has_unfinished(self) -> bool:
        # _pending_outputs counts: an internal pipeline flush (abort /
        # handoff) may have finished the LAST running request — its
        # finish event still needs a step() call to deliver, and every
        # driver loop gates step() on this predicate
        return bool(self.waiting or self.running or self._pending_outputs)

    def step(self) -> list[RequestOutput]:
        """One engine iteration: admit + prefill waiting requests, else decode.

        ALL admissible prefills are dispatched back-to-back and sampled
        in one batch with a single host sync, so the device queue stays
        full across the whole admission burst.

        Layer span engine.step (attrs kind = prefill | decode | mixed |
        flush | idle, rows and waiting as the step found them), with
        children engine.schedule, engine.prefill_dispatch,
        engine.decode_dispatch (engine.mixed_dispatch for the ragged
        program), engine.sync and engine.append: each idle gap of the
        device falls inside one of them."""
        with obs.layer_span("engine.step") as sp:
            sp.attrs["rows"] = len(self.running)
            sp.attrs["waiting"] = len(self.waiting)
            self._step_kind = "idle"
            outputs = self._step()
            sp.attrs["kind"] = self._step_kind
            return outputs

    def _call(self, cls: str, key: tuple, fn, *args):
        """Dispatch one engine program, counted by class; the first
        dispatch of a (class, key) — the static arguments and padded
        shapes that select the compiled program — is a compile or a
        cache load, counted under first_calls."""
        d = self._n["dispatches"]
        d[cls] = d.get(cls, 0) + 1
        if (cls, key) not in self._seen_programs:
            self._seen_programs.add((cls, key))
            f = self._n["first_calls"]
            f[cls] = f.get(cls, 0) + 1
        return fn(*args)

    def _step(self) -> list[RequestOutput]:
        if _chaos.ACTIVE is not None:
            for _f in _chaos.fire(
                "llm.engine.step",
                kinds=(_chaos.PREEMPT_ENGINE, _chaos.KILL_WORKER,
                       _chaos.DELAY_RPC),
                running=len(self.running), waiting=len(self.waiting),
            ):
                if _f.kind in (_chaos.PREEMPT_ENGINE, _chaos.KILL_WORKER):
                    # engine dies before mutating this round's state — the
                    # owner (e.g. openai_api._EngineRunner) recovers via
                    # recover() and re-enqueues in-flight requests
                    raise _chaos.EnginePreempted(
                        "chaos: engine preempted mid-step"
                    )
                if _f.kind == _chaos.DELAY_RPC:
                    # deterministic engine slowdown: overload tests build
                    # real queue depth without racing wall-clock
                    time.sleep(_f.delay_s)
        now_m = time.monotonic()
        if now_m >= self._telemetry_next:
            # throttled gauge refresh: a few dict writes per ~200ms, not
            # per decode step
            self._telemetry_next = now_m + 0.2
            self.update_telemetry_gauges()
        if self._pending_outputs:
            # outputs produced by an internal pipeline flush (abort /
            # handoff / recovery forced a sync outside step()): deliver
            # before doing anything else so no finish event is dropped
            out, self._pending_outputs = self._pending_outputs, []
            self._step_kind = "flush"
            return out
        if self.kvfetch is not None:
            # land completed prefetches BEFORE the admission check: the
            # scatter registers the blocks with reservation refs, so
            # the queue head's match_prefix finds its prefix resident
            # and _admission_need discounts the live-shared blocks
            self.kvfetch.tick()
        if self.waiting:
            with obs.layer_span("engine.schedule"):
                # QoS admission order: the highest-priority waiting request
                # is admitted first (stable — strictly FIFO when priorities
                # are uniform, i.e. every pre-fleet deployment)
                self._promote_priority()
                head = self.waiting[0]
                if head.priority > 0 and self.running and (
                    len(self.running) >= self.config.max_num_seqs
                    or self._admission_need(head) > self.allocator.num_free
                ):
                    # priority preemption: a paying tenant's request blocked
                    # on batch-slot or KV pressure displaces the lowest-
                    # priority running request (a batch tenant's decode /
                    # prefill) through the normal preempt/recover ladder —
                    # the victim recomputes, nothing is lost
                    victim = min(
                        self.running, key=lambda r: (r.priority, -r.arrival)
                    )
                    if victim.priority < head.priority:
                        flushed = self._pipe_flush()
                        if flushed:
                            return flushed
                        self._preempt_one(
                            below_priority=head.priority, reason="priority"
                        )
                        # the victim re-queued at the head: restore QoS order
                        # so the admission check below sees the paying tenant
                        self._promote_priority()
        if self.config.mixed_batch:
            # unified dispatch: admission + in-flight prefill chunks +
            # every decode row in ONE ragged program (llm/mixed.py);
            # steps with no prefill work fall through to the regular
            # decode ladder inside _mixed_step
            return self._mixed_step()
        if (
            self.waiting
            and len(self.running) < self.config.max_num_seqs
            # cheap read-only precheck: can the head of the queue
            # actually admit? Free blocks must cover its (recompute)
            # prompt MINUS live-shared prefix-cache hits, which adopt
            # by refcount and cost no free blocks. Without the check, a
            # block-starved waiting queue would flush the pipeline (and
            # force a full DeviceBatchState rebuild) every round just
            # to fail admission again; without the cache discount, a
            # prefix-sharing request would starve behind a free-pool
            # check its cache hit satisfies
            and self._admission_need(self.waiting[0])
            <= self.allocator.num_free
        ):
            # admission is a membership change: the in-flight pipelined
            # chunk (dispatched for the OLD batch) must land first
            flushed = self._pipe_flush()
            if flushed:
                return flushed
            admitted: list = []  # (req, last-token logits [1, V]) pairs
            while self.waiting and len(self.running) < self.config.max_num_seqs:
                got = self._prefill_one()
                if got is None:
                    break  # no cache room: decode to free blocks
                admitted.append(got)
            if admitted:
                self._step_kind = "prefill"
                reqs = [r for r, _ in admitted]
                with obs.layer_span("engine.sync"):
                    logits = jnp.concatenate([l for _, l in admitted], axis=0)
                    tok, logprob = self._sample_batch(logits, reqs)
                t1 = time.time()  # host sync done: first token exists
                with obs.layer_span("engine.append"):
                    outputs = self._append_tokens(reqs, tok, logprob)
                for r in reqs:
                    self._obs_span(
                        r, "engine.prefill",
                        r.t_prefill_start if r.t_prefill_start is not None else t1,
                        t1,
                        {"prompt_tokens": len(r.prompt_token_ids),
                         "cached_tokens": r._prefill_cached,
                         "recompute": r.num_preemptions > 0},
                    )
                    if r.t_first_token is None:
                        r.t_first_token = t1
                    r.t_span_cursor = t1
                self._obs_finalize(reqs, t1)
                return outputs
        if self.running:
            return self._decode_step()
        return []

    def recover(self, *, rebuild_kv: bool = False) -> list[str]:
        """Crash/preemption recovery: push every RUNNING request back to
        the head of the waiting queue with its generated prefix intact.

        Finished-prefix safety falls out of the preemption-recompute
        contract _preempt_one already honors: re-admission prefills
        ``prompt + output_token_ids``, so nothing generated is lost and
        nothing re-emits (callers see only tokens appended past the
        prefix). ``rebuild_kv=True`` additionally discards the allocator
        and KV cache (a crash of unknown provenance may have torn them);
        the prefix cache dies with them, correctness doesn't.

        Returns the re-enqueued request ids (post-mortem / logging)."""
        # the in-flight pipelined chunk may BE what crashed: drop it
        # un-synced (its tokens were never booked, so the re-admission
        # recompute covers exactly the delivered prefix)
        self._pipe_drop()
        # mid-prefill mixed cursors die with the batch: re-admission
        # recomputes each prompt from scratch (or its cached prefix)
        self._mixed_prefills.clear()
        now = time.time()
        victims = sorted(self.running, key=lambda r: r.arrival, reverse=True)
        self.running.clear()
        # orphan sweep: a crash INSIDE admission (after waiting.popleft,
        # before running.append) leaves a live request in neither deque —
        # without this it would never be stepped again and its caller
        # would hang forever
        queued = {r.request_id for r in victims} | {
            r.request_id for r in self.waiting
        }
        for r in self.requests.values():
            if (r.request_id not in queued
                    and r.status in (RequestStatus.WAITING,
                                     RequestStatus.RUNNING)):
                victims.append(r)
        if self.kvfetch is not None:
            # staged prefetch chains and reservations may reference the
            # state that just crashed: drop them (deep-tier copies stay
            # resurrectable); with rebuild_kv the block ids die with the
            # allocator and must NOT be freed into the new one
            self.kvfetch.reset(forget_blocks=rebuild_kv)
        if rebuild_kv:
            c = self.config
            self.allocator = BlockAllocator(c.num_blocks, c.block_size)
            self.cache = self._init_kv_cache()
            if self.kvtier is not None:
                # fresh allocator: re-attach the tier listeners and drop
                # the (now wrong) HBM metadata; spilled host/object
                # copies were sealed from correct pages and stay usable
                self.kvtier.rebind_allocator()
            for r in victims:
                r.seq = None  # blocks died with the old allocator
        moved = []
        for r in victims:
            if r.seq is not None:
                try:
                    r.seq.release()
                except Exception:  # noqa: BLE001 — torn allocator state
                    pass
            r.seq = None
            r.status = RequestStatus.WAITING
            r.num_preemptions += 1
            self.num_preemptions += 1
            try:
                preemption_counter().inc(
                    1, tags={"model": self.model_tag,
                             "tenant": r.tenant or "",
                             "reason": "recover"}
                )
            except Exception:  # noqa: BLE001
                pass
            r.t_queue_start = now
            r.t_span_cursor = None
            self.waiting.appendleft(r)  # reversed-arrival: oldest ends up first
            if self.drafter is not None:
                self.drafter.release(r.request_id)
            self._obs_span(r, "engine.recover", now, now,
                           {"rebuild_kv": rebuild_kv,
                            "output_tokens": len(r.output_token_ids)})
            moved.append(r.request_id)
        if moved:
            logger.warning(
                "engine recovered: re-enqueued %d in-flight request(s)%s",
                len(moved), " with fresh KV cache" if rebuild_kv else "",
            )
        return moved

    # -- disaggregated prefill/decode (ray_tpu.llm.disagg) --------------------
    # A prefill-role engine runs _prefill_one + first-token sampling, then
    # EXPORTS the sequence (KV pages + request state) instead of decoding
    # it; a decode-role engine IMPORTS it with zero recompute. The wire
    # unit is llm/disagg/handoff.KVHandoff; transports live in
    # llm/disagg/connector.py. Invariant both sides rely on: a request
    # with num_tokens N has KV written for positions 0..N-2 (the newest
    # sampled token is fed — and its KV written — by the NEXT step).

    def kv_cache_device(self):
        """The device this engine's paged KV cache lives on — the fabric
        transport endpoint for device-direct imports (registering the
        cache's own device makes the final import hop zero-copy)."""
        return next(iter(self.cache["k"].devices()))

    def peek_prefix_tokens(self, prompt_token_ids: list,
                           lora_id: Optional[str] = None) -> int:
        """Read-only probe: prompt tokens a prefix-cache hit would cover
        on THIS engine (the disagg decode pick's cache-awareness signal)."""
        return self.allocator.probe_prefix(
            list(map(int, prompt_token_ids)), self._lora_slot(lora_id)
        )

    def peek_prefix_tiered(self, prompt_token_ids: list,
                           lora_id: Optional[str] = None) -> dict:
        """Read-only TIERED probe: the longest contiguous prefix of the
        prompt this engine can serve without recompute across ALL tiers
        (HBM resident + host/object resurrectable), with the
        tier-discounted score prefix-aware routing ranks replicas by.
        Returns {"n_tokens", "discounted", "by_tier"}."""
        tokens = list(map(int, prompt_token_ids))
        salt = self._lora_slot(lora_id)
        if self.kvtier is not None:
            return self.kvtier.probe_tiers(tokens, salt)
        n = self.allocator.probe_prefix(tokens, salt)
        return {"n_tokens": n, "discounted": float(n),
                "by_tier": ({"hbm": n} if n else {})}

    def drop_prefix_cache(self, salt: Optional[int] = None) -> None:
        """Invalidate the prefix cache across EVERY tier: the HBM
        allocator's reuse pool, the host-DRAM and object-store spill
        tiers, and this engine's rows in the cluster prefix index (an
        empty snapshot ships immediately). ``salt`` scopes the drop to
        one adapter's chains (fleet canary swap) — other tenants' cached
        prefixes survive. The one entry point a weight
        swap must call — dropping HBM alone would leave deeper tiers
        serving K/V computed with the OLD weights."""
        # the allocator's drop_listener cascades into the tier manager
        self.allocator.drop_prefix_cache(salt=salt)

    def export_request(self, request_id: str, keep_on_device: bool = False):
        """Export a RUNNING request as a KVHandoff and drop local
        ownership. The request's blocks are released (full prompt blocks
        stay resurrectable in this engine's prefix cache — a re-prefill
        after a lost transfer hits them); callers transfer the handoff
        and import it on a decode engine. With ``keep_on_device`` the
        gathered pages stay device arrays (the fabric's device-direct
        path: the handoff is device-sealed and never staged through
        host RAM; use ``handoff.to_host()`` if an RPC edge ends up
        carrying it after all)."""
        # the exported pages must reflect the host's view of num_tokens:
        # land any in-flight pipelined chunk before gathering
        self._pipe_flush(deliver=True)
        from ray_tpu.llm.disagg.handoff import KVHandoff

        req = self.requests.get(request_id)
        if req is None or req.status != RequestStatus.RUNNING or req.seq is None:
            raise ValueError(
                f"request {request_id!r} is not RUNNING on this engine "
                "(only admitted, in-flight requests can be exported)"
            )
        if request_id in self._mixed_prefills:
            # mid-prompt mixed row: KV exists only up to the cursor, not
            # the num_tokens-1 positions the handoff invariant promises
            raise ValueError(
                f"request {request_id!r} is mid-prefill in a mixed batch; "
                "export after its prompt chunks complete"
            )
        c = self.config
        n_kv = req.num_tokens - 1  # positions with KV written
        slots = req.seq.slots_for_range(0, n_kv)
        # pad the gather to a power-of-two width (compiled-shape
        # bucketing on TPU); pad rows read the trash page and are
        # sliced off host-side after the device->host copy (device-side
        # on the keep_on_device path — the slice is a device op)
        width = max(1, 1 << (n_kv - 1).bit_length()) if n_kv else 1
        num_slots = c.num_blocks * c.block_size
        sl = np.full(width, num_slots, np.int32)
        sl[:n_kv] = slots
        sl = jnp.asarray(sl)
        if keep_on_device:
            k_pages = self.cache["k"][:, :, sl, :][:, :, :n_kv, :]
            v_pages = self.cache["v"][:, :, sl, :][:, :, :n_kv, :]
        else:
            k_pages = np.asarray(self.cache["k"][:, :, sl, :])[:, :, :n_kv, :]
            v_pages = np.asarray(self.cache["v"][:, :, sl, :])[:, :, :n_kv, :]
        lora_id = None
        if req.lora_slot:
            lora_id = next(
                (lid for lid, s in self._lora_slots.items() if s == req.lora_slot),
                None,
            )
        handoff = KVHandoff(
            request_id=req.request_id,
            prompt_token_ids=list(req.prompt_token_ids),
            output_token_ids=list(req.output_token_ids),
            sampling_params=req.sampling_params,
            key_data=np.asarray(jax.random.key_data(req._key)),
            num_kv_tokens=n_kv,
            k_pages=k_pages,
            v_pages=v_pages,
            model_sig=(c.model.n_layers, c.model.n_kv_heads, c.model.head_dim),
            lora_id=lora_id,
            cumulative_logprob=req.cumulative_logprob,
            token_logprobs=list(req.token_logprobs),
            t_arrival=req.arrival,
            t_first_prefill=req.t_first_prefill,
            t_first_token=req.t_first_token,
            # span-tiling: the llm.kv_transfer span starts where the
            # prefill span ended, so the request's phase spans stay
            # gap-free across the hop (obs coverage gate)
            t_export=(req.t_span_cursor if req.t_span_cursor is not None
                      else time.time()),
            trace=req.trace.to_dict() if req.trace is not None else None,
        )
        handoff.seal(device=keep_on_device)
        # drop local ownership; sealed full blocks stay in the prefix cache
        self.running.remove(req)
        req.seq.release()
        req.seq = None
        req.status = RequestStatus.MIGRATED
        self.requests.pop(request_id, None)
        if self.drafter is not None:
            self.drafter.release(request_id)
        return handoff

    def _kv_import_fn(self, width: int):
        fn = self._kv_imports.get(width)
        if fn is None:
            def llm_kv_scatter(cache, k, v, slots):
                return {
                    "k": cache["k"].at[:, :, slots, :].set(k),
                    "v": cache["v"].at[:, :, slots, :].set(v),
                }

            fn = jax.jit(llm_kv_scatter, donate_argnums=(0,))
            self._kv_imports[width] = fn
        return fn

    def _scatter_block_pages(self, k, v, blocks: list) -> None:
        """Scatter position-ordered host pages [L, KVH, n_kv, D] into
        whole ``blocks`` with ONE jitted set (power-of-two padded, pad
        rows hit the trash page). The single recipe tier resurrection
        (_resurrect_tiers) and the prefetch tick share — the scatter
        shape must never drift between them."""
        c = self.config
        bs = c.block_size
        n_kv = int(k.shape[2])
        width = max(1, 1 << (n_kv - 1).bit_length())
        num_slots = c.num_blocks * bs
        sl = np.full(width, num_slots, np.int32)  # pad rows hit the trash page
        pos = 0
        for b in blocks:
            sl[pos:pos + bs] = np.arange(b * bs, (b + 1) * bs)
            pos += bs
        dt = self.cache["k"].dtype
        kp = np.zeros(k.shape[:2] + (width,) + k.shape[3:], k.dtype)
        vp = np.zeros_like(kp)
        kp[:, :, :n_kv] = k
        vp[:, :, :n_kv] = v
        self.cache = self._call(
            "kv_scatter", (width,), self._kv_import_fn(width),
            self.cache, jnp.asarray(kp, dt), jnp.asarray(vp, dt),
            jnp.asarray(sl),
        )

    def import_handoff(self, handoff,
                       trace: Optional[trace_context.TraceContext] = None) -> str:
        """Adopt an exported request: scatter its KV pages into this
        engine's paged cache and enqueue it RUNNING — no prefill, no
        recompute (`num_cached_tokens` covers every transferred
        position). Raises NoFreeBlocksError when the cache can't hold it
        right now (callers may retry after decode frees blocks) and
        ValueError on a model/cache mismatch."""
        # joining the decode batch is a membership change: land the
        # in-flight pipelined chunk so the import sees settled state
        self._pipe_flush(deliver=True)
        c = self.config
        sig = (c.model.n_layers, c.model.n_kv_heads, c.model.head_dim)
        if tuple(handoff.model_sig) != sig:
            raise ValueError(
                f"handoff model signature {tuple(handoff.model_sig)} != "
                f"engine {sig}; prefill and decode pools must serve the "
                "same model"
            )
        rid = handoff.request_id
        if rid in self.requests:
            raise ValueError(f"request {rid!r} already live on this engine")
        n_kv = handoff.num_kv_tokens
        if handoff.k_pages.shape[2] != n_kv or handoff.v_pages.shape[2] != n_kv:
            raise ValueError(
                f"handoff KV pages cover {handoff.k_pages.shape[2]} tokens, "
                f"header says {n_kv}"
            )
        req = Request(rid, list(map(int, handoff.prompt_token_ids)),
                      handoff.sampling_params)
        req.output_token_ids = list(map(int, handoff.output_token_ids))
        req.cumulative_logprob = handoff.cumulative_logprob
        req.token_logprobs = list(handoff.token_logprobs)
        req.lora_slot = self._lora_slot(handoff.lora_id)
        req._key = jax.random.wrap_key_data(jnp.asarray(handoff.key_data))
        req.trace = (
            trace
            or trace_context.TraceContext.from_dict(handoff.trace)
            or trace_context.new_context()
        )
        req.arrival = handoff.t_arrival
        req.t_queue_start = handoff.t_arrival
        req.t_first_prefill = handoff.t_first_prefill
        req.t_first_token = handoff.t_first_token

        seq = SequenceBlocks(self.allocator)
        seq.chain = req.lora_slot  # salt the hash chain like _prefill_one
        seq.ensure_capacity(req.num_tokens)  # may raise NoFreeBlocksError
        width = max(1, 1 << (n_kv - 1).bit_length()) if n_kv else 1
        num_slots = c.num_blocks * c.block_size
        sl = np.full(width, num_slots, np.int32)  # pad rows hit the trash page
        sl[:n_kv] = seq.slots_for_range(0, n_kv)
        dt = self.cache["k"].dtype
        if isinstance(handoff.k_pages, jax.Array):
            # fabric device path: the pages arrived as device arrays on
            # this engine's endpoint device — pad and scatter entirely
            # on-device, never staging the multi-MB payload through host
            # RAM (device_put here is the final hop when the transport
            # endpoint differs from the cache's device)
            cache_devs = self.cache["k"].devices()
            kp, vp = handoff.k_pages, handoff.v_pages
            if kp.devices() != cache_devs:
                dev = next(iter(cache_devs))
                kp = jax.device_put(kp, dev)
                vp = jax.device_put(vp, dev)
            pad = [(0, 0), (0, 0), (0, width - n_kv), (0, 0)]
            k = jnp.pad(kp.astype(dt), pad)
            v = jnp.pad(vp.astype(dt), pad)
        else:
            k = np.zeros(
                handoff.k_pages.shape[:2] + (width,) + handoff.k_pages.shape[3:],
                handoff.k_pages.dtype)
            v = np.zeros_like(k)
            k[:, :, :n_kv] = handoff.k_pages
            v[:, :, :n_kv] = handoff.v_pages
        self.cache = self._kv_import_fn(width)(
            self.cache, jnp.asarray(k, dt), jnp.asarray(v, dt), jnp.asarray(sl)
        )
        seq.num_tokens = req.num_tokens
        # every transferred position counts as cached: zero recompute
        seq.num_cached_tokens = n_kv
        if c.enable_prefix_caching:
            # seal transferred full blocks so future prompts sharing this
            # prefix hit THIS engine's cache too
            written = req.prompt_token_ids + req.output_token_ids[:-1]
            seq.seal_full_blocks(written)
        req.seq = seq
        req.status = RequestStatus.RUNNING
        self.requests[rid] = req
        self.running.append(req)
        self.num_kv_imports += 1
        req.t_span_cursor = time.time()  # decode rounds tile from import
        return rid

    def generate(
        self,
        prompts: list,
        sampling_params: "SamplingParams | list[SamplingParams] | None" = None,
    ) -> list:
        """Blocking batch generation; returns output token lists in order."""
        if sampling_params is None or isinstance(sampling_params, SamplingParams):
            sampling_params = [sampling_params or SamplingParams()] * len(prompts)
        rids = [
            self.add_request(p, sp) for p, sp in zip(prompts, sampling_params)
        ]
        finals: dict[str, list] = {}
        while self.has_unfinished():
            for out in self.step():
                if out.finished:
                    finals[out.request_id] = out.output_token_ids
        return [finals[r] for r in rids]

    def update_telemetry_gauges(self) -> None:
        """Refresh this engine's utilization gauges (KV-page occupancy,
        HBM bytes, queue depth) in the process registry — the series the
        telemetry plane ships cluster-wide. Called throttled from step()
        and by TelemetryReporter collect callbacks; must never throw into
        the serving path."""
        try:
            g = utilization_gauges()
            tags = {"model": self.model_tag}
            c = self.config
            g["kv_pages_used"].set(c.num_blocks - self.allocator.num_free,
                                   tags=tags)
            g["kv_pages_total"].set(c.num_blocks, tags=tags)
            g["kv_hbm_bytes"].set(self._kv_cache_nbytes, tags=tags)
            g["queue_depth"].set(len(self.waiting), tags=tags)
            g["running"].set(len(self.running), tags=tags)
            if self.kvtier is not None:
                self.kvtier.update_gauges()
                # piggyback the prefix-index snapshot on the same
                # throttle (telemetry-style freshness, no extra timer)
                self.kvtier.flush_index()
        except Exception:  # noqa: BLE001 — observability must not break serving
            pass

    def stats(self) -> dict:
        out = {
            "num_waiting": len(self.waiting),
            "num_running": len(self.running),
            "free_blocks": self.allocator.num_free,
            "total_blocks": self.config.num_blocks,
            "num_prefill_batches": self.num_prefill_batches,
            "num_preemptions": self.num_preemptions,
            "weight_version": self.weight_version,
            "prefix_cache": {
                "hit_tokens": self.prefix_hit_tokens,
                "lookup_tokens": self.prefix_lookup_tokens,
                "hit_rate": (
                    round(self.prefix_hit_tokens / self.prefix_lookup_tokens, 4)
                    if self.prefix_lookup_tokens else 0.0
                ),
                "by_tier": dict(self.tier_hit_tokens),
            },
        }
        if self.kvtier is not None:
            # the tier breakdown GET /v1/stats surfaces (rides
            # engine.stats() through the serving layer unchanged)
            out["kv_tiers"] = self.kvtier.stats()
            if self.kvfetch is not None:
                # prefetch/fetch rollup rides the same surface
                out["kv_tiers"]["fetch"] = self.kvfetch.stats()
        if self.num_kv_imports:
            out["num_kv_imports"] = self.num_kv_imports
        if self.spec_stats is not None:
            out["spec"] = self.spec_stats.to_dict()
        if self._pipe_stats is not None and self._pipe_stats.dispatches:
            # the `pipeline` row of /v1/stats: chunk-size distribution,
            # host/device split, overlap ratio, early-exit savings
            out["pipeline"] = self._pipe_stats.to_dict()
        if self._mixed_stats is not None and self._mixed_stats.dispatches:
            # the mixed ragged dispatch's padding-waste accounting (the
            # --mixed bench's padding_waste_ratio reads this row)
            out["mixed"] = self._mixed_stats.to_dict()
        return out

    def warmup(self, sample_modes: tuple = ("greedy",),
               stop_widths: tuple = (1,)) -> dict:
        """Run every program of this engine's own tables once, so that
        none compiles (or loads) under traffic: prefill_buckets() x
        bt_widths(); decode_buckets() x CHUNK_BUCKETS x bt_widths() x
        ``stop_widths`` x ``sample_modes`` for the decode path the
        config selects (the defaults are what requests without stop ids
        at temperature 0 use); the mixed and verify programs where the
        config has them. Writes only the cache's trash page. Call it
        before traffic, from the thread that owns the engine. Returns
        {class: {"programs", "seconds", "compiled", "loaded"}}, the last
        two from the compile log (ray_tpu.obs.compile_log())."""
        from ray_tpu.llm.warmup import warm_engine

        return warm_engine(self, sample_modes, stop_widths)

    def counters(self) -> dict:
        """Always-on counts since the engine was built, as plain numbers
        read WITHOUT any lock (the serving runner's included): take two
        snapshots and subtract. decode_steps are device decode steps,
        decode_row_steps the live rows summed over them, so the decode
        batch's occupancy over an interval is
        d(decode_row_steps) / (d(decode_steps) x max_num_seqs);
        prefill_tokens were computed, prefill_cached_tokens came from the
        prefix cache; a first_call is a program (class, static
        arguments, padded shapes) dispatched for the first time, so a
        compile or a cache load."""
        n = self._n
        ps = self._pipe_stats
        steps = n["decode_steps"]
        return {
            "decode_steps": steps,
            "decode_row_steps": n["decode_row_steps"],
            "decode_tokens": n["decode_tokens"],
            "prefill_tokens": n["prefill_tokens"],
            "prefill_cached_tokens": n["prefill_cached_tokens"],
            "dispatches": dict(n["dispatches"]),
            "first_calls": dict(n["first_calls"]),
            "flushes": ps.flushes if ps is not None else 0,
            "rebuilds": ps.rebuilds if ps is not None else 0,
            "preemptions": self.num_preemptions,
            "max_num_seqs": self.config.max_num_seqs,
            "decode_occupancy": (
                n["decode_row_steps"] / (steps * self.config.max_num_seqs)
                if steps else 0.0
            ),
        }

    # -- request tracing (ray_tpu.obs) ---------------------------------------
    # Per-request lifecycle spans into the flight recorder + SLO
    # histograms. Phases tile: queue_wait [arrival/preempt -> prefill
    # dispatch], prefill [dispatch -> first token], then one span per
    # decode round (chunk or spec) from the request's span cursor — so a
    # retrieved trace covers the full e2e wall-clock; host scheduling
    # gaps are priced inside each round span as sched_gap_ms, never
    # hidden. Every hook swallows failures: observability must not
    # break decode.

    def _obs_span(self, req, name: str, t0: float, t1: float,
                  attrs: Optional[dict] = None, status: str = "ok") -> None:
        try:
            trace_recorder.get_recorder().record(
                name, t0, t1, ctx=req.trace, attrs=attrs, status=status
            )
        except Exception:  # noqa: BLE001
            pass

    def _obs_decode_round(self, batch: list, outputs: list, wall0: float,
                          name: str, n_steps: int,
                          extra: Optional[dict] = None) -> list:
        """Record one decode round for every participating request, then
        finalize the ones that finished. ``extra`` maps request_id ->
        additional span attrs (spec rounds attach draft/accept counts)."""
        self._n["decode_tokens"] += sum(len(o.new_token_ids) for o in outputs)
        try:
            t1 = time.time()
            active_ms = round((t1 - wall0) * 1e3, 3)
            by_rid = {o.request_id: o for o in outputs}
            for r in batch:
                out = by_rid.get(r.request_id)
                start = r.t_span_cursor if r.t_span_cursor is not None else wall0
                start = min(start, wall0)
                attrs = {
                    "n_steps": n_steps,
                    "new_tokens": len(out.new_token_ids) if out else 0,
                    "active_ms": active_ms,
                }
                gap_ms = (wall0 - start) * 1e3
                if gap_ms > 0.05:
                    attrs["sched_gap_ms"] = round(gap_ms, 3)
                if extra:
                    attrs.update(extra.get(r.request_id, ()))
                self._obs_span(r, name, start, t1, attrs)
                r.t_span_cursor = t1
            self._obs_finalize(batch, t1)
        except Exception:  # noqa: BLE001
            pass
        return outputs

    def _obs_finalize(self, reqs: list, t_end: float) -> None:
        """Root span + SLO observations for requests that just finished."""
        for r in reqs:
            if r.status != RequestStatus.FINISHED:
                continue
            try:
                n_out = len(r.output_token_ids)
                e2e = max(0.0, t_end - r.arrival)
                ttft = (
                    max(0.0, r.t_first_token - r.arrival)
                    if r.t_first_token is not None else None
                )
                tpot = (
                    (t_end - r.t_first_token) / (n_out - 1)
                    if r.t_first_token is not None and n_out > 1 else None
                )
                queue_wait = (
                    max(0.0, r.t_first_prefill - r.arrival)
                    if r.t_first_prefill is not None else None
                )
                prefill_span = (
                    max(0.0, r.t_first_token - r.t_first_prefill)
                    if r.t_first_token is not None
                    and r.t_first_prefill is not None else None
                )
                attrs = {
                    "request_id": r.request_id,
                    "finish_reason": r.finish_reason,
                    "prompt_tokens": len(r.prompt_token_ids),
                    "output_tokens": n_out,
                    "num_preemptions": r.num_preemptions,
                    "e2e_s": round(e2e, 6),
                    "pre_engine_wait_s": round(r.pre_engine_wait_s, 6),
                }
                if ttft is not None:
                    attrs["ttft_s"] = round(ttft, 6)
                if tpot is not None:
                    attrs["tpot_s"] = round(tpot, 6)
                if queue_wait is not None:
                    attrs["queue_wait_s"] = round(queue_wait, 6)
                self._obs_span(r, "llm.request", r.arrival, t_end, attrs)
                from ray_tpu.obs import slo

                slo.record_request_slo(
                    self.model_tag,
                    ttft_s=ttft, tpot_s=tpot, queue_wait_s=queue_wait,
                    e2e_s=e2e, finish_reason=r.finish_reason or "",
                    prefill_span_s=prefill_span,
                )
                if r.slo_tag and r.slo_tag != self.model_tag:
                    # fleet QoS/canary plane: the same observation under
                    # the request's own tag (a tenant or a canary
                    # replica) so evaluate_slo can grade it in isolation
                    slo.record_request_slo(
                        r.slo_tag,
                        ttft_s=ttft, tpot_s=tpot, queue_wait_s=queue_wait,
                        e2e_s=e2e, finish_reason=r.finish_reason or "",
                        prefill_span_s=prefill_span,
                    )
            except Exception:  # noqa: BLE001
                pass

    # -- scheduling internals -------------------------------------------------

    def _admission_need(self, req) -> int:
        """Free-pool blocks admitting ``req`` would actually consume
        (kv_cache.probe_admission_need over the recompute prompt, with
        the request's LoRA salt; the full count when prefix caching is
        off)."""
        if not self.config.enable_prefix_caching:
            return self.allocator.blocks_needed(req.num_tokens)
        return self.allocator.probe_admission_need(
            req.prompt_token_ids + req.output_token_ids, req.lora_slot
        )

    def _pad_to_bucket(self, n: int, buckets: list) -> int:
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    def _admit_one(self):
        """_admit_head inside the layer span engine.schedule."""
        with obs.layer_span("engine.schedule"):
            return self._admit_head()

    def _admit_head(self):
        """Admit the head of the waiting queue: prefix match (+ tiered
        resurrection), capacity reservation for the FULL recompute
        prompt, queue/hit bookkeeping — everything up to (but not
        including) dispatch, shared by the split prefill path
        (_prefill_one) and mixed admission (_mixed_admit). Returns
        (req, seq, prompt, matched) past the commit point, or None when
        the cache has no room (caller falls through to decode)."""
        c = self.config
        req = self.waiting[0]
        seq = SequenceBlocks(self.allocator)
        # after a preemption the recompute covers prompt + already-generated
        # tokens; outputs stay in output_token_ids so callers see them all
        prompt = req.prompt_token_ids + req.output_token_ids

        # prefix-cache hit: skip recomputing matched full blocks (always
        # leave >=1 token to prefill so we get next-token logits)
        matched_blocks: list = []
        matched = 0
        # adapters change K/V: salt the prefix-hash chain by lora slot so
        # sequences under different adapters never share cached blocks
        salt = req.lora_slot
        seq.chain = salt
        tier_counts: dict[str, int] = {}
        if c.enable_prefix_caching:
            blocks, matched, chain = self.allocator.match_prefix(prompt, salt)
            if matched >= len(prompt):
                # whole prompt cached — we still need last-token logits, so
                # re-match against prompt[:-1] to leave >=1 token to prefill
                self.allocator.free(blocks)
                blocks, matched, chain = self.allocator.match_prefix(prompt[:-1], salt)
            if matched:
                tier_counts["hbm"] = matched
            if self.kvtier is not None:
                # tiered resurrection: blocks past the HBM match may sit
                # spilled in host DRAM / the object store — scatter them
                # back (verified, zero recompute) and extend the match
                rblocks, rtokens, chain, rcounts = self._resurrect_tiers(
                    prompt, matched, chain, salt
                )
                if rblocks:
                    blocks = list(blocks) + rblocks
                    matched += rtokens
                    for t, n in rcounts.items():
                        tier_counts[t] = tier_counts.get(t, 0) + n
            if blocks:
                seq.adopt_prefix(blocks, chain, matched)
                matched_blocks = blocks

        suffix = prompt[matched:]
        try:
            seq.ensure_capacity(len(prompt))
        except NoFreeBlocksError:
            if matched_blocks:
                seq.release()
            return None  # no room: fall through to decode; retry later
        self.waiting.popleft()
        self.num_prefill_batches += 1
        self._n["prefill_tokens"] += len(prompt) - matched
        self._n["prefill_cached_tokens"] += matched
        if self.kvfetch is not None and matched:
            # blocks the prefetch tick scattered ahead of admission
            # match as HBM residents; re-attribute their hits to the
            # tier the prefetch pulled them from, so the per-tier mix
            # reflects where the KV actually came from. Taken only
            # PAST the admission commit point — an ensure_capacity
            # failure above leaves the attribution for the retry.
            for t, n in self.kvfetch.take_attribution(
                    req.request_id).items():
                move = min(n, tier_counts.get("hbm", 0))
                if move <= 0:
                    continue
                tier_counts["hbm"] -= move
                tier_counts[t] = tier_counts.get(t, 0) + move
            if tier_counts.get("hbm") == 0:
                tier_counts.pop("hbm", None)
        # prefix-cache accounting over the ORIGINAL prompt only: a
        # preemption recompute re-matching its own just-sealed blocks
        # would otherwise inflate the hit rate the decode pick trusts
        if req.num_preemptions == 0:
            self.prefix_lookup_tokens += len(req.prompt_token_ids)
            self.prefix_hit_tokens += min(matched, len(req.prompt_token_ids))
            for t, n in tier_counts.items():
                self.tier_hit_tokens[t] = self.tier_hit_tokens.get(t, 0) + n
            try:
                tags = {"model": self.model_tag}
                prefix_cache_lookup_counter().inc(
                    len(req.prompt_token_ids), tags=tags
                )
                for t, n in tier_counts.items():
                    prefix_cache_hit_counter().inc(
                        n, tags={"model": self.model_tag, "tier": t}
                    )
            except Exception:  # noqa: BLE001 — metrics must not break admission
                pass
        t_admit = time.time()
        self._obs_span(
            req, "engine.queue_wait", req.t_queue_start, t_admit,
            {"recompute": req.num_preemptions > 0},
        )
        req.t_prefill_start = t_admit
        if req.t_first_prefill is None:
            req.t_first_prefill = t_admit
        req._prefill_cached = matched
        return req, seq, prompt, matched

    def _prefill_one(self):
        """Prefill the head of the waiting queue: DISPATCH only, no host
        sync. Returns (req, last-token logits [1, V] device array), or
        None when the cache has no room (caller falls through to decode)."""
        got = self._admit_one()
        if got is None:
            return None
        req, seq, prompt, matched = got
        c = self.config

        with obs.layer_span("engine.prefill_dispatch"):
            num_slots = c.num_blocks * c.block_size
            bt = np.zeros((1, self._bt_width([len(seq.blocks)])), np.int32)
            bt[0, : len(seq.blocks)] = seq.blocks
            bt = jnp.asarray(bt)

            # chunked prefill: preemption recompute can exceed max_prefill_len;
            # each chunk extends context_lens, only the last chunk's logits count
            logits = None
            for start in range(matched, len(prompt), c.max_prefill_len):
                chunk = prompt[start : start + c.max_prefill_len]
                S_pad = self._pad_to_bucket(len(chunk), c.prefill_buckets())
                tokens = np.zeros((1, S_pad), np.int32)
                tokens[0, : len(chunk)] = chunk
                positions = np.zeros((1, S_pad), np.int32)
                positions[0, : len(chunk)] = np.arange(start, start + len(chunk))
                slots = np.full((1, S_pad), num_slots, np.int32)  # trash by default
                for i, p in enumerate(range(start, start + len(chunk))):
                    slots[0, i] = seq.slot(p)
                logits, self.cache = self._call(
                    "prefill", (S_pad, bt.shape[1]), self._prefill,
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(positions),
                    jnp.asarray([len(chunk)], jnp.int32),
                    jnp.asarray(slots),
                    bt,
                    jnp.asarray([start + len(chunk)], jnp.int32),
                    self.cache,
                    self._lora_arg(np.asarray([req.lora_slot], np.int32)),
                )
        seq.num_tokens = len(prompt)
        if c.enable_prefix_caching:
            seq.seal_full_blocks(prompt)
        req.seq = seq
        req.status = RequestStatus.RUNNING
        self.running.append(req)
        if self.kvfetch is not None:
            # the sequence holds its own refs now: release the prefetch
            # reservation and book the lead time
            self.kvfetch.consumed(req.request_id)
        return req, logits

    # -- mixed ragged batching (ray_tpu.llm.mixed) ---------------------------
    # One ragged program per step serves in-flight prefill chunks AND
    # every decode row (llm/mixed.MixedBatchPlan over
    # llama_decode.mixed_step over ops/ragged). Prompts stream
    # mixed_prefill_chunk tokens per step, so decode rows advance every
    # step regardless of prompt length. The split path stays the
    # identity oracle: token streams must match it bitwise.

    def _mixed_admit(self):
        """Admit the queue head WITHOUT dispatching its prompt: the
        mixed dispatch feeds it chunk-by-chunk from the cursor this
        records. Returns the request or None (no cache room)."""
        got = self._admit_one()
        if got is None:
            return None
        req, seq, prompt, matched = got
        # seq.num_tokens tracks positions with K/V WRITTEN — exactly the
        # matched prefix until chunks land (the cursor advances it)
        seq.num_tokens = matched
        req.seq = seq
        req.status = RequestStatus.RUNNING
        self.running.append(req)
        self._mixed_prefills[req.request_id] = matched
        if self.kvfetch is not None:
            self.kvfetch.consumed(req.request_id)
        return req

    def _mixed_step(self) -> list[RequestOutput]:
        """One mixed-batch iteration (EngineConfig.mixed_batch): admit
        waiting requests, then serve every in-flight prefill chunk plus
        every decode row in ONE ragged dispatch. Steps with no prefill
        work route to the regular decode ladder — the degenerate
        all-q_len=1 case costs exactly the split path's decode step
        (including spec rounds and the pipelined chunk overlap)."""
        c = self.config
        if (
            self.waiting
            and len(self.running) < c.max_num_seqs
            # same read-only precheck as the split path: see step()
            and self._admission_need(self.waiting[0])
            <= self.allocator.num_free
        ):
            # admission is a membership change for the pipelined decode
            # carry: land the in-flight chunk first
            flushed = self._pipe_flush()
            if flushed:
                return flushed
            while self.waiting and len(self.running) < c.max_num_seqs:
                if self._mixed_admit() is None:
                    break  # no cache room: decode to free blocks
        if not self._mixed_prefills:
            return self._decode_step() if self.running else []
        # prefill chunks in flight: the unified dispatch replaces the
        # decode ladder this step, so the pipelined carry (dispatched
        # for the old all-decode batch) must land first
        flushed = self._pipe_flush()
        if flushed:
            return flushed
        self._step_kind = "mixed"
        wall0 = time.time()
        with obs.layer_span("engine.mixed_dispatch"):
            # KV for this step's writes: mid-prompt rows reserved their full
            # recompute prompt at admission; decode rows grow one position
            while True:
                try:
                    for r in self.running:
                        if r.request_id not in self._mixed_prefills:
                            r.seq.ensure_capacity(r.num_tokens + 1)
                    break
                except NoFreeBlocksError:
                    if not self._preempt_one():
                        raise  # single running request can't fit: cache too small
            from ray_tpu.llm.mixed import MixedBatchPlan

            plan = MixedBatchPlan.build(self)
            logits, self.cache = self._call(
                "mixed", (len(plan.tokens), plan.bt.shape), self._mixed_fn,
                self.params,
                jnp.asarray(plan.tokens),
                jnp.asarray(plan.positions),
                jnp.asarray(plan.slots),
                jnp.asarray(plan.bt),
                jnp.asarray(plan.cu_q_lens),
                jnp.asarray(plan.context_lens),
                self.cache,
                self._lora_arg(plan.lora_ids),
            )
            plan.note(self._mixed_stats)

        # advance prefill cursors; a finishing prompt seals its full
        # blocks (the _prefill_one contract) and becomes a decode row
        done_set = set(plan.completes)
        prompt_done: list = []
        for row in range(plan.B):
            if plan.kinds[row] != "prefill":
                continue
            r = plan.reqs[row]
            end = plan.starts[row] + plan.chunk_lens[row]
            r.seq.num_tokens = end
            if row in done_set:
                if c.enable_prefix_caching:
                    r.seq.seal_full_blocks(
                        r.prompt_token_ids + r.output_token_ids
                    )
                del self._mixed_prefills[r.request_id]
                prompt_done.append(r)
            else:
                self._mixed_prefills[r.request_id] = end

        outputs: list[RequestOutput] = []
        if plan.emit_rows:
            emit_reqs = [plan.reqs[i] for i in plan.emit_rows]
            with obs.layer_span("engine.sync"):
                tok, logprob = self._sample_batch(
                    logits[np.asarray(plan.emit_rows)], emit_reqs
                )
            t1 = time.time()  # host sync done
            with obs.layer_span("engine.append"):
                outputs = self._append_tokens(emit_reqs, tok, logprob)
            for r in prompt_done:
                self._obs_span(
                    r, "engine.prefill",
                    r.t_prefill_start if r.t_prefill_start is not None else t1,
                    t1,
                    {"prompt_tokens": len(r.prompt_token_ids),
                     "cached_tokens": r._prefill_cached,
                     "recompute": r.num_preemptions > 0,
                     "mixed": True},
                )
                if r.t_first_token is None:
                    r.t_first_token = t1
                r.t_span_cursor = t1
            if prompt_done:
                self._obs_finalize(prompt_done, t1)
            dec = [
                j for j, i in enumerate(plan.emit_rows)
                if plan.kinds[i] == "decode"
            ]
            if dec:
                self._n["decode_steps"] += 1
                self._n["decode_row_steps"] += len(dec)
                self._obs_decode_round(
                    [emit_reqs[j] for j in dec], [outputs[j] for j in dec],
                    wall0, "engine.mixed_round", 1,
                )
        return outputs

    def _resurrect_tiers(self, prompt: list, matched: int, chain: int,
                         salt: int) -> tuple:
        """Pull spilled full blocks past the HBM match back into the
        paged cache: walk the prompt's chain hashes from ``chain``,
        take each verified SpilledBlock from the deepest tiers, and
        scatter all their pages in ONE jitted set (the import_handoff
        shape — ``num_cached_tokens`` covers every resurrected position,
        zero recompute). A corrupt entry stops the walk (recompute from
        there); so does allocation pressure. Returns
        (blocks, n_tokens, chain, {tier: tokens})."""
        mgr = self.kvtier
        c = self.config
        bs = c.block_size
        # >=1 token must stay un-cached so prefill yields next-token
        # logits — the same contract the HBM whole-prompt re-match keeps
        limit = (len(prompt) - 1) // bs
        start = matched // bs
        entries: list[tuple] = []  # (hash, tier|"hbm", SpilledBlock|block_id)
        h = chain
        for i in range(start, limit):
            blk = tuple(prompt[i * bs : (i + 1) * bs])
            h2 = self.allocator.chain_hash(h, blk)
            got = mgr.take_verified(h2, blk)
            if got is None:
                # head-first eviction leaves mid-chain blocks RESIDENT
                # past a spilled head (match_prefix stopped at the gap):
                # adopt them by refcount instead of recomputing KV this
                # engine still holds (probe_tiers counts them; the
                # admission path must serve what routing advertises)
                b = self.allocator.lookup(h2)
                if b is None:
                    break
                entries.append((h2, "hbm", b))
            else:
                entries.append((h2, got[0], got[1]))
            h = h2
        deep = [e for e in entries if e[1] != "hbm"]
        if not entries or not deep:
            # nothing spilled to pull back: pure-HBM adoption would be
            # wrong here (these refs belong past a gap match_prefix
            # never saw ONLY when a deep block bridged it) — release
            if entries:
                self.allocator.free([b for _h, _t, b in entries])
            return [], 0, chain, {}
        try:
            new_blocks = self.allocator.allocate(len(deep))
        except NoFreeBlocksError:
            # deep entries stay spilled (take_verified is non-destructive
            # on success); adopted HBM refs must be returned
            self.allocator.free([b for _h, t, b in entries if t == "hbm"])
            return [], 0, chain, {}
        k = np.concatenate([sb.handoff.k_pages for _h, _t, sb in deep], axis=2)
        v = np.concatenate([sb.handoff.v_pages for _h, _t, sb in deep], axis=2)
        self._scatter_block_pages(k, v, new_blocks)
        tier_counts: dict[str, int] = {}
        blocks: list[int] = []
        it_new = iter(new_blocks)
        parent = chain
        for idx, (h2, tier, payload) in enumerate(entries):
            if tier == "hbm":
                blocks.append(payload)  # adopted resident block, ref held
            else:
                b = next(it_new)
                # re-register in HBM (the seal listener re-advertises the
                # hbm row) and drop the deep-tier copy it came from
                self.allocator.register_full_block(
                    b, h2, parent_hash=parent, tokens=payload.tokens,
                    n_prefix_tokens=(start + idx + 1) * bs,
                )
                mgr.promoted(h2, tier)
                blocks.append(b)
            tier_counts[tier] = tier_counts.get(tier, 0) + bs
            parent = h2
        for tier, n in tier_counts.items():
            if tier != "hbm":  # adopted residents are hits, not resurrections
                mgr.count_resurrected(tier, n)
        return blocks, len(entries) * bs, parent, tier_counts

    def _promote_priority(self) -> None:
        """Move the highest-priority waiting request to the queue head.
        Stable: FIFO within a priority class, and a no-op when
        priorities are uniform — the pre-fleet engine stays strictly
        FIFO."""
        w = self.waiting
        if len(w) < 2:
            return
        best_i = max(range(len(w)), key=lambda i: (w[i].priority, -i))
        if best_i:
            req = w[best_i]
            del w[best_i]
            w.appendleft(req)

    def _preempt_one(self, below_priority: Optional[int] = None,
                     reason: str = "pressure") -> bool:
        """Kick a running request back to waiting (recompute). The
        victim is the lowest-priority, newest-arrival request —
        identical to the historical newest-arrival pick when priorities
        are uniform. ``below_priority`` (the priority-preemption path)
        only preempts a victim strictly below it, and may empty the
        batch (the displacing request admits next round); the KV-
        pressure path keeps the >=2 guard so a batch of one can always
        make progress."""
        if not self.running:
            return False
        if below_priority is None and len(self.running) <= 1:
            return False
        victim = min(self.running, key=lambda r: (r.priority, -r.arrival))
        if below_priority is not None and victim.priority >= below_priority:
            return False
        try:
            preemption_counter().inc(
                1, tags={"model": self.model_tag,
                         "tenant": victim.tenant or "",
                         "reason": reason}
            )
        except Exception:  # noqa: BLE001 — accounting, not correctness
            pass
        self.running.remove(victim)
        # a mid-prefill mixed row re-queues like any victim: drop the
        # cursor; re-admission recomputes prompt+outputs from scratch
        self._mixed_prefills.pop(victim.request_id, None)
        victim.seq.release()
        victim.seq = None
        # outputs are kept; re-admission prefills prompt+outputs (recompute)
        victim.status = RequestStatus.WAITING
        victim.num_preemptions += 1
        self.num_preemptions += 1
        self.waiting.appendleft(victim)
        now = time.time()
        self._obs_span(victim, "engine.preempt", now, now,
                       {"num_preemptions": victim.num_preemptions,
                        "reason": reason})
        victim.t_queue_start = now  # next queue_wait span starts here
        victim.t_span_cursor = None
        if self.drafter is not None:
            # re-admission recomputes from scratch; stale draft-cache
            # state would desync from the recomputed sequence
            self.drafter.release(victim.request_id)
        logger.info("preempted %s (recompute)", victim.request_id)
        return True

    def _bt_width(self, page_counts) -> int:
        """Block-table width for this call: the batch's real page count
        rounded up to a power of two (compiled-shape bucketing), capped
        at the model maximum. Sizing to max_blocks_per_seq regardless of
        context made the paged kernel's grid iterate (and the XLA gather
        materialize) every POSSIBLE page — at short contexts that is an
        order of magnitude of wasted work per step."""
        w = max(list(page_counts) or [1])
        w = 1 << max(0, (w - 1)).bit_length()
        # floor: tiny width buckets would recompile as contexts grow past
        # each power of two right at the start of every run
        w = max(w, min(16, self.config.max_blocks_per_seq))
        return min(w, self.config.max_blocks_per_seq)

    def _chunk_steps(self) -> int:
        """Device-side steps this round: the configured chunk, shrunk so
        no running request can overrun max_tokens/max_seq, floored to a
        power of two (compiled-shape bucketing)."""
        c = self.config
        n = max(1, c.decode_chunk)
        for r in self.running:
            # only the HARD max_seq wall shrinks the chunk (positions past
            # it would index off the RoPE table). A request near its
            # max_tokens just overshoots and _append_chunk discards the
            # excess — throttling the whole batch to the shortest request
            # would reinstate the per-token host sync under staggered load
            n = min(n, max(1, c.model.max_seq - r.num_tokens))
        return 1 << (n.bit_length() - 1)

    def _remaining(self, r) -> int:
        """Output tokens this request can still KEEP (max_tokens budget)."""
        return max(1, r.sampling_params.max_tokens - len(r.output_token_ids))

    def _decode_step(self) -> list[RequestOutput]:
        self._step_kind = "decode"
        if self.config.spec is not None:
            return self._spec_decode_step()
        if self.config.pipeline_decode:
            return self._pipelined_decode_step()
        return self._plain_decode_step()

    # -- pipelined decode (ray_tpu.llm.pipeline) ------------------------------
    # Chunk N+1 is dispatched from the device-resident carry BEFORE chunk
    # N's tokens are synced, so host bookkeeping overlaps device compute.
    # Membership changes (admission/abort/handoff/recovery) flush first;
    # rows that finish DURING the overlap are already `done` on device
    # (the stop ladder runs in-graph), so the early-dispatched chunk
    # computes the identical stream for live rows and nothing for dead
    # ones. Token identity vs the sync path is the contract.

    def _pipe_flush(self, deliver: bool = False) -> list[RequestOutput]:
        """Land the in-flight chunk (if any) and invalidate the
        device-resident state (callers flush precisely because
        membership is about to change). Returns the synced outputs;
        with ``deliver`` they are queued for the next step() instead."""
        rec, self._pipe_inflight = self._pipe_inflight, None
        self._pipe_state = None
        if rec is None:
            return []
        if self._pipe_stats is not None:
            self._pipe_stats.flushes += 1
        outs = self._pipe_sync(rec)
        # the gap to the next dispatch spans a membership change
        # (admission/prefill, abort, handoff) — none of it amortizes
        # with chunk length, so keep it out of the controller's
        # per-round overhead signal
        self._pipe_last_sync_t = None
        if deliver and outs:
            self._pending_outputs.extend(outs)
            return []
        if outs:
            self._step_kind = "flush"  # what the step that asked for it returns
        return outs

    def _pipe_drop(self) -> None:
        """Crash-path reset: discard the in-flight chunk WITHOUT syncing
        (the device program may be the thing that died). Un-synced
        tokens were never booked into output_token_ids, so recovery's
        recompute-from-prefix contract holds."""
        self._pipe_inflight = None
        self._pipe_state = None
        self._pipe_last_sync_t = None

    def _pipelined_decode_step(self) -> list[RequestOutput]:
        from ray_tpu.llm import pipeline as pl

        c = self.config
        if self._pipe_ctl is None:
            self._pipe_ctl = pl.ChunkController(initial=max(1, c.decode_chunk))
            self._pipe_stats = pl.PipelineStats()
        if any(
            len(r.sampling_params.stop_token_ids) > pl.STOP_WIDTH_CAP
            for r in self.running
        ):
            # unbounded stop sets don't fit the padded on-device matrix;
            # serve this batch on the sync path (identical tokens)
            self._pipe_stats.sync_fallbacks += 1
            outs = self._pipe_flush()
            return outs if outs else self._plain_decode_step()

        # the host work the in-flight chunk's device time hides
        with obs.layer_span("engine.decode_dispatch"):
            prev = self._dispatch_pipe_chunk()
        if isinstance(prev, list):
            return prev  # flushed, or preempted: no dispatch this round
        if prev is None:
            # cold start: nothing to overlap with yet; the next step()
            # dispatches chunk 2 and syncs this one
            return []
        return self._pipe_sync(prev)

    def _dispatch_pipe_chunk(self):
        """Prepare and dispatch the next pipelined chunk. Returns the
        chunk that was in flight before it (None on a cold start), or a
        list of outputs when the round ended in a flush or a preemption
        instead of a dispatch."""
        from ray_tpu.llm import pipeline as pl

        c = self.config
        t_prep0 = time.perf_counter()
        wall0 = time.time()
        prev = self._pipe_inflight
        self._pipe_inflight = None

        # chunk length: adaptive from the measured host round overhead
        # vs chunk wall, capped by the batch's largest remaining budget
        gap_ms = (
            (t_prep0 - self._pipe_last_sync_t) * 1e3
            if self._pipe_last_sync_t is not None else 0.0
        )
        cap = max((self._remaining(r) for r in self.running), default=1)
        n_steps = self._pipe_ctl.next_steps(cap=cap)

        # reserve KV for the chunk's writes (per-row clamped to budget
        # and the max_seq wall — done rows freeze in-graph, so the chunk
        # itself never needs the whole batch shrunk to the shortest row).
        # CRUCIALLY the horizon includes the un-synced in-flight chunk:
        # this dispatch continues from the device carry, which sits up
        # to prev_steps tokens past the host's num_tokens, and a write
        # past the reserved blocks would read block-table padding (0)
        # and clobber another sequence's block 0
        pending = prev["n_steps"] if prev is not None else 0
        try:
            for r in self.running:
                r.seq.ensure_capacity(
                    r.num_tokens + max(1, min(
                        pending + n_steps, self._remaining(r),
                        c.model.max_seq - r.num_tokens,
                    ))
                )
        except NoFreeBlocksError:
            # real cache pressure: preemption is a membership change —
            # land the in-flight chunk first so its tokens aren't lost,
            # then preempt and let the next round rebuild
            if prev is not None:
                self._pipe_inflight = prev
                return self._pipe_flush()
            self._pipe_state = None
            if not self._preempt_one():
                raise  # single running request can't fit: cache too small
            return []

        state = self._pipe_state
        if state is None:
            state = pl.DeviceBatchState.build(self, self.running)
            self._pipe_state = state
            if prev is None:
                self._pipe_stats.rebuilds += 1
        elif not state.refresh_block_tables(self.running):
            # a row outgrew the padded block-table width: flush + rebuild
            if prev is not None:
                self._pipe_inflight = prev
                return self._pipe_flush()
            state = pl.DeviceBatchState.build(self, self.running)
            self._pipe_state = state
            self._pipe_stats.rebuilds += 1

        # dispatch chunk N+1 from the device-resident carry (async: this
        # does NOT wait for chunk N)
        t_dispatch = time.perf_counter()
        toks, lps, n_emit, steps_run, carry = self._run_pipe_chunk(state, n_steps)
        state.adopt_carry(carry)
        host_prep_ms = (t_dispatch - t_prep0) * 1e3
        self._pipe_stats.record_dispatch(n_steps, host_prep_ms)
        self._pipe_inflight = {
            "batch": list(self.running),
            "row_of": dict(state.row_of),
            "toks": toks, "lps": lps, "n_emit": n_emit,
            "steps_run": steps_run, "n_steps": n_steps,
            "sample_mode": state.sample_mode,
            "t_dispatch": t_dispatch, "wall0": wall0, "gap_ms": gap_ms,
        }
        return prev

    def _run_pipe_chunk(self, state, n_steps: int) -> tuple:
        """One pipelined chunk from ``state`` (async): the single call
        site of the masked chunk programs, shared with warmup()."""
        fn = self._pipe_chunk_fn(n_steps, state.sample_mode, state.stop_w)
        lora = None
        if self._lora is not None:
            lora = {"ids": state.lora_ids, **self._lora}
        *out, self.cache = self._call(
            "pipe_chunk",
            (n_steps, state.sample_mode, state.stop_w, state.B_pad, state.bt_width),
            fn,
            self.params, state.tokens, state.positions, state.block_tables,
            state.context_lens, self.cache, state.temps, state.top_ks,
            state.top_ps, state.keys, state.starts, state.max_toks,
            state.done, state.stop_ids, state.stop_on_eos, lora,
        )
        return tuple(out)

    def _pipe_sync(self, rec) -> list[RequestOutput]:
        """Sync one dispatched chunk's tokens and run the host
        bookkeeping ladder for the rows still alive."""
        t0 = time.perf_counter()
        with obs.layer_span("engine.sync"):
            toks = np.asarray(rec["toks"])          # the host sync
            lps = np.asarray(rec["lps"])
            n_emit = np.asarray(rec["n_emit"])
            steps_run = int(rec["steps_run"])
        t1 = time.perf_counter()
        self._pipe_last_sync_t = t1
        sync_wait_ms = (t1 - t0) * 1e3
        chunk_ms = (t1 - rec["t_dispatch"]) * 1e3
        self._pipe_ctl.note_overhead(rec["gap_ms"] + sync_wait_ms)
        self._pipe_ctl.note_chunk(chunk_ms, rec["n_steps"], steps_run)
        self._pipe_stats.record_sync(
            steps_run=steps_run, sync_wait_ms=sync_wait_ms, chunk_ms=chunk_ms
        )
        self._n["decode_steps"] += steps_run
        # rows that finished in an earlier sync are done on device and
        # emitted nothing; only live rows get bookkeeping (their seq is
        # released on finish)
        live = [
            r for r in rec["batch"]
            if r.status == RequestStatus.RUNNING and r.seq is not None
        ]
        if not live:
            return []
        cols = [rec["row_of"][r.request_id] for r in live]
        row_counts = [int(n_emit[j]) for j in cols]
        # a row is live in a step exactly when it emits a token there
        self._n["decode_row_steps"] += sum(row_counts)
        with obs.layer_span("engine.append"):
            outputs = self._append_chunk(
                live, toks[:, cols], lps[:, cols], row_counts=row_counts,
            )
        return self._obs_decode_round(
            live, outputs, rec["wall0"], "engine.decode_chunk",
            rec["n_steps"],
        )

    def _spec_decode_step(self) -> list[RequestOutput]:
        """One speculative round: draft -> one batched verify pass ->
        distribution-preserving accept -> KV rollback.

        Per-row fallback is IN-BATCH: a row whose drafter proposed
        nothing feeds only its current token (draft_len 0), its verify
        logits at column 0 are exactly a decode step's, and acceptance
        emits 1 token sampled from them. Only when no row at all has a
        draft does the round fall back to the plain decode/chunk path —
        paying the (k+1)-wide program for zero drafts would be pure
        overhead."""
        c = self.config
        wall0 = time.time()
        k = c.spec.num_draft_tokens
        batch = list(self.running)

        # draft first (host-side): capacity needs depend on draft lengths
        draft_by_rid: dict[str, list] = {}
        for r in batch:
            # positions fed this round reach num_tokens-1+L and the pass
            # emits up to L+1 tokens: cap L by the max_tokens budget and
            # the hard max_seq wall (RoPE table)
            cap = min(k, self._remaining(r) - 1,
                      c.model.max_seq - r.num_tokens)
            d = (
                self.drafter.propose(
                    r.request_id, r.prompt_token_ids + r.output_token_ids, cap
                )
                if cap > 0 else []
            )
            draft_by_rid[r.request_id] = list(d)
        t_drafted = time.time()
        if not any(draft_by_rid.values()):
            return self._plain_decode_step()

        with obs.layer_span("engine.decode_dispatch"):
            # reserve KV for the drafted positions (verify scatters K/V at
            # num_tokens-1 .. num_tokens-1+L); preempt on real pressure only
            while True:
                try:
                    for r in self.running:
                        r.seq.ensure_capacity(
                            r.num_tokens + len(draft_by_rid[r.request_id])
                        )
                    break
                except NoFreeBlocksError:
                    if not self._preempt_one():
                        raise

            batch = list(self.running)
            drafts = [draft_by_rid[r.request_id] for r in batch]
            B = len(batch)
            B_pad = self._pad_to_bucket(B, c.decode_buckets())
            K1 = k + 1
            num_slots = c.num_blocks * c.block_size

            context_lens = np.zeros(B_pad, np.int32)
            draft_tokens = np.zeros((B_pad, k), np.int32)
            draft_lens = np.zeros(B_pad, np.int32)
            bt = np.zeros(
                (B_pad, self._bt_width([len(r.seq.blocks) for r in batch])),
                np.int32,
            )
            for i, r in enumerate(batch):
                d = drafts[i]
                context_lens[i] = r.num_tokens + len(d)
                draft_tokens[i, : len(d)] = d
                draft_lens[i] = len(d)
                bt[i, : len(r.seq.blocks)] = r.seq.blocks

            if c.mixed_batch:
                # ragged verify (ops/ragged via verify_tokens_ragged): pack
                # only the REAL 1 + draft_len tokens per row instead of
                # padding every row to a k+1 trash-slot rectangle — the
                # per-row bucket waste ROADMAP item 1 named. gather_idx
                # recovers the [B, K+1] logits layout accept_draft expects;
                # positions past a row's draft clamp to its last token and
                # are masked by draft_lens, so duplicated logits are never
                # consumed. Acceptance math downstream is unchanged.
                from ray_tpu.llm.mixed import token_bucket

                T_pad = token_bucket(sum(1 + len(d) for d in drafts))
                p_tokens = np.zeros(T_pad, np.int32)
                p_positions = np.zeros(T_pad, np.int32)
                p_slots = np.full(T_pad, num_slots, np.int32)
                p_lora = np.zeros(T_pad, np.int32)  # per-TOKEN adapter slots
                cu = np.zeros(B_pad + 1, np.int32)
                gather = np.zeros((B_pad, K1), np.int32)
                t = 0
                for i, r in enumerate(batch):
                    row = [
                        r.output_token_ids[-1] if r.output_token_ids
                        else r.prompt_token_ids[-1]
                    ] + drafts[i]
                    pos0 = r.num_tokens - 1  # position of the token being fed
                    p_tokens[t : t + len(row)] = row
                    p_positions[t : t + len(row)] = np.arange(
                        pos0, pos0 + len(row)
                    )
                    for j in range(len(row)):
                        p_slots[t + j] = r.seq.slot(pos0 + j)
                    p_lora[t : t + len(row)] = r.lora_slot
                    gather[i] = t + np.minimum(np.arange(K1), len(row) - 1)
                    t += len(row)
                    cu[i + 1] = t
                cu[B + 1 :] = t  # pad sequences: q_len 0
                logits, self.cache = self._call(
                    "verify", ("ragged", T_pad, bt.shape), self._verify_ragged_fn(),
                    self.params,
                    jnp.asarray(p_tokens),
                    jnp.asarray(p_positions),
                    jnp.asarray(p_slots),
                    jnp.asarray(bt),
                    jnp.asarray(cu),
                    jnp.asarray(context_lens),
                    jnp.asarray(gather),
                    self.cache,
                    self._lora_arg(p_lora),
                )
            else:
                tokens = np.zeros((B_pad, K1), np.int32)
                positions = np.zeros((B_pad, K1), np.int32)
                slots = np.full((B_pad, K1), num_slots, np.int32)  # trash default
                lora_ids = np.zeros(B_pad, np.int32)
                for i, r in enumerate(batch):
                    d = drafts[i]
                    last_tok = (
                        r.output_token_ids[-1] if r.output_token_ids
                        else r.prompt_token_ids[-1]
                    )
                    pos0 = r.num_tokens - 1  # position of the token being fed
                    row = [last_tok] + d
                    tokens[i, : len(row)] = row
                    positions[i, : len(row)] = np.arange(pos0, pos0 + len(row))
                    for j in range(len(row)):
                        slots[i, j] = r.seq.slot(pos0 + j)
                    lora_ids[i] = r.lora_slot

                logits, self.cache = self._call(
                    "verify", (K1, bt.shape), self._verify_fn(K1),
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(positions),
                    jnp.asarray(slots),
                    jnp.asarray(bt),
                    jnp.asarray(context_lens),
                    self.cache,
                    self._lora_arg(lora_ids),
                )

        from ray_tpu.llm.spec.accept import accept_draft

        # acceptance fast paths follow the batch's sampler mode: greedy ->
        # pure argmax comparisons; categorical -> tempered softmax, no
        # full-vocab sort; anything with top-k/top-p -> exact filtering
        batch_mode = self._sample_mode(batch)
        mode = batch_mode if batch_mode in ("greedy", "categorical") else "sample"
        temps = np.array(
            [r.sampling_params.temperature for r in batch] + [1.0] * (B_pad - B),
            np.float32,
        )
        top_ks = np.array(
            [r.sampling_params.top_k for r in batch] + [0] * (B_pad - B), np.int32
        )
        top_ps = np.array(
            [r.sampling_params.top_p for r in batch] + [1.0] * (B_pad - B),
            np.float32,
        )
        keys = [
            jax.random.fold_in(r._key, len(r.output_token_ids)) for r in batch
        ] + [jax.random.key(0)] * (B_pad - B)
        with obs.layer_span("engine.sync"):
            out_toks, out_lps, accepted = accept_draft(
                logits,
                jnp.asarray(draft_tokens),
                jnp.asarray(draft_lens),
                jnp.asarray(temps),
                jnp.asarray(top_ks),
                jnp.asarray(top_ps),
                jnp.stack(keys),
                mode=mode,
            )
            out_toks = np.asarray(out_toks)   # host sync
            out_lps = np.asarray(out_lps)
            accepted = np.asarray(accepted)
        t_verified = time.time()

        # keep accepted+1 tokens per row, run the usual stop ladder
        counts = (accepted[:B] + 1).tolist()
        self._n["decode_steps"] += 1
        self._n["decode_row_steps"] += B
        with obs.layer_span("engine.append"):
            outputs = self._append_chunk(
                batch, out_toks[:B].T, out_lps[:B].T, row_counts=counts
            )

        # KV rollback: blocks reserved for rejected draft positions are
        # returned; the stale K/V device-side is masked by context_lens
        # and rewritten when a real token reaches that position
        for r in batch:
            if r.status == RequestStatus.RUNNING and r.seq is not None:
                r.seq.truncate_to(r.num_tokens)

        # stats + observability
        st = self.spec_stats
        n_drafted = int(draft_lens[:B].sum())
        n_accepted = int(accepted[:B].sum())
        n_emitted = sum(len(o.new_token_ids) for o in outputs)
        st.steps += 1
        st.rows += B
        st.drafted += n_drafted
        st.accepted += n_accepted
        st.emitted += n_emitted
        from ray_tpu.llm.spec.stats import export_spec_stats

        export_spec_stats(st, n_drafted, n_accepted, n_emitted)
        draft_ms = round((t_drafted - wall0) * 1e3, 3)
        verify_ms = round((t_verified - t_drafted) * 1e3, 3)
        extra = {
            r.request_id: {
                "k": k,
                "drafted": int(draft_lens[i]),
                "accepted": int(accepted[i]),
                "draft_ms": draft_ms,
                "verify_ms": verify_ms,
            }
            for i, r in enumerate(batch)
        }
        return self._obs_decode_round(
            batch, outputs, wall0, "engine.spec_round", k, extra=extra
        )

    def _plain_decode_step(self) -> list[RequestOutput]:
        c = self.config
        wall0 = time.time()
        n_steps = self._chunk_steps()
        # grow each sequence by the chunk's slots it can actually USE —
        # overshoot steps past a request's max_tokens write the trash page
        # in-graph (decode_loop `remaining`), so reserving full-chunk KV
        # for a request that finishes next token would preempt a peer to
        # fund blocks nobody reads. Preempt on real cache pressure only.
        while True:
            try:
                for r in self.running:
                    r.seq.ensure_capacity(
                        r.num_tokens + min(n_steps, self._remaining(r))
                    )
                break
            except NoFreeBlocksError:
                if not self._preempt_one():
                    raise  # single running request can't fit: cache too small
        batch = list(self.running)
        B = len(batch)
        B_pad = self._pad_to_bucket(B, c.decode_buckets())
        num_slots = c.num_blocks * c.block_size

        # per-row assembly shared with the pipelined DeviceBatchState
        # (pipeline.assemble_batch_arrays): one source of truth for how
        # a Request becomes batch rows — the bitwise-identity contract
        # between the two paths depends on it
        from ray_tpu.llm.pipeline import assemble_batch_arrays

        a, keys = assemble_batch_arrays(
            batch, B_pad, self._bt_width([len(r.seq.blocks) for r in batch])
        )
        tokens, positions = a["tokens"], a["positions"]
        context_lens, lora_ids, bt = a["context_lens"], a["lora_ids"], a["bt"]

        self._n["decode_steps"] += n_steps
        self._n["decode_row_steps"] += B * n_steps
        if n_steps == 1:
            with obs.layer_span("engine.decode_dispatch"):
                slot_mapping = np.full(B_pad, num_slots, np.int32)
                for i, r in enumerate(batch):
                    slot_mapping[i] = r.seq.slot(int(positions[i]))
                logits, self.cache = self._call(
                    "decode", (B_pad, bt.shape[1]), self._decode,
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(positions),
                    jnp.asarray(slot_mapping),
                    jnp.asarray(bt),
                    jnp.asarray(context_lens),
                    self.cache,
                    self._lora_arg(lora_ids),
                )
            with obs.layer_span("engine.sync"):
                tok, logprob = self._sample_batch(logits[:B], batch)
            with obs.layer_span("engine.append"):
                outputs = self._append_tokens(batch, tok, logprob)
            return self._obs_decode_round(
                batch, outputs, wall0, "engine.decode_chunk", 1,
            )

        # multi-step chunk: decode+sample n_steps times on device, one
        # sync. keys derive from (stable request key, absolute output
        # index — a["starts"]): identical sampling regardless of how
        # co-running requests partition the chunks. remaining = this
        # chunk's keep-capacity (writes past it hit the trash page)
        mode = self._sample_mode(batch)
        with obs.layer_span("engine.decode_dispatch"):
            remaining = np.zeros(B_pad, np.int32)
            for i, r in enumerate(batch):
                remaining[i] = self._remaining(r)
            toks, logprobs, self.cache = self._call(
                "decode_chunk", (n_steps, mode, B_pad, bt.shape[1]),
                self._decode_chunk_fn(n_steps, mode),
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(positions),
                jnp.asarray(bt),
                jnp.asarray(context_lens),
                self.cache,
                jnp.asarray(a["temps"]),
                jnp.asarray(a["top_ks"]),
                jnp.asarray(a["top_ps"]),
                jnp.stack(keys),
                jnp.asarray(a["starts"]),
                jnp.asarray(remaining),
                self._lora_arg(lora_ids),
            )
        with obs.layer_span("engine.sync"):
            # np.asarray is the host sync: the full round trip ends here
            toks_np, logprobs_np = np.asarray(toks), np.asarray(logprobs)
        with obs.layer_span("engine.append"):
            outputs = self._append_chunk(batch, toks_np, logprobs_np)
        return self._obs_decode_round(
            batch, outputs, wall0, "engine.decode_chunk", n_steps,
        )

    # -- sampling + bookkeeping ----------------------------------------------

    def _sample_batch(self, logits, batch: list) -> tuple[np.ndarray, np.ndarray]:
        B = len(batch)
        temps = np.array([r.sampling_params.temperature for r in batch], np.float32)
        top_ks = np.array([r.sampling_params.top_k for r in batch], np.int32)
        top_ps = np.array([r.sampling_params.top_p for r in batch], np.float32)
        # key = fold(stable request key, absolute output index): the same
        # request samples the same stream whether it decodes token-by-token
        # or in chunks, under any co-running load (see _decode_step)
        keys = [
            jax.random.fold_in(r._key, len(r.output_token_ids)) for r in batch
        ]
        toks, logprobs = sample_tokens(
            logits[:B],
            jnp.asarray(temps),
            jnp.asarray(top_ks),
            jnp.asarray(top_ps),
            jnp.stack(keys),
            mode=self._sample_mode(batch),
        )
        return np.asarray(toks), np.asarray(logprobs)

    def _append_chunk(self, batch: list, toks, logprobs,
                      row_counts: Optional[list] = None) -> list[RequestOutput]:
        """Host bookkeeping after a device-side chunk: walk each request's
        token column in order, keep until a stop condition fires, discard
        the overshoot (its KV sits in the request's own unsealed blocks,
        released with the sequence). One RequestOutput per request.
        ``row_counts`` caps the walk per row (speculative decoding: row i
        emitted accepted_i + 1 tokens, the rest of its column is pad)."""
        c = self.config
        outputs = []
        n = toks.shape[0]
        for i, r in enumerate(batch):
            sp = r.sampling_params
            new_toks: list[int] = []
            finished = False
            for s in range(n if row_counts is None else min(n, row_counts[i])):
                t = int(toks[s, i])
                lp = float(logprobs[s, i])
                new_toks.append(t)
                r.output_token_ids.append(t)
                r.cumulative_logprob += lp
                if sp.logprobs:
                    r.token_logprobs.append(lp)
                if not sp.ignore_eos and t == c.eos_token_id:
                    finished, r.finish_reason = True, "stop"
                elif t in sp.stop_token_ids:
                    finished, r.finish_reason = True, "stop"
                elif len(r.output_token_ids) >= sp.max_tokens:
                    finished, r.finish_reason = True, "length"
                elif r.num_tokens >= c.model.max_seq:
                    finished, r.finish_reason = True, "length"
                if finished:
                    break
            num_cached = r.seq.num_cached_tokens if r.seq else 0
            written = r.prompt_token_ids + r.output_token_ids[:-1]
            if finished:
                r.status = RequestStatus.FINISHED
                self.running.remove(r)
                if c.enable_prefix_caching:
                    r.seq.seal_full_blocks(written)
                r.seq.release()
                self.requests.pop(r.request_id, None)
                if self.drafter is not None:
                    self.drafter.release(r.request_id)
            else:
                if c.enable_prefix_caching:
                    # seals only blocks fully covered by `written`; a
                    # mid-chunk boundary crossing is caught here too
                    r.seq.seal_full_blocks(written)
                r.seq.num_tokens = r.num_tokens
            outputs.append(
                RequestOutput(
                    request_id=r.request_id,
                    new_token_ids=new_toks,
                    output_token_ids=list(r.output_token_ids),
                    finished=finished,
                    finish_reason=r.finish_reason,
                    num_cached_tokens=num_cached,
                )
            )
        return outputs

    def _append_tokens(self, batch: list, toks, logprobs) -> list[RequestOutput]:
        """Single-step bookkeeping: the n=1 case of _append_chunk (ONE
        stop-condition/seal/release ladder, not two copies that drift)."""
        return self._append_chunk(
            batch, np.asarray(toks)[None, :], np.asarray(logprobs)[None, :]
        )

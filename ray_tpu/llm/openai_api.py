"""OpenAI-compatible LLM serving on ray_tpu.serve.

Reference analogs: python/ray/llm/_internal/serve/builders/
application_builders.py (build_openai_app), configs/openai_api_models.py
(request/response schemas), deployments/llm/vllm/vllm_deployment.py.
Here the deployment hosts the native engine (llm/engine.py) with a
dedicated engine-loop thread doing continuous batching; requests are
asyncio futures resolved as the loop emits tokens.

Endpoints: /v1/models, /v1/completions, /v1/chat/completions
(stream=true returns a complete SSE transcript; token-level streaming
is available via serve handles — get_app_handle(...).options(stream=True)),
/v1/stats, and the request-tracing surface (ray_tpu.obs): /v1/requests
(flight-recorder listing) + /v1/requests/{id}/trace (per-request span
tree with TTFT/TPOT/queue-wait and span-coverage honesty). Completion
payloads carry the trace_id.
"""

from __future__ import annotations

import asyncio
import json
import queue
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ray_tpu import obs
from ray_tpu.cluster.lockstats import TimedRLock
from ray_tpu.llm.engine import EngineConfig, LLMEngine, RequestOutput
from ray_tpu.llm.sampling import SamplingParams


from ray_tpu.utils.logging import get_logger

logger = get_logger("ray_tpu.llm.openai_api")


def _noop() -> None:
    """Release placeholder for rejected admissions (nothing reserved)."""


# ---------------------------------------------------------------------------
# tokenizer
# ---------------------------------------------------------------------------


class ByteTokenizer:
    """Self-contained fallback tokenizer: UTF-8 bytes + specials. Lets the
    stack run hermetically (no downloaded vocabulary); swap in any object
    with encode/decode/eos_token_id for a real model."""

    PAD, BOS, EOS = 0, 1, 2
    OFFSET = 3

    def __init__(self, vocab_size: int = 512):
        self.vocab_size = vocab_size
        self.eos_token_id = self.EOS

    def encode(self, text: str) -> list:
        return [self.BOS] + [
            min(b + self.OFFSET, self.vocab_size - 1) for b in text.encode()
        ]

    def decode(self, ids: list) -> str:
        bs = bytes(
            i - self.OFFSET for i in ids if self.OFFSET <= i < 256 + self.OFFSET
        )
        return bs.decode(errors="replace")


def default_chat_template(messages: list) -> str:
    """Minimal chat rendering (role-tagged turns + assistant cue)."""
    parts = []
    for m in messages:
        parts.append(f"<|{m['role']}|>\n{m['content']}\n")
    parts.append("<|assistant|>\n")
    return "".join(parts)


# ---------------------------------------------------------------------------
# engine runner: continuous-batching loop + per-request output queues
# ---------------------------------------------------------------------------


class _EngineRunner:
    """Continuous-batching loop + per-request output queues + crash
    recovery.

    Delivery is gated by a per-request ``delivered`` counter over the
    request's FULL output prefix (not the engine's per-round
    new_token_ids): after a crash the engine re-enqueues in-flight
    requests and recomputes their prefix (LLMEngine.recover), so the
    completion id stays idempotent — consumers see each output position
    exactly once, never a lost or duplicated token, whatever the engine
    died and recovered underneath them."""

    # recovery budget: more than MAX_RECOVERIES engine deaths inside
    # RECOVERY_WINDOW_S is a crash loop, not a preemption — fail loudly
    MAX_RECOVERIES = 3
    RECOVERY_WINDOW_S = 30.0

    def __init__(self, engine: LLMEngine, engine_factory=None):
        self.engine = engine
        self._engine_factory = engine_factory  # full-rebuild fallback
        # one lock for the engine and the maps below. Timed always (a
        # request's first seconds hang on it): wait and hold totals are
        # in stats()["runner_lock"], distributions under lock domain
        # "llm_runner" in the controlplane_lock_* histograms
        self.lock = TimedRLock("llm_runner", reentrant=False, always=True)
        self._queues: dict[str, queue.Queue] = {}
        # rid -> {"prompt_ids", "sp", "trace", "delivered"}: enough to
        # re-create the request on a fresh engine AND to dedupe delivery
        self._inflight: dict[str, dict] = {}
        self._recoveries: list[float] = []
        self.num_recoveries = 0
        self._wake = threading.Event()
        self._stop = False
        self._dead: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._loop, name="llm-engine-loop", daemon=True
        )
        self._thread.start()

    def submit(
        self,
        prompt_ids: list,
        sp: SamplingParams,
        request_id: Optional[str] = None,
        trace=None,
        **add_kwargs,
    ) -> tuple[str, queue.Queue]:
        """``add_kwargs`` pass through to ``engine.add_request`` (the
        fleet plane rides lora_id / priority / tenant / slo_tag here)
        and are replayed by the full-rebuild recovery rung.

        Layer span runner.submit (a child of the request's context,
        attr lock_wait_ms), entry to return on the CALLER's thread —
        the replica's event loop, which is blocked for as long. The
        engine's own clock (Request.arrival, queue_wait) starts only at
        add_request below; what the request lost before it is handed to
        the engine as pre_engine_wait_s."""
        q: queue.Queue = queue.Queue()
        with obs.layer_span("runner.submit", ctx=trace) as span, self.lock:
            span.attrs["lock_wait_ms"] = self.lock.last_wait_s * 1e3
            waited = time.time() - span.start
            # checked under the lock: the death handler drains _queues under
            # it, so an insert after the drain would hang its caller forever
            if self._dead is not None:
                raise RuntimeError(
                    f"engine loop died: {self._dead!r}"
                ) from self._dead
            rid = self.engine.add_request(
                prompt_ids, sp, request_id=request_id, trace=trace,
                **add_kwargs,
            )
            self.engine.requests[rid].pre_engine_wait_s = waited
            self._queues[rid] = q
            # "tokens" holds the DELIVERED output prefix (not just a
            # count): the full-rebuild recovery rung seeds the fresh
            # engine's request with it, so even unseeded sampling can
            # never splice two different continuations
            self._inflight[rid] = {
                "prompt_ids": list(prompt_ids), "sp": sp, "trace": trace,
                "tokens": [], "kwargs": dict(add_kwargs),
            }
        self._wake.set()
        return rid, q

    def abort(self, rid: str) -> None:
        with self.lock:
            self.engine.abort_request(rid)
            q = self._queues.pop(rid, None)
            self._inflight.pop(rid, None)
        if q is not None:
            q.put(None)

    def _deliver(self, out: RequestOutput) -> None:
        """Queue-put with idempotent delivery: only output positions past
        the per-request delivered watermark ship."""
        import dataclasses as _dc

        q = self._queues.get(out.request_id)
        rec = self._inflight.get(out.request_id)
        if rec is not None:
            new = list(out.output_token_ids[len(rec["tokens"]):])
            rec["tokens"].extend(new)
            out = _dc.replace(out, new_token_ids=new)
        if q is None:
            return
        if out.new_token_ids or out.finished:
            q.put(out)
        if out.finished:
            self._queues.pop(out.request_id, None)
            self._inflight.pop(out.request_id, None)

    def _loop(self) -> None:
        while not self._stop:
            with self.lock:
                busy = self.engine.has_unfinished()
            if not busy:
                self._wake.wait(timeout=0.2)
                self._wake.clear()
                continue
            try:
                # layer span runner.step = runner.lock_wait + engine.step
                # + runner.deliver: how long one turn holds the lock, and
                # whether the loop gets it back at once
                with obs.layer_span("runner.step"):
                    with obs.layer_span("runner.lock_wait"):
                        self.lock.acquire()
                    try:
                        outputs = self.engine.step()
                        with obs.layer_span("runner.deliver"):
                            for out in outputs:
                                self._deliver(out)
                    finally:
                        self.lock.release()
            except BaseException as e:  # a wedged step must not hang callers
                if not self._stop and self._try_recover(e):
                    continue
                logger.exception(
                    "engine loop failed; failing all in-flight requests"
                )
                self._dead = e
                with self.lock:
                    queues = list(self._queues.values())
                    self._queues.clear()
                    self._inflight.clear()
                for q in queues:
                    q.put(e)
                return

    def _try_recover(self, exc: BaseException) -> bool:
        """Engine crash/preemption recovery ladder: (1) requeue in-flight
        requests on the surviving engine (clean preemption), (2) requeue
        with a rebuilt KV cache (unknown crash), (3) fresh engine from the
        factory with every request re-created (engine object torn).
        Bounded by the recovery budget so a deterministic crash loop still
        fails fast."""
        now = time.time()
        self._recoveries = [
            t for t in self._recoveries if now - t < self.RECOVERY_WINDOW_S
        ]
        if len(self._recoveries) >= self.MAX_RECOVERIES:
            return False
        self._recoveries.append(now)
        self.num_recoveries += 1
        try:
            from ray_tpu.chaos.harness import EnginePreempted

            clean = isinstance(exc, EnginePreempted)
        except Exception:  # noqa: BLE001
            clean = False
        t0 = time.time()
        requeued: Optional[list] = None
        try:
            with self.lock:
                requeued = self.engine.recover(rebuild_kv=not clean)
        except BaseException:  # noqa: BLE001 — engine object itself is torn
            logger.exception("engine.recover failed; trying full rebuild")
            if self._engine_factory is None:
                return False
            try:
                with self.lock:
                    old = self.engine
                    self.engine = self._engine_factory()
                    self.engine.model_tag = old.model_tag
                    # re-create every in-flight request on the fresh
                    # engine WITH its delivered prefix restored: admission
                    # prefills prompt + outputs (the preemption-recompute
                    # contract), so the continuation extends exactly what
                    # the consumer already received — not a fresh sample
                    # spliced at the watermark
                    for rid, rec in self._inflight.items():
                        self.engine.add_request(
                            rec["prompt_ids"], rec["sp"], request_id=rid,
                            trace=rec["trace"], **rec.get("kwargs", {}),
                        )
                        self.engine.requests[rid].output_token_ids = list(
                            rec["tokens"]
                        )
                    requeued = list(self._inflight)
            except BaseException:  # noqa: BLE001
                logger.exception("engine rebuild failed")
                return False
        logger.warning(
            "engine loop recovered from %r (%d request(s) re-enqueued)",
            exc, len(requeued or ()),
        )
        try:
            from ray_tpu import obs

            obs.get_recorder().record(
                "engine.runner_recover", t0, time.time(),
                attrs={"cause": f"{type(exc).__name__}: {exc}"[:200],
                       "requeued": len(requeued or ()),
                       "clean_preemption": clean},
                status="error",
            )
        except Exception:  # noqa: BLE001
            pass
        self._wake.set()
        return True

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------


@dataclass
class LLMConfig:
    """Reference analog: ray.llm LLMConfig (server_models.py)."""

    model_id: str = "llama-tiny"
    engine: EngineConfig = field(default_factory=EngineConfig)
    tokenizer: Any = None  # encode/decode/eos_token_id; ByteTokenizer default
    params: Any = None     # model weights pytree; random-init if None
    seed: int = 0
    # admission control / load shedding (llm/admission.py); None = an
    # unbounded controller that still supports graceful drain
    admission: Any = None
    # disaggregated prefill/decode (llm/disagg): a DisaggConfig (or dict)
    # replaces the single engine with prefill+decode pools behind the
    # same OpenAI surface; its .engine defaults to `engine` above
    disagg: Any = None


class LLMServer:
    """Serve deployment hosting one engine (reference: VLLMDeployment)."""

    def __init__(self, config: LLMConfig):
        from ray_tpu.llm.admission import AdmissionConfig, AdmissionController

        self.config = config
        self.tokenizer = config.tokenizer or ByteTokenizer(
            config.engine.model.vocab_size
        )
        config.engine.eos_token_id = getattr(self.tokenizer, "eos_token_id", 2)
        self.orchestrator = None
        self.runner = None
        if config.disagg is not None:
            # disaggregated mode: prefill+decode pools replace the single
            # engine; submit/abort/stats route through the orchestrator
            from ray_tpu.llm.disagg import DisaggConfig, DisaggOrchestrator

            dcfg = config.disagg
            if isinstance(dcfg, dict):
                dcfg = DisaggConfig(**{"engine": config.engine, **dcfg})
            self.orchestrator = DisaggOrchestrator(
                dcfg, params=config.params, seed=config.seed,
                model_tag=config.model_id,
            )
        else:
            engine = LLMEngine(
                config.engine, params=config.params, seed=config.seed
            )
            engine.model_tag = config.model_id  # SLO histogram label

            def _rebuild_engine():
                # crash-recovery fallback: fresh engine, same weights/seed
                return LLMEngine(config.engine, params=config.params,
                                 seed=config.seed)

            self.runner = _EngineRunner(engine, engine_factory=_rebuild_engine)
        acfg = config.admission
        if isinstance(acfg, dict):
            acfg = AdmissionConfig(**acfg)
        # admission reservation state: see _admission_check
        self._admit_lock = threading.Lock()
        self._admit_reserved = 0
        self.admission = AdmissionController(
            acfg or AdmissionConfig(), model_tag=config.model_id
        )

    @property
    def engine(self) -> LLMEngine:
        if self.orchestrator is not None:
            # config access (eos, max_seq) — pools share one EngineConfig
            return self.orchestrator._decode[0].engine
        # via the runner: crash recovery may have swapped in a rebuilt one
        return self.runner.engine

    def __del__(self):
        try:
            self._stop_engines()
        except Exception:
            pass

    def _stop_engines(self):
        if self.orchestrator is not None:
            self.orchestrator.shutdown()
        if self.runner is not None:
            self.runner.shutdown()

    def shutdown(self):
        """Replica graceful-shutdown hook (serve.replica.prepare_shutdown
        calls this after its own in-flight drain): stop admission, give
        the engine a short drain, stop the loop."""
        try:
            self.drain(timeout_s=5.0)
        finally:
            self._stop_engines()

    def drain(self, timeout_s: float = 30.0) -> dict:
        """Maintenance-event drain: new requests get 503 + Retry-After
        while in-flight requests run to completion (bounded wait)."""
        self.admission.start_drain()
        deadline = time.time() + timeout_s
        if self.orchestrator is not None:
            while time.time() < deadline and self.orchestrator.has_unfinished():
                time.sleep(0.05)
            # count the orchestrator's inflight set, not engine queue
            # depths: a handoff in transit sits on NO engine, and a drain
            # that misses it reports clean while losing the request
            left = self.orchestrator.num_inflight()
            return {"drained": left == 0, "inflight": left}
        while time.time() < deadline:
            with self.runner.lock:
                if not self.engine.has_unfinished():
                    break
            time.sleep(0.05)
        with self.runner.lock:
            left = len(self.engine.waiting) + len(self.engine.running)
        return {"drained": left == 0, "inflight": left}

    # -- request plumbing -----------------------------------------------------

    def _sampling_from_body(self, body: dict) -> SamplingParams:
        return SamplingParams(
            max_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 1.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            seed=body.get("seed"),
            logprobs=bool(body.get("logprobs", False)),
        )

    async def _run(self, prompt_ids: list, sp: SamplingParams,
                   request_id: Optional[str] = None,
                   on_enqueued: Optional[Callable[[], None]] = None):
        """Async generator of RequestOutput. The ambient TraceContext is
        captured HERE (the caller's asyncio task) and handed to the
        engine explicitly — the engine loop is a separate thread where
        the contextvar is invisible."""
        loop = asyncio.get_running_loop()
        try:
            if self.orchestrator is not None:
                rid, q = self.orchestrator.submit(
                    prompt_ids, sp, request_id=request_id, trace=obs.current()
                )
                aborter = self.orchestrator.abort
            else:
                rid, q = self.runner.submit(
                    prompt_ids, sp, request_id=request_id, trace=obs.current()
                )
                aborter = self.runner.abort
        finally:
            # the admission reservation hands over to the real queue entry
            # here (or dies with a failed submit) — never held past this
            if on_enqueued is not None:
                on_enqueued()
        try:
            while True:
                out: Optional[RequestOutput] = await loop.run_in_executor(None, q.get)
                if out is None:
                    return
                if isinstance(out, BaseException):  # engine loop died
                    raise RuntimeError("engine loop failed") from out
                yield out
                if out.finished:
                    return
        finally:
            aborter(rid)

    async def _generate_text(self, prompt_ids: list, sp: SamplingParams,
                             request_id: Optional[str] = None,
                             on_enqueued: Optional[Callable[[], None]] = None):
        toks, reason = [], None
        async for out in self._run(prompt_ids, sp, request_id=request_id,
                                   on_enqueued=on_enqueued):
            toks = out.output_token_ids
            reason = out.finish_reason
        # strip eos token from the visible text
        if toks and toks[-1] == self.engine.config.eos_token_id:
            toks = toks[:-1]
        return self.tokenizer.decode(toks), toks, reason

    # -- handle-level streaming (token deltas) --------------------------------

    async def generate_stream(self, prompt: str, **kwargs):
        """Async generator of text deltas (serve streaming handles).

        Admission applies here too: a draining/overloaded server must not
        keep admitting via the streaming side door (that would hold
        has_unfinished() true and make every drain time out). Streams
        can't return an error payload, so rejection raises."""
        rej, admit_done = self._admission_check()
        if rej is not None:
            err = rej["error"]
            raise RuntimeError(
                f"admission rejected ({err['code']}): {err['message']}; "
                f"retry after {err['retry_after']}s"
            )
        try:
            sp = self._sampling_from_body(kwargs)
            ids = self.tokenizer.encode(prompt)
        except BaseException:
            admit_done()  # the reservation must not outlive a dead arrival
            raise
        try:
            async for delta in self._stream_deltas(ids, sp, admit_done):
                yield delta
        finally:
            # idempotent backstop: covers a generator abandoned before its
            # first iteration ever reached _run's submit (fires on close/GC)
            admit_done()

    async def _stream_deltas(self, ids, sp, admit_done):
        sent = ""
        first_mark = False
        async for out in self._run(ids, sp, on_enqueued=admit_done):
            toks = out.output_token_ids
            if toks and toks[-1] == self.engine.config.eos_token_id:
                toks = toks[:-1]
            text = self.tokenizer.decode(toks)
            # hold back a trailing replacement char: it's usually half of a
            # multi-byte sequence whose tail arrives with the next token
            if not out.finished:
                text = text.rstrip("�")
            if text.startswith(sent) and len(text) > len(sent):
                if not first_mark:
                    # streaming first-token mark: the client-visible TTFT
                    # point (engine TTFT excludes queue/decoding overhead
                    # this side of the loop thread)
                    first_mark = True
                    if obs.current() is not None:
                        now = time.time()
                        try:
                            obs.get_recorder().record(
                                "api.stream_first_token", now, now,
                                attrs={"tokens": len(toks)},
                            )
                        except Exception:  # noqa: BLE001
                            pass
                yield text[len(sent):]
                sent = text

    # -- HTTP surface ---------------------------------------------------------

    async def __call__(self, request):
        path, method = request.path, request.method
        if path.rstrip("/") == "/v1/models" and method == "GET":
            return self.models()
        if path.rstrip("/") == "/v1/stats" and method == "GET":
            return self.stats()
        if path.rstrip("/") == "/v1/requests" and method == "GET":
            return self.list_requests()
        parts = [p for p in path.split("/") if p]
        if (len(parts) == 4 and parts[:2] == ["v1", "requests"]
                and parts[3] == "trace" and method == "GET"):
            return self.request_trace(parts[2])
        if path.rstrip("/") == "/v1/completions" and method == "POST":
            return await self.completions(request.json())
        if path.rstrip("/") == "/v1/chat/completions" and method == "POST":
            return await self.chat_completions(request.json())
        if path.rstrip("/") == "/v1/drain" and method == "POST":
            # maintenance trigger: stop admission, finish in-flight work.
            # Off-loop: drain() polls synchronously for up to timeout_s,
            # and blocking the replica's event loop would freeze the very
            # in-flight responses the drain is waiting on (plus health
            # pings — the controller would kill a healthily-draining
            # replica)
            body = request.json() or {}
            timeout_s = float(body.get("timeout_s", 30.0))
            return await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.drain(timeout_s=timeout_s)
            )
        return {"error": {"message": f"no route {method} {path}", "code": 404}}

    # -- flight recorder surface ----------------------------------------------

    def list_requests(self, limit: int = 100) -> dict:
        """Flight-recorder listing: the last N traced requests (newest
        first) with trace ids, root span, e2e, span counts."""
        rec = obs.get_recorder()
        return {
            "object": "list",
            "data": rec.traces(limit=limit),
            "dropped_traces": rec.num_dropped_traces,
            "dropped_spans": rec.num_dropped_spans,
        }

    # span cap for one trace response: a runaway generation's trace must
    # not build a response that blows past RPC framing / HTTP sanity
    TRACE_MAX_SPANS = 2048

    def request_trace(self, request_id: str, max_spans: Optional[int] = None) -> dict:
        """Full span tree for one request (by engine/completion request
        id, or directly by trace id), plus e2e + span-coverage honesty.
        Bounded: at most ``max_spans`` spans (earliest first) with an
        explicit ``truncated`` flag."""
        cap = self.TRACE_MAX_SPANS if max_spans is None else int(max_spans)
        rec = obs.get_recorder()
        trace_id = rec.find_by_request(request_id) or request_id
        spans = rec.get(trace_id)
        if not spans:
            return {"error": {
                "message": f"no recorded trace for request {request_id!r} "
                "(evicted from the flight recorder, or never traced)",
                "type": "not_found_error",
                "code": 404,
            }}
        summary = rec.summary(trace_id) or {}
        total = len(spans)
        truncated = total > cap
        if truncated:
            spans = sorted(spans, key=lambda s: s.start)[:cap]
        return {
            "request_id": request_id,
            "trace_id": trace_id,
            **{k: v for k, v in summary.items() if k != "trace_id"},
            "spans": [s.to_dict() for s in spans],
            "truncated": truncated,
            "total_spans": total,
        }

    def stats(self) -> dict:
        """Engine scheduling/KV state + (when speculative decoding is on)
        acceptance-rate stats — the serving-side view of
        LLMEngine.stats(), so operators can read draft quality (and in
        disaggregated mode the per-pool + transfer-plane picture, incl.
        the prefix-cache hit rate the decode pick consumes) without
        scraping Prometheus.

        Never takes the runner's lock (under load that is a wait of
        whole engine steps, and this is the surface one reads under
        load): `counters` is LLMEngine.counters(), `trace` the
        process's layer spans (count and busy seconds by name),
        `runner_lock` the lock's own wait and hold totals."""
        from ray_tpu.util.metrics import snapshot_meta

        if self.orchestrator is not None:
            out = {
                "model_id": self.config.model_id,
                "mode": "disagg",
                **self.orchestrator.stats(),
            }
            out["admission"] = self.admission.stats()
            # snapshot timestamp + process-epoch id (the telemetry plane's
            # restart-detection header; free here via the same API)
            out["telemetry"] = snapshot_meta()
            return out
        engine = self.engine
        out = {"model_id": self.config.model_id}
        for _ in range(8):
            # engine.stats() copies a few small dicts that the loop
            # thread may be resizing mid-step: read again
            try:
                out.update(engine.stats())
                break
            except RuntimeError:
                continue
        out["counters"] = engine.counters()
        out["trace"] = obs.layer_counters()
        out["runner_lock"] = self.runner.lock.totals()
        out["admission"] = self.admission.stats()
        out["engine_recoveries"] = self.runner.num_recoveries
        out["telemetry"] = snapshot_meta()
        return out

    def _admission_check(self) -> tuple[Optional[dict], Callable[[], None]]:
        """Load-shedding decision for one arriving request.

        Returns ``(rejection, release)``. On admit (rejection None) a
        RESERVATION is counted against the queue depth until ``release()``
        runs (idempotent; _run fires it once the request is actually in
        the engine queue, the handler's finally is the backstop).
        Without the reservation, N concurrent arrivals could ALL pass the
        depth check before any of them enqueues — the check-then-enqueue
        race that let a 24-wide burst sail past max_queue_depth=3
        un-shed (caught tuning the overload chaos test)."""
        with self._admit_lock:
            if self.orchestrator is not None:
                depths = self.orchestrator.queue_depths()
                rej = self.admission.check(
                    num_waiting=sum(depths["prefill"]) + self._admit_reserved,
                    num_running=sum(depths["decode"]),
                )
            else:
                with self.runner.lock:
                    num_waiting = len(self.engine.waiting)
                    num_running = len(self.engine.running)
                rej = self.admission.check(
                    num_waiting=num_waiting + self._admit_reserved,
                    num_running=num_running,
                )
            if rej is not None:
                return rej, _noop
            self._admit_reserved += 1

        released = [False]

        def release() -> None:
            if not released[0]:
                released[0] = True
                with self._admit_lock:
                    self._admit_reserved -= 1

        return None, release

    def models(self) -> dict:
        return {
            "object": "list",
            "data": [
                {
                    "id": self.config.model_id,
                    "object": "model",
                    "owned_by": "ray_tpu",
                    "max_model_len": self.engine.config.model.max_seq,
                }
            ],
        }

    @staticmethod
    def _invalid_request(e: Exception) -> dict:
        """OpenAI-style error payload for bad sampling knobs: admission
        validation (SamplingParams) must surface as a client error, not
        an unhandled 500 from the serve layer."""
        return {
            "error": {
                "message": str(e),
                "type": "invalid_request_error",
                "code": 400,
            }
        }

    async def completions(self, body: dict) -> Any:
        rej, admit_done = self._admission_check()
        if rej is not None:
            return rej
        try:
            return await self._completions_admitted(body, admit_done)
        finally:
            # idempotent backstop: a no-op when _run already handed the
            # reservation to the engine queue; otherwise (parse error,
            # encode failure, empty prompt list) the reservation dies here
            admit_done()

    async def _completions_admitted(self, body: dict, admit_done) -> Any:
        try:
            sp = self._sampling_from_body(body)
        except (ValueError, TypeError) as e:
            return self._invalid_request(e)
        prompts = body.get("prompt", "")
        if not isinstance(prompts, list):
            prompts = [prompts]
        rid = f"cmpl-{uuid.uuid4().hex[:24]}"
        # request root span: engine request ids derive from the completion
        # id, so GET /v1/requests/{id}/trace resolves the whole trace
        with obs.span("api.completions", attrs={
            "request_id": rid,
            "model": body.get("model", self.config.model_id),
            "endpoint": "/v1/completions",
            "num_prompts": len(prompts),
        }) as ctx:
            id_lists = [self.tokenizer.encode(str(p)) for p in prompts]
            # one choice per prompt, generated concurrently via the engine;
            # the single admission reservation rides the first submit
            results = await asyncio.gather(
                *[
                    self._generate_text(
                        ids, sp,
                        request_id=rid if len(id_lists) == 1 else f"{rid}-{i}",
                        on_enqueued=admit_done if i == 0 else None,
                    )
                    for i, ids in enumerate(id_lists)
                ]
            )
            n_prompt = sum(len(ids) for ids in id_lists)
            n_out = sum(len(toks) for _, toks, _ in results)
            payload = {
                "id": rid,
                "object": "text_completion",
                "created": int(time.time()),
                "model": body.get("model", self.config.model_id),
                "trace_id": ctx.trace_id,
                "choices": [
                    {
                        "index": i,
                        "text": text,
                        "finish_reason": reason,
                        "logprobs": None,
                    }
                    for i, (text, _toks, reason) in enumerate(results)
                ],
                "usage": {
                    "prompt_tokens": n_prompt,
                    "completion_tokens": n_out,
                    "total_tokens": n_prompt + n_out,
                },
            }
        if body.get("stream"):
            return _sse_transcript(payload, "text_completion")
        return payload

    async def chat_completions(self, body: dict) -> Any:
        rej, admit_done = self._admission_check()
        if rej is not None:
            return rej
        try:
            return await self._chat_completions_admitted(body, admit_done)
        finally:
            admit_done()  # idempotent backstop, see completions()

    async def _chat_completions_admitted(self, body: dict, admit_done) -> Any:
        try:
            sp = self._sampling_from_body(body)
        except (ValueError, TypeError) as e:
            return self._invalid_request(e)
        messages = body.get("messages", [])
        rid = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        with obs.span("api.chat_completions", attrs={
            "request_id": rid,
            "model": body.get("model", self.config.model_id),
            "endpoint": "/v1/chat/completions",
        }) as ctx:
            prompt = default_chat_template(messages)
            ids = self.tokenizer.encode(prompt)
            text, toks, reason = await self._generate_text(
                ids, sp, request_id=rid, on_enqueued=admit_done
            )
            payload = {
                "id": rid,
                "object": "chat.completion",
                "created": int(time.time()),
                "model": body.get("model", self.config.model_id),
                "trace_id": ctx.trace_id,
                "choices": [
                    {
                        "index": 0,
                        "message": {"role": "assistant", "content": text},
                        "finish_reason": reason,
                    }
                ],
                "usage": {
                    "prompt_tokens": len(ids),
                    "completion_tokens": len(toks),
                    "total_tokens": len(ids) + len(toks),
                },
            }
        if body.get("stream"):
            return _sse_transcript(payload, "chat.completion.chunk")
        return payload


def _sse_transcript(payload: dict, obj: str) -> str:
    """Full-assembly SSE body (incremental HTTP streaming: see module doc)."""
    choice = payload["choices"][0]
    text = choice.get("text", choice.get("message", {}).get("content", ""))
    events = []
    chunk = dict(payload, object=obj)
    if obj.startswith("chat"):
        chunk = dict(chunk)
        chunk["choices"] = [
            {"index": 0, "delta": {"role": "assistant", "content": text},
             "finish_reason": choice["finish_reason"]}
        ]
    events.append(f"data: {json.dumps(chunk)}")
    events.append("data: [DONE]")
    return "\n\n".join(events) + "\n\n"


def build_openai_app(
    llm_config: LLMConfig,
    *,
    name: str = "llm",
    route_prefix: str = "/",
    num_replicas: int = 1,
    max_ongoing_requests: int = 64,
):
    """Deploy an OpenAI-compatible app; returns the ingress handle
    (reference: build_openai_app, application_builders.py)."""
    from ray_tpu import serve

    dep = serve.deployment(
        LLMServer,
        name=f"LLMServer:{llm_config.model_id}",
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
    )
    return serve.run(dep.bind(llm_config), name=name, route_prefix=route_prefix)

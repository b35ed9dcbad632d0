"""ray_tpu.llm.spec — speculative decoding for the paged-KV engine.

The r06 roofline profile puts decode firmly bandwidth-bound: every
generated token streams the whole model from HBM to produce one row of
logits. Speculative decoding converts k of those bandwidth-bound steps
into ONE compute-dense verification pass (models/llama_decode.
verify_tokens — the prefill path over a k+1-token suffix), so the
weights are read once per k+1 tokens instead of once per token, at
unchanged output distribution.

Pieces:

 * drafter.py  — proposal sources: a model-free prompt-lookup/n-gram
   drafter over the request's token history, and a small-draft-model
   drafter reusing models/llama_decode with its own KV cache;
 * accept.py   — distribution-preserving acceptance/rejection sampling
   (greedy short-circuit when the whole batch is greedy) + the
   resample-on-reject bonus token;
 * config.py   — SpecConfig (EngineConfig.spec), drafter construction;
 * stats.py    — acceptance-rate accounting -> engine.stats(),
   Prometheus counters/gauges, dashboard timeline spans.

KV bookkeeping: drafted K/V lands in the sequence's own unsealed blocks;
rejected positions are rolled back host-side with
SequenceBlocks.truncate_to (kv_cache.py) — device-side the stale slots
are simply masked by context_lens and overwritten by the next real
token at that position.
"""

from ray_tpu.llm.spec.accept import accept_draft
from ray_tpu.llm.spec.config import SpecConfig
from ray_tpu.llm.spec.drafter import (
    Drafter,
    DraftModelDrafter,
    PromptLookupDrafter,
)
from ray_tpu.llm.spec.stats import SpecStats

__all__ = [
    "Drafter",
    "DraftModelDrafter",
    "PromptLookupDrafter",
    "SpecConfig",
    "SpecStats",
    "accept_draft",
]

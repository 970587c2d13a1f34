"""LLM serving plane: batched prefill + continuous batching.

The training side of the platform runs one jitted step over a fixed batch;
serving traffic does not arrive that way — requests come and go, prompts
have wildly different lengths, and throughput comes from keeping every
decode slot busy (the two mechanisms the Gemma-on-TPU study credits with
most TPU serving throughput: single-pass prefill and continuous batching).

- ``scheduler``: request admission — a bounded FIFO with backpressure.
- ``engine``: ``PagedInferenceEngine``, the fixed-capacity slot batch over
  a paged KV pool. New requests are prefilled (one forward pass per
  bucketed prompt chunk, not one per token) into the pool's blocks and
  take a free slot of the live decode batch; finished slots free on
  EOS/limit; one jitted decode step advances every active slot at once and
  the loop idles when all slots drain.
- ``kv_cache``: the paged KV block pool + ref-counted radix prefix tree
  behind the engine — per-request page tables, prefill skipped for cached
  prompt prefixes, LRU eviction of unreferenced blocks under memory
  pressure.
- ``spec``: draft-free speculative decoding — n-gram prompt-lookup
  proposals verified by one batched multi-position forward; greedy rows
  emit up to ``spec_tokens+1`` tokens per decode step, bit-identical to
  non-speculative decode (acceptance is exact-match against the model's
  own argmax).
- ``tenancy``: the multi-tenant SLO layer — tenant policies (priority
  tiers, token-bucket rate limits, KV-block quotas, queue caps) enforced
  at admission, weighted fair queueing in the scheduler, chunked-prefill
  interleaving in the engine so one tenant's 32k-token prompt cannot
  starve another tenant's token stream.
- ``streams``: server-streamed delivery over the RPC plane — chunked
  long-poll frames whose position IS the gateway failover fence, with
  poll-cadence liveness (disconnected clients reaped within one decode
  round), bounded consumer buffers with backpressure-or-shed, and
  mid-stream ``InferCancel``.

Expose over the control plane with ``lzy_tpu.service.inference`` (the
``--serve-model`` flag of ``lzy_tpu.service.serve``).
"""

from lzy_tpu.serving.engine import EngineStats, PagedInferenceEngine
from lzy_tpu.serving.kv_cache import (
    BlockPool, KVCacheStats, NoFreeBlocks, RadixCache)
from lzy_tpu.serving.kv_tier import HostKVTier, StorageKVTier
from lzy_tpu.serving.scheduler import (
    AdmissionError, PromptTooLong, QuotaExceeded, Request, RequestQueue)
from lzy_tpu.serving.spec import NgramProposer
from lzy_tpu.serving.streams import StreamSession, StreamSessionManager
from lzy_tpu.serving.tenancy import (
    SloLimiter, TenantPolicy, TenantTable, TokenBucket)
from lzy_tpu.serving.disagg import DecodeEngine, PrefillEngine

__all__ = [
    "AdmissionError",
    "BlockPool",
    "DecodeEngine",
    "EngineStats",
    "HostKVTier",
    "KVCacheStats",
    "NgramProposer",
    "NoFreeBlocks",
    "PagedInferenceEngine",
    "PrefillEngine",
    "PromptTooLong",
    "QuotaExceeded",
    "RadixCache",
    "Request",
    "RequestQueue",
    "SloLimiter",
    "StorageKVTier",
    "StreamSession",
    "StreamSessionManager",
    "TenantPolicy",
    "TenantTable",
    "TokenBucket",
]

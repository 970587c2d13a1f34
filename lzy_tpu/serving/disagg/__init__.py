"""Disaggregated prefill/decode serving (the two-pool mode).

Monolithic replicas interleave prefill and decode on one device: a
single long prompt stalls the decode step of every co-resident request
for the whole prefill burst. Disaggregation splits the lifecycle across
two pools connected by the channels data plane:

    prefill pool ──(KV block manifest / direct stream)──► decode pool

- :class:`PrefillEngine` — admits prompt-only work, runs the standard
  chunked + radix-cached prefill, and finishes each request with a
  host-side :class:`~lzy_tpu.channels.kv_transfer.KVBlockExport` of the
  prompt's whole-block KV prefix attached (``request.kv_export``).
- ``engine.kv_io.export_kv`` / ``import_kv`` (``serving/kv_io.py``) — the
  pool-level halves: export pins tree blocks for the gather (refcounts make
  a concurrent eviction impossible), import allocates fresh blocks
  (evicting LRU unreferenced ones under pressure — never a resident
  request's) and registers the prefix in the destination radix tree.
- :class:`DecodeEngine` — a paged engine with an import queue drained
  at the top of every scheduling round, strictly before admissions.

The gateway-side orchestration (pool routing, transfer skip on expected
cache hits, re-prefill fallback) lives in ``lzy_tpu/gateway/disagg.py``;
the wire format and transports in ``lzy_tpu/channels/kv_transfer.py``.
Every piece degrades to "decode replica prefills locally" — a lost
transfer costs FLOPs, never correctness.
"""

from lzy_tpu.serving.disagg.decode import DecodeEngine
from lzy_tpu.serving.disagg.prefill import PrefillEngine

__all__ = [
    "DecodeEngine",
    "PrefillEngine",
]

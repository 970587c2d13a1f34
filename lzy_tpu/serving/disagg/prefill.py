"""Prefill-pool engine: admits prompt-only work, exports KV blocks.

A prefill replica runs the SAME chunked, radix-cached prefill path as a
monolithic ``PagedInferenceEngine`` — bucketed chunks, prefix-cache skip
of already-seen headers, block-budget admission — but never decodes: the
request finishes the moment its prompt's KV blocks are resident, with
the export snapshot attached for the gateway to ship to a decode
replica. That is the whole point of disaggregation: a 4k-token prompt
occupies this pool's device for its prefill passes and nothing else,
so it can never stall another request's inter-token latency (decode
lives in a different pool entirely).

Determinism note: the first *generated* token is deliberately NOT
produced here. The decode replica prefills the (sub-block) prompt tail
itself and samples the first token from its own rng stream — exactly
the draw order of a monolithic engine — which is what keeps
disaggregated output bit-identical, greedy and sampled.
"""

from __future__ import annotations


from lzy_tpu.serving.engine import _REQUESTS, PagedInferenceEngine
from lzy_tpu.serving.scheduler import Request
from lzy_tpu.utils.log import get_logger
from lzy_tpu.utils.metrics import REGISTRY

_LOG = get_logger(__name__)

_EXPORTS = REGISTRY.counter(
    "lzy_disagg_kv_exports_total",
    "prompt prefixes exported by prefill replicas")
_EXPORT_BLOCKS = REGISTRY.counter(
    "lzy_disagg_kv_export_blocks_total",
    "KV blocks exported by prefill replicas")
# deliberately NOT lzy_inference_ttft_seconds: that histogram is the
# fleet's client-facing submit→first-token latency, and prefill-pool
# "KV ready" samples would skew its distribution in one shared registry
_PREFILL_SECONDS = REGISTRY.histogram(
    "lzy_disagg_prefill_seconds",
    "prompt admission → KV blocks resident on a prefill replica",
    buckets=(0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0, 60.0))


class PrefillEngine(PagedInferenceEngine):
    """``PagedInferenceEngine`` that stops at the end of prefill.

    ``submit(prompt)`` admits a prompt-only request; when it finishes,
    ``request.kv_export`` holds the :class:`KVBlockExport` snapshot of
    the prompt's whole-block KV prefix (or None for sub-block prompts —
    nothing worth transferring). ``request.tokens`` stays empty: this
    engine generates nothing.
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._exports = 0
        self._export_blocks = 0

    def submit(self, prompt, *, request_id=None, deadline_s=None,
               tenant="default", priority=None, **_ignored) -> Request:
        # max_new_tokens=1 satisfies the base validation (prompt + 1 must
        # fit the cache) without reserving decode room that will never be
        # used; tenant/priority ride through so the prefill pool's WFQ
        # queue and KV quotas see the same identity the decode pool does
        return super().submit(prompt, max_new_tokens=1,
                              request_id=request_id, deadline_s=deadline_s,
                              tenant=tenant, priority=priority)

    def _finish_prefill(self, slot: int, req: Request, first: int) -> None:
        """Prefill tail: snapshot the prompt's KV blocks to the host
        while the slot still pins them (the gather runs on this — the
        engine's — thread, so no concurrent prefill can donate the pool
        buffers mid-read), then finish the request WITHOUT emitting the
        sampled token (see module docstring)."""
        now = self._clock.now()
        req.first_token_at = now            # "time to KV ready" here
        _PREFILL_SECONDS.observe(now - req.submitted_at)
        try:
            req.kv_export = self.kv_io.export_kv(req.prompt)
        except Exception as e:  # noqa: BLE001 — export is advisory
            _LOG.warning("kv export failed for %s: %s", req.id, e)
            req.kv_export = None
        if req.kv_export is not None:
            self._exports += 1
            self._export_blocks += req.kv_export.n_blocks
            _EXPORTS.inc()
            _EXPORT_BLOCKS.inc(req.kv_export.n_blocks)
        self._finished += 1
        _REQUESTS.inc(status="ok")
        self._tenant_count(req.tenant, "requests_finished")
        self._free(slot)      # tree keeps the prompt blocks cached
        req.finish()

    def stats(self):
        import dataclasses

        return dataclasses.replace(
            super().stats(),
            kv_exports=self._exports,
            kv_export_blocks=self._export_blocks,
        )

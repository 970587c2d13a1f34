"""Disaggregated prefill/decode gateway: two pools, one endpoint.

Extends :class:`~lzy_tpu.gateway.service.GatewayService` (whose fleet is
the **decode pool** — routing, fenced-token failover, health ticks and
autoscaling all apply to it unchanged) with a **prefill pool** and the
staging step that connects them. Per request:

1. route to a decode replica with the ordinary
   :class:`PrefixAffinityRouter` — the SAME index that predicts engine
   cache hits predicts when a transfer is pointless;
2. if the chosen decode replica is *expected* to hold the prompt's
   whole-block prefix already, **skip the transfer entirely** (counted:
   ``lzy_disagg_transfer_skipped_by_cache_total``) — repeat traffic to a
   warm replica pays neither prefill-pool time nor transfer bytes;
3. otherwise dispatch the prompt to a prefill replica (its own affinity
   router: prefill replicas accumulate radix caches too, so shared
   headers prefill once per *prefill* pool, not once per request), wait
   for the KV export, move it through the channels transport, and queue
   the import on the decode replica;
4. submit the FULL prompt to the decode engine. Its prefix match hits
   the imported blocks and only the sub-block tail prefills locally.

**Failure semantics**: every stage of (3) — prefill replica dead or
refusing admission, prefill failed mid-flight, transport stream dying
mid-transfer, import skipped under pool pressure — degrades to the
decode replica re-prefilling the prompt locally
(``lzy_disagg_reprefill_fallbacks_total``); the request itself NEVER
fails because of the prefill pool. Decode-side mid-stream death keeps
the parent's fenced-token failover (the retry re-stages KV for the new
replica).
"""

from __future__ import annotations

import threading
from typing import List, Optional

from lzy_tpu.channels.kv_transfer import InMemoryKVTransport
from lzy_tpu.chaos.faults import CHAOS, InjectedFault
from lzy_tpu.gateway.fleet import ReplicaFleet
from lzy_tpu.gateway.router import PrefixAffinityRouter
from lzy_tpu.gateway.service import GatewayService
from lzy_tpu.serving.scheduler import AdmissionError
from lzy_tpu.utils.log import get_logger
from lzy_tpu.utils.metrics import REGISTRY

_LOG = get_logger(__name__)

# chaos boundary: staging is best-effort BY CONTRACT — an injected
# failure here must surface as one more re-prefill fallback, never as a
# failed request
_FP_STAGE = CHAOS.register(
    "disagg.stage", error=InjectedFault,
    doc="prefill-pool KV staging for a routed decode replica")

_TRANSFERS = REGISTRY.counter(
    "lzy_disagg_transfers_total",
    "prefill→decode KV staging attempts by outcome "
    "(transferred/skipped_cache/skipped_short/fallback)")
_SKIPPED_CACHE = REGISTRY.counter(
    "lzy_disagg_transfer_skipped_by_cache_total",
    "transfers skipped because the decode replica already held the prefix")
_FALLBACKS = REGISTRY.counter(
    "lzy_disagg_reprefill_fallbacks_total",
    "requests that re-prefilled on the decode side after a prefill-pool "
    "or transfer failure")
_XFER_BYTES = REGISTRY.counter(
    "lzy_disagg_transfer_bytes_total",
    "KV bytes moved prefill→decode")
_XFER_SECONDS = REGISTRY.histogram(
    "lzy_disagg_transfer_seconds",
    "one KV staging round trip (prefill wait + transport + import queue)",
    buckets=(0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0))
_PREFILL_REPLICAS = REGISTRY.gauge(
    "lzy_disagg_prefill_replicas", "prefill pool replicas (READY)")


class DisaggGatewayService(GatewayService):
    """Two-pool serving front; wire-compatible with ``GatewayService``
    (``InferGenerate`` replies additionally carry ``prefilled_by`` and
    ``kv_transfer_ms``)."""

    def __init__(
        self,
        fleet: ReplicaFleet,                 # the DECODE pool
        prefill_fleet: ReplicaFleet,
        *,
        page_size: int = 16,
        prefill_router=None,
        transport=None,
        prefill_replicas: int = 1,
        prefill_timeout_s: float = 120.0,
        **kwargs,
    ):
        super().__init__(fleet, page_size=page_size, **kwargs)
        self.prefill_fleet = prefill_fleet
        # crash-recovery journal covers BOTH pools: prefill leases are
        # journaled (pool-tagged) so a successor re-adopts warm prefill
        # caches too, not just the decode fleet
        self.prefill_fleet.journal = self.journal
        if self.journal is not None:
            for replica in (prefill_fleet.replicas()
                            + prefill_fleet.replicas(state="DRAINING")):
                prefill_fleet.journal_lease(replica)
        self.prefill_router = (prefill_router if prefill_router is not None
                               else PrefixAffinityRouter(page_size))
        self.transport = transport if transport is not None \
            else InMemoryKVTransport()
        self._page = page_size
        self._prefill_target = prefill_replicas
        self._prefill_timeout_s = prefill_timeout_s
        self._tls = threading.local()
        self._xfer_lock = threading.Lock()
        self._transferred = 0
        self._skipped_cache = 0
        self._skipped_short = 0
        self._fallbacks = 0
        self._xfer_bytes = 0

    # -- request surface -----------------------------------------------------

    def generate(self, prompt, **kwargs) -> dict:
        self._tls.meta = {}        # fresh per call (failovers accumulate)
        return super().generate(prompt, **kwargs)

    def _meta(self) -> dict:
        meta = getattr(self._tls, "meta", None)
        if meta is None:
            meta = self._tls.meta = {}
        return meta

    def _note_result(self, req) -> None:
        """Terminal-attempt provenance: the decode engine records (at
        prefix-match time) which imported blocks the request actually
        HIT — i.e. which prefill replica really produced the KV it
        decoded from. Staged-but-refused imports (pool pressure, lost
        payload) leave this None and the request re-prefilled locally."""
        super()._note_result(req)
        self._meta()["kv_used_from"] = getattr(req, "kv_prefilled_by",
                                               None)

    def _reply_extras(self, gated: bool = True) -> dict:
        meta = self._meta()
        out = super()._reply_extras(gated)
        out.update({
            # the prefill replica whose KV the final serving attempt
            # actually USED (its imported blocks matched at prefill) —
            # None when the request re-prefilled locally, the prompt was
            # sub-block, or no import was ever staged. A repeat prompt
            # served straight from the decode replica's radix cache still
            # credits the pool that originally produced those blocks —
            # provenance follows the KV, not the transfer.
            "prefilled_by": meta.get("kv_used_from"),
            # the prefill replica whose KV was STAGED for the final
            # attempt (the decode engine folds imports in
            # opportunistically, so staged ≠ used: a refusal under pool
            # pressure silently re-prefills)
            "kv_staged_by": meta.get("prefilled_by"),
            "kv_transfer_ms": meta.get("kv_transfer_ms"),
            "kv_transfer_skipped": bool(meta.get("skipped", False)),
            "reprefills": int(meta.get("reprefills", 0)),
        })
        return out

    def _pre_submit(self, replica, prompt: List[int],
                    deadline_s: Optional[float] = None,
                    tenant: str = "default",
                    liveness=None) -> bool:
        """Parent routing loop's staging hook: probe the decode replica's
        admission gate FIRST — staging KV for a replica that cannot admit
        would waste a whole prefill + transfer and park imported blocks on
        a replica no routed request will match — then stage. Staged
        before submit so the import is queued (and therefore applied)
        before any scheduling round can admit the request.
        ``deadline_s`` is the request's REMAINING client deadline (a
        failover re-stages with what is left, not a fresh window): it
        caps the prefill wait and rides on the prefill-pool submit.
        A client ``liveness`` already reports gone skips the staging
        entirely (a prefill + transfer for a request the decode engine
        will reap on arrival is pure waste) — the submit still goes
        through, and the engine's reaper does the terminal accounting.

        With the fleet-global KV index on, the prefill pool keeps
        PRIORITY but is no longer the only source: when prefill-pool
        staging lands nothing (pool empty/refusing/mid-fault → the
        re-prefill fallback) and the router does not already expect the
        prefix resident on the routed replica, the global index is
        consulted for a DECODE-POOL sibling holding a deeper chain than
        the replica's own radix+tier coverage — the base gateway's
        cross-replica import path (``_stage_kv_import``), which used to
        be unreachable behind the disagg override, so a warm sibling's
        blocks now replace what was previously a guaranteed local
        re-prefill."""
        if self.kv_index is not None:
            # same per-attempt contract (and the same point — before the
            # admission probe) as the base gateway's _pre_submit
            self._reset_kv_import_meta()
        engine = replica.engine
        if getattr(engine, "closed", False) or \
                engine.queue.depth() >= engine.queue.max_depth:
            return False
        if liveness is not None and self._client_gone(liveness):
            return True
        self._stage_kv(replica, prompt, deadline_s=deadline_s,
                       tenant=tenant)
        if self.kv_index is not None:
            meta = self._meta()
            if not meta.get("prefilled_by") and not meta.get("skipped"):
                # nothing staged from the prefill pool AND no resident
                # expectation: a decode-pool sibling deeper than
                # radix+tier coverage is the next-best source
                self._stage_kv_import(replica, prompt,
                                      deadline_s=deadline_s)
        return True

    # -- KV staging ----------------------------------------------------------

    def _stage_kv(self, replica, prompt: List[int], *,
                  deadline_s: Optional[float] = None,
                  tenant: str = "default") -> None:
        """Best-effort: land the prompt's whole-block KV prefix on the
        chosen decode replica. Never raises — every failure path means
        the decode engine re-prefills locally."""
        meta = self._meta()
        meta.pop("prefilled_by", None)      # per-attempt: a failover
        meta.pop("kv_transfer_ms", None)    # restages for the new replica
        meta.pop("skipped", None)
        # only blocks the decode engine will actually match: it offers
        # prompt[:-1] to its radix tree so >=1 token always prefills
        n_full = (len(prompt) - 1) // self._page
        if n_full == 0:
            self._count("skipped_short")
            return
        prefix_len = n_full * self._page
        if self.router.match_len(replica.id, prompt) >= prefix_len:
            # the router EXPECTS the prefix resident on this replica; if
            # the expectation is stale the engine just prefills locally —
            # one redundant prefill, never a wrong token
            meta["skipped"] = True
            self._count("skipped_cache")
            _SKIPPED_CACHE.inc()
            return
        t0 = self._clock.now()
        try:
            CHAOS.hit("disagg.stage")
            staged = self._prefill_remote(prompt, deadline_s=deadline_s,
                                          tenant=tenant)
        except InjectedFault:
            staged = None        # chaos: staging died -> fallback path
        if staged is None:
            meta["reprefills"] = meta.get("reprefills", 0) + 1
            self._count("fallback")
            _FALLBACKS.inc()
            return
        prefilled_by, export = staged
        replica.engine.queue_kv_import(export)
        dt = self._clock.now() - t0
        with self._xfer_lock:
            self._transferred += 1
            self._xfer_bytes += export.nbytes
        _TRANSFERS.inc(outcome="transferred")
        _XFER_BYTES.inc(export.nbytes)
        _XFER_SECONDS.observe(dt)
        meta["prefilled_by"] = prefilled_by
        meta["kv_transfer_ms"] = round(1000 * dt, 3)

    def _prefill_remote(self, prompt: List[int], *,
                        deadline_s: Optional[float] = None,
                        tenant: str = "default"):
        """Run the prompt through a prefill replica and pull the export
        over the transport. Returns ``(prefill_replica_id, export)`` or
        None (→ re-prefill fallback). A prefill replica that fails
        mid-flight accrues toward its health verdict and the next
        candidate is tried; transport failures after a successful
        prefill fall straight back (the payload is gone).
        ``deadline_s`` (the request's remaining client deadline) caps
        both the prefill wait and the prefill request itself: a request
        with 2s left must not park behind a 120s prefill window — past
        the cap it degrades to local re-prefill, whose own deadline
        handling does the final accounting."""
        if deadline_s is not None and deadline_s <= 0:
            return None
        # the client budget is ANCHORED here and re-resolved per
        # candidate: one candidate's near-full wait must come off the
        # next one's, or N candidates could stage N× past the deadline
        deadline_at = (None if deadline_s is None
                       else self._clock.now() + deadline_s)
        loads = dict(self.prefill_fleet.loads())
        while loads:
            left = None
            if deadline_at is not None:
                left = deadline_at - self._clock.now()
                if left <= 0:
                    return None
            wait_s = (self._prefill_timeout_s if left is None
                      else min(self._prefill_timeout_s, left))
            rid, _ = self.prefill_router.choose(prompt, loads)
            replica = self.prefill_fleet.get(rid)
            if replica is None or \
                    not self.prefill_fleet.health.try_route(rid):
                loads.pop(rid, None)
                continue
            try:
                req = replica.engine.submit(prompt, deadline_s=left,
                                            tenant=tenant)
            except AdmissionError:
                # claimed-but-undispatched probe must not block the
                # replica for another open_s
                self.prefill_fleet.health.release_probe(rid)
                loads.pop(rid, None)
                continue
            except ValueError:
                # request-scoped (prompt > pool) — nothing was
                # dispatched, so the probe claim is released too
                self.prefill_fleet.health.release_probe(rid)
                return None
            self.prefill_router.observe(rid, prompt)
            if not req.wait(timeout=wait_s):
                req.cancel()
                _LOG.warning("disagg: prefill of %s on %s timed out",
                             req.id, rid)
                # no outcome recorded for this dispatch: free the probe
                # claim so a half-open replica is not starved for open_s
                self.prefill_fleet.health.release_probe(rid)
                return None
            if req.status == "cancelled":
                # the REQUEST's deadline died, not the replica: no
                # health accrual — the decode side finishes the
                # cancelled-with-partials contract
                self.prefill_fleet.health.release_probe(rid)
                return None
            if req.error:
                _LOG.warning("disagg: prefill replica %s failed (%s); "
                             "retiring from candidates", rid, req.error)
                self.prefill_fleet.health.record_failure(rid)
                self.prefill_router.forget(rid)
                self.prefill_fleet.check_health()
                loads.pop(rid, None)
                continue
            self.prefill_fleet.health.record_success(rid)
            export = getattr(req, "kv_export", None)
            if export is None:
                return None       # sub-block prompt: nothing to move
            export.prefilled_by = rid
            ref = None
            try:
                ref = self.transport.publish(f"kv-{req.id}", export)
                fetched = self.transport.fetch(ref)
            except Exception as e:  # noqa: BLE001 — mid-transfer death
                _LOG.warning("disagg: kv transfer for %s died mid-stream "
                             "(%s: %s); decode side will re-prefill",
                             req.id, type(e).__name__, e)
                return None
            finally:
                if ref is not None:
                    try:
                        self.transport.discard(ref)
                    except Exception:  # noqa: BLE001 — best-effort
                        pass
            return rid, fetched
        return None               # no live prefill replica at all

    def _count(self, outcome: str) -> None:
        with self._xfer_lock:
            if outcome == "skipped_cache":
                self._skipped_cache += 1
            elif outcome == "skipped_short":
                self._skipped_short += 1
            elif outcome == "fallback":
                self._fallbacks += 1
        _TRANSFERS.inc(outcome=outcome)

    # -- control loop --------------------------------------------------------

    def tick(self, now: Optional[float] = None) -> Optional[str]:
        """Parent tick (decode-pool health/autoscale) plus prefill-pool
        maintenance: retire dead prefill replicas and re-lease back to
        the configured pool size, one per tick."""
        for rid in self.prefill_fleet.check_health(now=now):
            self.prefill_router.forget(rid)
        ready = len(self.prefill_fleet.replicas())
        if ready < self._prefill_target:
            _LOG.warning("disagg: %d/%d prefill replicas; re-leasing",
                         ready, self._prefill_target)
            try:
                self.prefill_fleet.add_replica()
            except Exception:  # noqa: BLE001 — retried next tick
                _LOG.exception("disagg: prefill re-lease failed")
        _PREFILL_REPLICAS.set(float(len(self.prefill_fleet.replicas())))
        return super().tick(now)

    def close(self) -> None:
        super().close()
        self.prefill_fleet.close()

    # -- observability -------------------------------------------------------

    def stats(self, *, token: Optional[str] = None) -> dict:
        doc = super().stats(token=token)
        with self._xfer_lock:
            doc.update({
                "disagg": True,
                "prefill_replicas": len(self.prefill_fleet.replicas()),
                "kv_transfers": self._transferred,
                "kv_transfer_bytes": self._xfer_bytes,
                "kv_transfer_skipped_by_cache": self._skipped_cache,
                "kv_transfer_skipped_short": self._skipped_short,
                "reprefill_fallbacks": self._fallbacks,
            })
        return doc

    def fleet_stats(self, *, token: Optional[str] = None) -> dict:
        """Per-replica breakdown with a per-pool split: decode rows keep
        the parent shape (plus ``pool: "decode"``), prefill rows ride
        alongside with ``pool: "prefill"``."""
        doc = super().fleet_stats(token=token)
        for row in doc["replicas"]:
            row["pool"] = "decode"
        for state in ("READY", "DRAINING"):
            for replica in self.prefill_fleet.replicas(state=state):
                row = replica.engine.stats().doc()
                row.update({
                    "replica": replica.id,
                    "state": replica.state,
                    "pool": "prefill",
                    "vm_ids": list(replica.vm_ids),
                    "consecutive_failures":
                        self.prefill_fleet.health.failures(replica.id),
                })
                doc["replicas"].append(row)
        doc["pools"] = {
            "decode": sum(1 for r in doc["replicas"]
                          if r["pool"] == "decode"),
            "prefill": sum(1 for r in doc["replicas"]
                           if r["pool"] == "prefill"),
        }
        return doc

"""Replica fleet: engine lifecycle over allocator leases.

A replica is (an inference engine running its loop in a thread) + (a gang
leased from ``service/allocator.py``). The lease is what plugs the fleet
into the platform's existing control machinery instead of a bespoke
process registry:

- the allocator's durable ``allocate_gang`` FSM makes replica acquisition
  crash-safe and observable like any other allocation (same ops views,
  same metrics);
- the leased gang's worker agents heartbeat through AllocatorPrivate, so
  replica *host* health is read off ``Vm.heartbeat_ts`` — no second
  prober;
- draining FREES the gang back to the session cache rather than
  destroying it, so a scale-up shortly after a scale-down reuses the warm
  gang (the allocator's reuse cache becomes the fleet's boot
  accelerator).

Run unleased (``allocator=None``) the fleet is plain threads — the unit
test mode, and the degenerate single-host deployment.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

from lzy_tpu.gateway.health import HealthPolicy, HealthTracker
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.utils.log import get_logger
from lzy_tpu.utils.metrics import REGISTRY

_LOG = get_logger(__name__)

_REPLICAS = REGISTRY.gauge(
    "lzy_gateway_replicas", "fleet replicas by state")
_R_QUEUE = REGISTRY.gauge(
    "lzy_gateway_replica_queue_depth", "per-replica admission queue depth")
_R_BUSY = REGISTRY.gauge(
    "lzy_gateway_replica_slots_busy", "per-replica busy decode slots")
_RETIRED = REGISTRY.counter(
    "lzy_gateway_replicas_retired_total", "replicas retired by cause")

STARTING = "STARTING"
READY = "READY"
DRAINING = "DRAINING"
DEAD = "DEAD"

#: per-tenant TERMINAL counters banked on replica retirement and summed
#: fleet-wide (live fields — queue_depth, kv_blocks — are summed over
#: live replicas only; they die with the replica)
_TENANT_COUNTERS = ("requests_finished", "tokens_generated",
                    "requests_cancelled", "requests_preempted",
                    "requests_error")


@dataclasses.dataclass
class Replica:
    id: str
    engine: object                      # PagedInferenceEngine-compatible
    state: str = READY
    vm_ids: List[str] = dataclasses.field(default_factory=list)
    created_ts: float = dataclasses.field(default_factory=time.time)
    drain_since: Optional[float] = None

    @property
    def leased(self) -> bool:
        return bool(self.vm_ids)


class ReplicaFleet:
    """Owns replicas; the gateway service routes over :meth:`loads` and
    calls :meth:`check_health` / :meth:`reap_drained` from its tick."""

    def __init__(
        self,
        engine_factory: Callable[[], object],
        *,
        allocator=None,                  # Optional[AllocatorService]
        pool_label: str = "cpu-small",
        session_owner: str = "gateway-fleet",
        lease_timeout_s: float = 60.0,
        health: Optional[HealthTracker] = None,
        start_engines: bool = True,
        replica_prefix: str = "replica",
        clock=None,
    ):
        self._factory = engine_factory
        self._allocator = allocator
        self._pool_label = pool_label
        self._session_owner = session_owner
        self._lease_timeout_s = lease_timeout_s
        self._clock = clock if clock is not None else SYSTEM_CLOCK
        self.health = health or HealthTracker(HealthPolicy(),
                                              clock=self._clock)
        self._start_engines = start_engines
        # distinct prefixes keep ids unambiguous when several fleets share
        # a surface (the disagg gateway runs a "prefill" and a "decode"
        # pool behind one endpoint and replies name the prefill replica)
        self._replica_prefix = replica_prefix
        self._replicas: Dict[str, Replica] = {}
        self._session_id: Optional[str] = None
        self._seq = 0
        self._lock = threading.RLock()
        self._closed = False
        #: crash-recovery journal (gateway/journal.py), set by the
        #: owning GatewayService: add/adopt record the gang lease,
        #: retirement forgets it — what a successor re-adopts from
        self.journal = None
        # terminal counters of retired replicas: fleet aggregates must
        # stay MONOTONIC across scale-downs/failovers (a stats consumer
        # computing rates over InferStats would otherwise see negative
        # spikes every time a replica's history vanishes with it)
        self._retired_totals = {
            "requests_finished": 0, "tokens_generated": 0,
            "prefix_hit_tokens": 0, "prefix_lookup_tokens": 0,
            "spec_proposed_tokens": 0, "spec_accepted_tokens": 0,
            "spec_draft_truncated": 0,
            "decode_steps": 0, "decode_rows": 0, "decode_tokens": 0,
            "kv_imports": 0, "kv_import_blocks": 0,
            "kv_tier_demotions": 0, "kv_tier_promotions": 0,
            "kv_tier_dropped": 0}
        # per-tenant twin of the banked totals (terminal counters only —
        # live gauges like queue depth die with the replica)
        self._retired_tenants: Dict[str, Dict[str, int]] = {}

    # -- lifecycle -----------------------------------------------------------

    def add_replica(self) -> Replica:
        """Lease (if an allocator is wired) and start one replica. The
        engine is only built AFTER the lease lands, so a failed/timed-out
        allocation never leaves a loose engine thread."""
        with self._lock:
            if self._closed:
                raise RuntimeError("fleet is closed")
            self._seq += 1
            rid = f"{self._replica_prefix}-{self._seq}"
        vm_ids: List[str] = []
        if self._allocator is not None:
            vm_ids = self._lease()
        try:
            engine = self._factory()
        except BaseException:
            if vm_ids:
                self._allocator.free(vm_ids)
            raise
        if self._start_engines:
            engine.start()
        replica = Replica(id=rid, engine=engine, vm_ids=vm_ids)
        with self._lock:
            if self._closed:
                # the fleet closed while we were blocked in the lease:
                # inserting now would leak a running engine thread and a
                # never-freed gang — unwind instead
                unwind = True
            else:
                unwind = False
                self._replicas[rid] = replica
        if unwind:
            try:
                engine.close()
            except Exception:  # noqa: BLE001 — best-effort unwind
                pass
            if vm_ids:
                try:
                    self._allocator.free(vm_ids)
                except Exception:  # noqa: BLE001 — lease may be gone
                    pass
            raise RuntimeError("fleet is closed")
        self.health.record_success(rid)       # fresh streak
        self.journal_lease(replica)
        _LOG.info("fleet: replica %s up (lease %s)", rid, vm_ids or "none")
        self._update_gauges()
        return replica

    def journal_lease(self, replica: Replica) -> None:
        """Record (or re-record) one replica's gang lease in the
        crash-recovery journal; no-op without one."""
        journal = self.journal
        if journal is None:
            return
        with self._lock:
            session = self._session_id
        journal.record_lease(replica.id, replica.vm_ids, session,
                             pool=self._replica_prefix)

    def adopt_replica(self, replica_id: str, engine,
                      vm_ids: Optional[List[str]] = None) -> Replica:
        """Crash-recovery adoption: register an ALREADY-RUNNING engine
        (and its existing gang lease) under the predecessor's replica
        id, without leasing or starting anything. The warm engine keeps
        its radix cache and host KV tier — the whole point of adopting
        instead of re-leasing. The id sequence is advanced past the
        adopted id so later ``add_replica`` calls never collide."""
        vm_ids = list(vm_ids or ())
        with self._lock:
            if self._closed:
                raise RuntimeError("fleet is closed")
            if replica_id in self._replicas:
                raise ValueError(
                    f"replica {replica_id!r} already in the fleet")
            tail = replica_id.rsplit("-", 1)[-1]
            if tail.isdigit():
                self._seq = max(self._seq, int(tail))
            replica = Replica(id=replica_id, engine=engine,
                              vm_ids=vm_ids,
                              created_ts=self._clock.time())
            self._replicas[replica_id] = replica
        self.health.record_success(replica_id)   # fresh streak
        self.journal_lease(replica)
        _LOG.info("fleet: adopted replica %s (lease %s)", replica_id,
                  vm_ids or "none")
        self._update_gauges()
        return replica

    def adopt_session(self, session_id: Optional[str]) -> None:
        """Adopt the predecessor's allocator session: drains keep
        freeing into the same warm-gang cache, and close() deletes the
        right session instead of orphaning it."""
        with self._lock:
            if self._session_id is None:
                self._session_id = session_id

    def release_for_handoff(self) -> List[str]:
        """Rolling-restart handoff: strip the replica table WITHOUT
        closing engines or freeing leases (a successor fleet adopted
        them) and disown the allocator session (the successor owns it
        now — our close() must not delete it). Returns the released
        replica ids; the caller then drains/closes an empty fleet."""
        with self._lock:
            replicas = list(self._replicas.values())
            self._replicas.clear()
            self._session_id = None
        for replica in replicas:
            self.health.forget(replica.id)
        self._update_gauges()
        _LOG.info("fleet: released %d replica(s) for handoff",
                  len(replicas))
        return [r.id for r in replicas]

    def _lease(self) -> List[str]:
        with self._lock:
            if self._session_id is None:
                self._session_id = self._allocator.create_session(
                    self._session_owner)
            session = self._session_id
        return self._allocator.lease_gang(
            session, self._pool_label, timeout_s=self._lease_timeout_s)

    def drain(self, replica_id: str) -> None:
        """Stop routing to the replica; its in-flight work finishes and
        :meth:`reap_drained` retires it once idle."""
        with self._lock:
            replica = self._replicas.get(replica_id)
            if replica is None or replica.state != READY:
                return
            replica.state = DRAINING
            replica.drain_since = self._clock.time()
        _LOG.info("fleet: draining %s", replica_id)
        self._update_gauges()

    def reap_drained(self) -> List[str]:
        """Retire DRAINING replicas whose engines went idle."""
        retired = []
        for replica in self.replicas(state=DRAINING):
            s = replica.engine.stats()
            if s.busy == 0 and s.queue_depth == 0:
                self._retire(replica, cause="drained")
                retired.append(replica.id)
        return retired

    def check_health(self, now: Optional[float] = None) -> List[str]:
        """Mark-and-retire dead replicas; returns their ids. A dead
        replica's engine is closed (failing whatever it still held — the
        gateway's failover fences and resubmits) and its lease is
        RELEASED, not reused: the allocator's own GC decides whether the
        gang itself is still sound."""
        dead = []
        for replica in self.replicas() + self.replicas(state=DRAINING):
            hb = None
            if replica.leased and self._allocator is not None:
                try:
                    # the gang is one replica: its effective heartbeat is
                    # the STALEST host's — any one host going quiet (or
                    # vanishing) fails over the whole gang, never a
                    # partial shard set
                    hb = min(self._allocator.vm(v).heartbeat_ts
                             for v in replica.vm_ids)
                except KeyError:
                    dead.append((replica, "lease vanished"))
                    continue
            reason = self.health.verdict(
                replica.id, heartbeat_ts=hb,
                engine_closed=bool(getattr(replica.engine, "closed", False)),
                now=now)
            if reason is not None:
                dead.append((replica, reason))
        for replica, reason in dead:
            _LOG.warning("fleet: replica %s dead (%s); retiring",
                         replica.id, reason)
            self._retire(replica, cause="failed")
        return [r.id for r, _ in dead]

    def _retire(self, replica: Replica, *, cause: str) -> None:
        with self._lock:
            if self._replicas.pop(replica.id, None) is None:
                return
            replica.state = DEAD
        journal = self.journal
        if journal is not None:
            journal.forget_lease(replica.id)
        try:
            # bank the terminal counters BEFORE closing: aggregates must
            # not go backwards when this replica's engine is dropped
            s = replica.engine.stats()
            with self._lock:
                self._retired_totals["requests_finished"] += \
                    s.requests_finished
                self._retired_totals["tokens_generated"] += \
                    s.tokens_generated
                for key, attr in (("spec_proposed_tokens", "spec_proposed"),
                                  ("spec_accepted_tokens", "spec_accepted"),
                                  ("spec_draft_truncated",
                                   "spec_draft_truncated"),
                                  ("decode_steps", "decode_steps"),
                                  ("decode_rows", "decode_rows"),
                                  ("decode_tokens", "decode_tokens"),
                                  ("kv_imports", "kv_imports"),
                                  ("kv_import_blocks", "kv_import_blocks"),
                                  ("kv_tier_demotions",
                                   "kv_tier_demotions"),
                                  ("kv_tier_promotions",
                                   "kv_tier_promotions"),
                                  ("kv_tier_dropped", "kv_tier_dropped")):
                    self._retired_totals[key] += int(
                        getattr(replica.engine, attr, 0))
                kv = getattr(replica.engine, "kv", None)
                if kv is not None:
                    self._retired_totals["prefix_hit_tokens"] += \
                        kv.hit_tokens
                    self._retired_totals["prefix_lookup_tokens"] += \
                        kv.lookup_tokens
                by_tenant = getattr(replica.engine, "stats_by_tenant",
                                    None)
                if by_tenant is not None:
                    for tenant, row in by_tenant().items():
                        bank = self._retired_tenants.setdefault(
                            tenant, {k: 0 for k in _TENANT_COUNTERS})
                        for key in _TENANT_COUNTERS:
                            bank[key] += int(row.get(key, 0))
        except Exception:  # noqa: BLE001 — stats from a dying engine
            pass
        try:
            replica.engine.close()
        except Exception:  # noqa: BLE001 — already-dead engines may throw
            pass
        if replica.leased and self._allocator is not None:
            try:
                self._allocator.free(replica.vm_ids)
            except Exception:  # noqa: BLE001 — lease may already be gone
                pass
        self.health.forget(replica.id)
        _RETIRED.inc(cause=cause)
        if cause == "failed" and (len(replica.vm_ids) > 1 or
                                  getattr(replica.engine, "gang_size", 1) > 1):
            # a failure-retired gang replica is a whole-gang failover —
            # lazy import: fleet must not pull serving.sharded (and its
            # model stack) in at module load
            from lzy_tpu.serving.sharded.metrics import GANG_FAILOVERS
            GANG_FAILOVERS.inc()
        self._update_gauges()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            replicas = list(self._replicas.values())
        for replica in replicas:
            self._retire(replica, cause="shutdown")
        if self._session_id is not None and self._allocator is not None:
            try:
                self._allocator.delete_session(self._session_id)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass

    # -- views ---------------------------------------------------------------

    def get(self, replica_id: str) -> Optional[Replica]:
        with self._lock:
            return self._replicas.get(replica_id)

    def replicas(self, state: str = READY) -> List[Replica]:
        with self._lock:
            return [r for r in self._replicas.values() if r.state == state]

    def size(self) -> int:
        with self._lock:
            return len(self._replicas)

    def loads(self) -> Dict[str, int]:
        """Routable replicas -> load (queue depth + busy slots). A
        replica behind an OPEN circuit breaker (``health.routable``) is
        withheld from routing without being retired: flapping hosts stop
        eating failovers while their lease — and their warm cache — get
        ``open_s`` to recover."""
        out = {}
        for replica in self.replicas():
            s = replica.engine.stats()
            _R_QUEUE.set(float(s.queue_depth), replica=replica.id)
            _R_BUSY.set(float(s.busy), replica=replica.id)
            if not self.health.routable(replica.id):
                continue
            out[replica.id] = s.queue_depth + s.busy
        return out

    def breaker_retry_after_s(self) -> Optional[float]:
        """When every replica is breaker-blocked, the soonest half-open
        among them — the shed hint for a fully-tripped fleet."""
        waits = [self.health.breaker.retry_after_s(r.id)
                 for r in self.replicas()]
        waits = [w for w in waits if w is not None]
        return min(waits) if waits else None

    def aggregate(self) -> dict:
        """Fleet-level sums over READY+DRAINING engines (the numbers the
        autoscaler and stats surface read)."""
        with self._lock:
            agg = {"replicas": 0, "queue_depth": 0, "busy": 0, "slots": 0,
                   "kv_host_tier_blocks": 0, **self._retired_totals}
        for replica in self.replicas() + self.replicas(state=DRAINING):
            s = replica.engine.stats()
            agg["replicas"] += 1
            agg["queue_depth"] += s.queue_depth
            agg["busy"] += s.busy
            agg["slots"] += s.slots
            agg["requests_finished"] += s.requests_finished
            agg["tokens_generated"] += s.tokens_generated
            for key, attr in (("spec_proposed_tokens", "spec_proposed"),
                              ("spec_accepted_tokens", "spec_accepted"),
                              ("spec_draft_truncated",
                               "spec_draft_truncated"),
                              ("decode_steps", "decode_steps"),
                              ("decode_rows", "decode_rows"),
                              ("decode_tokens", "decode_tokens"),
                              ("kv_imports", "kv_imports"),
                              ("kv_import_blocks", "kv_import_blocks"),
                              ("kv_tier_demotions", "kv_tier_demotions"),
                              ("kv_tier_promotions", "kv_tier_promotions"),
                              ("kv_tier_dropped", "kv_tier_dropped")):
                agg[key] += int(getattr(replica.engine, attr, 0))
            kv = getattr(replica.engine, "kv", None)
            if kv is not None:
                agg["prefix_hit_tokens"] += kv.hit_tokens
                agg["prefix_lookup_tokens"] += kv.lookup_tokens
            # live occupancy (dies with the replica, not banked)
            if s.kv_host_tier_blocks is not None:
                agg["kv_host_tier_blocks"] += s.kv_host_tier_blocks
            # a model with two kinds of page (models/serving.py, kind
            # ``window``): the second kind's pages, live sums; a fleet of
            # models with one kind has none of these keys
            for key in ("kv_window_blocks_live", "kv_window_blocks_free",
                        "kv_window_pages_released"):
                if getattr(s, key, None) is not None:
                    agg[key] = agg.get(key, 0) + getattr(s, key)
        return agg

    def aggregate_tenants(self) -> Dict[str, Dict[str, int]]:
        """Fleet-level per-tenant sums (terminal counters stay MONOTONIC
        across retirements via the banked totals; queue depth and KV
        blocks are live sums over READY+DRAINING replicas)."""
        with self._lock:
            out = {t: dict(row) for t, row in self._retired_tenants.items()}
        for replica in self.replicas() + self.replicas(state=DRAINING):
            by_tenant = getattr(replica.engine, "stats_by_tenant", None)
            if by_tenant is None:
                continue
            for tenant, row in by_tenant().items():
                agg = out.setdefault(
                    tenant, {k: 0 for k in _TENANT_COUNTERS})
                for key, value in row.items():
                    agg[key] = agg.get(key, 0) + int(value)
        return out

    def _update_gauges(self) -> None:
        with self._lock:
            counts: Dict[str, int] = {}
            for replica in self._replicas.values():
                counts[replica.state] = counts.get(replica.state, 0) + 1
        for state in (READY, DRAINING):
            _REPLICAS.set(float(counts.get(state, 0)), state=state)

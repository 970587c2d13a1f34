"""The gateway front: one ``InferGenerate`` endpoint over the fleet.

Method-compatible with ``service/inference.InferenceService`` (generate/
stats/close + an ``iam`` attribute), so the control-plane server registers
it on the same RPC routes and ``serve.py --gateway`` slots it in where a
single engine used to sit. What it adds over one engine:

- **cache-aware dispatch**: every request is routed by the
  ``PrefixAffinityRouter`` (longest expected cached prefix, bounded load
  imbalance) and the router's expectation index is updated on submit;
- **failover with fenced tokens**: a request that dies mid-stream on one
  replica (engine loop death, preemption, replica shutdown) is resubmitted
  to another with the tokens already emitted *fenced* — the retry prompt
  is ``prompt + emitted`` and the final reply is ``emitted +
  continuation``, so the client-visible stream never repeats or drops a
  token. Under greedy decode the result is bit-identical to an
  uninterrupted run (deterministic continuation); failures that are the
  request's own fault (over-long prompt, invalid args) are NOT failed
  over — they would fail identically everywhere;
- **health + autoscaling tick**: a background loop (or an explicit
  ``tick(now)`` under test) retires dead replicas, reaps drained ones,
  and applies the autoscaler's lease/drain decisions.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

from lzy_tpu.chaos.faults import CHAOS
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.gateway.autoscale import DOWN, UP, Autoscaler
from lzy_tpu.gateway.fleet import ReplicaFleet
from lzy_tpu.gateway.router import PrefixAffinityRouter
from lzy_tpu.serving.scheduler import (
    AdmissionError, DEFAULT_TENANT, PromptTooLong, QuotaExceeded,
    any_to_tokens, plane_capacity, quota_error, shed_error)
from lzy_tpu.utils import trace
from lzy_tpu.utils.log import get_logger
from lzy_tpu.utils.metrics import REGISTRY

_LOG = get_logger(__name__)

#: reserved tenant speculative next-step prefills ride: background WFQ
#: share, and the requesting user's own per-tenant accounting never sees
#: the speculation (it is uncharged by contract)
SPECULATION_TENANT = "__wfsched__"

_FAILOVERS = REGISTRY.counter(
    "lzy_gateway_failovers_total",
    "requests resubmitted to another replica after a mid-stream failure")
_SCALE = REGISTRY.counter(
    "lzy_gateway_scale_events_total", "autoscale decisions by direction")
_REQUESTS = REGISTRY.counter(
    "lzy_gateway_requests_total", "gateway requests by outcome")

# chaos boundary: error mode refuses one candidate replica exactly like
# an AdmissionError from its engine — the routing loop tries the next
# one, and only an empty candidate set sheds to the client
_FP_DISPATCH = CHAOS.register(
    "gateway.dispatch", error=AdmissionError,
    doc="routed submit to one replica (degrades to the next candidate)")

# chaos boundary: the gateway process itself dying. Pure-crash point
# (no error mode): an InjectedCrash raised on the request path IS the
# simulated process death, survivable BY CONSTRUCTION when a journal is
# wired — the death handler is gateway/recovery.py (adopt leases,
# resubmit streams at their journaled fences), which the chaos soak
# runs on every injected death. Only hit on journal-backed gateways:
# without a journal there is nothing to recover from, and the older
# soaks' zero-failure contracts must keep holding.
_FP_CRASH = CHAOS.register(
    "gateway.crash", crash_ok=True, modes=(),
    doc="the gateway process dying mid-request (survivable by "
        "construction: the journal + recovery path restores fences, "
        "sessions and leases)")

#: engine-side failure prefixes that indicate the REPLICA failed, not the
#: request — safe (and required) to resubmit elsewhere with fenced tokens
_FAILOVER_ERRORS = ("engine loop died", "preempted", "engine shutting down")
#: failover-eligible errors that are CAPACITY signals, not replica faults:
#: resubmit elsewhere, but do not accrue toward the health verdict — a
#: paged engine preempting its youngest request under KV pressure is
#: working as designed, and retiring it would dump its whole load onto
#: the rest of the fleet mid-squeeze
_CAPACITY_ERRORS = ("preempted",)


class GatewayService:
    def __init__(
        self,
        fleet: ReplicaFleet,
        *,
        router=None,
        autoscaler: Optional[Autoscaler] = None,
        model_name: str = "custom",
        iam=None,
        page_size: int = 16,
        max_waiters: int = 16,
        max_failovers: int = 3,
        tick_period_s: float = 1.0,
        slo=None,
        kv_index=None,
        kv_transport=None,
        clock=None,
        journal=None,
        wf_park_ttl_s: float = 30.0,
    ):
        # injectable time (utils/clock): request deadlines, failover
        # budgets, tick cadence and the drain loop all run on it — the
        # load plane drives a whole fleet on a virtual clock; production
        # (clock=None) is bit-identical to the old time.* calls
        self._clock = clock if clock is not None else SYSTEM_CLOCK
        self.fleet = fleet
        self.router = router if router is not None else PrefixAffinityRouter(
            page_size)
        #: fleet-global tiered-KV prefix index (gateway/kv_index.py):
        #: replicas advertise which chunk-hash prefixes they hold and at
        #: which tier; a routed replica that would miss a prefix a
        #: sibling holds gets the sibling's blocks imported over the
        #: transport instead of re-prefilling. None = off (the default —
        #: serve.py enables it with the tier flags).
        self.kv_index = kv_index
        if kv_index is not None and kv_transport is None:
            from lzy_tpu.channels.kv_transfer import InMemoryKVTransport

            kv_transport = InMemoryKVTransport()
        self.kv_transport = kv_transport
        self._kvtier_tls = threading.local()
        self._kvtier_lock = threading.Lock()
        self._kvtier_imports = 0
        self._kvtier_import_bytes = 0
        self._kvtier_fallbacks = 0
        self._kvtier_seq = 0
        # last advertisement object per replica (tick-loop only): the
        # engine memoizes by cache version, so identity means unchanged
        self._kvtier_last_adv: dict = {}
        self.autoscaler = autoscaler
        self.model_name = model_name
        self.iam = iam                 # harness wires the cluster's IAM in
        #: tenant SLO enforcement (serving.tenancy.SloLimiter): token-
        #: bucket rate limits charged HERE — once per client request, at
        #: the fleet front — while WFQ/quotas live in the engines (per
        #: replica). None = unlimited (the single-tenant default).
        self.slo = slo
        self._max_failovers = max_failovers
        self._tick_period_s = tick_period_s
        self._max_waiters = int(max_waiters)
        self._waiters = threading.BoundedSemaphore(max_waiters)
        self._failovers = 0
        self._finished = 0
        self._shed = 0
        self._inflight = 0
        self._scale_ups = 0
        self._scale_downs = 0
        self._draining = False
        self._stop = self._clock.event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        #: chaos hook (``chaos.invariants.FenceAuditor``): when set, every
        #: failover fence and completion is reported for the monotonicity
        #: audit; None (production) costs one attribute check
        self.fence_auditor = None
        #: streaming front (InferStream/InferStreamPoll/InferCancel):
        #: the fence the failover path maintains IS the wire position
        from lzy_tpu.serving.streams import StreamSessionManager

        self.streams = StreamSessionManager(self, clock=self._clock)
        #: durable crash-recovery journal (gateway/journal.py): session
        #: births, routed attempts, fence advances and replica leases —
        #: what gateway/recovery.py restores a successor from. None
        #: (the default) costs nothing on the request path.
        self.journal = journal
        self.streams.journal = journal
        self.fleet.journal = journal
        if journal is not None:
            # replicas added BEFORE the gateway existed (test harnesses
            # build fleet-first) get their leases journaled now; ones
            # added later ride the fleet's own add/adopt hooks
            for replica in (self.fleet.replicas()
                            + self.fleet.replicas(state="DRAINING")):
                self.fleet.journal_lease(replica)
        #: set by recovery: the first post-restart tick force-refreshes
        #: the global KV index from every adopted replica (the memoized
        #: advertisement identity check is skipped once)
        self._kv_force_refresh = False
        #: workflow-aware scheduling (lzy_tpu/llm/sched.py): live fusion
        #: leases, session -> (replica_id, expires_at). A lease means
        #: the replica holds that conversation's KV PARKED resident
        #: across a tool gap, so the next step hard-pins there (reason
        #: "fused"). Leases are advisory and bounded: they expire with
        #: the engine-side park TTL, die with the replica (failover /
        #: health retirement drops them), and a stale one costs a lazy
        #: cleanup — never a wrong route (the engine re-matches its own
        #: radix tree regardless).
        self._wf_park_ttl = float(wf_park_ttl_s)
        self._wf_parked: Dict[str, Tuple[str, float]] = {}
        self._wf_lock = threading.Lock()

    # -- request surface -----------------------------------------------------

    def _auth(self, token: Optional[str]):
        """Authenticate and return the Subject (None when no IAM is
        wired — the single-tenant operator plane)."""
        if self.iam is not None:
            return self.iam.authenticate(token)
        return None

    def _resolve_tenant(self, subject, tenant: Optional[str]) -> str:
        """Tenant identity: the authenticated subject id when IAM is on
        (the wire field may only restate it — or be used by the
        operator's INTERNAL role to act on a tenant's behalf); the wire
        field, else the default tenant, on an IAM-less plane."""
        if subject is None:
            return tenant or DEFAULT_TENANT
        if tenant and tenant != subject.id:
            from lzy_tpu.iam import INTERNAL, AuthError

            if subject.role != INTERNAL:
                raise AuthError(
                    f"subject {subject.id} may not submit as tenant "
                    f"{tenant!r}")
            return tenant
        return subject.id

    def _slo_admit(self, tenant: str, prompt: List[int]):
        """Charge the tenant's rate buckets (and resolve its priority
        floor); QuotaExceeded propagates with the per-tenant retry hint
        — counted as a shed, since no replica was ever tried."""
        if self.slo is None:
            return None
        try:
            return self.slo.admit(tenant, len(prompt))
        except QuotaExceeded:
            with self._lock:
                self._shed += 1
            raise

    def _max_seq_len(self) -> Optional[int]:
        """The fleet's model window, read off any live replica (replicas
        are homogeneous); None while the fleet is empty — the engine's
        own admission check then covers it."""
        for state in ("READY", "DRAINING"):
            for replica in self.fleet.replicas(state=state):
                cfg = getattr(replica.engine, "cfg", None)
                if cfg is not None:
                    return int(cfg.max_seq_len)
        return None

    def _check_prompt_len(self, prompt: List[int],
                          max_new_tokens: int) -> None:
        """Admission-time rejection of prompts no replica can ever serve
        — BEFORE routing, so the request costs no replica an admission
        probe, no disagg plane a staged prefill, and no health tracker a
        bogus failure."""
        msl = self._max_seq_len()
        if msl is not None and len(prompt) + max_new_tokens > msl:
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len ({msl}); the "
                f"prompt can never be served — shorten it or reduce "
                f"max_new_tokens")

    def generate(self, prompt, *, max_new_tokens: int = 64,
                 token: Optional[str] = None,
                 timeout_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 greedy: Optional[bool] = None,
                 tenant: Optional[str] = None,
                 priority: Optional[int] = None,
                 session: Optional[str] = None,
                 stream=None, liveness=None,
                 resume_tokens: Optional[List[int]] = None,
                 journal_rid: Optional[str] = None) -> dict:
        """Blocking generate over the fleet; same contract as the single
        engine's RPC surface plus route metadata (``replica``,
        ``routed_by``, ``failovers``) in the reply. Backpressure is
        fleet-wide: only when EVERY routable replica refuses admission
        does the caller see ``Unavailable``. ``greedy`` is the
        per-request sampling override, carried across failover
        resubmissions (a greedy stream must stay greedy — and therefore
        deterministic — on the retry replica too). ``tenant``/``priority``
        are the SLO identity (docstring of :meth:`_resolve_tenant`);
        tenant-scoped refusals raise ``QuotaExceeded`` with a per-tenant
        ``retry_after_s``.

        ``session`` is a stable conversation id: the router pins it to
        the replica whose RadixCache holds the conversation's earlier
        steps (``routed_by: "session"``), within the load-imbalance
        bound. ``stream`` (a ``channels.token_stream.TokenStreamChannel``)
        receives tokens incrementally as the engine emits them; the
        stream position IS the failover fence, so a mid-stream replica
        death resumes the channel byte-identically (``resumptions``
        ticks, the token sequence does not change). The channel is
        closed with the request's terminal status before this method
        returns — or failed before it raises IF any tokens were
        published; an exception that never touched the stream leaves it
        open for the caller's retry policy. ``liveness`` is the reply
        channel's client probe, carried into every replica submission
        (and checked between failover attempts): a disconnected or
        cancelled client terminates the request within one decode round
        wherever it sits.

        ``resume_tokens`` is the crash-recovery entry
        (``gateway/recovery.py``): the journaled fence of a request the
        predecessor gateway was serving when it died. The generation
        restarts as ``prompt + resume_tokens`` through the ordinary
        failover machinery (``emitted`` pre-seeded, the stream
        re-attached at the fence), so the client's old resume token
        splices byte-identically. A resumed request was authenticated
        and SLO-charged at its ORIGINAL admission — recovery re-submits
        under the journaled tenant without a bearer token and without a
        second rate-bucket charge. ``journal_rid`` names this call's
        existing journal record (the streaming front passes the stream
        id); without one, a journal-backed gateway births a fresh unary
        record — settled with a typed status by recovery if the process
        dies before the reply."""
        with trace.span(trace.GATEWAY_GENERATE) as root:
            if self.journal is not None:
                CHAOS.hit("gateway.crash")
            if self.kv_index is not None:
                # fresh per call (failovers restage)
                self._kvtier_tls.meta = {}
            resumed = resume_tokens is not None
            subject = self._auth(token) if not resumed else None
            from lzy_tpu.rpc.core import Unavailable

            jrid = journal_rid
            try:
                if not resumed:
                    tenant = self._resolve_tenant(subject, tenant)
                else:
                    tenant = tenant or DEFAULT_TENANT
                prompt = any_to_tokens(prompt)
                self._check_prompt_len(prompt, int(max_new_tokens))
                if not resumed:
                    policy = self._slo_admit(tenant, prompt)
                    if policy is not None:
                        priority = policy.effective_priority(priority)
                if self._draining:
                    raise self._shed_error(
                        Unavailable,
                        "gateway is draining; retry another endpoint",
                        reason="draining", retry_after_s=None)
                # streaming session workers (liveness is not None) bypass
                # the waiter cap: they are dedicated threads bounded by the
                # session manager's max_sessions, and gating them here
                # would cap streams at the waiter count while starving
                # unary callers for each stream's whole lifetime
                gated = liveness is None
                if gated and not self._waiters.acquire(blocking=False):
                    raise self._shed_error(
                        Unavailable,
                        "all gateway waiter threads are busy; retry later",
                        reason="waiters_busy", retry_after_s=0.25)
                if self.journal is not None and jrid is None:
                    # unary birth (streamed calls carry the stream manager's
                    # record id), BELOW the draining/waiter shed gates: a
                    # fast-rejected request never ran and its reply is
                    # synchronous — journaling it would turn the cheap shed
                    # path into a per-rejection disk write under exactly
                    # the overload it absorbs. LEAN on purpose — a unary
                    # request can only ever be settled as orphaned on
                    # recovery (its reply channel dies with this process),
                    # so the record carries the identity the auditor needs
                    # and NOT the prompt/token payload
                    jrid = self.journal.record_birth(
                        prompt=(), max_new_tokens=int(max_new_tokens),
                        greedy=greedy, tenant=tenant, priority=priority,
                        session=session, deadline_s=deadline_s,
                        timeout_s=timeout_s, streamed=False,
                        subject_id=subject.id if subject is not None
                        else None)
                if root:
                    # everything up to here is admission: auth, tenant,
                    # prompt check, SLO, the shed gates, the journal
                    trace.note(tenant=tenant, prompt_tokens=len(prompt))
                    trace.emit(trace.GATEWAY_ADMIT, root.start,
                               trace.now())
                with self._lock:
                    self._inflight += 1
                try:
                    reply = self._generate(prompt,
                                           int(max_new_tokens),
                                           timeout_s=timeout_s or 120.0,
                                           deadline_s=deadline_s,
                                           greedy=greedy,
                                           tenant=tenant,
                                           priority=priority,
                                           session=session,
                                           stream=stream,
                                           liveness=liveness,
                                           resume_tokens=resume_tokens,
                                           journal_rid=jrid)
                finally:
                    with self._lock:
                        self._inflight -= 1
                    if gated:
                        self._waiters.release()
                if self.journal is not None and jrid is not None \
                        and journal_rid is None:
                    # settle the unary record we birthed (streamed records
                    # are settled by the session manager, which also owns
                    # the reply metadata); lean like the birth — status
                    # only, no token payload
                    self.journal.finish(jrid, reply.get("status", "ok"))
                return reply
            except BaseException as e:
                from lzy_tpu.durable.failures import InjectedCrash

                if self.journal is not None and jrid is not None \
                        and journal_rid is None \
                        and not isinstance(e, InjectedCrash):
                    # a real process death runs no except blocks: the
                    # injected stand-in must leave the record live for
                    # recovery to settle with its typed status
                    self.journal.finish(
                        jrid, "error", error=f"{type(e).__name__}: {e}")
                from lzy_tpu.channels.token_stream import fail_if_touched

                fail_if_touched(stream, e)
                raise

    def _shed_error(self, exc_type, msg: str, *, reason: str,
                    retry_after_s: Optional[float]):
        """Gateway-side shed: the per-service counter plus the shared
        wire format (``scheduler.shed_error`` owns the hint contract)."""
        with self._lock:
            self._shed += 1
        return shed_error(exc_type, msg, reason=reason,
                          retry_after_s=retry_after_s)

    def _generate(self, prompt: List[int], max_new_tokens: int, *,
                  timeout_s: float, deadline_s: Optional[float],
                  greedy: Optional[bool] = None,
                  tenant: str = DEFAULT_TENANT,
                  priority: Optional[int] = None,
                  session: Optional[str] = None,
                  stream=None, liveness=None,
                  resume_tokens: Optional[List[int]] = None,
                  journal_rid: Optional[str] = None) -> dict:
        from lzy_tpu.rpc.core import Unavailable

        t0 = self._clock.now()
        wall_deadline = t0 + timeout_s
        gated = liveness is None        # it holds one of the unary waiters
        fence = (self.fence_auditor.session(prompt)
                 if self.fence_auditor is not None else None)
        # fenced: already streamed tokens. A crash-recovery resubmission
        # seeds the fence with the predecessor's journaled tokens — the
        # loop below then behaves exactly like a failover retry: the
        # effective prompt is prompt + emitted and the stream
        # re-attaches at the fence position.
        emitted: List[int] = ([int(t) for t in resume_tokens]
                              if resume_tokens else [])
        if fence is not None and emitted:
            # the auditor must see the recovered fence as the baseline,
            # not as freshly-generated tokens
            fence.on_failover(emitted, prompt + emitted)
        failovers = 0
        tried_after_failure: set = set()
        route = None                     # (replica, reason) that SERVED it
        first_ttft_ms = None
        while True:
            remaining = max_new_tokens - len(emitted)
            if remaining <= 0:
                break
            if failovers and liveness is not None and self._client_gone(
                    liveness):
                # the client cancelled or vanished BETWEEN attempts
                # (mid-failover): finish with the cancelled contract —
                # fenced partials readable — instead of resubmitting a
                # request the retry replica would only reap anyway
                from lzy_tpu.serving.streams import CANCELS

                CANCELS.inc(phase="failover")
                if fence is not None:
                    fence.on_complete(emitted)
                if stream is not None:
                    stream.close("cancelled")
                _REQUESTS.inc(status="cancelled")
                with self._lock:
                    self._finished += 1
                return {
                    "request_id": None, "tokens": emitted,
                    "status": "cancelled", "ttft_ms": first_ttft_ms,
                    "model": self.model_name,
                    "replica": route[0] if route else None,
                    "routed_by": route[1] if route else None,
                    "failovers": failovers, **self._reply_extras(gated)}
            deadline_left = self._remaining_deadline(t0, deadline_s)
            if deadline_left is not None and deadline_left <= 0:
                # the client deadline ran out between attempts: finish
                # with the engine's own cancelled contract (partial
                # tokens readable) instead of resubmitting a request the
                # retry replica would only cancel anyway
                if fence is not None:
                    fence.on_complete(emitted)
                if stream is not None:
                    stream.close("cancelled")
                _REQUESTS.inc(status="cancelled")
                with self._lock:
                    self._finished += 1
                return {
                    "request_id": None, "tokens": emitted,
                    "status": "cancelled", "ttft_ms": first_ttft_ms,
                    "model": self.model_name,
                    "replica": route[0] if route else None,
                    "routed_by": route[1] if route else None,
                    "failovers": failovers, **self._reply_extras(gated)}
            effective_prompt = prompt + emitted
            with trace.span(trace.GATEWAY_ATTEMPT) as attempt:
                replica, routed_by, req = self._submit_routed(
                    effective_prompt, remaining,
                    t0=t0, deadline_s=deadline_s,
                    exclude=tried_after_failure, greedy=greedy,
                    tenant=tenant, priority=priority, session=session,
                    liveness=liveness)
                route = (replica.id, routed_by)
                if attempt:
                    trace.note(replica=replica.id, routed_by=routed_by,
                               failover=failovers, request=req.id)
                if self.journal is not None and journal_rid is not None:
                    self.journal.record_attempt(journal_rid, replica.id)
                if stream is not None:
                    # the fence is the stream position: this attempt's
                    # tokens land at len(emitted) + i, so a resumed
                    # attempt continues the channel exactly where the
                    # dead one stopped
                    from lzy_tpu.channels.token_stream import attach_request

                    attach_request(stream, req, len(emitted))
                if not req.wait(timeout=max(
                        0.0, wall_deadline - self._clock.now())):
                    req.cancel()
                    # no outcome will ever be recorded for this dispatch:
                    # a half-open probe claim must not outlive it
                    self.fleet.health.release_probe(replica.id)
                    raise TimeoutError(
                        f"request {req.id} not finished within "
                        f"{timeout_s}s")
            if first_ttft_ms is None and req.first_token_at is not None:
                first_ttft_ms = round(
                    1000 * (req.first_token_at - t0), 3)
            if req.error and req.status != "cancelled":
                if not req.error.startswith(_FAILOVER_ERRORS):
                    # request-scoped failure: identical on every replica
                    # (the replica itself worked — free its probe claim)
                    self.fleet.health.release_probe(replica.id)
                    _REQUESTS.inc(status="error")
                    raise RuntimeError(
                        f"request {req.id} failed: {req.error}")
                # replica-scoped failure: fence what it emitted and
                # resubmit elsewhere. Only genuine replica faults accrue
                # toward the health verdict — a KV-pressure preemption is
                # the engine working as designed, not a sick host
                emitted.extend(req.tokens)
                if fence is not None:
                    fence.on_failover(emitted, prompt + emitted)
                if stream is not None:
                    # tokens already published up to the fence; the retry
                    # attempt re-attaches at len(emitted) and the channel
                    # continues byte-identically
                    stream.note_resumption()
                if not req.error.startswith(_CAPACITY_ERRORS):
                    self.fleet.health.record_failure(replica.id)
                    self.router.forget(replica.id)
                    self._drop_leases_on(replica.id)
                    self.fleet.check_health()
                    # a FAULTED replica is out for this request; a merely
                    # SQUEEZED one stays eligible — the resubmission
                    # re-queues behind its admission gate (head-of-line
                    # waits for blocks), which on a single-replica fleet
                    # is the only way the request can ever finish
                    tried_after_failure.add(replica.id)
                else:
                    # a capacity preemption proves the replica WORKS:
                    # free any half-open probe claim, or "stays
                    # eligible" would be a lie — routable() would hide
                    # the replica behind its own live claim and a
                    # single-replica fleet could never finish
                    self.fleet.health.release_probe(replica.id)
                failovers += 1
                self._note_failover()
                if failovers > self._max_failovers:
                    _REQUESTS.inc(status="error")
                    raise Unavailable(
                        f"request failed over {failovers} times; last "
                        f"error: {req.error}")
                _LOG.warning(
                    "gateway: failover %d for request (replica %s: %s); "
                    "%d tokens fenced", failovers, replica.id, req.error,
                    len(emitted))
                continue
            # terminal: ok or cancelled-with-partials
            self.fleet.health.record_success(replica.id)
            emitted.extend(req.tokens)
            if fence is not None:
                fence.on_complete(emitted)
            status = req.status or "ok"
            self._note_result(req)
            if session is not None:
                # index the conversation TAIL (prompt + response) on the
                # serving replica: step N+1's prompt extends exactly
                # this sequence, so both the session pin and the chunk
                # chains predict the next step's cache locality. An
                # expectation is never authority — a stale one costs one
                # redundant prefill, never a wrong token.
                self.router.observe(replica.id, prompt + emitted,
                                    session=session)
            if stream is not None:
                stream.close(status)
            with self._lock:
                self._finished += 1
            _REQUESTS.inc(status=status)
            return {
                "request_id": req.id,
                "tokens": emitted,
                "status": status,
                "ttft_ms": first_ttft_ms,
                "model": self.model_name,
                # the replica that actually FINISHED the stream (after a
                # failover that is the retry's replica, not the dead one)
                "replica": route[0],
                "routed_by": route[1],
                "failovers": failovers,
                **self._reply_extras(gated),
            }
        # emitted already covers max_new_tokens (failover landed exactly
        # on the boundary): the stream is complete
        if fence is not None:
            fence.on_complete(emitted)
        if stream is not None:
            stream.close("ok")
        with self._lock:
            self._finished += 1
        _REQUESTS.inc(status="ok")
        return {"request_id": None, "tokens": emitted, "status": "ok",
                "ttft_ms": first_ttft_ms, "model": self.model_name,
                "replica": route[0] if route else None,
                "routed_by": route[1] if route else None,
                "failovers": failovers,
                **self._reply_extras(gated)}

    @staticmethod
    def _client_gone(liveness) -> bool:
        """Guarded liveness probe (a broken probe must not cancel a
        healthy request — same contract as ``Request.client_dead``)."""
        try:
            return not liveness()
        except Exception:  # noqa: BLE001 — treat a broken probe as alive
            return False

    def _remaining_deadline(self, t0: float,
                            deadline_s: Optional[float]) -> Optional[float]:
        """The client deadline is absolute from first submission
        (anchored at ``t0``); a failover resubmits with whatever is left
        of it — never a reset ``deadline_s``. Can return <= 0: the
        caller short-circuits to the cancelled status instead of
        submitting an already-dead request."""
        if deadline_s is None:
            return None
        return deadline_s - (self._clock.now() - t0)

    def _submit_routed(self, prompt: List[int], max_new_tokens: int, *,
                       t0: float, deadline_s: Optional[float],
                       exclude: set, greedy: Optional[bool] = None,
                       tenant: str = DEFAULT_TENANT,
                       priority: Optional[int] = None,
                       session: Optional[str] = None,
                       liveness=None):
        """Route + submit with per-replica admission fallback: a replica
        refusing admission (full queue, closed engine) drops out of the
        candidate set and the next-best one is tried; only an empty set
        is fleet-wide backpressure. The client deadline is carried as
        ``(t0, deadline_s)`` and re-resolved at every use: staging work
        in ``_pre_submit`` (a disagg remote prefill can legitimately
        take seconds) must come OFF the budget, not be granted back by
        anchoring the engine-side deadline after it."""
        from lzy_tpu.rpc.core import Unavailable

        loads = {rid: load for rid, load in self.fleet.loads().items()
                 if rid not in exclude}
        last_err: Optional[Exception] = None
        # fused hard pin: a live park lease routes the conversation's
        # next step to the replica holding its KV resident. Consumed
        # per-attempt — once the pinned replica drops out of the
        # candidate set (admission refusal, death) the loop degrades to
        # the ordinary routed path and the lease is lazily dropped.
        pinned = self._fused_pin(session) if session is not None else None
        while loads:
            t_route = trace.now() if trace.ON else 0.0
            rid, reason = self.router.choose(prompt, loads,
                                             session=session,
                                             pinned=pinned)
            replica = self.fleet.get(rid)
            # try_route CLAIMS a half-open breaker's single probe — at
            # dispatch, not during enumeration, so listing passes that
            # route elsewhere never burn a recovered replica's probe
            if replica is None or not self.fleet.health.try_route(rid):
                if rid == pinned:
                    # the leased replica is gone or sick: the parked KV
                    # died with it — fall back to ordinary routing
                    self._drop_lease(session)
                    pinned = None
                loads.pop(rid, None)
                continue
            if not self._pre_submit(
                    replica, prompt,
                    deadline_s=self._remaining_deadline(t0, deadline_s),
                    tenant=tenant, liveness=liveness):
                # claimed but never dispatched: release, or the replica
                # would sit probe-blocked for another open_s
                self.fleet.health.release_probe(rid)
                loads.pop(rid, None)
                continue
            # re-resolve AFTER staging; an expiry inside the staging
            # window submits with the floor and the engine cancels it
            # promptly under its own contract
            engine_deadline = self._remaining_deadline(t0, deadline_s)
            if engine_deadline is not None:
                engine_deadline = max(0.001, engine_deadline)
            if trace.ON:
                # the router's pick, the breaker's claim, the staging
                trace.emit(trace.GATEWAY_ROUTE, t_route, trace.now(),
                           replica=rid, routed_by=reason)
            try:
                CHAOS.hit("gateway.dispatch")
                req = replica.engine.submit(
                    prompt, max_new_tokens=max_new_tokens,
                    deadline_s=engine_deadline, greedy=greedy,
                    tenant=tenant, priority=priority,
                    liveness=liveness)
            except PromptTooLong:
                # permanent, request-scoped: it would fail identically
                # on every replica — no fallback, no health damage
                self.fleet.health.release_probe(rid)
                raise
            except AdmissionError as e:
                last_err = e
                self.fleet.health.release_probe(rid)
                loads.pop(rid, None)
                continue
            except BaseException:
                # request-scoped failures (invalid args) propagate to
                # the client, but nothing was dispatched — the probe
                # claim must not outlive the attempt
                self.fleet.health.release_probe(rid)
                raise
            self.router.observe(rid, prompt, session=session)
            return replica, reason, req
        # fleet-wide refusal: shed with the most informative hint we
        # have — an engine's own queue estimate, else the soonest
        # breaker half-open (a fully-tripped fleet recovers on the
        # breaker's clock, not the client's)
        retry_after = getattr(last_err, "retry_after_s", None)
        if retry_after is None:
            retry_after = self.fleet.breaker_retry_after_s()
        if isinstance(last_err, QuotaExceeded):
            # every replica refused on a TENANT limit (per-tenant queue
            # caps): surface the quota-exceeded status, not a generic
            # Unavailable, so the client backs off on its own clock
            with self._lock:
                self._shed += 1
            raise quota_error(
                f"tenant {last_err.tenant!r} over its queue cap on every "
                f"replica: {last_err}",
                tenant=last_err.tenant or tenant,
                reason=last_err.reason or "max_queued",
                retry_after_s=retry_after)
        raise self._shed_error(
            Unavailable,
            f"no replica can admit the request: "
            f"{last_err or 'no routable replicas'}",
            reason="no_replica", retry_after_s=retry_after)

    def _pre_submit(self, replica, prompt: List[int],
                    deadline_s: Optional[float] = None,
                    tenant: str = DEFAULT_TENANT,
                    liveness=None) -> bool:
        """Hook between routing and submission; False drops the replica
        from this request's candidate set. Subclasses use it for
        per-replica staging work that must not be wasted on a replica
        that cannot admit (the disagg gateway probes the queue and then
        stages KV here — bounded by the request's REMAINING deadline,
        queued under the request's tenant, and skipped entirely for a
        client ``liveness`` already reports gone). The base gateway's
        staging work is the fleet-global tiered-KV import: a routed
        replica about to miss a prefix a sibling advertises gets the
        sibling's blocks queued for import first — AFTER the admission
        probe (staging for a replica that cannot admit would waste a
        whole export + transfer and park imported blocks where no
        routed request will match them), bounded by the request's
        remaining deadline, and skipped for a client already gone."""
        if self.kv_index is None:
            return True
        self._reset_kv_import_meta()
        engine = replica.engine
        if getattr(engine, "closed", False) or \
                engine.queue.depth() >= engine.queue.max_depth:
            return False
        if not (liveness is not None and self._client_gone(liveness)):
            self._stage_kv_import(replica, prompt, deadline_s=deadline_s)
        return True

    # -- workflow-aware scheduling (lzy_tpu/llm/sched.py) ---------------------

    def _fused_pin(self, session: Optional[str]) -> Optional[str]:
        """The replica a live fusion lease pins ``session`` to, with
        lazy expiry (the engine-side TTL sweep is authoritative; this
        map only mirrors it for routing)."""
        if session is None:
            return None
        with self._wf_lock:
            lease = self._wf_parked.get(session)
            if lease is None:
                return None
            rid, expires = lease
            if self._clock.now() >= expires:
                del self._wf_parked[session]
                return None
            return rid

    def _drop_lease(self, session: Optional[str]) -> None:
        if session is None:
            return
        with self._wf_lock:
            self._wf_parked.pop(session, None)

    def _drop_leases_on(self, replica_id: str) -> None:
        """A dead/retired replica's parked KV died with it: drop every
        lease pointing at it so the next steps route normally (the
        engine's own close released the pins, or the host is gone)."""
        with self._wf_lock:
            for session in [s for s, (rid, _) in self._wf_parked.items()
                            if rid == replica_id]:
                del self._wf_parked[session]

    def park_conversation(self, session: str, tokens: Sequence[int],
                          ttl_s: Optional[float] = None) -> bool:
        """Park ``session``'s conversation KV — the radix chain covering
        ``tokens`` — resident on the replica that served it, for up to
        ``ttl_s`` (the gateway default when None). Called by the
        workflow scheduler when a ``generate -> tool-op`` step
        completes: the following ``generate`` then hard-pins to this
        replica ("fused" route) and prefills only its suffix. Advisory
        end to end — False (no session pin yet, replica gone, engine
        without a park surface, nothing cached) leaves the ordinary
        routed path untouched."""
        ttl = self._wf_park_ttl if ttl_s is None else float(ttl_s)
        rid = self._fused_pin(session)
        if rid is None:
            rid = self.router.session_replica(session)
        if rid is None:
            return False
        replica = self.fleet.get(rid)
        park = (getattr(replica.engine, "park_chain", None)
                if replica is not None else None)
        if park is None:
            return False
        try:
            ok = bool(park(f"conv:{session}", list(tokens), ttl_s=ttl))
        except Exception:  # noqa: BLE001 — parking is advisory
            ok = False
        if ok:
            with self._wf_lock:
                self._wf_parked[session] = (rid, self._clock.now() + ttl)
        else:
            self._drop_lease(session)
        return ok

    def unpark_conversation(self, session: str) -> bool:
        """Release ``session``'s fusion lease and its engine-side pins
        (blocks fall back to ordinary LRU cache). Harmless when nothing
        is parked."""
        rid = self._fused_pin(session)
        self._drop_lease(session)
        if rid is None:
            return False
        replica = self.fleet.get(rid)
        unpark = (getattr(replica.engine, "unpark_chain", None)
                  if replica is not None else None)
        if unpark is None:
            return False
        try:
            return bool(unpark(f"conv:{session}"))
        except Exception:  # noqa: BLE001 — advisory
            return False

    def speculate_prefill(self, session: str, tokens: Sequence[int], *,
                          tenant: str = DEFAULT_TENANT,
                          timeout_s: float = 30.0) -> bool:
        """Speculative next-step prefill: while the tool op runs, chunk-
        prefill the KNOWN prompt prefix of the conversation's next step
        (``tokens`` = prompt + reply of the step that just finished) on
        the leased replica as a 1-token greedy request at BACKGROUND
        priority (WFQ tier 2), then re-park so the freshly cached reply
        blocks ride the pin. The next step's TTFT becomes a suffix
        prefill. Uncharged and uncounted by design: no SLO admission, no
        waiter slot, no request accounting — the engine request rides a
        reserved internal tenant so the caller's own per-tenant counters
        and fair-queue share never pay for it. A wrong speculation is
        cache pollution that LRU-evicts once the pin lapses. Never
        raises."""
        del tenant  # accepted for interface symmetry; never charged
        rid = self._fused_pin(session)
        if rid is None:
            self._note_speculation("no_lease")
            return False
        replica = self.fleet.get(rid)
        if replica is None:
            self._drop_lease(session)
            self._note_speculation("no_lease")
            return False
        try:
            req = replica.engine.submit(
                [int(t) for t in tokens], max_new_tokens=1,
                deadline_s=timeout_s, greedy=True,
                tenant=SPECULATION_TENANT, priority=2)
        except Exception:  # noqa: BLE001 — speculation is advisory
            self._note_speculation("error")
            return False
        if not req.wait(timeout=timeout_s):
            req.cancel()
            self._note_speculation("timeout")
            return False
        if req.status != "ok":
            self._note_speculation("miss")
            return False
        # extend the pin over the blocks the speculation just cached
        # (the reply positions — decode never tree-caches them, so this
        # prefill is the only way they become matchable)
        self.park_conversation(session, tokens)
        self._note_speculation("ok")
        return True

    def _wf_parked_count(self) -> int:
        with self._wf_lock:
            return len(self._wf_parked)

    @staticmethod
    def _note_speculation(outcome: str) -> None:
        # lazy leaf import, same contract as _session_rate_gauge: the
        # gateway must not import the llm package at module scope
        from lzy_tpu.llm.metrics import SPECULATIONS

        SPECULATIONS.inc(outcome=outcome)

    def _reset_kv_import_meta(self) -> None:
        """Reset the PER-ATTEMPT staging meta up front (both gateways
        call this at the top of their ``_pre_submit``, BEFORE the
        admission probe): an attempt that skips staging — client gone,
        expired deadline, admission-probe drop — must not inherit, and
        report, the previous attempt's kv_import_staged_from/tier/ms."""
        meta = self._kvtier_meta()
        meta.pop("kv_import_staged_from", None)
        meta.pop("kv_import_tier", None)
        meta.pop("kv_import_ms", None)

    def _stage_kv_import(self, replica, prompt: List[int],
                         deadline_s: Optional[float] = None) -> None:
        """Best-effort cross-replica prefix import (the tiered-KV
        tentpole): consult the global index for a sibling holding a
        deeper whole-block prefix than the routed replica can cover
        (radix tree + its own tiers), export from the sibling on ITS
        scheduling thread, move the payload through the transport, and
        queue it on the routed replica — whose next scheduling round
        folds it in strictly before the request's admission. Never
        raises: every failure (source retired mid-export, transport
        death, the ``kvtier.import`` chaos fault) is one counted
        fallback and the replica re-prefills locally. ``deadline_s``
        is the request's REMAINING client deadline: the export wait is
        capped by it (a request with 200 ms left must not park behind a
        5 s sibling gather), and a nearly-expired request skips staging
        entirely — re-prefill is then the cheaper bet."""
        engine = replica.engine
        kv = getattr(engine, "kv", None)
        queue_import = getattr(engine, "queue_kv_import", None)
        if kv is None or queue_import is None:
            return
        export_timeout = 5.0
        if deadline_s is not None:
            if deadline_s < 0.05:
                return
            export_timeout = min(export_timeout, deadline_s)
        meta = self._kvtier_meta()       # attempt meta reset by caller
        page = kv.page_size
        n_full = (len(prompt) - 1) // page
        if n_full == 0:
            return
        prefix = [int(t) for t in prompt[:n_full * page]]
        # local coverage counts every rung the replica can promote from
        # on its own — importing what the host tier already holds would
        # waste a transfer
        tier_probe = getattr(engine, "kv_tier_match_len", None)
        local = (tier_probe(prefix) if tier_probe is not None
                 else kv.match_len(prefix))
        if local >= len(prefix):
            return
        holder = self.kv_index.best_holder(
            prefix, exclude=(replica.id,), min_depth_tokens=local)
        if holder is None:
            return
        t0 = self._clock.now()
        try:
            CHAOS.hit("kvtier.import")
            src = self.fleet.get(holder.replica_id)
            if src is None or getattr(src.engine, "request_kv_export",
                                      None) is None:
                raise LookupError(
                    f"holder {holder.replica_id} retired mid-route")
            export = src.engine.request_kv_export(
                prefix[:holder.depth_tokens], timeout_s=export_timeout)
            if export is None:
                raise LookupError(
                    f"holder {holder.replica_id} declined the export")
            if export.prefilled_by is None:
                # origin provenance rides the radix insert on the
                # importer: replies can say whose KV really warmed them
                export.prefilled_by = holder.replica_id
            with self._kvtier_lock:
                self._kvtier_seq += 1
                key = f"kvtier-{self._kvtier_seq}"
            ref = self.kv_transport.publish(key, export)
            try:
                fetched = self.kv_transport.fetch(ref)
            finally:
                try:
                    self.kv_transport.discard(ref)
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
            queue_import(fetched)
        except Exception as e:  # noqa: BLE001 — import is advisory
            from lzy_tpu.gateway.kv_index import IMPORT_FALLBACKS

            with self._kvtier_lock:
                self._kvtier_fallbacks += 1
            IMPORT_FALLBACKS.inc()
            _LOG.info("kvtier: cross-replica import from %s failed "
                      "(%s: %s); %s will re-prefill locally",
                      holder.replica_id, type(e).__name__, e, replica.id)
            return
        from lzy_tpu.gateway.kv_index import (
            IMPORT_BYTES, IMPORT_SECONDS, IMPORTS)

        dt = self._clock.now() - t0
        with self._kvtier_lock:
            self._kvtier_imports += 1
            self._kvtier_import_bytes += fetched.nbytes
        IMPORTS.inc(from_tier=holder.tier)
        IMPORT_BYTES.inc(fetched.nbytes)
        IMPORT_SECONDS.observe(dt)
        meta["kv_import_staged_from"] = holder.replica_id
        meta["kv_import_tier"] = holder.tier
        meta["kv_import_ms"] = round(1000 * dt, 3)

    def _kvtier_meta(self) -> dict:
        meta = getattr(self._kvtier_tls, "meta", None)
        if meta is None:
            meta = self._kvtier_tls.meta = {}
        return meta

    def _note_result(self, req) -> None:
        """Hook: the terminal request of a (possibly failed-over)
        generate, observed before the reply is built — subclasses read
        request-side provenance off it (the disagg gateway records which
        prefill pool's KV the final attempt actually used). With the
        global KV index on, the base gateway does the same for
        cross-replica imports: ``kv_prefilled_by`` is set at
        prefix-match time from the radix chain's origin, so it names
        the sibling whose KV the attempt REALLY decoded from — an
        import that was staged but skipped (pool too hot, mismatched
        payload) leaves it None, matching the re-prefill that actually
        happened."""
        if self.kv_index is not None:
            self._kvtier_meta()["kv_used_from"] = getattr(
                req, "kv_prefilled_by", None)

    def _reply_extras(self, gated: bool = True) -> dict:
        """Extra route metadata merged into every reply — subclasses
        extend (the disagg gateway adds ``prefilled_by`` /
        ``kv_transfer_ms``); unknown reply fields are preserved by older
        clients (proto3 rule). Every reply says what the plane holds as
        this call saw it (``scheduler.plane_capacity``): ``plane_slots``,
        the READY replicas' slots, and for a ``gated`` call (no
        ``liveness``: it held one of the unary waiters)
        ``plane_admits``, the waiter cap, which also bounds the slots. A
        caller that keeps many calls in flight sizes itself by them
        (``llm/sched.py``'s window of rows). With the global KV index on,
        replies carry the cross-replica import provenance: ``kv_import_from``
        is the sibling whose KV the serving attempt actually USED (its
        imported blocks matched at prefill — None when the attempt hit
        purely-local KV or re-prefilled), ``kv_import_staged_from`` the
        holder whose export was STAGED for the attempt (staged ≠ used:
        the engine folds imports in opportunistically and a refusal
        under pool pressure silently re-prefills), ``kv_import_tier``
        the rung the source exported from, and ``kv_import_ms`` the
        staging latency."""
        # one walk over the replicas a reply, on the caller's thread:
        # stats() reads counters and takes no lock a round holds
        out = plane_capacity(
            sum(r.engine.stats().slots for r in self.fleet.replicas()),
            self._max_waiters if gated else None)
        if self.kv_index is None:
            return out
        meta = self._kvtier_meta()
        out.update({
            "kv_import_from": meta.get("kv_used_from"),
            "kv_import_staged_from": meta.get("kv_import_staged_from"),
            "kv_import_tier": meta.get("kv_import_tier"),
            "kv_import_ms": meta.get("kv_import_ms"),
        })
        return out

    def _note_failover(self) -> None:
        with self._lock:
            self._failovers += 1
        _FAILOVERS.inc()

    # -- control loop --------------------------------------------------------

    def tick(self, now: Optional[float] = None) -> Optional[str]:
        """One health + autoscale round (the background loop calls this
        every ``tick_period_s``; tests call it with an injected clock).
        Returns the applied scale direction, if any."""
        now = now if now is not None else self._clock.time()
        for rid in self.fleet.check_health(now=now):
            self.router.forget(rid)
            self._drop_leases_on(rid)
            if self.kv_index is not None:
                self.kv_index.forget(rid)
                self._kvtier_last_adv.pop(rid, None)
        for rid in self.fleet.reap_drained():
            self.router.forget(rid)
            self._drop_leases_on(rid)
            if self.kv_index is not None:
                self.kv_index.forget(rid)
                self._kvtier_last_adv.pop(rid, None)
        force = self._kv_force_refresh
        self._kv_force_refresh = False
        self.refresh_kv_index(force=force)
        if self.journal is not None:
            # terminal journal records age out with the same ttl as the
            # stream manager's resume window — past it nothing can
            # re-poll them, so keeping the rows only grows the store
            self.journal.prune_terminal(self.streams.terminal_ttl_s)
        if self.autoscaler is None:
            return None
        ready = len(self.fleet.replicas())
        if ready < self.autoscaler.min_replicas:
            # recovery, not scaling: health-based retirement can take the
            # fleet below its floor (or to zero, where no queue pressure
            # can ever build because nothing admits) — re-lease without
            # waiting for pressure windows or cooldowns, one per tick
            _LOG.warning("gateway: %d/%d replicas; re-leasing",
                         ready, self.autoscaler.min_replicas)
            try:
                self.fleet.add_replica()
            except Exception:  # noqa: BLE001 — retried next tick
                _LOG.exception("gateway: recovery lease failed")
                return None
            with self._lock:
                self._scale_ups += 1
            _SCALE.inc(direction="up")
            return UP
        agg = self.fleet.aggregate()
        decision = self.autoscaler.tick(
            now, replicas=ready, queue_depth=agg["queue_depth"],
            busy=agg["busy"], slots=agg["slots"])
        if decision is None:
            return None
        if decision.direction == UP:
            _LOG.info("gateway: scaling up (%s)", decision.reason)
            try:
                self.fleet.add_replica()
            except Exception:  # noqa: BLE001 — a failed lease must not
                _LOG.exception("gateway: scale-up failed")  # kill the loop
                return None
            with self._lock:
                self._scale_ups += 1
            _SCALE.inc(direction="up")
            return UP
        _LOG.info("gateway: scaling down (%s)", decision.reason)
        coldest = self._coldest_replica()
        if coldest is None:
            return None
        self.fleet.drain(coldest)
        with self._lock:
            self._scale_downs += 1
        _SCALE.inc(direction="down")
        return DOWN

    def refresh_kv_index(self, force: bool = False) -> None:
        """Refresh the fleet-global prefix index from each replica's
        advertisement (chains by tier); pull-based and advisory — a
        stale entry costs one pointless import attempt at worst.
        Engines memoize the advertisement by cache-structure version
        (unchanged cache → SAME object), so a quiet fleet skips the
        re-hash entirely tick after tick. ``force=True`` (a recovered
        gateway's cold start) skips the identity memo and re-reads every
        replica — the index must be whole BEFORE the first routed
        request, not after the first periodic tick."""
        if self.kv_index is None:
            return
        from lzy_tpu.gateway.kv_index import chains_of

        for replica in self.fleet.replicas():
            chains = chains_of(replica.engine)
            if not chains:
                continue
            if not force and \
                    self._kvtier_last_adv.get(replica.id) is chains:
                continue
            self.kv_index.update_replica(replica.id, chains)
            self._kvtier_last_adv[replica.id] = chains

    def _coldest_replica(self) -> Optional[str]:
        """Drain victim: the replica with the least routing heat (fewest
        indexed prefix chains), load as tie-break — evicting the coldest
        cache forfeits the least accumulated prefill work."""
        loads = self.fleet.loads()
        if not loads:
            return None
        chains = self.router.stats().get("indexed_chains", {})
        return min(sorted(loads),
                   key=lambda r: (chains.get(r, 0), loads[r]))

    def start(self) -> "GatewayService":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._clock.wait(self._stop, self._tick_period_s):
                try:
                    self.tick()
                except Exception:  # noqa: BLE001 — the tick must not die
                    _LOG.exception("gateway tick failed")

        self._thread = threading.Thread(
            target=loop, name="gateway-tick", daemon=True)
        self._thread.start()
        return self

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown: stop admitting (new calls shed with
        ``draining``), let every in-flight request finish its stream,
        then close — which retires the fleet and releases every lease.
        Returns True if all in-flight work finished inside the budget
        (False: close() failed the stragglers with the usual shutdown
        error)."""
        self._draining = True
        deadline = self._clock.now() + timeout_s
        while self._clock.now() < deadline:
            with self._lock:
                if self._inflight == 0:
                    break
            self._clock.sleep(0.02)
        with self._lock:
            drained = self._inflight == 0
        if not drained:
            _LOG.warning("gateway drain: %d request(s) still in flight "
                         "after %.1fs; closing anyway", self._inflight,
                         timeout_s)
        self.close()
        return drained

    def close(self) -> None:
        self._stop.set()
        self.streams.close()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        self.fleet.close()

    # -- observability -------------------------------------------------------

    def _operator_view(self, subject) -> bool:
        """Stats scoping: no IAM (operator tool) and the INTERNAL role
        see the fleet; every other subject sees only its own tenant."""
        if subject is None:
            return True
        from lzy_tpu.iam import INTERNAL

        return subject.role == INTERNAL

    def _tenant_scoped_stats(self, tenant: str) -> dict:
        """One tenant's own counters — what a non-operator subject gets
        from ``InferStats`` (fleet internals are the operator's; a
        tenant's numbers are its own)."""
        from lzy_tpu.serving.tenancy import TENANT_ROW

        row = self.fleet.aggregate_tenants().get(
            tenant, dict(TENANT_ROW, queue_depth=0))
        return {"model": self.model_name, "gateway": True,
                "tenant": tenant, **row}

    def stats(self, *, token: Optional[str] = None) -> dict:
        """Fleet-level ``InferStats`` doc: aggregates + routing + scaling
        counters plus the per-tenant breakdown — for the operator (no
        IAM, or the INTERNAL role). Any other authenticated subject gets
        only its own tenant's counters (:meth:`_tenant_scoped_stats`).
        Per-replica breakdown lives in :meth:`fleet_stats`."""
        subject = self._auth(token)
        if not self._operator_view(subject):
            return self._tenant_scoped_stats(subject.id)
        agg = self.fleet.aggregate()
        routing = self.router.stats()
        hit_rate = 0.0
        if agg["prefix_lookup_tokens"]:
            hit_rate = agg["prefix_hit_tokens"] / agg["prefix_lookup_tokens"]
        spec_rate = spec_tps = 0.0
        if agg["spec_proposed_tokens"]:
            spec_rate = (agg["spec_accepted_tokens"]
                         / agg["spec_proposed_tokens"])
            # tokens-per-row-step only once speculation has actually
            # proposed something: a spec-off fleet reports 0.0, not a
            # trivially-true 1.0 (the stats comment promises zeros)
            if agg["decode_rows"]:
                spec_tps = agg["decode_tokens"] / agg["decode_rows"]
        with self._lock:
            fo, fin = self._failovers, self._finished
            ups, downs = self._scale_ups, self._scale_downs
            shed = self._shed
        doc = {
            "model": self.model_name,
            "gateway": True,
            "replicas": agg["replicas"],
            "replicas_ready": len(self.fleet.replicas()),
            "slots": agg["slots"],
            "busy": agg["busy"],
            "queue_depth": agg["queue_depth"],
            "requests_finished": fin,
            "tokens_generated": agg["tokens_generated"],
            "requests_shed": shed,
            "failovers": fo,
            "scale_ups": ups,
            "scale_downs": downs,
            "routed_total": routing["routed_total"],
            "routed_by_prefix": routing["routed_by_prefix"],
            "prefix_route_rate": routing["prefix_route_rate"],
            "fleet_prefix_hit_rate": round(hit_rate, 4),
            # fleet-wide speculative decoding (zeros when --serve-spec
            # is off: the counters simply never move)
            "spec_proposed_tokens": agg["spec_proposed_tokens"],
            "spec_accepted_tokens": agg["spec_accepted_tokens"],
            "spec_acceptance_rate": round(spec_rate, 4),
            "spec_tokens_per_step": round(spec_tps, 4),
            "spec_draft_truncated": agg["spec_draft_truncated"],
            # workflow-aware scheduling: conversations currently holding
            # a fusion lease (their KV parked resident across a tool gap)
            "wf_parked_sessions": self._wf_parked_count(),
            # per-tenant breakdown (operator view only — this branch)
            "tenants": self.fleet.aggregate_tenants(),
        }
        if self.kv_index is not None:
            with self._kvtier_lock:
                doc.update({
                    "kvtier": True,
                    "kvtier_imports": self._kvtier_imports,
                    "kvtier_import_bytes": self._kvtier_import_bytes,
                    "kvtier_reprefill_fallbacks": self._kvtier_fallbacks,
                })
            doc.update({
                "kvtier_demotions": agg.get("kv_tier_demotions", 0),
                "kvtier_promotions": agg.get("kv_tier_promotions", 0),
                "kvtier_host_blocks": agg.get("kv_host_tier_blocks", 0),
                "kvtier_index": self.kv_index.stats(),
            })
        if self.journal is not None:
            doc["journal"] = self.journal.stats()
        return doc

    def fleet_stats(self, *, token: Optional[str] = None) -> dict:
        """Per-replica breakdown (engine stats + lease + health);
        operator-only under IAM — replica internals are not tenant
        data."""
        subject = self._auth(token)
        if not self._operator_view(subject):
            from lzy_tpu.iam import AuthError

            raise AuthError(
                "fleet stats are operator-only (INTERNAL role); tenants "
                "read their own counters from InferStats")
        rows = []
        for state in ("READY", "DRAINING"):
            for replica in self.fleet.replicas(state=state):
                doc = replica.engine.stats().doc()
                doc.update({
                    "replica": replica.id,
                    "state": replica.state,
                    "vm_ids": list(replica.vm_ids),
                    "consecutive_failures":
                        self.fleet.health.failures(replica.id),
                })
                rows.append(doc)
        return {"model": self.model_name, "replicas": rows}

"""Request admission for the inference engine.

Historically a bounded FIFO; now a **weighted fair queue over per-tenant
subqueues** (virtual-time WFQ, a.k.a. start-time fair queuing): every
request carries a tenant and a priority tier, each tenant owns a FIFO
subqueue, and the queue dispenses the head with the smallest virtual
finish tag. Cost is measured in tokens (prompt + requested continuation)
scaled by the tenant's weight, so

- tenants sharing a replica split its token throughput by weight, not by
  arrival rate — a client flooding the queue only competes with itself;
- a starved tenant's head request always ages to the front: its start
  tag is clamped to the global virtual time, which advances with every
  dispatch, so no weight assignment can postpone it forever;
- with a single tenant (or uniform weights and one-at-a-time arrivals)
  dispatch order degrades to exactly the old FIFO.

Backpressure is two-layered: a *global* bound (``max_depth``) sheds with
the queue-wide drain estimate, and a *per-tenant* bound
(``TenantPolicy.max_queued``) sheds that tenant alone with a
tenant-scoped ``retry_after_s`` — one tenant's backlog never converts
into another tenant's rejection. Queue depth is exported globally and
per tenant so operators see *who* is saturating, not just that someone
is.
"""

from __future__ import annotations

import itertools
import threading
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from lzy_tpu.chaos.faults import CHAOS
from lzy_tpu.utils import trace
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.utils.metrics import REGISTRY

_QUEUE_DEPTH = REGISTRY.gauge(
    "lzy_inference_queue_depth", "requests admitted but not yet prefilled")
_TENANT_QUEUE = REGISTRY.gauge(
    "lzy_tenant_queue_depth",
    "requests admitted but not yet prefilled, by tenant")
_REJECTED = REGISTRY.counter(
    "lzy_inference_rejected_total", "requests refused at admission")
#: shared shedding counter (the gateway imports this rather than
#: re-declaring, so the metric has exactly one owner)
SHED_REQUESTS = REGISTRY.counter(
    "lzy_shed_requests_total",
    "requests shed with a retry-after hint instead of queued, by reason")
TENANT_SHED = REGISTRY.counter(
    "lzy_tenant_shed_total",
    "requests shed at a tenant-scoped limit, by tenant and reason")

#: the default tenant every request without an identity lands on — the
#: single-tenant deployments (and every pre-tenancy caller) run entirely
#: inside this one
DEFAULT_TENANT = "default"

#: priority tier -> WFQ weight. Tier 0 is interactive (largest share),
#: tier 1 the standard default, tier 2 batch/background. Weights are
#: RELATIVE shares of a contended replica's token throughput, not
#: absolute guarantees; an uncontended tenant always gets full speed.
TIER_WEIGHTS = {0: 4.0, 1: 2.0, 2: 1.0}
DEFAULT_PRIORITY = 1


def tier_weight(priority: Optional[int]) -> float:
    """WFQ weight for a priority tier (out-of-range tiers clamp)."""
    if priority is None:
        priority = DEFAULT_PRIORITY
    return TIER_WEIGHTS[min(max(int(priority), 0), max(TIER_WEIGHTS))]


class AdmissionError(RuntimeError):
    """The request queue is full; retry later (backpressure, not failure).

    ``retry_after_s`` is the load-shedding hint: how long the shedding
    layer estimates the caller should back off before the resource it
    was refused (queue space, waiter threads, a routable replica) is
    likely to exist again. The RPC front folds it into the
    ``Unavailable`` reply so well-behaved clients retry on the stack's
    schedule instead of hammering a saturated plane."""

    def __init__(self, msg: str, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class PromptTooLong(AdmissionError, ValueError):
    """The prompt can never be served by this plane (prompt +
    max_new_tokens exceeds the model's ``max_seq_len``, or the prompt
    alone exceeds a hard pool/quota bound). A *permanent* admission
    rejection: unlike its retryable parent it carries no retry hint, is
    never failed over (it would fail identically on every replica), and
    maps to INVALID_ARGUMENT on the wire — the request itself is wrong,
    not the plane's capacity. Raised at admission so an over-long prompt
    surfaces as one clear error instead of a shape/indexing failure deep
    inside prefill (which would also count against replica health)."""


class QuotaExceeded(AdmissionError):
    """A tenant-scoped SLO limit refused the request: token-bucket rate
    limit (requests/s or prompt-tokens/s), per-tenant queue depth, or
    per-tenant KV-block quota. Retryable — ``retry_after_s`` is sized to
    *that tenant's* refill/drain schedule, so a well-behaved client backs
    off on its own clock while other tenants are unaffected. Maps to
    RESOURCE_EXHAUSTED on the wire."""

    def __init__(self, msg: str, retry_after_s: Optional[float] = None,
                 tenant: Optional[str] = None, reason: Optional[str] = None):
        super().__init__(msg, retry_after_s)
        self.tenant = tenant
        self.reason = reason


def shed_error(exc_type, msg: str, *, reason: str,
               retry_after_s: Optional[float] = None):
    """Build (and count) a load-shedding rejection: the retry-after
    hint rides both the exception attribute (in-process callers) and
    the message suffix (it must survive RPC serialization). ONE owner
    for the wire format — the gateway and the single-engine front both
    build their rejections here."""
    SHED_REQUESTS.inc(reason=reason)
    if retry_after_s is not None:
        msg = f"{msg} (retry_after_s={retry_after_s:.2f})"
    err = exc_type(msg)
    err.retry_after_s = retry_after_s
    return err


def plane_capacity(slots: int, waiters: Optional[int] = None) -> dict:
    """What a reply says of the plane that built it, for a caller that
    sizes its own concurrency by it (``llm/sched.py``'s window of rows):
    ``plane_slots``, the decode slots that can work at once, and — for a
    caller the front gates — ``plane_admits``, the calls it takes at once
    before it sheds (``waiters_busy``), which also bounds the slots. A
    caller with a ``liveness`` probe is not gated (``waiters`` None). ONE
    owner for the field names: the gateways, the single-engine front and
    ``llm.EngineBackend`` all build them here."""
    if waiters is None:
        return {"plane_slots": int(slots)}
    return {"plane_slots": min(int(slots), int(waiters)),
            "plane_admits": int(waiters)}


def quota_error(msg: str, *, tenant: str, reason: str,
                retry_after_s: Optional[float] = None,
                counted: bool = True) -> QuotaExceeded:
    """Tenant-scoped twin of :func:`shed_error`: counts the shed under
    both the fleet-wide and the per-tenant counter and builds the
    :class:`QuotaExceeded` with the hint riding the message (wire) and
    the attribute (in-process). ``counted=False`` skips the counters —
    for refusals that are NOT client-facing (an engine probe the gateway
    retries elsewhere; the client-facing boundary counts those via
    :func:`count_tenant_shed` only when the refusal reaches the client)."""
    if counted:
        SHED_REQUESTS.inc(reason=reason)
        TENANT_SHED.inc(tenant=tenant, reason=reason)
    if retry_after_s is not None:
        msg = f"{msg} (retry_after_s={retry_after_s:.2f})"
    return QuotaExceeded(msg, retry_after_s=retry_after_s,
                         tenant=tenant, reason=reason)


def count_tenant_shed(err: QuotaExceeded) -> None:
    """Count an engine-raised (uncounted) quota refusal at the boundary
    where it becomes client-facing — the single-engine plane has no
    other replica to try, so the refusal IS the shed there."""
    SHED_REQUESTS.inc(reason=err.reason or "quota")
    TENANT_SHED.inc(tenant=err.tenant or DEFAULT_TENANT,
                    reason=err.reason or "quota")


_ids = itertools.count(1)


class Request:
    """One generation request riding through the engine.

    ``tokens`` accumulates generated ids (no prompt echo); ``result()``
    blocks until the engine marks the request finished. ``error`` carries
    an engine-side failure (e.g. over-long prompt at prefill time).

    ``deadline_s`` is a client deadline relative to submission: once it
    passes, the engine evicts the request mid-decode (slot and KV-cache
    blocks freed) and finishes it with the ``cancelled`` terminal status —
    partial tokens stay readable on ``tokens``, and the RPC surface
    returns them with ``status: "cancelled"`` instead of raising.

    ``greedy`` is a per-request sampling override: ``True`` forces argmax
    decoding for this row even on an engine configured with
    ``temperature>0`` (the row becomes eligible for speculative decoding
    — ``serving/spec.py``); ``False`` forces sampling with the engine's
    temperature/top_k/top_p; ``None`` (default) follows the engine-wide
    setting. Sampled rows sharing a batch with greedy rows keep the exact
    rng draw order they had before the override existed.

    ``tenant``/``priority`` are the SLO identity: the tenant names the
    WFQ subqueue (and the KV quota / rate-limit bucket), the priority
    tier sets the fairness weight. Both default to the single-tenant
    values, so pre-tenancy callers are unchanged."""

    def __init__(self, prompt: Sequence[int], max_new_tokens: int,
                 request_id: Optional[str] = None,
                 deadline_s: Optional[float] = None,
                 greedy: Optional[bool] = None,
                 tenant: str = DEFAULT_TENANT,
                 priority: Optional[int] = None,
                 liveness=None, clock=None):
        self.id = request_id or f"req-{next(_ids)}"
        self.prompt: List[int] = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.greedy = greedy
        self.tenant = str(tenant) if tenant else DEFAULT_TENANT
        self.priority = None if priority is None else int(priority)
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.status: Optional[str] = None     # "ok" | "cancelled" | "error"
        self.cancelled = False
        # injectable time (utils/clock): deadlines, TTFT and the waiter
        # wake-up all run on it — the load plane's virtual clock makes a
        # simulated hour of requests expire, finish and wake in virtual
        # time; the default is indistinguishable from time.monotonic()
        self._clock = clock if clock is not None else SYSTEM_CLOCK
        self.submitted_at = self._clock.now()
        self.deadline: Optional[float] = (
            self.submitted_at + float(deadline_s)
            if deadline_s is not None else None)
        #: when the engine popped it from the queue into a slot: what
        #: splits queue wait from prefill inside the time to first token
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        #: the submitting thread's open span (utils/trace.py), carried to
        #: the engine loop: ``engine.request`` is written under it
        self._trace_parent = trace.context() if trace.ON else None
        #: optional per-token hook (``channels.token_stream.attach_request``
        #: wires a stream here): called by the engine loop after every
        #: emission with this request; the engine guards it — a consumer
        #: bug must never kill the decode loop. None costs one attribute
        #: load per emitted token.
        self.token_sink = None
        #: optional reply-channel liveness probe (a streaming session's
        #: ``alive`` — ``serving.streams``): the engines call it every
        #: scheduling round via :meth:`client_dead`, so a client that
        #: disconnected (stopped polling) or stalled past the bounded
        #: buffer is reaped wherever the request sits — queued, staged,
        #: or slot-resident — within one decode round. None (unary
        #: callers) costs one attribute load per reap sweep.
        self.liveness = liveness
        #: scheduling phase, maintained by the engine: ``queued`` →
        #: ``prefill`` (staged) → ``decode`` (slot-resident). Read by
        #: streaming keepalive frames (a long prefill is not a stalled
        #: engine) and by the cancel-by-phase accounting.
        self.phase = "queued"
        #: provenance: the prefill-pool replica whose imported KV blocks
        #: this request's prefix match actually HIT (None: locally
        #: prefilled, dense engine, or no match) — set by the paged
        #: engine at prefill staging, read by the disagg gateway's reply
        self.kv_prefilled_by: Optional[str] = None
        self._done = self._clock.event()
        # WFQ bookkeeping (owned by RequestQueue): virtual start/finish
        # tags, arrival sequence, and the queued flag
        self._vstart = 0.0
        self._vfinish = 0.0
        self._qseq = 0
        self._queued = False

    def cancel(self) -> None:
        """Best-effort abandon (e.g. the waiting client timed out): a
        queued request is dropped at pop time, a slot-resident one is
        freed at the engine's next scheduling round — either way the
        engine stops spending decode steps on tokens nobody will read."""
        self.cancelled = True

    @property
    def expired(self) -> bool:
        """Client deadline passed (the engine reaps these like cancels)."""
        return self.deadline is not None and self._clock.now() > self.deadline

    @property
    def client_dead(self) -> bool:
        """The reply channel's liveness says nobody is reading — the
        engine reaps these like cancels (a dead client must never hold a
        slot or KV blocks to the full deadline). A liveness probe that
        RAISES is detached and treated as alive: a broken probe must not
        cancel a healthy request, and the deadline still bounds it."""
        probe = self.liveness
        if probe is None:
            return False
        try:
            return not probe()
        except Exception:  # noqa: BLE001 — see docstring
            self.liveness = None
            return False

    @property
    def reapable(self) -> bool:
        """Cancelled, past deadline, or abandoned by its client — the
        one predicate every reap sweep (queue, staged prefill jobs,
        slots) checks."""
        return self.cancelled or self.expired or self.client_dead

    def finish(self, error: Optional[str] = None,
               status: Optional[str] = None) -> None:
        self.error = error
        self.status = status or ("ok" if error is None else "error")
        self.finished_at = self._clock.now()
        if trace.ON and not self._done.is_set():
            self._trace_finish()
        self._done.set()

    def _trace_finish(self) -> None:
        """``engine.request`` and its three children, once, from the
        stamps: queued until admitted, prefill until the first token,
        decode until finished. A request that ended early has only the
        children it reached, the last of them running to the end."""
        end = self.finished_at
        admitted = end if self.admitted_at is None else self.admitted_at
        first = end if self.first_token_at is None else self.first_token_at
        ctx = trace.emit(trace.ENGINE_REQUEST, self.submitted_at, end,
                         parent=self._trace_parent, request=self.id,
                         status=self.status, tokens=len(self.tokens),
                         prompt_tokens=len(self.prompt))
        for name, a, b in (
                (trace.ENGINE_REQUEST_QUEUED, self.submitted_at, admitted),
                (trace.ENGINE_REQUEST_PREFILL, admitted, first),
                (trace.ENGINE_REQUEST_DECODE, first, end)):
            if b > a:
                trace.emit(name, a, b, parent=ctx)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until finished (any terminal status); True if it did."""
        return self._clock.wait(self._done, timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Generated token ids (prompt excluded); raises on engine error or
        timeout."""
        if not self._clock.wait(self._done, timeout):
            raise TimeoutError(
                f"request {self.id} not finished within {timeout}s")
        if self.error:
            raise RuntimeError(f"request {self.id} failed: {self.error}")
        return list(self.tokens)


#: the admission boundary: error mode refuses with the same retryable
#: AdmissionError a full queue produces (callers shed / try elsewhere)
_FP_ADMIT = CHAOS.register(
    "engine.admit", error=AdmissionError,
    doc="request admission into the engine queue")


class RequestQueue:
    """Bounded weighted-fair queue; thread-safe; wakes the engine loop
    on submit.

    Per-tenant FIFO subqueues dispatched by virtual finish tag (module
    docstring has the fairness argument). The bound is the load-shedding
    line: past it, ``submit`` rejects with a ``retry_after_s`` hint sized
    to the queue's recent drain rate instead of growing without bound
    (overload must surface as fast, cheap rejections — not as unbounded
    latency for everyone queued). ``policies`` (a
    ``serving.tenancy.TenantTable``-shaped object) supplies per-tenant
    weights and queue caps; without it every tenant gets the tier-1
    default weight and only the global bound applies."""

    def __init__(self, max_depth: int = 64, policies=None, clock=None):
        self.max_depth = max_depth
        self.policies = policies
        self._clock = clock if clock is not None else SYSTEM_CLOCK
        self._subq: Dict[str, deque] = {}
        self._finish_tag: Dict[str, float] = {}
        self._vtime = 0.0
        self._seq = 0
        self._depth = 0
        self._head: Optional[Request] = None     # pinned by peek()
        #: monotonic queue-mutation counter: bumped (under the lock) by
        #: every membership change — submit, any removal, drain. The
        #: engine stamps its overlap-window admission plan with this and
        #: only commits the plan if the version is untouched, so a plan
        #: computed while the device ran can never act on a queue that
        #: moved underneath it.
        self.version = 0
        self._lock = threading.Lock()
        # drain-rate estimate for the retry-after hint: EWMA of the
        # interval between pops (i.e. seconds per admitted request)
        self._last_pop: Optional[float] = None
        self._pop_interval_s = 0.05
        #: signalled on submit so an idle engine loop wakes immediately
        self.work_available = self._clock.event()

    # -- shed hints ----------------------------------------------------------

    def _retry_after_locked(self) -> float:
        """Estimated time until queue space exists — the time to drain
        half the queue at the recent pop rate, clamped to [0.05s, 10s].
        Caller holds ``self._lock``."""
        est = self._pop_interval_s * max(1.0, self._depth / 2.0)
        return min(10.0, max(0.05, est))

    def retry_after_s(self) -> float:
        with self._lock:
            return self._retry_after_locked()

    def _tenant_retry_locked(self, tenant: str) -> float:
        """Tenant-scoped hint: time to drain that tenant's own backlog
        at the recent pop rate. Approximate (the tenant drains at its
        weight share, not the full pop rate), but it keys the backoff to
        the offender's backlog instead of the fleet's."""
        backlog = len(self._subq.get(tenant, ()))
        est = self._pop_interval_s * max(1.0, float(backlog))
        return min(10.0, max(0.05, est))

    # -- admission -----------------------------------------------------------

    def submit(self, request: Request) -> Request:
        CHAOS.hit("engine.admit")
        tenant = request.tenant
        policy = (self.policies.resolve(tenant)
                  if self.policies is not None else None)
        with self._lock:
            if self._depth >= self.max_depth:
                # counted as a REJECTION here, as a SHED only where the
                # refusal is client-facing (the gateway retries other
                # replicas first — a probe refusal is not a shed request)
                _REJECTED.inc()
                raise AdmissionError(
                    f"inference queue full ({self.max_depth} waiting); "
                    f"retry later",
                    retry_after_s=self._retry_after_locked())
            cap = getattr(policy, "max_queued", None)
            sub = self._subq.get(tenant)
            if cap is not None and sub is not None and len(sub) >= cap:
                # counted as a REJECTION only (same convention as the
                # global bound above): the gateway retries other
                # replicas, so the shed counters move at the boundary
                # where the refusal reaches the client
                _REJECTED.inc()
                raise quota_error(
                    f"tenant {tenant!r} already has {len(sub)} request(s) "
                    f"queued (cap {cap}); retry later",
                    tenant=tenant, reason="max_queued",
                    retry_after_s=self._tenant_retry_locked(tenant),
                    counted=False)
            weight = (policy.effective_weight(request.priority)
                      if policy is not None
                      else tier_weight(request.priority))
            # start tag clamps to the global virtual time: a tenant that
            # sat idle (or starved) re-enters AT the front of the virtual
            # timeline, never behind a busy tenant's accumulated backlog
            start = max(self._vtime, self._finish_tag.get(tenant, 0.0))
            cost = (len(request.prompt) + request.max_new_tokens) \
                / max(weight, 1e-9)
            request._vstart = start
            request._vfinish = self._finish_tag[tenant] = start + cost
            self._seq += 1
            request._qseq = self._seq
            request._queued = True
            self._subq.setdefault(tenant, deque()).append(request)
            self._depth += 1
            self.version += 1
            _QUEUE_DEPTH.set(float(self._depth))
            _TENANT_QUEUE.set(float(len(self._subq[tenant])), tenant=tenant)
        self.work_available.set()
        return request

    # -- dispatch ------------------------------------------------------------

    def _select_locked(self) -> Optional[Request]:
        best = None
        for q in self._subq.values():
            head = q[0]
            if best is None or (head._vfinish, head._qseq) < \
                    (best._vfinish, best._qseq):
                best = head
        return best

    def _remove_locked(self, req: Request) -> None:
        q = self._subq.get(req.tenant)
        if q is None or not req._queued:
            return
        if q and q[0] is req:
            q.popleft()
        else:
            try:
                q.remove(req)
            except ValueError:
                return
        req._queued = False
        self._depth -= 1
        self.version += 1
        _TENANT_QUEUE.set(float(len(q)), tenant=req.tenant)
        if not q:
            del self._subq[req.tenant]
            # a drained tenant whose finish tag fell behind the virtual
            # clock carries no information — prune so the dict stays
            # bounded by ACTIVE tenants
            if self._finish_tag.get(req.tenant, 0.0) <= self._vtime:
                self._finish_tag.pop(req.tenant, None)
        _QUEUE_DEPTH.set(float(self._depth))
        if self._head is req:
            self._head = None

    def _note_pop_locked(self, req: Request) -> None:
        self._vtime = max(self._vtime, req._vstart)
        # sweep drained tenants whose finish tag fell behind the virtual
        # clock: their tag carries no information any more (a re-submit
        # would clamp to vtime anyway), and with IAM on tenant ids are
        # subject ids — without the sweep the dict grows by one entry
        # per user EVER seen, not per active tenant
        stale = [t for t, tag in self._finish_tag.items()
                 if tag <= self._vtime and t not in self._subq]
        for t in stale:
            del self._finish_tag[t]
        now = self._clock.now()
        if self._last_pop is not None:
            dt = now - self._last_pop
            self._pop_interval_s += 0.2 * (dt - self._pop_interval_s)
        # a pop that EMPTIES the queue ends the busy window: the gap to
        # the next pop would measure idleness, not drain rate, and one
        # 60s-idle sample would poison the retry-after hint for the next
        # ~dozen rejections
        self._last_pop = now if self._depth else None

    def pop(self) -> Optional[Request]:
        with self._lock:
            req = (self._head if self._head is not None
                   and self._head._queued else self._select_locked())
            if req is not None:
                self._remove_locked(req)
                self._note_pop_locked(req)
            self._head = None
            return req

    def pop_request(self, req: Request) -> bool:
        """Remove a SPECIFIC queued request (the engine admits by
        candidate, not strictly by head: a tenant over its KV quota is
        skipped without blocking the tenants behind it). False if the
        request was no longer queued."""
        with self._lock:
            if not req._queued:
                return False
            self._remove_locked(req)
            self._note_pop_locked(req)
            return True

    def peek(self) -> Optional[Request]:
        """Next request WFQ would dispatch, without removing it. The
        head is pinned: a later submit (even one with an earlier virtual
        finish tag) does not change what a subsequent :meth:`pop`
        returns — the single-consumer peek-then-pop contract the engine's
        budget-then-commit admission relies on."""
        with self._lock:
            if self._head is None or not self._head._queued:
                self._head = self._select_locked()
            return self._head

    def candidates(self) -> List[Request]:
        """Per-tenant head requests in WFQ dispatch order — the engine's
        admission scans these so one tenant blocked on its own quota
        never blocks another tenant's admissible head."""
        with self._lock:
            heads = [q[0] for q in self._subq.values()]
        return sorted(heads, key=lambda r: (r._vfinish, r._qseq))

    # -- maintenance ---------------------------------------------------------

    def reap_dead(self) -> List[Request]:
        """Remove every cancelled/expired/client-dead request, wherever
        it sits in the queue — a passed deadline must terminate promptly
        even while every slot is busy, not when a slot finally frees,
        and a request whose client disconnected while still QUEUED is
        reaped in place (``Request.client_dead`` probes the reply
        channel's liveness) instead of eventually wasting a slot on
        tokens nobody will read."""
        dead: List[Request] = []
        with self._lock:
            for q in list(self._subq.values()):
                dead.extend(r for r in q if r.reapable)
            for r in dead:
                self._remove_locked(r)
        return dead

    def depth(self) -> int:
        with self._lock:
            return self._depth

    def depth_of(self, tenant: str) -> int:
        with self._lock:
            return len(self._subq.get(tenant, ()))

    def tenants(self) -> List[str]:
        """Tenants with queued work (dispatch-order-agnostic)."""
        with self._lock:
            return sorted(self._subq)

    def drain(self) -> List[Request]:
        """Empty the queue (shutdown path); returns the unserved requests."""
        with self._lock:
            out: List[Request] = []
            for tenant, q in self._subq.items():
                out.extend(q)
                _TENANT_QUEUE.set(0.0, tenant=tenant)
            for r in out:
                r._queued = False
            self._subq.clear()
            self._depth = 0
            self._head = None
            self.version += 1
            _QUEUE_DEPTH.set(0.0)
        return out


def any_to_tokens(prompt: Any) -> List[int]:
    """Normalize a wire-side prompt (list of ints) defensively."""
    if not isinstance(prompt, (list, tuple)) or not prompt:
        raise ValueError("prompt must be a non-empty list of token ids")
    return [int(t) for t in prompt]

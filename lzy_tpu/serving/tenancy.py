"""Multi-tenant SLO policy: identity, rate limits, priorities, quotas.

The serving stack resolves every request to a **tenant** (the IAM
subject id when the plane runs with ``--with-iam``; the wire-supplied
tenant field, else ``"default"``, when it does not) and enforces the
tenant's :class:`TenantPolicy` at three layers:

- **admission rate** — :class:`SloLimiter` token buckets (requests/s and
  prompt-tokens/s) refuse *before any work happens* with a
  :class:`~lzy_tpu.serving.scheduler.QuotaExceeded` whose
  ``retry_after_s`` is sized to that tenant's own refill schedule;
- **queue share** — the WFQ request queue
  (``serving/scheduler.RequestQueue``) weights dispatch by the tenant's
  priority tier and caps its backlog (``max_queued``);
- **memory share** — the paged engine checks the tenant's resident +
  staged KV blocks against ``kv_block_quota`` before committing to pop a
  request (a tenant at its quota is *skipped*, not head-of-line-blocked,
  so its quota never converts into another tenant's latency).

Policies are plain data: operators ship a default (applied to every
unknown tenant) plus per-tenant overrides (``serve.py
--tenant-policies``). Nothing here guarantees *absolute* throughput —
weights and quotas carve up whatever the replica can do; an uncontended
tenant always gets full speed.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Callable, Dict, List, Optional

from lzy_tpu.chaos.faults import CHAOS
from lzy_tpu.serving.scheduler import (
    DEFAULT_PRIORITY, DEFAULT_TENANT, QuotaExceeded, quota_error,
    tier_weight)
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.utils.metrics import REGISTRY

# a tenant's terminal counters as the stats surface shows them, at zero
TENANT_ROW = {"requests_finished": 0, "tokens_generated": 0,
              "requests_cancelled": 0, "requests_preempted": 0,
              "requests_error": 0}
TENANT_REQUESTS = REGISTRY.counter(
    "lzy_tenant_requests_total",
    "finished requests by tenant and terminal status")
TENANT_TOKENS = REGISTRY.counter(
    "lzy_tenant_tokens_total", "generated tokens by tenant")
TENANT_TTFT = REGISTRY.histogram(
    "lzy_tenant_ttft_seconds",
    "submit-to-first-token latency by tenant (the per-tenant SLO number)",
    buckets=(0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0, 60.0))
TENANT_KV_BLOCKS = REGISTRY.gauge(
    "lzy_tenant_kv_blocks",
    "KV blocks resident or staged for a tenant's in-flight requests")
_RATE_LEVEL = REGISTRY.gauge(
    "lzy_tenant_rate_bucket_level",
    "token-bucket fill level by tenant and bucket (requests | tokens)")

#: the SLO admission boundary (rate limits + quotas): error mode refuses
#: with the same retryable QuotaExceeded a saturated bucket produces —
#: callers back off on the hint, other tenants are untouched
_FP_SLO = CHAOS.register(
    "slo.admit", error=QuotaExceeded,
    doc="tenant rate-limit/quota admission gate (gateway + engine front)")


@dataclasses.dataclass
class TenantPolicy:
    """One tenant's SLO contract. ``None`` limits are unenforced.

    ``priority`` is the tier (0 interactive, 1 standard, 2 batch) that
    sets the WFQ ``weight`` unless an explicit weight is given; a
    client-requested priority can only DOWNGRADE below the policy tier
    (see :meth:`effective_priority`) — self-upgrades would make the tier
    table advisory. ``burst_s`` sizes both token buckets: capacity =
    rate * burst_s (a tenant may burst that far ahead of its sustained
    rate, then drains at the rate)."""

    tenant: str = DEFAULT_TENANT
    priority: int = DEFAULT_PRIORITY
    weight: Optional[float] = None
    requests_per_s: Optional[float] = None
    prompt_tokens_per_s: Optional[float] = None
    burst_s: float = 2.0
    kv_block_quota: Optional[int] = None
    max_queued: Optional[int] = None

    def effective_priority(self, requested: Optional[int] = None) -> int:
        """The tier actually applied: the policy's, unless the client
        asked for a LOWER one (numerically higher — e.g. a latency-
        insensitive backfill job volunteering for the batch tier)."""
        if requested is None:
            return self.priority
        return max(int(requested), self.priority)

    def effective_weight(self, requested: Optional[int] = None) -> float:
        tier = tier_weight(self.effective_priority(requested))
        if self.weight is None:
            return tier
        # an explicit weight is the operator's CEILING: a client-requested
        # downgrade may shrink the share below it (the tier weight of the
        # downgraded tier) but never raise it past the configured weight
        return min(self.weight, tier) if requested is not None \
            and requested > self.priority else self.weight


class TenantTable:
    """Thread-safe tenant -> policy map with a default template.

    Unknown tenants resolve to a copy of the default policy (renamed),
    so "every tenant gets 10 req/s unless stated otherwise" is one
    line of config, not a registration requirement."""

    def __init__(self, default: Optional[TenantPolicy] = None):
        self._default = default if default is not None else TenantPolicy()
        self._policies: Dict[str, TenantPolicy] = {}
        self._lock = threading.Lock()

    @property
    def default(self) -> TenantPolicy:
        return self._default

    def set_policy(self, policy: TenantPolicy) -> None:
        with self._lock:
            self._policies[policy.tenant] = policy

    def resolve(self, tenant: str) -> TenantPolicy:
        with self._lock:
            policy = self._policies.get(tenant)
        if policy is not None:
            return policy
        return dataclasses.replace(self._default, tenant=tenant)

    def tenants(self) -> List[str]:
        with self._lock:
            return sorted(self._policies)

    @classmethod
    def from_doc(cls, doc: Dict[str, dict],
                 default: Optional[TenantPolicy] = None) -> "TenantTable":
        """Build from a JSON-shaped ``{tenant: {field: value}}`` doc
        (the ``--tenant-policies`` file). Unknown fields are rejected —
        a typo'd limit must not silently become "unenforced"."""
        table = cls(default=default)
        known = {f.name for f in dataclasses.fields(TenantPolicy)}
        for tenant, fields in doc.items():
            bad = sorted(set(fields) - known)
            if bad:
                raise ValueError(
                    f"tenant {tenant!r}: unknown policy fields {bad}; "
                    f"known: {sorted(known - {'tenant'})}")
            table.set_policy(TenantPolicy(tenant=tenant, **fields))
        return table


class TokenBucket:
    """Classic token bucket with an injectable clock (deterministic
    tests). ``try_take(n)`` returns ``None`` on success or the seconds
    until the take *could* succeed. Takes larger than the burst capacity
    are allowed once the bucket is full and drive the level negative
    (debt) — a single 32k-token prompt passes, but the tenant then waits
    out the debt at its sustained rate; refusing it outright would make
    the burst window a hard prompt-length cap."""

    def __init__(self, rate_per_s: float, burst: float,
                 clock: Optional[Callable[[], float]] = None):
        # the original injectable-clock seam, now generalized fleet-wide
        # in utils/clock.py; a bare callable stays accepted (pass
        # ``some_clock.now``)
        clock = clock if clock is not None else SYSTEM_CLOCK.now
        if rate_per_s <= 0:
            raise ValueError(f"rate must be > 0, got {rate_per_s}")
        self.rate = float(rate_per_s)
        self.burst = max(float(burst), 1.0)
        self._clock = clock
        self._level = self.burst
        self._t = clock()
        self._lock = threading.Lock()

    def _refill_locked(self) -> None:
        now = self._clock()
        self._level = min(self.burst,
                          self._level + (now - self._t) * self.rate)
        self._t = now

    def try_take(self, n: float = 1.0) -> Optional[float]:
        with self._lock:
            self._refill_locked()
            need = min(float(n), self.burst)
            if self._level >= need:
                self._level -= float(n)
                return None
            return (need - self._level) / self.rate

    def give_back(self, n: float) -> None:
        """Refund a provisional take (a later bucket refused the same
        admission): without this a retrying client would be double-
        charged on every refusal."""
        with self._lock:
            self._refill_locked()
            self._level = min(self.burst, self._level + float(n))

    def level(self) -> float:
        with self._lock:
            self._refill_locked()
            return self._level


class SloLimiter:
    """Admission-time rate limiting for a serving front (gateway or
    single-engine RPC service). One pair of buckets per tenant, created
    lazily from the tenant's policy. ``admit`` either returns (the
    request may proceed to routing/queueing) or raises
    :class:`QuotaExceeded` with a tenant-scoped ``retry_after_s`` — and
    it never half-charges: a refusal refunds any bucket it already
    debited, so retries are charged exactly once when they succeed."""

    def __init__(self, table: TenantTable,
                 clock: Optional[Callable[[], float]] = None,
                 enforce_backoff: bool = False,
                 backoff_step_s: float = 0.05,
                 backoff_cap_s: float = 10.0):
        self.table = table
        self._clock = clock if clock is not None else SYSTEM_CLOCK.now
        self._buckets: Dict[str, tuple] = {}
        self._lock = threading.Lock()
        # backoff ENFORCEMENT (off by default — additive behavior): the
        # load harness found that an advisory retry_after_s loses to a
        # hammering client — polling the bucket every few ms grabs each
        # refilled token ahead of every client that honored the hint, so
        # misbehavior WON throughput. With enforcement on, a refusal
        # opens a per-tenant backoff window sized to the hint; arrivals
        # inside the window are refused outright AND extend it by
        # ``backoff_step_s`` (capped at ``backoff_cap_s`` ahead of now),
        # so a hammering tenant starves itself while a hint-honoring one
        # sails through on schedule. Tenant-scoped by design: the
        # rate-limit identity is the tenant, so its clients share the
        # window the way they share the bucket.
        self._enforce_backoff = bool(enforce_backoff)
        self._backoff_step_s = float(backoff_step_s)
        self._backoff_cap_s = float(backoff_cap_s)
        self._backoff_until: Dict[str, float] = {}

    def _buckets_for(self, tenant: str, policy: TenantPolicy):
        with self._lock:
            pair = self._buckets.get(tenant)
            if pair is None:
                req_bucket = None
                if policy.requests_per_s is not None:
                    req_bucket = TokenBucket(
                        policy.requests_per_s,
                        policy.requests_per_s * policy.burst_s,
                        clock=self._clock)
                tok_bucket = None
                if policy.prompt_tokens_per_s is not None:
                    tok_bucket = TokenBucket(
                        policy.prompt_tokens_per_s,
                        policy.prompt_tokens_per_s * policy.burst_s,
                        clock=self._clock)
                pair = self._buckets[tenant] = (req_bucket, tok_bucket)
            return pair

    def _check_backoff(self, tenant: str) -> None:
        """Enforced-backoff gate (see ``__init__``): refuse — and
        extend — while the tenant's advertised window is open."""
        if not self._enforce_backoff:
            return
        now = self._clock()
        with self._lock:
            until = self._backoff_until.get(tenant)
            if until is None or now >= until:
                return
            # contempt of backoff: the early arrival pushes the window
            # out (bounded ahead of now by the cap) — but NEVER shrinks
            # it: a window already longer than the cap (deep token-debt
            # refusals advertise long waits) must not collapse toward
            # the cap just because the tenant hammered it
            until = max(until, min(now + self._backoff_cap_s,
                                   until + self._backoff_step_s))
            self._backoff_until[tenant] = until
        # CEIL to the wire precision: a round() hint can undershoot the
        # stored window by half a millisecond, and a client honoring
        # that hint EXACTLY (the virtual-clock harness does) would land
        # inside the window and be penalized as a hammerer
        hint = math.ceil((until - now) * 1000.0) / 1000.0
        raise quota_error(
            f"tenant {tenant!r} returned before its advertised "
            f"retry_after_s elapsed; backing the window off",
            tenant=tenant, reason="backoff",
            retry_after_s=hint)

    def _note_refusal(self, tenant: str, wait: float) -> None:
        if not self._enforce_backoff:
            return
        now = self._clock()
        with self._lock:
            # the window must equal the CLIENT-VISIBLE hint
            # (round(wait, 3) on the QuotaExceeded), never the unrounded
            # wait: a compliant client sleeping exactly the hint must
            # land at-or-after the window, not half a millisecond inside
            # it. If the rounding undershoots the true bucket refill,
            # the bucket itself refuses once more WITHOUT a backoff
            # penalty — a soft second hint, not a punishment.
            self._backoff_until[tenant] = max(
                self._backoff_until.get(tenant, 0.0),
                now + round(wait, 3))

    def admit(self, tenant: str, prompt_tokens: int) -> TenantPolicy:
        """Charge one request + its prompt tokens against the tenant's
        buckets; raises :class:`QuotaExceeded` on refusal. Returns the
        resolved policy so callers reuse the lookup (priority, quota)."""
        CHAOS.hit("slo.admit")
        policy = self.table.resolve(tenant)
        self._check_backoff(tenant)
        req_bucket, tok_bucket = self._buckets_for(tenant, policy)
        if req_bucket is not None:
            wait = req_bucket.try_take(1.0)
            if wait is not None:
                _RATE_LEVEL.set(req_bucket.level(), tenant=tenant,
                                bucket="requests")
                self._note_refusal(tenant, wait)
                raise quota_error(
                    f"tenant {tenant!r} over its {policy.requests_per_s:g} "
                    f"requests/s limit",
                    tenant=tenant, reason="requests_per_s",
                    retry_after_s=round(wait, 3))
            _RATE_LEVEL.set(req_bucket.level(), tenant=tenant,
                            bucket="requests")
        if tok_bucket is not None:
            wait = tok_bucket.try_take(float(prompt_tokens))
            if wait is not None:
                if req_bucket is not None:
                    req_bucket.give_back(1.0)
                _RATE_LEVEL.set(tok_bucket.level(), tenant=tenant,
                                bucket="tokens")
                self._note_refusal(tenant, wait)
                raise quota_error(
                    f"tenant {tenant!r} over its "
                    f"{policy.prompt_tokens_per_s:g} prompt-tokens/s limit "
                    f"({prompt_tokens} requested)",
                    tenant=tenant, reason="prompt_tokens_per_s",
                    retry_after_s=round(wait, 3))
            _RATE_LEVEL.set(tok_bucket.level(), tenant=tenant,
                            bucket="tokens")
        return policy

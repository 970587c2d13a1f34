"""Workflow-aware serving scheduler: the fan-in plane between ``llm``
ops and the serving fleet.

Every ``llm.generate`` body used to dispatch straight at the resolved
backend — one call, one route, one engine request, however many
concurrent workflow runs were asking. This module is the seam the
workflow→serving traffic flows through instead, and it is serving-aware
in three composing ways:

- **Admission fan-in + in-flight dedup** (:meth:`WorkflowScheduler.
  dispatch`): calls from different concurrent workflow runs coalesce
  through one submission plane, and identical GREEDY calls in flight at
  the same moment — same prompt, params, tenant and model digest, the
  same identity the op cache keys on — collapse to a single engine
  request whose reply fans out to every waiter. Counted
  (``lzy_wfsched_dedup_hits_total``), and never applied to sampled or
  streaming requests: a sampled reply is a draw, not a function of the
  inputs, and a stream's tokens belong to exactly one channel.
  Followers consume no fleet capacity at all — no engine request, no
  SLO charge, no waiter slot.

- **Op-chain fusion** (:meth:`WorkflowScheduler.note_step_done`): when
  a conversation step finishes ok, the gateway parks the conversation's
  radix chain resident on its replica (``park_conversation`` — a
  bounded tool-gap TTL lease) so the ``generate → tool-op → generate``
  chain's next step hard-pins there (routed_by ``"fused"``) and
  prefills only its suffix. Fallback is the ordinary routed path: a
  dead replica or an expired TTL costs one re-prefill, never a wrong
  token — greedy outputs stay bit-identical to the unfused oracle.

- **Speculative next-step prefill** (same hook): while the tool op
  runs, the KNOWN prompt prefix of the next step — the finished step's
  prompt + reply — is chunk-prefilled on the leased replica at
  background priority (WFQ tier 2), so the next step's TTFT is a
  suffix prefill. A dispatch for a session whose speculation is still
  in flight briefly waits for it (the speculation IS that step's
  prefill); wrong speculations are released uncounted as cache
  pollution once the pin lapses.

Flags (read at scheduler construction — i.e. per ``llm.configure``):
``LZY_WFSCHED_DEDUP``, ``LZY_WFSCHED_FUSE``, ``LZY_WFSCHED_SPECULATE``
(all default on), ``LZY_WFSCHED_PARK_TTL_S`` (gateway default when
unset).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional

from lzy_tpu.utils import trace
from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)

#: how long a dispatch waits for its session's in-flight speculation
#: before racing it (the speculation is that step's own prefill — a few
#: seconds of patience beats a duplicate full prefill; a wedged one
#: must not hold the step hostage)
_SPEC_AWAIT_S = 10.0
#: follower fallback: a waiter whose leader outlives the follower's own
#: budget dispatches for itself instead of waiting forever
_FOLLOWER_WAIT_S = 120.0


def _flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("0", "false", "no", "off", "")


def _traced_row(fn, item, parent, handed: float):
    """One row of :meth:`WorkflowScheduler.map` on its pool thread:
    ``llm.row`` runs from the hand-over, ``llm.row.pool_wait`` is the part
    before the function started."""
    with trace.span(trace.LLM_ROW, parent=parent, start=handed):
        trace.emit(trace.LLM_ROW_POOL_WAIT, handed, trace.now())
        return fn(item)


class _InFlight:
    """Leader/follower rendezvous for one dedup key: the leader carries
    the engine request, followers adopt its terminal reply."""

    __slots__ = ("done", "reply", "error", "followers")

    def __init__(self):
        self.done = threading.Event()
        self.reply: Optional[dict] = None
        self.error: Optional[BaseException] = None
        self.followers = 0


class WorkflowScheduler:
    """One per configured backend (:func:`scheduler_for`): the fan-in
    plane, the dedup table, and the fusion/speculation hooks. All three
    features degrade independently to the pre-scheduler behavior — a
    backend without a park surface simply never fuses, a sampled call
    simply never dedups."""

    def __init__(self, backend: Any, *,
                 dedup: Optional[bool] = None,
                 fuse: Optional[bool] = None,
                 speculate: Optional[bool] = None,
                 park_ttl_s: Optional[float] = None,
                 max_workers: int = 16):
        self.backend = backend
        self.dedup = _flag("LZY_WFSCHED_DEDUP", True) \
            if dedup is None else bool(dedup)
        self.fuse = _flag("LZY_WFSCHED_FUSE", True) \
            if fuse is None else bool(fuse)
        self.speculate = _flag("LZY_WFSCHED_SPECULATE", True) \
            if speculate is None else bool(speculate)
        if park_ttl_s is None:
            raw = os.environ.get("LZY_WFSCHED_PARK_TTL_S")
            park_ttl_s = float(raw) if raw else None
        #: None = the gateway's own default TTL
        self.park_ttl_s = park_ttl_s
        self._max_workers = max(1, int(max_workers))
        self._lock = threading.Lock()
        self._inflight: Dict[tuple, _InFlight] = {}
        #: session -> in-flight fusion future (park + speculative
        #: prefill); the next dispatch for that session awaits it
        self._spec: Dict[str, Any] = {}
        self._dedup_hits = 0
        self._dispatches = 0
        self._parks = 0
        self._speculations = 0
        self._closed = False
        # two pools, deliberately: batch fan-out rides the (bounded)
        # plane pool, fusion/speculation tasks ride their own small one
        # — a saturating generate_batch must not queue a speculation
        # behind itself and then wait on it from dispatch()
        self._pool = None
        self._fuse_pool = None

    # -- the plane ------------------------------------------------------------

    def _plane(self):
        with self._lock:
            if self._pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._pool = ThreadPoolExecutor(
                    self._max_workers, thread_name_prefix="lzy-wfsched")
            return self._pool

    def _fusion_pool(self):
        with self._lock:
            if self._fuse_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._fuse_pool = ThreadPoolExecutor(
                    4, thread_name_prefix="lzy-wfsched-fuse")
            return self._fuse_pool

    def map(self, fn, items: List[Any]) -> List[Any]:
        """Order-preserving fan-out over the shared plane pool — what
        ``llm.generate_batch`` rides instead of a private per-call
        thread pool. Items run ``fn`` concurrently (each lands back in
        :meth:`dispatch`, so in-flight dedup applies within the fan-out
        too); the first exception propagates after all rows settle."""
        if not items:
            return []
        if trace.ON:
            # each item carries the caller's open span and the time it was
            # handed over: the row's wait for one of the pool's threads
            parent = trace.context()
            futures = [self._plane().submit(_traced_row, fn, item, parent,
                                            trace.now())
                       for item in items]
        else:
            futures = [self._plane().submit(fn, item) for item in items]
        results, first_err = [], None
        for fut in futures:
            try:
                results.append(fut.result())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                results.append(None)
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        return results

    # -- admission fan-in + in-flight dedup -----------------------------------

    def dispatch(self, prompt_tokens: List[int], *,
                 max_new_tokens: int,
                 timeout_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 greedy: Optional[bool] = None,
                 tenant: Optional[str] = None,
                 priority: Optional[int] = None,
                 session: Optional[str] = None,
                 stream=None) -> dict:
        """One generate through the fan-in plane. Greedy, non-streaming
        calls dedup against identical in-flight twins; everything else
        passes straight through (one call, one engine request — exactly
        the pre-scheduler contract)."""
        from lzy_tpu.llm import metrics

        if session is not None:
            # fused ordering: if this conversation's speculative prefill
            # is still running, wait briefly — the speculation IS this
            # step's prefill, and racing it would pay a duplicate full
            # prefill for nothing
            self._await_speculation(session)
        with self._lock:
            self._dispatches += 1

        def call() -> dict:
            return self.backend.generate(
                prompt_tokens,
                max_new_tokens=max_new_tokens,
                timeout_s=timeout_s,
                deadline_s=deadline_s,
                greedy=greedy,
                tenant=tenant,
                priority=priority,
                session=session,
                stream=stream)

        if not (self.dedup and greedy is True and stream is None):
            with trace.span(trace.LLM_DISPATCH, role="solo"):
                return call()
        # the dedup identity mirrors the op cache key: prompt + the
        # output-determining params + model digest, plus the SLO
        # identity (a follower must not ride a reply another tenant's
        # quota paid for). Deadlines are excluded — only complete
        # ("ok") replies fan out, and a complete greedy reply is the
        # same under any deadline that let it finish.
        key = (self._digest(), tuple(prompt_tokens), int(max_new_tokens),
               tenant, priority)
        while True:
            with self._lock:
                entry = self._inflight.get(key)
                if entry is None:
                    entry = _InFlight()
                    self._inflight[key] = entry
                    leader = True
                else:
                    entry.followers += 1
                    leader = False
            if leader:
                try:
                    with trace.span(trace.LLM_DISPATCH, role="leader"):
                        entry.reply = call()
                except BaseException as e:
                    entry.error = e
                    raise
                finally:
                    with self._lock:
                        if self._inflight.get(key) is entry:
                            del self._inflight[key]
                        fanout = entry.followers
                    entry.done.set()
                    metrics.WFSCHED_DISPATCHES.inc(
                        role="leader" if fanout else "solo")
                return entry.reply
            # follower: adopt the leader's terminal reply without ever
            # touching the fleet
            with trace.span(trace.LLM_DISPATCH, role="follower"):
                adopted = entry.done.wait(timeout_s if timeout_s
                                          else _FOLLOWER_WAIT_S)
            if not adopted:
                # the leader outlived our budget — stop waiting and
                # dispatch for ourselves (no dedup credit)
                with trace.span(trace.LLM_DISPATCH, role="solo"):
                    return call()
            reply = entry.reply
            if entry.error is None and isinstance(reply, dict) \
                    and reply.get("status") == "ok":
                with self._lock:
                    self._dedup_hits += 1
                metrics.DEDUP_HITS.inc()
                metrics.WFSCHED_DISPATCHES.inc(role="follower")
                # fresh token list per waiter: Generation mutating its
                # tokens must never alias a sibling's
                return {**reply, "tokens": list(reply.get("tokens", []))}
            # the leader failed or was cancelled — that is ITS outcome,
            # never the followers': loop and either become the new
            # leader or follow one (a genuine request-scoped error then
            # fails each caller on its own dispatch)

    def note_batch_dedup(self, n: int = 1) -> None:
        """Batch-local dedup credit: ``llm.generate_batch`` collapses
        identical greedy rows BEFORE they reach :meth:`dispatch`, so it
        reports the collapsed rows here to keep :meth:`stats` honest."""
        with self._lock:
            self._dedup_hits += int(n)

    def _digest(self) -> str:
        try:
            return self.backend.model_digest()
        except Exception:  # noqa: BLE001 — identity only needs stability
            return "unknown"

    # -- op-chain fusion + speculative next-step prefill ----------------------

    def note_step_done(self, session: Optional[str],
                       full_tokens: List[int], *,
                       tenant: Optional[str] = None):
        """Called by the op body when a conversation step finishes ok:
        park the conversation's KV resident on its replica and — while
        the tool op between steps runs — speculatively prefill the next
        step's known prompt prefix (= ``full_tokens``) at background
        priority. Returns the in-flight future (tests drain it), or
        None when fusion does not apply. Never blocks the op body and
        never raises."""
        from lzy_tpu.llm import metrics

        if not self.fuse or session is None or self._closed:
            return None
        svc = getattr(self.backend, "service", None)
        if svc is None or not hasattr(svc, "park_conversation"):
            metrics.PARK_ATTEMPTS.inc(outcome="unsupported")
            return None
        try:
            fut = self._fusion_pool().submit(
                self._fuse_step, svc, str(session),
                [int(t) for t in full_tokens], tenant)
        except RuntimeError:          # pool shut down mid-close
            return None
        with self._lock:
            self._spec[str(session)] = fut

        def _cleanup(f, s=str(session)):
            with self._lock:
                if self._spec.get(s) is f:
                    del self._spec[s]

        fut.add_done_callback(_cleanup)
        return fut

    def _fuse_step(self, svc, session: str, tokens: List[int],
                   tenant: Optional[str]) -> bool:
        from lzy_tpu.llm import metrics

        try:
            if self.park_ttl_s is not None:
                ok = svc.park_conversation(session, tokens,
                                           ttl_s=self.park_ttl_s)
            else:
                ok = svc.park_conversation(session, tokens)
        except Exception:  # noqa: BLE001 — fusion is advisory
            ok = False
        metrics.PARK_ATTEMPTS.inc(outcome="parked" if ok else "declined")
        if not ok:
            return False
        with self._lock:
            self._parks += 1
        if not self.speculate:
            return True
        speculate = getattr(svc, "speculate_prefill", None)
        if speculate is None:
            return True
        try:
            if tenant is not None:
                spec_ok = speculate(session, tokens, tenant=tenant)
            else:
                spec_ok = speculate(session, tokens)
        except Exception:  # noqa: BLE001 — speculation is advisory
            spec_ok = False
        if spec_ok:
            with self._lock:
                self._speculations += 1
        return True

    def _await_speculation(self, session: str,
                           timeout_s: float = _SPEC_AWAIT_S) -> None:
        with self._lock:
            fut = self._spec.get(str(session))
        if fut is None:
            return
        try:
            fut.result(timeout=timeout_s)
        except Exception:  # noqa: BLE001 — advisory; the step proceeds
            pass

    def drain(self, timeout_s: float = 30.0) -> None:
        """Wait for every in-flight fusion/speculation task (tests and
        orderly shutdowns; the request path never calls this)."""
        with self._lock:
            pending = list(self._spec.values())
        for fut in pending:
            try:
                fut.result(timeout=timeout_s)
            except Exception:  # noqa: BLE001 — advisory
                pass

    # -- lifecycle ------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "dispatches": self._dispatches,
                "dedup_hits": self._dedup_hits,
                "dedup_waiting": sum(e.followers
                                     for e in self._inflight.values()),
                "parks": self._parks,
                "speculations": self._speculations,
                "spec_inflight": len(self._spec),
            }

    def close(self) -> None:
        with self._lock:
            self._closed = True
            pools = [p for p in (self._pool, self._fuse_pool)
                     if p is not None]
            self._pool = self._fuse_pool = None
        for pool in pools:
            pool.shutdown(wait=False)


# -- per-backend resolution ---------------------------------------------------

_lock = threading.Lock()
_scheduler: Optional[WorkflowScheduler] = None


def scheduler_for(backend: Any) -> WorkflowScheduler:
    """The process-global scheduler for ``backend`` — created on first
    use, replaced (and the old one closed) when the configured backend
    changes. Keyed on backend object identity, matching
    ``llm.configure``'s process-global contract."""
    global _scheduler
    old = None
    with _lock:
        if _scheduler is not None and _scheduler.backend is backend:
            return _scheduler
        old, _scheduler = _scheduler, WorkflowScheduler(backend)
        sched = _scheduler
    if old is not None:
        old.close()
    return sched


def current_scheduler() -> Optional[WorkflowScheduler]:
    """The live scheduler, if any (tests and bench probes read its
    counters; None before the first dispatch after a (re)configure)."""
    with _lock:
        return _scheduler


def reset() -> None:
    """Drop (and close) the process-global scheduler —
    ``llm.configure`` calls this so a fresh backend never inherits a
    stale dedup table or fusion leases."""
    global _scheduler
    with _lock:
        old, _scheduler = _scheduler, None
    if old is not None:
        old.close()

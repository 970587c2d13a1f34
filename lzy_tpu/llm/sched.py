"""Workflow-aware serving scheduler: the fan-in plane between ``llm``
ops and the serving fleet.

Every ``llm.generate`` body used to dispatch straight at the resolved
backend — one call, one route, one engine request, however many
concurrent workflow runs were asking. This module is the seam the
workflow→serving traffic flows through instead, and it is serving-aware
in four composing ways:

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

- **A window of rows in flight** (:meth:`WorkflowScheduler.map`, what
  ``llm.generate_batch`` rides): rows are handed over in the order they
  were asked for, first come first served over every concurrent batch
  of the process, and ``width`` of them are inside the backend at once,
  a blocked thread each. The width is read from the replies: every
  reply of a plane that knows of it carries ``plane_slots`` (the ready
  replicas' slots as that call saw them) and, for a call the front
  gates, ``plane_admits`` (its unary waiter cap, which also bounds the
  slots): ``serving.scheduler.plane_capacity``, added by both gateways,
  ``InferenceService`` and ``EngineBackend``, and carried untouched by
  ``RpcInferenceClient`` and by any proxy that forwards ``generate``.
  Width = the slots plus as many rows again queued behind them (a
  freed slot then finds its next row in the engine's queue, where WFQ,
  deadlines and the router see it), never over ``plane_admits``. Until
  a reply has carried the field the width is 16 — what the fixed pool
  of threads this replaced allowed, and ``GatewayService``'s default
  waiter cap — so an old plane or a test double is served as before. A
  later reply that reports fewer slots (a replica drained or lost)
  narrows it: rows inside stay, new ones wait. A shed row
  (``waiters_busy``, an ``AdmissionError``) is an exception, carries
  no reply, and so never widens it; ``DISPATCH_RETRIES_POLICY`` retries
  it as before. No option, flag or variable sets the width. Gauges
  ``lzy_wfsched_row_window`` / ``lzy_wfsched_rows_in_flight``;
  histogram ``lzy_wfsched_row_window_wait_seconds{window=<width>}``
  (the always-on twin of the ``llm.row.pool_wait`` span; a row that
  entered at once observes 0).

Flags (read at scheduler construction — i.e. per ``llm.configure``):
``LZY_WFSCHED_DEDUP``, ``LZY_WFSCHED_FUSE``, ``LZY_WFSCHED_SPECULATE``
(all default on), ``LZY_WFSCHED_PARK_TTL_S`` (gateway default when
unset).
"""

from __future__ import annotations

import itertools
import os
import threading
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from lzy_tpu.llm import metrics
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


#: rows in flight until a reply has said what the plane holds: the size of
#: the thread pool this window replaced, which is ``GatewayService``'s
#: default waiter cap — an old plane, or a test double, is served as before
_DEFAULT_WINDOW = 16
#: rows queued behind each slot the plane reports, so that a freed slot
#: finds its next row in the engine's queue (where WFQ, deadlines and the
#: router see it) and not behind a thread hand-over. Measured on the chip
#: at 0, 0.5 and 1 (PERF.md section 6, PR 30)
_BACKLOG_PER_SLOT = 1.0


class _Row:
    """One row of :meth:`WorkflowScheduler.map`, from its hand-over to
    its result."""

    __slots__ = ("fn", "item", "parent", "traced", "handed", "waited",
                 "width", "done", "result", "error")

    def __init__(self, fn, item, parent, traced: bool, handed: float):
        self.fn, self.item = fn, item
        #: the caller's open span and whether the recorder was on at the
        #: hand-over: ``llm.row`` runs from ``handed``
        self.parent, self.traced, self.handed = parent, traced, handed
        #: set at entry: seconds waited for the window, and its width then
        self.waited, self.width = 0.0, 0
        self.done = threading.Event()
        self.result: Any = None
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            if self.traced:
                with trace.span(trace.LLM_ROW, parent=self.parent,
                                start=self.handed):
                    # the part before the function started
                    trace.emit(trace.LLM_ROW_POOL_WAIT, self.handed,
                               trace.now())
                    self.result = self.fn(self.item)
            else:
                self.result = self.fn(self.item)
        except BaseException as e:  # noqa: BLE001 — re-raised by map()
            self.error = e
        finally:
            self.done.set()


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
                 park_ttl_s: Optional[float] = None):
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
        #: the window of rows in flight (:meth:`map`): rows handed over
        #: and not yet let in, in hand-over order over every concurrent
        #: batch; how many are inside; how many may be
        self._waiting: Deque[_Row] = deque()
        self._rows_in_flight = 0
        self._width = _DEFAULT_WINDOW
        self._row_threads = itertools.count()
        # fusion/speculation tasks ride a small pool of their own — a
        # saturating generate_batch must not queue a speculation behind
        # itself and then wait on it from dispatch()
        self._fuse_pool = None
        metrics.ROW_WINDOW.set(self._width)

    def _fusion_pool(self):
        with self._lock:
            if self._fuse_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._fuse_pool = ThreadPoolExecutor(
                    4, thread_name_prefix="lzy-wfsched-fuse")
            return self._fuse_pool

    # -- the window of rows in flight -----------------------------------------

    def map(self, fn, items: List[Any]) -> List[Any]:
        """Order-preserving fan-out through the window — what
        ``llm.generate_batch`` rides. Rows are handed over in the order
        they were asked for, first come first served over every
        concurrent batch of the process, and ``width`` of them run ``fn``
        at once, each on a thread of its own (each lands back in
        :meth:`dispatch`, so in-flight dedup applies within the fan-out
        too); the first exception propagates after all rows settle."""
        if not items:
            return []
        # each row carries the caller's open span and the time it was
        # handed over: its wait for the window is llm.row.pool_wait
        traced = trace.ON
        parent = trace.context() if traced else None
        handed = trace.now()
        rows = [_Row(fn, item, parent, traced, handed) for item in items]
        with self._lock:
            self._waiting.extend(rows)
            entering = self._enter_locked(handed)
        self._launch(entering)
        results, first_err = [], None
        for row in rows:
            row.done.wait()
            results.append(row.result)
            if row.error is not None and first_err is None:
                first_err = row.error
        if first_err is not None:
            raise first_err
        return results

    def _enter_locked(self, now: float, everything: bool = False
                      ) -> List[_Row]:
        """Rows the window lets in now, in hand-over order (the caller
        holds the lock, and launches them after releasing it)."""
        entering: List[_Row] = []
        while self._waiting and (everything
                                 or self._rows_in_flight < self._width):
            row = self._waiting.popleft()
            row.waited, row.width = max(0.0, now - row.handed), self._width
            self._rows_in_flight += 1
            entering.append(row)
        return entering

    def _launch(self, rows: List[_Row]) -> None:
        for row in rows:
            threading.Thread(
                target=self._run_rows, args=(row,), daemon=True,
                name=f"lzy-wfsched-{next(self._row_threads)}").start()

    def _run_rows(self, row: Optional[_Row]) -> None:
        """A row's thread: runs it, then the row its leaving lets in (a
        blocked thread a row and no executor: no thread idles between
        rows, and none is left to shut down)."""
        while row is not None:
            metrics.ROWS_IN_FLIGHT.add(1)
            metrics.ROW_WINDOW_WAIT.observe(row.waited,
                                            window=str(row.width))
            row.run()
            metrics.ROWS_IN_FLIGHT.add(-1)
            with self._lock:
                self._rows_in_flight -= 1
                entering = self._enter_locked(trace.now())
            row = entering.pop(0) if entering else None
            self._launch(entering)

    def _follow(self, reply: Any) -> None:
        """The window's width follows what the plane says it holds
        (``scheduler.plane_capacity``, in every reply of a plane that
        knows of it): its slots plus a backlog behind them, within what
        it admits without shedding. A reply without the field changes
        nothing, nor does an error: a shed row never widens the window.
        A smaller report narrows it: rows inside stay, new ones wait."""
        slots = reply.get("plane_slots") if isinstance(reply, dict) else None
        if type(slots) is not int or slots < 1:
            return
        width = slots + int(_BACKLOG_PER_SLOT * slots)
        admits = reply.get("plane_admits")
        if type(admits) is int and admits >= 1:
            width = min(width, admits)
        if width == self._width:
            return
        with self._lock:
            self._width = width
            entering = self._enter_locked(trace.now())
        metrics.ROW_WINDOW.set(width)
        self._launch(entering)

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
        if session is not None:
            # fused ordering: if this conversation's speculative prefill
            # is still running, wait briefly — the speculation IS this
            # step's prefill, and racing it would pay a duplicate full
            # prefill for nothing
            self._await_speculation(session)
        with self._lock:
            self._dispatches += 1

        def call() -> dict:
            reply = self.backend.generate(
                prompt_tokens,
                max_new_tokens=max_new_tokens,
                timeout_s=timeout_s,
                deadline_s=deadline_s,
                greedy=greedy,
                tenant=tenant,
                priority=priority,
                session=session,
                stream=stream)
            self._follow(reply)
            return reply

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
                "row_window": self._width,
                "rows_in_flight": self._rows_in_flight,
                "rows_waiting": len(self._waiting),
            }

    def close(self) -> None:
        """Ends fusion and lets every waiting row in at once: a batch
        handed over before a reconfigure is not held behind rows of a
        plane that may never answer (nothing in flight is cancelled)."""
        with self._lock:
            self._closed = True
            pool, self._fuse_pool = self._fuse_pool, None
            entering = self._enter_locked(trace.now(), everything=True)
        self._launch(entering)
        if pool is not None:
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

"""Engine-compatible fleet simulator: the real control plane, a modeled
forward pass.

``SimEngine`` presents the exact surface the gateway/fleet stack drives
(``submit``/``step``/``stats``/``queue``/``closed``/``close``/``cfg``),
and runs the REAL policy components — the WFQ :class:`RequestQueue`,
tenant quotas, admission verdicts, chunked-prefill budgeting, youngest
preemption and radix-style prefix caching — but replaces the device
forward with a virtual-time cost model (:class:`SimProfile`).  The load
driver steps it from a :class:`~lzy_tpu.utils.clock.VirtualClock`, so
hours of multi-tenant traffic replay in seconds of CPU while every
queueing, shedding, routing, breaker and autoscaling decision is made
by the same code that serves production traffic.

What is modeled rather than computed:

- a decode round costs ``decode_step_s`` (whole batch, like a jitted
  step) and every active slot emits one deterministic token
  (:func:`~lzy_tpu.load.trace.reply_tokens`);
- prefill costs ``prefill_token_s`` per *unmatched* prompt token,
  budgeted per round like the real chunked prefill;
- the KV pool is block accounting only: per-slot pages plus an LRU
  chain cache with the radix contract (whole-page prefix match, evict
  unreferenced LRU, youngest preemption when growth squeezes dry).

The numbers that come out are capacity-model numbers — TTFT and
inter-token latency under the *scheduling* dynamics — not kernel
benchmarks; ``benchmark/`` owns those.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Dict, List, Optional

from lzy_tpu.load.trace import reply_tokens
from lzy_tpu.serving.engine import EngineStats
from lzy_tpu.serving.scheduler import (
    AdmissionError, PromptTooLong, Request, RequestQueue)


@dataclasses.dataclass(frozen=True)
class SimProfile:
    """Virtual-cost model of one replica (defaults are roughly one
    accelerator-backed engine serving a small model)."""

    slots: int = 8
    max_queue: int = 64
    page_size: int = 16
    kv_blocks: int = 512
    max_seq_len: int = 4096
    decode_step_s: float = 0.03        # one decode round over the batch
    prefill_token_s: float = 0.00012   # per unmatched prompt token
    round_overhead_s: float = 0.001    # scheduling/dispatch tax per round
    prefill_budget: int = 512          # prompt tokens per round (chunked)


def _blocks_for(n_tokens: int, page: int) -> int:
    return -(-n_tokens // page)


class _SimPrefill:
    __slots__ = ("req", "slot", "matched", "done")

    def __init__(self, req: Request, slot: int, matched: int):
        self.req = req
        self.slot = slot
        self.matched = matched        # prompt tokens served by the cache
        self.done = 0                 # suffix tokens already prefilled


class SimEngine:
    """One simulated replica (see module docstring).  Drive it with
    :meth:`run_round` from the load driver's loop — ``start()`` is a
    no-op so the fleet's lifecycle calls stay valid."""

    def __init__(self, profile: SimProfile, *, clock, tenants=None,
                 collector=None, seed: int = 0):
        self.profile = profile
        self._clock = clock
        self.collector = collector
        self.cfg = SimpleNamespace(max_seq_len=profile.max_seq_len)
        self.queue = RequestQueue(profile.max_queue, policies=tenants,
                                  clock=clock)
        self.tenants = tenants
        # the fleet aggregate reads kv.hit_tokens/kv.lookup_tokens off
        # "the radix tree"; the sim's accounting lives on the engine
        # itself, so alias it (duck-typed: only those two attrs are read)
        self.kv = self
        self._seed = seed
        self._active: List[Optional[Request]] = [None] * profile.slots
        self._emitted_at: List[float] = [0.0] * profile.slots
        self._admit_seq: List[int] = [0] * profile.slots
        self._admissions = 0
        self._prefills: List[_SimPrefill] = []
        self._next_prefill = 0
        # chain cache: hash of a whole-page prefix chain -> LRU stamp
        # (the radix tree collapsed to its accounting: one block per
        # chain node, whole-page prefix match, LRU eviction)
        self._cache: Dict[int, int] = {}
        self._lru = 0
        # workflow-scheduler parking: key -> (chain hashes, expires_at).
        # Parked chains are PINNED against LRU eviction until their TTL
        # lapses (swept per round) or pressure sheds them — the sim's
        # analogue of the paged engine's _ParkedChain machinery, so the
        # load plane exercises fused op chains on the virtual clock.
        self._parked: Dict[str, tuple] = {}
        self._closed = False
        self._finished = 0
        self._cancelled = 0
        self._preempted = 0
        self._tokens_out = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.evictions = 0
        self.busy_until = 0.0

    # -- engine surface ------------------------------------------------------

    @property
    def closed(self) -> bool:
        return self._closed

    def start(self) -> "SimEngine":
        return self                   # the load driver steps us directly

    def close(self, timeout: float = 10.0) -> None:
        self._closed = True
        self._parked = {}
        for job in list(self._prefills):
            job.req.finish(error="engine shutting down")
        self._prefills = []
        for req in self.queue.drain():
            req.finish(error="engine shutting down")
        for slot, req in enumerate(self._active):
            if req is not None:
                req.finish(error="engine shutting down")
                self._active[slot] = None

    def submit(self, prompt, *, max_new_tokens: int = 64,
               request_id: Optional[str] = None,
               deadline_s: Optional[float] = None,
               greedy: Optional[bool] = None,
               tenant: str = "default",
               priority: Optional[int] = None,
               liveness=None) -> Request:
        if self._closed:
            raise AdmissionError("inference engine is shut down")
        prompt = list(prompt)
        if not prompt:
            raise ValueError("prompt must be non-empty")
        p = self.profile
        if len(prompt) + max_new_tokens > p.max_seq_len:
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"({p.max_seq_len})")
        if _blocks_for(len(prompt), p.page_size) > p.kv_blocks - 1:
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) exceeds the simulated "
                f"KV pool ({p.kv_blocks} blocks)")
        quota = self._tenant_quota(tenant or "default")
        if quota is not None \
                and _blocks_for(len(prompt), p.page_size) > quota:
            # same permanent rejection as the paged engine: past submit
            # the head could NEVER be admitted (the quota skip would
            # park it forever — a livelock the real engine also guards)
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) exceeds tenant "
                f"{tenant!r}'s kv_block_quota ({quota})")
        req = Request(prompt, max_new_tokens, request_id=request_id,
                      deadline_s=deadline_s, greedy=greedy,
                      tenant=tenant, priority=priority,
                      liveness=liveness, clock=self._clock)
        self.queue.submit(req)
        return req

    # -- KV block accounting -------------------------------------------------

    def _chain_hashes(self, tokens: List[int]) -> List[int]:
        """Chain hash per whole page.  ``hash(tuple-of-ints)`` is
        C-speed AND process-stable (PYTHONHASHSEED only perturbs
        str/bytes), and this sits on the per-request hot path — a
        per-token Python mix here dominated whole replays."""
        page = self.profile.page_size
        out, h = [], 0x5EED ^ self._seed
        for i in range(0, len(tokens) - len(tokens) % page, page):
            h = hash((h, tuple(tokens[i:i + page])))
            out.append(h)
        return out

    def _match(self, prompt: List[int]) -> int:
        """Whole-page cached prefix length (LRU-bumped), radix style:
        capped at prompt[:-1] so one token always prefills.  Hashes
        lazily — a cold prompt costs one page hash, not the full walk."""
        page = self.profile.page_size
        body = prompt[:-1]
        matched = 0
        h = 0x5EED ^ self._seed
        for i in range(0, len(body) - len(body) % page, page):
            h = hash((h, tuple(body[i:i + page])))
            if h not in self._cache:
                break
            self._lru += 1
            self._cache[h] = self._lru
            matched += page
        self.hit_tokens += matched
        self.lookup_tokens += len(prompt)
        return matched

    def _insert(self, prompt: List[int]) -> None:
        for h in self._chain_hashes(prompt):
            self._lru += 1
            self._cache[h] = self._lru
        self._shrink_cache()

    def _active_blocks(self) -> int:
        page = self.profile.page_size
        total = 0
        for slot, req in enumerate(self._active):
            if req is not None:
                total += _blocks_for(len(req.prompt) + len(req.tokens),
                                     page)
        for job in self._prefills:
            total += _blocks_for(len(job.req.prompt), page)
        return total

    def _shrink_cache(self) -> None:
        """Evict LRU cached chains past the pool budget (cached blocks
        are the overcommit slack, exactly like unreferenced radix
        leaves). Parked chains are pinned: under pressure the soonest-
        expiring parked chain is shed WHOLE before any pinned page goes
        — mirroring the paged engine's parked-before-preemption
        ordering."""
        budget = self.profile.kv_blocks - 1 - self._active_blocks()
        while len(self._cache) > max(0, budget):
            pinned = {h for hashes, _ in self._parked.values()
                      for h in hashes}
            victims = [h for h in self._cache if h not in pinned]
            if victims:
                victim = min(victims, key=self._cache.get)
                del self._cache[victim]
                self.evictions += 1
                continue
            if not self._parked:
                break
            shed = min(self._parked, key=lambda k: self._parked[k][1])
            del self._parked[shed]

    def _available(self) -> int:
        # cached chains are evictable (LRU), so they never subtract from
        # what an admission could obtain — same contract as the radix
        # tree's available()
        return self.profile.kv_blocks - 1 - self._active_blocks()

    def _can_admit(self, req: Request) -> bool:
        need = _blocks_for(len(req.prompt), self.profile.page_size)
        return self._available() >= need

    # -- workflow-scheduler parking (gateway park_conversation) --------------

    def park_chain(self, key, tokens, ttl_s: float = 30.0,
                   timeout_s: float = 5.0) -> bool:
        """Pin the cached whole-page prefix of ``tokens`` against LRU
        eviction for ``ttl_s`` virtual seconds — the sim analogue of the
        paged engine's park surface. Returns False (nothing pinned) when
        no prefix of ``tokens`` is cached."""
        del timeout_s                 # sync engine: parking is immediate
        if self._closed:
            return False
        page = self.profile.page_size
        tokens = list(tokens)
        hashes, h = [], 0x5EED ^ self._seed
        for i in range(0, len(tokens) - len(tokens) % page, page):
            h = hash((h, tuple(tokens[i:i + page])))
            if h not in self._cache:
                break
            self._lru += 1
            self._cache[h] = self._lru
            hashes.append(h)
        if not hashes:
            self._parked.pop(str(key), None)
            return False
        self._parked[str(key)] = (tuple(hashes),
                                  self._clock.now() + float(ttl_s))
        return True

    def unpark_chain(self, key, timeout_s: float = 5.0) -> bool:
        del timeout_s
        return self._parked.pop(str(key), None) is not None

    def _sweep_parked(self) -> None:
        now = self._clock.now()
        for key in [k for k, (_, exp) in self._parked.items()
                    if now >= exp]:
            del self._parked[key]

    def _tenant_quota(self, tenant: str) -> Optional[int]:
        if self.tenants is None:
            return None
        return self.tenants.resolve(tenant).kv_block_quota

    def _tenant_blocks(self, tenant: str) -> int:
        page = self.profile.page_size
        held = 0
        for req in self._active:
            if req is not None and req.tenant == tenant:
                held += _blocks_for(len(req.prompt) + len(req.tokens), page)
        for job in self._prefills:
            if job.req.tenant == tenant:
                held += _blocks_for(len(job.req.prompt), page)
        return held

    # -- scheduling round ----------------------------------------------------

    def has_work(self) -> bool:
        return bool(self.queue.depth() or self._prefills
                    or any(r is not None for r in self._active))

    def _finish_cancelled(self, req: Request) -> None:
        self._cancelled += 1
        if req.cancelled:
            why = "cancelled"
        elif req.expired:
            why = "cancelled: deadline exceeded"
        else:
            why = "cancelled: client disconnected"
        req.finish(error=why, status="cancelled")

    def _free_slot(self) -> Optional[int]:
        reserved = {job.slot for job in self._prefills}
        for slot, req in enumerate(self._active):
            if req is None and slot not in reserved:
                return slot
        return None

    def _reap(self) -> None:
        for req in self.queue.reap_dead():
            self._finish_cancelled(req)
        for job in list(self._prefills):
            if job.req.reapable:
                self._drop_prefill(job)
                self._finish_cancelled(job.req)
        for slot, req in enumerate(self._active):
            if req is not None and req.reapable:
                self._active[slot] = None
                self._finish_cancelled(req)

    def _drop_prefill(self, job: _SimPrefill) -> None:
        idx = self._prefills.index(job)
        del self._prefills[idx]
        if self._next_prefill > idx:
            self._next_prefill -= 1

    def _admit(self) -> bool:
        admitted = False
        while True:
            slot = self._free_slot()
            if slot is None:
                break
            rescan = False
            for req in self.queue.candidates():
                if req.reapable:
                    if self.queue.pop_request(req):
                        self._finish_cancelled(req)
                    rescan = True
                    break
                quota = self._tenant_quota(req.tenant)
                if quota is not None:
                    need = _blocks_for(len(req.prompt),
                                       self.profile.page_size)
                    if self._tenant_blocks(req.tenant) + need > quota:
                        continue            # tenant-scoped: skip, not block
                if not self._can_admit(req):
                    break                   # global capacity: all wait
                self.queue.pop_request(req)
                req.phase = "prefill"
                matched = self._match(req.prompt)
                self._prefills.append(_SimPrefill(req, slot, matched))
                admitted = True
                break
            if not rescan:
                break                       # one staging per round
        return admitted

    def _advance_prefill(self) -> float:
        """One budgeted prefill round (round-robin over jobs); returns
        its virtual cost.  The first token is stamped at the round's
        modeled COMPLETION time — the driver only advances the clock
        afterwards, so emission timestamps must carry the cost
        themselves or TTFT would exclude the prefill entirely."""
        if not self._prefills:
            return 0.0
        if self._next_prefill >= len(self._prefills):
            self._next_prefill = 0
        job = self._prefills[self._next_prefill]
        req = job.req
        remaining = len(req.prompt) - job.matched - job.done
        take = min(self.profile.prefill_budget, remaining)
        job.done += take
        cost = take * self.profile.prefill_token_s
        if job.done >= len(req.prompt) - job.matched:
            # prefill complete: first token, slot activation
            self._drop_prefill(job)
            slot = job.slot
            at = self._clock.now() + cost
            req.phase = "decode"
            req.first_token_at = at
            self._emit(slot, req, 0, at, activate=True)
            self._insert(req.prompt)
        else:
            self._next_prefill += 1
        return cost

    def _emit(self, slot: int, req: Request, idx: int, now: float,
              activate: bool = False) -> None:
        reply = getattr(req, "_sim_reply", None)
        if reply is None:
            # computed once per (attempt) prompt — the deterministic
            # continuation both the trace's history model and this
            # engine agree on
            reply = req._sim_reply = reply_tokens(req.prompt,
                                                  req.max_new_tokens)
        token = reply[idx]
        req.tokens.append(token)
        self._tokens_out += 1
        sink = req.token_sink
        if sink is not None:
            try:
                sink(req)
            except Exception:  # noqa: BLE001 — consumer bug, not ours
                req.token_sink = None
        if self.collector is not None:
            if len(req.tokens) > 1:
                self.collector.note_gap(now - self._emitted_at[slot])
            self.collector.note_token(req.tenant)
        self._emitted_at[slot] = now
        if len(req.tokens) >= req.max_new_tokens:
            self._finished += 1
            self._active[slot] = None
            req.finish()
        elif activate:
            self._active[slot] = req
            self._admissions += 1
            self._admit_seq[slot] = self._admissions

    def _preempt_youngest(self) -> None:
        victim = max(
            (s for s, r in enumerate(self._active) if r is not None),
            key=lambda s: self._admit_seq[s])
        req = self._active[victim]
        self._active[victim] = None
        self._preempted += 1
        # same error prefix as the paged engine: the gateway treats it
        # as a capacity signal (failover without health damage)
        req.finish(error="preempted: kv block pool exhausted")

    def _decode(self, offset: float) -> float:
        """One decode round; ``offset`` is the virtual cost already
        accrued this round (prefill), so emissions are stamped at the
        modeled step-completion instant."""
        active = [s for s, r in enumerate(self._active) if r is not None]
        if not active:
            return 0.0
        # growth: decode writes need block headroom; cached chains yield
        # first (_shrink_cache at round end), and when active rows ALONE
        # overflow the pool, the youngest is preempted — the overcommit
        # backstop, surfaced to the gateway as a capacity failover
        while self._active_blocks() > self.profile.kv_blocks - 1 \
                and any(r is not None for r in self._active):
            self._preempt_youngest()
        at = self._clock.now() + offset + self.profile.decode_step_s
        emitted = False
        for slot in active:
            req = self._active[slot]
            if req is None:
                continue    # preempted this round
            self._emit(slot, req, len(req.tokens), at)
            emitted = True
        return self.profile.decode_step_s if emitted else 0.0

    def run_round(self) -> float:
        """One scheduling round; returns its virtual duration (0.0 =
        nothing to do).  The driver advances the clock by the return
        value before this replica's next round."""
        if self._closed:
            return 0.0
        self._sweep_parked()
        self._reap()
        admitted = self._admit()
        cost = self._advance_prefill()
        cost += self._decode(cost)
        if cost == 0.0 and not admitted:
            return 0.0
        self._shrink_cache()
        return cost + self.profile.round_overhead_s

    # -- observability -------------------------------------------------------

    def stats(self) -> EngineStats:
        return EngineStats(
            slots=self.profile.slots,
            busy=sum(r is not None for r in self._active),
            queue_depth=self.queue.depth(),
            requests_finished=self._finished,
            tokens_generated=self._tokens_out,
            requests_cancelled=self._cancelled,
            kv_page_size=self.profile.page_size,
            kv_blocks_total=self.profile.kv_blocks - 1,
            kv_blocks_free=max(0, self._available() - len(self._cache)),
            kv_blocks_cached=len(self._cache),
            kv_evictions=self.evictions,
            kv_parked_chains=len(self._parked),
            kv_parked_blocks=sum(len(hs)
                                 for hs, _ in self._parked.values()),
            prefix_hit_rate=round(
                self.hit_tokens / self.lookup_tokens, 4)
            if self.lookup_tokens else 0.0,
            prefill_tokens_saved=self.hit_tokens,
        )

    @property
    def preempted(self) -> int:
        return self._preempted

"""Multi-tenant SLO serving: WFQ, rate limits, quotas, chunked prefill.

The isolation contract under test: whatever an aggressor tenant does —
saturating its rate limit, flooding the queue, dragging 100+-token
prompts through prefill, pinning KV blocks up to its quota — a victim
tenant's requests still admit, reach their first token within a bounded
number of engine rounds, and decode BIT-IDENTICALLY to an uncontended
``generate()`` run. Engine tests drive ``step()`` synchronously so every
fairness/interleaving assertion is deterministic (counted in scheduling
rounds, not wall time); the gateway test layers the token-bucket front
on top.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.chaos.invariants import audit_engine
from lzy_tpu.gateway import GatewayService, PrefixAffinityRouter, ReplicaFleet
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import (
    AdmissionError, PagedInferenceEngine, PromptTooLong,
    QuotaExceeded, Request, RequestQueue, SloLimiter, TenantPolicy,
    TenantTable, TokenBucket)

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


def _oracle_tokens(cfg, params, prompt_ids, n, **kw):
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _req(tenant="default", priority=None, cost=10):
    return Request([1] * (cost - 4), 4, tenant=tenant, priority=priority)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# token buckets


class TestTokenBucket:
    def test_burst_then_rate(self):
        clock = FakeClock()
        b = TokenBucket(10.0, 20.0, clock=clock)
        for _ in range(20):
            assert b.try_take(1) is None        # the full burst passes
        wait = b.try_take(1)
        assert wait == pytest.approx(0.1)       # 1 token at 10/s
        clock.advance(0.1)
        assert b.try_take(1) is None
        clock.advance(10.0)
        assert b.level() == pytest.approx(20.0)  # capped at burst

    def test_oversize_take_runs_a_debt(self):
        clock = FakeClock()
        b = TokenBucket(100.0, 200.0, clock=clock)
        # a single take larger than the burst is allowed from a full
        # bucket (a long prompt is not a hard cap) but drives the level
        # negative: the tenant then waits out the debt at its rate
        assert b.try_take(500.0) is None
        assert b.level() == pytest.approx(-300.0)
        wait = b.try_take(1.0)
        assert wait == pytest.approx((1 + 300) / 100.0)
        clock.advance(3.02)
        assert b.try_take(1.0) is None

    def test_give_back_refunds(self):
        clock = FakeClock()
        b = TokenBucket(1.0, 2.0, clock=clock)
        assert b.try_take(2) is None
        assert b.try_take(1) is not None
        b.give_back(2)
        assert b.try_take(2) is None


class TestPolicyTable:
    def test_priority_maps_to_weight_and_only_downgrades(self):
        p = TenantPolicy(tenant="t", priority=0)
        assert p.effective_weight() == 4.0
        assert p.effective_priority(None) == 0
        # a client may volunteer DOWN to batch tier, never up
        assert p.effective_priority(2) == 2
        low = TenantPolicy(tenant="t", priority=2)
        assert low.effective_priority(0) == 2
        assert low.effective_weight(0) == 1.0

    def test_explicit_weight_is_a_ceiling_under_downgrade(self):
        # an operator-throttled weight must not be ESCAPABLE by a client
        # volunteering for a lower tier whose tier weight is larger
        throttled = TenantPolicy(tenant="t", priority=1, weight=0.5)
        assert throttled.effective_weight() == 0.5
        assert throttled.effective_weight(2) == 0.5      # not tier 2's 1.0
        # a downgrade may still SHRINK a generous weight to the tier's
        boosted = TenantPolicy(tenant="t", priority=0, weight=8.0)
        assert boosted.effective_weight() == 8.0
        assert boosted.effective_weight(2) == 1.0
        # and a requested upgrade never dislodges the configured weight
        assert throttled.effective_weight(0) == 0.5

    def test_resolve_unknown_tenant_gets_default(self):
        table = TenantTable(default=TenantPolicy(requests_per_s=5.0))
        p = table.resolve("newcomer")
        assert p.tenant == "newcomer" and p.requests_per_s == 5.0
        table.set_policy(TenantPolicy(tenant="vip", priority=0))
        assert table.resolve("vip").priority == 0

    def test_from_doc_rejects_unknown_fields(self):
        with pytest.raises(ValueError, match="unknown policy fields"):
            TenantTable.from_doc({"a": {"requets_per_s": 3}})
        table = TenantTable.from_doc(
            {"a": {"priority": 0, "kv_block_quota": 8}})
        assert table.resolve("a").kv_block_quota == 8


# ---------------------------------------------------------------------------
# the WFQ request queue (no model needed)


class TestWfqQueue:
    def test_single_tenant_is_fifo(self):
        q = RequestQueue(max_depth=16)
        reqs = [_req() for _ in range(6)]
        for r in reqs:
            q.submit(r)
        assert [q.pop() for _ in range(6)] == reqs

    def test_weighted_interleave_favors_high_tier(self):
        table = TenantTable()
        table.set_policy(TenantPolicy(tenant="hi", priority=0))   # w=4
        table.set_policy(TenantPolicy(tenant="lo", priority=2))   # w=1
        q = RequestQueue(max_depth=32, policies=table)
        for _ in range(8):
            q.submit(_req("hi"))
        for _ in range(8):
            q.submit(_req("lo"))
        first8 = [q.pop().tenant for _ in range(8)]
        # 4:1 weights -> the first window is dominated by the high tier
        assert first8.count("hi") >= 6
        # nothing is lost: all 16 drain
        assert sum(1 for _ in range(8) if q.pop() is not None) == 8

    def test_starved_tenant_ages_to_front(self):
        table = TenantTable()
        table.set_policy(TenantPolicy(tenant="heavy", priority=0))
        table.set_policy(TenantPolicy(tenant="late", priority=2))
        q = RequestQueue(max_depth=64, policies=table)
        for _ in range(20):
            q.submit(_req("heavy"))
        for _ in range(10):      # advance virtual time
            q.pop()
        late = _req("late")
        q.submit(late)
        # despite the worst weight and 10 queued heavy requests, the
        # newcomer's start tag clamps to the advanced virtual time: it
        # pops within a handful of dispatches (bounded by the weight
        # ratio), not after the backlog
        pops = [q.pop() for _ in range(5)]
        assert late in pops

    def test_per_tenant_cap_sheds_only_that_tenant(self):
        table = TenantTable(default=TenantPolicy(max_queued=2))
        q = RequestQueue(max_depth=64, policies=table)
        q.submit(_req("agg"))
        q.submit(_req("agg"))
        with pytest.raises(QuotaExceeded) as ei:
            q.submit(_req("agg"))
        assert ei.value.tenant == "agg"
        assert ei.value.reason == "max_queued"
        assert ei.value.retry_after_s is not None
        assert isinstance(ei.value, AdmissionError)
        # the victim is untouched by the aggressor's cap
        q.submit(_req("vic"))
        assert q.depth_of("vic") == 1

    def test_global_cap_still_applies(self):
        q = RequestQueue(max_depth=2)
        q.submit(_req("a"))
        q.submit(_req("b"))
        with pytest.raises(AdmissionError) as ei:
            q.submit(_req("c"))
        assert not isinstance(ei.value, QuotaExceeded)
        assert ei.value.retry_after_s is not None

    def test_peek_pins_the_head_across_cross_tenant_submits(self):
        table = TenantTable()
        table.set_policy(TenantPolicy(tenant="lo", priority=2))
        table.set_policy(TenantPolicy(tenant="hi", priority=0))
        q = RequestQueue(max_depth=8, policies=table)
        lo = _req("lo")
        q.submit(lo)
        assert q.peek() is lo
        q.submit(_req("hi"))     # earlier virtual finish than lo's
        # the peeked head is pinned: budget-then-commit admission must
        # pop what it budgeted for
        assert q.pop() is lo

    def test_candidates_order_and_pop_request(self):
        table = TenantTable()
        table.set_policy(TenantPolicy(tenant="hi", priority=0))
        q = RequestQueue(max_depth=8, policies=table)
        a = _req("std")
        b = _req("hi")
        q.submit(a)
        q.submit(b)
        cands = q.candidates()
        assert set(cands) == {a, b}
        assert q.pop_request(cands[-1])
        assert not q.pop_request(cands[-1])     # already removed
        assert q.pop() is cands[0]

    def test_reap_dead_spans_tenants(self):
        q = RequestQueue(max_depth=8)
        a, b = _req("a"), _req("b")
        q.submit(a)
        q.submit(b)
        a.cancel()
        b.cancel()
        assert set(q.reap_dead()) == {a, b}
        assert q.depth() == 0

    def test_finish_tags_swept_for_drained_tenants(self):
        # with IAM on, tenant ids are subject ids: the virtual-time tag
        # map must stay bounded by ACTIVE tenants, not by every tenant
        # ever seen. Tags are swept once the clock passes them, so after
        # enough foreground traffic the drained tenants are gone.
        q = RequestQueue(max_depth=256)
        for i in range(20):
            q.submit(_req(f"one-shot-{i}"))
        while q.pop() is not None:
            pass
        for _ in range(4):          # ongoing traffic advances vtime
            q.submit(_req("steady", cost=200))
        while q.pop() is not None:
            pass
        assert len(q._finish_tag) <= 1, sorted(q._finish_tag)


# ---------------------------------------------------------------------------
# the SLO limiter (rate buckets at the serving front)


class TestSloLimiter:
    def test_aggressor_saturates_without_touching_victim(self):
        clock = FakeClock()
        table = TenantTable(default=TenantPolicy(requests_per_s=2.0,
                                                 burst_s=1.0))
        slo = SloLimiter(table, clock=clock)
        slo.admit("agg", 4)
        slo.admit("agg", 4)
        with pytest.raises(QuotaExceeded) as ei:
            slo.admit("agg", 4)
        assert ei.value.tenant == "agg"
        assert ei.value.reason == "requests_per_s"
        assert ei.value.retry_after_s and ei.value.retry_after_s > 0
        assert f"retry_after_s={ei.value.retry_after_s:.2f}" in str(ei.value)
        # the victim's buckets are its own
        slo.admit("vic", 4)
        clock.advance(1.0)
        slo.admit("agg", 4)     # refilled on the aggressor's clock

    def test_token_refusal_refunds_the_request_take(self):
        clock = FakeClock()
        table = TenantTable(default=TenantPolicy(
            requests_per_s=100.0, prompt_tokens_per_s=10.0, burst_s=1.0))
        slo = SloLimiter(table, clock=clock)
        slo.admit("t", 1000)     # oversize passes ONCE on a full bucket
        with pytest.raises(QuotaExceeded) as ei:
            slo.admit("t", 5)    # then the debt refuses further tokens
        assert ei.value.reason == "prompt_tokens_per_s"
        # ...but the refusal refunded its request-bucket take: only the
        # one admitted request was ever charged there
        req_bucket = slo._buckets["t"][0]
        assert req_bucket.level() == pytest.approx(99.0)

    def test_enforced_backoff_punishes_hammering(self):
        """Load-harness finding (ISSUE 13): with an ADVISORY hint, a
        client polling the bucket every few ms grabs each refilled token
        ahead of everyone who honored the hint — misbehavior won
        throughput. With ``enforce_backoff=True`` an early return is
        refused AND extends the tenant's window, so hammering starves
        itself while the hint-honoring schedule is served on time."""
        clock = FakeClock()
        table = TenantTable(default=TenantPolicy(requests_per_s=1.0,
                                                 burst_s=1.0))
        slo = SloLimiter(table, clock=clock, enforce_backoff=True,
                         backoff_step_s=0.05)
        slo.admit("ham", 4)
        with pytest.raises(QuotaExceeded) as ei:
            slo.admit("ham", 4)
        hint = ei.value.retry_after_s
        assert hint and hint > 0
        # hammer: returns every 10 ms ignoring the hint — every poll is
        # refused with reason="backoff" and pushes the window out, so
        # even past the ORIGINAL hint the tenant stays refused
        polls = 0
        for _ in range(200):
            clock.advance(0.01)
            with pytest.raises(QuotaExceeded) as ei2:
                slo.admit("ham", 4)
            polls += 1
            if clock.t - 1000.0 > hint + 0.5:
                break
        assert ei2.value.reason == "backoff"
        assert polls > 10
        # a polite tenant with the same policy: refused once, waits out
        # ITS hint, admitted on schedule
        slo.admit("pol", 4)
        with pytest.raises(QuotaExceeded) as ei3:
            slo.admit("pol", 4)
        clock.advance(ei3.value.retry_after_s + 0.001)
        slo.admit("pol", 4)     # honoring the hint still wins service

    def test_backoff_enforcement_off_by_default(self):
        clock = FakeClock()
        table = TenantTable(default=TenantPolicy(requests_per_s=1.0,
                                                 burst_s=1.0))
        slo = SloLimiter(table, clock=clock)
        slo.admit("t", 4)
        with pytest.raises(QuotaExceeded):
            slo.admit("t", 4)
        clock.advance(1.0)      # refilled: advisory mode admits again
        slo.admit("t", 4)


# ---------------------------------------------------------------------------
# chunked prefill: decode interleave + bit identity


class TestChunkedPrefill:
    @pytest.mark.parametrize("kernel", ["lax", "pallas"])
    def test_long_prompt_interleaves_with_decode(self, tiny_model, kernel):
        """A resident request keeps emitting tokens BETWEEN a long
        prompt's prefill rounds — the decode-steps-between-prefill-chunks
        assertion — and both outputs stay bit-identical to the oracle, on
        either read path (the kernel's decode rounds between lax prefill
        chunks; held to the oracle in float32, where its reordered sums
        stay below any gap between two logits)."""
        cfg, params = tiny_model
        if kernel == "pallas":
            cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        engine = PagedInferenceEngine(
            cfg, params, slots=2, prefill_chunk=16, prefill_budget=16,
            page_size=PAGE, kernel=kernel)
        short = [3, 5, 7]
        long = [(7 * i) % 60 + 1 for i in range(120)]
        n_short = 40 if kernel == "lax" else 14    # interpreted: keep it short
        r_short = engine.submit(short, max_new_tokens=n_short)
        engine.step()                       # short resident and decoding
        assert len(r_short.tokens) >= 1
        r_long = engine.submit(long, max_new_tokens=8)
        engine.step()                       # stage + first budget round
        assert engine.prefill.jobs
        interleaved = 0
        rounds = 1
        while engine.prefill.jobs and rounds < 50:
            before = len(r_short.tokens)
            done_before = engine.prefill.jobs[0].done
            engine.step()
            rounds += 1
            if engine.prefill.jobs:
                # bounded advance per round: at most the budget (one
                # chunk here) of prompt tokens moved
                assert engine.prefill.jobs[0].done - done_before <= 16
            if len(r_short.tokens) > before:
                interleaved += 1
        # the 120-token prompt must have taken several rounds, and the
        # resident stream advanced during (not after) them
        assert rounds >= 6
        assert interleaved >= 5
        while not (r_short.done and r_long.done):
            engine.step()
        assert r_short.tokens == _oracle_tokens(cfg, params, short, n_short)
        assert r_long.tokens == _oracle_tokens(cfg, params, long, 8)
        audit_engine(engine)
        engine.close()

    def test_victim_ttft_bounded_in_rounds(self, tiny_model):
        """A short prompt staged behind a long one reaches its first
        token in O(1) engine rounds (round-robin job advance), NOT after
        the aggressor's whole prefill — the structural TTFT bound."""
        cfg, params = tiny_model
        engine = PagedInferenceEngine(
            cfg, params, slots=2, page_size=PAGE, prefill_chunk=16,
            prefill_budget=16)
        aggressor = [(3 * i) % 50 + 1 for i in range(160)]  # 10 rounds
        victim = [9, 2, 4]
        r_agg = engine.submit(aggressor, max_new_tokens=4)
        engine.step()       # aggressor staged + first chunk
        r_vic = engine.submit(victim, max_new_tokens=6)
        rounds_to_first = 0
        while r_vic.first_token_at is None:
            engine.step()
            rounds_to_first += 1
            assert rounds_to_first < 8, \
                "victim TTFT grew with the aggressor's prompt length"
        # victim decodes bit-identically while the aggressor still
        # prefills; aggressor finishes later, also bit-identical
        while not (r_vic.done and r_agg.done):
            engine.step()
        assert r_vic.tokens == _oracle_tokens(cfg, params, victim, 6)
        assert r_agg.tokens == _oracle_tokens(cfg, params, aggressor, 4)
        audit_engine(engine)
        engine.close()

    def test_prefix_reuse_still_bit_identical_when_chunked(self, tiny_model):
        cfg, params = tiny_model
        engine = PagedInferenceEngine(
            cfg, params, slots=2, page_size=PAGE, prefill_chunk=16,
            prefill_budget=16)
        header = list(range(1, 3 * PAGE + 1))
        p1 = header + [40]
        p2 = header + [41, 42]
        r1 = engine.submit(p1, max_new_tokens=6)
        while not r1.done:
            engine.step()
        saved_before = engine.kv.hit_tokens
        r2 = engine.submit(p2, max_new_tokens=6)
        while not r2.done:
            engine.step()
        assert engine.kv.hit_tokens > saved_before      # prefix was reused
        assert r1.tokens == _oracle_tokens(cfg, params, p1, 6)
        assert r2.tokens == _oracle_tokens(cfg, params, p2, 6)
        audit_engine(engine)
        engine.close()

    def test_cancel_mid_prefill_releases_staged_blocks(self, tiny_model):
        cfg, params = tiny_model
        engine = PagedInferenceEngine(
            cfg, params, slots=2, page_size=PAGE, prefill_chunk=16,
            prefill_budget=16)
        free0 = engine.kv.pool.free_count()
        r = engine.submit([(5 * i) % 60 + 1 for i in range(120)],
                          max_new_tokens=4)
        engine.step()                      # staged, first chunk run
        assert engine.prefill.jobs
        r.cancel()
        engine.step()
        assert not engine.prefill.jobs
        assert r.status == "cancelled"
        assert engine.kv.pool.free_count() == free0
        audit_engine(engine)
        engine.close()


# ---------------------------------------------------------------------------
# per-tenant KV quotas (paged admission)


class TestKvQuota:
    def test_quota_skips_tenant_without_blocking_others(self, tiny_model):
        cfg, params = tiny_model
        table = TenantTable()
        # agg may hold at most 3 blocks (= 24 tokens incl. decode room)
        table.set_policy(TenantPolicy(tenant="agg", kv_block_quota=3))
        engine = PagedInferenceEngine(
            cfg, params, slots=3, page_size=PAGE, prefill_chunk=16,
            tenants=table)
        a1 = engine.submit([1] * 17, max_new_tokens=4, tenant="agg")
        engine.step()
        assert a1.first_token_at is not None    # 3 blocks: at quota
        # agg's second request cannot admit (quota), but the later-queued
        # victim admits right past it
        a2 = engine.submit([2] * 17, max_new_tokens=4, tenant="agg")
        v = engine.submit([3, 4, 5], max_new_tokens=4, tenant="vic")
        engine.step()
        assert v.first_token_at is not None
        assert a2.first_token_at is None
        assert engine.queue.depth_of("agg") == 1
        # quota frees with agg's own completions; a2 then admits
        while not a1.done:
            engine.step()
        for _ in range(30):
            engine.step()
            if a2.done:
                break
        assert a2.done and a2.error is None
        while not v.done:
            engine.step()
        assert v.tokens == _oracle_tokens(cfg, params, [3, 4, 5], 4)
        audit_engine(engine)
        engine.close()

    def test_prompt_over_quota_rejected_at_submit(self, tiny_model):
        cfg, params = tiny_model
        table = TenantTable(default=TenantPolicy(kv_block_quota=2))
        engine = PagedInferenceEngine(
            cfg, params, slots=2, page_size=PAGE, tenants=table)
        with pytest.raises(PromptTooLong, match="kv_block_quota"):
            engine.submit([1] * (3 * PAGE), max_new_tokens=2, tenant="t")
        engine.close()


# ---------------------------------------------------------------------------
# over-long prompts: clear AdmissionError at admission, everywhere


class TestPromptTooLongAdmission:
    def test_rejected_at_submit(self, tiny_model):
        cfg, params = tiny_model
        too_long = [1] * (cfg.max_seq_len - 4)
        for engine in (PagedInferenceEngine(cfg, params, slots=1),
                       PagedInferenceEngine(cfg, params, slots=1,
                                            page_size=PAGE)):
            with pytest.raises(PromptTooLong, match="max_seq_len"):
                engine.submit(too_long, max_new_tokens=16)
            # the typed rejection is BOTH a retol-safe AdmissionError and
            # a ValueError (INVALID_ARGUMENT on the wire)
            with pytest.raises(AdmissionError):
                engine.submit(too_long, max_new_tokens=16)
            with pytest.raises(ValueError):
                engine.submit(too_long, max_new_tokens=16)
            engine.close()

    def test_gateway_rejects_before_routing_without_health_damage(
            self, tiny_model):
        cfg, params = tiny_model

        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=1))
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny")
        try:
            fleet.add_replica()
            with pytest.raises(PromptTooLong, match="max_seq_len"):
                gw.generate([1] * cfg.max_seq_len, max_new_tokens=16,
                            timeout_s=10)
            stats = gw.stats()
            assert stats["failovers"] == 0
            for replica in fleet.replicas():
                assert fleet.health.failures(replica.id) == 0
            # the plane still serves fine afterwards
            res = gw.generate([5, 6], max_new_tokens=4, timeout_s=30)
            assert res["status"] == "ok"
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# the isolation acceptance test: aggressor vs victim through the gateway


class TestMultiTenantIsolation:
    def test_aggressor_cannot_starve_victim(self, tiny_model):
        """Aggressor saturates its rate limit + KV quota with long
        prompts; the victim's short requests all admit, decode
        bit-identically to the oracle, and keep a bounded TTFT; the
        aggressor's rejections carry its own retry_after_s."""
        cfg, params = tiny_model
        table = TenantTable()
        table.set_policy(TenantPolicy(
            tenant="agg", priority=2, requests_per_s=4.0, burst_s=1.0,
            kv_block_quota=20, max_queued=2))
        table.set_policy(TenantPolicy(tenant="vic", priority=0))
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(
                cfg, params, slots=4, page_size=PAGE, prefill_chunk=16,
                prefill_budget=16, tenants=table).start())
        gw = GatewayService(
            fleet, router=PrefixAffinityRouter(PAGE), model_name="tiny",
            slo=SloLimiter(table), max_waiters=8)
        victim_prompts = [[9, i % 40 + 2, 3] for i in range(6)]
        try:
            fleet.add_replica()
            # uncontended victim TTFT baseline (post-compile)
            gw.generate(victim_prompts[0], max_new_tokens=4, timeout_s=60)
            base = [gw.generate(p, max_new_tokens=6, timeout_s=60)
                    for p in victim_prompts]
            base_ttft = max(r["ttft_ms"] for r in base)

            stop = threading.Event()
            quota_errors = []

            def aggress():
                i = 0
                while not stop.is_set():
                    prompt = [(i + 3 * j) % 50 + 1 for j in range(120)]
                    try:
                        gw.generate(prompt, max_new_tokens=4,
                                    timeout_s=60, tenant="agg")
                    except QuotaExceeded as e:
                        quota_errors.append(e)
                        time.sleep(0.01)
                    i += 1

            threads = [threading.Thread(target=aggress, daemon=True)
                       for _ in range(3)]
            for t in threads:
                t.start()
            try:
                time.sleep(0.2)          # let the aggressors saturate
                contended = [gw.generate(p, max_new_tokens=6,
                                         timeout_s=60, tenant="vic")
                             for p in victim_prompts]
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=30)
            # every victim request admitted and finished clean
            assert all(r["status"] == "ok" for r in contended)
            # bit-identical to the uncontended oracle, aggressors be
            # damned (greedy engine-wide: temperature 0)
            for p, r in zip(victim_prompts, contended):
                assert r["tokens"] == _oracle_tokens(cfg, params, p, 6)
            # TTFT stays within a bounded factor of uncontended (the
            # bound is generous — CI wall clocks are noisy — but it
            # catches the failure mode: waiting out a full long-prompt
            # prefill or the aggressor's queue backlog)
            worst = max(r["ttft_ms"] for r in contended)
            assert worst <= max(40.0 * base_ttft, 2000.0), \
                f"victim TTFT p99 {worst}ms vs uncontended {base_ttft}ms"
            # the aggressor actually hit its limits, with usable hints
            assert quota_errors, "aggressor never got rate-limited"
            assert all(e.tenant == "agg" for e in quota_errors)
            assert any(e.retry_after_s for e in quota_errors)
            # per-tenant stats kept the books for both
            tenants = gw.stats()["tenants"]
            assert tenants["vic"]["requests_finished"] >= len(contended)
            for replica in fleet.replicas():
                audit_engine(replica.engine)
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# IAM-scoped serving: tenant identity from the bearer token


class TestIamScopedServing:
    @pytest.fixture()
    def iam(self):
        from lzy_tpu.durable.store import OperationStore
        from lzy_tpu.iam import INTERNAL, IamService

        iam = IamService(OperationStore(":memory:"))
        tokens = {
            "vic": iam.create_subject("vic"),
            "agg": iam.create_subject("agg"),
            "ops": iam.create_subject("ops", role=INTERNAL),
        }
        return iam, tokens

    def _service(self, tiny_model, iam, **engine_kw):
        from lzy_tpu.service.inference import InferenceService

        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2, **engine_kw).start()
        return InferenceService(engine, model_name="tiny", iam=iam)

    def test_tenant_is_the_authenticated_subject(self, tiny_model, iam):
        iam, tokens = iam
        svc = self._service(tiny_model, iam)
        try:
            res = svc.generate([3, 4], max_new_tokens=4,
                               token=tokens["vic"], timeout_s=60)
            assert res["status"] == "ok"
            rows = svc.engine.stats_by_tenant()
            assert rows["vic"]["requests_finished"] == 1
            assert "default" not in rows
        finally:
            svc.close()

    def test_subject_cannot_masquerade_but_operator_can(
            self, tiny_model, iam):
        from lzy_tpu.iam import AuthError

        iam, tokens = iam
        svc = self._service(tiny_model, iam)
        try:
            with pytest.raises(AuthError, match="may not submit as"):
                svc.generate([3, 4], max_new_tokens=2,
                             token=tokens["vic"], tenant="agg",
                             timeout_s=60)
            # the INTERNAL role may act on a tenant's behalf (ops tooling)
            res = svc.generate([3, 4], max_new_tokens=2,
                               token=tokens["ops"], tenant="agg",
                               timeout_s=60)
            assert res["status"] == "ok"
            assert svc.engine.stats_by_tenant()["agg"][
                "requests_finished"] == 1
        finally:
            svc.close()

    def test_stats_scoped_per_subject(self, tiny_model, iam):
        iam, tokens = iam
        svc = self._service(tiny_model, iam)
        try:
            svc.generate([3, 4], max_new_tokens=2, token=tokens["vic"],
                         timeout_s=60)
            svc.generate([5, 6], max_new_tokens=2, token=tokens["agg"],
                         timeout_s=60)
            # a tenant sees ITS OWN counters, nothing else
            mine = svc.stats(token=tokens["vic"])
            assert mine["tenant"] == "vic"
            assert mine["requests_finished"] == 1
            assert "tenants" not in mine and "slots" not in mine
            # the operator sees the engine plus every tenant's row
            ops = svc.stats(token=tokens["ops"])
            assert ops["slots"] == 2
            assert set(ops["tenants"]) == {"vic", "agg"}
        finally:
            svc.close()

    def test_gateway_stats_and_fleet_stats_scoping(self, tiny_model, iam):
        from lzy_tpu.iam import AuthError

        iam, tokens = iam
        cfg, params = tiny_model
        fleet = ReplicaFleet(lambda: PagedInferenceEngine(cfg, params, slots=2))
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny", iam=iam)
        try:
            fleet.add_replica()
            gw.generate([3, 4], max_new_tokens=2, token=tokens["vic"],
                        timeout_s=60)
            mine = gw.stats(token=tokens["vic"])
            assert mine["tenant"] == "vic"
            assert mine["requests_finished"] == 1
            assert "replicas" not in mine
            ops = gw.stats(token=tokens["ops"])
            assert ops["replicas"] == 1
            assert ops["tenants"]["vic"]["requests_finished"] == 1
            with pytest.raises(AuthError, match="operator-only"):
                gw.fleet_stats(token=tokens["vic"])
            assert gw.fleet_stats(token=tokens["ops"])["replicas"]
        finally:
            gw.close()

    def test_token_rotation_mid_stream(self, tiny_model, iam):
        from lzy_tpu.iam import AuthError

        iam, tokens = iam
        svc = self._service(tiny_model, iam)
        try:
            results = {}

            def run():
                results["res"] = svc.generate(
                    [7, 8], max_new_tokens=48, token=tokens["vic"],
                    timeout_s=60)

            t = threading.Thread(target=run)
            t.start()
            # rotate the subject while (most likely) mid-decode: the
            # IN-FLIGHT stream finishes — auth happens at admission —
            # but the stale token admits nothing new
            iam.rotate_subject("vic")
            with pytest.raises(AuthError, match="revoked"):
                svc.generate([9], max_new_tokens=2, token=tokens["vic"],
                             timeout_s=60)
            t.join(timeout=60)
            assert results["res"]["status"] == "ok"
            assert len(results["res"]["tokens"]) == 48
            # a re-issued token works again
            fresh = iam.issue_token("vic")
            assert svc.generate([9], max_new_tokens=2, token=fresh,
                                timeout_s=60)["status"] == "ok"
        finally:
            svc.close()

    def test_unauthenticated_rejection_on_every_new_field(
            self, tiny_model, iam):
        """Every new RPC field rides InferGenerate/InferStats, which
        refuse before reading them: no token, bad token, and legacy
        formats are all rejected regardless of tenant/priority args."""
        from lzy_tpu.iam import AuthError

        iam, tokens = iam
        svc = self._service(tiny_model, iam)
        try:
            for bad in (None, "garbage", "a:b:c", tokens["vic"] + "x"):
                with pytest.raises(AuthError):
                    svc.generate([1, 2], max_new_tokens=2, token=bad,
                                 tenant="vic", priority=0, timeout_s=5)
                with pytest.raises(AuthError):
                    svc.stats(token=bad)
        finally:
            svc.close()

    def test_wire_schema_validates_new_fields(self):
        from lzy_tpu.rpc.schema import REQUESTS, SchemaError

        schema = REQUESTS["InferGenerate"]
        schema.validate({"prompt": [1], "tenant": "t", "priority": 1})
        with pytest.raises(SchemaError):
            schema.validate({"prompt": [1], "tenant": 7})
        with pytest.raises(SchemaError):
            schema.validate({"prompt": [1], "priority": "high"})

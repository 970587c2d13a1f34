"""Chaos harness: seeded fault injection, invariant auditors, degradation.

Acceptance criterion (ISSUE 6): a multi-seed soak runs mixed
greedy+sampled traffic through a disaggregated gateway with faults armed
at every registered serving point; every invariant auditor stays clean
and greedy output is bit-identical to the uninterrupted ``generate()``
oracle. Any failing seed replays deterministically: the failure message
prints the seed and the fired schedule
(``LZY_CHAOS_SEED=<seed> pytest tests/test_chaos.py -k soak``).

Unit layers underneath: fault-plan determinism, the unified backoff
policy, the circuit breaker (flapping replicas stop being routed before
the streak verdict fires), load shedding with retry-after, graceful
drain, the invariant auditors themselves, and remaining-deadline
threading across failover and disagg staging.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.chaos import (
    CHAOS, FaultPlan, FenceAuditor, InvariantViolation, audit_engine,
    audit_fleet_leases, audit_kv_counts, audit_pool, audit_radix)
from lzy_tpu.chaos.faults import CRASH, DELAY, ERROR, FaultPoint, SLOW
from lzy_tpu.gateway import (
    Autoscaler, DisaggGatewayService, GatewayService, HealthPolicy,
    HealthTracker, PrefixAffinityRouter, ReplicaFleet)
from lzy_tpu.gateway.health import BreakerPolicy, CircuitBreaker
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.rpc.core import Unavailable
from lzy_tpu.serving import (
    AdmissionError, DecodeEngine, PagedInferenceEngine,
    PrefillEngine, QuotaExceeded, RadixCache, SloLimiter, TenantPolicy,
    TenantTable)
from lzy_tpu.serving.scheduler import RequestQueue
from lzy_tpu.utils.backoff import RetryPolicy

pytestmark = pytest.mark.chaos

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with no plan armed, whatever broke."""
    CHAOS.disarm()
    yield
    CHAOS.disarm()


def _oracle_tokens(cfg, params, prompt_ids, n):
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


# ---------------------------------------------------------------------------
# fault plan


class TestFaultPlan:
    def _decisions(self, seed, n=64, **kw):
        plan = FaultPlan(seed, **kw)
        point = FaultPoint("x", crash_ok=True,
                           modes=(ERROR, DELAY, SLOW, CRASH))
        return [plan.decide(point) for _ in range(n)]

    def test_same_seed_same_schedule(self):
        a = self._decisions(7, rate=0.3)
        b = self._decisions(7, rate=0.3)
        assert a == b
        assert any(d is not None for d in a)

    def test_seeds_diverge(self):
        assert self._decisions(1, rate=0.3) != self._decisions(2, rate=0.3)

    def test_per_point_streams_are_independent(self):
        """A point's decision stream depends only on (seed, its own hit
        count) — interleaving hits of OTHER points must not perturb it
        (the replayability argument)."""
        p1 = FaultPoint("one")
        p2 = FaultPoint("two")
        solo = FaultPlan(5, rate=0.5)
        solo_stream = [solo.decide(p1) for _ in range(32)]
        mixed = FaultPlan(5, rate=0.5)
        mixed_stream = []
        for i in range(32):
            mixed.decide(p2)            # interleaved traffic on point two
            mixed_stream.append(mixed.decide(p1))
        assert mixed_stream == solo_stream

    def test_max_faults_bounds_each_point(self):
        plan = FaultPlan(3, rate=1.0, modes=(ERROR,), max_faults=4)
        point = FaultPoint("x")
        fired = [plan.decide(point) for _ in range(32)]
        assert sum(d is not None for d in fired) == 4
        assert plan.fired == 4 and len(plan.schedule) == 4
        # the cap is PER POINT (a global budget would let thread
        # interleaving across points decide who gets the last slot,
        # breaking seed replay): a second point still fires
        assert plan.decide(FaultPoint("y")) is not None

    def test_disallowed_mode_never_fires(self):
        # crash on a point without crash_ok is silently withheld
        plan = FaultPlan(3, rate=1.0, modes=(CRASH,))
        assert all(plan.decide(FaultPoint("x")) is None for _ in range(16))

    def test_point_allowlist(self):
        plan = FaultPlan(3, rate=1.0, modes=(ERROR,), points=("a",))
        assert plan.decide(FaultPoint("b")) is None
        assert plan.decide(FaultPoint("a")) is not None

    def test_arm_rejects_unknown_points_and_double_arm(self):
        with pytest.raises(KeyError):
            CHAOS.arm(FaultPlan(1, points=("no.such.point",)))
        CHAOS.arm(FaultPlan(1, points=("engine.admit",)))
        try:
            with pytest.raises(RuntimeError):
                CHAOS.arm(FaultPlan(2))
        finally:
            CHAOS.disarm()

    def test_error_mode_raises_the_registered_type(self):
        """The admission boundary degrades via AdmissionError — the
        injected fault must be that exact type, or the degradation path
        under test would not be the production one."""
        CHAOS.arm(FaultPlan(1, rate=1.0, modes=(ERROR,),
                            points=("engine.admit",)))
        q = RequestQueue(max_depth=4)
        from lzy_tpu.serving.scheduler import Request

        with pytest.raises(AdmissionError, match="injected fault"):
            q.submit(Request([1], 1))
        CHAOS.disarm()
        q.submit(Request([1], 1))       # disarmed: admission works

    def test_describe_names_seed_and_fired_schedule(self):
        plan = FaultPlan(42, rate=1.0, modes=(ERROR,))
        plan.decide(FaultPoint("x"))
        text = plan.describe()
        assert "seed=42" in text and "x hit=1 -> error" in text


# ---------------------------------------------------------------------------
# unified backoff policy


class TestRetryPolicy:
    def test_attempt_count_and_terminal_error(self):
        calls = []

        def boom():
            calls.append(1)
            raise IOError("nope")

        with pytest.raises(IOError):
            RetryPolicy(attempts=3, base_s=0.0).call(boom)
        assert len(calls) == 3

    def test_retry_if_gates_retries(self):
        calls = []

        def boom():
            calls.append(1)
            raise ValueError("fatal")

        with pytest.raises(ValueError):
            RetryPolicy(attempts=5, base_s=0.0).call(
                boom, retry_if=lambda e: isinstance(e, IOError))
        assert len(calls) == 1

    def test_full_jitter_bounds_and_determinism(self):
        import random

        policy = RetryPolicy(attempts=8, base_s=0.5, cap_s=2.0)
        a = [policy.delay_s(k, random.Random(9)) for k in range(1, 8)]
        b = [policy.delay_s(k, random.Random(9)) for k in range(1, 8)]
        assert a == b                       # injected rng => deterministic
        for k, d in enumerate(a, start=1):
            assert 0.0 <= d <= min(2.0, 0.5 * 2 ** (k - 1))

    def test_unjittered_doubles_to_cap(self):
        policy = RetryPolicy(attempts=8, base_s=0.5, cap_s=2.0,
                             jitter=False)
        assert [policy.delay_s(k) for k in (1, 2, 3, 4)] == \
            [0.5, 1.0, 2.0, 2.0]

    def test_transfer_config_preserves_per_part_retry_counts(self):
        from lzy_tpu.storage.transfer import TransferConfig

        cfg = TransferConfig(retries=3, backoff_s=0.01)
        assert cfg.retry_policy.attempts == 3
        assert cfg.retry_policy.base_s == 0.01

    def test_success_after_failures_returns_value(self):
        state = {"n": 0}

        def flaky():
            state["n"] += 1
            if state["n"] < 3:
                raise IOError("blip")
            return "ok"

        assert RetryPolicy(attempts=4, base_s=0.0).call(flaky) == "ok"


# ---------------------------------------------------------------------------
# circuit breaker


class TestCircuitBreaker:
    def test_flapping_opens_before_the_streak_verdict(self):
        """fail/success alternation never builds a 3-streak (the health
        verdict stays None) but crosses the windowed threshold — the
        breaker must stop routing while the verdict keeps the lease."""
        tracker = HealthTracker(
            HealthPolicy(max_consecutive_failures=3),
            breaker=BreakerPolicy(failure_threshold=3, window_s=10.0,
                                  open_s=5.0))
        t = 0.0
        for i in range(3):
            tracker.breaker.record_failure("r", now=t + i)
            if i < 2:
                tracker.record_success("r")
        assert tracker.verdict("r") is None      # streak never accrued
        assert not tracker.routable("r", now=t + 3)

    def test_half_open_probe_closes_or_reopens(self):
        br = CircuitBreaker(BreakerPolicy(failure_threshold=2,
                                          window_s=10.0, open_s=5.0))
        br.record_failure("r", now=0.0)
        br.record_failure("r", now=1.0)
        assert not br.routable("r", now=2.0)
        assert br.retry_after_s("r", now=2.0) == pytest.approx(4.0)
        # past open_s: half-open lets EXACTLY ONE dispatched probe
        # through — a burst must not pile onto a possibly-still-broken
        # replica. routable() (the listing gate) never claims; only
        # try_route() (the dispatch gate) does.
        assert br.routable("r", now=6.4)         # listable...
        assert br.routable("r", now=6.45)        # ...without consuming
        assert br.try_route("r", now=6.5)        # dispatch claims it
        assert not br.try_route("r", now=6.55)   # probe already claimed
        assert not br.routable("r", now=6.55)    # claim visible to lists
        br.record_failure("r", now=6.6)          # probe failed: re-open
        assert not br.try_route("r", now=7.0)
        assert br.try_route("r", now=12.0)       # half-open again
        br.record_success("r")                   # probe succeeded
        assert br.routable("r", now=12.1)
        assert br.try_route("r", now=12.1)       # closed: no claiming
        assert br.try_route("r", now=12.15)
        assert br.state("r", now=12.15) == "closed"

    def test_release_probe_unblocks_an_undispatched_claim(self):
        """A try_route claim whose request is then refused admission
        must be released, or the recovered replica sits probe-blocked
        for another open_s with no probe in flight."""
        br = CircuitBreaker(BreakerPolicy(failure_threshold=1,
                                          window_s=10.0, open_s=5.0))
        br.record_failure("r", now=0.0)
        assert br.try_route("r", now=6.0)        # half-open: claims
        assert not br.try_route("r", now=6.1)
        br.release_probe("r")                    # dispatch refused
        assert br.try_route("r", now=6.2)        # next caller re-probes

    def test_open_breaker_withholds_replica_from_routing(self):
        class _FakeEngine:
            closed = False

            def stats(self):
                from lzy_tpu.serving.engine import EngineStats

                return EngineStats(slots=1, busy=0, queue_depth=0,
                                   requests_finished=0, tokens_generated=0)

            def close(self):
                pass

        tracker = HealthTracker(
            breaker=BreakerPolicy(failure_threshold=2, window_s=30.0,
                                  open_s=60.0))
        fleet = ReplicaFleet(_FakeEngine, start_engines=False,
                             health=tracker)
        a = fleet.add_replica()
        b = fleet.add_replica()
        assert set(fleet.loads()) == {a.id, b.id}
        tracker.record_failure(a.id)
        tracker.record_failure(a.id)
        assert set(fleet.loads()) == {b.id}      # open breaker: withheld
        assert fleet.breaker_retry_after_s() is not None
        tracker.forget(a.id)
        assert set(fleet.loads()) == {a.id, b.id}


# ---------------------------------------------------------------------------
# load shedding


class TestLoadShedding:
    def test_full_queue_sheds_with_retry_after(self):
        from lzy_tpu.serving.scheduler import Request

        q = RequestQueue(max_depth=1)
        q.submit(Request([1], 1))
        with pytest.raises(AdmissionError) as err:
            q.submit(Request([2], 1))
        assert err.value.retry_after_s is not None
        assert 0.05 <= err.value.retry_after_s <= 10.0

    def test_gateway_shed_counts_and_hints(self, tiny_model):
        cfg, params = tiny_model
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=1, max_queue=1),
            start_engines=False)
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny")
        try:
            replica = fleet.add_replica()
            # fill slot-less queue: engine not stepping, so both park
            replica.engine.submit([1, 2], max_new_tokens=2)
            with pytest.raises(Unavailable) as err:
                gw.generate([3, 4], max_new_tokens=2)
            assert getattr(err.value, "retry_after_s", None) is not None
            assert "retry_after_s" in str(err.value)
            assert gw.stats()["requests_shed"] == 1
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# graceful drain


class TestGracefulDrain:
    def test_engine_drain_finishes_inflight_then_refuses(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2).start()
        req = eng.submit([5, 9, 3], max_new_tokens=6)
        assert eng.drain(timeout_s=60.0)
        assert req.done and req.error is None
        assert req.tokens == _oracle_tokens(cfg, params, [5, 9, 3], 6)
        assert eng.closed
        with pytest.raises(AdmissionError):
            eng.submit([1, 2], max_new_tokens=2)

    def test_gateway_drain_completes_inflight_and_closes_fleet(
            self, tiny_model):
        cfg, params = tiny_model
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=2,
                                         page_size=PAGE))
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny")
        fleet.add_replica()
        result = {}

        def run():
            try:
                result["res"] = gw.generate([7, 2, 8], max_new_tokens=12,
                                            timeout_s=120)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                result["err"] = e

        t = threading.Thread(target=run)
        t.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and gw._inflight == 0:
            time.sleep(0.002)
        assert gw.drain(timeout_s=60.0)
        t.join(60)
        assert "err" not in result, result.get("err")
        assert result["res"]["tokens"] == _oracle_tokens(
            cfg, params, [7, 2, 8], 12)
        # fleet retired, engines closed, new calls shed as draining
        assert fleet.replicas() == []
        with pytest.raises(Unavailable, match="draining"):
            gw.generate([1, 2], max_new_tokens=2)


# ---------------------------------------------------------------------------
# invariant auditors


class TestInvariants:
    def test_healthy_paged_engine_audits_clean(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        reqs = [eng.submit(list(range(10 + i)), max_new_tokens=6)
                for i in range(3)]
        for _ in range(200):
            if all(r.done for r in reqs):
                break
            eng.step()
            audit_engine(eng)       # clean after EVERY scheduling round
        assert all(r.done for r in reqs)
        audit_engine(eng)

    def test_auditor_catches_a_staged_jobs_lost_block(self, tiny_model):
        """The jobs are read through ``engine.prefill.jobs``: a block given
        back behind a staged job is the job walk's to catch (the pool
        itself is consistent), and an engine without the object fails the
        audit loudly, not silently."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   prefill_budget=8)
        eng.submit(list(range(1, 3 * PAGE)), max_new_tokens=2)
        eng.step()
        job, = eng.prefill.jobs
        audit_engine(eng)
        eng.kv.release(job.table[-1:])
        with pytest.raises(InvariantViolation, match="prefill job"):
            audit_engine(eng)
        job.table.pop()
        audit_engine(eng)
        prefill = eng.__dict__.pop("prefill")
        with pytest.raises(AttributeError, match="prefill"):
            audit_engine(eng)
        eng.prefill = prefill
        eng.close()

    def test_auditor_catches_a_leaked_block(self):
        rc = RadixCache(8, PAGE)
        blocks = rc.allocate(2)
        audit_pool(rc)
        rc.pool._ref[blocks[0]] = 0      # drop the ref without freeing
        with pytest.raises(InvariantViolation, match="leaked"):
            audit_pool(rc)

    def test_auditor_catches_free_list_double_ownership(self):
        rc = RadixCache(8, PAGE)
        block = rc.allocate(1)[0]
        rc.pool._free.append(block)      # freed while still referenced
        with pytest.raises(InvariantViolation, match="free list"):
            audit_pool(rc)

    def test_auditor_catches_a_broken_tree_link(self):
        rc = RadixCache(8, PAGE)
        blocks = rc.allocate(2)
        tokens = list(range(2 * PAGE))
        rc.insert(tokens, blocks)
        rc.release(blocks)
        audit_radix(rc)
        node = rc._node_of[blocks[1]]
        node.parent = rc._root           # detach from its true parent
        with pytest.raises(InvariantViolation, match="parent link"):
            audit_radix(rc)

    @pytest.mark.parametrize("drift, named", [
        (lambda rc, node: setattr(rc, "_cached", rc._cached + 1),
         "kept cached count 3 != 2"),
        (lambda rc, node: setattr(rc, "_evictable", rc._evictable - 1),
         "kept evictable count 1 != 2"),
        (lambda rc, node: setattr(node, "busy", 1), "kept busy count"),
        (lambda rc, node: rc._lru.clear(), "eviction order has no entry"),
    ], ids=["cached", "evictable", "busy", "lru"])
    def test_auditor_catches_a_drifted_kept_count(self, drift, named):
        rc = RadixCache(8, PAGE)
        blocks = rc.allocate(2)
        rc.insert(list(range(2 * PAGE)), blocks)
        rc.release(blocks)
        audit_kv_counts(rc)
        drift(rc, rc._node_of[blocks[1]])
        with pytest.raises(InvariantViolation, match=named):
            audit_kv_counts(rc)

    def test_fence_auditor_rejects_a_shrunk_fence(self):
        session = FenceAuditor().session([1, 2, 3])
        session.on_failover([5, 6], [1, 2, 3, 5, 6])
        with pytest.raises(InvariantViolation, match="shrank"):
            session.on_failover([5], [1, 2, 3, 5])

    def test_fence_auditor_rejects_a_wrong_retry_prompt(self):
        session = FenceAuditor().session([1, 2, 3])
        with pytest.raises(InvariantViolation, match="retry prompt"):
            session.on_failover([5, 6], [1, 2, 3, 5])

    def test_fence_auditor_accepts_a_clean_stream(self):
        fa = FenceAuditor()
        session = fa.session([1, 2, 3])
        session.on_failover([5, 6], [1, 2, 3, 5, 6])
        session.on_complete([5, 6, 7, 8])
        assert fa.failovers_seen == 1 and fa.completions_seen == 1

    def test_fleet_lease_audit_catches_double_lease(self, tiny_model):
        cfg, params = tiny_model
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=1),
            start_engines=False)
        a = fleet.add_replica()
        b = fleet.add_replica()
        audit_fleet_leases(fleet)
        a.vm_ids.append("vm-x")
        b.vm_ids.append("vm-x")
        with pytest.raises(InvariantViolation, match="leased to both"):
            audit_fleet_leases(fleet)


# ---------------------------------------------------------------------------
# remaining-deadline threading (satellite: failover + disagg staging)


class TestDeadlineAcrossFailover:
    def test_failover_resubmits_with_remaining_deadline(self, tiny_model):
        """The retry after a mid-stream death must carry the REMAINING
        client deadline (anchored at first submission), not a reset
        ``deadline_s``."""
        cfg, params = tiny_model
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=2))
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny")
        seen = []
        try:
            for _ in range(2):
                replica = fleet.add_replica()
                orig = replica.engine.submit

                def spy(prompt, *, _orig=orig, **kw):
                    seen.append(kw.get("deadline_s"))
                    return _orig(prompt, **kw)

                replica.engine.submit = spy
            result = {}

            def run():
                try:
                    result["res"] = gw.generate(
                        [7, 2, 8, 1], max_new_tokens=24,
                        timeout_s=120, deadline_s=300.0)
                except BaseException as e:  # noqa: BLE001
                    result["err"] = e

            t = threading.Thread(target=run)
            t.start()
            victim = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and victim is None:
                for replica in fleet.replicas():
                    live = [r for r in replica.engine._active
                            if r is not None]
                    if live and len(live[0].tokens) >= 3:
                        victim = replica
                        break
                time.sleep(0.005)
            assert victim is not None, "request never reached mid-decode"

            def boom():
                raise RuntimeError("replica host on fire")

            victim.engine.step = boom
            t.join(120)
            assert "err" not in result, result.get("err")
            assert result["res"]["failovers"] == 1
            assert len(seen) == 2
            assert seen[0] is not None and seen[0] <= 300.0
            # the retry carried strictly less than the first submission:
            # time elapsed mid-stream came off the same anchored budget
            assert seen[1] < seen[0]
        finally:
            gw.close()

    def test_disagg_staging_carries_the_deadline_to_the_prefill_pool(
            self, tiny_model):
        cfg, params = tiny_model
        decode_fleet = ReplicaFleet(
            lambda: DecodeEngine(cfg, params, slots=2, page_size=PAGE),
            replica_prefix="decode")
        prefill_fleet = ReplicaFleet(
            lambda: PrefillEngine(cfg, params, slots=2, page_size=PAGE),
            replica_prefix="prefill")
        gw = DisaggGatewayService(
            decode_fleet, prefill_fleet, page_size=PAGE,
            router=PrefixAffinityRouter(PAGE),
            prefill_router=PrefixAffinityRouter(PAGE), model_name="tiny")
        seen = []
        try:
            decode_fleet.add_replica()
            pf = prefill_fleet.add_replica()
            orig = pf.engine.submit

            def spy(prompt, **kw):
                seen.append(kw.get("deadline_s"))
                return orig(prompt, **kw)

            pf.engine.submit = spy
            prompt = list(range(2 * PAGE)) + [40]
            res = gw.generate(prompt, max_new_tokens=4, timeout_s=120,
                              deadline_s=600.0)
            assert res["status"] == "ok"
            assert res["prefilled_by"] == pf.id
            assert len(seen) == 1
            assert seen[0] is not None and 0 < seen[0] <= 600.0
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# autoscaler stability (satellite)


class TestAutoscalerStability:
    def test_flapping_pressure_around_threshold_never_scales(self):
        """Queue depth oscillating across the threshold every second can
        never satisfy the sustain window — zero decisions, zero lease
        churn."""
        scaler = Autoscaler(min_replicas=1, max_replicas=4,
                            up_queue_per_replica=4.0, up_sustain_s=2.0,
                            down_busy_fraction=0.25, down_sustain_s=5.0,
                            cooldown_s=10.0)
        decisions = []
        for i in range(60):
            queue = 8 if i % 2 == 0 else 0
            d = scaler.tick(float(i), replicas=1, queue_depth=queue,
                            busy=1, slots=2)
            if d is not None:
                decisions.append((i, d))
        assert decisions == []

    def test_cooldown_bounds_scale_rate_under_sustained_flap(self):
        """Even pressure sustained long enough to fire repeatedly is
        paced by the shared cooldown: decisions are spaced >= cooldown_s,
        bounding lease/drain churn."""
        scaler = Autoscaler(min_replicas=1, max_replicas=8,
                            up_queue_per_replica=2.0, up_sustain_s=1.0,
                            down_busy_fraction=0.25, down_sustain_s=1.0,
                            cooldown_s=10.0)
        fired = []
        replicas = 1
        for t in range(0, 60):
            d = scaler.tick(float(t), replicas=replicas,
                            queue_depth=50, busy=replicas,
                            slots=replicas)
            if d is not None:
                fired.append(t)
                replicas += 1
        assert len(fired) >= 2
        gaps = [b - a for a, b in zip(fired, fired[1:])]
        assert all(g >= 10 for g in gaps)

    def test_drain_waits_for_inflight_decode_to_retire(self, tiny_model):
        """A DRAINING replica with a slot mid-decode must not be reaped
        until the slot retires — in-flight work finishes on the warm
        engine, never gets dumped."""
        cfg, params = tiny_model
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=2))
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny")
        try:
            replica = fleet.add_replica()
            req = replica.engine.submit([5, 9, 3], max_new_tokens=40)
            fleet.drain(replica.id)
            assert fleet.reap_drained() == []    # busy: must wait
            assert replica.id in [r.id for r in
                                  fleet.replicas(state="DRAINING")]
            assert req.result(timeout=120) == _oracle_tokens(
                cfg, params, [5, 9, 3], 40)
            deadline = time.monotonic() + 30
            reaped = []
            while time.monotonic() < deadline and not reaped:
                reaped = fleet.reap_drained()
                time.sleep(0.01)
            assert reaped == [replica.id]
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# the chaos soak: disagg gateway + faults at every registered point


def _build_disagg(cfg, params, *, decode=2, prefill=1, tenants=None,
                  prefill_budget=None):
    # a small host tier on every engine puts the kvtier.demote /
    # kvtier.import fault points in play for the soak: evictions demote,
    # admissions attempt promotion, and an injected failure at either
    # must degrade to classic eviction / local re-prefill with greedy
    # output still bit-identical to the oracle
    kw = dict(slots=2, page_size=PAGE, temperature=0.7,
              tenants=tenants, prefill_budget=prefill_budget,
              kv_host_tier_bytes=1 << 20)
    decode_fleet = ReplicaFleet(
        lambda: DecodeEngine(cfg, params, **kw),
        replica_prefix="decode")
    prefill_fleet = ReplicaFleet(
        lambda: PrefillEngine(cfg, params, **kw),
        replica_prefix="prefill")
    scaler = Autoscaler(min_replicas=decode, max_replicas=decode + 1,
                        up_sustain_s=3600.0, down_sustain_s=3600.0,
                        cooldown_s=0.1)
    slo = SloLimiter(tenants) if tenants is not None else None
    gw = DisaggGatewayService(
        decode_fleet, prefill_fleet, page_size=PAGE,
        router=PrefixAffinityRouter(PAGE),
        prefill_router=PrefixAffinityRouter(PAGE),
        autoscaler=scaler, prefill_replicas=prefill, model_name="tiny",
        slo=slo)
    for _ in range(decode):
        decode_fleet.add_replica()
    for _ in range(prefill):
        prefill_fleet.add_replica()
    return gw, decode_fleet, prefill_fleet


def _audit_all(gw, decode_fleet, prefill_fleet):
    for fleet in (decode_fleet, prefill_fleet):
        audit_fleet_leases(fleet)
        for replica in fleet.replicas():
            audit_engine(replica.engine)


def _chaos_round(tiny_model, seed, *, n_requests, max_faults,
                 tenants=False):
    """One seeded soak: mixed greedy+sampled traffic with faults armed
    at EVERY registered point; auditors after every request; greedy
    bit-identical to the uninterrupted oracle. With ``tenants`` the
    traffic is two-tenant with heavy-tailed prompt lengths (an aggressor
    dragging 10+-block prompts next to a short-prompt victim) through
    the SLO layer — rate limits, WFQ, KV quotas, chunked prefill — and
    the same auditors/oracle must hold."""
    cfg, params = tiny_model
    header = list(range(2 * PAGE))          # shared whole-block prefix
    table = None
    if tenants:
        table = TenantTable(default=TenantPolicy(
            requests_per_s=200.0, prompt_tokens_per_s=20000.0,
            burst_s=1.0, kv_block_quota=24, max_queued=8))
        table.set_policy(TenantPolicy(
            tenant="agg", priority=2, requests_per_s=100.0,
            prompt_tokens_per_s=8000.0, burst_s=1.0, kv_block_quota=20,
            max_queued=6))
        table.set_policy(TenantPolicy(tenant="vic", priority=0))
    gw, decode_fleet, prefill_fleet = _build_disagg(
        cfg, params, tenants=table,
        prefill_budget=2 * PAGE if tenants else None)
    gw.fence_auditor = FenceAuditor()
    plan = CHAOS.arm(FaultPlan(
        seed, rate=0.08, modes=(ERROR, DELAY, CRASH),
        max_faults=max_faults))      # per-point cap (seed-replayable)
    try:
        for i in range(n_requests):
            greedy = i % 2 == 0
            tenant = None
            if tenants:
                tenant = "agg" if i % 3 == 0 else "vic"
            if tenants and tenant == "agg" and i % 6 == 0:
                # the heavy tail: a 10-block prompt through chunked
                # prefill while the victim's short prompts interleave
                prompt = header + [(i * 5 + j) % 50 + 1
                                   for j in range(10 * PAGE)]
            else:
                prompt = header + [40 + (i * 7) % 20, 30 + i]
            n = 10 + (i % 3)
            res = None
            for _ in range(30):         # shed/Unavailable => client retry
                try:
                    res = gw.generate(prompt, max_new_tokens=n,
                                      timeout_s=120, greedy=greedy,
                                      tenant=tenant)
                    break
                except QuotaExceeded as e:
                    # tenant-scoped shed: back off on ITS hint
                    time.sleep(min(e.retry_after_s or 0.02, 0.05))
                except Unavailable:
                    gw.tick()           # re-lease toward the floor
                    time.sleep(0.02)
            assert res is not None, f"request {i} shed forever"
            assert res["status"] == "ok", res
            if greedy:
                assert res["tokens"] == _oracle_tokens(
                    cfg, params, prompt, n), f"request {i} diverged"
            else:
                assert len(res["tokens"]) == n
            gw.tick()
            _audit_all(gw, decode_fleet, prefill_fleet)
        # the quiet tail: with the plan exhausted, the fleet must be
        # fully recovered and still bit-exact
        CHAOS.disarm()
        final = gw.generate(header + [63], max_new_tokens=8,
                            timeout_s=120, greedy=True)
        assert final["tokens"] == _oracle_tokens(
            cfg, params, header + [63], 8)
        _audit_all(gw, decode_fleet, prefill_fleet)
        assert gw.fence_auditor.completions_seen >= n_requests
    except AssertionError as e:
        pytest.fail(
            f"chaos seed {seed} failed: {e}\n--- replay ---\n"
            f"LZY_CHAOS_SEED={seed} pytest tests/test_chaos.py -k soak\n"
            f"{plan.describe()}")
    finally:
        CHAOS.disarm()
        gw.close()
    return plan


class TestChaosSmoke:
    def test_fixed_seed_smoke(self, tiny_model):
        """Tier-1: one fixed seed, faults armed at every registered
        point, auditors clean, greedy bit-identical to the oracle."""
        plan = _chaos_round(tiny_model, seed=20260803, n_requests=6,
                            max_faults=1)
        # the smoke must actually have injected something, or it proves
        # nothing; the fixed seed makes this stable
        assert plan.fired > 0, plan.describe()

    def test_fixed_seed_multi_tenant_smoke(self, tiny_model):
        """Tier-1 twin with the SLO layer armed: two tenants,
        heavy-tailed prompts, faults at every point INCLUDING the new
        slo.admit admission boundary — auditors clean, greedy
        bit-identical."""
        plan = _chaos_round(tiny_model, seed=20260804, n_requests=6,
                            max_faults=1, tenants=True)
        assert plan.fired > 0, plan.describe()


@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("LZY_SLOW"),
                    reason="multi-seed chaos soak: set LZY_SLOW=1")
class TestChaosSoak:
    def test_multi_seed_soak(self, tiny_model):
        from tests.conftest import record_tier_run

        env_seed = os.environ.get("LZY_CHAOS_SEED")
        seeds = ([int(env_seed)] if env_seed
                 else [11, 23, 37, 41, 53])
        total = 0
        for seed in seeds:
            plan = _chaos_round(tiny_model, seed, n_requests=10,
                                max_faults=2)
            total += plan.fired
        assert total > 0
        record_tier_run("chaos_soak",
                        f"seeds={seeds} faults_fired={total}")

    def test_multi_tenant_soak(self, tiny_model):
        """The ISSUE-7 soak: two tenants (long-prompt aggressor,
        short-prompt victim) with the SLO layer on — rate limits, WFQ,
        KV quotas, chunked prefill — faults armed at every point, fence
        and pool auditors after every request, greedy bit-identical."""
        from tests.conftest import record_tier_run

        env_seed = os.environ.get("LZY_CHAOS_SEED")
        seeds = [int(env_seed)] if env_seed else [7, 19, 31]
        total = 0
        for seed in seeds:
            plan = _chaos_round(tiny_model, seed, n_requests=12,
                                max_faults=2, tenants=True)
            total += plan.fired
        assert total > 0
        record_tier_run("chaos_soak_multi_tenant",
                        f"seeds={seeds} faults_fired={total}")

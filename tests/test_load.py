"""Million-user load plane (lzy_tpu/load): trace determinism, the
virtual-clock capacity smoke, and overload robustness.

THE acceptance smoke (ISSUE 13): replay over one simulated hour of
multi-tenant traffic (>= 20k requests) against a fleet-in-threads
gateway in < 60 s wall on CPU, deterministically per seed, and emit a
non-degenerate SLO-curve artifact — TTFT/inter-token p99 vs replica
count plus a shed-rate frontier.  The robustness payload: shed-honoring
clients succeed (backoff on ``retry_after_s``), a hammering client gets
pushback instead of service, queue memory stays bounded, and the
autoscaler absorbs bursts without flapping.
"""

import dataclasses
import hashlib
import os

import pytest

from lzy_tpu.load import (
    Collector, FleetConfig, LoadDriver, SimProfile, TraceConfig,
    build_fleet, capacity_artifact, generate_trace, replay, trace_bytes)
from lzy_tpu.utils.clock import VirtualClock

pytestmark = pytest.mark.load


class TestTraceDeterminism:
    def test_same_seed_byte_identical(self):
        cfg = TraceConfig(seed=11, duration_s=300.0, users=8, tenants=4)
        a, b = trace_bytes(cfg), trace_bytes(cfg)
        assert a == b
        assert hashlib.sha256(a).hexdigest() == \
            hashlib.sha256(trace_bytes(cfg)).hexdigest()

    def test_different_seed_differs(self):
        cfg = TraceConfig(seed=11, duration_s=300.0, users=8, tenants=4)
        assert trace_bytes(cfg) != trace_bytes(
            dataclasses.replace(cfg, seed=12))

    def test_workload_shape(self):
        """Heavy-tailed tenants, conversation revisits, bursty think
        times — the knobs actually move the generated trace."""
        cfg = TraceConfig(seed=3, duration_s=1200.0, users=24, tenants=6)
        users = generate_trace(cfg)
        assert len(users) == 24
        turns = [t for turns in users for t in turns]
        assert len(turns) > 500
        tenants = {t.tenant for t in turns}
        assert len(tenants) >= 3
        # heavy tail: the most popular tenant dominates the least
        counts = sorted((sum(1 for t in turns if t.tenant == ten)
                         for ten in tenants), reverse=True)
        assert counts[0] >= 3 * counts[-1]
        # sessions revisit: some session appears in >1 burst of turns
        assert any(not t.fresh for t in turns)


class TestReplayDeterminism:
    def test_identical_capacity_metrics_across_two_runs(self):
        cfg = TraceConfig(seed=5, duration_s=180.0, users=10, tenants=4)
        fc = FleetConfig(replicas=2, profile=SimProfile(
            slots=4, max_queue=32, kv_blocks=256))
        r1 = replay(cfg, fc)
        r2 = replay(cfg, fc)
        assert r1.requests > 100
        assert r1.metrics() == r2.metrics()

    def test_seed_changes_metrics(self):
        fc = FleetConfig(replicas=2)
        r1 = replay(TraceConfig(seed=1, duration_s=120.0, users=6), fc)
        r2 = replay(TraceConfig(seed=2, duration_s=120.0, users=6), fc)
        assert r1.metrics() != r2.metrics()


class TestCapacitySmoke:
    """The acceptance smoke: >= 1 simulated hour, >= 20k requests, more
    than ten times faster than the clock it simulates, non-degenerate
    operating curves."""

    def test_one_hour_twenty_k_requests(self):
        trace = TraceConfig(seed=6, duration_s=560.0, users=36,
                            tenants=8)
        fleet = FleetConfig(replicas=2, profile=SimProfile(
            slots=8, max_queue=48, kv_blocks=384))
        frontier_fleet = FleetConfig(replicas=1, retry_limit=3,
                                     profile=SimProfile(
                                         slots=4, max_queue=16,
                                         kv_blocks=160))
        artifact = capacity_artifact(
            trace, fleet, replica_counts=[1, 2, 4],
            load_factors=[1.0, 5.0],
            frontier_fleet_cfg=frontier_fleet)
        slo, frontier = artifact["slo_curve"], artifact["shed_frontier"]
        requests = (sum(r["requests"] for r in slo)
                    + sum(r["requests"] for r in frontier))
        # scale: >= 1 simulated hour and >= 20k requests, and the wall
        # time as a ratio (a loaded box moves seconds, not the ratio's
        # order of magnitude)
        assert artifact["replay"]["virtual_s"] >= 3600.0
        assert requests >= 20_000, requests
        assert artifact["replay"]["speedup_x"] > 10.0
        # SLO curve non-degenerate: real latencies, p99 >= p50, and
        # more replicas strictly improve tail TTFT across the sweep
        for row in slo:
            assert row["ttft_p99_ms"] >= row["ttft_p50_ms"] > 0.0
            assert row["itl_p99_ms"] >= row["itl_p50_ms"] > 0.0
            assert row["ok"] > 0
        by_n = {row["replicas"]: row for row in slo}
        assert by_n[4]["ttft_p99_ms"] < by_n[2]["ttft_p99_ms"] \
            < by_n[1]["ttft_p99_ms"]
        # shed-rate frontier non-degenerate: overload actually sheds,
        # shedding grows with offered load, queue memory stays bounded
        assert frontier[0]["load_factor"] < frontier[-1]["load_factor"]
        assert frontier[-1]["shed_rate"] > frontier[0]["shed_rate"]
        assert frontier[-1]["shed_rate"] > 0.05
        cap = (frontier_fleet.profile.max_queue
               * max(4, frontier_fleet.replicas * 2))
        for row in frontier:
            assert row["peak_queue_depth"] <= cap
            assert row["retries"] > 0      # pushback was exercised

    def test_session_affinity_shows_in_prefix_hits(self):
        """Conversation re-visits + session pinning: the fleet serves a
        real share of prompt tokens from cache expectations (the radix
        accounting the SimEngine models)."""
        cfg = TraceConfig(seed=9, duration_s=240.0, users=12, tenants=4)
        clock = VirtualClock()
        collector = Collector()
        fc = FleetConfig(replicas=2)
        gw, fleet = build_fleet(fc, clock, collector)
        try:
            driver = LoadDriver(gw, fleet, clock, cfg, fleet_cfg=fc,
                                collector=collector)
            report = driver.run()
            assert report.ok > 120
            agg = fleet.aggregate()
            assert agg["prefix_lookup_tokens"] > 0
            hit_rate = (agg["prefix_hit_tokens"]
                        / agg["prefix_lookup_tokens"])
            assert hit_rate > 0.2, hit_rate
            assert gw.router.stats()["routed_total"] > 0
        finally:
            gw.close()


class TestAgentPipeline:
    """Satellite of ISSUE 20: the agent-pipeline trace shape replayed
    through the virtual-clock fleet. Pipeline sessions are multi-step
    conversations whose inter-turn gap is a seed-deterministic TOOL op;
    after each ok turn the driver mirrors the workflow scheduler's
    fused-chain hook (park the conversation KV + speculative next-step
    prefill), so the fused win is measurable against the unfused
    baseline on the SAME trace."""

    # a fleet with KV headroom: parking pins pages, and speculation
    # spends engine rounds to buy next-step TTFT — on a pool already at
    # the eviction cliff the spend outweighs the win (the bench probe
    # sweeps that trade; here the contract under test is the win)
    TRACE = TraceConfig(seed=3, duration_s=120.0, users=12, tenants=4,
                        agent_pipeline_p=0.8, tool_gap_s=0.5)
    FLEET = FleetConfig(replicas=2)

    def test_pipeline_knob_off_keeps_traces_byte_identical(self):
        """agent_pipeline_p=0 draws no extra randomness: the default
        workload is byte-identical to what pre-pipeline seeds produced
        (every turn non-pipeline, same rng stream)."""
        cfg = TraceConfig(seed=11, duration_s=300.0, users=8, tenants=4)
        users = generate_trace(cfg)
        assert all(not t.pipeline for turns in users for t in turns)
        assert trace_bytes(cfg) == trace_bytes(cfg)

    def test_pipeline_trace_shape(self):
        users = generate_trace(self.TRACE)
        turns = [t for turns in users for t in turns]
        pipe = [t for t in turns if t.pipeline]
        assert len(pipe) > 50
        assert any(not t.pipeline for t in turns)
        # tool gaps are short relative to human think times
        gaps = sorted(t.think_s for t in pipe)
        assert gaps[len(gaps) // 2] < self.TRACE.think_s / 2

    def test_fused_replay_parks_speculates_and_beats_unfused_ttft(self):
        fused = replay(self.TRACE, self.FLEET)
        unfused = replay(self.TRACE, self.FLEET, fuse_pipeline=False)
        # the fused hooks actually fired: conversations parked across
        # tool gaps and speculative next-step prefills landed
        assert fused.pipeline_turns > 50
        assert fused.parked_turns > 0
        assert fused.speculations_ok > 0
        assert unfused.parked_turns == 0
        # the perf claim: with the next step's prefix speculatively
        # cached, median TTFT drops vs the identical unfused trace
        assert fused.ok > 100 and unfused.ok > 100
        assert fused.ttft_p50_ms < unfused.ttft_p50_ms

    def test_fused_replay_is_deterministic(self):
        r1 = replay(self.TRACE, self.FLEET)
        r2 = replay(self.TRACE, self.FLEET)
        assert r1.parked_turns == r2.parked_turns > 0
        assert r1.metrics() == r2.metrics()


class TestGatewayRestart:
    """Satellite of ISSUE 15: a scheduled mid-trace ``gateway_restart``
    event (virtual-clock deterministic) performs a zero-downtime rolling
    restart — a journal-backed successor adopts the predecessor's
    replica engines and the predecessor drains. Contract: zero failed
    requests, bounded added TTFT p99."""

    TRACE = TraceConfig(seed=13, duration_s=300.0, users=12, tenants=4)
    FLEET = FleetConfig(replicas=2, profile=SimProfile(
        slots=6, max_queue=32, kv_blocks=256))

    def test_mid_trace_restart_zero_failures_bounded_ttft(self):
        base = replay(self.TRACE, self.FLEET)
        restarted = replay(self.TRACE, dataclasses.replace(
            self.FLEET, gateway_restart_at_s=150.0))
        # the restart actually happened, by adoption not re-lease
        assert restarted.gateway_restarts == 1
        assert restarted.restart_adopted == self.FLEET.replicas
        # zero failed requests: every offered request finished ok (the
        # draining predecessor sheds at most into a retry, never a
        # failure)
        assert restarted.errors == 0
        assert restarted.timeout == 0
        assert restarted.shed == 0
        assert restarted.ok == restarted.requests > 200
        assert restarted.ok >= base.ok
        # bounded added tail latency: the swap is one draining window,
        # not a re-warm — p99 stays within 50% + one retry backoff of
        # the uninterrupted run
        assert restarted.ttft_p99_ms <= 1.5 * base.ttft_p99_ms + 1000.0

    def test_restart_replay_is_deterministic(self):
        cfg = dataclasses.replace(self.FLEET, gateway_restart_at_s=150.0)
        r1 = replay(self.TRACE, cfg)
        r2 = replay(self.TRACE, cfg)
        assert r1.gateway_restarts == r2.gateway_restarts == 1
        assert r1.metrics() == r2.metrics()


class TestShedHonoring:
    """Load clients honor ``retry_after_s`` — and the plane survives the
    client that does not."""

    def _run(self, hammer):
        trace = TraceConfig(seed=4, duration_s=200.0, users=10,
                            tenants=2, think_s=2.0)
        policies = {
            "t0": {"requests_per_s": 3.0, "burst_s": 1.0,
                   "max_queued": 8},
            "t1": {"requests_per_s": 3.0, "burst_s": 1.0,
                   "max_queued": 8},
        }
        fc = FleetConfig(replicas=1, retry_limit=6,
                         tenant_policies=policies,
                         profile=SimProfile(slots=4, max_queue=16,
                                            kv_blocks=192))
        return replay(trace.scaled(4.0), fc,
                      hammer_tenant="t1" if hammer else None,
                      max_virtual_s=600.0)

    def test_polite_replay_succeeds_hammer_gets_pushback(self):
        """Same trace twice: once all-polite, once with tenant t1
        hammering (retries every 20 ms, hints ignored).  Found-and-fixed
        by this harness: with an ADVISORY hint the hammer used to win
        the bucket refill race outright; ``SloLimiter`` backoff
        enforcement makes honoring the hint the winning strategy."""
        polite_run = self._run(hammer=False)
        hammer_run = self._run(hammer=True)
        p_t1 = polite_run.outcomes_by_tenant.get("t1", {})
        h_t1 = hammer_run.outcomes_by_tenant.get("t1", {})
        # the polite client replays on retry_after_s and gets served
        assert p_t1.get("ok", 0) > 0
        assert p_t1.get("retries", 0) > 0
        # hammering the same tenant converts service into sheds: the
        # enforced backoff window means misbehavior buys pushback, not
        # throughput
        assert h_t1.get("shed", 0) > p_t1.get("shed", 0)
        assert h_t1.get("ok", 0) < p_t1.get("ok", 0)
        # the OTHER tenant is untouched by t1's behavior change
        p_t0 = polite_run.outcomes_by_tenant.get("t0", {})
        h_t0 = hammer_run.outcomes_by_tenant.get("t0", {})
        assert h_t0.get("ok", 0) >= int(0.9 * p_t0.get("ok", 0))
        # bounded queue memory in both worlds
        assert polite_run.peak_queue_depth <= 16
        assert hammer_run.peak_queue_depth <= 16


class TestAutoscalerUnderBursts:
    def test_bursty_traffic_scales_up_without_flapping(self):
        trace = TraceConfig(seed=8, duration_s=600.0, users=24,
                            tenants=4, think_s=6.0, burst_factor=10.0,
                            burst_on_s=120.0, burst_off_s=120.0)
        fc = FleetConfig(
            replicas=1,
            autoscaler=dict(min_replicas=1, max_replicas=6,
                            up_queue_per_replica=4.0, up_sustain_s=5.0,
                            down_busy_fraction=0.2, down_sustain_s=120.0,
                            cooldown_s=30.0),
            profile=SimProfile(slots=4, max_queue=32, kv_blocks=256))
        report = replay(trace, fc, max_virtual_s=1800.0)
        assert report.scale_ups >= 1, report.doc()
        # no flapping: bounded lease churn over the whole replay
        assert report.scale_ups + report.scale_downs <= 12
        assert report.ok > 600


@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("LZY_SLOW"),
                    reason="full capacity sweep: set LZY_SLOW=1")
class TestFullSweep:
    def test_full_operating_curves(self, tmp_path):
        """LZY_SLOW tier: the bigger artifact — longer traces, wider
        sweeps, plus the WFQ-weight and autoscaler-gain tuning rows."""
        import json

        from conftest import record_tier_run
        from lzy_tpu.load import (
            autoscaler_gain_sweep, wfq_weight_sweep)

        trace = TraceConfig(seed=0, duration_s=1800.0, users=64,
                            tenants=8)
        fleet = FleetConfig(replicas=2, profile=SimProfile(
            slots=8, max_queue=64, kv_blocks=512))
        artifact = capacity_artifact(
            trace, fleet, replica_counts=[1, 2, 4, 8],
            load_factors=[1.0, 2.0, 4.0, 8.0],
            frontier_fleet_cfg=FleetConfig(
                replicas=2, retry_limit=4,
                profile=SimProfile(slots=4, max_queue=24,
                                   kv_blocks=192)))
        artifact["wfq_weight_sweep"] = wfq_weight_sweep(
            dataclasses.replace(trace, duration_s=600.0), fleet,
            [0.5, 2.0, 8.0])
        artifact["autoscaler_gain_sweep"] = autoscaler_gain_sweep(
            dataclasses.replace(trace, duration_s=600.0), fleet, [
                dict(min_replicas=1, max_replicas=8, up_sustain_s=2.0,
                     cooldown_s=5.0),
                dict(min_replicas=1, max_replicas=8, up_sustain_s=10.0,
                     cooldown_s=30.0),
            ])
        out = tmp_path / "capacity_full.json"
        out.write_text(json.dumps(artifact, indent=1, sort_keys=True))
        slo = artifact["slo_curve"]
        assert slo[-1]["ttft_p99_ms"] < slo[0]["ttft_p99_ms"]
        # a bigger WFQ weight buys the tenant tokens share
        ws = artifact["wfq_weight_sweep"]
        assert ws[-1]["tenant_tokens"] >= ws[0]["tenant_tokens"]
        # twitchier gains scale more
        gs = artifact["autoscaler_gain_sweep"]
        assert gs[0]["scale_ups"] >= gs[-1]["scale_ups"]
        record_tier_run("load:full-sweep",
                        f"{sum(r['requests'] for r in slo)} requests")

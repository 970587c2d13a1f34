"""Serving fleet gateway: routing, failover, autoscaling, RPC surface.

The gateway is a correctness-transparent layer: whatever replica a
request lands on, the reply must be bit-identical to the single-engine
path (greedy AND sampled), including across a mid-stream replica death —
the failover fences the already-emitted tokens and the retry continues
from them. The cache-aware part is a throughput property with an in-tree
baseline: the same shared-prefix workload through the same fleet must
show a strictly higher aggregate radix hit rate under prefix-affinity
routing than under round-robin.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.gateway import (
    Autoscaler, GatewayService, HealthPolicy, HealthTracker,
    PrefixAffinityRouter, ReplicaFleet, RoundRobinRouter, chunk_hashes)
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine

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


def _make_gateway(cfg, params, *, replicas=3, slots=2, router=None,
                  autoscaler=None, start_engines=True, allocator=None,
                  **engine_kw):
    def factory():
        return PagedInferenceEngine(cfg, params, slots=slots,
                                    page_size=PAGE, **engine_kw)

    fleet = ReplicaFleet(factory, allocator=allocator,
                         start_engines=start_engines)
    gw = GatewayService(
        fleet, router=router or PrefixAffinityRouter(PAGE),
        autoscaler=autoscaler, model_name="tiny")
    for _ in range(replicas):
        fleet.add_replica()
    return gw, fleet


class TestChunkHashes:
    def test_chain_property(self):
        a = chunk_hashes(list(range(24)), 8)
        b = chunk_hashes(list(range(16)), 8)
        assert len(a) == 3 and len(b) == 2
        assert a[:2] == b            # shared prefix -> shared chain hashes

    def test_divergence_breaks_the_chain(self):
        a = chunk_hashes(list(range(24)), 8)
        other = list(range(8)) + [99] * 16
        c = chunk_hashes(other, 8)
        assert a[0] == c[0] and a[1] != c[1] and a[2] != c[2]

    def test_partial_chunk_ignored(self):
        assert chunk_hashes([1, 2, 3], 8) == []


class TestPrefixAffinityRouter:
    def test_routes_to_expected_prefix_holder(self):
        r = PrefixAffinityRouter(4)
        prompt = list(range(12))
        loads = {"a": 0, "b": 0}
        first, why = r.choose(prompt, loads)
        assert why == "load"
        r.observe(first, prompt)
        again, why = r.choose(prompt, loads)
        assert (again, why) == (first, "prefix")
        # a prompt sharing only the first chunk still prefers the holder
        sibling = prompt[:4] + [60, 61, 62, 63]
        got, why = r.choose(sibling, loads)
        assert (got, why) == (first, "prefix")

    def test_imbalance_bound_overrides_affinity(self):
        r = PrefixAffinityRouter(4, max_imbalance=2)
        prompt = list(range(8))
        r.observe("hot", prompt)
        got, why = r.choose(prompt, {"hot": 3, "cold": 0})
        assert (got, why) == ("cold", "load")
        got, why = r.choose(prompt, {"hot": 2, "cold": 0})
        assert (got, why) == ("hot", "prefix")

    def test_forget_drops_the_index(self):
        r = PrefixAffinityRouter(4)
        prompt = list(range(8))
        r.observe("a", prompt)
        assert r.match_len("a", prompt) == 8
        r.forget("a")
        assert r.match_len("a", prompt) == 0

    def test_index_is_bounded_lru(self):
        r = PrefixAffinityRouter(2, index_chains_per_replica=4)
        for i in range(8):
            r.observe("a", [i * 2, i * 2 + 1])
        assert r.stats()["indexed_chains"]["a"] == 4
        # oldest chains evicted, newest retained
        assert r.match_len("a", [14, 15]) == 2
        assert r.match_len("a", [0, 1]) == 0

    def test_eviction_never_strands_orphan_descendants(self):
        """Chains match ancestor-to-descendant, so eviction must take the
        deepest entries of the oldest prompt first — evicting an ancestor
        while its descendant survives would leave permanently
        unmatchable index entries."""
        r = PrefixAffinityRouter(2, index_chains_per_replica=3)
        r.observe("a", [1, 2, 3, 4])        # depths 0,1 at clock 1
        r.observe("a", [9, 8, 7, 6])        # depths 0,1 at clock 2
        # cap 3: the OLD prompt's deepest chain went, its ancestor stayed
        assert r.match_len("a", [1, 2]) == 2
        assert r.match_len("a", [1, 2, 3, 4]) == 2
        assert r.match_len("a", [9, 8, 7, 6]) == 4

    def test_round_robin_cycles(self):
        r = RoundRobinRouter()
        loads = {"a": 0, "b": 9, "c": 0}
        picks = [r.choose([1], loads)[0] for _ in range(6)]
        assert picks == ["a", "b", "c", "a", "b", "c"]


class TestHealthTracker:
    def test_failure_streak_marks_dead_and_success_resets(self):
        h = HealthTracker(HealthPolicy(max_consecutive_failures=3))
        for _ in range(2):
            h.record_failure("r")
        assert h.verdict("r") is None
        h.record_success("r")
        for _ in range(2):
            h.record_failure("r")
        assert h.verdict("r") is None          # streak was reset
        h.record_failure("r")
        assert "consecutive" in h.verdict("r")

    def test_heartbeat_staleness(self):
        h = HealthTracker(HealthPolicy(heartbeat_timeout_s=30))
        assert h.verdict("r", heartbeat_ts=1000.0, now=1010.0) is None
        assert "stale" in h.verdict("r", heartbeat_ts=1000.0, now=1031.0)
        # unleased replicas have no heartbeat signal at all
        assert h.verdict("r", heartbeat_ts=None, now=1e12) is None

    def test_engine_death_is_immediate(self):
        h = HealthTracker()
        assert h.verdict("r", engine_closed=True) == "engine loop died"


class TestAutoscaler:
    def test_up_requires_sustained_pressure(self):
        a = Autoscaler(max_replicas=4, up_queue_per_replica=4,
                       up_sustain_s=5, cooldown_s=10)
        assert a.tick(0, replicas=2, queue_depth=20, busy=8, slots=8) is None
        assert a.tick(3, replicas=2, queue_depth=20, busy=8, slots=8) is None
        d = a.tick(6, replicas=2, queue_depth=20, busy=8, slots=8)
        assert d.direction == "up"
        # cooldown suppresses the next verdict
        assert a.tick(8, replicas=3, queue_depth=30, busy=12,
                      slots=12) is None

    def test_pressure_window_resets_when_queue_drains(self):
        a = Autoscaler(up_queue_per_replica=4, up_sustain_s=5)
        assert a.tick(0, replicas=1, queue_depth=9, busy=4, slots=4) is None
        assert a.tick(4, replicas=1, queue_depth=0, busy=1, slots=4) is None
        # pressure returns: the window starts over
        assert a.tick(6, replicas=1, queue_depth=9, busy=4, slots=4) is None
        assert a.tick(12, replicas=1, queue_depth=9, busy=4,
                      slots=4).direction == "up"

    def test_down_on_sustained_idle_respects_min(self):
        a = Autoscaler(min_replicas=2, down_busy_fraction=0.25,
                       down_sustain_s=30, cooldown_s=0)
        assert a.tick(0, replicas=3, queue_depth=0, busy=0, slots=12) is None
        d = a.tick(31, replicas=3, queue_depth=0, busy=0, slots=12)
        assert d.direction == "down"
        a2 = Autoscaler(min_replicas=2, down_sustain_s=30)
        a2.tick(0, replicas=2, queue_depth=0, busy=0, slots=8)
        assert a2.tick(31, replicas=2, queue_depth=0, busy=0,
                       slots=8) is None      # at the floor

    def test_max_replicas_caps_up(self):
        a = Autoscaler(max_replicas=2, up_sustain_s=0, cooldown_s=0)
        a.tick(0, replicas=2, queue_depth=99, busy=8, slots=8)
        assert a.tick(1, replicas=2, queue_depth=99, busy=8,
                      slots=8) is None


class TestGatewayParity:
    def test_greedy_bit_identical_over_three_replicas(self, tiny_model):
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=3)
        try:
            prompts = [[3 + i, 5, 7] for i in range(6)]
            replicas_used = set()
            for p in prompts:
                res = gw.generate(p, max_new_tokens=4, timeout_s=120)
                assert res["status"] == "ok" and res["failovers"] == 0
                assert res["tokens"] == _oracle_tokens(cfg, params, p, 4)
                replicas_used.add(res["replica"])
            s = gw.stats()
            assert s["replicas"] == 3 and s["requests_finished"] == 6
        finally:
            gw.close()

    def test_sampled_bit_identical_to_single_engine(self, tiny_model):
        """One sampled request through a fresh 3-replica fleet must match
        a fresh single engine bit-for-bit: every replica seeds the same
        rng stream, and the first request consumes the same draws."""
        cfg, params = tiny_model
        kw = dict(temperature=0.8, top_k=20, seed=7)
        solo = PagedInferenceEngine(cfg, params, slots=2, **kw)
        ref = solo.submit([5, 9, 3], max_new_tokens=6)
        while not ref.done:
            solo.step()
        gw, _ = _make_gateway(cfg, params, replicas=3, **kw)
        try:
            res = gw.generate([5, 9, 3], max_new_tokens=6, timeout_s=120)
            assert res["tokens"] == ref.result(0)
        finally:
            gw.close()

    def test_request_scoped_errors_do_not_fail_over(self, tiny_model):
        cfg, params = tiny_model
        gw, _ = _make_gateway(cfg, params, replicas=2)
        try:
            with pytest.raises(ValueError, match="exceeds"):
                gw.generate([1] * 10, max_new_tokens=cfg.max_seq_len,
                            timeout_s=10)
            assert gw.stats()["failovers"] == 0
        finally:
            gw.close()

    def test_fleet_wide_backpressure(self, tiny_model):
        from lzy_tpu.rpc.core import Unavailable

        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2, slots=1,
                                  start_engines=False, max_queue=1)
        try:
            # fill every replica's admission queue directly; no loops run,
            # so the gateway sees AdmissionError from each and only then
            # surfaces retryable backpressure
            for replica in fleet.replicas():
                replica.engine.submit([1, 2], max_new_tokens=2)
            with pytest.raises(Unavailable, match="no replica can admit"):
                gw.generate([3, 4], max_new_tokens=2, timeout_s=5)
        finally:
            gw.close()


class TestPrefixAffinityHitRate:
    """The acceptance property: on a shared-prefix workload the affinity
    router concentrates each prefix family on one replica, so the
    fleet-aggregate radix hit rate beats round-robin on the SAME fleet
    shape and workload."""

    def _drive(self, cfg, params, router):
        gw, fleet = _make_gateway(cfg, params, replicas=3, router=router)
        try:
            # four families over three replicas: round-robin cannot stay
            # aligned (family i lands on a different replica every round),
            # while affinity pins each family wherever it first landed
            families = [
                list(range(0, 16)),           # two full PAGE-chunks each
                list(range(20, 36)),
                list(range(40, 56)),
                list(range(8, 24)),
            ]
            for round_ in range(3):
                for fam, prefix in enumerate(families):
                    prompt = prefix + [60 + fam, 50 + round_, round_]
                    res = gw.generate(prompt, max_new_tokens=2,
                                      timeout_s=120)
                    assert res["status"] == "ok"
            agg = fleet.aggregate()
            assert agg["prefix_lookup_tokens"] > 0
            return (agg["prefix_hit_tokens"] / agg["prefix_lookup_tokens"],
                    gw.stats())
        finally:
            gw.close()

    def test_affinity_beats_round_robin(self, tiny_model):
        cfg, params = tiny_model
        affinity_rate, affinity_stats = self._drive(
            cfg, params, PrefixAffinityRouter(PAGE))
        rr_rate, _ = self._drive(cfg, params, RoundRobinRouter())
        assert affinity_rate > rr_rate, (
            f"prefix-affinity routing must raise the aggregate radix hit "
            f"rate over round-robin (affinity {affinity_rate:.3f} vs rr "
            f"{rr_rate:.3f})")
        # and the router actually routed repeats by prefix
        assert affinity_stats["routed_by_prefix"] > 0
        assert affinity_stats["fleet_prefix_hit_rate"] == round(
            affinity_rate, 4)


class TestFailover:
    def test_replica_killed_mid_decode_completes_elsewhere(self,
                                                           tiny_model):
        """Kill the serving replica's engine loop mid-stream: the request
        must complete on another replica with output identical to an
        uninterrupted single-engine run, the already-emitted tokens
        fenced (never repeated, never dropped), and the dead replica
        retired from routing."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=3)
        result = {}

        def run():
            try:
                result["res"] = gw.generate([7, 2, 8, 1],
                                            max_new_tokens=24,
                                            timeout_s=120)
            except BaseException as e:  # surfaced in the main thread
                result["err"] = e

        try:
            t = threading.Thread(target=run)
            t.start()
            victim, req = None, None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                for replica in fleet.replicas():
                    live = [r for r in replica.engine._active
                            if r is not None]
                    if live and len(live[0].tokens) >= 3:
                        victim, req = replica, live[0]
                        break
                if victim:
                    break
                time.sleep(0.005)
            assert victim is not None, "request never reached mid-decode"

            def boom():
                raise RuntimeError("replica host on fire")

            victim.engine.step = boom
            t.join(120)
            assert "err" not in result, result.get("err")
            res = result["res"]
            assert res["tokens"] == _oracle_tokens(cfg, params,
                                                   [7, 2, 8, 1], 24)
            assert res["failovers"] == 1 and res["status"] == "ok"
            assert victim.id not in [r.id for r in fleet.replicas()]
            assert gw.stats()["failovers"] == 1
        finally:
            gw.close()


class TestLeasedFleet:
    def test_replicas_lease_through_the_allocator(self, tiny_model):
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.allocator import IDLE, RUNNING

        cfg, params = tiny_model
        cluster = InProcessCluster()
        gw, fleet = _make_gateway(cfg, params, replicas=2,
                                  allocator=cluster.allocator)
        try:
            for replica in fleet.replicas():
                assert replica.vm_ids, "replica must hold a lease"
                vm = cluster.allocator.vm(replica.vm_ids[0])
                assert vm.status == RUNNING
                assert vm.heartbeat_ts > 0
            res = gw.generate([5, 9, 3], max_new_tokens=3, timeout_s=120)
            assert res["tokens"] == _oracle_tokens(cfg, params,
                                                   [5, 9, 3], 3)
            # draining frees the gang back to the session cache (IDLE)...
            victim = fleet.replicas()[0]
            fleet.drain(victim.id)
            gw.tick()
            assert victim.id not in [r.id for r in fleet.replicas()]
            assert cluster.allocator.vm(victim.vm_ids[0]).status == IDLE
            # fleet aggregates stay monotonic across the retirement: the
            # drained replica's served tokens are banked, not dropped
            assert fleet.aggregate()["tokens_generated"] >= 3
            # ...and the next lease reuses the warm gang
            fresh = fleet.add_replica()
            assert fresh.vm_ids == victim.vm_ids
        finally:
            gw.close()
            cluster.shutdown()

    def test_stale_heartbeat_retires_the_replica(self, tiny_model):
        from lzy_tpu.service import InProcessCluster

        cfg, params = tiny_model
        cluster = InProcessCluster()
        gw, fleet = _make_gateway(cfg, params, replicas=2,
                                  allocator=cluster.allocator)
        try:
            victim = fleet.replicas()[0]
            horizon = time.time() + 10 * HealthPolicy().heartbeat_timeout_s
            dead = fleet.check_health(now=horizon)
            # ALL replicas look stale at that horizon; the point is that
            # staleness alone retires them without any request traffic
            assert victim.id in dead
            assert victim.id not in [r.id for r in fleet.replicas()]
        finally:
            gw.close()
            cluster.shutdown()


class TestAutoscaleIntegration:
    def test_queue_pressure_scales_up_then_idle_drains(self, tiny_model):
        from lzy_tpu.service import InProcessCluster

        cfg, params = tiny_model
        cluster = InProcessCluster()
        scaler = Autoscaler(min_replicas=1, max_replicas=3,
                            up_queue_per_replica=4, up_sustain_s=0.5,
                            down_busy_fraction=0.25, down_sustain_s=1.0,
                            cooldown_s=0.1)
        gw, fleet = _make_gateway(cfg, params, replicas=1,
                                  autoscaler=scaler,
                                  allocator=cluster.allocator)
        try:
            only = fleet.replicas()[0]
            backlog = [only.engine.submit([1 + i, 2, 3], max_new_tokens=40)
                       for i in range(8)]
            t0 = time.time()
            assert gw.tick(now=t0) is None          # window opens
            assert gw.tick(now=t0 + 1.0) == "up"    # sustained -> lease
            assert len(fleet.replicas()) == 2
            assert all(r.vm_ids for r in fleet.replicas())
            for req in backlog:
                req.result(timeout=120)
            t1 = time.time()
            assert gw.tick(now=t1) is None          # idle window opens
            assert gw.tick(now=t1 + 2.0) == "down"
            gw.tick(now=t1 + 3.0)                   # reap the drained one
            assert len(fleet.replicas()) == 1
            assert gw.stats()["scale_ups"] == 1
            assert gw.stats()["scale_downs"] == 1
        finally:
            gw.close()
            cluster.shutdown()


class TestFleetRecovery:
    def test_fleet_releases_to_min_replicas_after_total_loss(self,
                                                            tiny_model):
        """Health-based retirement can take the fleet to zero, where no
        queue pressure can ever build (nothing admits) — the tick must
        re-lease back to the autoscaler's floor on its own."""
        cfg, params = tiny_model
        scaler = Autoscaler(min_replicas=2, max_replicas=4)
        gw, fleet = _make_gateway(cfg, params, replicas=2,
                                  autoscaler=scaler)
        try:
            for replica in fleet.replicas():
                replica.engine.close()        # closed engine == dead
            assert gw.tick() == "up"          # retire both, re-lease one
            assert gw.tick() == "up"          # ...and the second
            assert len(fleet.replicas()) == 2
            assert gw.tick() is None          # at the floor: steady state
            res = gw.generate([5, 9, 3], max_new_tokens=3, timeout_s=120)
            assert res["tokens"] == _oracle_tokens(cfg, params,
                                                   [5, 9, 3], 3)
        finally:
            gw.close()


class TestGatewayRpc:
    def test_generate_and_fleet_stats_over_the_control_plane(
            self, tiny_model, tmp_path):
        from lzy_tpu.rpc import RpcInferenceClient
        from lzy_tpu.service import InProcessCluster

        cfg, params = tiny_model

        def factory(cluster):
            gw, _ = _make_gateway(cfg, params, replicas=3)
            return gw

        cluster = InProcessCluster(
            db_path=str(tmp_path / "meta.db"),
            storage_uri=f"file://{tmp_path}/storage",
            worker_mode="process",
            inference_factory=factory,
        )
        try:
            client = RpcInferenceClient(cluster.rpc_server.address)
            try:
                res = client.generate([5, 9, 3], max_new_tokens=4,
                                      timeout_s=120)
                assert res["tokens"] == _oracle_tokens(cfg, params,
                                                       [5, 9, 3], 4)
                assert res["replica"] and res["routed_by"]
                stats = client.stats()
                assert stats["gateway"] is True and stats["replicas"] == 3
                fs = client.fleet_stats()
                assert len(fs["replicas"]) == 3
                assert {r["state"] for r in fs["replicas"]} == {"READY"}
            finally:
                client.close()
        finally:
            cluster.shutdown()

    def test_fleet_stats_not_found_on_single_engine_plane(
            self, tiny_model, tmp_path):
        from lzy_tpu.rpc import RpcInferenceClient
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.inference import InferenceService

        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=1).start()
        cluster = InProcessCluster(
            db_path=str(tmp_path / "meta.db"),
            storage_uri=f"file://{tmp_path}/storage",
            worker_mode="process",
            inference_service=InferenceService(engine, model_name="tiny"),
        )
        try:
            client = RpcInferenceClient(cluster.rpc_server.address)
            try:
                # a single-engine plane does not serve the method at all
                # (UNIMPLEMENTED -> RuntimeError client-side)
                with pytest.raises(RuntimeError):
                    client.fleet_stats()
            finally:
                client.close()
        finally:
            cluster.shutdown()

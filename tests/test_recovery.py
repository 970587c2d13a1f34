"""Control-plane crash recovery: the gateway that can die.

THE acceptance property (ISSUE 15): a gateway process death mid-stream
— greedy AND sampled rows in flight — followed by a restart yields
byte-identical output via the ORIGINAL resume token, with adopted (not
re-leased) replicas, zero failed requests in the chaos soak at
``gateway.crash`` rate 1.0, and every journaled live request accounted
for by the recovery auditor (re-attached, re-submitted-at-fence, or
terminally failed with a typed status — never silently dropped).

The journal's degradation contract rides along: a failing durable
append (``journal.append`` chaos at rate 1.0) is a counted warning and
a memory-only record, never a failed request.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.chaos.faults import CHAOS, CRASH, ERROR, FaultPlan
from lzy_tpu.chaos.invariants import (
    FenceAuditor, InvariantViolation, audit_recovery)
from lzy_tpu.durable.failures import InjectedCrash
from lzy_tpu.durable.store import OperationStore
from lzy_tpu.gateway import (
    GatewayJournal, GatewayService, PrefixAffinityRouter, ReplicaFleet,
    recover_gateway, simulate_gateway_death)
from lzy_tpu.gateway.journal import ORPHANED
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


def _make_ctx(cfg, params, *, replicas=2, slots=2, sharded=False,
              allocator=None, store=None, **engine_kw):
    """A journal-backed gateway fleet plus everything a successor needs
    (the factory, the shared store, the fence auditor)."""
    store = store if store is not None else OperationStore(":memory:")
    journal = GatewayJournal(store)

    def factory():
        if sharded:
            from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine

            return ShardedPagedInferenceEngine(cfg, params, slots=slots,
                                               page_size=PAGE, tp=2,
                                               **engine_kw)
        return PagedInferenceEngine(cfg, params, slots=slots,
                                    page_size=PAGE, **engine_kw)

    fleet = ReplicaFleet(factory, allocator=allocator)
    auditor = FenceAuditor()
    gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                        model_name="tiny", journal=journal)
    gw.fence_auditor = auditor
    for _ in range(replicas):
        fleet.add_replica()
    return {
        "gw": gw, "journal": journal, "factory": factory,
        "auditor": auditor, "allocator": allocator,
        "recoveries": 0, "reports": [],
    }


def _kill_and_recover(ctx, *, dead_replicas=(), engine_source=None):
    """Simulate the gateway process death, then build + recover a
    successor sharing the journal. ``dead_replicas`` close those
    engines first (a lease that died WITH the process). Runs the
    recovery auditor against the pre-death live snapshot."""
    old = ctx["gw"]
    pre_live = ctx["journal"].live_requests()
    engines = {}
    from lzy_tpu.gateway.fleet import DRAINING

    for replica in (old.fleet.replicas()
                    + old.fleet.replicas(state=DRAINING)):
        engines[replica.id] = replica.engine
    for rid in dead_replicas:
        engines[rid].close()
    simulate_gateway_death(old)
    fleet2 = ReplicaFleet(ctx["factory"], allocator=ctx["allocator"])
    gw2 = GatewayService(fleet2, router=PrefixAffinityRouter(PAGE),
                         model_name="tiny", journal=ctx["journal"],
                         kv_index=old.kv_index)
    gw2.fence_auditor = ctx["auditor"]
    src = engine_source if engine_source is not None \
        else (lambda rid, vms: engines.get(rid))
    report = recover_gateway(gw2, engine_source=src,
                             allocator=ctx["allocator"])
    audit_recovery(ctx["journal"], gw2, pre_live)
    ctx["gw"] = gw2
    ctx["recoveries"] += 1
    ctx["reports"].append(report)
    return report, engines


def _poll_until(gw, rid, pos, *, min_tokens=1, budget_s=60.0):
    """Poll one stream until at least ``min_tokens`` NEW tokens arrived
    (or done); returns (new_tokens, new_pos, last_frame)."""
    out = []
    deadline = time.monotonic() + budget_s
    frame = None
    while len(out) < min_tokens and time.monotonic() < deadline:
        frame = gw.streams.poll(rid, pos, wait_s=1.0)
        out.extend(frame["tokens"])
        pos += len(frame["tokens"])
        if frame["done"]:
            break
    assert frame is not None and (len(out) >= min_tokens
                                  or frame["done"]), \
        f"stream {rid} produced {len(out)} tokens in {budget_s}s"
    return out, pos, frame


def _drain(gw, rid, pos, *, budget_s=120.0):
    """Poll to the done frame; returns (tokens_from_pos, final_frame)."""
    out = []
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        frame = gw.streams.poll(rid, pos, wait_s=2.0)
        out.extend(frame["tokens"])
        pos += len(frame["tokens"])
        if frame["done"]:
            return out, frame
    raise AssertionError(f"stream {rid} not done within {budget_s}s")


class TestJournalDegrade:
    """journal.append failure = degraded-to-memory with a counted
    warning, NEVER a failed request."""

    def test_appends_degrade_to_memory_under_chaos(self, tiny_model):
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=1)
        gw, journal = ctx["gw"], ctx["journal"]
        plan = FaultPlan(991, rate=1.0, modes=(ERROR,),
                         points=("journal.append",))
        CHAOS.arm(plan)
        try:
            res = gw.generate([5, 9, 3], max_new_tokens=4, timeout_s=120)
            assert res["status"] == "ok"
            assert res["tokens"] == _oracle_tokens(cfg, params,
                                                   [5, 9, 3], 4)
        finally:
            CHAOS.disarm()
            gw.close()
        assert journal.degraded >= 2          # birth + finish at least
        # the in-memory mirror still carries the (settled) record
        docs = journal.requests()
        assert any(d.get("status") == "terminal" for d in docs.values())

    def test_unit_roundtrip(self):
        journal = GatewayJournal(OperationStore(":memory:"))
        rid = journal.record_birth(prompt=[1, 2], max_new_tokens=8,
                                   streamed=True, tenant="t0",
                                   session="conv-1")
        journal.record_attempt(rid, "replica-1")
        journal.advance_fence(rid, 0, [4, 5])
        journal.advance_fence(rid, 0, [4])    # covered range = no-op
        journal.advance_fence(rid, 5, [9])    # gap = refused
        journal.advance_fence(rid, 1, [7, 8])  # diverging overlap = drop
        doc = journal.live_requests()[rid]
        assert doc["fence"] == [4, 5] and doc["routed"] == ["replica-1"]
        journal.finish(rid, "ok", fence=[4, 5, 6], reply={"replica": "r"})
        doc = journal.requests()[rid]
        assert doc["status"] == "terminal" and doc["terminal"] == "ok"
        assert doc["fence"] == [4, 5, 6]
        journal.forget(rid)
        assert rid not in journal.requests()

    def test_fence_delta_parts_reassemble_across_processes(self):
        """Fence advances journal O(frame) DELTA parts; a successor
        journal (fresh instance, same store — the cross-process path)
        reassembles the full fence from them."""
        store = OperationStore(":memory:")
        a = GatewayJournal(store)
        rid = a.record_birth(prompt=[9], max_new_tokens=16,
                             streamed=True)
        a.advance_fence(rid, 0, [1, 2])
        a.advance_fence(rid, 2, [3, 4, 5])
        # an overlapping frame (a re-polled range + new tail) appends
        # only the genuinely-new suffix
        a.advance_fence(rid, 3, [4, 5, 6])
        b = GatewayJournal(store)             # the successor's view
        doc = b.live_requests()[rid]
        assert doc["fence"] == [1, 2, 3, 4, 5, 6]
        # forget drops the parts too
        b.forget(rid)
        assert rid not in b.requests()
        c = GatewayJournal(store)
        assert c._assembled_fences() == {}

    def test_lease_roundtrip_with_pool_tag(self):
        journal = GatewayJournal(OperationStore(":memory:"))
        journal.record_lease("decode-1", ["vm-1", "vm-2"], "sess-9",
                             pool="decode")
        doc = journal.leases()["decode-1"]
        assert doc["vm_ids"] == ["vm-1", "vm-2"]
        assert doc["pool"] == "decode"
        journal.forget_lease("decode-1")
        assert journal.leases() == {}


class TestKillTheGateway:
    """THE acceptance test: death mid-stream, greedy and sampled rows
    in flight, byte-identical resume via the ORIGINAL tokens."""

    def test_mid_stream_death_greedy_and_sampled(self, tiny_model):
        cfg, params = tiny_model
        # a sampling fleet with a per-request greedy override: exactly
        # the mixed traffic the soak runs
        ctx = _make_ctx(cfg, params, replicas=2,
                        temperature=0.8, top_k=20, seed=7)
        gw = ctx["gw"]
        n = 20
        g_prompt, s_prompt = [7, 2, 8, 1], [5, 9, 3, 4]
        g_open = gw.streams.open(g_prompt, max_new_tokens=n,
                                 timeout_s=120, greedy=True)
        s_open = gw.streams.open(s_prompt, max_new_tokens=n,
                                 timeout_s=120)
        g_rid, s_rid = g_open["request_id"], s_open["request_id"]
        g_seen, g_pos, _ = _poll_until(gw, g_rid, 0, min_tokens=4)
        s_seen, s_pos, _ = _poll_until(gw, s_rid, 0, min_tokens=4)

        old_ids = sorted(r.id for r in gw.fleet.replicas())
        report, engines = _kill_and_recover(ctx)
        gw2 = ctx["gw"]
        try:
            # adopted, not re-leased: same ids, same ENGINE OBJECTS
            assert sorted(report.adopted) == old_ids
            assert not report.dropped_leases
            for replica in gw2.fleet.replicas():
                assert replica.engine is engines[replica.id]
            assert sorted(report.resubmitted) == sorted([g_rid, s_rid])

            # the ORIGINAL resume tokens, from the clients' positions
            g_rest, g_frame = _drain(gw2, g_rid, g_pos)
            s_rest, s_frame = _drain(gw2, s_rid, s_pos)
            g_final = g_seen + g_rest
            s_final = s_seen + s_rest
            assert g_frame["status"] == "ok" and s_frame["status"] == "ok"
            # greedy: byte-identical to an uninterrupted generate()
            assert g_final == _oracle_tokens(cfg, params, g_prompt, n)
            # sampled: the fence never repeats or drops a token and the
            # stream completes to the full budget
            assert s_final[:len(s_seen)] == s_seen
            assert len(s_final) == n
            assert g_frame["resumptions"] >= 1
            # re-polling position 0 on the SUCCESSOR replays the whole
            # stream byte-identically (idempotent frames survive death)
            replay, _ = _drain(gw2, g_rid, 0)
            assert replay == g_final
        finally:
            gw2.close()

    def test_adoption_preserves_leases(self, tiny_model):
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.allocator import RUNNING

        cfg, params = tiny_model
        cluster = InProcessCluster()
        ctx = _make_ctx(cfg, params, replicas=2,
                        allocator=cluster.allocator)
        gw = ctx["gw"]
        try:
            lease_by_id = {r.id: list(r.vm_ids)
                           for r in gw.fleet.replicas()}
            assert all(lease_by_id.values())
            vms_before = sorted(v.id for v in cluster.allocator.vms())
            report, _ = _kill_and_recover(ctx)
            gw2 = ctx["gw"]
            # no new VMs were allocated and every adopted replica holds
            # its ORIGINAL gang, still RUNNING
            assert sorted(v.id for v in cluster.allocator.vms()) == \
                vms_before
            for replica in gw2.fleet.replicas():
                assert list(replica.vm_ids) == lease_by_id[replica.id]
                for vm_id in replica.vm_ids:
                    assert cluster.allocator.vm(vm_id).status == RUNNING
            res = gw2.generate([5, 9, 3], max_new_tokens=3,
                               timeout_s=120)
            assert res["status"] == "ok"
        finally:
            ctx["gw"].close()
            cluster.shutdown()

    def test_dead_lease_dropped_and_freed(self, tiny_model):
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.allocator import IDLE

        cfg, params = tiny_model
        cluster = InProcessCluster()
        ctx = _make_ctx(cfg, params, replicas=2,
                        allocator=cluster.allocator)
        gw = ctx["gw"]
        try:
            victim = gw.fleet.replicas()[0]
            report, _ = _kill_and_recover(ctx,
                                          dead_replicas=(victim.id,))
            gw2 = ctx["gw"]
            assert victim.id in report.dropped_leases
            assert victim.id not in [r.id for r in gw2.fleet.replicas()]
            assert victim.id not in ctx["journal"].leases()
            # the dead replica's gang went back to the session cache
            for vm_id in victim.vm_ids:
                assert cluster.allocator.vm(vm_id).status == IDLE
        finally:
            ctx["gw"].close()
            cluster.shutdown()

    def test_boot_recovery_never_drops_the_live_fleets_leases(
            self, tiny_model):
        """The serve.py boot path recovers AFTER the builders populated
        a fresh fleet (whose add_replica just journaled its own leases
        under the same ids a predecessor used): recovery must skip
        those rows — dropping them would forget the journal AND free
        RUNNING gangs the live fleet is using."""
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.allocator import RUNNING

        cfg, params = tiny_model
        cluster = InProcessCluster()
        ctx = _make_ctx(cfg, params, replicas=2,
                        allocator=cluster.allocator)
        gw = ctx["gw"]
        try:
            report = recover_gateway(gw, engine_source=None,
                                     allocator=cluster.allocator)
            assert report.dropped_leases == []
            assert report.adopted == []
            assert sorted(ctx["journal"].leases()) == \
                sorted(r.id for r in gw.fleet.replicas())
            for replica in gw.fleet.replicas():
                for vm_id in replica.vm_ids:
                    assert cluster.allocator.vm(vm_id).status == RUNNING
            res = gw.generate([5, 9, 3], max_new_tokens=3,
                              timeout_s=120)
            assert res["status"] == "ok"
        finally:
            gw.close()
            cluster.shutdown()

    def test_lost_final_frame_window(self, tiny_model):
        """The predecessor FINISHED the generation but died before the
        client read the done frame: the successor rehydrates the
        terminal session and the old resume token reads the tail."""
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=1)
        gw = ctx["gw"]
        n = 8
        opened = gw.streams.open([7, 2, 8, 1], max_new_tokens=n,
                                 timeout_s=120)
        rid = opened["request_id"]
        sess = gw.streams._get(rid)
        assert sess.finished.wait(60.0)       # server-side complete
        seen, pos, _ = _poll_until(gw, rid, 0, min_tokens=2)
        report, _ = _kill_and_recover(ctx)
        gw2 = ctx["gw"]
        try:
            assert rid in report.rehydrated_terminal
            rest, frame = _drain(gw2, rid, pos)
            assert frame["status"] == "ok"
            assert seen + rest == _oracle_tokens(cfg, params,
                                                 [7, 2, 8, 1], n)
            assert frame["reply"].get("status") == "ok"
        finally:
            gw2.close()

    def test_unary_request_orphaned_with_typed_status(self, tiny_model):
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=1)
        gw = ctx["gw"]
        done = {}

        def run():
            try:
                done["res"] = gw.generate([6, 1, 2], max_new_tokens=48,
                                          timeout_s=120)
            except BaseException as e:  # noqa: BLE001
                done["err"] = e

        t = threading.Thread(target=run)
        t.start()
        deadline = time.monotonic() + 60
        journal = ctx["journal"]
        while time.monotonic() < deadline and not journal.live_requests():
            time.sleep(0.005)
        assert journal.live_requests(), "unary birth never journaled"
        report, _ = _kill_and_recover(ctx)
        gw2 = ctx["gw"]
        try:
            assert len(report.orphaned) == 1
            rid = report.orphaned[0]
            doc = journal.requests()[rid]
            assert doc["status"] == "terminal"
            assert doc["terminal"] == ORPHANED
            t.join(120)
        finally:
            gw2.close()

    def test_successor_with_fresh_journal_instance_keeps_journaling(
            self, tiny_model):
        """The REAL cross-process shape: the successor constructs its
        OWN GatewayJournal over the same store. Recovery must hydrate
        the new journal's mirror, or every later fence advance and the
        terminal settle would no-op and the store record would stay
        live-with-a-stale-fence — resubmitting an already-finished
        request on the NEXT death."""
        cfg, params = tiny_model
        store = OperationStore(":memory:")
        ctx = _make_ctx(cfg, params, replicas=1, store=store)
        gw = ctx["gw"]
        n = 12
        prompt = [7, 2, 8, 1]
        opened = gw.streams.open(prompt, max_new_tokens=n, timeout_s=120)
        rid = opened["request_id"]
        seen, pos, _ = _poll_until(gw, rid, 0, min_tokens=3)
        engines = {r.id: r.engine for r in gw.fleet.replicas()}
        simulate_gateway_death(gw)
        journal2 = GatewayJournal(store)       # FRESH instance
        fleet2 = ReplicaFleet(ctx["factory"])
        gw2 = GatewayService(fleet2, router=PrefixAffinityRouter(PAGE),
                             model_name="tiny", journal=journal2)
        report = recover_gateway(
            gw2, engine_source=lambda r, vms: engines.get(r))
        try:
            assert rid in report.resubmitted
            rest, frame = _drain(gw2, rid, pos)
            assert frame["status"] == "ok"
            final = seen + rest
            assert final == _oracle_tokens(cfg, params, prompt, n)
            sess = gw2.streams._get(rid)
            assert sess.finished.wait(30.0)
            # a THIRD journal instance (the next process) must see the
            # record settled with the full fence — proof the successor
            # kept journaling through its fresh instance
            journal3 = GatewayJournal(store)
            doc = journal3.requests()[rid]
            assert doc["status"] == "terminal"
            assert doc["terminal"] == "ok"
            assert doc["fence"] == final
        finally:
            gw2.close()

    def test_malformed_prompt_does_not_leak_a_session(self, tiny_model):
        """A prompt the journal birth cannot serialize must unwind the
        registered session (a leak would count toward max_sessions
        forever) and surface the typed bad-prompt error."""
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=1)
        gw = ctx["gw"]
        try:
            for _ in range(3):
                with pytest.raises((ValueError, TypeError)):
                    gw.streams.open(["not-a-token"], max_new_tokens=4)
            assert gw.streams.sessions() == []
            assert ctx["journal"].live_requests() == {}
        finally:
            gw.close()

    def test_auditor_catches_a_silent_drop(self, tiny_model):
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=1)
        gw, journal = ctx["gw"], ctx["journal"]
        try:
            rid = journal.record_birth(prompt=[1, 2], max_new_tokens=4,
                                       streamed=True)
            pre_live = journal.live_requests()
            # a "recovery" that neither re-attaches nor settles
            with pytest.raises(InvariantViolation, match="silently"):
                audit_recovery(journal, gw, pre_live)
        finally:
            gw.close()


class TestGangRecovery:
    """Sharded gang replicas recover ALL-OR-NOTHING: a journaled lease
    whose gang lost even one shard host while the gateway was down is
    never re-adopted — the SPMD programs span every shard, so a partial
    gang has no degraded mode. The lease is dropped whole (journal row
    forgotten, engine closed); intact gangs adopt exactly like
    single-device replicas."""

    def test_gang_with_dead_host_dropped_whole_intact_gang_adopted(
            self, tiny_model):
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=2, sharded=True)
        gw = ctx["gw"]
        engines = {}
        try:
            # both gangs serve before the crash
            res = gw.generate([5, 9, 3], max_new_tokens=3, timeout_s=120)
            assert res["status"] == "ok"
            victim, survivor = gw.fleet.replicas()
            engines.update({r.id: r.engine for r in gw.fleet.replicas()})

            def src(rid, vms):
                eng = engines.get(rid)
                if rid == victim.id and eng is not None:
                    # one shard host died WITH the gateway: the recovering
                    # successor must see gang_intact False and refuse the
                    # whole lease, not adopt a 1-of-2 gang
                    eng.mark_host_dead(1, "host lost in the outage")
                return eng

            report, _ = _kill_and_recover(ctx, engine_source=src)
            gw2 = ctx["gw"]
            assert victim.id in report.dropped_leases
            assert victim.id not in ctx["journal"].leases()
            ids = [r.id for r in gw2.fleet.replicas()]
            assert victim.id not in ids
            # the intact gang was ADOPTED (same engine object, no
            # rebuild) and still serves bit-identically
            assert survivor.id in ids
            adopted = next(r for r in gw2.fleet.replicas()
                           if r.id == survivor.id)
            assert adopted.engine is engines[survivor.id]
            assert adopted.engine.gang_size == 2
            res = gw2.generate([5, 9, 3], max_new_tokens=3,
                               timeout_s=120)
            assert res["status"] == "ok"
        finally:
            ctx["gw"].close()
            for eng in engines.values():
                if not getattr(eng, "closed", False):
                    eng.close()


class TestDisaggRecovery:
    """A disagg gateway journals BOTH pools: recovery adopts each lease
    into its own fleet and each fleet back onto its OWN allocator
    session (decode vs prefill sessions must never cross — freeing a
    gang into the wrong pool's cache or double-deleting one session on
    shutdown)."""

    def test_adopts_each_pool_onto_its_own_session(self):
        from lzy_tpu.gateway import DisaggGatewayService
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.inference import build_disagg_gateway_service

        cluster = InProcessCluster()
        store = OperationStore(":memory:")
        journal = GatewayJournal(store)
        svc = build_disagg_gateway_service(
            "tiny", prefill_replicas=1, decode_replicas=1, slots=2,
            start=False, journal=journal, allocator=cluster.allocator)
        try:
            decode_sess = svc.fleet._session_id
            prefill_sess = svc.prefill_fleet._session_id
            assert decode_sess and prefill_sess
            assert decode_sess != prefill_sess
            engines = {r.id: r.engine for r in svc.fleet.replicas()}
            engines.update({r.id: r.engine
                            for r in svc.prefill_fleet.replicas()})
            simulate_gateway_death(svc)

            d2 = ReplicaFleet(lambda: None,
                              allocator=cluster.allocator,
                              session_owner="disagg-decode",
                              replica_prefix="decode")
            p2 = ReplicaFleet(lambda: None,
                              allocator=cluster.allocator,
                              session_owner="disagg-prefill",
                              replica_prefix="prefill")
            gw2 = DisaggGatewayService(d2, p2, page_size=16,
                                       model_name="tiny",
                                       journal=GatewayJournal(store))
            report = recover_gateway(
                gw2, engine_source=lambda r, vms: engines.get(r),
                allocator=cluster.allocator)
            try:
                assert sorted(report.adopted) == ["decode-1",
                                                 "prefill-1"]
                assert [r.id for r in d2.replicas()] == ["decode-1"]
                assert [r.id for r in p2.replicas()] == ["prefill-1"]
                # each pool re-adopted ITS OWN allocator session
                assert d2._session_id == decode_sess
                assert p2._session_id == prefill_sess
                res = gw2.generate([5, 9, 3], max_new_tokens=3,
                                   timeout_s=120)
                assert res["status"] == "ok"
            finally:
                gw2.close()
        finally:
            cluster.shutdown()


class TestKvIndexRecovery:
    """Satellite: the fleet-global prefix index is force-refreshed from
    every adopted replica BEFORE the first routed request, and rows of
    leases that died with the old process are forgotten."""

    def test_index_repopulated_before_first_routed_request(self,
                                                           tiny_model):
        from lzy_tpu.gateway.kv_index import GlobalKVIndex

        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=2,
                        kv_host_tier_bytes=1 << 20)
        gw = ctx["gw"]
        gw.kv_index = GlobalKVIndex(PAGE)
        prompt = list(range(2 * PAGE)) + [3]
        res = gw.generate(prompt, max_new_tokens=2, timeout_s=120)
        assert res["status"] == "ok"
        warm = res["replica"]
        gw.tick()
        assert gw.kv_index.stats()["replicas_advertising"] >= 1

        report, _ = _kill_and_recover(ctx)
        gw2 = ctx["gw"]
        try:
            # BEFORE any tick or request on the successor: the index is
            # already whole (recovery force-refreshed it), and the
            # flag re-asserts the refresh on the first tick
            stats = gw2.kv_index.stats()
            assert warm in stats["indexed_chains"]
            assert stats["indexed_chains"][warm] >= 2
            assert gw2._kv_force_refresh is True
            gw2.tick()
            assert gw2._kv_force_refresh is False
        finally:
            gw2.close()

    def test_dead_lease_rows_forgotten(self, tiny_model):
        from lzy_tpu.gateway.kv_index import GlobalKVIndex

        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=2,
                        kv_host_tier_bytes=1 << 20)
        gw = ctx["gw"]
        gw.kv_index = GlobalKVIndex(PAGE)
        prompt = list(range(2 * PAGE)) + [3]
        # warm BOTH replicas' caches so both advertise
        for replica in gw.fleet.replicas():
            req = replica.engine.submit(prompt, max_new_tokens=2)
            assert req.result(timeout=120) is not None
        gw.tick()
        assert gw.kv_index.stats()["replicas_advertising"] == 2
        victim = gw.fleet.replicas()[0].id
        report, _ = _kill_and_recover(ctx, dead_replicas=(victim,))
        gw2 = ctx["gw"]
        try:
            assert victim in report.dropped_leases
            stats = gw2.kv_index.stats()
            assert victim not in stats["indexed_chains"]
            assert stats["replicas_advertising"] == 1
        finally:
            gw2.close()


def _run_with_recovery(ctx, prompt, n, *, greedy):
    """Drive one streamed request to completion, treating every
    injected gateway.crash — surfaced as an InjectedCrash from
    open/poll or as an error frame naming the injected crash — as a
    process death: kill, recover, resume at the SAME (request_id,
    position). Returns the full token list."""
    pos, out, rid = 0, [], None
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        gw = ctx["gw"]
        try:
            if rid is None:
                opened = gw.streams.open(prompt, max_new_tokens=n,
                                         timeout_s=120, greedy=greedy)
                rid = opened["request_id"]
            frame = gw.streams.poll(rid, pos, wait_s=2.0)
        except InjectedCrash:
            _kill_and_recover(ctx)
            continue
        if frame["done"] and frame.get("status") == "error":
            err = frame.get("error") or ""
            assert "injected crash" in err, \
                f"unexpected stream failure: {err}"
            _kill_and_recover(ctx)
            continue
        out.extend(frame["tokens"])
        pos += len(frame["tokens"])
        if frame["done"]:
            assert frame["status"] == "ok", frame
            return out
    raise AssertionError("request did not finish under chaos")


@pytest.mark.chaos
class TestGatewayCrashSoak:
    """gateway.crash at rate 1.0: every hit on the journal-backed
    request path dies until max_faults runs out — zero failed requests,
    greedy rows byte-identical to the oracle, recovery audited after
    every death."""

    def test_fixed_seed_crash_soak(self, tiny_model):
        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=2,
                        temperature=0.8, top_k=20, seed=11)
        n = 10
        rows = [([7, 2, 8, 1], True), ([5, 9, 3], False),
                ([9, 1, 4, 6], True), ([3, 3, 8], False)]
        plan = FaultPlan(1234, rate=1.0, modes=(CRASH,),
                         points=("gateway.crash",), max_faults=4)
        CHAOS.arm(plan)
        try:
            results = [
                _run_with_recovery(ctx, p, n, greedy=g)
                for p, g in rows
            ]
        finally:
            CHAOS.disarm()
            ctx["gw"].close()
        assert plan.fired >= 1, "the crash point never fired"
        assert ctx["recoveries"] >= 1
        for (prompt, greedy), tokens in zip(rows, results):
            assert len(tokens) == n
            if greedy:
                assert tokens == _oracle_tokens(cfg, params, prompt, n)

    @pytest.mark.skipif(
        not __import__("os").environ.get("LZY_SLOW"),
        reason="multi-seed gateway-death soak: set LZY_SLOW=1")
    def test_slow_multi_seed_soak(self, tiny_model):
        cfg, params = tiny_model
        for seed in (1, 2, 3):
            ctx = _make_ctx(cfg, params, replicas=2,
                            temperature=0.8, top_k=20, seed=seed)
            n = 12
            rows = [([7 + seed, 2, 8, 1], True), ([5, 9, 3 + seed], False),
                    ([2, 4, 6, 8], True), ([1, 1, 2 + seed], False),
                    ([6, 5, 4], True), ([8, 8, 1], False)]
            plan = FaultPlan(seed * 101, rate=1.0, modes=(CRASH,),
                             points=("gateway.crash",), max_faults=6)
            CHAOS.arm(plan)
            try:
                results = [
                    _run_with_recovery(ctx, p, n, greedy=g)
                    for p, g in rows
                ]
            finally:
                CHAOS.disarm()
                ctx["gw"].close()
            for (prompt, greedy), tokens in zip(rows, results):
                assert len(tokens) == n
                if greedy:
                    assert tokens == _oracle_tokens(cfg, params,
                                                    prompt, n)
            assert ctx["auditor"].completions_seen >= 1


class _FakeClock:
    """Recording clock for the reconnect-ladder test: time advances a
    bit per read so deadlines move; sleeps are recorded, not slept."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def time(self):
        self.t += 0.001
        return self.t

    def now(self):
        return self.time()

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s


class _FlakyRpc:
    """JsonRpcClient stand-in routing stream methods at a live
    StreamSessionManager, with a connection-refused window (the gateway
    restart) injected per call."""

    def __init__(self, manager):
        self.manager = manager
        self.fail_next = 0
        self.failures_seen = 0

    def call(self, method, payload=None, timeout_s=None, *,
             retry=False, idempotency_key=None):
        from lzy_tpu.rpc.core import Unavailable

        payload = payload or {}
        if self.fail_next > 0:
            self.fail_next -= 1
            self.failures_seen += 1
            raise Unavailable("connection refused (gateway restarting)")
        if method == "InferStream":
            return self.manager.open(
                payload["prompt"],
                max_new_tokens=payload["max_new_tokens"],
                timeout_s=payload.get("timeout_s"),
                greedy=payload.get("greedy"))
        if method == "InferStreamPoll":
            return self.manager.poll(
                payload["request_id"], payload.get("position", 0),
                wait_s=payload.get("wait_s", 1.0))
        if method == "InferCancel":
            return self.manager.cancel(payload["request_id"])
        raise KeyError(method)

    def close(self):
        pass


class TestReconnectLadder:
    """Satellite: connection refused during the restart → backoff →
    resume at the fence on the successor, with a resume token minted by
    the PREDECESSOR process."""

    def test_ladder_resumes_at_fence_on_successor(self, tiny_model):
        from lzy_tpu.rpc.control import RpcInferenceClient
        from lzy_tpu.utils.backoff import RetryPolicy

        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=2)
        gw = ctx["gw"]
        rpc = _FlakyRpc(gw.streams)
        clock = _FakeClock()
        client = RpcInferenceClient(
            client=rpc, clock=clock,
            reconnect=RetryPolicy(attempts=8, base_s=0.05, cap_s=0.2,
                                  jitter=False))
        n = 16
        prompt = [7, 2, 8, 1]
        opened = client.stream_open(prompt, max_new_tokens=n)
        rid = opened["request_id"]
        tokens = []
        frames = client.iter_stream(rid, 0, wait_s=1.0,
                                    deadline_s=3600.0)
        restarted = False
        try:
            for frame in frames:
                tokens.extend(frame.get("tokens", ()))
                if not restarted and len(tokens) >= 3:
                    # the restart window: the next polls are refused,
                    # the successor recovers the journal, and the SAME
                    # iterator (the predecessor's resume token) rides
                    # the ladder onto the new process
                    _kill_and_recover(ctx)
                    rpc.manager = ctx["gw"].streams
                    rpc.fail_next = 3
                    restarted = True
                if frame.get("done"):
                    assert frame["status"] == "ok"
                    break
        finally:
            ctx["gw"].close()
        assert restarted
        assert rpc.failures_seen == 3
        # the ladder actually backed off between refused polls
        assert len(clock.sleeps) >= 3
        assert all(s > 0 for s in clock.sleeps[:3])
        assert tokens == _oracle_tokens(cfg, params, prompt, n)

    def test_ladder_gives_up_past_budget(self, tiny_model):
        from lzy_tpu.rpc.control import RpcInferenceClient
        from lzy_tpu.rpc.core import Unavailable
        from lzy_tpu.utils.backoff import RetryPolicy

        cfg, params = tiny_model
        ctx = _make_ctx(cfg, params, replicas=1)
        gw = ctx["gw"]
        rpc = _FlakyRpc(gw.streams)
        client = RpcInferenceClient(
            client=rpc, clock=_FakeClock(),
            reconnect=RetryPolicy(attempts=3, base_s=0.01, cap_s=0.01,
                                  jitter=False))
        try:
            opened = client.stream_open([5, 9, 3], max_new_tokens=4)
            rpc.fail_next = 99                # the gateway never returns
            with pytest.raises(Unavailable):
                for _ in client.iter_stream(opened["request_id"], 0,
                                            wait_s=0.5):
                    pass
            assert rpc.failures_seen == 4     # 1 + the 3-attempt ladder
        finally:
            gw.close()

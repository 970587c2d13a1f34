"""Streaming inference delivery (``serving/streams`` + the
``InferStream``/``InferStreamPoll``/``InferCancel`` wire surface).

What this file pins:

- **frames are the fence**: position-tagged long-poll frames reproduce
  the ``generate()`` oracle byte-identically, and re-polling any
  position re-reads the identical continuation (the resume token);
- **robustness is the headline**: a client that disconnects while
  QUEUED is reaped in place (no slot ever spent), a slot-resident one
  is evicted within one decode round with KV blocks released and pool
  invariants clean, slow consumers are shed at the bounded buffer, and
  ``InferCancel`` lands in every phase (queued / prefill / decode /
  mid-failover) on dense, paged and disagg planes;
- **chaos**: fixed-seed faults at the new ``rpc.stream`` (frame
  drop / connection death) and ``stream.consumer`` (dead client)
  points, a replica death mid-stream resuming byte-identically through
  the gateway fence, and an LZY_SLOW streaming soak with auditors.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.chaos.faults import CHAOS, DELAY, ERROR, FaultPlan
from lzy_tpu.chaos.invariants import FenceAuditor, audit_engine
from lzy_tpu.gateway import (
    GatewayService, PrefixAffinityRouter, ReplicaFleet)
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.streams import (
    CANCELS, ConsumerGone, RESUMES, SHED_SLOW, StreamSessionManager)
from lzy_tpu.service.inference import InferenceService

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


@pytest.fixture(autouse=True)
def _disarmed():
    yield
    CHAOS.disarm()


def _oracle_tokens(cfg, params, prompt_ids, n):
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _service(cfg, params, *, slots=2, **engine_kw):
    engine = PagedInferenceEngine(cfg, params, slots=slots,
                                  page_size=PAGE, **engine_kw)
    engine.start()
    return InferenceService(engine, model_name="tiny"), engine


def _drain_stream(streams, rid, *, start=0, wait_s=2.0, budget_s=60.0):
    """Poll a session to completion; returns (tokens, final_frame)."""
    pos, toks = start, []
    deadline = time.monotonic() + budget_s
    while True:
        frame = streams.poll(rid, pos, wait_s=wait_s)
        toks.extend(frame["tokens"])
        pos += len(frame["tokens"])
        if frame["done"]:
            return toks, frame
        assert time.monotonic() < deadline, "stream never finished"


def _counter(counter, **labels):
    from lzy_tpu.utils.metrics import _label_key

    return counter._values.get(_label_key(labels), 0.0)


def _make_gateway(cfg, params, *, replicas=2, slots=2, **engine_kw):
    fleet = ReplicaFleet(
        lambda: PagedInferenceEngine(cfg, params, slots=slots,
                                     page_size=PAGE, **engine_kw))
    gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                        model_name="tiny")
    for _ in range(replicas):
        fleet.add_replica()
    return gw, fleet


# -- channel-level ack / lag plumbing -----------------------------------------

class TestChannelAck:
    def test_ack_is_monotonic_and_bounded(self):
        from lzy_tpu.channels.token_stream import TokenStreamChannel

        ch = TokenStreamChannel()
        ch.publish(0, [1, 2, 3, 4])
        assert ch.consumer_lag == 4
        ch.ack(3)
        assert ch.acked == 3 and ch.consumer_lag == 1
        ch.ack(1)                      # a resume re-read never rewinds
        assert ch.acked == 3
        ch.ack(99)                     # cannot ack past the fence
        assert ch.acked == 4

    def test_wait_past_returns_keepalive_not_raises(self):
        from lzy_tpu.channels.token_stream import TokenStreamChannel

        ch = TokenStreamChannel()
        out = ch.wait_past(0, timeout_s=0.02)
        assert out["tokens"] == [] and not out["closed"]
        ch.publish(0, [7])
        out = ch.wait_past(0, timeout_s=1.0)
        assert out["tokens"] == [7] and not out["closed"]
        ch.close("ok")
        out = ch.wait_past(1, timeout_s=0.1)
        assert out["closed"] and out["status"] == "ok"

    def test_read_and_iter_record_consumer_progress(self):
        from lzy_tpu.channels.token_stream import TokenStreamChannel

        ch = TokenStreamChannel()
        ch.publish(0, [1, 2, 3])
        assert ch.read(0, timeout_s=1.0) == [1, 2, 3]
        assert ch.acked == 3


# -- frames, resume tokens, keepalives ----------------------------------------

class TestStreamFrames:
    def test_frames_reproduce_the_oracle(self, tiny_model):
        cfg, params = tiny_model
        svc, engine = _service(cfg, params)
        try:
            opened = svc.streams.open([5, 9, 3], max_new_tokens=10,
                                      greedy=True)
            toks, frame = _drain_stream(svc.streams,
                                        opened["request_id"])
            assert toks == _oracle_tokens(cfg, params, [5, 9, 3], 10)
            assert frame["status"] == "ok"
            # the done frame carries the unary reply's route metadata
            assert frame["reply"]["model"] == "tiny"
            assert "tokens" not in frame["reply"]
        finally:
            svc.close()

    def test_repoll_any_position_is_byte_identical(self, tiny_model):
        """The resume token in action: after the stream completes, every
        (request_id, position) re-read returns exactly the suffix an
        uninterrupted consumer saw — a client that lost its connection
        (or its reply) resumes with no splice and no gap."""
        cfg, params = tiny_model
        svc, _ = _service(cfg, params)
        try:
            opened = svc.streams.open([5, 9, 3], max_new_tokens=8,
                                      greedy=True)
            rid = opened["request_id"]
            toks, _ = _drain_stream(svc.streams, rid)
            before = _counter(RESUMES)
            for pos in (0, 3, len(toks)):
                frame = svc.streams.poll(rid, pos, wait_s=1.0)
                assert frame["tokens"] == toks[pos:]
                assert frame["done"]
            assert _counter(RESUMES) > before
        finally:
            svc.close()

    def test_poll_past_the_fence_is_rejected(self, tiny_model):
        cfg, params = tiny_model
        svc, _ = _service(cfg, params)
        try:
            opened = svc.streams.open([5, 9, 3], max_new_tokens=4,
                                      greedy=True)
            rid = opened["request_id"]
            _drain_stream(svc.streams, rid)
            with pytest.raises(ValueError, match="past the fence"):
                svc.streams.poll(rid, 999, wait_s=0.1)
        finally:
            svc.close()

    def test_unknown_stream_is_not_found(self, tiny_model):
        cfg, params = tiny_model
        svc, _ = _service(cfg, params)
        try:
            with pytest.raises(KeyError):
                svc.streams.poll("stream-nope", 0, wait_s=0.1)
        finally:
            svc.close()

    def test_keepalive_carries_the_queued_phase(self, tiny_model):
        """A keepalive frame distinguishes a stalled engine from a
        request that simply has not started: while slot-starved, the
        frame says ``queued``; once decoding it says ``decode``."""
        cfg, params = tiny_model
        svc, engine = _service(cfg, params, slots=1)
        try:
            first = svc.streams.open([5, 9], max_new_tokens=120,
                                     greedy=True)
            # wait until the first request actually holds the slot
            deadline = time.monotonic() + 30
            while not any(r is not None for r in engine._active):
                assert time.monotonic() < deadline
                time.sleep(0.005)
            second = svc.streams.open([6, 1], max_new_tokens=4,
                                      greedy=True)
            frame = svc.streams.poll(second["request_id"], 0,
                                     wait_s=0.05)
            assert frame["keepalive"] and frame["phase"] == "queued"
            svc.streams.cancel(first["request_id"])
            toks, done = _drain_stream(svc.streams,
                                       second["request_id"])
            assert done["status"] == "ok" and len(toks) == 4
        finally:
            svc.close()

    def test_fast_admission_errors_surface_on_open(self, tiny_model):
        from lzy_tpu.serving.scheduler import PromptTooLong

        cfg, params = tiny_model
        svc, _ = _service(cfg, params)
        try:
            with pytest.raises(PromptTooLong):
                svc.streams.open([5, 9], max_new_tokens=100000)
            assert svc.streams.sessions() == []     # nothing leaked
        finally:
            svc.close()

    def test_session_cap_sheds_opens(self, tiny_model):
        from lzy_tpu.rpc.core import Unavailable

        cfg, params = tiny_model
        svc, _ = _service(cfg, params, slots=1)
        svc.streams.max_sessions = 1
        try:
            svc.streams.open([5, 9], max_new_tokens=120, greedy=True)
            with pytest.raises(Unavailable, match="retry_after_s"):
                svc.streams.open([6, 1], max_new_tokens=4)
        finally:
            svc.close()


# -- client-disconnect reaping ------------------------------------------------

class TestClientDisconnect:
    def test_queued_dead_client_never_occupies_a_slot(self, tiny_model):
        """The satellite fix: a request whose client disconnected while
        still QUEUED is reaped in place by ``RequestQueue.reap_dead``'s
        liveness check — previously only deadline reaping covered it,
        so a dead client's request would eventually burn a slot."""
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=1)   # synchronous
        occupant = engine.submit([5, 9], max_new_tokens=60, greedy=True)
        ghost = engine.submit([6, 1], max_new_tokens=60, greedy=True,
                              tenant="ghost", liveness=lambda: False)
        for _ in range(8):
            engine.step()
            assert engine._active[0] is not ghost, \
                "dead client occupied a slot"
        assert ghost.done and ghost.status == "cancelled"
        assert "disconnected" in ghost.error
        assert not occupant.done or occupant.status == "ok"
        row = engine.stats_by_tenant()["ghost"]
        assert row["requests_cancelled"] == 1    # counted exactly once
        occupant.cancel()
        engine.close()

    def test_slot_resident_disconnect_evicted_within_one_round(
            self, tiny_model):
        """Mid-decode disconnect: the next scheduling round frees the
        slot and every KV block; pool invariants audit clean."""
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2,
                                      page_size=PAGE)
        alive = {"v": True}
        req = engine.submit([5, 9, 3], max_new_tokens=120, greedy=True,
                            tenant="flaky", liveness=lambda: alive["v"])
        rounds = 0
        while len(req.tokens) < 3:
            engine.step()
            rounds += 1
            assert rounds < 300
        slot = engine._active.index(req)
        assert engine._slot_blocks[slot], "expected resident blocks"
        alive["v"] = False
        engine.step()                       # ONE round reaps it
        assert req.done and req.status == "cancelled"
        assert engine._active[slot] is None
        assert engine._slot_blocks[slot] == []
        audit_engine(engine)
        assert engine.stats_by_tenant()["flaky"]["requests_cancelled"] \
            == 1
        engine.close()

    def test_stale_stream_session_reaps_by_poll_cadence(self, tiny_model):
        """End to end: a stream nobody polls counts as a disconnected
        client after ``liveness_timeout_s`` and the engine evicts it."""
        cfg, params = tiny_model
        svc, engine = _service(cfg, params)
        svc.streams.liveness_timeout_s = 0.2
        try:
            opened = svc.streams.open([5, 9], max_new_tokens=200,
                                      greedy=True)
            sess = svc.streams._get(opened["request_id"])
            deadline = time.monotonic() + 30
            while not sess.channel.closed:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert sess.channel.status == "cancelled"
            assert "disconnected" in (sess.dead_reason or "")
            time.sleep(0.1)
            assert all(r is None for r in engine._active)
        finally:
            svc.close()

    def test_parked_poll_counts_as_liveness(self):
        """A poll BLOCKED in the long-poll wait is a live connection:
        wait_s may exceed the liveness window without the actively
        waiting client's request being reaped as disconnected. Driven
        against a fake service whose generate probes liveness every
        round (exactly the engine reaper's cadence) while producing
        nothing for a while — a long prefill."""

        class _SlowPrefill:
            model_name = "fake"

            def generate(self, prompt, stream=None, liveness=None,
                         **kw):
                deadline = time.monotonic() + 0.9
                while time.monotonic() < deadline:
                    if not liveness():
                        stream.close("cancelled")
                        return {"status": "cancelled", "tokens": []}
                    time.sleep(0.01)
                stream.publish(0, [1, 2])
                stream.close("ok")
                return {"status": "ok", "tokens": [1, 2],
                        "request_id": "r-1"}

        mgr = StreamSessionManager(_SlowPrefill(),
                                   liveness_timeout_s=0.25)
        opened = mgr.open([1], max_new_tokens=2, greedy=True)
        rid = opened["request_id"]
        # park 0.6s — past the 0.25s liveness window — while nothing
        # is produced: the parked poll must keep the request alive
        frame = mgr.poll(rid, 0, wait_s=0.6)
        assert frame["keepalive"], frame
        sess = mgr._get(rid)
        assert sess.dead_reason is None
        toks, done = _drain_stream(mgr, rid, wait_s=0.6)
        assert done["status"] == "ok" and toks == [1, 2]

    def test_broken_liveness_probe_never_cancels(self, tiny_model):
        """A RAISING probe is detached and treated as alive — a bug in
        the streaming layer must not kill a healthy request (the
        deadline still bounds it)."""
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=1)

        def boom():
            raise RuntimeError("probe bug")

        req = engine.submit([5, 9], max_new_tokens=4, greedy=True,
                            liveness=boom)
        for _ in range(60):
            if req.done:
                break
            engine.step()
        assert req.done and req.status == "ok"
        assert req.liveness is None          # detached after one raise
        engine.close()


# -- bounded buffers: slow-consumer shed --------------------------------------

class TestSlowConsumerShed:
    def test_stalled_consumer_is_shed_not_buffered(self, tiny_model):
        cfg, params = tiny_model
        svc, engine = _service(cfg, params)
        svc.streams.ack_window = 4
        # short grace: with a warm XLA compilation cache the tiny model
        # decodes ~1ms/token, and a 0.2s grace let the 200-token request
        # FINISH before the stall window elapsed (the shed never fired
        # and the test flaked fast-machine-dependently); 0.05s still
        # spans dozens of decode rounds past the ack window
        svc.streams.stall_grace_s = 0.05
        try:
            before = _counter(SHED_SLOW)
            opened = svc.streams.open([5, 9], max_new_tokens=200,
                                      greedy=True)
            rid = opened["request_id"]
            # one poll keeps the client "connected" but acks nothing
            # beyond position 0 — the producer runs ahead of the window
            svc.streams.poll(rid, 0, wait_s=0.5)
            sess = svc.streams._get(rid)
            deadline = time.monotonic() + 30
            while not sess.channel.closed:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert _counter(SHED_SLOW) == before + 1
            assert "slow consumer" in (sess.dead_reason or "")
            # the shed frees the slot like any cancel
            time.sleep(0.1)
            assert all(r is None for r in engine._active)
            # the terminal frame names the shed for the (slow) client
            frame = svc.streams.poll(rid, sess.channel.position,
                                     wait_s=1.0)
            assert frame["done"] and frame["status"] == "cancelled"
            assert "slow consumer" in (frame["error"] or "")
        finally:
            svc.close()


# -- cancellation in every phase ----------------------------------------------

class TestCancelPhases:
    def _deltas(self):
        return {phase: _counter(CANCELS, phase=phase)
                for phase in ("queued", "prefill", "decode", "failover")}

    def test_cancel_queued_paged(self, tiny_model):
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=1,
                                      page_size=PAGE)
        before = self._deltas()
        occupant = engine.submit([5, 9], max_new_tokens=60, greedy=True)
        victim = engine.submit([6, 1], max_new_tokens=60, greedy=True,
                               liveness=lambda: True)
        engine.step()
        victim.cancel()
        engine.step()
        assert victim.done and victim.status == "cancelled"
        audit_engine(engine)
        after = self._deltas()
        assert after["queued"] == before["queued"] + 1
        assert after["decode"] == before["decode"]
        occupant.cancel()
        engine.close()

    def test_cancel_mid_prefill_releases_staged_blocks(self, tiny_model):
        """Chunked prefill holds a staged job across rounds; a cancel
        mid-job releases every staged block (pool conservation audited)
        and counts under the ``prefill`` phase."""
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=1,
                                      page_size=PAGE,
                                      prefill_chunk=PAGE,
                                      prefill_budget=PAGE)
        before = self._deltas()
        prompt = [(i * 7) % 50 + 1 for i in range(6 * PAGE)]
        req = engine.submit(prompt, max_new_tokens=8, greedy=True,
                            liveness=lambda: True)
        engine.step()                     # stages + first budget round
        assert engine.prefill.jobs, "job should be staged"
        req.cancel()
        engine.step()
        assert req.done and req.status == "cancelled"
        assert not engine.prefill.jobs
        audit_engine(engine)
        after = self._deltas()
        assert after["prefill"] == before["prefill"] + 1
        engine.close()

    def test_cancel_mid_decode_frees_blocks_one_round(self, tiny_model):
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2,
                                      page_size=PAGE)
        before = self._deltas()
        req = engine.submit([5, 9, 3], max_new_tokens=120, greedy=True,
                            liveness=lambda: True)
        while len(req.tokens) < 2:
            engine.step()
        req.cancel()
        engine.step()
        assert req.done and req.status == "cancelled"
        assert all(r is None for r in engine._active)
        audit_engine(engine)
        after = self._deltas()
        assert after["decode"] == before["decode"] + 1
        engine.close()

    def test_cancel_queued_and_decoding_in_one_round(self, tiny_model):
        """A queued and a decoding request cancelled before the same
        round: both land in it, each under its own phase, and the pool
        comes out whole."""
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=1,
                                      page_size=PAGE)
        before = self._deltas()
        occupant = engine.submit([5, 9], max_new_tokens=60, greedy=True,
                                 liveness=lambda: True)
        queued = engine.submit([6, 1], max_new_tokens=60, greedy=True,
                               liveness=lambda: True)
        while len(occupant.tokens) < 2:
            engine.step()
        queued.cancel()
        occupant.cancel()
        engine.step()
        assert queued.status == "cancelled"
        assert occupant.status == "cancelled"
        audit_engine(engine)
        after = self._deltas()
        assert after["queued"] == before["queued"] + 1
        assert after["decode"] == before["decode"] + 1
        engine.close()

    def test_cancel_mid_failover_short_circuits(self, tiny_model):
        """InferCancel landing while the gateway is BETWEEN attempts
        (the replica died, the retry has not been submitted): the
        gateway finishes with the cancelled contract — fenced partials
        readable — instead of resubmitting, and the cancel counts under
        the ``failover`` phase."""
        from lzy_tpu.channels.token_stream import TokenStreamChannel

        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        before = self._deltas()
        alive = {"v": True}
        ch = TokenStreamChannel()
        result = {}

        def run():
            try:
                result["reply"] = gw.generate(
                    [7, 2, 8, 1], max_new_tokens=48, greedy=True,
                    timeout_s=120, stream=ch,
                    liveness=lambda: alive["v"])
            except BaseException as e:  # noqa: BLE001
                result["err"] = e

        t = threading.Thread(target=run)
        t.start()
        try:
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
            assert victim is not None, "never reached mid-decode"

            def boom():
                raise RuntimeError("replica host on fire")

            # kill the replica FIRST (its loop can no longer reap), then
            # drop the client: the gateway hits the failover path and
            # must not resubmit the corpse
            victim.engine.step = boom
            alive["v"] = False
            t.join(60)
            assert "err" not in result, result.get("err")
            reply = result["reply"]
            assert reply["status"] == "cancelled"
            assert ch.status == "cancelled"
            # fenced partials delivered, never duplicated
            oracle = _oracle_tokens(cfg, params, [7, 2, 8, 1], 48)
            assert reply["tokens"] == oracle[:len(reply["tokens"])]
            assert ch.tokens() == reply["tokens"]
            after = self._deltas()
            assert after["failover"] == before["failover"] + 1
        finally:
            gw.close()

    def test_cancel_on_disagg_plane_audits_clean(self, tiny_model):
        """Mid-stream cancel through the two-pool plane: decode slot
        and KV blocks released, both pools' invariants clean, shed
        counters unmoved (a cancel is not a shed)."""
        from lzy_tpu.gateway.disagg import DisaggGatewayService
        from lzy_tpu.serving import DecodeEngine, PrefillEngine

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
            prefill_router=PrefixAffinityRouter(PAGE),
            model_name="tiny")
        decode_fleet.add_replica()
        prefill_fleet.add_replica()
        try:
            opened = gw.streams.open(
                [(i * 3) % 50 + 1 for i in range(2 * PAGE)] + [9],
                max_new_tokens=200, greedy=True)
            rid = opened["request_id"]
            frame = gw.streams.poll(rid, 0, wait_s=5.0)
            pos = len(frame["tokens"])
            assert not frame["done"]
            gw.streams.cancel(rid)
            toks, done = _drain_stream(gw.streams, rid, start=pos)
            assert done["status"] == "cancelled"
            time.sleep(0.2)
            for fleet in (decode_fleet, prefill_fleet):
                for replica in fleet.replicas():
                    assert all(r is None
                               for r in replica.engine._active)
                    audit_engine(replica.engine)
        finally:
            gw.close()


# -- chaos: the new fault points ----------------------------------------------

@pytest.mark.chaos
class TestStreamChaos:
    def test_fixed_seed_rpc_stream_faults_survived(self, tiny_model):
        """Faults at ``rpc.stream`` (frame drop/delay) during a streamed
        generation over the REAL wire: the client's poll retry resumes
        at the fence position and the delivered sequence is
        byte-identical to the oracle."""
        import tempfile

        from lzy_tpu.channels.token_stream import TokenStreamChannel
        from lzy_tpu.rpc import RpcInferenceClient
        from lzy_tpu.service import InProcessCluster

        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2).start()
        tmp = tempfile.mkdtemp()
        cluster = InProcessCluster(
            db_path=f"{tmp}/meta.db", storage_uri=f"file://{tmp}/s",
            worker_mode="process",
            inference_service=InferenceService(engine,
                                               model_name="tiny"))
        plan = CHAOS.arm(FaultPlan(
            20260805, rate=0.4, modes=(ERROR, DELAY),
            points=("rpc.stream",), max_faults=4))
        try:
            client = RpcInferenceClient(cluster.rpc_server.address)
            ch = TokenStreamChannel()
            reply = client.generate([5, 9, 3], max_new_tokens=16,
                                    greedy=True, stream=ch)
            oracle = _oracle_tokens(cfg, params, [5, 9, 3], 16)
            assert reply["tokens"] == oracle
            assert ch.tokens() == oracle and ch.status == "ok"
            assert plan.fired > 0, plan.describe()
            client.close()
        finally:
            CHAOS.disarm()
            cluster.shutdown()

    def test_fixed_seed_consumer_death_reaps_within_round(
            self, tiny_model):
        """``stream.consumer`` error mode is the client dying mid-poll:
        the session flips dead and the engine evicts the request —
        slot free, pool clean — within one decode round."""
        cfg, params = tiny_model
        svc, engine = _service(cfg, params)
        plan = CHAOS.arm(FaultPlan(
            7, rate=1.0, modes=(ERROR,), points=("stream.consumer",),
            max_faults=1))
        try:
            opened = svc.streams.open([5, 9], max_new_tokens=200,
                                      greedy=True)
            rid = opened["request_id"]
            with pytest.raises(ConsumerGone):
                svc.streams.poll(rid, 0, wait_s=1.0)
            sess = svc.streams._get(rid)
            deadline = time.monotonic() + 30
            while not sess.channel.closed:
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert sess.channel.status == "cancelled"
            time.sleep(0.1)
            assert all(r is None for r in engine._active)
            audit_engine(engine)
            assert plan.fired == 1
        finally:
            CHAOS.disarm()
            svc.close()

    def test_replica_death_mid_stream_resumes_byte_identical(
            self, tiny_model):
        """The acceptance headline: kill the serving replica mid-stream
        and the long-poll consumer sees a byte-identical sequence — the
        fence is the wire position, verified by the channel's splice
        gate and the fence auditor."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=3)
        gw.fence_auditor = FenceAuditor()
        try:
            opened = gw.streams.open([7, 2, 8, 1], max_new_tokens=24,
                                     greedy=True, timeout_s=120)
            rid = opened["request_id"]
            got = []
            killed = False
            pos = 0
            deadline = time.monotonic() + 90
            while True:
                frame = gw.streams.poll(rid, pos, wait_s=1.0)
                got.extend(frame["tokens"])
                pos += len(frame["tokens"])
                if not killed and len(got) >= 3:
                    # kill whichever replica currently decodes it
                    for replica in fleet.replicas():
                        if any(r is not None
                               for r in replica.engine._active):
                            def boom():
                                raise RuntimeError("host on fire")
                            replica.engine.step = boom
                            killed = True
                            break
                if frame["done"]:
                    break
                assert time.monotonic() < deadline
            assert killed, "request finished before the kill"
            oracle = _oracle_tokens(cfg, params, [7, 2, 8, 1], 24)
            assert got == oracle
            assert frame["status"] == "ok"
            assert frame["resumptions"] == 1
            assert frame["reply"]["failovers"] == 1
            assert gw.fence_auditor.completions_seen >= 1
        finally:
            gw.close()


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.skipif(not os.environ.get("LZY_SLOW"),
                    reason="streaming chaos soak: set LZY_SLOW=1")
class TestStreamingSoak:
    def test_streaming_soak_with_fence_auditors(self, tiny_model):
        """LZY_SLOW soak: a batch of streamed generations through the
        gateway with faults armed at rpc.stream + stream.consumer +
        engine.step — every surviving stream byte-identical to the
        oracle, every killed one cleanly cancelled, auditors clean
        after each, and the fleet fully recovered at the end."""
        from tests.conftest import record_tier_run

        from lzy_tpu.gateway import Autoscaler

        cfg, params = tiny_model
        seed = int(os.environ.get("LZY_CHAOS_SEED", "20260806"))
        fleet = ReplicaFleet(
            lambda: PagedInferenceEngine(cfg, params, slots=2,
                                         page_size=PAGE))
        gw = GatewayService(
            fleet, router=PrefixAffinityRouter(PAGE),
            # self-healing floor: a chaos-killed replica is re-leased by
            # the tick, so the soak exercises recovery, not extinction
            autoscaler=Autoscaler(min_replicas=2, max_replicas=3),
            model_name="tiny")
        for _ in range(2):
            fleet.add_replica()
        gw.fence_auditor = FenceAuditor()
        plan = CHAOS.arm(FaultPlan(
            seed, rate=0.1, modes=(ERROR, DELAY),
            points=("rpc.stream", "stream.consumer", "engine.step"),
            max_faults=3))
        ok = cancelled = 0
        try:
            for i in range(12):
                prompt = [7, 2, (i * 5) % 50 + 1]
                n = 10 + (i % 4)
                opened = None
                for _ in range(20):
                    try:
                        opened = gw.streams.open(
                            prompt, max_new_tokens=n, greedy=True,
                            timeout_s=120)
                        break
                    except Exception:  # noqa: BLE001 — shed, retry
                        gw.tick()
                        time.sleep(0.02)
                assert opened is not None, f"request {i} shed forever"
                rid = opened["request_id"]
                got, pos, frame = [], 0, None
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    try:
                        frame = gw.streams.poll(rid, pos, wait_s=1.0)
                    except ConsumerGone:
                        continue     # the server killed us; read tail
                    except ConnectionError:
                        continue     # dropped frame: re-poll the fence
                    got.extend(frame["tokens"])
                    pos += len(frame["tokens"])
                    if frame["done"]:
                        break
                assert frame is not None and frame["done"]
                oracle = _oracle_tokens(cfg, params, prompt, n)
                if frame["status"] == "ok":
                    assert got == oracle, f"request {i} diverged"
                    ok += 1
                else:
                    # cancelled (consumer killed) or error (the whole
                    # fleet was momentarily dead): the delivered prefix
                    # must still be fenced — never spliced, never wrong
                    assert frame["status"] in ("cancelled", "error")
                    assert got == oracle[:len(got)], \
                        f"request {i} spliced"
                    cancelled += 1
                gw.tick()
                for replica in fleet.replicas():
                    audit_engine(replica.engine)
            CHAOS.disarm()
            final = gw.streams.open([7, 2, 63], max_new_tokens=8,
                                    greedy=True)
            got, frame = _drain_stream(gw.streams,
                                       final["request_id"])
            assert got == _oracle_tokens(cfg, params, [7, 2, 63], 8)
            assert ok >= 6, (ok, cancelled)
            record_tier_run("slow:stream_soak",
                            f"seed={seed} ok={ok} "
                            f"cancelled={cancelled} "
                            f"fired={plan.fired}")
        except AssertionError as e:
            pytest.fail(
                f"streaming soak seed {seed} failed: {e}\n--- replay "
                f"---\nLZY_CHAOS_SEED={seed} LZY_SLOW=1 pytest "
                f"tests/test_streaming.py -k soak\n{plan.describe()}")
        finally:
            CHAOS.disarm()
            gw.close()


# -- the wire surface ---------------------------------------------------------

class TestRpcStreamDelivery:
    @pytest.fixture()
    def cluster(self, tiny_model, tmp_path):
        from lzy_tpu.service import InProcessCluster

        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2).start()
        cluster = InProcessCluster(
            db_path=str(tmp_path / "meta.db"),
            storage_uri=f"file://{tmp_path}/storage",
            worker_mode="process",
            inference_service=InferenceService(engine,
                                               model_name="tiny"))
        cluster._test_engine = engine
        try:
            yield cluster
        finally:
            cluster.shutdown()

    def test_streamed_generate_matches_unary(self, tiny_model, cluster):
        from lzy_tpu.channels.token_stream import TokenStreamChannel
        from lzy_tpu.rpc import RpcInferenceClient

        cfg, params = tiny_model
        client = RpcInferenceClient(cluster.rpc_server.address)
        try:
            ch = TokenStreamChannel()
            reply = client.generate([5, 9, 3], max_new_tokens=12,
                                    greedy=True, stream=ch)
            oracle = _oracle_tokens(cfg, params, [5, 9, 3], 12)
            assert reply["tokens"] == oracle
            assert reply["status"] == "ok" and reply["model"] == "tiny"
            assert ch.tokens() == oracle and ch.status == "ok"
        finally:
            client.close()

    def test_connection_death_resumes_from_position(self, tiny_model,
                                                    cluster):
        """Kill the client's CONNECTION mid-stream: a brand-new client
        resumes from the last consumed position and the concatenation
        is byte-identical to an uninterrupted run."""
        from lzy_tpu.rpc import RpcInferenceClient

        cfg, params = tiny_model
        client = RpcInferenceClient(cluster.rpc_server.address)
        opened = client.stream_open([5, 9, 3], max_new_tokens=12,
                                    greedy=True)
        rid = opened["request_id"]
        frame = client.stream_poll(rid, 0, wait_s=2.0)
        got = list(frame["tokens"])
        client.close()                      # the connection dies
        client2 = RpcInferenceClient(cluster.rpc_server.address)
        try:
            pos = len(got)
            for frame in client2.iter_stream(rid, pos):
                got.extend(frame["tokens"])
            assert got == _oracle_tokens(cfg, params, [5, 9, 3], 12)
        finally:
            client2.close()

    def test_infer_cancel_frees_within_one_round(self, tiny_model,
                                                 cluster):
        from lzy_tpu.rpc import RpcInferenceClient

        client = RpcInferenceClient(cluster.rpc_server.address)
        try:
            opened = client.stream_open([5, 9], max_new_tokens=200,
                                        greedy=True)
            rid = opened["request_id"]
            frame = client.stream_poll(rid, 0, wait_s=2.0)
            client.cancel(rid)
            pos = len(frame["tokens"])
            for frame in _frames(client, rid, pos):
                if frame["done"]:
                    break
            assert frame["status"] == "cancelled"
            deadline = time.monotonic() + 10
            engine = cluster._test_engine
            while any(r is not None for r in engine._active):
                assert time.monotonic() < deadline
                time.sleep(0.02)
        finally:
            client.close()


def _frames(client, rid, pos):
    while True:
        frame = client.stream_poll(rid, pos, wait_s=2.0)
        yield frame
        pos += len(frame["tokens"])
        if frame["done"]:
            return

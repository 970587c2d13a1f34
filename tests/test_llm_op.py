"""Workflow-native LLM inference (``lzy_tpu/llm`` + token streams).

The acceptance properties this file pins:

- a multi-step workflow (``generate → plain op → generate``) through the
  gateway is greedy **bit-identical** to the monolithic ``generate()``
  oracle;
- a cached ``llm_op`` re-execution **skips the fleet entirely**;
- a ``TokenStreamChannel`` resumes **byte-identically** across an
  injected mid-stream replica death (the fence IS the stream position);
- conversation-affinity routing measurably **beats round-robin** on
  aggregate radix prefix hit rate;
- generations round-trip the whiteboard index as versioned fields.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu import Lzy, llm, op
from lzy_tpu.channels.token_stream import (
    STREAMS, StorageTokenStreamReader, StorageTokenStreamWriter,
    StreamFailed, StreamSpliceError, TokenStreamChannel)
from lzy_tpu.gateway import (
    GatewayService, PrefixAffinityRouter, ReplicaFleet, RoundRobinRouter)
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate as oracle_generate
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig
from lzy_tpu.storage.registry import client_for

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


@pytest.fixture(autouse=True)
def _clean_backend():
    yield
    llm.configure(None)


def _oracle_tokens(cfg, params, prompt_ids, n, **kw):
    out = oracle_generate(cfg, params,
                          jnp.asarray([prompt_ids], jnp.int32),
                          max_new_tokens=n, **kw)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _make_gateway(cfg, params, *, replicas=2, slots=2, router=None,
                  **engine_kw):
    def factory():
        return PagedInferenceEngine(cfg, params, slots=slots,
                                    page_size=PAGE, **engine_kw)

    fleet = ReplicaFleet(factory)
    gw = GatewayService(fleet,
                        router=router or PrefixAffinityRouter(PAGE),
                        model_name="tiny")
    for _ in range(replicas):
        fleet.add_replica()
    return gw, fleet


def _local_lzy(uri: str) -> Lzy:
    reg = DefaultStorageRegistry()
    reg.register_storage("default", StorageConfig(uri=uri), default=True)
    return Lzy(storage_registry=reg)


# -- token stream channel -----------------------------------------------------

class TestTokenStreamChannel:
    def test_positioned_publish_dedupes_and_appends(self):
        ch = TokenStreamChannel()
        ch.publish(0, [1, 2, 3])
        ch.publish(0, [1, 2, 3, 4])       # overlap verified, 4 appended
        ch.publish(4, [5])
        assert ch.tokens() == [1, 2, 3, 4, 5]
        ch.publish(2, [3, 4, 5])          # full duplicate: no-op
        assert ch.tokens() == [1, 2, 3, 4, 5]

    def test_gap_and_divergence_raise(self):
        ch = TokenStreamChannel()
        ch.publish(0, [1, 2])
        with pytest.raises(StreamSpliceError):
            ch.publish(3, [9])            # gap
        with pytest.raises(StreamSpliceError):
            ch.publish(0, [1, 9])         # fence violation
        assert ch.tokens() == [1, 2]      # stream unharmed

    def test_iteration_sees_every_token_once_then_terminates(self):
        ch = TokenStreamChannel()
        got = []

        def consume():
            for tok in ch:
                got.append(tok)

        t = threading.Thread(target=consume)
        t.start()
        for i in range(5):
            ch.publish(i, [i * 10])
        ch.close("ok")
        t.join(10)
        assert got == [0, 10, 20, 30, 40]
        assert ch.status == "ok"

    def test_failed_stream_raises_for_consumers(self):
        ch = TokenStreamChannel()
        ch.publish(0, [1])
        ch.fail("replica on fire")
        with pytest.raises(StreamFailed):
            list(iter(ch))
        with pytest.raises(StreamFailed):
            ch.read(1, timeout_s=1)

    def test_read_returns_suffix_and_respects_close(self):
        ch = TokenStreamChannel()
        ch.publish(0, [1, 2, 3])
        assert ch.read(1) == [2, 3]
        ch.close("ok")
        assert ch.read(3) == []           # closed, nothing past 3

    def test_registry_rendezvous(self):
        ch = STREAMS.get_or_create("t-reg-1")
        assert STREAMS.get_or_create("t-reg-1") is ch
        assert STREAMS.get("t-reg-1") is ch
        STREAMS.release("t-reg-1")
        assert STREAMS.get("t-reg-1") is None

    def test_storage_spill_round_trip(self):
        client = client_for(StorageConfig(uri="mem://tokspill"))
        w = StorageTokenStreamWriter(client, "mem://tokspill/s1",
                                     chunk_tokens=4)
        w.append([1, 2, 3, 4, 5])         # one full chunk + tail
        w.append([6])
        w.finish("ok")
        r = StorageTokenStreamReader(client, "mem://tokspill/s1")
        doc = r.read_all(timeout_s=5)
        assert doc["tokens"] == [1, 2, 3, 4, 5, 6]
        assert doc["status"] == "ok"
        assert list(StorageTokenStreamReader(
            client, "mem://tokspill/s1").iter_tokens(timeout_s=5)) == \
            [1, 2, 3, 4, 5, 6]

    def test_storage_spill_failure_surfaces(self):
        client = client_for(StorageConfig(uri="mem://tokspill"))
        w = StorageTokenStreamWriter(client, "mem://tokspill/s2")
        w.append([7])
        w.finish("error", error="boom")
        with pytest.raises(StreamFailed):
            StorageTokenStreamReader(
                client, "mem://tokspill/s2").read_all(timeout_s=5)

    def test_stalled_spill_mirror_commits_error_not_truncated_ok(self):
        """If the spill mirror thread outlives the join budget, the
        manifest must record an error — never an 'ok' with fewer tokens
        than the stream carried (a reader would trust the truncation)."""
        from lzy_tpu.llm.op import _finish_spill

        client = client_for(StorageConfig(uri="mem://tokspill"))
        ch = TokenStreamChannel()
        ch.publish(0, [1, 2, 3])
        ch.close("ok")
        w = StorageTokenStreamWriter(client, "mem://tokspill/s3")
        w.append([1])                      # mirror fell behind

        class StalledThread:
            def join(self, timeout=None):
                pass

            def is_alive(self):
                return True

        _finish_spill(ch, w, StalledThread())
        with pytest.raises(StreamFailed, match="stalled"):
            StorageTokenStreamReader(
                client, "mem://tokspill/s3").read_all(timeout_s=5)


# -- direct (workflow-less) surface ------------------------------------------

class TestDirectGenerate:
    def test_direct_call_hits_engine_and_streams(self, tiny_model):
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2).start()
        try:
            llm.configure(llm.EngineBackend(engine, model_name="tiny"))
            ch = TokenStreamChannel()
            g = llm.generate([7, 2, 8, 1], max_new_tokens=6,
                             greedy=True, stream=ch)
            assert isinstance(g, llm.Generation)
            oracle = _oracle_tokens(cfg, params, [7, 2, 8, 1], 6)
            assert g.tokens == oracle
            assert ch.tokens() == oracle and ch.status == "ok"
            assert g.full_tokens() == [7, 2, 8, 1] + oracle
        finally:
            engine.close()

    def test_batch_fans_out_one_node(self, tiny_model):
        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2).start()
        try:
            llm.configure(llm.EngineBackend(engine, model_name="tiny"))
            prompts = [[5, 9, 3], [7, 2, 8, 1]]
            out = llm.generate_batch(prompts, max_new_tokens=4,
                                     greedy=True)
            assert [g.tokens for g in out] == [
                _oracle_tokens(cfg, params, p, 4) for p in prompts]
        finally:
            engine.close()


class TestServiceBackendDegradation:
    def test_session_survives_a_surface_without_stream_or_token(self):
        """RpcInferenceClient's shape: takes session, not stream/token.
        The backend must deliver the session hint instead of letting a
        None-valued extension force the degraded (hint-dropping) path —
        this is what makes conversation affinity work over the wire."""
        calls = {}

        class RpcLike:
            def generate(self, prompt, *, max_new_tokens=64,
                         timeout_s=None, deadline_s=None, greedy=None,
                         tenant=None, priority=None, session=None):
                calls["session"] = session
                return {"tokens": [1], "status": "ok"}

        b = llm.ServiceBackend(RpcLike(), digest="d")
        reply = b.generate([1, 2], max_new_tokens=2, timeout_s=5,
                           deadline_s=None, greedy=True, tenant=None,
                           priority=None, session="conv-1", stream=None)
        assert reply["status"] == "ok"
        assert calls["session"] == "conv-1"

    def test_legacy_surface_gets_terminal_stream_flush(self):
        """A pre-session surface: extensions strip one at a time and an
        attached stream still terminates with the full token sequence."""

        class Legacy:
            def generate(self, prompt, *, max_new_tokens=64,
                         timeout_s=None, deadline_s=None, greedy=None,
                         tenant=None, priority=None):
                return {"tokens": [4, 5], "status": "ok"}

        ch = TokenStreamChannel()
        b = llm.ServiceBackend(Legacy(), digest="d")
        reply = b.generate([1], max_new_tokens=2, session="s", stream=ch)
        assert reply["tokens"] == [4, 5]
        assert ch.tokens() == [4, 5] and ch.status == "ok"


# -- workflow pipeline vs the oracle -----------------------------------------

@op
def tool_extend(g: llm.Generation, extra: list) -> list:
    """The 'tool' step of an agent pipeline: fold the generation back
    into the next prompt."""
    return g.full_tokens() + list(extra)


class TestWorkflowPipeline:
    def test_three_step_conversation_bit_identical_and_pinned(
            self, tiny_model):
        """generate → tool op → generate → tool op → generate through a
        2-replica gateway: every step bit-identical to the monolithic
        oracle, and the conversation pinned to ONE replica by session
        affinity."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-e2e-pipeline")
            conv = llm.Conversation("pipeline-conv")
            with lzy.workflow("agent") as wf:
                p1 = list(range(16)) + [3]
                g1 = llm.generate(p1, max_new_tokens=5, greedy=True,
                                  conversation=conv)
                p2 = tool_extend(g1, [41, 42])
                g2 = llm.generate(p2, max_new_tokens=5, greedy=True,
                                  conversation=conv)
                p3 = tool_extend(g2, [43])
                g3 = llm.generate(p3, max_new_tokens=5, greedy=True,
                                  conversation=conv)
                wb = llm.record_generation(wf, g3, conversation=conv)
            # (a) bit-identity vs the monolithic oracle at every step
            e1 = _oracle_tokens(cfg, params, p1, 5)
            full2 = p1 + e1 + [41, 42]
            e2 = _oracle_tokens(cfg, params, full2, 5)
            full3 = full2 + e2 + [43]
            e3 = _oracle_tokens(cfg, params, full3, 5)
            assert g1.tokens == e1
            assert g2.tokens == e2 and g2.prompt == full2
            assert g3.tokens == e3 and g3.prompt == full3
            # (b) the fused op chain kept the conversation on one
            # replica: after each step the workflow scheduler parks the
            # conversation's KV there, so steps 2 and 3 HARD-pin to the
            # leased replica (routed_by "fused" supersedes the session
            # hint; a lapsed lease falls back to "session")
            assert g1.replica == g2.replica == g3.replica
            assert g2.routed_by in ("fused", "session")
            assert g3.routed_by in ("fused", "session")
            router = gw.router.stats()
            # step 1 has no pin yet; steps 2+3 route pinned either way
            assert router["session_routed"] + \
                router.get("fused_routed", 0) == 2
            # (c) the recorded generation round-trips the index
            found = lzy.whiteboards(name=llm.GENERATION_WB_NAME,
                                    tags=[f"conversation:{conv.id}"])
            assert [w.id for w in found] == [wb.id]
            assert found[0].tokens == e3
            assert found[0].prompt == full3
            assert found[0].model_digest == g3.model_digest
            assert found[0].provenance["replica"] == g3.replica
            assert found[0].provenance["step"] == 3
        finally:
            gw.close()

    def _drive_conversations(self, cfg, params, router):
        """The affinity-vs-round-robin workload: THREE interleaved
        3-step conversations through a 2-replica paged gateway, via the
        workflow surface (3 on 2 so round-robin cannot accidentally
        alias into perfect affinity). Returns the fleet-aggregate radix
        hit rate."""
        gw, fleet = _make_gateway(cfg, params, replicas=2, router=router)
        try:
            llm.configure(gw)
            lzy = _local_lzy(f"mem://llm-aff-{type(router).__name__}")
            convs = [llm.Conversation(f"aff-{i}") for i in range(3)]
            bases = [list(range(16)), list(range(30, 46)),
                     list(range(8, 24))]
            with lzy.workflow("chat") as wf:
                prompts = list(bases)
                for _ in range(3):
                    for i, conv in enumerate(convs):
                        g = llm.generate(prompts[i], max_new_tokens=4,
                                         greedy=True, conversation=conv)
                        prompts[i] = tool_extend(g, [60 + i])
                    wf.barrier()
            agg = fleet.aggregate()
            assert agg["prefix_lookup_tokens"] > 0
            return agg["prefix_hit_tokens"] / agg["prefix_lookup_tokens"]
        finally:
            gw.close()

    def test_conversation_affinity_beats_round_robin(self, tiny_model):
        cfg, params = tiny_model
        affinity = self._drive_conversations(cfg, params,
                                             PrefixAffinityRouter(PAGE))
        rr = self._drive_conversations(cfg, params, RoundRobinRouter())
        assert affinity > rr, (
            f"conversation affinity must raise the aggregate radix hit "
            f"rate over round-robin (affinity {affinity:.3f} vs rr "
            f"{rr:.3f})")


# -- caching ------------------------------------------------------------------

class TestLlmOpCaching:
    def test_cached_rerun_skips_the_fleet(self, tiny_model):
        """Same prompt/params/digest on a second workflow run: the op
        cache satisfies the call and the gateway never sees a request."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-cache")
            from lzy_tpu.llm.metrics import CACHED_HITS

            hits0 = sum(CACHED_HITS._values.values())
            with lzy.workflow("cached"):
                g = llm.generate([5, 9, 3, 1, 2, 6, 7, 4],
                                 max_new_tokens=4, greedy=True)
            first = list(g.tokens)
            served = gw.stats()["requests_finished"]
            assert served == 1
            with lzy.workflow("cached"):
                g2 = llm.generate([5, 9, 3, 1, 2, 6, 7, 4],
                                  max_new_tokens=4, greedy=True)
            assert list(g2.tokens) == first
            assert gw.stats()["requests_finished"] == 1   # fleet skipped
            assert sum(CACHED_HITS._values.values()) == hits0 + 1
        finally:
            gw.close()

    def test_sampled_requests_opt_out_of_the_cache(self, tiny_model):
        """Sampling is a draw, not a function of the inputs: by default
        a non-greedy llm_op re-executes (the fleet is hit again)."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=1,
                                  temperature=0.8, seed=3)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-cache-sampled")
            for _ in range(2):
                with lzy.workflow("sampled"):
                    llm.generate([5, 9, 3, 1, 2, 6, 7, 4],
                                 max_new_tokens=3)
            assert gw.stats()["requests_finished"] == 2
        finally:
            gw.close()

    def test_cancelled_generation_never_poisons_the_cache(self):
        """A deadline-truncated reply (status 'cancelled', partial
        tokens) must NOT be cached: the deadline is excluded from the
        cache key, so a poisoned entry would serve the truncation
        forever — even after the caller raises the deadline."""
        calls = {"n": 0}

        class CancelThenOk:
            def generate(self, prompt, *, max_new_tokens=64,
                         timeout_s=None, deadline_s=None, greedy=None,
                         tenant=None, priority=None, session=None):
                calls["n"] += 1
                if calls["n"] == 1:
                    return {"tokens": [1], "status": "cancelled"}
                return {"tokens": [1, 2, 3, 4], "status": "ok"}

        llm.configure(llm.ServiceBackend(CancelThenOk(), digest="d"))
        lzy = _local_lzy("mem://llm-cache-cancelled")
        with lzy.workflow("doomed"):
            g1 = llm.generate([3, 1, 4], max_new_tokens=4, greedy=True,
                              deadline_s=0.001)
        assert g1.status == "cancelled" and list(g1.tokens) == [1]
        # same key (deadline_s lives in runtime_opts, excluded) — the
        # cancelled result must MISS and the plane must be hit again
        with lzy.workflow("doomed"):
            g2 = llm.generate([3, 1, 4], max_new_tokens=4, greedy=True,
                              deadline_s=60.0)
        assert calls["n"] == 2
        assert g2.status == "ok" and list(g2.tokens) == [1, 2, 3, 4]
        # and a COMPLETE result still caches: third run skips the plane
        with lzy.workflow("doomed"):
            g3 = llm.generate([3, 1, 4], max_new_tokens=4, greedy=True,
                              deadline_s=60.0)
        assert calls["n"] == 2
        assert g3.status == "ok" and list(g3.tokens) == [1, 2, 3, 4]

    def test_model_digest_keys_the_cache(self, tiny_model):
        """A different served model (digest) must MISS a cache entry
        keyed under the old digest — the digest is an op input."""
        cfg, params = tiny_model
        gw, _ = _make_gateway(cfg, params, replicas=1)
        try:
            llm.configure(llm.ServiceBackend(gw, digest="model-A"))
            lzy = _local_lzy("mem://llm-cache-digest")
            with lzy.workflow("dig"):
                llm.generate([9, 8, 7, 6, 5, 4, 3, 2], max_new_tokens=3,
                             greedy=True)
            assert gw.stats()["requests_finished"] == 1
            llm.configure(llm.ServiceBackend(gw, digest="model-B"))
            with lzy.workflow("dig"):
                llm.generate([9, 8, 7, 6, 5, 4, 3, 2], max_new_tokens=3,
                             greedy=True)
            assert gw.stats()["requests_finished"] == 2
        finally:
            gw.close()


# -- streaming through the fleet, including mid-stream death ------------------

class TestStreamedGeneration:
    def test_workflow_stream_delivers_incrementally(self, tiny_model):
        cfg, params = tiny_model
        gw, _ = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-stream")
            ch = TokenStreamChannel()
            got = []
            consumer = threading.Thread(
                target=lambda: got.extend(iter(ch)))
            consumer.start()
            with lzy.workflow("streamed"):
                g = llm.generate([7, 2, 8, 1], max_new_tokens=8,
                                 greedy=True, stream=ch)
                tokens = list(g.tokens)
            consumer.join(30)
            oracle = _oracle_tokens(cfg, params, [7, 2, 8, 1], 8)
            assert tokens == oracle and got == oracle
            assert ch.status == "ok" and ch.resumptions == 0
            # a caller-owned channel is dropped from the rendezvous
            # registry once terminal (the caller holds the object; a
            # long-lived worker must not retain every finished stream)
            assert STREAMS.get(ch.id) is None
        finally:
            gw.close()

    def test_mid_stream_replica_kill_resumes_byte_identically(
            self, tiny_model):
        """Kill the serving replica mid-stream: the gateway fences the
        emitted tokens, the retry resumes the CHANNEL at the fence, and
        the consumer-visible sequence is byte-identical to an
        uninterrupted run (resumptions == 1 is the only trace)."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=3)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-stream-kill")
            ch = TokenStreamChannel()
            result = {}

            def run():
                try:
                    with lzy.workflow("streamed-kill"):
                        g = llm.generate([7, 2, 8, 1],
                                         max_new_tokens=24, greedy=True,
                                         stream=ch, timeout_s=120)
                        result["tokens"] = list(g.tokens)
                        result["failovers"] = g.failovers
                except BaseException as e:  # noqa: BLE001 — main thread
                    result["err"] = e

            t = threading.Thread(target=run)
            t.start()
            victim = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                for replica in fleet.replicas():
                    live = [r for r in replica.engine._active
                            if r is not None]
                    if live and len(live[0].tokens) >= 3:
                        victim = replica
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
            oracle = _oracle_tokens(cfg, params, [7, 2, 8, 1], 24)
            assert result["tokens"] == oracle
            assert result["failovers"] == 1
            # the stream: byte-identical, resumed exactly once
            assert ch.tokens() == oracle
            assert ch.resumptions == 1
            assert ch.status == "ok"
        finally:
            gw.close()


# -- chaos: llm.dispatch fault point ------------------------------------------

@pytest.mark.chaos
class TestLlmDispatchChaos:
    def test_fixed_seed_dispatch_fault_is_survived(self, tiny_model):
        """Fixed-seed plan armed at llm.dispatch (rate 1.0, one fault):
        the first dispatch raises the typed error, the backoff retry
        completes the generation, output stays oracle-identical."""
        from lzy_tpu.chaos.faults import CHAOS, ERROR, FaultPlan
        from lzy_tpu.llm.metrics import DISPATCH_RETRIES

        cfg, params = tiny_model
        gw, _ = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-chaos")
            retries0 = sum(DISPATCH_RETRIES._values.values())
            CHAOS.arm(FaultPlan(11, rate=1.0, modes=(ERROR,),
                                points=("llm.dispatch",), max_faults=1))
            try:
                with lzy.workflow("chaotic"):
                    g = llm.generate([7, 2, 8, 1], max_new_tokens=5,
                                     greedy=True)
                    tokens = list(g.tokens)
            finally:
                plan = CHAOS.disarm()
            assert plan.fired == 1, plan.describe()
            assert tokens == _oracle_tokens(cfg, params, [7, 2, 8, 1], 5)
            assert sum(DISPATCH_RETRIES._values.values()) == retries0 + 1
        finally:
            gw.close()

    def test_fixed_seed_mid_stream_crash_resumes_fenced(self, tiny_model):
        """The satellite chaos test: a seeded CRASH at ``engine.step``
        (seed 2 fires at that point's 8th working round — mid-stream for
        a 24-token generation) kills the serving replica's loop under a
        workflow-driven streamed generation. The gateway fences the
        emitted tokens, the retry replica re-attaches the channel at the
        fence, and the consumer-visible stream is byte-identical to an
        uninterrupted run — replayable from the printed seed."""
        from lzy_tpu.chaos.faults import CHAOS, CRASH, FaultPlan

        cfg, params = tiny_model
        gw, _ = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-chaos-kill")
            ch = TokenStreamChannel()
            CHAOS.arm(FaultPlan(2, rate=0.15, modes=(CRASH,),
                                points=("engine.step",), max_faults=1))
            try:
                with lzy.workflow("chaotic-stream"):
                    g = llm.generate([7, 2, 8, 1], max_new_tokens=24,
                                     greedy=True, stream=ch,
                                     timeout_s=120)
                    tokens = list(g.tokens)
                    failovers = g.failovers
            finally:
                plan = CHAOS.disarm()
            assert plan.fired == 1, plan.describe()
            oracle = _oracle_tokens(cfg, params, [7, 2, 8, 1], 24)
            assert tokens == oracle, plan.describe()
            assert failovers == 1, plan.describe()
            # the stream: byte-identical, resumed exactly once at the
            # fence — the crash's only consumer-visible trace
            assert ch.tokens() == oracle
            assert ch.resumptions == 1
            assert ch.status == "ok"
        finally:
            gw.close()

    def test_exhausted_retries_surface_the_typed_error(self, tiny_model):
        """Every attempt faulted: the op fails with the dispatch error
        (workflow-level retries/caching own what happens next) — and
        with no stream attached nothing hangs."""
        from lzy_tpu.chaos.faults import CHAOS, ERROR, FaultPlan
        from lzy_tpu.core.workflow import RemoteCallError

        cfg, params = tiny_model
        gw, _ = _make_gateway(cfg, params, replicas=1)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://llm-chaos-exhaust")
            CHAOS.arm(FaultPlan(11, rate=1.0, modes=(ERROR,),
                                points=("llm.dispatch",)))
            try:
                with pytest.raises(RemoteCallError):
                    with lzy.workflow("doomed"):
                        llm.generate([7, 2, 8, 1], max_new_tokens=3,
                                     greedy=True)
            finally:
                CHAOS.disarm()
        finally:
            gw.close()


# -- KV provenance through the radix tree -------------------------------------

class TestKvProvenance:
    def test_chain_origin_follows_imported_blocks(self, tiny_model):
        """import_kv tags radix nodes with the producing prefill
        replica; a request matching them records it (the disagg reply's
        `prefilled_by` used-semantics), while locally-prefilled chains
        stay origin-free."""

        cfg, params = tiny_model
        src = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        dst = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        prompt = list(range(16)) + [3]
        req = src.submit(prompt, max_new_tokens=2)
        while not req.done:
            src.step()
        export = src.kv_io.export_kv(prompt[:16])
        export.prefilled_by = "prefill-7"
        assert dst.kv_io.import_kv(export) == 2
        assert dst.kv.chain_origin(prompt[:16]) == "prefill-7"
        # a request through the engine records the used origin
        req2 = dst.submit(prompt, max_new_tokens=2)
        while not req2.done:
            dst.step()
        assert req2.kv_prefilled_by == "prefill-7"
        # locally-prefilled chains carry no origin
        assert src.kv.chain_origin(prompt[:16]) is None
        req3 = src.submit(prompt, max_new_tokens=2)
        while not req3.done:
            src.step()
        assert req3.kv_prefilled_by is None


# -- e2e: InProcessCluster + gateway fleet ------------------------------------

class TestClusterEndToEnd:
    def test_cluster_workflow_against_two_replica_gateway(self):
        """The satellite e2e: a 3-step conversation workflow through an
        InProcessCluster whose serving plane is a 2-replica gateway —
        greedy output bit-identical to the oracle, the conversation
        pinned to one replica, whiteboard fields round-tripping through
        the index."""
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.inference import (
            _build_engine_parts, build_gateway_service)

        cluster = InProcessCluster(
            storage_uri="mem://llm-cluster",
            inference_factory=lambda c: build_gateway_service(
                "tiny", replicas=2, slots=2, page_size=PAGE,
                allocator=c.allocator, autoscale=False))
        gw = cluster.inference_service
        try:
            llm.configure(gw)
            cfg, params = _build_engine_parts("tiny", checkpoint=None,
                                              seed=0)
            lzy = cluster.lzy()
            conv = llm.Conversation("cluster-conv")
            with lzy.workflow("cluster-agent") as wf:
                p1 = list(range(16)) + [3]
                g1 = llm.generate(p1, max_new_tokens=4, greedy=True,
                                  conversation=conv)
                p2 = tool_extend(g1, [41])
                g2 = llm.generate(p2, max_new_tokens=4, greedy=True,
                                  conversation=conv)
                p3 = tool_extend(g2, [42])
                g3 = llm.generate(p3, max_new_tokens=4, greedy=True,
                                  conversation=conv)
                wb = llm.record_generation(wf, g3, conversation=conv)
                steps = [(list(g.prompt), list(g.tokens), g.replica,
                          g.routed_by) for g in (g1, g2, g3)]
            # (a) greedy bit-identity vs the generate() oracle
            running = list(range(16)) + [3]
            for i, (prompt, tokens, _, _) in enumerate(steps):
                assert prompt == running, f"step {i + 1} prompt"
                expected = _oracle_tokens(cfg, params, running, 4)
                assert tokens == expected, f"step {i + 1} tokens"
                running = running + expected + [41 + i]
            # (b) affinity kept the conversation on one replica
            replicas = {r for _, _, r, _ in steps}
            assert len(replicas) == 1
            # fused (parked-KV hard pin) when the workflow scheduler's
            # lease held across the tool gap; session otherwise
            assert all(why in ("fused", "session")
                       for why in [w for _, _, _, w in steps][1:])
            # (c) whiteboard round-trip through the cluster's index
            found = lzy.whiteboards(name=llm.GENERATION_WB_NAME,
                                    tags=[f"conversation:{conv.id}"])
            assert [w.id for w in found] == [wb.id]
            assert found[0].tokens == steps[2][1]
            assert found[0].provenance["routed_by"] in ("fused",
                                                        "session")
            # the tenant rode the workflow auth context into the fleet
            tenants = gw.stats()["tenants"]
            assert "test-user" in tenants
            assert tenants["test-user"]["requests_finished"] == 3
        finally:
            cluster.shutdown()

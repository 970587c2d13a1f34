"""Tiered KV cache: HBM → host RAM → storage, with cross-replica import.

Acceptance criterion (ISSUE 11): a replica that misses a prefix locally
imports a sibling's (or the storage tier's) blocks instead of
re-prefilling — demonstrated by bit-identical greedy output against the
uninterrupted ``generate()`` oracle with ``lzy_kvtier_imports_total``
moved and prefill-tokens-saved accounted — and ANY tier/transport
failure (including the ``kvtier.demote``/``kvtier.import`` chaos
faults at rate 1.0) degrades to a local re-prefill with the request
never failing.

Layers:

- host-tier units: LRU within the byte budget, take/peek/restore
  semantics, storage spill in the ``kv_block_manifest`` format;
- engine integration: radix eviction demotes instead of drops,
  admission promotes back, provenance rides the re-insert;
- the gateway's fleet-global prefix index + cross-replica import;
- invariants: a payload lives in exactly one tier
  (``audit_kv_tier``), byte accounting, double-residency detection;
- fixed-seed chaos: every tier op failing leaves greedy output
  bit-identical.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lzy_tpu.chaos import (
    CHAOS, FaultPlan, InvariantViolation, audit_engine, audit_kv_tier)
from lzy_tpu.chaos.faults import ERROR
from lzy_tpu.gateway import (
    GatewayService, GlobalKVIndex, ReplicaFleet, RoundRobinRouter)
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine, RadixCache
from lzy_tpu.serving.kv_tier import HostKVTier, StorageKVTier, TierEntry

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


@pytest.fixture(autouse=True)
def _disarmed():
    CHAOS.disarm()
    yield
    CHAOS.disarm()


def _oracle(cfg, params, prompt, n):
    out = generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run(engine, prompt, n=6):
    """Drive a synchronous engine to one request's completion."""
    req = engine.submit(prompt, max_new_tokens=n)
    for _ in range(500):
        engine.step()
        if req.done:
            break
    assert req.done, "request never finished"
    assert req.error is None, req.error
    return list(req.tokens)


def _entry(chain, nbytes=64, origin=None):
    return tuple(chain), {"k": np.zeros((nbytes // 4,), np.float32)}, origin


# ---------------------------------------------------------------------------
# host-tier units


class TestHostTierUnits:
    def test_put_take_peek_roundtrip(self):
        tier = HostKVTier(1 << 16, PAGE)
        chain, leaves, _ = _entry(range(PAGE))
        assert tier.put(chain, leaves)
        assert tier.peek(chain) is not None          # peek keeps it
        entry = tier.take(chain)
        assert entry is not None and entry.chain == chain
        assert entry.tier == "host"
        assert tier.take(chain) is None              # take popped it
        # promotions count LANDED promotions, not takes (a failed
        # promotion restores the entry and must not inflate the stat)
        assert tier.stats()["promotions"] == 0
        tier.note_promoted(entry.tier)
        assert tier.stats()["promotions"] == 1

    def test_budget_lru_evicts_oldest_without_storage(self):
        # budget fits exactly two 256-byte entries; the third put must
        # evict the LRU one, counted as a drop (no lower tier)
        tier = HostKVTier(512, PAGE)
        c1, l1, _ = _entry(range(PAGE), 256)
        c2, l2, _ = _entry(range(PAGE, 2 * PAGE), 256)
        c3, l3, _ = _entry(range(2 * PAGE, 3 * PAGE), 256)
        tier.put(c1, l1)
        tier.put(c2, l2)
        tier.peek(c1)        # peek must NOT refresh LRU (read-only)
        tier.put(c3, l3)
        assert tier.peek(c1) is None                 # oldest evicted
        assert tier.peek(c2) is not None
        assert tier.peek(c3) is not None
        s = tier.stats()
        assert s["dropped"] == 1 and s["host_bytes"] <= 512

    def test_oversize_entry_drops_immediately(self):
        tier = HostKVTier(64, PAGE)
        chain, leaves, _ = _entry(range(PAGE), 256)
        assert not tier.put(chain, leaves)
        assert tier.stats()["host_blocks"] == 0
        assert tier.stats()["dropped"] == 1

    def test_overflow_spills_to_storage_in_manifest_format(self):
        from lzy_tpu.channels.kv_transfer import (
            KV_MANIFEST_FORMAT, parse_kv_manifest)
        from lzy_tpu.storage.mem import MemStorageClient

        storage = MemStorageClient()
        st = StorageKVTier(storage, "mem://bucket/kvtier", PAGE)
        tier = HostKVTier(256, PAGE, storage=st)
        c1, l1, o1 = _entry(range(PAGE), 256, origin="replica-9")
        c2, l2, _ = _entry(range(PAGE, 2 * PAGE), 256)
        tier.put(c1, l1, origin=o1)
        tier.put(c2, l2)                 # budget overflow: c1 -> storage
        # spills upload on a worker thread (never the engine's
        # scheduling thread); flush before asserting on the landing
        assert tier.flush_spills()
        assert tier.peek(c1) is None
        assert tier.stats()["demotions_to_storage"] == 1
        # the spilled object IS a kv_block_manifest naming a whole payload
        doc = parse_kv_manifest(storage.read_bytes(st._uri(c1)))
        assert doc["format"] == KV_MANIFEST_FORMAT
        assert doc["tokens"] == list(c1)
        assert doc["prefilled_by"] == "replica-9"
        for meta in doc["leaves"].values():
            assert storage.exists(meta["uri"])       # leaves landed first
        # promotion falls through host -> storage; provenance survives
        entry = tier.take(c1)
        assert entry is not None and entry.tier == "storage"
        assert entry.origin == "replica-9"
        np.testing.assert_array_equal(entry.leaves["k"], l1["k"])

    def test_storage_rejects_a_foreign_chain(self):
        from lzy_tpu.storage.mem import MemStorageClient

        storage = MemStorageClient()
        st = StorageKVTier(storage, "mem://bucket/kvtier2", PAGE)
        chain, leaves, _ = _entry(range(PAGE), 64)
        st.put(TierEntry(chain, leaves))
        other = tuple(range(PAGE, 2 * PAGE))
        # copy the spilled manifest under the OTHER chain's uri: the
        # token check must fail closed (garbage KV must never scatter)
        storage.write_bytes(st._uri(other), storage.read_bytes(
            st._uri(chain)))
        assert st.get(other) is None
        assert st.get(chain) is not None


# ---------------------------------------------------------------------------
# engine integration: demote on eviction, promote at admission


class TestTierEngine:
    def _engine(self, tiny_model, **kw):
        cfg, params = tiny_model
        kw.setdefault("slots", 1)
        kw.setdefault("page_size", PAGE)
        kw.setdefault("kv_blocks", 5)    # 4 usable: evictions guaranteed
        return PagedInferenceEngine(cfg, params, **kw)

    def test_eviction_demotes_and_admission_promotes_bit_identical(
            self, tiny_model):
        cfg, params = tiny_model
        eng = self._engine(tiny_model, kv_host_tier_bytes=1 << 20)
        try:
            a = list(range(1, 3 * PAGE + 1)) + [5]
            b = list(range(30, 54)) + [7]
            ta = _run(eng, a)
            assert ta == _oracle(cfg, params, a, 6)
            _run(eng, b)                 # evicts A's blocks -> host tier
            s = eng.kv_tier.stats()
            assert s["demotions"] > 0 and s["host_blocks"] > 0
            audit_engine(eng)
            saved_before = eng.kv.stats().prefill_tokens_saved
            ta2 = _run(eng, a)           # promoted back from host RAM
            assert ta2 == ta
            assert eng.kv_tier.stats()["promotions"] > 0
            # the promoted prefix counts as prefill work SAVED — the
            # honest accounting the acceptance criterion asks for
            assert eng.kv.stats().prefill_tokens_saved > saved_before
            audit_engine(eng)
            st = eng.stats()
            assert st.kv_tier_demotions > 0 and st.kv_tier_promotions > 0
            assert st.kv_host_tier_bytes is not None
        finally:
            eng.close()

    def test_storage_tier_warms_a_fresh_replica(self, tiny_model):
        """Cross-replica warm-up through the fleet-shared storage rung:
        engine 1 demotes through its host tier into storage; a FRESH
        engine 2 sharing the storage root promotes those chains at
        admission — the autoscale/failover cache-warm-up path, bit
        identical to an uninterrupted local run."""
        from lzy_tpu.storage.mem import MemStorageClient

        cfg, params = tiny_model
        st = StorageKVTier(MemStorageClient(), "mem://bucket/fleet-tier",
                           PAGE)
        a = list(range(1, 3 * PAGE + 1)) + [5]
        b = list(range(30, 54)) + [7]
        e1 = self._engine(tiny_model, kv_host_tier_bytes=0,
                          kv_storage_tier=st)
        try:
            ta = _run(e1, a)
            _run(e1, b)                  # A's blocks spill to storage
            assert e1.kv_tier.flush_spills()
            assert st.stats()["storage_blocks"] > 0
        finally:
            e1.close()
        e2 = self._engine(tiny_model, kv_host_tier_bytes=0,
                          kv_storage_tier=st)
        try:
            ta2 = _run(e2, a)
            assert ta2 == ta == _oracle(cfg, params, a, 6)
            assert e2.kv_tier.stats()["promotions_from_storage"] > 0
            assert e2.kv.stats().prefill_tokens_saved > 0
            audit_engine(e2)
        finally:
            e2.close()

    def test_mismatched_quant_tier_fails_closed(self, tiny_model):
        """A quantized pool must not scatter an fp tier payload (and
        vice versa): promotion fails closed and the prompt re-prefills —
        wrong-but-served is the one outcome the tier may never produce."""
        from lzy_tpu.storage.mem import MemStorageClient

        cfg, params = tiny_model
        st = StorageKVTier(MemStorageClient(), "mem://bucket/quant-tier",
                           PAGE)
        a = list(range(1, 3 * PAGE + 1)) + [5]
        b = list(range(30, 54)) + [7]
        e1 = self._engine(tiny_model, kv_host_tier_bytes=0,
                          kv_storage_tier=st)
        try:
            _run(e1, a)
            _run(e1, b)
        finally:
            e1.close()
        e2 = self._engine(tiny_model, kv_host_tier_bytes=0,
                          kv_storage_tier=st, kv_quant="int8")
        try:
            ta = _run(e2, a)             # promotion refused, local prefill
            assert len(ta) == 6
            # nothing from the fp spill may be resident in the int8 pool
            assert e2.kv_imports == 0
            audit_engine(e2)
        finally:
            e2.close()


# ---------------------------------------------------------------------------
# the gateway's fleet-global prefix index + cross-replica import


def _build_gateway(cfg, params, *, kv_index=True, replicas=2, **ekw):
    ekw.setdefault("slots", 2)
    ekw.setdefault("page_size", PAGE)
    ekw.setdefault("kv_blocks", 32)
    fleet = ReplicaFleet(
        lambda: PagedInferenceEngine(cfg, params, **ekw))
    gw = GatewayService(
        fleet,
        # round-robin pins request i to replica (i % N): the second
        # request DETERMINISTICALLY lands on the cold replica — the
        # shape the cross-replica import exists for
        router=RoundRobinRouter(PAGE),
        kv_index=GlobalKVIndex(PAGE) if kv_index else None,
        model_name="tiny")
    for _ in range(replicas):
        fleet.add_replica()
    return gw, fleet


class TestCrossReplicaImport:
    def test_cold_replica_imports_instead_of_reprefilling(
            self, tiny_model):
        """THE acceptance test: shared-prefix traffic routed to a cold
        replica imports the warm sibling's blocks over the transport —
        greedy output bit-identical to the oracle, imports counted,
        prefill tokens saved on the importer."""
        from lzy_tpu.gateway.kv_index import IMPORTS

        cfg, params = tiny_model
        gw, fleet = _build_gateway(cfg, params)
        try:
            shared = list(range(1, 4 * PAGE + 1))
            p1, p2 = shared + [5], shared + [9]
            imports_before = sum(IMPORTS._values.values())
            r1 = gw.generate(p1, max_new_tokens=6, timeout_s=120)
            assert r1["tokens"] == _oracle(cfg, params, p1, 6)
            gw.tick()        # replicas advertise into the global index
            r2 = gw.generate(p2, max_new_tokens=6, timeout_s=120)
            assert r2["tokens"] == _oracle(cfg, params, p2, 6)
            assert r2["replica"] != r1["replica"]
            # staged AND used: the sibling's export was staged for this
            # attempt, and the prefix match really hit its blocks
            assert r2["kv_import_staged_from"] == r1["replica"]
            assert r2["kv_import_from"] == r1["replica"]
            assert r2["kv_import_tier"] == "hbm"
            assert r2["kv_import_ms"] is not None
            stats = gw.stats()
            assert stats["kvtier_imports"] == 1
            assert stats["kvtier_import_bytes"] > 0
            cold = fleet.get(r2["replica"]).engine
            assert cold.kv_imports == 1
            assert cold.kv.stats().prefill_tokens_saved >= 4 * PAGE
            # the wire metric the acceptance criterion names
            imports_now = sum(IMPORTS._values.values())
            assert imports_now > imports_before
            for replica in fleet.replicas():
                audit_engine(replica.engine)
        finally:
            gw.close()

    def test_transport_death_degrades_to_local_reprefill(
            self, tiny_model):
        from lzy_tpu.channels.kv_transfer import InMemoryKVTransport

        cfg, params = tiny_model
        gw, fleet = _build_gateway(cfg, params)
        try:
            gw.kv_transport = InMemoryKVTransport()
            gw.kv_transport.fail_next_fetch = 1
            shared = list(range(1, 4 * PAGE + 1))
            r1 = gw.generate(shared + [5], max_new_tokens=6,
                             timeout_s=120)
            gw.tick()
            r2 = gw.generate(shared + [9], max_new_tokens=6,
                             timeout_s=120)
            # the transfer died mid-stream; the request NEVER fails —
            # the cold replica re-prefilled locally
            assert r2["status"] == "ok"
            assert r2["tokens"] == _oracle(cfg, params, shared + [9], 6)
            assert r2["kv_import_from"] is None
            assert gw.stats()["kvtier_reprefill_fallbacks"] == 1
        finally:
            gw.close()

    def test_index_forgets_retired_replicas(self, tiny_model):
        cfg, params = tiny_model
        gw, fleet = _build_gateway(cfg, params)
        try:
            shared = list(range(1, 3 * PAGE + 1))
            r1 = gw.generate(shared + [5], max_new_tokens=4,
                             timeout_s=120)
            gw.tick()
            assert gw.kv_index.stats()["replicas_advertising"] >= 1
            gw.kv_index.forget(r1["replica"])
            idx = gw.kv_index.stats()["indexed_chains"]
            assert r1["replica"] not in idx
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# invariants


class TestTierInvariants:
    def test_double_residency_is_caught(self):
        kv = RadixCache(8, PAGE)
        tier = HostKVTier(1 << 16, PAGE)
        chain = list(range(PAGE))
        blocks = kv.allocate(1)
        kv.insert(chain, blocks)
        kv.release(blocks)
        # bypass the discard hook: the SAME chain filed in the tier
        tier.restore(TierEntry(tuple(chain),
                               {"k": np.zeros((4,), np.float32)}))
        with pytest.raises(InvariantViolation, match="double residency"):
            audit_kv_tier(kv, tier)

    def test_byte_drift_is_caught(self):
        kv = RadixCache(8, PAGE)
        tier = HostKVTier(1 << 16, PAGE)
        tier.put(tuple(range(PAGE, 2 * PAGE)),
                 {"k": np.zeros((4,), np.float32)})
        tier._bytes += 1
        with pytest.raises(InvariantViolation, match="byte accounting"):
            audit_kv_tier(kv, tier)

    def test_partial_chain_is_caught(self):
        kv = RadixCache(8, PAGE)
        tier = HostKVTier(1 << 16, PAGE)
        tier.restore(TierEntry(tuple(range(PAGE - 1)),
                               {"k": np.zeros((4,), np.float32)}))
        with pytest.raises(InvariantViolation, match="whole-block"):
            audit_kv_tier(kv, tier)

    def test_clean_tier_audits_clean(self):
        kv = RadixCache(8, PAGE)
        tier = HostKVTier(1 << 16, PAGE)
        tier.put(tuple(range(PAGE)), {"k": np.zeros((4,), np.float32)})
        audit_kv_tier(kv, tier)


# ---------------------------------------------------------------------------
# fixed-seed chaos: every tier op failing must be invisible to clients


@pytest.mark.chaos
class TestKvTierChaos:
    def test_all_demotions_failing_stays_bit_identical(self, tiny_model):
        """kvtier.demote at rate 1.0: every demotion is injected dead —
        the tier degrades to classic eviction, greedy output stays
        bit-identical to the generate() oracle, auditors stay clean."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   kv_blocks=5,
                                   kv_host_tier_bytes=1 << 20)
        plan = CHAOS.arm(FaultPlan(20260811, rate=1.0, modes=(ERROR,),
                                   points=("kvtier.demote",)))
        try:
            a = list(range(1, 3 * PAGE + 1)) + [5]
            b = list(range(30, 54)) + [7]
            assert _run(eng, a) == _oracle(cfg, params, a, 6)
            assert _run(eng, b) == _oracle(cfg, params, b, 6)
            assert _run(eng, a) == _oracle(cfg, params, a, 6)
            assert plan.fired > 0, plan.describe()
            assert eng.kv_tier.stats()["host_blocks"] == 0
            assert eng.kv_tier.stats()["dropped"] > 0
            audit_engine(eng)
        finally:
            CHAOS.disarm()
            eng.close()

    def test_all_promotions_failing_stays_bit_identical(self, tiny_model):
        """kvtier.import at rate 1.0: every promotion attempt dies —
        admission falls back to a full local re-prefill, bit-identical,
        popped entries restored to the tier (no payload leak)."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   kv_blocks=5,
                                   kv_host_tier_bytes=1 << 20)
        try:
            a = list(range(1, 3 * PAGE + 1)) + [5]
            b = list(range(30, 54)) + [7]
            ta = _run(eng, a)
            _run(eng, b)
            demoted = eng.kv_tier.stats()["host_blocks"]
            assert demoted > 0
            plan = CHAOS.arm(FaultPlan(20260812, rate=1.0, modes=(ERROR,),
                                       points=("kvtier.import",)))
            assert _run(eng, a) == ta == _oracle(cfg, params, a, 6)
            assert plan.fired > 0, plan.describe()
            CHAOS.disarm()
            # nothing was promoted while the point was armed (the fault
            # fires before any entry is popped), and the re-prefill's
            # radix insert reclaimed A's chains for HBM — one tier owns
            # them, which is exactly what the auditor checks
            assert eng.kv_tier.stats()["promotions"] == 0
            audit_engine(eng)
            # the quiet tail: evict A again, then promote it cleanly
            _run(eng, b)
            assert _run(eng, a) == ta
            assert eng.kv_tier.stats()["promotions"] > 0
            audit_engine(eng)
        finally:
            CHAOS.disarm()
            eng.close()

    def test_gateway_import_fault_never_fails_the_request(
            self, tiny_model):
        """kvtier.import injected at the gateway's cross-replica staging:
        the import attempt dies, the fallback is counted, and the routed
        replica serves bit-identically by re-prefilling."""
        cfg, params = tiny_model
        gw, fleet = _build_gateway(cfg, params)
        plan = CHAOS.arm(FaultPlan(20260813, rate=1.0, modes=(ERROR,),
                                   points=("kvtier.import",)))
        try:
            shared = list(range(1, 4 * PAGE + 1))
            r1 = gw.generate(shared + [5], max_new_tokens=6,
                             timeout_s=120)
            gw.tick()
            r2 = gw.generate(shared + [9], max_new_tokens=6,
                             timeout_s=120)
            assert r2["status"] == "ok"
            assert r2["tokens"] == _oracle(cfg, params, shared + [9], 6)
            assert r2["kv_import_from"] is None
            assert r1["status"] == "ok"
            assert plan.fired > 0, plan.describe()
            assert gw.stats()["kvtier_reprefill_fallbacks"] >= 1
            for replica in fleet.replicas():
                audit_engine(replica.engine)
        finally:
            CHAOS.disarm()
            gw.close()


class TestBatchedDemotionGathers:
    """Satellite (ROADMAP item 2 remainder): one eviction round's
    per-block device→host copies coalesce into a single gather per
    cache leaf (``RadixCache.on_evict`` → ``KvIO.demote``)."""

    def test_one_gather_per_leaf_per_eviction_round(self, tiny_model):
        cfg, params = tiny_model
        # pool: 1 scratch + 6 usable. Request A caches a 4-block chain;
        # request B (disjoint 4-block prompt + growth) then needs more
        # than the free list holds — ONE allocate call evicts several of
        # A's blocks in a single round.
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   kv_blocks=7,
                                   kv_host_tier_bytes=8 << 20)
        try:
            a = list(range(1, 4 * PAGE + 1)) + [3]
            b = [(11 * i) % 60 + 1 for i in range(4 * PAGE)] + [9]
            assert _run(eng, a) == _oracle(cfg, params, a, 6)
            assert eng.kv_tier_gather_rounds == 0
            rounds_before = eng.kv_tier_gather_rounds
            ops_before = eng.kv_tier_gather_ops
            demoted_before = eng.kv_tier.stats()["demotions"]
            assert _run(eng, b) == _oracle(cfg, params, b, 6)
            rounds = eng.kv_tier_gather_rounds - rounds_before
            ops = eng.kv_tier_gather_ops - ops_before
            demoted = eng.kv_tier.stats()["demotions"] - demoted_before
            n_leaves = len(eng.kv_io.leaf_keys)
            # the count-of-transfers contract: >= 2 blocks demoted in
            # ONE round, paying exactly one gather PER LEAF — not one
            # per (leaf x block) as the per-block path did
            assert demoted >= 2, demoted
            assert rounds == 1, (rounds, demoted)
            assert ops == n_leaves, (ops, n_leaves, demoted)
            # demoted payloads are real: each chain is promotable
            assert eng.kv_tier.stats()["host_blocks"] == demoted
            audit_engine(eng)
            audit_kv_tier(eng.kv, eng.kv_tier)
        finally:
            eng.close()

    def test_batched_demotions_promote_back_bit_identical(self,
                                                          tiny_model):
        """The batched payloads are byte-correct: re-running the evicted
        prompt promotes the demoted chain back and the output stays
        bit-identical with prefill tokens saved."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   kv_blocks=7,
                                   kv_host_tier_bytes=8 << 20)
        try:
            a = list(range(1, 4 * PAGE + 1)) + [3]
            b = [(11 * i) % 60 + 1 for i in range(4 * PAGE)] + [9]
            _run(eng, a)
            _run(eng, b)                     # batch-demotes A's chain
            saved_before = eng.kv.stats().prefill_tokens_saved
            promoted_before = eng.kv_tier.stats()["promotions"]
            assert _run(eng, a) == _oracle(cfg, params, a, 6)
            assert eng.kv_tier.stats()["promotions"] > promoted_before
            assert eng.kv.stats().prefill_tokens_saved > saved_before
            audit_engine(eng)
            audit_kv_tier(eng.kv, eng.kv_tier)
        finally:
            eng.close()

"""Disaggregated prefill/decode serving (serving/disagg + gateway/disagg).

Acceptance criterion (ISSUE 4): disaggregated greedy and sampled outputs
are bit-identical to the monolithic ``PagedInferenceEngine`` and the
sequential ``generate()`` oracle — including under a prefill-replica kill
mid-transfer, where the request silently re-prefills on the decode side
and NEVER fails. Unit layers underneath: manifest encode/decode, refcount
integrity on the exporting pool while a transfer is in flight, and import
into a nearly-full pool (evict-then-import, never corrupting resident
requests).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.channels.kv_transfer import (
    InMemoryKVTransport, KVBlockExport, KVTransferError, StorageKVTransport,
    build_kv_manifest, fetch_kv_export, parse_kv_manifest, spill_kv_export)
from lzy_tpu.gateway import (
    DisaggGatewayService, PrefixAffinityRouter, ReplicaFleet)
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import (
    DecodeEngine, NoFreeBlocks, PagedInferenceEngine, PrefillEngine)

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


def _drive(eng, *reqs, rounds=300):
    for _ in range(rounds):
        if all(r.done for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _prefill_export(cfg, params, prompt, **kw):
    """Run one prompt through a synchronous PrefillEngine; returns the
    export its request carries."""
    pf = PrefillEngine(cfg, params, slots=1, page_size=PAGE, **kw)
    req = pf.submit(prompt)
    _drive(pf, req)
    assert req.error is None, req.error
    return req.kv_export


@pytest.fixture(scope="module")
def export16(tiny_model):
    """One shared export of the 2-block prompt ``range(16) + [40]`` —
    engine construction is the expensive part of these tests, and the
    export itself is read-only for every consumer."""
    cfg, params = tiny_model
    return _prefill_export(cfg, params, list(range(16)) + [40])


def _make_disagg(cfg, params, *, prefill=1, decode=2, slots=2,
                 start_engines=True, transport=None, **engine_kw):
    decode_fleet = ReplicaFleet(
        lambda: DecodeEngine(cfg, params, slots=slots, page_size=PAGE,
                             **engine_kw),
        start_engines=start_engines, replica_prefix="decode")
    prefill_fleet = ReplicaFleet(
        lambda: PrefillEngine(cfg, params, slots=slots, page_size=PAGE,
                              **engine_kw),
        start_engines=start_engines, replica_prefix="prefill")
    gw = DisaggGatewayService(
        decode_fleet, prefill_fleet, page_size=PAGE,
        router=PrefixAffinityRouter(PAGE),
        prefill_router=PrefixAffinityRouter(PAGE),
        transport=transport, prefill_replicas=prefill, model_name="tiny")
    for _ in range(decode):
        decode_fleet.add_replica()
    for _ in range(prefill):
        prefill_fleet.add_replica()
    return gw, decode_fleet, prefill_fleet


class TestManifest:
    def _export(self):
        rng = np.random.default_rng(0)
        return KVBlockExport(
            tokens=list(range(16)), page_size=PAGE,
            leaves={
                "['layer_0']['k']": rng.standard_normal(
                    (2, PAGE, 2, 4)).astype(np.float32),
                "['layer_0']['v']": rng.standard_normal(
                    (2, PAGE, 2, 4)).astype(np.float32),
            },
            prefilled_by="prefill-1")

    def test_manifest_roundtrip(self):
        export = self._export()
        uris = {k: f"mem://kv/{i}" for i, k in enumerate(export.leaves)}
        doc = parse_kv_manifest(build_kv_manifest(export, uris))
        assert doc["page_size"] == PAGE
        assert doc["tokens"] == export.tokens
        assert doc["prefilled_by"] == "prefill-1"
        assert set(doc["leaves"]) == set(export.leaves)
        meta = doc["leaves"]["['layer_0']['k']"]
        assert meta["shape"] == [2, PAGE, 2, 4]
        assert meta["dtype"] == "float32"

    def test_parse_rejects_foreign_documents(self):
        with pytest.raises(ValueError, match="manifest"):
            parse_kv_manifest(b'{"format": "jax_sharded_array"}')
        with pytest.raises(ValueError, match="version"):
            parse_kv_manifest(
                b'{"format": "kv_block_manifest", "v": 99}')

    def test_storage_spill_fetch_roundtrip(self):
        from lzy_tpu.storage.mem import MemStorageClient

        storage = MemStorageClient()
        export = self._export()
        uri = spill_kv_export(storage, "mem://bucket/xfer/kv-1", export)
        back = fetch_kv_export(storage, uri)
        assert back.tokens == export.tokens
        assert back.page_size == PAGE
        assert back.prefilled_by == "prefill-1"
        for key, arr in export.leaves.items():
            np.testing.assert_array_equal(back.leaves[key], arr)

    def test_storage_transport_discard_removes_payload(self):
        from lzy_tpu.storage.mem import MemStorageClient

        storage = MemStorageClient()
        transport = StorageKVTransport(storage, "mem://bucket/xfers")
        ref = transport.publish("kv-9", self._export())
        assert transport.fetch(ref).tokens == list(range(16))
        transport.discard(ref)
        with pytest.raises(KVTransferError):
            transport.fetch(ref)

    def test_in_memory_transport_peer_death(self):
        transport = InMemoryKVTransport()
        ref = transport.publish("kv-1", self._export())
        transport.fail_next_fetch = 1
        with pytest.raises(KVTransferError, match="mid-stream"):
            transport.fetch(ref)
        # the next fetch (a retry in a real fabric) succeeds again
        assert transport.fetch(ref).page_size == PAGE


class TestExportImportUnits:
    def test_export_pins_blocks_while_in_flight(self, tiny_model):
        """Refcount integrity on the exporting pool mid-transfer: while
        the gather runs, the exported blocks are pinned — an allocation
        storm cannot evict them — and after the export every refcount is
        back to zero (the tree keeps the blocks cached)."""
        cfg, params = tiny_model
        pf = PrefillEngine(cfg, params, slots=1, page_size=PAGE,
                           kv_blocks=8)               # 7 usable
        prompt = list(range(16)) + [40]               # 2 full blocks
        req = pf.submit(prompt)
        _drive(pf, req)
        seen = {}

        def while_pinned():
            pinned = [b for b in range(pf.kv.pool.n_blocks)
                      if pf.kv.pool.refcount(b) > 0]
            seen["pinned"] = len(pinned)
            # everything evictable is allocatable EXCEPT the pinned
            # blocks: draining the pool must fail before touching them
            with pytest.raises(NoFreeBlocks):
                pf.kv.allocate(pf.kv.available() + 1)
            seen["match_during"] = pf.kv.match_len(prompt[:16])

        export = pf.kv_io.export_kv(prompt, on_pinned=while_pinned)
        assert export is not None and export.n_blocks == 2
        assert seen["pinned"] == 2
        assert seen["match_during"] == 16
        assert all(pf.kv.pool.refcount(b) == 0
                   for b in range(pf.kv.pool.n_blocks)), "leaked refs"
        # the exported prefix is still cached locally (tree unchanged)
        assert pf.kv.match_len(prompt[:16]) == 16
        # same engine: a sub-block prompt has nothing worth transferring
        short = pf.submit([5, 9, 3])
        _drive(pf, short)
        assert short.error is None and short.kv_export is None

    def test_import_into_nearly_full_pool_evicts_then_imports(
            self, tiny_model, export16):
        """Evict-then-import: a destination pool whose blocks are all
        cached (unreferenced) makes room by LRU eviction; a pool whose
        blocks are PINNED by a resident request refuses the import —
        and the resident request decodes on, bit-identical."""
        cfg, params = tiny_model
        export = export16
        de = DecodeEngine(cfg, params, slots=2, page_size=PAGE,
                          kv_blocks=4)                # 3 usable
        # fill the pool: a finished request leaves 2 cached blocks + 1 free
        warm = de.submit(list(range(32, 48)) + [41], max_new_tokens=2)
        _drive(de, warm)
        assert de.kv.match_len(list(range(32, 48))) == 16
        assert de.kv_io.import_kv(export) == 2        # 1 free + 1 evicted
        assert de.kv.evictions >= 1, "import did not need eviction"
        assert de.kv.match_len(export.tokens) == 16
        # now pin the whole pool with a live request and import on top
        resident = de.submit(list(range(48, 64)) + [42, 43],
                             max_new_tokens=5)
        de.step()
        assert not resident.done
        # a fresh 2-block payload (tokens differ; the refusal happens on
        # the block budget before any leaf data is read)
        import dataclasses
        big = dataclasses.replace(export, tokens=list(range(16, 32)))
        # free+evictable cannot cover 2 blocks with the resident pinned:
        # the import is refused outright, never forced
        assert de.kv.available() < 2
        assert de.kv_io.import_kv(big) == 0
        _drive(de, resident)
        assert resident.result(0) == _oracle_tokens(
            cfg, params, resident.prompt, 5), "resident request corrupted"

    def test_import_contract_on_one_engine(self, tiny_model, export16):
        """Three import contracts on ONE decode engine (construction is
        the expensive part): a page-size-mismatched payload is skipped; a
        queued import applies strictly before the admission that wants it
        (prefill runs only the sub-block tail); re-importing an
        already-cached prefix is a no-op that allocates nothing."""
        import dataclasses

        cfg, params = tiny_model
        de = DecodeEngine(cfg, params, slots=1, page_size=PAGE)
        # 1) page-size mismatch → skipped outright
        assert de.kv_io.import_kv(
            dataclasses.replace(export16, page_size=PAGE * 2)) == 0
        # 2) queued import lands before the admission round
        prompt = export16.tokens + [40, 41]
        de.queue_kv_import(export16)
        req = de.submit(prompt, max_new_tokens=4)
        _drive(de, req)
        assert req.result(0) == _oracle_tokens(cfg, params, prompt, 4)
        s = de.stats()
        assert s.kv_imports == 1 and s.kv_import_blocks == 2
        assert s.prefill_tokens_saved == 16
        # 3) the prefix is now cached: importing it again is a no-op
        free_before = de.kv.pool.free_count()
        assert de.kv_io.import_kv(export16) == 0
        assert de.kv.pool.free_count() == free_before


class TestDisaggParity:
    """The acceptance property: two-pool output == monolithic paged
    engine == sequential oracle, greedy and sampled."""

    def test_greedy_bit_identical_two_pool_fleet(self, tiny_model):
        cfg, params = tiny_model
        gw, _, _ = _make_disagg(cfg, params, prefill=1, decode=2)
        try:
            mono = PagedInferenceEngine(cfg, params, slots=2,
                                        page_size=PAGE)
            prompts = [list(range(i, i + 20)) + [3, i] for i in range(4)]
            for p in prompts:
                res = gw.generate(p, max_new_tokens=6, timeout_s=120)
                assert res["status"] == "ok" and res["failovers"] == 0
                oracle = _oracle_tokens(cfg, params, p, 6)
                assert res["tokens"] == oracle
                m = mono.submit(p, max_new_tokens=6)
                _drive(mono, m)
                assert res["tokens"] == m.result(0)
                # long prompts went through the prefill pool
                assert res["prefilled_by"].startswith("prefill-")
                assert res["kv_transfer_ms"] is not None
            s = gw.stats()
            assert s["disagg"] is True
            assert s["kv_transfers"] == 4
            assert s["kv_transfer_bytes"] > 0
        finally:
            gw.close()

    def test_sampled_bit_identical_to_monolithic(self, tiny_model):
        """Fresh two-pool fleet vs fresh monolithic engine, same seed:
        the decode replica samples the first token from its own suffix
        prefill — the same rng draw order as a monolithic engine — so
        the sampled stream matches bit-for-bit."""
        cfg, params = tiny_model
        kw = dict(temperature=0.8, top_k=20, seed=7)
        prompt = list(range(8, 28)) + [5]
        mono = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                    **kw)
        ref = mono.submit(prompt, max_new_tokens=6)
        _drive(mono, ref)
        gw, _, _ = _make_disagg(cfg, params, prefill=1, decode=2, **kw)
        try:
            res = gw.generate(prompt, max_new_tokens=6, timeout_s=120)
            assert res["tokens"] == ref.result(0)
            assert res["prefilled_by"] is not None
        finally:
            gw.close()

    def test_short_prompt_direct_and_repeat_prefix_skips_transfer(
            self, tiny_model):
        """One gateway, the two no-transfer paths in order: a sub-block
        prompt never touches the prefill pool at all, and a prompt whose
        prefix is expected on the chosen decode replica pays neither
        prefill-pool time nor transfer bytes on the repeat."""
        cfg, params = tiny_model
        gw, _, prefill_fleet = _make_disagg(cfg, params, prefill=1,
                                            decode=2)
        try:
            # sub-block prompt: routed straight to decode
            res = gw.generate([5, 9, 3], max_new_tokens=3, timeout_s=120)
            assert res["tokens"] == _oracle_tokens(cfg, params,
                                                   [5, 9, 3], 3)
            assert res["prefilled_by"] is None
            pf = prefill_fleet.replicas()[0]
            assert pf.engine.stats().requests_finished == 0
            # first long prompt: transferred (staged AND used — the
            # decode engine's prefix match hit the imported blocks)
            shared = list(range(16))
            first = gw.generate(shared + [40, 41], max_new_tokens=3,
                                timeout_s=120)
            assert first["prefilled_by"] is not None
            assert first["kv_staged_by"] == first["prefilled_by"]
            # repeat of the shared prefix: affinity-routed, transfer
            # skipped — nothing newly staged, but the KV actually used
            # still credits the pool that produced it (provenance
            # follows the blocks, not the transfer)
            again = gw.generate(shared + [50], max_new_tokens=3,
                                timeout_s=120)
            assert again["tokens"] == _oracle_tokens(
                cfg, params, shared + [50], 3)
            assert again["kv_transfer_skipped"] is True
            assert again["kv_staged_by"] is None
            assert again["prefilled_by"] == first["prefilled_by"]
            assert again["replica"] == first["replica"]
            s = gw.stats()
            assert s["kv_transfer_skipped_by_cache"] == 1
            assert s["kv_transfers"] == 1
        finally:
            gw.close()


class TestPrefillDeath:
    def test_prefill_kill_and_transport_death_fall_back(self, tiny_model):
        """One gateway, both mid-transfer failure windows in sequence —
        either way the decode side silently re-prefills, the request
        NEVER fails, and output stays bit-identical to the oracle.

        1. The transport stream dies AFTER a successful prefill (the
           literal mid-transfer window, injected at fetch).
        2. The only prefill replica's engine loop dies while the request
           is in flight; the dead replica is retired and the next tick
           re-leases the pool back to size, after which transfers flow
           again."""
        cfg, params = tiny_model
        transport = InMemoryKVTransport()
        gw, _, prefill_fleet = _make_disagg(cfg, params, prefill=1,
                                            decode=1, transport=transport)
        try:
            # 1) payload dies between publish and fetch
            transport.fail_next_fetch = 1
            p = list(range(40, 60)) + [2]
            res = gw.generate(p, max_new_tokens=5, timeout_s=120)
            assert res["status"] == "ok" and res["reprefills"] == 1
            assert res["tokens"] == _oracle_tokens(cfg, params, p, 5)
            # 2) prefill replica host dies mid-request
            victim = prefill_fleet.replicas()[0]

            def boom():
                raise RuntimeError("prefill host on fire")

            victim.engine.step = boom
            p = list(range(20)) + [7]
            res = gw.generate(p, max_new_tokens=5, timeout_s=120)
            assert res["status"] == "ok"
            assert res["tokens"] == _oracle_tokens(cfg, params, p, 5)
            assert res["reprefills"] == 1
            assert res["prefilled_by"] is None
            assert gw.stats()["reprefill_fallbacks"] == 2
            # the dead replica left the pool; the tick restores the size
            assert victim.id not in [r.id for r in
                                     prefill_fleet.replicas()]
            gw.tick()
            assert len(prefill_fleet.replicas()) == 1
            # and the restored pool serves transfers again
            p2 = list(range(30, 50)) + [8]
            res2 = gw.generate(p2, max_new_tokens=4, timeout_s=120)
            assert res2["tokens"] == _oracle_tokens(cfg, params, p2, 4)
            assert res2["prefilled_by"] is not None
        finally:
            gw.close()

    def test_decode_replica_killed_mid_stream_fails_over(self, tiny_model):
        """Decode-side death keeps the parent gateway's fenced-token
        failover, and the retry restages KV for the surviving replica:
        final output identical to an uninterrupted run."""
        cfg, params = tiny_model
        gw, decode_fleet, _ = _make_disagg(cfg, params, prefill=1,
                                           decode=2)
        result = {}
        prompt = list(range(4, 24)) + [9]

        def run():
            try:
                result["res"] = gw.generate(prompt, max_new_tokens=24,
                                            timeout_s=120)
            except BaseException as e:  # surfaced in the main thread
                result["err"] = e

        try:
            t = threading.Thread(target=run)
            t.start()
            victim = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                for replica in decode_fleet.replicas():
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
                raise RuntimeError("decode host on fire")

            victim.engine.step = boom
            t.join(120)
            assert "err" not in result, result.get("err")
            res = result["res"]
            assert res["tokens"] == _oracle_tokens(cfg, params, prompt, 24)
            assert res["failovers"] == 1 and res["status"] == "ok"
            assert victim.id not in [r.id for r in decode_fleet.replicas()]
        finally:
            gw.close()


class TestDisaggRpc:
    def test_disagg_generate_and_pool_stats_over_the_control_plane(
            self, tiny_model, tmp_path):
        """In-process two-pool fleet behind the real RPC stack: replies
        carry prefilled_by/kv_transfer_ms, InferStats carries the disagg
        counters, InferFleetStats splits per pool."""
        from lzy_tpu.rpc import RpcInferenceClient
        from lzy_tpu.service import InProcessCluster

        cfg, params = tiny_model

        def factory(cluster):
            gw, _, _ = _make_disagg(cfg, params, prefill=1, decode=2)
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
                p = list(range(20)) + [3]
                res = client.generate(p, max_new_tokens=4, timeout_s=120)
                assert res["tokens"] == _oracle_tokens(cfg, params, p, 4)
                assert res["prefilled_by"].startswith("prefill-")
                assert res["kv_transfer_ms"] is not None
                stats = client.stats()
                assert stats["disagg"] is True
                assert stats["kv_transfers"] == 1
                fs = client.fleet_stats()
                assert fs["pools"] == {"decode": 2, "prefill": 1}
                pools = {r["replica"]: r["pool"] for r in fs["replicas"]}
                assert pools["prefill-1"] == "prefill"
                assert pools["decode-1"] == "decode"
            finally:
                client.close()
        finally:
            cluster.shutdown()


class TestGlobalIndexInPreSubmit:
    """Satellite (ROADMAP item 2 remainder): the disagg ``_pre_submit``
    also consults the fleet-global KV index — prefill-pool staging keeps
    priority, but when the pool lands nothing, a DECODE-pool sibling
    holding a deeper chain than the routed replica's own radix+tier
    coverage is imported where previously the request always
    re-prefilled locally."""

    def _make(self, cfg, params, *, prefill=0, decode=2):
        from lzy_tpu.gateway import GlobalKVIndex, RoundRobinRouter

        decode_fleet = ReplicaFleet(
            lambda: DecodeEngine(cfg, params, slots=2, page_size=PAGE,
                                 kv_blocks=32),
            replica_prefix="decode")
        prefill_fleet = ReplicaFleet(
            lambda: PrefillEngine(cfg, params, slots=2, page_size=PAGE,
                                  kv_blocks=32),
            replica_prefix="prefill")
        gw = DisaggGatewayService(
            decode_fleet, prefill_fleet, page_size=PAGE,
            # round-robin pins request i to decode replica (i % N): the
            # second request DETERMINISTICALLY lands on the cold sibling
            router=RoundRobinRouter(PAGE),
            prefill_router=RoundRobinRouter(PAGE),
            prefill_replicas=prefill, model_name="tiny",
            kv_index=GlobalKVIndex(PAGE))
        for _ in range(decode):
            decode_fleet.add_replica()
        for _ in range(prefill):
            prefill_fleet.add_replica()
        return gw, decode_fleet

    def test_decode_sibling_import_replaces_reprefill(self, tiny_model):
        """Prefill pool EMPTY (every staging falls back): request 2,
        routed to the cold decode replica, imports the warm sibling's
        blocks instead of re-prefilling — bit-identical output, import
        counted on the cold engine, prefill tokens saved."""
        cfg, params = tiny_model
        gw, dfleet = self._make(cfg, params, prefill=0)
        try:
            shared = list(range(1, 4 * PAGE + 1))
            r1 = gw.generate(shared + [5], max_new_tokens=6,
                             timeout_s=120)
            assert r1["tokens"] == _oracle_tokens(cfg, params,
                                                  shared + [5], 6)
            assert r1["prefilled_by"] is None       # pool is empty
            assert r1["reprefills"] == 1            # fallback counted
            gw.tick()       # decode replicas advertise into the index
            r2 = gw.generate(shared + [9], max_new_tokens=6,
                             timeout_s=120)
            assert r2["tokens"] == _oracle_tokens(cfg, params,
                                                  shared + [9], 6)
            assert r2["replica"] != r1["replica"]
            # the import was staged from the decode-pool SIBLING (not a
            # prefill replica) and the prefix match really hit it
            assert r2["kv_import_staged_from"] == r1["replica"]
            assert r2["kv_import_from"] == r1["replica"]
            assert r2["kv_import_tier"] == "hbm"
            cold = dfleet.get(r2["replica"]).engine
            assert cold.kv_imports == 1
            assert cold.kv.stats().prefill_tokens_saved >= 4 * PAGE
            stats = gw.stats()
            assert stats["kvtier_imports"] == 1
            assert stats["reprefill_fallbacks"] == 2
        finally:
            gw.close()

    def test_prefill_pool_keeps_priority(self, tiny_model):
        """With a live prefill pool, staging comes from it and the
        global index is NOT consulted (no cross-replica import)."""
        cfg, params = tiny_model
        gw, dfleet = self._make(cfg, params, prefill=1)
        try:
            shared = list(range(1, 4 * PAGE + 1))
            r1 = gw.generate(shared + [5], max_new_tokens=4,
                             timeout_s=120)
            assert r1["kv_staged_by"] is not None
            assert r1["kv_staged_by"].startswith("prefill-")
            gw.tick()
            r2 = gw.generate(shared + [9], max_new_tokens=4,
                             timeout_s=120)
            assert r2["tokens"] == _oracle_tokens(cfg, params,
                                                  shared + [9], 4)
            # the prefill pool staged (or the router expected residency);
            # either way no decode-sibling import was needed
            assert gw.stats()["kvtier_imports"] == 0
        finally:
            gw.close()

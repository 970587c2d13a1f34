"""Paged KV-cache pool + radix prefix caching (lzy_tpu/serving/kv_cache).

Two layers of coverage:

- **Pool/tree units**: refcount discipline, LRU eviction order (the tree
  uses a logical clock, so order is deterministic), the
  only-unreferenced-blocks-evict invariant, and free/cached accounting.
- **Engine integration**: the paged engine must be BIT-IDENTICAL to the
  dense sequential oracle — with prefix caching cold and hot, greedy and
  sampled — because the paged attention path gathers blocks back into
  exactly the dense layout before the shared softmax code runs. Pressure
  tests drive the engine past the block budget and assert eviction takes
  cached blocks in LRU order, preemption takes the youngest request, and
  in-flight requests are never corrupted. Deadline tests cover the
  ``cancelled`` terminal status for slot-resident and queued requests.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import (
    BlockPool, NoFreeBlocks, PagedInferenceEngine,
    RadixCache)

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


def _oracle_tokens(cfg, params, prompt_ids, n, **kw):
    """Solo generate() continuation (dense sequential-path oracle)."""
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _drive(eng, *reqs, rounds=200):
    for _ in range(rounds):
        if all(r.done for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


class TestBlockPool:
    def test_alloc_refcount_release_cycle(self):
        pool = BlockPool(4, PAGE)
        assert pool.free_count() == 3          # block 0 is scratch
        a = pool.alloc()
        assert a != 0 and pool.refcount(a) == 1
        assert pool.incref(a) == 2
        assert pool.decref(a) == 1
        assert pool.decref(a) == 0
        pool.release_to_free(a)
        assert pool.free_count() == 3

    def test_exhaustion_raises(self):
        pool = BlockPool(3, PAGE)
        pool.alloc(), pool.alloc()
        with pytest.raises(NoFreeBlocks):
            pool.alloc()

    def test_freeing_referenced_block_is_a_bug(self):
        pool = BlockPool(3, PAGE)
        b = pool.alloc()
        with pytest.raises(AssertionError):
            pool.release_to_free(b)


class TestRadixCache:
    def _filled(self, n_blocks=16):
        """Cache with two 2-block prompts inserted and fully released:
        every block cached-unreferenced (evictable)."""
        kv = RadixCache(n_blocks, PAGE)
        pa = list(range(16))          # blocks: chunks (0..7), (8..15)
        pb = list(range(16, 32))
        ba = kv.allocate(2)
        kv.insert(pa, ba)
        kv.release(ba)
        bb = kv.allocate(2)
        kv.insert(pb, bb)
        kv.release(bb)
        return kv, pa, pb

    def test_match_whole_blocks_only(self):
        kv, pa, _ = self._filled()
        blocks, n = kv.match(pa[:12])          # 1.5 chunks → 1 block
        assert n == 8 and len(blocks) == 1
        assert kv.pool.refcount(blocks[0]) == 1
        kv.release(blocks)

    def test_match_refs_pin_against_eviction(self):
        kv, pa, pb = self._filled(n_blocks=5)  # 4 usable, all cached
        held, n = kv.match(pa)
        assert n == 16
        # allocating everything evictable must take pb's blocks, not pa's
        kv.allocate(2)
        assert kv.match_len(pa) == 16, "referenced blocks were evicted"
        assert kv.match_len(pb) == 0
        kv.release(held)

    def test_lru_eviction_order_is_deterministic(self):
        kv, pa, pb = self._filled(n_blocks=5)
        # touch pa AFTER pb: pb's leaves become the LRU victims
        kv.match_len(pb)                       # probe does NOT bump LRU
        held, _ = kv.match(pa)
        kv.release(held)                       # unpinned again, but recent
        kv.allocate(2)
        assert kv.match_len(pa) == 16
        assert kv.match_len(pb) == 0

    def test_eviction_is_leaf_first(self):
        kv = RadixCache(4, PAGE)               # 3 usable: the whole chain
        prompt = list(range(24))               # 3 chained blocks
        blocks = kv.allocate(3)
        kv.insert(prompt, blocks)
        kv.release(blocks)
        kv.allocate(1)                         # evicts ONE block: the leaf
        assert kv.match_len(prompt) == 16      # parents survive

    def test_available_counts_free_plus_evictable(self):
        kv, pa, _ = self._filled(n_blocks=9)   # 8 usable, 4 cached
        assert kv.available() == 8
        held, _ = kv.match(pa)                 # pin 2
        assert kv.available() == 6
        kv.release(held)
        assert kv.available() == 8

    def test_allocate_never_overcommits(self):
        kv = RadixCache(4, PAGE)
        kv.allocate(3)
        with pytest.raises(NoFreeBlocks):
            kv.allocate(1)

    def test_insert_keeps_existing_node_block(self):
        kv = RadixCache(8, PAGE)
        prompt = list(range(8))
        first = kv.allocate(1)
        assert kv.insert(prompt, first) == 1
        dup = kv.allocate(1)
        assert kv.insert(prompt, dup) == 0     # node exists; dup stays private
        kv.release(first)
        kv.release(dup)                        # private dup → free list
        assert kv.match_len(prompt) == 8


class TestKvMetricsExported:
    def test_kv_metrics_in_registry(self):
        from lzy_tpu.utils.metrics import REGISTRY

        kv = RadixCache(8, PAGE)
        blocks = kv.allocate(2)
        kv.insert(list(range(16)), blocks)
        kv.release(blocks)
        kv.match(list(range(16)))
        text = REGISTRY.exposition()
        for name in ("lzy_kv_blocks", "lzy_kv_blocks_free",
                     "lzy_kv_blocks_cached", "lzy_kv_evictions_total",
                     "lzy_kv_prefix_hit_tokens_total",
                     "lzy_kv_prefix_hit_rate"):
            assert name in text


class TestPagedEngineParity:
    """Acceptance criterion: with prefix caching enabled, requests sharing
    a >= 2-block prompt prefix decode bit-identically to the dense
    sequential oracle, and the stats report the reuse."""

    SHARED = [5, 9, 3, 7, 1, 2, 8, 4, 6, 0, 5, 9, 3, 7, 1, 2]  # 2 blocks

    def test_prefix_hit_is_bit_identical_and_reported(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        a = eng.submit(self.SHARED + [11, 12, 13], max_new_tokens=8)
        _drive(eng, a)
        assert a.result(0) == _oracle_tokens(cfg, params, a.prompt, 8)
        assert eng.stats().prefill_tokens_saved == 0     # cold cache

        b = eng.submit(self.SHARED + [21, 22], max_new_tokens=6)
        c = eng.submit(self.SHARED + [31], max_new_tokens=6)
        _drive(eng, b, c)
        assert b.result(0) == _oracle_tokens(cfg, params, b.prompt, 6)
        assert c.result(0) == _oracle_tokens(cfg, params, c.prompt, 6)
        s = eng.stats()
        # both hit the 2-block (16-token) shared prefix
        assert s.prefill_tokens_saved == 32
        assert s.prefix_hit_rate > 0
        assert s.kv_page_size == PAGE

    def test_staggered_requests_and_slot_reuse(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        a = eng.submit([5, 9, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        b = eng.submit([7, 2, 8, 1, 4], max_new_tokens=4)
        eng.step()
        assert len(b.tokens) >= 1, "B waited for the running batch to drain"
        _drive(eng, a, b)
        assert a.result(0) == _oracle_tokens(cfg, params, a.prompt, 12)
        assert b.result(0) == _oracle_tokens(cfg, params, b.prompt, 4)
        # C lands in a vacated slot whose blocks went back to the pool
        c = eng.submit([7, 2, 8, 1], max_new_tokens=5)
        _drive(eng, c)
        assert c.result(0) == _oracle_tokens(cfg, params, c.prompt, 5)

    def test_sampled_decode_does_not_depend_on_paging(self, tiny_model):
        """Same seed, same arrival schedule, temperature > 0: how the
        pool is cut into pages (one page a row against pages of 8) must
        not move a single draw — both consume the engine-wide rng in the
        same order over the same logits."""
        cfg, params = tiny_model
        kw = dict(slots=2, temperature=0.8, top_k=20, seed=7)
        whole = PagedInferenceEngine(cfg, params,
                                     page_size=cfg.max_seq_len, **kw)
        paged = PagedInferenceEngine(cfg, params, page_size=PAGE, **kw)
        w1 = whole.submit([5, 9, 3, 7], max_new_tokens=6)
        p1 = paged.submit([5, 9, 3, 7], max_new_tokens=6)
        whole.step(), paged.step()
        w2 = whole.submit([8, 1], max_new_tokens=5)
        p2 = paged.submit([8, 1], max_new_tokens=5)
        _drive(whole, w1, w2)
        _drive(paged, p1, p2)
        assert p1.result(0) == w1.result(0)
        assert p2.result(0) == w2.result(0)
        assert p1.result(0) != _oracle_tokens(cfg, params, p1.prompt, 6)

    def test_full_block_prompt_and_one_token_request(self, tiny_model):
        """Edge shapes: a prompt that is exactly N full blocks (the match
        cap must still leave one token to forward), and max_new_tokens=1
        (slot never activates; blocks release at prefill)."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE)
        exact = self.SHARED                     # 16 tokens = 2 blocks
        a = eng.submit(exact, max_new_tokens=4)
        _drive(eng, a)
        assert a.result(0) == _oracle_tokens(cfg, params, exact, 4)
        b = eng.submit(exact, max_new_tokens=1)
        _drive(eng, b)
        assert b.result(0) == _oracle_tokens(cfg, params, exact, 1)
        # the second run may only match 1 block (15 of 16 tokens offered)
        assert eng.stats().prefill_tokens_saved >= 8
        assert eng.stats().busy == 0

    def test_eos_frees_blocks(self, tiny_model):
        cfg, params = tiny_model
        prompt = [5, 9, 3]
        first = _oracle_tokens(cfg, params, prompt, 1)[0]
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   eos_token=first)
        r = eng.submit(prompt, max_new_tokens=16)
        eng.step()
        assert r.done and r.result(0) == [first]
        s = eng.stats()
        assert s.busy == 0
        # every block is either free or cached-unreferenced
        assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total


class TestCachePressure:
    def test_squeeze_preempts_youngest_never_corrupts_oldest(self,
                                                             tiny_model):
        """Deterministic squeeze: 7 usable blocks, two growing requests.
        The younger must be preempted with a clean error; the older must
        run to completion BIT-IDENTICAL to the oracle (its blocks were
        never touched)."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   kv_blocks=8)
        a = eng.submit([5, 9, 3, 7, 1, 2, 8, 4, 6], max_new_tokens=30)
        b = eng.submit([11, 12, 13, 14, 15, 16, 17], max_new_tokens=30)
        for _ in range(120):
            if a.done and b.done:
                break
            eng.step()
        assert a.error is None
        assert a.result(0) == _oracle_tokens(cfg, params, a.prompt, 30)
        assert b.error is not None and "preempted" in b.error
        assert len(b.tokens) > 0            # it generated until the squeeze
        s = eng.stats()
        assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total

    def test_eviction_takes_lru_cached_blocks_first(self, tiny_model):
        """Fill the pool with two finished requests' cached prefixes, then
        admit a third that needs eviction: the LRU prefix goes, the
        recently-matched one survives."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   kv_blocks=8)              # 7 usable
        old = list(range(16))                                # 2 blocks
        hot = list(range(16, 32))                            # 2 blocks
        r1 = eng.submit(old + [40], max_new_tokens=2)
        _drive(eng, r1)
        r2 = eng.submit(hot + [41], max_new_tokens=2)
        _drive(eng, r2)
        # touch 'hot' again so 'old' is the LRU victim
        r3 = eng.submit(hot + [42], max_new_tokens=2)
        _drive(eng, r3)
        assert eng.kv.match_len(hot) == 16
        # a big new prompt forces eviction of the remaining cold blocks
        r4 = eng.submit(list(range(32, 32 + 33)), max_new_tokens=2)
        _drive(eng, r4)
        assert r4.result(0) == _oracle_tokens(cfg, params, r4.prompt, 2)
        assert eng.stats().kv_evictions > 0
        assert eng.kv.match_len(old) == 0, "LRU prefix should be gone"

    def test_refcount_integrity_after_eos_and_cancel(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        a = eng.submit(list(range(20)), max_new_tokens=20)
        b = eng.submit(list(range(16)) + [50], max_new_tokens=3)
        eng.step()
        eng.step()       # both resident
        a.cancel()
        _drive(eng, a, b)
        assert a.status == "cancelled"
        assert b.result(0) == _oracle_tokens(cfg, params, b.prompt, 3)
        # no block may retain a reference once nothing is in flight
        pool = eng.kv.pool
        assert all(pool.refcount(blk) == 0
                   for blk in range(pool.n_blocks)), "leaked block refs"
        s = eng.stats()
        assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total

    def test_never_coverable_prompt_rejected_at_submit(self, tiny_model):
        """A prompt needing more blocks than the pool can EVER supply must
        fail fast at submit — queued it would park at the head of the
        admission queue forever and starve every request behind it."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   kv_blocks=4)               # 3 usable
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(list(range(32)), max_new_tokens=2)     # needs 4
        # 2 prompt blocks + growth into the 3rd: completes inside the pool
        ok = eng.submit(list(range(16)), max_new_tokens=2)
        _drive(eng, ok)
        assert ok.result(0) == _oracle_tokens(cfg, params, ok.prompt, 2)

    def test_admission_waits_for_block_budget(self, tiny_model):
        """A prompt whose blocks cannot be covered yet must WAIT in the
        queue (head-of-line) — not fail — and admit once blocks free."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   kv_blocks=8)               # 7 usable
        a = eng.submit(list(range(32)), max_new_tokens=8)     # 4 blocks
        eng.step()
        big = eng.submit(list(range(30, 62)), max_new_tokens=2)   # 4 more
        eng.step()
        assert not a.done
        assert not big.done and len(big.tokens) == 0
        assert eng.stats().queue_depth == 1                  # still queued
        _drive(eng, a, big)
        assert big.result(0) == _oracle_tokens(cfg, params, big.prompt, 2)


class TestDeadlines:
    def test_slot_resident_deadline_evicts_mid_decode(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE)
        r = eng.submit([5, 9, 3], max_new_tokens=200, deadline_s=0.2)
        deadline = time.monotonic() + 30
        while not r.done and time.monotonic() < deadline:
            eng.step()
            time.sleep(0.01)
        assert r.status == "cancelled"
        assert "deadline" in (r.error or "")
        assert len(r.tokens) > 0              # partial output stays readable
        s = eng.stats()
        assert s.busy == 0 and s.requests_cancelled == 1
        assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total

    def test_queued_request_expires_at_pop(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        hog = eng.submit([5, 9, 3], max_new_tokens=100)
        doomed = eng.submit([1, 2], max_new_tokens=5, deadline_s=0.05)
        eng.step()
        time.sleep(0.1)
        eng.step()
        assert doomed.done and doomed.status == "cancelled"
        assert not hog.done

    def test_deadline_surfaces_as_cancelled_status_over_rpc_service(
            self, tiny_model):
        """InferGenerate's surface: a deadline-cancelled request RETURNS
        (not raises) with status "cancelled" and the partial tokens."""
        from lzy_tpu.service.inference import InferenceService

        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1,
                                   page_size=PAGE).start()
        try:
            svc = InferenceService(eng, model_name="tiny")
            res = svc.generate([5, 9, 3], max_new_tokens=100_000 // 500,
                               timeout_s=30, deadline_s=0.2)
            assert res["status"] == "cancelled"
            assert res["model"] == "tiny"
            ok = svc.generate([5, 9, 3], max_new_tokens=2, timeout_s=30)
            assert ok["status"] == "ok"
            assert ok["tokens"] == _oracle_tokens(cfg, params, [5, 9, 3], 2)
        finally:
            eng.close()

    def test_rejects_nonpositive_deadline(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        with pytest.raises(ValueError, match="deadline"):
            eng.submit([1, 2], max_new_tokens=2, deadline_s=0.0)

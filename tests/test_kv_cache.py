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

from lzy_tpu.chaos import invariants
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


    def test_cost_counters_reach_the_registry(self):
        from benchmark.harness.serve import exposition_samples
        from lzy_tpu.utils.metrics import REGISTRY

        def read():
            c = exposition_samples(REGISTRY.exposition())
            return (c.get("lzy_kv_tree_visits_total", 0.0),
                    c.get("lzy_kv_calls_total", 0.0))

        kv = RadixCache(8, PAGE)
        visits0, calls0 = read()
        blocks = kv.allocate(2)
        kv.insert(list(range(16)), blocks)
        kv.available()                  # flushed by the next call's gauges
        kv.release(blocks)
        visits, calls = read()
        assert calls - calls0 == kv.calls == 4
        assert visits - visits0 == kv.tree_visits > 0


def _check_against_reckoning(kv):
    """The kept numbers, and the next victim, against the from-scratch
    walks that defined them until PR 34."""
    free, cached = kv.pool.free_count(), invariants.reckon_cached(kv)
    assert kv.cached_count() == cached
    assert kv.available() == free + invariants.reckon_evictable(kv)
    st = kv.stats()
    assert (st.blocks_total, st.blocks_free, st.blocks_cached,
            st.evictions, st.prefix_hit_tokens, st.prefix_lookup_tokens) \
        == (kv.pool.n_blocks - 1, free, cached, kv.evictions,
            kv.hit_tokens, kv.lookup_tokens)
    want = invariants.reckon_victims(kv, 1)
    assert kv._next_victim() is (want[0] if want else None)
    invariants.audit_pool(kv)
    invariants.audit_radix(kv)
    invariants.audit_kv_counts(kv)


class _Walker:
    """A seeded random walk over a small pool: requests that share
    prefixes and branch (a three-token vocabulary), that insert under
    nodes they did not match (a duplicate insert: referenced nodes under
    unreferenced ancestors), that grow, finish and are exported, with the
    free list empty most of the time. Every call is held to the
    reckoning, and every eviction round to the victims it predicts."""

    def __init__(self, kv, page, seed):
        self.kv, self.page = kv, page
        self.rng = np.random.default_rng(seed)
        self.live = []                  # (tokens, blocks) of open requests
        self.prompts = []
        self.batches, self.inserted = [], []
        kv.on_evict = lambda b: self.batches.append(list(b))
        kv.on_insert = self.inserted.append
        self.evicted = 0

    def _prompt(self):
        n = int(self.rng.integers(1, 6 * self.page))
        fresh = self.rng.integers(0, 3, size=n).tolist()
        if self.prompts and self.rng.random() < 0.7:
            base = self.prompts[int(self.rng.integers(len(self.prompts)))]
            cut = int(self.rng.integers(0, len(base) + 1))
            fresh = (base[:cut] + fresh)[:8 * self.page]
        self.prompts = (self.prompts + [fresh])[-24:]
        return fresh

    def _allocate(self, n):
        kv = self.kv
        free = kv.pool.free_count()
        if n > free + invariants.reckon_evictable(kv):
            with pytest.raises(NoFreeBlocks):
                kv.allocate(n)
            assert kv.pool.free_count() == free    # nothing was taken
            return None
        want = [(kv.chain_tokens(v), v.block, v.origin)
                for v in invariants.reckon_victims(kv, max(0, n - free))]
        self.batches.clear()
        out = kv.allocate(n)
        got = [v for batch in self.batches for v in batch]
        assert got == want
        if want:
            assert len(self.batches) == 1          # one round, one call
        self.evicted += len(want)
        assert kv.evictions == self.evicted
        assert len(out) == n == len(set(out))
        return out

    def _insert(self, tokens, blocks):
        kv, page = self.kv, self.page
        known = kv.match_len(tokens) // page
        upto = min(len(tokens) // page, len(blocks)) if kv.reuse else 0
        self.inserted.clear()
        created = kv.insert(tokens, blocks)
        assert created == max(0, upto - known)
        assert self.inserted == [tuple(tokens[:k * page])
                                 for k in range(known + 1, upto + 1)]

    def step(self):
        kv, rng = self.kv, self.rng
        op = rng.choice(["admit", "admit", "blind", "grow", "finish",
                         "finish", "export", "probe"])
        if op in ("admit", "blind"):
            tokens = self._prompt()
            held = []
            if op == "admit":       # "blind" skips the match: its insert
                held, n = kv.match(tokens[:-1])    # meets nodes it holds
                assert n == len(held) * self.page  # no reference on
                _check_against_reckoning(kv)
            need = -(-len(tokens) // self.page) - len(held)
            fresh = self._allocate(need)
            if fresh is None:
                kv.release(held)
            else:
                _check_against_reckoning(kv)
                self._insert(tokens, held + fresh)
                self.live.append((tokens, held + fresh))
        elif op == "grow" and self.live:
            fresh = self._allocate(int(rng.integers(1, 3)))
            if fresh is not None:
                self.live[int(rng.integers(len(self.live)))][1].extend(fresh)
        elif op == "finish" and self.live:
            _, blocks = self.live.pop(int(rng.integers(len(self.live))))
            kv.release(blocks)
        elif op == "export" and self.prompts:
            tokens = self.prompts[int(rng.integers(len(self.prompts)))]
            stamp = kv._clock
            held, _ = kv.lookup(tokens)
            assert kv._clock == stamp              # no LRU bump
            _check_against_reckoning(kv)
            kv.release(held)
        elif self.prompts:
            kv.match_len(self.prompts[-1])
            kv.chain_origin(self.prompts[-1])
        _check_against_reckoning(kv)


class TestKeptCounts:
    """PR 34: the cache keeps what it used to recompute by walking the
    tree. The walks live on in ``chaos/invariants.py`` as the oracle."""

    @pytest.mark.parametrize("page", [1, 4, 8])
    @pytest.mark.parametrize("reuse", [True, False])
    def test_random_walk_holds_to_the_reckoning(self, reuse, page):
        kv = RadixCache(40, page)
        kv.reuse = reuse
        walker = _Walker(kv, page, seed=1000 * page + reuse)
        for _ in range(2500):
            walker.step()
        if reuse:
            assert walker.evicted > 100, "the walk never ran the pool dry"
        for _, blocks in walker.live:
            kv.release(blocks)
        _check_against_reckoning(kv)
        assert kv.available() == kv.pool.n_blocks - 1

    @staticmethod
    def _beside(n_other):
        """A cache that holds ``n_other`` blocks of other prompts (chains
        of 10, released), and one 4-block chain inserted and held."""
        kv = RadixCache(n_other + 64, PAGE)
        for i in range(n_other // 10):
            blocks = kv.allocate(10)
            kv.insert([1000 + i] + list(range(10 * PAGE - 1)), blocks)
            kv.release(blocks)
        assert kv.cached_count() == n_other
        chain = list(range(7, 7 + 4 * PAGE))
        blocks = kv.allocate(4)
        return kv, chain, blocks

    def test_allocate_from_the_free_list_visits_no_node(self):
        kv, _, _ = self._beside(5000)
        before = kv.tree_visits
        kv.allocate(1)
        kv.available()
        assert kv.tree_visits == before

    @pytest.mark.parametrize("op", ["insert", "release", "match", "lookup"])
    def test_a_chain_costs_the_same_beside_any_tree(self, op):
        def visits(n_other):
            kv, chain, blocks = self._beside(n_other)
            if op != "insert":
                kv.insert(chain, blocks)
            if op in ("match", "lookup"):
                kv.release(blocks)
            before = kv.tree_visits
            if op == "insert":
                assert kv.insert(chain, blocks) == 4
            elif op == "release":
                kv.release(blocks)
            else:
                assert getattr(kv, op)(chain)[1] == 4 * PAGE
            return kv.tree_visits - before

        assert 4 <= visits(100) == visits(5000) <= 12

    def test_eviction_cost_does_not_follow_the_tree(self):
        def visits(n_other):
            kv, _, _ = self._beside(n_other)
            kv.allocate(kv.pool.free_count())      # the free list is empty
            before = kv.tree_visits
            kv.allocate(8)                         # eight victims
            return kv.tree_visits - before

        assert visits(100) == visits(5000) <= 24

    def test_the_eviction_heap_stays_bounded(self):
        """A hot leaf that is matched and released for ever leaves one
        stale entry a round: the heap is rebuilt from the tree before it
        outgrows the pool, and still gives the victims up in order."""
        kv, chain, blocks = self._beside(20)
        kv.insert(chain, blocks)
        kv.release(blocks)
        for _ in range(500):
            held, _ = kv.match(chain)
            kv.release(held)
            assert len(kv._lru) <= 2 * len(kv._node_of) + 65
        _check_against_reckoning(kv)
        kv.allocate(kv.pool.free_count())
        order = [v.block for v in invariants.reckon_victims(kv, 24)]
        seen = []
        kv.on_evict = lambda victims: seen.extend(
            block for _, block, _ in victims)
        for _ in range(24):
            kv.allocate(1)
        assert seen == order and order[-4:] == blocks[::-1]

    def test_stats_may_be_read_from_another_thread(self):
        import sys
        import threading

        kv = RadixCache(64, PAGE)
        errors, halt = [], threading.Event()

        def reader():
            try:
                while not halt.is_set():
                    st = kv.stats()
                    assert 0 <= st.blocks_cached <= st.blocks_total
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        thread = threading.Thread(target=reader, daemon=True)
        thread.start()
        try:
            for i in range(3000):                  # inserts, then evicts
                blocks = kv.allocate(3)
                kv.insert([i] + list(range(3 * PAGE - 1)), blocks)
                kv.release(blocks)
        finally:
            halt.set()
            thread.join(timeout=30)
            sys.setswitchinterval(interval)
        assert not thread.is_alive()
        assert not errors, errors
        assert kv.evictions > 2000


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

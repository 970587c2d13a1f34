"""Paged KV-cache pool with radix prefix caching (serving plane).

The continuous-batching engine historically gave every decode slot a dense
``[max_seq_len, ...]`` KV allocation and prefilled every prompt from token
0 — HBM paid for the *longest possible* request while serving mostly short
ones, and shared prompt prefixes (system prompts, few-shot headers) were
recomputed on every arrival. This module is the standard serving-fabric
fix, in two pieces:

- :class:`BlockPool` — a fixed pool of ``page_size``-token KV **blocks**.
  A request's cache is a *page table* (list of block ids) instead of a
  dense row, so HBM is committed page-by-page as the request actually
  grows. Block 0 is a reserved scratch page: idle decode rows and padded
  positions write there, so an engine-side indexing bug can corrupt only
  garbage nobody reads.
- :class:`RadixCache` — the pool plus a ref-counted radix tree over
  **full-block token chunks**: node = one block whose ``page_size`` token
  ids are the edge key. A new request walks its prompt down the tree and
  reuses every matched block (prefill skips those tokens entirely); full
  prompt blocks are inserted back after prefill so the next request can
  hit them. Blocks referenced by an in-flight request are pinned
  (refcount > 0); unreferenced tree leaves are evicted LRU under memory
  pressure — eviction can therefore never touch live state.

LRU order uses a logical clock (a counter bumped per tree operation), not
wall time, so eviction order is deterministic under test.

What a call costs follows the blocks and the chain that call touches, never
the size of the tree: the cache keeps its cached count, its evictable count
and its eviction order as it goes (see :class:`RadixCache`). The
from-scratch walks that define those numbers live in
``lzy_tpu/chaos/invariants.py`` (``audit_kv_counts``), where the audit and
the tests hold the kept numbers to them.

Prefix hit rate, blocks in use/free, evictions, and prefill tokens saved
are exported via ``lzy_tpu.utils.metrics.REGISTRY`` and surfaced through
``InferStats`` (see ``serving/engine.py``).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

_BLOCKS = REGISTRY.gauge(
    "lzy_kv_blocks", "KV block pool capacity (scratch block included)")
_FREE = REGISTRY.gauge(
    "lzy_kv_blocks_free", "KV blocks on the free list")
_CACHED = REGISTRY.gauge(
    "lzy_kv_blocks_cached",
    "unreferenced blocks held by the prefix tree (reusable, evictable)")
_EVICTIONS = REGISTRY.counter(
    "lzy_kv_evictions_total", "prefix-tree blocks evicted under pressure")
_HIT_TOKENS = REGISTRY.counter(
    "lzy_kv_prefix_hit_tokens_total",
    "prompt tokens served from cached prefix blocks (prefill skipped)")
_LOOKUP_TOKENS = REGISTRY.counter(
    "lzy_kv_prefix_lookup_tokens_total",
    "prompt tokens offered to the prefix tree at admission")
_HIT_RATE = REGISTRY.gauge(
    "lzy_kv_prefix_hit_rate",
    "cumulative hit tokens / lookup tokens")
_TREE_VISITS = REGISTRY.counter(
    "lzy_kv_tree_visits_total",
    "radix-tree nodes the KV manager's calls touched (descents, parent-chain "
    "updates, eviction picks)")
_CALLS = REGISTRY.counter(
    "lzy_kv_calls_total",
    "KV manager calls: allocate, release, match, lookup, insert, available")


class NoFreeBlocks(RuntimeError):
    """The pool cannot satisfy an allocation even after evicting every
    unreferenced cached block — the caller must wait, shed, or preempt."""


@dataclasses.dataclass
class KVCacheStats:
    blocks_total: int          # pool capacity minus the scratch block
    blocks_free: int
    blocks_cached: int         # unreferenced blocks kept by the tree
    evictions: int
    prefix_hit_tokens: int
    prefix_lookup_tokens: int

    @property
    def prefill_tokens_saved(self) -> int:
        return self.prefix_hit_tokens

    @property
    def hit_rate(self) -> float:
        if self.prefix_lookup_tokens == 0:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_lookup_tokens


class BlockPool:
    """Fixed pool of ``page_size``-token KV blocks with refcounts.

    Allocation hands out block *ids* (rows of the engine's pooled
    ``[n_blocks, page_size, kv, d]`` cache arrays); the K/V data itself
    lives on device. Refcounts count request holders — the pool never
    decides what an unreferenced block means (cached vs dead); that policy
    lives in :class:`RadixCache`.
    """

    def __init__(self, n_blocks: int, page_size: int):
        # 0: the pool of a model that keeps no paged leaf at all
        # (models/serving.py): no block, not even the scratch one
        if n_blocks < 2 and n_blocks != 0:
            raise ValueError(
                f"pool needs >= 2 blocks (1 scratch + 1 usable), got "
                f"{n_blocks}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_blocks = n_blocks
        self.page_size = page_size
        # LIFO free list, block 0 reserved as the scratch page
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._ref = [0] * n_blocks

    def free_count(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        """One fresh block, refcount 1 (the caller's reference)."""
        if not self._free:
            raise NoFreeBlocks("kv block pool exhausted")
        block = self._free.pop()
        self._ref[block] = 1
        return block

    def incref(self, block: int) -> int:
        self._ref[block] += 1
        return self._ref[block]

    def decref(self, block: int) -> int:
        if self._ref[block] <= 0:
            raise AssertionError(f"decref of unreferenced block {block}")
        self._ref[block] -= 1
        return self._ref[block]

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def release_to_free(self, block: int) -> None:
        if self._ref[block] != 0:
            raise AssertionError(
                f"freeing block {block} with refcount {self._ref[block]}")
        self._free.append(block)


class _Node:
    """One radix-tree node: a full block whose edge key is its token chunk."""

    __slots__ = ("chunk", "block", "children", "parent", "last_access",
                 "origin", "busy")

    def __init__(self, chunk: Optional[Tuple[int, ...]], block: Optional[int],
                 parent: Optional["_Node"]):
        self.chunk = chunk
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.parent = parent
        self.last_access = 0
        # provenance: which remote producer (disagg prefill replica) this
        # block's KV came from; None = computed locally. Read by
        # chain_origin so replies can say who REALLY produced the KV.
        self.origin: Optional[str] = None
        # 1 if this node's block is referenced, plus the children whose
        # own ``busy`` is not 0: 0 says the whole subtree is unreferenced,
        # which is when the block counts in ``available()``
        self.busy = 0


class RadixCache:
    """Block pool + ref-counted radix tree over token-id chunks.

    The engine calls, per request lifecycle:

    - :meth:`match` at prefill — longest cached whole-block prefix; the
      matched blocks are incref'd (pinned for the request's lifetime).
    - :meth:`allocate` — fresh blocks for the unmatched suffix and for
      decode growth, evicting LRU unreferenced tree leaves as needed.
    - :meth:`insert` after prefill — registers the prompt's full blocks
      so future requests can hit them.
    - :meth:`release` on EOS/cancel/preempt — drops the request's refs;
      unreferenced blocks *in* the tree stay cached (evictable),
      unreferenced blocks *outside* it return to the free list.

    Three things are kept as the calls go, so that no call walks the tree:
    ``_cached`` (tree blocks with refcount 0), ``_evictable`` (tree blocks
    in a fully unreferenced subtree: nodes whose ``busy`` is 0) and
    ``_lru``, a heap of the unreferenced leaves by ``last_access``. They
    change where a tree block's refcount crosses 0 <-> 1 (``_mark_busy`` /
    ``_mark_idle`` carry the crossing up the parent chain, and stop at the
    first ancestor it does not flip), where a node is created and where a
    victim leaves. Heap entries are never removed in place: one whose node
    has left the tree, gained a child or a reference is dropped when it
    reaches the top, one whose ``last_access`` has moved is pushed back
    under the new value. Two candidates cannot carry the same
    ``last_access`` (a tick of the clock stamps one root-to-node path, and
    two leaves never share one); were they to, the one that entered the
    heap first leaves first.

    ``stats()`` and the gauges read those integers only, so another thread
    may call ``stats()`` while the engine's thread changes the tree.
    """

    def __init__(self, n_blocks: int, page_size: int):
        self.pool = BlockPool(n_blocks, page_size)
        self.page_size = page_size
        self._root = _Node(None, None, None)
        self._node_of: Dict[int, _Node] = {}
        self._clock = 0          # logical LRU clock — deterministic
        # bumped only when the TREE changes shape (insert created nodes,
        # eviction removed one) — not on lookups: the gateway's
        # advertisement cache keys on it to skip re-hashing an
        # unchanged cache every tick
        self.structure_version = 0
        self.evictions = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        # False for a model that keeps per-slot state beside its pages
        # (models/serving.py): a matched prefix would skip prefill for
        # tokens whose state nobody kept, so every lookup finds nothing
        # (and still counts as a lookup) and nothing is inserted
        self.reuse = True
        # tier hooks (serving/kv_tier.py): ``on_evict(victims)`` receives
        # every victim of ONE eviction round — ``[(chain_tokens, block,
        # origin), ...]`` — in a single call BEFORE the evicted leaves'
        # blocks return to the free list, so the engine's demotion hook
        # can gather their K/V rows to host memory there, one gather per
        # cache leaf for the round; ``on_insert(chain)``
        # fires for each NEWLY created tree node with its full root→node
        # token chain — the engine drops any demoted-tier copy of that
        # chain (the HBM copy is authoritative, and a chain must live in
        # exactly one tier for the conservation audit to hold). Both are
        # guarded: a hook failure degrades to classic eviction / a
        # harmless stale tier entry, never a broken tree.
        self.on_evict = None
        self.on_insert = None
        self._cached = 0
        self._evictable = 0
        self._lru: List[Tuple[int, int, _Node]] = []
        self._lru_seq = 0
        # what the calls cost, cumulative: tree nodes touched, and calls.
        # Plain integers; _update_gauges carries them to the registry
        self.tree_visits = 0
        self.calls = 0
        self._flushed = (0, 0)
        self._update_gauges()

    # -- tree ----------------------------------------------------------------

    def _chunks(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        page = self.page_size
        return [tuple(tokens[i:i + page])
                for i in range(0, len(tokens) - len(tokens) % page, page)]

    def _walk(self, tokens: Sequence[int]) -> List[_Node]:
        """Prefix descent shared by every lookup flavor: the chain of
        tree nodes matching ``tokens``' whole-block prefix. The callers
        layer their own policy (refs, metrics, LRU bumps) on top, so the
        descent rule itself can never diverge between the admission path
        and the export path."""
        node = self._root
        out: List[_Node] = []
        if not self.reuse:
            return out
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            out.append(child)
            node = child
        return out

    def match(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens`` in whole blocks; returns
        ``(block_ids, n_tokens_matched)``. Matched blocks are incref'd —
        callers own one reference per returned block (drop it with
        :meth:`release`). Pass ``prompt[:-1]`` to guarantee at least one
        suffix token remains for prefill (logits need a real forward
        position)."""
        self.calls += 1
        self._clock += 1
        chain = self._walk(tokens)
        self.tree_visits += len(chain)
        for child in chain:
            child.last_access = self._clock
        blocks = self._pin(chain)
        self.hit_tokens += len(blocks) * self.page_size
        self.lookup_tokens += len(tokens)
        _HIT_TOKENS.inc(len(blocks) * self.page_size)
        _LOOKUP_TOKENS.inc(len(tokens))
        self._update_gauges()
        return blocks, len(blocks) * self.page_size

    def lookup(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached whole-block prefix with the blocks PINNED (one
        reference each — drop them with :meth:`release`) but WITHOUT the
        hit/lookup accounting or LRU bump of :meth:`match`. This is the
        KV-export path (disaggregated serving reads blocks out of the
        tree to ship them to a decode replica): an export must not
        distort the admission hit-rate stats or the eviction order the
        serving traffic established."""
        self.calls += 1
        chain = self._walk(tokens)
        self.tree_visits += len(chain)
        blocks = self._pin(chain)
        return blocks, len(blocks) * self.page_size

    def _pin(self, chain: List[_Node]) -> List[int]:
        """One reference on each node's block, for the caller."""
        blocks: List[int] = []
        for node in chain:
            blocks.append(node.block)
            if self.pool.incref(node.block) == 1:
                self._cached -= 1
                self._mark_busy(node)
        return blocks

    def _mark_busy(self, node: _Node) -> None:
        """``node``'s block took its first reference: its subtree, and
        every ancestor's whose subtree was unreferenced until now, stops
        being evictable."""
        while node.parent is not None:
            self.tree_visits += 1
            node.busy += 1
            if node.busy != 1:
                return
            self._evictable -= 1
            node = node.parent

    def _mark_idle(self, node: _Node) -> None:
        """``node``'s block lost its last reference: the reverse of
        :meth:`_mark_busy`."""
        if not node.children:
            self._push_candidate(node)
        while node.parent is not None:
            self.tree_visits += 1
            node.busy -= 1
            if node.busy:
                return
            self._evictable += 1
            node = node.parent

    def _push_candidate(self, node: _Node) -> None:
        """``node`` became an unreferenced leaf: it joins the eviction
        order. The heap is rebuilt from the tree once stale entries
        outnumber the nodes two to one, which keeps it no larger than the
        pool at a cost that the pushes since the last rebuild have paid."""
        if len(self._lru) > 2 * len(self._node_of) + 64:
            self.tree_visits += len(self._node_of)
            self._lru = [
                (n.last_access, i, n)
                for i, n in enumerate(self._node_of.values())
                if not n.children and self.pool.refcount(n.block) == 0]
            heapq.heapify(self._lru)
            self._lru_seq = len(self._lru)
        self._lru_seq += 1
        heapq.heappush(self._lru, (node.last_access, self._lru_seq, node))

    def match_len(self, tokens: Sequence[int]) -> int:
        """Read-only probe of :meth:`match` — no refs taken, no metrics,
        no LRU bump. Safe to call repeatedly (tests and operators peek at
        cache contents with it) without distorting hit-rate stats or
        eviction order."""
        return len(self._walk(tokens)) * self.page_size

    def insert(self, tokens: Sequence[int], blocks: Sequence[int],
               origin: Optional[str] = None) -> int:
        """Register full-chunk ``blocks`` (one per ``page_size`` chunk of
        ``tokens``) in the tree; returns how many nodes were newly created.
        Chunks that already have a node keep the existing block — the
        caller's duplicate block simply stays private to its request.
        ``origin`` tags NEWLY created nodes with the remote producer of
        their KV (a disagg prefill replica id); existing nodes keep their
        provenance (whoever computed the resident bytes)."""
        self.calls += 1
        if not self.reuse:
            return 0
        self._clock += 1
        node = self._root
        created = 0
        chain: List[int] = []
        for chunk, block in zip(self._chunks(tokens), blocks):
            chain.extend(chunk)
            child = node.children.get(chunk)
            if child is None:
                child = _Node(chunk, block, node)
                child.origin = origin
                node.children[chunk] = child
                self._node_of[block] = child
                created += 1
                # the inserting request holds the block, as a rule
                self._evictable += 1
                if self.pool.refcount(block) > 0:
                    self._mark_busy(child)
                else:
                    self._cached += 1
                    self._push_candidate(child)
                if self.on_insert is not None:
                    try:
                        self.on_insert(tuple(chain))
                    except Exception:  # noqa: BLE001 — advisory hook
                        pass
            self.tree_visits += 1
            child.last_access = self._clock
            node = child
        if created:
            self.structure_version += 1
        self._update_gauges()
        return created

    def chain_origin(self, tokens: Sequence[int]) -> Optional[str]:
        """Remote producer of the cached prefix covering ``tokens``, if
        any node in the matched chain was imported (first imported node
        wins — the deepest local extension rides on that producer's
        prefix). Read-only: no refs, no metrics, no LRU bump."""
        for child in self._walk(tokens):
            if child.origin is not None:
                return child.origin
        return None

    # -- allocation / eviction ----------------------------------------------

    def allocate(self, n: int) -> List[int]:
        """``n`` fresh blocks (refcount 1 each), evicting LRU unreferenced
        tree leaves as needed. Raises :class:`NoFreeBlocks` — *before*
        taking any block — if the pool cannot cover the request even after
        evicting everything evictable.

        Evictions for one allocate call form ONE round: every victim is
        detached first, the demotion hook runs once over the whole batch
        (``on_evict`` — one device→host gather per cache leaf instead of
        per block), and only then do the blocks return to the free list —
        the hook must see the victims' K/V before anything can overwrite
        it."""
        self.calls += 1
        have = self.pool.free_count() + self._evictable
        if n > have:
            raise NoFreeBlocks(
                f"need {n} blocks, only {have} available "
                f"(free + evictable)")
        victims: List[_Node] = []
        while self.pool.free_count() + len(victims) < n:
            victim = self._detach_victim()
            assert victim is not None, \
                "available() promised an evictable block"
            victims.append(victim)
        if victims:
            if trace.ON:
                trace.event(trace.KV_EVICT, blocks=len(victims))
            self._offer_demotions(victims)
            for victim in victims:
                self.pool.release_to_free(victim.block)
                self.evictions += 1
                _EVICTIONS.inc()
        out = [self.pool.alloc() for _ in range(n)]
        self._update_gauges()
        return out

    def _offer_demotions(self, victims: List["_Node"]) -> None:
        """Offer one eviction round's victims for demotion (guarded —
        a hook failure degrades to the classic drop)."""
        if self.on_evict is None:
            return
        try:
            self.on_evict([(self.chain_tokens(v), v.block, v.origin)
                           for v in victims])
        except Exception:  # noqa: BLE001 — demotion is advisory
            pass

    def _detach_victim(self) -> Optional["_Node"]:
        """Detach the LRU unreferenced leaf from the tree WITHOUT
        returning its block to the free list (the caller batches the
        demotion hook first).  ``chain_tokens`` stays valid on the
        detached node — parents are intact, only the child link is cut."""
        victim = self._next_victim()
        if victim is None:
            return None
        heapq.heappop(self._lru)
        parent = victim.parent
        del parent.children[victim.chunk]
        del self._node_of[victim.block]
        self.structure_version += 1
        self._cached -= 1
        self._evictable -= 1
        if (parent is not self._root and not parent.children
                and self.pool.refcount(parent.block) == 0):
            self.tree_visits += 1
            self._push_candidate(parent)
        return victim

    def _next_victim(self) -> Optional["_Node"]:
        """The unreferenced leaf with the lowest ``last_access``, left in
        the tree and at the top of the heap; stale entries above it go."""
        lru = self._lru
        while lru:
            self.tree_visits += 1
            stamp, _, node = lru[0]
            if (self._node_of.get(node.block) is not node or node.children
                    or self.pool.refcount(node.block) != 0):
                heapq.heappop(lru)
            elif stamp != node.last_access:
                self._lru_seq += 1
                heapq.heapreplace(
                    lru, (node.last_access, self._lru_seq, node))
            else:
                return node
        return None

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block. Unreferenced blocks in the tree
        stay cached (evictable); unreferenced blocks outside it return to
        the free list immediately."""
        self.calls += 1
        for b in blocks:
            if self.pool.decref(b) == 0:
                node = self._node_of.get(b)
                if node is None:
                    self.pool.release_to_free(b)
                else:
                    self._cached += 1
                    self._mark_idle(node)
        self._update_gauges()

    def chain_tokens(self, node: "_Node") -> List[int]:
        """The full root→``node`` token chain (the tier identity of the
        node's block)."""
        chunks: List[Tuple[int, ...]] = []
        while node is not self._root and node is not None:
            chunks.append(node.chunk)
            node = node.parent
        out: List[int] = []
        for chunk in reversed(chunks):
            out.extend(chunk)
        return out

    def available(self) -> int:
        """Blocks an :meth:`allocate` could obtain right now: the free
        list plus every tree block in a fully-unreferenced subtree (those
        evict leaf-by-leaf until the whole subtree is gone). A referenced
        node can sit under an unreferenced ancestor (``insert`` keeps an
        existing node's block), so the count is of subtrees, not of
        blocks."""
        self.calls += 1
        return self.pool.free_count() + self._evictable

    def cached_count(self) -> int:
        """Tree blocks currently unreferenced (reusable, evictable)."""
        return self._cached

    # -- observability -------------------------------------------------------

    def _update_gauges(self) -> None:
        _BLOCKS.set(float(self.pool.n_blocks))
        _FREE.set(float(self.pool.free_count()))
        _CACHED.set(float(self._cached))
        _HIT_RATE.set(self.hit_tokens / self.lookup_tokens
                      if self.lookup_tokens else 0.0)
        visits, calls = self._flushed
        self._flushed = (self.tree_visits, self.calls)
        if self.tree_visits != visits:     # the decode round's allocate(1)
            _TREE_VISITS.inc(self.tree_visits - visits)    # touches no node
        _CALLS.inc(self.calls - calls)

    def stats(self) -> KVCacheStats:
        return KVCacheStats(
            blocks_total=max(0, self.pool.n_blocks - 1),  # less scratch
            blocks_free=self.pool.free_count(),
            blocks_cached=self._cached,
            evictions=self.evictions,
            prefix_hit_tokens=self.hit_tokens,
            prefix_lookup_tokens=self.lookup_tokens,
        )


def blocks_for(n_tokens: int, page_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache positions."""
    return -(-n_tokens // page_size)


def kv_block_bytes(*, page_size: int, n_kv_heads: int, head_dim: int,
                   n_layers: int = 1, dtype="bfloat16",
                   kv_quant: Optional[str] = None) -> int:
    """HBM payload bytes ONE pool block commits across the model: K + V
    arrays for every layer (each decoder layer owns a pool of the same
    block-id space, so a block allocation pins a row in all of them).
    ``kv_quant="int8"`` stores one byte per element — exactly half of
    bf16, which is what doubles resident block count at fixed pool
    bytes. Quantization sidecars (per-position scale/zero-point,
    :func:`kv_quant_sidecar_bytes`) are metadata accounted OUTSIDE the
    payload budget, like the page tables themselves."""
    elem = 1 if kv_quant == "int8" else np.dtype(dtype).itemsize
    return 2 * n_layers * page_size * n_kv_heads * head_dim * elem


def kv_quant_sidecar_bytes(*, page_size: int, n_kv_heads: int,
                           n_layers: int = 1,
                           kv_quant: Optional[str] = None) -> int:
    """Bytes of quantization metadata riding next to one block: an f32
    scale and zero-point per written position per head, for K and for V,
    per layer (``ops/paged_attention.KVQuant``). Zero without
    quantization. ~``8 / head_dim`` of the int8 payload — small, but
    reported so capacity planning can be honest about it."""
    if kv_quant is None:
        return 0
    return 2 * n_layers * page_size * n_kv_heads * 2 * 4


def blocks_for_bytes(pool_bytes: int, *, page_size: int, n_kv_heads: int,
                     head_dim: int, n_layers: int = 1, dtype="bfloat16",
                     kv_quant: Optional[str] = None) -> int:
    """Pool size (block count, scratch included) a payload byte budget
    buys — the sizing rule behind ``PagedInferenceEngine(kv_pool_bytes=)``
    and ``--serve-kv-pool-mb``. At a fixed budget, ``kv_quant="int8"``
    yields 2x the blocks of bf16 — directly multiplying radix-cache
    working set and decode-growth headroom."""
    per = kv_block_bytes(page_size=page_size, n_kv_heads=n_kv_heads,
                         head_dim=head_dim, n_layers=n_layers,
                         dtype=dtype, kv_quant=kv_quant)
    return max(2, pool_bytes // per)


# -- pages with a second lifetime: behind a window they go back ----------------------

_WINDOW_BLOCKS = REGISTRY.gauge(
    "lzy_kv_window_blocks",
    "window-page pool capacity (scratch block included): pages of the "
    "layers that read only their last positions")
_WINDOW_FREE = REGISTRY.gauge(
    "lzy_kv_window_blocks_free", "window pages on the free list")
_WINDOW_LIVE = REGISTRY.gauge(
    "lzy_kv_window_blocks_live", "window pages held by rows and prefill jobs")
_WINDOW_RELEASED = REGISTRY.counter(
    "lzy_kv_window_pages_released_total",
    "window pages returned to their pool because they fell wholly behind "
    "the window of a live row or of a prefill job")


class WindowRow:
    """One row's pages of the window kind: ``table`` is its page table in
    position order (0, the scratch block, wherever it holds nothing) and the
    pages ``lo .. hi`` of it are held; ``reserve`` is what the pool keeps
    aside for it beyond those (a prefill job's, so that a job admitted
    beside others never finds the pool empty half way through its prompt)."""

    __slots__ = ("table", "lo", "hi", "reserve", "budget")

    def __init__(self, pages_per_seq: int):
        self.table = np.zeros((pages_per_seq,), np.int32)
        self.lo = self.hi = self.reserve = self.budget = 0

    @property
    def held(self) -> int:
        return self.hi - self.lo


def window_bound(window: int, chunk: int, page_size: int,
                 pages_per_seq: int) -> int:
    """The most window pages a row holds at once: the window, the prefill
    chunk being written, and a page for where the window's edge falls
    inside one; never more than the table."""
    return min(pages_per_seq, blocks_for(window + chunk, page_size) + 1)


def divide_pool(pool_bytes: int, base, most_window: int,
                most_paged: int, page_size: int) -> tuple:
    """``kv_pool_bytes`` between the two kinds of page of a model with
    ``window`` leaves: ``(window blocks, bytes for the paged kind)``.
    Each kind gets what ``slots`` rows at ``max_seq_len`` come to at
    their most (``most_window``, ``most_paged`` blocks: a row never
    holds more, and with the prefix cache off nothing else would), if
    the budget covers both; where it does not, each gets its share of
    the budget in proportion to that. Each kind at its own price: a
    model whose window layers cache another width answers
    ``window_token_bytes`` (``models/serving.py``); absent, both cost
    ``kv_token_bytes``."""
    token = base.kv_token_bytes(None)
    per_window = page_size * base.window_layers * getattr(
        base, "window_token_bytes", base.kv_token_bytes)(None)
    want_window = most_window * per_window
    want_paged = most_paged * page_size * base.kv_layers * token
    if want_window + want_paged <= pool_bytes:
        return most_window, want_paged
    for_window = pool_bytes * want_window // (want_window + want_paged)
    return max(2, for_window // per_window), pool_bytes - for_window


class WindowPages:
    """The allocator of the paged leaves that lose their tokens behind a
    window (``models/serving.py``, kind ``window``): a query at position
    ``p`` reads keys ``p - window < j <= p`` there, so a page that lies
    wholly behind the window of everything dispatched is returned to the
    free list, where another row can take it, and the row's table reads
    scratch in its place. No tree and no sharing: a page has one holder.

    A row never holds more than :attr:`bound` pages
    (:func:`window_bound`), however long its context.

    Programs run on the device in the order they were dispatched, so a page
    released while a round is in flight is safe to hand out at once: whoever
    takes it writes it in a later program."""

    def __init__(self, n_blocks: int, page_size: int, window: int,
                 pages_per_seq: int, chunk: int):
        self.pool = BlockPool(n_blocks, page_size)
        self.page_size, self.window = page_size, window
        self.pages_per_seq = pages_per_seq
        self.bound = window_bound(window, chunk, page_size, pages_per_seq)
        self.reserved = 0          # set aside for prefill jobs under way
        self.released = 0          # pages returned behind a window, ever
        self._update_gauges()

    def row(self) -> WindowRow:
        return WindowRow(self.pages_per_seq)

    def need(self, n_tokens: int) -> int:
        """The most pages a prompt of ``n_tokens`` holds at once."""
        return min(blocks_for(n_tokens, self.page_size), self.bound)

    def available(self) -> int:
        """Free pages no prefill job under way has been promised."""
        return self.pool.free_count() - self.reserved

    def live(self) -> int:
        return self.pool.n_blocks - 1 - self.pool.free_count()

    def reserve(self, row: WindowRow, n_tokens: int) -> None:
        """Set aside what a prompt of ``n_tokens`` will hold at its most,
        or raise :class:`NoFreeBlocks`."""
        need = self.need(n_tokens)
        if self.available() < need:
            raise NoFreeBlocks(
                f"window pages: {need} needed, {self.available()} free")
        row.budget = row.reserve = need
        self.reserved += need

    def unreserve(self, row: WindowRow) -> None:
        """The prompt is done (or dropped): what was set aside and not
        taken is anybody's again."""
        self.reserved -= row.reserve
        row.reserve = row.budget = 0

    def cover(self, row: WindowRow, first: int, end: int) -> bool:
        """Make ``row`` hold the pages of positions ``first .. end - 1``
        (``first``: the oldest position anything dispatched from now on
        reads; below 0 counts as 0): pages wholly behind ``first`` go back
        to the free list, pages up to ``end`` are taken, from the row's
        reserve first. True if the table changed. Raises
        :class:`NoFreeBlocks` with nothing taken."""
        page = self.page_size
        lo = max(row.lo, min(max(first, 0) // page, row.hi))
        hi = max(row.hi, blocks_for(end, page))
        gone, take = lo - row.lo, hi - row.hi
        # a job under way keeps set aside what it may still come to hold
        reserve = max(0, row.budget - (hi - lo)) if row.budget else 0
        if self.available() + gone - take + row.reserve - reserve < 0:
            raise NoFreeBlocks(
                f"window pages: {take} needed, {self.available()} free "
                f"beside what prefill jobs were promised")
        self._give_back(row, lo)
        if gone:
            self.released += gone
            _WINDOW_RELEASED.inc(gone)
        for at in range(row.hi, hi):
            row.table[at] = self.pool.alloc()
        self.reserved += reserve - row.reserve
        row.reserve, row.lo, row.hi = reserve, lo, hi
        if gone or take:
            self._update_gauges()
        return bool(gone or take)

    def _give_back(self, row: WindowRow, upto: int) -> None:
        """The row's pages ``row.lo .. upto`` go back to the free list and
        its table reads scratch there."""
        for at in range(row.lo, upto):
            block = int(row.table[at])
            row.table[at] = 0
            self.pool.decref(block)
            self.pool.release_to_free(block)

    def release(self, row: WindowRow) -> None:
        """The row ended: every page it holds goes back, its reserve too."""
        self._give_back(row, row.hi)
        row.lo = row.hi = 0
        self.unreserve(row)
        self._update_gauges()

    def _update_gauges(self) -> None:
        _WINDOW_BLOCKS.set(float(self.pool.n_blocks))
        _WINDOW_FREE.set(float(self.pool.free_count()))
        _WINDOW_LIVE.set(float(self.live()))

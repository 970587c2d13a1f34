"""The engine's KV I/O: what moves a block's payload into or out of the pool
between rounds, or pins it there across them.

One object, :class:`KvIO`, which ``PagedInferenceEngine`` owns and which
reaches the scheduler through what it is built from alone. It holds the
tiers (``serving/kv_tier.py``: radix eviction DEMOTES block payloads to
pinned host RAM and onward to storage instead of dropping them, admission
PROMOTES them back), export and import of a prefix between pools
(:class:`~lzy_tpu.channels.kv_transfer.KVBlockExport`, the host-side
snapshot the channels data plane moves between replicas), the parked
conversation chains of workflow-aware scheduling (``lzy_tpu/llm/sched.py``)
and the hand-off by which another thread has any of it done on the
scheduling thread: the only thread that may read or scatter the pool's
leaves, because a concurrent prefill would donate those buffers.

All of it is advisory: a failed demotion is the classic drop, a failed
promotion or import a local re-prefill, a failed park the ordinary routed
path. The four movers (demotion, promotion, export, import) move a block's
payload through ONE gather and ONE scatter: the pool's leaves at these
block ids, keyed by ``keystr(path)``, refused when the leaf set, a shape or
a dtype differs. Block *ids* never leave the pool.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from lzy_tpu.channels.kv_transfer import KVBlockExport
from lzy_tpu.chaos.faults import CHAOS
from lzy_tpu.models import serving
from lzy_tpu.serving.kv_cache import NoFreeBlocks
from lzy_tpu.serving.kv_tier import GATHER_BATCHES, HostKVTier
from lzy_tpu.utils.log import get_logger
from lzy_tpu.utils.metrics import REGISTRY

_LOG = get_logger(__name__)

# park/release events are engine-owned; the scheduler-side lzy_wfsched_*
# counters live in lzy_tpu/llm/metrics.py
PARKED = REGISTRY.counter(
    "lzy_wfsched_parked_total",
    "conversation KV chains parked (pinned resident) across tool gaps")
PARKED_RELEASED = REGISTRY.counter(
    "lzy_wfsched_parked_released_total",
    "parked chain releases by reason "
    "(reason=repark|ttl|pressure|explicit|shutdown)")


class StateLeavesUnsupported(ValueError):
    """A mechanism that shares, moves or rewinds cache by index and pages
    was asked of a model whose cache has per-slot state leaves. The message
    names the mechanism."""


class WindowLeavesUnsupported(ValueError):
    """A mechanism that moves, shares or rewinds pages by a prefix's tokens
    was asked of a model some of whose paged leaves lose their tokens
    behind a window (``models/serving.py``, kind ``window``): the pages
    behind it have gone back to their pool. The message names the
    mechanism."""


# a pool whose leaves are not all ``paged`` (models/serving.py): what shares
# a prefix is turned off (``reuse``), what rewinds cache by its index
# (``spec``) or keeps pages without their rows (``tier``) is refused when
# the engine is built, what moves or pins pages by a prefix's tokens
# (``moved``: parking, import, export) when it is called. By leaf kind, the
# error and why. Each could be made to work over a window's second kind of
# page (a hit would have to bring the window's worth of window pages with
# it); none has been
_REFUSALS = {
    serving.STATE: (StateLeavesUnsupported, {
        "reuse": "keeps per-slot state",
        "spec": "a rejected draft is rewound by moving an index, and a "
                "per-slot state that has consumed it cannot be rewound",
        "tier": "a demoted prefix is pages without the state that belongs "
                "after them",
        "moved": "it moves or pins pages by a prefix's tokens, and this "
                 "model's per-slot state is not in any page"}),
    serving.WINDOW: (WindowLeavesUnsupported, {
        "reuse": "has window leaves",
        "spec": "a rejected draft is rewound by moving an index, and a "
                "page that went back behind the drafted positions' window "
                "cannot be called back",
        "tier": "a demoted prefix is pages of one kind",
        "moved": "it moves or pins pages by a prefix's tokens, and this "
                 "model's window leaves have returned the pages behind "
                 "the window"}),
}


def leaf_refusal(kinds) -> Optional[tuple]:
    """``(error type, reasons)`` of the first leaf kind among ``kinds``
    that the mechanisms above do not work over; None for a pool of paged
    leaves alone."""
    for kind, refusal in _REFUSALS.items():
        if kind in kinds:
            return refusal
    return None


@dataclasses.dataclass
class _Call:
    """One callable another thread handed to the scheduling thread."""
    what: str                   # for the log line of a failure
    run: Callable[[], Any]
    result: Any                 # the caller's default until ``run`` returns
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)


class KvIO:
    """``payload`` / ``adopt`` read and replace the pool's leaves (the cache
    tree's, less its index leaves) and ``leaf_keys`` names them in that
    order; ``drain`` fetches the round in flight; ``wake`` wakes a parked
    loop; ``threaded`` says whether a loop thread schedules (else the
    caller is, by the engine's single-driver contract, the scheduling
    thread) and ``closed`` whether the engine refuses work; ``refusal`` is
    what :func:`leaf_refusal` says of the pool's leaf kinds;
    ``note_import(outcome, blocks)`` is told of every import applied or
    skipped."""

    def __init__(self, kv, page_size: int, clock, *, leaf_keys, refusal,
                 payload: Callable[[], list], adopt: Callable[[list], None],
                 drain: Callable[[str], Any], wake: Callable[[], None],
                 threaded: Callable[[], bool], closed: Callable[[], bool],
                 note_import: Callable[[str, int], None],
                 tier=None, host_tier_bytes: Optional[int] = None,
                 storage_tier=None, mesh_shape=None):
        self.kv = kv
        self._page = page_size
        self._clock = clock
        self._payload, self._adopt = payload, adopt
        self._drain, self._wake = drain, wake
        self._threaded, self._closed = threaded, closed
        self._note_import = note_import
        self._mesh_shape = mesh_shape
        self.leaf_keys: List[str] = list(leaf_keys)
        self._refusal = refusal
        if tier is None and (host_tier_bytes is not None
                             or storage_tier is not None):
            tier = HostKVTier(host_tier_bytes or 0, page_size,
                              storage=storage_tier)
        self.tier = tier
        if tier is not None:
            kv.on_evict = self.demote
            kv.on_insert = tier.discard
        # device→host gather accounting for the demotion path: one
        # BATCHED gather per cache leaf per eviction round (not one per
        # evicted block) — the count-of-transfers contract the batching
        # test pins
        self.gather_ops = 0
        self.gather_rounds = 0
        self._chains_cache: Optional[tuple] = None
        # transferred KVBlockExports fold into the pool+tree between
        # engine steps, strictly before admissions (a queued import is
        # resident by the time the request that wants it prefills)
        self._pending_imports: List[Any] = []
        self.imports = 0
        self.import_blocks = 0
        # key -> (blocks, expires_at): a parked conversation prefix, its
        # radix blocks carrying one pinned reference each
        # (``RadixCache.lookup``) until release, so the tool gap of a fused
        # op chain cannot evict the conversation's KV out from under step
        # 2. Mutated only on the scheduling thread; bounded by the TTL
        # sweep in ``service`` (engine-clock deadlines), shed under pool
        # pressure strictly before any resident request is preempted, and
        # released wholesale at close
        self._parked: Dict[str, tuple] = {}
        # what other threads asked of the scheduling thread, in arrival
        # order; the lock covers this list and the import queue, and no
        # call runs and no pin is released while it is held
        self._calls: List[_Call] = []
        self._lock = threading.Lock()

    # -- one gather, one scatter ---------------------------------------------

    def gather(self, blocks: Sequence[int]) -> Dict[str, np.ndarray]:
        """The payload of ``blocks``: every leaf of the pool at those ids,
        on the host, by the leaf's ``keystr``. ONE ``[n, page, ...]``
        gather and host transfer a leaf; on a sharded pool ``leaf[ids]``
        gathers the FULL logical rows (the host read assembles every
        shard), so a payload is always logical."""
        ids = jnp.asarray(blocks, jnp.int32)
        return {key: np.asarray(leaf[ids])
                for key, leaf in zip(self.leaf_keys, self._payload())}

    def scatter(self, blocks: Sequence[int],
                leaves: Dict[str, np.ndarray]) -> None:
        """Write a payload (``leaves[key]`` is ``[len(blocks), page,
        ...]``) into the pool at ``blocks``. The payload must describe
        EXACTLY this pool's leaves: a quantized payload carries int8
        codes + scale/zero-point sidecar leaves an fp pool does not have
        (and vice versa), and silently ignoring the difference would
        scatter quantization CODES into a pool that reads them as KV
        VALUES — garbage served with no error anywhere. Raises before the
        pool is touched."""
        if set(leaves) != set(self.leaf_keys):
            odd = sorted(set(leaves) ^ set(self.leaf_keys))
            raise ValueError(
                f"kv payload leaves do not match the pool's cache leaves "
                f"(off by {odd[:4]}...) — mismatched kv_quant between "
                f"the pool that gathered them and this one?")
        pool = self._payload()
        for key, leaf in zip(self.leaf_keys, pool):
            data = leaves[key]
            if (data.shape[0] != len(blocks)
                    or data.shape[1:] != leaf.shape[1:]
                    or data.dtype != leaf.dtype):
                raise ValueError(
                    f"kv leaf {data.shape}/{data.dtype} does not fit "
                    f"pool leaf {leaf.shape}/{leaf.dtype} (mismatched "
                    f"kv_quant?)")
        ids = jnp.asarray(blocks, jnp.int32)
        self._adopt([leaf.at[ids].set(jnp.asarray(leaves[key]))
                     for key, leaf in zip(self.leaf_keys, pool)])

    @staticmethod
    def _stacked(entries) -> Optional[Dict[str, np.ndarray]]:
        """Tier entries (a block each) as one payload; None where they do
        not all carry the same leaves."""
        keys = set(entries[0].leaves)
        if any(set(e.leaves) != keys for e in entries):
            return None
        return {k: np.stack([e.leaves[k] for e in entries])
                for k in entries[0].leaves}

    def _whole(self, tokens: Sequence[int]) -> List[int]:
        """The whole-block prefix of ``tokens``."""
        n = len(tokens) // self._page * self._page
        return [int(t) for t in tokens[:n]]

    def _tier_run(self, probe, prefix: List[int], depth: int) -> list:
        """What ``probe`` (the tier's ``has`` / ``take`` / ``peek``) finds
        for the blocks of ``prefix`` past the first ``depth``, chain by
        chain as far as they are contiguous."""
        found: list = []
        while (depth + len(found)) * self._page < len(prefix):
            hit = probe(tuple(prefix[:(depth + len(found) + 1) * self._page]))
            if hit is None:
                break
            found.append(hit)
        return found

    # -- the tiers -------------------------------------------------------------

    def demote(self, victims) -> None:
        """``RadixCache.on_evict``: demote one eviction round's victims —
        ``[(chain_tokens, block, origin), ...]`` — with the per-block
        device→host copies COALESCED into a single gather per cache leaf
        (int8 sidecar leaves included — they are ordinary cache leaves).
        Every failure — including the ``kvtier.demote`` chaos fault inside
        ``put`` — degrades to the classic drop the eviction was going to
        do anyway, counted per victim."""
        tier = self.tier
        victims = [(chain, block, origin) for chain, block, origin
                   in victims if chain]
        if tier is None or not victims:
            return
        try:
            gathered = self.gather([block for _, block, _ in victims])
            self.gather_ops += len(gathered)
            self.gather_rounds += 1
            GATHER_BATCHES.inc()
        except Exception as e:  # noqa: BLE001 — demotion is advisory
            for _ in victims:
                tier.note_dropped()
            _LOG.debug("kvtier: batched demotion of %d chain(s) dropped "
                       "(%s: %s)", len(victims), type(e).__name__, e)
            return
        for i, (chain, _, origin) in enumerate(victims):
            try:
                # per-victim COPY, not a view: a view would pin the whole
                # [n_victims, ...] gather base in host RAM for as long as
                # ANY sibling entry survives in the tier, while the
                # tier's byte accounting only books the slice — the
                # budget would stop bounding real memory. The copy is a
                # host memcpy; the device->host transfer above is still
                # one gather per leaf (the batching win).
                leaves = {key: arr[i].copy()
                          for key, arr in gathered.items()}
                tier.put(tuple(int(t) for t in chain), leaves,
                         origin=origin)
            except Exception as e:  # noqa: BLE001 — demotion is advisory
                tier.note_dropped()
                _LOG.debug("kvtier: demotion of a %d-token chain dropped "
                           "(%s: %s)", len(chain), type(e).__name__, e)

    def tier_match_len(self, tokens: Sequence[int]) -> int:
        """Tokens coverable by the radix tree PLUS contiguously
        promotable tier chains — the probe the gateway uses to value a
        tier hit like a radix hit before staging a sibling import.
        Read-only: no refs, no promotion, no LRU bumps."""
        prefix = self._whole(tokens)
        depth = self.kv.match_len(prefix) // self._page
        if self.tier is not None:
            depth += len(self._tier_run(self.tier.has, prefix, depth))
        return depth * self._page

    def promote(self, tokens: Sequence[int]) -> int:
        """Extend the radix match for ``tokens`` from the host/storage
        tiers: pop contiguous tier chains past the resident prefix,
        re-allocate pool blocks for them (evict-then-import — resident
        refcounted blocks are untouchable by construction), scatter the
        payloads in, and re-insert the chains with their origin
        provenance. Returns blocks promoted; 0 on any failure — the
        request simply re-prefills the tail locally (``kvtier.import``
        chaos proves that path bit-identical)."""
        tier = self.tier
        if tier is None:
            return 0
        page = self._page
        prefix = self._whole(tokens)
        matched = self.kv.match_len(prefix) // page
        if matched * page >= len(prefix):
            return 0
        entries: List[Any] = []
        pin_blocks: List[int] = []
        blocks: List[int] = []
        try:
            CHAOS.hit("kvtier.import")
            entries = self._tier_run(tier.take, prefix, matched)
            if not entries:
                return 0
            # pin the already-resident prefix: the allocate below may
            # evict unreferenced leaves, and evicting an ancestor of the
            # chain being promoted would corrupt the insert
            if matched:
                pin_blocks, _ = self.kv.lookup(prefix[:matched * page])
            blocks = self.kv.allocate(len(entries))
            payload = self._stacked(entries)
            if payload is None:
                raise ValueError(
                    "tier entries do not all carry the same cache leaves")
            self.scatter(blocks, payload)
            # per-chain inserts so each node keeps ITS producer's
            # provenance (a host-promoted chain may ride on a block a
            # sibling replica originally prefilled)
            for i, entry in enumerate(entries):
                self.kv.insert(prefix[:(matched + i + 1) * page],
                               pin_blocks + blocks[:i + 1],
                               origin=entry.origin)
            self.kv.release(blocks)
            if pin_blocks:
                self.kv.release(pin_blocks)
            for entry in entries:
                # counted at SUCCESS, not at take: a failed promotion
                # must not make the tier look effective
                tier.note_promoted(getattr(entry, "tier", None) or "host")
            return len(entries)
        except Exception as e:  # noqa: BLE001 — promotion is advisory
            # roll back: popped host entries are re-filed (their payload
            # never logically left the tier), refs dropped, and the
            # caller re-prefills — a failed promotion costs FLOPs, never
            # correctness and never a failed request
            for entry in entries:
                if getattr(entry, "tier", None) == "host":
                    tier.restore(entry)
            if blocks:
                self.kv.release(blocks)
            if pin_blocks:
                self.kv.release(pin_blocks)
            _LOG.info("kvtier: promotion failed (%s: %s); falling back "
                      "to local prefill", type(e).__name__, e)
            return 0

    # -- export and import -------------------------------------------------------

    def export_kv(self, tokens: Sequence[int], *,
                  on_pinned: Optional[Callable[[], None]] = None,
                  ) -> Optional[KVBlockExport]:
        """Snapshot the cached KV blocks covering ``tokens``' whole-block
        prefix. Returns None when no full block of the prefix is cached
        (nothing to transfer). ``on_pinned`` is a test hook invoked while
        the blocks are pinned (between gather and release) so refcount
        integrity under an in-flight transfer is assertable.

        Call from the engine's scheduling thread (the loop, or the test
        driver between ``step()`` calls): the gather reads the live cache
        tree, and a concurrent prefill would donate those buffers."""
        prefix = self._whole(tokens)
        if not prefix:
            return None
        blocks, matched = self.kv.lookup(prefix)
        if matched == 0:
            return None
        try:
            leaves = self.gather(blocks)
            if on_pinned is not None:
                on_pinned()
            # shard structure rides as metadata only: every payload leaf
            # shards on its kv_heads axis, axis 2 of the pool leaf == axis
            # 2 of the gathered rows [n_blocks, page, kv_heads(, head_dim)]
            shard_axes = None if self._mesh_shape is None \
                else {key: 2 for key in leaves}
            return KVBlockExport(
                tokens=prefix[:matched], page_size=self._page,
                leaves=leaves,
                mesh_shape=self._mesh_shape, shard_axes=shard_axes)
        finally:
            self.kv.release(blocks)

    def import_kv(self, export: KVBlockExport) -> int:
        """Fold a transferred prefix into the pool + radix tree; returns
        the number of blocks imported (0 = skipped: page-size mismatch,
        prefix already cached, payload malformed, or pool too hot even
        after evicting everything evictable). Never raises and never
        touches a block any resident request references — the worst
        outcome of an import is a local re-prefill.

        Must run between engine steps on the engine's scheduling thread
        (``apply_imports`` does, at the top of a round)."""
        if export.page_size != self._page:
            _LOG.warning("kv import skipped: page_size %d != engine %d",
                         export.page_size, self._page)
            return 0
        tokens = export.tokens
        n = export.n_blocks
        if n == 0 or len(tokens) % export.page_size:
            return 0
        if self.kv.match_len(tokens) >= len(tokens):
            return 0                  # already cached end-to-end: free hit
        try:
            blocks = self.kv.allocate(n)       # evict-then-import
        except NoFreeBlocks:
            _LOG.info("kv import skipped: pool too hot for %d blocks", n)
            return 0
        try:
            # mesh-shape gate, mirroring the scatter's kv_quant one: an
            # export from a DIFFERENTLY-sharded pool fails closed (local
            # re-prefill). Unsharded exports (mesh_shape None) import
            # anywhere — the scatter replicates/slices per the
            # destination's placement — but a sharded manifest names the
            # exact pool geometry it came from, and a silent geometry
            # change is how per-shard payload formats rot into
            # garbage-served-with-no-error
            if export.mesh_shape is not None and \
                    tuple(export.mesh_shape) != \
                    tuple(self._mesh_shape or ()):
                raise ValueError(
                    f"kv export mesh_shape {tuple(export.mesh_shape)} does "
                    f"not match the importing pool's {self._mesh_shape} — "
                    f"sharded imports are geometry-exact (fail closed)")
            self.scatter(blocks, export.leaves)
        except Exception as e:  # noqa: BLE001 — a bad payload must not leak
            self.kv.release(blocks)   # refcount 1, outside the tree → freed
            _LOG.warning("kv import failed (%s: %s); falling back to local "
                         "prefill", type(e).__name__, e)
            return 0
        # provenance rides the tree: requests whose prefix match hits these
        # nodes record which prefill replica really produced their KV
        self.kv.insert(tokens, blocks,
                       origin=getattr(export, "prefilled_by", None))
        self.kv.release(blocks)       # stays cached-unreferenced in the tree
        return n

    def queue_kv_import(self, export) -> None:
        """Enqueue a transferred prefix (``KVBlockExport``); applied
        between engine steps, strictly before admissions. Queue BEFORE
        submitting the request that wants it."""
        self._refuse("KV import")
        with self._lock:
            self._pending_imports.append(export)
        self._wake()

    def apply_imports(self) -> bool:
        with self._lock:
            if not self._pending_imports:
                return False
            pending, self._pending_imports = self._pending_imports, []
        applied = False
        for export in pending:
            n = self.import_kv(export)
            if n:
                applied = True
                self.imports += 1
                self.import_blocks += n
                self._note_import("applied", n)
            else:
                self._note_import("skipped", 0)
        return applied

    def request_kv_export(self, tokens: Sequence[int],
                          timeout_s: float = 5.0):
        """Snapshot this pool's cached KV covering ``tokens``' prefix —
        radix-resident blocks plus host-tier continuation chains — as one
        ``KVBlockExport``, WITHOUT the caller touching the live cache: the
        gather runs on the scheduling thread between steps. Returns None
        on timeout, shutdown, or nothing cached — the caller (the
        gateway's cross-replica import) degrades to a local re-prefill."""
        self._refuse("KV export")
        tokens = list(tokens)
        return self._on_scheduler(
            "kv export", lambda: self._export_now(tokens), None, timeout_s)

    def _export_now(self, tokens: Sequence[int]):
        """Compose the export: the pinned radix gather (``export_kv``)
        for the HBM-resident prefix, extended block-by-block from the
        host tier (``peek`` — the source keeps its copy; the importer
        allocates its own fresh blocks)."""
        page = self._page
        prefix = self._whole(tokens)
        export = self.export_kv(prefix)
        depth = len(export.tokens) // page if export is not None else 0
        extra = [] if self.tier is None \
            else self._tier_run(self.tier.peek, prefix, depth)
        if not extra:
            return export
        leaves = self._stacked(extra)
        if leaves is None or (export is not None
                              and set(leaves) != set(export.leaves)):
            return export           # mismatched leaf sets: HBM part only
        if export is not None:
            leaves = {k: np.concatenate([np.asarray(arr), leaves[k]])
                      for k, arr in export.leaves.items()}
        return KVBlockExport(
            tokens=prefix[:(depth + len(extra)) * page],
            page_size=page, leaves=leaves)

    def kv_chains(self, limit: int = 4096) -> dict:
        """Chains this replica could serve an import from, by tier —
        the advertisement the gateway's global prefix index refreshes
        each tick. Best-effort and lock-free over the tree (the index
        is an expectation; a torn walk costs at worst one pointless
        import attempt that degrades to re-prefill). Cached by the
        tree/tier structure versions: an unchanged cache returns the
        SAME object, which the gateway uses to skip re-hashing the
        whole advertisement every tick."""
        version = (self.kv.structure_version,
                   self.tier.version if self.tier is not None else 0)
        cached = self._chains_cache
        if cached is not None and cached[0] == version:
            return cached[1]
        out = {"hbm": [], "host": []}
        try:
            # LEAF chains only: the index registers every chunk depth of
            # a chain, so interior-node chains would be pure redundancy —
            # wasted hashing per tick, and worse, shallow chains crowding
            # the advertisement limit out of the deep ones that make
            # imports worth staging
            def walk(node, prefix):
                for child in list(node.children.values()):
                    if len(out["hbm"]) >= limit:
                        return
                    chain = prefix + list(child.chunk)
                    if not child.children:
                        out["hbm"].append(chain)
                    walk(child, chain)

            walk(self.kv._root, [])
        except Exception:  # noqa: BLE001 — advertisement is advisory
            pass
        if self.tier is not None:
            try:
                out["host"] = [list(c) for c in self.tier.chains()[:limit]]
            except Exception:  # noqa: BLE001 — advertisement is advisory
                pass
        self._chains_cache = (version, out)
        return out

    # -- parked conversation chains ----------------------------------------------

    def park_chain(self, key: str, tokens: Sequence[int],
                   ttl_s: float = 30.0, timeout_s: float = 5.0) -> bool:
        """Pin the longest cached whole-block prefix of ``tokens`` under
        ``key`` for up to ``ttl_s`` so it survives the tool gap of a
        fused ``generate -> tool-op -> generate`` chain. Re-parking a
        key refreshes both the pin (covering newly cached blocks, e.g.
        after a speculative prefill) and the TTL. The pin itself runs on
        the scheduling thread, and the whole surface is advisory: False
        (nothing cached, timeout, shutdown) degrades the caller to the
        ordinary routed path."""
        self._refuse("parking a conversation's chain")
        key, tokens, ttl_s = str(key), list(tokens), float(ttl_s)
        return bool(self._on_scheduler(
            "park", lambda: self._park_now(key, tokens, ttl_s), False,
            timeout_s))

    def unpark_chain(self, key: str, timeout_s: float = 5.0) -> bool:
        """Release a parked chain's pins (the blocks fall back to
        ordinary LRU-evictable cache entries). False if nothing was
        parked under ``key`` — releasing twice is harmless."""
        key = str(key)
        return bool(self._on_scheduler(
            "park", lambda: self._release_parked(key, "explicit"), False,
            timeout_s))

    def _park_now(self, key: str, tokens: List[int], ttl_s: float) -> bool:
        self._release_parked(key, "repark")
        # lookup, not match: a park must not distort the hit-rate stats
        # or the LRU order the serving traffic established
        blocks, _ = self.kv.lookup(tokens)
        if not blocks:
            return False
        self._parked[key] = (blocks, self._clock.now() + ttl_s)
        PARKED.inc()
        return True

    def _release_parked(self, key: str, reason: str) -> bool:
        chain = self._parked.pop(key, None)
        if chain is None:
            return False
        self.kv.release(chain[0])
        PARKED_RELEASED.inc(reason=reason)
        return True

    def shed_parked(self, need_blocks: int) -> bool:
        """Release parked chains — soonest expiry first — until
        ``need_blocks`` are coverable; True if any went. Parked chains are
        strictly cheaper to lose than any resident request: a released pin
        costs a future re-prefill (its blocks fall back to evictable
        cache), a preemption throws away decode work."""
        shed = False
        while self._parked and self.kv.available() < need_blocks:
            key = min(self._parked, key=lambda k: self._parked[k][1])
            shed = self._release_parked(key, "pressure")
        return shed

    def stats(self) -> dict:
        """The ``EngineStats`` fields filled here: imports, the parked
        chains and the blocks they pin and, with a tier, the host rung's
        occupancy and the ladder's counters (hbm→host + host→storage, and
        back)."""
        parked = list(self._parked.values())
        out = {"kv_imports": self.imports,
               "kv_import_blocks": self.import_blocks,
               "kv_parked_chains": len(parked),
               "kv_parked_blocks": sum(len(blocks) for blocks, _ in parked)}
        if self.tier is not None:
            ts = self.tier.stats()
            out.update(
                kv_host_tier_blocks=ts["host_blocks"],
                kv_host_tier_bytes=ts["host_bytes"],
                kv_tier_demotions=(ts["demotions"]
                                   + ts["demotions_to_storage"]),
                kv_tier_promotions=(ts["promotions"]
                                    + ts["promotions_from_storage"]),
                kv_tier_dropped=ts["dropped"],
                kv_storage_tier_blocks=ts.get("storage_blocks"))
        return out

    # -- the scheduling thread's side -----------------------------------------------

    def _refuse(self, mechanism: str) -> None:
        if self._refusal is not None:
            error, why = self._refusal
            raise error(f"{mechanism}: {why['moved']}")

    def _on_scheduler(self, what: str, run: Callable[[], Any], default: Any,
                      timeout_s: float) -> Any:
        """Run ``run`` on the scheduling thread and hand back what it
        returns: at once where no loop thread runs (by the engine's
        single-driver contract the caller IS the scheduling thread), else
        queued for the top of the loop's next round and waited for.
        ``default`` on timeout, shutdown or an exception: every caller is
        advisory."""
        if self._closed():
            return default
        if not self._threaded():
            try:
                return run()
            except Exception:  # noqa: BLE001 — advisory
                return default
        call = _Call(what, run, default)
        with self._lock:
            self._calls.append(call)
        self._wake()
        if not call.done.wait(timeout_s):
            return default
        return call.result

    def service(self) -> bool:
        """Round work ahead of the reap and the admissions: queued imports
        strictly before the round's admissions (an import queued before a
        submit is always resident by the time that request prefills), then
        what other threads asked for, in arrival order behind one drain,
        then the parked chains' TTL sweep."""
        did = self.apply_imports()
        with self._lock:
            calls = self._calls
            if calls:
                self._calls = []
        if calls:
            # a chain parked or exported may be a live row's: its tokens
            # first
            self._drain("io")
            for call in calls:
                try:
                    call.result = call.run()
                except Exception as e:  # noqa: BLE001 — advisory
                    _LOG.warning("%s request failed (%s: %s)", call.what,
                                 type(e).__name__, e)
                finally:
                    call.done.set()
            did = True
        if self._parked:
            now = self._clock.now()
            for key in [k for k, (_, expires_at) in self._parked.items()
                        if now >= expires_at]:
                self._release_parked(key, "ttl")
        return did

    def close(self) -> None:
        """After the loop thread was joined (so single-threaded by
        construction): close the tier, wake every waiter parked on a call
        the loop will never run (it reads its default: no export, not
        parked, and degrades), release the parked pins."""
        if self.tier is not None:
            self.tier.close()
        with self._lock:
            calls, self._calls = self._calls, []
        for call in calls:
            call.done.set()
        for key in list(self._parked):
            self._release_parked(key, "shutdown")

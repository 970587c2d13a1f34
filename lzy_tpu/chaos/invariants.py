"""Runtime invariant auditors for the serving stack.

Chaos tests assert these after injected faults (and soak tests between
requests): each auditor walks live data structures and raises
:class:`InvariantViolation` with the precise accounting that broke, so a
seeded replay lands on the first corrupt state instead of a downstream
symptom. Auditors are READ-ONLY and take no locks beyond what the
audited object's python attributes imply — call them from the test
thread between requests, not concurrently with a mutating hot loop.

The invariants:

- **block-pool conservation** (:func:`audit_pool`): every pool block is
  exactly one of {scratch, free-list, referenced, cached-in-tree};
  a block that is none of them has LEAKED, a block that is two of them
  is double-owned.
- **radix-tree consistency** (:func:`audit_radix`): parent/child links
  mirror each other, chunk keys are page-size, the block->node map is
  exactly the set of tree nodes, tree blocks are never on the free list.
- **kept KV counts** (:func:`audit_kv_counts`): the numbers the radix
  cache keeps as it goes (cached blocks, evictable blocks, each node's
  ``busy``, the eviction order's candidates) equal a from-scratch
  reckoning over the whole tree — the walks that WERE the cache's
  ``available()``, ``cached_count()`` and victim pick until PR 34.
- **engine/slot consistency** (:func:`audit_engine`): an active slot's
  page table mirrors its block list, its position fits its allocated
  pages, and every held block is actually referenced.
- **tiered-KV residency** (:func:`audit_kv_tier`): with a host tier
  behind the pool, a block's payload lives in exactly ONE rung — a
  host-tier chain must not also be radix-resident (double residency),
  chains are whole-block and root-anchored, and the tier's byte
  accounting matches its entries and budget.
- **fleet lease accounting** (:func:`audit_fleet_leases`): no VM is
  leased to two replicas; with an allocator wired, every live replica's
  VMs exist and are RUNNING.
- **fenced-token monotonicity** (:class:`FenceAuditor`): across gateway
  failovers a request's emitted stream only ever extends — the final
  reply starts with every snapshot fenced at a failover, and the retry
  prompt carried exactly prompt+fenced.
- **crash-recovery completeness** (:func:`audit_recovery`): after a
  gateway recovery, every request the journal held LIVE at the death is
  exactly one of re-attached/re-submitted-at-fence (a session with its
  id exists on the successor) or terminally failed with a typed status
  — none silently dropped, and a resubmitted session's fence still
  starts with everything the predecessor served.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence


class InvariantViolation(AssertionError):
    """An auditor found corrupted accounting; the message carries the
    exact blocks/ids that broke."""


# -- KV block pool ----------------------------------------------------------

def audit_pool(kv) -> None:
    """``kv`` is a ``serving.kv_cache.RadixCache``."""
    pool = kv.pool
    free = list(pool._free)
    free_set = set(free)
    if len(free) != len(free_set):
        raise InvariantViolation(f"free list has duplicates: {free}")
    for b in free:
        if not 0 < b < pool.n_blocks:
            raise InvariantViolation(f"free list holds invalid block {b}")
        if pool._ref[b] != 0:
            raise InvariantViolation(
                f"block {b} is on the free list with refcount "
                f"{pool._ref[b]}")
    if 0 in free_set or 0 in kv._node_of:
        raise InvariantViolation("scratch block 0 left the reserved state")
    if pool._ref[0] != 0:
        raise InvariantViolation(
            f"scratch block 0 has refcount {pool._ref[0]}")
    leaked, negative = [], []
    for b in range(1, pool.n_blocks):
        if pool._ref[b] < 0:
            negative.append(b)
        if pool._ref[b] == 0 and b not in free_set and b not in kv._node_of:
            leaked.append(b)
    if negative:
        raise InvariantViolation(f"negative refcounts on blocks {negative}")
    if leaked:
        raise InvariantViolation(
            f"leaked blocks (unreferenced, not free, not cached): {leaked}")


def audit_radix(kv) -> None:
    """Structural consistency of the radix tree over ``kv``'s pool."""
    free_set = set(kv.pool._free)
    seen: Dict[int, object] = {}

    def walk(node, depth: int) -> None:
        for chunk, child in node.children.items():
            if child.parent is not node:
                raise InvariantViolation(
                    f"node for block {child.block}: parent link broken")
            if child.chunk != chunk:
                raise InvariantViolation(
                    f"node for block {child.block}: edge key != node chunk")
            if len(chunk) != kv.page_size:
                raise InvariantViolation(
                    f"node for block {child.block}: chunk of {len(chunk)} "
                    f"tokens (page_size {kv.page_size})")
            if child.block in seen:
                raise InvariantViolation(
                    f"block {child.block} appears at two tree nodes")
            if child.block in free_set:
                raise InvariantViolation(
                    f"tree block {child.block} is on the free list")
            seen[child.block] = child
            walk(child, depth + 1)

    walk(kv._root, 0)
    if set(seen) != set(kv._node_of):
        raise InvariantViolation(
            f"block->node map out of sync with the tree: map has "
            f"{sorted(set(kv._node_of) - set(seen))} extra, tree has "
            f"{sorted(set(seen) - set(kv._node_of))} unmapped")
    for b, node in kv._node_of.items():
        if node is not seen[b]:
            raise InvariantViolation(
                f"block {b}: map points at a detached node")


# -- what the radix cache keeps, reckoned from scratch ------------------------
#
# These walks defined RadixCache.available(), cached_count() and the
# victim pick until the cache began to keep the numbers (PR 34). They stay
# as the definition: the audit below and tests/test_kv_cache.py hold the
# kept numbers to them. Each costs a pass over the whole tree.

def reckon_cached(kv) -> int:
    """Tree blocks with refcount 0."""
    return sum(1 for b in kv._node_of if kv.pool.refcount(b) == 0)


def reckon_evictable(kv) -> int:
    """Tree blocks in a fully unreferenced subtree: what ``available()``
    adds to the free list."""

    def count(node):
        n_evictable, all_free = 0, True
        for child in node.children.values():
            c_n, c_free = count(child)
            n_evictable += c_n
            all_free = all_free and c_free
        if node is kv._root:
            return n_evictable, all_free
        if all_free and kv.pool.refcount(node.block) == 0:
            return n_evictable + 1, True
        return n_evictable, False

    return count(kv._root)[0]


def reckon_victims(kv, n: int) -> list:
    """The first ``n`` nodes an eviction round would take, in order: each
    time the unreferenced leaf with the lowest ``last_access`` (the first
    in tree order on a tie), a node counting as a leaf once its children
    are taken. Nothing is detached."""
    gone: set = set()
    out: list = []

    def leaves(node, found):
        for child in node.children.values():
            if any(id(c) not in gone for c in child.children.values()):
                leaves(child, found)
            elif id(child) not in gone \
                    and kv.pool.refcount(child.block) == 0:
                found.append(child)
        return found

    for _ in range(n):
        found = leaves(kv._root, [])
        if not found:
            break
        victim = min(found, key=lambda node: node.last_access)
        gone.add(id(victim))
        out.append(victim)
    return out


def audit_kv_counts(kv) -> None:
    """The radix cache's kept numbers against the reckoning above."""
    cached = reckon_cached(kv)
    if kv._cached != cached:
        raise InvariantViolation(
            f"kept cached count {kv._cached} != {cached} unreferenced "
            f"tree blocks")
    evictable = reckon_evictable(kv)
    if kv._evictable != evictable:
        raise InvariantViolation(
            f"kept evictable count {kv._evictable} != {evictable} tree "
            f"blocks in unreferenced subtrees")
    # the heap's pick is the LRU unreferenced leaf as long as each such
    # leaf has an entry stamped at or below its last_access (an entry
    # stamped lower is pushed back under the true value when it surfaces)
    lowest: Dict[int, int] = {}
    for stamp, _, node in kv._lru:
        lowest[id(node)] = min(stamp, lowest.get(id(node), stamp))
    for b, node in kv._node_of.items():
        busy = (kv.pool.refcount(b) > 0) + sum(
            1 for c in node.children.values() if c.busy)
        if node.busy != busy:
            raise InvariantViolation(
                f"block {b}: kept busy count {node.busy} != {busy} "
                f"(own reference + busy children)")
        if busy or node.children:
            continue
        if lowest.get(id(node), node.last_access + 1) > node.last_access:
            raise InvariantViolation(
                f"block {b} is an unreferenced leaf but the eviction "
                f"order has no entry at or below its last_access "
                f"{node.last_access}")


def audit_engine(engine) -> None:
    """Slot/table/pool consistency of one inference engine. Paged
    engines get the full block audit; dense engines the position
    bounds."""
    active = engine._active
    for slot, req in enumerate(active):
        pos = int(engine._pos[slot])
        if req is None:
            continue
        if pos > engine.cfg.max_seq_len:
            raise InvariantViolation(
                f"slot {slot}: position {pos} beyond max_seq_len")
    kv = getattr(engine, "kv", None)
    if kv is None:
        return
    audit_pool(kv)
    audit_radix(kv)
    audit_kv_counts(kv)
    audit_kv_tier(kv, getattr(engine, "kv_tier", None))
    page = engine._page
    held: Dict[int, int] = {}
    for slot, req in enumerate(active):
        blocks = engine._slot_blocks[slot]
        if req is None:
            if blocks:
                raise InvariantViolation(
                    f"idle slot {slot} still holds blocks {blocks}")
            continue
        pos = int(engine._pos[slot])
        if pos > len(blocks) * page:
            raise InvariantViolation(
                f"slot {slot}: position {pos} beyond its {len(blocks)} "
                f"allocated page(s)")
        for b in blocks:
            if kv.pool._ref[b] < 1:
                raise InvariantViolation(
                    f"slot {slot} holds unreferenced block {b}")
            held[b] = held.get(b, 0) + 1
        table = list(engine._tables[slot][:len(blocks)])
        if table != blocks:
            raise InvariantViolation(
                f"slot {slot}: page table {table} != block list {blocks}")
        if any(engine._tables[slot][len(blocks):]):
            raise InvariantViolation(
                f"slot {slot}: page table rows past the allocated prefix "
                f"are not scratch")
    for b, holders in held.items():
        if kv.pool._ref[b] < holders:
            raise InvariantViolation(
                f"block {b}: {holders} slot holder(s) but refcount "
                f"{kv.pool._ref[b]}")
    # staged (mid-prefill) jobs: their blocks are pinned but not yet
    # slot-resident, their reserved slot must still read as idle (its
    # page-table row stays scratch until activation — decode rounds
    # interleaved with the prefill write garbage only to block 0)
    free_set = set(kv.pool._free)
    for job in engine.prefill.jobs:
        if engine._active[job.slot] is not None:
            raise InvariantViolation(
                f"prefill job for {job.req.id} reserves slot {job.slot} "
                f"which is also active")
        if engine._slot_blocks[job.slot] or any(engine._tables[job.slot]):
            raise InvariantViolation(
                f"slot {job.slot} exposes blocks while its prefill job "
                f"is still staging")
        for b in job.table:
            if kv.pool._ref[b] < 1:
                raise InvariantViolation(
                    f"prefill job for {job.req.id} holds unreferenced "
                    f"block {b}")
            if b in free_set:
                raise InvariantViolation(
                    f"prefill job for {job.req.id} holds free-list "
                    f"block {b}")


def audit_kv_tier(kv, tier) -> None:
    """Demoted-tier residency over a ``RadixCache`` + ``HostKVTier``
    pair: the block-pool conservation audit says every pool block is
    exactly one of {scratch, free, referenced, cached}; this extends
    the partition with the demoted rung — a payload the host tier
    holds must NOT also be a radix-resident chain (exactly one tier
    owns it), every tier chain is whole-block, and the tier's byte sum
    matches its own accounting and budget."""
    if tier is None:
        return
    with tier._lock:
        entries = list(tier._entries.values())
        booked_bytes = tier._bytes
    total = 0
    for entry in entries:
        chain = list(entry.chain)
        if not chain or len(chain) % kv.page_size:
            raise InvariantViolation(
                f"tier entry chain of {len(chain)} tokens is not "
                f"whole-block (page_size {kv.page_size})")
        if not entry.leaves:
            raise InvariantViolation(
                f"tier entry for a {len(chain)}-token chain has no "
                f"payload leaves")
        if kv.match_len(chain) >= len(chain):
            raise InvariantViolation(
                f"chain of {len(chain)} tokens is resident in BOTH the "
                f"radix tree and the host tier (double residency)")
        total += entry.nbytes
    if total != booked_bytes:
        raise InvariantViolation(
            f"host tier byte accounting drifted: entries sum to {total} "
            f"but the tier books {booked_bytes}")
    if booked_bytes > tier.budget_bytes:
        raise InvariantViolation(
            f"host tier over budget: {booked_bytes} > "
            f"{tier.budget_bytes} bytes")


# -- fleet ------------------------------------------------------------------

def audit_fleet_leases(fleet, allocator=None) -> None:
    """Lease accounting over a ``gateway.fleet.ReplicaFleet``."""
    from lzy_tpu.gateway.fleet import DRAINING, READY

    with fleet._lock:
        replicas = list(fleet._replicas.values())
    owner: Dict[str, str] = {}
    for replica in replicas:
        if replica.state not in (READY, DRAINING):
            raise InvariantViolation(
                f"replica {replica.id} held in state {replica.state}")
        for vm_id in replica.vm_ids:
            if vm_id in owner:
                raise InvariantViolation(
                    f"vm {vm_id} leased to both {owner[vm_id]} and "
                    f"{replica.id}")
            owner[vm_id] = replica.id
        if allocator is not None:
            from lzy_tpu.service.allocator import RUNNING

            for vm_id in replica.vm_ids:
                try:
                    vm = allocator.vm(vm_id)
                except KeyError:
                    raise InvariantViolation(
                        f"replica {replica.id} leases vanished vm {vm_id}")
                if vm.status != RUNNING:
                    raise InvariantViolation(
                        f"replica {replica.id} leases vm {vm_id} in "
                        f"status {vm.status}")


# -- crash recovery ---------------------------------------------------------

def audit_recovery(journal, gateway,
                   pre_live: Dict[str, dict]) -> None:
    """Recovery completeness over a recovered ``GatewayService``.

    ``pre_live`` is the journal's live-request snapshot taken BEFORE
    recovery ran (``journal.live_requests()`` at the death). The
    contract: every one of those requests is now exactly one of

    - **re-attached / re-submitted-at-fence** — a session with its id
      exists on the successor's stream manager, and its channel's
      prefix is byte-identical to the journaled fence (the resume
      token keeps reading the same bytes);
    - **terminally failed with a typed status** — the journal record
      is terminal and names a status (``orphaned_by_restart``, a real
      terminal outcome, or ``error`` with a message).

    Anything else is a silently-dropped request — the exact bug class
    this auditor exists to catch."""
    live_sessions = set(gateway.streams.sessions())
    docs = journal.requests()
    for rid in sorted(pre_live):
        if rid in live_sessions:
            sess = gateway.streams._get(rid)
            fence = [int(t) for t in pre_live[rid].get("fence") or ()]
            got = sess.channel.tokens()[:len(fence)]
            if got != fence:
                raise InvariantViolation(
                    f"recovered session {rid} diverges from its "
                    f"journaled fence: journal {fence}, channel prefix "
                    f"{got}")
            continue
        doc = docs.get(rid)
        if doc is None:
            raise InvariantViolation(
                f"journaled live request {rid} vanished in recovery — "
                f"neither re-attached nor terminally settled")
        if doc.get("status") != "terminal" or not doc.get("terminal"):
            raise InvariantViolation(
                f"journaled live request {rid} was silently dropped: "
                f"no successor session and no typed terminal status "
                f"(journal says {doc.get('status')!r}/"
                f"{doc.get('terminal')!r})")


# -- fenced tokens ----------------------------------------------------------

class FenceAuditor:
    """Asserts the gateway's fenced-token contract per request.

    Install on a ``GatewayService`` (``gw.fence_auditor = FenceAuditor()``);
    the gateway opens one :class:`FenceSession` per request and reports
    every failover fence and the completion through it. The contract:
    each fence snapshot extends the previous one (tokens are never
    dropped or reordered by a failover), the retry prompt is exactly
    ``prompt + fenced``, and the final reply starts with the last fence.
    Sessions are per-call objects, so abandoned requests (shed, timed
    out) can never leak state into a later request's audit.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.failovers_seen = 0
        self.completions_seen = 0

    def session(self, prompt: Sequence[int]) -> "FenceSession":
        return FenceSession(self, prompt)

    def _note(self, what: str) -> None:
        with self._lock:
            if what == "failover":
                self.failovers_seen += 1
            else:
                self.completions_seen += 1


class FenceSession:
    """One request's fence history (see :class:`FenceAuditor`)."""

    def __init__(self, auditor: FenceAuditor, prompt: Sequence[int]):
        self._auditor = auditor
        self._prompt = list(prompt)
        self._fence: List[int] = []

    def on_failover(self, emitted: Sequence[int],
                    retry_prompt: Sequence[int]) -> None:
        snap = list(emitted)
        if snap[:len(self._fence)] != self._fence:
            raise InvariantViolation(
                f"fence shrank or reordered across a failover: "
                f"{self._fence} -> {snap}")
        if list(retry_prompt) != self._prompt + snap:
            raise InvariantViolation(
                "retry prompt is not prompt + fenced tokens")
        self._fence = snap
        self._auditor._note("failover")

    def on_complete(self, tokens: Sequence[int]) -> None:
        if list(tokens[:len(self._fence)]) != self._fence:
            raise InvariantViolation(
                f"final reply does not start with the fenced tokens: "
                f"fence {self._fence}, reply {list(tokens)}")
        self._auditor._note("complete")

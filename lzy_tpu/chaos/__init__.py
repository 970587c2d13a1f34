"""Chaos harness: deterministic fault injection + invariant auditors.

The durable layer has had an ``InjectedFailures`` hook list since its
first restart tests (``lzy_tpu/durable/failures.py``, mirroring the
reference's ``InjectedFailures.java``); every serving-stack feature
since then shipped its OWN hand-written kill test instead. This package
generalizes the idea into a uniform layer (FlowMesh's argument — a
serving fabric must make failure handling first-class and uniformly
testable, not a pile of per-feature patches):

- ``faults`` — named **fault points** threaded through every
  serving-stack boundary (allocator lease/heartbeat, engine step and
  admission, KV transport, storage puts/gets, gateway dispatch), armed
  with a **seed-deterministic fault plan** drawing crash / delay /
  error / slow-degrade modes. Any soak failure replays from its printed
  seed: each point's decisions depend only on the seed and that point's
  own hit count, never on cross-thread interleaving.
- ``invariants`` — runtime auditors chaos tests assert after injected
  faults: KV block-pool refcount conservation, radix-tree structural
  consistency, fenced-token monotonicity across gateway failovers,
  fleet lease accounting.

Production cost is one armed-check per boundary (``CHAOS.hit`` returns
immediately when no plan is armed).
"""

from lzy_tpu.chaos.faults import (
    CHAOS, CRASH, DELAY, ERROR, FaultPlan, FaultPoint, InjectedFault, SLOW)
from lzy_tpu.chaos.invariants import (
    FenceAuditor, InvariantViolation, audit_engine, audit_fleet_leases,
    audit_kv_counts, audit_kv_tier, audit_pool, audit_radix, audit_recovery)

__all__ = [
    "CHAOS",
    "CRASH",
    "DELAY",
    "ERROR",
    "FaultPlan",
    "FaultPoint",
    "FenceAuditor",
    "InjectedFault",
    "InvariantViolation",
    "SLOW",
    "audit_engine",
    "audit_fleet_leases",
    "audit_kv_counts",
    "audit_kv_tier",
    "audit_pool",
    "audit_radix",
    "audit_recovery",
]

"""``lzy_llm_*`` metrics: the workflow-native inference surface.

Deliberately a leaf module (imports only the metrics registry): the
counters are shared by layers that must not import each other — the
``llm`` op body, the token-stream channel (resumptions), the gateway
router (conversation affinity), and the workflow service (cache drops of
``llm_generate`` tasks) — so everyone lazy-imports THIS module and no
cycle can form.
"""

from __future__ import annotations

from lzy_tpu.utils.metrics import REGISTRY

#: generations executed through the llm op surface, by terminal status
#: (a cache hit never reaches the op body and therefore never counts
#: here — it counts under ``lzy_llm_cached_hits_total`` instead)
GENERATIONS = REGISTRY.counter(
    "lzy_llm_generations_total",
    "llm_op generations executed against the serving plane, by status")

#: tokens produced through the llm op surface
GENERATED_TOKENS = REGISTRY.counter(
    "lzy_llm_generated_tokens_total",
    "tokens generated through the llm_op surface")

#: llm_op calls satisfied from the workflow result cache — the fleet was
#: never touched
CACHED_HITS = REGISTRY.counter(
    "lzy_llm_cached_hits_total",
    "llm_op calls satisfied from the op result cache (no fleet dispatch)")

#: token streams resumed at the fence after a mid-stream replica death
STREAM_RESUMPTIONS = REGISTRY.counter(
    "lzy_llm_stream_resumptions_total",
    "token streams resumed byte-identically after a mid-stream failover")

#: share of PINNED session routes that landed on the conversation's
#: pinned replica (the RadixCache that holds its prior steps); a
#: conversation's first step has no pin yet and does not count
CONVERSATION_AFFINITY_RATE = REGISTRY.gauge(
    "lzy_llm_conversation_affinity_hit_rate",
    "cumulative share of pinned conversation routes that kept their "
    "pinned replica (first steps, which cannot hit, are not counted)")

#: retries of the llm dispatch boundary (chaos point ``llm.dispatch``
#: and real transient gateway refusals both land here)
DISPATCH_RETRIES = REGISTRY.counter(
    "lzy_llm_dispatch_retries_total",
    "llm_op dispatch attempts retried after a transient dispatch error")

# -- workflow-aware scheduling (lzy_tpu/llm/sched.py) -------------------------
# The scheduler-side lzy_wfsched_* family. The engine-side half (parked
# chains and their releases) lives in lzy_tpu/serving/engine.py — both
# modules are already on the dashboard generator's import list.

#: every generate dispatched through the workflow scheduler's fan-in
#: plane, by role: "leader" carried an engine request other in-flight
#: callers adopted, "solo" had no concurrent twin, "follower" adopted a
#: leader's reply (no engine request of its own)
WFSCHED_DISPATCHES = REGISTRY.counter(
    "lzy_wfsched_dispatches_total",
    "generates through the workflow fan-in plane, by role "
    "(role=leader|solo|follower)")

#: identical in-flight greedy calls collapsed onto a leader's single
#: engine request (sampled/streaming calls are never deduplicated)
DEDUP_HITS = REGISTRY.counter(
    "lzy_wfsched_dedup_hits_total",
    "in-flight identical greedy generates collapsed to one engine "
    "request")

#: fused op-chain park attempts after a conversation step, by outcome
PARK_ATTEMPTS = REGISTRY.counter(
    "lzy_wfsched_park_attempts_total",
    "conversation park attempts after an ok step, by outcome "
    "(outcome=parked|declined|unsupported)")

#: speculative next-step prefills, by outcome ("ok" = the next step's
#: known prefix is now cached on the leased replica; wrong speculations
#: are released uncounted as cache pollution when the pin lapses)
SPECULATIONS = REGISTRY.counter(
    "lzy_wfsched_speculations_total",
    "speculative next-step prefills, by outcome "
    "(outcome=ok|miss|timeout|error|no_lease)")

# -- the window of rows in flight (WorkflowScheduler.map) ---------------------

#: rows ``llm.generate_batch`` may keep inside the backend at once: 16
#: until a reply has said what the plane holds, then what follows from
#: its ``plane_slots`` / ``plane_admits``
ROW_WINDOW = REGISTRY.gauge(
    "lzy_wfsched_row_window",
    "width in force of the window of generate_batch rows in flight")

#: rows inside the window now (over the width for a while after the
#: plane reported fewer slots: rows in flight are never cancelled)
ROWS_IN_FLIGHT = REGISTRY.gauge(
    "lzy_wfsched_rows_in_flight",
    "generate_batch rows inside the window (handed to the backend)")

#: seconds a row waited between its hand-over and its entry, by the
#: width in force when it entered — the always-on twin of the
#: ``llm.row.pool_wait`` span. A row that entered at once observes
#: exactly 0: the share above the ``le="0.0"`` bucket is how often the
#: window held a row back
ROW_WINDOW_WAIT = REGISTRY.histogram(
    "lzy_wfsched_row_window_wait_seconds",
    "seconds a generate_batch row waited for the window, by the width "
    "in force at its entry (window=<rows>)",
    buckets=(0.0, 0.001, 0.01, 0.1, 1.0, 10.0, 60.0, 600.0))

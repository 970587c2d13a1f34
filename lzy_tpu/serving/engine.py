"""The serving engine: continuous batching over a paged KV pool.

One class, :class:`PagedInferenceEngine`, serves ``generate``-style
requests from a fixed batch of ``slots`` rows over one model. Which modules
a configuration runs is asked of the configuration object, and what kinds
of cache leaves they keep of the module class (the protocol is written down
in ``models/serving.py``): this file names no model.

- **One pool, page tables, a radix tree** (``serving/kv_cache.py``): keys
  and values live in one pool of ``page_size``-token blocks shared by all
  slots; a request holds a page table and commits HBM page by page as it
  grows. Prompts are matched against a ref-counted radix tree of cached
  blocks, so only the unmatched suffix is prefilled; admission is budgeted
  against free + evictable blocks, eviction removes unreferenced cached
  blocks (LRU), and a squeeze on decode growth preempts the YOUNGEST row
  (a clean ``preempted`` error), never corrupts one.
- **One read path decision, made by the code**: attention reads the pool
  through the page table (``ops/paged_attention.py``). ``kernel="auto"``
  is, on a TPU, the Pallas kernel written for a program's shape
  (``kernel_path``: the decode kernel for decode and verify windows, the
  chunk kernel for prefill chunks, over a float pool) and the portable
  ``lax`` read for an int8 pool; ``"lax"`` is also what ``"auto"`` is off
  the TPU, the sharded
  gang's read, and the tests' bit-exact reference against
  ``models/generate.py``.
- **Prefill on arrival, in budgeted chunks** (``serving/prefill.py``, one
  object the engine owns): a prompt's suffix runs through the model as
  batch-1 bucketed chunks against the same pool, at most ``prefill_budget``
  tokens a scheduling round, interleaved with the resident rows' decode
  steps; the finished job's slot starts generating on the very next step.
- **One jitted step a round, one fence**: the decode hot loop is ONE jitted
  step over the ``[slots]`` rows, whose positions live in one ``[slots]``
  vector; a finished row leaves its slot immediately (its blocks go back to
  the pool or stay cached in the tree), and with every slot idle the loop
  parks on the queue's event instead of spinning the device.
- **One round in flight**: a step hands back its tokens and positions as
  device arrays, so round n+1 is dispatched from round n's outputs before
  round n's tokens are fetched. ``step()`` fetches the round it dispatched
  at once; the loop thread of ``start()`` fetches it one turn late, behind
  the next dispatch (``_InFlight``), so the fence's tail, ``emit`` and the
  next turn's front half run under a device program. A finished prompt
  needs no token on the host either: its slot is activated on the device
  (``_activate``: the first token as its last program picked it, scattered
  into the round's inputs), the next round is dispatched behind the
  prompt's programs, and the first token is fetched after that. A turn that
  needs the tokens first (a proposer, a squeeze, the last row) drains the
  round in flight and goes on as ``step()`` does.
- **Deadlines, tenants, KV I/O**: per-request deadlines and dead clients
  evict mid-decode with a ``cancelled`` status; WFQ, queue caps and KV
  quotas come from a ``TenantTable``; cross-replica KV import / export,
  the host and storage tiers and parked conversation chains are one
  object the engine owns (``serving/kv_io.py``), serviced between rounds
  on the scheduling thread.

Sampling is engine-wide (greedy by default). Under ``temperature>0`` the
rng stream is shared by the whole batch, so a request's sampled tokens
depend on what else is in flight — per-request determinism needs
``temperature=0`` (the serving default).

TTFT, generated tokens, decode step latency, queue depth and slot
occupancy are exported via ``lzy_tpu.utils.metrics.REGISTRY`` (scraped by
``/metrics`` on both the console and the metrics server).

With ``spec_tokens > 0`` the engine runs **draft-free speculative
decoding** (``lzy_tpu/serving/spec.py`` has the design): proposals for the
greedy rows are scored by ONE verify forward of a fixed width (exactly one
extra compiled program), accepted on the device (``spec.accept``) and the
rejected tail rolled back (``_decode_verify``, ``_post_verify_rollback``),
so a failed speculation is invisible to the radix cache and to the tokens.

``serving/sharded`` (a gang over a mesh) and ``serving/disagg`` (prefill
and decode pools) subclass the engine and change where arrays live or what
a finished prefill does; the scheduler is theirs unchanged.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from lzy_tpu.chaos.faults import CHAOS, CRASH, DELAY, ERROR, SLOW
from lzy_tpu.models import serving
from lzy_tpu.models.generate import init_cache, prefill_width, sample_token
from lzy_tpu.serving.kv_cache import (
    NoFreeBlocks, RadixCache, WindowPages, blocks_for, divide_pool,
    window_bound)
from lzy_tpu.serving.kv_io import (  # noqa: F401 — the two errors are
    # what the engine's refusals raise, and are imported from here
    KvIO, StateLeavesUnsupported, WindowLeavesUnsupported, leaf_refusal)
from lzy_tpu.serving.prefill import Job, Prefill, ProgramBuild
from lzy_tpu.serving.scheduler import (
    AdmissionError, PromptTooLong, Request, RequestQueue)
from lzy_tpu.serving.tenancy import (
    TENANT_KV_BLOCKS, TENANT_REQUESTS, TENANT_ROW, TENANT_TOKENS, TENANT_TTFT)
from lzy_tpu.serving.spec import (
    ACCEPT_RATE as _SPEC_RATE, ACCEPTED as _SPEC_ACCEPTED, accept,
    DRAFT_TRUNCATED as _SPEC_TRUNCATED, NgramProposer,
    PROPOSED as _SPEC_PROPOSED, TOKENS_PER_STEP as _SPEC_TPS,
    VERIFY_STEPS as _SPEC_STEPS)
from lzy_tpu.utils import jaxenv, trace
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.utils.log import get_logger
from lzy_tpu.utils.metrics import REGISTRY

_LOG = get_logger(__name__)
_loop_ids = itertools.count(1)


class PoolCorruption(RuntimeError):
    """A device call failed AFTER the shared KV block pool's buffers were
    donated into it — the pool is gone, so the failure is engine-fatal
    (the loop's death handler fails all outstanding requests), never
    request-scoped like a staging failure (which touched nothing shared)."""

_TTFT = REGISTRY.histogram(
    "lzy_inference_ttft_seconds",
    "submit-to-first-token latency (includes queueing and prefill)",
    buckets=(0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0,
             5.0, 10.0, 60.0))
_STEP = REGISTRY.histogram(
    "lzy_inference_decode_step_seconds",
    "one jitted decode step over the slot batch",
    buckets=(0.001, 0.005, 0.01, 0.02, 0.03, 0.04, 0.05, 0.065, 0.08,
             0.1, 0.25, 1.0, 5.0))
_TOKENS = REGISTRY.counter(
    "lzy_inference_tokens_total", "generated tokens (all requests)")
_REQUESTS = REGISTRY.counter(
    "lzy_inference_requests_total", "finished requests by outcome")
_BUSY = REGISTRY.gauge(
    "lzy_inference_slots_busy", "decode slots currently generating")
_SLOTS = REGISTRY.gauge(
    "lzy_inference_slots", "decode slot capacity")
_TPS = REGISTRY.gauge(
    "lzy_inference_tokens_per_s",
    "instantaneous decode throughput (active slots / last step wall time)")

# chaos boundaries (lzy_tpu/chaos): both run inside the engine loop,
# whose death handler fails outstanding requests and flips ``closed`` —
# the exact failure domain the gateway's fenced-token failover covers —
# so a hard crash is survivable fleet-wide, not just an error
_FP_STEP = CHAOS.register(
    "engine.step", crash_ok=True, modes=(ERROR, DELAY, SLOW, CRASH),
    doc="one engine scheduling round (loop death -> gateway failover)")
_FP_PREFILL = CHAOS.register(
    "engine.prefill", crash_ok=True, modes=(ERROR, DELAY, SLOW, CRASH),
    doc="paged prefill device section (pool donated -> engine-fatal)")

# decode-round scheduling (docs/serving.md "Decode-round scheduling"):
# each round dispatches ONE fused device program and takes ONE
# device->host fence — the contract the transfer-count regression test
# pins. Phase timers cover the whole loop, at the boundaries of the
# ``engine.*`` spans (utils/trace.py). Before the decode half: ``kv_io``
# (cross-replica KV imports/exports, parked chains), ``reap`` (cancelled
# requests), ``admit`` (pop, verdict, prefill staging: radix match, block
# allocation, eviction), ``prefill`` (one budgeted prefill advance, less
# ``prefill_fence``: the wait for the first token of a finished prompt,
# taken behind the next round's dispatch and observed in the turns that
# finish one alone). The decode half:
# ``plan`` (host work before the dispatch), ``dispatch`` (input upload and
# the program's enqueue), ``overlap`` (host work run while the device
# computes), ``fence`` (the single blocking transfer), ``emit`` (token
# delivery + batched accounting after the fence). And ``park``: the
# loop's wait when a round found nothing to do.
_ROUND_PHASE = REGISTRY.histogram(
    "lzy_engine_round_phase_seconds",
    "engine-loop phase wall time (phase=kv_io|reap|admit|prefill|"
    "prefill_fence|plan|dispatch|overlap|fence|emit|park)",
    buckets=(0.0001, 0.0005, 0.001, 0.005, 0.02, 0.05, 0.25, 1.0))
# a stall has a name without a trace: a phase of the loop (``park`` is a
# wait, not a phase of work) that outlasted this edge of the histogram
# above is counted by its label and logged with the round it fell in
_SLOW_PHASE_S = 0.25
_SLOW_PHASE = REGISTRY.counter(
    "lzy_engine_slow_phase_total",
    "engine-loop phases other than park that took longer than 0.25 s, "
    "by phase")
_ROUND_FENCES = REGISTRY.counter(
    "lzy_engine_round_fences_total",
    "device-to-host fences taken by decode rounds (contract: exactly "
    "one per round)")
_ROUNDS = REGISTRY.counter(
    "lzy_engine_rounds_total",
    "decode scheduling rounds by kind (kind=decode|verify)")
# the loop thread keeps one decode round in flight (``_InFlight``): how
# often it does (over ``lzy_engine_round_fences_total``), why it did not,
# and what a finish learnt one round late costs
_ROUNDS_OVERLAPPED = REGISTRY.counter(
    "lzy_engine_rounds_overlapped_total",
    "decode rounds dispatched while the round before them was unfetched")
_ROUND_DRAINS = REGISTRY.counter(
    "lzy_engine_round_drains_total",
    "decode rounds the loop fetched with no later round dispatched over "
    "them, by what needed their tokens first "
    "(reason=admission|spec|squeeze|last_row|io|stop)")
_ACTIVATIONS = REGISTRY.counter(
    "lzy_engine_prompt_activations_total",
    "finished prompts by how their slot began to decode (how=device: the "
    "next round was dispatched with the first token still on the device, "
    "nothing drained and nothing waited for; how=drained: the round in "
    "flight was fetched and the first token waited for first: a proposer "
    "reads it, or it is the request's only token)")
_OVERRUN_ROWS = REGISTRY.counter(
    "lzy_engine_overrun_rows_total",
    "rows a decode round carried whose token was dropped at its fetch: the "
    "request had finished, been reaped or been preempted after the dispatch")
_OVERLAP_COMMITS = REGISTRY.counter(
    "lzy_engine_admission_plan_total",
    "admission plans computed in the overlap window, by outcome "
    "(outcome=committed|stale|empty)")

# set-up, and a program built after it. The constructor (``engine.init``:
# the kernels' check, the pool, the jitted steps) and ``warmup()``
# (``engine.warmup``) are timed whole, and the seconds JAX spent building
# programs inside each (utils/jaxenv.py's meter) beside them: the
# difference is what set-up costs beyond its builds. A program built later
# is built by the round that first needs it, on the scheduling thread: one
# that kept rows in decode waiting is counted, with the seconds they waited.
_SETUP_SECONDS = REGISTRY.counter(
    "lzy_engine_setup_seconds_total",
    "wall seconds of engine set-up (phase=init|warmup: the constructor, "
    "warmup())")
_SETUP_BUILD_SECONDS = REGISTRY.counter(
    "lzy_engine_setup_build_seconds_total",
    "seconds of lzy_engine_setup_seconds_total that were program builds "
    "(trace + lower + compile), by the same phase")
_SERVING_BUILDS = REGISTRY.counter(
    "lzy_engine_serving_builds_total",
    "programs built on the scheduling thread after set-up while rows were "
    "in decode, by site: every such row waited for the whole build")
_BUILD_STALLED = REGISTRY.counter(
    "lzy_engine_build_stalled_row_seconds_total",
    "build seconds of lzy_engine_serving_builds_total times the rows in "
    "decode that waited")


def _setup_phase(name: str, phase: str, site: Optional[str] = None):
    """Decorates the constructor and ``warmup()``: the span ``name`` when
    the recorder is on, the always-on seconds of the phase and of the
    builds inside it, and (``site``) a build context around the whole of
    it for the builds no narrower context claims."""
    def decorate(fn):
        @functools.wraps(fn)
        def timed(self, *args, **kwargs):
            t0, built0 = trace.now(), jaxenv.thread_build_seconds()
            builds = trace.NOOP if site is None \
                else trace.building(site, rows=0, warm=True)
            self._setting_up = True
            try:
                with trace.span(name), builds:
                    return fn(self, *args, **kwargs)
            finally:
                self._setting_up = False
                _SETUP_SECONDS.inc(trace.now() - t0, phase=phase)
                _SETUP_BUILD_SECONDS.inc(
                    jaxenv.thread_build_seconds() - built0, phase=phase)
        return timed
    return decorate


@dataclasses.dataclass
class _InFlight:
    """A decode round the device was handed and whose tokens the host has
    not fetched. ``step()`` fetches it before it returns; the loop thread
    of ``start()`` keeps one across turns and fetches it behind the next
    round's dispatch. Tokens are applied by REQUEST, not by slot: a row
    that finished, was reaped or was preempted between the dispatch and
    the fetch (its slot empty, or another request's by then) drops its
    token."""
    seq: int                    # the round's number (``round`` on its spans)
    out: Any                    # what the fence fetches: tokens (+ counts)
    rows: List[tuple]           # [(slot, request)] it was dispatched with
    rng: Any                    # the engine's key before the round
    t0: float                   # dispatch start (engine clock)


@dataclasses.dataclass
class EngineStats:
    slots: int
    busy: int
    queue_depth: int
    requests_finished: int
    tokens_generated: int
    requests_cancelled: int = 0
    # KV paging fields (doc() leaves out what a producer does not fill)
    kv_page_size: Optional[int] = None
    kv_blocks_total: Optional[int] = None
    kv_blocks_free: Optional[int] = None
    kv_blocks_cached: Optional[int] = None
    kv_evictions: Optional[int] = None
    prefix_hit_rate: Optional[float] = None
    prefill_tokens_saved: Optional[int] = None
    # disaggregated serving fields (serving/disagg engines only):
    # prefill replicas report exports, decode replicas report imports
    kv_exports: Optional[int] = None
    kv_export_blocks: Optional[int] = None
    kv_imports: Optional[int] = None
    kv_import_blocks: Optional[int] = None
    # tiered KV cache fields (engines with a host/storage tier —
    # serving/kv_tier.py): occupancy of the host rung plus the demotion/
    # promotion ladder counters; None (and off the wire) without a tier
    kv_host_tier_blocks: Optional[int] = None
    kv_host_tier_bytes: Optional[int] = None
    kv_tier_demotions: Optional[int] = None
    kv_tier_promotions: Optional[int] = None
    kv_tier_dropped: Optional[int] = None
    kv_storage_tier_blocks: Optional[int] = None
    # workflow-aware scheduling: conversation chains
    # currently parked across fused op-chain tool gaps, and the blocks
    # they pin resident
    kv_parked_chains: Optional[int] = None
    kv_parked_blocks: Optional[int] = None
    # speculative decoding fields (spec_tokens > 0 only; serving/spec.py)
    spec_tokens: Optional[int] = None
    spec_proposed_tokens: Optional[int] = None
    spec_accepted_tokens: Optional[int] = None
    spec_acceptance_rate: Optional[float] = None
    spec_verify_steps: Optional[int] = None
    spec_tokens_per_step: Optional[float] = None
    # drafts truncated by _grow_for_spec's NoFreeBlocks backstop (a
    # silent perf cliff until it was counted — a pool sized too tight
    # quietly degrades speculation to 1-token steps)
    spec_draft_truncated: Optional[int] = None
    # which kernel the decode step reads KV through (pallas/lax) and the
    # active KV quantization mode
    kernel_path: Optional[str] = None
    kv_quant: Optional[str] = None
    # bytes one cached token costs the pool over all its layers (keys and
    # values a head, or one vector with no head axis: the model's answer)
    kv_token_bytes: Optional[int] = None
    # a model with ``window`` leaves (models/serving.py) has two kinds of
    # page: every ``kv_blocks_*`` above counts the ``paged`` kind (pages
    # that keep every token), these the ``window`` kind; None without one
    kv_window_blocks_total: Optional[int] = None
    kv_window_blocks_free: Optional[int] = None
    kv_window_blocks_live: Optional[int] = None
    kv_window_pages_released: Optional[int] = None

    def doc(self) -> dict:
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None}


def _kv_io_stat(name: str) -> property:
    return property(lambda self: self.kv_io.stats().get(name, 0))


class PagedInferenceEngine:
    """Serve ``generate``-style requests from a shared slot batch over a
    paged KV pool with radix prefix reuse (the module docstring has the
    design).

    Drive it either with the background loop (``start()``/``close()``, the
    serving-front mode) or synchronously with ``step()`` from one thread
    (the deterministic test mode) — not both at once.

    ``kv_blocks`` (or ``kv_pool_bytes``) sizes the pool: the default gives
    every slot ``max_seq_len`` tokens of blocks, less overcommits HBM
    (short requests stop paying for the longest possible one), more grows
    the prefix cache's working set. Greedy output under the ``lax`` read is
    bit-identical to the solo ``generate()`` oracle, and so are sampled
    draws under one seed; the Pallas kernel is within a written tolerance
    of it.
    """

    @_setup_phase(trace.ENGINE_INIT, "init", site=trace.SITE_AUX)
    def __init__(
        self,
        cfg: Any,
        params: Any,
        *,
        slots: int = 4,
        page_size: int = 16,
        kv_blocks: Optional[int] = None,
        kv_pool_bytes: Optional[int] = None,
        kv_window_blocks: Optional[int] = None,
        kv_quant: Optional[str] = None,
        # tombstone: the benchmark's configuration files pass
        # ``"native_attention": true``; the benchmark issue that takes it
        # out there (ROADMAP B2) removes the keyword here
        native_attention: bool = True,
        kernel: str = "auto",
        kv_host_tier_bytes: Optional[int] = None,
        kv_storage_tier=None,
        kv_tier=None,
        max_queue: int = 64,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_token: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        seed: int = 0,
        spec_tokens: int = 0,
        spec_ngram: int = 3,
        proposer=None,
        prefill_budget: Optional[int] = None,
        max_prefill_jobs: Optional[int] = None,
        tenants=None,
        clock=None,
    ):
        from lzy_tpu.ops.interpret import resolve as pallas_interpreted
        from lzy_tpu.ops.paged_attention import (
            DISPATCHES, QUANT_BLOCKS_RESIDENT, default_kernel)

        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if spec_tokens < 0:
            raise ValueError(f"spec_tokens must be >= 0, got {spec_tokens}")
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {prefill_budget}")
        if max_prefill_jobs is not None and max_prefill_jobs < 1:
            raise ValueError(
                f"max_prefill_jobs must be >= 1, got {max_prefill_jobs}")
        base = cfg.serving_config()
        if spec_tokens + 1 >= base.max_seq_len:
            raise ValueError(
                f"spec_tokens ({spec_tokens}) must leave room in "
                f"max_seq_len ({base.max_seq_len})")
        if page_size < 1 or base.max_seq_len % page_size:
            raise ValueError(
                f"page_size ({page_size}) must divide max_seq_len "
                f"({base.max_seq_len})")
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"unknown kv_quant {kv_quant!r}; known: int8")
        if kernel not in ("auto", "lax", "pallas"):
            raise ValueError(
                f"unknown kernel {kernel!r}; known: auto, lax, pallas")
        if not native_attention:
            raise ValueError(
                "native_attention=False: the gather read (the pool copied "
                "back into a dense [B, L, KV, D] layout every step) is "
                "gone; kernel='lax' reads through the page table and gives "
                "the same bits")
        if kernel == "pallas" and kv_quant is not None:
            raise ValueError(
                "kernel='pallas' reads float pools only; an int8 pool "
                "is served by kernel='lax' (or 'auto')")
        # a model none of whose cache leaves is a pool of pages
        # (models/serving.py: ``kv_layers`` 0 and no window) has no block to
        # size, no page table, and nothing to be admitted by but a free slot
        self._pooled = bool(base.kv_layers) \
            or getattr(base, "kv_window", None) is not None
        if not self._pooled:
            for name, given in (("kv_blocks", kv_blocks),
                                ("kv_pool_bytes", kv_pool_bytes)):
                if given is not None:
                    raise ValueError(
                        f"{name}: {type(base).__name__} keeps no page pool "
                        f"(kv_layers 0: its cache is state a slot), so "
                        f"there is nothing for it to size")
        self._page = page_size
        self._pages_per_seq = base.max_seq_len // page_size \
            if self._pooled else 0
        self._kv_quant = kv_quant
        # kernel selection (docs/serving.md): "auto" is, on a TPU, the
        # kernels that compile there (Pallas) and the portable lax read
        # anywhere else; "pallas" is taken at the caller's word and
        # checked below. A program gets the kernel written for its
        # shape (ops.paged_attention.kernel_path: decode, chunk) and an
        # int8 pool the lax read: kernel_path is the decode step's.
        self._paged_kernel = default_kernel() if kernel == "auto" \
            else kernel
        self.cfg = base
        self.params = params
        self.slots = slots
        # injectable time (utils/clock): TTFT/step timestamps, deadlines
        # and the loop's idle park all run on it, so a virtual clock can
        # drive the whole engine deterministically; the system default
        # is bit-identical to the old time.monotonic()/sleep() calls
        self._clock = clock if clock is not None else SYSTEM_CLOCK
        self.eos_token = eos_token
        # the width of a prefill program follows the round's budget and
        # what the model says its kernels take (models/generate.py
        # prefill_width); a caller's ``prefill_chunk`` is used as given
        self.prefill_chunk = prefill_chunk if prefill_chunk is not None \
            else prefill_width(prefill_budget, base.widest_prefill)
        self._temperature = temperature
        self._top_k, self._top_p = top_k, top_p
        self._rng = jax.random.PRNGKey(seed)
        # draft-free speculation (serving/spec.py): gamma proposal tokens
        # per greedy row, verified in one fixed-width forward. ``proposer``
        # is injectable (tests force full acceptance/rejection with
        # oracle/adversarial drafts); default is prompt lookup.
        self.spec_tokens = int(spec_tokens)
        self._proposer = None
        if self.spec_tokens > 0:
            self._proposer = proposer if proposer is not None else \
                NgramProposer(max_ngram=spec_ngram, gamma=self.spec_tokens)
        # per-slot incremental lookup state (NgramIndex) — BUILT in the
        # overlap window of the round after a row activates (the O(history)
        # build is proposer bookkeeping, not critical-path work; a row's
        # first round simply proposes nothing, which can change which
        # rounds speculate but never what they emit), then extended by the
        # tokens emitted since, so drafting is O(suffix occurrences), not
        # O(history), per round
        self._spec_index: List[Optional[Any]] = [None] * slots

        self._active: List[Optional[Request]] = [None] * slots
        # last token per slot, as of the last round FETCHED: it lags the
        # device by the round in flight
        self._cur = np.zeros((slots,), np.int32)
        # host mirror of each slot's cache index (tokens resident in the
        # row's KV cache); what speculation rolls back to after rejection.
        # It moves at a round's DISPATCH, so the next round's block growth
        # and page table need no token of the round in flight
        self._pos = np.zeros((slots,), np.int64)
        # the per-round jit inputs, on the device. ``_cur_dev``/``_pos_dev``
        # are uploaded once, below, and from then on are the PREVIOUS
        # step's own outputs: the device keeps its own state between rounds
        # and the host uploads nothing. A finished prompt's row is written
        # into them on the device (``_activate``), never rebuilt from the
        # host mirrors, which lack the round in flight. The greedy mask
        # holds nothing the host does not know: it is uploaded again after
        # an admission (None = stale). Idle rows drift in the device copies
        # (stale token/position garbage) — harmless by construction: rows
        # are independent, idle writes land on the scratch block, and idle
        # outputs are never read.
        self._cur_dev: Any = None        # [slots] int32 last tokens
        self._pos_dev: Any = None        # [slots] int32 cache positions
        self._mask_dev: Any = None       # [slots] bool greedy mask
        # finished prompts whose slot decodes already and whose first token
        # the host has not fetched: ``(slot, request, [1] device array)``,
        # settled in the turn that made them (``_settle_first``)
        self._first_pending: List[tuple] = []
        # device->host fences taken by decode rounds — public so the
        # transfer-count regression test can pin the one-fence contract
        self.host_fetches = 0
        # the round in flight (``_InFlight``), its number, the thread that
        # may leave one across turns (``start()``'s loop; ``step()`` called
        # from any other fetches what it dispatched before it returns),
        # when the last fence ended, and the seconds a drain has taken
        # inside the loop phase now being timed
        self._inflight: Optional[_InFlight] = None
        self._round_seq = 0
        self._loop_ident: Optional[int] = None
        self._fenced_at = 0.0
        self._drain_wait = 0.0
        # admission plan computed in the overlap window (while the device
        # runs): (queue.version, free slot, candidate-or-None); committed
        # by the next round's _admit iff the queue did not move
        self._admission_plan: Any = None
        # per-round token accounting, flushed ONCE per round (metric
        # counters take a lock per inc — per-token increments were
        # measurable host overhead in the decode hot loop)
        self._round_tokens: dict = {}

        self.kernel_path = self._path_of(1)
        # what one cached token costs the pool, every layer: the model's
        # answer (models/serving.py), whatever its pages hold
        self._kv_token_bytes = base.kv_layers * base.kv_token_bytes(kv_quant)
        self._dispatches = DISPATCHES
        # the resident gauge is process-global and this process may run
        # several quantized pools (disagg: prefill + decode); each engine
        # contributes its own delta so the exported value is the SUM, and
        # close() withdraws the contribution (no stale reading after a
        # drain)
        self._quant_resident = QUANT_BLOCKS_RESIDENT
        self._quant_resident_seen = 0
        self._quant_resident_lock = threading.Lock()
        # a model with ``window`` leaves has a second kind of page, with a
        # pool, tables and a free list of its own (models/serving.py)
        window = getattr(base, "kv_window", None)
        if window is None and kv_window_blocks is not None:
            raise ValueError(
                f"kv_window_blocks: {type(base).__name__} has no window "
                f"leaves")
        if window is not None:
            # every slot's most, and the scratch block
            most = slots * window_bound(window, self.prefill_chunk,
                                        page_size, self._pages_per_seq) + 1
            if kv_pool_bytes is not None and kv_window_blocks is None:
                kv_window_blocks, kv_pool_bytes = divide_pool(
                    kv_pool_bytes, base, most,
                    slots * self._pages_per_seq + 1, page_size)
            elif kv_window_blocks is None:
                kv_window_blocks = most
        if kv_pool_bytes is not None:
            if kv_blocks is not None:
                raise ValueError(
                    "pass kv_blocks or kv_pool_bytes, not both")
            # size the pool by its HBM payload budget over what the model
            # says a cached token costs a layer (keys and values a head, or
            # one vector with no head axis): int8 blocks are half the bytes
            # of bf16 blocks, so the same budget holds ~2x the blocks — the
            # whole point of kv_quant
            kv_blocks = max(2, kv_pool_bytes // (
                page_size * self._kv_token_bytes))
        if kv_blocks is None:
            # dense-equivalent HBM by default (+1 scratch); pass less to
            # overcommit, more to grow the prefix cache's working set. No
            # block at all, not even the scratch one, where no leaf is paged
            kv_blocks = slots * self._pages_per_seq + 1 if self._pooled \
                else 0
        if kv_blocks < 2 and self._pooled:
            raise ValueError(f"kv_blocks must be >= 2, got {kv_blocks}")
        self._kv_blocks = kv_blocks
        if self._paged_kernel == "pallas" and not pallas_interpreted(None):
            # no silent drop to the interpreter or to lax: what the TPU
            # lowering refuses of the model's kernels, its read of this
            # pool among them, it refuses here, before a pool exists
            base.check_kernels(
                slots=slots, kv_blocks=kv_blocks if self._pooled else None,
                page_size=page_size,
                pages_per_seq=self._pages_per_seq, kv_quant=kv_quant,
                **({} if window is None
                   else {"window_blocks": kv_window_blocks}))
        self.kv = RadixCache(kv_blocks, page_size)
        # the window kind's allocator and each slot's row of it; None for
        # a model with one kind of page
        self._win = None if window is None else WindowPages(
            kv_window_blocks, page_size, window, self._pages_per_seq,
            self.prefill_chunk)
        # page tables: [slots, pages_per_seq] block ids (0 = scratch pad);
        # _slot_blocks mirrors the allocated prefix of each row in python
        self._tables = np.zeros((slots, self._pages_per_seq), np.int32)
        # device mirror of _tables, uploaded once and reused until a
        # table write dirties it (upload-once discipline — see
        # _page_table_dev); every _tables mutation site sets it to None
        self._pt_dev = None
        self._slot_blocks: List[List[int]] = [[] for _ in range(slots)]
        # with no pool there is no table to say which rows are live (a live
        # row's first page is not the scratch block): the rows themselves,
        # set when a prompt's state is spliced in, cleared when the slot is
        # freed, uploaded where a table would be
        self._live = None if self._pooled else np.zeros((slots,), np.int32)
        if self._win is not None:
            # the window kind's twin of ``_tables`` / ``_slot_blocks``; a
            # write to either table dirties the one device mirror
            self._win_tables = np.zeros_like(self._tables)
            self._win_rows = [self._win.row() for _ in range(slots)]
        # per-row cached-token counts live in _pos
        self._admit_seq = np.zeros((slots,), np.int64)  # admission order
        self._admissions = 0
        # the programs this engine has called once (``_first``): a prefill
        # width by its number, the others by name. And what was built in
        # the round now running, for the slow-phase line
        self._built: set = set()
        self._round_builds: list = []
        self._stat_counters: tuple = ()
        self._dispatch_paths: dict = {}   # positions a row -> path labels
        self._build_decode_path(base)
        self._cur_dev = self._programs.upload(self._cur)
        self._pos_dev = self._programs.upload(
            np.zeros((slots,), np.int32))
        if self._pooled != any(k in serving.POOLS for k in self._leaf_kinds):
            raise ValueError(
                f"{type(base).__name__} says kv_layers {base.kv_layers} and "
                f"its module's cache leaves are of kinds "
                f"{sorted(set(self._leaf_kinds))}: a pool of pages has a "
                f"layer that writes it, and the other way round")
        refusal = leaf_refusal(self._leaf_kinds)
        if refusal is not None:
            self._refuse_for_leaves(
                *refusal, kv_tier is not None or kv_storage_tier is not None
                or kv_host_tier_bytes is not None)
        # the tiers, parked chains, KV import and export
        # (serving/kv_io.py), serviced between rounds on the scheduling
        # thread: the object reaches the scheduler through these alone
        self.kv_io = KvIO(
            self.kv, page_size, self._clock,
            leaf_keys=self._leaf_keys, refusal=refusal,
            payload=lambda: self._payload,
            adopt=lambda leaves: setattr(self, "_payload", leaves),
            drain=self._drain,
            wake=lambda: self.queue.work_available.set(),
            threaded=lambda: self._thread is not None,
            closed=lambda: self._closed,
            note_import=self._note_kv_import,
            tier=kv_tier, host_tier_bytes=kv_host_tier_bytes,
            storage_tier=kv_storage_tier,
            mesh_shape=getattr(self, "kv_mesh_shape", None))

        self.prefill_budget = (None if prefill_budget is None
                               else int(prefill_budget))
        # a staged prompt from its admission to its first token
        # (serving/prefill.py): the scheduler stages, advances, reaps and
        # closes it, and is told how each job ended
        self.prefill = Prefill(
            base, self._model, self.params,
            leaf_kinds=self._leaf_kinds, treedef=self._cache_treedef,
            state_at=self._state_at, pool_at=self._pool_at,
            build=self._programs, kv=self.kv, kv_io=self.kv_io,
            win=self._win, page_size=page_size, pooled=self._pooled,
            chunk=self.prefill_chunk, budget=self.prefill_budget,
            max_jobs=max_prefill_jobs,
            sampling=(temperature, top_k, top_p),
            tells_real=self._tells_real, clock=self._clock,
            payload=lambda: self._payload, rng=lambda: self._rng,
            set_rng=lambda key: setattr(self, "_rng", key),
            row_greedy=self._row_greedy, first=self._first,
            count_dispatch=self._count_dispatch,
            enter=lambda: CHAOS.hit("engine.prefill"), fatal=PoolCorruption,
            finished=self._prompt_done, failed=self._fail_request,
            cancelled=self._finish_cancelled)
        # what the round's decode half did, for the engine.round span
        self._round_kind: Optional[str] = None
        self._round_rows = self._round_emitted = 0
        # per-tenant SLO state: policy table (WFQ weights, queue caps, KV
        # quotas) and terminal accounting for the scoped stats surface
        self.tenants = tenants
        # written by the engine loop, snapshotted by RPC stats threads —
        # the lock covers first-seen row insertion vs. iteration
        self._tenant_counts: dict = {}
        self._tenant_counts_lock = threading.Lock()

        self.queue = RequestQueue(max_queue, policies=tenants,
                                  clock=self._clock)
        self._finished = 0
        self._cancelled = 0
        self._tokens_out = 0
        # speculation + throughput accounting (public: the gateway fleet
        # aggregates these across replicas, banking them on retirement)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        self.spec_draft_truncated = 0   # drafts cut by NoFreeBlocks
        self.decode_steps = 0     # decode rounds (normal + verify)
        self.decode_rows = 0      # cumulative active rows over rounds
        self.decode_tokens = 0    # tokens emitted by decode rounds
        self._stop = threading.Event()
        self._closed = False
        self._draining = False
        # every admitted, not-yet-terminal request — what drain() waits
        # on. Queue depth + busy slots is NOT enough: between the pop
        # and slot activation a request is mid-prefill and visible in
        # neither, and drain closing in that window would kill it.
        self._outstanding: set = set()
        self._outstanding_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        _SLOTS.set(float(slots))
        _BUSY.set(0.0)

    # -- cache payload/treedef split ---------------------------------------

    def _adopt_cache(self, tree) -> None:
        """Split the freshly built cache tree into PAYLOAD leaves (k/v —
        whatever the model owns) and the per-layer ``index`` leaves. The
        index leaves all mirror one [slots] position vector, so the
        engine keeps exactly one (``_pos`` on the host, ``_pos_dev`` on
        the device) and re-broadcasts it into the tree at every use: a
        jitted step whose outputs were CSE'd into a shared index buffer
        can no longer poison the next donation, because index leaves are
        never round-tripped through a step at all."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        self._cache_treedef = treedef
        self._leaf_kinds = [serving.leaf_kind(self._model, p)
                            for p, _ in flat]
        self._leaf_is_index = [k == serving.INDEX for k in self._leaf_kinds]
        # what a block's payload is keyed by when it leaves the pool
        # (serving/kv_io.py): each payload leaf's path
        self._leaf_keys = [jax.tree_util.keystr(p) for (p, _), idx
                           in zip(flat, self._leaf_is_index) if not idx]
        self._payload = [leaf for (p, leaf), idx
                         in zip(flat, self._leaf_is_index) if not idx]
        # which payload leaves are per-slot state ([slots, ...], one row a
        # slot), by their place in the payload; the rest are keys and values
        payload_kinds = [k for k in self._leaf_kinds if k != serving.INDEX]
        self._state_at = [i for i, k in enumerate(payload_kinds)
                          if k == serving.STATE]
        self._pool_at = [i for i, k in enumerate(payload_kinds)
                         if k != serving.STATE]
        self._has_state = bool(self._state_at)
        self._state_names = [self._leaf_keys[i] for i in self._state_at]
        # a model with state leaves, or one that counts (``STATS``), is
        # told which positions of a program are real (``valid_len``)
        self._tells_real = self._has_state or bool(type(self._model).STATS)

    def state_leaves(self) -> Dict[str, Any]:
        """The per-slot state leaves of the decode tree as they stand, by
        their path in the cache (``{}`` for a model that has none):
        ``[slots, ...]`` device arrays, a row a slot. A freed slot's row
        stays as its last round left it until the next prompt's state is
        spliced in, so what a finished request's recurrence ended at can
        be read afterwards. A step donates these buffers: read them while
        no round is in flight."""
        return dict(zip(self._state_names,
                        (self._payload[i] for i in self._state_at)))

    def _assemble_cache(self, payload, index_leaf):
        """Full cache tree from payload leaves + ONE index value placed
        at every index leaf (traced inside jit; eager callers must pass
        distinct buffers per leaf if the result will be donated)."""
        leaves, it = [], iter(payload)
        for idx in self._leaf_is_index:
            leaves.append(index_leaf if idx else next(it))
        return jax.tree_util.tree_unflatten(self._cache_treedef, leaves)

    def _split_cache(self, tree):
        """Inverse of :meth:`_assemble_cache`: payload leaves plus the
        FIRST index leaf (the model advances every layer's index
        identically, so one survives as the step's new position)."""
        leaves = jax.tree_util.tree_leaves(tree)
        payload = [leaf for leaf, idx in zip(leaves, self._leaf_is_index)
                   if not idx]
        new_pos = next(leaf for leaf, idx
                       in zip(leaves, self._leaf_is_index) if idx)
        return payload, new_pos

    # -- sampling helpers --------------------------------------------------

    def _pick_next(self, logits, greedy_mask, rng):
        """Per-row next token inside a jitted step: sample with the
        engine-wide params (exactly one rng split — the draw order every
        bit-identical guarantee leans on), then overwrite rows pinned
        greedy with argmax. All-greedy engines (temperature<=0) already
        get argmax from ``sample_token``; the ``where`` is then a no-op."""
        nxt, rng = sample_token(logits, self._temperature, rng,
                                top_k=self._top_k, top_p=self._top_p)
        nxt = jnp.where(
            greedy_mask, jnp.argmax(logits, axis=-1).astype(jnp.int32), nxt)
        return nxt, rng

    def _row_greedy(self, req: Request) -> bool:
        """Effective sampling mode for a request: its own override, else
        the engine-wide temperature."""
        if req.greedy is not None:
            return bool(req.greedy)
        return self._temperature <= 0.0

    def _greedy_mask(self) -> np.ndarray:
        """[slots] bool — True rows take argmax in the jitted step (idle
        rows are arbitrarily True; their tokens are never read)."""
        return np.asarray(
            [self._row_greedy(r) if r is not None else True
             for r in self._active], bool)

    # -- request surface ---------------------------------------------------

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 64,
               request_id: Optional[str] = None,
               deadline_s: Optional[float] = None,
               greedy: Optional[bool] = None,
               tenant: str = "default",
               priority: Optional[int] = None,
               liveness=None) -> Request:
        """Admit a request (raises ``AdmissionError`` under backpressure,
        ``PromptTooLong`` if it can never fit the cache). Returns the
        :class:`Request`; wait with ``request.result(timeout)``.
        ``deadline_s``: optional client deadline relative to now — once it
        passes the engine evicts the request mid-decode (slot and cache
        blocks freed) and finishes it with the ``cancelled`` status.
        ``greedy``: per-request sampling override (True forces argmax —
        and with it speculation eligibility — on a sampling engine; None
        follows the engine-wide temperature). ``tenant``/``priority``:
        SLO identity — the WFQ subqueue and fairness tier the request
        queues under (quotas and rate limits key on the tenant).
        ``liveness``: optional reply-channel probe (returns False once
        the client is gone) — checked every scheduling round, so a
        disconnected client's request is reaped from the queue in place
        or evicted from its slot within one decode round."""
        if self._closed or self._draining:
            # fail fast instead of admitting into a queue no loop will ever
            # drain (shutdown stops the engine before the RPC server, so
            # this window is reachable over the wire; the front maps it to
            # the same retryable Unavailable a full queue produces). A
            # DRAINING engine still finishes its in-flight rows but must
            # not take on new ones — the graceful-shutdown contract.
            raise AdmissionError("inference engine is shut down")
        prompt = list(prompt)
        if not prompt:
            raise ValueError("prompt must be non-empty")
        # reject prompts the pool — or the tenant's quota — can NEVER
        # cover: past submit they would park in the queue forever
        # (admission waits for blocks that cannot exist) and waste a
        # tenant's WFQ share on an unservable head
        need = self._blocks_for(len(prompt))
        if need > max(self._kv_blocks - 1, 0):
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) needs "
                f"{need} KV blocks but the "
                f"pool only has {self._kv_blocks - 1}; raise kv_blocks or "
                f"shorten the prompt")
        quota = self._tenant_quota(tenant or "default")
        if quota is not None and need > quota:
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) needs "
                f"{need} KV blocks but "
                f"tenant {tenant!r} is capped at {quota}; shorten the "
                f"prompt or raise the tenant's kv_block_quota")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if len(prompt) + max_new_tokens > self.cfg.max_seq_len:
            # a clear, typed rejection AT ADMISSION: past this point the
            # prompt would die as a shape/indexing error deep inside
            # prefill — opaque to the client and chargeable to replica
            # health even though the request itself is at fault
            raise PromptTooLong(
                f"prompt ({len(prompt)} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"({self.cfg.max_seq_len}); the prompt can never be "
                f"served — shorten it or reduce max_new_tokens")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        req = Request(prompt, max_new_tokens, request_id=request_id,
                      deadline_s=deadline_s, greedy=greedy,
                      tenant=tenant, priority=priority,
                      liveness=liveness, clock=self._clock)
        self.queue.submit(req)
        with self._outstanding_lock:
            self._outstanding = {r for r in self._outstanding
                                 if not r.done}
            self._outstanding.add(req)
        if self._closed:
            # raced a concurrent close(): its shutdown sweeps may have
            # already run, and nothing will ever pop this queue — fail
            # fast instead of stranding the waiter for its full timeout
            req.cancel()
            if not req.done:
                req.finish(error="engine shutting down")
            raise AdmissionError("inference engine is shut down")
        return req

    # -- engine loop -------------------------------------------------------

    def step(self) -> bool:
        """One scheduling round: reap cancelled slots, admit (stage) a
        waiting request into a free slot, advance at most one prefill
        job by the token budget, then advance every active slot by one
        jitted decode step. Returns False when there was nothing to do.

        Prefill and decode INTERLEAVE: with a ``prefill_budget`` a long
        prompt's prefill is spread over many rounds, each of which also
        runs a decode step for the resident rows — bounded inter-token
        latency for them, bounded time-to-first-chunk for newly staged
        short prompts (jobs rotate round-robin).

        When it returns, every token of the decode round it dispatched
        has been emitted: ``plan -> dispatch -> overlap -> fence -> emit``,
        one fence a round. Called by the loop thread of ``start()`` the
        fence lags the dispatch by one turn (``plan(n+1) -> dispatch(n+1)
        -> overlap -> fence(n) -> emit(n)``): still one fence a round,
        taken while the device runs the next one.

        A turn that finishes a prompt waits for nothing before that
        dispatch: the slot is activated on the device (``_prompt_done``),
        round n+1 carries the new row and is queued behind the prompt's
        last program, and the first token is fetched and emitted after it
        (behind round n's fence on the loop thread), in the same turn: when
        ``step()`` returns, from whichever thread, the first token of a
        prompt it finished has been emitted."""
        now = self._clock.now
        lag = threading.get_ident() == self._loop_ident
        self._round_kind = None
        self._round_rows = self._round_emitted = 0
        if self._round_builds:
            self._round_builds = []
        with trace.span(trace.ENGINE_ROUND) as rnd:
            t0 = now()
            with trace.span(trace.ENGINE_KV_IO):
                serviced = self.kv_io.service()
            t1 = now()
            kv_io_dt = self._less_drains(t1 - t0)
            if CHAOS.armed is not None and (
                    self.queue.depth() or self.prefill.jobs
                    or any(r is not None for r in self._active)):
                # chaos boundary, hit only on rounds with real work so a
                # parked loop's idle spins don't consume the fault
                # schedule. The armed check comes FIRST: disarmed
                # (production) rounds must not pay the queue-lock probe in
                # the hottest loop
                CHAOS.hit("engine.step")
            with trace.span(trace.ENGINE_REAP):
                self._reap_cancelled()
            t2 = now()
            with trace.span(trace.ENGINE_ADMIT):
                admitted = self._admit()
            t3 = now()
            with trace.span(trace.ENGINE_PREFILL):
                progressed = self.prefill.advance()
            t4 = now()
            # a finished prompt that had to have its first token at once
            # (``_prompt_done``) drained the round in flight and waited
            # inside ``advance``: observed as fence, emit and prefill_fence,
            # not as prefill
            prefill_dt = self._less_drains(t4 - t3) - self.prefill.fence_wait
            stepped = self._decode(lag)
            # observed after the round's fence, like the decode half's
            self._observe_phase("kv_io", kv_io_dt)
            self._observe_phase("reap", t2 - t1)
            self._observe_phase("admit", t3 - t2)
            self._observe_phase("prefill", prefill_dt)
            # the wait for a finished prompt's first token, wherever in the
            # turn it fell (as a rule behind the decode half's dispatch)
            fence_wait = self.prefill.fence_wait
            if fence_wait:      # only a turn that finished a prompt
                self._observe_phase("prefill_fence", fence_wait)
            worked = serviced or admitted or progressed or stepped
            if rnd and stepped:
                trace.note(kind=self._round_kind, rows=self._round_rows,
                           emitted=self._round_emitted)
            elif rnd:
                trace.note(kind="prefill_only" if worked else "idle")
        return worked

    def _less_drains(self, dt: float) -> float:
        """A loop phase's seconds less what a drain of the round in flight
        took inside it (its fence and emit are observed under their own
        labels)."""
        dt -= self._drain_wait
        self._drain_wait = 0.0
        return dt

    def _reap_cancelled(self) -> None:
        """Free slots whose waiter abandoned the request (client
        timeout), whose client deadline passed, or whose reply channel
        reports the client gone (``Request.client_dead`` — a streaming
        consumer that disconnected or stalled past its bounded buffer):
        decode steps are the scarce resource, and spending them on
        tokens nobody will read starves live requests. Either way the
        request terminates with the ``cancelled`` status (partial
        tokens stay readable)."""
        for req in self.queue.reap_dead():
            self._finish_cancelled(req)
        self.prefill.reap()
        for slot, req in enumerate(self._active):
            if req is not None and req.reapable:
                # free BEFORE finishing: finish() wakes the waiter, and a
                # client that sees its request done must also see the
                # slot/blocks released (stats read-your-writes)
                self._free(slot)
                self._finish_cancelled(req)

    def _finish_cancelled(self, req: Request) -> None:
        _REQUESTS.inc(status="cancelled")
        TENANT_REQUESTS.inc(tenant=req.tenant, status="cancelled")
        self._tenant_count(req.tenant, "requests_cancelled")
        self._cancelled += 1
        if req.cancelled:
            why = "cancelled"
        elif req.expired:
            why = "cancelled: deadline exceeded"
        else:
            why = "cancelled: client disconnected"
        if req.liveness is not None:
            # stream-delivered request: count the cancel under the phase
            # it was reaped in (queued / prefill / decode) — the
            # observable difference between "the queue absorbed it" and
            # "a slot was burned first"
            from lzy_tpu.serving.streams import CANCELS

            CANCELS.inc(phase=req.phase)
        req.finish(error=why, status="cancelled")

    def _tenant_count(self, tenant: str, key: str, n: int = 1) -> None:
        with self._tenant_counts_lock:
            self._tenant_counts.setdefault(
                tenant, dict(TENANT_ROW))[key] += n

    def _free_slot(self) -> Optional[int]:
        """A slot neither active nor reserved by a pending prefill job;
        none while ``max_prefill_jobs`` prompts are staged."""
        if self.prefill.full:
            return None
        reserved = self.prefill.slots()
        for slot, req in enumerate(self._active):
            if req is None and slot not in reserved:
                return slot
        return None

    def _try_stage(self, slot: int, req: Request) -> bool:
        """Pop one admitted candidate and stage its prefill; a
        request-scoped staging failure finishes the request in place.
        True iff a prefill job was staged."""
        self.queue.pop_request(req)
        req.phase = "prefill"
        if req.admitted_at is None:
            req.admitted_at = self._clock.now()
        try:
            job = self.prefill.stage(slot, req)
        except Exception as e:  # noqa: BLE001 — request-scoped
            self._fail_request(req, e, "prefill staging")
            return False
        if trace.ON:
            trace.note(request=req.id, prompt_tokens=len(req.prompt),
                       prefix_hit_tokens=job.matched, blocks=len(job.table))
        return True

    def _fail_request(self, req: Request, e: Exception, what: str) -> None:
        """A request-scoped failure (staging, or a prefill round past its
        device section): counted, and the request finished with it."""
        _LOG.warning("%s failed for %s: %s", what, req.id, e)
        _REQUESTS.inc(status="error")
        TENANT_REQUESTS.inc(tenant=req.tenant, status="error")
        self._tenant_count(req.tenant, "requests_error")
        req.finish(error=f"{type(e).__name__}: {e}")

    def _commit_admission_plan(self) -> Optional[bool]:
        """Commit the admission choice precomputed in the previous
        round's overlap window (:meth:`_plan_admission`). Returns the
        round's admission outcome, or None to fall back to the full
        scan: the plan only commits when the queue version is untouched
        AND the non-queue admission state (the slot, the resource
        verdict, the candidate's liveness) re-verifies."""
        plan, self._admission_plan = self._admission_plan, None
        if plan is None:
            return None
        version, slot, choice = plan
        if version != self.queue.version:
            _OVERLAP_COMMITS.inc(outcome="stale")
            return None
        if choice is None:
            # the overlap-window scan already ran against this exact
            # queue state and found nothing admissible — skip the rescan
            _OVERLAP_COMMITS.inc(outcome="empty")
            return False
        if (self._active[slot] is not None or slot in self.prefill.slots()
                or choice.reapable
                or self._admit_verdict(choice) != "admit"):
            # admission state moved without a queue mutation (deadline
            # passed, block pool shrank): replan from scratch
            _OVERLAP_COMMITS.inc(outcome="stale")
            return None
        _OVERLAP_COMMITS.inc(outcome="committed")
        return True if self._try_stage(slot, choice) else None

    def _admit(self) -> bool:
        fast = self._commit_admission_plan()
        if fast is not None:
            _BUSY.set(float(sum(r is not None for r in self._active)))
            return fast
        admitted = False
        while True:
            slot = self._free_slot()
            if slot is None:
                break
            rescan = False
            for req in self.queue.candidates():
                if req.reapable:
                    if self.queue.pop_request(req):
                        self._finish_cancelled(req)
                    rescan = True
                    break
                verdict = self._admit_verdict(req)
                if verdict == "skip":
                    continue
                if verdict == "wait":
                    break
                if self._try_stage(slot, req):
                    admitted = True
                else:
                    rescan = True
                break
            if rescan:
                continue
            # at most ONE staging per scheduling round: admissions run
            # between decode steps, and one-per-round caps the scheduling
            # work (and, with no budget, the inter-token latency spike)
            # at a single prefill while the rest of the queue joins over
            # the next few rounds
            break
        _BUSY.set(float(sum(r is not None for r in self._active)))
        return admitted

    def _prompt_done(self, job: Job, first) -> None:
        """What a finished prompt does to its slot (``Prefill``'s
        ``finished``): the job's blocks and window row become the slot's,
        and the slot starts generating from ``first``, the ``[1]`` token on
        the device as the prompt's last program picked it.

        Nothing here waits for that program. The row is activated on the
        device (``_activate``), so the turn's decode half dispatches the
        next round with the new row in it, over the round in flight and
        behind the prompt's programs in the device's queue; the token is
        fetched after that, in the same turn (``_settle_first``). An EOS
        first token is learnt one round late, as a decode row's is: the row
        rides that round and its token is dropped. Two kinds of prompt take
        the drained path instead (the round in flight fetched, then the
        first token waited for, then the slot's fate decided): one whose
        first token is its last, known before any fetch (it never activates
        a slot), and any prompt of an engine with a proposer, whose next
        turn reads the newest token."""
        req, slot, table = job.req, job.slot, job.table
        # register the prompt's full blocks for future prefix hits (the
        # matched prefix nodes already exist and are skipped; pad garbage
        # only ever lands past the prompt, never inside a full block)
        n_full = len(req.prompt) // self._page
        if n_full:
            self.kv.insert(req.prompt[:n_full * self._page], table[:n_full])
        self._tables[slot, :len(table)] = table
        self._tables[slot, len(table):] = 0
        if self._live is not None:
            self._live[slot] = 1
        self._pt_dev = None
        self._slot_blocks[slot] = list(table)
        if job.window is not None:
            # the slot's row from here on; what was set aside for the
            # prompt and not taken is anybody's again
            self._win.unreserve(job.window)
            self._win_rows[slot] = job.window
            self._win_tables[slot] = job.window.table
            job.window = None
        self._admissions += 1
        self._admit_seq[slot] = self._admissions
        # the prompt is now cache-resident; the first generated token is
        # not (the next decode step writes it at this position)
        self._pos[slot] = len(req.prompt)
        one_token = req.max_new_tokens == 1
        if not one_token:
            with self._first("activate", trace.SITE_AUX, phase="prefill"):
                self._cur_dev, self._pos_dev = self._activate(
                    self._cur_dev, self._pos_dev, first, np.int32(slot),
                    np.int32(len(req.prompt)))
            # the live row set changed, which the greedy mask follows: it
            # holds nothing the host does not know, and is uploaded again
            self._mask_dev = None
        if one_token or self._proposer is not None:
            _ACTIVATIONS.inc(how="drained")
            # the round in flight was queued in front of the prompt's
            # programs: its tokens go out now, not behind them
            self._drain("admission")
            self._finish_prefill(slot, req, self.prefill.fence(first))
        else:
            _ACTIVATIONS.inc(how="device")
            self._active[slot] = req
            self._first_pending.append((slot, req, first))

    def _settle_first(self) -> None:
        """Fetch and emit the first token of the prompt this turn finished
        and activated on the device. Called behind the dispatch of the
        round that carries the new row (and, on the loop thread, behind the
        fence of the round before it, which left the device first), or by
        whatever drains the turn before that: never later than the turn's
        end, so no reap, preemption or admission falls between the
        activation and the token."""
        if not self._first_pending:
            return
        pending, self._first_pending = self._first_pending, []
        for slot, req, first in pending:
            self._finish_prefill(slot, req, self.prefill.fence(first))

    def _finish_prefill(self, slot: int, req: Request, first: int) -> None:
        """Shared prefill tail, once the first token is on the host: record
        TTFT, emit the token, and either free the slot (the token ended the
        request) or leave it decoding."""
        req.phase = "decode"
        now = self._clock.now()
        req.first_token_at = now
        _TTFT.observe(now - req.submitted_at)
        TENANT_TTFT.observe(now - req.submitted_at, tenant=req.tenant)
        self._emit(slot, req, first, active=False)
        if req.done:
            self._free(slot)
        else:
            self._active[slot] = req
            self._cur[slot] = first
        self._flush_token_accounting()

    def _fetch(self, arr) -> np.ndarray:
        """THE round fence: the one device→host transfer a decode round
        is allowed. Counted (``host_fetches``) so the transfer-count
        regression test can pin the contract at exactly one per round:
        taken at once by ``step()``, one turn after the dispatch by the
        loop thread (``_fence_emit``), never twice and never skipped."""
        self.host_fetches += 1
        _ROUND_FENCES.inc()
        return np.asarray(arr)

    def _device_inputs(self):
        """The per-round jit inputs, device-resident across rounds.
        ``_cur_dev``/``_pos_dev`` are the previous step's own outputs, with
        the rows of prompts finished since written in on the device
        (``_activate``): nothing is uploaded, and no token of that step or
        of those prompts is needed on the host (the step may still be in
        flight, the first tokens unfetched). The greedy mask alone is
        rebuilt from the host after an admission, by
        ``ProgramBuild.upload``: an explicit copy (``jnp.array``), never
        ``jnp.asarray``, which zero-copies the live numpy buffer."""
        if self._mask_dev is None:
            self._mask_dev = self._programs.upload(self._greedy_mask())
        return self._cur_dev, self._pos_dev, self._mask_dev

    def _stale_inputs(self) -> int:
        """How many of the round's inputs (the greedy mask, the page
        table) the dispatch is about to upload from the host: 0 in a round
        that follows no admission and grew no page."""
        return (self._mask_dev is None) + (self._pt_dev is None)

    def _overlap_window(self) -> None:
        """Host work run BETWEEN the round's dispatch and its fence —
        while the device computes, for free on the wall clock: the next
        round's admission plan and deferred proposer index builds."""
        self._plan_admission()
        self._drain_side_work()

    def _plan_admission(self) -> None:
        """Precompute the next round's admission choice (WFQ candidate
        scan + resource verdict) and stamp it with the queue version;
        ``_admit`` commits it next round iff the queue has not moved
        since (any submit/pop/reap bumps the version)."""
        slot = self._free_slot()
        if slot is None:
            self._admission_plan = None
            return
        version = self.queue.version
        choice = None
        for req in self.queue.candidates():
            if req.reapable:
                # reaping mutates terminal state — not overlap-safe;
                # leave it for the next round's full scan
                self._admission_plan = None
                return
            verdict = self._admit_verdict(req)
            if verdict == "skip":
                continue
            if verdict == "admit":
                choice = req
            break
        self._admission_plan = (version, slot, choice)

    def _drain_side_work(self) -> None:
        """Deferred proposer bookkeeping: build the per-slot NgramIndex
        for rows that activated since the last round. O(history) per new
        row — exactly the work that used to run on the critical path
        before the dispatch; proposals never change emitted tokens (only
        which rounds get to speculate), so deferral is output-invisible."""
        if self._proposer is None:
            return
        index_fn = getattr(self._proposer, "index", None)
        if index_fn is None:
            return
        for slot, req in enumerate(self._active):
            if req is None or not self._row_greedy(req):
                continue
            if self._spec_index[slot] is None:
                self._spec_index[slot] = index_fn(req.prompt + req.tokens)

    def _flush_token_accounting(self) -> None:
        """Batched per-round metric flush: one counter inc per tenant
        per round instead of three lock acquisitions per TOKEN."""
        if not self._round_tokens:
            return
        pending, self._round_tokens = self._round_tokens, {}
        total = 0
        for tenant, n in pending.items():
            total += n
            TENANT_TOKENS.inc(n, tenant=tenant)
            self._tenant_count(tenant, "tokens_generated", n)
        _TOKENS.inc(total)

    def _decode(self, lag: bool = False) -> bool:
        """The decode half of a turn. ``lag`` (the loop thread of
        ``start()``): the round dispatched here stays in flight and the
        one fetched is the round before it, so the fence's tail, the emit
        and the next turn's front half run under a device program. The
        round in flight is drained first where this turn needs its
        tokens: a proposer reads the newest token, or every live row ends
        with the token in flight. A prompt finished in this turn needs
        none: its row is in the device inputs already, and its first token
        is fetched behind the dispatch (and behind the fence of the round
        before, which the device ran first), in front of this round's own
        tokens."""
        drained = False
        if self._inflight is not None:
            why = self._needs_tokens()
            if why is not None:
                drained = self._drain(why)
                self._drain_wait = 0.0      # it fell in no timed phase
        if not any(r is not None for r in self._active):
            return drained
        t_plan = self._clock.now()
        with trace.span(trace.ENGINE_DECODE_PLAN):
            self._grow_for_decode()
            if not any(r is not None for r in self._active):
                return drained      # the squeeze preempted everyone
            plan = self._spec_plan()
        # with a proposer every round is fetched in its own turn (the
        # proposals read its tokens), which the loop thread counts
        spec = "spec" if lag and self._proposer is not None else None
        lag = lag and spec is None
        if plan is not None:
            return self._decode_verify(plan, t_plan, spec)
        t0 = self._clock.now()
        plan_dt = self._less_drains(t0 - t_plan)
        before = self._inflight
        with trace.span(trace.ENGINE_DECODE_DISPATCH):
            self._round_seq += 1
            if trace.ON:
                trace.note(uploads=self._stale_inputs(),
                           round=self._round_seq,
                           overlapped=before is not None)
            rec = self._dispatch_decode(t0)
        t1 = self._clock.now()
        with trace.span(trace.ENGINE_DECODE_OVERLAP):
            self._overlap_window()
        t2 = self._clock.now()
        if before is not None:
            _ROUNDS_OVERLAPPED.inc()
            self._fence_emit(before)
        self._settle_first()
        if not lag:
            self._fence_emit(rec, spec)
        # observed after the turn's fence: these take locks
        self._observe_phase("plan", plan_dt)
        self._observe_phase("dispatch", t1 - t0)
        self._observe_phase("overlap", t2 - t1)
        return True

    def _dispatch_decode(self, t0: float) -> _InFlight:
        """Hand the device one decode round over the active rows and move
        the host's positions with it (the 1-token step puts one more
        token into every active row's cache): everything the next round's
        plan needs is known once this returns, the tokens are not."""
        rng = self._rng
        # a model whose layers sow counts returns them packed behind the
        # tokens, as the one array the fence fetches
        (cur, pos, mask), tables = self._round_inputs()
        self._count_dispatch(1)
        # builds here only where warmup() was not called (after it jit
        # finds what it traced, lowered and compiled there)
        with self._first("decode", trace.SITE_DECODE, phase="dispatch"):
            (self._payload, self._pos_dev, self._cur_dev, self._rng,
             *packed) = self._decode_step(self._payload, self.params, cur,
                                          pos, tables, mask, rng)
        rows = [(slot, req) for slot, req in enumerate(self._active)
                if req is not None]
        for slot, _ in rows:
            self._pos[slot] += 1
        out = packed[0] if packed else self._cur_dev
        # asked for now, in front of whatever is queued next: the fence
        # then waits for this round, not for a copy behind a later program
        out.copy_to_host_async()
        rec = self._inflight = _InFlight(
            seq=self._round_seq, out=out, rows=rows, rng=rng, t0=t0)
        self._round_kind, self._round_rows = "decode", len(rows)
        return rec

    def _needs_tokens(self) -> Optional[str]:
        """Why this turn cannot dispatch over the round in flight, or
        None: what ``lzy_engine_round_drains_total`` is labelled with."""
        riding = self._riding()
        if all(req is None or slot in riding
               for slot, req in enumerate(self._active)):
            # a length finish is known before the fetch: no round is
            # dispatched past the end of the last live row
            return "last_row"
        return None

    def _riding(self) -> set:
        """Slots whose request ends by length with the token in flight:
        still active until that token is fetched, so they ride the next
        round (its token for them is dropped) but grow no block for it. A
        first token is fetched in the turn that dispatched its row's first
        round, so ``req.tokens`` holds it by the time the row is in
        flight."""
        rec = self._inflight
        if rec is None:
            return set()
        return {slot for slot, req in rec.rows
                if self._active[slot] is req
                and len(req.tokens) + 1 >= req.max_new_tokens}

    def _drain(self, reason: str) -> bool:
        """Fetch and emit what the device owes the host, with no later
        round queued behind it: the round in flight, then the first token
        of a prompt this turn finished (the device ran them in that order).
        False when there is neither. What it takes is observed as ``fence``,
        ``emit`` and ``prefill_fence`` and kept out of the phase it fell
        in."""
        rec = self._inflight
        if rec is None and not self._first_pending:
            return False
        t0 = self._clock.now()
        if rec is not None:
            self._fence_emit(rec, reason)
        self._settle_first()
        self._drain_wait += self._clock.now() - t0
        return True

    def _fence_emit(self, rec: _InFlight,
                    drained: Optional[str] = None) -> None:
        """A round's ONE fence and its emit: the tokens, and behind them
        whatever counts the model's layers carried out of the step. A
        token goes to the request the row was dispatched for if that
        request still holds the slot; otherwise the row over-ran (an EOS
        or a reap learnt one round late) and the token is dropped. The
        emit span's ``rows`` and ``model_stats`` are this round's, the
        rows being what it was dispatched with."""
        if drained is not None:
            _ROUND_DRAINS.inc(reason=drained)
        t2 = self._clock.now()
        with trace.span(trace.ENGINE_DECODE_FENCE):
            nxt = self._fetch(rec.out)
            if trace.ON:
                trace.note(bytes=nxt.nbytes, round=rec.seq)
        if self._inflight is rec:
            self._inflight = None
        t3 = self._clock.now()
        # the round's own seconds: from its dispatch, or from the fence
        # before it where it was queued behind that round
        dt = t3 - max(rec.t0, self._fenced_at)
        self._fenced_at = t3
        with trace.span(trace.ENGINE_DECODE_EMIT):
            _STEP.observe(dt)
            nxt = self._note_model_stats(nxt, rec)
            emitted = 0
            for slot, req in rec.rows:
                if self._active[slot] is req:
                    self._emit(slot, req, int(nxt[slot]), active=True)
                    emitted += 1
            if emitted < len(rec.rows):
                _OVERRUN_ROWS.inc(len(rec.rows) - emitted)
            self._note_decode_round(emitted, len(rec.rows), dt)
            _BUSY.set(float(sum(r is not None for r in self._active)))
        _ROUNDS.inc(kind="decode")
        self._round_kind = "decode"
        self._observe_phase("fence", t3 - t2)
        self._observe_phase("emit", self._clock.now() - t3)

    # -- speculative decode (serving/spec.py) ------------------------------

    def _spec_plan(self) -> Optional[dict]:
        """Per-slot proposals for this round, or None for a normal
        1-token step. None whenever speculation is off, no greedy row has
        a usable draft, or any ACTIVE row sits too close to the cache
        edge (the fixed-width ``[B, gamma+1]`` write would clamp/wrap
        past ``max_seq_len`` and corrupt real positions — those rows are
        about to finish anyway, so the whole batch takes plain steps).

        The speculated positions must be block-backed: a proposal may
        only run as far as this row's allocated pages reach (writes past
        them land on the scratch block and could never be accepted).
        Growth here is best-effort — NoFreeBlocks truncates the draft
        instead of preempting anyone; speculation is an optimization and
        must never cost a live request its blocks."""
        if self._proposer is None:
            return None
        width = self.spec_tokens + 1
        plan: dict = {}
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            if int(self._pos[slot]) + width > self.cfg.max_seq_len:
                return None
            if not self._row_greedy(req):
                continue
            remaining = req.max_new_tokens - len(req.tokens)
            if remaining <= 1:
                continue   # one more token finishes it: nothing to gain
            p = self._propose_for(slot, req)
            p = p[:min(self.spec_tokens, remaining - 1)]
            if p:
                plan[slot] = [int(t) for t in p]
        for slot in list(plan):
            want = len(plan[slot])
            covered = self._grow_for_spec(slot, want)
            if covered < want:
                # the NoFreeBlocks backstop fired — count it: a pool
                # sized too tight silently degrades speculation toward
                # 1-token steps, and until this counter existed the only
                # symptom was a mysteriously low tokens-per-step
                self.spec_draft_truncated += 1
                _SPEC_TRUNCATED.inc()
            plan[slot] = plan[slot][:covered]
            if not plan[slot]:
                del plan[slot]
        return plan or None

    def _propose_for(self, slot: int, req: Request) -> List[int]:
        """Draft for one row, through its per-slot incremental index
        when the proposer offers one (injected test proposers may not —
        they get the plain stateless call)."""
        hist = req.prompt + req.tokens
        index_fn = getattr(self._proposer, "index", None)
        if index_fn is None:
            return self._proposer.propose(hist)
        idx = self._spec_index[slot]
        if idx is None or len(idx) > len(hist):
            # no index yet (or a stale one): the O(history) build is
            # overlap-window work (_drain_side_work), never plan-phase
            # work — this round simply doesn't speculate for the row.
            # Output-invisible: proposals only change which rounds get
            # to speculate, never which tokens come out
            self._spec_index[slot] = None
            return []
        if len(idx) < len(hist):
            idx.extend(hist[len(idx):])
        return idx.propose()

    def _decode_verify(self, plan: dict, t_plan: float,
                       drained: Optional[str] = None) -> bool:
        """One speculative round: a single fused verify program scores
        ``[slots, gamma+1]`` positions (last emitted token + each row's
        padded proposal), computes acceptance ON DEVICE (``spec.accept``)
        and returns one packed ``[slots, gamma+2]`` emit matrix — the
        round's only host transfer. Greedy rows emit 1..gamma+1 tokens;
        sampled/no-draft rows emit exactly one, drawn from the same
        position-0 logits (and the same single rng split) a plain step
        would have produced. The cache index comes back already rolled
        over the rejected tail (``new_pos = pos + count``) — K/V written
        at rejected positions stays in place as garbage beyond the
        rewound index, invisible to every mask and overwritten before it
        could surface. Never left in flight: the next round's proposals
        read this round's tokens (``drained`` labels the loop thread's
        count of that)."""
        t0 = self._clock.now()
        gamma = self.spec_tokens
        self._round_seq += 1
        if drained is not None:
            _ROUND_DRAINS.inc(reason=drained)
        with trace.span(trace.ENGINE_DECODE_DISPATCH):
            if trace.ON:
                trace.note(uploads=self._stale_inputs(),
                           round=self._round_seq, overlapped=False)
            prop = np.zeros((self.slots, gamma), np.int32)
            plen = np.zeros((self.slots,), np.int32)
            for slot, p in plan.items():
                prop[slot, :len(p)] = p
                plen[slot] = len(p)
            (cur, pos, mask), tables = self._round_inputs()
            self._dispatches.inc(path=self._path_of(gamma + 1))
            with self._first("verify", trace.SITE_VERIFY, phase="dispatch"):
                (self._payload, packed, self._cur_dev, self._pos_dev,
                 self._rng) = self._verify_step(
                    self._payload, self.params, cur, jnp.asarray(prop),
                    jnp.asarray(plen), pos, tables, mask, self._rng)
        t1 = self._clock.now()
        with trace.span(trace.ENGINE_DECODE_OVERLAP):
            self._overlap_window()
        t2 = self._clock.now()
        with trace.span(trace.ENGINE_DECODE_FENCE):
            packed = self._fetch(packed)   # the round's ONE fence
            if trace.ON:
                trace.note(bytes=packed.nbytes, round=self._round_seq)
        t3 = self._fenced_at = self._clock.now()
        dt = t3 - t0
        with trace.span(trace.ENGINE_DECODE_EMIT):
            _STEP.observe(dt)
            self._verify_emit(plan, packed, gamma, dt)
        t4 = self._clock.now()
        # observed AFTER the fence (the device is already idle — these
        # lock-taking observes never sit between dispatch and transfer)
        _ROUNDS.inc(kind="verify")
        self._round_kind = "verify"
        for phase, took in (("plan", t0 - t_plan), ("dispatch", t1 - t0),
                            ("overlap", t2 - t1), ("fence", t3 - t2),
                            ("emit", t4 - t3)):
            self._observe_phase(phase, took)
        return True

    def _verify_emit(self, plan: dict, packed, gamma: int,
                     dt: float) -> None:
        """The verify round after its fence: unpack, advance, emit."""
        # unpack per-row emit lists from the packed matrix (host-side
        # indexing only — no further device traffic)
        emit: dict = {}
        prop_total = acc_total = 0
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            n = int(packed[slot, gamma + 1])
            emit[slot] = [int(t) for t in packed[slot, :n]]
            p = plan.get(slot)
            if p is not None:
                self.spec_proposed += len(p)
                self.spec_accepted += n - 1
                prop_total += len(p)
                acc_total += n - 1
        if prop_total:
            _SPEC_PROPOSED.inc(prop_total)
        if acc_total:
            _SPEC_ACCEPTED.inc(acc_total)

        # advance positions BEFORE emitting: _free (via _emit on
        # EOS/limit) resets freed rows on top of this, and the rollback
        # releases blocks past the new lengths
        for slot in emit:
            self._pos[slot] += len(emit[slot])
        self._post_verify_rollback()

        emitted = rows = 0
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            rows += 1
            for tok in emit[slot]:
                # EOS (or the length limit) inside the accepted window:
                # _emit finished the request; the rest is discarded
                if req.done:
                    break
                self._emit(slot, req, int(tok), active=True)
                emitted += 1
        self.spec_steps += 1
        _SPEC_STEPS.inc()
        self._note_decode_round(emitted, rows, dt)
        _BUSY.set(float(sum(r is not None for r in self._active)))

    def _observe_phase(self, phase: str, dt: float) -> None:
        """One phase of this round into the histogram; a slow one is
        also counted and logged, so a stalled loop names the phase it
        stalled in where no trace was being taken."""
        _ROUND_PHASE.observe(dt, phase=phase)
        if dt > _SLOW_PHASE_S:
            _SLOW_PHASE.inc(phase=phase)
            decoded = self._round_kind is not None
            # a slow phase that held a build says so: it was no stall
            built = "".join(
                f"; built {b.describe()}" for b in self._round_builds
                if b.attrs.get("phase") == phase)
            _LOG.warning(
                "engine loop: phase %s took %.3f s (round kind=%s rows=%d)%s",
                phase, dt, self._round_kind if decoded else "no_decode",
                self._round_rows if decoded else 0, built)

    def _note_decode_round(self, emitted: int, rows: int, dt: float) -> None:
        self._flush_token_accounting()
        self.decode_steps += 1
        self.decode_rows += rows
        self.decode_tokens += emitted
        self._round_rows, self._round_emitted = rows, emitted
        _TPS.set(emitted / dt if dt > 0 else 0.0)
        if self.spec_tokens:
            if self.spec_proposed:
                _SPEC_RATE.set(self.spec_accepted / self.spec_proposed)
            # per ROW-step: 1.0 = every row advanced one token (no win);
            # the ceiling is spec_tokens + 1
            _SPEC_TPS.set(self.decode_tokens / self.decode_rows)

    def _emit(self, slot: int, req: Request, token: int, *,
              active: bool) -> None:
        """Record one generated token; finish + free the slot on EOS or
        length limit. ``active`` distinguishes a slot-resident request
        (needs freeing) from one still mid-insertion."""
        req.tokens.append(token)
        sink = req.token_sink
        if sink is not None:
            # token streaming (channels/token_stream): deliver while the
            # request is still decoding. Guarded — a consumer bug must
            # cost the consumer its stream, never the engine its loop
            try:
                sink(req)
            except Exception:  # noqa: BLE001 — stream-side failure
                _LOG.exception("token sink failed for %s; detaching",
                               req.id)
                req.token_sink = None
        self._tokens_out += 1
        # metric counters are flushed once per round (side-queue
        # accounting — see _flush_token_accounting), not per token
        self._round_tokens[req.tenant] = \
            self._round_tokens.get(req.tenant, 0) + 1
        hit_eos = self.eos_token is not None and token == self.eos_token
        if hit_eos or len(req.tokens) >= req.max_new_tokens:
            self._finished += 1
            _REQUESTS.inc(status="ok")
            TENANT_REQUESTS.inc(tenant=req.tenant, status="ok")
            self._tenant_count(req.tenant, "requests_finished")
            if active:
                # free BEFORE finish(): the waiter wakes on finish and
                # must observe the slot/blocks already released
                self._free(slot)
            req.finish()
        elif active:
            self._cur[slot] = token

    def _free(self, slot: int) -> None:
        """Host-mirror reset only: the freed row's DEVICE state (token,
        position, greedy-mask bit) is left stale on purpose — idle rows
        are garbage-tolerant (writes land on the scratch block, outputs
        are never read), and the re-admission that makes the slot matter
        again writes all three (``_prompt_done``). The slot's blocks go
        back to the pool (or stay cached in the tree)."""
        self._active[slot] = None
        self._cur[slot] = 0
        self._pos[slot] = 0
        self._spec_index[slot] = None
        blocks = self._slot_blocks[slot]
        self._slot_blocks[slot] = []
        self._tables[slot, :] = 0
        if self._live is not None:
            self._live[slot] = 0
        self._pt_dev = None
        self._admit_seq[slot] = 0
        self.kv.release(blocks)
        if self._win is not None:
            self._win.release(self._win_rows[slot])
            self._win_tables[slot, :] = 0
        rec = self._inflight
        if rec is not None and not any(
                self._active[at] is req for at, req in rec.rows):
            # no row of the round in flight survives: that round would not
            # have run had the finish been known in time, so the rng goes
            # on from the key it had before it (the blocks released above
            # stay untouched by it all the same: whoever takes them is
            # queued on the device behind that round)
            self._rng = rec.rng

    # -- lifecycle ---------------------------------------------------------

    @_setup_phase(trace.ENGINE_WARMUP, "warmup")
    def warmup(self) -> None:
        """AOT-compile the decode (and, with speculation on, verify)
        programs before the first request: jit compiles lazily, so
        without this the first client pays the whole compile on its TTFT.
        Compiled via ``.lower(...).compile()`` from ABSTRACT cache avals
        — no scratch cache is ever materialized, so warming an engine
        whose KV pool is sized to fill HBM cannot OOM the boot. The
        in-process HLO-keyed compilation cache (and the persistent one
        serve.py enables) then makes the first real call's "compile" a
        lookup.

        No prefill width is compiled here, by decision: a width's trace
        and lowering are Python over the unrolled layers and cost seconds
        whatever the compile cache holds, and a deployment's prompts may
        never reach some of the six. ``prefill_step`` compiles a width
        when the first request reaches it; a server that wants that paid
        before it opens sends one request a width (docs/serving.md)."""
        # a gang's avals carry the REAL shardings (the pool's placement,
        # replicated round inputs), so the warmed executable is the one
        # the first request dispatches
        aval = self._programs.aval
        payload = [aval(leaf.shape, leaf.dtype, leaf.sharding)
                   for leaf in self._payload]
        vec = aval((self.slots,), jnp.int32)
        mask = aval((self.slots,), jnp.bool_)
        rng = aval(self._rng.shape, self._rng.dtype)
        # with no pool the live rows arrive where a page table would
        pt = aval((self.slots, self._pages_per_seq), jnp.int32) \
            if self._pooled else vec
        if self._win is not None:
            pt = (pt, pt)

        def compiled(step, *mids):
            # ``mids``: the step's own args between ``params`` and the
            # page table
            step.lower(payload, self.params, *mids, pt, mask, rng).compile()

        with self._build(trace.SITE_DECODE):
            compiled(self._decode_step, vec, vec)
        with self._build(trace.SITE_AUX, "activate"):
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            self._activate.lower(vec, vec, aval((1,), jnp.int32), scalar,
                                 scalar).compile()
        if self._has_state:
            with self._build(trace.SITE_SPLICE):
                self.prefill.compile_splice(payload)
        if self.spec_tokens > 0:
            prop = aval((self.slots, self.spec_tokens), jnp.int32)
            with self._build(trace.SITE_VERIFY):
                compiled(self._verify_step, vec, prop, vec, vec)

    # -- a program's build has a site and a name ---------------------------

    def _first(self, key, site: str, **attrs):
        """A build context around the first call of one of the engine's
        programs (``key``: a prefill width, or a name), where jit traces,
        lowers and compiles it or reads it from the cache; nothing at all
        (one set lookup) from then on."""
        if key in self._built:
            return trace.NOOP
        return self._build(site, key, **attrs)

    @contextlib.contextmanager
    def _build(self, site: str, key=None, **attrs):
        """``trace.building(site)`` with what the engine knows: ``warm``
        (inside the constructor or ``warmup()``) and ``rows``, the rows
        in decode that wait for it (0 in set-up). A build after set-up is
        kept for the round's slow-phase line, and counted where rows
        waited."""
        warm = self._setting_up
        rows = 0 if warm else sum(r is not None for r in self._active)
        with trace.building(site, rows=rows, warm=warm, **attrs) as b:
            yield b
        if key is not None:
            self._built.add(key)
        if b.built and not warm:
            self._round_builds.append(b)
            if rows:
                _SERVING_BUILDS.inc(site=site)
                _BUILD_STALLED.inc(b.seconds * rows)

    @property
    def closed(self) -> bool:
        """True once the engine refuses admissions — clean shutdown OR a
        dead loop. The gateway's health checks read this to retire a
        replica whose engine died under it."""
        return self._closed

    def start(self) -> "PagedInferenceEngine":
        """Run the engine loop in a daemon thread (the serving-front mode)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            # so that a profile's host plane shows the loop on a line of
            # its own, where its ``engine.*`` annotations name idle gaps
            trace.name_thread(f"lzy-engine-{next(_loop_ids)}")
            # this thread's step() leaves a decode round in flight
            self._loop_ident = threading.get_ident()
            try:
                while not self._stop.is_set():
                    if not self.step():
                        # all slots drained and the queue is empty: park
                        # until the next submit instead of spinning the
                        # device
                        t0 = self._clock.now()
                        with trace.span(trace.ENGINE_PARK):
                            self._clock.wait(self.queue.work_available,
                                             timeout=0.5)
                            self.queue.work_available.clear()
                        _ROUND_PHASE.observe(self._clock.now() - t0,
                                             phase="park")
                # stopped with a round in flight: its tokens are delivered
                # before close() sheds the rows
                self._drain("stop")
            except BaseException:  # noqa: BLE001 — engine-fatal
                # a step()-level failure (device OOM, a poisoned compile) is
                # engine-fatal, not request-scoped: without this the daemon
                # thread would die silently while the RPC surface stays up —
                # every in-flight waiter burning its full timeout and every
                # future submit queueing forever. Fail loudly: log, fail all
                # outstanding requests, and refuse new admissions.
                _LOG.exception("inference engine loop died; failing all "
                               "outstanding requests")
                self._closed = True
                # a round in flight is dropped, not fetched: the device may
                # be what died, and a waiter must not wait on it. Its rows
                # fail below with the tokens they had
                self._inflight = None
                self._first_pending = []
                self._fail_outstanding("error", "engine loop died")
            finally:
                self._loop_ident = None

        self._thread = threading.Thread(
            target=loop, name="inference-engine", daemon=True)
        self._thread.start()
        return self

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown, phase one: stop admitting, let in-flight
        rows finish, then close. Returns True if everything finished
        inside ``timeout_s`` (False: close() failed the stragglers with
        the usual shutdown error). Safe on a synchronous engine only if
        something else still calls step(); the serving-front mode (loop
        thread) drains itself."""
        self._draining = True
        self.queue.work_available.set()     # wake a parked loop
        deadline = self._clock.now() + timeout_s
        drained = False
        while self._clock.now() < deadline:
            if self._closed:
                break           # the loop died; close() cleans up
            with self._outstanding_lock:
                self._outstanding = {r for r in self._outstanding
                                     if not r.done}
                busy = bool(self._outstanding)
            if not busy:
                drained = True
                break
            self._clock.sleep(0.01)
        self.close()
        return drained

    def close(self, timeout: float = 10.0) -> None:
        self._closed = True      # refuse admissions before the loop stops
        self._stop.set()
        self.queue.work_available.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        # staged prefills release their resources (blocks back to the
        # pool); their requests are failed by the untracked sweep
        self.prefill.close()
        self._fail_outstanding("shed", "engine shutting down")
        if self._kv_quant is not None:
            self._note_quant_resident(0)
        # the loop thread was joined above: the tier is closed, the waiters
        # woken and the parked pins released single-threaded by construction
        self.kv_io.close()

    def _fail_outstanding(self, status: str, error: str) -> None:
        """Every request the engine still holds ends with ``error``: the
        queue's, the slots', and those in NEITHER structure, the
        mid-prefill window (popped, not yet slot-resident: without this
        sweep a waiter would burn its whole timeout; found by the chaos
        soak, seed 23). Only callable once the loop is stopped/dead:
        nothing else can finish them concurrently."""
        held = self.queue.drain() + [r for r in self._active
                                     if r is not None]
        self._active[:] = [None] * self.slots
        with self._outstanding_lock:
            held += [r for r in self._outstanding if not r.done]
            self._outstanding.clear()
        for req in held:
            if not req.done:
                _REQUESTS.inc(status=status)
                req.finish(error=error)
        _BUSY.set(0.0)

    def stats(self) -> EngineStats:
        ks = self.kv.stats()
        if self._kv_quant is not None:
            # blocks currently holding int8 data: everything usable that
            # is not on the free list (slot-resident + radix-cached)
            self._note_quant_resident(ks.blocks_total - ks.blocks_free)
        extra = self.kv_io.stats()
        if self._win is not None:
            extra.update(
                kv_window_blocks_total=self._win.pool.n_blocks - 1,
                kv_window_blocks_free=self._win.pool.free_count(),
                kv_window_blocks_live=self._win.live(),
                kv_window_pages_released=self._win.released)
        if self.spec_tokens > 0:
            rate = (self.spec_accepted / self.spec_proposed
                    if self.spec_proposed else 0.0)
            tps = (self.decode_tokens / self.decode_rows
                   if self.decode_rows else 0.0)
            extra.update(
                spec_tokens=self.spec_tokens,
                spec_proposed_tokens=self.spec_proposed,
                spec_accepted_tokens=self.spec_accepted,
                spec_acceptance_rate=round(rate, 4),
                spec_verify_steps=self.spec_steps,
                spec_tokens_per_step=round(tps, 4),
                spec_draft_truncated=self.spec_draft_truncated)
        return EngineStats(
            slots=self.slots,
            busy=sum(r is not None for r in self._active),
            queue_depth=self.queue.depth(),
            requests_finished=self._finished,
            tokens_generated=self._tokens_out,
            requests_cancelled=self._cancelled,
            kv_page_size=self._page,
            kv_blocks_total=ks.blocks_total,
            kv_blocks_free=ks.blocks_free,
            kv_blocks_cached=ks.blocks_cached,
            kv_evictions=ks.evictions,
            prefix_hit_rate=round(ks.hit_rate, 4),
            prefill_tokens_saved=ks.prefill_tokens_saved,
            kernel_path=self.kernel_path,
            kv_quant=self._kv_quant,
            kv_token_bytes=self._kv_token_bytes,
            **extra)

    def stats_by_tenant(self) -> dict:
        """Per-tenant terminal counters plus live queue depth — the
        scoped half of the stats surface (a tenant sees its own row, the
        operator sees them all; the gateway fleet aggregates these
        across replicas), with the KV blocks each tenant holds."""
        with self._tenant_counts_lock:
            out = {t: dict(d) for t, d in self._tenant_counts.items()}
        for tenant in self.queue.tenants():
            row = out.setdefault(tenant, dict(TENANT_ROW))
            row["queue_depth"] = self.queue.depth_of(tenant)
        for row in out.values():
            row.setdefault("queue_depth", 0)
        tenants = set(out)
        tenants.update(r.tenant for r in self._active if r is not None)
        tenants.update(j.req.tenant for j in self.prefill.jobs)
        for tenant in tenants:
            held = self._tenant_block_usage(tenant)
            row = out.setdefault(tenant, dict(TENANT_ROW, queue_depth=0))
            row["kv_blocks"] = held
            TENANT_KV_BLOCKS.set(float(held), tenant=tenant)
        return out

    def _refuse_for_leaves(self, error, why: dict, tier_asked: bool) -> None:
        """A pool whose leaves are not all ``paged`` (``models/serving.py``;
        ``serving/kv_io.py`` has the error and the reasons by leaf kind):
        what shares a prefix is turned off, what moves or rewinds cache by
        index and pages is refused, by name."""
        if self.spec_tokens > 0:
            raise error(f"speculative decoding (spec_tokens="
                        f"{self.spec_tokens}): {why['spec']}")
        if tier_asked:
            raise error(f"the tiered KV cache (kv_host_tier_bytes / "
                        f"kv_storage_tier / kv_tier): {why['tier']}")
        # a matched prefix would skip prefill for tokens whose state rows
        # or window pages nobody kept
        self.kv.reuse = False
        _LOG.info(
            "%s %s: the radix prefix cache is off (every match is 0 "
            "tokens, finished prompts are not inserted)",
            type(self._model).__name__, why["reuse"])

    # -- construction --------------------------------------------------------

    def _path_of(self, t: int) -> str:
        """The read path of a program with ``t`` query positions a row:
        the label of its ``lzy_kernel_dispatch_total`` count."""
        return self.cfg.read_path(self._paged_kernel, t=t,
                                  kv_quant=self._kv_quant)

    def _build_decode_path(self, base: Any) -> None:
        self._programs = build = self._program_build()
        slots, pages = self.slots, self._pages_per_seq
        # one module for decode rounds and batch-1 prefill: prefill reuses
        # the SAME pool arrays with a batch-1 index (and, where the model
        # has them, the job's own batch-1 state rows)
        second = {} if self._win is None \
            else {"window_pages": self._win.pool.n_blocks}
        self._model = base.paged_model(
            page_size=self._page, kv_pages=self._kv_blocks,
            kernel=self._paged_kernel, kv_quant=self._kv_quant, **second,
            **build.model_kw)
        dummy_pt = jnp.zeros((slots, pages), jnp.int32)
        tables = {"page_table": dummy_pt} if self._win is None \
            else {"page_table": dummy_pt, "window_table": dummy_pt}
        self._adopt_cache(init_cache(lambda: self._model.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32),
            **tables)))
        self._build_steps()

    def _program_build(self) -> ProgramBuild:
        """A gang says what its mesh changes (serving/sharded/engine.py)."""
        return ProgramBuild()

    def _build_steps(self) -> None:
        """The jitted decode and verify programs over ``self._model``
        (``serving/prefill.py`` builds the prefill's, from the same
        ``ProgramBuild``). A model with state leaves, or one that counts
        (``STATS``), is also told which positions are real (``valid_len``):
        an idle slot (zeroed page table: block 0 is the scratch block no
        row owns) has none."""
        build = self._programs
        self._stat_counters = tuple(type(self._model).STATS)
        has_stats = bool(self._stat_counters)
        tells_real, pooled = self._tells_real, self._pooled
        mutable = ["cache", "stats"] if has_stats else ["cache"]

        def decode_step(payload, params, cur, pos, page_table,
                        greedy_mask, rng):
            cache = self._assemble_cache(payload, pos)
            # a model with window leaves is handed both kinds' tables
            tables = {"page_table": page_table}
            if isinstance(page_table, tuple):
                page_table, window_table = page_table
                tables = {"page_table": page_table,
                          "window_table": window_table}
            if not pooled:
                # no pool, no table: what arrives in its place is the rows
                # that are live (``_live``), 1 or 0 a slot
                tables, real = {}, {"valid_len": page_table}
            else:
                real = {"valid_len":
                        (page_table[:, 0] != 0).astype(jnp.int32)} \
                    if tells_real else {}
            logits, updated = self._model.apply(
                {"params": params, "cache": cache}, cur[:, None],
                mutable=mutable, **tables, **real, **build.apply_kw)
            with trace.part(trace.SAMPLE):
                nxt, rng = self._pick_next(logits[:, -1], greedy_mask, rng)
            payload, new_pos = self._split_cache(updated["cache"])
            if not has_stats:
                return payload, new_pos, nxt, rng
            # the layers' counts ride behind the tokens in the one array
            # the round's fence fetches
            with trace.part(trace.SAMPLE):
                counts = sum(jax.tree_util.tree_leaves(updated["stats"]))
                return payload, new_pos, nxt, rng, jnp.concatenate(
                    [nxt, counts.astype(jnp.int32)])

        self._decode_step = build.jit(decode_step, donate=(0,))

        def activate_slot(cur, pos, first, slot, at):
            """A finished prompt's row enters the round's inputs where they
            live: its first token (``[1]``, as ``prefill_step`` picked it)
            and the position that token will be written at."""
            return cur.at[slot].set(first[0]), pos.at[slot].set(at)

        # nothing is donated: with a round in flight ``cur`` is the very
        # array that round's fence will fetch, and ``[slots]`` int32 are not
        # worth a buffer's reuse. A gang's come out replicated, as its
        # decode program was warmed to take them
        self._activate = build.jit(
            activate_slot,
            **({} if build.replicated is None else
               {"out_shardings": (build.replicated, build.replicated)}))

        def verify_step(payload, params, cur, prop, prop_len, pos,
                        page_table, greedy_mask, rng):
            # the [B, gamma+1] chunk scatters through the page table
            # (positions past a row's allocated blocks land on the
            # scratch page — garbage nobody can accept)
            cache = self._assemble_cache(payload, pos)
            toks = jnp.concatenate([cur[:, None], prop], axis=1)
            logits, updated = self._model.apply(
                {"params": params, "cache": cache}, toks,
                page_table=page_table, mutable=["cache"], **build.apply_kw)
            with trace.part(trace.SAMPLE):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt, rng = self._pick_next(logits[:, 0], greedy_mask, rng)
            payload, _ = self._split_cache(updated["cache"])
            with trace.part(trace.SAMPLE):
                packed, new_cur, new_pos = accept(prop, prop_len, greedy,
                                                  nxt, pos)
            return payload, packed, new_cur, new_pos, rng

        self._verify_step = build.jit(verify_step, donate=(0,))

    # -- admission -----------------------------------------------------------

    def _blocks_for(self, n_tokens: int) -> int:
        """Blocks of the paged pool that ``n_tokens`` cache positions
        take: none where the model keeps no pool."""
        return blocks_for(n_tokens, self._page) if self._pooled else 0

    def _tenant_quota(self, tenant: str) -> Optional[int]:
        if self.tenants is None:
            return None
        return self.tenants.resolve(tenant).kv_block_quota

    def _tenant_block_usage(self, tenant: str) -> int:
        """Blocks this tenant currently pins on THIS replica: resident
        slots plus staged (mid-prefill) jobs. Quotas are per-replica —
        each replica owns its own pool."""
        held = 0
        for slot, req in enumerate(self._active):
            if req is not None and req.tenant == tenant:
                held += len(self._slot_blocks[slot])
        return held + sum(len(job.table) for job in self.prefill.jobs
                          if job.req.tenant == tenant)

    def _can_admit(self, req: Request) -> bool:
        """Admission is gated on the BLOCK budget, not the slot count: the
        whole prompt must be coverable right now (matched prefix counted
        conservatively — it may or may not already be pinned by another
        request). Decode growth beyond the prompt is overcommitted and
        backstopped by eviction + youngest-preemption."""
        # drain queued KV imports at the admission gate: a submit can
        # land mid-step (after the top-of-loop drain but before _admit
        # pops it), and its staged import must be resident before the
        # prefill's prefix match runs. No-op when the queue is empty.
        self.kv_io.apply_imports()
        need = self._blocks_for(len(req.prompt))
        # parked tool-gap chains yield to live admissions: shed them
        # (soonest expiry first) before making anyone wait
        self.kv_io.shed_parked(need)
        if self._win is not None and self._win.available() \
                < self._win.need(len(req.prompt)):
            return False       # both kinds of page must hold the prompt
        return self.kv.available() >= need

    def _admit_verdict(self, req: Request) -> str:
        """``"admit"`` (pop and stage), ``"wait"`` (global capacity —
        the whole queue waits so big prompts are never starved by
        smaller late arrivals), or ``"skip"`` (a *tenant-scoped* limit:
        this tenant's head steps aside without blocking other tenants'
        admissible heads — one tenant's quota must never become another
        tenant's latency). Tenant KV quota first (a tenant AT its quota
        is skipped: its blocks free as its own requests finish), then
        the global pool budget (a genuine capacity wait)."""
        quota = self._tenant_quota(req.tenant)
        if quota is not None:
            need = self._blocks_for(len(req.prompt))
            if self._tenant_block_usage(req.tenant) + need > quota:
                return "skip"
        return "admit" if self._can_admit(req) else "wait"

    # -- KV I/O (serving/kv_io.py): the replica's surface ----------------------
    # what the gateway, the fleet and the tests call on a replica's engine;
    # each is the object's method or counter of (nearly) the same name

    def park_chain(self, key: str, tokens: Sequence[int],
                   ttl_s: float = 30.0, timeout_s: float = 5.0) -> bool:
        return self.kv_io.park_chain(key, tokens, ttl_s, timeout_s)

    def unpark_chain(self, key: str, timeout_s: float = 5.0) -> bool:
        return self.kv_io.unpark_chain(key, timeout_s)

    def request_kv_export(self, tokens: Sequence[int],
                          timeout_s: float = 5.0):
        return self.kv_io.request_kv_export(tokens, timeout_s)

    def queue_kv_import(self, export) -> None:
        self.kv_io.queue_kv_import(export)

    def _note_kv_import(self, outcome: str, blocks: int) -> None:
        """Metrics hook — the disagg ``DecodeEngine`` counts its
        ``lzy_disagg_kv_imports_total`` family here."""

    def kv_tier_match_len(self, tokens: Sequence[int]) -> int:
        return self.kv_io.tier_match_len(tokens)

    def kv_chains(self, limit: int = 4096) -> dict:
        return self.kv_io.kv_chains(limit)

    kv_tier = property(lambda self: self.kv_io.tier)
    prefill_rounds = property(lambda self: self.prefill.rounds)
    kv_tier_gather_ops = property(lambda self: self.kv_io.gather_ops)
    kv_tier_gather_rounds = property(lambda self: self.kv_io.gather_rounds)
    kv_imports = property(lambda self: self.kv_io.imports)
    kv_import_blocks = property(lambda self: self.kv_io.import_blocks)
    # read by the fleet aggregate; 0 without a tier
    kv_tier_demotions = _kv_io_stat("kv_tier_demotions")
    kv_tier_promotions = _kv_io_stat("kv_tier_promotions")
    kv_tier_dropped = _kv_io_stat("kv_tier_dropped")

    # -- decode --------------------------------------------------------------

    def _grow_for_decode(self) -> None:
        """Make sure every active slot has a block for its next write
        position; under a squeeze, evict cached blocks (allocate does)
        and as a last resort preempt the youngest active request — never
        a block some other in-flight request references."""
        if not self._pooled:
            return              # a row grows no page: its state is its slot's
        # positions moved at the last dispatch, so this needs no token of
        # a round in flight; a row that ends with that token grows nothing
        # (its over-run write lands on the scratch block past its blocks)
        riding = self._riding()
        for slot, req in enumerate(self._active):
            if req is None or slot in riding:
                continue
            pidx = int(self._pos[slot]) // self._page
            # ``is req``: a drain below may finish the row itself
            while self._active[slot] is req \
                    and pidx >= len(self._slot_blocks[slot]):
                try:
                    block = self.kv.allocate(1)[0]
                except NoFreeBlocks:
                    if self.kv_io.shed_parked(1):
                        # parked chains are sacrificed before ANY
                        # resident request: then retry
                        continue
                    if self._drain("squeeze"):
                        # the victim is owed the token in flight, and a
                        # row it finishes may free what is needed
                        continue
                    victim = self._preempt_youngest()
                    if victim == slot:
                        break     # preempted ourselves; slot is free now
                    continue
                self._slot_blocks[slot].append(block)
                self._tables[slot, len(self._slot_blocks[slot]) - 1] = block
                self._pt_dev = None
            # the second kind of page: this round writes position ``pos``,
            # and the oldest position anything dispatched still reads is
            # the round in flight's, ``pos - 1 - window + 1``
            while self._win is not None and self._active[slot] is req:
                pos, row = int(self._pos[slot]), self._win_rows[slot]
                try:
                    if self._win.cover(row, pos - self._win.window, pos + 1):
                        self._win_tables[slot] = row.table
                        self._pt_dev = None
                    break
                except NoFreeBlocks:
                    if self._drain("squeeze"):
                        continue
                    if self._preempt_youngest() == slot:
                        break

    def _preempt_youngest(self) -> int:
        """Fail the most recently admitted active request (its waiter gets
        a clean ``preempted`` error) and free its blocks; protecting older
        requests first matches their larger sunk decode cost."""
        victim = max(
            (s for s, r in enumerate(self._active) if r is not None),
            key=lambda s: self._admit_seq[s])
        req = self._active[victim]
        _LOG.warning("kv block pool exhausted: preempting %s", req.id)
        if trace.ON:
            trace.event(trace.ENGINE_PREEMPT, request=req.id,
                        blocks=len(self._slot_blocks[victim]))
        _REQUESTS.inc(status="preempted")
        TENANT_REQUESTS.inc(tenant=req.tenant, status="preempted")
        self._tenant_count(req.tenant, "requests_preempted")
        self._free(victim)     # free before finish (see _reap_cancelled)
        req.finish(error="preempted: kv block pool exhausted")
        return victim

    def _page_table_dev(self):
        """Device mirror of ``_tables``, uploaded once and reused until
        a table mutation dirties it — the per-round ``jnp.asarray`` of
        an unchanged page table was a textbook re-upload hot loop.
        An explicit copy (``ProgramBuild.upload``): asarray would
        zero-copy the live ``_tables`` buffer and later host writes would
        mutate the device view mid-flight."""
        if self._pt_dev is None:
            up = self._programs.upload
            if not self._pooled:
                self._pt_dev = up(self._live)       # no table: live rows
            elif self._win is None:
                self._pt_dev = up(self._tables)
            else:
                self._pt_dev = (up(self._tables), up(self._win_tables))
        return self._pt_dev

    def _count_dispatch(self, t: int) -> None:
        """One program over ``t`` positions a row was dispatched: its
        attention read's path, and the paths of the model's own kernels."""
        paths = self._dispatch_paths.get(t)
        if paths is None:
            paths = self._dispatch_paths[t] = (
                self._path_of(t),) + tuple(self._model.cfg.kernel_paths(t))
        for path in paths:
            self._dispatches.inc(path=path)

    def _round_inputs(self):
        """``(cur, pos, mask)`` and the page table, on the device; their
        first uploads' small programs are ``engine.aux`` builds."""
        with self._first("inputs", trace.SITE_AUX, phase="dispatch"):
            return self._device_inputs(), self._page_table_dev()

    def _note_model_stats(self, fetched: np.ndarray,
                          rec: _InFlight) -> np.ndarray:
        """Split what the fence fetched into the ``[slots]`` tokens and
        the model's counts, and add the counts to their counters."""
        if not self._stat_counters:
            return fetched
        counts = [int(n) for n in fetched[self.slots:]]
        for counter, n in zip(self._stat_counters, counts):
            counter.inc(n)
        if trace.ON:
            # on the emit span of the round that was FETCHED: the rows it
            # was dispatched with (an over-run row's work was done, and is
            # in the counts) and each count under its counter's name
            trace.note(rows=len(rec.rows),
                       model_stats={c.name: n for c, n in zip(
                           self._stat_counters, counts)})
        return fetched[:self.slots]

    # -- speculative decode over the block pool -------------------------------

    def _grow_for_spec(self, slot: int, want: int) -> int:
        """Allocate blocks so positions ``pos .. pos+want`` are real
        (``pos`` itself is already covered by ``_grow_for_decode``);
        returns how many proposal tokens are actually coverable. Only
        FREE-LIST blocks back a draft — ``allocate`` under a dry free
        list would evict LRU cached prefix blocks, and a draft that gets
        rejected would have flushed the prefix cache for nothing (and
        re-flushed it every verify round on low-acceptance traffic);
        truncating the draft instead costs at most the speculation win."""
        page, pos = self._page, int(self._pos[slot])
        last = (pos + want) // page
        while len(self._slot_blocks[slot]) <= last:
            if self.kv.pool.free_count() == 0:
                break      # never evict cached blocks for a draft
            try:
                block = self.kv.allocate(1)[0]
            except NoFreeBlocks:
                break
            self._slot_blocks[slot].append(block)
            self._tables[slot, len(self._slot_blocks[slot]) - 1] = block
            self._pt_dev = None
        covered = len(self._slot_blocks[slot]) * page
        return min(want, max(0, covered - pos - 1))

    def _post_verify_rollback(self) -> None:
        """Return growth blocks that became WHOLLY rejected to the pool.
        Only blocks past the rewound length can qualify, and those are
        always decode-growth allocations private to this slot (prompt
        blocks — including radix-shared, refcounted ones — all sit below
        ``_pos``, which never rewinds into the prompt), so resident
        prefix blocks and the radix tree are untouched by construction:
        a rejected speculation is invisible to future prefix matches.
        ``_pos + 1``, not ``_pos``: the block covering the NEXT write
        position stays — releasing it on a page boundary would only make
        ``_grow_for_decode`` re-allocate it next round, possibly evicting
        a cached block for nothing."""
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            keep = blocks_for(int(self._pos[slot]) + 1, self._page)
            blocks = self._slot_blocks[slot]
            if len(blocks) > keep:
                tail = blocks[keep:]
                del blocks[keep:]
                self._tables[slot, keep:] = 0
                self._pt_dev = None
                self.kv.release(tail)

    def _note_quant_resident(self, resident: int) -> None:
        with self._quant_resident_lock:
            if self._closed:
                # a stats() call racing (or arriving after) close() must
                # not re-inflate the process gauge the close withdrew —
                # a closed engine's contribution is pinned at zero
                resident = 0
            delta = resident - self._quant_resident_seen
            self._quant_resident_seen = resident
        if delta:
            self._quant_resident.add(float(delta))

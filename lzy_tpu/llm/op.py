"""``llm.generate``: inference as a first-class workflow citizen.

The serving stack (engines → gateway → disagg → tenancy) and the
dataflow stack (``@op`` → workflows → channels → whiteboards) grew side
by side; this module is the join. ``llm.generate(prompt, ...)`` called
inside ``with lzy.workflow(...)`` registers an ordinary :class:`LzyCall`
whose body dispatches to the serving plane — so its result is a typed
:class:`Generation` proxy that flows through the graph like any op
output, ``generate → tool op → generate`` agent/RAG pipelines are plain
lzy graphs, and independent generations fan out through the graph
executor's existing concurrency. Outside a workflow it just runs — the
same contract ``@op`` functions have.

What riding the workflow buys a generation, for free:

- **caching**: ``cache=True``-style op caching keyed on (prompt, params,
  model digest) — a cached re-execution never touches the fleet. Sampled
  requests opt out (their output is a draw, not a function of the
  inputs); ``greedy=True`` generations cache by default.
- **conversation affinity**: a :class:`Conversation` handle carried
  across steps feeds the gateway router a stable session hint, so step
  N+1 lands on the replica whose RadixCache holds steps 1..N.
- **streaming**: a ``channels.token_stream.TokenStreamChannel`` receives
  tokens as the engine emits them; the gateway's fenced-token failover
  makes a mid-stream replica death invisible to the channel. Against a
  REMOTE plane (``LZY_LLM_ENDPOINT``) the same channel is fed by the
  ``InferStream`` chunked long-poll (``rpc/schema.py``): tokens arrive
  incrementally over the wire, and a dropped worker connection resumes
  at the fence position byte-identically.
- **provenance**: ``record_generation`` versions the result (prompt,
  params, model digest, token ids, routing/KV provenance) as whiteboard
  fields queryable after the run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

from lzy_tpu.chaos.faults import CHAOS
from lzy_tpu.utils import trace
from lzy_tpu.utils.backoff import RetryPolicy
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.utils.ids import gen_id
from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)

#: op name the cache namespace and graph tasks carry — also what the
#: workflow service matches to count fleet-skipping cache drops
LLM_OP_NAME = "llm_generate"


class LlmDispatchError(RuntimeError):
    """Transient failure dispatching a generation to the serving plane;
    the op retries it under the platform backoff policy."""


# chaos boundary: one dispatch attempt to the serving plane. Survivable
# by contract — the op body retries with backoff, and only exhausted
# retries surface to the workflow (which applies ITS retry/caching
# semantics on top).
_FP_DISPATCH = CHAOS.register(
    "llm.dispatch", error=LlmDispatchError,
    doc="one llm_op dispatch to the serving plane (retried with backoff)")

#: dispatch retry law: quick, capped — the gateway already does its own
#: fleet-wide admission fallback per attempt
DISPATCH_RETRIES_POLICY = RetryPolicy(attempts=3, base_s=0.05, cap_s=1.0)


@dataclasses.dataclass
class Generation:
    """Typed result of one generation — what flows through the graph.

    ``tokens`` excludes the prompt echo; ``full_tokens()`` is the
    concatenation a follow-up step feeds back as its prompt prefix
    (which is exactly what makes conversation prefix-affinity pay).
    Routing/KV provenance fields are None outside a gateway/disagg
    plane."""

    prompt: List[int]
    tokens: List[int]
    status: str
    model: str
    model_digest: str
    params: Dict[str, Any]
    request_id: Optional[str] = None
    replica: Optional[str] = None
    routed_by: Optional[str] = None
    failovers: int = 0
    #: disagg provenance: the prefill-pool replica whose KV the serving
    #: attempt actually USED (not merely staged)
    prefilled_by: Optional[str] = None
    ttft_ms: Optional[float] = None
    conversation_id: Optional[str] = None
    step: Optional[int] = None
    wall_ms: Optional[float] = None

    def full_tokens(self) -> List[int]:
        return list(self.prompt) + list(self.tokens)

    def provenance(self) -> Dict[str, Any]:
        """The per-step provenance document whiteboards record."""
        return {
            "request_id": self.request_id, "status": self.status,
            "replica": self.replica, "routed_by": self.routed_by,
            "failovers": self.failovers,
            "prefilled_by": self.prefilled_by,
            "ttft_ms": self.ttft_ms, "wall_ms": self.wall_ms,
            "conversation_id": self.conversation_id, "step": self.step,
        }


class Conversation:
    """Stable session handle for multi-step pipelines.

    Carried (by value) through every ``llm.generate`` of one logical
    conversation, it gives the gateway router a stable pin: step N+1
    routes to the replica whose RadixCache holds steps 1..N. The id is
    the identity — pass an explicit one (``Conversation("support-123")``)
    when re-runs should share cache entries; the default is a fresh
    random id per object.
    """

    def __init__(self, conversation_id: Optional[str] = None):
        self.id = conversation_id or gen_id("conv")
        self._steps = 0

    def next_step(self) -> int:
        """Client-side step counter (called at op registration)."""
        self._steps += 1
        return self._steps

    @property
    def steps(self) -> int:
        return self._steps

    def __repr__(self) -> str:
        return f"Conversation(id={self.id!r}, steps={self._steps})"


# -- the op body (module-level: pickles by reference to workers) --------------

def llm_generate(prompt, gen_params, model_digest,
                 conversation=None, runtime_opts=None):
    """Dispatch ONE generation to the resolved serving plane (the op
    body ``llm.generate`` registers; also the direct-call path outside a
    workflow). Runs wherever the runtime schedules it.

    ``gen_params`` holds what determines the output (and therefore the
    op cache key); ``runtime_opts`` holds operational knobs — timeouts,
    deadline, stream wiring, the workflow identity — excluded from the
    key (``CacheSettings.exclude_args``): bumping a timeout must not
    re-dispatch an already-cached greedy generation."""
    from lzy_tpu.llm import metrics
    from lzy_tpu.llm.backend import resolve_backend
    from lzy_tpu.llm.sched import scheduler_for

    backend = resolve_backend()
    sched = scheduler_for(backend)
    params = dict(gen_params)
    opts = dict(runtime_opts or {})
    step = params.pop("step", None)
    tenant = params.pop("tenant", None)
    wf_user = opts.pop("wf_user", None)
    if tenant is None and getattr(backend, "token", None) is None:
        # IAM-less plane: the workflow identity is the best tenant we
        # have. With a token the plane derives the tenant itself — a
        # restated wire tenant that mismatched the subject would be
        # rejected.
        tenant = wf_user
    stream, spill, spill_thread, stream_owned = _resolve_stream(opts)
    session = conversation.id if conversation is not None else None
    prompt_tokens = [int(t) for t in prompt]
    t0 = SYSTEM_CLOCK.now()

    def dispatch():
        CHAOS.hit("llm.dispatch")
        return sched.dispatch(
            prompt_tokens,
            max_new_tokens=params.get("max_new_tokens", 64),
            timeout_s=opts.get("timeout_s"),
            deadline_s=opts.get("deadline_s"),
            greedy=params.get("greedy"),
            tenant=tenant,
            priority=params.get("priority"),
            session=session,
            stream=stream)

    def retryable(e: BaseException) -> bool:
        # only retry while the stream is untouched: once tokens were
        # published (or the channel terminated), the consumer has seen
        # this attempt — a silent redo would splice streams. The serving
        # surfaces cooperate: a pre-dispatch failure leaves a virgin
        # (zero-token) stream OPEN, so transient sheds retry here with
        # the consumer none the wiser; the except path below owns the
        # terminal fail once retries are exhausted.
        if stream is not None and (stream.closed or stream.position):
            return False
        if isinstance(e, LlmDispatchError):
            return True
        from lzy_tpu.rpc.core import Unavailable
        from lzy_tpu.serving.scheduler import (
            AdmissionError, PromptTooLong)

        if isinstance(e, PromptTooLong):
            return False              # permanent: identical everywhere
        return isinstance(e, (AdmissionError, Unavailable))

    try:
        reply = DISPATCH_RETRIES_POLICY.call(
            dispatch, what="llm dispatch", retry_if=retryable,
            on_retry=lambda n, e: metrics.DISPATCH_RETRIES.inc())
    except BaseException as e:
        metrics.GENERATIONS.inc(status="error")
        if stream is not None and not stream.closed:
            stream.fail(f"{type(e).__name__}: {e}")
        raise
    finally:
        if spill is not None:
            _finish_spill(stream, spill, spill_thread)
        if stream_owned and stream is not None:
            # terminal either way by now (the surfaces close the channel
            # before returning; the except path failed it): drop the
            # rendezvous entry so a long-lived worker does not retain
            # every finished stream until the registry cap evicts it
            from lzy_tpu.channels.token_stream import STREAMS

            STREAMS.release(stream.id)
    status = reply.get("status", "ok")
    metrics.GENERATIONS.inc(status=status)
    metrics.GENERATED_TOKENS.inc(len(reply.get("tokens", ())))
    if session is not None and status == "ok":
        # fused op chain: park this conversation's KV resident on its
        # replica and speculatively prefill the next step's known prompt
        # prefix (this step's prompt + reply) while the tool op between
        # steps runs — the next dispatch for this session awaits it
        sched.note_step_done(
            session, prompt_tokens + list(reply.get("tokens", [])),
            tenant=tenant)
    return Generation(
        prompt=prompt_tokens,
        tokens=list(reply.get("tokens", [])),
        status=status,
        model=reply.get("model", getattr(backend, "model_name", "custom")),
        model_digest=model_digest,
        params=dict(gen_params),
        request_id=reply.get("request_id"),
        replica=reply.get("replica"),
        routed_by=reply.get("routed_by"),
        failovers=int(reply.get("failovers", 0) or 0),
        prefilled_by=reply.get("prefilled_by"),
        ttft_ms=reply.get("ttft_ms"),
        conversation_id=session,
        step=step,
        wall_ms=round(1000 * (SYSTEM_CLOCK.now() - t0), 3),
    )


def llm_generate_batch(prompts, gen_params, model_digest,
                       conversation=None, runtime_opts=None):
    """Batch body: fan the prompts through the workflow scheduler's
    shared plane (they are independent — the engine batches them across
    slots; one op node keeps them one graph edge). Greedy batches dedup
    WITHIN the fan-out too: identical rows dispatch once and every
    duplicate adopts a copy of the reply (and since each unique row
    lands back in :meth:`WorkflowScheduler.dispatch`, cross-workflow
    in-flight dedup still applies on top). Conversations apply per the
    single-prompt contract on every row; streams are rejected at the
    factory (:func:`generate`) — concurrent rows publishing divergent
    tokens at overlapping positions of ONE channel is a splice, not a
    stream."""
    from lzy_tpu.llm import metrics
    from lzy_tpu.llm.backend import resolve_backend
    from lzy_tpu.llm.sched import scheduler_for

    if not prompts:
        return []
    sched = scheduler_for(resolve_backend())
    greedy = dict(gen_params).get("greedy") is True
    dedupable = sched.dedup and greedy
    # identical greedy rows collapse before dispatch: key by prompt
    # (params/digest are batch-constant); sampled rows stay unique —
    # each is its own draw
    row_keys: List[Any] = []
    unique: Dict[Any, List[int]] = {}
    for i, p in enumerate(prompts):
        key = tuple(int(t) for t in p) if dedupable else ("row", i)
        row_keys.append(key)
        unique.setdefault(key, list(p))
    with trace.span(trace.LLM_BATCH, rows=len(prompts),
                    deduplicated=len(prompts) - len(unique)):
        results = sched.map(
            lambda p: llm_generate(p, gen_params, model_digest,
                                   conversation, runtime_opts),
            list(unique.values()))
    by_key = dict(zip(unique.keys(), results))
    out, adopted = [], set()
    for key in row_keys:
        g = by_key[key]
        if key in adopted:
            # duplicate row adopting its twin's reply: fresh token
            # lists per row — siblings must never alias
            metrics.DEDUP_HITS.inc()
            metrics.WFSCHED_DISPATCHES.inc(role="follower")
            sched.note_batch_dedup()
            g = dataclasses.replace(g, prompt=list(g.prompt),
                                    tokens=list(g.tokens),
                                    params=dict(g.params))
        adopted.add(key)
        out.append(g)
    return out


def _resolve_stream(opts):
    """In-process transport first, storage spill as the fallback: a
    ``stream_id`` resolves (or creates) the channel in the process
    registry; a ``stream_spill_uri`` additionally mirrors it to chunked
    storage objects so a consumer in ANOTHER process can follow along
    (``channels.token_stream.StorageTokenStreamReader``)."""
    stream_id = opts.pop("stream_id", None)
    spill_uri = opts.pop("stream_spill_uri", None)
    owned = bool(opts.pop("stream_owned", False))
    if stream_id is None and spill_uri is None:
        return None, None, None, False
    from lzy_tpu.channels.token_stream import (
        STREAMS, StorageTokenStreamWriter, TokenStreamChannel)

    stream = (STREAMS.get_or_create(stream_id) if stream_id is not None
              else TokenStreamChannel())
    spill = spill_thread = None
    if spill_uri is not None:
        from lzy_tpu.storage.registry import client_for
        from lzy_tpu.storage import StorageConfig
        import threading

        client = client_for(StorageConfig(uri=spill_uri))
        spill = StorageTokenStreamWriter(client, spill_uri)

        def mirror(ch=stream, w=spill):
            try:
                for tok in ch:
                    w.append([tok])
            except Exception:  # noqa: BLE001 — finish() records status
                pass

        spill_thread = threading.Thread(target=mirror,
                                        name="llm-stream-spill",
                                        daemon=True)
        spill_thread.start()
    return stream, spill, spill_thread, owned


def _finish_spill(stream, spill, spill_thread) -> None:
    stalled = False
    if spill_thread is not None:
        spill_thread.join(timeout=30.0)
        stalled = spill_thread.is_alive()
    try:
        if stalled:
            # the mirror is still draining: committing now would
            # truncate the durable stream under an "ok" manifest — a
            # reader must see the truncation as a failure instead
            spill.finish(status="error",
                         error="spill mirror stalled; durable stream "
                               "is incomplete")
            return
        status = (stream.status or "ok") if stream is not None else "ok"
        spill.finish(status=status,
                     error=stream.error if stream is not None else None)
    except Exception:  # noqa: BLE001 — the reply owns the result
        _LOG.exception("token stream spill finish failed")


def _count_cache_hit() -> None:
    from lzy_tpu.llm.metrics import CACHED_HITS

    CACHED_HITS.inc()


def _generation_cacheable(result) -> bool:
    """Cache veto (``core.call.result_cacheable``): only a COMPLETE
    generation may be cached. A deadline/cancel-truncated reply returns
    ``status="cancelled"`` with partial tokens — and the deadline that
    truncated it is deliberately excluded from the cache key, so caching
    it would serve the truncation forever, even after the caller raises
    the deadline."""
    results = result if isinstance(result, list) else [result]
    return all(isinstance(g, Generation) and g.status == "ok"
               for g in results)


#: runtime hook (``runtime/local.py``): a cache-satisfied llm call never
#: runs this body, so the runtime counts the skip for us
llm_generate.__lzy_on_cache_hit__ = _count_cache_hit
llm_generate_batch.__lzy_on_cache_hit__ = _count_cache_hit
#: runtime hook (``core.call.result_cacheable``): non-ok generations
#: must not poison the op cache
llm_generate.__lzy_result_cacheable__ = _generation_cacheable
llm_generate_batch.__lzy_result_cacheable__ = _generation_cacheable


# -- the user-facing factory --------------------------------------------------

def generate(prompt, *,
             max_new_tokens: int = 64,
             greedy: Optional[bool] = None,
             conversation: Optional[Conversation] = None,
             tenant: Optional[str] = None,
             priority: Optional[int] = None,
             deadline_s: Optional[float] = None,
             timeout_s: Optional[float] = None,
             cache: Optional[bool] = None,
             cache_version: str = "1.0",
             stream=None,
             stream_spill_uri: Optional[str] = None,
             model_digest: Optional[str] = None,
             description: str = ""):
    """Generate from the serving plane — as a workflow op when a
    workflow is active (returns a lazy :class:`Generation` proxy), else
    directly (returns the :class:`Generation`).

    ``prompt``: token ids (or a proxy of them from an upstream op); a
    list of prompts fans out as ONE op node returning
    ``List[Generation]`` (see also :func:`generate_batch`).

    **Caching** (``cache``): ``None`` (default) caches exactly the
    deterministic requests — ``greedy=True`` — keyed on (prompt, params,
    model digest); sampled requests opt out (a cached draw would freeze
    randomness the caller asked for). ``True`` forces caching anyway,
    ``False`` disables. Streaming requests never cache (a hit skips the
    body, so there would be nothing to stream). Operational knobs that
    cannot change the output — ``timeout_s``, ``deadline_s``, stream
    wiring, the workflow identity — are excluded from the key: bumping
    a timeout re-uses the cached generation instead of re-dispatching.

    **Conversation** affinity, **tenant/priority/deadline** (the SLO
    identity; tenant defaults to the workflow's authenticated user on an
    IAM-less plane), and **streaming** (``stream``: a
    ``TokenStreamChannel`` or an id resolved in the process registry;
    ``stream_spill_uri``: chunked storage mirror for cross-process
    consumers) are documented on the module.
    """
    from lzy_tpu.core.workflow import LzyWorkflow

    batch = _is_batch(prompt)
    if batch and (stream is not None or stream_spill_uri is not None):
        raise ValueError(
            "streaming applies to a single generation: a batch fanning "
            "into one channel would interleave rows at overlapping "
            "positions — call generate() per prompt, each with its own "
            "stream")
    params = {
        "max_new_tokens": int(max_new_tokens),
        "greedy": greedy,
        "tenant": tenant,
        "priority": priority,
    }
    opts = {
        "deadline_s": deadline_s,
        "timeout_s": timeout_s,
    }
    stream_id = _register_stream(stream)
    if stream_id is not None:
        opts["stream_id"] = stream_id
        if not isinstance(stream, str):
            # the caller holds the channel OBJECT — the registry entry
            # exists only to ferry the id to the op body, so the body
            # releases it once the generation is terminal (an id-only
            # registration stays: its consumer may resolve it later)
            opts["stream_owned"] = True
    if stream_spill_uri is not None:
        opts["stream_spill_uri"] = stream_spill_uri
    step = conversation.next_step() if conversation is not None else None
    if step is not None:
        params["step"] = step
    wf = LzyWorkflow.get_active()
    body = llm_generate_batch if batch else llm_generate
    if wf is None:
        digest = model_digest or _backend_digest()
        return body(prompt, params, digest, conversation, opts)

    _check_stream_travels(wf, stream, stream_spill_uri)
    auth = wf.owner.runtime.auth_context()
    if auth.get("user") is not None:
        opts["wf_user"] = auth["user"]
    digest = model_digest or _backend_digest()
    streaming = stream_id is not None or stream_spill_uri is not None
    if cache is None:
        effective_cache = (greedy is True) and not streaming
    else:
        effective_cache = bool(cache) and not streaming
        if cache and streaming:
            _LOG.warning("llm.generate: caching disabled for a streaming "
                         "request (a cache hit skips the body — nothing "
                         "would stream)")
    from lzy_tpu.core.call import CacheSettings, LzyCall
    from lzy_tpu.core.signatures import infer_and_validate_call_signature

    signature = infer_and_validate_call_signature(
        body, prompt, params, digest, conversation, opts,
        output_types=(list if batch else Generation,))
    call = LzyCall(
        workflow=wf,
        signature=signature,
        env=wf.owner.env.combine(wf.env),
        # runtime_opts carries knobs that cannot change the output
        # (timeouts, deadline, stream wiring, workflow identity) — they
        # must not fragment the cache key
        cache=CacheSettings(cache=effective_cache, version=cache_version,
                            exclude_args=("runtime_opts",)),
        description=description or
        (f"llm generation (conversation {conversation.id} step {step})"
         if conversation is not None else "llm generation"),
    )
    wf.register_call(call)
    return call.build_results()


def generate_batch(prompts: Sequence[Sequence[int]], **kwargs):
    """Explicit batch form of :func:`generate` — one op node, a
    ``List[Generation]`` result, rows dispatched concurrently."""
    prompts = [list(p) for p in prompts]
    if not all(_is_tokens(p) for p in prompts):
        raise ValueError("generate_batch wants a list of token-id lists")
    return generate(prompts, **kwargs)


def _is_tokens(p) -> bool:
    return isinstance(p, (list, tuple)) and \
        all(isinstance(t, int) for t in p)


def _is_batch(prompt) -> bool:
    return isinstance(prompt, (list, tuple)) and len(prompt) > 0 and \
        isinstance(prompt[0], (list, tuple))


def _register_stream(stream) -> Optional[str]:
    if stream is None:
        return None
    if isinstance(stream, str):
        return stream
    from lzy_tpu.channels.token_stream import STREAMS

    return STREAMS.register(stream)


def _check_stream_travels(wf, stream, spill_uri) -> None:
    """A live channel object cannot cross a process boundary — only its
    id travels, and a worker resolving the id gets a FRESH channel in
    its own registry: the caller's object would never see a token and
    the consumer would park until its read timeout. On a runtime whose
    op bodies leave this process, reject the live object (the spill
    mirror is the cross-process transport) and flag a bare id without
    one."""
    if wf.owner.runtime.in_process() or stream is None:
        return
    if not isinstance(stream, str):
        raise ValueError(
            "a live TokenStreamChannel cannot follow an op to another "
            "process — pass stream_spill_uri= and read it back with "
            "channels.token_stream.StorageTokenStreamReader (or pass a "
            "string stream id resolved by a consumer in the WORKER "
            "process)")
    if spill_uri is None:
        _LOG.warning(
            "llm.generate: stream id %r on a multi-process runtime has "
            "no consumer here — tokens surface only in the worker's "
            "registry; add stream_spill_uri= for a cross-process reader",
            stream)


def _backend_digest() -> str:
    from lzy_tpu.llm.backend import LlmBackendError, resolve_backend

    try:
        return resolve_backend().model_digest()
    except LlmBackendError:
        # the registering client may not reach the plane (workers do);
        # the cache key is weaker without a digest — say so once
        _LOG.warning("llm.generate: no backend reachable at registration; "
                     "model digest unknown (pass model_digest= for a "
                     "stable cache key)")
        return "unknown"

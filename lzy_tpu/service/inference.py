"""Inference service: the serving engine on the control-plane RPC stack.

The reference platform's control plane schedules *workflows*; this module
makes the same deployable process also serve *models* — the
``--serve-model`` mode of ``lzy_tpu.service.serve`` builds one of these and
hands it to ``InProcessCluster``, whose ``ControlPlaneServer`` registers
the ``InferGenerate``/``InferStats`` RPC methods next to the workflow
surface (one gRPC port, one IAM, one metrics registry).

Auth model mirrors the rest of the plane: with IAM wired every call needs
a bearer token (any authenticated subject may generate; stats too — they
carry no tenant data, only engine health); without IAM the surface is the
single-tenant operator tool the rest of the plane is.
"""

from __future__ import annotations

from typing import Any, Optional

from lzy_tpu.serving.scheduler import (
    AdmissionError, DEFAULT_TENANT, PromptTooLong, QuotaExceeded,
    any_to_tokens, plane_capacity)
from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)

#: named model configs --serve-model accepts; weights are random-initialized
#: unless --model-checkpoint points at an Orbax export of the same config
MODEL_CONFIGS = ("tiny", "llama3_8b", "llama3_70b")


class InferenceService:
    """Thin RPC-facing wrapper over a :class:`PagedInferenceEngine`.

    ``max_waiters`` bounds how many RPC handler threads may BLOCK in
    ``generate`` at once: the control plane's gRPC pool is shared with the
    workflow surface (16 threads), and without a cap a burst of generate
    calls parked in ``req.result()`` would starve worker heartbeats and
    task RPCs on the same port. Beyond the cap, callers get the same
    ``Unavailable`` backpressure a full queue produces.

    ``slo`` (``serving.tenancy.SloLimiter``) enforces per-tenant rate
    limits at this front; the tenant itself is the authenticated IAM
    subject (or the wire-supplied tenant on an IAM-less plane)."""

    def __init__(self, engine, model_name: str = "custom", iam=None,
                 max_waiters: int = 8, slo=None):
        import threading

        from lzy_tpu.serving.streams import StreamSessionManager

        self.engine = engine
        self.model_name = model_name
        self.iam = iam        # harness wires the cluster's IAM in here
        self.slo = slo
        self._max_waiters = int(max_waiters)
        self._waiters = threading.BoundedSemaphore(max_waiters)
        #: streaming front (InferStream/InferStreamPoll/InferCancel):
        #: chunked long-poll token delivery with liveness reaping,
        #: bounded consumer buffers, and mid-stream cancellation
        self.streams = StreamSessionManager(self)

    def _auth(self, token: Optional[str]):
        if self.iam is not None:
            return self.iam.authenticate(token)
        return None

    def _resolve_tenant(self, subject, tenant: Optional[str]) -> str:
        if subject is None:
            return tenant or DEFAULT_TENANT
        if tenant and tenant != subject.id:
            from lzy_tpu.iam import INTERNAL, AuthError

            if subject.role != INTERNAL:
                raise AuthError(
                    f"subject {subject.id} may not submit as tenant "
                    f"{tenant!r}")
            return tenant
        return subject.id

    def generate(self, prompt, *, max_new_tokens: int = 64,
                 token: Optional[str] = None,
                 timeout_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 greedy: Optional[bool] = None,
                 tenant: Optional[str] = None,
                 priority: Optional[int] = None,
                 session: Optional[str] = None,
                 stream=None, liveness=None) -> dict:
        """Blocking generate: admit, wait, return generated token ids.
        Backpressure (full queue OR all waiter threads busy) surfaces as
        ``Unavailable`` BEFORE any work happens — safe for the caller to
        retry with backoff; the plane never buffers unboundedly. On
        timeout the request is cancelled so the engine stops spending
        decode steps on it. ``deadline_s`` is the engine-side client
        deadline: once it passes, the request is evicted mid-decode and
        the call RETURNS (not raises) with ``status: "cancelled"`` and
        whatever tokens were generated before the eviction. ``greedy``
        is the per-request sampling override (True forces argmax — and
        speculation eligibility — on a sampling engine).
        ``tenant``/``priority``: the SLO identity (IAM subject id wins
        when IAM is wired); tenant-scoped refusals raise
        ``QuotaExceeded`` (RESOURCE_EXHAUSTED on the wire) with a
        per-tenant ``retry_after_s``; over-long prompts raise
        ``PromptTooLong`` (INVALID_ARGUMENT) at admission. ``session``
        is accepted for surface parity with the gateway (a routing hint
        is meaningless with one engine); ``stream`` (a
        ``channels.token_stream.TokenStreamChannel``) receives tokens
        incrementally and is closed before this returns — or failed
        before it raises if any tokens were published (a never-touched
        stream is left open for the caller's retry policy). ``liveness``
        (a zero-arg callable) is the reply channel's client probe: once
        it returns False the engine reaps the request wherever it sits
        — queued, staged, or slot-resident — within one decode round."""
        subject = self._auth(token)
        from lzy_tpu.rpc.core import Unavailable

        tenant = self._resolve_tenant(subject, tenant)
        prompt = any_to_tokens(prompt)
        if self.slo is not None:
            policy = self.slo.admit(tenant, len(prompt))
            priority = policy.effective_priority(priority)
        # the waiter cap protects the SHARED gRPC handler pool from
        # parking in req.wait(); a streaming session's worker (the only
        # caller passing liveness) is a dedicated thread whose
        # concurrency is already bounded by the session manager's
        # max_sessions — gating it here would silently cap streams at
        # the waiter count AND starve unary traffic for the lifetime of
        # every long-lived stream
        gated = liveness is None
        if gated and not self._waiters.acquire(blocking=False):
            raise Unavailable(
                "all inference waiter threads are busy; retry later")
        try:
            try:
                req = self.engine.submit(
                    prompt,
                    max_new_tokens=int(max_new_tokens),
                    deadline_s=deadline_s,
                    greedy=greedy,
                    tenant=tenant,
                    priority=priority,
                    liveness=liveness)
            except PromptTooLong:
                # permanent rejection keeps its INVALID_ARGUMENT wire
                # status — not the generic capacity Unavailable below
                raise
            except QuotaExceeded as e:
                # the engine queue raises these UNCOUNTED (the gateway
                # retries other replicas; a probe refusal is not a shed)
                # — here there is no other replica, so the refusal is
                # client-facing and counts. Wire status stays
                # RESOURCE_EXHAUSTED with the per-tenant retry hint.
                from lzy_tpu.serving.scheduler import count_tenant_shed

                count_tenant_shed(e)
                raise
            except AdmissionError as e:
                # client-facing shed (single-engine plane: no other
                # replica to try); shed_error owns the hint's wire format
                from lzy_tpu.serving.scheduler import shed_error

                raise shed_error(
                    Unavailable, str(e), reason="admission",
                    retry_after_s=getattr(e, "retry_after_s", None),
                ) from None
            if stream is not None:
                from lzy_tpu.channels.token_stream import attach_request

                attach_request(stream, req, 0)
            if not req.wait(timeout=timeout_s or 120.0):
                req.cancel()
                raise TimeoutError(
                    f"request {req.id} not finished within "
                    f"{timeout_s or 120.0}s")
            if req.error and req.status != "cancelled":
                raise RuntimeError(f"request {req.id} failed: {req.error}")
            tokens = list(req.tokens)
            if stream is not None:
                stream.close(req.status or "ok")
        except BaseException as e:
            from lzy_tpu.channels.token_stream import fail_if_touched

            fail_if_touched(stream, e)
            raise
        finally:
            if gated:
                self._waiters.release()
        ttft_ms = None
        if req.first_token_at is not None:
            ttft_ms = round(1000 * (req.first_token_at - req.submitted_at), 3)
        # what the plane holds, as the gateways' replies say it: a caller
        # with many calls in flight sizes itself by it (llm/sched.py)
        return {"request_id": req.id, "tokens": tokens,
                "status": req.status or "ok",
                "ttft_ms": ttft_ms, "model": self.model_name,
                **plane_capacity(self.engine.stats().slots,
                                 self._max_waiters if gated else None)}

    def stats(self, *, token: Optional[str] = None) -> dict:
        """Engine stats. Scoped per subject: the operator (no IAM, or
        the INTERNAL role) sees engine internals plus every tenant's
        counters; any other subject sees only its own tenant's row."""
        subject = self._auth(token)
        if subject is not None:
            from lzy_tpu.iam import INTERNAL
            from lzy_tpu.serving.tenancy import TENANT_ROW

            if subject.role != INTERNAL:
                row = self.engine.stats_by_tenant().get(
                    subject.id, dict(TENANT_ROW, queue_depth=0))
                return {"model": self.model_name, "tenant": subject.id,
                        **row}
        return {"model": self.model_name, **self.engine.stats().doc(),
                "tenants": self.engine.stats_by_tenant()}

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown: stop admitting, finish in-flight rows,
        then close (``serve.py`` calls this on SIGTERM before tearing
        the cluster down)."""
        return self.engine.drain(timeout_s)

    def close(self) -> None:
        self.streams.close()
        self.engine.close()


def _build_engine_parts(model: str, *, checkpoint: Optional[str],
                        seed: int):
    """Config + params for a named model (shared by the single-engine and
    fleet builders; the fleet shares ONE params tree across replicas —
    the engines never mutate it)."""
    import jax

    from lzy_tpu.models import llama, unbox

    if model not in MODEL_CONFIGS:
        raise ValueError(
            f"unknown --serve-model {model!r}; known: {MODEL_CONFIGS}")
    cfg = getattr(llama.LlamaConfig, model)()
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(seed))
    params: Any = unbox(boxed)
    if checkpoint:
        from lzy_tpu.parallel.orbax_interop import import_orbax

        _LOG.info("restoring %s weights from %s", model, checkpoint)
        params = import_orbax(checkpoint, template=params)
    return cfg, params


def _build_kv_storage_tier(kv_storage_tier, page_size: int):
    """Resolve the ``--kv-storage-tier`` value: a URI becomes ONE shared
    ``StorageKVTier`` (every replica in the process spills to — and
    promotes from — the same root, which is what makes the storage rung
    fleet-global); an already-built tier object passes through."""
    if kv_storage_tier is None:
        return None
    if not isinstance(kv_storage_tier, str):
        return kv_storage_tier
    from lzy_tpu.serving.kv_tier import StorageKVTier
    from lzy_tpu.storage.api import StorageConfig
    from lzy_tpu.storage.registry import client_for

    client = client_for(StorageConfig(uri=kv_storage_tier))
    return StorageKVTier(client, kv_storage_tier, page_size)


def build_gateway_service(
    model: str,
    *,
    replicas: int = 3,
    slots: int = 4,
    max_queue: int = 64,
    eos_token: Optional[int] = None,
    checkpoint: Optional[str] = None,
    seed: int = 0,
    prefill_chunk: Optional[int] = None,
    page_size: int = 16,
    kv_blocks: Optional[int] = None,
    kv_pool_bytes: Optional[int] = None,
    kv_quant: Optional[str] = None,
    kernel: str = "auto",
    kv_host_tier_bytes: Optional[int] = None,
    kv_storage_tier=None,
    serve_mesh: Optional[int] = None,
    kv_global_index: Optional[bool] = None,
    routing: str = "prefix",
    allocator=None,
    pool_label: str = "cpu-small",
    autoscale: bool = True,
    min_replicas: Optional[int] = None,
    max_replicas: Optional[int] = None,
    spec_tokens: int = 0,
    warm_start: bool = False,
    start: bool = True,
    prefill_budget: Optional[int] = None,
    tenants=None,
    journal=None,
):
    """Construct the serving fleet gateway (``serve.py --gateway``): N
    engine replicas behind one ``InferGenerate`` endpoint with
    prefix-affinity routing, health/failover, and (optionally)
    allocator-driven autoscaling between ``min_replicas`` and
    ``max_replicas`` (defaults: ``replicas`` .. ``2 * replicas``).

    ``kv_host_tier_bytes``/``kv_storage_tier`` build the tiered KV cache
    behind each replica (``--kv-host-tier-mb``/``--kv-storage-tier``;
    docs/serving.md "Tiered KV cache"); ``kv_global_index`` turns on the
    gateway's fleet-global prefix index + cross-replica import (default:
    on exactly when a tier is configured).

    ``routing``: ``"prefix"`` (cache-aware, the default) or ``"rr"``
    (round-robin — the measurable baseline). ``allocator``: an
    ``AllocatorService`` to lease replica gangs through (None runs the
    fleet unleased, plain threads). ``spec_tokens`` > 0 enables
    draft-free speculative decoding on every replica (``--serve-spec``);
    ``warm_start`` AOT-compiles each replica's decode/verify programs at
    boot instead of on the first request. ``prefill_budget`` bounds
    prefill tokens per engine step (chunked-prefill interleaving), spent as
    one program as wide as the widest bucket under it (``prefill_chunk``
    None: the engine derives the width; a number is used as given);
    ``tenants`` (a ``serving.tenancy.TenantTable``) turns on the
    multi-tenant SLO layer: token-bucket rate limits at the gateway,
    WFQ + per-tenant queue caps + KV quotas in every replica.
    ``journal`` (a ``gateway.journal.GatewayJournal`` over the durable
    store plane) turns on control-plane crash recovery: session births,
    fence advances and replica leases are journaled so a successor
    process restores them (``serve.py --gateway-journal``;
    docs/serving.md "Control-plane recovery").

    ``serve_mesh`` (``--serve-mesh N``) makes every replica a GANG: a
    ``ShardedPagedInferenceEngine`` running the forwards tensor-sharded
    over a 1×N mesh (output stays bit-identical to single-device —
    docs/serving.md "Sharded replicas"). Health and
    recovery treat the gang as one replica: one dead host fails over the
    whole gang.
    """
    from lzy_tpu.gateway import (
        Autoscaler, GatewayService, PrefixAffinityRouter, ReplicaFleet,
        RoundRobinRouter)
    from lzy_tpu.serving import PagedInferenceEngine

    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    if routing not in ("prefix", "rr"):
        raise ValueError(f"unknown routing {routing!r}; use prefix or rr")
    cfg, params = _build_engine_parts(model, checkpoint=checkpoint,
                                      seed=seed)
    engine_kw = dict(
        slots=slots, max_queue=max_queue, eos_token=eos_token,
        prefill_chunk=prefill_chunk, seed=seed, spec_tokens=spec_tokens,
        prefill_budget=prefill_budget, tenants=tenants,
        page_size=page_size, kv_blocks=kv_blocks,
        kv_pool_bytes=kv_pool_bytes, kv_quant=kv_quant, kernel=kernel,
        kv_host_tier_bytes=kv_host_tier_bytes,
        kv_storage_tier=_build_kv_storage_tier(kv_storage_tier, page_size))

    def engine_factory():
        if serve_mesh is not None:
            from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine

            engine = ShardedPagedInferenceEngine(
                cfg, params, tp=serve_mesh, **engine_kw)
        else:
            engine = PagedInferenceEngine(cfg, params, **engine_kw)
        if warm_start:
            engine.warmup()
        return engine

    fleet = ReplicaFleet(engine_factory, allocator=allocator,
                         pool_label=pool_label)
    router_cls = PrefixAffinityRouter if routing == "prefix" \
        else RoundRobinRouter
    autoscaler = None
    if autoscale:
        autoscaler = Autoscaler(
            min_replicas=min_replicas or replicas,
            max_replicas=max_replicas or 2 * replicas)
    slo = None
    if tenants is not None:
        from lzy_tpu.serving.tenancy import SloLimiter

        slo = SloLimiter(tenants)
    if kv_global_index is None:
        # tiered mode implies the fleet-global index: a tier without it
        # would warm only the replica that demoted
        kv_global_index = (kv_host_tier_bytes is not None
                           or kv_storage_tier is not None)
    kv_index = None
    if kv_global_index:
        from lzy_tpu.gateway.kv_index import GlobalKVIndex

        kv_index = GlobalKVIndex(page_size)
    service = GatewayService(
        fleet,
        router=router_cls(page_size),
        autoscaler=autoscaler,
        model_name=model,
        slo=slo,
        kv_index=kv_index,
        journal=journal,
    )
    try:
        for _ in range(replicas):
            fleet.add_replica()
    except BaseException:
        service.close()
        raise
    # cache identity for llm_op: what this plane serves, honestly keyed
    # on config + weight provenance (llm/backend.model_digest_for)
    from lzy_tpu.llm.backend import model_digest_for

    service.model_digest = model_digest_for(model, cfg,
                                            checkpoint=checkpoint,
                                            seed=seed)
    if start:
        service.start()
    return service


def build_disagg_gateway_service(
    model: str,
    *,
    prefill_replicas: int = 1,
    decode_replicas: int = 2,
    slots: int = 4,
    max_queue: int = 64,
    eos_token: Optional[int] = None,
    checkpoint: Optional[str] = None,
    seed: int = 0,
    prefill_chunk: Optional[int] = None,
    page_size: int = 16,
    kv_blocks: Optional[int] = None,
    kv_pool_bytes: Optional[int] = None,
    kv_quant: Optional[str] = None,
    kernel: str = "auto",
    kv_host_tier_bytes: Optional[int] = None,
    kv_storage_tier=None,
    routing: str = "prefix",
    allocator=None,
    pool_label: str = "cpu-small",
    autoscale: bool = True,
    min_replicas: Optional[int] = None,
    max_replicas: Optional[int] = None,
    transport=None,
    spec_tokens: int = 0,
    warm_start: bool = False,
    start: bool = True,
    prefill_budget: Optional[int] = None,
    tenants=None,
    kv_global_index: Optional[bool] = None,
    journal=None,
):
    """Construct the disaggregated serving gateway (``serve.py --disagg``):
    a pool of ``prefill_replicas`` :class:`~lzy_tpu.serving.PrefillEngine`
    replicas feeding KV blocks over the channels transport to a pool of
    ``decode_replicas`` :class:`~lzy_tpu.serving.DecodeEngine` replicas
    behind one ``InferGenerate`` endpoint. Both pools are paged by
    construction (KV blocks are the transfer unit). Autoscaling applies
    to the decode pool; the prefill pool is held at its configured size
    by the tick (dead replicas re-leased). ``spec_tokens`` > 0 enables
    draft-free speculative decoding on the DECODE pool (prefill replicas
    never decode, so the flag does not reach them); ``warm_start``
    AOT-compiles decode/verify at replica boot.
    """
    from lzy_tpu.gateway import (
        Autoscaler, DisaggGatewayService, PrefixAffinityRouter,
        ReplicaFleet, RoundRobinRouter)
    from lzy_tpu.serving import DecodeEngine, PrefillEngine

    if prefill_replicas < 1 or decode_replicas < 1:
        raise ValueError(
            f"disagg needs >= 1 replica per pool, got prefill="
            f"{prefill_replicas} decode={decode_replicas}")
    if routing not in ("prefix", "rr"):
        raise ValueError(f"unknown routing {routing!r}; use prefix or rr")
    cfg, params = _build_engine_parts(model, checkpoint=checkpoint,
                                      seed=seed)
    # kv_quant/kernel apply to BOTH pools: the transfer payload is the
    # raw cache leaves, so a quantized decode pool needs the prefill
    # pool producing int8 blocks + sidecars of the same shape (a
    # mismatch degrades safely — import_kv fails closed and the prompt
    # re-prefills locally — but transfers nothing)
    # the tier rides BOTH pools: prefill replicas accumulate (and evict)
    # radix caches too, and the shared storage rung lets a decode
    # replica promote what a prefill replica demoted
    common = dict(slots=slots, max_queue=max_queue,
                  prefill_chunk=prefill_chunk, seed=seed,
                  page_size=page_size, kv_blocks=kv_blocks,
                  kv_pool_bytes=kv_pool_bytes, kv_quant=kv_quant,
                  kernel=kernel, kv_host_tier_bytes=kv_host_tier_bytes,
                  kv_storage_tier=_build_kv_storage_tier(
                      kv_storage_tier, page_size),
                  prefill_budget=prefill_budget, tenants=tenants)

    def decode_factory():
        engine = DecodeEngine(cfg, params, eos_token=eos_token,
                              spec_tokens=spec_tokens, **common)
        if warm_start:
            engine.warmup()
        return engine

    def prefill_factory():
        return PrefillEngine(cfg, params, **common)

    decode_fleet = ReplicaFleet(decode_factory, allocator=allocator,
                                pool_label=pool_label,
                                session_owner="disagg-decode",
                                replica_prefix="decode")
    prefill_fleet = ReplicaFleet(prefill_factory, allocator=allocator,
                                 pool_label=pool_label,
                                 session_owner="disagg-prefill",
                                 replica_prefix="prefill")
    router_cls = PrefixAffinityRouter if routing == "prefix" \
        else RoundRobinRouter
    autoscaler = None
    if autoscale:
        autoscaler = Autoscaler(
            min_replicas=min_replicas or decode_replicas,
            max_replicas=max_replicas or 2 * decode_replicas)
    slo = None
    if tenants is not None:
        from lzy_tpu.serving.tenancy import SloLimiter

        slo = SloLimiter(tenants)
    if kv_global_index is None:
        # same implication as the monolithic gateway: a tier without the
        # fleet-global index would warm only the replica that demoted
        kv_global_index = (kv_host_tier_bytes is not None
                           or kv_storage_tier is not None)
    kv_index = None
    if kv_global_index:
        from lzy_tpu.gateway.kv_index import GlobalKVIndex

        kv_index = GlobalKVIndex(page_size)
    service = DisaggGatewayService(
        decode_fleet,
        prefill_fleet,
        page_size=page_size,
        router=router_cls(page_size),
        prefill_router=router_cls(page_size),
        autoscaler=autoscaler,
        transport=transport,
        prefill_replicas=prefill_replicas,
        model_name=model,
        slo=slo,
        kv_index=kv_index,
        journal=journal,
    )
    try:
        for _ in range(decode_replicas):
            decode_fleet.add_replica()
        for _ in range(prefill_replicas):
            prefill_fleet.add_replica()
    except BaseException:
        service.close()
        raise
    from lzy_tpu.llm.backend import model_digest_for

    service.model_digest = model_digest_for(model, cfg,
                                            checkpoint=checkpoint,
                                            seed=seed)
    if start:
        service.start()
    return service


def build_inference_service(
    model: str,
    *,
    slots: int = 4,
    max_queue: int = 64,
    eos_token: Optional[int] = None,
    checkpoint: Optional[str] = None,
    seed: int = 0,
    prefill_chunk: Optional[int] = None,
    page_size: int = 16,
    kv_blocks: Optional[int] = None,
    kv_pool_bytes: Optional[int] = None,
    kv_quant: Optional[str] = None,
    kernel: str = "auto",
    kv_host_tier_bytes: Optional[int] = None,
    kv_storage_tier=None,
    serve_mesh: Optional[int] = None,
    spec_tokens: int = 0,
    warm_start: bool = False,
    start: bool = True,
    prefill_budget: Optional[int] = None,
    tenants=None,
) -> InferenceService:
    """Construct the engine for a named config and wrap it for RPC.

    ``model`` is one of :data:`MODEL_CONFIGS`. Without ``checkpoint`` the
    weights are random-initialized — enough for smoke tests and load
    drills; real deployments pass an Orbax export
    (``parallel.orbax_interop.export_orbax``) of the matching config.

    The engine (``serving.PagedInferenceEngine``) serves from the paged
    KV-cache pool with radix prefix caching: ``kv_blocks`` blocks of
    ``page_size`` tokens shared by all slots (default: ``max_seq_len``
    tokens a slot — size it below that to overcommit HBM, above to grow
    the prefix cache; docs/serving.md has the tradeoffs). Attention reads
    KV through the page table (``kernel``: auto/pallas/lax — ``auto`` is
    the Pallas kernels on a TPU and the portable lax read anywhere
    else); ``kv_quant="int8"`` halves pooled KV bytes (~2x blocks at
    fixed HBM, boundedly-divergent output) — docs/serving.md "Paged
    attention & KV quantization".

    ``spec_tokens`` > 0 enables draft-free speculative decoding
    (``serving/spec.py``): up to that many prompt-lookup draft tokens
    verified per decode step. ``warm_start=True`` AOT-compiles the
    decode (and verify) programs before the first request lands —
    combined with the persistent XLA compilation cache (``serve.py``
    enables it) a restarted server answers its first request without
    paying a fresh compile on TTFT.

    ``prefill_budget`` bounds prompt tokens prefilled per engine round
    (chunked-prefill interleaving — long prompts cannot starve resident
    rows), spent as one program as wide as the widest bucket under it;
    ``tenants`` (a ``serving.tenancy.TenantTable``) turns on the
    multi-tenant SLO layer: rate limits at this front, WFQ + queue caps
    + KV quotas in the engine (docs/serving.md "Multi-tenant SLO
    serving").
    """
    from lzy_tpu.serving import PagedInferenceEngine

    cfg, params = _build_engine_parts(model, checkpoint=checkpoint,
                                      seed=seed)
    engine_kw = dict(
        slots=slots, max_queue=max_queue, eos_token=eos_token,
        prefill_chunk=prefill_chunk, seed=seed, spec_tokens=spec_tokens,
        prefill_budget=prefill_budget, tenants=tenants,
        page_size=page_size, kv_blocks=kv_blocks,
        kv_pool_bytes=kv_pool_bytes, kv_quant=kv_quant, kernel=kernel,
        kv_host_tier_bytes=kv_host_tier_bytes,
        kv_storage_tier=_build_kv_storage_tier(kv_storage_tier, page_size))
    if serve_mesh is not None:
        from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine

        engine: PagedInferenceEngine = ShardedPagedInferenceEngine(
            cfg, params, tp=serve_mesh, **engine_kw)
    else:
        engine = PagedInferenceEngine(cfg, params, **engine_kw)
    if warm_start:
        engine.warmup()
    if start:
        engine.start()
    slo = None
    if tenants is not None:
        from lzy_tpu.serving.tenancy import SloLimiter

        slo = SloLimiter(tenants)
    service = InferenceService(engine, model_name=model, slo=slo)
    from lzy_tpu.llm.backend import model_digest_for

    service.model_digest = model_digest_for(model, cfg,
                                            checkpoint=checkpoint,
                                            seed=seed)
    return service

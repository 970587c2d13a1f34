"""Control-plane server entrypoint (the deployable binary).

What the reference runs as per-service Micronaut mains (``AllocatorMain``,
``LzyService`` etc.) deploys here as ONE process: metadata store + durable
executor + allocator + channel manager + graph executor + workflow service +
whiteboard service, served over gRPC. The container image
(``docker/Dockerfile.controlplane``) uses this as its entrypoint.

Modes (``--backend``):
- ``process`` (default): workers are OS processes on THIS host — the
  single-machine distributed mode (docs/deployment.md §3);
- ``gke``: workers are TPU pods created through the Kubernetes API
  (``GkeTpuBackend``); requires ``--worker-image`` and
  ``--advertise`` (the address pods dial back, e.g. the Service DNS name).

Example (GKE):
    python -m lzy_tpu.service.serve \\
        --db /var/lzy/meta.db --storage-uri s3://bucket/lzy \\
        --port 18700 --advertise lzy-control.lzy-tpu:18700 \\
        --backend gke --worker-image gcr.io/proj/lzy-tpu-worker:latest \\
        --with-iam
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m lzy_tpu.service.serve",
        description="Serve the lzy-tpu control plane over gRPC.",
    )
    parser.add_argument("--db", default="/var/lzy/meta.db",
                        help="metadata store path (SQLite)")
    parser.add_argument("--storage-uri", required=True,
                        help="durable storage root (s3:// or file://)")
    parser.add_argument("--port", type=int, default=18700)
    parser.add_argument("--backend", choices=("process", "gke"),
                        default="process")
    parser.add_argument("--worker-image", default=None,
                        help="worker image for --backend gke "
                             "(docker/Dockerfile.worker)")
    parser.add_argument("--advertise", default=None,
                        help="address workers dial back (pod Service DNS on "
                             "gke; defaults to 127.0.0.1:<port>)")
    parser.add_argument("--namespace", default="lzy-tpu")
    parser.add_argument("--service-account", default=None)
    parser.add_argument("--with-iam", action="store_true",
                        help="enforce authentication (mint subjects with "
                             "`python -m lzy_tpu auth`)")
    parser.add_argument("--debug-rpc", action="store_true",
                        help="expose the fault-injection surface (never in "
                             "production)")
    parser.add_argument("--gc-period-s", type=float, default=300.0)
    parser.add_argument("--serve-model", default=None,
                        help="also serve an LLM from this process: a named "
                             "config (tiny, llama3_8b, llama3_70b) exposed "
                             "as InferGenerate/InferStats on the same gRPC "
                             "port (docs/serving.md)")
    parser.add_argument("--model-checkpoint", default=None,
                        help="Orbax export to restore --serve-model weights "
                             "from (random init without it)")
    parser.add_argument("--serve-slots", type=int, default=4,
                        help="continuous-batching decode slots")
    parser.add_argument("--serve-queue", type=int, default=64,
                        help="admission queue depth (beyond it requests are "
                             "shed with UNAVAILABLE)")
    parser.add_argument("--serve-eos-token", type=int, default=None,
                        help="token id that terminates generation early")
    parser.add_argument("--serve-page-size", type=int, default=64,
                        help="tokens per KV block of the paged pool "
                             "(must divide the model's max_seq_len)")
    parser.add_argument("--serve-kv-blocks", type=int, default=None,
                        help="KV block pool size (default: max_seq_len "
                             "tokens a slot; smaller overcommits HBM, "
                             "larger grows the prefix cache)")
    parser.add_argument("--serve-kv-pool-mb", type=int, default=None,
                        help="size the KV block pool by payload byte "
                             "budget instead of --serve-kv-blocks: "
                             "blocks = budget // per-block bytes "
                             "(kv_cache.blocks_for_bytes) — at a fixed "
                             "budget --serve-kv-quant int8 holds 2x "
                             "the blocks")
    parser.add_argument("--serve-kv-quant", choices=("int8",),
                        default=None,
                        help="KV-block quantization: "
                             "int8 stores pooled K/V at half the bytes "
                             "(~2x resident blocks at fixed HBM; output "
                             "boundedly diverges from fp — docs/"
                             "serving.md 'Paged attention & KV "
                             "quantization')")
    parser.add_argument("--kv-host-tier-mb", type=int, default=None,
                        help="tiered KV cache: radix-cache eviction "
                             "DEMOTES block payloads "
                             "to this much pinned host RAM (LRU) instead "
                             "of dropping them; admission promotes them "
                             "back — warm prefixes survive HBM pressure "
                             "(docs/serving.md 'Tiered KV cache'). On a "
                             "--gateway plane this also enables the "
                             "fleet-global prefix index: a replica that "
                             "misses a prefix a sibling holds imports the "
                             "sibling's blocks instead of re-prefilling")
    parser.add_argument("--kv-storage-tier", default=None,
                        help="storage rung of the tiered KV cache: a "
                             "storage URI (file://, mem://, s3://, "
                             "azure://) host-tier overflow spills to in "
                             "the kv_block_manifest format. Replicas "
                             "sharing the same root share the tier — "
                             "cross-replica cache warm-up after "
                             "autoscale/failover is a storage read, not "
                             "a re-prefill")
    parser.add_argument("--serve-mesh", type=int, default=None,
                        metavar="N",
                        help="serve every replica as a GANG: the "
                             "prefill/decode/verify forwards run "
                             "tensor-sharded over a 1xN device mesh "
                             "(composes with --gateway — "
                             "health/recovery treat the "
                             "gang as one replica, one dead host fails "
                             "over the whole gang). Output is "
                             "bit-identical to single-device serving "
                             "(docs/serving.md 'Sharded replicas')")
    parser.add_argument("--serve-kernel",
                        choices=("auto", "pallas", "lax"), default="auto",
                        help="how attention reads the pool through the "
                             "page table: pallas (decode kernel: reads "
                             "the live context only; compiles for a TPU), "
                             "lax (portable), auto is pallas on a TPU and "
                             "lax anywhere else and under --serve-mesh")
    parser.add_argument("--serve-spec", action="store_true",
                        help="draft-free speculative decoding: n-gram "
                             "prompt lookup proposes up to --spec-tokens "
                             "continuation tokens per greedy row, one "
                             "batched forward verifies them — up to "
                             "spec-tokens+1 tokens per decode step, "
                             "bit-identical output (docs/serving.md "
                             "'Speculative decoding')")
    parser.add_argument("--spec-tokens", type=int, default=4,
                        help="max draft tokens per verify step under "
                             "--serve-spec (gamma)")
    parser.add_argument("--serve-prefill-budget", type=int, default=256,
                        help="max prompt tokens prefilled per engine "
                             "scheduling round (chunked-prefill "
                             "interleaving: long prompts advance in "
                             "bounded chunks BETWEEN decode steps so "
                             "they cannot starve resident requests' "
                             "token streams; the width of a prefill "
                             "program follows it; 0 runs each prompt's "
                             "prefill in one round)")
    parser.add_argument("--serve-slo", action="store_true",
                        help="multi-tenant SLO enforcement: per-tenant "
                             "token-bucket rate limits and KV quotas at "
                             "admission, weighted fair queueing by "
                             "priority tier (tenant = IAM subject under "
                             "--with-iam; docs/serving.md 'Multi-tenant "
                             "SLO serving'). Implied by any --tenant-* "
                             "flag")
    parser.add_argument("--tenant-rps", type=float, default=None,
                        help="default per-tenant requests/s limit")
    parser.add_argument("--tenant-tps", type=float, default=None,
                        help="default per-tenant prompt-tokens/s limit")
    parser.add_argument("--tenant-kv-quota", type=int, default=None,
                        help="default per-tenant KV-block quota per "
                             "replica (paged engines)")
    parser.add_argument("--tenant-max-queued", type=int, default=None,
                        help="default per-tenant admission-queue cap per "
                             "replica")
    parser.add_argument("--tenant-burst-s", type=float, default=2.0,
                        help="token-bucket burst window (bucket capacity "
                             "= rate * burst)")
    parser.add_argument("--tenant-policies", default=None,
                        help="JSON file of per-tenant policy overrides: "
                             "{tenant: {priority, weight, requests_per_s, "
                             "prompt_tokens_per_s, kv_block_quota, "
                             "max_queued, burst_s}}")
    parser.add_argument("--stream-ack-window", type=int, default=1024,
                        help="streaming delivery: max tokens a consumer "
                             "may lag the producer before it counts as "
                             "stalled (bounded buffer; docs/serving.md "
                             "'Streaming delivery')")
    parser.add_argument("--stream-stall-grace-s", type=float, default=5.0,
                        help="streaming delivery: continuous stall beyond "
                             "the ack window tolerated before the slow "
                             "consumer is shed (request cancelled, slot "
                             "and KV blocks freed)")
    parser.add_argument("--stream-liveness-s", type=float, default=15.0,
                        help="streaming delivery: a stream not polled for "
                             "this long counts as a disconnected client — "
                             "its request is reaped from the queue in "
                             "place or evicted from its slot within one "
                             "decode round")
    parser.add_argument("--stream-max-sessions", type=int, default=64,
                        help="streaming delivery: concurrent stream "
                             "sessions before opens shed with a retry "
                             "hint (each session pins a worker thread)")
    parser.add_argument("--drain-timeout-s", type=float, default=30.0,
                        help="graceful-shutdown budget on SIGTERM/SIGINT: "
                             "the serving plane stops admitting, finishes "
                             "in-flight requests for up to this long, and "
                             "releases replica leases before the process "
                             "exits (0 skips the drain and closes hard)")
    parser.add_argument("--no-warm-start", action="store_true",
                        help="skip the AOT warm-up of decode/verify "
                             "programs at engine boot (first request then "
                             "pays the compile on its TTFT) and the "
                             "persistent XLA compilation cache")
    parser.add_argument("--gateway", action="store_true",
                        help="front --serve-model with the serving fleet "
                             "gateway: N engine replicas behind one "
                             "InferGenerate endpoint with prefix-affinity "
                             "routing, health/failover, and autoscaling "
                             "(docs/serving.md 'Fleet serving')")
    parser.add_argument("--replicas", type=int, default=3,
                        help="initial replica count under --gateway "
                             "(autoscaling may grow the fleet to 2x this, "
                             "or cap it with --max-replicas)")
    parser.add_argument("--max-replicas", type=int, default=None,
                        help="autoscaling ceiling under --gateway")
    parser.add_argument("--gateway-routing", choices=("prefix", "rr"),
                        default="prefix",
                        help="prefix: cache-aware routing (default); "
                             "rr: round-robin baseline")
    parser.add_argument("--gateway-pool", default="cpu-small",
                        help="allocator pool the gateway leases replica "
                             "gangs from")
    parser.add_argument("--gateway-journal", default=None, nargs="?",
                        const="auto",
                        help="control-plane crash recovery under "
                             "--gateway/--disagg: journal session "
                             "births, stream fences and replica leases "
                             "to this SQLite path (bare flag derives "
                             "<--db>.gwjournal). On boot, a journal "
                             "holding a predecessor's state is "
                             "recovered: live streams are re-submitted "
                             "as prompt + fenced tokens (the resume "
                             "token (request_id, position) keeps "
                             "working), non-resumable requests are "
                             "settled with a typed status, and stale "
                             "leases are released to the warm-gang "
                             "cache (docs/serving.md 'Control-plane "
                             "recovery')")
    parser.add_argument("--disagg", action="store_true",
                        help="disaggregated prefill/decode serving: a "
                             "prefill replica pool exports paged KV blocks "
                             "over the channels data plane to a decode "
                             "replica pool behind one InferGenerate "
                             "endpoint (docs/serving.md 'Disaggregated "
                             "prefill/decode'); implies paged engines and "
                             "gateway routing/failover semantics")
    parser.add_argument("--prefill-replicas", type=int, default=1,
                        help="prefill pool size under --disagg")
    parser.add_argument("--decode-replicas", type=int, default=2,
                        help="decode pool size under --disagg "
                             "(autoscaling may grow it; cap with "
                             "--max-replicas)")
    args = parser.parse_args(argv)

    from lzy_tpu.service import InProcessCluster

    if args.gateway and not args.serve_model:
        parser.error("--gateway requires --serve-model")
    if args.disagg and not args.serve_model:
        parser.error("--disagg requires --serve-model")
    if args.disagg and args.gateway:
        parser.error("--disagg IS a gateway mode; pass one or the other")
    if args.serve_kv_pool_mb is not None and args.serve_kv_blocks is not None:
        parser.error("pass --serve-kv-blocks or --serve-kv-pool-mb, "
                     "not both")
    if args.serve_mesh is not None:
        if args.serve_mesh < 2:
            parser.error("--serve-mesh needs N >= 2 (a 1-device mesh is "
                         "the engine without it)")
        if args.disagg:
            parser.error("--serve-mesh does not compose with --disagg "
                         "yet; use --gateway")
        if args.serve_kernel == "pallas":
            parser.error("--serve-kernel pallas cannot serve sharded "
                         "(custom calls do not partition); use lax")

    warm_start = bool(args.serve_model) and not args.no_warm_start
    spec_tokens = args.spec_tokens if args.serve_spec else 0
    kv_pool_bytes = (args.serve_kv_pool_mb * (1 << 20)
                     if args.serve_kv_pool_mb is not None else None)
    kv_host_tier_bytes = (args.kv_host_tier_mb * (1 << 20)
                          if args.kv_host_tier_mb is not None else None)
    prefill_budget = args.serve_prefill_budget or None
    tenants = None
    slo_on = args.serve_slo or any(
        v is not None for v in (args.tenant_rps, args.tenant_tps,
                                args.tenant_kv_quota,
                                args.tenant_max_queued)) \
        or args.tenant_policies
    if args.serve_model and slo_on:
        import json as _json

        from lzy_tpu.serving.tenancy import TenantPolicy, TenantTable

        default = TenantPolicy(
            requests_per_s=args.tenant_rps,
            prompt_tokens_per_s=args.tenant_tps,
            kv_block_quota=args.tenant_kv_quota,
            max_queued=args.tenant_max_queued,
            burst_s=args.tenant_burst_s)
        doc = {}
        if args.tenant_policies:
            with open(args.tenant_policies) as fh:
                doc = _json.load(fh)
        tenants = TenantTable.from_doc(doc, default=default)
    if warm_start:
        # the warm start's persistent half: a restarted server reads its
        # decode/prefill/verify programs back instead of compiling them on
        # the first requests' TTFT (must precede the first compile)
        from lzy_tpu.utils.jaxenv import enable_compile_cache

        enable_compile_cache()

    if args.gateway_journal and not (args.gateway or args.disagg):
        parser.error("--gateway-journal needs a fleet front "
                     "(--gateway or --disagg)")
    journal = None
    predecessor_leases = None
    if args.gateway_journal:
        from lzy_tpu.durable.store import OperationStore
        from lzy_tpu.gateway.journal import GatewayJournal

        journal_path = (args.gateway_journal
                        if args.gateway_journal != "auto"
                        else args.db + ".gwjournal")
        journal = GatewayJournal(OperationStore(journal_path))
        # snapshot the PREDECESSOR's lease rows NOW: the fresh fleet's
        # add_replica journals its own leases under the same
        # replica-1..N keys, overwriting these before recovery runs
        predecessor_leases = journal.leases()

    inference_service = None
    inference_factory = None
    if args.serve_model and args.disagg:
        from lzy_tpu.service.inference import build_disagg_gateway_service

        # factory for the same reason as --gateway below: the two pools
        # lease through the cluster's allocator, which exists only once
        # the cluster is up
        def inference_factory(cluster):
            return build_disagg_gateway_service(
                args.serve_model,
                prefill_replicas=args.prefill_replicas,
                decode_replicas=args.decode_replicas,
                max_replicas=args.max_replicas,
                slots=args.serve_slots,
                max_queue=args.serve_queue,
                eos_token=args.serve_eos_token,
                checkpoint=args.model_checkpoint,
                page_size=args.serve_page_size,
                kv_blocks=args.serve_kv_blocks,
                kv_pool_bytes=kv_pool_bytes,
                kv_quant=args.serve_kv_quant,
                kernel=args.serve_kernel,
                kv_host_tier_bytes=kv_host_tier_bytes,
                kv_storage_tier=args.kv_storage_tier,
                routing=args.gateway_routing,
                allocator=cluster.allocator,
                pool_label=args.gateway_pool,
                spec_tokens=spec_tokens,
                warm_start=warm_start,
                prefill_budget=prefill_budget,
                tenants=tenants,
                journal=journal,
            )
    elif args.serve_model and args.gateway:
        from lzy_tpu.service.inference import build_gateway_service

        # built via factory so the fleet can lease its replicas through
        # the cluster's allocator (which exists only once the cluster is
        # up); the gateway then rides the same RPC routes a single engine
        # would
        def inference_factory(cluster):
            return build_gateway_service(
                args.serve_model,
                replicas=args.replicas,
                max_replicas=args.max_replicas,
                slots=args.serve_slots,
                max_queue=args.serve_queue,
                eos_token=args.serve_eos_token,
                checkpoint=args.model_checkpoint,
                page_size=args.serve_page_size,
                kv_blocks=args.serve_kv_blocks,
                kv_pool_bytes=kv_pool_bytes,
                kv_quant=args.serve_kv_quant,
                kernel=args.serve_kernel,
                kv_host_tier_bytes=kv_host_tier_bytes,
                kv_storage_tier=args.kv_storage_tier,
                serve_mesh=args.serve_mesh,
                routing=args.gateway_routing,
                allocator=cluster.allocator,
                pool_label=args.gateway_pool,
                spec_tokens=spec_tokens,
                warm_start=warm_start,
                prefill_budget=prefill_budget,
                tenants=tenants,
                journal=journal,
            )
    elif args.serve_model:
        from lzy_tpu.service.inference import build_inference_service

        inference_service = build_inference_service(
            args.serve_model,
            slots=args.serve_slots,
            max_queue=args.serve_queue,
            eos_token=args.serve_eos_token,
            checkpoint=args.model_checkpoint,
            page_size=args.serve_page_size,
            kv_blocks=args.serve_kv_blocks,
            kv_pool_bytes=kv_pool_bytes,
            kv_quant=args.serve_kv_quant,
            kernel=args.serve_kernel,
            kv_host_tier_bytes=kv_host_tier_bytes,
            kv_storage_tier=args.kv_storage_tier,
            serve_mesh=args.serve_mesh,
            spec_tokens=spec_tokens,
            warm_start=warm_start,
            prefill_budget=prefill_budget,
            tenants=tenants,
        )

    backend = None
    if args.backend == "gke":
        if not args.worker_image:
            parser.error("--backend gke requires --worker-image")
        from lzy_tpu.service.backends import GkeTpuBackend

        backend = GkeTpuBackend(
            control_address=args.advertise or f"127.0.0.1:{args.port}",
            storage_uri=args.storage_uri,
            image=args.worker_image,
            namespace=args.namespace,
            service_account=args.service_account,
        )

    cluster = InProcessCluster(
        db_path=args.db,
        storage_uri=args.storage_uri,
        with_iam=args.with_iam,
        backend=backend,
        worker_mode="process" if backend is None else "thread",
        rpc_port=args.port,
        debug_rpc=args.debug_rpc,
        gc_period_s=args.gc_period_s,
        inference_service=inference_service,
        inference_factory=inference_factory,
    )
    # streaming-delivery knobs (the session manager is built with
    # library defaults; the flags are the deployment's word)
    serving_now = cluster.inference_service or inference_service
    if serving_now is not None and hasattr(serving_now, "streams"):
        streams = serving_now.streams
        streams.ack_window = args.stream_ack_window
        streams.stall_grace_s = args.stream_stall_grace_s
        streams.liveness_timeout_s = args.stream_liveness_s
        streams.max_sessions = args.stream_max_sessions
    if journal is not None and serving_now is not None:
        # boot-time crash recovery: a journal holding a predecessor's
        # state restores it BEFORE the port starts answering. With
        # in-process engines there is nothing to re-adopt across a
        # process death (engine_source=None: stale leases are released
        # to the warm-gang session cache instead); live streams are
        # re-submitted at their journaled fences onto the fresh fleet,
        # so a client's old resume token answers on this process.
        from lzy_tpu.gateway.recovery import recover_gateway

        try:
            report = recover_gateway(serving_now, engine_source=None,
                                     allocator=cluster.allocator,
                                     leases=predecessor_leases)
            if report.resubmitted or report.orphaned or \
                    report.rehydrated_terminal or report.dropped_leases:
                print(f"gateway journal recovered: "
                      f"{len(report.resubmitted)} stream(s) resumed at "
                      f"their fences, "
                      f"{len(report.rehydrated_terminal)} terminal "
                      f"stream(s) rehydrated (lost-final-frame window), "
                      f"{len(report.orphaned)} unary request(s) "
                      f"orphaned, {len(report.dropped_leases)} stale "
                      f"lease(s) released", flush=True)
        except Exception as e:  # noqa: BLE001 — serve anyway
            print(f"gateway journal recovery failed ({e}); serving "
                  f"with a fresh control plane", flush=True)

    if args.serve_model:
        from lzy_tpu.utils.jaxenv import device_line

        print(f"serving engines on {device_line()}", flush=True)
    server = cluster.serve(args.port)
    model = f", model={args.serve_model}" if args.serve_model else ""
    if args.gateway:
        model += (f", gateway={args.replicas}x"
                  f" ({args.gateway_routing} routing)")
    if args.disagg:
        model += (f", disagg={args.prefill_replicas}p/"
                  f"{args.decode_replicas}d"
                  f" ({args.gateway_routing} routing)")
    print(f"lzy-tpu control plane serving on {server.address} "
          f"(backend={args.backend}, "
          f"iam={'on' if args.with_iam else 'off'}{model})",
          flush=True)

    stop = threading.Event()

    def handle(signum, frame):
        print(f"signal {signum}; shutting down", flush=True)
        stop.set()

    signal.signal(signal.SIGTERM, handle)
    signal.signal(signal.SIGINT, handle)
    stop.wait()
    # graceful drain: stop admission, finish in-flight rows, release
    # leases — THEN tear the cluster down. cluster.inference_service
    # also covers the factory-built gateway/disagg services.
    serving = cluster.inference_service or inference_service
    if serving is not None:
        if args.drain_timeout_s > 0 and hasattr(serving, "drain"):
            print(f"draining serving plane (up to "
                  f"{args.drain_timeout_s:g}s)", flush=True)
            serving.drain(args.drain_timeout_s)
        else:
            serving.close()
    cluster.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

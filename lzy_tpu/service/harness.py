"""In-process cluster harness.

Counterpart of the reference's ``LzyInThread``
(``test-context/src/main/java/ai/lzy/test/context/LzyInThread.java:14-70``),
which boots every service in one JVM for multi-node semantics without a
cluster: one metadata store + durable executor + allocator (thread VMs) +
channel manager + graph executor + workflow service, and an ``lzy()`` factory
returning a fully wired SDK facade on the RemoteRuntime. This is also the
local single-machine deployment mode, not just a test rig.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

from lzy_tpu.channels.manager import ChannelManager
from lzy_tpu.core.lzy import Lzy
from lzy_tpu.durable.pg_store import store_for
from lzy_tpu.durable import OperationsExecutor, OperationStore
from lzy_tpu.serialization import default_registry
from lzy_tpu.service.allocator import AllocatorService
from lzy_tpu.service.backends import ThreadVmBackend
from lzy_tpu.service.graph_executor import GraphExecutor
from lzy_tpu.service.workflow_service import WorkflowService
from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig
from lzy_tpu.utils.clock import SYSTEM_CLOCK
from lzy_tpu.storage.registry import client_for
from lzy_tpu.types import PoolSpec, TpuPoolSpec, VmSpec


class LeaderLeaseHeld(RuntimeError):
    """Another control-plane process holds this store's leader lease."""

DEFAULT_POOLS: List[PoolSpec] = [
    # CPU default mirrors the reference's 4 vCPU / 32 GB pool
    # (docs/tutorials/3-basics.md:42); TPU pools per BASELINE configs
    VmSpec(label="cpu-small", cpu_count=4, ram_gb=32),
    VmSpec(label="cpu-large", cpu_count=16, ram_gb=128),
    TpuPoolSpec(label="tpu-v5e-8", tpu_type="v5e", topology="2x4"),
    TpuPoolSpec(label="tpu-v5e-16", tpu_type="v5e", topology="4x4"),
    TpuPoolSpec(label="tpu-v5e-64", tpu_type="v5e", topology="8x8"),
]


class InProcessCluster:
    def __init__(
        self,
        *,
        storage_uri: str = "mem://cluster",
        db_path: str = ":memory:",
        pools: Optional[Sequence[PoolSpec]] = None,
        workers: int = 4,
        max_running_tasks: int = 8,
        poll_period_s: float = 0.02,
        vm_boot_delay_s: float = 0.0,
        p2p_spill_root: Optional[str] = None,
        with_iam: bool = False,
        container_runtime="auto",         # forwarded to thread workers
        worker_mode: str = "thread",      # "thread" | "process"
        worker_pythonpath: Optional[str] = None,
        rpc_port: int = 0,                # fixed port lets workers reconnect
        debug_rpc: bool = False,          # expose fault-injection over RPC
        gc_period_s: Optional[float] = None,   # background GC timer
        execution_ttl_s: float = 86_400.0,     # stale-execution reap age
        backend=None,                     # explicit VmBackend (e.g. GKE)
        leader_lease_ttl_s: float = 30.0,      # control-plane leader lease
        inference_service=None,           # serving plane (serve --serve-model)
        inference_factory=None,           # callable(cluster) -> service;
                                          # runs AFTER the allocator exists so
                                          # a gateway fleet (serve --gateway)
                                          # can lease replicas through it
    ):
        self._rpc_port = rpc_port
        self.storage_uri = storage_uri
        self.store = store_for(db_path)
        # Exactly one control-plane process may drive a given metadata
        # store: the mutating paths are in-process read-modify-write (the
        # reference runs replicated services against Postgres with leader-
        # leased GC; the analog here is a CAS lease row in the shared
        # store). A second plane on the same db fails LOUDLY at boot
        # instead of corrupting, and can take over once the lease expires
        # (crash) or is released (clean shutdown). See docs/deployment.md.
        import uuid as _uuid

        self._lease_owner = f"plane-{os.getpid()}-{_uuid.uuid4().hex[:8]}"
        self._lease_ttl = leader_lease_ttl_s
        self._lease_stop = None
        self._lease_acquired = False
        self.fenced = False
        if db_path != ":memory:":
            if not self.store.try_acquire_lease(
                    "control-plane", self._lease_owner, self._lease_ttl):
                holder = self.store.lease_holder("control-plane")
                self.store.close()
                raise LeaderLeaseHeld(
                    f"metadata store {db_path!r} is already driven by "
                    f"control plane {holder[0] if holder else '?'} (lease "
                    f"expires in "
                    f"{holder[1] - SYSTEM_CLOCK.time():.0f}s); "
                    f"exactly one plane "
                    f"per store — stop it, or wait for its lease to lapse"
                    if holder else
                    f"could not acquire the control-plane lease on "
                    f"{db_path!r}")
            self._lease_acquired = True
            # renewal starts IMMEDIATELY (a slow construction must not let
            # the lease lapse mid-boot — split-brain window); _fence()
            # guards attributes that construction has not assigned yet
            import threading as _threading

            self._lease_stop = _threading.Event()

            def renew_loop():
                while not self._lease_stop.wait(self._lease_ttl / 3):
                    if not self.store.renew_lease(
                            "control-plane", self._lease_owner,
                            self._lease_ttl):
                        self._fence()
                        return

            self._lease_thread = _threading.Thread(
                target=renew_loop, name="leader-lease", daemon=True)
            self._lease_thread.start()
        # a constructor failure must release the lease (and stop renewing)
        # or every retry in this process would see LeaderLeaseHeld forever
        try:
            self._init_services(
                storage_uri=storage_uri, pools=pools, workers=workers,
                max_running_tasks=max_running_tasks,
                poll_period_s=poll_period_s,
                vm_boot_delay_s=vm_boot_delay_s,
                p2p_spill_root=p2p_spill_root, with_iam=with_iam,
                container_runtime=container_runtime, worker_mode=worker_mode,
                worker_pythonpath=worker_pythonpath, debug_rpc=debug_rpc,
                gc_period_s=gc_period_s, execution_ttl_s=execution_ttl_s,
                backend=backend, inference_service=inference_service,
                inference_factory=inference_factory,
            )
        except BaseException:
            if self._lease_acquired:
                self._lease_stop.set()
                self._lease_thread.join(timeout=5.0)
                try:
                    self.store.release_lease("control-plane",
                                             self._lease_owner)
                except Exception:  # noqa: BLE001 — best-effort unwind
                    pass
            raise
        if self.fenced:
            # the lease was lost WHILE construction ran: _fence() fired
            # before these components existed, so fence again now that
            # they do, and refuse to hand out a split-brain plane
            self._fence()
            if getattr(self, "_gc_thread", None) is not None:
                self._gc_thread.join(timeout=5.0)
            self.store.close()
            raise LeaderLeaseHeld(
                "control-plane lease lost during construction — another "
                "plane took over; this instance is fenced")

    def _init_services(self, *, storage_uri, pools, workers,
                       max_running_tasks, poll_period_s, vm_boot_delay_s,
                       p2p_spill_root, with_iam, container_runtime,
                       worker_mode, worker_pythonpath, debug_rpc,
                       gc_period_s, execution_ttl_s, backend,
                       inference_service=None, inference_factory=None):
        self.executor = OperationsExecutor(self.store, workers=workers)
        self.channels = ChannelManager(store=self.store)
        self.serializers = default_registry()
        self.storage_client = client_for(StorageConfig(uri=storage_uri))
        self.rpc_server = None
        if backend is not None:
            # cloud deployments pass a ready backend (GkeTpuBackend) whose
            # workers dial back over the network; worker_mode is ignored
            self.backend = backend
        elif worker_mode == "process":
            from lzy_tpu.service.backends import ProcessVmBackend

            if storage_uri.startswith("mem://"):
                raise ValueError(
                    "process workers need cross-process storage (file:// or "
                    "s3://), not mem://"
                )
            self.backend = ProcessVmBackend(
                control_address_factory=lambda: self.rpc_server.address,
                storage_uri=storage_uri,
                spill_root=p2p_spill_root,
                extra_pythonpath=worker_pythonpath,
                # a plane that serves a model in-process holds the chip;
                # its workers must not ask for it (docs/deployment.md)
                worker_platform="cpu" if (
                    inference_service is not None
                    or inference_factory is not None) else None,
            )
        else:
            self.backend = ThreadVmBackend(
                self.channels, self.storage_client, self.serializers,
                launch_delay_s=vm_boot_delay_s, spill_root=p2p_spill_root,
                container_runtime=container_runtime,
            )
        self.iam = None
        if with_iam:
            from lzy_tpu.iam import IamService

            self.iam = IamService(self.store)
        # disk subsystem: local directory-backed disks next to the metadata
        # store (the PVC manager replaces this in a GKE deployment)
        import tempfile

        from lzy_tpu.service.disks import DiskService, LocalDiskManager

        self.disks = DiskService(
            self.store, self.executor,
            LocalDiskManager(tempfile.mkdtemp(prefix="lzy-disks-")),
        )
        self.allocator = AllocatorService(
            self.store, self.executor, self.backend, pools or DEFAULT_POOLS,
            iam=self.iam, disks=self.disks,
        )
        self.backend.allocator = self.allocator
        self.graph_executor = GraphExecutor(
            self.store, self.executor, self.allocator, self.channels,
            max_running_tasks=max_running_tasks, poll_period_s=poll_period_s,
        )
        self.workflow_service = WorkflowService(
            self.store, self.executor, self.allocator, self.channels,
            self.graph_executor, self.storage_client, iam=self.iam,
        )
        from lzy_tpu.service.whiteboard_service import WhiteboardService
        from lzy_tpu.whiteboards.index import WhiteboardIndex

        self.whiteboard_index = WhiteboardIndex(self.storage_client,
                                                storage_uri)
        self.whiteboard_service = WhiteboardService(
            self.whiteboard_index, iam=self.iam,
        )
        self._debug_rpc = debug_rpc
        # serving plane: the ControlPlaneServer registers the inference
        # surface when this is set, and the cluster's IAM guards it like
        # every other route (wired here so the service never runs open on
        # an IAM-enabled plane)
        self.inference_service = inference_service
        # a factory builds the service against the LIVE cluster — the
        # multi-replica gateway fleet leases replicas through this
        # cluster's allocator. It must run AFTER the RPC server exists
        # (below): with a process backend the leased workers dial back to
        # that server to register, so building the fleet first would
        # deadlock the lease. The server registers the inference routes
        # when either the service or the pending factory is present, and
        # resolves the service at call time.
        self._inference_factory = (
            inference_factory if inference_service is None else None)
        if (inference_service is not None
                and getattr(inference_service, "iam", None) is None):
            inference_service.iam = self.iam
        if worker_mode == "process":
            from lzy_tpu.rpc import ControlPlaneServer

            self.rpc_server = ControlPlaneServer(self, port=self._rpc_port,
                                                 debug=debug_rpc)
        if self._inference_factory is not None:
            self.inference_service = self._inference_factory(self)
            if getattr(self.inference_service, "iam", None) is None:
                self.inference_service.iam = self.iam
        # background GC (the reference runs GarbageCollector timers inside
        # each service; here one timer covers allocator + executions)
        self._gc_stop = None
        self._gc_thread = None
        if gc_period_s is not None:
            import threading

            self._gc_stop = threading.Event()

            def gc_loop():
                while not self._gc_stop.wait(gc_period_s):
                    try:
                        self.allocator.gc_tick()
                        self.workflow_service.gc_tick(ttl_s=execution_ttl_s)
                    except Exception:  # noqa: BLE001 — GC must never die
                        import logging

                        logging.getLogger(__name__).exception("gc tick failed")

            self._gc_thread = threading.Thread(target=gc_loop,
                                               name="cluster-gc", daemon=True)
            self._gc_thread.start()

    def serve(self, port: int = 0):
        """Expose the control plane over gRPC (for remote SDK clients); with
        worker_mode="process" a server is already running. ``port`` defaults
        to the constructor's ``rpc_port``."""
        port = port or self._rpc_port
        if self.rpc_server is not None:
            if port not in (0, self.rpc_server.port):
                raise RuntimeError(
                    f"control plane already serving on port "
                    f"{self.rpc_server.port}; cannot rebind to {port}"
                )
            return self.rpc_server
        from lzy_tpu.rpc import ControlPlaneServer

        self.rpc_server = ControlPlaneServer(self, port=port,
                                             debug=self._debug_rpc)
        return self.rpc_server

    @property
    def client(self) -> WorkflowService:
        """In-process 'stub': same method surface a gRPC client would have."""
        return self.workflow_service

    def lzy(self, *, user: str = "test-user", token: Optional[str] = None,
            stream_logs: bool = False, poll_period_s: float = 0.02) -> Lzy:
        from lzy_tpu.runtime.remote import RemoteRuntime  # avoid import cycle
        storage = DefaultStorageRegistry()
        storage.register_storage(
            "default", StorageConfig(uri=self.storage_uri), default=True
        )
        return Lzy(
            runtime=RemoteRuntime(
                self.client, user=user, token=token,
                poll_period_s=poll_period_s, stream_logs=stream_logs,
            ),
            storage_registry=storage,
            serializer_registry=self.serializers,
        )

    def resume_pending_operations(self) -> int:
        """Crash-recovery entry: re-enqueue all RUNNING durable ops
        (``LzyService.restartNotCompletedOps`` parity)."""
        return self.executor.restore()

    def _fence(self) -> None:
        """Leader lease lost (we stalled past the TTL and a successor took
        over): stop mutating the shared store NOW. Detection without
        enforcement would be split-brain — the successor is already
        reclaiming our durable ops, so our RPC surface, executor and GC
        must go dark; in-flight work is the successor's to re-drive."""
        import logging

        logging.getLogger(__name__).error(
            "control-plane lease lost — another plane took over; fencing: "
            "stopping RPC server, executor and GC on this plane")
        self.fenced = True
        # getattr-guarded: renewal runs from the moment the lease is taken,
        # so a (pathological) loss DURING construction fences whatever
        # exists so far; __init__ re-checks self.fenced once construction
        # completes and fences the rest (raising LeaderLeaseHeld)
        if getattr(self, "_gc_stop", None) is not None:
            self._gc_stop.set()
        try:
            if getattr(self, "rpc_server", None) is not None:
                self.rpc_server.stop()
        except Exception:  # noqa: BLE001 — fencing is best-effort teardown
            logging.getLogger(__name__).exception("fencing: rpc stop failed")
        try:
            if getattr(self, "executor", None) is not None:
                self.executor.shutdown()
        except Exception:  # noqa: BLE001 — fencing is best-effort teardown
            logging.getLogger(__name__).exception(
                "fencing: executor stop failed")

    def shutdown(self) -> None:
        if self._lease_stop is not None:
            self._lease_stop.set()
            self._lease_thread.join(timeout=5.0)
        if self._gc_stop is not None:
            # stop AND join: an in-flight tick must not race VM destruction
            # below or outlive the store it reads
            self._gc_stop.set()
            self._gc_thread.join(timeout=10.0)
            if self._gc_thread.is_alive():
                import logging

                logging.getLogger(__name__).warning(
                    "gc thread still running after 10s; teardown may race it"
                )
        for vm in list(self.allocator.vms()):
            try:
                self.backend.destroy(vm)
            except Exception:
                pass
        if self.inference_service is not None:
            # stop the engine loop before the RPC server: a decode thread
            # outliving the plane would keep finishing requests nobody can
            # collect
            try:
                self.inference_service.close()
            except Exception:
                pass
        if self.rpc_server is not None:
            self.rpc_server.stop()
        self.executor.shutdown()
        if self._lease_stop is not None:
            # clean handover: release so a successor boots immediately
            # instead of waiting out the TTL. LAST mutation before close —
            # releasing any earlier would let the successor start writing
            # while this plane's GC/VM/executor teardown is still mutating
            try:
                self.store.release_lease("control-plane", self._lease_owner)
            except Exception:  # noqa: BLE001 — store may already be closed
                pass
        self.store.close()

"""VM backends.

``ThreadVmBackend`` — the reference's ``ThreadVmAllocator``
(``lzy/allocator/.../alloc/impl/ThreadVmAllocator.java:30``) promoted to a
first-class local backend: a "VM" is a worker agent running in this process.
It powers LocalRuntime-grade dev loops, the in-process cluster harness, and all
tests.

``GkeTpuBackend`` — the production path skeleton: provisions TPU slice node
pools / pod slices via the Kubernetes API the way ``KuberVmAllocator``
(``alloc/impl/kuber/KuberVmAllocator.java:47``) creates VM pods. Gated on a
kubernetes client being importable; the control-plane contract (launch →
worker registers → heartbeats) is identical to the thread backend, which is
what the rest of the system is tested against.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional

from lzy_tpu.channels.manager import ChannelManager
from lzy_tpu.serialization import SerializerRegistry
from lzy_tpu.service.allocator import Vm, VmBackend
from lzy_tpu.service.worker import WorkerAgent
from lzy_tpu.storage.api import StorageClient
from lzy_tpu.types import PoolSpec
from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)


def _bootstrap_token(allocator, vm: Vm) -> Optional[str]:
    """Launch credential for out-of-process workers: a fresh OTT per launch
    when the plane runs IAM, else nothing. Falls back to the durable token
    only for allocators without the mint hook (test doubles)."""
    if allocator is not None and hasattr(allocator, "mint_bootstrap_token"):
        return allocator.mint_bootstrap_token(vm.id)
    return vm.worker_token


class ThreadVmBackend(VmBackend):
    def __init__(
        self,
        channels: ChannelManager,
        storage_client: StorageClient,
        serializers: Optional[SerializerRegistry] = None,
        *,
        heartbeat_period_s: float = 1.0,
        launch_delay_s: float = 0.0,      # simulate boot latency in tests
        spill_root: Optional[str] = None,  # per-VM dirs; enables native p2p
        container_runtime="auto",          # forwarded to WorkerAgent
    ):
        self._channels = channels
        self._storage = storage_client
        self._serializers = serializers
        self._heartbeat_period_s = heartbeat_period_s
        self._launch_delay_s = launch_delay_s
        self._spill_root = spill_root
        self._container_runtime = container_runtime
        self._agents: Dict[str, WorkerAgent] = {}
        self._lock = threading.Lock()
        self.allocator = None             # wired by the harness after both exist

    def launch(self, vm: Vm, pool: PoolSpec) -> None:
        # idempotent: a durable-op resume may re-request hosts already booting
        with self._lock:
            if vm.id in self._agents:
                return
            self._agents[vm.id] = None  # booking marker

        def boot() -> None:
            if self._launch_delay_s:
                from lzy_tpu.utils.clock import SYSTEM_CLOCK

                SYSTEM_CLOCK.sleep(self._launch_delay_s)
            spill = None
            if self._spill_root is not None:
                spill = os.path.join(self._spill_root, vm.id)
            agent = WorkerAgent(
                vm.id,
                allocator=self.allocator,
                channels=self._channels,
                storage_client=self._storage,
                serializers=self._serializers,
                heartbeat_period_s=self._heartbeat_period_s,
                spill_root=spill,
                container_runtime=self._container_runtime,
            )
            with self._lock:
                self._agents[vm.id] = agent
            try:
                agent.start()
            except KeyError:
                # allocation was rolled back while booting
                agent.stop()
                with self._lock:
                    self._agents.pop(vm.id, None)

        threading.Thread(target=boot, name=f"boot-{vm.id}", daemon=True).start()

    def destroy(self, vm: Vm) -> None:
        with self._lock:
            agent = self._agents.pop(vm.id, None)
        if agent is not None:
            agent.stop()


class ProcessVmBackend(VmBackend):
    """Each VM is a real OS process running ``lzy_tpu.rpc.worker_main`` — its
    own interpreter and JAX runtime, talking to the control plane over gRPC
    (the local analog of the reference's one-worker-binary-per-VM model, and
    the template a cloud backend follows with pods instead of processes).

    One process for each chip: workers run on the CPU unless the caller's
    environment names a platform, and ``worker_platform`` overrides even
    that — a control plane that serves a model in-process holds the chip,
    so it passes ``"cpu"`` and its workers never ask for it. Each worker
    logs the platform it was given when it registers. Who owns the chip
    when an ``@op(tpu=...)`` runs under ``--backend process`` on a TPU host
    is an open question (ROADMAP)."""

    def __init__(self, *, control_address_factory: Callable[[], str],
                 storage_uri: str, spill_root: Optional[str] = None,
                 extra_pythonpath: Optional[str] = None,
                 worker_platform: Optional[str] = None):
        self._control_address_factory = control_address_factory
        self._storage_uri = storage_uri
        self._spill_root = spill_root
        self._extra_pythonpath = extra_pythonpath
        self._worker_platform = worker_platform
        self._procs: Dict[str, "object"] = {}
        self._lock = threading.Lock()
        self.allocator = None

    def launch(self, vm: Vm, pool: PoolSpec) -> None:
        import pathlib
        import subprocess
        import sys

        with self._lock:
            if vm.id in self._procs:
                return  # idempotent across durable-op resume
            self._procs[vm.id] = None
        repo_root = str(pathlib.Path(__file__).resolve().parents[2])
        env = dict(os.environ)
        pypath = [repo_root]
        if self._extra_pythonpath:
            pypath.append(self._extra_pythonpath)
        if env.get("PYTHONPATH"):
            pypath.append(env["PYTHONPATH"])
        env["PYTHONPATH"] = os.pathsep.join(pypath)
        if self._worker_platform:
            env["JAX_PLATFORMS"] = self._worker_platform
        else:
            env.setdefault("JAX_PLATFORMS", "cpu")
        bootstrap = _bootstrap_token(self.allocator, vm)
        if bootstrap:
            # via env, not argv: tokens must not show up in `ps`; and a
            # one-time credential, not the durable one — registration swaps
            # it (reference OTT bootstrap)
            env["LZY_WORKER_TOKEN"] = bootstrap
        args = [
            sys.executable, "-m", "lzy_tpu.rpc.worker_main",
            "--control", self._control_address_factory(),
            "--vm-id", vm.id,
            "--storage-uri", self._storage_uri,
        ]
        if self._spill_root:
            args += ["--spill-root", os.path.join(self._spill_root, vm.id)]
        try:
            proc = subprocess.Popen(args, env=env, cwd=repo_root)
        except BaseException:
            with self._lock:
                self._procs.pop(vm.id, None)  # clear the booking marker
            raise
        with self._lock:
            self._procs[vm.id] = proc

    def destroy(self, vm: Vm) -> None:
        with self._lock:
            proc = self._procs.pop(vm.id, None)
        if proc is not None and getattr(proc, "poll", lambda: 1)() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except Exception:
                proc.kill()
                proc.wait()  # reap; an unreaped child is a zombie


class GkeTpuBackend(VmBackend):
    """Cloud path: one Vm record = one TPU host pod in a slice node pool.

    Mirrors ``KuberVmAllocator.allocate``
    (``lzy/allocator/.../kuber/KuberVmAllocator.java:84-197``): build the pod
    spec, create it through the k8s API (idempotent on 409 so durable-op
    resume never double-provisions), delete on destroy (404 tolerated), and
    reconcile leaked pods by label. The worker env/arg contract matches
    ``PodSpecBuilder.java:91-150``: the pod runs ``lzy_tpu.rpc.worker_main``
    with the control-plane address, VM id, storage, and (secret-mounted
    via env) the VM's WORKER token; registration/heartbeat then proceed
    exactly as for process workers.
    """

    def __init__(self, *, control_address: str, storage_uri: str,
                 image: str, namespace: str = "lzy-tpu",
                 api=None, service_account: Optional[str] = None,
                 spill_dir: str = "/tmp/lzy-spill"):
        from lzy_tpu.service.kube import KubeApi, KubernetesKubeApi

        self._api: "KubeApi" = api or KubernetesKubeApi()
        self._namespace = namespace
        self._image = image
        self._control_address = control_address
        self._storage_uri = storage_uri
        self._service_account = service_account
        self._spill_dir = spill_dir
        self.allocator = None

    # dynamic-mount path contract (KuberMountHolderManager parity)
    HOST_MOUNT_BASE = "/var/lib/lzy-mounts"   # per-VM dir on the node
    WORKER_MOUNT_DIR = "/mnt/lzy"             # where workers see the dir

    @staticmethod
    def pod_name(vm: Vm) -> str:
        return f"lzy-{vm.id}".lower().replace("_", "-")

    @staticmethod
    def holder_name(vm: Vm, mount_name: str) -> str:
        return f"lzy-mnt-{vm.id}-{mount_name}".lower().replace("_", "-")

    def build_pod_manifest(self, vm: Vm, pool: PoolSpec) -> dict:
        from lzy_tpu.service.kube import GKE_TPU_ACCELERATOR
        from lzy_tpu.types import TpuPoolSpec, _CHIPS_PER_HOST

        is_tpu = isinstance(pool, TpuPoolSpec) and pool.tpu_type
        env = [
            {"name": "LZY_WORKER_ADVERTISE_HOST",
             "valueFrom": {"fieldRef": {"fieldPath": "status.podIP"}}},
        ]
        bootstrap = _bootstrap_token(self.allocator, vm)
        if bootstrap:
            # one-time credential: anyone who reads this pod spec after the
            # worker registered holds a burned token (reference OTT bootstrap)
            env.append({"name": "LZY_WORKER_TOKEN", "value": bootstrap})
        container = {
            "name": "worker",
            "image": self._image,
            "args": [
                "python", "-m", "lzy_tpu.rpc.worker_main",
                "--control", self._control_address,
                "--vm-id", vm.id,
                "--storage-uri", self._storage_uri,
                "--spill-root", f"{self._spill_dir}/{vm.id}",
                "--port", "18900",
            ],
            "env": env,
            "ports": [{"containerPort": 18900, "name": "worker-api"}],
        }
        # dynamic disk mounts surface under /mnt/lzy: a mount-holder pod
        # binds each PVC into the per-VM host dir, and HostToContainer
        # propagation makes it appear here without restarting the worker
        container["volumeMounts"] = [{
            "name": "lzy-dyn-mounts",
            "mountPath": self.WORKER_MOUNT_DIR,
            "mountPropagation": "HostToContainer",
        }]
        spec: dict = {
            "containers": [container],
            "restartPolicy": "Never",
            "volumes": [{
                "name": "lzy-dyn-mounts",
                "hostPath": {"path": f"{self.HOST_MOUNT_BASE}/{vm.id}",
                             "type": "DirectoryOrCreate"},
            }],
        }
        if self._service_account:
            spec["serviceAccountName"] = self._service_account
        if is_tpu:
            chips = _CHIPS_PER_HOST[pool.tpu_type]
            spec["nodeSelector"] = {
                "cloud.google.com/gke-tpu-accelerator":
                    GKE_TPU_ACCELERATOR[pool.tpu_type],
                "cloud.google.com/gke-tpu-topology": pool.topology,
            }
            container["resources"] = {
                "requests": {"google.com/tpu": str(chips)},
                "limits": {"google.com/tpu": str(chips)},
            }
        return {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {
                "name": self.pod_name(vm),
                "labels": {
                    "lzy/vm-id": vm.id,
                    "lzy/gang-id": vm.gang_id,
                    "lzy/session-id": vm.session_id,
                    "lzy/host-index": str(vm.host_index),
                    "app.kubernetes.io/managed-by": "lzy-tpu",
                },
            },
            "spec": spec,
        }

    def launch(self, vm: Vm, pool: PoolSpec) -> None:
        from lzy_tpu.service.kube import KubeConflict, KubeNotFound

        manifest = self.build_pod_manifest(vm, pool)
        try:
            self._api.create_pod(self._namespace, manifest)
        except KubeConflict:
            # durable-op resume re-requests hosts already created — but only
            # a live pod counts: one that already terminated (ImagePull
            # failure, crashed worker; restartPolicy=Never) would stall the
            # gang until the task deadline, so recreate it
            # (KuberVmAllocator inspects the existing pod the same way)
            phase = self._pod_phase(vm)
            if phase in ("Failed", "Succeeded"):
                _LOG.warning("pod %s exists but is %s; recreating",
                             self.pod_name(vm), phase)
                try:
                    self._api.delete_pod(self._namespace, self.pod_name(vm))
                except KubeNotFound:
                    pass
                self._api.create_pod(self._namespace, manifest)
            else:
                _LOG.info("pod %s already exists (%s); resume",
                          self.pod_name(vm), phase or "phase unknown")

    def _pod_phase(self, vm: Vm) -> Optional[str]:
        for manifest in self._api.list_pods(
            self._namespace, label_selector=f"lzy/vm-id={vm.id}"
        ):
            # mount-holder pods share the vm-id label; only the worker pod's
            # phase may drive the recreate decision
            if manifest.get("metadata", {}).get("name") == self.pod_name(vm):
                return manifest.get("status", {}).get("phase")
        return None

    def mount(self, vm: Vm, disk, mount) -> str:
        """Realize a PVC-backed disk next to a RUNNING worker pod via a
        mount-holder pod (``KuberMountHolderManager`` parity): k8s cannot
        attach a volume to a live pod, so the holder mounts the claim and
        bind-mounts it into the per-VM host dir; Bidirectional propagation
        makes it visible inside the worker under ``WORKER_MOUNT_DIR``.
        Returns the worker-visible path. Idempotent per mount name."""
        from lzy_tpu.service.disks import PvcDiskManager, validate_mount_name
        from lzy_tpu.service.kube import KubeConflict

        # re-validated here: the name is embedded in a privileged shell line
        validate_mount_name(mount.mount_name)
        name = self.holder_name(vm, mount.mount_name)
        host_dir = f"{self.HOST_MOUNT_BASE}/{vm.id}"
        manifest = {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {
                "name": name,
                "labels": {
                    "lzy/vm-id": vm.id,
                    "lzy/mount-name": mount.mount_name,
                    "lzy/role": "mount-holder",
                    "app.kubernetes.io/managed-by": "lzy-tpu",
                },
            },
            "spec": {
                "restartPolicy": "Never",
                # land on the worker's node or the bind-mount is invisible
                "affinity": {"podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {
                            "matchLabels": {"lzy/vm-id": vm.id}},
                        "topologyKey": "kubernetes.io/hostname",
                    }],
                }},
                "containers": [{
                    "name": "holder",
                    "image": self._image,
                    "command": ["sh", "-c"],
                    "args": [
                        f"mkdir -p /host/{mount.mount_name} && "
                        f"mount --bind "
                        f"{'-o ro ' if mount.read_only else ''}"
                        f"/disk /host/{mount.mount_name} && "
                        f"sleep infinity"
                    ],
                    "securityContext": {"privileged": True},
                    "volumeMounts": [
                        {"name": "disk", "mountPath": "/disk"},
                        {"name": "host", "mountPath": "/host",
                         "mountPropagation": "Bidirectional"},
                    ],
                }],
                "volumes": [
                    {"name": "disk", "persistentVolumeClaim": {
                        "claimName": PvcDiskManager.claim_name(disk.id),
                        "readOnly": mount.read_only}},
                    {"name": "host", "hostPath": {
                        "path": host_dir, "type": "DirectoryOrCreate"}},
                ],
            },
        }
        try:
            self._api.create_pod(self._namespace, manifest)
        except KubeConflict:
            pass  # durable-op resume
        return f"{self.WORKER_MOUNT_DIR}/{mount.mount_name}"

    def unmount(self, vm: Vm, mount_name: str) -> None:
        from lzy_tpu.service.kube import KubeNotFound

        try:
            self._api.delete_pod(self._namespace,
                                 self.holder_name(vm, mount_name))
        except KubeNotFound:
            pass

    def destroy(self, vm: Vm) -> None:
        from lzy_tpu.service.kube import KubeNotFound

        try:
            self._api.delete_pod(self._namespace, self.pod_name(vm))
        except KubeNotFound:
            pass
        # mount-holder pods die with the VM
        for manifest in self._api.list_pods(
            self._namespace,
            label_selector=f"lzy/vm-id={vm.id},lzy/role=mount-holder",
        ):
            try:
                self._api.delete_pod(self._namespace,
                                     manifest["metadata"]["name"])
            except KubeNotFound:
                pass

    def reconcile_orphans(self, live_vm_ids) -> List[str]:
        """Delete managed pods whose VM record no longer exists (crash between
        pod creation and record cleanup) — KuberVmAllocator GC parity.
        Returns deleted pod names."""
        from lzy_tpu.service.kube import KubeNotFound

        live = set(live_vm_ids)
        deleted = []
        for manifest in self._api.list_pods(
            self._namespace,
            label_selector="app.kubernetes.io/managed-by=lzy-tpu",
        ):
            meta = manifest.get("metadata", {})
            vm_id = meta.get("labels", {}).get("lzy/vm-id")
            if vm_id and vm_id not in live:
                try:
                    self._api.delete_pod(self._namespace, meta["name"])
                    deleted.append(meta["name"])
                except KubeNotFound:
                    pass
        return deleted

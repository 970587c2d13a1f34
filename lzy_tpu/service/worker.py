"""Worker agent: the on-VM execution engine.

Counterpart of the reference worker (``lzy/worker/.../WorkerApiImpl.java:48`` —
Init/Execute with an in-process LRO service) plus the remote entrypoint
(``pylzy/lzy/api/v1/startup.py:185-229``): read inputs from channels, run the
op, write outputs/exception, pump stdout/stderr to the log plane. The
``AllocatorAgent`` register+heartbeat timer
(``allocator-api/.../AllocatorAgent.java:26-110``) is folded in.

TPU-first notes:
- inputs take the device-residency fast path when the value is already in HBM
  on this slice (ICI), falling back to the durable storage peer;
- a gang task runs SPMD: every host executes the same program. Isolated
  workers (process/pod, ``LZY_WORKER_ISOLATED``) run the full body on every
  rank — each joins the mesh via ``lzy_tpu.parallel.initialize_gang`` and
  rank 0 alone publishes outputs (proven end to end by
  ``tests/test_rpc_workers.py::test_multihost_spmd_psum_across_worker_processes``,
  a real cross-process collective). Under the in-process thread backend only
  host 0 executes the body (one process = one JAX runtime; the program would
  collide with itself) while ranks>0 participate in the gang barrier.
"""

from __future__ import annotations

import contextvars
import io
import os
import pickle
import sys
import threading
import time
import traceback
from typing import Any, Dict, Optional

from lzy_tpu.channels.manager import ChannelManager, ChannelFailed, CONSUMER, PRODUCER
from lzy_tpu.serialization import SerializerRegistry, default_registry
from lzy_tpu.service.graph import TaskDesc
from lzy_tpu.storage.api import StorageClient, join_uri
from lzy_tpu.utils.ids import gen_id
from lzy_tpu.utils.log import get_logger, logging_context

_LOG = get_logger(__name__)

# gang context visible to user code through lzy_tpu.parallel.gang_info()
_GANG: contextvars.ContextVar[Optional[Dict[str, Any]]] = contextvars.ContextVar(
    "lzy_gang", default=None
)


def current_gang() -> Optional[Dict[str, Any]]:
    return _GANG.get()


# mounts visible to op bodies: {mount_name: {"path": str, "read_only": bool}}
# (the realized form of dynamic disk mounts, MountDynamicDiskAction parity)
_MOUNTS: contextvars.ContextVar[Optional[Dict[str, Any]]] = contextvars.ContextVar(
    "lzy_mounts", default=None
)


def current_mounts() -> Dict[str, Any]:
    """Disk mounts available to the currently-executing op, keyed by the
    mount name given to ``AllocatorService.mount_disk``. Empty outside a
    mounted worker."""
    return dict(_MOUNTS.get() or {})


class _StdRouter(io.TextIOBase):
    """Thread-safe stdout/stderr tee: lines from a task thread go to that
    task's log buffer (and the real stream); other threads pass through.
    Installed once per process — the analog of the worker's Kafka log pump
    (``WorkerApiImpl.java:161-165``)."""

    _route: contextvars.ContextVar = contextvars.ContextVar("lzy_stdroute", default=None)

    def __init__(self, real):
        self._real = real

    def write(self, s: str) -> int:
        buf = self._route.get()
        if buf is not None:
            buf.write(s)
        return self._real.write(s)

    def flush(self) -> None:
        self._real.flush()

    @classmethod
    def install(cls) -> None:
        if not isinstance(sys.stdout, cls):
            sys.stdout = cls(sys.stdout)
        if not isinstance(sys.stderr, cls):
            sys.stderr = cls(sys.stderr)


class WorkerAgent:
    """One per VM/host. ``execute`` returns an operation id immediately
    (LocalOperationService parity); the graph executor polls ``status``."""

    def __init__(
        self,
        vm_id: str,
        *,
        allocator,                        # AllocatorService (register/heartbeat)
        channels: ChannelManager,
        storage_client: StorageClient,
        serializers: Optional[SerializerRegistry] = None,
        heartbeat_period_s: float = 5.0,
        spill_root: Optional[str] = None,  # enables the native p2p slot server
        advertise_host: str = "127.0.0.1", # routable address for p2p peers
        max_heartbeat_failures: Optional[int] = None,
        on_disconnected=None,              # called when the limit is reached
        container_runtime="auto",          # ContainerRuntime | None | "auto"
    ):
        self.vm_id = vm_id
        self._allocator = allocator
        self._channels = channels
        self._storage = storage_client
        self._serializers = serializers or default_registry()
        self._ops: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._owner: Optional[str] = None
        self._slot_server = None
        self._spill_root = spill_root
        self._advertise_host = advertise_host
        if spill_root is not None:
            from lzy_tpu.native import SlotServer, native_available

            os.makedirs(spill_root, exist_ok=True)
            if native_available():  # negative result is cached; boot stays fast
                self._slot_server = SlotServer(spill_root)
        self._max_heartbeat_failures = max_heartbeat_failures
        self._on_disconnected = on_disconnected
        if container_runtime == "auto":
            from lzy_tpu.env.container_runtime import default_runtime

            container_runtime = default_runtime()
        self._container_runtime = container_runtime
        self._env_realizer = None          # built lazily (isolated mode only)
        self._env_lock = threading.RLock()
        self._mounts: Dict[str, Dict[str, Any]] = {}   # name -> {path, read_only}
        self._devices_logged = False
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, args=(heartbeat_period_s,),
            name=f"hb-{vm_id}", daemon=True,
        )

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        self._allocator.register_vm(self.vm_id, self)
        self._hb_thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._slot_server is not None:
            self._slot_server.stop()
            self._slot_server = None

    # -- dynamic disk mounts (MountDynamicDiskAction parity) -------------------

    def add_mount(self, name: str, path: str, read_only: bool = False) -> None:
        """Bind a realized disk path into this worker; later-started op bodies
        see it via ``current_mounts()``. Idempotent per mount name."""
        with self._lock:
            self._mounts[name] = {"path": path, "read_only": read_only}

    def remove_mount(self, name: str) -> None:
        with self._lock:
            self._mounts.pop(name, None)

    def _heartbeat_loop(self, period_s: float) -> None:
        failures = 0
        while not self._stop.wait(period_s):
            try:
                self._allocator.heartbeat(self.vm_id)
                failures = 0
            except Exception:
                failures += 1
                _LOG.warning("heartbeat failed for %s (%d consecutive)",
                             self.vm_id, failures)
                if (self._max_heartbeat_failures is not None
                        and failures >= self._max_heartbeat_failures):
                    # control plane is gone: a process worker must exit or it
                    # leaks forever (the allocator's GC reaps our record)
                    _LOG.error("control plane unreachable; disconnecting %s",
                               self.vm_id)
                    if self._on_disconnected is not None:
                        self._on_disconnected()
                    return

    # -- WorkerApi.Init / Execute parity ---------------------------------------

    def init(self, owner: str) -> None:
        """Take ownership for an execution (``WorkerApiImpl.init:230``)."""
        self._owner = owner

    def execute(self, task: TaskDesc, gang_rank: int, gang: Dict[str, Any]) -> str:
        # deterministic op id → idempotent: a crashed graph-executor step that
        # re-requests execution after resume gets the already-running op back
        # instead of launching the program a second time
        op_id = f"workerop-{task.id}-r{gang_rank}"
        with self._lock:
            if op_id in self._ops:
                return op_id
            self._ops[op_id] = {"status": "RUNNING", "error": None,
                                "exception_uri": None}
        thread = threading.Thread(
            target=self._run, args=(op_id, task, gang_rank, gang),
            name=f"task-{task.name}-r{gang_rank}", daemon=True,
        )
        thread.start()
        return op_id

    def status(self, op_id: str) -> Dict[str, Any]:
        with self._lock:
            return dict(self._ops[op_id])

    # -- execution -------------------------------------------------------------

    def _run(self, op_id: str, task: TaskDesc, gang_rank: int,
             gang: Dict[str, Any]) -> None:
        _StdRouter.install()
        log_buf = io.StringIO()
        token_route = _StdRouter._route.set(log_buf)
        token_gang = _GANG.set({"rank": gang_rank, "size": task.gang_size, **gang})
        with self._lock:
            mounts_snapshot = dict(self._mounts)
        token_mounts = _MOUNTS.set(mounts_snapshot)
        try:
            with logging_context(task=task.id, vm=self.vm_id, rank=str(gang_rank)):
                self._execute_task(task, gang_rank)
            with self._lock:
                self._ops[op_id]["status"] = "DONE"
        except BaseException as e:
            tb = traceback.format_exc()
            _LOG.error("task %s failed on %s: %s", task.id, self.vm_id, tb)
            exception_uri = None
            if gang_rank == 0 and not isinstance(e, ChannelFailed):
                exception_uri = self._store_exception(task, e, tb)
                for out in task.outputs:
                    try:
                        self._channels.transfer_failed(out.id, repr(e))
                    except KeyError:
                        pass
            with self._lock:
                self._ops[op_id].update(
                    status="FAILED", error=repr(e), exception_uri=exception_uri
                )
        finally:
            _MOUNTS.reset(token_mounts)
            _GANG.reset(token_gang)
            _StdRouter._route.reset(token_route)
            # every rank's output reaches the log plane (isolated gang ranks
            # run the full SPMD body; a rank-3 crash must be diagnosable)
            self._flush_logs(task, log_buf.getvalue(), rank=gang_rank)

    def _execute_task(self, task: TaskDesc, gang_rank: int) -> None:
        # isolated workers (own interpreter, real remote backends) sync the
        # user's local modules first; in-process thread workers share the
        # client interpreter and skip (startup.py LOCAL_MODULES parity)
        module_dirs: list = []
        if task.module_archives and os.environ.get("LZY_WORKER_ISOLATED"):
            import tempfile

            from lzy_tpu.env.modules import unpack_modules

            dest = tempfile.mkdtemp(prefix="lzy_modules_")
            unpack_modules(task.module_archives, self._storage, dest)
            module_dirs.append(dest)
        for ref in task.input_entries:
            self._channels.bind(ref.id, CONSUMER, task.id)
        for ref in task.outputs:
            self._channels.bind(ref.id, PRODUCER, task.id)

        isolated = bool(os.environ.get("LZY_WORKER_ISOLATED"))
        if gang_rank != 0 and not isolated:
            # non-zero ranks of an IN-PROCESS gang: one process = one JAX
            # runtime, so only host 0 can run the program; the others wait
            # for its outputs. Isolated (process/pod) gang workers fall
            # through and execute the full SPMD body below instead — every
            # host runs the same program, ranks join via initialize_gang().
            # No timeout: a healthy training op can run for hours; the graph
            # deadline is the backstop.
            for out in task.outputs:
                self._channels.wait_available(out.id, timeout_s=None)
            return

        # env assembly BEFORE touching inputs: a wrong env must fail here
        # with an attributable message, not at unpickle time inside the op
        # (CondaEnvironment.install parity — fail fast on unbuildable envs).
        # Containerized ops skip it: their deps live in the image, and a
        # host-side diff would reject envs the container satisfies.
        overlay = None if task.container else self._prepare_python_env(task)

        import contextlib

        # the overlay must cover unpickling too: the pickled func/args (and
        # the serialized outputs) may reference overlay-installed packages
        with contextlib.ExitStack() as stack:
            if overlay is not None:
                from lzy_tpu.env.realize import applied_overlay

                # overlays rebind process-global import state; one at a time
                stack.enter_context(self._env_lock)
                stack.enter_context(applied_overlay(overlay))

            args = [self._read_entry(ref) for ref in task.args]
            kwargs = {k: self._read_entry(ref)
                      for k, ref in task.kwargs.items()}
            func = self._load_func(task.func_uri)

            from lzy_tpu.utils.env import applied_env_vars

            with applied_env_vars(task.env_vars):
                from lzy_tpu.utils.trace import PROFILE_ENV, profile_enabled

                profile_ctx = contextlib.nullcontext()
                if profile_enabled(task.env_vars) and task.std_logs_uri:
                    if task.container:
                        # the op runs in a separate container process; a
                        # host-side jax trace would capture nothing and
                        # upload a blank profile
                        _LOG.warning(
                            "%s=1 ignored for containerized op %s: profile "
                            "inside the image instead", PROFILE_ENV,
                            task.name,
                        )
                    else:
                        # op-level XLA profiling as a platform feature:
                        # artifacts land next to the run's logs
                        from lzy_tpu.utils.trace import profiled

                        profile_ctx = profiled(
                            upload_prefix=join_uri(
                                task.std_logs_uri, "traces", task.id),
                            storage=self._storage,
                        )
                with profile_ctx:
                    if task.container:
                        result = self._run_in_container(
                            task, func, args, kwargs,
                            extra_paths=module_dirs,
                        )
                    else:
                        result = func(*args, **kwargs)
                        self._log_op_devices()

            n_out = len(task.outputs)
            outputs = (result if n_out > 1 and isinstance(result, tuple)
                       else (result,))
            if len(outputs) != n_out:
                raise ValueError(
                    f"op {task.name}() returned {len(outputs)} values, "
                    f"declared {n_out}"
                )

            if gang_rank != 0:
                # SPMD convention (reference worker + jax multi-host alike):
                # every host computes, host 0 alone publishes — EXCEPT that
                # global sharded outputs need every rank's shards (no single
                # process holds them all), so non-zero ranks spill theirs
                # and join the per-entry barrier rank 0 also passes
                from lzy_tpu.channels.sharded_spill import (
                    is_global_array,
                    spill_with_vote,
                )

                for ref, value in zip(task.outputs, outputs):
                    if is_global_array(value):
                        # vote-based: a failed rank raises EVERYWHERE after
                        # all converge instead of wedging the others in a
                        # bare barrier
                        spill_with_vote(self._storage, ref.uri, value)
                return

            from lzy_tpu.core.call import result_cacheable

            cacheable = result_cacheable(func, result)
            for ref, value in zip(task.outputs, outputs):
                self._write_entry(ref, value, cacheable=cacheable)
                self._channels.transfer_completed(ref.id)

    # -- environment assembly (execution-env parity) ---------------------------

    def _prepare_python_env(self, task: TaskDesc):
        """Returns an overlay dir to apply around the op, or None.

        Isolated workers (own interpreter) build a pip overlay for the diff;
        shared-interpreter thread workers cannot mutate the process other ops
        share, so they validate and fail fast on any mismatch."""
        if not task.python_env:
            return None
        from lzy_tpu.env.realize import EnvRealizer, validate_spec

        if not os.environ.get("LZY_WORKER_ISOLATED"):
            validate_spec(task.python_env)
            return None
        with self._env_lock:
            if self._env_realizer is None:
                import tempfile

                root = (os.path.join(self._spill_root, "envs")
                        if self._spill_root
                        else tempfile.mkdtemp(prefix="lzy_envs_"))
                self._env_realizer = EnvRealizer(root)
        return self._env_realizer.realize(task.python_env)

    def _run_in_container(self, task: TaskDesc, func, args, kwargs,
                          extra_paths=()):
        """Execute the op inside its image via the exchange-dir protocol
        (DockerEnvironment parity); channels/storage stay host-side.
        ``extra_paths``: synced user-module dirs the image must import from."""
        import tempfile

        import cloudpickle

        from lzy_tpu.env.container_runtime import (
            ContainerError,
            container_from_doc,
        )
        from lzy_tpu.service import container_exec as ce

        if self._container_runtime is None:
            raise ContainerError(
                f"op {task.name} requires container image "
                f"{task.container.get('image')!r} but this worker has no "
                f"container runtime (set LZY_CONTAINER_RUNTIME or install "
                f"docker)"
            )
        container = container_from_doc(task.container)
        exchange = tempfile.mkdtemp(prefix=f"lzy_ctr_{task.id}_")
        try:
            with open(os.path.join(exchange, ce.PAYLOAD), "wb") as f:
                cloudpickle.dump(
                    {"func": func, "args": args, "kwargs": kwargs}, f
                )
            rc = self._container_runtime.run_exec(
                container, exchange, env=dict(task.env_vars),
                extra_paths=tuple(extra_paths),
            )
            error_path = os.path.join(exchange, ce.ERROR)
            if os.path.exists(error_path):
                try:
                    with open(error_path, "rb") as f:
                        exc = pickle.load(f)
                except Exception:
                    # the exception class lives in an image-only package;
                    # fall back to the textual traceback so the real failure
                    # is never masked by a host-side ModuleNotFoundError
                    text_path = os.path.join(exchange, ce.ERROR_TEXT)
                    detail = ""
                    if os.path.exists(text_path):
                        with open(text_path) as f:
                            detail = f.read()
                    raise ContainerError(
                        f"op {task.name} failed in container "
                        f"(exception class not importable on host):\n{detail}"
                    )
                raise exc
            result_path = os.path.join(exchange, ce.RESULT)
            if rc != 0 or not os.path.exists(result_path):
                raise ContainerError(
                    f"container exec for op {task.name} exited rc={rc} "
                    f"without a result"
                )
            with open(result_path, "rb") as f:
                return pickle.load(f)
        finally:
            # pickled args/results can be huge; never let exchanges pile up
            import shutil

            shutil.rmtree(exchange, ignore_errors=True)

    # -- data plane (startup.py read_data/write_data parity) -------------------

    def _read_entry(self, ref) -> Any:
        ch = self._channels.wait_available(ref.id)
        device_value = self._channels.device.take(ref.id)
        if device_value is not None:
            return device_value  # ICI fast path: value never left the slice
        meta = self._read_meta(ref.uri)
        serializer = self._serializers.find_by_format(meta["data_format"])
        # direct peer transfer (native slot stream) before the storage peer
        if ch.slot_peer is not None and self._spill_root is not None:
            from lzy_tpu.channels.p2p import fetch_via_peer

            dest = os.path.join(self._spill_root,
                                f"in-{ref.id.replace('/', '_')}")
            if fetch_via_peer(ch.slot_peer, dest):
                with open(dest, "rb") as f:
                    return serializer.deserialize(f)
        src = self._storage.open_read(ref.uri)
        try:
            return serializer.deserialize(src)
        finally:
            src.close()

    def _write_entry(self, ref, value: Any, *,
                     cacheable: bool = True) -> None:
        import json

        from lzy_tpu.channels.sharded_spill import is_global_array

        if is_global_array(value):
            # multi-host output: shard-parallel spill + manifest entry
            # (rank>0 shards were spilled by their own processes)
            return self._write_global_entry(ref, value)

        self._channels.device.offer(ref.id, value)
        serializer = self._serializers.find_by_instance(value)
        buf = io.BytesIO()
        serializer.serialize(value, buf)
        data = buf.getvalue()
        if self._slot_server is not None:
            # best-effort fast path: any spill failure falls back to the
            # storage peer below instead of failing the task
            try:
                from lzy_tpu.channels.p2p import SlotPeer
                from lzy_tpu.native import fnv1a_file

                name = ref.id.replace("/", "_")
                spill = os.path.join(self._spill_root, name)
                with open(spill, "wb") as f:
                    f.write(data)
                self._channels.publish_peer(ref.id, SlotPeer(
                    host=self._advertise_host, port=self._slot_server.port,
                    name=name, fnv1a=fnv1a_file(spill),
                ))
            except Exception:
                _LOG.warning("p2p spill of %s failed; storage only", ref.id)
        # multipart + retries for big values (multi-GB arrays), one retried
        # write for small ones
        from lzy_tpu.storage.transfer import upload_bytes

        upload_bytes(self._storage, ref.uri, data)
        from lzy_tpu.utils import hashing

        scheme = serializer.data_scheme(value)
        doc = {
            "hash": hashing.hash_bytes(data),
            "data_format": scheme.data_format,
            "schema_content": scheme.schema_content,
            "meta": scheme.meta,
        }
        if not cacheable:
            # op vetoed caching this result (result_cacheable): stored
            # for this execution's consumers, never a future cache hit
            doc["cacheable"] = False
        self._storage.write_bytes(
            ref.uri + ".meta", json.dumps(doc).encode("utf-8"),
        )

    def _write_global_entry(self, ref, value: Any) -> None:
        """Rank 0's half of the gang spill protocol: write own shards, wait
        for every rank's shards to land, then publish the manifest as the
        entry object — the channel completes only once the value is whole."""
        import json

        from lzy_tpu.channels.sharded_spill import (
            MANIFEST_FORMAT,
            build_manifest,
            spill_with_vote,
        )
        from lzy_tpu.utils import hashing

        spill_with_vote(self._storage, ref.uri, value)
        manifest = build_manifest(value, ref.uri)
        self._storage.write_bytes(ref.uri, manifest)
        self._storage.write_bytes(
            ref.uri + ".meta",
            json.dumps({
                "hash": hashing.hash_bytes(manifest),
                "data_format": MANIFEST_FORMAT,
                "schema_content": "jax.Array",
                "meta": {"shape": list(value.shape),
                         "dtype": str(value.dtype)},
            }).encode("utf-8"),
        )

    def _read_meta(self, uri: str) -> Dict[str, Any]:
        import json

        return json.loads(self._storage.read_bytes(uri + ".meta").decode("utf-8"))

    def _load_func(self, func_uri: str):
        data = self._storage.read_bytes(func_uri)
        obj = pickle.loads(data)
        # an LzyOp wrapper (shipped by reference for module-level ops) unwraps
        # to its function: there is no active workflow on the worker, so the
        # wrapper would run eagerly anyway — unwrapping skips re-validation
        func = getattr(obj, "func", None)
        return func if callable(func) else obj

    def _log_op_devices(self) -> None:
        """Say once which devices this worker's ops compute on, after the
        first op that brought JAX up. Not at registration: querying the
        devices there would take the chip before a gang worker reached
        ``jax.distributed.initialize``. Without this line an
        ``@op(tpu=...)`` body that a CPU-pinned process worker runs computes
        on the CPU and nothing says so."""
        if self._devices_logged or "jax" not in sys.modules:
            return
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            return
        self._devices_logged = True
        from lzy_tpu.utils.jaxenv import device_line

        _LOG.warning("worker %s ops compute on %s", self.vm_id,
                     device_line())

    def _store_exception(self, task: TaskDesc, e: BaseException, tb: str) -> str:
        e.add_note(f"[remote traceback from {self.vm_id}]\n{tb}")
        import cloudpickle

        try:
            payload = cloudpickle.dumps(e)
        except Exception:
            payload = cloudpickle.dumps(RuntimeError(f"{e!r} (unpicklable)\n{tb}"))
        self._storage.write_bytes(task.exception.uri, payload)
        return task.exception.uri

    def _flush_logs(self, task: TaskDesc, text: str, rank: int = 0) -> None:
        if not text or not task.std_logs_uri:
            return
        name = f"{task.id}.log" if rank == 0 else f"{task.id}.r{rank}.log"
        uri = join_uri(task.std_logs_uri, name)
        try:
            self._storage.write_bytes(uri, text.encode("utf-8"))
        except Exception:
            _LOG.warning("failed to flush logs for task %s", task.id)

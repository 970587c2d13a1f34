"""Process-worker entrypoint: ``python -m lzy_tpu.rpc.worker_main``.

The process analog of the reference worker binary (``lzy/worker/.../Worker.java:
32-242``): boots, starts its own gRPC server (WorkerApi parity: Init/Execute/
Status), registers its endpoint with the control plane (AllocatorPrivate
parity), heartbeats, and executes tasks with full OS-process isolation —
its own interpreter, its own JAX runtime, channels and registration via RPC,
data via shared storage (file:// or s3://; mem:// cannot cross processes).
"""

from __future__ import annotations

import argparse
import os
import threading


from lzy_tpu.rpc.control import RpcAllocatorClient, RpcChannelsClient
from lzy_tpu.rpc.core import JsonRpcClient, JsonRpcServer
from lzy_tpu.service.graph import TaskDesc
from lzy_tpu.service.worker import WorkerAgent
from lzy_tpu.storage import StorageConfig
from lzy_tpu.storage.registry import client_for
from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--control", required=True, help="control-plane address")
    parser.add_argument("--vm-id", required=True)
    parser.add_argument("--storage-uri", required=True)
    parser.add_argument("--spill-root", default=None)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--advertise-host", default=os.environ.get(
        "LZY_WORKER_ADVERTISE_HOST", "127.0.0.1"),
        help="routable address peers use to reach this worker (pod IP on "
             "multi-host deployments)")
    args = parser.parse_args(argv)

    os.environ.setdefault("LZY_WORKER_ISOLATED", "1")  # sync user modules

    # WORKER-role IAM token minted by the allocator at launch (env, never
    # argv): presented on every control-plane call, and required back from
    # the control plane on our own WorkerApi — nobody else knows it. Shared
    # as a WorkerToken holder so heartbeat-delivered refreshes reach every
    # client (long-lived VMs must never age out of authentication).
    from lzy_tpu.rpc.control import WorkerToken

    raw_token = os.environ.get("LZY_WORKER_TOKEN") or None
    token = WorkerToken(raw_token) if raw_token else None

    control = JsonRpcClient(args.control)
    storage = client_for(StorageConfig(uri=args.storage_uri))
    channels = RpcChannelsClient(control, token=token)

    stop_event = threading.Event()
    agent_box = {}

    def check_caller(p):
        if token is not None and not token.accepts(p.get("token")):
            from lzy_tpu.iam import AuthError  # maps to PERMISSION_DENIED

            raise AuthError("WorkerApi call without the VM's token")

    def h_init(p):
        check_caller(p)
        agent_box["agent"].init(p.get("owner", ""))
        return {}

    def h_execute(p):
        check_caller(p)
        op_id = agent_box["agent"].execute(
            TaskDesc.from_doc(p["task"]), p["gang_rank"], p.get("gang", {})
        )
        return {"op_id": op_id}

    def h_status(p):
        check_caller(p)
        return agent_box["agent"].status(p["op_id"])

    def h_shutdown(p):
        check_caller(p)
        stop_event.set()
        return {}

    def h_mount(p):
        check_caller(p)
        agent_box["agent"].add_mount(p["name"], p["path"],
                                     p.get("read_only", False))
        return {}

    def h_unmount(p):
        check_caller(p)
        agent_box["agent"].remove_mount(p["name"])
        return {}

    server = JsonRpcServer({
        "Init": h_init,
        "Execute": h_execute,
        "Status": h_status,
        "Shutdown": h_shutdown,
        "Mount": h_mount,
        "Unmount": h_unmount,
    }, port=args.port, advertise_host=args.advertise_host)

    allocator = RpcAllocatorClient(control, endpoint=server.address,
                                   token=token)
    agent = WorkerAgent(
        args.vm_id,
        allocator=allocator,
        channels=channels,
        storage_client=storage,
        spill_root=args.spill_root,
        advertise_host=args.advertise_host,
        heartbeat_period_s=2.0,
        # a dead control plane must not leak this process forever
        max_heartbeat_failures=5,
        on_disconnected=stop_event.set,
    )
    agent_box["agent"] = agent
    agent.start()          # registers endpoint + starts heartbeats
    # The platform this worker's ops will compute on is the one its launcher
    # gave it (ProcessVmBackend pins "cpu" unless told otherwise; a TPU pod
    # leaves it unset). Said here, at registration, without touching the
    # backend: a gang worker must reach jax.distributed.initialize first.
    # The device kind follows in the log once an op has brought JAX up
    # (WorkerAgent).
    _LOG.warning(
        "worker %s serving on %s; JAX_PLATFORMS=%s", args.vm_id,
        server.address,
        os.environ.get("JAX_PLATFORMS") or "<unset: first accelerator>")

    stop_event.wait()
    agent.stop()
    server.stop()


if __name__ == "__main__":
    main()

"""Smoke-test the deployable control-plane entrypoint (VERDICT r3 #3).

``lzy_tpu.service.serve`` is the control-plane image's ENTRYPOINT
(``docker/Dockerfile.controlplane``) and the only main() composing
workflow + executor + allocator + channels + whiteboards for deployment —
it must not be the one untested module in the tree. This spawns it as a
real subprocess (the same way the container runs it), drives a two-op
workflow with a whiteboard through the gRPC surface, and checks clean
SIGTERM shutdown plus the arg-error paths. Mirrors the role of the
reference's service mains (e.g. ``lzy/lzy-service/.../LzyServiceMain``
started by its docker-compose) without needing a docker daemon.
"""

import dataclasses
import os
import pathlib
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest

from lzy_tpu import op, whiteboard
from lzy_tpu.core.lzy import Lzy
from lzy_tpu.runtime.remote import RemoteRuntime
from lzy_tpu.rpc import RpcWorkflowClient
from lzy_tpu.rpc.control import RpcWhiteboardClient
from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig

TESTS_DIR = str(pathlib.Path(__file__).parent)
REPO_ROOT = str(pathlib.Path(__file__).parents[1])


# module level: the serve subprocess's process workers import this module
# (PYTHONPATH below) and resolve the ops by reference
@op
def serve_double(x: int) -> int:
    return x * 2


@op
def serve_add(a: int, b: int) -> int:
    return a + b


@whiteboard("serve_e2e_result")
@dataclasses.dataclass
class ServeResult:
    total: int


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_serve(args, *, timeout_s: float = 30.0):
    """Start serve.py exactly as the container does; wait for readiness.
    Its output goes to a file, not a pipe: nobody reads a pipe once the
    banner is seen, and a server that logs past the pipe's 64 KiB then
    blocks in write() with a request in flight (XLA logs a line of 3 KiB
    for every program it reads back from the compile cache)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT, TESTS_DIR] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.setdefault("JAX_PLATFORMS", "cpu")
    log = tempfile.NamedTemporaryFile(
        "w", prefix="serve-", suffix=".log", delete=False)
    proc = subprocess.Popen(
        [sys.executable, "-m", "lzy_tpu.service.serve", *args],
        stdout=log, stderr=subprocess.STDOUT, env=env,
    )
    log.close()
    proc.log_path = log.name
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and proc.poll() is None:
        banner = _output(proc)
        if "serving on" in banner:
            return proc, banner
        time.sleep(0.1)
    proc.kill()
    raise AssertionError(
        f"serve.py never became ready; output:\n{_output(proc)}")


def _output(proc) -> str:
    with open(proc.log_path) as f:
        return f.read()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("serve")
    port = _free_port()
    storage_uri = f"file://{tmp}/storage"
    proc, _ = _spawn_serve([
        "--db", str(tmp / "meta.db"),
        "--storage-uri", storage_uri,
        "--port", str(port),
        "--backend", "process",
        "--gc-period-s", "60",
    ])
    yield proc, f"127.0.0.1:{port}", storage_uri
    if proc.poll() is None:
        proc.kill()
        proc.wait(10)


class TestServeEntrypoint:
    def test_two_op_workflow_with_whiteboard_end_to_end(self, served):
        proc, address, storage_uri = served
        wf_client = RpcWorkflowClient(address)
        wb_client = RpcWhiteboardClient(address)
        storage = DefaultStorageRegistry()
        storage.register_storage(
            "default", StorageConfig(uri=storage_uri), default=True)
        lzy = Lzy(
            runtime=RemoteRuntime(wf_client, poll_period_s=0.1,
                                  stream_logs=False, graph_timeout_s=180),
            storage_registry=storage,
        )
        lzy._whiteboard_client = wb_client
        try:
            with lzy.workflow("serve-smoke") as wf:
                wb = wf.create_whiteboard(ServeResult, tags=["serve-smoke"])
                total = serve_add(serve_double(4), serve_double(9))
                wb.total = total
                assert int(total) == 26
            found = wb_client.query(tags=["serve-smoke"])
            assert len(found) == 1
            assert found[0].status == "FINALIZED"
        finally:
            wf_client.close()
            wb_client.close()
        assert proc.poll() is None, "control plane died during the workflow"

    def test_sigterm_shuts_down_cleanly(self, served):
        # ordered after the workflow test (same module-scoped fixture):
        # shutdown is the last thing the smoke checks
        proc, _, _ = served
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(30)
        out = _output(proc)
        assert rc == 0, f"non-zero exit {rc}; output tail:\n{out[-2000:]}"
        assert "shutting down" in out


class TestServeModel:
    def test_serve_model_generates_over_rpc(self, tmp_path):
        """--serve-model boots the inference plane in the deployable
        process: InferGenerate/InferStats answer on the same gRPC port as
        the workflow surface."""
        from lzy_tpu.rpc import RpcInferenceClient

        port = _free_port()
        proc, banner = _spawn_serve([
            "--db", str(tmp_path / "m.db"),
            "--storage-uri", f"file://{tmp_path}/s",
            "--port", str(port),
            "--serve-model", "tiny",
            "--serve-slots", "2",
        ], timeout_s=120)
        try:
            assert "model=tiny" in banner
            client = RpcInferenceClient(f"127.0.0.1:{port}")
            try:
                res = client.generate([5, 9, 3], max_new_tokens=4,
                                      timeout_s=120)
                assert res["model"] == "tiny"
                assert len(res["tokens"]) == 4
                assert res["ttft_ms"] is not None
                stats = client.stats()
                assert stats["slots"] == 2
                assert stats["requests_finished"] >= 1
            finally:
                client.close()
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(10)

    def test_serve_slo_quota_over_the_wire(self, tmp_path):
        """--serve-slo + --tenant-rps: the tenant's second request inside
        the burst window comes back as the typed QuotaExceeded
        (RESOURCE_EXHAUSTED) with the retry_after_s hint rehydrated from
        the wire — the quota-exceeded status end to end through a real
        server process."""
        from lzy_tpu.rpc import RpcInferenceClient
        from lzy_tpu.serving import QuotaExceeded

        port = _free_port()
        proc, banner = _spawn_serve([
            "--db", str(tmp_path / "m.db"),
            "--storage-uri", f"file://{tmp_path}/s",
            "--port", str(port),
            "--serve-model", "tiny",
            "--serve-slots", "2",
            "--serve-slo",
            # 0.01 req/s: the first request's compile time (seconds)
            # must not refill the bucket before the second call
            "--tenant-rps", "0.01",
            "--tenant-burst-s", "100",
        ], timeout_s=120)
        try:
            client = RpcInferenceClient(f"127.0.0.1:{port}")
            try:
                res = client.generate([5, 9], max_new_tokens=2,
                                      timeout_s=120, tenant="cust-a")
                assert res["status"] == "ok"
                with pytest.raises(QuotaExceeded) as ei:
                    client.generate([5, 9], max_new_tokens=2,
                                    timeout_s=120, tenant="cust-a")
                assert "cust-a" in str(ei.value)
                assert ei.value.retry_after_s is not None
                # another tenant's bucket is untouched
                assert client.generate([5, 9], max_new_tokens=2,
                                       timeout_s=120,
                                       tenant="cust-b")["status"] == "ok"
            finally:
                client.close()
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(10)

    def test_unknown_model_fails_fast(self, tmp_path):
        res = subprocess.run(
            [sys.executable, "-m", "lzy_tpu.service.serve",
             "--db", str(tmp_path / "m.db"),
             "--storage-uri", f"file://{tmp_path}/s",
             "--serve-model", "gpt99"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=120, cwd=REPO_ROOT,
        )
        assert res.returncode != 0
        assert "gpt99" in res.stdout


class TestServeArgErrors:
    def _run(self, args):
        return subprocess.run(
            [sys.executable, "-m", "lzy_tpu.service.serve", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=60, cwd=REPO_ROOT,
        )

    def test_missing_storage_uri_fails_fast(self):
        res = self._run([])
        assert res.returncode == 2
        assert "--storage-uri" in res.stdout

    def test_gke_requires_worker_image(self, tmp_path):
        res = self._run([
            "--db", str(tmp_path / "m.db"),
            "--storage-uri", f"file://{tmp_path}/s",
            "--backend", "gke",
        ])
        assert res.returncode == 2
        assert "--worker-image" in res.stdout

    def test_gateway_journal_requires_fleet_front(self, tmp_path):
        res = self._run([
            "--db", str(tmp_path / "m.db"),
            "--storage-uri", f"file://{tmp_path}/s",
            "--serve-model", "tiny",
            "--gateway-journal",
        ])
        assert res.returncode == 2
        assert "--gateway-journal" in res.stdout

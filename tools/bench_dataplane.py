"""Data-plane throughput benchmark (VERDICT r3 #4, r2 #6).

SURVEY.md's own rule (§"native code": justified only where profiling
demands it) requires numbers for the native engines; the reference's
analogous layer is its multipart transmitter
(``util/util-s3/.../transfer/loop/UploadProcessingLoop.java``) and its
slots streaming library. This measures, on this host:

- ``slot_native``:   1 GiB pull through ``native/slot_stream.cpp`` over
                     loopback TCP (the producer→consumer channel path);
- ``slot_python``:   the same 1 GiB through a pure-python socket server —
                     the baseline the native engine must beat;
- ``multipart_up`` / ``multipart_down``: the concurrent ranged transfer
                     engine (``storage/transfer.py``) against fs storage;
- ``naive_up`` / ``naive_down``: single-stream write/read of the same
                     file — the baseline for the multipart engine;
- ``sharded_spill``: spill + manifest + reassemble of a sharded
                     ``jax.Array`` on the 8-device CPU mesh
                     (``channels/sharded_spill.py``).

Prints one JSON line per scenario: {"scenario", "gib", "wall_s", "gbps"}.
Record results in BASELINE.md "Measured". Run:
    python tools/bench_dataplane.py [--gib 1.0]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# force (not setdefault): this is a CPU data-plane bench whatever the
# ambient platform, and the 8-device virtual mesh is the whole point
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

GIB = 1 << 30


def settle() -> None:
    """Flush dirty pages so one scenario's writeback doesn't tax the next
    (single-core host: background writeback steals the only CPU)."""
    os.sync()


def best_of(n: int, fn) -> float:
    """Best wall time of n runs — the least-interfered sample on a shared
    single-core host."""
    best = float("inf")
    for _ in range(n):
        settle()
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def emit(scenario: str, nbytes: int, wall_s: float, **extra) -> None:
    print(json.dumps({
        "scenario": scenario,
        "gib": round(nbytes / GIB, 3),
        "wall_s": round(wall_s, 3),
        "gbps": round(nbytes / GIB / wall_s, 3),
        **extra,
    }), flush=True)


def make_payload(path: str, nbytes: int) -> None:
    """Incompressible-ish payload written fast (urandom once, tiled)."""
    block = os.urandom(1 << 20)
    with open(path, "wb") as f:
        left = nbytes
        while left > 0:
            f.write(block[:min(left, len(block))])
            left -= len(block)


# -- python socket baseline --------------------------------------------------


class PySlotServer:
    """Minimal pure-python analog of the native slot server: serves one
    file over loopback with a plain send loop (64 KiB chunks — the
    typical naive choice)."""

    def __init__(self, path: str):
        self._path = path
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(2)
        self.port = self._srv.getsockname()[1]
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self) -> None:
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            with conn, open(self._path, "rb") as f:
                while True:
                    chunk = f.read(64 * 1024)
                    if not chunk:
                        break
                    try:
                        conn.sendall(chunk)
                    except OSError:
                        break

    def stop(self) -> None:
        self._srv.close()


def py_pull(port: int, dest: str) -> None:
    s = socket.socket()
    s.connect(("127.0.0.1", port))
    with open(dest, "wb") as f:
        while True:
            chunk = s.recv(64 * 1024)
            if not chunk:
                break
            f.write(chunk)
    s.close()


# -- scenarios ---------------------------------------------------------------


def bench_slots(src: str, tmp: str, nbytes: int) -> None:
    from lzy_tpu.native import native_available
    from lzy_tpu.native.slots import SlotServer, pull

    if not native_available():
        print(json.dumps({"scenario": "slot_native",
                          "error": "native engine unavailable"}), flush=True)
        return
    name = os.path.basename(src)
    with SlotServer(os.path.dirname(src)) as srv:
        dest = os.path.join(tmp, "native-pull.bin")
        # warm the page cache symmetrically for both contenders
        pull("127.0.0.1", srv.port, name, dest)
        emit("slot_native", nbytes,
             best_of(3, lambda: pull("127.0.0.1", srv.port, name, dest)))
        os.unlink(dest)

    psrv = PySlotServer(src)
    dest = os.path.join(tmp, "py-pull.bin")
    py_pull(psrv.port, dest)
    emit("slot_python", nbytes, best_of(3, lambda: py_pull(psrv.port, dest)))
    psrv.stop()
    os.unlink(dest)


class _GenericOnly:
    """Wrapper hiding the local fast-path methods, to measure the ranged
    concurrent machinery itself (the path network object stores take)."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name in ("upload_file", "download_file"):
            raise AttributeError(name)
        return getattr(self._inner, name)


def bench_multipart(src: str, tmp: str, nbytes: int) -> None:
    from lzy_tpu.storage import StorageConfig, client_for
    from lzy_tpu.storage.transfer import download, upload

    client = client_for(StorageConfig(uri=f"file://{tmp}/store"))
    uri = f"file://{tmp}/store/payload.bin"

    # the engine as callers see it (picks the local-fs kernel-copy path)
    emit("engine_up", nbytes, best_of(3, lambda: upload(client, uri, src)))
    dest = os.path.join(tmp, "engine-down.bin")
    emit("engine_down", nbytes,
         best_of(3, lambda: download(client, uri, dest)))
    os.unlink(dest)

    # the generic ranged machinery (what s3:// rides; fs is a lower bound
    # since parts contend on one disk instead of separate network streams)
    generic = _GenericOnly(client)
    emit("ranged_up", nbytes, best_of(3, lambda: upload(generic, uri, src)))
    dest = os.path.join(tmp, "ranged-down.bin")
    emit("ranged_down", nbytes,
         best_of(3, lambda: download(generic, uri, dest)))
    os.unlink(dest)

    # naive single-stream baseline over the same backend surface
    naive_uri = f"file://{tmp}/store/naive.bin"

    def naive_up():
        with open(src, "rb") as f:
            client.write(naive_uri, f)

    emit("naive_up", nbytes, best_of(3, naive_up))
    dest = os.path.join(tmp, "naive-down.bin")

    def naive_down():
        with open(dest, "wb") as out:
            client.read(naive_uri, out)

    emit("naive_down", nbytes, best_of(3, naive_down))
    os.unlink(dest)


def bench_sharded_spill(tmp: str, nbytes: int) -> None:
    import jax

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lzy_tpu.channels.sharded_spill import (
        assemble, build_manifest, spill_local_shards)
    from lzy_tpu.storage import StorageConfig, client_for

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("dp",))
    n_rows = max(len(devices), nbytes // (4 * 4096))
    n_rows -= n_rows % len(devices)
    arr = jax.device_put(
        jnp.arange(n_rows * 4096, dtype=jnp.float32).reshape(n_rows, 4096),
        NamedSharding(mesh, P("dp", None)))
    actual = arr.size * arr.dtype.itemsize
    storage = client_for(StorageConfig(uri=f"file://{tmp}/spill"))
    base_uri = f"file://{tmp}/spill/entry"

    t0 = time.perf_counter()
    spill_local_shards(storage, base_uri, arr)
    manifest = build_manifest(arr, base_uri)
    emit("sharded_spill_out", actual, time.perf_counter() - t0,
         shards=len(devices))

    doc = json.loads(manifest.decode("utf-8"))
    t0 = time.perf_counter()
    out = assemble(doc, storage=storage)
    emit("sharded_spill_in", actual, time.perf_counter() - t0,
         shards=len(devices))
    assert out.shape == arr.shape


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gib", type=float, default=1.0,
                    help="payload size for the stream/multipart scenarios")
    args = ap.parse_args()
    nbytes = int(args.gib * GIB)
    tmp = tempfile.mkdtemp(prefix="bench-dataplane-")
    src = os.path.join(tmp, "payload-src.bin")
    make_payload(src, nbytes)
    try:
        bench_slots(src, tmp, nbytes)
        bench_multipart(src, tmp, nbytes)
        bench_sharded_spill(tmp, nbytes // 4)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()

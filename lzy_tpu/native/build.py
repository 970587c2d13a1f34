"""Shared build-on-demand loader for the C++ engines under ``native/``.

``make`` runs once per process before the first load, whatever
``native/build`` already holds: it is incremental, so an up-to-date tree
costs a few milliseconds, and a binary is never loaded on trust. One lock
for the process and one lock file for the machine: the slot engine and the
data loader build into the same directory, and two concurrent makes racing
on shared targets corrupt each other (test workers, process workers).
Failures are cached — retrying the compiler on every call would put its
timeout on hot paths (VM boot, batch assembly).
"""

from __future__ import annotations

import ctypes
import fcntl
import pathlib
import subprocess
import threading
from typing import Dict, Union

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
NATIVE_DIR = _REPO_ROOT / "native"
BUILD_DIR = NATIVE_DIR / "build"


class NativeUnavailable(RuntimeError):
    pass


_lock = threading.Lock()
_cache: Dict[str, Union[ctypes.CDLL, NativeUnavailable]] = {}
_made = False


def _make() -> None:
    """Bring ``native/build`` up to date with the sources (once per
    process; callers hold ``_lock``)."""
    global _made
    if _made:
        return
    BUILD_DIR.mkdir(exist_ok=True)
    with open(BUILD_DIR / ".make.lock", "w") as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        subprocess.run(
            ["make", "-C", str(NATIVE_DIR)],
            check=True, capture_output=True, text=True, timeout=120,
        )
    _made = True


def load_native_lib(so_name: str) -> ctypes.CDLL:
    """CDLL for ``native/build/<so_name>``, building the native tree on
    first use; raises (and caches) NativeUnavailable when the toolchain or
    the build is broken. Symbol signatures are the caller's business."""
    cached = _cache.get(so_name)
    if cached is not None:
        if isinstance(cached, NativeUnavailable):
            raise cached
        return cached
    with _lock:
        cached = _cache.get(so_name)
        if cached is not None:
            if isinstance(cached, NativeUnavailable):
                raise cached
            return cached
        try:
            _make()
            lib = ctypes.CDLL(str(BUILD_DIR / so_name))
        except (OSError, subprocess.CalledProcessError,
                subprocess.TimeoutExpired) as e:
            detail = getattr(e, "stderr", "") or str(e)
            err = NativeUnavailable(
                f"could not build/load {so_name}: {detail}"
            )
            _cache[so_name] = err
            raise err from e
        _cache[so_name] = lib
        return lib

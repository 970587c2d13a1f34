"""Where this process's JAX keeps compiled programs, and what it runs on.

One helper for every entry point that compiles (``service/serve.py``,
``chip_smoke.py``, the test tier): the persistent compilation
cache is placed from outside through ``JAX_COMPILATION_CACHE_DIR``, which
JAX reads by itself, and otherwise sits at one fixed path inside the
checkout. The path is part of a cache entry's key, so a directory built from
a temporary name, a pid or the time never hits.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

#: the cache when ``JAX_COMPILATION_CACHE_DIR`` is unset (ignored by git,
#: and left out of what the chip tool copies: see ``.chiprunignore``)
CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on, before the first compile.

    Returns the directory set in code, or None where the environment names
    one: then JAX's own handling of the variable stands and nothing is set
    here. Every program is cached, whatever its compile time, unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise: a serving
    engine's warm start is many programs of about a second each.
    """
    import jax

    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def device_summary() -> dict:
    """The devices as JAX reports them. Initializes the backend: a process
    that must not take the chip does not call this."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_line() -> str:
    """:func:`device_summary` as the one line the logs carry."""
    return "platform={platform} kind={kind!r} count={count}".format(
        **device_summary())

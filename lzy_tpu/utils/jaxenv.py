"""Where this process's JAX keeps compiled programs, and what it runs on.

One helper for every entry point that compiles (``service/serve.py``,
``chip_smoke.py``, the test tier): the persistent compilation
cache is placed from outside through ``JAX_COMPILATION_CACHE_DIR``, which
JAX reads by itself, and otherwise sits at one fixed path inside the
checkout. The path is part of a cache entry's key, so a directory built from
a temporary name, a pid or the time never hits.

**The build meter.** What JAX builds in this process is counted here, from
JAX's own ``jax.monitoring`` events (emitted synchronously on the thread
that builds), always on and at no cost except while JAX builds a program:

- ``lzy_program_build_seconds{site,stage}``: a histogram of a stage's own
  seconds. ``stage`` is ``trace`` (jaxpr tracing), ``lower`` (jaxpr to
  MLIR), ``compile`` (the backend's whole duration: a compile, or the read
  of the persistent cache in its place) or ``cache_read`` (that read
  alone, which ``compile`` holds too: add up the first three, never all
  four). A stage nested in another (a ``jit`` traced inside a trace) is
  taken out of the outer one's seconds, so the three add up to wall time.
- ``lzy_program_builds_total{site,cache}``: one a backend compile request;
  ``cache`` is ``hit`` (read from the persistent cache), ``miss`` (the
  cache was asked and did not have it) or ``off`` (it was not asked).

``site`` is the innermost ``trace.building(SITE)`` context open on the
building thread (``utils/trace.py`` lists the sites), else ``other``. The
stage seconds are also added to that context, so the span
``program.build`` carries the numbers the counters took.
"""

from __future__ import annotations

import os
import pathlib
import threading
from typing import Optional

from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

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

    install_build_meter()
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


_BUILD_SECONDS = REGISTRY.histogram(
    "lzy_program_build_seconds",
    "seconds JAX spent building programs, by the site that asked and the "
    "stage (stage=trace|lower|compile|cache_read; compile holds cache_read)",
    buckets=(0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 120.0, 600.0))
_BUILDS = REGISTRY.counter(
    "lzy_program_builds_total",
    "backend compile requests, by the site that asked and what the "
    "persistent cache did (cache=hit|miss|off)")

_STAGE = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
# every request that asks the persistent cache says so first, and a hit
# says so after: the request's ``compile`` duration then closes it. (JAX's
# ``cache_misses`` is emitted only when an entry is written.)
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_tls = threading.local()
_install = threading.Lock()
_installed = False


def install_build_meter() -> None:
    """Register the listeners, once a process: from
    :func:`enable_compile_cache` (so an entry point's earliest builds are
    counted, under ``other``) and from the first ``trace.building``."""
    global _installed
    if _installed:
        return
    with _install:
        if _installed:
            return
        import jax.monitoring as mon

        mon.register_scalar_listener(_on_stage_start)
        mon.register_event_duration_secs_listener(_on_duration)
        mon.register_event_listener(_on_event)
        _installed = True


def _on_stage_start(event, value, **_):
    # JAX reports a stage's start as a scalar (its wall-clock stamp) under
    # the name of the duration that follows: one frame a stage in flight,
    # holding the seconds of the stages that ran inside it
    if event in _STAGE:
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(0.0)


def _on_duration(event, seconds, **_):
    stage = _STAGE.get(event)
    build = trace.open_build()
    site = build.site if build is not None else trace.SITE_OTHER
    if stage is None:
        if event == _CACHE_READ:
            _BUILD_SECONDS.observe(seconds, site=site, stage="cache_read")
            if build is not None:
                build.add("cache_read", seconds)
        return
    stack = getattr(_tls, "stack", None)
    inside = stack.pop() if stack else 0.0
    if stack:
        stack[-1] += seconds
    own = max(0.0, seconds - inside)
    _BUILD_SECONDS.observe(own, site=site, stage=stage)
    _tls.seconds = getattr(_tls, "seconds", 0.0) + own
    if build is not None:
        build.add(stage, own)
    if stage == "compile":
        cache = getattr(_tls, "cache", None) or "off"
        _tls.cache = None
        _BUILDS.inc(site=site, cache=cache)
        if build is not None:
            build.add_request(cache)


def _on_event(event, **_):
    if event == _CACHE_ASKED:
        _tls.cache = "miss"
    elif event == _CACHE_HIT:
        _tls.cache = "hit"


def thread_build_seconds() -> float:
    """Seconds of builds (trace + lower + compile) JAX has reported on the
    calling thread: a reading before a block and one after say how much of
    the block was builds."""
    return getattr(_tls, "seconds", 0.0)


def build_totals() -> dict:
    """The meter's totals since the process began, over every site:
    ``seconds`` of backend compile requests (cache reads included),
    ``requests`` and ``cache_hits`` among them."""
    counts = _BUILDS.values()
    return {"seconds": sum(v for key, v in _BUILD_SECONDS.sums().items()
                           if ("stage", "compile") in key),
            "requests": int(sum(counts.values())),
            "cache_hits": int(sum(v for key, v in counts.items()
                                  if ("cache", "hit") in key))}


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

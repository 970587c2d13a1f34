"""What every cell's runner shares: where the benchmark's files are, the
manifest, the compile counter, the set-up phases and the percentile rule."""

from __future__ import annotations

import json
import os
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)

#: a percentile is reported only while this many samples lie beyond it
TAIL_SAMPLES = 10


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_files(manifest: dict, workload: str) -> dict:
    """Everything one cell is made of, found by name from the manifest:
    ``configs/<config>.json`` (the file the manifest gives), ``traffic/
    <traffic>.json``, and ``metrics/<name>.json`` for every per-layer metric
    whose entry lists the cell (or lists none: then every cell reports it)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (known: {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    with open(os.path.join(REPO, config["file"])) as f:
        config_doc = json.load(f)

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config_doc,
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
        "per_layer": [dict(m, **load_json("metrics", m["name"] + ".json"))
                      for m in manifest["per_layer"] if reported(m)],
    }


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0–100) by linear interpolation between the
    order statistics, as ``numpy.percentile`` defaults to."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def highest_percentile(n: int, tail: int = TAIL_SAMPLES) -> float:
    """The highest percentile that keeps ``tail`` of ``n`` samples beyond
    it (0 when there are not even ``tail`` samples)."""
    return max(0.0, 100.0 * (1.0 - tail / n)) if n > 0 else 0.0


class CompileMeter:
    """Counts JAX's compile requests in this process (a read of the
    persistent cache included): the window has to see none."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    """Set-up, itemised: ``mark(name)`` closes the phase that ran since the
    last mark. Printed on a line of its own, never in the result line."""

    def __init__(self, t0: float):
        self.t0 = self._last = t0
        self.seconds: dict = {}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))

"""Metrics: counters/gauges/histograms with Prometheus text exposition.

Counterpart of the reference's per-service Prometheus metrics
(``AllocatorMetrics``/``LzyServiceMetrics`` + ``PrometheusMetricReporter``
HTTP server, SURVEY.md §5.5), stdlib-only: a process-global registry, labeled
series, and an optional exposition endpoint in the standard text format.
"""

from __future__ import annotations

import http.server
import threading
from typing import Dict, List, Optional, Sequence, Tuple


def _label_key(labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted(labels.items()))


def _escape(value: str) -> str:
    # Prometheus text format: backslash, double-quote, newline must be escaped
    # in label values or the whole scrape becomes unparseable
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(key) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"' for k, v in key)
    return "{" + inner + "}"


class Counter:
    def __init__(self, name: str, help_: str):
        self.name, self.help = name, help_
        self._values: Dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def values(self) -> Dict[tuple, float]:
        """Every series as it stands, by its sorted ``(label, value)``
        pairs."""
        with self._lock:
            return dict(self._values)

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        with self._lock:
            for key, v in sorted(self._values.items()):
                out.append(f"{self.name}{_fmt_labels(key)} {v}")
        return out


class Gauge:
    def __init__(self, name: str, help_: str):
        self.name, self.help = name, help_
        self._values: Dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._values[_label_key(labels)] = value

    def add(self, amount: float, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:
            for key, v in sorted(self._values.items()):
                out.append(f"{self.name}{_fmt_labels(key)} {v}")
        return out


class Histogram:
    DEFAULT_BUCKETS = (0.005, 0.025, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0, 600.0)

    def __init__(self, name: str, help_: str,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name, self.help = name, help_
        self.buckets = tuple(buckets)
        self._counts: Dict[tuple, List[int]] = {}
        self._sums: Dict[tuple, float] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * (len(self.buckets) + 1))
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    counts[i] += 1
            counts[-1] += 1  # +Inf
            self._sums[key] = self._sums.get(key, 0.0) + value

    def sums(self) -> Dict[tuple, float]:
        """Every series' sum of observations, keyed as
        :meth:`Counter.values` keys."""
        with self._lock:
            return dict(self._sums)

    def time(self, **labels: str):
        hist = self

        from lzy_tpu.utils.clock import SYSTEM_CLOCK

        class _Timer:
            def __enter__(self):
                self._t0 = SYSTEM_CLOCK.now()
                return self

            def __exit__(self, *exc):
                hist.observe(SYSTEM_CLOCK.now() - self._t0, **labels)

        return _Timer()

    def collect(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        with self._lock:
            for key, counts in sorted(self._counts.items()):
                for i, bound in enumerate(self.buckets):
                    lk = _fmt_labels(key + (("le", str(bound)),))
                    out.append(f"{self.name}_bucket{lk} {counts[i]}")
                lk = _fmt_labels(key + (("le", "+Inf"),))
                out.append(f"{self.name}_bucket{lk} {counts[-1]}")
                out.append(f"{self.name}_sum{_fmt_labels(key)} {self._sums[key]}")
                out.append(f"{self.name}_count{_fmt_labels(key)} {counts[-1]}")
        return out


class MetricsRegistry:
    def __init__(self) -> None:
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get_or_create(name, lambda: Counter(name, help_), Counter)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get_or_create(name, lambda: Gauge(name, help_), Gauge)

    def histogram(self, name: str, help_: str = "",
                  buckets: Sequence[float] = Histogram.DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(
            name, lambda: Histogram(name, help_, buckets), Histogram
        )

    def _get_or_create(self, name, factory, expected_type):
        with self._lock:
            metric = self._metrics.get(name)
            if metric is None:
                metric = factory()
                self._metrics[name] = metric
            elif not isinstance(metric, expected_type):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(metric).__name__}"
                )
            return metric

    def exposition(self) -> str:
        lines: List[str] = []
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            lines.extend(m.collect())
        return "\n".join(lines) + "\n"

    def serve(self, port: int = 0) -> "MetricsServer":
        return MetricsServer(self, port)


class MetricsServer:
    """`GET /metrics` exposition endpoint (PrometheusMetricReporter parity)."""

    def __init__(self, registry: MetricsRegistry, port: int = 0):
        reg = registry

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                if self.path != "/metrics":
                    self.send_response(404)
                    self.end_headers()
                    return
                body = reg.exposition().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # quiet
                pass

        self._httpd = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="metrics", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


# the process-global default registry, like prometheus's default collector
REGISTRY = MetricsRegistry()

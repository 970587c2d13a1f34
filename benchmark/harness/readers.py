"""The per-layer metrics' readers. A metric's file names one of these and
its arguments; a reader takes what the run observed (``obs``) and returns
the value, or None where there was nothing to read (the harness then leaves
the metric out). A new metric over an existing kind of source is a new file
under ``metrics/`` and a new entry in ``BENCHMARK.json``."""

from __future__ import annotations

import re

from benchmark.harness import costs, trace as xtrace
from benchmark.harness.common import percentile


def latency_field(obs, *, field):
    """A number the latency accounting already made (``late_p95``)."""
    return (obs.get("latencies") or {}).get(field)


def call_overhead(obs, *, percentile_q):
    """Time a ``gateway.generate`` call spent outside the engine's
    submit -> finish, over the calls that ended in the window."""
    xs = [t_out - t_in - engine_s for t_in, t_out, engine_s, _ in
          obs.get("calls", [])
          if obs["t_open"] <= t_out <= obs["t_close"] and engine_s > 0]
    return percentile(xs, percentile_q) if xs else None


def outside_calls_share(obs):
    """Share of the clients' unit time, inside the window, in which the
    client had no row inside ``gateway.generate``: the entry layer's own
    time (fan-out, dedup, the pool's queue, fan-in, the workflow)."""
    calls, rows = obs.get("calls", []), obs.get("row_client")
    if not calls or not rows:
        return None
    by_client: dict = {}
    for a, b, _, key in calls:
        if key in rows and b > obs["t_open"] and a < obs["t_close"]:
            by_client.setdefault(rows[key], []).append(
                (max(a, obs["t_open"]), min(b, obs["t_close"])))
    # every client is inside a unit throughout the window, so the clients'
    # unit time is the window times the clients
    covered = sum(xtrace.length(xtrace.union(iv))
                  for iv in by_client.values())
    whole = len(obs["clients"]) * (obs["t_close"] - obs["t_open"])
    return 100.0 * (1.0 - covered / whole)


def calls_in_flight(obs):
    """Mean number of rows inside ``gateway.generate`` over the window."""
    calls = obs.get("calls", [])
    if not calls:
        return None
    inside = sum(max(0.0, min(b, obs["t_close"]) - max(a, obs["t_open"]))
                 for a, b, _, _ in calls)
    return inside / (obs["t_close"] - obs["t_open"])


def counter_share(obs, *, numerator, denominator, scale=100.0):
    """Sums of the program's counters over the window, as a share."""
    c = obs.get("counters") or {}
    den = sum(c.get(k, 0.0) for k in denominator)
    if not den:
        return None
    return scale * sum(c.get(k, 0.0) for k in numerator) / den


def sample_share(obs, *, numerator, denominator, scale=100.0):
    """Mean over the sampler's rows of one column over another. Columns:
    busy, slots, blocks_total, blocks_cached (unreferenced, kept by the
    radix tree), blocks_live (neither free nor cached: held by resident
    rows), queue_depth."""
    rows = obs.get("samples") or []
    if not rows:
        return None
    cols = {"busy": 1, "slots": 2, "blocks_total": 3, "queue_depth": 5,
            "blocks_cached": 6}

    def col(r, name):
        if name == "blocks_live":
            return (r[3] or 0) - (r[4] or 0) - (r[6] or 0)
        return r[cols[name]] or 0

    den = sum(col(r, denominator) for r in rows)
    return scale * sum(col(r, numerator) for r in rows) / den if den else None


def module_time(obs, *, module, percentile_q):
    """Device time of each execution of a jitted program, from the trace."""
    xs = (obs.get("trace") or {}).get("modules", {}).get(module)
    return percentile(xs, percentile_q) if xs else None


def decode_roofline(obs, *, module):
    """Bound: HBM. The least time the traced decode rounds could take
    (weights once a round, plus the keys and values of the rows resident
    during the trace, over the chip's bytes/s) against the device time they
    took. Resident context is read from the clients' token arrivals inside
    the traced span."""
    from benchmark.harness import accounting

    xs = (obs.get("trace") or {}).get("modules", {}).get(module)
    m, span = obs.get("model") or {}, obs.get("trace_span")
    if not xs or not span or not obs.get("rows"):
        return None
    context = accounting.resident_tokens(obs["rows"], *span)
    need = costs.decode_step_bytes(m["param_bytes"], m["kv_bytes_per_token"],
                                   context)
    least = need / costs.peaks(obs["device_kind"])["bytes_per_s"]
    return 100.0 * least * len(xs) / sum(xs)


def train_mfu(obs):
    """Forward and backward FLOPs per token times the window's tokens/s,
    over the chips' peak."""
    m, rate = obs.get("model") or {}, (obs.get("rate") or {})
    if not rate.get("tokens_per_s"):
        return None
    flops = costs.train_flops_per_token(m["cfg"], m["seq"])
    peak = costs.peaks(obs["device_kind"])["flops_per_s"] * m["chips"]
    return 100.0 * flops * rate["tokens_per_s"] / peak


def collective_exposed(obs):
    """Time in collectives during which no compute ran on that device, as a
    share of the traced window (mean over devices)."""
    t = obs.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]


def kernel_roofline(obs, *, match, module):
    """Bound: compute. The flash kernels' FLOPs for the traced steps
    (forward once, backward twice the forward; with recomputation the
    forward runs again and its time is in the denominator all the same)
    over the chip's peak, against the kernels' traced device time."""
    t, m = obs.get("trace") or {}, obs.get("model") or {}
    events = [e for e in t.get("op_events", [])
              if re.search(match, e[2][:200])]
    steps = len(t.get("modules", {}).get(module, []))
    if not events or not steps:
        return None
    per_chip_tokens = m["batch"] * m["seq"] / m["chips"]
    flops = steps * per_chip_tokens * costs.attention_flops_per_token(
        m["cfg"], m["seq"])
    least = flops / costs.peaks(obs["device_kind"])["flops_per_s"]
    return 100.0 * least / (sum(e[1] for e in events) / 1e9)


READERS = {f.__name__: f for f in (
    latency_field, call_overhead, outside_calls_share, calls_in_flight,
    counter_share, sample_share, module_time, decode_roofline, train_mfu,
    collective_exposed, kernel_roofline)}


def read(metric: dict, obs: dict):
    return READERS[metric["reader"]](obs, **metric.get("args", {}))

"""From a profiler trace (``.xplane.pb``) to numbers. Reads the file with
``jax.profiler.ProfileData`` alone.

What a TPU trace holds (seen on a v5e, JAX as installed): a plane
``/device:TPU:<n>`` for each chip with the lines ``XLA Modules`` (one event
for each execution of a jitted program, named ``jit_<name>(<hash>)``),
``XLA Ops`` (one event for each HLO operation, named by its HLO text) and
``Async XLA Ops`` (the span from an asynchronous operation's start to its
done); and a plane ``/host:CPU`` with a line for each host thread."""

from __future__ import annotations

import glob
import os
import re

_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
_OP_NAME = re.compile(r"^%?([\w.\-]+)")
_SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """``{"devices": {plane name: {line name: [(start_ns, dur_ns, name)]}},
    "host": {thread line: [...]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            where = out["devices"].setdefault(plane.name, {})
        elif plane.name == "/host:CPU":
            where = out["host"]
        else:
            continue
        for line in plane.lines:
            where[line.name] = [(float(e.start_ns), float(e.duration_ns),
                                 e.name) for e in line.events]
    return out


def union(intervals: list) -> list:
    """Merged ``(start, end)`` intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def length(merged: list) -> float:
    return sum(e - s for s, e in merged)


def subtract(a: list, b: list) -> list:
    """The part of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def module_name(event_name: str) -> str:
    """``jit_decode_step(1562...)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def op_label(hlo: str) -> str:
    """A short, stable label for an HLO operation: its kind and the shape it
    makes, as ``fusion_bf16_32_14336_``; names' numbers are dropped so that
    the same operation in each layer groups together."""
    m = _OP_NAME.match(hlo)
    kind = re.sub(r"[.\d]+$", "", m.group(1)) if m else "op"
    shape = _SHAPE.search(hlo)
    label = kind + ("_" + shape.group(1) if shape else "")
    return re.sub(r"[^\w\-]+", "_", label)[:60]


def _iv(events):
    return [(s, s + d) for s, d, _ in events]


def reduce(trace: dict) -> dict:
    """Everything the readers and the result line take from one trace.
    Times are seconds; per-device quantities are averaged over devices."""
    devices = trace["devices"]
    if not devices:
        return {}
    window_ns = busy_ns = exposed_ns = collective_ns = 0.0
    modules: dict = {}            # module -> [durations in s] (device 0)
    ops: dict = {}                # "module:label" -> [seconds, count]
    gaps_first = []
    op_events_first = []
    for idx, (name, lines) in enumerate(sorted(devices.items())):
        xla_ops = lines.get("XLA Ops", [])
        mods = sorted(lines.get("XLA Modules", []))
        everything = _iv(xla_ops) or _iv(mods)
        if not everything:
            continue
        lo = min(s for s, _ in everything)
        hi = max(e for _, e in everything)
        window_ns += hi - lo
        busy = union(everything)
        busy_ns += length(busy)
        coll = [e for e in xla_ops if _COLLECTIVE.search(e[2][:160])]
        coll += [e for e in lines.get("Async XLA Ops", [])
                 if _COLLECTIVE.search(e[2][:160])]
        compute = union(_iv([e for e in xla_ops
                             if not _COLLECTIVE.search(e[2][:160])]))
        coll_u = union(_iv(coll))
        collective_ns += length(coll_u)
        exposed_ns += length(subtract(coll_u, compute))
        if idx:
            continue
        for s, d, n in mods:
            modules.setdefault(module_name(n), []).append(d / 1e9)
        starts = [m[0] for m in mods]
        import bisect

        for s, d, n in xla_ops:
            i = bisect.bisect_right(starts, s) - 1
            owner = module_name(mods[i][2]) if i >= 0 and \
                s < mods[i][0] + mods[i][1] else "_no_module_"
            row = ops.setdefault(owner + ":" + op_label(n), [0.0, 0])
            row[0] += d / 1e9
            row[1] += 1
        gaps_first = subtract([(lo, hi)], busy)
        op_events_first = xla_ops
    n = max(1, len(devices))
    return {
        "window_s": window_ns / n / 1e9, "busy_s": busy_ns / n / 1e9,
        "collective_s": collective_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "modules": modules, "ops": ops,
        "op_events": op_events_first,
        "idle_gaps": attribute_gaps(gaps_first, trace["host"]),
        "devices": len(devices),
    }


def attribute_gaps(gaps: list, host: dict, *, long_ns: float = 20e3) -> dict:
    """Each idle gap of the device longer than ``long_ns`` goes to the host
    event that covers most of it (the innermost, that is the shortest, of
    those that cover its middle); shorter ones to ``_short_gaps_``; one that
    no host event covers to ``_no_host_event_``. Host and device clocks
    agree to a millisecond or so, which is the resolution of this table.
    Returns ``{label: seconds}``."""
    import bisect

    events = sorted((s, s + d, n) for line in host.values()
                    for s, d, n in line if d > 0)
    starts = [e[0] for e in events]
    out: dict = {}
    for s, e in gaps:
        if e - s < long_ns:
            out["_short_gaps_"] = out.get("_short_gaps_", 0.0) + (e - s) / 1e9
            continue
        mid = (s + e) / 2
        hi = bisect.bisect_right(starts, mid)
        best = None
        # the events that began before the middle; look back a bounded way
        for ev in events[max(0, hi - 400):hi]:
            if ev[1] >= mid and (best is None
                                 or ev[1] - ev[0] < best[1] - best[0]):
                best = ev
        label = re.sub(r"[^\w.:\-]+", "_", best[2])[:60] if best \
            else "_no_host_event_"
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(((k + f"__x{v[1]}", v[0])
                  for k, v in reduced.get("ops", {}).items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced.get("idle_gaps", {}).items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def start(trace_dir: str) -> None:
    """Start a trace without the Python tracer: it records every Python
    call of every thread, which slows a host that serves while it is traced
    and buries the few events that say what the host was doing."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()

"""The engine loop's spans placed on the device trace's clock: the device's
idle time read by the phase the loop was in, and two latencies a decode
round. ``harness/trace.py`` ``attribute_gaps`` gives each idle gap whole to
the innermost host event over its middle, which is the runtime's
(``np.asarray``, ``ReadSyncFlag``): it says which call the host was in, not
which phase of the loop, and under the round's fence it mixes a program that
has not started yet with one that has finished and whose tokens the loop has
not got. This reader cuts the gaps at the spans' boundaries instead.

**The offset.** The program stamps its records (``obs["spans"]``) with
``time.monotonic()``. While it records it also puts an instant annotation
``lzy.clock.<time.monotonic_ns()>`` into the profile's ``/host:CPU`` plane, at
the recorder's start and then one a second from the engine's loop. An
annotation's start on the profiler's clock less the number in its name is an
offset; the median over the trace's annotations is what a record's stamp, in
nanoseconds, is moved by. ``anchor_spread_us`` is the largest offset less the
smallest: two clocks of one host, so tens of microseconds. With fewer than
two annotations in the trace nothing is placed and every value is left out.

**The loop's self time.** The records named in ``LOOP`` are written by the
engine loop's thread alone (a cell has one engine). A name's intervals are its
records less their children among those names: ``engine.round`` less every
phase (what no phase covers), ``engine.prefill`` less ``engine.prefill.fence``.

**The device.** Device 0 as ``harness/trace.py`` ``reduce`` takes it: busy is
the union of its ``XLA Ops`` events, the traced window runs from the first
event's start to the last one's end, idle is the window less busy. An idle
share here is a share of that window, so the shares of names that cover the
loop between them add up to the result line's ``1 - busy_s / window_s``.

**A decode round** is an ``engine.decode.dispatch`` and an
``engine.decode.fence`` record with one parent, both inside the window, paired
with the first execution of ``PROGRAM`` that *ends* after the dispatch record's
start (a program lasts milliseconds, so the pairing holds while the two clocks
disagree by less than that; the first that *starts* after it pairs a round
with its successor's program as soon as the device's clock is early by more
than the launch takes, which it is: below).

**The device's clock.** A program cannot start before its enqueue began, nor
a fence end before its program did. On a v5e the profile's device plane sits
0.3 to 2.7 ms *early* against its host plane, another amount in every trace
(PERF.md section 6, PR 39: programs "start" a millisecond before their
dispatch span does), which is as large as the gaps to be read. So the smallest
(program start - dispatch start) and the smallest (fence end - program end)
over the rounds bracket what the device's clock may be moved by, and the
records are placed against the middle of that bracket: after it both minima
are equal. ``clock_window_ms`` is their sum, which no shift changes: the
width of the bracket, so half of it is the error bar of a latency below and,
times the rounds a second, of the split of idle time between the fence and
the phases around it. Where it is negative no one shift makes every round
causal (the clocks drift, or a round is paired wrongly): nothing is moved and
the two latencies are left out.

**Two latencies a round**, after that: the launch lag is the device's *idle*
time between the end of the dispatch record and the program's start (the host
has handed the round over and the device has nothing to run; a prefill
program queued in front is busy time, not lag); the fence tail is the end of
the fence record less the program's end (the device has finished and the
loop has not got its tokens).

The xplane file is found here (the newest directory under ``benchmark/
.trace/``, which ``run.py`` makes before the run and removes after the
readers) and loaded once a process; what is made of it is kept in
``obs["placed_spans"]`` for the metrics that follow."""

import bisect
import functools
import os

from benchmark.harness import common
from benchmark.harness import trace as xtrace

ANCHOR = "lzy.clock."
ROUND = "engine.round"
DISPATCH, FENCE = "engine.decode.dispatch", "engine.decode.fence"
PROGRAM = "jit_decode_step"         # the decode round's program, any model
LOOP = (ROUND, "engine.kv_io", "engine.reap", "engine.admit",
        "engine.prefill", "engine.prefill.fence", "engine.decode.plan",
        DISPATCH, "engine.decode.overlap", FENCE, "engine.decode.emit",
        "engine.park")


def newest_trace():
    """The xplane file of the newest directory under ``.trace/``, or None."""
    root = os.path.join(common.BENCH_DIR, ".trace")
    try:
        dirs = [os.path.join(root, d) for d in os.listdir(root)]
    except FileNotFoundError:
        return None
    dirs = [d for d in dirs if os.path.isdir(d)]
    return xtrace.find_xplane(max(dirs, key=os.path.getmtime)) if dirs \
        else None


@functools.lru_cache(maxsize=1)
def _load(path):
    return xtrace.load(path)


def anchors_in(host: dict) -> list:
    """``(the number in the name, start on the profiler's clock)``, both in
    nanoseconds, of every anchor annotation of the host plane."""
    found = []
    for line in host.values():
        for start, _, name in line:
            if name.startswith(ANCHOR) and name[len(ANCHOR):].isdigit():
                found.append((int(name[len(ANCHOR):]), start))
    return found


def device_zero(devices: dict):
    """``(programs, busy, window)`` of the first device plane: its ``XLA
    Modules`` events sorted by start, the merged intervals in which an
    operation ran, and ``(lo, hi)``; None where the plane holds nothing."""
    if not devices:
        return None
    lines = devices[sorted(devices)[0]]
    programs = sorted(lines.get("XLA Modules", []))
    ran = [(s, s + d) for s, d, _ in lines.get("XLA Ops", []) or programs]
    if not ran:
        return None
    window = (min(s for s, _ in ran), max(e for _, e in ran))
    return programs, xtrace.union(ran), window


def _on_profilers_clock(span: dict, offset: float) -> tuple:
    return span["start"] * 1e9 + offset, span["end"] * 1e9 + offset


def self_time(spans: list, offset: float) -> dict:
    """``{name: merged intervals}`` on the profiler's clock, in nanoseconds,
    for the names in ``LOOP``: each record less its children among them."""
    loop = [s for s in spans if s["name"] in LOOP]
    names = {s["id"]: s["name"] for s in loop}
    own = {name: [] for name in LOOP}
    children = {name: [] for name in LOOP}
    for s in loop:
        iv = _on_profilers_clock(s, offset)
        own[s["name"]].append(iv)
        if s["parent"] in names:
            children[names[s["parent"]]].append(iv)
    return {name: xtrace.subtract(xtrace.union(own[name]),
                                  xtrace.union(children[name]))
            for name in LOOP}


class Idle:
    """The device's idle intervals (merged, in nanoseconds) with the idle
    time before each, so that the idle time inside any interval is two
    look-ups."""

    def __init__(self, window, busy):
        self.gaps = xtrace.subtract([window], busy)
        self.starts = [s for s, _ in self.gaps]
        self.before = [0.0]
        for s, e in self.gaps:
            self.before.append(self.before[-1] + e - s)

    def until(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        s, e = self.gaps[i]
        return self.before[i] + min(t, e) - s

    def inside(self, intervals: list) -> float:
        return sum(self.until(e) - self.until(s)
                   for s, e in xtrace.union(intervals))


def decode_rounds(spans, offset, programs, window) -> list:
    """``(dispatch start, dispatch end, fence end, program start, program
    end)`` in nanoseconds for each decode round inside the window."""
    runs = [(s, s + d) for s, d, n in programs
            if xtrace.module_name(n) == PROGRAM]
    ends = [e for _, e in runs]
    halves: dict = {}
    for s in spans:
        if s["name"] in (DISPATCH, FENCE) and s["parent"] is not None:
            halves.setdefault(s["parent"], {})[s["name"]] = \
                _on_profilers_clock(s, offset)
    out = []
    for pair in halves.values():
        if len(pair) < 2:
            continue
        (d0, d1), (_, f1) = pair[DISPATCH], pair[FENCE]
        i = bisect.bisect_right(ends, d0)
        if d0 >= window[0] and f1 <= window[1] and i < len(runs):
            out.append((d0, d1, f1) + runs[i])
    return out


def place(spans, anchors, programs, busy, window):
    """Everything the metrics read, from plain lists (the arithmetic, apart
    from the files): ``spans`` the program's records, ``anchors`` as
    ``anchors_in`` gives them, ``programs`` / ``busy`` / ``window`` as
    ``device_zero`` does. None with fewer than two anchors."""
    if len(anchors) < 2:
        return None
    offsets = sorted(start - ns for ns, start in anchors)
    offset = common.percentile(offsets, 50)
    idle = Idle(window, busy)
    placed = {"anchor_spread_us": (offsets[-1] - offsets[0]) / 1e3,
              "window_ns": window[1] - window[0], "idle": idle,
              "clock_skew_ns": 0.0}
    rounds = decode_rounds(spans, offset, programs, window)
    if rounds:
        lead = min(p0 - d0 for d0, _, _, p0, _ in rounds)
        tail = min(f1 - p1 for _, _, f1, _, p1 in rounds)
        placed["clock_window_ms"] = (lead + tail) / 1e6
        if lead + tail >= 0:
            # the device's clock moved later by ``skew``: the records moved
            # earlier by it, which is the same and leaves the window alone
            skew = placed["clock_skew_ns"] = (tail - lead) / 2
            placed["launch_lag_ms_p50"] = common.percentile(
                [max(0.0, idle.until(p0) - idle.until(d1 - skew))
                 for _, d1, _, p0, _ in rounds], 50) / 1e6
            placed["fence_tail_ms_p50"] = common.percentile(
                [f1 - skew - p1 for _, _, f1, _, p1 in rounds], 50) / 1e6
    placed["offset_ns"] = offset - placed["clock_skew_ns"]
    placed["self"] = self_time(spans, placed["offset_ns"])
    placed["rounds"] = rounds
    return placed


def idle_share(placed, *, under=None, outside=None):
    """The device's idle time under the self time of the names in ``under``
    (or under none of the names in ``outside``), in % of the traced
    window."""
    idle = placed["idle"]
    ns = idle.inside([iv for name in under or outside
                      for iv in placed["self"][name]])
    if under is None:
        ns = idle.before[-1] - ns
    return 100.0 * ns / placed["window_ns"]


def placement(obs):
    """``place`` over the run's trace and ``obs["spans"]``, made once."""
    if "placed_spans" not in obs:
        path = newest_trace() if obs.get("spans") else None
        trace = _load(path) if path else None
        device = trace and device_zero(trace["devices"])
        obs["placed_spans"] = device and place(
            obs["spans"], anchors_in(trace["host"]), *device)
    return obs["placed_spans"]


def read(obs, *, value, under=None, outside=None):
    placed = placement(obs)
    if not placed:
        return None
    if value == "idle_share":
        return idle_share(placed, under=under, outside=outside)
    return placed.get(value)

"""The arithmetic of ``readers/placed_spans.py`` and ``readers/span_stat.py``
on lists built by hand. Times below are milliseconds on the profiler's clock;
the program's records are written 5 ms earlier on ``time.monotonic()``, which
is the offset the three anchors give.

The device runs three decode programs, a prefill program in front of the
second, and an instant and a short program that open and end the window (10
to 110 ms, busy 36.5, idle 63.5). Round A is a plain decode round; round B
has a prefill program queued in front of its decode program; then the loop
parks for 20 ms; round C begins 2 ms after the park ended, inside a gap of 6
ms that an attribution by the gap's middle would give whole to one phase.
The quickest launch (round A: 2 ms from the dispatch's start) and the
shortest tail (rounds A and B: 2 ms) are equal, so the device's clock sits in
the middle of its bracket as it is and nothing is moved; the tests that move
it say so."""

import json
import os

import pytest

from benchmark.harness import common, readers
from benchmark.harness import trace as xtrace
from benchmark.readers import placed_spans, span_stat

OFFSET_MS = 5.0
MS = 1e6                                   # nanoseconds
ANCHORS = [(n * 1_000_000_000, n * 1_000_000_000 + OFFSET_MS * MS + late)
           for n, late in enumerate((-10.0, 0.0, 10.0))]
PROGRAMS = [(10 * MS, 0.0, "jit__split_rng(3)"),
            (13 * MS, 7 * MS, "jit_decode_step(1)"),
            (32 * MS, 13 * MS, "jit_prefill_step(2)"),
            (45 * MS, 5 * MS, "jit_decode_step(1)"),
            (86 * MS, 11 * MS, "jit_decode_step(1)"),
            (109.5 * MS, 0.5 * MS, "jit__split_rng(3)")]
BUSY = xtrace.union([(s, s + d) for s, d, _ in PROGRAMS])
WINDOW = (10 * MS, 110 * MS)
_ids = iter(range(1, 1000))


def span(name, a, b, parent=None, **attrs):
    """A record from ``a`` to ``b`` ms of the profiler's clock."""
    return {"name": name, "start": (a - OFFSET_MS) / 1e3,
            "end": (b - OFFSET_MS) / 1e3, "thread": "inference-engine",
            "id": next(_ids), "parent": parent, "request": None,
            "attrs": attrs}


def a_round(a, b, phases):
    rnd = span("engine.round", a, b)
    out = [rnd]
    for name, lo, hi, *under in phases:
        parent = rnd["id"] if not under else next(
            s["id"] for s in out if s["name"] == under[0])
        out.append(span(name, lo, hi, parent))
    return out


def the_spans(fence_a_ends=22.0):
    return (
        a_round(10, 30, [("engine.decode.plan", 10, 11),
                         ("engine.decode.dispatch", 11, 12),
                         ("engine.decode.overlap", 12, 12.5),
                         ("engine.decode.fence", 12.5, fence_a_ends),
                         ("engine.decode.emit", fence_a_ends, 23)])
        + a_round(30, 60, [("engine.admit", 30, 31),
                           ("engine.prefill", 31, 40),
                           ("engine.prefill.fence", 36, 40, "engine.prefill"),
                           ("engine.decode.plan", 40, 41),
                           ("engine.decode.dispatch", 41, 42),
                           ("engine.decode.overlap", 42, 42),
                           ("engine.decode.fence", 42, 52),
                           ("engine.decode.emit", 52, 53)])
        + [span("engine.park", 60, 80),
           span("lzy.clock", 60, 60, monotonic_ns=55_000_000),
           span("engine.request.queued", 20, 60)]
        + a_round(82, 110, [("engine.prefill", 82, 82.5),
                            ("engine.prefill.fence", 82.2, 82.5,
                             "engine.prefill"),
                            ("engine.decode.plan", 82.5, 83),
                            ("engine.decode.dispatch", 83, 84),
                            ("engine.decode.overlap", 84, 84.2),
                            ("engine.decode.fence", 84.2, 100),
                            ("engine.decode.emit", 100, 101)]))


FENCE = ["engine.decode.fence"]
HOST = ["engine.decode.plan", "engine.decode.dispatch",
        "engine.decode.overlap", "engine.decode.emit"]
FRONT = ["engine.kv_io", "engine.reap", "engine.admit", "engine.prefill",
         "engine.prefill.fence"]
PARK = ["engine.park"]


def test_idle_time_is_cut_at_the_spans_boundaries():
    placed = placed_spans.place(the_spans(), ANCHORS, PROGRAMS, BUSY, WINDOW)
    assert placed["offset_ns"] == OFFSET_MS * MS
    assert placed["clock_skew_ns"] == 0.0
    assert placed["anchor_spread_us"] == pytest.approx(0.02)
    assert placed["idle"].before[-1] == pytest.approx(63.5 * MS)
    share = lambda names: placed_spans.idle_share(placed, under=names)  # noqa: E731
    # the window is 100 ms, so a share in % is milliseconds
    assert share(FENCE) == pytest.approx(0.5 + 2.0 + 2.0 + 1.8 + 3.0)
    assert share(HOST) == pytest.approx(3.5 + 1.0 + 2.7)
    # round B's phases under the prefill program are busy time; engine.prefill
    # is charged less its fence
    assert share(FRONT) == pytest.approx(1.0 + 1.0 + 0.2 + 0.3)
    assert share(["engine.prefill"]) == pytest.approx(1.0 + 0.2)
    assert share(PARK) == pytest.approx(20.0)
    unplaced = placed_spans.idle_share(
        placed, outside=FENCE + HOST + FRONT + PARK)
    # the rounds' ends that no phase covers, and the 2 ms between the park
    # and round C: the gap from 80 to 86 is cut in five
    assert unplaced == pytest.approx(7.0 + 7.0 + 2.0 + 8.5)
    assert share(["engine.round"]) == pytest.approx(7.0 + 7.0 + 8.5)
    total = share(FENCE) + share(HOST) + share(FRONT) + share(PARK) + unplaced
    assert total == pytest.approx(63.5, abs=1e-9)


def test_launch_lag_and_fence_tail_a_round():
    placed = placed_spans.place(the_spans(), ANCHORS, PROGRAMS, BUSY, WINDOW)
    # dispatch start and end, fence end, program start and end; round B's
    # program waits 3 ms behind a prefill program
    assert [[round(x / MS, 3) for x in r] for r in sorted(
        placed["rounds"])] == [[11, 12, 22, 13, 20], [41, 42, 52, 45, 50],
                               [83, 84, 100, 86, 97]]
    # idle after the dispatch: 1, 0 (the prefill program is busy time, not
    # lag) and 2 ms; tails 2, 2 and 3 ms
    assert placed["launch_lag_ms_p50"] == pytest.approx(1.0, abs=1e-4)
    assert placed["fence_tail_ms_p50"] == pytest.approx(2.0, abs=1e-4)
    assert placed["clock_window_ms"] == pytest.approx(2.0 + 2.0, abs=1e-4)


def _values(placed):
    shares = [placed_spans.idle_share(placed, under=names)
              for names in (FENCE, HOST, FRONT, PARK)]
    return shares + [placed["launch_lag_ms_p50"],
                     placed["fence_tail_ms_p50"], placed["clock_window_ms"]]


@pytest.mark.parametrize("early_ms", (1.7, -0.8))
def test_a_device_plane_off_by_a_millisecond_reads_as_one_that_is_not(
        early_ms):
    """What a v5e's profile does: its device plane sits a millisecond or two
    early against its host plane, so that programs seem to start before
    their dispatch began (1.7 early: round A's by 0.7 ms). The bracket the
    rounds give moves the records by that much, and every value is what it
    was; a pairing by the first program to *start* after the dispatch would
    have given round A round B's program."""
    shift = early_ms * MS
    programs = [(s - shift, d, n) for s, d, n in PROGRAMS]
    busy = [(s - shift, e - shift) for s, e in BUSY]
    window = (WINDOW[0] - shift, WINDOW[1] - shift)
    moved = placed_spans.place(the_spans(), ANCHORS, programs, busy, window)
    assert moved["clock_skew_ns"] == pytest.approx(shift)
    assert len(moved["rounds"]) == 3
    still = placed_spans.place(the_spans(), ANCHORS, PROGRAMS, BUSY, WINDOW)
    assert _values(moved) == pytest.approx(_values(still), abs=1e-6)


def test_rounds_that_no_one_shift_makes_causal_leave_the_latencies_out():
    # round A's fence ends 2.5 ms before its program does, and its program
    # starts 2 ms after its dispatch began: no clock is off both ways
    placed = placed_spans.place(the_spans(fence_a_ends=17.5), ANCHORS,
                                PROGRAMS, BUSY, WINDOW)
    assert placed["clock_window_ms"] == pytest.approx(2.0 - 2.5, abs=1e-4)
    assert placed["clock_skew_ns"] == 0.0
    assert "launch_lag_ms_p50" not in placed
    assert "fence_tail_ms_p50" not in placed
    # the shares need no pairing and are still there, unmoved
    assert placed_spans.idle_share(placed, under=PARK) == pytest.approx(20.0)


def test_a_round_cut_by_the_windows_edge_is_not_paired():
    early = (12 * MS, WINDOW[1])          # round A's dispatch began before
    placed = placed_spans.place(the_spans(), ANCHORS, PROGRAMS, BUSY, early)
    assert len(placed["rounds"]) == 2


def test_one_anchor_places_nothing():
    assert placed_spans.place(the_spans(), ANCHORS[:1], PROGRAMS, BUSY,
                              WINDOW) is None


def _metric(name):
    with open(os.path.join(common.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def _trace():
    return {"devices": {"/device:TPU:0": {"XLA Modules": PROGRAMS,
                                          "XLA Ops": PROGRAMS}},
            "host": {"python3": [(13 * MS, 1.0, "np.asarray")],
                     "lzy-engine-1": [
                         (start, 0.0, f"lzy.clock.{ns}")
                         for ns, start in ANCHORS]}}


FIVE = ["device.idle_decode_fence_share", "device.idle_decode_host_share",
        "device.idle_prefill_share", "device.idle_park_share",
        "device.idle_unplaced_share"]
PLACED = FIVE + ["device.launch_lag_ms_p50", "device.fence_tail_ms_p50",
                 "trace.clock_window_ms", "trace.anchor_spread_us",
                 "device.backlog_idle_fence_share",
                 "device.backlog_idle_host_share",
                 "device.backlog_fence_tail_ms_p50"]


def test_the_registered_files_read_and_the_five_shares_add_up(monkeypatch):
    monkeypatch.setattr(placed_spans, "newest_trace", lambda: "a path")
    monkeypatch.setattr(placed_spans, "_load", lambda path: _trace())
    obs = {"spans": the_spans(), "t_open": 0.0, "t_close": 1.0}
    got = {name: readers.read(_metric(name), obs) for name in PLACED}
    assert got["device.idle_decode_fence_share"] == pytest.approx(9.3)
    assert got["device.idle_park_share"] == pytest.approx(20.0)
    assert sum(got[name] for name in FIVE) == pytest.approx(63.5, abs=1e-9)
    assert got["device.launch_lag_ms_p50"] == pytest.approx(1.0, abs=1e-4)
    assert got["device.fence_tail_ms_p50"] == \
        got["device.backlog_fence_tail_ms_p50"] == \
        pytest.approx(2.0, abs=1e-4)
    assert got["trace.clock_window_ms"] == pytest.approx(4.0, abs=1e-4)
    assert got["trace.anchor_spread_us"] == pytest.approx(0.02)
    # either fence; every phase of a round but the fences
    assert got["device.backlog_idle_fence_share"] == pytest.approx(9.3 + 0.3)
    assert got["device.backlog_idle_host_share"] == pytest.approx(7.2 + 2.2)


def test_nothing_to_place_leaves_every_metric_out(monkeypatch):
    monkeypatch.setattr(placed_spans, "newest_trace", lambda: None)
    for obs in ({"spans": the_spans()}, {"spans": []}, {}):
        assert [readers.read(_metric(name), obs) for name in PLACED] == \
            [None] * len(PLACED)
    # a trace of a program that wrote no anchor: a device plane, no offset
    monkeypatch.setattr(placed_spans, "newest_trace", lambda: "a path")
    monkeypatch.setattr(placed_spans, "_load",
                        lambda path: dict(_trace(), host={}))
    assert readers.read(_metric(FIVE[0]), {"spans": the_spans()}) is None


def test_the_newest_trace_directory_is_the_runs(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "BENCH_DIR", str(tmp_path))
    assert placed_spans.newest_trace() is None
    for age, cell in enumerate(("newer", "older")):
        d = tmp_path / ".trace" / cell / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        stamp = 1_000_000 - 100 * age
        os.utime(tmp_path / ".trace" / cell, (stamp, stamp))
    assert placed_spans.newest_trace() == str(
        tmp_path / ".trace" / "newer" / "plugins" / "profile" / "run"
        / "host.xplane.pb")


def test_span_stat_counts_a_span_where_it_ends():
    def req(name, a, b):
        return {"name": name, "start": a, "end": b}

    obs = {"t_open": 10.0, "t_close": 20.0, "trace_span": (12.0, 16.0),
           "spans": [req("engine.request.queued", 9.0, 10.5),     # 1.5
                     req("engine.request.queued", 11.0, 11.5),    # 0.5
                     req("engine.request.queued", 15.0, 15.25),   # 0.25
                     req("engine.request.queued", 19.0, 21.0),    # out
                     req("engine.request.prefill", 8.0, 9.0),     # out
                     req("engine.request.prefill", 12.0, 12.5),
                     req("engine.admit", 13.0, 13.75)]}
    queued = dict(name="engine.request.queued", clip="window")
    assert span_stat.read(obs, stat="mean", **queued) == pytest.approx(0.75)
    assert span_stat.read(obs, stat="p50", **queued) == pytest.approx(0.5)
    assert span_stat.read(obs, stat="max", **queued) == pytest.approx(1.5)
    assert span_stat.read(obs, name="engine.request.queued", stat="max",
                          clip="trace") == pytest.approx(0.25)
    assert span_stat.read(obs, name=["engine.admit",
                                     "engine.request.prefill"],
                          stat="max") == pytest.approx(0.75)
    assert span_stat.read(obs, name="engine.park", stat="max") is None
    assert span_stat.read(dict(obs, trace_span=None), stat="mean",
                          name="engine.admit", clip="trace") is None
    for name in ("request.queue_wait_mean_s", "request.prefill_mean_s",
                 "engine.longest_leaf_s"):
        assert readers.find(_metric(name)) is span_stat.read
    assert readers.read(_metric("request.queue_wait_mean_s"), obs) == \
        pytest.approx(0.75)
    assert readers.read(_metric("request.prefill_mean_s"), obs) == \
        pytest.approx(0.5)
    assert readers.read(_metric("engine.longest_leaf_s"), obs) == \
        pytest.approx(0.75)

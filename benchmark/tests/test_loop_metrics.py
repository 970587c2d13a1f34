"""PR 26's two counter metrics (data files over ``counter_share``): the CPU
rehearsal lists them for ``chat-steady`` alone, and a program without the
loop's new phase labels still gives a reading."""

import argparse
import json
import os

from benchmark import run as bench_run
from benchmark.harness import readers
from benchmark.tests.test_rehearsal import CELLS, _files

NEW = {"engine.loop_host_share", "engine.prefill_share_of_loop"}
METRICS = os.path.join(os.path.dirname(__file__), "..", "metrics")


def _share(name, counters):
    with open(os.path.join(METRICS, name + ".json")) as f:
        m = json.load(f)
    return readers.READERS[m["reader"]]({"counters": counters}, **m["args"])


def test_the_rehearsal_lists_the_counter_metrics_for_chat_alone():
    names = {}
    for which in ("chat", "backlog"):
        args = argparse.Namespace(workload=CELLS[which][0], seed=7,
                                  seconds=3.0, trace=1)
        out = bench_run.run_cell(args, _files(*CELLS[which]),
                                 require_tpu=False)
        names[which] = set(out["rehearsal"]["metric_names"])
    assert NEW <= names["chat"] and not NEW & names["backlog"]
    assert "engine.host_share_of_round" in names["chat"]


def test_the_waits_for_the_device_are_no_host_time():
    c = {"round_phase." + k: v for k, v in {
        "kv_io": 1, "reap": 1, "admit": 4, "prefill": 10, "prefill_fence": 8,
        "plan": 6, "dispatch": 2, "overlap": 1, "fence": 60, "emit": 7,
        "park": 50}.items()}
    assert _share("engine.loop_host_share", c) == 100.0 * 31 / 100
    assert _share("engine.prefill_share_of_loop", c) == 100.0 * 22 / 100


def test_a_program_without_the_new_labels_still_reads():
    c = {"round_phase." + k: v for k, v in {
        "plan": 6, "overlap": 1, "fence": 60, "emit": 13}.items()}
    assert _share("engine.loop_host_share", c) == _share(
        "engine.host_share_of_round", c) == 100.0 * 19 / 80
    assert _share("engine.prefill_share_of_loop", c) == 0.0
    assert _share("engine.loop_host_share", {}) is None

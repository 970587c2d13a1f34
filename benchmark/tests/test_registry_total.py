"""PR 53's reader of set-up (``readers/registry_total.py``) and its six
metric files: totals of the program's registry less the window's own
difference, by series, label and site; None for a program that has no such
series; and the CPU rehearsal lists the six (five for the training cell)."""

import argparse
import json
import os

import jax
import jax.numpy as jnp

from benchmark import run as bench_run
from benchmark.harness import common, readers
from benchmark.readers import registry_total
from benchmark.tests.test_rehearsal import CELLS, _files
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

METRICS = os.path.join(os.path.dirname(__file__), "..", "metrics")
SIX = {"setup.program_build_s", "setup.build_python_share",
       "setup.programs_built", "setup.cache_hit_share",
       "setup.other_build_s", "setup.engine_init_s"}


def _metric(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def _read(name, obs=None):
    return readers.read(_metric(name), obs or {})


def test_samples_parse_names_labels_and_values():
    rows = registry_total.samples(
        '# TYPE a counter\na{site="x.y",cache="hit"} 3.0\nb_sum 0.5\n')
    assert rows == [('a{site="x.y",cache="hit"}', "a",
                     {"site": "x.y", "cache": "hit"}, 3.0),
                    ("b_sum", "b_sum", {}, 0.5)]


def test_a_program_without_the_series_reads_none():
    assert registry_total.read({}, numerator=["lzy_no_such_total"]) is None
    assert registry_total.read(
        {}, numerator=[{"series": "lzy_no_such_seconds_sum",
                        "labels": {"stage": ["trace"]}}],
        denominator=["lzy_program_builds_total"]) is None


def test_totals_by_site_and_the_window_is_taken_off():
    x = jnp.ones((5,))
    before = {name: _read(name) or 0.0 for name in SIX}
    with trace.building(trace.SITE_DECODE):
        jax.jit(lambda x: x * 3 + 1)(x)
    jax.jit(lambda x: x * 5 - 1)(x)                    # site other
    built = _read("setup.programs_built") - before["setup.programs_built"]
    assert built == 1
    assert _read("setup.program_build_s") > before["setup.program_build_s"]
    assert _read("setup.other_build_s") > before["setup.other_build_s"]
    assert 0 < _read("setup.build_python_share") < 100
    assert 0 <= _read("setup.cache_hit_share") <= 100
    # what the window itself counted is not set-up
    key = next(k for k, series, labels, _ in registry_total.samples(
        REGISTRY.exposition()) if series == "lzy_program_builds_total"
        and labels["site"] == trace.SITE_DECODE)
    assert _read("setup.programs_built", {"counters": {key: 1.0}}) == \
        before["setup.programs_built"]


def test_the_six_are_in_the_manifest_under_setup_s():
    m = common.load_manifest()
    mine = [x for x in m["per_layer"] if x["name"] in SIX]
    assert [x["name"] for x in m["per_layer"][-6:]] == [
        x["name"] for x in mine] and len(mine) == 6
    cells = [w["name"] for w in m["workloads"]]
    for x in mine:
        assert x["moves"] == "setup_s" and x["source"] == "program_counter"
        assert x["workloads"] == [c for c in cells if not (
            x["name"] == "setup.engine_init_s" and c == "train-fsdp4")]


def test_the_rehearsal_lists_them():
    names = {}
    for which in ("chat", "train"):
        args = argparse.Namespace(workload=CELLS[which][0], seed=5,
                                  seconds=3.0, trace=1)
        out = bench_run.run_cell(args, _files(*CELLS[which]),
                                 require_tpu=False)
        names[which] = set(out["rehearsal"]["metric_names"])
    assert SIX <= names["chat"]
    # one process: the chat cell's engine has left its set-up seconds in
    # the registry, so only the manifest keeps the sixth from the train cell
    assert SIX - {"setup.engine_init_s"} <= names["train"]
    assert "setup.engine_init_s" not in names["train"]

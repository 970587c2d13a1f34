"""BENCHMARK.json against the contract's limits that can be checked here."""

import json
import os
import re

from benchmark.harness import common, readers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_names_units_and_keys():
    m = common.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert len(json.dumps(m)) < 64 * 1024
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        names.append(c["name"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in m["workloads"]}
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = next(e for e in m["end_to_end"] if e["name"] == x["moves"])
        # the moved metric is reported in every cell where this one is
        assert set(x.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", [])) <= cells
        names.append(x["name"])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= \
        max(1, len(m["workloads"]) // 4)


def test_every_cell_finds_its_files_and_every_metric_its_reader():
    m = common.load_manifest()
    for w in m["workloads"]:
        files = common.cell_files(m, w["name"])
        assert files["traffic"]["kind"] in ("open_loop", "closed_loop_units",
                                            "train_job")
        e2e = {x["name"] for x in files["end_to_end"]}
        assert e2e > {"setup_s"}
        # the traffic file says which value each of them reports
        assert set(files["traffic"]["reports"]) == e2e - {"setup_s"}
        assert files["per_layer"]
        for x in files["per_layer"]:
            assert x["reader"] in readers.READERS
        assert files["config"]["name"] == w["config"]
        assert set(files["config"]["reduced"]) == set(next(
            c["reduced"] for c in m["configs"] if c["name"] == w["config"]))


def test_files_under_paths_are_named_from_the_allowed_characters():
    m = common.load_manifest()
    for root in m["paths"]:
        for d, _, fs in os.walk(os.path.join(common.REPO, root)):
            if "__pycache__" in d or "/.trace" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), common.REPO)
                assert PATH.match(rel), rel

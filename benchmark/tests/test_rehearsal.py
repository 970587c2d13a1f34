"""Each cell's code path through ``run.py`` at a tiny configuration on the
CPU. A rehearsal writes no number under a device metric's name; the command
itself fails without a TPU."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.harness import common

HERE = os.path.dirname(__file__)


def _files(cell, config, traffic, chips, like):
    def load(kind, name):
        with open(os.path.join(HERE, kind, name + ".json")) as f:
            return json.load(f)

    real = common.cell_files(common.load_manifest(), like)
    return {"cell": {"name": cell, "chips": chips},
            "config": load("configs", config),
            "traffic": load("traffic", traffic),
            "end_to_end": real["end_to_end"], "per_layer": real["per_layer"]}


CELLS = {
    "chat": ("tiny-chat", "tiny-serve", "tiny-chat", 1, "chat-steady"),
    "backlog": ("tiny-backlog", "tiny-serve", "tiny-backlog", 1,
                "batch-backlog"),
    "train": ("tiny-train", "tiny-train", "tiny-train", 4, "train-fsdp4"),
}


@pytest.mark.parametrize("which", sorted(CELLS))
@pytest.mark.parametrize("trace", (0, 1))
def test_cell_rehearses_on_the_cpu(which, trace):
    args = argparse.Namespace(workload=CELLS[which][0], seed=2 ** 31 + 11,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, _files(*CELLS[which]), require_tpu=False)
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    # no number under a device metric's name
    assert out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in out["device"]
    named = out["rehearsal"]["metric_names"]
    if not trace:
        assert "setup_s" in named and len(named) >= 2
    else:
        assert "breakdown" in out


def test_the_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", "chat-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=common.REPO, timeout=300)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())

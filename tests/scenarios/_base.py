"""Shared scenario bootstrap: an in-process cluster + SDK facade, stdout-only
deterministic output (scenario tier modeled on the reference's
pylzy/tests/scenarios/<name> + expected_stdout diffing, SURVEY.md §4.4)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def make_lzy():
    from lzy_tpu.service import InProcessCluster

    cluster = InProcessCluster(storage_uri="mem://scenario")
    return cluster, cluster.lzy()

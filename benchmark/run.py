#!/usr/bin/env python3
"""One cell of the benchmark, on the machine this is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Finds the cell's files by name from ``BENCHMARK.json`` (``harness/common.py``
``cell_files``), builds the system under test from the configuration, warms
every shape the traffic uses, checks the outputs against the plain
reference, runs the traffic's ramp, measures for ``--seconds``, and prints
one JSON object as the last line of its output. With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` a few seconds
of the window are traced and the metrics are its per-layer metrics.

It runs on a TPU or not at all: where JAX finds no TPU, or fewer chips than
the cell asks for, it exits with code 1 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T0 = time.monotonic()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def run_cell(args, files: dict, *, require_tpu: bool = True) -> dict:
    """Returns the result line as a dict. ``require_tpu=False`` is the CPU
    rehearsal's way in (``benchmark/tests``): the same code path at a tiny
    size, whose result carries no metric values."""
    from benchmark.harness import common, readers, serve, train
    from benchmark.harness import trace as xtrace
    from lzy_tpu.utils.jaxenv import device_summary, enable_compile_cache

    # the program's one fixed cache path inside the checkout, or the
    # directory the environment names
    enable_compile_cache()
    device = device_summary()
    chips = files["cell"]["chips"]
    if require_tpu and device["platform"] != "tpu":
        print(f"benchmark: JAX found platform={device['platform']!r} "
              f"({device['kind']}), not a TPU", file=sys.stderr)
        raise SystemExit(1)
    if device["count"] < chips:
        print(f"benchmark: the cell asks for {chips} chip(s), JAX reports "
              f"{device['count']}", file=sys.stderr)
        raise SystemExit(1)
    meter = common.CompileMeter()
    phases = common.Phases(T0)
    phases.mark("import")
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(BENCH_DIR, ".trace",
                                 files["cell"]["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    kind = files["traffic"]["kind"]
    runner = train.run if kind == "train_job" else serve.run
    out = runner(files, args, meter, phases, trace_dir)
    obs = out["obs"]
    setup_s = obs["t_open"] - T0
    obs["device_kind"] = device["kind"]

    doc_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": chips,
                  "memory_peak_bytes": common.memory_peak_bytes()}
    metrics: dict = {}
    breakdown = None
    if args.trace:
        path = xtrace.find_xplane(trace_dir)
        obs["trace"] = xtrace.reduce(xtrace.load(path)) if path else {}
        doc_device["busy_s"] = obs["trace"].get("busy_s", 0.0)
        doc_device["window_s"] = obs["trace"].get("window_s", 0.0)
        breakdown = xtrace.breakdown(obs["trace"])
        for m in files["per_layer"]:
            try:
                value = readers.read(m, obs)
            except KeyError:
                if require_tpu:
                    raise
                value = None      # the CPU has no row in the table of peaks
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        # the traffic file says which of its runner's values each
        # end-to-end metric of the cell reports
        reports = files["traffic"]["reports"]
        for m in files["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" \
                else out["values"].get(reports[m["name"]])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    print(json.dumps({"setup_phases_s": phases.seconds,
                      "compile": {"requests": meter.compiles,
                                  "cache_hits": meter.cache_hits,
                                  "seconds": meter.seconds},
                      "notes": out["notes"]}, default=str), flush=True)
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": doc_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if not require_tpu:
        # a CPU rehearsal writes no number under a device metric's name
        result["rehearsal"] = {"metric_names": sorted(metrics)}
        result["metrics"] = {}
        result["device"] = {k: doc_device[k]
                            for k in ("platform", "kind", "count")}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark.harness import common

    manifest = common.load_manifest()
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    files = common.cell_files(manifest, args.workload)
    result = run_cell(args, files)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # every thread this run started has been joined or is a daemon blocked
    # on a closed engine; leave at once, with the run's own exit code
    os._exit(code)

"""Where a program's device time goes, in the program's own words.

    python tools/part_table.py cell <workload> [--seed N] [--top 12]
    python tools/part_table.py trace <file.xplane.pb> [--top 12]

``cell`` runs one benchmark cell traced (``benchmark/run.py``'s own
``run_cell``, on a TPU or not at all), with the part metrics that
``benchmark/metrics/part_metrics.per_layer.json`` holds ready for
``BENCHMARK.json`` read beside the registered ones, and prints the result
line; then, from the run's trace, what ``trace`` prints for a trace file: for
each jitted program its device seconds, its share by ``part.<name>``
(``lzy_tpu/utils/trace.py`` ``PARTS``; ``benchmark/readers/part_share.py``
reads the trace's own ``tf_op``), the coverage, what is unnamed by ``tf_op``,
and its longest operations as ``[us a run, instruction, the label a
breakdown gives it, tf_op, part, flops, bytes_accessed]``. One JSON object a
line, so a chip call's last lines are the table.

:func:`program_ops` is the one join of a trace to ``op_name`` that ``tools/``
holds: the ``round`` commands of ``latent_select_bench.py``,
``motif_bench.py`` and ``longcat_bench.py`` trace a scratch program and call
it."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def program_ops(path: str, module: str, *, least_us: float = 0.0) -> dict:
    """``module`` in the trace at ``path``, a run: ``program_us``; ``ops``
    ``[[us, instruction, label, tf_op, part], ...]``, the longest first,
    those of ``least_us`` or more; ``by_part_us`` and ``by_label_us`` (the
    forty longest labels) over every operation. Self time throughout: a
    loop's body is listed under its own instructions."""
    from benchmark.readers import part_share

    row = part_share.operations(path).get(module)
    if not row:
        return {}
    runs = max(1, row["runs"])
    ops = sorted(([own * 1e6 / runs, name, label, tf_op, part]
                  for name, (own, _, label, tf_op, part, _, _)
                  in row["ops"].items()), reverse=True)
    by_part: dict = {}
    by_label: dict = {}
    for us, _, label, _, part in ops:
        by_part[part] = by_part.get(part, 0.0) + us
        by_label[label] = by_label.get(label, 0.0) + us

    def longest(sums, n=None):
        return {k: round(v, 1) for k, v in sorted(
            sums.items(), key=lambda kv: -kv[1])[:n]}

    return {"program_us": round(row["seconds"] * 1e6 / runs, 1),
            "by_part_us": longest(by_part),
            "by_label_us": longest(by_label, 40),
            "ops": [[round(us, 1)] + rest for us, *rest in ops
                    if us >= least_us]}


def traced_ops(step, *args, module: str = "jit_step", rounds: int = 5,
               least_us: float = 0.0) -> dict:
    """:func:`program_ops` of ``rounds`` traced calls of the jitted ``step``
    (warmed by one call first), whose trace knows it as ``module``: what the
    ``round`` and ``chunk`` commands of ``latent_select_bench.py``,
    ``motif_bench.py`` and ``longcat_bench.py`` print."""
    import jax

    from benchmark.harness import trace as tr

    jax.block_until_ready(step(*args))
    where = tempfile.mkdtemp(prefix="part_table_")
    try:
        tr.start(where)
        for _ in range(rounds):
            out = step(*args)
        jax.block_until_ready(out)
        tr.stop()
        return program_ops(tr.find_xplane(where), module, least_us=least_us)
    finally:
        shutil.rmtree(where, ignore_errors=True)


def describe(path: str, top: int) -> list:
    """The lines ``trace`` prints: one a program."""
    from benchmark.readers import part_share

    # what the reader costs a traced run: the metadata's decoding, and the
    # whole table (the trace's events come loaded, as they do in a run)
    t0 = time.monotonic()
    part_share.device_metadata(path)
    t1 = time.monotonic()
    part_share.operations.cache_clear()
    ops = part_share.operations(path)
    t2 = time.monotonic()
    table = part_share.table(path)
    lines = [{"metadata_decode_s": round(t1 - t0, 3),
              "table_s": round(t2 - t1, 3),
              "file_bytes": os.path.getsize(path)}]
    for module, row in sorted(table.items(), key=lambda kv: -kv[1]["seconds"]):
        whole = row["seconds"]
        if not whole:
            continue
        by_part = {p: round(100.0 * s / whole, 2) for p, s in sorted(
            row["parts"].items(), key=lambda kv: -kv[1])}
        runs = max(1, ops[module]["runs"])
        # flops and bytes an execution, as the compiler counted them for an
        # instruction, times its calls an execution
        sums: dict = {}
        for _, calls, _, _, part, flops, moved in ops[module]["ops"].values():
            got = sums.setdefault(part, [0, 0])
            got[0] += int(flops * calls / runs)
            got[1] += int(moved * calls / runs)
        lines.append({
            "module": module, "runs": runs,
            "device_ms_a_run": round(1e3 * whole / runs, 4),
            "parts_share": by_part,
            "named_share": round(sum(by_part.values()), 2),
            "busy_share": round(100.0 * sum(
                v[0] for v in ops[module]["ops"].values()) / whole, 2),
            "unnamed_share_by_tf_op": [
                [k, round(100.0 * s / whole, 2)] for k, s in sorted(
                    row["unnamed"].items(), key=lambda kv: -kv[1])[:top]],
            "flops_and_bytes_a_run_by_part": dict(sorted(sums.items())),
            "longest": [r + list(ops[module]["ops"][r[1]][5:])
                        for r in program_ops(path, module)["ops"][:top]]})
    return lines


def run_cell(workload: str, seed: int, top: int) -> None:
    from benchmark import run
    from benchmark.harness import common
    from benchmark.readers import placed_spans

    manifest = common.load_manifest()
    with open(os.path.join(common.BENCH_DIR, "metrics",
                           "part_metrics.per_layer.json")) as f:
        held = {m["name"] for m in manifest["per_layer"]}
        manifest["per_layer"] += [m for m in json.load(f)
                                  if m["name"] not in held]
    files = common.cell_files(manifest, workload)
    args = argparse.Namespace(workload=workload, seed=seed, trace=1,
                              seconds=float(manifest["run_seconds"]))
    found = {}

    def keep(path, **_):
        # ``run_cell`` removes the cell's trace once its readers are done;
        # the table below wants the file: note it, and remove it after
        found["xplane"] = placed_spans.newest_trace() or found.get("xplane")
        found["dir"] = path

    shutil.rmtree(os.path.join(common.BENCH_DIR, ".trace", workload),
                  ignore_errors=True)
    with mock.patch.object(run.shutil, "rmtree", keep):
        result = run.run_cell(args, files)
    print(json.dumps(result), flush=True)
    try:
        if found.get("xplane"):
            for line in describe(found["xplane"], top):
                print(json.dumps(dict(line, workload=workload)), flush=True)
    finally:
        if found.get("dir"):
            shutil.rmtree(found["dir"], ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = p.add_subparsers(dest="what", required=True)
    cell = sub.add_parser("cell")
    cell.add_argument("workload")
    cell.add_argument("--seed", type=int, default=0)
    trace = sub.add_parser("trace")
    trace.add_argument("path")
    for s in (cell, trace):
        s.add_argument("--top", type=int, default=12)
    args = p.parse_args(argv)
    if args.what == "trace":
        for line in describe(args.path, args.top):
            print(json.dumps(line), flush=True)
    else:
        run_cell(args.workload, args.seed, args.top)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # as benchmark/run.py leaves: a served cell's daemon threads are blocked
    # on a closed engine
    os._exit(code)

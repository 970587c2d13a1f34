"""Compile-level TPU performance evidence, no device required.

The compile-level half of the perf story needs no chip: this tool
AOT-compiles the flagship train step against **deviceless TPU topologies**
(`jax.experimental.topologies.get_topology_desc`) — the same libtpu
compiler the real chip uses — and records what the scheduler actually
built:

- per-device FLOPs and HBM bytes from XLA's cost analysis,
- the collective census of the SPMD module (op counts + bytes moved, a
  collective once by its channel), and which collectives of the scheduled
  ``ENTRY`` are asynchronous (:func:`collectives_scheduled`),
- compiled memory footprint (does the config fit in 16 GB HBM?),
- what a rematerialised layer's backward runs a second time
  (:func:`recompute_census`: matmuls, kernels, the rest), and what each
  ``while`` of the step holds (:func:`loop_census`: the fused
  cross-entropy's three matmuls and no collective),
- the roofline-implied MFU bound for the flagship config, and
- the partitioner's stderr (asserting no "Involuntary full
  rematerialization" resharding cliffs — the CPU-dryrun warning assert
  from __graft_entry__.py, promoted to the real TPU target).

Outputs ``tpu_evidence/AOT_ANALYSIS.json`` + ``.md``. Run:

    python tools/aot_analysis.py            # all targets but the by-hand
    python tools/aot_analysis.py bench_1chip  # one target
    python tools/aot_analysis.py mistral-7b-v0.3-train-l8-fsdp4
                                            # the benchmark's training cell
                                            # under each remat spelling

The equivalence argument: XLA-TPU compilation is deterministic given
(HLO, topology, compiler version); the scheduled module this tool
analyses is byte-identical to what a run of that step would execute on
hardware, so FLOPs/bytes/collectives/memory are *facts* about the real
program, and only the wall-clock (hence achieved MFU) still needs the
chip. Reference perf target: BASELINE.md north star ≥ 0.40 MFU.
"""

from __future__ import annotations

import datetime
import json
import os
import re
import sys
import time

# the default backend stays the CPU: the TPU is only described, never attached
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# --- v5e public hardware model (roofline constants) -------------------------
# peak bf16 FLOPs and HBM from the Cloud TPU v5e public spec sheet; the
# ICI number is the conservative single-axis bidirectional ring figure
# (2 x 4.5e10 B/s one-way per link); a 2D-torus collective can use both
# axes, so real collectives can beat this bound by up to 2x.
V5E = {
    "peak_bf16_flops": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_capacity": 16e9,
    "ici_ring_bytes_per_s": 9e10,
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all",
)

# sync definition lines look like:
#   %all-gather.3 = bf16[8,2048,1024]{2,1,0:T(8,128)(2,1)} all-gather(...)
_DEF_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"\("
)
# async pairs return a TUPLE from -start:
#   %cp.s = (bf16[64,..], bf16[64,..]) collective-permute-start(...)
# (the TPU partitioner lowers windowed einsums to thousands of these —
# round-5 lesson: a census that only reads sync ops calls a permute-ring
# module "1 all-gather" and mis-rooflines it); bytes moved = the RESULT
# (last tuple element) shape; the matching -done defines no collective
_ASYNC_RE = re.compile(
    r"=\s*\((.*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"-start\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_CHANNEL_RE = re.compile(r"channel_id=(\d+)")


def collective_census(hlo_text: str) -> dict:
    """Count SPMD collectives and the bytes each moves (output shape). A
    collective is counted once by its ``channel_id``, kind and shape: the
    TPU compiler writes an asynchronous one into the body of its start, of
    its done and of every compute fusion that carries its steps, under one
    channel (and every collective a ``shard_map`` body asks for itself
    carries channel 1, whatever it is)."""
    census = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    largest = []
    seen = set()
    for line in hlo_text.splitlines():
        m = _DEF_RE.search(line)
        if m is not None:
            dtype, dims, op = m.groups()
            desc = op
        else:
            m = _ASYNC_RE.search(line)
            if m is None:
                continue
            tuple_body, op = m.groups()
            shapes = _SHAPE_RE.findall(tuple_body)
            if not shapes:
                continue
            # the tuple mixes (operand, result, sync-flag scalars...); the
            # moved payload is the largest element (= result: >= operand
            # for all-gather, == operand for a permute)
            dtype, dims = max(shapes, key=lambda s: _shape_bytes(*s))
            desc = op + "-async"
        channel = _CHANNEL_RE.search(line, m.end())
        if channel is not None:
            key = (channel.group(1), op, dtype, dims)
            if key in seen:
                continue
            seen.add(key)
        nbytes = _shape_bytes(dtype, dims)
        census[op]["count"] += 1
        census[op]["bytes"] += nbytes
        largest.append((nbytes, f"{desc} {dtype}[{dims}]"))
    out = {op: v for op, v in census.items() if v["count"]}
    if largest:
        largest.sort(reverse=True)
        # aggregate identical shapes so the top list reads as a histogram
        agg: dict = {}
        for nbytes, desc in largest:
            agg.setdefault(desc, [0, 0])
            agg[desc][0] += 1
            agg[desc][1] += nbytes
        top = sorted(agg.items(), key=lambda kv: -kv[1][1])[:10]
        out["_largest"] = [
            {"shape": desc, "count": n, "bytes": total}
            for desc, (n, total) in top
        ]
    return out


# a computation's header, ``%name (params) -> result {`` at column 0, and an
# instruction of its body, ``  [ROOT] %name = <shape> <opcode>(``
_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+.*?\s([a-z][a-z\-]*)\(")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")


def _computations(hlo_text: str):
    """``({computation: its instruction lines}, the ENTRY's name)``."""
    bodies, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION_RE.match(line)
            current = m.group(2) if m else None
            if m:
                bodies[current] = []
                if m.group(1):
                    entry = current
        elif current is not None:
            bodies[current].append(line)
    return bodies, entry


def collectives_scheduled(hlo_text: str) -> dict:
    """The collectives of the scheduled ``ENTRY`` by kind, asynchronous and
    synchronous: ``{kind: {"async": n, "sync": n}}``, each counted once by
    its ``channel_id`` and kind (loops' bodies are not read:
    :func:`loop_census` reads those; since PR 54 the cross-entropy's holds
    none, and the head's reduce-scatter is an instruction of ``ENTRY``).

    How the TPU compiler writes them. A synchronous one is the collective
    itself, or a ``fusion`` whose called computation holds it: a gradient's
    reduce-scatter is ``fusion(...), calls=%all-reduce-scatter.N``, an
    ``all-reduce`` with the ``dynamic-slice`` fused on. An asynchronous one
    is a pair of fusions named ``%async-collective-start.N`` / ``-done.N``
    (or a plain ``<kind>-start`` / ``-done``) with the same channel in their
    bodies; its steps may ride in compute fusions between the two
    (``calls=%async_collective_fusion.N``), whose bodies hold the channel
    again. So: a channel is asynchronous if one of the ``ENTRY``
    instructions that reach it is a start."""
    bodies, entry = _computations(hlo_text)

    def collective_of(line):
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            return None
        name, opcode = m.groups()
        started = opcode.endswith("-start")
        kind = opcode[:-len("-start")] if started else opcode
        if kind not in _COLLECTIVES:
            return None
        channel = _CHANNEL_RE.search(line)
        # one without a channel is its own; the collectives a shard_map
        # body asks for itself all carry channel 1, so the kind is in the key
        return (channel.group(1) if channel else name, kind), kind, started

    reached: dict = {}

    def under(computation):
        if computation not in reached:
            reached[computation] = found = []
            for line in bodies.get(computation, ()):
                hit = collective_of(line)
                if hit is not None:
                    found.append(hit)
                for callee in _CALLS_RE.findall(line):
                    found.extend(under(callee))
        return reached[computation]

    channels: dict = {}            # channel -> [kind, asynchronous]
    for line in bodies.get(entry, ()):
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            continue
        name = m.group(1)
        hit = collective_of(line)
        found = [hit] if hit is not None else []
        callees = _CALLS_RE.findall(line)
        for callee in callees:
            found.extend(under(callee))
        for channel, kind, started in found:
            if kind == "all-reduce" and any(
                    c.startswith("all-reduce-scatter") for c in callees):
                kind = "reduce-scatter"
            row = channels.setdefault(channel, [kind, False])
            if started or name.startswith("async-collective-start"):
                row[1] = True
    out: dict = {}
    for kind, asynchronous in channels.values():
        row = out.setdefault(kind, {"async": 0, "sync": 0})
        row["async" if asynchronous else "sync"] += 1
    return out


_WHILE_BODY_RE = re.compile(r"\swhile\(.*\bbody=%([\w.\-]+)")
_MATMULS = ("convolution", "dot")


def loop_census(hlo_text: str) -> list:
    """One row a ``while`` of the module, in the order they are written:
    ``{"body": name, "matmuls": n, "collectives": {kind: n}}``, counted
    over the body and every computation the body calls. On the TPU a
    ``dot_general`` is a ``convolution`` inside a fusion. The train step's
    only loop with matmuls is the fused cross-entropy's
    (``ops/chunked_ce.py``): three of the head's size and no collective
    says the loop is local and nothing is multiplied twice."""
    bodies, _ = _computations(hlo_text)

    def under(computation, seen):
        if computation in seen:
            return
        seen.add(computation)
        for line in bodies.get(computation, ()):
            m = _INSTRUCTION_RE.match(line)
            if m is not None:
                yield m.group(2)
            for callee in _CALLS_RE.findall(line):
                yield from under(callee, seen)

    rows = []
    for lines in bodies.values():
        for line in lines:
            m = _WHILE_BODY_RE.search(line)
            if m is None:
                continue
            row = {"body": m.group(1), "matmuls": 0, "collectives": {}}
            for opcode in under(m.group(1), set()):
                kind = opcode[:-len("-start")] \
                    if opcode.endswith("-start") else opcode
                if opcode in _MATMULS:
                    row["matmuls"] += 1
                elif kind in _COLLECTIVES:
                    row["collectives"][kind] = \
                        row["collectives"].get(kind, 0) + 1
            rows.append(row)
    return rows


# an instruction the backward runs a second time carries the scope
# ``jax.checkpoint`` gives its recomputation in ``op_name`` metadata:
#   ... metadata={op_name="jit(step)/.../rematted_computation/.../dot_general"
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_REMAT_SCOPE = "rematted_computation"


def recompute_census(hlo_text: str) -> dict:
    """Instructions of the module whose ``op_name`` lies under
    ``rematted_computation`` (what a rematerialised layer's backward runs
    again), by the kind of the traced operation they came from: matmuls
    (``dot_general``), kernels (``pallas_call``), and the rest. A count of
    lines, fusions' inner instructions included: 0 is the statement, the
    size of a non-zero count says little."""
    census = {"dot_general": 0, "pallas_call": 0, "other": 0}
    for line in hlo_text.splitlines():
        m = _OP_NAME_RE.search(line)
        if m is None or _REMAT_SCOPE not in m.group(1):
            continue
        inner = m.group(1).split(_REMAT_SCOPE, 1)[1]
        kind = next((k for k in ("pallas_call", "dot_general")
                     if k in inner), "other")
        census[kind] += 1
    return census


class StderrCapture:
    """Tee fd 2 so C++ partitioner warnings are assertable (python warning
    hooks never see absl logging) — same mechanism as __graft_entry__."""

    def __enter__(self):
        import threading

        self._orig = os.dup(2)
        self._read_fd, write_fd = os.pipe()
        os.dup2(write_fd, 2)
        os.close(write_fd)
        self._chunks = []

        def pump():
            while True:
                chunk = os.read(self._read_fd, 1 << 16)
                if not chunk:
                    return
                self._chunks.append(chunk)
                os.write(self._orig, chunk)

        self._thread = threading.Thread(target=pump, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        os.dup2(self._orig, 2)
        self._thread.join(5)
        os.close(self._read_fd)
        os.close(self._orig)
        return False

    def text(self) -> str:
        return b"".join(self._chunks).decode("utf-8", "replace")


def _topology(name: str):
    from jax.experimental import topologies

    if name == "v5e-1":
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1x1",
            chips_per_host_bounds=(1, 1, 1))
    if name == "v5e-4":
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    if name == "v5e-16":
        # 4 chips/host default -> 4 processes: a real multi-host topology
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:4x4")
    if name == "v5e-16-1host":
        # same 16 chips, single process: isolates multi-host DCN effects
        # from the sharding itself when a multi-proc module looks odd
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:4x4",
            chips_per_host_bounds=(4, 4, 1))
    raise ValueError(name)


def analyze(tag: str, cfg, topo_name: str, *, global_batch: int,
            seq_len: int, mesh_axes: dict, packed: bool = False) -> dict:
    """AOT-compile the full train step for one config and extract evidence.
    ``packed``: the batch carries ``segments`` beside ``tokens`` (packed
    documents: the flash kernels' segmented variants, per-document RoPE)."""
    import optax

    from lzy_tpu.models import count_params, llama, unbox
    from lzy_tpu.models.common import param_logical_axes
    from lzy_tpu.parallel import MeshSpec, TrainState, make_train_step

    t0 = time.time()
    topo = _topology(topo_name)
    devices = list(topo.devices)
    n_chips = len(devices)
    mesh = MeshSpec(**mesh_axes).build(devices)

    boxed = jax.eval_shape(
        lambda k: llama.init_params(cfg, k)[0], jax.random.PRNGKey(0))
    axes = param_logical_axes(boxed)
    params = unbox(boxed)
    n_params = count_params(params)

    tx = optax.adamw(3e-4)
    state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
    step, _, batch_sharding = make_train_step(
        llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
        param_logical_axes=axes, batch_logical_axes=("batch", "seq"))
    row = jax.ShapeDtypeStruct(
        (global_batch, seq_len), jnp.int32, sharding=batch_sharding)
    batch = {"tokens": row, "segments": row} if packed else {"tokens": row}

    print(f"[{tag}] lowering + compiling ({n_chips} chips, "
          f"{n_params/1e6:.0f}M params, batch {global_batch}x{seq_len})...",
          flush=True)
    with StderrCapture() as scan:
        compiled = step.lower(state, batch).compile()
    compile_s = time.time() - t0
    stderr_text = scan.text()
    remat_warnings = stderr_text.count("Involuntary full rematerialization")

    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    census = collective_census(hlo)
    scheduled = collectives_scheduled(hlo)
    recompute = recompute_census(hlo)
    loops = loop_census(hlo)

    # --- roofline ---------------------------------------------------------
    flops_dev = float(ca.get("flops", 0.0))        # per-device (SPMD module)
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    t_hbm = bytes_dev / V5E["hbm_bytes_per_s"]
    # ring model: an N-way all-gather/reduce-scatter moves (N-1)/N of its
    # gathered bytes through each chip's ring links; all-reduce costs 2x a
    # reduce-scatter; a collective-permute hop moves its bytes once
    tokens_dev = global_batch * seq_len / n_chips
    model_flops_dev = 6.0 * n_params * tokens_dev  # 6ND, matches train.mfu()
    # XLA's cost analysis counts a while body ONCE — a windowed einsum
    # (how the TPU partitioner implements fsdp matmuls, as
    # collective-permute rings) under-reports its flops by the trip
    # count. The 6ND model flops are a hard floor for a train step, so
    # the roofline takes the max.
    flops_floor = max(flops_dev, model_flops_dev)
    t_mxu = flops_floor / V5E["peak_bf16_flops"]
    n = n_chips
    ici_bytes = 0.0
    for op, v in census.items():
        if op.startswith("_"):
            continue
        factor = {"all-gather": (n - 1) / n,
                  "reduce-scatter": (n - 1) / n,
                  "all-reduce": 2 * (n - 1) / n,
                  "collective-permute": 1.0,
                  "all-to-all": (n - 1) / n}[op]
        ici_bytes += v["bytes"] * factor
    t_ici = ici_bytes / V5E["ici_ring_bytes_per_s"] if n > 1 else 0.0
    t_bound = max(t_mxu, t_hbm, t_ici)
    mfu_bound = model_flops_dev / (V5E["peak_bf16_flops"] * t_bound)
    # donated state aliases its output slots (alias_size), so live HBM is
    # args + temps + code + the non-aliased output remainder
    hbm_need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.generated_code_size_in_bytes
                + max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes))

    rec = {
        "tag": tag,
        "topology": topo_name,
        "chips": n_chips,
        "processes": len({d.process_index for d in devices}),
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1} or {"1chip": 1},
        "model_params": n_params,
        "global_batch": global_batch,
        "seq_len": seq_len,
        "compile_seconds": round(compile_s, 1),
        "per_device": {
            "flops": flops_dev,
            "hbm_bytes_accessed": bytes_dev,
            "xla_optimal_seconds": float(ca.get("optimal_seconds", 0.0)),
        },
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "hbm_needed_gb": round(hbm_need / 1e9, 2),
            "fits_16gb_hbm": bool(hbm_need < V5E["hbm_capacity"]),
        },
        "collectives": census,
        "collectives_scheduled": scheduled,
        "recompute": recompute,
        "loops": loops,
        "roofline": {
            "t_mxu_ms": round(1e3 * t_mxu, 3),
            "t_hbm_ms": round(1e3 * t_hbm, 3),
            "t_ici_ms": round(1e3 * t_ici, 3),
            "bound": ("mxu" if t_bound == t_mxu
                      else "hbm" if t_bound == t_hbm else "ici"),
            "step_time_lower_bound_ms": round(1e3 * t_bound, 3),
            "mfu_upper_bound": round(mfu_bound, 4),
            "hardware_flops_utilization_at_bound": round(t_mxu / t_bound, 4),
        },
        "partitioner": {
            "involuntary_remat_warnings": remat_warnings,
            "stderr_bytes": len(stderr_text),
        },
    }
    print(f"[{tag}] done in {compile_s:.0f}s: mfu_bound="
          f"{rec['roofline']['mfu_upper_bound']}, bound by "
          f"{rec['roofline']['bound']}, collectives="
          f"{ {k: v['count'] for k, v in census.items() if not k.startswith('_')} }, "
          f"remat_warnings={remat_warnings}; a chip: argument "
          f"{ma.argument_size_in_bytes / 1e9:.2f} GB + temp "
          f"{ma.temp_size_in_bytes / 1e9:.2f} GB + code "
          f"{ma.generated_code_size_in_bytes / 1e9:.2f} GB, "
          f"{flops_dev / 1e12:.1f} TFLOP, recomputed: {recompute}; "
          f"loops (matmuls, collectives): "
          f"{[(r['matmuls'], r['collectives']) for r in loops]}; "
          f"scheduled (async / sync): "
          f"{ {k: (v['async'], v['sync']) for k, v in scheduled.items()} }",
          flush=True)
    return rec


#: the benchmark's training cell (``BENCHMARK.json`` ``train-fsdp4``)
_CELL = "mistral-7b-v0.3-train-l8-fsdp4"


def _cell_targets() -> dict:
    """The step ``benchmark/harness/train.py`` builds for ``train-fsdp4``,
    from the benchmark's own files: its configuration through the model
    file's ``program_config``, its mesh, its packed batch. Three rows: what
    the cell runs (``LlamaConfig``'s default policy), and the two other
    ways to spell rematerialisation, so that the step's memory and its
    recomputation can be read side by side. By hand only (one to one
    and a half minutes a compile): ``python tools/aot_analysis.py mistral-7b-v0.3-train-l8-fsdp4``
    runs the three."""
    from benchmark.models import mistral

    def doc(*path):
        with open(os.path.join(REPO, "benchmark", *path)) as f:
            return json.load(f)

    config, traffic = doc("configs", _CELL + ".json"), \
        doc("traffic", "train-fsdp4.json")
    rows = {"": {}, ":nothing": {"remat_policy": "nothing"},
            ":noremat": {"remat": False}}
    return {
        _CELL + suffix: dict(
            cfg=mistral.program_config(config, **over), topo="v5e-4",
            global_batch=traffic["batch"], seq_len=traffic["seq"],
            mesh_axes=config["mesh"], packed=True, by_hand=True)
        for suffix, over in rows.items()}


def targets() -> dict:
    """The configs ``tpu_evidence/AOT_ANALYSIS.*`` records: a ~350M-param
    Llama sized for one v5e chip, and its v5e-16 variants; and the
    benchmark's training cell (:func:`_cell_targets`), by hand."""
    import dataclasses

    from lzy_tpu.models.llama import LlamaConfig

    # the fused-b16 headline (fused CE + nothing-saveable remat, batch 16
    # — the config whose row says fits: yes); the dense no-remat config
    # survives as the secondary probe and the kept-as-evidence non-fitting
    # northstar row
    cfg = LlamaConfig(
        vocab_size=32_768, d_model=1024, n_layers=20, n_heads=8,
        n_kv_heads=8, d_ff=4096, max_seq_len=2048,
        remat=True, remat_policy="nothing", fused_ce=True,
        tie_embeddings=True, use_flash_kernel=True,
    )
    batch, seq = 16, 2048
    dense = dataclasses.replace(cfg, fused_ce=False, remat=False)
    dense_batch = 8
    return {
        # the one-chip headline: one v5e chip, 350M llama,
        # fused-b16 (8.55 GB / bound 0.79 — fits)
        "bench_1chip": dict(
            cfg=cfg, topo="v5e-1", global_batch=batch, seq_len=seq,
            mesh_axes={"fsdp": -1}),
        # the demoted dense b8 secondary probe; its row documents WHY the
        # promotion happened (17.1 GB with remat off — fits: NO)
        "bench_1chip_dense_b8": dict(
            cfg=dense, topo="v5e-1", global_batch=dense_batch, seq_len=seq,
            mesh_axes={"fsdp": -1}),
        # BASELINE.json north star: multi-host v5e-16, pure fsdp,
        # same per-chip load as the old dense headline. The plain config is
        # kept although it does NOT fit (17.05 GB, the f32 logits +
        # remat=False activations) — that OOM row is itself evidence the
        # step needs the fused variant on this topology
        "northstar_v5e16_fsdp": dict(
            cfg=dense, topo="v5e-16", global_batch=dense_batch * 16,
            seq_len=seq, mesh_axes={"fsdp": -1}),
        # the config to actually run on a v5e-16:
        # logits-free chunked CE + dots-remat restores the memory headroom
        # (fused alone missed the 15.75 GB budget by 221 MB), which also
        # stops the scheduler's all-gather refetching (param re-gathers
        # under HBM pressure) that inflates t_ici
        "northstar_v5e16_fsdp_fused": dict(
            cfg=dataclasses.replace(cfg, remat_policy="dots"),
            topo="v5e-16", global_batch=dense_batch * 16, seq_len=seq,
            mesh_axes={"fsdp": -1}),
        # best-per-chip candidate on the slice: fused CE WITHOUT remat —
        # logits-free frees enough HBM at b8/chip that no recompute
        # re-reads are needed; dots-remat costs ~2x HBM traffic
        "northstar_v5e16_fsdp_fused_noremat": dict(
            cfg=dataclasses.replace(cfg, remat=False), topo="v5e-16",
            global_batch=dense_batch * 16, seq_len=seq,
            mesh_axes={"fsdp": -1}),
        # control experiment: identical config on a single-host 16-chip
        # topology — separates what the partitioner does to the sharding
        # from what it does about the DCN (4-process) boundary
        "northstar_v5e16_1host_fused": dict(
            cfg=dataclasses.replace(cfg, remat_policy="dots"),
            topo="v5e-16-1host", global_batch=dense_batch * 16, seq_len=seq,
            mesh_axes={"fsdp": -1}),
        # dp x fsdp hybrid on the same slice: dp=4 cuts the param
        # all-gather ring from 16 to 4 chips at the cost of 4x grad
        # all-reduce participants — the analysis quantifies the tradeoff
        "v5e16_dp4_fsdp4": dict(
            cfg=dense, topo="v5e-16", global_batch=dense_batch * 16,
            seq_len=seq, mesh_axes={"dp": 4, "fsdp": -1}),
        **_cell_targets(),
    }


def main(argv: list) -> int:
    only = set(argv) or None
    out_dir = os.path.join(REPO, "tpu_evidence")
    os.makedirs(out_dir, exist_ok=True)
    libtpu = "unknown"
    try:
        import libtpu  # noqa: F401

        libtpu = getattr(libtpu, "__version__", "present")
    except Exception:
        pass
    results, errors = [], []
    for tag, spec in targets().items():
        # a name given on the command line also picks its ":" rows; a
        # by-hand target runs only when named
        named = only and (tag in only or tag.split(":")[0] in only)
        if not named and (only or spec.get("by_hand")):
            continue
        try:
            results.append(analyze(
                tag, spec["cfg"], spec["topo"],
                global_batch=spec["global_batch"], seq_len=spec["seq_len"],
                mesh_axes=spec["mesh_axes"],
                packed=spec.get("packed", False)))
        except Exception as e:  # noqa: BLE001 — record, keep going
            import traceback

            traceback.print_exc()
            errors.append({"tag": tag, "error": f"{type(e).__name__}: {e}"})
    # a partial run (explicit tags) merges over the existing artifact so
    # iterating on one config never drops the others' evidence
    json_path = os.path.join(out_dir, "AOT_ANALYSIS.json")
    if only and os.path.exists(json_path):
        try:
            with open(json_path) as f:
                prev = json.load(f)
            ran = {r["tag"] for r in results} | {e["tag"] for e in errors}
            results = [r for r in prev.get("results", [])
                       if r["tag"] not in ran] + results
            errors = [e for e in prev.get("errors", [])
                      if e["tag"] not in ran] + errors
            order = list(targets())
            results.sort(key=lambda r: order.index(r["tag"])
                         if r["tag"] in order else 99)
        except Exception:  # noqa: BLE001 — a corrupt artifact just rewrites
            pass
    doc = {
        "generated": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "jax_version": jax.__version__,
        "libtpu": libtpu,
        "hardware_model": V5E,
        "method": (
            "jit(train_step).lower(abstract_state).compile() against a "
            "deviceless TPU topology (jax.experimental.topologies); the "
            "compiled module is byte-identical to the on-chip program, so "
            "FLOPs/bytes/collectives/memory are facts about the real "
            "program; only wall-clock needs the chip"),
        "results": results,
        "errors": errors,
    }
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
    _write_md(doc, os.path.join(out_dir, "AOT_ANALYSIS.md"))
    print(f"wrote {json_path}")
    return 1 if errors and not results else 0


def _write_md(doc: dict, path: str) -> None:
    lines = [
        "# AOT compile-level performance evidence",
        "",
        f"Generated {doc['generated']} · jax {doc['jax_version']} · "
        f"libtpu {doc['libtpu']}",
        "",
        "Achieved MFU is a chip measurement and is not in here. This "
        "artifact pins what is measurable *without* the chip: the "
        "flagship train step is AOT-compiled against deviceless v5e "
        "topologies with the same libtpu compiler the chip uses; the "
        "scheduled modules below are byte-identical to what would run.",
        "",
        "| config | chips | mesh | params | batchxseq | FLOPs/dev | "
        "HBM GB/dev | fits 16 GB | collectives (count) | bound | "
        "step >= ms | **MFU <=** |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in doc["results"]:
        col = ", ".join(
            f"{k.replace('all-', 'a').replace('reduce-scatter', 'rs')}"
            f"x{v['count']}" for k, v in r["collectives"].items()
            if not k.startswith("_")) or "none"
        mesh = "x".join(f"{k}{v}" for k, v in r["mesh"].items())
        lines.append(
            f"| {r['tag']} | {r['chips']} | {mesh} "
            f"| {r['model_params']/1e6:.0f}M "
            f"| {r['global_batch']}x{r['seq_len']} "
            f"| {r['per_device']['flops']/1e12:.2f}T "
            f"| {r['memory']['hbm_needed_gb']} "
            f"| {'yes' if r['memory']['fits_16gb_hbm'] else 'NO'} "
            f"| {col} | {r['roofline']['bound']} "
            f"| {r['roofline']['step_time_lower_bound_ms']} "
            f"| **{r['roofline']['mfu_upper_bound']}** |")
    lines += [
        "",
        "- `FLOPs/dev` is XLA's cost analysis of the compiled per-device "
        "SPMD module (includes attention quadratic + remat recompute, so "
        "it exceeds the 6ND model FLOPs the MFU numerator uses).",
        "- `MFU <=` is the roofline bound: 6ND token-FLOPs per device / "
        "(197 bf16-TFLOPs x max(t_mxu, t_hbm, t_ici)). It is an upper "
        "bound on what the driver bench can measure for that config, and "
        "directly comparable to the >= 0.40 north star.",
        "- ICI uses the conservative single-axis bidirectional-ring model "
        "(90 GB/s per chip); 2D-torus collectives can halve t_ici.",
        "- Every compile is asserted free of 'Involuntary full "
        "rematerialization' partitioner warnings (resharding cliffs): ",
    ]
    for r in doc["results"]:
        lines.append(
            f"  - {r['tag']}: {r['partitioner']['involuntary_remat_warnings']}"
            f" warnings, compiled in {r['compile_seconds']}s")
    lines += [
        "- Bytes a chip (argument + temp + code) and the instructions under "
        "`rematted_computation` (what the backward runs a second time: "
        "matmuls / kernels / the rest); the scheduled `ENTRY`'s collectives "
        "by kind, asynchronous / synchronous:",
    ]
    for r in doc["results"]:
        m, again = r["memory"], r.get("recompute")
        scheduled = r.get("collectives_scheduled")
        lines.append(
            f"  - {r['tag']}: {m['argument_bytes'] / 1e9:.2f} + "
            f"{m['temp_bytes'] / 1e9:.2f} + {m['code_bytes'] / 1e9:.2f} GB"
            + (f"; {again['dot_general']} / {again['pallas_call']} / "
               f"{again['other']}" if again else "")
            + ("; " + ", ".join(
                f"{kind} {n['async']} / {n['sync']}"
                for kind, n in scheduled.items()) if scheduled else ""))
    if doc["errors"]:
        lines += ["", "## Errors", ""]
        for e in doc["errors"]:
            lines.append(f"- **{e['tag']}**: {e['error']}")
    lines += [
        "",
        "Full per-config detail (memory breakdown, collective bytes, XLA "
        "optimal-seconds) in `AOT_ANALYSIS.json`. Regenerate: "
        "`python tools/aot_analysis.py`.",
        "",
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines))


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

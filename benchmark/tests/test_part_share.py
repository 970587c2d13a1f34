"""``readers/part_share.py`` on two small traces recorded on a v5e.

``probe_v5e.xplane.pb`` (``test_trace.py``'s: a ``decode_step`` and a
``prefill_step`` with no scope at all) holds the decoder to what the file
says, and to TensorFlow's ``xplane_pb2`` where that is installed.
``probe_parts_v5e.xplane.pb`` is :func:`record`'s: five rounds of a
``decode_step`` whose work lies in two parts and outside any (``part.proj``
around two products and a tanh, ``part.ffn`` around a loop of three products,
a sum over rows under no part), then five of a ``prefill_step`` with no part.

    python benchmark/tests/test_part_share.py record    # on the chip

writes ``chiprun_out/probe_parts_v5e.xplane.pb``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "probe_v5e.xplane.pb")
PARTS = os.path.join(HERE, "data", "probe_parts_v5e.xplane.pb")
PROGRAM_ID = 15625001468071110663


def record(out_dir: str = "chiprun_out") -> str:
    import glob
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    from benchmark.harness import trace as xtrace
    from lzy_tpu.utils import trace

    def decode_step(x, w):
        with trace.part(trace.PROJ):
            y = jnp.tanh(x @ w) @ w

        def body(c, _):
            with trace.part(trace.FFN):
                return jnp.maximum(c @ w, 0.0).astype(c.dtype), None

        y, _ = jax.lax.scan(body, y, None, length=3)
        return jnp.sum(y.astype(jnp.float32), axis=0)

    def prefill_step(x, w):
        return jnp.sum(jnp.tanh(x @ w).astype(jnp.float32))

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 1024), jnp.bfloat16)
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16) * 0.03
    steps = [jax.jit(decode_step), jax.jit(prefill_step)]
    for step in steps:
        jax.block_until_ready(step(x, w))
    where = tempfile.mkdtemp(prefix="probe_parts_")
    xtrace.start(where)
    for step in steps:
        for _ in range(5):
            jax.block_until_ready(step(x, w))
    xtrace.stop()
    os.makedirs(out_dir, exist_ok=True)
    target = os.path.join(out_dir, "probe_parts_v5e.xplane.pb")
    shutil.copy(xtrace.find_xplane(where), target)
    shutil.rmtree(where, ignore_errors=True)
    print(target, os.path.getsize(target), jax.devices()[0].device_kind)
    return target


def _traced(monkeypatch, path):
    """``obs`` of a traced run whose trace is the file at ``path``."""
    from benchmark.readers import part_share, placed_spans

    monkeypatch.setattr(placed_spans, "newest_trace", lambda: path)
    part_share.table.cache_clear()
    part_share.operations.cache_clear()
    return {"trace": {}}


def test_the_decoder_finds_the_name_the_program_gave():
    from benchmark.readers import part_share

    planes = part_share.device_metadata(RECORDED)
    assert list(planes) == ["/device:TPU:0"]
    names = part_share.names_of(planes["/device:TPU:0"])
    (key,) = [k for k in names
              if k[1].startswith("%convolution_tanh_fusion.2 = ")]
    assert key[0] == PROGRAM_ID
    stats = names[key]
    assert stats["tf_op"] == "jit(decode_step)/dot_general:"
    assert stats["flops"] == 1074790400
    assert stats["bytes_accessed"] == 5242880
    assert stats["hlo_category"] == "convolution fusion"


def test_the_decoder_agrees_with_xplane_pb2():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from benchmark.readers import part_share

    space = xplane_pb2.XSpace()
    with open(RECORDED, "rb") as f:
        space.ParseFromString(f.read())
    mine = part_share.device_metadata(RECORDED)
    planes = [p for p in space.planes if p.name.startswith("/device:TPU:")]
    assert sorted(mine) == sorted(p.name for p in planes)
    for plane in planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        assert len(mine[plane.name]) == len(plane.event_metadata) > 0
        for ident, event in plane.event_metadata.items():
            name, stats = mine[plane.name][ident]
            assert name == event.name
            want = {}
            for s in event.stats:
                value = getattr(s, s.WhichOneof("value"))
                if s.WhichOneof("value") == "ref_value":
                    value = stat_names[value]
                want[stat_names[s.metadata_id]] = value
            assert stats == want


def test_a_program_without_parts_reads_none(monkeypatch):
    from benchmark.readers import part_share

    obs = _traced(monkeypatch, RECORDED)
    for module in ("jit_decode_step", "jit_prefill_step"):
        assert part_share.read(obs, part="*", module=module) is None
        assert part_share.read(obs, part="proj", module=module) is None
    # the trace knows the operations all the same, by what JAX called them
    table = part_share.table(RECORDED)
    assert set(table) == {"jit_decode_step", "jit_prefill_step"}
    assert max(table["jit_decode_step"]["unnamed"],
               key=table["jit_decode_step"]["unnamed"].get) \
        == "jit(decode_step)/dot_general"
    # and an untraced run reads nothing at all
    assert part_share.read({}, part="*", module="jit_decode_step") is None


def test_shares_add_up_to_the_coverage(monkeypatch):
    from benchmark.readers import part_share

    obs = _traced(monkeypatch, PARTS)
    read = lambda part, module="jit_decode_step": part_share.read(  # noqa
        obs, part=part, module=module)
    proj, ffn, named = read("proj"), read("ffn"), read("*")
    assert proj > 10 and ffn > 10
    assert proj + ffn == pytest.approx(named, rel=1e-9)
    assert read(["proj", "ffn"]) == pytest.approx(named, rel=1e-9)
    # the sum over rows lies outside every part: named, and not everything
    assert 50 < named < 100
    # a part the program never opens reads 0 beside the others, not None
    assert read("head") == 0.0
    assert read("*", "jit_prefill_step") is None
    assert read("*", "jit_no_such_step") is None
    # the loop of three products is one ``while`` whose body's operations
    # are events of their own: self time files them once
    row = part_share.operations(PARTS)["jit_decode_step"]
    assert row["runs"] == 5
    busy = sum(v[0] for v in row["ops"].values())
    assert busy <= row["seconds"] * (1 + 1e-9)
    assert {v[4] for v in row["ops"].values()} >= {"proj", "ffn",
                                                   part_share.UNNAMED}


def test_self_time_is_an_events_time_less_its_childrens():
    from benchmark.readers import part_share

    events = [(0.0, 100.0, "while"), (10.0, 30.0, "a"), (50.0, 40.0, "b"),
              (55.0, 10.0, "c"), (200.0, 5.0, "d")]
    got = {name: own for _, own, name in part_share.self_times(events)}
    assert got == {"while": 30.0, "a": 30.0, "b": 30.0, "c": 10.0, "d": 5.0}
    assert part_share.part_of(
        "jit(step)/transpose(jvp(part.ffn))/mlp/dot_general") == "ffn"
    assert part_share.part_of("jit(f)/layer_0/part.proj/q_proj/dot") == "proj"
    assert part_share.part_of("jit(f)/apart.proj/x") == part_share.UNNAMED
    assert part_share.part_of("") == part_share.UNNAMED


def test_every_part_metric_finds_its_reader_and_a_part_of_the_vocabulary():
    import json

    from benchmark.harness import common, readers
    from lzy_tpu.utils import trace

    manifest = common.load_manifest()
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {e["name"]: set(e.get("workloads", cells))
           for e in manifest["end_to_end"]}
    with open(os.path.join(common.BENCH_DIR, "metrics",
                           "part_metrics.per_layer.json")) as f:
        entries = json.load(f)
    assert len({m["name"] for m in entries}) == len(entries) == 20
    for entry in entries:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert set(entry["workloads"]) <= e2e[entry["moves"]]
        doc = common.load_json("metrics", entry["name"] + ".json")
        assert doc["name"] == entry["name"]
        assert callable(readers.find(doc))
        part = doc["args"]["part"]
        assert part == "*" or part in trace.PARTS


if __name__ == "__main__":
    if sys.argv[1:] == ["record"]:
        record()
    else:
        raise SystemExit(__doc__)

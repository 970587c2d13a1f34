"""The Brumby model file and its cell, rehearsed on the CPU at a tiny size:
the harness's own path end to end, the file's names, the four limits and the
broken programs each of them catches, the counts against a hand count, the
cell's files found by name and every new metric file through its reader.

    python -m pytest benchmark/tests/test_brumby_model_file.py
"""

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import common, readers
from benchmark.harness import traffic as gen
from benchmark.models import REQUIRED
from benchmark.models import brumby as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs", "brumby-14b-serve-l8.json")
CELL = "longgen-steady"

#: the per-layer metrics this cell brought
NEW = ("step.retention_share_of_decode", "kernel.retention_update_roofline",
       "engine.state_splice_s_p50")
#: accepted metrics whose lists the cell joined
JOINED = ("loadgen.late_p95_s", "client.tpot_p85_s", "client.ttft_mean_s",
          "client.ttft_p85_s", "client.longest_silence_s",
          "gateway.overhead_p50_s", "engine.host_share_of_round",
          "step.decode_s_p50", "step.prefill_chunk_s_p50",
          "engine.loop_host_share", "engine.prefill_share_of_loop",
          "engine.slots_busy_share", "trace.anchor_spread_us",
          "request.queue_wait_mean_s", "request.prefill_mean_s",
          "engine.longest_leaf_s", "engine.decode_overlap_share",
          "step.decode_roofline", "setup.program_build_s",
          "setup.build_python_share", "setup.programs_built",
          "setup.cache_hit_share", "setup.other_build_s",
          "setup.engine_init_s")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def _cell():
    return common.cell_files(common.load_manifest(), CELL)


def _real_cfg():
    with open(REAL) as f:
        return model.program_config(json.load(f))


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(
        trace, monkeypatch):
    for name in ("_JUDGED", "_STATE_GAPS", "_COARSE"):
        monkeypatch.setattr(model, name, [])
    real = _cell()
    doc = _load("configs", "tiny-brumby")
    files = {"cell": {"name": "tiny-longgen", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-longgen"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-longgen", seed=2 ** 31 + 56,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, files, require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    named = out["rehearsal"]["metric_names"]
    if trace:
        # what a CPU trace and the counters can feed; the device-trace
        # metrics need a TPU's planes
        assert {"engine.slots_busy_share", "engine.decode_overlap_share",
                "request.prefill_mean_s",
                "engine.state_splice_s_p50"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)
    assert len(model._STATE_GAPS) == 2 and max(model._STATE_GAPS) < 1e-5
    assert max(model._COARSE) < model.STATE_COARSE_TOL


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "retention_state_bytes", "retention_step_bytes",
        "retention_chunk_flops", "decode_step_bytes", "reference_logits",
        "state_gaps", "held_to_the_limits", "failed_limits"))
    assert 0 < model.GAP_RATIO < 1 and 0 < model.LOGIT_TIE_TOL
    assert 0 < model.STATE_REL_TOL < 1 and 0 < model.STATE_COARSE_TOL < 1


def test_the_reference_imports_nothing_from_the_program():
    import ast

    with open(model.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    assert not any("lzy_tpu" in ast.dump(n) for n in top)
    # the program's side reaches the program from inside three functions
    # (the last: the engine whose state is read); none is the reference's
    inside = {fn.name: sorted({n.module for n in ast.walk(fn)
                               if isinstance(n, ast.ImportFrom)
                               and (n.module or "").startswith("lzy_tpu")})
              for fn in ast.walk(tree) if isinstance(fn, ast.FunctionDef)}
    inside = {k: v for k, v in inside.items() if v}
    assert set(inside) == {"program_config", "init_params",
                           "_serving_engine"}
    assert not any("power_retention" in m
                   for mods in inside.values() for m in mods)


def test_the_manifest_finds_the_cells_files_by_name():
    m = common.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert CELL in cells and cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "brumby-14b-serve-l8"
    assert len(cells) == 11 and len(m["configs"]) == 10
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = _cell()
    assert files["model"] is model
    assert files["config"]["name"] == "brumby-14b-serve-l8"
    assert "kv_pool_bytes" not in files["config"]["engine"]
    assert [e["name"] for e in files["end_to_end"]] == ["tpot_p50_s",
                                                        "setup_s"]
    names = [x["name"] for x in files["per_layer"]]
    assert set(NEW) <= set(names) and set(JOINED) <= set(names)
    assert not [n for n in names if n.startswith(("kv.", "moe."))]
    for x in files["per_layer"]:
        assert x["moves"] == ("setup_s" if x["name"].startswith("setup.")
                              else "tpot_p50_s")
        assert x["reader"] and x["what"]


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernel, the counts and the span: None, never an error."""
    obs = {"trace": {"modules": {"jit_decode_step": [0.01],
                                 "jit_prefill_step": [0.02]},
                     "ops": {"jit_decode_step:fusion": (0.01, 1),
                             "jit_prefill_step:fusion": (0.02, 1)}},
           "trace_span": (0.0, 1.0), "t_open": 0.0, "t_close": 51.0,
           "spans": [{"name": "engine.prefill", "start": 0.1, "end": 0.2,
                      "attrs": {"tokens": 256, "start": 0}}],
           "counters": {}, "model": {"module": model, "cfg": None},
           "device_kind": "TPU v5 lite"}
    new = [x for x in _cell()["per_layer"] if x["name"] in NEW]
    assert len(new) == len(NEW)
    for x in new:
        assert readers.read(x, obs) is None, x["name"]


def test_the_new_readers_read_what_the_program_records():
    cfg = _real_cfg()
    rows, steps, took = 12, 10, 0.15
    obs = {"trace": {"modules": {"jit_decode_step": [0.02] * steps},
                     "ops": {"jit_decode_step:power_retention_update_f32":
                             (took, steps * cfg.n_layers),
                             "jit_decode_step:fusion": (0.05, 90)}},
           "trace_span": (0.0, 1.0), "t_open": 0.0, "t_close": 51.0,
           "spans": [{"name": "engine.decode.emit", "start": 0.1,
                      "end": 0.2, "attrs": {"rows": rows, "model_stats": {
                          "lzy_retention_rows_total": rows * cfg.n_layers}}},
                     {"name": "engine.prefill.state", "start": 3.0,
                      "end": 3.002, "attrs": {}}],
           "counters": {}, "model": {"module": model, "cfg": cfg},
           "device_kind": "TPU v5 lite"}
    by_name = {x["name"]: x for x in _cell()["per_layer"]}
    share = readers.read(by_name["step.retention_share_of_decode"], obs)
    assert share == pytest.approx(100 * took / 0.2)
    roof = readers.read(by_name["kernel.retention_update_roofline"], obs)
    least = 2 * rows * 272_646_144 * steps / 819e9
    assert roof == pytest.approx(100 * least / took)
    assert 0 < roof < 100
    assert readers.read(by_name["engine.state_splice_s_p50"], obs) \
        == pytest.approx(0.002)


def test_the_traffic_files_multiset():
    """16 levels from 1,024 to 13,633, two of them past the 8,256 tokens a
    state equals; answers 256-2,048; nothing over 18,432."""
    tr = _cell()["traffic"]
    cfg = _real_cfg()
    levels = sorted(set(gen.quantiles(tr["prompt_len"], 16)))
    assert (levels[0], levels[-1]) == (1024, 13633) and len(levels) == 16
    assert sum(n > 8256 for n in levels) == 2
    assert (tr["prompt_len"]["median"], tr["prompt_len"]["sigma"],
            tr["output_len"]["median"], tr["output_len"]["sigma"]) \
        == (3072, 0.8, 768, 0.6)
    assert (tr["prompt_len"]["min"], tr["prompt_len"]["max"]) \
        == (1024, 16384)
    assert (tr["output_len"]["min"], tr["output_len"]["max"]) == (256, 2048)
    pairs = gen.length_pairs(tr, 64)
    assert all(p + o <= tr["max_total"] == 18432 for p, o in pairs)
    assert tr["max_total"] <= cfg.max_seq_len
    ratio = tr["requests_per_s"] / tr["knee_requests_per_s"]
    assert abs(ratio - 0.8) < 0.01
    assert tr["kind"] == "open_loop" and tr["gaps"]["dist"] == "exponential"
    chk = tr["correctness"]
    assert (chk["requests"], chk["decode_tokens"], chk["pad_to"]) \
        == (4, 256, 4352)
    fits = [n for n in levels if n + chk["decode_tokens"] <= chk["pad_to"]]
    picks = [fits[(2 * i + 1) * len(fits) // (2 * chk["requests"])]
             for i in range(chk["requests"])]
    assert picks == [1070, 1651, 2541, 3271]
    assert chk["pad_to"] % (model._QUERY_BLOCK // 2) == 0
    # a request stays some 20 s (919 tokens at 21 ms and its prefill)
    assert tr["ramp_s"] >= 1.5 * 20


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "Brumby-14B-Base"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers"]
    # no width, no head, no row of the vocabulary is cut
    assert (doc["hidden_size"], doc["vocab_size"], doc["intermediate_size"],
            doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["head_dim"], doc["max_position_embeddings"]) \
        == (5120, 151936, 17408, 40, 8, 128, 32768)
    assert doc["num_hidden_layers"] == 8
    for key in ("assumed", "deployment", "guarantees", "page_size",
                "prefill_jobs"):
        assert doc[key]
    for key in ("sources", "degree", "chunk_size", "gate", "gate_bias",
                "scale", "eps", "qk_norm", "output", "state_dtype",
                "product_dtype"):
        assert doc["assumed"][key]
    assert doc["retention_state_dtype"] == doc["residual_dtype"] == "float32"
    assert doc["engine"]["slots"] in (12, 16)
    assert doc["engine"]["page_size"] == 64
    assert not {"kv_pool_bytes", "kv_blocks"} & set(doc["engine"])
    cfg = model.program_config(doc)
    assert cfg.gate_bias == model.RETENTION_GATE_BIAS and cfg.degree == 2
    with pytest.raises(ValueError, match="retention_state_dtype"):
        model.program_config({**doc, "retention_state_dtype": "bfloat16"})
    with pytest.raises(ValueError, match="retention_product_dtype"):
        model.program_config({**doc, "retention_product_dtype": "float32"})
    with pytest.raises(ValueError, match="sliding_window"):
        model.program_config({**doc, "sliding_window": 4096})


def test_counts_at_the_published_widths():
    """Against a hand count."""
    cfg = _real_cfg()
    assert model.kv_bytes_per_token(cfg) == 0
    a_layer = 8 * (8256 * 128 + 8256) * 4
    assert a_layer == 34_080_768
    assert model.retention_state_bytes(cfg) == 8 * a_layer == 272_646_144
    assert model.retention_step_bytes(cfg, 12) == 2 * 12 * 272_646_144
    # what the program stores is the padded layout: 0.8% more, not charged
    assert cfg.state_bytes == 8 * 8 * (8320 * 128 + 8320) * 4 == 274_759_680
    layer = 2 * 26_214_400 + 2 * 5_242_880 + 40_960 + 267_386_880 + 10_496
    assert layer == 330_352_896
    params = 8 * layer + 2 * 777_912_320 + 5120
    table = 151936 * 5120 * 2
    assert model.decode_step_bytes(cfg, 2 * params, 50_000, 10) \
        == 2 * params - table + 2 * 10 * 272_646_144
    # nothing follows the context's length
    assert model.decode_step_bytes(cfg, 2 * params, 500_000, 10) \
        == model.decode_step_bytes(cfg, 2 * params, 0, 10)
    a_position = 2 * 48 * 8256 * 129 + 4 * 40 * 128 * 128
    assert model.retention_chunk_flops(cfg, 4096, 256) \
        == 8 * 256 * a_position
    assert 104e6 < a_position < 106e6


# -- the limits and the broken programs -----------------------------------------

def _tiny():
    from lzy_tpu.models import brumby as program

    cfg = dataclasses.replace(program.BrumbyConfig.tiny(),
                              gate_bias=model.RETENTION_GATE_BIAS)
    params = program.init_params(cfg, jax.random.PRNGKey(5))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * (4.0 if getattr(path[-1], "key", "")
                                   == "kernel" else 1.0), params)
    return cfg, params


def _served(cfg, params, reference_cfg=None):
    """Two requests through an engine of the (possibly broken) program:
    what the model file reads of them against the sound reference's
    ``reference_cfg``."""
    from lzy_tpu.serving import PagedInferenceEngine

    reference_cfg = reference_cfg or cfg
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  kernel="lax", prefill_budget=32)
    mine, ctrl, state_gap, coarse = [], [], 0.0, 0.0
    try:
        for seed, n in ((1, 43), (2, 75)):
            prompt = np.random.default_rng(seed).integers(
                1, cfg.vocab_size, n).tolist()
            req = engine.submit(prompt, max_new_tokens=24, greedy=True)
            for _ in range(2000):
                if not engine.step():
                    break
            assert req.done and req.error is None
            full = jnp.asarray([prompt + list(req.tokens)])
            rows = np.arange(n - 1, full.shape[1] - 1)
            x, states, _ = model.features(params, full, reference_cfg,
                                          last=int(rows[-1]))
            exact = model.head_logits(params, x[rows], reference_cfg)
            x, _, _ = model.features(params, full, reference_cfg,
                                     jnp.bfloat16, last=int(rows[-1]))
            control = np.asarray(model.head_logits(
                params, x[rows], reference_cfg,
                jnp.bfloat16)).argmax(axis=-1)
            mine.append(model.gaps(exact, req.tokens))
            ctrl.append(model.gaps(exact, control))
            state = model.state_gaps(engine.state_leaves(), states,
                                     reference_cfg)
            state_gap = max(state_gap, state["gap"])
            coarse = max(coarse, state["coarse"])
    finally:
        engine.close()
    return np.concatenate(mine), np.concatenate(ctrl), state_gap, coarse


def _round_to_bfloat16(fn):
    def rounded(*args, **kw):
        y, s, z = fn(*args, **kw)
        return y, jax.lax.reduce_precision(s, 8, 7), \
            jax.lax.reduce_precision(z, 8, 7)
    return rounded


#: the program broken one way each, and the limits that have to see it
BROKEN = {
    "sound": (),
    "bfloat16_state": ("STATE_COARSE_TOL",),
    "no_normaliser": ("GAP_RATIO",),
    "no_gate": ("GAP_RATIO", "STATE_REL_TOL"),
    "pad_advances_the_state": ("STATE_REL_TOL",),
    "no_sqrt2": ("STATE_REL_TOL",),
}


@pytest.mark.parametrize("fault", list(BROKEN))
def test_each_broken_program_fails_a_limit(fault, monkeypatch, capsys):
    from lzy_tpu.models import brumby as program
    from lzy_tpu.ops import power_retention as pr

    monkeypatch.setattr(model, "GAP_RATIO_MIN_TOKENS", 32)
    cfg, params = _tiny()
    broken = cfg
    if fault == "bfloat16_state":
        for name in ("retention_chunk_scan", "retention_state_update"):
            monkeypatch.setattr(pr, name,
                                _round_to_bfloat16(getattr(pr, name)))
    elif fault == "no_normaliser":
        # a constant where the carried normaliser was
        broken = dataclasses.replace(cfg, retention_eps=1e4)
    elif fault == "no_gate":
        broken = dataclasses.replace(cfg, gate_bias=1e4)     # exp(l) = 1
    elif fault == "pad_advances_the_state":
        monkeypatch.setattr(
            program, "row_mask",
            lambda valid_len, b, t: jnp.ones((b, t), bool))
    elif fault == "no_sqrt2":
        monkeypatch.setattr(pr, "_SQRT2", 1.0)
        pr._weights.cache_clear()
    try:
        mine, ctrl, state_gap, coarse = _served(broken, params, cfg)
    finally:
        pr._weights.cache_clear()
    failed = model.failed_limits(mine, ctrl, state_gap, coarse)
    with capsys.disabled():
        print(f"\nbrumby {fault}: failed {failed}; mean gap "
              f"{mine.mean():.5f} (control {ctrl.mean():.5f}), worst "
              f"{mine.max():.4f}, state gap {state_gap:.5f}, coarse "
              f"{coarse:.5f}")
    assert set(BROKEN[fault]) <= set(failed)
    assert bool(failed) == (fault != "sound")
    # and the harness's one comparison sees it
    exact = np.zeros((len(mine), 8), np.float32)
    held = model.held_to_the_limits(exact, np.zeros(len(mine), int), mine,
                                    ctrl, state_gap, coarse)
    worst = float((held.max(-1) - held[:, 0]).max())
    assert (worst > model.LOGIT_TIE_TOL) == (
        bool(set(failed) - {"LOGIT_TIE_TOL"}))

"""The Command A+ (``cohere2_moe``) model file: the program (window and full
attention layers through two kinds of page) against the plain reference (no
cache, every position against what it may see) through the harness at a tiny
size (one chip's share: experts 4-7 of 16; a window of 48 that the longer
prompts pass), the bfloat16 control and the two limits as the harness's one
comparison sees them, the byte and operation counts against numbers counted
by hand at the published widths, the readers of the new metrics, the traffic
file's multiset, the configuration file against the catalog, and the manifest
with its seven cells. New entries are found **by name**, never by place."""

import argparse
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
from benchmark.models import cohere2_moe as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "command-a-plus-serve-l4-ep8.json")
CELL = "mixed-steady"

#: the per-layer metrics this cell brought
NEW = ("step.attn_share_of_decode", "step.attn_share_of_prefill",
       "kernel.paged_decode_roofline", "kernel.paged_prefill_roofline",
       "kv.window_keys_share", "kv.window_pages_released_per_s")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def _cell():
    return common.cell_files(common.load_manifest(), CELL)


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = _cell()
    doc = _load("configs", "tiny-command-a")
    files = {"cell": {"name": "tiny-mixed", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-mixed"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-mixed", seed=2 ** 31 + 41,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, files, require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    named = out["rehearsal"]["metric_names"]
    if trace:
        # what a CPU trace and the counters can feed; the device-trace
        # metrics need a TPU's planes
        assert {"moe.held_assignment_share", "moe.experts_touched_share",
                "engine.slots_busy_share", "kv.prefix_hit_share",
                "kv.window_keys_share",
                "kv.window_pages_released_per_s"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "experts_step_bytes", "attention_step_bytes", "chunk_read_flops",
        "decode_step_bytes", "reference_logits", "control_choices"))
    assert 0 < model.DIFFER_RATIO < 1 < model.LOGIT_TIE_TOL


def test_the_manifest_has_seven_cells_and_the_new_one_finds_its_files():
    m = common.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert len(cells) == 7 and CELL in cells
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "command-a-plus-serve-l4-ep8"
    files = _cell()
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    assert [e["name"] for e in files["end_to_end"]] == ["tpot_p50_s",
                                                        "setup_s"]
    names = {x["name"] for x in files["per_layer"]}
    assert set(NEW) <= names
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL] for n in NEW)
    for x in files["per_layer"]:
        assert callable(readers.find(x))
        assert x["moves"] == "tpot_p50_s"
    # what the cell leaves to others: another model's kernels, the closed
    # loop's metrics, and the placed-span metrics that wait for their
    # benchmark issue (PERF.md section 7)
    assert not names & {
        "step.mla_share_of_decode", "kernel.mla_decode_roofline",
        "step.kda_share_of_decode", "kernel.decode_step_roofline",
        "step.decode_roofline", "device.launch_lag_ms_p50",
        "device.fence_tail_ms_p50", "trace.clock_window_ms",
        "device.idle_decode_fence_share", "device.idle_decode_host_share",
        "device.idle_prefill_share", "device.idle_park_share",
        "device.idle_unplaced_share"}
    # every other metric the other open-loop expert cells share
    theirs = {x["name"] for x in m["per_layer"]
              if {"doc-steady", "longdoc-steady"} <= set(
                  x.get("workloads", ()))}
    assert {n for n in theirs if not n.startswith(("device.", "trace."))} \
        <= names


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernels, the counts or the span's ``start``: None, never an
    error."""
    obs = {"trace": {"modules": {"jit_decode_step": [0.01],
                                 "jit_prefill_step": [0.02]},
                     "ops": {"jit_decode_step:fusion": (0.01, 1),
                             "jit_prefill_step:fusion": (0.02, 1)}},
           "trace_span": (0.0, 1.0), "t_open": 0.0, "t_close": 51.0,
           "spans": [{"name": "engine.prefill", "start": 0.1, "end": 0.2,
                      "attrs": {"tokens": 256}}],
           "counters": {}, "model": {"module": model, "cfg": None},
           "device_kind": "TPU v5 lite"}
    new = [x for x in _cell()["per_layer"] if x["name"] in NEW]
    assert len(new) == len(NEW)
    for x in new:
        assert readers.read(x, obs) is None, x["name"]


def test_the_traffic_files_multiset():
    """16 levels from 636 to 15872, 8 of them past the window, carrying
    81% of the prompt tokens; answers 32-384; nothing over 16384."""
    tr = _cell()["traffic"]
    levels = sorted(set(gen.quantiles(tr["prompt_len"], 16)))
    assert len(levels) == 16 and (levels[0], levels[-1]) == (636, 15872)
    assert abs(sum(levels) / 16 - 5721) < 1
    over = [n for n in levels if n > 4096]
    assert len(over) == 8 and abs(sum(over) / sum(levels) - 0.81) < 0.005
    pairs = gen.length_pairs(tr, 64)
    assert all(p + o <= tr["max_total"] == 16384 for p, o in pairs)
    assert min(o for _, o in pairs) >= 32 and max(o for _, o in pairs) <= 384
    assert tr["block_requests"] == 8 and tr["ramp_s"] == 18.0
    assert abs(tr["requests_per_s"] / tr["knee_requests_per_s"] - 0.8) < 0.01 \
        or abs(tr["requests_per_s"] / tr["knee_requests_per_s"] - 0.7) < 0.01
    # the correctness requests: 4 x 256 behind prompts the harness picks
    # from the levels; two under the window and two past it
    chk = tr["correctness"]
    fits = [n for n in levels if n + chk["decode_tokens"] <= chk["pad_to"]]
    picks = [fits[(2 * i + 1) * len(fits) // (2 * chk["requests"])]
             for i in range(chk["requests"])]
    assert (chk["requests"], chk["decode_tokens"]) == (4, 256)
    assert picks == [1096, 2739, 4430, 8903]
    assert chk["pad_to"] % model._QUERY_BLOCK == 0


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"command-a-plus-05-2026"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size",
                              "max_position_embeddings"]
    # no width is cut
    assert (doc["hidden_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["head_dim"],
            doc["intermediate_size"], doc["router_width"],
            doc["num_experts_per_tok"], doc["num_shared_experts"],
            doc["sliding_window"]) == (4096, 128, 8, 128, 4096, 128, 8, 4,
                                       4096)
    assert doc["layer_types"] == row["config"]["layer_types"][:4]
    for key in ("assumed", "deployment", "guarantees", "kv_pool_division"):
        assert doc[key]
    for key in ("rotary_pairing", "shared_experts", "expert_width",
                "image_tower", "initial_values"):
        assert doc["assumed"][key]
    assert doc["engine"] == {
        "slots": 32, "page_size": 32, "kernel": "auto",
        "kv_pool_bytes": 4 << 30, "max_queue": 256, "prefill_budget": 256}


def _unit_scale(params):
    """Variance-preserving weights at the tiny widths (as
    tests/test_zz_cohere2_moe.py): normal(0.02) hides errors there."""
    big = ("kernel", "experts_gate", "experts_up", "experts_down", "router")

    def fix(p, leaf):
        if p[-1].key in big:
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if p[-1].key == "kernel_t":             # stored [out, in]
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        if p[-1].key == "embed_tokens":
            return leaf / 0.02
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    cfg = model.program_config(_load("configs", "tiny-command-a"))
    return cfg, _unit_scale(model.init_params(cfg, 3))


def test_the_reference_against_the_program_and_the_control_apart():
    from lzy_tpu.models import cohere2_moe as program

    cfg, params = _tiny()
    assert cfg.experts_held == (4, 8) and cfg.n_routed_experts == 16
    assert (cfg.window, cfg.kv_layers, cfg.window_layers) == (48, 1, 3)
    assert params["layer_1_moe"]["experts_gate"].shape[0] == 4
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 128).tolist()])
    rows = jnp.arange(128)
    got = np.asarray(program.Cohere2Moe(cfg).apply({"params": params},
                                                   toks)[0])
    want = np.asarray(model.reference_logits(params, toks, rows, cfg))
    assert np.abs(got - want).max() < 2e-4
    control = np.asarray(model.reference_logits(params, toks, rows, cfg,
                                                jnp.bfloat16))
    assert np.abs(control - want).max() > 5e-3
    # the share is real: a chosen expert held elsewhere adds nothing
    weights = np.asarray(model.route(
        jnp.asarray(np.random.default_rng(6).normal(
            size=(20, cfg.d_model)).astype(np.float32)),
        params["layer_1_moe"], cfg))
    assert weights.shape == (20, 4)
    assert (weights > 0).sum() < 20 * cfg.top_k


def _harness_says_correct(logits, tokens):
    gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_paired_limit_reaches_the_harness_as_one_comparison():
    """A run whose tokens differ from the reference's as often as its
    bfloat16 control's do comes out not correct, though no token is over
    ``LOGIT_TIE_TOL``; one that differs a third as often (the program's
    reading) is correct."""
    rng = np.random.default_rng(0)
    n = model.DIFFER_RATIO_MIN_TOKENS
    exact = rng.normal(size=(n, 50)).astype(np.float32)
    best = exact.argmax(axis=-1)

    def gaps_of(differ):
        served = best.copy()
        served[:differ] = (best[:differ] + 1) % 50
        e = exact.copy()
        e[np.arange(differ), served[:differ]] = \
            e[np.arange(differ), best[:differ]] - 0.05
        return e, served, model.gaps(e, served)

    e, served, judged = gaps_of(13)
    _, _, control = gaps_of(38)
    assert _harness_says_correct(e, served)              # one limit alone
    assert _harness_says_correct(model.held_to_both_limits(
        e, served, judged, control), served)
    assert not _harness_says_correct(model.held_to_both_limits(
        e, served, judged, judged), served)
    assert not _harness_says_correct(model.held_to_both_limits(
        *gaps_of(38)[:2], control, control), gaps_of(38)[1])
    # fewer judged tokens than the limit is held over: not held yet
    assert _harness_says_correct(model.held_to_both_limits(
        e[:100], served[:100], judged[:100], judged[:100]), served[:100])


def test_logits_at_keeps_the_runs_tally_of_both(monkeypatch):
    cfg, params = _tiny()
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size, 64)
    padded = jnp.asarray([toks.tolist()])
    rows = jnp.arange(40, 46)
    monkeypatch.setattr(model, "_JUDGED", [])
    got = np.asarray(model.logits_at(params, padded, rows, cfg))
    want = np.asarray(model.reference_logits(params, padded, rows, cfg))
    assert (got == want).all() and len(model._JUDGED) == 1
    mine, control = model._JUDGED[0]
    assert mine.shape == control.shape == (6,) and (control >= 0).all()


def test_what_the_program_cannot_honour_is_refused():
    doc = _load("configs", "tiny-command-a")
    model.program_config(doc)
    for key, value in (("expert_selection_fn", "softmax"),
                       ("shared_expert_combination_strategy", "sum"),
                       ("use_parallel_block", False),
                       ("use_qk_norm", True)):
        with pytest.raises(ValueError, match=key):
            model.program_config({**doc, key: value})


def test_counts_at_the_published_widths():
    cfg = model.program_config(json.load(open(REAL)))
    assert (cfg.n_layers, cfg.kv_layers, cfg.window_layers, cfg.n_held) \
        == (4, 1, 3, 16)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.window) \
        == (32768, 16384, 4096)
    assert model.layer_token_bytes(cfg) == cfg.kv_token_bytes() == 4096
    assert model.kv_bytes_per_token(cfg) == 16_384
    assert model.expert_bytes(cfg) == 100_663_296     # 3 x 4096 x 4096 x 2 B
    assert model.routed_param_bytes(cfg) == 4 * 16 * 100_663_296
    experts = 4 * 4 * 100_663_296                     # a quarter reached
    assert model.experts_step_bytes(cfg, 10, 0.25) == experts
    with pytest.raises(TypeError):                    # never an expectation
        model.experts_step_bytes(cfg, 10)
    # a round's keys as the program counts them: a row at 9,999 reads
    # 10,000 in the full layer and 4,096 in each window layer
    assert model.attention_step_bytes(cfg, 10_000 + 3 * 4096) \
        == 22_288 * 4096
    # a chunk of 256 from 8,192: every query past the window
    p = np.arange(8192, 8448) + 1.0
    assert model.chunk_read_flops(cfg, 8192, 256) == 4.0 * 128 * 128 * (
        p.sum() + 3 * 256 * 4096)
    # one from 0: nobody has reached it
    q = np.arange(256) + 1.0
    assert model.chunk_read_flops(cfg, 0, 256) == 4.0 * 128 * 128 * 4 \
        * q.sum()
    # the program's parameter bytes at these widths (counted from shapes):
    # bfloat16 a layer: q and o 2 x 67,108,864, k and v 2 x 4,194,304, the
    # norm 4096, shared 3 x 67,108,864, routed 16 x 50,331,648; the final
    # norm and the tied embedding 134,217,728; float32: 524,288 a router
    from lzy_tpu.models import cohere2_moe as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes))
    bf16 = 4 * (2 * 67_108_864 + 2 * 4_194_304 + 4096 + 3 * 67_108_864
                + 16 * 50_331_648) + 4096 + 134_217_728
    assert param_bytes == 2 * bf16 + 4 * 4 * 524_288
    # the ISSUE's reckoning, 4.733 B parameters and 9.47 GB, within 1%
    assert abs((bf16 + 4 * 524_288) / 4.733e9 - 1) < 0.01
    assert abs(param_bytes / 9.47e9 - 1) < 0.01
    outside = param_bytes - 4 * 16 * 100_663_296
    # ten rows of 4,000 tokens each: the windows bind nowhere
    got = model.decode_step_bytes(cfg, param_bytes, 40_000, 10, 0.25)
    assert abs(got - (outside + experts + 16_384 * 40_000)) < 1.0
    # two rows of 20,000 tokens between them: the window layers are charged
    # no more than 2 x 4096
    got = model.decode_step_bytes(cfg, param_bytes, 20_000, 2, 0.25)
    assert abs(got - (outside + experts + 4096 * 20_000
                      + 3 * 4096 * 8192)) < 1.0
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0, 0.0) == outside
    with pytest.raises(TypeError):
        model.decode_step_bytes(cfg, param_bytes, 40_000, 10)


def _emit(end, rows, window_keys, full_keys):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_attn_window_keys_total": window_keys,
                "lzy_attn_full_keys_total": full_keys,
                "lzy_attn_rows_total": 4 * rows,
                "lzy_moe_experts_touched_total": 0,
                "lzy_moe_experts_held_total": 64}}}


def _metric(name):
    return next(x for x in _cell()["per_layer"] if x["name"] == name)


def test_the_decode_roofline_charges_the_keys_the_rounds_counted():
    """Two traced rounds that read 30,000 and 50,000 keys over the layers:
    40,000 a round."""
    cfg = model.program_config(json.load(open(REAL)))
    obs = {"trace": {"modules": {"jit_decode_step": [0.010, 0.012]},
                     "ops": {"jit_decode_step:paged_group_decode":
                             (0.002, 8),
                             "jit_decode_step:fusion.1": (0.020, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "spans": [_emit(0.3, 4, 18_000, 12_000),
                     _emit(0.6, 6, 24_000, 26_000),
                     _emit(1.5, 9, 99_000, 99_000)],     # past the span
           "model": {"module": model, "cfg": cfg}}
    want = 100.0 * (40_000 * 4096 / 819e9) * 2 / 0.002
    got = readers.read(_metric("kernel.paged_decode_roofline"), obs)
    assert abs(got - want) < 1e-6 and 10.0 < got < 100.0
    assert abs(readers.read(_metric("step.attn_share_of_decode"), obs)
               - 100.0 * 0.002 / 0.022) < 1e-9


def test_the_chunk_roofline_charges_the_positions_the_spans_name():
    cfg = model.program_config(json.load(open(REAL)))

    def prefill(end, start, tokens):
        return {"name": "engine.prefill", "start": end - 0.001, "end": end,
                "attrs": {"start": start, "tokens": tokens, "chunks": 1}}

    obs = {"trace": {"modules": {"jit_prefill_step": [0.030, 0.030]},
                     "ops": {"jit_prefill_step:paged_group_prefill":
                             (0.004, 8),
                             "jit_prefill_step:fusion.1": (0.056, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "spans": [prefill(0.2, 8192, 256), prefill(0.4, 0, 256),
                     prefill(1.4, 4096, 256),            # past the span
                     {"name": "engine.prefill", "start": 0.5, "end": 0.6,
                      "attrs": {}}],                     # nothing staged
           "model": {"module": model, "cfg": cfg}}
    flops = model.chunk_read_flops(cfg, 8192, 256) \
        + model.chunk_read_flops(cfg, 0, 256)
    want = 100.0 * flops / 197e12 / 0.004
    got = readers.read(_metric("kernel.paged_prefill_roofline"), obs)
    assert abs(got - want) < 1e-6 and 5.0 < got < 100.0
    assert abs(readers.read(_metric("step.attn_share_of_prefill"), obs)
               - 100.0 * 0.004 / 0.060) < 1e-9


def test_the_counter_metrics():
    obs = {"t_open": 10.0, "t_close": 61.0, "counters": {
        "lzy_attn_window_keys_total": 60_000.0,
        "lzy_attn_full_keys_total": 40_000.0,
        "lzy_kv_window_pages_released_total": 102.0}}
    assert abs(readers.read(_metric("kv.window_keys_share"), obs)
               - 50.0) < 1e-3
    assert readers.read(_metric("kv.window_pages_released_per_s"),
                        obs) == 2.0

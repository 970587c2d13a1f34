"""The Jamba model file: the program (Mamba-1 layers beside attention at a
group of 5, state leaves beside the paged pool) against the plain reference
through the harness at a tiny size, the bfloat16 control and the two limits
as the harness's one comparison sees them, the byte and operation counts
against numbers written out by hand at the published widths, the readers of
the new metrics, the traffic file's multiset, the configuration file against
the catalog, and the manifest finding the cell's files. New entries are found
**by name**, never by place or by count."""

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
from benchmark.models import jamba as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs", "jamba2-3b-serve.json")
CELL = "longctx-steady"

#: the per-layer metrics this cell brought
NEW = ("step.scan_share_of_prefill", "step.ssm1_share_of_decode",
       "kernel.selective_update_roofline", "kernel.selective_scan_roofline")
#: accepted metrics whose lists the cell joined
JOINED = ("loadgen.late_p95_s", "client.tpot_p85_s", "client.ttft_mean_s",
          "client.ttft_p85_s", "client.longest_silence_s",
          "gateway.overhead_p50_s", "engine.host_share_of_round",
          "kv.prefix_hit_share", "step.decode_s_p50",
          "step.prefill_chunk_s_p50", "engine.loop_host_share",
          "engine.prefill_share_of_loop", "engine.slots_busy_share",
          "trace.anchor_spread_us", "request.queue_wait_mean_s",
          "request.prefill_mean_s", "engine.longest_leaf_s",
          "engine.decode_overlap_share", "step.attn_share_of_decode",
          "step.attn_share_of_prefill", "kernel.paged_decode_roofline",
          "kernel.paged_prefill_roofline", "step.decode_roofline")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def _cell():
    return common.cell_files(common.load_manifest(), CELL)


def _real_cfg():
    with open(REAL) as f:
        return model.program_config(json.load(f))


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = _cell()
    doc = _load("configs", "tiny-jamba")
    files = {"cell": {"name": "tiny-longctx", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-longctx"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-longctx", seed=2 ** 31 + 44,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, files, require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    named = out["rehearsal"]["metric_names"]
    if trace:
        # what a CPU trace and the counters can feed; the device-trace
        # metrics need a TPU's planes
        assert {"engine.slots_busy_share", "kv.prefix_hit_share",
                "engine.decode_overlap_share",
                "request.prefill_mean_s"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "scan_bytes", "state_step_bytes", "attention_step_bytes",
        "chunk_read_flops", "decode_step_bytes", "reference_logits",
        "control_choices", "selective_recurrence"))
    assert 0 < model.GAP_RATIO < 1 <= model.LOGIT_TIE_TOL


def test_the_reference_imports_nothing_from_the_programs_models():
    import ast

    with open(model.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    assert not any("lzy_tpu" in ast.dump(n) for n in top)
    # the program's side reaches the program from inside its three functions
    # (the third finds the engine whose state the third limit reads)
    inside = {fn.name for fn in ast.walk(tree)
              if isinstance(fn, ast.FunctionDef)
              for n in ast.walk(fn) if isinstance(n, ast.ImportFrom)
              and (n.module or "").startswith("lzy_tpu")}
    assert inside == {"program_config", "init_params", "_serving_engine"}


def test_the_manifest_finds_the_cells_files_by_name():
    m = common.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert CELL in cells and cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "jamba2-3b-serve"
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    config = next(c for c in m["configs"] if c["name"] == "jamba2-3b-serve")
    assert config["reduced"] == ["max_position_embeddings"]
    assert config["file"] == "benchmark/configs/jamba2-3b-serve.json"
    files = _cell()
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    assert [e["name"] for e in files["end_to_end"]] == ["tpot_p50_s",
                                                        "setup_s"]
    names = {x["name"] for x in files["per_layer"]}
    assert names == set(NEW) | set(JOINED)
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL] for n in NEW)
    assert all(by_name[n]["workloads"][-1] == CELL for n in JOINED)
    for x in files["per_layer"]:
        assert callable(readers.find(x))
        assert x["moves"] == "tpot_p50_s"
    # what the cell leaves to others: the experts', the windows', other
    # models' kernels and the placed-span metrics held back since PR 41
    assert not names & {
        "step.experts_share_of_decode", "kernel.grouped_experts_roofline",
        "moe.held_assignment_share", "kv.window_keys_share",
        "step.decode_counted_roofline", "kernel.ssm_update_roofline",
        "step.chunk_read_share_of_prefill", "device.launch_lag_ms_p50",
        "device.fence_tail_ms_p50", "trace.clock_window_ms",
        "device.idle_decode_fence_share", "device.idle_park_share"}


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernels and the counts: None, never an error."""
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


def test_the_traffic_files_multiset():
    """16 levels from 2224 to 30177 (mean 10,224: 40 programs of 256);
    answers 48-512; nothing over 33280."""
    tr = _cell()["traffic"]
    levels = sorted(set(gen.quantiles(tr["prompt_len"], 16)))
    assert len(levels) == 16
    assert tr["prompt_len"]["median"] in (8192, 6144)
    if tr["prompt_len"]["median"] == 8192:
        assert (levels[0], levels[-1]) == (2224, 30177)
        assert abs(sum(levels) / 16 - 10224) < 1
    pairs = gen.length_pairs(tr, 64)
    assert all(p + o <= tr["max_total"] == 33280 for p, o in pairs)
    assert min(o for _, o in pairs) >= 48 and max(o for _, o in pairs) <= 512
    assert tr["block_requests"] == 8 and tr["ramp_s"] >= 18.0
    ratio = tr["requests_per_s"] / tr["knee_requests_per_s"]
    assert abs(ratio - 0.8) < 0.01 or abs(ratio - 0.7) < 0.01
    assert round(tr["requests_per_s"] * 51) >= 30
    chk = tr["correctness"]
    fits = [n for n in levels if n + chk["decode_tokens"] <= chk["pad_to"]]
    picks = [fits[(2 * i + 1) * len(fits) // (2 * chk["requests"])]
             for i in range(chk["requests"])]
    assert (chk["requests"], chk["decode_tokens"], chk["pad_to"]) \
        == (4, 256, 8448)
    assert len(set(picks)) == 4 and max(picks) + 256 <= 8448
    assert chk["pad_to"] % model._QUERY_BLOCK == 0
    assert chk["pad_to"] % model._SCAN_TURN == 0
    # twice the longest request fits the context limit
    assert 2 * tr["max_total"] <= _real_cfg().max_seq_len + 1024


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "AI21-Jamba2-3B"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["max_position_embeddings"]
    # no width, no depth, no row of the vocabulary is cut
    assert (doc["hidden_size"], doc["num_hidden_layers"], doc["vocab_size"],
            doc["intermediate_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["mamba_d_state"],
            doc["mamba_dt_rank"], doc["mamba_expand"], doc["mamba_d_conv"]) \
        == (2560, 28, 65536, 8192, 20, 1, 16, 160, 2, 4)
    for key in ("assumed", "deployment", "guarantees", "page_size"):
        assert doc[key]
    for key in ("head_dim", "layer_order", "initial_values", "mamba",
                "attention"):
        assert doc["assumed"][key]
    assert doc["ssm_state_dtype"] == "float32"
    assert doc["engine"] == {
        "slots": 32, "page_size": 128, "kernel": "auto",
        "kv_pool_bytes": 2 << 30, "max_queue": 256, "prefill_budget": 256}


def _unit_scale(params):
    """Variance-preserving weights at the tiny widths (as
    tests/test_zz_jamba.py): normal(0.02) hides errors there."""
    def fix(p, leaf):
        if p[-1].key == "kernel":
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if p[-1].key == "embed_tokens":
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    cfg = model.program_config(_load("configs", "tiny-jamba"))
    return cfg, _unit_scale(model.init_params(cfg, 3))


def test_the_reference_against_the_program_and_the_control_apart():
    from lzy_tpu.models import jamba as program

    cfg, params = _tiny()
    assert cfg.layer_kinds == ("mamba", "mamba", "mamba", "attention")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (5, 1, 16)
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 128).tolist()])
    rows = jnp.arange(128)
    got = np.asarray(program.Jamba(cfg).apply({"params": params}, toks)[0])
    want = np.asarray(model.reference_logits(params, toks, rows, cfg))
    assert np.abs(got - want).max() < 2e-4
    control = np.asarray(model.reference_logits(params, toks, rows, cfg,
                                                jnp.bfloat16))
    assert np.abs(control - want).max() > 5e-3


def _harness_says_correct(logits, tokens):
    gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_paired_limit_reaches_the_harness_as_one_comparison():
    """A run whose tokens sit as far below the reference's best as its
    bfloat16 control's do comes out not correct, though no token is over
    ``LOGIT_TIE_TOL``; one whose mean gap is a third of the control's (the
    program's reading) is correct."""
    rng = np.random.default_rng(0)
    n = model.GAP_RATIO_MIN_TOKENS
    exact = rng.normal(size=(n, 50)).astype(np.float32)
    best = exact.argmax(axis=-1)

    def gaps_of(differ):
        served = best.copy()
        served[:differ] = (best[:differ] + 1) % 50
        e = exact.copy()
        e[np.arange(differ), served[:differ]] = \
            e[np.arange(differ), best[:differ]] - 0.05
        return e, served, model.gaps(e, served)

    e, served, judged = gaps_of(40)
    _, _, control = gaps_of(120)
    assert abs(judged.mean() / control.mean() - 1 / 3) < 1e-3
    assert _harness_says_correct(e, served)              # one limit alone
    assert _harness_says_correct(model.held_to_the_limits(
        e, served, judged, control), served)
    assert not _harness_says_correct(model.held_to_the_limits(
        e, served, judged, judged), served)
    assert not _harness_says_correct(model.held_to_the_limits(
        *gaps_of(120)[:2], control, control), gaps_of(120)[1])
    # the readings the limit was set between, as recorded
    for seed, _, _, mine, ctl, worst, _ in model.CALIBRATION["program"]:
        assert mine < model.GAP_RATIO * ctl and worst < model.LOGIT_TIE_TOL
    for name in ("no_inner_norms", "residual_stream_in_bfloat16"):
        assert all(mine > model.GAP_RATIO * ctl
                   for _, _, _, mine, ctl, _, _ in model.CALIBRATION[name])
    # fewer judged tokens than the limit is held over: not held yet
    assert _harness_says_correct(model.held_to_the_limits(
        e[:100], served[:100], judged[:100], judged[:100]), served[:100])


def test_the_state_limit_reaches_the_harness_too():
    rng = np.random.default_rng(0)
    exact = rng.normal(size=(8, 50)).astype(np.float32)
    served = exact.argmax(axis=-1)
    # one served token is not the reference's choice, as some seventy of a
    # run's are: the lowered logit reaches the harness through such a token
    served[3] = (served[3] + 1) % 50
    exact[3, served[3]] = exact[3].max() - 0.05
    none = np.zeros(0)
    assert _harness_says_correct(model.held_to_the_limits(
        exact, served, none, none, 0.5 * model.STATE_REL_TOL), served)
    assert not _harness_says_correct(model.held_to_the_limits(
        exact, served, none, none, 1.5 * model.STATE_REL_TOL), served)
    # the readings the limit was set between, as recorded
    sound = [row[1] for row in model.STATE_CALIBRATION["program"]]
    fault = [row[1] for row in
             model.STATE_CALIBRATION["state_rounded_to_bfloat16"]]
    assert len(sound) >= 6 and len(fault) >= 6
    assert max(sound) < model.STATE_REL_TOL < min(fault)


def test_logits_at_keeps_the_runs_tally_and_reads_the_slots_state(
        monkeypatch):
    """The served request's state is read from the engine that serves the
    weights (no engine: refused, not skipped); a state rounded to bfloat16
    reads a thousand times further off at these float32 widths."""
    from lzy_tpu.serving import PagedInferenceEngine

    cfg, params = _tiny()
    prompt = np.random.default_rng(2).integers(
        1, cfg.vocab_size, 41).tolist()
    monkeypatch.setattr(model, "_JUDGED", [])
    monkeypatch.setattr(model, "_STATE_GAPS", [])
    with pytest.raises(LookupError, match="0 engines serve"):
        model.logits_at(params, jnp.asarray([prompt + [0] * 23]),
                        jnp.arange(40, 46), cfg)
    monkeypatch.setattr(model, "_JUDGED", [])
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  kernel="pallas", prefill_budget=64)
    try:
        req = engine.submit(prompt, max_new_tokens=6, greedy=True)
        for _ in range(100):
            if not engine.step():
                break
        full = prompt + list(req.tokens)
        padded = jnp.asarray([full + [0] * (64 - len(full))])
        rows = jnp.arange(40, 46)
        got = np.asarray(model.logits_at(params, padded, rows, cfg))
        want = np.asarray(model.reference_logits(params, padded, rows, cfg))
        assert (got == want).all() and len(model._JUDGED) == 1
        mine, control = model._JUDGED[0]
        assert mine.shape == control.shape == (6,) and (control >= 0).all()
        assert model._STATE_GAPS[0] < 1e-5
        _, states = model.features(params, padded, cfg, last=45)
        rough = {name: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
                 for name, leaf in engine.state_leaves().items()}
        assert max(model.state_gaps(rough, states, params,
                                    cfg)["slow"]) > 1e-3
    finally:
        engine.close()


def test_what_the_program_cannot_honour_is_refused():
    doc = _load("configs", "tiny-jamba")
    model.program_config(doc)
    for key, value in (("num_experts", 16), ("tie_word_embeddings", False),
                       ("mamba_proj_bias", True), ("sliding_window", 4096),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            model.program_config({**doc, key: value})
    with pytest.raises(ValueError, match="ssm_state_dtype bfloat16"):
        model.program_config({**doc, "ssm_state_dtype": "bfloat16"})
    with pytest.raises(ValueError, match="residual_dtype"):
        model.program_config({**doc, "residual_dtype": "bfloat16"})


def test_counts_at_the_published_widths():
    """Every number written out by hand."""
    cfg = _real_cfg()
    assert (cfg.n_layers, cfg.kv_layers, cfg.mamba_layers) == (28, 2, 26)
    assert (cfg.d_inner, cfg.ssm_state, cfg.conv_kernel) == (5120, 16, 4)
    assert (cfg.vocab_size, cfg.max_seq_len) == (65536, 65536)
    # keys and values: 2 layers x (128 + 128) x 2 bytes
    assert model.kv_bytes_per_token(cfg) == 1024
    assert cfg.kv_layers * cfg.kv_token_bytes() == 1024
    # a slot: 26 x (16 x 5120 x 4 + 3 x 5120 x 2)
    assert model.ssm_state_bytes(cfg) == 26 * 327_680 == 8_519_680
    assert model.conv_state_bytes(cfg) == 26 * 30_720 == 798_720
    assert model.slot_state_bytes(cfg) == 9_318_400
    # the update of 5 live rows: their recurrence state read and written
    assert model.state_step_bytes(cfg, 5) == 2 * 5 * 8_519_680
    # one program of 256 real positions: a position is x, dt and y a channel
    # and B and C a state entry in float32 = (3 x 5120 + 32) x 4 = 61,568
    # bytes a layer; a program the state in and out and A = 3 x 327,680
    a_layer = 256 * 61_568 + 983_040
    assert model.scan_bytes(cfg, 256) == 26 * a_layer == 435_355_648
    assert model.scan_bytes(cfg, 100, 1) == 26 * (100 * 61_568 + 983_040)
    assert model.scan_bytes(cfg, 512, 2) == 2 * model.scan_bytes(cfg, 256)
    # never the [T, Di, N] products: those alone would be 256 x 327,680
    # bytes a layer, five times the whole charge
    assert 256 * 327_680 > 5 * a_layer
    # a round's keys as the program counts them: a row at 9,999 reads
    # 10,000 keys in each of the two layers, 512 bytes a key and its value
    assert model.attention_step_bytes(cfg, 2 * 10_000) == 20_000 * 512
    # a chunk of 256 from 8,192 in two layers
    p = np.arange(8192, 8448) + 1.0
    assert model.chunk_read_flops(cfg, 8192, 256) \
        == 4.0 * 20 * 128 * 2 * p.sum()
    # the program's parameters, counted from shapes: 3,029,337,472, of which
    # float32 a Mamba layer: conv 4 x 5120 + 5120, dt_bias 5120, A_log
    # 81,920, D 5120 = 117,760
    from lzy_tpu.models import jamba as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == 3_029_337_472
    param_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    f32 = 26 * 117_760
    assert param_bytes == 2 * (3_029_337_472 - f32) + 4 * f32
    assert abs(param_bytes / 6.06e9 - 1) < 0.005
    # a Mamba layer 104,161,472; an attention layer 76,682,240; the rest
    assert 26 * 104_161_472 + 2 * 76_682_240 + 167_774_720 \
        == 3_029_337_472
    # five rows of 8,000 tokens each
    got = model.decode_step_bytes(cfg, param_bytes, 40_000, 5)
    assert got == param_bytes + 1024 * 40_000 + 2 * 5 * 9_318_400
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0) == param_bytes
    # state and keys under 3% of a round's bytes at these rows
    assert (got - param_bytes) / got < 0.03


def _emit(end, rows, keys):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_ssm_rows_total": 26 * rows,
                "lzy_attn_full_keys_total": keys,
                "lzy_attn_rows_total": 2 * rows}}}


def _prefill(end, start, tokens, chunks=1):
    return {"name": "engine.prefill", "start": end - 0.001, "end": end,
            "attrs": {"start": start, "tokens": tokens, "chunks": chunks}}


def _metric(name):
    return next(x for x in _cell()["per_layer"] if x["name"] == name)


def test_the_update_roofline_charges_the_rows_the_rounds_counted():
    cfg = _real_cfg()
    obs = {"trace": {"modules": {"jit_decode_step": [0.008, 0.008]},
                     "ops": {"jit_decode_step:selective_state_update":
                             (0.0008, 52),
                             "jit_decode_step:paged_group_decode":
                             (0.0002, 4),
                             "jit_decode_step:fusion.1": (0.015, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "spans": [_emit(0.3, 4, 60_000), _emit(0.6, 6, 100_000),
                     _emit(1.5, 9, 999_000)],            # past the span
           "model": {"module": model, "cfg": cfg}}
    want = 100.0 * (2 * 5 * 8_519_680 / 819e9) * 2 / 0.0008
    got = readers.read(_metric("kernel.selective_update_roofline"), obs)
    assert abs(got - want) < 1e-6 and 10.0 < got < 100.0
    assert abs(readers.read(_metric("step.ssm1_share_of_decode"), obs)
               - 100.0 * 0.0008 / 0.016) < 1e-9
    # the attention read's, by the keys the rounds counted: 80,000 a round
    want = 100.0 * (80_000 * 512 / 819e9) * 2 / 0.0002
    got = readers.read(_metric("kernel.paged_decode_roofline"), obs)
    assert abs(got - want) < 1e-6


def test_the_scan_roofline_charges_the_tokens_the_spans_name():
    cfg = _real_cfg()
    obs = {"trace": {"modules": {"jit_prefill_step": [0.016, 0.016]},
                     "ops": {"jit_prefill_step:selective_scan":
                             (0.0060, 52),
                             "jit_prefill_step:paged_group_prefill":
                             (0.0010, 4),
                             "jit_prefill_step:fusion.1": (0.025, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "spans": [_prefill(0.2, 8192, 256), _prefill(0.4, 512, 100),
                     _prefill(1.4, 4096, 256),           # past the span
                     {"name": "engine.prefill", "start": 0.5, "end": 0.6,
                      "attrs": {}}],                     # nothing staged
           "model": {"module": model, "cfg": cfg}}
    need = model.scan_bytes(cfg, 256) + model.scan_bytes(cfg, 100)
    want = 100.0 * need / 819e9 / 0.0060
    got = readers.read(_metric("kernel.selective_scan_roofline"), obs)
    assert abs(got - want) < 1e-6 and 5.0 < got < 100.0
    assert abs(readers.read(_metric("step.scan_share_of_prefill"), obs)
               - 100.0 * 0.0060 / 0.032) < 1e-9
    flops = model.chunk_read_flops(cfg, 8192, 256) \
        + model.chunk_read_flops(cfg, 512, 100)
    got = readers.read(_metric("kernel.paged_prefill_roofline"), obs)
    assert abs(got - 100.0 * flops / 197e12 / 0.0010) < 1e-6


def test_the_decode_roofline_takes_the_four_argument_count():
    cfg = _real_cfg()
    now = 0.5
    # two rows resident through the traced span, 8,000 and 12,000 tokens
    rows = [(8000, [(0.0, 1), (now, 40), (2.0, 200)]),
            (12000, [(0.0, 1), (now, 40), (2.0, 200)])]
    obs = {"trace": {"modules": {"jit_decode_step": [0.0080, 0.0082]}},
           "trace_span": (0.1, 0.9), "device_kind": "TPU v5 lite",
           "rows": rows,
           "model": {"module": model, "cfg": cfg,
                     "param_bytes": 6_061_737_984}}
    got = readers.read(_metric("step.decode_roofline"), obs)
    assert got is not None and 80.0 < got < 100.0

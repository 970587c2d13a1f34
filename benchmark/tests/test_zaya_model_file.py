"""The ZAYA model file: the program (compressed convolutional attention with
a carried window, one expert a token behind an MLP router with a carry,
state leaves beside the paged pool) against the plain reference through the
harness at a tiny size, the bfloat16 control and the two limits as the
harness's one comparison sees them, the balancing rule, the byte counts
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
from benchmark.models import zaya as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs", "zaya1-8b-serve-l24.json")
CELL = "think-steady"

#: the per-layer metrics this cell brought
NEW = ("step.cca_share_of_decode", "kernel.cca_update_roofline")
#: accepted metrics whose lists the cell joined
JOINED = ("loadgen.late_p95_s", "client.tpot_p85_s", "client.ttft_mean_s",
          "client.ttft_p85_s", "client.longest_silence_s",
          "gateway.overhead_p50_s", "engine.host_share_of_round",
          "kv.prefix_hit_share", "step.decode_s_p50",
          "step.prefill_chunk_s_p50", "engine.loop_host_share",
          "engine.prefill_share_of_loop", "engine.slots_busy_share",
          "trace.anchor_spread_us", "request.queue_wait_mean_s",
          "request.prefill_mean_s", "engine.longest_leaf_s",
          "engine.decode_overlap_share", "step.experts_share_of_decode",
          "step.experts_share_of_prefill", "kernel.grouped_experts_roofline",
          "moe.experts_touched_share", "moe.held_assignment_share",
          "step.decode_counted_roofline", "step.paged_read_share_of_decode",
          "step.chunk_read_share_of_prefill")


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
    doc = _load("configs", "tiny-zaya")
    files = {"cell": {"name": "tiny-think", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-think"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-think", seed=2 ** 31 + 48,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, files, require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    named = out["rehearsal"]["metric_names"]
    if trace:
        # what a CPU trace and the counters can feed; the device-trace
        # metrics need a TPU's planes
        assert {"engine.slots_busy_share", "kv.prefix_hit_share",
                "engine.decode_overlap_share", "request.prefill_mean_s",
                "moe.experts_touched_share",
                "moe.held_assignment_share"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "experts_step_bytes", "cca_step_bytes", "decode_step_bytes",
        "reference_logits", "balance_router", "program_choices",
        "window_gaps"))
    assert 0 < model.GAP_RATIO < 1 <= model.LOGIT_TIE_TOL


def test_the_reference_imports_nothing_from_the_programs_models():
    import ast

    with open(model.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    assert not any("lzy_tpu" in ast.dump(n) for n in top)
    # the program's side reaches the program from inside its four functions
    # (the last two: the program's own choices, and the engine whose windows
    # are read)
    inside = {fn.name for fn in ast.walk(tree)
              if isinstance(fn, ast.FunctionDef)
              for n in ast.walk(fn) if isinstance(n, ast.ImportFrom)
              and (n.module or "").startswith("lzy_tpu")}
    assert inside == {"program_config", "init_params", "_program_layer",
                      "_serving_engine"}


def test_the_manifest_finds_the_cells_files_by_name():
    m = common.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert CELL in cells and cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "zaya1-8b-serve-l24"
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    config = next(c for c in m["configs"]
                  if c["name"] == "zaya1-8b-serve-l24")
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "max_position_embeddings"]
    assert config["file"] == "benchmark/configs/zaya1-8b-serve-l24.json"
    files = _cell()
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    assert [e["name"] for e in files["end_to_end"]] == ["tpot_p50_s",
                                                        "setup_s"]
    names = {x["name"] for x in files["per_layer"]}
    assert names == set(NEW) | set(JOINED)
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL] for n in NEW)
    assert all(CELL in by_name[n]["workloads"] for n in JOINED)
    for x in files["per_layer"]:
        assert callable(readers.find(x))
        assert x["moves"] == "tpot_p50_s"
    # what the cell leaves to others: other models' kernels, the group
    # read's metrics, and the placed-span metrics held back since PR 41
    assert not names & {
        "step.decode_roofline", "kernel.ssm_update_roofline",
        "kernel.paged_decode_roofline", "step.attn_share_of_decode",
        "kv.window_keys_share", "device.launch_lag_ms_p50",
        "device.fence_tail_ms_p50", "trace.clock_window_ms",
        "device.idle_decode_fence_share", "device.idle_park_share"}


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernel and the counts: None, never an error."""
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
    """16 levels from 87 to 1704 (mean 512: two programs of 256); answers
    128-2048; nothing over 4096."""
    tr = _cell()["traffic"]
    levels = sorted(set(gen.quantiles(tr["prompt_len"], 16)))
    assert len(levels) == 16 and (levels[0], levels[-1]) == (87, 1704)
    assert abs(sum(levels) / 16 - 512) < 1
    assert (tr["prompt_len"]["median"], tr["output_len"]["median"]) \
        == (384, 640)
    pairs = gen.length_pairs(tr, 64)
    assert all(p + o <= tr["max_total"] == 4096 for p, o in pairs)
    assert min(o for _, o in pairs) >= 128
    assert max(o for _, o in pairs) <= 2048
    assert tr["block_requests"] == 8 and tr["ramp_s"] >= 18.0
    ratio = tr["requests_per_s"] / tr["knee_requests_per_s"]
    assert abs(ratio - 0.8) < 0.01
    assert round(tr["requests_per_s"] * 51) >= 60
    chk = tr["correctness"]
    fits = [n for n in levels if n + chk["decode_tokens"] <= chk["pad_to"]]
    picks = [fits[(2 * i + 1) * len(fits) // (2 * chk["requests"])]
             for i in range(chk["requests"])]
    assert (chk["requests"], chk["decode_tokens"], chk["pad_to"]) \
        == (4, 256, 2304)
    assert picks == [171, 318, 530, 1102]
    assert chk["pad_to"] % model._QUERY_BLOCK == 0
    assert tr["max_total"] <= _real_cfg().max_seq_len


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "ZAYA1-8B"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "max_position_embeddings"]
    # no width, no expert, no row of the vocabulary is cut
    assert (doc["hidden_size"], doc["vocab_size"], doc["num_experts"],
            doc["num_experts_per_tok"], doc["moe_intermediate_size"],
            doc["router_hidden_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["head_dim"], doc["cca_time0"],
            doc["cca_time1"]) \
        == (2048, 262272, 16, 1, 2048, 256, 8, 2, 128, 2, 2)
    assert doc["num_hidden_layers"] == 24 == len(doc["layer_types"])
    assert doc["rope_parameters"] == row["config"]["rope_parameters"]
    for key in ("assumed", "deployment", "guarantees", "page_size"):
        assert doc[key]
    for key in ("sources", "convolutions", "query_key_mean", "value_shift",
                "norms_and_temperature", "rotary", "router",
                "no_skip_expert", "residual_scaling", "carried_window",
                "initial_values", "routing_spread"):
        assert doc["assumed"][key]
    assert doc["cca_window_dtype"] == doc["residual_dtype"] == "float32"
    assert doc["engine"]["slots"] == 64
    assert doc["engine"]["kv_pool_bytes"] == 3 << 30


def _unit_scale(params):
    """Variance-preserving weights at the tiny widths (as
    tests/test_zz_zaya.py): normal(0.02) hides errors there."""
    def fix(p, leaf):
        name = p[-1].key
        if name in ("kernel", "router_down") or name.startswith("experts_"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name == "embed_tokens":
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    from lzy_tpu.models import zaya as program

    cfg = model.program_config(_load("configs", "tiny-zaya"))
    return cfg, _unit_scale(program.init_params(cfg, jax.random.PRNGKey(3)))


def test_the_reference_against_the_program_and_the_control_apart():
    from lzy_tpu.models import zaya as program

    cfg, params = _tiny()
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (3, 4, 2, 16)
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 128).tolist()])
    rows = jnp.arange(128)
    got = np.asarray(program.Zaya(cfg).apply({"params": params}, toks)[0])
    want = np.asarray(model.reference_logits(params, toks, rows, cfg))
    assert np.abs(got - want).max() < 2e-4
    control = np.asarray(model.reference_logits(params, toks, rows, cfg,
                                                jnp.bfloat16))
    assert np.abs(control - want).max() > 5e-3
    # the control's router ties and flips; the program's layers choose what
    # the reference chooses
    _, _, exact = model.features(params, toks, cfg)
    _, _, rough = model.features(params, toks, cfg, jnp.bfloat16)
    assert (np.asarray(rough) != np.asarray(exact)).sum() > 0
    assert (model.program_choices(params, toks, cfg)
            == np.asarray(exact)).all()


def test_the_balancing_rule_evens_the_load():
    """Sign updates of a bias against the load: a softmax router whose
    experts start four units of logit apart ends with every expert inside
    the band; ``balance_router`` moves the biases and nothing else."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4096, 16)) + np.linspace(-2.0, 2.0, 16)
    scores = jnp.asarray(jax.nn.softmax(logits, axis=-1), jnp.float32)
    _, raw = model._balance(scores, jnp.zeros((16,)), steps=0, n=16)
    beta, load = model._balance(scores, jnp.zeros((16,)),
                                steps=model.BALANCE_STEPS, n=16)
    assert float(raw.min()) * 16 < 0.1 and float(raw.max()) * 16 > 4
    assert float(load.min()) * 16 > 1 - model.BALANCE_BAND / 2
    assert float(load.max()) * 16 < 1 + model.BALANCE_BAND / 2
    assert float(beta[0]) > float(beta[-1])
    cfg, params = _tiny()
    balanced, spread = model.balance_router(params, cfg, 7)
    assert spread["band"] == model.BALANCE_BAND
    assert spread["even"] == (
        spread["least_share_x_experts"] >= 1 - model.BALANCE_BAND
        and spread["most_share_x_experts"] <= 1 + model.BALANCE_BAND)
    same = jax.tree_util.tree_map_with_path(
        lambda p, a, b: p[-1].key == "router_bias" or bool((a == b).all()),
        params, balanced)
    assert all(jax.tree_util.tree_leaves(same))
    moved = [balanced[f"layer_{i}"]["moe"]["router_bias"]
             for i in range(cfg.n_layers)]
    assert all(float(jnp.abs(b).max()) > 0.05 for b in moved)


def _harness_says_correct(logits, tokens):
    gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_paired_limit_reaches_the_harness_as_one_comparison():
    """A run whose tokens sit as far below the reference's best as its
    bfloat16 control's do comes out not correct, though no token is over
    ``LOGIT_TIE_TOL``; one whose mean gap is a third of the control's is
    correct."""
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
    assert len(model.CALIBRATION["program"]) >= 9
    for name, rows in model.CALIBRATION.items():
        for row in rows:
            mine, ctl, worst = row[3], row[4], row[5]
            assert mine < model.GAP_RATIO * ctl, (name, row)
            assert worst < model.LOGIT_TIE_TOL, (name, row)
    assert max(r[3] / r[4] for r in model.CALIBRATION["program"]) \
        < model.GAP_RATIO - 0.15
    # fewer judged tokens than the limit is held over: not held yet
    assert _harness_says_correct(model.held_to_the_limits(
        e[:100], served[:100], judged[:100], judged[:100]), served[:100])


def test_logits_at_keeps_the_runs_tally_and_reads_the_slots_window(
        monkeypatch, capsys):
    """The served request's windows are read from the engine that serves
    the weights (no engine: refused, not skipped)."""
    from lzy_tpu.ops.interpret import set_interpret
    from lzy_tpu.serving import PagedInferenceEngine

    set_interpret(True)
    cfg, params = _tiny()
    prompt = np.random.default_rng(2).integers(
        1, cfg.vocab_size, 41).tolist()
    monkeypatch.setattr(model, "_JUDGED", [])
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
        line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
        said = line["zaya_judged"]
        assert said["tokens"] == 6 and said["window_slot"] == 0
        assert said["window_gap_worst_layer"] < 1e-5
        # 46 positions fed x 3 layers, the program's choices the reference's
        assert said["choices"] == 46 * 3 and said["flipped_choices"] == 0
        _, windows, _ = model.features(params, padded, cfg, last=45)
        rough = {name: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
                 for name, leaf in engine.state_leaves().items()}
        assert min(model.window_gaps(rough, windows, cfg)["layers"]) > 1e-3
    finally:
        engine.close()


def test_what_the_program_cannot_honour_is_refused():
    doc = _load("configs", "tiny-zaya")
    model.program_config(doc)
    for key, value in (("num_experts_per_tok", 2),
                       ("tie_word_embeddings", False),
                       ("sliding_window", 4096), ("cca_time1", 3),
                       ("hidden_act", "gelu"),
                       ("layer_types", ["hybrid", "hybrid", "other"])):
        with pytest.raises(ValueError, match=key):
            model.program_config({**doc, key: value})
    with pytest.raises(ValueError, match="cca_window_dtype bfloat16"):
        model.program_config({**doc, "cca_window_dtype": "bfloat16"})
    with pytest.raises(ValueError, match="residual_dtype"):
        model.program_config({**doc, "residual_dtype": "bfloat16"})


def test_counts_at_the_published_widths():
    """Every number written out by hand."""
    cfg = _real_cfg()
    assert (cfg.n_layers, cfg.kv_layers, cfg.n_held) == (24, 24, 16)
    assert (cfg.vocab_size, cfg.max_seq_len) == (262272, 4096)
    # keys and values: 24 layers x 2 x 2 heads x 128 x 2 bytes
    assert model.kv_bytes_per_token(cfg) == 24 * 1024 == 24_576
    assert cfg.kv_layers * cfg.kv_token_bytes() == 24_576
    # a slot's windows: 24 x 2 positions x 1408 channels x 4 bytes
    assert model.window_width(cfg) == cfg.window_width == 1408
    assert model.window_bytes(cfg) == 24 * 11_264 == 270_336
    # an expert: 3 x 2048 x 2048 x 2 bytes; 16 a layer, 24 layers
    assert model.expert_bytes(cfg) == 25_165_824
    assert model.routed_param_bytes(cfg) == 24 * 16 * 25_165_824 \
        == 9_663_676_416
    # fifteen of sixteen experts reached: 15 x 24 x 25.2 MB
    assert model.experts_step_bytes(cfg, 45, 15 / 16) \
        == 24 * 15 * 25_165_824
    # the update of 40 live rows a layer: the window read and written
    # (4 x 1408), the position in (1408 + 128), q, k, v out (1280 + 256),
    # float32 = 34,816 bytes a row; the weights once: 10 x 256 x 128 x 2
    # + (4 x 1280 + 2) x 4 = 675,848
    assert model.cca_step_bytes(cfg, 40) == 24 * (40 * 34_816 + 675_848)
    assert model.cca_step_bytes(cfg, 0) == 24 * 675_848
    # the program's parameters, counted from shapes
    from lzy_tpu.models import zaya as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == 5_519_134_896
    # a layer 207,583,506 (the first, without the stream's pair and the
    # carry's scale: 207,579,154), the tied embedding, the final norm
    assert 23 * 207_583_506 + 207_579_154 + 262272 * 2048 + 2048 \
        == 5_519_134_896
    param_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert param_bytes == 11_071_009_472
    # 45 rows of 900 tokens each that reached 15 of 16 experts
    got = model.decode_step_bytes(cfg, param_bytes, 40_500, 45, 15 / 16)
    outside = param_bytes - 9_663_676_416
    assert got == outside + 24 * 15 * 25_165_824 + 24_576 * 40_500 \
        + 2 * 45 * 270_336
    # the head is most of what lies outside the experts
    assert 262272 * 2048 * 2 / outside > 0.75
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0, 0.0) == outside


def _emit(end, rows, touched):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_moe_assignments_total": 24 * rows,
                "lzy_moe_held_assignments_total": 24 * rows,
                "lzy_moe_experts_touched_total": touched,
                "lzy_moe_experts_held_total": 24 * 16,
                "lzy_attn_full_keys_total": 24 * rows * 900,
                "lzy_attn_rows_total": 24 * rows,
                "lzy_cca_rows_total": 24 * rows}}}


def _metric(name):
    return next(x for x in _cell()["per_layer"] if x["name"] == name)


def test_the_rooflines_charge_what_the_rounds_counted():
    cfg = _real_cfg()
    obs = {"trace": {"modules": {"jit_decode_step": [0.020, 0.020]},
                     "ops": {"jit_decode_step:cca_mix_update":
                             (0.0010, 48),
                             "jit_decode_step:grouped_experts":
                             (0.0300, 48),
                             "jit_decode_step:fusion.1": (0.009, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "spans": [_emit(0.3, 40, 340), _emit(0.6, 50, 360),
                     _emit(1.5, 9, 99)],                 # past the span
           "model": {"module": model, "cfg": cfg}}
    want = 100.0 * (model.cca_step_bytes(cfg, 45) / 819e9) * 2 / 0.0010
    got = readers.read(_metric("kernel.cca_update_roofline"), obs)
    assert abs(got - want) < 1e-6 and 1.0 < got < 100.0
    assert abs(readers.read(_metric("step.cca_share_of_decode"), obs)
               - 100.0 * 0.0010 / 0.040) < 1e-9
    # the experts', by the share the rounds counted: 700 of 768
    share = 700 / 768
    want = 100.0 * (24 * 16 * share * 25_165_824 / 819e9) * 2 / 0.0300
    got = readers.read(_metric("kernel.grouped_experts_roofline"), obs)
    assert abs(got - want) < 1e-6 and got < 100.0

"""The MiniCPM-SALA model file: the program (block-sparse attention that
chooses its key blocks from compressed keys inside the paged read, lightning
layers with a decayed state a slot) against the plain reference through the
harness at a tiny size, the bfloat16 control and the four limits as the
harness's one comparison sees them, the byte and operation counts against
numbers written out by hand at the published widths, the readers of the new
metrics, the traffic file's multiset, the configuration file against the
catalog, and the manifest finding the cell's files. New entries are found
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
from benchmark.models import minicpm_sala as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "minicpm-sala-serve-l18.json")
CELL = "sparse-steady"

#: the per-layer metrics this cell brought
NEW = ("step.select_share_of_decode", "step.select_share_of_prefill",
       "step.sparse_read_share_of_decode",
       "step.sparse_read_share_of_prefill", "step.lightning_share_of_decode",
       "kv.sparse_blocks_read_share", "kernel.sparse_decode_roofline",
       "kernel.sparse_select_roofline", "kernel.sparse_prefill_roofline",
       "kernel.lightning_update_roofline")
#: accepted metrics whose lists the cell joined
JOINED = ("loadgen.late_p95_s", "client.tpot_p85_s", "client.ttft_mean_s",
          "client.ttft_p85_s", "client.longest_silence_s",
          "gateway.overhead_p50_s", "engine.host_share_of_round",
          "kv.prefix_hit_share", "step.decode_s_p50",
          "step.prefill_chunk_s_p50", "engine.loop_host_share",
          "engine.prefill_share_of_loop", "engine.slots_busy_share",
          "trace.anchor_spread_us", "request.queue_wait_mean_s",
          "request.prefill_mean_s", "engine.longest_leaf_s",
          "engine.decode_overlap_share", "step.decode_roofline")


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
    for name in ("_JUDGED", "_STATE_GAPS", "_COARSE", "_CHOICES"):
        monkeypatch.setattr(model, name, [])
    real = _cell()
    doc = _load("configs", "tiny-minicpm-sala")
    files = {"cell": {"name": "tiny-sparse", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-sparse"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-sparse", seed=2 ** 31 + 51,
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
                "kv.sparse_blocks_read_share"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)
    # every request selected, and what it chose was the reference's
    assert model._CHOICES and sum(d for d, _ in model._CHOICES) == 0
    assert max(model._STATE_GAPS) < 1e-5


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "sparse_read_step_bytes", "select_step_bytes",
        "lightning_step_bytes", "lightning_scan_flops",
        "sparse_prefill_flops", "decode_step_bytes", "reference_logits",
        "program_choices", "choices_differ", "state_gaps"))
    assert 0 < model.GAP_RATIO < 1 <= model.LOGIT_TIE_TOL
    assert 0 < model.STATE_REL_TOL < 1 and 0 < model.CHOICE_DIFFER_TOL < 1
    assert 0 < model.STATE_COARSE_TOL < 1


def test_the_reference_imports_nothing_from_the_program():
    import ast

    with open(model.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    assert not any("lzy_tpu" in ast.dump(n) for n in top)
    # the program's side reaches the program from inside its four functions
    # (the last two: the program's own choices, and the engine whose state
    # is read); none of them is the reference's
    inside = {fn.name: sorted({n.module for n in ast.walk(fn)
                               if isinstance(n, ast.ImportFrom)
                               and (n.module or "").startswith("lzy_tpu")})
              for fn in ast.walk(tree) if isinstance(fn, ast.FunctionDef)}
    inside = {k: v for k, v in inside.items() if v}
    assert set(inside) == {"program_config", "init_params",
                           "program_choices", "_serving_engine"}
    assert not any("sparse_attention" in m or "mamba2" in m
                   for mods in inside.values() for m in mods)


def test_the_manifest_finds_the_cells_files_by_name():
    m = common.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert CELL in cells and cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "minicpm-sala-serve-l18"
    assert len(cells) == 10
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    config = next(c for c in m["configs"]
                  if c["name"] == "minicpm-sala-serve-l18")
    assert config["reduced"] == ["num_hidden_layers", "mixer_types",
                                 "max_position_embeddings"]
    assert config["source"] == \
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
    assert config["file"] == "benchmark/configs/minicpm-sala-serve-l18.json"
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
    # what the cell leaves to others: other models' kernels, and the
    # placed-span metrics held back since PR 41
    assert not names & {
        "kernel.ssm_update_roofline", "kernel.paged_decode_roofline",
        "step.paged_read_share_of_decode", "kv.window_keys_share",
        "device.launch_lag_ms_p50", "device.fence_tail_ms_p50",
        "trace.clock_window_ms", "device.idle_decode_fence_share",
        "device.idle_park_share"}


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
    """14 levels from 8,192 to 28,413 (every one past dense_len); answers
    48-384; nothing over 33,792."""
    tr = _cell()["traffic"]
    cfg = _real_cfg()
    levels = sorted(set(gen.quantiles(tr["prompt_len"], 16)))
    assert (levels[0], levels[-1]) == (8192, 28413) and len(levels) == 14
    assert min(levels) >= cfg.dense_len
    assert (tr["prompt_len"]["median"], tr["output_len"]["median"]) \
        == (12288, 160)
    assert (tr["prompt_len"]["min"], tr["prompt_len"]["max"]) \
        == (8192, 32768)
    pairs = gen.length_pairs(tr, 64)
    assert all(p + o <= tr["max_total"] == 33792 for p, o in pairs)
    assert min(o for _, o in pairs) >= 48
    assert max(o for _, o in pairs) <= 384
    assert tr["ramp_s"] >= 20.0
    ratio = tr["requests_per_s"] / tr["knee_requests_per_s"]
    assert abs(ratio - 0.8) < 0.01
    # 10 requests in five balanced blocks of 2, and the last of them due
    # early enough to be read and answered by the cut (one block of 10 in
    # the file's fixed order ends on 19,358, 28,413 and 12,729 tokens due in
    # the window's last 5.2 s: no rate answers those)
    due = gen.open_loop_segment(tr, seed=1, stream=2, duration_s=51.0,
                                vocab=cfg.vocab_size)
    assert tr["block_requests"] == 2 and len(due) == 10
    assert max(r["due"] for r in due) < 51.0 - 3.5
    assert len(due[-1]["prompt"]) == 9469
    # the traced span opens on the longest prompt's prefill
    at = [r["due"] for r in due if len(r["prompt"]) == 28413]
    assert at[0] < tr["trace_after_s"] < at[0] + 1.0 <= 51.0 / 4
    chk = tr["correctness"]
    fits = [n for n in levels if n + chk["decode_tokens"] <= chk["pad_to"]]
    picks = [fits[(2 * i + 1) * len(fits) // (2 * chk["requests"])]
             for i in range(chk["requests"])]
    assert (chk["requests"], chk["decode_tokens"], chk["pad_to"]) \
        == (4, 256, 16640)
    assert picks == [8664, 10253, 12729, 14726]
    assert chk["pad_to"] % model._QUERY_BLOCK == 0
    assert tr["max_total"] <= cfg.max_seq_len
    assert tr["tpot_min_tokens"] == 16


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "MiniCPM-SALA"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    # no width, no head, no row of the vocabulary is cut
    assert (doc["hidden_size"], doc["vocab_size"], doc["intermediate_size"],
            doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["head_dim"], doc["lightning_nh"],
            doc["lightning_head_dim"]) \
        == (4096, 73448, 16384, 32, 2, 128, 32, 128)
    assert doc["num_hidden_layers"] == 18 == len(doc["mixer_types"])
    assert doc["mixer_types"] == row["config"]["mixer_types"][:18]
    for key in ("assumed", "deployment", "guarantees", "page_size"):
        assert doc[key]
    for key in ("sources", "sparse_config", "compressed_keys",
                "block_scores", "window_in_blocks", "ties",
                "mode_fixed_at_admission", "choice_is_a_querys_own",
                "lightning_decay", "lightning_activations",
                "minicpm4_attention", "unused_keys", "residual_stream",
                "lightning_state", "initial_values"):
        assert doc["assumed"][key]
    assert doc["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    assert doc["lightning_state_dtype"] == doc["residual_dtype"] == "float32"
    assert doc["engine"]["slots"] == 16 and doc["engine"]["page_size"] == 64
    assert doc["engine"]["kv_pool_bytes"] == 1 << 30


def _tiny():
    from lzy_tpu.models import minicpm_sala as program

    cfg = model.program_config(_load("configs", "tiny-minicpm-sala"))
    return cfg, program.init_params(cfg, jax.random.PRNGKey(3))


def test_the_control_stands_apart_and_chooses_otherwise():
    cfg, params = _tiny()
    assert (cfg.n_layers, cfg.kv_layers, cfg.n_heads, cfg.n_kv_heads) \
        == (4, 2, 4, 2)
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 260).tolist()])
    rows = np.arange(260)
    want = np.asarray(model.reference_logits(params, toks, rows, cfg))
    control = np.asarray(model.reference_logits(params, toks, rows, cfg,
                                                jnp.bfloat16))
    assert np.abs(control - want).max() > 5e-3
    _, _, exact = model.features(params, toks, cfg)
    _, _, rough = model.features(params, toks, cfg, jnp.bfloat16)
    differing, compared = model.choices_differ(rough, exact, 259)
    assert compared == 2 * 2 * 260 and 0 < differing < compared / 2
    # a table narrower or wider than the padded sequence's blocks compares
    # over the blocks both have, and nothing may be chosen past them
    narrow = [np.asarray(c)[..., :17] for c in exact]
    assert model.choices_differ(narrow, exact, 259) == (0, compared)
    wide = [np.pad(np.asarray(c), ((0, 0), (0, 0), (0, 5))) for c in exact]
    assert model.choices_differ(wide, exact, 259) == (0, compared)
    wide[0][0, 7, -1] = True
    assert model.choices_differ(wide, exact, 259) == (1, compared)
    # a window block left out differs everywhere past the first blocks
    skipped = [np.asarray(c).copy() for c in exact]
    for c in skipped:
        at = np.arange(c.shape[1])
        c[:, at, at // 16] = False
    assert model.choices_differ(skipped, exact, 259)[0] == compared
    # the recurrence is the plain one
    q, k, v = (jnp.asarray(np.random.default_rng(i).standard_normal(
        (5, 2, 4)), jnp.float32) for i in range(3))
    decay = jnp.asarray([0.5, 0.9])
    o, kept = model.decayed_recurrence(q, k, v, decay, 3)
    state = np.zeros((2, 4, 4))
    for t in range(5):
        state = np.asarray(decay)[:, None, None] * state \
            + np.asarray(k)[t][:, :, None] * np.asarray(v)[t][:, None, :]
        assert np.abs(np.asarray(o)[t] - np.einsum(
            "hk,hkv->hv", np.asarray(q)[t], state)).max() < 1e-5
        if t == 3:
            assert np.abs(np.asarray(kept) - state).max() < 1e-5


def _harness_says_correct(logits, tokens):
    gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_limits_reach_the_harness_as_one_comparison():
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
    assert _harness_says_correct(e, served)
    assert _harness_says_correct(model.held_to_the_limits(
        e, served, judged, control), served)
    # the mean gap as large as the control's
    assert not _harness_says_correct(model.held_to_the_limits(
        e, served, judged, judged), served)
    # a state off by more than the limit, or too many choices not the
    # reference's, though every token is the reference's own
    e0, served0, judged0 = gaps_of(0)
    assert _harness_says_correct(model.held_to_the_limits(
        e0, served0, judged0, control, model.STATE_REL_TOL * 0.9,
        model.CHOICE_DIFFER_TOL * 0.9), served0)
    assert not _harness_says_correct(model.held_to_the_limits(
        e0, served0, judged0, control, model.STATE_REL_TOL * 1.1), served0)
    assert not _harness_says_correct(model.held_to_the_limits(
        e0, served0, judged0, control, 0.0, model.CHOICE_DIFFER_TOL * 1.1),
        served0)
    # a state that a bfloat16 holds exactly
    assert _harness_says_correct(model.held_to_the_limits(
        e0, served0, judged0, control, 0.0, 0.0,
        model.STATE_COARSE_TOL * 0.9), served0)
    assert not _harness_says_correct(model.held_to_the_limits(
        e0, served0, judged0, control, 0.0, 0.0, 1.0), served0)
    # fewer judged tokens than the ratio is held over: not held yet
    assert _harness_says_correct(model.held_to_the_limits(
        e[:100], served[:100], judged[:100], judged[:100]), served[:100])


def test_every_limit_lies_between_its_readings():
    """The sound program's readings on one side of each limit, an unsound
    program's or the control's on the other (``CALIBRATION``: chip runs)."""
    cal = model.CALIBRATION
    sound = cal["program"]
    fault = {name: cal[name][0] for name in (
        "state_rounded_to_bfloat16", "choice_made_once_a_tile",
        "window_block_skipped", "state_in_the_wrong_slot")}
    assert max(r[5] for r in sound) < model.LOGIT_TIE_TOL \
        < fault["state_in_the_wrong_slot"][5]
    # the one limit a factor of 3 from the control (a ratio of 1)
    assert max(r[3] / r[4] for r in sound) < model.GAP_RATIO / 1.9
    assert all(fault[n][3] / fault[n][4] > 2 * model.GAP_RATIO for n in (
        "choice_made_once_a_tile", "window_block_skipped",
        "state_in_the_wrong_slot"))
    assert max(r[7] for r in sound) < model.STATE_REL_TOL \
        < min(cal["control"]["state_gap"])
    assert max(r[8] for r in sound) < model.CHOICE_DIFFER_TOL \
        < cal["control"]["choices_differ"]
    assert max(r[9] for r in sound if r[9] is not None) \
        < model.STATE_COARSE_TOL < fault["state_rounded_to_bfloat16"][9]
    # what the state's distance and the tokens do not see, the fourth does
    rough = fault["state_rounded_to_bfloat16"]
    assert rough[7] < model.STATE_REL_TOL \
        and rough[3] / rough[4] < model.GAP_RATIO


def test_counts_at_the_published_widths():
    """Every number written out by hand."""
    cfg = _real_cfg()
    assert (cfg.n_layers, cfg.kv_layers, cfg.lightning_layers) == (18, 4, 14)
    # keys and values 2 x 2 heads x 128 x 2 bytes, compressed keys 2 heads x
    # 128 x 4 bytes / 16: 1,088 a sparse layer, 4 layers
    assert model.kv_bytes_per_token(cfg) == 4 * 1088 == 4352
    assert cfg.kv_layers * cfg.kv_token_bytes() == 4352
    # a slot's states: 14 x 32 x 128 x 128 x 4 bytes
    assert model.lightning_state_bytes(cfg) == 14 * 2_097_152 == 29_360_128
    assert model.lightning_step_bytes(cfg, 3) == 2 * 3 * 29_360_128
    # a query past 6,208 positions reads 97 blocks a group
    assert model.blocks_read(cfg, 12_288) == 97
    assert model.blocks_read(cfg, 3_200) == 50
    # 3 rows x 194 chosen blocks (both groups) x (keys + values) x 16 KiB
    assert model.sparse_read_step_bytes(cfg, 3, 194) \
        == 4 * 3 * 194 * 2 * 16_384
    # 3 rows x 384 visible blocks (both groups) x 4 compressed keys x 512 B
    assert model.select_step_bytes(cfg, 3, 384) == 4 * 3 * 384 * 4 * 512
    # one query at position 12,288: 96 whole blocks and one position
    assert model.sparse_prefill_flops(cfg, 12_288, 1) \
        == 4 * 32 * (96 * 64 + 1) * 512
    assert model.sparse_prefill_flops(cfg, 0, 2) == 4 * 32 * 3 * 512
    # the scan: 14 layers x 32 heads x (4 x 128 x 128 + 4 x 128 x 128)
    assert model.lightning_scan_flops(cfg, 1) == 14 * 32 * 8 * 128 * 128
    from lzy_tpu.models import minicpm_sala as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(x.size for x in leaves) == 5_609_842_944
    # 14 lightning layers (5 x 4096^2 + 3 x 128 norms + the MLP and two
    # norms), 4 sparse ones, the embedding, the head, the final norm
    mlp = 3 * 4096 * 16384 + 2 * 4096
    assert 14 * (5 * 4096 ** 2 + 3 * 128 + mlp) \
        + 4 * (3 * 4096 ** 2 + 2 * 4096 * 256 + 2 * 128 + mlp) \
        + 2 * 73448 * 4096 + 4096 == 5_609_842_944
    param_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    # 3 rows of 12,288 tokens: the chosen pages, never the context
    got = model.decode_step_bytes(cfg, param_bytes, 3 * 12_288, 3)
    table = 73448 * 4096 * 2
    assert got == param_bytes - table + 4 * 3 * 194 * 2 * 16_384 \
        + 4 * 3 * 384 * 4 * 512 + 2 * 3 * 29_360_128
    # half of what the whole context's keys and values would come to
    assert 4 * 3 * 194 * 2 * 16_384 < 4 * 1024 * 3 * 12_288 / 1.9
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0) \
        == param_bytes - table


def _emit(end, rows, blocks_read, visible):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_sparse_blocks_visible_total": 4 * rows * visible,
                "lzy_sparse_blocks_read_total": 4 * rows * blocks_read,
                "lzy_sparse_rows_total": 4 * rows,
                "lzy_sparse_dense_rows_total": 0,
                "lzy_lightning_rows_total": 14 * rows}}}


def _metric(name):
    return next(x for x in _cell()["per_layer"] if x["name"] == name)


def test_the_rooflines_charge_what_the_rounds_counted():
    cfg = _real_cfg()
    obs = {"trace": {"modules": {"jit_decode_step": [0.020, 0.020],
                                 "jit_prefill_step": [0.030]},
                     "ops": {"jit_decode_step:sparse_decode_attention":
                             (0.0004, 8),
                             "jit_decode_step:sparse_select_decode":
                             (0.0006, 8),
                             "jit_decode_step:lightning_state_update":
                             (0.0010, 28),
                             "jit_prefill_step:sparse_prefill_attention":
                             (0.0040, 4),
                             "jit_prefill_step:sparse_select_prefill":
                             (0.0020, 4),
                             "jit_decode_step:fusion.1": (0.009, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "t_open": 0.0, "t_close": 51.0,
           "counters": {"lzy_sparse_blocks_read_total": 194.0,
                        "lzy_sparse_blocks_visible_total": 400.0},
           "spans": [_emit(0.3, 2, 194, 384), _emit(0.6, 4, 194, 384),
                     _emit(1.5, 9, 99, 99),              # past the span
                     {"name": "engine.prefill", "start": 0.1, "end": 0.2,
                      "attrs": {"tokens": 256, "start": 12_288}}],
           "model": {"module": model, "cfg": cfg}}
    want = 100.0 * (model.sparse_read_step_bytes(cfg, 3, 194) / 819e9) * 2 \
        / 0.0004
    got = readers.read(_metric("kernel.sparse_decode_roofline"), obs)
    assert abs(got - want) < 1e-6 and 1.0 < got < 100.0
    want = 100.0 * (model.select_step_bytes(cfg, 3, 384) / 819e9) * 2 \
        / 0.0006
    got = readers.read(_metric("kernel.sparse_select_roofline"), obs)
    assert abs(got - want) < 1e-6 and 0.0 < got < 100.0
    want = 100.0 * (model.lightning_step_bytes(cfg, 3) / 819e9) * 2 / 0.0010
    got = readers.read(_metric("kernel.lightning_update_roofline"), obs)
    assert abs(got - want) < 1e-6 and 1.0 < got < 100.0
    want = 100.0 * (model.sparse_prefill_flops(cfg, 12_288, 256) / 197e12) \
        / 0.0040
    got = readers.read(_metric("kernel.sparse_prefill_roofline"), obs)
    assert abs(got - want) < 1e-6 and 1.0 < got < 100.0
    for name, mine, whole in (
            ("step.select_share_of_decode", 0.0006, 0.040),
            ("step.sparse_read_share_of_decode", 0.0004, 0.040),
            ("step.lightning_share_of_decode", 0.0010, 0.040),
            ("step.select_share_of_prefill", 0.0020, 0.030),
            ("step.sparse_read_share_of_prefill", 0.0040, 0.030)):
        assert abs(readers.read(_metric(name), obs)
                   - 100.0 * mine / whole) < 1e-9, name
    assert abs(readers.read(_metric("kv.sparse_blocks_read_share"), obs)
               - 0.485) < 1e-9

"""The Ouro model file and its cell, rehearsed on the CPU at a tiny size: the
harness's own path end to end, the file's names, the reference against a
direct sum written again in numpy, the three limits and the broken programs
each of them catches, the counts against a hand count, the cell's files found
by name and every new metric file through its reader.

    python -m pytest benchmark/tests/test_ouro_model_file.py
"""

import argparse
import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import common, readers
from benchmark.harness import traffic as gen
from benchmark.models import REQUIRED
from benchmark.models import ouro as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs", "ouro-2.6b-serve.json")
CELL = "looped-steady"

#: the per-layer metrics this cell brought
NEW = ("kernel.loop_paged_read_roofline", "kv.loop_pool_live_share",
       "kv.loop_pool_cached_share", "loop.exit_pass_mean")
#: accepted metrics whose lists the cell joined
JOINED = ("loadgen.late_p95_s", "client.tpot_p85_s", "client.ttft_mean_s",
          "client.ttft_p85_s", "client.longest_silence_s",
          "gateway.overhead_p50_s", "engine.host_share_of_round",
          "step.decode_s_p50", "step.prefill_chunk_s_p50",
          "engine.loop_host_share", "engine.prefill_share_of_loop",
          "engine.slots_busy_share", "trace.anchor_spread_us",
          "request.queue_wait_mean_s", "request.prefill_mean_s",
          "engine.longest_leaf_s", "engine.decode_overlap_share",
          "step.decode_roofline", "kv.prefix_hit_share",
          "step.paged_read_share_of_decode",
          "step.chunk_read_share_of_prefill", "setup.program_build_s",
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


def _fresh_tally(monkeypatch):
    monkeypatch.setattr(model, "_JUDGED", [])
    monkeypatch.setattr(model, "_EXITS", {"rows": 0, "sum": 0})


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(
        trace, monkeypatch):
    _fresh_tally(monkeypatch)
    real = _cell()
    doc = _load("configs", "tiny-ouro")
    files = {"cell": {"name": "tiny-looped", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-looped"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-looped", seed=2 ** 31 + 58,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, files, require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    named = out["rehearsal"]["metric_names"]
    if trace:
        # what a CPU trace and the counters can feed; the device-trace
        # metrics need a TPU's planes
        assert {"engine.slots_busy_share", "engine.decode_overlap_share",
                "request.prefill_mean_s", "kv.loop_pool_live_share",
                "kv.loop_pool_cached_share", "loop.exit_pass_mean",
                "kv.prefix_hit_share"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)
    assert len(model._JUDGED) == 2
    assert model._EXITS["rows"] == 14 and model._EXITS["sum"] == 3 * 14


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "attention_step_bytes", "stack_bytes", "decode_step_bytes",
        "reference_logits", "exit_masses", "exit_passes", "exit_slack",
        "held_to_the_limits", "failed_limits", "post_norm_gain"))
    assert 0 < model.GAP_RATIO < 1 and 0 < model.LOGIT_TIE_TOL
    assert 0 < model.EXIT_MARGIN < 0.5


def test_the_reference_imports_nothing_from_the_program():
    import ast

    with open(model.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    assert not any("lzy_tpu" in ast.dump(n) for n in top)
    # the program's side reaches the program from inside three functions
    # (the last: the registry the decode rounds' counts go to); none is the
    # reference's, and none reaches the program's kernels
    inside = {fn.name: sorted({n.module for n in ast.walk(fn)
                               if isinstance(n, ast.ImportFrom)
                               and (n.module or "").startswith("lzy_tpu")})
              for fn in ast.walk(tree) if isinstance(fn, ast.FunctionDef)}
    inside = {k: v for k, v in inside.items() if v}
    assert inside == {"program_config": ["lzy_tpu.models.ouro"],
                      "init_params": ["lzy_tpu.models"],
                      "_loop_counters": ["lzy_tpu.utils.metrics"]}


def test_the_manifest_finds_the_cells_files_by_name():
    m = common.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert CELL in cells and cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "ouro-2.6b-serve"
    assert len(cells) == 12 and len(m["configs"]) == 11
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = _cell()
    assert files["model"] is model
    assert files["config"]["name"] == "ouro-2.6b-serve"
    assert files["config"]["engine"]["kv_pool_bytes"] == 9 * 2 ** 30
    assert [e["name"] for e in files["end_to_end"]] == ["tpot_p50_s",
                                                        "setup_s"]
    names = [x["name"] for x in files["per_layer"]]
    assert set(NEW) <= set(names) and set(JOINED) <= set(names)
    assert len(names) == len(NEW) + len(JOINED)
    assert not [n for n in names if n.startswith(("moe.", "device."))]
    for x in files["per_layer"]:
        assert x["moves"] == ("setup_s" if x["name"].startswith("setup.")
                              else "tpot_p50_s")
        assert x["reader"] and x["what"]


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the counters: None, never an error."""
    obs = {"trace": {"modules": {"jit_decode_step": [0.01]},
                     "ops": {"jit_decode_step:fusion": (0.01, 1)}},
           "trace_span": (0.0, 1.0), "t_open": 0.0, "t_close": 51.0,
           "spans": [{"name": "engine.decode.emit", "start": 0.1,
                      "end": 0.2, "attrs": {"rows": 3}}],
           "counters": {}, "samples": [],
           "model": {"module": model, "cfg": None},
           "device_kind": "TPU v5 lite"}
    new = [x for x in _cell()["per_layer"] if x["name"] in NEW]
    assert len(new) == len(NEW)
    for x in new:
        assert readers.read(x, obs) is None, x["name"]


def test_the_new_readers_read_what_the_program_records():
    cfg = _real_cfg()
    rows, steps, took = 8, 10, 0.15
    keys = 192 * rows * 600                 # 8 rows at position 599
    obs = {"trace": {"modules": {"jit_decode_step": [0.04] * steps},
                     "ops": {"jit_decode_step:paged_decode_attention_bf16":
                             (took, steps * 192),
                             "jit_decode_step:fusion": (0.25, 900)}},
           "trace_span": (0.0, 1.0), "t_open": 0.0, "t_close": 51.0,
           "spans": [{"name": "engine.decode.emit", "start": 0.1,
                      "end": 0.2, "attrs": {"rows": rows, "model_stats": {
                          "lzy_attn_full_keys_total": keys,
                          "lzy_loop_rows_total": rows,
                          "lzy_loop_exit_pass_total": 4 * rows}}}],
           "counters": {"lzy_loop_rows_total": 900.0,
                        "lzy_loop_exit_pass_total": 3600.0},
           # (t, busy, slots, blocks_total, free, queue_depth, cached)
           "samples": [(1.0, 8, 16, 384, 100, 0, 84),
                       (2.0, 9, 16, 384, 60, 0, 100)],
           "model": {"module": model, "cfg": cfg},
           "device_kind": "TPU v5 lite"}
    by_name = {x["name"]: x for x in _cell()["per_layer"]}
    roof = readers.read(by_name["kernel.loop_paged_read_roofline"], obs)
    least = keys * 8192 * steps / 819e9
    assert roof == pytest.approx(100 * least / took)
    assert 0 < roof < 100
    assert readers.read(by_name["kv.loop_pool_live_share"], obs) \
        == pytest.approx(100 * (200 + 224) / 768)
    assert readers.read(by_name["kv.loop_pool_cached_share"], obs) \
        == pytest.approx(100 * 184 / 768)
    assert readers.read(by_name["loop.exit_pass_mean"], obs) \
        == pytest.approx(4.0)
    assert readers.read(by_name["step.paged_read_share_of_decode"], obs) \
        == pytest.approx(100 * took / 0.4)


def test_the_traffic_files_multiset():
    """16 levels from 64 to 1,024 (median 256), answers 128-1,024 (median
    384), nothing over 2,048."""
    tr = _cell()["traffic"]
    cfg = _real_cfg()
    levels = sorted(set(gen.quantiles(tr["prompt_len"], 16)))
    assert len(levels) == 16 and levels[0] >= 64 and levels[-1] <= 1024
    assert (tr["prompt_len"]["median"], tr["prompt_len"]["sigma"],
            tr["output_len"]["median"], tr["output_len"]["sigma"]) \
        == (256, 0.7, 384, 0.6)
    assert (tr["prompt_len"]["min"], tr["prompt_len"]["max"]) == (64, 1024)
    assert (tr["output_len"]["min"], tr["output_len"]["max"]) == (128, 1024)
    pairs = gen.length_pairs(tr, 64)
    assert all(p + o <= tr["max_total"] == 2048 for p, o in pairs)
    assert tr["max_total"] <= cfg.max_seq_len
    ratio = tr["requests_per_s"] / tr["knee_requests_per_s"]
    assert abs(ratio - 0.8) < 0.01
    assert tr["kind"] == "open_loop" and tr["gaps"]["dist"] == "exponential"
    assert tr["block_requests"] == 8
    chk = tr["correctness"]
    assert (chk["requests"], chk["decode_tokens"], chk["pad_to"]) \
        == (4, 256, 1280)
    assert all(n + chk["decode_tokens"] <= chk["pad_to"] for n in levels)
    # every warm-up prompt at once fits the pool: 383 usable pages of 16
    assert sum(-(-(n + 2) // 16) for n in levels) < 383
    # a request stays some 20 s (a mean of 430 tokens at 40 ms and its
    # prefill)
    assert tr["ramp_s"] >= 1.5 * 20


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "Ouro-2.6B"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["max_position_embeddings"]
    # no width, no head, no layer, no pass, no row of the vocabulary is cut
    assert (doc["hidden_size"], doc["vocab_size"], doc["intermediate_size"],
            doc["num_attention_heads"], doc["num_key_value_heads"],
            doc["head_dim"], doc["num_hidden_layers"],
            doc["total_ut_steps"], doc["early_exit_threshold"]) \
        == (2048, 49152, 5632, 16, 16, 128, 48, 4, 1)
    assert doc["max_position_embeddings"] == 4096
    for key in ("assumed", "deployment", "guarantees"):
        assert doc[key]
    for key in ("sources", "sandwich_norm", "final_norm_every_pass",
                "cache_a_pass_and_layer", "exit_gate", "no_bias", "rotary",
                "residual_stream", "initial_values"):
        assert doc["assumed"][key]
    assert doc["engine"] == {
        "slots": 16, "page_size": 16, "kernel": "auto",
        "kv_pool_bytes": 9 * 2 ** 30, "max_queue": 256,
        "prefill_budget": 256}
    for key, value in (("sliding_window", 4096), ("total_ut_steps", 0),
                       ("early_exit_threshold", 0),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"factor": 2.0})):
        with pytest.raises(ValueError, match=key):
            model.program_config({**doc, key: value})


def test_counts_at_the_published_widths():
    """Against a hand count."""
    cfg = _real_cfg()
    assert model.kv_bytes_per_token(cfg) == 4 * 48 * 2 * 16 * 128 * 2 \
        == 1_572_864
    assert model.attention_step_bytes(cfg, 1000) == 8192 * 1000
    a_layer = 4 * 4_194_304 + 3 * 11_534_336 + 4 * 2048
    assert a_layer == 51_388_416
    assert model.stack_bytes(cfg) == 2 * 48 * a_layer == 4_933_287_936
    params = 48 * a_layer + 2048 + 2049 + 2 * 100_663_296
    assert params == 2_667_974_657
    # the gate is float32: 2,049 x 4 bytes
    param_bytes = 2 * (params - 2049) + 4 * 2049
    table = 49152 * 2048 * 2
    once = table + 2 * 2048 + 4 * 2049          # the head, N_f, the gate
    assert model.decode_step_bytes(cfg, param_bytes, 4500, 8) \
        == 4 * 4_933_287_936 + once + (4500 + 8) * 1_572_864
    # 27 GB a round at 4,500 resident tokens: 33 ms at 819 GB/s
    assert 26.9e9 < model.decode_step_bytes(cfg, param_bytes, 4500, 8) \
        < 27.1e9


# -- the reference against a direct sum ---------------------------------------

def _tiny(threshold=1.0):
    from lzy_tpu.models import ouro as program

    cfg = dataclasses.replace(
        model.program_config(_load("configs", "tiny-ouro")),
        early_exit_threshold=threshold)
    params = program.init_params(cfg, jax.random.PRNGKey(5))
    gain = model.post_norm_gain(cfg)

    def fix(path, leaf):
        name = path[-1].key
        if name == "kernel":
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name in ("lm_head", "exit_gate"):
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        if name == "scale" and path[-2].key.endswith("post_norm"):
            return jnp.full_like(leaf, gain)
        return leaf

    return cfg, jax.tree_util.tree_map_with_path(fix, params)


def _direct(params, toks, cfg):
    """The equations once more, numpy, float64, a position at a time over
    whole matrices: ``(logits [T, V], t* [T])``."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    st, t = p["stack"], len(toks)
    h, d = cfg.n_heads, cfg.head_dim

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True)
                           + cfg.norm_eps) * w["scale"]

    def rope(x):
        inv = cfg.rope_theta ** -(np.arange(0, d, 2) / d)
        ang = np.arange(t)[:, None, None] * inv
        a, b = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               a * np.sin(ang) + b * np.cos(ang)], -1)

    x = p["embed_tokens"][np.asarray(toks)]
    states, lams = [], []
    for _ in range(cfg.total_ut_steps):
        for i in range(cfg.n_layers):
            w = st[f"layer_{i}"]
            u = norm(x, w["attn_norm"])
            q, k, v = (
                (u @ w["attn"][n]["kernel"]).reshape(t, h, d)
                for n in ("q_proj", "k_proj", "v_proj"))
            s = np.einsum("qhd,shd->hqs", rope(q), rope(k)) / np.sqrt(d)
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            pr = np.exp(s - s.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            y = np.einsum("hqs,shd->qhd", pr, v).reshape(t, h * d) \
                @ w["attn"]["o_proj"]["kernel"]
            a = x + norm(y, w["attn_post_norm"])
            n = norm(a, w["mlp_norm"])
            g = n @ w["gate_proj"]["kernel"]
            y = (g / (1 + np.exp(-g)) * (n @ w["up_proj"]["kernel"])) \
                @ w["down_proj"]["kernel"]
            x = a + norm(y, w["mlp_post_norm"])
        x = norm(x, st["final_norm"])
        states.append(x)
        lams.append(1 / (1 + np.exp(-(x @ st["exit_gate"]
                                      + st["exit_gate_bias"]))))
    mass, survive, t_star = np.zeros(t), np.ones(t), np.zeros(t, int)
    for i, lam in enumerate(lams):
        last = i == len(lams) - 1
        mass = mass + (survive if last else lam * survive)
        survive = survive * (1 - lam)
        take = (t_star == 0) & ((mass >= cfg.early_exit_threshold) | last)
        t_star[take] = i + 1
    chosen = np.stack(states)[t_star - 1, np.arange(t)]
    return chosen @ p["lm_head"].T, t_star


@pytest.mark.parametrize("threshold", [1.0, 0.6])
def test_the_reference_is_the_direct_sum(threshold):
    cfg, params = _tiny(threshold)
    toks = np.random.default_rng(7).integers(1, cfg.vocab_size, 60)
    want, t_want = _direct(params, toks, cfg)
    got, t_star, sure = model.reference(params, jnp.asarray([toks]),
                                        np.arange(60), cfg)
    assert sure.sum() >= 50 and (t_star[sure] == t_want[sure]).all()
    assert np.abs(np.asarray(got) - want)[sure].max() < 2e-4
    assert len(set(t_want.tolist())) >= (1 if threshold == 1.0 else 2)
    # blocks of queries: a sequence longer than a block gives the same
    long = np.random.default_rng(8).integers(1, cfg.vocab_size, 200)
    whole = np.asarray(model.reference_logits(
        params, jnp.asarray([long]), np.arange(200), cfg))
    old = model._QUERY_BLOCK
    try:
        model._QUERY_BLOCK = 64
        model._layer.clear_cache()
        blocked = np.asarray(model.reference_logits(
            params, jnp.asarray([long]), np.arange(200), cfg))
    finally:
        model._QUERY_BLOCK = old
        model._layer.clear_cache()
    assert np.abs(whole - blocked).max() < 2e-4


def test_the_exit_rule_by_hand():
    # gates of three passes at four positions
    gates = np.log(np.asarray([[0.7, 0.2, 0.5, 0.999],
                               [0.5, 0.5, 0.3, 0.5],
                               [0.1, 0.1, 0.9, 0.1]])
                   / (1 - np.asarray([[0.7, 0.2, 0.5, 0.999],
                                      [0.5, 0.5, 0.3, 0.5],
                                      [0.1, 0.1, 0.9, 0.1]])))
    masses = np.asarray(model.exit_masses(gates))
    assert np.allclose(masses[0], [0.7, 0.2, 0.5, 0.999], atol=1e-6)
    assert np.allclose(masses[1], [0.85, 0.6, 0.65, 0.9995], atol=1e-6)
    assert np.allclose(masses[2], 1.0, atol=1e-6)
    t_star, sure = model.exit_passes(masses, 0.6, 0.02)
    assert t_star.tolist() == [1, 2, 2, 1]
    assert sure.tolist() == [True, False, True, True]   # 0.6 is the edge
    t_star, sure = model.exit_passes(masses, 1.0, 0.02)
    assert t_star.tolist() == [3, 3, 3, 3]
    assert sure.tolist() == [True, True, True, False]   # a gate saturated
    # the program's sum against the reference's: bounds of the rows that
    # were not judged
    assert model.exit_slack(20, 80, 16, 64, 4) == 0      # all read pass 4
    assert model.exit_slack(20, 79, 16, 64, 4) == 0      # a spare row's 3
    assert model.exit_slack(20, 60, 16, 64, 4) == 8      # pass 3 throughout
    assert model.exit_slack(20, 81, 16, 64, 4) == 1
    assert model.exit_slack(10, 40, 16, 64, 4) == 6      # rows uncounted


# -- the limits and the broken programs -----------------------------------------

def _served(cfg, params, reference_cfg=None):
    """Two requests through an engine of the (possibly broken) program:
    what the model file reads of them against the sound reference's
    ``reference_cfg``: the program's gaps, the control's, the exit sum's
    distance past its bound."""
    from lzy_tpu.serving import PagedInferenceEngine

    reference_cfg = reference_cfg or cfg
    rows0, sum0 = model._loop_counters()
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  kernel="lax", prefill_budget=32)
    mine, ctrl, judged, judged_sum = [], [], 0, 0
    try:
        for seed, n in ((1, 43), (2, 75)):
            prompt = np.random.default_rng(seed).integers(
                1, cfg.vocab_size, n).tolist()
            req = engine.submit(prompt, max_new_tokens=96, greedy=True)
            for _ in range(2000):
                if not engine.step():
                    break
            assert req.done and req.error is None
            full = jnp.asarray([prompt + list(req.tokens)])
            rows = np.arange(n - 1, full.shape[1] - 1)
            exact, t_star, sure = model.reference(params, full, rows,
                                                  reference_cfg)
            control = np.asarray(model.reference_logits(
                params, full, rows, reference_cfg,
                jnp.bfloat16)).argmax(axis=-1)
            mine.append(model.gaps(exact, req.tokens))
            ctrl.append(model.gaps(exact, control))
            judged += int(sure[1:].sum())
            judged_sum += int(t_star[1:][sure[1:]].sum())
    finally:
        engine.close()
    rows1, sum1 = model._loop_counters()
    slack = model.exit_slack(rows1 - rows0, sum1 - sum0, judged, judged_sum,
                             reference_cfg.total_ut_steps)
    return np.concatenate(mine), np.concatenate(ctrl), slack


def _rounded(stream):
    """``y`` as ``stream`` holds it, by ``reduce_precision``: a pair of
    converts is one the compiler may drop (xla_allow_excess_precision)."""
    if stream is None:
        return lambda y: y
    info = jnp.finfo(stream)
    return lambda y: jax.lax.reduce_precision(y, info.nexp, info.nmant)


def _broken_pass(feed_unnormed=False, head_reads=None, stream=None):
    """``OuroPass`` and ``OuroLayer`` written again with a switch for each
    part a break changes: what enters the next pass, the pass the head
    reads, and the type every norm's result, every sublayer's result and
    the stream are kept in (None: as the program keeps them)."""
    from lzy_tpu.models import ouro as program

    kept = _rounded(stream)

    class Layer(nn.Module):
        cfg: program.OuroConfig

        @nn.compact
        def __call__(self, x, step, at):
            cfg = self.cfg

            def norm(name, y):
                return kept(program.RMSNorm(cfg.norm_eps, cfg.param_dtype,
                                            name=name)(y))

            y = kept(program.OuroAttention(cfg, name="attn")(
                norm("attn_norm", x).astype(cfg.dtype), step, at))
            a = kept(x + norm("attn_post_norm", y))
            n = norm("mlp_norm", a).astype(cfg.dtype)
            hid = jax.nn.silu(program.dense(cfg.d_ff, "gate_proj", cfg)(n)) \
                * program.dense(cfg.d_ff, "up_proj", cfg)(n)
            y = kept(program.dense(cfg.d_model, "down_proj", cfg,
                                   jnp.float32)(hid))
            return kept(a + norm("mlp_post_norm", y))

    class BrokenPass(nn.Module):
        cfg: program.OuroConfig

        @nn.compact
        def __call__(self, carry, step, at):
            cfg = self.cfg
            x, chosen, survive, mass, exit_pass = carry
            x = kept(x)
            for i in range(cfg.n_layers):
                x = Layer(cfg, name=f"layer_{i}")(x, step, at)
            hidden = kept(program.RMSNorm(cfg.norm_eps, cfg.param_dtype,
                                          name="final_norm")(x))
            w = self.param("exit_gate", program.normal(), (cfg.d_model,),
                           jnp.float32)
            bias = self.param("exit_gate_bias", program.normal(), (),
                              jnp.float32)
            lam = jax.nn.sigmoid(jnp.einsum(
                "bte,e->bt", hidden, w,
                precision=jax.lax.Precision.HIGHEST) + bias)
            last = step == cfg.total_ut_steps - 1
            mass = mass + jnp.where(last, survive, lam * survive)
            take = (exit_pass == 0) & (
                (mass >= cfg.early_exit_threshold) | last)
            if head_reads is not None:
                take = (exit_pass == 0) & (step == head_reads - 1)
            chosen = jnp.where(take[..., None], hidden, chosen)
            exit_pass = jnp.where(take, step + 1, exit_pass)
            survive = survive * (1.0 - lam)
            return (x if feed_unnormed else hidden, chosen, survive, mass,
                    exit_pass), None

    return BrokenPass


def _teacher_forced(cfg, params, reference_cfg, sequences=12, length=160):
    """The same readings over many more positions than two served requests
    give: the (possibly broken) program's greedy choice at every position of
    seeded sequences, beside the control's."""
    from lzy_tpu.models import ouro as program

    mine, ctrl = [], []
    rows = np.arange(length)
    for seed in range(sequences):
        toks = jnp.asarray([np.random.default_rng(seed).integers(
            1, cfg.vocab_size, length)])
        exact = model.reference_logits(params, toks, rows, reference_cfg)
        control = np.asarray(model.reference_logits(
            params, toks, rows, reference_cfg, jnp.bfloat16)).argmax(-1)
        got = np.asarray(program.Ouro(cfg).apply(
            {"params": params}, toks)[0]).argmax(-1)
        mine.append(model.gaps(exact, got))
        ctrl.append(model.gaps(exact, control))
    return np.concatenate(mine), np.concatenate(ctrl), 0.0


#: the program broken one way each, and the limits that have to see it (at
#: the tiny size ``T`` is 3: "three passes for four" is two for three, and
#: "the head reading pass 3" of 4 is pass 2 of 3)
BROKEN = {
    "sound": (),
    "a_pass_short": ("GAP_RATIO", "EXIT_MARGIN"),
    "pass_0s_keys": ("GAP_RATIO",),
    "next_pass_fed_the_unnormed_state": ("GAP_RATIO",),
    "no_attn_post_norm": ("GAP_RATIO",),
    "no_mlp_post_norm": ("GAP_RATIO",),
    "head_reads_the_pass_before": ("GAP_RATIO", "EXIT_MARGIN"),
    "sixteen_bit_activations": ("GAP_RATIO",),
}


@pytest.mark.parametrize("fault", list(BROKEN))
def test_each_broken_program_fails_a_limit(fault, monkeypatch, capsys):
    import importlib

    from lzy_tpu.models import ouro as program

    pa = importlib.import_module("lzy_tpu.ops.paged_attention")
    monkeypatch.setattr(model, "GAP_RATIO_MIN_TOKENS", 32)
    cfg, params = _tiny()
    broken, passes = cfg, cfg.total_ut_steps
    if fault == "a_pass_short":
        broken = dataclasses.replace(cfg, total_ut_steps=passes - 1)
    elif fault == "pass_0s_keys":
        real = pa.paged_attention
        monkeypatch.setattr(
            pa, "paged_attention",
            lambda q, k, v, table, pos, **kw: real(
                q, k, v, table // passes * passes, pos, **kw))
    elif fault == "next_pass_fed_the_unnormed_state":
        monkeypatch.setattr(program, "OuroPass",
                            _broken_pass(feed_unnormed=True))
    elif fault in ("no_attn_post_norm", "no_mlp_post_norm"):
        real_norm, gone = program.RMSNorm, fault[3:]
        monkeypatch.setattr(
            program, "RMSNorm",
            lambda eps, dtype, name=None: (lambda y: y) if name == gone
            else real_norm(eps, dtype, name=name))
    elif fault == "head_reads_the_pass_before":
        monkeypatch.setattr(program, "OuroPass",
                            _broken_pass(head_reads=passes - 1))
    elif fault == "sixteen_bit_activations":
        # what the products take, every norm's and sublayer's result and
        # the stream: the precision under the one the cell states (there the
        # products alone take bfloat16)
        broken = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        monkeypatch.setattr(program, "OuroPass",
                            _broken_pass(stream=jnp.bfloat16))
    # two served requests judge 190 tokens, of which a rounding flips two or
    # three: the precision break is read over 1,920 positions instead
    mine, ctrl, slack = _teacher_forced(broken, params, cfg) \
        if fault == "sixteen_bit_activations" \
        else _served(broken, params, cfg)
    failed = model.failed_limits(mine, ctrl, slack)
    with capsys.disabled():
        print(f"\nouro {fault}: failed {failed}; mean gap "
              f"{mine.mean():.5f} (control {ctrl.mean():.5f}), worst "
              f"{mine.max():.4f}, exit slack {slack:.0f}")
    assert set(BROKEN[fault]) <= set(failed)
    assert bool(failed) == (fault != "sound")
    # and the harness's one comparison sees it
    exact = np.zeros((len(mine), 8), np.float32)
    held = model.held_to_the_limits(exact, np.zeros(len(mine), int), mine,
                                    ctrl, slack)
    worst = float((held.max(-1) - held[:, 0]).max())
    assert (worst > model.LOGIT_TIE_TOL) == (
        bool(set(failed) - {"LOGIT_TIE_TOL"}))

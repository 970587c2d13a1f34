"""The Moonlight-16B-A3B (``deepseek_v3``) model file: the program (absorbed
latent attention through the paged latent cache) against the plain reference
(the published non-absorbed form, no cache) through the harness at a tiny
size (one chip's share: experts 4-7 of 16), the bfloat16 control and the two
limits as the harness's one comparison sees them, the byte counts against
numbers counted by hand at the published widths, the readers of the new
metrics, the configuration file against the catalog, and the manifest with
its six cells."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import common, readers
from benchmark.models import REQUIRED
from benchmark.models import deepseek_v3 as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "moonlight-16b-a3b-serve-ep4.json")

#: the per-layer metrics this cell brought
NEW = ("step.mla_share_of_decode", "kernel.mla_decode_roofline",
       "step.mla_share_of_prefill")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = common.cell_files(common.load_manifest(), "longdoc-steady")
    doc = _load("configs", "tiny-moonlight")
    files = {"cell": {"name": "tiny-longdoc", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-longdoc"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-longdoc", seed=2 ** 31 + 36,
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
                "engine.prefill_share_of_loop"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "experts_step_bytes", "latent_step_bytes", "decode_step_bytes"))
    assert 0 < model.GAP_RATIO < 1 < model.LOGIT_TIE_TOL


def test_the_manifest_has_six_cells_and_the_new_one_finds_its_files():
    m = common.load_manifest()
    assert [w["name"] for w in m["workloads"]][-1] == "longdoc-steady"
    assert len(m["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = common.cell_files(m, "longdoc-steady")
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    names = {x["name"] for x in files["per_layer"]}
    assert set(NEW) <= names
    # what the cell leaves to others: the uniform expectation's roofline,
    # the delta rule's metrics, the closed loop's
    assert not names & {"step.decode_roofline", "step.kda_share_of_decode",
                        "kernel.kda_update_roofline",
                        "kernel.decode_step_roofline"}
    for x in files["per_layer"]:
        assert callable(readers.find(x))
        assert x["moves"] == "tpot_p50_s"
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == ["longdoc-steady"] for n in NEW)
    # every metric the other open-loop expert cell is on and that applies
    assert names - set(NEW) == {
        x["name"] for x in m["per_layer"]
        if "doc-steady" in x.get("workloads", ())} - {
            "step.kda_share_of_decode", "kernel.kda_update_roofline"}


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernels or the counts: None, never an error."""
    files = common.cell_files(common.load_manifest(), "longdoc-steady")
    obs = {"trace": {"modules": {"jit_decode_step": [0.01],
                                 "jit_prefill_step": [0.02]},
                     "ops": {"jit_decode_step:fusion": (0.01, 1),
                             "jit_prefill_step:fusion": (0.02, 1)}},
           "trace_span": (0.0, 1.0), "spans": [], "counters": {},
           "model": {"module": model, "cfg": None}, "device_kind":
           "TPU v5 lite"}
    new = [x for x in files["per_layer"] if x["name"] in NEW]
    assert len(new) == 3
    for x in new:
        assert readers.read(x, obs) is None


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"Moonlight-16B-A3B"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["n_routed_experts", "vocab_size"]
    assert doc["num_hidden_layers"] == 27            # no layer cut away
    assert doc["max_position_embeddings"] == 8192    # as published
    for key in ("assumed", "deployment", "guarantees"):
        assert doc[key]


def _unit_scale(params):
    """Variance-preserving weights at the tiny widths (as
    tests/test_zz_deepseek_v3.py): normal(0.02) hides errors there."""
    big = ("kernel", "experts_gate", "experts_up", "experts_down", "router")

    def fix(p, leaf):
        if p[-1].key in big:
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if p[-1].key == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    cfg = model.program_config(_load("configs", "tiny-moonlight"))
    return cfg, _unit_scale(model.init_params(cfg, 3))


def test_the_reference_against_the_program_and_the_control_apart():
    """The uncached program (absorbed) and the reference (expanded) agree to
    the order of their sums; the bfloat16 control does not."""
    from lzy_tpu.models import deepseek_v3 as program

    cfg, params = _tiny()
    assert cfg.experts_held == (4, 8) and cfg.n_routed_experts == 16
    assert params["layer_1_moe"]["experts_gate"].shape[0] == 4
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 48).tolist()])
    rows = jnp.arange(48)
    got = np.asarray(program.DeepseekV3(cfg).apply({"params": params},
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
    """A run whose tokens sit as far below the reference's best as its
    bfloat16 control's do comes out not correct, though no token is over
    ``LOGIT_TIE_TOL``; one that sits at two thirds of the control's mean gap
    (the program's reading) is correct."""
    rng = np.random.default_rng(0)
    n = model.GAP_RATIO_MIN_TOKENS
    exact = rng.normal(size=(n, 50)).astype(np.float32)
    best = exact.argmax(axis=-1)
    served = best.copy()
    served[:n // 5] = (best[:n // 5] + 1) % 50
    exact[np.arange(n // 5), served[:n // 5]] = \
        exact[np.arange(n // 5), best[:n // 5]] - 0.3
    judged = model.gaps(exact, served)                   # mean 0.06
    assert _harness_says_correct(exact, served)          # one limit alone
    assert _harness_says_correct(model.held_to_both_limits(
        exact, served, judged, judged * 1.5), served)
    assert not _harness_says_correct(model.held_to_both_limits(
        exact, served, judged, judged), served)
    # fewer judged tokens than the limit is held over: not held yet
    assert _harness_says_correct(model.held_to_both_limits(
        exact[:100], served[:100], judged[:100], judged[:100]),
        served[:100])


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


def test_init_params_is_the_programs_initialiser_a_layer_at_a_time():
    """The dense layer, the first expert layer, the embedding, the head and
    the norms are the program's initialiser over two layers under the first
    key; expert layer i + 2 is the same initialiser's expert layer under
    key i + 1."""
    import dataclasses

    from lzy_tpu.models import deepseek_v3 as program

    cfg = model.program_config(_load("configs", "tiny-moonlight"))
    assert cfg.n_layers == 3
    mine = model.init_params(cfg, 7)
    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), mine) \
        == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    short = dataclasses.replace(cfg, n_layers=2)
    for key, theirs, ours in ((keys[0], "layer_1", "layer_1"),
                              (keys[1], "layer_1", "layer_2")):
        plain = program.init_params(short, key)
        for suffix in ("", "_norm", "_ffn_norm", "_moe"):
            for a, b in zip(jax.tree_util.tree_leaves(plain[theirs + suffix]),
                            jax.tree_util.tree_leaves(mine[ours + suffix])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-8)
    first = program.init_params(short, keys[0])
    for name in ("embed_tokens", "lm_head", "layer_0_mlp"):
        for a, b in zip(jax.tree_util.tree_leaves(first[name]),
                        jax.tree_util.tree_leaves(mine[name])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)
    # two expert layers are not the same draw
    assert not np.allclose(np.asarray(mine["layer_1_moe"]["router"]),
                           np.asarray(mine["layer_2_moe"]["router"]))


def test_what_the_program_cannot_honour_is_refused():
    doc = _load("configs", "tiny-moonlight")
    model.program_config(doc)
    for key, value in (("q_lora_rank", 24), ("n_group", 2),
                       ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            model.program_config({**doc, key: value})


def test_byte_counts_at_the_published_widths():
    cfg = model.program_config(json.load(open(REAL)))
    assert (cfg.n_layers, cfg.expert_layers, cfg.n_held) == (27, 26, 16)
    assert (cfg.vocab_size, cfg.max_seq_len) == (40960, 8192)
    assert model.kv_bytes_per_token(cfg) == 31_104      # 27 x 576 x 2 B
    assert cfg.kv_layers * cfg.kv_token_bytes() == 34_560   # in 640 lanes
    assert model.expert_bytes(cfg) == 17_301_504        # 3 x 2048 x 1408 x 2 B
    assert model.routed_param_bytes(cfg) == 26 * 16 * 17_301_504
    # a quarter of the held experts: 4 a layer, 26 layers
    experts = 26 * 4 * 17_301_504
    assert model.experts_step_bytes(cfg, 10, 0.25) == experts
    with pytest.raises(TypeError):                       # never an expectation
        model.experts_step_bytes(cfg, 10)
    # ten rows of 4,000 cached tokens each: 1.24 GB a round
    assert model.latent_step_bytes(cfg, 10, 4000.0) == 10 * 4000 * 31_104
    # the program's parameter bytes at these widths (counted from shapes):
    # bfloat16: 27 attention layers of 13,763,072 (q 6,291,456, kv_a
    # 1,179,648, its norm 512, kv_b 2,097,152, o 4,194,304), 54 norms of
    # 2048, the dense MLP 69,206,016, 26 x (16 x 8,650,752 routed +
    # 17,301,504 shared), the final norm, embedding and head 167,772,160;
    # float32: 131,136 a router
    from lzy_tpu.models import deepseek_v3 as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes))
    bf16 = 27 * 13_763_072 + 55 * 2048 + 69_206_016 \
        + 26 * (16 * 8_650_752 + 17_301_504) + 167_772_160
    assert param_bytes == 2 * bf16 + 4 * 26 * 131_136
    # the ISSUE's reckoning, 4.661 B parameters and 9.32 GB, within 1%
    assert abs(bf16 / 4.661e9 - 1) < 0.01
    assert abs(param_bytes / 9.32e9 - 1) < 0.01
    outside = param_bytes - 26 * 16 * 17_301_504 - 40960 * 2048 * 2
    want = outside + experts + 31_104 * 40_000
    got = model.decode_step_bytes(cfg, param_bytes, 40_000, 10, 0.25)
    assert abs(got - want) < 1.0
    assert 4.9e9 < got < 5.1e9                           # 6.1 ms at 819 GB/s
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0, 0.0) == outside
    with pytest.raises(TypeError):
        model.decode_step_bytes(cfg, param_bytes, 40_000, 10)


def _emit(end, rows, context, touched=0):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_mla_context_tokens_total": 27 * context,
                "lzy_mla_rows_total": 27 * rows,
                "lzy_moe_experts_touched_total": touched,
                "lzy_moe_experts_held_total": 26 * 16}}}


def test_the_latent_roofline_charges_the_context_the_rounds_counted():
    """Two traced rounds of 4 and 6 rows that read 12,000 and 28,000 cached
    tokens a layer: 5 rows a round at a mean context of 4,000."""
    cfg = model.program_config(json.load(open(REAL)))
    metric = next(x for x in common.cell_files(
        common.load_manifest(), "longdoc-steady")["per_layer"]
        if x["name"] == "kernel.mla_decode_roofline")
    obs = {"trace": {"modules": {"jit_decode_step": [0.010, 0.012]},
                     "ops": {"jit_decode_step:mla_paged_decode": (0.003, 54),
                             "jit_decode_step:fusion.1": (0.019, 90)}},
           "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
           "spans": [_emit(0.3, 4, 12_000), _emit(0.6, 6, 28_000),
                     _emit(1.5, 9, 99_000)],           # past the span
           "model": {"module": model, "cfg": cfg}}
    need = model.latent_step_bytes(cfg, 5, 4000.0)
    want = 100.0 * (need / 819e9) * 2 / 0.003
    got = readers.read(metric, obs)
    assert abs(got - want) < 1e-6 and 20.0 < got < 100.0
    share = next(x for x in common.cell_files(
        common.load_manifest(), "longdoc-steady")["per_layer"]
        if x["name"] == "step.mla_share_of_decode")
    assert abs(readers.read(share, obs) - 100.0 * 0.003 / 0.022) < 1e-9

"""The Motif-3-Beta (``motif``) model file: the program (four mixed residual
streams, absorbed differential latent attention over two pools, PolyNorm
experts) against the plain reference (the published non-absorbed form, no
cache) through the harness at a tiny size (one chip's share: experts 4-7 of
16), the reference against a direct sum, the three limits as the harness's one
comparison sees them, every counting function against counts by hand at the
published widths, the readers of the new metrics, the configuration file
against the catalog, and the manifest with its cell."""

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
from benchmark.models import motif as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "motif-3-beta-serve-l5-ep8.json")

#: the per-layer metrics this cell brought
NEW = ("step.mhc_share_of_decode", "step.mhc_share_of_prefill",
       "step.polynorm_experts_share_of_decode",
       "step.polynorm_experts_share_of_prefill",
       "step.diff_read_share_of_decode", "attn.noise_weight_mean",
       "kv.hyper_window_pages_released_per_s", "kv.hyper_pool_live_share",
       "kernel.polynorm_experts_roofline", "kernel.mhc_decode_roofline",
       "kernel.mhc_prefill_roofline")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = common.cell_files(common.load_manifest(), "hyper-steady")
    doc = _load("configs", "tiny-motif")
    files = {"cell": {"name": "tiny-hyper", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-hyper"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-hyper", seed=2 ** 31 + 65,
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
                "attn.noise_weight_mean", "kv.hyper_pool_live_share",
                "kv.hyper_window_pages_released_per_s",
                "engine.prefill_share_of_loop"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "kv_bytes_per_token", "latent_step_bytes", "expert_bytes",
        "experts_step_bytes", "routed_param_bytes", "mhc_step_bytes",
        "mhc_prefill_bytes", "decode_step_bytes"))
    assert 0 < model.DIFFER_RATIO < 1 < model.GAP_RATIO < model.LOGIT_TIE_TOL
    # every reading of the program under its limits; the all-bfloat16
    # control is 1 on the first by construction, over it
    cal = model.CALIBRATION
    ratios = [a / b for a, b in zip(cal["differ"], cal["control_differ"])]
    assert len(ratios) == 14 and max(ratios) < model.DIFFER_RATIO - 0.25
    assert max(cal["gap_ratio"]) < model.GAP_RATIO / 2
    assert max(cal["worst_gap"] + cal["control_worst_gap"]) \
        < model.LOGIT_TIE_TOL / 4
    # the tie limit from the logits' own deviation: about 3.5 of them
    assert 3.0 < model.LOGIT_TIE_TOL / cal["logit_std"] < 4.0


def test_the_manifest_has_the_cell_and_it_finds_its_files():
    m = common.load_manifest()
    assert [w["name"] for w in m["workloads"]][-1] == "hyper-steady"
    assert len(m["workloads"]) == 14 and len(m["configs"]) == 13
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = common.cell_files(m, "hyper-steady")
    assert files["cell"]["chips"] == 1
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    names = {x["name"] for x in files["per_layer"]}
    assert set(NEW) <= names
    # the full layer's read is ops/mla.py's kernel as it is
    assert {"step.mla_share_of_decode", "step.mla_share_of_prefill",
            "kernel.mla_decode_roofline",
            "step.decode_counted_roofline"} <= names
    # what the cell leaves to others: another kernel's and model's texts
    assert not names & {"step.experts_share_of_decode",
                        "step.experts_share_of_prefill",
                        "kernel.grouped_experts_roofline",
                        "kv.window_keys_share",
                        "kv.window_pages_released_per_s",
                        "kv.window_latent_pages_released_per_s",
                        "step.decode_roofline"}
    for x in files["per_layer"]:
        assert callable(readers.find(x))
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == ["hyper-steady"] for n in NEW)
    assert all(by_name[n]["moves"] == "tpot_p50_s" for n in NEW)
    texts = ([c[k] for c in m["configs"] for k in ("why", "source")]
             + [w["why"] for w in m["workloads"]]
             + [x["layer"] for x in m["per_layer"]] + m["command"])
    assert all(1 <= len(t) <= 200 and t.isprintable() for t in texts)


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernels or the counts: None, never an error."""
    files = common.cell_files(common.load_manifest(), "hyper-steady")
    obs = {"trace": {"modules": {"jit_decode_step": [0.01],
                                 "jit_prefill_step": [0.02]},
                     "ops": {"jit_decode_step:fusion": (0.01, 1),
                             "jit_prefill_step:fusion": (0.02, 1)}},
           "trace_span": (0.0, 1.0), "spans": [], "counters": {},
           "model": {"module": model, "cfg": None}, "device_kind":
           "TPU v5 lite"}
    new = [x for x in files["per_layer"] if x["name"] in NEW]
    assert len(new) == len(NEW)
    for x in new:
        assert readers.read(x, obs) is None


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"Motif-3-Beta"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == [
        "num_hidden_layers", "n_dense_first_layers", "num_experts",
        "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"]
    assert (doc["router_width"], doc["experts_held_from"]) == (384, 0)
    for key in ("assumed", "deployment", "guarantees"):
        assert doc[key]
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == doc["name"])
    assert entry["reduced"] == doc["reduced"]
    assert entry["source"] == doc["source"]


# -- the reference ------------------------------------------------------------

def _unit_scale(params):
    def fix(path, leaf):
        if path[-1].key in ("kernel", "experts_gate", "experts_up",
                            "experts_down", "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if path[-1].key == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        if path[-1].key == "embed_tokens":
            return leaf / 0.02
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    from lzy_tpu.models import motif as program

    cfg = model.program_config(_load("configs", "tiny-motif"))
    return cfg, _unit_scale(program.init_params(cfg, jax.random.PRNGKey(3)))


def test_the_reference_against_the_program_and_the_control_apart():
    from lzy_tpu.models import motif as program

    cfg, params = _tiny()
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 64).tolist()])
    want = np.asarray(model.reference_logits(params, toks, jnp.arange(64),
                                             cfg))
    got = np.asarray(program.Motif(cfg).apply({"params": params}, toks)[0])
    assert np.abs(got - want).max() < 2e-4
    control = np.asarray(model.reference_logits(
        params, toks, jnp.arange(64), cfg, jnp.bfloat16))
    assert np.abs(control - want).max() > 4e-3


def test_the_references_attention_is_a_direct_sum():
    """One window layer's attention at one query, by loops over heads and
    positions in float64: the expanded keys and values of the query's
    group, a softmax a head over the window, the signal head's output less
    ``lam`` times its group's noise head's, the gate a channel."""
    cfg, params = _tiny()
    w = params["layer_1"]
    t, q_at = 16, 11
    u = np.random.default_rng(2).normal(size=(t, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model._attention(
            jnp.asarray(u, jnp.float32), w, cfg, jnp.dtype(jnp.float32),
            windowed=True))[q_at]
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    hs, g = cfg.n_heads - cfg.n_noise_heads, cfg.n_noise_heads
    per = hs // g

    def norm(x, scale):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.norm_eps) \
            * f64(scale)

    def rope(x, pos):
        d = x.shape[-1]
        ang = pos * cfg.swa_rope_theta ** (-np.arange(0, d, 2) / d)
        a, b = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               a * np.sin(ang) + b * np.cos(ang)], -1)

    c_q = norm(u @ f64(w["q_a_proj"]["kernel"]), w["q_a_norm"]["scale"])
    q = (c_q @ f64(w["q_b_proj"]["kernel"])).reshape(t, cfg.n_heads, dn + dr)
    kva = u @ f64(w["kv_a_proj"]["kernel"])
    c = norm(kva[:, :r], w["kv_a_norm"]["scale"])
    kv = np.einsum("tr,rgx->tgx", c, f64(w["kv_b_proj"]))
    lam = 1 / (1 + np.exp(-(u @ f64(w["lambda_proj"]["kernel"]))))
    gate = 1 / (1 + np.exp(-(u @ f64(w["gate_proj"]["kernel"]))))
    seen = [s for s in range(t) if q_at - cfg.window < s <= q_at]

    def read(head, group):
        qn, qr = q[q_at, head, :dn], rope(q[q_at, head, dn:], q_at)
        s = np.asarray([
            (qn @ kv[p, group, :dn] + qr @ rope(kva[p, r:], p))
            / np.sqrt(dn + dr) for p in seen])
        pr = np.exp(s - s.max())
        pr /= pr.sum()
        return sum(pr[i] * kv[p, group, dn:] for i, p in enumerate(seen))

    out = np.zeros((hs, dv))
    for head in range(hs):
        group = head // per
        out[head] = read(head, group) \
            - lam[q_at, head] * read(hs + group, group)
    want = (out.reshape(-1) * gate[q_at]) @ f64(w["o_proj"]["kernel"])
    assert np.abs(got - want).max() < 1e-4


def test_the_references_mix_is_twenty_explicit_sweeps():
    raw = np.random.default_rng(4).normal(size=(6, 4, 4))
    m = np.exp(raw)
    for _ in range(20):
        m /= m.sum(-1, keepdims=True)
        m /= m.sum(-2, keepdims=True)
    got = np.asarray(model.sinkhorn(jnp.asarray(raw, jnp.float32), 20))
    assert np.abs(got - m).max() < 1e-6
    assert np.abs(got.sum(-2) - 1).max() < 1e-6
    assert np.abs(got.sum(-1) - 1).max() < 1e-3


def _harness_says_correct(logits, tokens):
    gap = logits.max(-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_other_limits_reach_the_harness_as_one_comparison():
    rng = np.random.default_rng(0)
    exact = (1.3 * rng.normal(size=(600, 2000))).astype(np.float32)
    best = exact.argmax(-1)

    def gaps(differing, size):
        """600 gaps of which the first ``differing`` are ``size``."""
        g = np.zeros(600)
        g[:differing] = size
        return g

    def correct(mine, control, tokens=best, n=600):
        return _harness_says_correct(model.held_to_the_limits(
            exact[:n], tokens[:n], mine[:n], control[:n]), tokens[:n])

    # a sound run: 20 tokens differ against the control's 40
    assert correct(gaps(20, 0.05), gaps(40, 0.1))
    # as many as the control's, or over 0.9 of them: not correct, though
    # every token handed in is the reference's best
    assert not correct(gaps(40, 0.05), gaps(40, 0.1))
    assert not correct(gaps(37, 0.05), gaps(40, 0.1))
    assert correct(gaps(36, 0.05), gaps(40, 0.1))
    held = model.held_to_the_limits(exact, best, gaps(40, 0.05),
                                    gaps(40, 0.1))
    gap = held.max(-1) - held[np.arange(600), best]
    assert (gap > model.LOGIT_TIE_TOL).all() \
        and (gap <= 2 * model.LOGIT_TIE_TOL).all()
    # few tokens differ, but by much: the mean gap's guard
    assert not correct(gaps(10, 1.0), gaps(40, 0.1))
    assert correct(gaps(10, 0.7), gaps(40, 0.1))
    # too few tokens to take a ratio of: the two wait
    assert correct(gaps(40, 0.05), gaps(40, 0.1), n=100)
    # one token simply wrong: the third limit
    wrong = best.copy()
    wrong[7] = exact[7].argmin()
    assert not correct(gaps(20, 0.05), gaps(40, 0.1), tokens=wrong)


def test_logits_at_keeps_the_runs_tally(monkeypatch, capsys):
    cfg, params = _tiny()
    monkeypatch.setattr(model, "_JUDGED", [])
    toks = np.random.default_rng(5).integers(1, cfg.vocab_size, 64).tolist()
    tokens = jnp.asarray([toks])
    rows = np.arange(40, 50)
    exact = np.asarray(model.reference_logits(params, tokens, rows, cfg))
    served = exact.argmax(-1)
    full = np.asarray(tokens).copy()
    full[0, rows + 1] = served
    # the sequence changed behind the judged rows: judge row by row is not
    # the point here; the tally and the line on stderr are
    got = model.logits_at(params, jnp.asarray(full), rows, cfg)
    assert got.shape == exact.shape and len(model._JUDGED) == 1
    line = [x for x in capsys.readouterr().err.splitlines()
            if "motif_judged" in x][-1]
    doc = json.loads(line)["motif_judged"]
    assert doc["tokens"] == 10 and "gap_ratio" in doc \
        and "differ_ratio" in doc


def test_init_params_is_the_programs_initialiser_a_layer_at_a_time():
    from lzy_tpu.models import motif as program

    cfg, _ = _tiny()
    mine = model.init_params(cfg, 7)
    whole = program.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(mine) \
        == jax.tree_util.tree_structure(whole)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(whole)):
        assert a.shape == b.shape and a.dtype == b.dtype
    again = model.init_params(cfg, 7)
    assert all((a == b).all() for a, b in zip(
        jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(again)))
    other = model.init_params(cfg, 2 ** 31 + 8)
    assert not (np.asarray(other["layer_2"]["o_proj"]["kernel"])
                == np.asarray(mine["layer_2"]["o_proj"]["kernel"])).all()
    # no two layers draw the same weights
    assert not (np.asarray(mine["layer_1"]["o_proj"]["kernel"])
                == np.asarray(mine["layer_2"]["o_proj"]["kernel"])).all()


def test_what_the_program_cannot_honour_is_refused():
    doc = json.load(open(REAL))
    for key, value in (("hidden_act", "silu"), ("mhc_enabled", False),
                       ("diff_v2", False)):
        with pytest.raises(ValueError, match=key):
            model.program_config(dict(doc, **{key: value}))


# -- the counts ---------------------------------------------------------------

def test_counts_at_the_published_widths():
    cfg = model.program_config(json.load(open(REAL)))
    assert (cfg.n_layers, cfg.expert_layers, cfg.n_held) == (5, 4, 48)
    assert (cfg.vocab_size, cfg.max_seq_len) == (27520, 12288)
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (1, 4, 128)
    assert cfg.kv_token_bytes() == 1280
    # a token of a row at the longest context: 1,280 in the paged pool and
    # 4 x 1,280 x 128 / 12,288 = 53 in the window pool
    assert model.kv_bytes_per_token(cfg) == 1280 + 53
    assert cfg.latent_values == 576      # of the 640 lanes a page lays out
    assert model.expert_bytes(cfg) == 31_457_280        # 3 x 4096 x 1280 x 2
    assert model.routed_param_bytes(cfg) == 4 * 48 * 31_457_280
    # a quarter of the held experts: 12 a layer, 4 layers
    experts = 4 * 12 * 31_457_280
    assert model.experts_step_bytes(cfg, 40, 0.25) == experts
    with pytest.raises(TypeError):                       # never an expectation
        model.experts_step_bytes(cfg, 40)
    # forty rows that each read 3,000 positions of the one full layer
    assert model.latent_step_bytes(cfg, 40, 3000.0) == 40 * 3000 * 1152
    # each sublayer's phi, 24 x 16,384 float32, once a program; ten
    # sublayers; the streams stay near the core and are not charged
    phi = 24 * 16384 * 4
    assert model.mhc_step_bytes(cfg, 40) == model.mhc_step_bytes(cfg, 8) \
        == 10 * phi
    assert model.mhc_prefill_bytes(cfg, 700, 3) == 3 * 10 * phi
    # a row at position 2,999: 1,152 x 3,000 in the full layer and 4 x
    # 1,152 x 128 in the window layers
    ctx = 1152 * 3000 + 4 * 1152 * 128
    assert model.row_context_bytes(cfg, 2999) == ctx
    assert model.row_context_bytes(cfg, 99) == 1152 * 100 * 5
    from lzy_tpu.models import motif as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes))
    assert abs(param_bytes / 7.877e9 - 1) < 2e-3
    outside = param_bytes - 4 * 48 * 31_457_280 - 27520 * 4096 * 2
    assert abs(outside / 1.61e9 - 1) < 0.02     # 1.59 GB + the connections
    want = outside + experts + 40 * ctx
    got = model.decode_step_bytes(cfg, param_bytes, 120_000, 40, 0.25)
    assert abs(got - want) < 1.0
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0, 0.0) == outside
    with pytest.raises(TypeError):
        model.decode_step_bytes(cfg, param_bytes, 120_000, 40)


def _emit(end, rows, context, touched):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_mla_context_tokens_total": context,
                "lzy_mla_rows_total": rows,
                "lzy_moe_experts_touched_total": touched,
                "lzy_moe_experts_held_total": 4 * 48}}}


def _prefill(end, start, tokens):
    return {"name": "engine.prefill", "start": end - 0.01, "end": end,
            "attrs": {"start": start, "tokens": tokens}}


def test_the_rooflines_charge_what_the_rounds_and_the_programs_counted():
    """Two traced rounds of 30 and 50 rows that read 90,000 and 150,000
    cached positions and reached 96 and 112 of 192 held experts: 40 rows a
    round at 3,000 a row, a share of 13 / 24; two prefill programs of 256
    and 188 real positions."""
    cfg = model.program_config(json.load(open(REAL)))
    files = common.cell_files(common.load_manifest(), "hyper-steady")
    by_name = {x["name"]: x for x in files["per_layer"]}
    obs = {"trace": {
        "modules": {"jit_decode_step": [0.007, 0.008],
                    "jit_prefill_step": [0.015, 0.014]},
        "ops": {"jit_decode_step:polynorm_experts_f32_64_4096_": (0.008, 8),
                "jit_decode_step:mhc_pre_f32_64_4096_": (0.0003, 20),
                "jit_decode_step:mhc_post_f32_64_16384_": (0.0001, 20),
                "jit_decode_step:mla_paged_decode_bf16_": (0.0006, 2),
                "jit_decode_step:fusion.1": (0.006, 90),
                "jit_prefill_step:polynorm_experts_f32_256_": (0.018, 8),
                "jit_prefill_step:mhc_pre_f32_256_4096_": (0.0017, 20),
                "jit_prefill_step:mhc_post_f32_256_": (0.0003, 20),
                "jit_prefill_step:fusion.2": (0.009, 90)}},
        "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
        "spans": [_emit(0.3, 30, 90_000, 96), _emit(0.6, 50, 150_000, 112),
                  _emit(1.5, 9, 99_000, 1),              # past the span
                  _prefill(0.2, 1024, 256), _prefill(0.4, 1280, 188),
                  _prefill(1.4, 0, 256)],
        "counters": {"lzy_diff_noise_weight_milli_total": 512_000.0,
                     "lzy_diff_signal_reads_total": 1024.0,
                     "lzy_kv_window_pages_released_total": 102.0},
        "t_open": 0.0, "t_close": 51.0,
        "model": {"module": model, "cfg": cfg}}

    def read(name):
        return readers.read(by_name[name], obs)

    need = model.experts_step_bytes(cfg, 40, 208 / 384)
    want = 100.0 * (need / 819e9) * 2 / 0.008
    got = read("kernel.polynorm_experts_roofline")
    assert abs(got - want) < 1e-6 and 0 < got < 100
    need = model.mhc_step_bytes(cfg, 40)
    want = 100.0 * (need / 819e9) * 2 / 0.0004
    got = read("kernel.mhc_decode_roofline")
    assert abs(got - want) < 1e-6 and 0 < got < 100
    need = model.mhc_prefill_bytes(cfg, 256, 1) \
        + model.mhc_prefill_bytes(cfg, 188, 1)
    want = 100.0 * (need / 819e9) / 0.002
    got = read("kernel.mhc_prefill_roofline")
    assert abs(got - want) < 1e-6 and 0 < got < 100
    need = model.latent_step_bytes(cfg, 40, 3000.0)
    want = 100.0 * (need / 819e9) * 2 / 0.0006
    got = read("kernel.mla_decode_roofline")
    assert abs(got - want) < 1e-6 and 0 < got < 100
    assert abs(read("step.mhc_share_of_decode")
               - 100.0 * 0.0004 / 0.015) < 1e-9
    assert abs(read("step.polynorm_experts_share_of_prefill")
               - 100.0 * 0.018 / 0.029) < 1e-9
    assert abs(read("attn.noise_weight_mean") - 0.5) < 1e-9
    assert abs(read("kv.hyper_window_pages_released_per_s") - 2.0) < 1e-9

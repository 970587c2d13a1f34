"""The LongCat-Flash (``longcat_flash``) model file: the program (shortcut
layers of two absorbed latent attentions over two paged leaves, an expert
layer whose router is wider than the experts with weights) against the plain
reference (the published non-absorbed form, no cache) through the harness at
a tiny size (one chip's share: experts 4-7 of 16, 8 identity experts), the
reference against a direct sum, the two limits as the harness's one
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
from benchmark.models import longcat_flash as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "longcat-flash-omni-serve-l4-ep32.json")
CELL = "shortcut-steady"

#: the per-layer metrics this cell brought
NEW = ("moe.zero_assignment_share", "moe.zero_weight_share",
       "kv.shortcut_pool_live_share", "step.dense_ffn_share_of_decode")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = common.cell_files(common.load_manifest(), CELL)
    doc = _load("configs", "tiny-longcat")
    files = {"cell": {"name": "tiny-shortcut", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-shortcut"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-shortcut", seed=2 ** 31 + 67,
                              seconds=3.0, trace=trace)
    out = bench_run.run_cell(args, files, require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["metrics"] == {}
    named = out["rehearsal"]["metric_names"]
    if trace:
        # what a CPU trace and the counters can feed; the device-trace
        # metrics need a TPU's planes
        assert {"moe.held_assignment_share", "moe.experts_touched_share",
                "moe.zero_assignment_share", "moe.zero_weight_share",
                "kv.shortcut_pool_live_share", "engine.slots_busy_share",
                "kv.prefix_hit_share",
                "engine.prefill_share_of_loop"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "kv_bytes_per_token", "latent_step_bytes", "expert_bytes",
        "experts_step_bytes", "routed_param_bytes", "decode_step_bytes"))
    assert 0 < model.GAP_RATIO < 1 < model.LOGIT_TIE_TOL
    # every reading of the program under its limits with room; the
    # all-bfloat16 control is 1 on the first by construction, over it
    cal = model.CALIBRATION
    assert len(cal["gap_ratio"]) >= 6
    assert max(cal["gap_ratio"]) < model.GAP_RATIO < 1
    assert max(cal["worst_gap"] + cal["control_worst_gap"]) \
        < model.LOGIT_TIE_TOL
    assert 2.5 < model.LOGIT_TIE_TOL / cal["logit_std"] < 4.0


def test_the_manifest_has_the_cell_and_it_finds_its_files():
    m = common.load_manifest()
    assert [w["name"] for w in m["workloads"]][-1] == CELL
    assert len(m["workloads"]) == 15 and len(m["configs"]) == 14
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = common.cell_files(m, CELL)
    assert files["cell"]["chips"] == 1
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    names = {x["name"] for x in files["per_layer"]}
    assert set(NEW) <= names
    # the reads are the two kernels as they are
    assert {"step.mla_share_of_decode", "step.mla_share_of_prefill",
            "kernel.mla_decode_roofline", "step.experts_share_of_decode",
            "step.experts_share_of_prefill",
            "kernel.grouped_experts_roofline",
            "step.decode_counted_roofline", "kv.prefix_hit_share"} <= names
    # what the cell leaves to others: other kernels' and models' texts
    assert not names & {"step.decode_roofline", "kv.window_keys_share",
                        "step.polynorm_experts_share_of_decode",
                        "kv.hyper_pool_live_share",
                        "kv.latent_pool_live_share"}
    for x in files["per_layer"]:
        assert callable(readers.find(x))
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL] for n in NEW)
    assert all(by_name[n]["moves"] == "tpot_p50_s" for n in NEW)
    texts = ([c[k] for c in m["configs"] for k in ("why", "source")]
             + [w["why"] for w in m["workloads"]]
             + [x["layer"] for x in m["per_layer"]] + m["command"])
    assert all(1 <= len(t) <= 200 and t.isprintable() for t in texts)


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the counts: None, never an error."""
    files = common.cell_files(common.load_manifest(), CELL)
    obs = {"trace": {"modules": {"jit_decode_step": [0.01],
                                 "jit_prefill_step": [0.02]},
                     "ops": {"jit_decode_step:copy": (0.01, 1),
                             "jit_prefill_step:copy": (0.02, 1)}},
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
                   if '"LongCat-Flash-Omni"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_layers", "n_routed_experts",
                              "vocab_size", "max_position_embeddings"]
    assert (doc["router_width"], doc["experts_held_from"]) == (768, 0)
    for key in ("assumed", "deployment", "guarantees"):
        assert doc[key]
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == doc["name"])
    assert entry["reduced"] == doc["reduced"]
    assert entry["source"] == doc["source"]
    cfg = model.program_config(doc)
    assert (cfg.n_routed_experts, cfg.n_weighted, cfg.experts_held) \
        == (768, 512, (0, 16))
    assert cfg.kv_layers == 8 and cfg.kv_token_bytes() == 1280


# -- the reference ------------------------------------------------------------

def _unit_scale(params):
    def fix(path, leaf):
        if path[-1].key in ("kernel", "experts_gate", "experts_up",
                            "experts_down"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if path[-1].key == "router":
            return leaf * (3.0 * leaf.shape[-2] ** -0.5 / 0.02)
        if path[-1].key == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    from lzy_tpu.models import longcat_flash as program

    cfg = model.program_config(_load("configs", "tiny-longcat"))
    return cfg, _unit_scale(program.init_params(cfg, jax.random.PRNGKey(3)))


def test_the_reference_against_the_program_and_the_control_apart():
    from lzy_tpu.models import longcat_flash as program

    cfg, params = _tiny()
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 64).tolist()])
    want = np.asarray(model.reference_logits(params, toks, jnp.arange(64),
                                             cfg))
    got = np.asarray(program.LongcatFlash(cfg).apply(
        {"params": params}, toks, mutable=["stats"])[0][0])
    assert np.abs(got - want).max() < 2e-4
    control = np.asarray(model.reference_logits(
        params, toks, jnp.arange(64), cfg, jnp.bfloat16))
    assert np.abs(control - want).max() > 4e-3


def test_the_references_layer_is_a_direct_sum():
    """One shortcut layer at sixteen positions, by loops over heads, positions
    and experts in float64: two attentions in the expanded form with both
    scale corrections, two dense parts, the expert layer read from the first
    sublayer's ``u`` and added after the second dense part, the identity
    term ``z u``."""
    cfg, params = _tiny()
    t = 16
    x0 = np.random.default_rng(2).normal(size=(t, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.layer(
            jnp.asarray(x0, jnp.float32), params, 1, cfg,
            jnp.dtype(jnp.float32)))
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    r, dn, dr, dv, h = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.n_heads)

    def norm(x, scale):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.norm_eps) \
            * f64(scale)

    def rope(x, pos):
        d = x.shape[-1]
        ang = pos * cfg.rope_theta ** (-np.arange(0, d, 2) / d)
        a, b = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               a * np.sin(ang) + b * np.cos(ang)], -1)

    def silu(v):
        return v / (1 + np.exp(-v))

    def attention(x, w):
        """Every position's output (the second sublayer needs them all)."""
        c_q = 64 ** 0.5 / 24 ** 0.5 * norm(
            x @ f64(w["q_a_proj"]["kernel"]), w["q_a_norm"]["scale"])
        q = (c_q @ f64(w["q_b_proj"]["kernel"])).reshape(t, h, dn + dr)
        kva = x @ f64(w["kv_a_proj"]["kernel"])
        c = 2 ** 0.5 * norm(kva[:, :r], w["kv_a_norm"]["scale"])
        kv = np.einsum("tr,rhx->thx", c, f64(w["kv_b_proj"]))
        out = np.zeros((t, h, dv))
        for at in range(t):
            for head in range(h):
                s = np.asarray([
                    (q[at, head, :dn] @ kv[p, head, :dn]
                     + rope(q[at, head, dn:], at) @ rope(kva[p, r:], p))
                    / np.sqrt(dn + dr) for p in range(at + 1)])
                pr = np.exp(s - s.max())
                pr /= pr.sum()
                out[at, head] = sum(pr[p] * kv[p, head, dn:]
                                    for p in range(at + 1))
        return out.reshape(t, -1) @ f64(w["o_proj"]["kernel"])

    def ffn(u, w):
        return (silu(u @ f64(w["gate_proj"]["kernel"]))
                * (u @ f64(w["up_proj"]["kernel"]))) \
            @ f64(w["down_proj"]["kernel"])

    def moe(u, w):
        lo, hi = cfg.experts_held
        logits = u @ f64(w["router"])
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out = np.zeros_like(u)
        for at in range(t):
            chosen = np.argsort(-(p[at] + f64(w["router_bias"])))[:cfg.top_k]
            for e in chosen:
                weight = cfg.routed_scaling * p[at, e]
                if e >= cfg.n_weighted:
                    out[at] += weight * u[at]
                elif lo <= e < hi:
                    k = e - lo
                    out[at] += weight * (
                        (silu(u[at] @ f64(w["experts_gate"][k]))
                         * (u[at] @ f64(w["experts_up"][k])))
                        @ f64(w["experts_down"][k]))
        return out

    x = x0
    for j in (0, 1):
        a = x + attention(norm(x, params[f"layer_1_norm_{j}"]["scale"]),
                          params[f"layer_1_attn_{j}"])
        u = norm(a, params[f"layer_1_ffn_norm_{j}"]["scale"])
        if j == 0:
            s = moe(u, params["layer_1_moe"])
        x = a + ffn(u, params[f"layer_1_mlp_{j}"])
    want = x + s
    # positions that chose a held expert or an identity expert, and both
    assert (np.abs(s).max(-1) > 0.05).sum() >= 8
    assert np.abs(got - want).max() < 1e-4


def _harness_says_correct(logits, tokens):
    gap = logits.max(-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_first_limit_reaches_the_harness_as_one_comparison():
    rng = np.random.default_rng(0)
    exact = rng.normal(size=(600, 2000)).astype(np.float32)
    best = exact.argmax(-1)

    def correct(mine, control, n=600):
        return _harness_says_correct(model.held_to_both_limits(
            exact[:n], best[:n], mine[:n], control[:n]), best[:n])

    ctrl = np.full(600, 0.1)
    # a sound run: the program's mean gap half the control's
    assert correct(np.full(600, 0.05), ctrl)
    # as far as the control, or over GAP_RATIO of it: not correct, though
    # every token handed in is the reference's best
    assert not correct(ctrl, ctrl)
    assert not correct(np.full(600, 0.1 * model.GAP_RATIO + 0.002), ctrl)
    assert correct(np.full(600, 0.1 * model.GAP_RATIO - 0.002), ctrl)
    # too few judged tokens: the ratio is not read
    assert correct(ctrl, ctrl, n=model.GAP_RATIO_MIN_TOKENS - 1)
    # the second limit is the harness's own: one token simply wrong
    wrong = best.copy()
    wrong[17] = int(np.argsort(exact[17])[0])
    assert not _harness_says_correct(model.held_to_both_limits(
        exact, wrong, np.full(600, 0.05), ctrl), wrong)


# -- the counts ---------------------------------------------------------------

def test_the_counting_functions_against_counts_by_hand():
    cfg = model.program_config(json.load(open(REAL)))
    # eight leaves of 576 bfloat16 values
    assert model.kv_bytes_per_token(cfg) == 8 * 1152 == 9216
    assert model.latent_step_bytes(cfg, 96, 1750) == 96 * 1750 * 9216
    assert model.expert_bytes(cfg) == 3 * 6144 * 2048 * 2 == 75_497_472
    assert model.routed_param_bytes(cfg) == 4 * 16 * 75_497_472
    assert model.experts_step_bytes(cfg, 96, 0.75) \
        == 4 * 16 * 0.75 * 75_497_472
    # the parameters by shapes: 4 x (638.9 M + 16 x 37.75 M) + 2 x 100.7 M
    attention = (6144 * 1536 + 1536 + 1536 * 12288 + 6144 * 576 + 512
                 + 512 * 16384 + 8192 * 6144)
    dense = 3 * 6144 * 12288
    outside = 2 * attention + 2 * dense + 4 * 6144
    router = 6144 * 768 + 768
    layer_bytes = 2 * outside + 4 * router + 16 * 75_497_472
    embed = 16384 * 6144 * 2
    param_bytes = 4 * layer_bytes + 2 * embed + 6144 * 2
    assert abs(param_bytes / 1e9 - 10.37) < 0.02
    got = model.decode_step_bytes(cfg, param_bytes, 170_000, 96, 0.75)
    want = (4 * (2 * outside + 4 * router) + embed + 6144 * 2
            + 4 * 16 * 0.75 * 75_497_472 + 170_000 * 9216)
    assert got == want
    # a round at 96 rows: 5.3 GB outside the experts, 3.6 GB of experts,
    # 1.6 GB of latents
    assert abs((want - 4 * 16 * 0.75 * 75_497_472 - 170_000 * 9216) / 1e9
               - 5.33) < 0.03

"""The Solar-Open2 model file: the program against the plain reference
through the harness at a tiny size (one chip's share: experts 4-11 of 16),
the reference's delta rule against a recurrence written out by hand, the
bfloat16 control and the two limits as the harness's one comparison sees
them, the refusal that holds the state's precision, the byte counts against
numbers counted by hand at the published widths, the reader of the counted
decode roofline, and the manifest with its five cells."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import common, readers
from benchmark.models import solar_open2 as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "solar-open2-serve-l8-ep8.json")


#: the per-layer metrics this cell brought, in the manifest's order
NEW = ("step.kda_share_of_decode", "kernel.kda_update_roofline",
       "step.experts_share_of_prefill", "step.decode_counted_roofline")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = common.cell_files(common.load_manifest(), "doc-steady")
    doc = _load("configs", "tiny-solar")
    files = {"cell": {"name": "tiny-doc", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-doc"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-doc", seed=2 ** 31 + 33,
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


def test_the_manifest_has_five_cells_and_the_new_one_finds_its_files():
    m = common.load_manifest()
    assert [w["name"] for w in m["workloads"]] == [
        "chat-steady", "batch-backlog", "train-fsdp4", "reason-steady",
        "doc-steady"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = common.cell_files(m, "doc-steady")
    assert files["model"] is model and files["config"]["kind"] == "serve"
    names = {x["name"] for x in files["per_layer"]}
    assert set(NEW) <= names
    # the decode roofline that charges the uniform expectation is not the
    # new cell's: its own reads the counted share
    assert "step.decode_roofline" not in names
    assert len(names) == 21
    for x in files["per_layer"]:
        assert callable(readers.find(x))
    # the new metrics are the new cell's alone, and last in the manifest
    assert [x["name"] for x in m["per_layer"][-4:]] == list(NEW)
    assert all(x["workloads"] == ["doc-steady"] for x in m["per_layer"][-4:])


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernel or the counts: None, never an error."""
    files = common.cell_files(common.load_manifest(), "doc-steady")
    obs = {"trace": {"modules": {"jit_decode_step": [0.01]},
                     "ops": {"jit_decode_step:fusion": (0.01, 1)}},
           "trace_span": (0.0, 1.0), "spans": [], "counters": {},
           "model": {"module": model, "cfg": None}, "device_kind":
           "TPU v5 lite"}
    new = [x for x in files["per_layer"] if x["name"] in NEW]
    assert len(new) == 4
    for x in new:
        assert readers.read(x, obs) is None
    # with rows on the clients' side and no counts on the spans, still None
    obs["rows"] = [(100, [(0.1, 1), (0.9, 9)])]
    for x in new:
        assert readers.read(x, obs) is None


def test_the_configuration_file_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"Solar-Open2-250B"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert set(doc["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    for key in ("assumed", "deployment", "guarantees"):
        assert doc[key]


def _unit_scale(params):
    """Variance-preserving weights at the tiny widths (as
    tests/test_solar_open2.py): normal(0.02) hides errors there."""
    big = ("kernel", "experts_gate", "experts_up", "experts_down", "router")
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if p[-1].key in big else leaf, params)


def _tiny():
    cfg = model.program_config(_load("configs", "tiny-solar"))
    return cfg, _unit_scale(model.init_params(cfg, 3))


def test_the_references_delta_rule_is_the_recurrence_written_by_hand():
    """``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
    ``o_t = S_t^T q_t``, the matrices written out in numpy float64."""
    rng = np.random.default_rng(3)
    t, h, d = 10, 2, 8
    q, k, v = (rng.normal(size=(t, h, d)) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    alpha = rng.uniform(0.1, 1.0, size=(t, h, d))
    beta = rng.uniform(0.0, 2.0, size=(t, h))
    want = np.zeros((t, h, d))
    for j in range(h):
        s = np.zeros((d, d))
        for i in range(t):
            kk = k[i, j][:, None]
            s = (np.eye(d) - beta[i, j] * kk @ kk.T) @ np.diag(alpha[i, j]) \
                @ s + beta[i, j] * kk @ v[i, j][None, :]
            want[i, j] = s.T @ q[i, j]
    got = model.delta_rule(*(jnp.asarray(a, jnp.float32)
                             for a in (q, k, v, alpha, beta)))
    assert np.abs(got - want).max() < 1e-5


def test_the_bfloat16_control_is_another_result():
    """At a tiny size the control's logits differ and its choices sit below
    the reference's best; how far, at the published widths, is the model
    file's argument (chip readings), not this test's."""
    cfg, params = _tiny()
    tokens = jnp.asarray([np.random.default_rng(4).integers(
        1, cfg.vocab_size, 161).tolist()])
    rows = jnp.arange(160)
    exact = np.asarray(model.reference_logits(params, tokens, rows, cfg))
    rough = np.asarray(model.reference_logits(params, tokens, rows, cfg,
                                              jnp.bfloat16))
    assert np.abs(rough - exact).max() > 1e-2        # it is another result
    picked = model.control_choices(params, tokens, rows, cfg)
    assert (picked == rough.argmax(-1)).all()
    assert model.gaps(exact, picked).max() > 1e-3
    assert model.gaps(exact, exact.argmax(-1)).max() == 0.0


def _harness_says_correct(logits, tokens) -> bool:
    """``harness/serve.py`` ``warm_and_check``'s comparison, to the
    letter."""
    logits = np.asarray(logits)
    gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def _logits_with_gaps(gap):
    """``[n, 8]`` logits whose best is column 0 and whose column 1 sits
    ``gap[i]`` below it; the tokens that choose column 1 where the gap is
    not 0."""
    gap = np.asarray(gap, np.float32)
    exact = np.full((len(gap), 8), -9.0, np.float32)
    exact[:, 0] = 1.0
    exact[:, 1] = 1.0 - gap
    return exact, (gap > 0).astype(int)


def test_both_limits_reach_the_harness_through_its_one_comparison():
    n = model.WIDE_GAP_MIN_TOKENS
    wide = model.WIDE_GAP + 0.1
    assert wide < model.LOGIT_TIE_TOL

    def run(gap, judged=None):
        """The harness's verdict on one request whose tokens sit ``gap``
        below the best, ``judged`` being the run's gaps so far."""
        exact, chosen = _logits_with_gaps(gap)
        held = model.held_to_both_limits(
            exact, chosen, gap if judged is None else judged)
        return exact, held, chosen

    # one token in a hundred far below the best: under both limits
    sound = np.where(np.arange(n) % 100 == 0, wide, 0.0)
    assert np.mean(sound > model.WIDE_GAP) < model.WIDE_GAP_SHARE
    exact, held, chosen = run(sound)
    assert (held == exact).all() and _harness_says_correct(held, chosen)
    # one in twenty: the share is over its limit, the largest gap is not,
    # and the harness's comparison says not correct
    rough = np.where(np.arange(n) % 20 == 0, wide, 0.0)
    assert np.mean(rough > model.WIDE_GAP) > model.WIDE_GAP_SHARE
    exact, held, chosen = run(rough)
    assert _harness_says_correct(exact, chosen)         # by the largest gap
    assert not _harness_says_correct(held, chosen)
    # ... and what it reads is the true largest gap plus LOGIT_TIE_TOL
    assert abs(model.gaps(held, chosen).max()
               - (wide + model.LOGIT_TIE_TOL)) < 1e-5
    # the share is the run's: a sound request after rough ones is refused,
    # a rough quarter among sound ones is not
    quarter = n // 4
    exact, held, chosen = run(sound[:quarter], np.concatenate(
        [rough, sound[:quarter]]))
    assert not _harness_says_correct(held, chosen)
    last = np.where(np.arange(quarter) % 50 == 0, wide, 0.0)
    whole = np.concatenate([sound[:quarter]] * 3 + [last])
    assert np.mean(last > model.WIDE_GAP) > model.WIDE_GAP_SHARE \
        > np.mean(whole > model.WIDE_GAP)
    exact, held, chosen = run(last, whole)
    assert _harness_says_correct(held, chosen)
    # one token far below, the share sound: the largest gap alone refuses
    one = np.zeros(n)
    one[7] = model.LOGIT_TIE_TOL + 0.1
    exact, held, chosen = run(one)
    assert (held == exact).all()
    assert not _harness_says_correct(held, chosen)
    # fewer judged tokens than the share needs: the largest gap alone
    exact, held, chosen = run(rough[:n - 1])
    assert _harness_says_correct(held, chosen)


def test_the_cell_judges_as_many_tokens_as_the_share_needs():
    chk = _real_traffic()["correctness"]
    assert chk["requests"] * chk["decode_tokens"] >= model.WIDE_GAP_MIN_TOKENS
    # and every prompt level leaves them room under the padded length
    assert _real_traffic()["prompt_len"]["max"] + chk["decode_tokens"] \
        <= chk["pad_to"]


def _real_traffic():
    with open(os.path.join(common.BENCH_DIR, "traffic",
                           "doc-steady.json")) as f:
        return json.load(f)


def test_logits_at_reads_the_served_tokens_from_the_sequence():
    """The harness hands ``logits_at`` the prompt with the served tokens
    after it and the rows that chose them: served the reference's own
    choices the logits come back as they are, whatever their number."""
    cfg, params = _tiny()
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, 30).tolist()
    full = list(prompt)
    for _ in range(3):                                   # greedy, by the reference
        lg = model.reference_logits(params, jnp.asarray([full + [0] * 8]),
                                    jnp.asarray([len(full) - 1]), cfg)
        full.append(int(np.asarray(lg)[0].argmax()))
    padded = jnp.asarray([full + [0] * 7])
    rows = jnp.arange(len(prompt) - 1, len(full) - 1)
    before = len(model._JUDGED)
    got = np.asarray(model.logits_at(params, padded, rows, cfg))
    # the run's tally has this request's gaps: all 0
    assert len(model._JUDGED) == before + 1
    assert (model._JUDGED.pop() == 0).all()
    want = np.asarray(model.reference_logits(params, padded, rows, cfg))
    assert (got == want).all()
    assert _harness_says_correct(got, full[len(prompt):])


def test_a_program_that_keeps_another_state_dtype_is_refused():
    doc = _load("configs", "tiny-solar")
    model.program_config(doc)                         # float32: served
    with pytest.raises(ValueError, match="kda_state_dtype bfloat16"):
        model.program_config({**doc, "kda_state_dtype": "bfloat16"})
    assert json.load(open(REAL))["kda_state_dtype"] == "float32"


def test_init_params_is_the_programs_initialiser_as_it_is():
    from lzy_tpu.models import solar_open2 as program

    cfg = model.program_config(_load("configs", "tiny-solar"))
    plain = program.init_params(cfg, jax.random.PRNGKey(7))
    mine = model.init_params(cfg, 7)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(mine)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)


def test_the_reference_holds_the_share_it_is_given():
    cfg, params = _tiny()
    assert cfg.experts_held == (4, 12) and cfg.n_routed_experts == 16
    layer = params["layer_1_moe"]
    assert layer["experts_gate"].shape[0] == 8
    u = jnp.asarray(np.random.default_rng(6).normal(
        size=(20, cfg.d_model)).astype(np.float32))
    weights = np.asarray(model.route(u, layer, cfg))
    assert weights.shape == (20, 8)
    assert ((weights > 0).sum(axis=1) <= cfg.top_k).all()
    assert (weights > 0).sum() < 20 * cfg.top_k


def test_byte_counts_at_the_published_widths():
    cfg = model.program_config(json.load(open(REAL)))
    assert model.kv_bytes_per_token(cfg) == 8192        # 2 layers x 2 x 8 x 128 x 2 B
    assert model.expert_bytes(cfg) == 31_457_280        # 3 x 4096 x 1280 x 2 B
    assert model.kda_state_bytes(cfg) == 25_165_824     # 6 x 64 x 128 x 128 x 4 B
    assert model.conv_state_bytes(cfg) == 884_736       # 6 x 3 x 24576 x 2 B
    # a slot's state, the configuration's 26.05 MB
    assert model.kda_state_bytes(cfg) + model.conv_state_bytes(cfg) \
        == 26_050_560
    assert model.routed_param_bytes(cfg) == 10_066_329_600   # 8 x 40 experts
    # the share of the held experts the program counted: a quarter is 10
    # experts a layer, 2.52 GB over the eight layers
    experts = 8 * 10 * 31_457_280
    assert model.experts_step_bytes(cfg, 14, 0.25) == experts
    with pytest.raises(TypeError):                       # never an expectation
        model.experts_step_bytes(cfg, 14)
    assert model.state_step_bytes(cfg, 14) == 2 * 14 * 25_165_824
    # the program's parameter bytes at these widths (counted from shapes):
    # bfloat16: 6 KDA layers of 137,625,600, 2 attention layers of
    # 109,051,904, 8 x (40 x 15,728,640 routed + 15,728,640 shared), 17
    # norms of 4096, embedding and head 201,326,592; float32: 106,688 a KDA
    # layer (convolution, dt_bias, A_log, head norm), 1,311,040 a router
    from lzy_tpu.models import solar_open2 as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes))
    assert param_bytes == 2 * 6_404_247_552 + 4 * 11_128_448 \
        == 12_853_008_896
    outside = 12_853_008_896 - 10_066_329_600 - 24576 * 4096 * 2
    want = outside + experts + 8192 * 20_000 + 2 * 14 * 26_050_560
    got = model.decode_step_bytes(cfg, param_bytes, 20_000, 14, 0.25)
    assert abs(got - want) < 1.0
    assert 5.9e9 < got < 6.1e9                           # 7.3 ms at 819 GB/s
    # no rows: the weights outside the experts, nothing else
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0, 0.0) == outside
    with pytest.raises(TypeError):
        model.decode_step_bytes(cfg, param_bytes, 20_000, 14)


def test_the_counted_decode_roofline_charges_what_the_rounds_counted():
    """Two traced rounds of 4 and 6 rows that reached 10 and 14 of the 320
    held-expert slots (8 layers x 40): rows 5 a round, share 24 / 640; the
    clients saw 4 rows resident with 8000 tokens of context, so a round's
    5 rows hold 10000."""
    cfg = model.program_config(json.load(open(REAL)))
    metric = next(x for x in common.cell_files(
        common.load_manifest(), "doc-steady")["per_layer"]
        if x["name"] == "step.decode_counted_roofline")
    param_bytes = 12_853_008_896

    def emit(end, rows, touched):
        return {"name": "engine.decode.emit", "start": end - 0.001,
                "end": end, "attrs": {"rows": rows, "model_stats": {
                    "lzy_moe_experts_touched_total": touched,
                    "lzy_moe_experts_held_total": 320}}}

    # four clients' rows, each resident over the whole traced second with
    # 2000 tokens of context (a prompt of 1990 and tokens 10 -> 11)
    rows = [(1990, [(0.0, 10), (1.0, 11)])] * 4
    obs = {"trace": {"modules": {"jit_decode_step": [0.010, 0.012]}},
           "trace_span": (0.0, 1.0), "rows": rows, "device_kind":
           "TPU v5 lite", "spans": [emit(0.3, 4, 10), emit(0.6, 6, 14),
                                    emit(1.5, 9, 99)],   # past the span
           "model": {"module": model, "cfg": cfg,
                     "param_bytes": param_bytes}}
    need = model.decode_step_bytes(cfg, param_bytes, 10_000, 5, 24 / 640)
    want = 100.0 * (need / 819e9) * 2 / 0.022
    got = readers.read(metric, obs)
    assert abs(got - want) < 1e-6 and 20.0 < got < 100.0

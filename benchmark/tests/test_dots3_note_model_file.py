"""The dots3-note-prev (``dots3_note``) model file: the program (absorbed
latent attention over the tokens an indexer picks, a latent window pool)
against the plain reference (the published non-absorbed form, an exact top-k
by a sort, no cache) through the harness at a tiny size (one chip's share:
experts 4-7 of 16), the reference against a direct sum, the three limits as
the harness's one comparison sees them, every counting function against
counts by hand at the published widths, the readers of the new metrics, the
configuration file against the catalog, and the manifest with its cell."""

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
from benchmark.models import dots3_note as model

HERE = os.path.dirname(__file__)
REAL = os.path.join(common.BENCH_DIR, "configs",
                    "dots3-note-prev-serve-l5-ep8.json")

#: the per-layer metrics this cell brought
NEW = ("step.index_share_of_prefill", "step.index_share_of_decode",
       "step.chosen_read_share_of_prefill",
       "step.chosen_read_share_of_decode", "kv.latent_chosen_share",
       "kv.latent_pool_live_share", "kv.window_latent_pages_released_per_s",
       "kernel.latent_index_roofline",
       "kernel.latent_index_prefill_roofline",
       "kernel.latent_chosen_decode_roofline",
       "kernel.latent_chosen_prefill_roofline")


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", (0, 1))
def test_program_serves_the_references_tokens_through_the_harness(trace):
    real = common.cell_files(common.load_manifest(), "indexed-steady")
    doc = _load("configs", "tiny-dots3-note")
    files = {"cell": {"name": "tiny-indexed", "chips": 1}, "config": doc,
             "model": common.model_for(doc),
             "traffic": _load("traffic", "tiny-indexed"),
             "end_to_end": real["end_to_end"],
             "per_layer": real["per_layer"]}
    args = argparse.Namespace(workload="tiny-indexed", seed=2 ** 31 + 62,
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
                "kv.latent_chosen_share", "kv.latent_pool_live_share",
                "kv.window_latent_pages_released_per_s",
                "engine.prefill_share_of_loop"} <= set(named)
    else:
        assert {"setup_s", "tpot_p50_s"} <= set(named)


def test_the_model_file_has_every_serve_name():
    assert all(hasattr(model, name) for name in REQUIRED["serve"])
    assert all(callable(getattr(model, name)) for name in (
        "experts_step_bytes", "index_step_bytes", "chosen_step_bytes",
        "index_prefill_flops", "chosen_prefill_flops", "decode_step_bytes"))
    assert 1 < model.GAP_RATIO < model.LOGIT_TIE_TOL
    assert 0 < model.CHOICE_DIFFER_TOL < model.CHOICE_DRIFT_TOL < 0.5
    # every reading of the program under its limit; every one of the
    # all-bfloat16 control over the precision limit, and its tokens over
    # the limit that ties the replay to the served ones
    cal = model.CALIBRATION
    assert max(cal["first_layer_differ_share"]) < model.CHOICE_DIFFER_TOL \
        < min(cal["control_first_layer_differ_share"])
    assert max(cal["second_layer_differ_share"]) < model.CHOICE_DRIFT_TOL
    assert max(cal["replay_worst_gap"]) < model.REPLAY_TIE_TOL \
        < min(cal["control_replay_worst_gap"])
    assert max(cal["gap_ratio"]) < model.GAP_RATIO
    assert max(cal["worst_gap"]) < model.LOGIT_TIE_TOL


def test_the_manifest_has_the_cell_and_it_finds_its_files():
    m = common.load_manifest()
    assert [w["name"] for w in m["workloads"]][-1] == "indexed-steady"
    assert len(m["workloads"]) == 13 and len(m["configs"]) == 12
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    files = common.cell_files(m, "indexed-steady")
    assert files["cell"]["chips"] == 1
    assert files["model"] is model and files["config"]["kind"] == "serve"
    assert files["traffic"]["kind"] == "open_loop"
    names = {x["name"] for x in files["per_layer"]}
    assert set(NEW) <= names
    # what the cell leaves to others: another model's texts and counts
    assert not names & {"kv.window_keys_share",
                        "kv.window_pages_released_per_s",
                        "step.mla_share_of_decode",
                        "step.mla_share_of_prefill",
                        "kernel.mla_decode_roofline", "step.decode_roofline"}
    for x in files["per_layer"]:
        assert callable(readers.find(x))
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert all(by_name[n]["workloads"] == ["indexed-steady"] for n in NEW)
    assert all(by_name[n]["moves"] == "tpot_p50_s" for n in NEW)
    # the contract's texts: 1 to 200 printable characters on one line (the
    # first hand-in's configuration `why` had 206 and was refused unrun)
    texts = ([c[k] for c in m["configs"] for k in ("why", "source")]
             + [w["why"] for w in m["workloads"]]
             + [x["layer"] for x in m["per_layer"]] + m["command"])
    assert all(1 <= len(t) <= 200 and t.isprintable() for t in texts)
    # every metric the other long-prompt expert cell is on and that applies
    assert names - set(NEW) == {
        x["name"] for x in m["per_layer"]
        if "longdoc-steady" in x.get("workloads", ())} - {
            "step.mla_share_of_decode", "step.mla_share_of_prefill",
            "kernel.mla_decode_roofline", "device.idle_decode_fence_share",
            "device.idle_decode_host_share", "device.idle_prefill_share",
            "device.idle_park_share", "device.idle_unplaced_share",
            "device.launch_lag_ms_p50", "device.fence_tail_ms_p50",
            "trace.clock_window_ms"}


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """Laid over the parent's checkout, the metric files read a program
    without the kernels or the counts: None, never an error."""
    files = common.cell_files(common.load_manifest(), "indexed-steady")
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
                   if '"dots3-note-prev"' in line)
    doc = json.load(open(REAL))
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in doc["reduced"]:
            assert doc["published"][key] == value and key in \
                doc["why_reduced"]
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "n_routed_experts", "vocab_size",
                              "max_position_embeddings"]
    assert doc["layer_types"] == row["config"]["layer_types"][:5]
    assert (doc["router_width"], doc["experts_held_from"]) == (256, 0)
    for key in ("assumed", "deployment", "guarantees"):
        assert doc[key]
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == doc["name"])
    assert entry["reduced"] == doc["reduced"]
    assert entry["source"] == doc["source"]


def _unit_scale(params):
    """Variance-preserving weights at the tiny widths (as
    tests/test_zz_dots3_note.py): normal(0.02) hides errors there."""
    big = ("kernel", "experts_gate", "experts_up", "experts_down", "router")

    def fix(p, leaf):
        if p[-1].key in big:
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if p[-1].key == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        if p[-1].key == "embed_tokens":
            return leaf / 0.02
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


def _tiny():
    cfg = model.program_config(_load("configs", "tiny-dots3-note"))
    return cfg, _unit_scale(model.init_params(cfg, 3))


def test_the_reference_against_the_program_and_the_control_apart():
    """The uncached program (absorbed, a gather of the chosen) and the
    reference (expanded, a mask from a sort) agree to the order of their
    sums; the bfloat16 control does not."""
    from lzy_tpu.models import dots3_note as program

    cfg, params = _tiny()
    assert cfg.experts_held == (4, 8) and cfg.n_routed_experts == 16
    assert params["layer_1_moe"]["experts_gate"].shape[0] == 4
    toks = jnp.asarray([np.random.default_rng(1).integers(
        1, cfg.vocab_size, 48).tolist()])
    rows = jnp.arange(48)
    got = np.asarray(program.Dots3Note(cfg).apply({"params": params},
                                                  toks)[0])
    want = np.asarray(model.reference_logits(params, toks, rows, cfg))
    assert np.abs(got - want).max() < 2e-4
    control = np.asarray(model.reference_logits(params, toks, rows, cfg,
                                                jnp.bfloat16))
    assert np.abs(control - want).max() > 5e-3


def test_the_references_choice_is_a_direct_sum_and_a_sort():
    """One full layer's index by hand, in float64: ``I(t, s) = sum_j w_j
    relu(q_j . k(s))`` from the same weights, the 8 largest a query by a
    stable sort; the reference's mask at every position is that."""
    cfg, params = _tiny()
    toks = jnp.asarray([np.random.default_rng(2).integers(
        1, cfg.vocab_size, 40).tolist()])
    rows = np.arange(40)
    _, chose = model.reference(params, toks, rows, cfg)
    w = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                               params["layer_0"])
    x = np.asarray(params["embed_tokens"], np.float64)[np.asarray(toks[0])]
    scale = np.asarray(params["layer_0_norm"]["scale"], np.float64)
    u = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + cfg.norm_eps) * scale
    cq = u @ w["q_a_proj"]["kernel"]
    cq = cq / np.sqrt((cq ** 2).mean(-1, keepdims=True) + cfg.norm_eps) \
        * w["q_a_norm"]["scale"] * (cfg.d_model / cfg.q_lora_rank) ** 0.5
    pos = jnp.arange(40)
    qi = np.asarray(model.rotary(jnp.asarray(
        (cq @ w["index_q_proj"]["kernel"]).reshape(40, 2, 16), jnp.float32),
        pos, cfg.rope_theta, 8), np.float64)
    k = u @ w["index_k_proj"]["kernel"]
    k = k - k.mean(-1, keepdims=True)
    k = k / np.sqrt((k ** 2).mean(-1, keepdims=True) + 1e-6) \
        * w["index_k_norm"]["scale"] + w["index_k_norm"]["bias"]
    ki = np.asarray(model.rotary(jnp.asarray(k, jnp.float32), pos,
                                 cfg.rope_theta, 8), np.float64)
    wi = u @ w["index_w_proj"]["kernel"]
    score = np.einsum("tjs,tj->ts", np.maximum(
        np.einsum("tjd,sd->tjs", qi, ki), 0.0), wi)
    mine = np.asarray(chose[0])
    for t in range(40):
        order = np.lexsort((np.arange(t + 1), -score[t, :t + 1]))[:8]
        assert set(np.nonzero(mine[t])[0]) == set(order.tolist()), t


def _harness_says_correct(logits, tokens):
    gap = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
    return float(gap.max()) <= model.LOGIT_TIE_TOL


def test_the_other_limits_reach_the_harness_as_one_comparison():
    """A run whose tokens sit more than ``GAP_RATIO`` times as far below
    the reference's best as its bfloat16 control's do, or whose indexer's
    choices are not the reference's, comes out not correct, though no token
    is over ``LOGIT_TIE_TOL``."""
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
    sound = [model.CHOICE_DIFFER_TOL / 2, model.CHOICE_DRIFT_TOL / 2]
    assert _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged * 1.5, sound), served)
    assert _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged, sound), served)    # as its control
    assert not _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged / (model.GAP_RATIO * 1.1), sound),
        served)
    # the first full layer over the precision limit; a later one over the
    # guard (and under it: a later layer is not held to the first's limit)
    assert not _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged * 1.5,
        [model.CHOICE_DIFFER_TOL * 1.01, sound[1]]), served)
    assert _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged * 1.5,
        [sound[0], model.CHOICE_DIFFER_TOL * 10]), served)
    assert not _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged * 1.5,
        [sound[0], model.CHOICE_DRIFT_TOL * 1.01]), served)
    # a served token the replay does not rank first
    assert not _harness_says_correct(model.held_to_the_limits(
        exact, served, judged, judged * 1.5, sound,
        model.REPLAY_TIE_TOL * 1.01), served)
    # fewer judged tokens than the limit is held over: not held yet
    assert _harness_says_correct(model.held_to_the_limits(
        exact[:100], served[:100], judged[:100], judged[:100] / 5, sound),
        served[:100])
    # a failed limit reaches the harness whatever the tokens: the
    # reference's own best among them
    best = exact.argmax(axis=-1)
    assert model.harness_says_correct(exact, best)
    assert not model.harness_says_correct(model.held_to_the_limits(
        exact, best, judged * 0, judged, [1.0, 1.0]), best)


def test_logits_at_keeps_the_runs_tally_and_replays_the_choices(monkeypatch):
    """The replay runs in the engine's shapes (here: 3 slots, a table of 16
    pages of 8, chunks of 16): the prompt through the batch-1 prefill program,
    the served tokens through the decode program of 3 rows, one live."""
    cfg, params = _tiny()
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size, 128)
    padded = jnp.asarray([toks.tolist()])
    rows = jnp.arange(40, 46)
    for name in ("_JUDGED", "_CHOICES", "_CONTROL_CHOICES", "_REPLAY_GAPS"):
        monkeypatch.setattr(model, name, [])
    with pytest.raises(LookupError, match="0 engines"):
        model.engine_shapes(params)
    monkeypatch.setattr(model, "engine_shapes", lambda params: {
        "slots": 3, "pages_per_seq": 16, "page_size": 8, "chunk": 16,
        "kernel": "lax"})
    # the "served" tokens: the reference's own, one after another
    for row in range(40, 46):
        padded = padded.at[0, row + 1].set(int(np.asarray(
            model.reference_logits(params, padded, [row], cfg)).argmax()))
    want = np.asarray(model.reference_logits(params, padded, rows, cfg))
    got = np.asarray(model.logits_at(params, padded, rows, cfg))
    assert (got == want).all() and len(model._JUDGED) == 1
    mine, control = model._JUDGED[0]
    assert mine.shape == control.shape == (6,) and (control >= 0).all()
    # six judged positions x 8 chosen a full layer, none differing; the
    # served tokens are the replay's own largest
    assert model._CHOICES == [[(0, 48), (0, 48)]]
    assert model.differ_shares(model._CHOICES) == [0.0, 0.0]
    assert model._REPLAY_GAPS == [0.0]                    # no tie among six
    masks, own = model.program_replay(
        params, padded, cfg, rows=np.arange(3, 46), slots=2,
        pages_per_seq=2, page_size=64, chunk=8, kernel="lax")
    assert [m.shape for m in masks] == [(43, 46)] * 2
    assert masks[0].sum(axis=1).tolist() == [min(p + 1, 8)
                                             for p in range(3, 46)]
    assert np.abs(own - np.asarray(model.reference_logits(
        params, padded, np.arange(3, 46), cfg))).max() < 2e-4
    # a planted fault: the first 8 positions in place of the best
    first = [np.zeros_like(m) for m in masks]
    for m in first:
        m[:, :8] = True
    assert min(model.differ_shares([model.choices_differ(first, masks)])) \
        > model.CHOICE_DRIFT_TOL
    with pytest.raises(ValueError, match="12 pages of a table of 8"):
        model.program_replay(params, padded, cfg, rows=np.arange(90, 96),
                             slots=2, pages_per_seq=8, page_size=8, chunk=8,
                             kernel="lax")


def test_init_params_is_the_programs_initialiser_a_layer_at_a_time():
    """The shapes and types are the program's own initialiser's; no two
    layers are the same draw; the same seed gives the same weights."""
    from lzy_tpu.models import dots3_note as program

    cfg = model.program_config(_load("configs", "tiny-dots3-note"))
    mine = model.init_params(cfg, 7)
    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), mine) \
        == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes)
    assert not np.allclose(np.asarray(mine["layer_1_moe"]["router"]),
                           np.asarray(mine["layer_2_moe"]["router"]))
    assert not np.allclose(np.asarray(mine["layer_2"]["o_proj"]["kernel"]),
                           np.asarray(mine["layer_3"]["o_proj"]["kernel"]))
    again = model.init_params(cfg, 7)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(again)):
        assert (np.asarray(a) == np.asarray(b)).all()
    large = model.init_params(cfg, 2 ** 31 + 7)          # a driver's seed
    assert np.isfinite(np.asarray(large["lm_head"])).all()


def test_what_the_program_cannot_honour_is_refused():
    doc = _load("configs", "tiny-dots3-note")
    model.program_config(doc)
    for key, value in (("scoring_func", "softmax"),
                       ("attention_gate_type", "elementwise"),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            model.program_config({**doc, key: value})


def test_counts_at_the_published_widths():
    cfg = model.program_config(json.load(open(REAL)))
    assert (cfg.n_layers, cfg.expert_layers, cfg.n_held) == (5, 4, 32)
    assert (cfg.vocab_size, cfg.max_seq_len) == (19008, 50176)
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (2, 3, 513)
    # the pools: 640 + 128 lanes a full layer, 1,152 a sliding layer
    assert (cfg.kv_token_bytes(), cfg.window_token_bytes()) == (1536, 2304)
    # a token of a row at the longest context: 3,072 in the paged pool and
    # 3 x 2,304 x 513 / 50,176 = 70 in the window pool
    assert model.kv_bytes_per_token(cfg) == 3072 + 70
    assert model.latent_values(cfg, False) == 576
    assert model.latent_values(cfg, True) == 1088
    assert model.expert_bytes(cfg) == 47_185_920        # 3 x 5120 x 1536 x 2
    assert model.routed_param_bytes(cfg) == 4 * 32 * 47_185_920
    # a quarter of the held experts: 8 a layer, 4 layers
    experts = 4 * 8 * 47_185_920
    assert model.experts_step_bytes(cfg, 3, 0.25) == experts
    with pytest.raises(TypeError):                       # never an expectation
        model.experts_step_bytes(cfg, 3)
    # three rows that each see 20,000 positions: 256 bytes a position a
    # full layer; and read 2,048 chosen: 1,152 bytes a token a full layer
    assert model.index_step_bytes(cfg, 3, 20_000.0) == 3 * 20_000 * 2 * 256
    assert model.chosen_step_bytes(cfg, 3, 2048.0) == 3 * 2048 * 2 * 1152
    # a prefill program of 256 positions from 8,192: queries at 8,192 ..
    # 8,447 see p + 1 positions; 16,384 operations a pair a full layer
    pairs = sum(range(8193, 8449))
    assert model.index_prefill_flops(cfg, 8192, 256) == pairs * 2 * 16_384
    assert model.chosen_prefill_flops(cfg, 8192, 256) \
        == 256 * 2048 * 2 * 278_528
    # one that crosses index_topk: positions 1,920 .. 2,175
    assert model.index_prefill_flops(cfg, 1920, 256) \
        == sum(range(2049, 2177)) * 2 * 16_384
    assert model.chosen_prefill_flops(cfg, 1920, 256) == (
        sum(range(1921, 2049)) + 128 * 2048) * 2 * 278_528
    assert model.index_prefill_flops(cfg, 0, 256) == 0
    # a row at position 19,999: 2 x 256 x 20,000 of index keys, 2 x 1,152 x
    # 2,048 of chosen latents, 3 x 2,176 x 513 of window latents
    row = 2 * 256 * 20_000 + 2 * 1152 * 2048 + 3 * 2176 * 513
    assert model.row_context_bytes(cfg, 19_999) == row
    assert model.row_context_bytes(cfg, 99) == 100 * (512 + 2304 + 6528)
    # the program's parameter bytes at these widths (counted from shapes):
    # 4.087 B parameters, 8.17 GB (ISSUE 62's reckoning), within 0.1%
    from lzy_tpu.models import dots3_note as program

    shapes = jax.eval_shape(lambda: program.init_params(
        cfg, jax.random.PRNGKey(0)))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes))
    assert abs(param_bytes / 8.17e9 - 1) < 2e-3
    outside = param_bytes - 4 * 32 * 47_185_920 - 19008 * 5120 * 2
    assert abs(outside / 1.94e9 - 1) < 0.02     # 1.74 GB + the head's slice
    want = outside + experts + 3 * row
    got = model.decode_step_bytes(cfg, param_bytes, 60_000, 3, 0.25)
    assert abs(got - want) < 1.0
    assert 3.4e9 < got < 3.6e9                           # 4.3 ms at 819 GB/s
    assert model.decode_step_bytes(cfg, param_bytes, 0, 0, 0.0) == outside
    with pytest.raises(TypeError):
        model.decode_step_bytes(cfg, param_bytes, 60_000, 3)


def _emit(end, rows, context, touched=0):
    return {"name": "engine.decode.emit", "start": end - 0.001, "end": end,
            "attrs": {"rows": rows, "model_stats": {
                "lzy_latent_visible_tokens_total": 2 * context,
                "lzy_latent_chosen_tokens_total": 2 * rows * 2048,
                "lzy_latent_select_rows_total": 2 * rows,
                "lzy_latent_rows_total": 2 * rows,
                "lzy_moe_experts_touched_total": touched,
                "lzy_moe_experts_held_total": 4 * 32}}}


def _prefill(end, start, tokens):
    return {"name": "engine.prefill", "start": end - 0.01, "end": end,
            "attrs": {"start": start, "tokens": tokens}}


def test_the_rooflines_charge_what_the_rounds_and_the_programs_counted():
    """Two traced rounds of 2 and 4 rows that saw 40,000 and 80,000 cached
    positions a layer: 3 rows a round at 20,000 visible a row; two prefill
    programs of 256 from 8,192 and 8,448."""
    cfg = model.program_config(json.load(open(REAL)))
    files = common.cell_files(common.load_manifest(), "indexed-steady")
    by_name = {x["name"]: x for x in files["per_layer"]}
    obs = {"trace": {
        "modules": {"jit_decode_step": [0.004, 0.005],
                    "jit_prefill_step": [0.012, 0.013]},
        "ops": {"jit_decode_step:latent_index_decode": (0.0008, 4),
                "jit_decode_step:mla_paged_decode": (0.0004, 4),
                "jit_decode_step:fusion.1": (0.007, 90),
                "jit_prefill_step:latent_index_prefill": (0.004, 4),
                "jit_prefill_step:mla_paged_decode": (0.006, 4),
                "jit_prefill_step:fusion.2": (0.015, 90)}},
        "trace_span": (0.0, 1.0), "device_kind": "TPU v5 lite",
        "spans": [_emit(0.3, 2, 40_000), _emit(0.6, 4, 80_000),
                  _emit(1.5, 9, 99_000),                 # past the span
                  _prefill(0.2, 8192, 256), _prefill(0.4, 8448, 256),
                  _prefill(1.4, 8704, 256)],
        "counters": {"lzy_latent_chosen_tokens_total": 2048.0,
                     "lzy_latent_visible_tokens_total": 20_000.0,
                     "lzy_kv_window_pages_released_total": 102.0},
        "t_open": 0.0, "t_close": 51.0,
        "model": {"module": model, "cfg": cfg}}

    def read(name):
        return readers.read(by_name[name], obs)

    need = model.index_step_bytes(cfg, 3, 20_000.0)
    want = 100.0 * (need / 819e9) * 2 / 0.0008
    assert abs(read("kernel.latent_index_roofline") - want) < 1e-6
    need = model.chosen_step_bytes(cfg, 3, 2048.0)
    want = 100.0 * (need / 819e9) * 2 / 0.0004
    assert abs(read("kernel.latent_chosen_decode_roofline") - want) < 1e-6
    flops = model.index_prefill_flops(cfg, 8192, 256) \
        + model.index_prefill_flops(cfg, 8448, 256)
    want = 100.0 * (flops / 197e12) / 0.004
    got = read("kernel.latent_index_prefill_roofline")
    assert abs(got - want) < 1e-6 and 0 < got < 100
    flops = 2 * model.chosen_prefill_flops(cfg, 8192, 256)
    want = 100.0 * (flops / 197e12) / 0.006
    got = read("kernel.latent_chosen_prefill_roofline")
    assert abs(got - want) < 1e-6 and 0 < got < 100
    assert abs(read("step.index_share_of_decode")
               - 100.0 * 0.0008 / 0.009) < 1e-9
    assert abs(read("step.chosen_read_share_of_prefill")
               - 100.0 * 0.006 / 0.025) < 1e-9
    assert abs(read("kv.latent_chosen_share") - 0.1024) < 1e-9
    assert abs(read("kv.window_latent_pages_released_per_s") - 2.0) < 1e-9

"""ZAYA1 on the serving path: the model against the benchmark's plain float32
reference (compressed convolutional attention with its carried window, one
expert a token behind an MLP router with a carry between layers, residual
scaling), the guards that fail the comparison when a part is left out or
computed in a lower precision, and the model through ``PagedInferenceEngine``
(a window leaf a layer beside the paged pool). Tiny widths, seeded weights,
CPU, Pallas kernels interpreted (``tests/conftest.py``)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import zaya as ref
from lzy_tpu.models import experts, serving
from lzy_tpu.models import zaya as zm
from lzy_tpu.ops import cca
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops.paged_attention import CHUNK_PATH
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import StateLeavesUnsupported
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths; at the tiny ones it would shrink every sublayer's output under
    the stream's biases, every row would reach one expert and a lost window
    would hide under the tolerance. Rescale each matrix to fan_in ** -0.5 and
    the tied embedding to hidden ** -0.5 (logits of unit variance)."""
    def fix(path, leaf):
        name = path[-1].key
        if name in ("kernel", "router_down") or name.startswith("experts_"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name == "embed_tokens":
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = zm.ZayaConfig.tiny()
    return cfg, _unit_scale(zm.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _forward(cfg, params, toks):
    return np.asarray(zm.Zaya(cfg).apply({"params": params}, toks)[0])


def _want(tiny, toks):
    cfg, params = tiny
    return np.asarray(ref.reference_logits(
        params, toks, jnp.arange(toks.shape[1]), cfg))


# -- the configuration --------------------------------------------------------

def test_it_answers_the_serving_protocol():
    cfg = zm.ZayaConfig()
    assert cfg.serving_config() is cfg
    assert cfg.kv_token_bytes(None) == 1024 and cfg.kv_layers == 40
    assert (cfg.window_width, cfg.n_held, cfg.widest_prefill) \
        == (1408, 16, 256)
    assert cfg.read_path("pallas", t=1) == "pallas"
    assert cfg.read_path("pallas", t=256) == CHUNK_PATH
    assert cfg.read_path("lax", t=1) == "lax"
    assert cfg.kernel_paths(1) == (cca.UPDATE_PATH, gexp.PATH)
    assert cfg.kernel_paths(256) == (cca.MIX_PATH, gexp.PATH)
    with pytest.raises(ValueError, match="kv_quant"):
        cfg.paged_model(page_size=16, kv_pages=3, kernel="lax",
                        kv_quant="int8")
    with pytest.raises(ValueError, match="kv_quant"):
        cfg.check_kernels(slots=4, kv_quant="int8")
    assert zm.Zaya.CACHE_KINDS == {
        "k": "paged", "v": "paged", "index": "index", "window": "state"}
    assert [c.name for c in zm.Zaya.STATS] == [
        "lzy_moe_assignments_total", "lzy_moe_held_assignments_total",
        "lzy_moe_experts_touched_total", "lzy_moe_experts_held_total",
        "lzy_attn_full_keys_total", "lzy_attn_rows_total",
        "lzy_cca_rows_total"]
    with pytest.raises(ValueError, match="one expert a token"):
        zm.ZayaConfig(top_k=2)
    with pytest.raises(ValueError, match="experts_held"):
        zm.ZayaConfig(experts_held=(8, 24))


def _config_doc():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "zaya1-8b-serve-l24.json")
    with open(path) as f:
        return json.load(f)


def test_program_config_reads_the_published_widths():
    cfg = ref.program_config(_config_doc())
    assert (cfg.d_model, cfg.n_layers, cfg.expert_width) == (2048, 24, 2048)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (8, 2, 128)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.top_k,
            cfg.router_width) == (16, (0, 16), 1, 256)
    assert (cfg.rotary_fraction, cfg.rope_theta, cfg.norm_eps) \
        == (0.5, 5e6, 1e-5)
    assert (cfg.vocab_size, cfg.max_seq_len) == (262272, 4096)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert cfg.window_dtype == jnp.float32
    shapes = jax.eval_shape(
        lambda: zm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 5_519_134_896


@pytest.mark.parametrize("key,value", [
    ("num_experts_per_tok", 2), ("sliding_window", 4096), ("cca_time0", 4),
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("layer_types", ["hybrid"] * 23 + ["hybrid_sliding"]),
    ("cca_window_dtype", "bfloat16"), ("residual_dtype", "bfloat16")])
def test_program_config_refuses_what_the_program_cannot_honour(key, value):
    with pytest.raises(ValueError, match=key):
        ref.program_config(dict(_config_doc(), **{key: value}))


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: the window update, the expert product and
    both reads at 8 / 2 heads of 128, at the decode step's shapes and the
    widest chunk's."""
    doc = _config_doc()
    cfg = ref.program_config(doc)
    eng = doc["engine"]
    per_page = eng["page_size"] * cfg.kv_layers * cfg.kv_token_bytes(None)
    cfg.check_kernels(
        slots=eng["slots"], kv_blocks=eng["kv_pool_bytes"] // per_page,
        page_size=eng["page_size"],
        pages_per_seq=cfg.max_seq_len // eng["page_size"])


def test_the_window_leaf_is_float32_whatever_the_activations_are():
    model = dataclasses.replace(
        zm.ZayaConfig.tiny(), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16).paged_model(
            page_size=8, kv_pages=9, kernel="lax", kv_quant=None)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 16), jnp.int32)))["cache"]
    by_name = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        by_name.setdefault(path[-1].key, []).append(leaf)
    assert len(by_name["window"]) == 3
    assert all(s.dtype == jnp.float32 and s.shape == (2, 2 * 112)
               for s in by_name["window"])
    assert by_name["k"][0].shape == (9, 8, 2, 16)
    assert by_name["k"][0].dtype == jnp.bfloat16


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    want = _want(tiny, toks)
    assert np.abs(_forward(cfg, params, toks) - want).max() < TOL
    # and they are logits worth comparing: unit spread over the vocabulary,
    # rows that reach different experts in every layer
    assert 0.3 < float(want.std()) < 3.0
    _, _, choices = ref.features(params, toks, cfg)
    assert all(len(set(row.tolist())) >= 4 for row in np.asarray(choices))
    assert (ref.program_choices(params, toks, cfg)
            == np.asarray(choices)).all()


def _paged(cfg, kernel="pallas"):
    model = cfg.paged_model(page_size=8, kv_pages=9, kernel=kernel,
                            kv_quant=None)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]],
                        jnp.int32)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32),
                               page_table=table))["cache"])
    return model, table, cache


def _through_the_cache(model, table, cache, params, toks, first=16,
                       between=None):
    """A prefill chunk of ``first`` positions, chunks of up to 16 padded to
    16 that start from the carried window, up to position 29, then one
    position at a time through the update kernel: every position's logits,
    the last round's counts and the cache. ``between`` is applied to the
    cache after every program."""
    def run(cache, chunk, width):
        real = len(chunk)
        pad = chunk + [0] * (width - real)
        logits, upd = model.apply(
            {"params": params, "cache": cache}, jnp.asarray([pad]),
            page_table=table, valid_len=jnp.asarray([real], jnp.int32),
            mutable=["cache", "stats"])
        cache = upd["cache"]
        if len(pad) != real:            # the engine rewinds a padded index
            cache = jax.tree_util.tree_map_with_path(
                lambda p, leaf: leaf - (len(pad) - real)
                if p[-1].key == "index" else leaf, cache)
        if between is not None:
            cache = between(cache)
        return cache, np.asarray(logits[0, :real]), upd["stats"]

    got = []
    cache, out, _ = run(cache, toks[:first], first)
    got.append(out)
    at = first
    while at < 29:
        chunk = toks[at:min(at + 13, 29)]
        cache, out, _ = run(cache, chunk, 16)            # padded to 16
        got.append(out)
        at += len(chunk)
    stats = None
    for tok in toks[29:]:
        cache, out, stats = run(cache, [tok], 1)
        got.append(out)
    return np.concatenate(got), stats, cache


@pytest.mark.parametrize("kernel,first", [
    ("pallas", 16), ("pallas", 15), ("pallas", 14), ("pallas", 1),
    ("lax", 16)])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        tiny, kernel, first):
    """Logits, not tokens. Chunks that start at every position modulo 3
    (after 16, 15, 14 or one position: a chunk of one goes through the
    update kernel), pads behind ``valid_len``, then decode."""
    cfg, params = tiny
    toks = _tokens(3, 45, cfg.vocab_size)
    want = _want(tiny, jnp.asarray([toks]))
    got, stats, cache = _through_the_cache(*_paged(cfg, kernel), params,
                                           toks, first)
    assert np.abs(got - want).max() < TOL
    # the last decode position: one row of 3 layers chose an expert held
    # here, read its 45 keys and moved its window
    counts = sum(jax.tree_util.tree_leaves(stats)).tolist()
    assert counts[:2] == [3, 3] and counts[3] == 3 * 16
    assert 1 <= counts[2] <= 3 and counts[4:] == [3 * 45, 3, 3]
    # the window after the sequence is the reference's
    _, windows, _ = ref.features(params, jnp.asarray([toks]), cfg)
    mine = [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(cache)[0]
            if path[-1].key == "window"]
    assert len(mine) == cfg.n_layers
    for leaf, exact in zip(mine, windows):
        assert np.abs(np.asarray(leaf[0]) - np.asarray(exact)).max() < 1e-4


def test_a_window_kept_in_a_lower_type_fails_the_comparison(tiny):
    """The window rounded to bfloat16 after every program does not pass."""
    cfg, params = tiny
    toks = _tokens(3, 45, cfg.vocab_size)
    want = _want(tiny, jnp.asarray([toks]))
    rough = lambda cache: jax.tree_util.tree_map_with_path(
        lambda p, leaf: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
        if p[-1].key == "window" else leaf, cache)
    got, _, _ = _through_the_cache(*_paged(cfg), params, toks, between=rough)
    assert np.abs(got - want).max() > 5 * TOL


def test_a_lost_window_fails_the_comparison(tiny):
    """Every program starting from a fresh window (the carry dropped)."""
    cfg, params = tiny
    toks = _tokens(3, 45, cfg.vocab_size)
    want = _want(tiny, jnp.asarray([toks]))
    lost = lambda cache: jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.zeros_like(leaf)
        if p[-1].key == "window" else leaf, cache)
    got, _, _ = _through_the_cache(*_paged(cfg), params, toks, between=lost)
    assert np.abs(got - want).max() > 100 * TOL


def _plain_mix(window, new, v1, valid_len, mixer, *, heads, groups, dtype,
               mean=True, shift=True):
    """``cca.cca_mix`` written again, whole arrays at a time, with a switch
    for each part a guard leaves out."""
    b, t, width = new.shape
    c = mixer.b0.shape[-1]
    d = c // (heads + groups)
    seq = jnp.concatenate([window.reshape(b, 2, width), new], axis=1)
    p = seq[..., :c]
    a = mixer.w0[0] * p[:, :-1] + mixer.w0[1] * p[:, 1:] + mixer.b0
    a = a.reshape(b, t + 1, heads + groups, d)
    conv = jnp.einsum("bthi,hio->btho", a[:, :-1], mixer.w1[:, :d]) \
        + jnp.einsum("bthi,hio->btho", a[:, 1:], mixer.w1[:, d:]) \
        + mixer.b1.reshape(heads + groups, d)
    lat = new[..., :c].reshape(b, t, heads + groups, d)
    m = (lat[:, :, :heads] + jnp.repeat(lat[:, :, heads:], heads // groups,
                                        axis=2)) / 2
    mk = m.reshape(b, t, groups, heads // groups, d).mean(axis=3)
    q = conv[:, :, :heads] + (m if mean else 0.0)
    k = conv[:, :, heads:] + (mk if mean else 0.0)
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6) * d ** 0.5
    k = unit(k) * mixer.tau[:, None]
    v = jnp.concatenate(
        [v1, seq[:, 1:t + 1, c:] if shift else new[..., c:]], axis=-1)
    return (unit(q).reshape(b, t, -1), k.reshape(b, t, -1), v,
            seq[:, t:].reshape(b, -1))


@pytest.mark.parametrize("part", ["whole", "mean", "shift"])
def test_without_the_mean_or_the_value_shift_the_comparison_fails(
        tiny, monkeypatch, part):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    want = _want(tiny, toks)
    off = {} if part == "whole" else {part: False}
    monkeypatch.setattr(
        zm.cca, "cca_mix",
        lambda *a, **kw: _plain_mix(*a, **kw, **off))
    worst = np.abs(_forward(cfg, params, toks) - want).max()
    assert worst < TOL if part == "whole" else worst > 100 * TOL


def _without(params, part):
    """The weights of a program that has no ``part``: its neutral value."""
    def fix(path, leaf):
        name = path[-1].key
        if part == "temperature" and name == "temperature":
            return jnp.ones_like(leaf)
        if part == "carry" and name == "carry_scale":
            return jnp.zeros_like(leaf)
        if part == "scaling" and name.endswith(("_stream_scale",
                                                "_out_scale")):
            return jnp.ones_like(leaf)
        if part == "scaling" and name.endswith(("_stream_bias",
                                                "_out_bias")):
            return jnp.zeros_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.mark.parametrize("part", ["temperature", "carry", "scaling"])
def test_without_a_learned_part_the_comparison_fails(tiny, part):
    """The temperature, the router's carry between layers and the residual
    scaling are drawn away from their neutral values, so a program without
    them (the same program on neutral weights) is not the reference."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    got = _forward(cfg, _without(params, part), toks)
    assert np.abs(got - _want(tiny, toks)).max() > 50 * TOL


def test_a_router_in_bfloat16_fails_the_comparison(tiny, monkeypatch):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    held = zm.held_weights
    monkeypatch.setattr(
        zm, "held_weights", lambda layer, scores, *a, **kw: held(
            layer, scores.astype(jnp.bfloat16).astype(jnp.float32), *a,
            **kw))
    got = _forward(cfg, params, toks)
    assert np.abs(got - _want(tiny, toks)).max() > 5 * TOL


def test_the_weight_is_the_chosen_probability_itself(tiny):
    """One expert a token: renormalised over its choices the weight would
    always be 1."""
    cfg, params = tiny
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.d_model))
    layer = zm.RoutedExperts(cfg, 3)
    (out, r), seen = layer.apply(
        {"params": params["layer_0"]["moe"]}, u,
        mutable=["intermediates", "stats"])
    assert r.shape == (24, cfg.router_width) and out.shape == u.shape
    chosen = np.asarray(seen["intermediates"]["chosen"][0])[:, 0]
    pi, _ = ref._router(u[0], params["layer_0"]["moe"], None, cfg,
                        jnp.float32)
    want = np.argmax(np.asarray(pi) + np.asarray(
        params["layer_0"]["moe"]["router_bias"]), axis=-1)
    assert (chosen == want).all() and len(set(chosen.tolist())) > 3
    assert float(np.asarray(pi).max()) < 0.9
    assert seen["stats"]["moe"].tolist()[:2] == [24, 24]


def test_the_halves_add_up_to_the_whole_layer(tiny):
    """``(0, 8)`` and ``(8, 16)``: the router keeps its 16 outputs and its
    one expert a token, a half computes its own experts' part."""
    cfg, params = tiny
    w = params["layer_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    carry = jax.random.normal(jax.random.PRNGKey(6), (32, cfg.router_width))
    whole, r = zm.RoutedExperts(cfg).apply({"params": w}, u, carry)
    parts, counts = [], []
    for lo, hi in ((0, 8), (8, 16)):
        half = {k: v[lo:hi] if k.startswith("experts_") else v
                for k, v in w.items()}
        (out, r_half), seen = zm.RoutedExperts(dataclasses.replace(
            cfg, experts_held=(lo, hi))).apply(
            {"params": half}, u, carry, mutable=["stats"])
        assert (np.asarray(r_half) == np.asarray(r)).all()
        parts.append(np.asarray(out))
        counts.append(seen["stats"]["moe"].tolist())
    assert np.abs(parts[0] + parts[1] - np.asarray(whole)).max() < 1e-5
    assert np.abs(parts[0]).max() > 1e-3 and np.abs(parts[1]).max() > 1e-3
    # 32 rows chose one expert each; every choice fell on one half
    assert [c[0] for c in counts] == [32, 32]
    assert counts[0][1] + counts[1][1] == 32
    assert [c[3] for c in counts] == [8, 8]


# -- through the engine -------------------------------------------------------

def _engine(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("slots", 3)
    kw.setdefault("kernel", "pallas")
    return PagedInferenceEngine(
        cfg, params, page_size=8, prefill_chunk=16, **kw)


def _drain(engine, limit=600):
    for _ in range(limit):
        if not engine.step():
            return
    raise AssertionError("the engine did not go idle")


def _gap(tiny, prompt, tokens):
    """How far below the reference's best logit each served token sits."""
    cfg, params = tiny
    full = list(prompt) + list(tokens)
    logits = np.asarray(ref.reference_logits(
        params, jnp.asarray([full]),
        jnp.arange(len(prompt) - 1, len(full) - 1), cfg))
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == name:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


_LENGTHS, _BUDGETS = (37, 5, 48, 21, 9, 30), (12, 20, 6, 10, 15, 4)
_COUNTED = tuple(c.name for c in zm.Zaya.STATS) \
    + ("lzy_state_slots_reset_total",)


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, one mixed run: prompts whose last chunk is padded (37,
    5, 21, 9, 30) and not (48), a budget that splits the long prompts over
    rounds while the short ones already decode, more requests than slots so
    that slots are reused after longer requests."""
    cfg, _ = tiny
    engine = _engine(tiny, prefill_budget=16)
    engine.warmup()
    before = {n: _counter(n) for n in _COUNTED}
    prompts = [_tokens(10 + i, n, cfg.vocab_size)
               for i, n in enumerate(_LENGTHS)]
    with trace.recording() as rec:
        reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
                for p, m in zip(prompts, _BUDGETS)]
        _drain(engine)
        spans = rec.drain()
    after = {n: _counter(n) for n in before}
    yield {"engine": engine, "prompts": prompts, "reqs": reqs,
           "spans": spans,
           "counted": {n: after[n] - before[n] for n in before}}
    engine.close()


@pytest.mark.parametrize("i", range(6))
def test_engine_serves_the_references_tokens(tiny, served, i):
    req, prompt = served["reqs"][i], served["prompts"][i]
    assert req.done and req.error is None
    assert len(req.tokens) == _BUDGETS[i]
    assert _gap(tiny, prompt, req.tokens) < TOL


def test_the_window_is_spliced_inside_prefill_and_slots_start_fresh(served):
    spans = served["spans"]
    splices = [s for s in spans if s.name == trace.ENGINE_PREFILL_STATE]
    assert len(splices) == len(served["prompts"])
    by_id = {s.id: s for s in spans}
    assert all(by_id[s.parent].name == trace.ENGINE_PREFILL
               for s in splices)
    assert served["counted"]["lzy_state_slots_reset_total"] == 6


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    rows = engine.decode_rows * cfg.n_layers
    # resident rows a layer a round; idle slots and slots in the middle of
    # a prefill are not counted
    assert counted["lzy_cca_rows_total"] == rows
    assert counted["lzy_attn_rows_total"] == rows
    assert counted["lzy_moe_assignments_total"] == rows
    assert counted["lzy_moe_held_assignments_total"] == rows
    assert counted["lzy_moe_experts_held_total"] \
        == engine.decode_steps * cfg.n_layers * 16
    assert 0 < counted["lzy_moe_experts_touched_total"] <= rows
    least = sum(n + k - 1 for n, m in zip(_LENGTHS, _BUDGETS)
                for k in range(2, m + 1)) * cfg.n_layers
    assert counted["lzy_attn_full_keys_total"] >= least
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        "rows" in s.attrs and set(s.attrs["model_stats"])
        == {c.name for c in zm.Zaya.STATS} for s in emits)
    assert sum(s.attrs["model_stats"]["lzy_cca_rows_total"] for s in emits) \
        == counted["lzy_cca_rows_total"]


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (cca.UPDATE_PATH, cca.MIX_PATH, gexp.PATH, "pallas",
                 CHUNK_PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].kernel_path == "pallas"


def test_radix_match_is_zero_and_nothing_is_cached(served):
    engine = served["engine"]
    assert engine.kv.lookup_tokens > 0 and engine.kv.hit_tokens == 0
    assert engine.stats().kv_blocks_cached == 0
    again = engine.submit(served["prompts"][0], max_new_tokens=12,
                          greedy=True)
    _drain(engine)
    assert engine.kv.hit_tokens == 0
    assert again.tokens == served["reqs"][0].tokens


def test_a_reused_slot_starts_from_the_padded_start(tiny):
    cfg, _ = tiny
    engine = _engine(tiny, slots=1)
    long = _tokens(30, 60, cfg.vocab_size)
    short = _tokens(31, 7, cfg.vocab_size)
    first = engine.submit(long, max_new_tokens=25, greedy=True)
    second = engine.submit(short, max_new_tokens=9, greedy=True)
    _drain(engine)
    assert _gap(tiny, long, first.tokens) < TOL
    assert _gap(tiny, short, second.tokens) < TOL
    engine.close()


def test_a_finished_requests_window_stays_in_its_slot(tiny):
    """``state_leaves()``: a freed slot keeps what its last round left, the
    reference's window after the prompt and every served token but the last
    (emitted, never fed); another slot's stands far off."""
    cfg, params = tiny
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                  prefill_chunk=16, kernel="pallas")
    prompt = _tokens(60, 40, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=6, greedy=True)
    _drain(engine)
    leaves = engine.state_leaves()
    assert len(leaves) == cfg.n_layers
    assert all(leaf.shape == (2, 2 * cfg.window_width)
               for leaf in leaves.values())
    fed = jnp.asarray([prompt + list(req.tokens)[:-1]])
    _, windows, _ = ref.features(params, fed, cfg)
    gap = ref.window_gaps(leaves, windows, cfg)
    assert gap["slot"] == 0 and len(gap["layers"]) == cfg.n_layers
    assert max(gap["layers"]) < 1e-5
    rough = {name: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
             for name, leaf in leaves.items()}
    assert min(ref.window_gaps(rough, windows, cfg)["layers"]) > 1e-3
    engine.close()


def test_the_widest_program_carries_the_window(tiny):
    """The cell's shape: no ``prefill_chunk`` given, a budget of 256, so a
    program of 256 positions and a padded tail that starts from the carried
    window."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=512)
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                  kernel="pallas", prefill_budget=256)
    assert engine.prefill_chunk == 256
    prompt = _tokens(50, 300, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=5, greedy=True)
    _drain(engine)
    assert _gap((cfg, params), prompt, req.tokens) < TOL
    engine.close()


def test_cache_leaves_are_declared_by_kind(served):
    engine = served["engine"]
    kinds = engine._leaf_kinds
    assert kinds.count(serving.STATE) == 3              # a window a layer
    assert kinds.count(serving.PAGED) == 2 * 3          # k, v a layer
    assert kinds.count(serving.INDEX) == 3
    slots = engine.slots
    for i, leaf in enumerate(engine._payload):
        assert (leaf.shape[0] == slots) == (i in engine._state_at)


def test_llm_generate_through_the_gateway(tiny):
    from lzy_tpu import llm
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)

    cfg, _ = tiny
    fleet = ReplicaFleet(lambda: _engine(tiny, slots=2))
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(8),
                             model_name="zaya-tiny", page_size=8)
    try:
        fleet.add_replica()
        llm.configure(gateway)
        prompt = _tokens(40, 19, cfg.vocab_size)
        gen = llm.generate(prompt, max_new_tokens=7, greedy=True,
                           cache=False)
        assert gen.status == "ok" and len(gen.tokens) == 7
        assert _gap(tiny, prompt, list(gen.tokens)) < TOL
    finally:
        llm.configure(None)
        gateway.close()


@pytest.mark.parametrize("mechanism", [
    "speculation", "host tier", "storage tier", "parking", "import",
    "export", "sharded engine", "int8 pool"])
def test_each_refusal_names_its_mechanism(tiny, mechanism):
    cfg, params = tiny
    if mechanism == "speculation":
        with pytest.raises(StateLeavesUnsupported, match="speculative"):
            _engine(tiny, spec_tokens=2)
    elif mechanism == "host tier":
        with pytest.raises(StateLeavesUnsupported, match="tiered KV"):
            _engine(tiny, kv_host_tier_bytes=1 << 20)
    elif mechanism == "storage tier":
        with pytest.raises(StateLeavesUnsupported, match="tiered KV"):
            _engine(tiny, kv_storage_tier="mem://tier-refused-zaya")
    elif mechanism == "sharded engine":
        from lzy_tpu.serving.sharded import NoPartitionRules
        from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine

        with pytest.raises(NoPartitionRules, match="sharded engine"):
            ShardedPagedInferenceEngine(cfg, params, tp=2, slots=2)
    elif mechanism == "int8 pool":
        with pytest.raises(ValueError, match="kv_quant"):
            _engine(tiny, kv_quant="int8", kernel="lax")
    else:
        engine = _engine(tiny, slots=1)
        try:
            if mechanism == "parking":
                with pytest.raises(StateLeavesUnsupported, match="parking"):
                    engine.park_chain("conv:1", [1, 2, 3])
            elif mechanism == "import":
                with pytest.raises(StateLeavesUnsupported, match="import"):
                    engine.queue_kv_import(object())
            else:
                with pytest.raises(StateLeavesUnsupported, match="export"):
                    engine.request_kv_export([1, 2, 3])
        finally:
            engine.close()


def test_the_engine_names_no_model_and_no_vocabulary():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "lzy_tpu", "serving", "engine.py")) as f:
        text = f.read().lower()
    assert "zaya" not in text and "cca" not in text


def test_the_older_families_keep_their_scoring(tiny):
    """``held_weights`` takes the family's scores: a sigmoid router's are
    made by ``sigmoid_scores`` under the parameter names it always had."""
    import flax.linen as nn

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, um):
            scores, bias = experts.sigmoid_scores(self, um, 8)
            return experts.held_weights(
                self, scores, jnp.ones((um.shape[0],), bool), top_k=2,
                held=(0, 8), bias=bias, scaling=2.5)

    um = jax.random.normal(jax.random.PRNGKey(0), (12, 16))
    variables = Layer().init(jax.random.PRNGKey(1), um)
    assert set(variables["params"]) == {"router", "router_bias"}
    w = np.asarray(Layer().apply(variables, um))
    assert ((w > 0).sum(axis=1) == 2).all()
    assert np.abs(w.sum(axis=1) - 2.5).max() < 1e-5

"""Ouro on the serving path: the model against the benchmark's plain float32
reference (a stack run ``T`` times with shared weights, sandwich norms, a
key/value cache a (pass, layer), an exit gate that picks the pass the head
reads), the guards that fail the comparison when a pass reads another's keys,
and the model through ``PagedInferenceEngine`` (paged leaves whose block holds
every pass). Tiny widths, seeded weights, CPU, Pallas kernels interpreted
(``tests/conftest.py``)."""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import ouro as ref
from lzy_tpu.models import ouro as om
from lzy_tpu.models import serving
from lzy_tpu.ops.paged_attention import CHUNK_PATH
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4


def _unit_scale(params):
    """The initialiser's normal(0.02) keeps variance at the published
    widths; at the tiny ones rescale each matrix to fan_in ** -0.5, and the
    head and the gate to hidden ** -0.5: logits and gates of unit variance,
    so that passes differ and exit at different places."""
    def fix(path, leaf):
        name = path[-1].key
        if name == "kernel":
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name in ("lm_head", "exit_gate"):
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        if name == "embed_tokens":
            return leaf / 0.02
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = om.OuroConfig.tiny()
    return cfg, _unit_scale(om.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _forward(cfg, params, toks):
    logits, aux = om.Ouro(cfg).apply({"params": params}, toks,
                                     mutable=["intermediates"])
    return np.asarray(logits[0]), \
        np.asarray(aux["intermediates"]["exit_pass"][0][0])


def _want(cfg, params, toks):
    logits, t_star, sure = ref.reference(
        params, toks, np.arange(toks.shape[1]), cfg)
    return np.asarray(logits), t_star, sure


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == name:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# -- the configuration --------------------------------------------------------

def test_it_answers_the_serving_protocol():
    cfg = om.OuroConfig()
    assert cfg.serving_config() is cfg
    # a cached token: one entry a (pass, layer), 8,192 bytes each
    assert (cfg.n_layers, cfg.total_ut_steps, cfg.kv_layers) == (48, 4, 192)
    assert cfg.kv_token_bytes(None) == 8192
    assert cfg.kv_layers * cfg.kv_token_bytes(None) == 1_572_864
    assert cfg.widest_prefill == 256
    assert cfg.read_path("pallas", t=1) == "pallas"
    assert cfg.read_path("pallas", t=256) == CHUNK_PATH
    assert cfg.read_path("lax", t=1) == "lax"
    assert cfg.kernel_paths(1) == cfg.kernel_paths(256) == ()
    assert om.Ouro.CACHE_KINDS == {"index": "index"}
    assert [c.name for c in om.Ouro.STATS] == [
        "lzy_loop_rows_total", "lzy_loop_exit_pass_total",
        "lzy_attn_full_keys_total", "lzy_attn_rows_total"]


def _config_doc():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "ouro-2.6b-serve.json")
    with open(path) as f:
        return json.load(f)


def test_program_config_reads_the_published_widths():
    doc = _config_doc()
    cfg = ref.program_config(doc)
    assert (cfg.d_model, cfg.n_layers, cfg.d_ff) == (2048, 48, 5632)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.total_ut_steps, cfg.early_exit_threshold) == (4, 1.0)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    assert (cfg.vocab_size, cfg.max_seq_len) == (49152, 4096)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    shapes = jax.eval_shape(
        lambda: om.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 2_667_974_657
    # the counts the yardstick's readers take, against counts by hand
    assert ref.kv_bytes_per_token(cfg) == 1_572_864
    assert ref.stack_bytes(cfg) == 2 * 48 * 51_388_416
    # the engine's page count from the configuration's byte budget
    eng = doc["engine"]
    assert eng["kv_pool_bytes"] // (
        eng["page_size"] * cfg.kv_layers * cfg.kv_token_bytes(None)) == 384


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("use_sliding_window", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("layer_types", ["full_attention"] * 47 + ["sliding_attention"]),
    ("tie_word_embeddings", True), ("total_ut_steps", 0),
    ("early_exit_threshold", 0.0), ("early_exit_threshold", 1.5),
    ("hidden_act", "gelu")])
def test_from_published_refuses_what_the_program_cannot_honour(key, value):
    with pytest.raises(ValueError, match=key):
        ref.program_config(dict(_config_doc(), **{key: value}))


@pytest.mark.parametrize("call", ["paged_model", "check_kernels", "engine"])
def test_an_int8_pool_is_refused_by_name(tiny, call):
    cfg, params = tiny
    with pytest.raises(ValueError, match="kv_quant"):
        if call == "paged_model":
            cfg.paged_model(page_size=8, kv_pages=3, kernel="lax",
                            kv_quant="int8")
        elif call == "check_kernels":
            cfg.check_kernels(slots=4, kv_quant="int8")
        else:
            _engine(tiny, kv_quant="int8", kernel="lax")


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: both reads at 16 / 16 heads of 128 over the
    pool as the programs see it, ``384 x 4`` blocks."""
    doc = _config_doc()
    cfg = ref.program_config(doc)
    eng = doc["engine"]
    cfg.check_kernels(
        slots=eng["slots"], kv_blocks=384, page_size=eng["page_size"],
        pages_per_seq=cfg.max_seq_len // eng["page_size"])


def test_the_tree_holds_the_layers_once_and_the_cache_every_pass(tiny):
    cfg, params = tiny
    layers = [k for k in params["stack"] if k.startswith("layer_")]
    assert len(layers) == cfg.n_layers == 3
    model = cfg.paged_model(page_size=8, kv_pages=9, kernel="lax",
                            kv_quant=None)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 16), jnp.int32)))
    assert jax.tree_util.tree_structure(
        nn.meta.unbox(shapes["params"])) \
        == jax.tree_util.tree_structure(params)
    by_name = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            shapes["cache"])[0]:
        by_name.setdefault(path[-1].key, []).append(leaf)
    assert sorted(by_name) == ["index", "k", "v"]
    assert len(by_name["index"]) == 1
    assert len(by_name["k"]) == len(by_name["v"]) == cfg.n_layers
    # [pages, T, page, KV, D]: a block's passes side by side
    assert all(s.shape == (9, 3, 8, 4, 16) for s in by_name["k"])
    entries = sum(s.shape[1] for s in by_name["k"])
    assert entries == cfg.kv_layers == cfg.n_layers * cfg.total_ut_steps
    kinds = {serving.leaf_kind(model, p)
             for p, _ in jax.tree_util.tree_flatten_with_path(
                 shapes["cache"])[0] if p[-1].key != "index"}
    assert kinds == {"paged"}


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("threshold", [1.0, 0.6, 1e-6])
def test_forward_and_the_pass_read_are_the_references(tiny, threshold):
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, early_exit_threshold=threshold)
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    want, t_star, sure = _want(cfg, params, toks)
    got, exit_pass = _forward(cfg, params, toks)
    assert sure.sum() >= 40
    assert (exit_pass[sure] == t_star[sure]).all()
    assert np.abs(got - want)[sure].max() < TOL
    assert 0.3 < float(want.std()) < 3.0
    if threshold == 1.0:
        assert (t_star == cfg.total_ut_steps).all()
    elif threshold == 0.6:
        # the gate decides: positions leave at different passes
        assert len(set(t_star.tolist())) >= 2
    else:
        assert (t_star == 1).all()


def test_one_pass_is_the_stack_once(tiny):
    """``total_ut_steps`` 1: the stack once, the final norm, the head; the
    same parameter tree serves any number of passes."""
    cfg, params = tiny
    once = dataclasses.replace(cfg, total_ut_steps=1)
    toks = jnp.asarray([_tokens(4, 24, cfg.vocab_size)])
    got, exit_pass = _forward(once, params, toks)
    assert (exit_pass == 1).all()
    hiddens, _ = ref.features(params, toks, once)
    assert hiddens.shape[0] == 1
    want = np.asarray(ref.head_logits(params, hiddens[0]))
    assert np.abs(got - want).max() < TOL
    # and three passes are not one
    assert np.abs(_forward(cfg, params, toks)[0] - want).max() > 100 * TOL


def _paged(cfg, kernel="pallas", rows=1):
    model = cfg.paged_model(page_size=8, kv_pages=9, kernel=kernel,
                            kv_quant=None)
    table = np.zeros((rows, 16), np.int32)
    table[0, :6] = [1, 2, 3, 4, 5, 6]
    table = jnp.asarray(table)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((rows, 1), jnp.int32),
                               page_table=table))["cache"])
    return model, table, cache


def _through_the_cache(model, table, cache, params, toks, first=16,
                       between=None):
    """A prefill chunk of ``first`` positions, chunks of up to 13 padded to
    16, up to position 29, then one position at a time: every position's
    logits, the last round's counts and the cache. ``between`` is applied to
    the cache after the prefill's last program."""
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
    if between is not None:
        cache = between(cache)
    stats = None
    for tok in toks[29:]:
        cache, out, stats = run(cache, [tok], 1)
        got.append(out)
    return np.concatenate(got), stats, cache


@pytest.mark.parametrize("kernel,first", [
    ("pallas", 16), ("lax", 15), ("lax", 1), ("lax", 16)])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        tiny, kernel, first):
    """Logits, not tokens, at every position: the program's ``T`` caches a
    layer against a reference that has none."""
    cfg, params = tiny
    toks = _tokens(3, 34, cfg.vocab_size)
    want, _, _ = _want(cfg, params, jnp.asarray([toks]))
    got, stats, _ = _through_the_cache(*_paged(cfg, kernel), params, toks,
                                       first)
    assert np.abs(got - want).max() < TOL
    # the last decode position: one row, read from the last pass, and its
    # 34 keys in each of the 9 (pass, layer)
    assert sum(jax.tree_util.tree_leaves(stats)).tolist() \
        == [1, 3, 9 * 34, 9]


def _pool_leaves(fix):
    """``fix`` applied to every ``k`` and ``v`` leaf of a cache."""
    return lambda cache: jax.tree_util.tree_map_with_path(
        lambda p, leaf: fix(leaf) if p[-1].key in ("k", "v") else leaf,
        cache)


@pytest.mark.parametrize("spoil", ["pass_1_zeroed", "pass_0_for_all"])
def test_a_pass_reads_its_own_keys(tiny, spoil):
    """After the prefill, pass 1's entries zeroed, or pass 0's entries
    copied over every pass's: the decode that follows is not the
    reference's."""
    cfg, params = tiny
    toks = _tokens(3, 34, cfg.vocab_size)
    want, _, _ = _want(cfg, params, jnp.asarray([toks]))
    fix = (lambda leaf: leaf.at[:, 1].set(0)) if spoil == "pass_1_zeroed" \
        else (lambda leaf: jnp.broadcast_to(leaf[:, :1], leaf.shape))
    got, _, _ = _through_the_cache(*_paged(cfg, "lax"), params, toks,
                                   between=_pool_leaves(fix))
    assert np.abs(got[:29] - want[:29]).max() < TOL
    assert np.abs(got[29:] - want[29:]).max() > 100 * TOL


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_an_idle_slot_and_a_pad_write_the_scratch_block_alone(tiny, kernel):
    cfg, params = tiny
    model, table, cache = _paged(cfg, kernel, rows=2)
    toks = _tokens(5, 16, cfg.vocab_size)

    def pools(cache):
        return [np.asarray(leaf) for p, leaf in
                jax.tree_util.tree_flatten_with_path(cache)[0]
                if p[-1].key in ("k", "v")]

    # a chunk of 16 of which 5 are real, beside an idle slot
    _, upd = model.apply(
        {"params": params, "cache": cache},
        jnp.asarray([toks, [0] * 16]), page_table=table,
        valid_len=jnp.asarray([5, 0], jnp.int32), mutable=["cache"])
    for leaf in pools(upd["cache"]):
        # the row's first block: its 5 real positions in every pass
        assert (np.abs(leaf[1, :, :5]).sum(axis=(2, 3)) > 0).all()
        assert not leaf[1, :, 5:].any() and not leaf[2:].any()
        # the pads and the idle slot: the scratch block, in every pass
        assert (np.abs(leaf[0]).sum(axis=(1, 2, 3)) > 0).all()
    # a decode round: the live row writes position 5, the idle slot scratch
    cache = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.asarray([5, 0], jnp.int32)
        if p[-1].key == "index" else leaf, upd["cache"])
    before = pools(cache)
    logits, upd = model.apply(
        {"params": params, "cache": cache}, jnp.asarray([[7], [0]]),
        page_table=table, valid_len=jnp.asarray([1, 0], jnp.int32),
        mutable=["cache", "stats"])
    for old, new in zip(before, pools(upd["cache"])):
        changed = np.argwhere(np.abs(new - old).sum(axis=(3, 4)) > 0)
        assert {(int(b), int(o)) for b, _, o in changed} <= {(1, 5), (0, 0)}
        assert {int(t) for b, t, _ in changed if b == 1} == {0, 1, 2}
    assert sum(jax.tree_util.tree_leaves(upd["stats"])).tolist() \
        == [1, 3, 9 * 6, 9]
    if kernel == "pallas":
        # the decode read gives an idle row 0, in every pass: its logits
        # are those of a row that attended to nothing
        assert np.isfinite(np.asarray(logits)).all()


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
        np.arange(len(prompt) - 1, len(full) - 1), cfg))
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


_LENGTHS, _BUDGETS = (37, 5, 48, 21, 9, 30), (6, 10, 3, 5, 8, 4)
_COUNTED = tuple(c.name for c in om.Ouro.STATS)


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, one mixed run: prompts whose last chunk is padded and
    not, a budget that splits the long prompts over rounds while the short
    ones already decode, more requests than slots."""
    cfg, _ = tiny
    engine = _engine(tiny, prefill_budget=16)
    engine.warmup()
    before = {n: _counter(n) for n in _COUNTED}
    prompts = [_tokens(10 + i, n, cfg.vocab_size)
               for i, n in enumerate(_LENGTHS)]
    reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
            for p, m in zip(prompts, _BUDGETS)]
    _drain(engine)
    after = {n: _counter(n) for n in before}
    yield {"engine": engine, "prompts": prompts, "reqs": reqs,
           "counted": {n: after[n] - before[n] for n in before}}
    engine.close()


@pytest.mark.parametrize("i", range(6))
def test_engine_serves_the_references_tokens(tiny, served, i):
    req, prompt = served["reqs"][i], served["prompts"][i]
    assert req.done and req.error is None
    assert len(req.tokens) == _BUDGETS[i]
    assert _gap(tiny, prompt, req.tokens) < TOL


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    rows = engine.decode_rows
    assert counted["lzy_loop_rows_total"] == rows
    # the published threshold: every row is read from the last pass
    assert counted["lzy_loop_exit_pass_total"] == rows * cfg.total_ut_steps
    assert counted["lzy_attn_rows_total"] == rows * cfg.kv_layers
    least = sum(n + k - 1 for n, m in zip(_LENGTHS, _BUDGETS)
                for k in range(2, m + 1)) * cfg.kv_layers
    assert counted["lzy_attn_full_keys_total"] >= least


def test_pages_are_sized_by_every_pass(tiny, served):
    """The engine's page count from a byte budget: a page is ``page x
    kv_layers x kv_token_bytes``, whatever the model's layers."""
    cfg, params = tiny
    engine = served["engine"]
    assert engine.kernel_path == "pallas"
    assert engine.kv.reuse                      # every leaf is paged
    per_page = 8 * cfg.kv_layers * cfg.kv_token_bytes(None)
    assert per_page == 8 * 9 * 2 * 4 * 16 * 4
    sized = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                 kernel="lax", kv_pool_bytes=11 * per_page)
    try:
        assert sized.kv.pool.n_blocks == 11
        assert sized._payload[0].shape == (11, 3, 8, 4, 16)
        assert sized.stats().kv_token_bytes == 9 * 2 * 4 * 16 * 4
    finally:
        sized.close()


def test_a_threshold_under_one_is_served_and_counted(tiny):
    """At 0.6 rows leave at different passes: the served tokens are the
    reference's, and the counted sum of ``t*`` is the reference's over the
    decode positions (an over-run round's row is counted too: the bound of
    ``benchmark/models/ouro.py`` ``exit_slack``)."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, early_exit_threshold=0.6)
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                  prefill_chunk=16, kernel="lax")
    try:
        before = [_counter(n) for n in _COUNTED[:2]]
        prompt = _tokens(21, 19, cfg.vocab_size)
        req = engine.submit(prompt, max_new_tokens=14, greedy=True)
        _drain(engine)
        rows, total = (_counter(n) - b for n, b in zip(_COUNTED[:2], before))
    finally:
        engine.close()
    full = prompt + list(req.tokens)
    at = np.arange(len(prompt) - 1, len(full) - 1)
    logits, t_star, sure = ref.reference(params, jnp.asarray([full]), at, cfg)
    logits = np.asarray(logits)
    gap = logits.max(-1) - logits[np.arange(len(at)), req.tokens]
    assert gap[sure].max() < TOL
    assert len(set(t_star.tolist())) >= 2
    assert sure[1:].all()
    assert ref.exit_slack(rows, total, len(at) - 1, int(t_star[1:].sum()),
                          cfg.total_ut_steps) == 0
    assert rows >= len(at) - 1 and total < rows * cfg.total_ut_steps


def test_a_shared_prefix_gives_the_cold_prefills_tokens(tiny):
    """A prompt whose first blocks are served from the radix cache (every
    pass of a shared block) decodes what a cold engine decodes."""
    cfg, _ = tiny
    first = _tokens(30, 35, cfg.vocab_size)
    second = first[:24] + _tokens(31, 9, cfg.vocab_size)
    warm = _engine(tiny, kernel="lax")
    try:
        a = warm.submit(first, max_new_tokens=4, greedy=True)
        _drain(warm)
        hits = warm.kv.hit_tokens
        b = warm.submit(second, max_new_tokens=8, greedy=True)
        _drain(warm)
        assert warm.kv.hit_tokens - hits == 24
    finally:
        warm.close()
    cold = _engine(tiny, kernel="lax")
    try:
        c = cold.submit(second, max_new_tokens=8, greedy=True)
        _drain(cold)
    finally:
        cold.close()
    assert a.error is None and list(b.tokens) == list(c.tokens)
    assert _gap(tiny, second, b.tokens) < TOL


def test_export_and_import_move_every_pass_of_a_block(tiny):
    cfg, _ = tiny
    prompt = _tokens(32, 27, cfg.vocab_size)
    source, sink = _engine(tiny, kernel="lax"), _engine(tiny, kernel="lax")
    try:
        req = source.submit(prompt, max_new_tokens=3, greedy=True)
        _drain(source)
        export = source.kv_io.export_kv(prompt)
        assert export.n_blocks == 3 and export.tokens == prompt[:24]
        assert len(export.leaves) == 2 * cfg.n_layers
        # a block's rows carry the pass axis: [blocks, T, page, KV, D]
        assert all(v.shape == (3, 3, 8, 4, 16)
                   for v in export.leaves.values())
        assert all(np.abs(v).sum(axis=(2, 3, 4)).all()
                   for v in export.leaves.values())
        assert sink.kv_io.import_kv(export) == 3
        assert sink.kv.match_len(prompt) == 24
        again = sink.submit(prompt, max_new_tokens=3, greedy=True)
        _drain(sink)
        assert list(again.tokens) == list(req.tokens)
        back = sink.kv_io.export_kv(prompt)
        for key, rows in export.leaves.items():
            assert np.array_equal(np.asarray(rows),
                                  np.asarray(back.leaves[key]))
    finally:
        source.close()
        sink.close()


@pytest.mark.parametrize("mechanism", [
    "speculation", "parking", "host tier", "sharded engine"])
def test_each_mechanism_moves_a_blocks_passes_together(tiny, mechanism):
    """What moves pages by block id and an index serves a block of ``T``
    passes as it serves any paged leaf (``docs/serving.md`` has the table);
    the sharded engine refuses by name."""
    cfg, params = tiny
    prompt = _tokens(50, 21, cfg.vocab_size)
    if mechanism == "sharded engine":
        from lzy_tpu.serving.sharded import (
            NoPartitionRules, ShardedPagedInferenceEngine)

        with pytest.raises(NoPartitionRules, match="sharded engine"):
            ShardedPagedInferenceEngine(cfg, params, tp=2, slots=2)
        return
    kw = {"speculation": {"spec_tokens": 2},
          "host tier": {"kv_host_tier_bytes": 1 << 22, "kv_blocks": 12,
                        "slots": 1},
          "parking": {"slots": 1}}[mechanism]
    engine = _engine(tiny, kernel="lax", **kw)
    try:
        req = engine.submit(prompt, max_new_tokens=10, greedy=True)
        _drain(engine)
        assert req.error is None and _gap(tiny, prompt, req.tokens) < TOL
        if mechanism == "speculation":
            assert engine.spec_proposed > 0
        elif mechanism == "parking":
            assert engine.park_chain("conv:1", prompt[:16])
            assert engine.unpark_chain("conv:1")
        else:
            # another prompt takes every usable block: the first one's
            # blocks are demoted, all passes, and come back when it returns
            other = engine.submit(_tokens(51, 80, cfg.vocab_size),
                                  max_new_tokens=2, greedy=True)
            _drain(engine)
            assert other.error is None and engine.kv_tier_demotions >= 2
            again = engine.submit(prompt, max_new_tokens=10, greedy=True)
            _drain(engine)
            assert engine.kv_tier_promotions >= 2
            assert list(again.tokens) == list(req.tokens)
    finally:
        engine.close()


def test_llm_generate_through_the_gateway(tiny):
    from lzy_tpu import llm
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)

    cfg, _ = tiny
    fleet = ReplicaFleet(lambda: _engine(tiny, slots=2))
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(8),
                             model_name="ouro-tiny", page_size=8)
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


def test_the_engine_names_no_model():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "lzy_tpu", "serving", "engine.py")) as f:
        text = f.read().lower()
    assert "ouro" not in text and "total_ut_steps" not in text

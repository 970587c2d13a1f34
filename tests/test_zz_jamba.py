"""Jamba on the serving path: the model against the benchmark's plain float32
reference (the per-position selective recurrence, attention with no
positional embedding at a group of 5), the precision guards, and the model
through ``PagedInferenceEngine`` (two state leaves a Mamba layer beside the
paged pool). Tiny widths, seeded weights, CPU, Pallas kernels interpreted
(``tests/conftest.py``)."""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import jamba as ref
from lzy_tpu.models import jamba as jm
from lzy_tpu.models import serving
from lzy_tpu.ops import mamba1
from lzy_tpu.ops.paged_attention import GROUP_CHUNK_PATH, GROUP_DECODE_PATH
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import StateLeavesUnsupported
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths (0.02 is about 2560 ** -0.5); at the tiny ones it would shrink
    every mixer's output to nothing and a lost state or a missing norm would
    hide under the tolerance. Rescale each matrix to fan_in ** -0.5, and the
    tied embedding to hidden ** -0.5 (logits of unit variance)."""
    def fix(path, leaf):
        name = path[-1].key
        if name == "kernel":
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name == "embed_tokens":
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = jm.JambaConfig.tiny()
    return cfg, _unit_scale(jm.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the configuration --------------------------------------------------------

def test_the_layer_order_is_the_two_published_keys():
    cfg = jm.JambaConfig()
    kinds = cfg.layer_kinds
    assert len(kinds) == 28
    assert [i for i, k in enumerate(kinds) if k == jm.ATTENTION] == [7, 21]
    assert (cfg.kv_layers, cfg.mamba_layers) == (2, 26)
    assert all(ref.layer_is_attention(cfg, i) == (k == jm.ATTENTION)
               for i, k in enumerate(kinds))
    tiny = jm.JambaConfig.tiny()
    assert tiny.layer_kinds.index(jm.ATTENTION) == 7
    assert (tiny.kv_layers, tiny.mamba_layers) == (1, 13)
    assert tiny.n_heads % 8 and tiny.n_kv_heads == 1
    with pytest.raises(ValueError, match="no attention layer"):
        jm.JambaConfig(n_layers=4)


def test_it_answers_the_serving_protocol():
    cfg = jm.JambaConfig()
    assert cfg.serving_config() is cfg
    assert cfg.kv_token_bytes(None) * cfg.kv_layers == 1024
    assert cfg.widest_prefill == 256
    assert cfg.read_path("pallas", t=1) == GROUP_DECODE_PATH
    assert cfg.read_path("pallas", t=256) == GROUP_CHUNK_PATH
    assert cfg.read_path("lax", t=1) == "lax"
    assert cfg.kernel_paths(1) == (mamba1.UPDATE_PATH,)
    assert cfg.kernel_paths(256) == (mamba1.SCAN_PATH,)
    assert cfg.kernel_paths(64) == (mamba1.SCAN_PATH,)
    with pytest.raises(ValueError, match="kv_quant"):
        cfg.paged_model(page_size=128, kv_pages=3, kernel="lax",
                        kv_quant="int8")
    assert jm.Jamba.CACHE_KINDS == {
        "k": "paged", "v": "paged", "index": "index", "conv": "state",
        "ssm": "state"}
    assert [c.name for c in jm.Jamba.STATS] == [
        "lzy_ssm_rows_total", "lzy_attn_full_keys_total",
        "lzy_attn_rows_total"]


def test_the_head_is_the_embedding(tiny):
    cfg, params = tiny
    assert "lm_head" not in params
    assert params["embed_tokens"].shape == (cfg.vocab_size, cfg.d_model)
    toks = jnp.asarray([_tokens(8, 16, cfg.vocab_size)])
    moved = dict(params, embed_tokens=params["embed_tokens"].at[5].mul(3.0))
    a = jm.Jamba(cfg).apply({"params": params}, toks)
    b = jm.Jamba(cfg).apply({"params": moved}, toks)
    # row 5 of the embedding is logit 5's weights, and nobody else's
    assert np.abs(np.asarray(b[..., 5] - 3.0 * a[..., 5])).max() < 1e-4
    others = np.delete(np.arange(cfg.vocab_size), 5)
    assert np.abs(np.asarray(b - a))[..., others].max() < 1e-5


def test_state_leaves_are_float32_whatever_the_activations_are():
    model = dataclasses.replace(
        jm.JambaConfig.tiny(), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16).paged_model(
            page_size=8, kv_pages=9, kernel="lax", kv_quant=None)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 16), jnp.int32)))["cache"]
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    by_name = {}
    for path, leaf in flat:
        by_name.setdefault(path[-1].key, []).append(leaf)
    assert len(by_name["ssm"]) == len(by_name["conv"]) == 13
    assert all(s.dtype == jnp.float32 and s.shape == (2, 16, 128)
               for s in by_name["ssm"])
    assert all(s.dtype == jnp.bfloat16 and s.shape == (2, 3, 128)
               for s in by_name["conv"])
    # the pools every PagedAttention block keeps; the read takes a page's
    # rows with the heads side by side
    assert by_name["k"][0].shape == (9, 8, 1, 16)


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    got = jm.Jamba(cfg).apply({"params": params}, toks)[0]
    want = ref.reference_logits(params, toks, jnp.arange(48), cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    # and they are logits worth comparing: unit spread over the vocabulary
    assert 0.3 < float(np.asarray(want).std()) < 3.0


def test_the_references_recurrence_is_the_written_one():
    rng = np.random.default_rng(3)
    t, di, n = 32, 24, 16
    x = rng.normal(size=(t, di))
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(t, di)))
    a = -np.exp(rng.uniform(0, np.log(16), size=(n, di)))
    bm, cm = rng.normal(size=(t, n)), rng.normal(size=(t, n))
    s, want = np.zeros((n, di)), np.zeros((t, di))
    for i in range(t):
        for ch in range(di):
            for k in range(n):
                s[k, ch] = np.exp(step[i, ch] * a[k, ch]) * s[k, ch] \
                    + step[i, ch] * bm[i, k] * x[i, ch]
            want[i, ch] = s[:, ch] @ cm[i]
    f = lambda m: jnp.asarray(m, jnp.float32)
    got, state = ref.selective_recurrence(f(x), f(step), f(a), f(bm), f(cm),
                                          jnp.float32)
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert np.abs(np.asarray(state) - s).max() < 1e-4


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


def _through_the_cache(model, table, cache, params, toks):
    """A prefill chunk, a padded chunk that starts from the carried state,
    then one position at a time through the update kernel: every
    position's logits."""
    def run(cache, chunk, real):
        pad = chunk + [0] * (16 - len(chunk)) if len(chunk) > 1 else chunk
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
    cache, out, _ = run(cache, toks[:16], 16)
    got.append(out)
    cache, out, _ = run(cache, toks[16:29], 13)      # padded to 16
    got.append(out)
    stats = None
    for tok in toks[29:]:
        cache, out, stats = run(cache, [tok], 1)
        got.append(out)
    return np.concatenate(got), stats


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        tiny, kernel):
    """Logits, not tokens."""
    cfg, params = tiny
    toks = _tokens(3, 45, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks]), jnp.arange(45), cfg))
    got, stats = _through_the_cache(*_paged(cfg, kernel), params, toks)
    assert np.abs(got - want).max() < TOL
    # the last decode position: 13 Mamba layers moved one row's state, the
    # attention layer read the 45 keys of one row
    counts = sum(jax.tree_util.tree_leaves(stats))
    assert counts.tolist() == [13, 45, 1]


def test_a_bfloat16_state_fails_the_comparison(tiny, monkeypatch):
    """The first precision guard: the same program with its recurrence
    state rounded to bfloat16 after every call does not pass."""
    cfg, params = tiny
    toks = _tokens(3, 45, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks]), jnp.arange(45), cfg))
    scan, update = mamba1.selective_scan, mamba1.selective_state_update
    rough = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)

    def rough_scan(*a, **kw):
        y, s = scan(*a, **kw)
        return y, rough(s)

    def rough_update(*a, **kw):
        y, s = update(*a, **kw)
        return y, rough(s)

    monkeypatch.setattr(jm.mamba1, "selective_scan", rough_scan)
    monkeypatch.setattr(jm.mamba1, "selective_state_update", rough_update)
    got, _ = _through_the_cache(*_paged(cfg), params, toks)
    assert np.abs(got - want).max() > 5 * TOL


def test_a_mixer_without_its_inner_norms_fails_the_comparison(
        tiny, monkeypatch):
    """The second guard: ``dt``, ``B`` and ``C`` handed on as the projection
    gave them (Mamba as published, without Jamba's three norms)."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 48, cfg.vocab_size)])
    want = np.asarray(ref.reference_logits(params, toks, jnp.arange(48),
                                           cfg))

    class Skipped(nn.Module):
        eps: float
        param_dtype: object

        @nn.compact
        def __call__(self, x):
            if self.name in ("dt_norm", "b_norm", "c_norm"):
                return x
            scale = self.param("scale", nn.initializers.ones,
                               (x.shape[-1],), self.param_dtype)
            return x * jax.lax.rsqrt(jnp.mean(
                jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale

    monkeypatch.setattr(jm, "RMSNorm", Skipped)
    got = np.asarray(jm.Jamba(cfg).apply({"params": params}, toks)[0])
    assert np.abs(got - want).max() > 100 * TOL


def _config_doc():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "jamba2-3b-serve.json")
    with open(path) as f:
        return json.load(f)


def test_program_config_reads_the_published_widths():
    cfg = ref.program_config(_config_doc())
    assert (cfg.d_model, cfg.n_layers, cfg.d_ff) == (2560, 28, 8192)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (20, 1, 128)
    assert (cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.conv_kernel) \
        == (5120, 16, 160, 4)
    assert (cfg.attn_period, cfg.attn_offset) == (14, 7)
    assert (cfg.vocab_size, cfg.max_seq_len) == (65536, 65536)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    shapes = jax.eval_shape(
        lambda: jm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 3_029_337_472


def test_program_config_refuses_what_the_program_cannot_honour():
    with pytest.raises(ValueError, match="ssm_state_dtype bfloat16"):
        ref.program_config(dict(_config_doc(), ssm_state_dtype="bfloat16"))
    with pytest.raises(ValueError, match="num_experts"):
        ref.program_config(dict(_config_doc(), num_experts=16))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        ref.program_config(dict(_config_doc(), tie_word_embeddings=False))


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: both new kernels and the read at 20 / 1
    heads, at the decode step's shapes and the widest chunk's."""
    doc = _config_doc()
    cfg = ref.program_config(doc)
    eng = doc["engine"]
    cfg.check_kernels(
        slots=eng["slots"], kv_blocks=eng["kv_pool_bytes"] // (
            eng["page_size"] * 1024), page_size=eng["page_size"],
        pages_per_seq=cfg.max_seq_len // eng["page_size"])


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
_COUNTED = ("lzy_ssm_rows_total", "lzy_attn_full_keys_total",
            "lzy_attn_rows_total", "lzy_state_slots_reset_total")


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


def test_state_is_spliced_inside_prefill_and_slots_start_from_zero(served):
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
    # resident rows a Mamba layer a round; idle slots and slots in the
    # middle of a prefill are not counted
    assert counted["lzy_ssm_rows_total"] \
        == engine.decode_rows * cfg.mamba_layers
    assert counted["lzy_attn_rows_total"] \
        == engine.decode_rows * cfg.kv_layers
    # a row that emits its k-th token (k >= 2) decodes at position
    # prompt + k - 2 and reads prompt + k - 1 keys; the first token is the
    # prefill's. A finish learnt a round late adds whole rows, so hold the
    # keys to the rows they belong to
    least = sum(n + k - 1 for n, m in zip(_LENGTHS, _BUDGETS)
                for k in range(2, m + 1))
    assert counted["lzy_attn_full_keys_total"] >= least
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        "rows" in s.attrs and set(s.attrs["model_stats"])
        == {c.name for c in jm.Jamba.STATS} for s in emits)
    assert sum(s.attrs["model_stats"]["lzy_ssm_rows_total"] for s in emits) \
        == counted["lzy_ssm_rows_total"]


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (mamba1.SCAN_PATH, mamba1.UPDATE_PATH, GROUP_DECODE_PATH,
                 GROUP_CHUNK_PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].kernel_path == GROUP_DECODE_PATH


def test_radix_match_is_zero_and_nothing_is_cached(served):
    engine = served["engine"]
    assert engine.kv.lookup_tokens > 0 and engine.kv.hit_tokens == 0
    assert engine.stats().kv_blocks_cached == 0
    again = engine.submit(served["prompts"][0], max_new_tokens=12,
                          greedy=True)
    _drain(engine)
    assert engine.kv.hit_tokens == 0
    assert again.tokens == served["reqs"][0].tokens


def test_a_reused_slot_starts_from_zero_state(tiny):
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


def test_a_finished_requests_state_stays_in_its_slot(tiny):
    """``state_leaves()``: a freed slot keeps what its last round left, the
    reference's state after the prompt and every served token but the last
    (emitted, never fed); a bfloat16 state would stand three orders off."""
    cfg, params = tiny
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                  prefill_chunk=16, kernel="pallas")
    prompt = _tokens(60, 40, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=6, greedy=True)
    _drain(engine)
    leaves = engine.state_leaves()
    assert len(leaves) == 2 * cfg.mamba_layers
    assert all(leaf.shape[0] == engine.slots for leaf in leaves.values())
    fed = jnp.asarray([prompt + list(req.tokens)[:-1]])
    _, states = ref.features(params, fed, cfg)
    gap = ref.state_gaps(leaves, states, params, cfg)
    assert gap["slot"] == 0 and len(gap["all"]) == cfg.mamba_layers
    assert max(gap["all"] + gap["slow"]) < 1e-5
    rough = {name: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
             for name, leaf in leaves.items()}
    assert min(ref.state_gaps(rough, states, params, cfg)["slow"]) > 1e-3
    engine.close()


def test_the_widest_program_carries_the_state(tiny):
    """The cell's shape: no ``prefill_chunk`` given, a budget of 256, so a
    program of 256 positions and a padded tail that starts from the carried
    state."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=512)
    assert cfg.widest_prefill == 256
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
    assert kinds.count(serving.STATE) == 2 * 13         # conv, ssm x 13
    assert kinds.count(serving.PAGED) == 2              # k, v x 1 attention
    assert kinds.count(serving.INDEX) == 1
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
                             model_name="jamba-tiny", page_size=8)
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
            _engine(tiny, kv_storage_tier="mem://tier-refused-jamba")
    elif mechanism == "sharded engine":
        from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine
        from lzy_tpu.serving.sharded import NoPartitionRules

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


def test_the_engine_names_no_model():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "lzy_tpu", "serving", "engine.py")) as f:
        text = f.read().lower()
    assert "jamba" not in text and "mamba" not in text

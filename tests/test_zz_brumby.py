"""Brumby (power retention in every layer) on the serving path: the model
against the benchmark's plain float32 reference, which computes the attention
form of the same function and shares no code with the program, and the model
through ``PagedInferenceEngine``: state leaves alone, so no page pool, no
page table, admission by a free slot. Tiny widths, seeded weights, CPU, the
update kernel interpreted (``tests/conftest.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import brumby as ref
from lzy_tpu.models import brumby
from lzy_tpu.models import serving
from lzy_tpu.ops import power_retention as pr
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import StateLeavesUnsupported
from lzy_tpu.serving.scheduler import PromptTooLong
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 5e-5

PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def tiny():
    # the benchmark's gate constant: decays of 0.93 to 0.997, so that what a
    # state remembers reaches back past a chunk
    cfg = dataclasses.replace(brumby.BrumbyConfig.tiny(),
                              gate_bias=ref.RETENTION_GATE_BIAS)
    params = brumby.init_params(cfg, jax.random.PRNGKey(1))
    # unit-scale projections: at 0.02 N and a width of 64 the mixer is lost
    # beside the residual stream and an error in it would not show
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * (4.0 if getattr(path[-1], "key", "")
                                   == "kernel" else 1.0), params)
    return cfg, params


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _engine(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("slots", 3)
    kw.setdefault("page_size", 16)
    kw.setdefault("kernel", "lax")
    kw.setdefault("prefill_budget", 32)
    return PagedInferenceEngine(cfg, params, **kw)


def _drain(engine, limit=3000):
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


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == name:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# -- the configuration --------------------------------------------------------

def test_it_answers_the_serving_protocol():
    cfg = brumby.BrumbyConfig.from_published(PUBLISHED)
    assert cfg.serving_config() is cfg
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (40, 40, 8, 128)
    assert cfg.kv_layers == 0 and cfg.kv_token_bytes(None) == 0
    assert cfg.widest_prefill == 256
    assert cfg.read_path("pallas", t=1) == pr.UPDATE_PATH
    assert cfg.read_path("lax", t=256) == pr.SCAN_PATH
    assert cfg.kernel_paths(1) == cfg.kernel_paths(256) == ()
    # S and z a layer: 8 heads of 65 tiles of 128 x 128, and of 65 x 128
    assert cfg.state_bytes == 40 * 8 * (65 * 128 * 128 + 65 * 128) * 4
    with pytest.raises(ValueError, match="kv_quant"):
        cfg.paged_model(page_size=64, kv_pages=0, kernel="lax",
                        kv_quant="int8")
    assert brumby.Brumby.CACHE_KINDS == {"S": "state", "z": "state",
                                         "index": "index"}
    assert [c.name for c in brumby.Brumby.STATS] \
        == ["lzy_retention_rows_total"]
    assert not set(brumby.Brumby.CACHE_KINDS.values()) & set(serving.POOLS)


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu")])
def test_from_published_refuses_what_it_cannot_honour(key, value):
    with pytest.raises(ValueError, match=key):
        brumby.BrumbyConfig.from_published({**PUBLISHED, key: value})


def test_a_state_of_another_type_or_degree_is_refused():
    with pytest.raises(ValueError, match="float32"):
        brumby.BrumbyConfig(state_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="degree"):
        brumby.BrumbyConfig(degree=4)


# -- the model against the reference --------------------------------------------

def test_uncached_forward_is_the_references_attention_form(tiny):
    cfg, params = tiny
    toks = _tokens(3, 70, cfg.vocab_size)
    mine = brumby.Brumby(cfg).apply({"params": params},
                                    jnp.asarray([toks]))[0]
    want = ref.reference_logits(params, jnp.asarray([toks]),
                                np.arange(len(toks)), cfg)
    np.testing.assert_allclose(mine, want, atol=TOL, rtol=1e-4)


def test_the_references_state_is_its_own_feature_map(tiny):
    """The reference's phi against the program's, written apart: the same
    layout, and the product it has to keep."""
    a, b = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
    np.testing.assert_allclose(ref.phi(a), pr.phi(a), rtol=1e-6)
    np.testing.assert_allclose((ref.phi(a) * ref.phi(b)).sum((-1, -2)),
                               jnp.square((a * b).sum(-1)), rtol=1e-4,
                               atol=1e-4)


# -- the model through the engine -----------------------------------------------

#: more requests than slots; prompts that end inside a chunk, on a chunk's
#: end and inside the first chunk
_LENGTHS, _BUDGETS = (75, 32, 130, 9, 64, 41), (8, 12, 6, 10, 5, 7)


@pytest.fixture(scope="module")
def served(tiny):
    cfg, _ = tiny
    engine = _engine(tiny)
    engine.warmup()
    before = _counter("lzy_retention_rows_total")
    prompts = [_tokens(10 + i, n, cfg.vocab_size)
               for i, n in enumerate(_LENGTHS)]
    with trace.recording() as rec:
        reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
                for p, m in zip(prompts, _BUDGETS)]
        queued = engine.stats().queue_depth
        _drain(engine)
        spans = rec.drain()
    yield {"engine": engine, "prompts": prompts, "reqs": reqs,
           "spans": spans, "queued": queued,
           "rows": _counter("lzy_retention_rows_total") - before}
    engine.close()


@pytest.mark.parametrize("i", range(6))
def test_engine_serves_the_references_tokens(tiny, served, i):
    req, prompt = served["reqs"][i], served["prompts"][i]
    assert req.done and req.error is None
    assert len(req.tokens) == _BUDGETS[i]
    assert _gap(tiny, prompt, req.tokens) < TOL


def test_more_requests_than_slots_queue_and_finish(served):
    assert served["queued"] == 6 and served["engine"].slots == 3
    assert all(r.done and r.error is None for r in served["reqs"])
    assert served["engine"].stats().requests_finished == 6


def test_no_page_is_allocated_and_no_table_kept(tiny, served):
    engine = served["engine"]
    s = engine.stats()
    assert (s.kv_blocks_total, s.kv_blocks_free, s.kv_blocks_cached) \
        == (0, 0, 0)
    assert s.kv_token_bytes == 0 and s.kv_evictions == 0
    assert engine.kv.pool.n_blocks == 0 and engine._tables.size == 0
    assert all(blocks == [] for blocks in engine._slot_blocks)
    # what a round is handed in a table's place: the live rows, one a slot
    assert engine._page_table_dev().shape == (engine.slots,)
    assert not np.asarray(engine._page_table_dev()).any()
    assert not [k for k in engine.kv_io.leaf_keys
                if not k.endswith(("['S']", "['z']"))]
    # nothing was matched, inserted or evicted
    assert engine.kv.hit_tokens == 0 and engine.kv.structure_version == 0
    admits = [sp for sp in served["spans"] if sp.name == "engine.admit"
              and "blocks" in sp.attrs]
    assert admits and all(sp.attrs["blocks"] == 0 for sp in admits)


def test_one_fence_a_round_carries_the_count(tiny, served):
    cfg, _ = tiny
    engine = served["engine"]
    assert engine.host_fetches == engine.decode_steps
    assert served["rows"] == engine.decode_rows * cfg.n_layers
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        set(s.attrs["model_stats"]) == {"lzy_retention_rows_total"}
        and s.attrs["model_stats"]["lzy_retention_rows_total"]
        == s.attrs["rows"] * cfg.n_layers for s in emits)


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (pr.UPDATE_PATH, pr.SCAN_PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].kernel_path == pr.UPDATE_PATH


def test_a_prefill_program_counts_the_chunk_kernel_once(tiny):
    """``retention_chunk_pallas`` a prefill program, whatever its width, and
    the old label is gone: two programs of 32 and a tail of 8 for 70 tokens
    under a budget of 32."""
    cfg, _ = tiny
    engine = _engine(tiny, slots=2)

    assert pr.SCAN_PATH == "retention_chunk_pallas"

    def chunks():
        return _counter(f'lzy_kernel_dispatch_total{{path="{pr.SCAN_PATH}"}}')

    before = chunks(), _counter("lzy_engine_prefill_programs_total")
    engine.submit(_tokens(70, 70, cfg.vocab_size), max_new_tokens=2,
                  greedy=True)
    _drain(engine)
    programs = _counter("lzy_engine_prefill_programs_total") - before[1]
    assert programs == 3 and chunks() - before[0] == programs
    assert "retention_chunk_lax" not in REGISTRY.exposition()
    engine.close()


def test_check_kernels_lowers_both_kernels_at_the_served_shapes(monkeypatch):
    """The update over every slot, the chunk scan over one row of the widest
    program: a kernel the chip refuses is refused at construction."""
    asked = {}
    cfg = brumby.BrumbyConfig.from_published(PUBLISHED)
    with monkeypatch.context() as patched:
        patched.setattr(pr, "lower_update_for_tpu",
                        lambda **kw: asked.setdefault("update", kw))
        patched.setattr(pr, "lower_chunk_for_tpu",
                        lambda **kw: asked.setdefault("chunk", kw))
        cfg.check_kernels(slots=16)
    heads = dict(heads=40, kv_heads=8, head_dim=128, dtype=cfg.dtype)
    assert asked["update"] == dict(batch=16, **heads)
    assert asked["chunk"] == dict(batch=1, t=256, chunk=128, **heads)
    # and for real at the tiny size (a lowering, no compile, no device)
    brumby.BrumbyConfig.tiny().check_kernels(slots=3)


def test_a_finished_requests_state_stays_in_its_slot(tiny):
    """``state_leaves()``: a freed slot keeps what its last round left, the
    reference's direct sum after the prompt and every served token but the
    last (emitted, never fed); rounded to bfloat16 it fails the fourth
    limit, and a state one position further fails the third."""
    cfg, params = tiny
    engine = _engine(tiny, slots=2)
    prompt = _tokens(60, 85, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=6, greedy=True)
    _drain(engine)
    leaves = engine.state_leaves()
    assert len(leaves) == 2 * cfg.n_layers
    assert {leaf.shape for name, leaf in leaves.items()
            if name.endswith("['S']")} == {(2, 2, 9, 16, 16)}
    fed = jnp.asarray([prompt + list(req.tokens)[:-1]])
    _, exact, decays = ref.features(params, fed, cfg)
    gap = ref.state_gaps(leaves, exact, cfg)
    assert gap["slot"] == 0 and gap["gap"] < 1e-5
    assert gap["coarse"] < ref.STATE_COARSE_TOL
    assert float(decays.min()) > 0.5 and float(decays.max()) < 1.0
    rough = {name: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
             for name, leaf in leaves.items()}
    assert ref.state_gaps(rough, exact, cfg)["coarse"] == 1.0
    _, short, _ = ref.features(params, fed[:, :-1], cfg)
    assert ref.state_gaps(leaves, short, cfg)["gap"] > ref.STATE_REL_TOL
    engine.close()


def test_a_request_past_max_seq_len_is_refused(tiny):
    cfg, _ = tiny
    engine = _engine(tiny, slots=1)
    with pytest.raises(PromptTooLong, match="max_seq_len"):
        engine.submit(_tokens(1, cfg.max_seq_len - 3, cfg.vocab_size),
                      max_new_tokens=8)
    # up to the last position it is served: no pool to run out of
    req = engine.submit(_tokens(2, 20, cfg.vocab_size), max_new_tokens=3,
                        greedy=True)
    _drain(engine)
    assert req.done and req.error is None and len(req.tokens) == 3
    engine.close()


@pytest.mark.parametrize("kw", [{"kv_pool_bytes": 1 << 20},
                                {"kv_blocks": 64}])
def test_a_pool_size_is_refused_by_name(tiny, kw):
    with pytest.raises(ValueError, match=f"{next(iter(kw))}: BrumbyConfig "
                                         f"keeps no page pool"):
        _engine(tiny, **kw)


def test_the_page_moving_mechanisms_refuse_the_model_by_name(tiny):
    with pytest.raises(StateLeavesUnsupported, match="speculative"):
        _engine(tiny, spec_tokens=2)
    with pytest.raises(StateLeavesUnsupported, match="tiered KV cache"):
        _engine(tiny, kv_host_tier_bytes=1 << 20)
    with pytest.raises(ValueError, match="kv_quant"):
        _engine(tiny, kv_quant="int8")
    from lzy_tpu.serving.sharded import (
        NoPartitionRules, ShardedPagedInferenceEngine)

    with pytest.raises(NoPartitionRules, match="sharded engine"):
        ShardedPagedInferenceEngine(*tiny, tp=2, slots=2)
    engine = _engine(tiny, slots=1)
    assert engine.kv.reuse is False
    toks = _tokens(5, 40, tiny[0].vocab_size)
    for call, name in ((lambda: engine.park_chain("c", toks), "park"),
                       (lambda: engine.request_kv_export(toks), "export"),
                       (lambda: engine.queue_kv_import(None), "import")):
        with pytest.raises(StateLeavesUnsupported, match="per-slot state"):
            call()
    engine.close()


def test_staged_prompts_are_bounded_and_the_rest_wait(tiny):
    """``max_prefill_jobs``: a job holds a batch-1 copy of every state leaf,
    so a burst stages two prompts and queues the others; all are served."""
    cfg, _ = tiny
    engine = _engine(tiny, slots=3, max_prefill_jobs=2, prefill_budget=16)
    prompts = [_tokens(30 + i, 70, cfg.vocab_size) for i in range(3)]
    reqs = [engine.submit(p, max_new_tokens=4, greedy=True) for p in prompts]
    most = 0
    for _ in range(3000):
        if not engine.step():
            break
        most = max(most, len(engine.prefill.jobs))
    assert most == 2
    assert len(engine.prefill.spare_state) <= 2
    for req, prompt in zip(reqs, prompts):
        assert req.done and req.error is None
        assert _gap(tiny, prompt, req.tokens) < TOL
    with pytest.raises(ValueError, match="max_prefill_jobs"):
        _engine(tiny, max_prefill_jobs=0)
    engine.close()


def test_a_model_that_miscounts_its_pool_is_refused(tiny, monkeypatch):
    """``kv_layers`` 0 and a paged leaf, or the other way round: the engine
    asks both and they must agree."""
    cfg, params = tiny
    monkeypatch.setattr(brumby.Brumby, "CACHE_KINDS",
                        {"S": "paged", "z": "state", "index": "index"})
    with pytest.raises(ValueError, match="kv_layers 0"):
        PagedInferenceEngine(cfg, params, slots=1, page_size=16,
                             kernel="lax")


def test_the_decode_step_donates_every_state_leaf(tiny):
    """The compiled round holds one copy of the state: every ``S`` and ``z``
    is donated to an output of its shape (the chip's compile, where the
    kernel updates in place, is ``tests/test_aot_topology.py``'s)."""
    cfg, _ = tiny
    engine = _engine(tiny, slots=2)
    payload = [jax.ShapeDtypeStruct(x.shape, x.dtype)
               for x in engine._payload]
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = engine._decode_step.lower(
        payload, engine.params, vec, vec, vec,
        jax.ShapeDtypeStruct((2,), jnp.bool_),
        jax.ShapeDtypeStruct(engine._rng.shape, engine._rng.dtype)).as_text()
    assert text.count("tf.aliasing_output") == 2 * cfg.n_layers
    engine.close()

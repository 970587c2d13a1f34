"""The DeepSeek-V3 family (Moonlight-16B-A3B) on the serving path: the
absorbed latent attention through the paged latent cache against the
benchmark's plain float32 reference in the published, non-absorbed form
(logits, not tokens), the expert shares, the precision guards, the seam the
engine sizes its pool through, and the model through
``PagedInferenceEngine`` with every mechanism a latent leaf serves or
refuses. Tiny widths, seeded weights, CPU, Pallas kernels interpreted
(``tests/conftest.py``).

The file's name sorts last on purpose (as ``test_zz_deepseek_mla.py``'s):
the tier runs ``--dist loadfile``, which hands files to workers in their
order, and these two are long; run last they shift no earlier file, so
``tests/test_load.py``'s wall-clock smoke test keeps the neighbours it had
(beside ``tests/test_mla.py`` it passed its 60 s twice)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import deepseek_v3 as ref
from lzy_tpu.models import deepseek_v3 as ds
from lzy_tpu.models import experts, serving
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mla
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums and by the algebraic form of the attention alone
TOL = 2e-4


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths; at the tiny ones it would shrink every layer's output to nothing
    and a wrong expert or a lost page would hide under the tolerance.
    Rescale each matrix to fan_in ** -0.5."""
    def fix(path, leaf):
        name = path[-1].key
        if name in ("kernel", "experts_gate", "experts_up", "experts_down",
                    "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = ds.DeepseekV3Config.tiny()
    return cfg, _unit_scale(ds.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 40, cfg.vocab_size)])
    got, seen = ds.DeepseekV3(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.reference_logits(params, toks, jnp.arange(40), cfg)
    assert np.abs(got[0] - want).max() < TOL
    assert seen["intermediates"]["layer_1_moe"]["chosen"][0].shape \
        == (40, cfg.top_k)
    # uncached, the attention sows nothing; the two expert layers do
    total = np.asarray(sum(jax.tree_util.tree_leaves(seen["stats"])))
    assert total.shape == (len(ds.DeepseekV3.STATS),)
    assert list(total[[0, 1, 3, 4, 5]]) == [
        40 * cfg.top_k * 2, 40 * cfg.top_k * 2, cfg.n_held * 2, 0, 0]


def test_the_references_rotary_is_the_programs():
    from lzy_tpu.models.llama import _rope

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(20, 29)
    assert np.abs(np.asarray(_rope(x, pos[None], 50000.0)[0])
                  - np.asarray(ref.rotary(x[0], pos, 50000.0))).max() < 1e-6


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        tiny, kernel):
    """Logits, not tokens: a prefill chunk that fills page 0, a padded chunk
    across the page boundary, then one position at a time over the next page
    boundary (32), each through the paged latent cache."""
    cfg, params = tiny
    model = cfg.paged_model(page_size=16, kv_pages=8, kernel=kernel,
                            kv_quant=None)
    toks = _tokens(3, 45, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks]), jnp.arange(45), cfg))
    table = jnp.asarray([[5, 2, 7, 0, 0, 0, 0, 0]], jnp.int32)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32),
                               page_table=table))["cache"])

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
        counts = np.asarray(sum(jax.tree_util.tree_leaves(upd["stats"])))
        return cache, np.asarray(logits[0, :real]), counts

    got = []
    cache, out, _ = run(cache, toks[:16], 16)
    got.append(out)
    cache, out, counts = run(cache, toks[16:29], 13)      # padded to 16
    got.append(out)
    # the last real query of the chunk sits at 28 and reads 29, a layer
    assert list(counts[-2:]) == [29 * cfg.n_layers, cfg.n_layers]
    for tok in toks[29:]:
        cache, out, _ = run(cache, [tok], 1)
        got.append(out)
    assert np.abs(np.concatenate(got) - want).max() < TOL


def test_the_shares_add_up(tiny):
    """Four chips hold 4 of the 16 routed experts each (the deployment's
    four, 16 of 64). What each computes for the layer, with the shared pair
    (which every chip computes alike) counted once, adds up to the uncut
    layer: in the program, and to the reference's uncut layer. Attention and
    the dense layer are whole on every chip and are no share of anything."""
    cfg, params = tiny
    layer = params["layer_1_moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 24, cfg.d_model)).astype(np.float32))
    big = ("experts_gate", "experts_up", "experts_down")

    def cut(lo, hi):
        c = dataclasses.replace(cfg, experts_held=(lo, hi))
        return c, dict(layer, **{n: layer[n][lo:hi] for n in big})

    def program(lo, hi):
        c, w = cut(lo, hi)
        out, _ = experts.GatedExperts(c, other_stats=2).apply(
            {"params": w}, u, mutable=["stats"])
        return np.asarray(out[0])

    def reference(lo, hi):
        c, w = cut(lo, hi)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.routed_experts(u[0], w, c)
                              + ref.shared_expert(u[0], w))

    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared_expert(u[0], layer))
    uncut = reference(0, 16)
    for layer_fn in (program, reference):
        shares = [layer_fn(lo, lo + 4) for lo in range(0, 16, 4)]
        summed = sum(s - shared for s in shares) + shared
        assert np.abs(summed - uncut).max() < TOL
        # a share alone is not the layer: the cut is real
        assert np.abs(shares[0] - uncut).max() > 10 * TOL
    assert np.abs(program(4, 8) - reference(4, 8)).max() < TOL


# -- the precision guards -----------------------------------------------------

def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_router_tells_apart_scores_that_tie_in_bfloat16():
    """Two experts whose sigmoid scores differ by 1e-4 at the edge of the
    choice: float32 scores pick the larger; scores rounded to bfloat16 tie.
    The activations' dtype is bfloat16 here, as it is served."""
    cfg = dataclasses.replace(ds.DeepseekV3Config.tiny(), dtype=jnp.bfloat16)
    layer = experts.GatedExperts(cfg, other_stats=2)
    u = jnp.zeros((1, 1, cfg.d_model), jnp.float32).at[0, 0, 0].set(1.0)
    params = dict(layer.init(jax.random.PRNGKey(0), u)["params"])
    logits = np.linspace(-3.0, -2.0, cfg.n_routed_experts).astype(np.float32)
    logits[[0, 1, 2]] = 2.0, 1.5, 1.0          # three clear choices
    logits[3], logits[4] = 0.1000, 0.1004      # the fourth: expert 4, by 1e-4
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    assert _bf16(scores[3]) == _bf16(scores[4])
    params["router"] = jnp.zeros_like(params["router"]).at[0].set(logits)
    params["router_bias"] = jnp.zeros_like(params["router_bias"])
    _, seen = layer.apply({"params": params}, u.astype(cfg.dtype),
                          mutable=["intermediates", "stats"])
    chosen = set(np.asarray(seen["intermediates"]["chosen"][0]).ravel())
    assert chosen == {0, 1, 2, 4}


def test_a_bfloat16_reference_fails_the_tolerance(tiny):
    """The control (the reference wholly in bfloat16) is not within the
    tolerance the program is held to."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(4, 40, cfg.vocab_size)])
    exact = np.asarray(ref.reference_logits(params, toks, jnp.arange(40),
                                            cfg))
    control = np.asarray(ref.reference_logits(params, toks, jnp.arange(40),
                                              cfg, jnp.bfloat16))
    assert np.abs(control - exact).max() > 20 * TOL


def test_the_latent_leaf_has_no_head_axis_and_takes_the_activations_type():
    model = dataclasses.replace(
        ds.DeepseekV3Config.tiny(), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16).paged_model(
            page_size=16, kv_pages=9, kernel="lax", kv_quant=None)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 8), jnp.int32)))["cache"]
    leaves = [(path[-1].key, leaf) for path, leaf
              in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert sorted({k for k, _ in leaves}) == ["index", "latent"]
    pools = [leaf for k, leaf in leaves if k == "latent"]
    assert len(pools) == 3 and all(
        p.shape == (9, 16, 128) and p.dtype == jnp.bfloat16 for p in pools)


# -- the seam -----------------------------------------------------------------

def test_every_documented_name_is_answered():
    """Every bullet of ``models/serving.py``'s protocol is an attribute of
    this configuration too; ``n_kv_heads`` and ``head_dim`` are asked of a
    model whose pages hold keys and values a head, and this one has
    neither."""
    import re

    doc = serving.__doc__.split("**The module class**")[0]
    names = re.findall(r"^- ``(\w+)", doc, re.M)
    assert {"kv_token_bytes", "read_path", "check_kernels", "kv_layers",
            "widest_prefill", "kernel_paths", "paged_model"} <= set(names)
    cfg = ds.DeepseekV3Config.tiny()
    for name in names + ["max_seq_len", "vocab_size", "dtype", "n_heads"]:
        assert hasattr(cfg, name), name
    assert not hasattr(cfg, "n_kv_heads") and not hasattr(cfg, "head_dim")


def _older_configurations():
    from lzy_tpu.models import nemotron_h, solar_open2
    from lzy_tpu.models.llama import LlamaConfig

    return [LlamaConfig.tiny(), nemotron_h.NemotronHConfig.tiny(),
            solar_open2.SolarOpen2Config.tiny()]


@pytest.mark.parametrize("which", range(3))
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_the_older_pools_come_out_block_for_block_as_before(which, kv_quant):
    """What the engine now divides a byte budget by is what
    ``kv_cache.blocks_for_bytes`` divided it by."""
    from lzy_tpu.serving.kv_cache import blocks_for_bytes, kv_block_bytes

    cfg = _older_configurations()[which].serving_config()
    if kv_quant and which:
        with pytest.raises(ValueError, match="kv_quant"):
            cfg.paged_model(page_size=16, kv_pages=4, kernel="lax",
                            kv_quant=kv_quant)
        return
    per_token = cfg.kv_layers * cfg.kv_token_bytes(kv_quant)
    assert 16 * per_token == kv_block_bytes(
        page_size=16, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_layers=cfg.kv_layers, dtype=cfg.dtype, kv_quant=kv_quant)
    for budget in (1 << 16, 3_000_000, 1 << 26):
        assert max(2, budget // (16 * per_token)) == blocks_for_bytes(
            budget, page_size=16, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, n_layers=cfg.kv_layers, dtype=cfg.dtype,
            kv_quant=kv_quant)


@pytest.mark.parametrize("which", range(3))
def test_an_engine_sized_by_bytes_holds_the_blocks_it_held(which, tiny):
    from lzy_tpu.serving.kv_cache import blocks_for_bytes

    cfg = _older_configurations()[which]
    if which == 0:
        from lzy_tpu.models.llama import Llama
        params = Llama(cfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    elif which == 1:
        from lzy_tpu.models import nemotron_h
        params = nemotron_h.init_params(cfg, jax.random.PRNGKey(0))
    else:
        from lzy_tpu.models import solar_open2
        params = solar_open2.init_params(cfg, jax.random.PRNGKey(0))
    budget = 200_000
    engine = PagedInferenceEngine(cfg, params, slots=1, page_size=16,
                                  kernel="lax", kv_pool_bytes=budget)
    base = cfg.serving_config()
    try:
        assert engine._kv_blocks == blocks_for_bytes(
            budget, page_size=16, n_kv_heads=base.n_kv_heads,
            head_dim=base.head_dim, n_layers=base.kv_layers,
            dtype=base.dtype)
        assert engine.stats().kv_token_bytes \
            == base.kv_layers * base.kv_token_bytes(None)
    finally:
        engine.close()


def test_a_latent_pool_is_sized_by_its_own_token_bytes(tiny):
    cfg, params = tiny
    # 3 layers x 128 lanes x 4 bytes: 1536 bytes a token, 24576 a page
    assert cfg.kv_layers * cfg.kv_token_bytes() == 1536
    engine = PagedInferenceEngine(cfg, params, slots=1, page_size=16,
                                  kernel="lax", kv_pool_bytes=10 * 24576 + 5)
    try:
        assert engine._kv_blocks == 10
        assert engine.stats().kv_token_bytes == 1536
        assert engine.kernel_path == mla.LAX_PATH
    finally:
        engine.close()


def _published():
    return {
        "hidden_size": 2048, "num_hidden_layers": 27,
        "num_attention_heads": 16, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "q_lora_rank": None, "first_k_dense_replace": 1,
        "n_routed_experts": 64, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.446,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True,
        "moe_intermediate_size": 1408, "intermediate_size": 11264,
        "rope_theta": 50000.0, "rope_scaling": None, "rms_norm_eps": 1e-5,
        "vocab_size": 163840, "max_position_embeddings": 8192,
        "tie_word_embeddings": False}


def test_the_published_keys_give_the_name_its_count():
    """15.96 B parameters, 2.9 B of them active a token, the embedding
    table counted as the name counts it (shapes only)."""
    cfg = ds.DeepseekV3Config.from_published(_published())
    assert cfg == ds.DeepseekV3Config()
    shapes = jax.eval_shape(lambda: ds.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 1e9 - 15.96) < 0.01
    expert = 3 * 2048 * 1408
    active = total - 26 * (64 - 6) * expert
    assert abs(active / 1e9 - 2.91) < 0.02


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("norm_topk_prob", False)])
def test_what_the_program_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ds.DeepseekV3Config.from_published(dict(_published(), **{key: value}))


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: both latent reads over a pool of 512 pages
    a slot at 32 slots, and the gated experts at 2048 x 1408 (tiles of
    2048 x 128: 1408 = 11 x 128 has no wider divisor in lanes)."""
    cfg = dataclasses.replace(ds.DeepseekV3Config(), experts_held=(0, 16))
    cfg.check_kernels(slots=32, kv_blocks=7000, page_size=16,
                      pages_per_seq=512)
    assert gexp._tile(1408, 2048, 2) == 128
    with pytest.raises(ds.LatentPoolUnsupported, match="kv_quant"):
        cfg.check_kernels(slots=32, kv_quant="int8")


# -- through the engine -------------------------------------------------------

def _engine(tiny, **kw):
    """The lax read unless a test asks for the kernel: the interpreted
    kernel is held to it in ``tests/test_zz_deepseek_mla.py``, and through the engine
    by the mixed run below and by speculation's verify window."""
    cfg, params = tiny
    kw.setdefault("slots", 3)
    kw.setdefault("kernel", "lax")
    kw.setdefault("prefill_chunk", 16)
    return PagedInferenceEngine(cfg, params, page_size=16, **kw)


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
_COUNTED = tuple(c.name for c in ds.DeepseekV3.STATS)


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, one mixed run: prompts whose last chunk is padded and
    not, a budget that splits the long prompts over rounds while the short
    ones already decode, more requests than slots."""
    cfg, _ = tiny
    engine = _engine(tiny, prefill_budget=16, kernel="pallas")
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


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    # resident rows x experts a token, an expert layer a round: idle slots
    # and slots in the middle of a prefill are not counted
    assert counted["lzy_moe_assignments_total"] \
        == engine.decode_rows * cfg.top_k * cfg.expert_layers
    assert counted["lzy_moe_experts_held_total"] \
        == engine.decode_steps * cfg.n_held * cfg.expert_layers
    assert counted["lzy_mla_rows_total"] \
        == engine.decode_rows * cfg.n_layers
    # a decoded token at position p read p + 1 cached vectors, a layer: a
    # request of n prompt and m answer tokens decodes at n .. n + m - 2
    # (its first token is the prefill's)
    want = sum(sum(range(n + 1, n + m)) for n, m in zip(_LENGTHS, _BUDGETS))
    assert counted["lzy_mla_context_tokens_total"] == want * cfg.n_layers
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        "rows" in s.attrs and set(s.attrs["model_stats"]) == set(_COUNTED)
        for s in emits)


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (mla.DECODE_PATH, mla.PREFILL_PATH, gexp.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].stats().kernel_path == mla.DECODE_PATH


def test_cache_leaves_are_declared_by_kind(served):
    engine = served["engine"]
    kinds = engine._leaf_kinds
    assert kinds.count(serving.PAGED) == 3 and not engine._has_state
    assert kinds.count(serving.STATE) == 0
    assert all(leaf.shape == (engine._kv_blocks, 16, 128)
               for leaf in engine._payload)


def test_a_radix_hit_gives_the_logits_of_a_cold_prefill(tiny):
    """The first expert model whose prefix cache is on: a prompt that
    shares 32 tokens (two pages) with a finished one skips their prefill,
    and what it serves sits as close to the reference as a cold engine's."""
    cfg, _ = tiny
    shared = _tokens(60, 32, cfg.vocab_size)
    first = shared + _tokens(61, 9, cfg.vocab_size)
    second = shared + _tokens(62, 13, cfg.vocab_size)
    warm = _engine(tiny, slots=1)
    a = warm.submit(first, max_new_tokens=6, greedy=True)
    _drain(warm)
    assert warm.kv.reuse and warm.kv.hit_tokens == 0
    b = warm.submit(second, max_new_tokens=8, greedy=True)
    _drain(warm)
    assert warm.kv.hit_tokens == 32
    assert warm.stats().prefill_tokens_saved == 32
    cold = _engine(tiny, slots=1)
    c = cold.submit(second, max_new_tokens=8, greedy=True)
    _drain(cold)
    assert cold.kv.hit_tokens == 0
    assert b.tokens == c.tokens
    assert _gap(tiny, first, a.tokens) < TOL
    assert _gap(tiny, second, b.tokens) < TOL
    warm.close()
    cold.close()


def test_the_widest_program_reads_the_prefix_through_the_table(tiny):
    """The cell's shape: no ``prefill_chunk`` given, a budget of 256, so a
    program of 256 positions and a padded tail that reads 16 pages of
    prefix (the kernel's tiles over such a chunk: ``tests/test_zz_deepseek_mla.py``)."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=512)
    assert cfg.widest_prefill == 256
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  kernel="lax", prefill_budget=256)
    assert engine.prefill_chunk == 256
    assert engine._path_of(256) == mla.LAX_PATH
    prompt = _tokens(50, 300, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=5, greedy=True)
    _drain(engine)
    assert _gap((cfg, params), prompt, req.tokens) < TOL
    engine.close()


def test_llm_generate_through_the_gateway(tiny):
    from lzy_tpu import llm
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)

    cfg, _ = tiny
    fleet = ReplicaFleet(lambda: _engine(tiny, slots=2))
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(16),
                             model_name="moonlight-tiny", page_size=16)
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


# -- each mechanism works over the latent leaf, or refuses by name ------------

def test_speculation_rewinds_the_latent_pool_by_its_index(tiny):
    """A verify window of three positions a row reads through the decode
    kernel (T = 3); a rejected draft is rewound by the index alone."""
    cfg, _ = tiny
    engine = _engine(tiny, slots=2, spec_tokens=2, kernel="pallas")
    prompt = (_tokens(70, 6, cfg.vocab_size) * 6)[:33]     # n-grams to draft
    req = engine.submit(prompt, max_new_tokens=14, greedy=True)
    _drain(engine)
    assert engine.spec_steps > 0 and len(req.tokens) == 14
    assert _gap(tiny, prompt, req.tokens) < TOL
    engine.close()


def test_parking_pins_a_conversations_latent_pages(tiny):
    cfg, _ = tiny
    engine = _engine(tiny, slots=1)
    prompt = _tokens(71, 40, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=4, greedy=True)
    _drain(engine)
    assert engine.park_chain("conv:1", prompt + list(req.tokens))
    _drain(engine, limit=5)
    assert engine.stats().kv_parked_chains == 1
    assert engine.stats().kv_parked_blocks >= 2
    engine.close()


def test_export_and_import_move_the_latent_leaf_by_block_id(tiny):

    cfg, _ = tiny
    prompt = _tokens(72, 37, cfg.vocab_size)
    source = _engine(tiny, slots=1)
    a = source.submit(prompt, max_new_tokens=5, greedy=True)
    _drain(source)
    export = source.kv_io.export_kv(prompt)
    assert export is not None and len(export.tokens) == 32
    assert all(leaf.shape == (2, 16, 128)
               for leaf in export.leaves.values()) and len(export.leaves) == 3
    target = _engine(tiny, slots=1)
    assert target.kv_io.import_kv(export) == 2
    b = target.submit(prompt, max_new_tokens=5, greedy=True)
    _drain(target)
    assert target.stats().prefill_tokens_saved == 32
    assert b.tokens == a.tokens
    source.close()
    target.close()


def test_the_host_tier_demotes_and_promotes_latent_pages(tiny):
    cfg, _ = tiny
    engine = _engine(tiny, slots=1, kv_blocks=5, kv_host_tier_bytes=1 << 20)
    a = _tokens(73, 49, cfg.vocab_size)
    b = _tokens(74, 41, cfg.vocab_size)
    first = engine.submit(a, max_new_tokens=6, greedy=True)
    _drain(engine)
    engine.submit(b, max_new_tokens=6, greedy=True)   # evicts a's pages
    _drain(engine)
    assert engine.kv_tier.stats()["demotions"] > 0
    again = engine.submit(a, max_new_tokens=6, greedy=True)
    _drain(engine)
    assert engine.kv_tier.stats()["promotions"] > 0
    assert again.tokens == first.tokens
    assert _gap(tiny, a, again.tokens) < TOL
    engine.close()


@pytest.mark.parametrize("mechanism", ["int8 pool", "sharded engine"])
def test_each_refusal_names_its_mechanism(tiny, mechanism):
    cfg, params = tiny
    if mechanism == "int8 pool":
        with pytest.raises(ds.LatentPoolUnsupported, match="kv_quant"):
            _engine(tiny, kv_quant="int8")
    else:
        from lzy_tpu.serving.sharded import (
            NoPartitionRules, ShardedPagedInferenceEngine)

        with pytest.raises(NoPartitionRules, match="sharded engine"):
            ShardedPagedInferenceEngine(cfg, params, tp=2, slots=2)


def test_the_engine_names_no_model():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "lzy_tpu", "serving", "engine.py")) as f:
        text = f.read().lower()
    for word in ("deepseek", "moonlight", "latent", "mla"):
        assert word not in text, word

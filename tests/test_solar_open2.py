"""Solar-Open2 on the serving path: the model against the benchmark's plain
float32 reference (the per-token delta rule, dense experts), the expert
shares, the precision guards, and the model through
``PagedInferenceEngine`` (KDA state beside the paged pool). Tiny widths,
seeded weights, CPU, Pallas kernels interpreted (``tests/conftest.py``)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import solar_open2 as ref
from lzy_tpu.models import serving
from lzy_tpu.models import solar_open2 as so
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import kda
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import StateLeavesUnsupported
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths (0.02 is about 4096 ** -0.5); at the tiny ones it would shrink
    every mixer's output to nothing and a wrong expert or a lost state would
    hide under the tolerance. Rescale each matrix to fan_in ** -0.5."""
    def fix(path, leaf):
        name = path[-1].key
        if name in ("kernel", "experts_gate", "experts_up", "experts_down",
                    "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = so.SolarOpen2Config.tiny()
    return cfg, _unit_scale(so.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 40, cfg.vocab_size)])
    got, stats = so.SolarOpen2(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.reference_logits(params, toks, jnp.arange(40), cfg)
    assert np.abs(got[0] - want).max() < TOL
    chosen = stats["intermediates"]["layer_1_moe"]["chosen"][0]
    assert chosen.shape == (40, cfg.top_k)
    total = sum(jax.tree_util.tree_leaves(stats["stats"]))
    assert list(np.asarray(total)) == [
        40 * cfg.top_k * cfg.n_layers, 40 * cfg.top_k * cfg.n_layers,
        int(total[2]), cfg.n_held * cfg.n_layers]
    assert 0 < int(total[2]) <= cfg.n_held * cfg.n_layers


@pytest.mark.parametrize("under", ["jit", "grad"])
def test_into_heads_is_a_reshape_and_gradients_pass(tiny, under):
    """``paged_blocks.into_heads`` keeps the compiler from laying a
    projection's weight out for its heads; to the arithmetic it is a reshape,
    bit for bit, and ``grad`` of an uncached forward through
    ``PagedAttention`` (layer 0's here) still reaches the projections."""
    from lzy_tpu.models.paged_blocks import PagedAttention, into_heads

    y = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 24), jnp.float32)
    if under == "jit":
        split = jax.jit(lambda y: into_heads(y, 2, 5, 4, 6))
        for y in (y, y.astype(jnp.bfloat16)):
            got = split(y)
            assert got.shape == (2, 5, 4, 6) and got.dtype == y.dtype
            assert np.array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(y.astype(jnp.float32)).reshape(2, 5, 4, 6))
        return
    w = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 4, 6), jnp.float32)
    got = jax.grad(lambda y: jnp.sum(into_heads(y, 2, 5, 4, 6) * w))(y)
    assert np.array_equal(np.asarray(got), np.asarray(w).reshape(2, 5, 24))
    cfg, params = tiny
    assert 0 in cfg.attn_layers
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 12, cfg.d_model),
                          jnp.float32)

    def loss(layer):
        return jnp.sum(PagedAttention(cfg).apply({"params": layer}, u) ** 2)

    grads = jax.jit(jax.grad(loss))(params["layer_0"])
    for name in ("q_proj", "k_proj", "v_proj"):
        g = np.asarray(grads[name]["kernel"])
        assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_the_references_delta_rule_is_the_written_recurrence():
    """``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T`` with the
    matrices written out, in numpy float64."""
    rng = np.random.default_rng(7)
    t, h, d = 12, 2, 8
    q, k, v = (rng.normal(size=(t, h, d)) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    alpha = rng.uniform(0.2, 1.0, size=(t, h, d))
    beta = rng.uniform(0.0, 2.0, size=(t, h))
    want = np.zeros((t, h, d))
    for j in range(h):
        s = np.zeros((d, d))
        for i in range(t):
            kk = k[i, j][:, None]
            s = (np.eye(d) - beta[i, j] * kk @ kk.T) @ np.diag(alpha[i, j]) \
                @ s + beta[i, j] * kk @ v[i, j][None, :]
            want[i, j] = s.T @ q[i, j]
    got = ref.delta_rule(*(jnp.asarray(a, jnp.float32)
                           for a in (q, k, v, alpha, beta)))
    assert np.abs(got - want).max() < 1e-5


def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        tiny):
    """Logits, not tokens: a prefill chunk, a padded chunk that carries the
    state (two scan chunks of 8 each), then one position at a time through
    the update kernel."""
    cfg, params = tiny
    model = cfg.paged_model(page_size=16, kv_pages=8, kernel="pallas",
                            kv_quant=None)
    toks = _tokens(3, 45, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks]), jnp.arange(45), cfg))
    table = jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0]], jnp.int32)
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
        return cache, np.asarray(logits[0, :real])

    got = []
    cache, out = run(cache, toks[:16], 16)
    got.append(out)
    cache, out = run(cache, toks[16:29], 13)      # padded to 16
    got.append(out)
    for tok in toks[29:]:
        cache, out = run(cache, [tok], 1)
        got.append(out)
    assert np.abs(np.concatenate(got) - want).max() < TOL


def test_the_shares_add_up(tiny):
    """Eight chips hold 2 of the 16 routed experts each (the deployment's
    eight, 40 of 320). What each computes for the layer, with the shared
    expert (which every chip computes alike) counted once, adds up to the
    uncut layer: in the program, and to the reference's uncut layer."""
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
        out, _ = so.GatedExperts(c).apply({"params": w}, u,
                                          mutable=["stats"])
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
        shares = [layer_fn(lo, lo + 2) for lo in range(0, 16, 2)]
        summed = sum(s - shared for s in shares) + shared
        assert np.abs(summed - uncut).max() < TOL
        # a share alone is not the layer: the cut is real
        assert np.abs(shares[0] - uncut).max() > 10 * TOL
    assert np.abs(program(4, 6) - reference(4, 6)).max() < TOL


# -- the precision guards -----------------------------------------------------

def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_router_tells_apart_scores_that_tie_in_bfloat16():
    """Two experts whose sigmoid scores differ by 1e-4 at the edge of the
    choice: float32 scores pick the larger; scores rounded to bfloat16 tie.
    The activations' dtype is bfloat16 here, as it is served."""
    cfg = dataclasses.replace(so.SolarOpen2Config.tiny(),
                              dtype=jnp.bfloat16)
    layer = so.GatedExperts(cfg)
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
    assert seen["intermediates"]["chosen"][0].dtype == jnp.int32
    chosen = set(np.asarray(seen["intermediates"]["chosen"][0]).ravel())
    assert chosen == {0, 1, 2, 4}


def test_state_leaves_are_float32_whatever_the_activations_are():
    model = dataclasses.replace(
        so.SolarOpen2Config.tiny(), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16).paged_model(
            page_size=16, kv_pages=9, kernel="lax", kv_quant=None)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 8), jnp.int32)))["cache"]
    leaves = {path[-1].key: leaf for path, leaf
              in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert leaves["kda"].dtype == jnp.float32
    assert leaves["kda"].shape == (2, 8, 16, 16)
    assert leaves["conv"].shape == (2, 3, 3 * 8 * 16)
    assert leaves["conv"].dtype == leaves["k"].dtype == jnp.bfloat16


def _config_doc():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "solar-open2-serve-l8-ep8.json")
    with open(path) as f:
        return json.load(f)


def test_program_config_reads_the_published_widths():
    cfg = ref.program_config(_config_doc())
    assert (cfg.d_model, cfg.n_layers, cfg.attn_layers) == (4096, 8, (0, 4))
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel) == (64, 128, 4)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.top_k) \
        == (320, (0, 40), 8)
    assert (cfg.expert_width, cfg.shared_width) == (1280, 1280)
    assert (cfg.vocab_size, cfg.max_seq_len) == (24576, 4608)


def test_program_config_refuses_a_state_leaf_of_another_type():
    with pytest.raises(ValueError, match="kda_state_dtype bfloat16"):
        ref.program_config(dict(_config_doc(), kda_state_dtype="bfloat16"))
    with pytest.raises(ValueError, match="use_rope"):
        ref.program_config(dict(_config_doc(), use_rope=True))


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: what Mosaic's lowering would refuse at the
    first request is refused here (the engine asks at construction). The
    attention read at 64 query / 8 key-value heads and 288 pages a slot is
    the engine's own check (``tests/test_aot_topology.py`` compiles it)."""
    from lzy_tpu.ops.paged_attention import lower_pallas_for_tpu

    cfg = ref.program_config(_config_doc())
    cfg.check_kernels(slots=32)
    lower_pallas_for_tpu(
        batch=32, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=4096, page_size=16,
        pages_per_seq=288, dtype=cfg.dtype)


# -- through the engine -------------------------------------------------------

def _engine(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("slots", 3)
    kw.setdefault("kernel", "pallas")
    return PagedInferenceEngine(
        cfg, params, page_size=16, prefill_chunk=16, **kw)


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


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, one mixed run: prompts whose last chunk is padded (37,
    5, 21, 9) and not (48), a budget that splits the long prompts over
    rounds while the short ones already decode, more requests than slots so
    that slots are reused after longer requests."""
    cfg, _ = tiny
    engine = _engine(tiny, prefill_budget=16)
    engine.warmup()
    before = {n: _counter(n) for n in (
        "lzy_moe_assignments_total", "lzy_moe_held_assignments_total",
        "lzy_moe_experts_held_total", "lzy_state_slots_reset_total")}
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
    # resident rows x experts a token, a layer a round: idle slots' rows
    # and slots in the middle of a prefill are not counted
    assert counted["lzy_moe_assignments_total"] \
        == engine.decode_rows * cfg.top_k * cfg.n_layers
    assert counted["lzy_moe_held_assignments_total"] \
        == counted["lzy_moe_assignments_total"]      # all 16 held here
    assert counted["lzy_moe_experts_held_total"] \
        == engine.decode_steps * cfg.n_held * cfg.n_layers
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        "rows" in s.attrs and set(s.attrs["model_stats"])
        == {c.name for c in so.SolarOpen2.STATS} for s in emits)


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (kda.SCAN_PATH, kda.UPDATE_PATH, gexp.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text


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


def test_the_widest_program_carries_the_state_over_its_scan_chunks(tiny):
    """The cell's shape: no ``prefill_chunk`` given, a budget of 256, so a
    program of 256 positions (32 scan chunks of 8 here) and a padded tail
    that starts from the carried state."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, max_seq_len=512)
    assert cfg.widest_prefill == 256
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
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
    assert kinds.count(serving.STATE) == 2 * 3          # conv, kda x 3 KDA
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
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(16),
                             model_name="solar-open2-tiny", page_size=16)
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
            _engine(tiny, kv_storage_tier="mem://tier-refused-solar")
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
    assert "solar" not in text and "nemotron" not in text

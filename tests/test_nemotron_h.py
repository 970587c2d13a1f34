"""Nemotron-H on the serving path: the Mamba-2 scan and state update, the
dropless product over held experts, the model against the benchmark's plain
float32 reference, and the model through ``PagedInferenceEngine`` (per-slot
state beside the paged pool). Tiny widths, seeded weights, CPU, Pallas
kernels interpreted (``tests/conftest.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import nemotron_h as ref
from lzy_tpu.models import nemotron_h as nh
from lzy_tpu.models import serving
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mamba2
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import StateLeavesUnsupported
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4


# -- ops ----------------------------------------------------------------------

def _recurrence(x, dt, a, b, c, state):
    """The plain recurrence, one position after another, in numpy."""
    bsz, t, h, p = x.shape
    rep = h // b.shape[2]
    b, c = np.repeat(b, rep, axis=2), np.repeat(c, rep, axis=2)
    ys = []
    for i in range(t):
        decay = np.exp(dt[:, i] * a)[:, :, None, None]
        state = decay * state + (dt[:, i][:, :, None] * x[:, i])[..., None] \
            * b[:, i][:, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", state, c[:, i]))
    return np.stack(ys, 1), state


def _ssm_inputs(seed=0, bsz=2, t=40, h=16, p=8, n=128, g=2):
    rng = np.random.default_rng(seed)
    f = np.float32
    x = rng.normal(size=(bsz, t, h, p)).astype(f)
    dt = (np.log1p(np.exp(rng.normal(size=(bsz, t, h)))) * 0.1).astype(f)
    a = -np.exp(rng.uniform(0, 2.5, size=(h,))).astype(f)
    b = rng.normal(size=(bsz, t, g, n)).astype(f)
    c = rng.normal(size=(bsz, t, g, n)).astype(f)
    s0 = rng.normal(size=(bsz, h, p, n)).astype(f)
    return x, dt, a, b, c, s0


@pytest.mark.parametrize("chunk", [128, 16, 7])
def test_scan_is_the_recurrence(chunk):
    x, dt, a, b, c, s0 = _ssm_inputs()
    want_y, want_s = _recurrence(x, dt, a, b, c, s0.copy())
    y, s = mamba2.ssd_chunk_scan(x, dt, a, b, c, jnp.asarray(s0),
                                 chunk=chunk)
    assert np.abs(y - want_y).max() < 1e-4
    assert np.abs(s - want_s).max() < 1e-5


def test_scan_freezes_the_state_where_dt_is_zero():
    x, dt, a, b, c, s0 = _ssm_inputs(t=24)
    dt[1, 10:] = 0.0
    _, s = mamba2.ssd_chunk_scan(x, dt, a, b, c, jnp.asarray(s0), chunk=16)
    _, short = mamba2.ssd_chunk_scan(x[:, :10], dt[:, :10], a, b[:, :10],
                                     c[:, :10], jnp.asarray(s0), chunk=16)
    assert np.abs(np.asarray(s)[1] - np.asarray(short)[1]).max() < 1e-6


def test_decode_update_is_the_scan_one_position_at_a_time():
    x, dt, a, b, c, s0 = _ssm_inputs(t=12)
    dt[0, 5] = 0.0                      # an idle row keeps its state
    want_y, want_s = mamba2.ssd_chunk_scan(x, dt, a, b, c, jnp.asarray(s0),
                                           chunk=128)
    s, ys = jnp.asarray(s0), []
    for i in range(x.shape[1]):
        before = np.asarray(s)
        y, s = mamba2.ssm_state_update(s, x[:, i], dt[:, i], a, b[:, i],
                                       c[:, i])
        ys.append(y)
        if i == 5:                      # skipped: not moved, y = 0
            assert np.array_equal(np.asarray(s)[0], before[0])
            assert not np.asarray(y)[0].any()
    live = dt != 0.0
    got = np.stack(ys, 1)
    assert np.abs((got - want_y) * live[..., None]).max() < 1e-4
    assert np.abs(s - want_s).max() < 1e-5


def test_decode_update_with_no_live_row_moves_nothing():
    x, dt, a, b, c, s0 = _ssm_inputs(t=1)
    y, s = mamba2.ssm_state_update(jnp.asarray(s0), x[:, 0], 0.0 * dt[:, 0],
                                   a, b[:, 0], c[:, 0])
    assert np.array_equal(np.asarray(s), s0) and not np.asarray(y)[1].any()


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_state_is_float32_over_a_long_slow_recurrence():
    """The precision guard the benchmark's ``correct`` cannot be (served
    tokens hardly see the state's precision: benchmark/models/nemotron_h.py
    ``LOGIT_TIE_TOL``): a head that forgets slowly (``dt A`` = -0.001) adds
    increments far smaller than its state; over a prompt and a stretch of
    decode positions the program's state must stay with the float64
    recurrence, where a state rounded to bfloat16 after every position
    (what "keep the state in bf16" would do) is visibly another result."""
    rng = np.random.default_rng(5)
    bsz, t, h, p, n, g = 1, 512, 16, 8, 128, 2
    x = rng.normal(size=(bsz, t + 32, h, p)).astype(np.float32)
    b = rng.normal(size=(bsz, t + 32, g, n)).astype(np.float32)
    c = rng.normal(size=(bsz, t + 32, g, n)).astype(np.float32)
    dt = np.full((bsz, t + 32, h), 1e-3, np.float32)
    a = -np.ones((h,), np.float32)
    s0 = np.zeros((bsz, h, p, n), np.float32)

    def recurrence(rounding):
        state = s0.astype(np.float64)
        bb = np.repeat(b, h // g, axis=2).astype(np.float64)
        for i in range(t + 32):
            state = np.exp(dt[:, i] * a)[:, :, None, None] * state \
                + (dt[:, i][:, :, None] * x[:, i])[..., None] \
                * bb[:, i][:, :, None, :]
            state = rounding(state)
        return state

    want = recurrence(lambda s: s)
    rough = recurrence(lambda s: _bf16(s).astype(np.float64))
    _, state = mamba2.ssd_chunk_scan(
        *(jnp.asarray(v[:, :t]) for v in (x, dt)), jnp.asarray(a),
        jnp.asarray(b[:, :t]), jnp.asarray(c[:, :t]), jnp.asarray(s0),
        chunk=128)
    for i in range(t, t + 32):
        _, state = mamba2.ssm_state_update(
            state, jnp.asarray(x[:, i]), jnp.asarray(dt[:, i]),
            jnp.asarray(a), jnp.asarray(b[:, i]), jnp.asarray(c[:, i]))
    assert state.dtype == jnp.float32
    scale = np.abs(want).mean()
    assert np.abs(np.asarray(state) - want).mean() < 1e-5 * scale
    assert np.abs(rough - want).mean() > 1e-2 * scale


def test_the_router_tells_apart_scores_that_tie_in_bfloat16():
    """The other precision guard: two experts whose sigmoid scores differ
    by 1e-4 at the edge of the choice. Float32 scores pick the larger;
    scores rounded to bfloat16 tie (spacing 0.004 near 0.5) and ``top_k``
    would then take the lower index. The activations' dtype is bfloat16
    here, as it is served."""
    cfg = dataclasses.replace(nh.NemotronHConfig.tiny(),
                              dtype=jnp.bfloat16)
    layer = nh.LatentExperts(cfg)
    u = jnp.zeros((1, 1, cfg.d_model), jnp.float32).at[0, 0, 0].set(1.0)
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    logits = np.linspace(-3.0, -2.0, cfg.n_routed_experts).astype(np.float32)
    logits[[0, 1, 2]] = 2.0, 1.5, 1.0          # three clear choices
    logits[3], logits[4] = 0.1000, 0.1004      # the fourth: expert 4, by 1e-4
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    assert 5e-5 < scores[4] - scores[3] < 2e-4
    assert _bf16(scores[3]) == _bf16(scores[4])
    params = dict(params)
    params["router"] = jnp.zeros_like(params["router"]).at[0].set(logits)
    params["router_bias"] = jnp.zeros_like(params["router_bias"])
    _, seen = layer.apply({"params": params}, u.astype(cfg.dtype),
                          mutable=["intermediates", "stats"])
    chosen = set(np.asarray(seen["intermediates"]["chosen"][0]).ravel())
    assert chosen == {0, 1, 2, 4}


def test_state_leaves_are_float32_whatever_the_activations_are():
    cfg = dataclasses.replace(
        nh.NemotronHConfig.tiny(), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16).paged_model(
            page_size=16, kv_pages=9, native=True, kernel="lax",
            kv_quant=None)
    shapes = jax.eval_shape(lambda: cfg.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 8), jnp.int32)))["cache"]
    dtypes = {path[-1].key: leaf.dtype for path, leaf
              in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert dtypes["ssm"] == jnp.float32
    assert dtypes["conv"] == dtypes["k"] == dtypes["v"] == jnp.bfloat16


def _expert_inputs(m=16, latent=128, width=256, e=8, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    x = rng.normal(size=(m, latent)).astype(f)
    w1 = (rng.normal(size=(e, latent, width)) * 0.1).astype(f)
    w2 = (rng.normal(size=(e, width, latent)) * 0.1).astype(f)
    w = (rng.uniform(size=(m, e)) * (rng.uniform(size=(m, e)) < 0.3)
         ).astype(f)
    return x, w1, w2, w


@pytest.mark.parametrize("case", ["mixed", "nobody", "everybody", "one_row"])
def test_grouped_experts_is_dropless_and_exact(case):
    x, w1, w2, w = _expert_inputs()
    if case == "mixed":
        w[:, 3] = 0.0                   # experts nobody chose are skipped
        w[:, 7] = 0.0
    elif case == "nobody":
        w[:] = 0.0
    elif case == "everybody":           # far over any capacity: no drop
        w[:] = 1.0
    else:
        w[1:] = 0.0
    want = sum((np.maximum(x @ w1[i], 0) ** 2 * w[:, i:i + 1]) @ w2[i]
               for i in range(w.shape[1]))
    with jax.default_matmul_precision("highest"):
        got = gexp.grouped_experts(x, w1, w2, w)
        oracle = gexp.lax_grouped_experts(x, w1, w2, w)
    scale = max(1.0, float(np.abs(want).max()))
    assert np.abs(got - want).max() < 1e-4 * scale
    assert np.abs(oracle - want).max() < 1e-4 * scale


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: what Mosaic's lowering would refuse at the
    first request is refused here (the engine asks at construction)."""
    nh.NemotronHConfig(experts_held=(0, 128)).check_kernels(slots=64)


# -- the model against the reference ------------------------------------------

def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths (0.02 is about 4096 ** -0.5); at the tiny ones it would shrink
    every mixer's output to nothing and a wrong expert or a lost state would
    hide under the tolerance. Rescale each matrix to fan_in ** -0.5."""
    def fix(path, leaf):
        name = path[-1].key
        if name in ("kernel", "experts_w1", "experts_w2", "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = nh.NemotronHConfig.tiny()
    return cfg, _unit_scale(nh.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def test_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 40, cfg.vocab_size)])
    got, stats = nh.NemotronH(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.logits_at(params, toks, jnp.arange(40), cfg)
    assert np.abs(got[0] - want).max() < TOL
    # a row's choices, for whoever measures router swaps against a reference
    chosen = stats["intermediates"]["layer_1"]["chosen"][0]
    assert chosen.shape == (40, cfg.top_k)
    layers = cfg.pattern.count("E")
    total = sum(jax.tree_util.tree_leaves(stats["stats"]))
    assert list(np.asarray(total)) == [
        40 * cfg.top_k * layers, 40 * cfg.top_k * layers,
        int(total[2]), cfg.n_held * layers]
    assert 0 < int(total[2]) <= cfg.n_held * layers


def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        tiny):
    """Logits, not tokens: a padded prefill chunk, then chunks that carry
    the state, then one position at a time through the update kernel."""
    cfg, params = tiny
    model = cfg.paged_model(page_size=16, kv_pages=8, native=True,
                            kernel="pallas", kv_quant=None)
    toks = _tokens(3, 45, cfg.vocab_size)
    want = np.asarray(ref.logits_at(params, jnp.asarray([toks]),
                                    jnp.arange(45), cfg))
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
    """Four chips hold 4 of the 16 routed experts each. What each computes
    for the layer, with the shared expert (which every chip computes alike)
    counted once, adds up to the uncut layer: in the program and in the
    reference."""
    cfg, params = tiny
    layer = params["layer_1"]
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 24, cfg.d_model)).astype(np.float32))

    def cut(lo, hi):
        c = dataclasses.replace(cfg, experts_held=(lo, hi))
        w = dict(layer, experts_w1=layer["experts_w1"][lo:hi],
                 experts_w2=layer["experts_w2"][lo:hi])
        return c, w

    def program(lo, hi):
        c, w = cut(lo, hi)
        out, _ = nh.LatentExperts(c).apply({"params": w}, u,
                                           mutable=["stats"])
        return np.asarray(out[0])

    def reference(lo, hi):
        c, w = cut(lo, hi)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref._experts(u[0], w, c, jnp.float32))

    with jax.default_matmul_precision("highest"):
        shared = np.asarray(
            jnp.square(jax.nn.relu(u[0] @ layer["shared_w1"]["kernel"]))
            @ layer["shared_w2"]["kernel"])
    for layer_fn in (program, reference):
        whole = layer_fn(0, 16)
        shares = [layer_fn(lo, lo + 4) for lo in (0, 4, 8, 12)]
        summed = sum(s - shared for s in shares) + shared
        assert np.abs(summed - whole).max() < TOL
        # a share alone is not the layer: the cut is real
        assert np.abs(shares[0] - whole).max() > 10 * TOL
    assert np.abs(program(4, 8) - reference(4, 8)).max() < TOL


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
    logits = np.asarray(ref.logits_at(
        params, jnp.asarray([full]),
        jnp.arange(len(prompt) - 1, len(full) - 1), cfg))
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            if line.split(" ")[0] == name:
                return float(line.rsplit(" ", 1)[1])
    return 0.0


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
    lengths, budgets = (37, 5, 48, 21, 9, 30), (12, 20, 6, 10, 15, 4)
    prompts = [_tokens(10 + i, n, cfg.vocab_size)
               for i, n in enumerate(lengths)]
    with trace.recording() as rec:
        reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
                for p, m in zip(prompts, budgets)]
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
    assert len(req.tokens) == (12, 20, 6, 10, 15, 4)[i]
    assert _gap(tiny, prompt, req.tokens) < TOL


def test_prompts_were_split_over_rounds_while_rows_decoded(served):
    engine = served["engine"]
    assert engine.prefill_rounds > len(served["prompts"])
    rounds = [s for s in served["spans"] if s.name == trace.ENGINE_ROUND]
    assert any(r.attrs.get("rows", 0) >= 2 for r in rounds)


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
    layers = cfg.pattern.count("E")
    # resident rows x experts a token, a layer a round: idle slots' rows
    # and slots in the middle of a prefill are not counted
    assert counted["lzy_moe_assignments_total"] \
        == engine.decode_rows * cfg.top_k * layers
    assert counted["lzy_moe_held_assignments_total"] \
        == counted["lzy_moe_assignments_total"]      # all 16 held here
    assert counted["lzy_moe_experts_held_total"] \
        == engine.decode_steps * cfg.n_held * layers


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (mamba2.SCAN_PATH, mamba2.UPDATE_PATH, gexp.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text


def test_radix_match_is_zero_and_nothing_is_cached(tiny, served):
    cfg, _ = tiny
    engine = served["engine"]
    assert engine.kv.lookup_tokens > 0 and engine.kv.hit_tokens == 0
    assert engine.stats().kv_blocks_cached == 0
    # the same prompt again: still no hit, and the same tokens
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


def test_cache_leaves_are_declared_by_kind(served):
    engine = served["engine"]
    kinds = engine._leaf_kinds
    assert kinds.count(serving.STATE) == 2 * 2          # conv, ssm x 2 M
    assert kinds.count(serving.PAGED) == 2              # k, v x 1 attention
    assert kinds.count(serving.INDEX) == 1
    slots = engine.slots
    for i, leaf in enumerate(engine._payload):
        assert (leaf.shape[0] == slots) == (i in engine._state_at)


@pytest.mark.parametrize("mechanism", [
    "speculation", "host tier", "storage tier", "parking", "import",
    "export", "sharded engine", "gather read", "int8 pool"])
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
            _engine(tiny, kv_storage_tier="mem://tier-refused")
    elif mechanism == "sharded engine":
        from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine
        from lzy_tpu.serving.sharded import NoPartitionRules

        with pytest.raises(NoPartitionRules, match="sharded engine"):
            ShardedPagedInferenceEngine(cfg, params, tp=2, slots=2)
    elif mechanism == "gather read":
        with pytest.raises(ValueError, match="gather read"):
            _engine(tiny, native_attention=False)
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

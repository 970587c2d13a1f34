"""MiniCPM-SALA on the serving path: the model against the benchmark's plain
float32 reference (block-sparse attention that chooses its key blocks from
compressed keys, lightning layers with a decayed state a slot), the choice
itself compared as sets, and the model through ``PagedInferenceEngine``
(three paged leaves a sparse layer under one page table, a state leaf a
lightning layer, the request's mode fixed at admission). Tiny widths, seeded
weights, CPU, Pallas kernels interpreted (``tests/conftest.py``)."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import minicpm_sala as ref
from lzy_tpu.models import minicpm_sala as sala
from lzy_tpu.models import serving
from lzy_tpu.ops import mamba2
from lzy_tpu.ops import sparse_attention as sp
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import StateLeavesUnsupported
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-5
#: the stated band of the program in bfloat16 (activations and products; the
#: weights, the stream and the state stay float32) against the float32
#: reference, on logits that spread by about 0.2: a twentieth of that
BF16_BAND = 0.03


@pytest.fixture(scope="module")
def tiny():
    cfg = sala.MiniCPMSalaConfig.tiny()
    return cfg, sala.init_params(cfg, jax.random.PRNGKey(1))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _through_the_cache(cfg, params, toks, n_prompt, *, kernel="lax",
                       width=32):
    """The paged module run as the engine runs it, batch 1: the prompt in
    chunks of ``width`` (the last one padded), the rest one position a
    program. Returns ``(logits [T, V], cache)``."""
    page = cfg.sparse.block_size
    total = len(toks)
    pages = -(-(total + width) // page)
    module = cfg.paged_model(page_size=page, kv_pages=pages + 1,
                             kernel=kernel, kv_quant=None)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    cache = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                        page_table=table)["cache"]
    told = jnp.asarray([n_prompt])

    @functools.partial(jax.jit, static_argnames=("t",))
    def step(cache, chunk, take, at, *, t):
        # the positions are the caller's, as they are the engine's
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.full_like(leaf, at)
            if getattr(path[-1], "key", None) == "index" else leaf, cache)
        logits, upd = module.apply(
            {"params": params, "cache": cache}, chunk, page_table=table,
            valid_len=take, mutable=["cache"],
            **({"prompt_len": told} if t > 1 else {}))
        return logits[0], upd["cache"]

    out, at = [], 0
    while at < total:
        t = width if at < n_prompt else 1
        take = min(t, (n_prompt if t > 1 else total) - at)
        chunk = np.zeros((1, t), np.int32)
        chunk[0, :take] = toks[at:at + take]
        logits, cache = step(cache, jnp.asarray(chunk), jnp.asarray([take]),
                             jnp.int32(at), t=t)
        out.append(np.asarray(logits[:take]))
        at += take
    return np.concatenate(out), cache


def _want(tiny, toks, n_prompt, dtype=jnp.float32):
    cfg, params = tiny
    return np.asarray(ref.reference_logits(
        params, jnp.asarray([toks]), np.arange(len(toks)), cfg, dtype,
        prompt_len=n_prompt))


# -- the configuration --------------------------------------------------------

def test_it_answers_the_serving_protocol():
    cfg = sala.MiniCPMSalaConfig()
    assert cfg.serving_config() is cfg
    # keys and values a head in bfloat16 and a sixteenth of a float32
    # compressed key a head
    assert cfg.kv_token_bytes(None) == 1024 + 64
    assert (cfg.n_layers, cfg.kv_layers, cfg.lightning_layers) == (10, 2, 8)
    assert cfg.widest_prefill == 256
    assert cfg.read_path("pallas", t=1) == sp.DECODE_PATH
    assert cfg.read_path("pallas", t=256) == sp.PREFILL_PATH
    assert cfg.read_path("lax", t=1) == "lax"
    assert cfg.kernel_paths(1) == (sp.SELECT_DECODE_PATH, mamba2.UPDATE_PATH)
    assert cfg.kernel_paths(256) == (sp.SELECT_PREFILL_PATH,
                                     mamba2.SCAN_PATH)
    with pytest.raises(ValueError, match="kv_quant"):
        cfg.paged_model(page_size=64, kv_pages=3, kernel="lax",
                        kv_quant="int8")
    with pytest.raises(ValueError, match="a selector block is a page"):
        cfg.paged_model(page_size=16, kv_pages=3, kernel="lax",
                        kv_quant=None)
    assert sala.MiniCPMSala.CACHE_KINDS == {
        "k": "paged", "v": "paged", "ck": "paged", "index": "index",
        "state": "state", "sparse": "state"}
    assert [c.name for c in sala.MiniCPMSala.STATS] == [
        "lzy_sparse_blocks_visible_total", "lzy_sparse_blocks_read_total",
        "lzy_sparse_rows_total", "lzy_sparse_dense_rows_total",
        "lzy_lightning_rows_total"]
    assert sala.MiniCPMSala.TOLD_PROMPT_LEN
    with pytest.raises(ValueError, match="mixer_types"):
        sala.MiniCPMSalaConfig(mixer_types=("mamba",))
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12


def _config_doc():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "minicpm-sala-serve-l18.json")
    with open(path) as f:
        return json.load(f)


def test_program_config_reads_the_published_widths():
    cfg = ref.program_config(_config_doc())
    assert (cfg.d_model, cfg.n_layers, cfg.d_ff) == (4096, 18, 16384)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.lightning_heads, cfg.lightning_head_dim) == (32, 128)
    assert [i for i, kind in enumerate(cfg.mixer_types)
            if kind == sala.SPARSE] == [0, 9, 16, 17]
    assert (cfg.depth, cfg.scale_emb, cfg.scale_depth, cfg.dim_model_base) \
        == (32, 12.0, 1.4, 256)
    assert cfg.sparse == sp.SparseSpec(32, 16, 64, 64, 1, 2048)
    assert (cfg.dense_len, cfg.vocab_size, cfg.max_seq_len) \
        == (8192, 73448, 33792)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert cfg.state_dtype == jnp.float32
    shapes = jax.eval_shape(
        lambda: sala.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 5_609_842_944


@pytest.mark.parametrize("key,value", [
    ("attn_use_rope", True), ("lightning_use_rope", False),
    ("qk_norm", False), ("use_output_gate", False),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("lightning_nkv", 8), ("mixer_types", ["minicpm4"] * 17 + ["mamba"]),
    ("lightning_state_dtype", "bfloat16"), ("residual_dtype", "bfloat16")])
def test_program_config_refuses_what_the_program_cannot_honour(key, value):
    with pytest.raises(ValueError, match=key):
        ref.program_config(dict(_config_doc(), **{key: value}))


def test_the_configuration_file_lists_what_it_assumed_and_cut():
    doc = _config_doc()
    assert doc["reduced"] == ["num_hidden_layers", "mixer_types",
                              "max_position_embeddings"]
    assert doc["published"]["num_hidden_layers"] == 32
    assert doc["published"]["mixer_types"][:18] == doc["mixer_types"]
    assert doc["published"]["max_position_embeddings"] == 524288
    for key in ("sparse_config", "window_in_blocks", "ties",
                "mode_fixed_at_admission", "lightning_decay",
                "lightning_activations", "unused_keys"):
        assert key in doc["assumed"], key


def test_the_reference_keeps_clear_of_the_programs_ops():
    with open(ref.__file__) as f:
        text = f.read()
    assert "ops.sparse_attention" not in text
    assert "import sparse_attention" not in text
    assert "import mamba2" not in text and "ops.mamba2" not in text
    assert 'default_matmul_precision("highest")' in text


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("n_prompt,kernel", [
    (300, "lax"), (100, "lax"), (135, "pallas")])
def test_prefill_then_decode_through_the_cache_gives_the_references_logits(
        tiny, n_prompt, kernel):
    """Chunks of 32 (the last one padded), then one position a program: a
    long prompt drops blocks (19 visible, 7 read), a short one reads
    everything; the compression kernel of 8 at stride 4 straddles chunk and
    page edges throughout."""
    cfg, params = tiny
    toks = _tokens(n_prompt, n_prompt + 5, cfg.vocab_size)
    got, _ = _through_the_cache(cfg, params, toks, n_prompt, kernel=kernel)
    want = _want(tiny, toks, n_prompt)
    assert np.abs(got - want).max() < TOL


def test_the_program_in_bfloat16_stays_within_the_stated_band(tiny):
    cfg, params = tiny
    toks = _tokens(7, 200, cfg.vocab_size)
    got, _ = _through_the_cache(
        dataclasses.replace(cfg, dtype=jnp.bfloat16), params, toks, 195)
    want = _want(tiny, toks, 195)
    off = np.abs(got - want).max()
    assert 1e-4 < off < BF16_BAND
    # and the all-bfloat16 control stands further off than the program
    assert np.abs(_want(tiny, toks, 195, jnp.bfloat16) - want).max() > off


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_the_choice_itself_is_the_references(tiny, kernel):
    """The set of blocks every (position, group, sparse layer) chose,
    program against reference, in float32: equal. The program's are its own
    selector's over the prompt's chunks and the decode positions
    (``program_choices``); the reference is never handed them."""
    cfg, params = tiny
    n_prompt, total = 210, 214
    toks = np.asarray([_tokens(3, total, cfg.vocab_size)])
    _, _, exact = ref.features(params, jnp.asarray(toks), cfg,
                               prompt_len=n_prompt)
    mine = ref.program_choices(params, toks, cfg, prompt_len=n_prompt,
                               last=total - 1, kernel=kernel)
    assert len(mine) == len(exact) == 2
    assert ref.choices_differ(mine, exact, total - 1) == (0, 2 * 2 * total)
    read = np.asarray(exact[0])[:, -1].sum(-1)
    assert (read == cfg.sparse.most_read).all() and total // 16 + 1 == 14
    # a choice made once a tile is not the reference's
    tiled = [np.repeat(m[:, ::8], 8, axis=1)[:, :total] for m in mine]
    assert ref.choices_differ(tiled, exact, total - 1)[0] > total


def test_a_request_under_dense_len_chooses_nothing(tiny):
    cfg, params = tiny
    toks = np.asarray([_tokens(4, 140, cfg.vocab_size)])
    _, _, exact = ref.features(params, jnp.asarray(toks), cfg, prompt_len=127)
    assert exact == []
    # decided by the prompt, not by where the sequence has got to
    _, _, exact = ref.features(params, jnp.asarray(toks), cfg, prompt_len=128)
    assert len(exact) == 2


def test_the_state_is_float32_whatever_the_activations_are():
    cfg = dataclasses.replace(sala.MiniCPMSalaConfig.tiny(),
                              dtype=jnp.bfloat16)
    model = cfg.paged_model(page_size=16, kv_pages=4, kernel="lax",
                            kv_quant=None)
    cache = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 3), jnp.int32)))["cache"]
    assert cache["layer_1"]["state"].dtype == jnp.float32
    assert cache["layer_0"]["ck"].dtype == jnp.float32
    assert cache["layer_0"]["k"].dtype == jnp.bfloat16
    assert cache["layer_0"]["ck"].shape == (4, 4, 2, 16)
    assert cache["sparse"].shape == (2,)


# -- through the engine -------------------------------------------------------

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
        np.arange(len(prompt) - 1, len(full) - 1), cfg,
        prompt_len=len(prompt)))
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == name:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


#: sparse (300, 130, 200), dense (40, 127, 64): more requests than slots, a
#: dense row beside a sparse one in the same rounds, padded last chunks
_LENGTHS, _BUDGETS = (300, 40, 130, 127, 200, 64), (8, 12, 6, 10, 5, 4)
_COUNTED = tuple(c.name for c in sala.MiniCPMSala.STATS) \
    + ("lzy_state_slots_reset_total",)


@pytest.fixture(scope="module")
def served(tiny):
    cfg, _ = tiny
    engine = _engine(tiny)
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


def test_a_dense_row_and_a_sparse_row_share_rounds(tiny, served):
    cfg, _ = tiny
    emits = [s.attrs["model_stats"] for s in served["spans"]
             if s.name == "engine.decode.emit"]
    both = [m for m in emits if m["lzy_sparse_rows_total"]
            and m["lzy_sparse_dense_rows_total"]]
    assert both
    for m in emits:
        assert m["lzy_sparse_blocks_read_total"] \
            <= m["lzy_sparse_blocks_visible_total"]
        assert m["lzy_sparse_blocks_read_total"] <= \
            m["lzy_sparse_rows_total"] * cfg.n_kv_heads \
            * cfg.sparse.most_read


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    rows = engine.decode_rows
    assert counted["lzy_lightning_rows_total"] == rows * cfg.lightning_layers
    assert counted["lzy_sparse_rows_total"] \
        + counted["lzy_sparse_dense_rows_total"] == rows * cfg.kv_layers
    sparse_rows = sum(m - 1 for n, m in zip(_LENGTHS, _BUDGETS)
                      if n >= cfg.dense_len)
    assert counted["lzy_sparse_rows_total"] == sparse_rows * cfg.kv_layers
    # every selecting row reads its 7 blocks a group of the 9 to 20 it sees
    assert counted["lzy_sparse_blocks_read_total"] \
        == counted["lzy_sparse_rows_total"] * cfg.n_kv_heads \
        * cfg.sparse.most_read
    assert counted["lzy_sparse_blocks_visible_total"] \
        > counted["lzy_sparse_blocks_read_total"]
    assert counted["lzy_state_slots_reset_total"] == 6
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        "rows" in s.attrs and set(s.attrs["model_stats"])
        == {c.name for c in sala.MiniCPMSala.STATS} for s in emits)


def test_kernel_paths_are_counted(tiny):
    engine = _engine(tiny, slots=2, kernel="pallas")
    prompt = _tokens(70, 135, tiny[0].vocab_size)
    req = engine.submit(prompt, max_new_tokens=3, greedy=True)
    _drain(engine)
    assert _gap(tiny, prompt, req.tokens) < TOL
    text = REGISTRY.exposition()
    for path in (sp.SELECT_DECODE_PATH, sp.SELECT_PREFILL_PATH,
                 sp.DECODE_PATH, sp.PREFILL_PATH, mamba2.UPDATE_PATH,
                 mamba2.SCAN_PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert engine.kernel_path == sp.DECODE_PATH
    engine.close()


def test_radix_match_is_zero_and_nothing_is_cached(served):
    engine = served["engine"]
    assert engine.kv.lookup_tokens > 0 and engine.kv.hit_tokens == 0
    assert engine.stats().kv_blocks_cached == 0


def test_a_finished_requests_state_stays_in_its_slot(tiny):
    """``state_leaves()``: a freed slot keeps what its last round left, the
    reference's state after the prompt and every served token but the last
    (emitted, never fed); rounded to bfloat16 it stands far off."""
    cfg, params = tiny
    engine = _engine(tiny, slots=2)
    prompt = _tokens(60, 150, cfg.vocab_size)
    req = engine.submit(prompt, max_new_tokens=6, greedy=True)
    _drain(engine)
    leaves = engine.state_leaves()
    states = {k: v for k, v in leaves.items() if k.endswith("['state']")}
    assert len(states) == cfg.lightning_layers
    assert all(leaf.shape == (2, 8, 16, 16) for leaf in states.values())
    mode = [v for k, v in leaves.items() if k.endswith("['sparse']")]
    assert len(mode) == 1 and int(mode[0][0]) == 1
    fed = jnp.asarray([prompt + list(req.tokens)[:-1]])
    _, exact, _ = ref.features(params, fed, cfg, prompt_len=len(prompt))
    gap = ref.state_gaps(leaves, exact, cfg)
    assert gap["slot"] == 0 and len(gap["all"]) == cfg.lightning_layers
    assert max(gap["all"]) < 1e-5 and max(gap["slow"]) < 1e-5
    rough = {name: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
             for name, leaf in leaves.items()}
    assert min(ref.state_gaps(rough, exact, cfg)["slow"]) > 1e-3
    engine.close()


def test_a_reused_slot_starts_from_zero_and_takes_the_new_mode(tiny):
    cfg, _ = tiny
    engine = _engine(tiny, slots=1)
    long = _tokens(30, 140, cfg.vocab_size)
    short = _tokens(31, 20, cfg.vocab_size)
    first = engine.submit(long, max_new_tokens=4, greedy=True)
    second = engine.submit(short, max_new_tokens=9, greedy=True)
    _drain(engine)
    assert _gap(tiny, long, first.tokens) < TOL
    assert _gap(tiny, short, second.tokens) < TOL
    mode = [v for k, v in engine.state_leaves().items()
            if k.endswith("['sparse']")][0]
    assert int(mode[0]) == 0
    engine.close()


def test_cache_leaves_are_declared_by_kind(served):
    engine = served["engine"]
    kinds = engine._leaf_kinds
    assert kinds.count(serving.STATE) == 3 + 1     # a state a layer, the mode
    assert kinds.count(serving.PAGED) == 3 * 2     # k, v, ck a sparse layer
    assert kinds.count(serving.INDEX) == 5
    slots = engine.slots
    for i, leaf in enumerate(engine._payload):
        assert (leaf.shape[0] == slots) == (i in engine._state_at)
    assert engine.stats().kv_token_bytes == 2 * (2 * 2 * 16 * 4 + 2 * 16)


def test_llm_generate_through_the_gateway(tiny):
    from lzy_tpu import llm
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)

    cfg, _ = tiny
    fleet = ReplicaFleet(lambda: _engine(tiny, slots=2))
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(16),
                             model_name="minicpm-sala-tiny", page_size=16)
    try:
        fleet.add_replica()
        llm.configure(gateway)
        prompt = _tokens(40, 133, cfg.vocab_size)
        gen = llm.generate(prompt, max_new_tokens=5, greedy=True,
                           cache=False)
        assert gen.status == "ok" and len(gen.tokens) == 5
        assert _gap(tiny, prompt, list(gen.tokens)) < TOL
    finally:
        llm.configure(None)
        gateway.close()


@pytest.mark.parametrize("mechanism", [
    "speculation", "host tier", "storage tier", "parking", "import",
    "export", "int8 pool"])
def test_each_refusal_names_its_mechanism(tiny, mechanism):
    if mechanism == "speculation":
        with pytest.raises(StateLeavesUnsupported, match="speculative"):
            _engine(tiny, spec_tokens=2)
    elif mechanism == "host tier":
        with pytest.raises(StateLeavesUnsupported, match="tiered KV"):
            _engine(tiny, kv_host_tier_bytes=1 << 20)
    elif mechanism == "storage tier":
        with pytest.raises(StateLeavesUnsupported, match="tiered KV"):
            _engine(tiny, kv_storage_tier="mem://tier-refused-sala")
    elif mechanism == "int8 pool":
        with pytest.raises(ValueError, match="kv_quant"):
            _engine(tiny, kv_quant="int8")
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
    for name in ("engine.py", "kv_io.py", "kv_cache.py", "scheduler.py"):
        with open(os.path.join(root, "lzy_tpu", "serving", name)) as f:
            text = f.read().lower()
        assert "minicpm" not in text and "sala" not in text
        assert "lightning" not in text and "dense_len" not in text

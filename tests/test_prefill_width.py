"""The width of a prefill program follows the round's budget: the plan, the
engine against ``generate()`` and the plain reference at the widths' edges,
the interleaving with decode rounds, and the three counters. Tiny widths,
CPU, Pallas kernels interpreted (``tests/conftest.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import nemotron_h as ref
from lzy_tpu.models import llama, unbox
from lzy_tpu.models import nemotron_h as nh
from lzy_tpu.models.generate import (
    PREFILL_BUCKETS, generate, prefill_plan, prefill_width)
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving import prefill as prefill_mod

LENGTHS = (63, 64, 65, 255, 256, 257, 300, 700)
NEW_TOKENS = 4
#: float32 at the tiny size: program and reference differ by the order of
#: their sums alone (tests/test_nemotron_h.py)
TOL = 2e-4


# -- the width and the plan ---------------------------------------------------

@pytest.mark.parametrize("budget, widest, width", [
    (None, None, 256), (256, None, 256), (64, None, 64), (100, None, 64),
    (1000, None, 256), (256, 128, 128), (256, 64, 64), (32, 256, 32),
    (None, 128, 128), (4, None, 8), (256, 100, 64),
])
def test_width_is_the_widest_bucket_under_budget_and_model(budget, widest,
                                                           width):
    assert prefill_width(budget, widest) == width


@pytest.mark.parametrize("budget", [None, 64, 256])
def test_plan_tiles_the_prompt_in_buckets_and_pads_the_tail_alone(budget):
    chunk = prefill_width(budget)
    for t0 in range(1, 1101):
        plan = prefill_plan(t0, chunk, 4096)
        assert [start for start, _, _ in plan] == [
            sum(take for _, take, _ in plan[:i]) for i in range(len(plan))]
        assert sum(take for _, take, _ in plan) == t0
        assert all(width in PREFILL_BUCKETS and width <= chunk
                   for _, _, width in plan)
        assert all(take == width == chunk for _, take, width in plan[:-1])
        _, take, width = plan[-1]
        # the buckets double: the pad is under half the last program
        assert 0 <= width - take < max(width // 2, PREFILL_BUCKETS[0])
        assert len(plan) == -(-t0 // chunk)


# -- the engine against generate() and the plain reference --------------------

def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _drain(engine, limit=2000):
    for _ in range(limit):
        if not engine.step():
            return
    raise AssertionError("the engine did not go idle")


def _serve(cfg, params, **kw):
    engine = PagedInferenceEngine(cfg, params, slots=3, page_size=16,
                                  prefill_budget=256, **kw)
    prompts = [_tokens(40 + i, n, cfg.vocab_size)
               for i, n in enumerate(LENGTHS)]
    reqs = [engine.submit(p, max_new_tokens=NEW_TOKENS, greedy=True)
            for p in prompts]
    _drain(engine)
    engine.close()
    return {"width": engine.prefill_chunk, "prompts": prompts, "reqs": reqs}


@pytest.fixture(scope="module")
def llama_tiny():
    cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=64),
                              max_seq_len=1024)
    return cfg, unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])


@pytest.fixture(scope="module")
def llama_served(llama_tiny):
    return _serve(*llama_tiny)


@pytest.mark.parametrize("i", range(len(LENGTHS)), ids=map(str, LENGTHS))
def test_llama_engine_gives_generates_tokens(llama_tiny, llama_served, i):
    cfg, params = llama_tiny
    assert llama_served["width"] == 256
    req, prompt = llama_served["reqs"][i], llama_served["prompts"][i]
    assert req.done and req.error is None
    want = generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                    max_new_tokens=NEW_TOKENS, prefill_chunk=256)
    assert req.tokens == np.asarray(want)[0, len(prompt):].tolist()


def _unit_scale(params):
    """Each matrix at fan_in ** -0.5, so that at the tiny widths a wrong
    expert or a lost state does not hide under the tolerance
    (tests/test_nemotron_h.py)."""
    def fix(path, leaf):
        if path[-1].key in ("kernel", "experts_w1", "experts_w2", "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def nemotron_tiny():
    cfg = dataclasses.replace(nh.NemotronHConfig.tiny(), max_seq_len=1024)
    return cfg, _unit_scale(nh.init_params(cfg, jax.random.PRNGKey(1)))


@pytest.fixture(scope="module")
def nemotron_served(nemotron_tiny):
    return _serve(*nemotron_tiny, kernel="pallas")


@pytest.mark.parametrize("i", range(len(LENGTHS)), ids=map(str, LENGTHS))
def test_nemotron_engine_gives_the_references_tokens(
        nemotron_tiny, nemotron_served, i):
    """A padded wide chunk must not advance the state (``valid_len``): the
    tokens after it sit on the float32 reference's best logits."""
    cfg, params = nemotron_tiny
    assert nemotron_served["width"] == min(256, cfg.widest_prefill)
    req, prompt = nemotron_served["reqs"][i], nemotron_served["prompts"][i]
    assert req.done and req.error is None and len(req.tokens) == NEW_TOKENS
    full = prompt + req.tokens
    logits = np.asarray(ref.logits_at(
        params, jnp.asarray([full]),
        jnp.arange(len(prompt) - 1, len(full) - 1), cfg))
    gap = logits.max(-1) - logits[np.arange(NEW_TOKENS), req.tokens]
    assert float(gap.max()) < TOL


# -- rounds, and a model that answers a narrower width -------------------------

@dataclasses.dataclass(frozen=True)
class _Narrow(LlamaConfig):
    """A family whose kernels take 64 positions a program."""

    @property
    def widest_prefill(self) -> int:
        return 64


def _count(counter):
    return sum(counter._values.values())


@pytest.mark.parametrize("config, width, programs", [
    (LlamaConfig, 256, 4), (_Narrow, 64, 16)], ids=["widest", "answers64"])
def test_a_long_prompt_takes_budgeted_rounds_between_decode_rounds(
        llama_tiny, config, width, programs):
    """1024 prompt tokens under a budget of 256 are four prefill rounds,
    each followed by a decode round of the resident row; the programs are
    as wide as the budget, or as the model says its kernels take."""
    base, params = llama_tiny
    cfg = config(**dataclasses.asdict(
        dataclasses.replace(base, max_seq_len=2048)))
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  prefill_budget=256)
    try:
        assert engine.prefill_chunk == width
        resident = engine.submit(_tokens(1, 5, cfg.vocab_size),
                                 max_new_tokens=40, greedy=True)
        while len(resident.tokens) < 2:
            engine.step()
        long = engine.submit(_tokens(2, 1024, cfg.vocab_size),
                             max_new_tokens=2, greedy=True)
        before = {c: _count(c) for c in (
            prefill_mod._PREFILL_PROGRAMS, prefill_mod._PREFILL_TOKENS,
            prefill_mod._PREFILL_POSITIONS)}
        rounds0 = engine.prefill_rounds
        for n in range(1, 5):
            emitted = len(resident.tokens)
            assert not long.tokens
            engine.step()
            assert engine.prefill_rounds == rounds0 + n
            assert len(resident.tokens) == emitted + 1
        assert len(long.tokens) >= 1
        moved = {c: _count(c) - v for c, v in before.items()}
        assert moved[prefill_mod._PREFILL_PROGRAMS] == programs
        assert moved[prefill_mod._PREFILL_TOKENS] == 1024
        assert moved[prefill_mod._PREFILL_POSITIONS] == programs * width
    finally:
        engine.close()


def test_the_counters_add_up_to_the_plans(llama_tiny):
    """Tokens are the prompts' unmatched suffixes, positions the widths of
    their plans, programs the plans' lengths."""
    cfg, params = llama_tiny
    engine = PagedInferenceEngine(cfg, params, slots=2, page_size=16,
                                  prefill_budget=256)
    shared = _tokens(3, 320, cfg.vocab_size)
    prompts = [shared + _tokens(4, 41, cfg.vocab_size),
               _tokens(5, 700, cfg.vocab_size),
               shared + _tokens(6, 200, cfg.vocab_size)]
    counters = (prefill_mod._PREFILL_TOKENS, prefill_mod._PREFILL_POSITIONS,
                prefill_mod._PREFILL_PROGRAMS)
    before = [_count(c) for c in counters]
    try:
        for prompt in prompts:     # one after another: the third matches
            engine.submit(prompt, max_new_tokens=2, greedy=True)
            _drain(engine)
    finally:
        engine.close()
    tokens, positions, programs = (
        _count(c) - v for c, v in zip(counters, before))
    suffixes = [len(prompts[0]), len(prompts[1]), 200]   # 320 = 20 pages
    plans = [prefill_plan(n, 256, cfg.max_seq_len) for n in suffixes]
    assert tokens == sum(suffixes)
    assert programs == sum(len(p) for p in plans)
    assert positions == sum(w for p in plans for _, _, w in p)
    assert positions >= tokens

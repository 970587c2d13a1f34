"""Speculative decoding tests (serving/spec.py + engine verify path).

The load-bearing guarantee is bit-identity: speculation may only change
HOW FAST tokens appear, never WHICH tokens appear. Greedy output with
speculation on must equal the solo ``generate()`` oracle and the
non-speculative engines, dense and paged; sampled rows sharing a batch
with speculating greedy rows must be bit-identical to a spec-off run
(same rng draw order). Acceptance itself is made deterministic where a
test needs it by injecting a proposer: an ORACLE proposer (drafts the
model's actual continuation — every token accepted) and an ADVERSARIAL
one (drafts tokens guaranteed wrong — every token rejected, exercising
the rollback path), so the accept and reject machinery are each pinned
down exactly, not sampled by luck of the n-gram matcher.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import (
    NgramProposer, PagedInferenceEngine)

VOCAB = 64


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=VOCAB)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


def _oracle(cfg, params, prompt_ids, n):
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _drain(engine, reqs, rounds=800):
    for _ in range(rounds):
        if all(r.done for r in reqs):
            return
        engine.step()
    raise AssertionError("engine did not finish its requests")


class _OracleProposer:
    """Drafts the model's actual greedy continuation: full acceptance."""

    def __init__(self, seqs, gamma):
        self.seqs = [list(map(int, s)) for s in seqs]
        self.gamma = gamma

    def propose(self, tokens):
        t = list(tokens)
        for s in self.seqs:
            if len(s) > len(t) and s[:len(t)] == t:
                return s[len(t):len(t) + self.gamma]
        return []


class _AdversarialProposer(_OracleProposer):
    """Drafts tokens guaranteed to differ from the argmax: every
    proposal fully rejected, every verify round rolled back."""

    def propose(self, tokens):
        return [(t + 1) % VOCAB for t in super().propose(tokens)]


PROMPTS = [
    [5, 9, 3, 7, 2],
    [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3, 4],   # repetitive: n-gram hits
    [40, 41, 42],
]


class TestNgramProposer:
    def test_longest_suffix_match_wins(self):
        p = NgramProposer(max_ngram=3, gamma=4)
        # suffix [7,8] recurs (followed by 9,1); 1-gram [8] also recurs
        # with a different continuation — the longer match must win
        assert p.propose([7, 8, 9, 1, 8, 4, 7, 8]) == [9, 1, 8, 4]

    def test_full_window_preferred_on_runs(self):
        # the NEAREST occurrence of the suffix of a constant run offers a
        # 1-token window; an earlier one offers the whole gamma
        p = NgramProposer(max_ngram=3, gamma=4)
        assert p.propose([6] * 12) == [6, 6, 6, 6]

    def test_no_match_proposes_nothing(self):
        p = NgramProposer(max_ngram=3, gamma=4)
        assert p.propose([1, 2, 3, 4, 5, 6]) == []
        assert p.propose([9]) == []

    def test_gamma_truncation(self):
        p = NgramProposer(max_ngram=2, gamma=2)
        assert p.propose([5, 6, 7, 8, 5, 6]) == [7, 8]

    def test_validation(self):
        with pytest.raises(ValueError, match="gamma"):
            NgramProposer(gamma=0)
        with pytest.raises(ValueError, match="min_ngram"):
            NgramProposer(max_ngram=2, min_ngram=3)


def _for_kernel(tiny_model, kernel):
    """The model a read path is held to the oracle on: ``lax`` makes the
    oracle's sums in the oracle's order, in any dtype; the kernel's online
    softmax reorders them, which in float32 compute stays far below any
    gap between two logits."""
    cfg, params = tiny_model
    if kernel == "pallas":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    return cfg, params


class TestGreedyBitIdentical:
    @pytest.mark.parametrize("kernel", ["lax", "pallas"])
    def test_spec_on_matches_oracle_and_spec_off(self, tiny_model, kernel):
        cfg, params = _for_kernel(tiny_model, kernel)
        n = 20 if kernel == "lax" else 8       # interpreted: keep it short
        expected = [_oracle(cfg, params, p, n) for p in PROMPTS]

        def build(spec):
            return PagedInferenceEngine(
                cfg, params, slots=2, page_size=16, spec_tokens=spec,
                kernel=kernel)

        for spec in (0, 4):
            eng = build(spec)
            reqs = [eng.submit(p, max_new_tokens=n) for p in PROMPTS]
            _drain(eng, reqs)
            for r, exp in zip(reqs, expected):
                assert r.result() == exp
            eng.close()

    def test_full_acceptance_emits_oracle_tokens_faster(self, tiny_model):
        cfg, params = tiny_model
        n, gamma = 16, 4
        prompt = PROMPTS[0]
        exp = _oracle(cfg, params, prompt, n)
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=16, spec_tokens=gamma,
            proposer=_OracleProposer([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=n)
        _drain(eng, [req])
        assert req.result() == exp
        s = eng.stats()
        assert s.spec_acceptance_rate == 1.0
        assert s.spec_proposed_tokens == s.spec_accepted_tokens > 0
        # gamma+1 tokens per verify round: far fewer rounds than tokens
        assert eng.decode_steps < n - 1
        assert s.spec_tokens_per_step > 2.0
        eng.close()

    def test_full_rejection_still_bit_identical(self, tiny_model):
        cfg, params = tiny_model
        n, gamma = 12, 3
        prompt = PROMPTS[1]
        exp = _oracle(cfg, params, prompt, n)
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=16, spec_tokens=gamma,
            proposer=_AdversarialProposer([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=n)
        _drain(eng, [req])
        assert req.result() == exp
        s = eng.stats()
        assert s.spec_proposed_tokens > 0
        assert s.spec_accepted_tokens == 0
        assert s.spec_acceptance_rate == 0.0
        eng.close()


class TestPagedRollbackIntegrity:
    def test_forced_full_rejection_never_corrupts_the_pool(
            self, tiny_model):
        """Adversarial drafts force a rollback every verify round while a
        radix-cached prefix is pinned by refcount; afterwards the pool
        must balance exactly and the cached prefix must still decode
        bit-identically (a rollback that freed or scribbled on a
        resident/refcounted block would break one of the two)."""
        cfg, params = tiny_model
        n, gamma, page = 12, 3, 4
        prompt = [11, 12, 13, 14, 15, 16, 17, 18, 19, 20]   # 2 full blocks
        exp = _oracle(cfg, params, prompt, n)
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=page, kv_blocks=40,
            spec_tokens=gamma,
            proposer=_AdversarialProposer([prompt + exp], gamma))
        # request 1 caches the prompt's full blocks in the radix tree
        r1 = eng.submit(prompt, max_new_tokens=4)
        _drain(eng, [r1])
        cached = set(eng.kv._node_of)
        assert cached, "prompt blocks should be tree-resident"
        # request 2 pins the cached prefix and speculates (all rejected)
        r2 = eng.submit(prompt, max_new_tokens=n)
        _drain(eng, [r2])
        assert r2.result() == exp
        assert eng.stats().spec_accepted_tokens == 0
        # pool balances: every block is free or cached-unreferenced
        ks = eng.kv.stats()
        assert ks.blocks_free + ks.blocks_cached == ks.blocks_total
        for b in eng.kv._node_of:
            assert eng.kv.pool.refcount(b) == 0
        # tree-resident prefix blocks survived every rollback
        assert cached <= set(eng.kv._node_of)
        # and their contents are untouched: a third request reuses the
        # cached prefix and must still match the oracle exactly
        r3 = eng.submit(prompt, max_new_tokens=n)
        _drain(eng, [r3])
        assert r3.result() == exp
        assert eng.kv.stats().prefix_hit_tokens > 0
        eng.close()


class TestMixedBatch:
    def test_sampled_rows_bit_identical_with_spec_on(self, tiny_model):
        """A sampling engine with one greedy=True (speculating) row and
        one sampled row: the sampled row's tokens must not move when
        speculation is enabled (same rng draw order), and the greedy row
        must match the greedy oracle."""
        cfg, params = tiny_model
        n = 10
        greedy_prompt, sampled_prompt = PROMPTS[1], PROMPTS[0]
        exp_greedy = _oracle(cfg, params, greedy_prompt, n)
        outs = {}
        for spec in (0, 4):
            eng = PagedInferenceEngine(
                cfg, params, slots=2, temperature=0.8, top_k=20, seed=7,
                spec_tokens=spec)
            r_sampled = eng.submit(sampled_prompt, max_new_tokens=n)
            r_greedy = eng.submit(greedy_prompt, max_new_tokens=n,
                                  greedy=True)
            _drain(eng, [r_sampled, r_greedy])
            outs[spec] = (r_sampled.result(), r_greedy.result())
            eng.close()
        assert outs[0][0] == outs[4][0], "sampled row moved under spec"
        assert outs[0][1] == outs[4][1] == exp_greedy
        # ... and the sampled row really did sample (not argmax)
        assert outs[0][0] != _oracle(cfg, params, sampled_prompt, n)


class TestEosAndLimits:
    def test_eos_inside_accepted_window_truncates(self, tiny_model):
        cfg, params = tiny_model
        gamma = 4
        prompt = PROMPTS[0]
        exp = _oracle(cfg, params, prompt, 12)
        # an eos whose FIRST occurrence is mid-stream (an earlier
        # duplicate would legitimately end the request sooner)
        j = next(i for i in range(1, len(exp)) if exp[i] not in exp[:i])
        eos = exp[j]
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=16, spec_tokens=gamma,
            eos_token=eos,
            proposer=_OracleProposer([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=12)
        _drain(eng, [req])
        # emission stops AT the eos even though the accepted window went
        # past it; nothing after the eos leaks out
        assert req.result() == exp[:j + 1]
        assert eng.stats().busy == 0       # slot freed
        eng.close()

    def test_max_new_tokens_exact_under_full_acceptance(self, tiny_model):
        cfg, params = tiny_model
        gamma = 4
        prompt = PROMPTS[1]
        exp = _oracle(cfg, params, prompt, 16)
        eng = PagedInferenceEngine(
            cfg, params, slots=1, spec_tokens=gamma,
            proposer=_OracleProposer([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=7)
        _drain(eng, [req])
        assert req.result() == exp[:7]     # never a token beyond the cap
        eng.close()


class TestStatsAndWarmup:
    def test_counters_sum_and_surface(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=16, spec_tokens=3)
        reqs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS[:2]]
        _drain(eng, reqs)
        s = eng.stats()
        assert s.spec_tokens == 3
        assert 0 <= s.spec_accepted_tokens <= s.spec_proposed_tokens
        assert s.spec_verify_steps == eng.spec_steps
        if s.spec_proposed_tokens:
            assert s.spec_acceptance_rate == pytest.approx(
                s.spec_accepted_tokens / s.spec_proposed_tokens, abs=1e-3)
        doc = s.doc()
        for key in ("spec_tokens", "spec_proposed_tokens",
                    "spec_accepted_tokens", "spec_acceptance_rate",
                    "spec_verify_steps", "spec_tokens_per_step"):
            assert key in doc
        # emitted decode tokens reconcile with the per-round accounting
        assert eng.decode_tokens <= eng.decode_rows * (3 + 1)
        eng.close()

    def test_spec_off_omits_spec_fields(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        doc = eng.stats().doc()
        assert "spec_tokens" not in doc
        assert "spec_acceptance_rate" not in doc
        eng.close()

    @pytest.mark.parametrize("kernel", ["lax", "pallas"])
    def test_warmup_does_not_perturb_decode(self, tiny_model, kernel):
        cfg, params = _for_kernel(tiny_model, kernel)
        n = 10
        exp = _oracle(cfg, params, PROMPTS[1], n)
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=16, spec_tokens=3, kernel=kernel)
        eng.warmup()
        req = eng.submit(PROMPTS[1], max_new_tokens=n)
        _drain(eng, [req])
        assert req.result() == exp
        eng.close()

    def test_spec_tokens_validation(self, tiny_model):
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="spec_tokens"):
            PagedInferenceEngine(cfg, params, spec_tokens=-1)
        with pytest.raises(ValueError, match="spec_tokens"):
            PagedInferenceEngine(cfg, params,
                                 spec_tokens=cfg.max_seq_len)


class TestServiceSurface:
    def test_flags_thread_through_the_service_builder(self):
        """The serve.py path: build_inference_service(spec_tokens=...,
        warm_start=True) produces a speculating, pre-warmed engine, and
        the per-request greedy override reaches it through
        InferenceService.generate — output still equals the oracle."""
        from lzy_tpu.service.inference import build_inference_service

        svc = build_inference_service(
            "tiny", slots=2, page_size=16, spec_tokens=3, warm_start=True)
        try:
            assert svc.engine.spec_tokens == 3
            scfg = svc.engine.cfg
            prompt = PROMPTS[1]
            out = svc.generate(prompt, max_new_tokens=8, greedy=True,
                               timeout_s=60)
            assert out["status"] == "ok"
            exp = _oracle(scfg, svc.engine.params, prompt, 8)
            assert out["tokens"] == exp
            stats = svc.stats()
            assert stats["spec_tokens"] == 3
            assert "spec_acceptance_rate" in stats
        finally:
            svc.close()

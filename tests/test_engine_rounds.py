"""Decode-round fence contract (engine.py one-sync-per-round).

The restructured round scheduler promises exactly ONE device→host
transfer per decode round: every per-row read — next-token ids, EOS
decisions, spec acceptance lengths — rides a single fused program whose
one output crosses the fence via ``PagedInferenceEngine._fetch``. These
tests pin that contract two ways:

- ``host_fetches`` (the engine's own fence counter) must advance by
  exactly 1 per steady-state decode round, on both read paths (``lax``,
  and the Pallas kernel under the interpreter) and in a spec-verify round;
- a counting transfer shim swapped in for the engine module's ``np``
  must see every device→host conversion go through ``_fetch`` — a
  regression that fetches device data outside the fence (per-row
  ``np.asarray``, the pre-restructure shape) trips the shim even though
  it never touches ``host_fetches``.

The oracle rides along: the same loop must still equal ``generate()``
under forced full-acceptance and full-rejection proposers when the
verify window is read by the kernel (the ``lax`` twins are in
test_spec_decode.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving import engine as engine_mod

VOCAB = 64

PROMPTS = [
    [5, 9, 3, 7, 2],
    [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3, 4],
]


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


def _reach_steady_decode(eng, reqs, rounds=200):
    """Step until every request is resident in a slot (prefill done,
    queue empty) — from here on each step() is exactly one decode
    round."""
    for _ in range(rounds):
        if (not eng.prefill.jobs and eng.queue.depth() == 0
                and sum(r is not None for r in eng._active) == len(reqs)):
            return
        eng.step()
    raise AssertionError("requests never reached steady decode")


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
    """Drafts tokens guaranteed wrong: full rejection every round."""

    def propose(self, tokens):
        return [(t + 1) % VOCAB for t in super().propose(tokens)]


class _CountingNp:
    """Transfer shim: proxies the engine module's ``np`` and counts
    ``asarray``/``array`` calls whose argument is a device array — i.e.
    every device→host conversion the engine code performs."""

    def __init__(self, real):
        self._real = real
        self.device_fetches = 0

    def __getattr__(self, name):
        return getattr(self._real, name)

    def _counting(self, fn, a, *args, **kw):
        if isinstance(a, jax.Array):
            self.device_fetches += 1
        return fn(a, *args, **kw)

    def asarray(self, a, *args, **kw):
        return self._counting(self._real.asarray, a, *args, **kw)

    def array(self, a, *args, **kw):
        return self._counting(self._real.array, a, *args, **kw)


def _build(cfg, params, *, kernel="lax", spec=0, proposer=None):
    kw = dict(slots=2, spec_tokens=spec, page_size=16, kernel=kernel)
    if proposer is not None:
        kw["proposer"] = proposer
    return PagedInferenceEngine(cfg, params, **kw)


class TestOneFencePerRound:
    @pytest.mark.parametrize("kernel", ["lax", "pallas"])
    def test_plain_decode_one_fetch_per_round(self, tiny_model, kernel):
        cfg, params = tiny_model
        eng = _build(cfg, params, kernel=kernel)
        reqs = [eng.submit(p, max_new_tokens=40) for p in PROMPTS]
        _reach_steady_decode(eng, reqs)
        for _ in range(8):
            before = eng.host_fetches
            assert eng.step()
            assert eng.host_fetches == before + 1
        eng.close()

    @pytest.mark.parametrize("accept", [True, False])
    def test_spec_verify_one_fetch_per_round(self, tiny_model, accept):
        cfg, params = tiny_model
        n, gamma = 30, 3
        prompt = PROMPTS[1]
        exp = _oracle(cfg, params, prompt, n)
        cls = _OracleProposer if accept else _AdversarialProposer
        eng = _build(cfg, params, spec=gamma,
                     proposer=cls([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=n)
        _reach_steady_decode(eng, [req])
        rounds = 0
        while not req.done and rounds < 100:
            before = eng.host_fetches
            eng.step()
            rounds += 1
            assert eng.host_fetches == before + 1
        assert req.done and req.result() == exp
        if accept:
            # the fence budget is per ROUND, so full acceptance buys
            # tokens without buying transfers: far fewer fetches than
            # emitted tokens
            assert eng.decode_steps < n - 1
        eng.close()

    @pytest.mark.parametrize("kernel", ["lax", "pallas"])
    def test_shim_sees_no_fetch_outside_the_fence(
            self, tiny_model, kernel, monkeypatch):
        cfg, params = tiny_model
        eng = _build(cfg, params, kernel=kernel)
        reqs = [eng.submit(p, max_new_tokens=40) for p in PROMPTS]
        _reach_steady_decode(eng, reqs)
        shim = _CountingNp(np)
        monkeypatch.setattr(engine_mod, "np", shim)
        rounds = 8
        before = eng.host_fetches
        for _ in range(rounds):
            assert eng.step()
        # every device→host conversion the engine performed went
        # through _fetch: shim total == fence counter delta == rounds
        assert eng.host_fetches - before == rounds
        assert shim.device_fetches == rounds
        eng.close()


@pytest.fixture(scope="module")
def tiny_f32(tiny_model):
    # float32 compute: the kernel's online softmax reorders the sums, and
    # in float32 that stays far below any gap between two logits, so its
    # greedy tokens are the oracle's
    cfg, params = tiny_model
    return dataclasses.replace(cfg, dtype=jnp.float32), params


class TestKernelVerifyWindowUnderForcedProposers:
    def test_full_acceptance_matches_oracle(self, tiny_f32):
        cfg, params = tiny_f32
        n, gamma = 16, 4
        prompt = PROMPTS[0]
        exp = _oracle(cfg, params, prompt, n)
        eng = _build(cfg, params, kernel="pallas", spec=gamma,
                     proposer=_OracleProposer([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=n)
        _drain(eng, [req])
        assert req.result() == exp
        s = eng.stats()
        assert s.spec_acceptance_rate == 1.0
        assert eng.decode_steps < n - 1
        eng.close()

    def test_full_rejection_matches_oracle(self, tiny_f32):
        cfg, params = tiny_f32
        n, gamma = 12, 3
        prompt = PROMPTS[1]
        exp = _oracle(cfg, params, prompt, n)
        eng = _build(cfg, params, kernel="pallas", spec=gamma,
                     proposer=_AdversarialProposer([prompt + exp], gamma))
        req = eng.submit(prompt, max_new_tokens=n)
        _drain(eng, [req])
        assert req.result() == exp
        s = eng.stats()
        assert s.spec_proposed_tokens > 0
        assert s.spec_accepted_tokens == 0
        eng.close()

"""The decode loop keeps one round in flight (``serving/engine.py``).

A decode step hands back its tokens and positions as device arrays, so the
loop thread of ``start()`` dispatches round n+1 from round n's outputs and
only then fetches round n's tokens: ``plan(n+1) -> dispatch(n+1) -> overlap
-> fence(n) -> emit(n)``. ``step()`` called from any other thread keeps its
contract (every token of the round it dispatched is emitted when it
returns). These tests hold the overlapped turn to the ``step()``-driven
engine, which the other files hold to ``generate()``:

- the same greedy tokens over mixed lengths, EOS and length finishes, for
  keys and values (the toy Llama), a model with per-slot state (the tiny
  Nemotron), a latent pool (the tiny Moonlight) and the gang on a 1x2 mesh;
- a finish learnt one round late (EOS, a cancel, a deadline) drops the
  over-run token, writes no page another request holds, and leaves a chain
  a later prefix hit serves a cold prefill's tokens from;
- one fence a round still (``host_fetches == decode_steps``), no round past
  the end of the last length-limited row, the rng untouched by a round no
  row survives;
- a finished prompt needs no token on the host: its slot is activated on the
  device, the next round is dispatched over the round in flight and behind
  the prompt's programs, and the first token is fetched after that, in the
  same turn (``lzy_engine_prompt_activations_total{how}``); an EOS first
  token is learnt one round late, a request of one token never activates a
  slot, a proposer keeps the drained path;
- whatever needs the tokens first drains (a proposer, a squeeze, a parked
  chain, a stop), counted by reason;
- ``close()`` and a dead loop with a round in flight finish every waiter.

Most cases drive the overlapped turn from the test's own thread, by naming
it the loop's (``_lagging``): one turn a ``step()``, nothing timed. The file
is named to sort last (``tests/test_zz_prefill_dispatch.py`` says why).
"""

import threading
import time

import jax
import numpy as np
import pytest

from lzy_tpu.models import deepseek_v3, llama, nemotron_h, unbox
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils import trace
from lzy_tpu.utils.clock import SystemClock
from lzy_tpu.utils.metrics import REGISTRY

KINDS = ("llama", "nemotron", "moonlight", "gang")
OVERLAPPED = "lzy_engine_rounds_overlapped_total"
FENCES = "lzy_engine_round_fences_total"
OVERRUN = "lzy_engine_overrun_rows_total"
DRAINS = "lzy_engine_round_drains_total"
ACTIVATIONS = "lzy_engine_prompt_activations_total"


@pytest.fixture(scope="module")
def models():
    """``kind -> (cfg, params)``, each built on first use."""
    built = {}

    def get(kind):
        if kind not in built:
            key = jax.random.PRNGKey(1)
            if kind in ("llama", "gang"):
                cfg = LlamaConfig.tiny(vocab_size=64)
                params = unbox(llama.init_params(cfg, key)[0])
            elif kind == "nemotron":
                cfg = nemotron_h.NemotronHConfig.tiny()
                params = nemotron_h.init_params(cfg, key)
            else:
                cfg = deepseek_v3.DeepseekV3Config.tiny()
                params = deepseek_v3.init_params(cfg, key)
            built[kind] = cfg, params
        return built[kind]

    return get


def _engine(model, *, gang=False, **kw):
    cfg, params = model
    kw.setdefault("slots", 3)
    kw.setdefault("prefill_budget", 16)
    if gang:
        from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine

        return ShardedPagedInferenceEngine(cfg, params, page_size=16, tp=2,
                                           **kw)
    return PagedInferenceEngine(cfg, params, page_size=16, kernel="lax",
                                **kw)


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _counter(name, **labels):
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in labels.items())
                   + "}" if labels else "")
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == want:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _drains():
    return {r: _counter(DRAINS, reason=r) for r in
            ("admission", "spec", "squeeze", "last_row", "io", "stop")}


def _activations():
    return {how: _counter(ACTIVATIONS, how=how)
            for how in ("device", "drained")}


def _moved(after, before):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _lagging(engine):
    """The calling thread is the engine's loop from here on: its
    ``step()`` leaves a decode round in flight, as ``start()``'s does."""
    engine._loop_ident = threading.get_ident()
    return engine


def _run(engine, reqs, limit=600):
    for _ in range(limit):
        if all(r.done for r in reqs) and engine._inflight is None:
            return
        engine.step()
    raise AssertionError("the engine did not finish its requests")


# six requests over three slots: prompts from under a page to several
# chunks of the budget, budgets from one token to sixty
_MIX = ((11, 5, 40), (12, 23, 60), (13, 37, 9), (14, 9, 1), (15, 50, 33),
        (16, 17, 25))


def _submit_mix(engine, vocab):
    return [engine.submit(_tokens(seed, n, vocab), max_new_tokens=budget)
            for seed, n, budget in _MIX]


def _reference(model, *, gang=False, eos=None, **kw):
    """The ``step()``-driven engine's tokens for the mix, and its
    requests."""
    engine = _engine(model, gang=gang, eos_token=eos, **kw)
    reqs = _submit_mix(engine, model[0].vocab_size)
    _run(engine, reqs)
    engine.close()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [list(r.tokens) for r in reqs]


def _an_eos(reference):
    """A token that ends some request early and leaves another to its
    length: the one most requests decode somewhere past their second."""
    seen = {}
    for tokens in reference:
        for t in set(tokens[2:-1]):
            seen[t] = seen.get(t, 0) + 1
    assert seen, "no request decodes past its second token"
    return max(seen, key=lambda t: (seen[t], t))


def _first_new(tokens, least=3):
    """``(k, tokens[k])`` of the first token from index ``least`` on that
    the request had not decoded before: as its EOS it ends the request at
    ``k + 1`` tokens."""
    for k in range(least, len(tokens)):
        if tokens[k] not in tokens[:k]:
            return k, tokens[k]
    raise AssertionError(f"nothing new after {least} tokens in {tokens}")


@pytest.mark.parametrize("kind", KINDS)
def test_loop_thread_emits_the_tokens_of_step(models, kind):
    """The loop of ``start()`` against ``step()``: mixed lengths, EOS and
    length finishes, more requests than slots, chunked prefill between
    the rounds."""
    model, gang = models(kind), kind == "gang"
    eos = _an_eos(_reference(model, gang=gang))
    want = _reference(model, gang=gang, eos=eos)
    budgets = [b for _, _, b in _MIX]
    assert any(len(t) < b for t, b in zip(want, budgets)), "no EOS finish"
    assert any(len(t) == b for t, b in zip(want, budgets)), "no length finish"
    engine = _engine(model, gang=gang, eos_token=eos).start()
    try:
        overlapped = _counter(OVERLAPPED)
        reqs = _submit_mix(engine, model[0].vocab_size)
        assert all(r.wait(120) for r in reqs)
    finally:
        engine.close()
    assert [r.error for r in reqs] == [None] * len(reqs)
    assert [list(r.tokens) for r in reqs] == want
    assert engine.host_fetches == engine.decode_steps
    assert engine._inflight is None
    assert _counter(OVERLAPPED) > overlapped


@pytest.mark.parametrize("kind", KINDS)
def test_overlapped_turns_by_hand_emit_the_tokens_of_step(models, kind):
    """The same, one overlapped turn a ``step()`` from this thread: the
    turn's order is fixed, so every finish is learnt exactly one round
    late and every over-run happens."""
    model, gang = models(kind), kind == "gang"
    eos = _an_eos(_reference(model, gang=gang))
    want = _reference(model, gang=gang, eos=eos)
    engine = _lagging(_engine(model, gang=gang, eos_token=eos))
    overrun, fences = _counter(OVERRUN), _counter(FENCES)
    overlapped = _counter(OVERLAPPED)
    reqs = _submit_mix(engine, model[0].vocab_size)
    _run(engine, reqs)
    assert [list(r.tokens) for r in reqs] == want
    assert engine.host_fetches == engine.decode_steps
    assert _counter(FENCES) - fences == engine.decode_steps
    assert _counter(OVERRUN) > overrun
    assert 0 < _counter(OVERLAPPED) - overlapped < engine.decode_steps
    engine.close()


def test_a_single_length_limited_row_dispatches_no_round_past_its_end(
        models):
    engine = _lagging(_engine(models("llama"), slots=1))
    before = _drains()
    overrun = _counter(OVERRUN)
    req = engine.submit(_tokens(3, 7, 64), max_new_tokens=12)
    _run(engine, [req])
    assert len(req.tokens) == 12
    # the first token is the prefill's; eleven decode rounds, none beyond
    assert engine.decode_steps == engine.host_fetches == 11
    assert _counter(OVERRUN) == overrun
    after = _drains()
    assert after["last_row"] - before["last_row"] == 1
    # the finished prompt found no round in flight to drain
    assert after["admission"] == before["admission"]
    engine.close()


def test_an_eos_overrun_writes_no_page_another_request_holds(models):
    """Request A ends on EOS while B decodes on: the round dispatched
    before A's EOS was fetched carries A once more. That round may write
    A's own pages and the scratch page and B's current page, nothing
    else; and A's prompt chain serves a later prefix hit the tokens of a
    cold prefill."""
    model = models("llama")
    a_prompt, b_prompt = _tokens(21, 37, 64), _tokens(22, 9, 64)
    probe = _engine(model)
    a = probe.submit(a_prompt, max_new_tokens=30)
    _run(probe, [a])
    probe.close()
    k, eos = _first_new(a.tokens)
    engine = _lagging(_engine(model, eos_token=eos))
    a = engine.submit(a_prompt, max_new_tokens=30)
    b = engine.submit(b_prompt, max_new_tokens=60)
    overrun = _counter(OVERRUN)
    pages = held = None
    for _ in range(200):
        if a.done:
            break
        # what the turn's dispatch (the only program it queues once both
        # prompts are in) may touch
        slot_a = engine._active.index(a) if a in engine._active else None
        if slot_a is not None and b in engine._active:
            slot_b = engine._active.index(b)
            held = set(engine._slot_blocks[slot_a]) | {0} | {
                engine._slot_blocks[slot_b][-1]}
            pages = [np.asarray(engine._payload[i])
                     for i in engine._pool_at]
        engine.step()
    assert a.done and list(a.tokens)[-1] == eos and len(a.tokens) == k + 1
    assert not b.done and engine._inflight is not None
    assert b in [req for _, req in engine._inflight.rows]
    assert a in [req for _, req in engine._inflight.rows]   # the over-run
    for before, i in zip(pages, engine._pool_at):
        after = np.asarray(engine._payload[i])
        changed = {int(p) for p in np.nonzero(
            (before != after).reshape(before.shape[0], -1).any(axis=1))[0]}
        assert changed <= held, (changed, held)
    _run(engine, [b])
    assert _counter(OVERRUN) - overrun >= 1
    cold = _engine(model, eos_token=eos)
    want_b = cold.submit(b_prompt, max_new_tokens=60)
    c_prompt = a_prompt[:32] + _tokens(23, 5, 64)
    want_c = cold.submit(c_prompt, max_new_tokens=12)
    _run(cold, [want_b, want_c])
    cold.close()
    assert list(b.tokens) == list(want_b.tokens)
    hits = engine.kv.stats().prefill_tokens_saved
    c = engine.submit(c_prompt, max_new_tokens=12)
    _run(engine, [c])
    assert engine.kv.stats().prefill_tokens_saved - hits == 32
    assert list(c.tokens) == list(want_c.tokens)
    engine.close()


class _Skewed(SystemClock):
    skew = 0.0

    def now(self):
        return super().now() + self.skew


@pytest.mark.parametrize("how", ("cancel", "deadline"))
def test_a_row_reaped_with_a_round_in_flight_drops_its_token(models, how):
    model = models("llama")
    prompts = [_tokens(31, 20, 64), _tokens(32, 11, 64)]
    ref = _engine(model)
    want = ref.submit(prompts[1], max_new_tokens=40)
    _run(ref, [want])
    ref.close()
    clock = _Skewed()
    engine = _lagging(_engine(model, clock=clock))
    doomed = engine.submit(prompts[0], max_new_tokens=200,
                           deadline_s=50.0 if how == "deadline" else None)
    kept = engine.submit(prompts[1], max_new_tokens=40)
    for _ in range(100):
        engine.step()
        if len(doomed.tokens) >= 5 and len(kept.tokens) >= 5:
            break
    assert engine._inflight is not None
    assert doomed in [req for _, req in engine._inflight.rows]
    overrun, had = _counter(OVERRUN), len(doomed.tokens)
    if how == "cancel":
        doomed.cancel()
    else:
        clock.skew = 100.0
    engine.step()       # reaped before the round in flight is fetched
    assert doomed.done and doomed.status == "cancelled"
    assert len(doomed.tokens) == had            # the token in flight: gone
    assert _counter(OVERRUN) - overrun == 1
    _run(engine, [kept])
    assert list(kept.tokens) == list(want.tokens)
    assert engine.host_fetches == engine.decode_steps
    s = engine.stats()
    assert s.busy == 0
    assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total
    engine.close()


def test_a_round_no_row_survives_leaves_the_rng_alone(models):
    """Sampled decoding: a request ends on EOS, the round queued behind
    that token carried no other row, and the next request draws what it
    draws after a ``step()``-driven engine's (where that round never
    ran)."""
    model = models("llama")
    first, second = _tokens(41, 13, 64), _tokens(42, 21, 64)

    def serve(lag, eos):
        engine = _engine(model, slots=2, temperature=0.9, seed=7,
                         eos_token=eos)
        if lag:
            _lagging(engine)
        out = []
        for prompt in (first, second):
            req = engine.submit(prompt, max_new_tokens=24)
            _run(engine, [req])
            out.append(list(req.tokens))
        engine.close()
        return out

    plain = serve(False, None)
    k, eos = _first_new(plain[0])
    want = serve(False, eos)
    assert len(want[0]) == k + 1
    overrun = _counter(OVERRUN)
    assert serve(True, eos) == want
    assert _counter(OVERRUN) - overrun >= 1


def test_speculation_drains_every_round_and_changes_nothing(models):
    model = models("llama")
    want = _reference(model, spec_tokens=3)
    assert want == _reference(model)
    engine = _lagging(_engine(model, spec_tokens=3))
    before, overlapped = _drains(), _counter(OVERLAPPED)
    reqs = _submit_mix(engine, 64)
    for _ in range(600):
        if all(r.done for r in reqs):
            break
        engine.step()
        assert engine._inflight is None     # nothing is left in flight
    assert [list(r.tokens) for r in reqs] == want
    assert _counter(OVERLAPPED) == overlapped
    assert _drains()["spec"] - before["spec"] == engine.decode_steps \
        == engine.host_fetches
    engine.close()


def test_a_squeeze_drains_before_it_preempts(models):
    """``test_kv_cache``'s pool-exhaustion scenario: seven usable pages,
    two growing requests. The younger is preempted with the tokens a
    ``step()``-driven engine had given it, and the older's are
    untouched."""
    model = models("llama")

    def serve(lag):
        engine = _engine(model, slots=2, kv_blocks=8, prefill_budget=None)
        if lag:
            _lagging(engine)
        old = engine.submit(_tokens(51, 40, 64), max_new_tokens=40)
        young = engine.submit(_tokens(52, 30, 64), max_new_tokens=70)
        _run(engine, [old, young])
        engine.close()
        return old, young

    before = _drains()
    want_old, want_young = serve(False)
    assert "preempted" in (want_young.error or "") and want_old.error is None
    assert _drains()["squeeze"] == before["squeeze"]
    old, young = serve(True)
    assert "preempted" in (young.error or "") and old.error is None
    assert list(young.tokens) == list(want_young.tokens)
    assert list(old.tokens) == list(want_old.tokens)
    assert _drains()["squeeze"] - before["squeeze"] >= 1


def test_a_park_request_drains_the_round_in_flight(models):
    engine = _lagging(_engine(models("llama")))
    prompt = _tokens(61, 40, 64)
    req = engine.submit(prompt, max_new_tokens=30)
    for _ in range(50):
        engine.step()
        if len(req.tokens) >= 4:
            break
    assert engine._inflight is not None
    before = _drains()
    # ``park_chain`` from another thread queues it, as for a running loop
    engine._thread, parked = threading.current_thread(), []
    asker = threading.Thread(target=lambda: parked.append(
        engine.park_chain("conversation", prompt)))
    asker.start()
    while not engine.kv_io._calls:
        time.sleep(0.001)
    engine.step()
    asker.join(5.0)
    engine._thread = None
    assert parked == [True]
    assert _drains()["io"] - before["io"] == 1
    _run(engine, [req])
    assert len(req.tokens) == 30 and req.error is None
    engine.close()


def test_counters_and_span_attributes_pair_a_fence_with_its_dispatch(models):
    """A state model, so the emit span carries counts: the fence of round
    n comes after the dispatch of round n+1, both numbered; the emit
    span's rows are what the fetched round was dispatched with."""
    engine = _lagging(_engine(models("nemotron"), slots=2))
    long = engine.submit(_tokens(71, 9, 64), max_new_tokens=40)
    short = engine.submit(_tokens(72, 5, 64), max_new_tokens=6)
    for _ in range(100):
        engine.step()
        if len(long.tokens) >= 2 and len(short.tokens) >= 2:
            break
    overlapped, fences = _counter(OVERLAPPED), _counter(FENCES)
    overrun, before = _counter(OVERRUN), _drains()
    steps = engine.decode_steps
    with trace.recording() as rec:
        _run(engine, [long, short])
        recs = rec.drain()
    rounds = engine.decode_steps - steps
    assert _counter(FENCES) - fences == rounds
    # every round but the one in flight at the start was dispatched here,
    # all of them over an unfetched round: nothing drained but the last
    assert _counter(OVERLAPPED) - overlapped == rounds - 1
    after = _drains()
    assert _moved(after, before) == {"last_row": 1}
    assert _counter(OVERRUN) - overrun == 1      # ``short``, by length
    dispatches = [r for r in recs if r.name == trace.ENGINE_DECODE_DISPATCH]
    fetched = [r for r in recs if r.name == trace.ENGINE_DECODE_FENCE]
    emits = [r for r in recs if r.name == trace.ENGINE_DECODE_EMIT]
    assert all(d.attrs["overlapped"] is True for d in dispatches)
    numbers = [d.attrs["round"] for d in dispatches]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    assert [f.attrs["round"] for f in fetched] == \
        list(range(numbers[0] - 1, numbers[-1] + 1))
    by_round = {d.attrs["round"]: d for d in dispatches}
    for fence in fetched[:-1]:
        # fetched behind the next round's dispatch, in that round's turn
        nxt = by_round[fence.attrs["round"] + 1]
        assert nxt.end <= fence.start and nxt.parent == fence.parent
    assert len(emits) == len(fetched)
    assert all(set(e.attrs) == {"rows", "model_stats"} for e in emits)
    # ``short`` rides the round behind its last token: that round's emit
    # says two rows, as it was dispatched, and delivers one token
    rows = [e.attrs["rows"] for e in emits]
    assert rows == sorted(rows, reverse=True) and set(rows) == {1, 2}
    engine.close()


def test_close_with_a_round_in_flight_finishes_every_waiter(models):
    engine = _engine(models("llama")).start()
    reqs = [engine.submit(_tokens(81 + i, 9 + 7 * i, 64),
                          max_new_tokens=150) for i in range(3)]
    deadline = time.monotonic() + 60
    while min(len(r.tokens) for r in reqs) < 3:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    before = _drains()["stop"]
    engine.close()
    assert all(r.done for r in reqs)
    assert all(r.error is None or "shutting down" in r.error for r in reqs)
    assert engine._inflight is None
    assert engine.host_fetches == engine.decode_steps
    assert _drains()["stop"] - before <= 1


def test_a_dead_loop_with_a_round_in_flight_finishes_every_waiter(models):
    engine = _engine(models("llama")).start()
    reqs = [engine.submit(_tokens(91 + i, 9 + 7 * i, 64),
                          max_new_tokens=150) for i in range(3)]
    deadline = time.monotonic() + 60
    while min(len(r.tokens) for r in reqs) < 3:
        assert time.monotonic() < deadline
        time.sleep(0.002)

    def boom():
        raise RuntimeError("device on fire")

    engine.step = boom
    for r in reqs:
        with pytest.raises(RuntimeError, match="engine loop died"):
            r.result(timeout=30)
    assert engine.closed and engine._inflight is None
    engine.close()


def test_step_from_another_thread_keeps_its_contract(models):
    """``step()`` called by anyone but the loop thread leaves nothing in
    flight: what it dispatched is emitted when it returns."""
    engine = _engine(models("llama"))
    overlapped, before = _counter(OVERLAPPED), _drains()
    req = engine.submit(_tokens(95, 9, 64), max_new_tokens=10)
    seen = 0
    while not req.done:
        engine.step()
        assert engine._inflight is None
        assert len(req.tokens) > seen or req.done
        seen = len(req.tokens)
    assert engine.host_fetches == engine.decode_steps == 9
    assert _counter(OVERLAPPED) == overlapped and _drains() == before
    engine.close()


# -- a finished prompt activates its slot on the device -----------------------


def _decoding(engine, prompt, budget, least=3):
    """A request stepped until it has ``least`` tokens: a resident row,
    with a round in flight where the engine lags."""
    req = engine.submit(prompt, max_new_tokens=budget)
    for _ in range(100):
        engine.step()
        if len(req.tokens) >= least:
            return req
    raise AssertionError("the row never got going")


def _until_one_chunk_is_left(engine, req):
    """Step until ``req``'s staged prompt has one program to go: the next
    turn finishes it."""
    for _ in range(200):
        job = next((j for j in engine.prefill.jobs if j.req is req), None)
        if job is not None and job.next_chunk == len(job.plan) - 1:
            return job
        assert not req.tokens, "the prompt finished before it was looked at"
        engine.step()
    raise AssertionError("the prompt was never staged")


@pytest.mark.parametrize("kind", KINDS)
def test_a_finished_prompt_drains_nothing_and_waits_for_nothing(models, kind):
    """A row decodes, a second prompt finishes with a round in flight. That
    turn: no drain, the next round dispatched over the round in flight
    with the new row in it before anything is fetched, then round n's
    fence, then the first token's (the prefill's own, one a finished
    prompt), and the first token out when the turn ends. And the tokens of
    a ``step()``-driven engine in the end."""
    model, gang = models(kind), kind == "gang"
    vocab = model[0].vocab_size
    prompts = [_tokens(101, 12, vocab), _tokens(102, 41, vocab)]

    def submit(engine):
        return [engine.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, (40, 12))]

    ref = _engine(model, gang=gang)
    want = submit(ref)
    _run(ref, want)
    ref.close()
    engine = _lagging(_engine(model, gang=gang))
    old = _decoding(engine, prompts[0], 40)
    new = engine.submit(prompts[1], max_new_tokens=12)
    _until_one_chunk_is_left(engine, new)
    assert engine._inflight is not None and not new.tokens
    drains, acts = _drains(), _activations()
    overlapped, fetches = _counter(OVERLAPPED), engine.host_fetches
    steps, had = engine.decode_steps, len(old.tokens)
    with trace.recording() as rec:
        engine.step()
        recs = rec.drain()
    assert _drains() == drains
    assert _moved(_activations(), acts) == {"device": 1}
    assert _counter(OVERLAPPED) - overlapped == 1
    # one decode round fetched (the one that was in flight), one fence of
    # the prefill's own, and the new row rides the round now in flight
    assert engine.host_fetches - fetches == 1 == engine.decode_steps - steps
    assert len(new.tokens) == 1 and len(old.tokens) == had + 1
    assert new.first_token_at is not None
    assert new in [req for _, req in engine._inflight.rows]
    assert not engine._first_pending
    dispatch, = [r for r in recs if r.name == trace.ENGINE_DECODE_DISPATCH]
    fence, = [r for r in recs if r.name == trace.ENGINE_DECODE_FENCE]
    first, = [r for r in recs if r.name == trace.ENGINE_PREFILL_FENCE]
    assert dispatch.attrs["overlapped"] is True
    assert dispatch.end <= fence.start and fence.end <= first.start
    assert fence.attrs["round"] == dispatch.attrs["round"] - 1
    _run(engine, [old, new])
    assert [list(r.tokens) for r in (old, new)] == \
        [list(r.tokens) for r in want]
    assert engine.host_fetches == engine.decode_steps
    assert _drains()["admission"] == drains["admission"]
    engine.close()


def test_an_eos_first_token_is_learnt_one_round_late(models):
    """The first token is the EOS: it is emitted and ends the request. The
    round dispatched before it was fetched carried the row once: it wrote
    the prompt's own pages, the scratch page and the other row's pages and
    no other, and its token for the row is dropped and counted."""
    model = models("llama")
    a_prompt, b_prompt = _tokens(111, 9, 64), _tokens(112, 37, 64)
    probe = _engine(model)
    b = probe.submit(b_prompt, max_new_tokens=4)
    _run(probe, [b])
    probe.close()
    eos = b.tokens[0]
    ref = _engine(model, eos_token=eos)
    want_a = ref.submit(a_prompt, max_new_tokens=40)
    _run(ref, [want_a])
    ref.close()
    assert len(want_a.tokens) > 12, "the resident row ends too early"
    engine = _lagging(_engine(model, eos_token=eos))
    a = _decoding(engine, a_prompt, 40)
    b = engine.submit(b_prompt, max_new_tokens=30)
    job = _until_one_chunk_is_left(engine, b)
    slot_a, table = engine._active.index(a), list(job.table)
    pages = [np.asarray(engine._payload[i]) for i in engine._pool_at]
    overrun, acts, drains = _counter(OVERRUN), _activations(), _drains()
    engine.step()
    assert list(b.tokens) == [eos] and b.done and b.error is None
    assert _moved(_activations(), acts) == {"device": 1}
    assert _drains() == drains
    assert b not in engine._active
    assert b in [req for _, req in engine._inflight.rows]   # it rode along
    held = set(table) | {0} | set(engine._slot_blocks[slot_a])
    for before, i in zip(pages, engine._pool_at):
        after = np.asarray(engine._payload[i])
        changed = {int(p) for p in np.nonzero(
            (before != after).reshape(before.shape[0], -1).any(axis=1))[0]}
        assert changed <= held, (changed, held)
    engine.step()
    assert _counter(OVERRUN) - overrun == 1
    _run(engine, [a])
    assert list(a.tokens) == list(want_a.tokens)
    assert engine.host_fetches == engine.decode_steps
    s = engine.stats()
    assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total
    engine.close()


def test_a_request_of_one_token_never_activates_a_slot(models):
    engine = _lagging(_engine(models("llama")))
    old = _decoding(engine, _tokens(121, 12, 64), 40)
    one = engine.submit(_tokens(122, 20, 64), max_new_tokens=1)
    _until_one_chunk_is_left(engine, one)
    assert engine._inflight is not None
    activated = []
    activate = engine._activate
    engine._activate = lambda *a: activated.append(a) or activate(*a)
    acts, drains, seen = _activations(), _drains(), []
    real = engine._finish_prefill
    engine._finish_prefill = lambda slot, req, first: (
        seen.append((engine._active[slot], engine._inflight)),
        real(slot, req, first))
    engine.step()
    assert one.done and len(one.tokens) == 1 and one.error is None
    assert not activated and one not in engine._active
    # today's path: the round in flight fetched, then the token waited for
    assert seen == [(None, None)]
    assert _moved(_activations(), acts) == {"drained": 1}
    assert _moved(_drains(), drains) == {"admission": 1}
    _run(engine, [old])
    assert len(old.tokens) == 40
    s = engine.stats()
    assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total
    engine.close()


def test_a_request_cancelled_with_its_first_token_pending_is_reaped(models):
    """The client goes away between the activation and the first token's
    fetch (here: while the host does its overlap work). The token was made
    and is delivered, as a decode row's is; the next turn's reap frees slot
    and pages, and the round that carried the row drops its token."""
    model = models("llama")
    ref = _engine(model)
    want = ref.submit(_tokens(131, 12, 64), max_new_tokens=40)
    _run(ref, [want])
    ref.close()
    engine = _lagging(_engine(model))
    old = _decoding(engine, _tokens(131, 12, 64), 40)
    gone = engine.submit(_tokens(132, 41, 64), max_new_tokens=30)
    _until_one_chunk_is_left(engine, gone)
    window = engine._overlap_window
    engine._overlap_window = lambda: (gone.cancel(), window())
    overrun, cancelled = _counter(OVERRUN), engine.stats().requests_cancelled
    engine.step()
    engine._overlap_window = window
    assert len(gone.tokens) == 1 and not gone.done
    assert not engine._first_pending
    assert gone in [req for _, req in engine._inflight.rows]
    engine.step()
    assert gone.done and gone.status == "cancelled"
    assert len(gone.tokens) == 1            # the token in flight: gone
    assert gone not in engine._active
    assert engine.stats().requests_cancelled - cancelled == 1
    assert _counter(OVERRUN) - overrun == 1
    _run(engine, [old])
    assert list(old.tokens) == list(want.tokens)
    s = engine.stats()
    assert s.busy == 0
    assert s.kv_blocks_free + s.kv_blocks_cached == s.kv_blocks_total
    engine.close()


def test_a_proposer_keeps_the_drained_path(models):
    model = models("llama")
    want = _reference(model)
    engine = _lagging(_engine(model, spec_tokens=3))
    acts = _activations()
    activated = []
    activate = engine._activate
    engine._activate = lambda *a: activated.append(a) or activate(*a)
    reqs = _submit_mix(engine, 64)
    _run(engine, reqs)
    assert [list(r.tokens) for r in reqs] == want
    assert _moved(_activations(), acts) == {"drained": len(_MIX)}
    # all but the request of one token entered the round's inputs there
    assert len(activated) == len(_MIX) - 1
    engine.close()


def test_step_from_another_thread_emits_the_first_token_it_finished(models):
    """Not the loop's thread: the same path (activated on the device, the
    next round dispatched first), and everything the turn owes is out when
    ``step()`` returns, the first token in front of the round's own."""
    engine = _engine(models("llama"))
    acts, drains = _activations(), _drains()
    req = engine.submit(_tokens(141, 41, 64), max_new_tokens=10)
    job = _until_one_chunk_is_left(engine, req)
    with trace.recording() as rec:
        engine.step()
        recs = rec.drain()
    assert len(req.tokens) == 2 and req.first_token_at is not None
    assert engine._inflight is None and not engine._first_pending
    assert engine.host_fetches == engine.decode_steps == 1
    first, = [r for r in recs if r.name == trace.ENGINE_PREFILL_FENCE]
    fence, = [r for r in recs if r.name == trace.ENGINE_DECODE_FENCE]
    dispatch, = [r for r in recs if r.name == trace.ENGINE_DECODE_DISPATCH]
    assert dispatch.end <= first.start and first.end <= fence.start
    assert _moved(_activations(), acts) == {"device": 1}
    assert _drains() == drains
    _run(engine, [req])
    assert len(req.tokens) == 10
    engine.close()

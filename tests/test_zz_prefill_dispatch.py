"""A prefill round is one dispatch (``serving/engine.py``).

A prefill round makes ONE device call: the jitted ``prefill_step``, which
builds its index leaves, cuts and pads its chunk, and picks the first token
inside the program, told what to do by the job's buffer (a cursor, a row of
four numbers a chunk, the page table, the prompt: one shape whatever the
prompt), which rides in the dispatch of the job's first round and stays on
the device with its cursor moved on. The round that finishes a prompt adds
the rng's one split (a program of its own, compiled once: inside
``prefill_step`` it would be lowered again at every width). These tests hold
that
for the four kinds of cache the engine serves: keys and values (the toy
Llama), a latent page with no head axis (the tiny Moonlight) and the two
families with per-slot state beside the pool (the tiny Nemotron and Solar);
(a) and (e) also for the gang over a mesh, whose ``prefill_step`` is its own:

(a) ``lzy_engine_prefill_device_calls_total`` rises by one a round, and by
    the rng's split (and a state model's splice) on a prompt's last;
(b) prompts of lengths never seen compile nothing;
(c) a padded tail leaves the first token, the pool pages the prompt owns and
    the spliced state rows bit-identical to a one-shot prefill, and to the
    host-side form this replaced (slice and pad on the host, an index leaf
    a layer, the pick outside), kept here as the reference;
(d) seeded sampling draws the same first tokens whether the prefill rounds
    of three requests interleave or run one after another;
(e) ``warmup()`` traces, lowers and compiles no ``prefill_step``: a width is
    compiled by the first request that reaches it, once, and a width nothing
    reaches costs set-up nothing;
(f) greedy tokens are those of the same requests served alone with a one-shot
    prefill, whatever interleaves (each kind is held to its own reference
    decode in its own file: ``test_engine_serves_the_references_tokens``; the
    toy Llama here too, to ``generate()``).

The file is named to sort last: under ``--dist loadfile`` files go to the
workers in name order, and a long file in the middle of the alphabet shifts
which files run beside ``tests/test_load.py``'s wall-clock smoke test (68 s
of the 60 it allows, once, with this file as ``test_prefill_dispatch.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import deepseek_v3, llama, nemotron_h, serving, \
    solar_open2, unbox
from lzy_tpu.models.generate import generate, prefill_plan
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils.metrics import REGISTRY

KINDS = ("llama", "nemotron", "solar", "moonlight")
CALLS = "lzy_engine_prefill_device_calls_total"


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
            elif kind == "solar":
                cfg = solar_open2.SolarOpen2Config.tiny()
                params = solar_open2.init_params(cfg, key)
            else:
                cfg = deepseek_v3.DeepseekV3Config.tiny()
                params = deepseek_v3.init_params(cfg, key)
            built[kind] = cfg, params
        return built[kind]

    return get


def _engine(model, *, gang=False, **kw):
    """``gang``: the same engine tensor-sharded over two of the virtual
    devices (``serving/sharded``), whose ``prefill_step`` is its own."""
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


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == name:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _drain(engine, reqs, limit=400):
    for _ in range(limit):
        if all(r.done for r in reqs):
            assert all(r.error is None for r in reqs), \
                [r.error for r in reqs]
            return
        engine.step()
    raise AssertionError("the engine did not finish its requests")


class _Compiles:
    """What JAX traced, lowered and asked its backend for while ``on``, by
    the jitted function's name: a read of the persistent cache is a request
    too, so none means every program was already in the process. A listener
    cannot be taken off again, so it stays, switched off."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax.monitoring as mon

        self.on = False
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, seconds, fun_name="", **_):
        if self.on and event in self.STAGES:
            # the backend's stage calls the function ``jit(name)``
            name = fun_name.removeprefix("jit(").removesuffix(")")
            getattr(self, self.STAGES[event]).append(name)

    @property
    def requests(self):
        return len(self.compiled)

    def __enter__(self):
        self.traced, self.lowered, self.compiled = [], [], []
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


# -- (a) one device call a round ----------------------------------------------

@pytest.mark.parametrize("kind", KINDS + ("gang",))
def test_a_prefill_round_is_one_device_call(models, kind):
    """Two prompts of three and four chunks, one after the other. Every
    round is the program alone, but: the first round of a state model's
    first job makes a row a state leaf (the second job starts from the
    rows the first left behind); the round that finishes a prompt adds
    the rng's split and, on a state model, the splice; a gang places a
    job's buffer on its mesh by a call of its own."""
    engine = _engine(models(kind), gang=kind == "gang")
    cfg = engine.cfg
    state_leaves = len(engine._state_at)
    try:
        for nth, n in enumerate((40, 53)):
            req = engine.submit(_tokens(nth, n, cfg.vocab_size),
                                max_new_tokens=1)
            rounds = []
            while not req.done:
                before = _counter(CALLS)
                programs = _counter("lzy_engine_prefill_programs_total")
                engine.step()
                assert _counter("lzy_engine_prefill_programs_total") \
                    == programs + 1
                rounds.append(int(_counter(CALLS) - before))
            assert req.error is None
            assert len(rounds) == -(-n // 16)
            first = (2 if kind == "gang" else 1) \
                + (state_leaves if nth == 0 else 0)
            last = 3 if state_leaves else 2
            assert rounds == [first] + [1] * (len(rounds) - 2) + [last], \
                rounds
    finally:
        engine.close()


# -- (b) no program's shape follows the prompt's length -----------------------

@pytest.mark.parametrize("kind", KINDS)
def test_unseen_prompt_lengths_compile_nothing(models, compiles, kind):
    engine = _engine(models(kind), prefill_budget=32)
    cfg = engine.cfg
    assert engine.prefill_chunk == 32
    try:
        # one prompt a width bucket (tails of 8, 16 and 32), decoded a
        # few tokens so that the decode half is compiled too
        warm = [engine.submit(_tokens(n, n, cfg.vocab_size),
                              max_new_tokens=3) for n in (5, 44, 64)]
        _drain(engine, warm)
        with compiles:
            cold = [engine.submit(_tokens(n, n, cfg.vocab_size),
                                  max_new_tokens=3)
                    for n in (3, 7, 9, 13, 21, 38, 57, 70)]
            _drain(engine, cold)
        assert compiles.requests == 0
    finally:
        engine.close()


# -- (c) a padded tail, against a one-shot prefill and the host-side form -----

def _prefill_only(engine, prompt):
    """Prefill ``prompt`` (one token asked for, so no decode round runs)
    and return the first token with what the prompt left on the device:
    each paged leaf's pages of the blocks it owned, and each state leaf's
    row of its slot."""
    held = {}
    finish = engine._finish_prefill

    def spy(slot, req, first):
        held["slot"], held["blocks"] = slot, list(engine._slot_blocks[slot])
        finish(slot, req, first)

    engine._finish_prefill = spy
    req = engine.submit(prompt, max_new_tokens=1)
    _drain(engine, [req])
    blocks = np.asarray(held["blocks"])
    pages = [np.asarray(engine._payload[i])[blocks]
             for i in engine._pool_at]
    rows = [np.asarray(engine._payload[i])[held["slot"]]
            for i in engine._state_at]
    return req.tokens[0], held["blocks"], pages, rows


def _host_side_prefill(engine, prompt, blocks):
    """The form ``prefill_step`` replaced, on ``engine``'s own pool and
    module: the chunk sliced and padded on the host, a fresh ``[1]`` index
    leaf a layer at ``start``, the program told its last real index, the
    first token picked outside it."""
    cfg, model = engine.cfg, engine._model
    tells_real = engine._tells_real

    @jax.jit
    def step(cache, params, tokens, page_table, last_idx):
        real = {"valid_len": jnp.reshape(last_idx + 1, (1,))} \
            if tells_real else {}
        logits, updated = model.apply(
            {"params": params, "cache": cache}, tokens,
            page_table=page_table, mutable=["cache"], **real)
        return updated["cache"], jax.lax.dynamic_index_in_dim(
            logits, last_idx, axis=1, keepdims=False)

    table = np.zeros((1, cfg.max_seq_len // 16), np.int32)
    table[0, :len(blocks)] = blocks
    leaves = [None if kind == serving.INDEX
              else jnp.zeros((1,) + leaf.shape[1:], leaf.dtype)
              if kind == serving.STATE else leaf
              for kind, leaf in zip(
                  engine._leaf_kinds,
                  jax.tree_util.tree_leaves(engine._assemble_cache(
                      engine._payload, jnp.zeros((1,), jnp.int32))))]
    arr = jnp.asarray([prompt], jnp.int32)
    for start, take, width in prefill_plan(
            len(prompt), engine.prefill_chunk, cfg.max_seq_len):
        tokens = arr[:, start:start + take]
        if width != take:
            tokens = jnp.pad(tokens, ((0, 0), (0, width - take)))
        cache = jax.tree_util.tree_unflatten(engine._cache_treedef, [
            jnp.full((1,), start, jnp.int32) if leaf is None else leaf
            for leaf in leaves])
        cache, last = step(cache, engine.params, tokens, jnp.asarray(table),
                           jnp.asarray(take - 1, jnp.int32))
        leaves = [None if kind == serving.INDEX else leaf
                  for kind, leaf in zip(engine._leaf_kinds,
                                        jax.tree_util.tree_leaves(cache))]
    first = int(jnp.argmax(last, axis=-1)[0])
    blocks = np.asarray(blocks)
    pages = [np.asarray(leaf)[blocks]
             for kind, leaf in zip(engine._leaf_kinds, leaves)
             if kind == serving.PAGED]
    rows = [np.asarray(leaf)[0]
            for kind, leaf in zip(engine._leaf_kinds, leaves)
            if kind == serving.STATE]
    return first, pages, rows


@pytest.mark.parametrize("kind", KINDS)
def test_a_padded_tail_is_bit_identical(models, kind):
    """37 tokens in chunks of 16: 16 + 16 + a tail of 5 padded to 8, over
    three rounds; the same plan in one round; and the host-side form."""
    model = models(kind)
    prompt = _tokens(7, 37, model[0].vocab_size)
    chunked = _engine(model)
    one_shot = _engine(model, prefill_budget=None, prefill_chunk=16)
    host = _engine(model)
    try:
        assert chunked.prefill_chunk == 16
        first, blocks, pages, rows = _prefill_only(chunked, prompt)
        assert chunked.prefill_rounds == 3
        assert len(rows) == len(chunked._state_at)
        assert any(np.any(p != 0) for p in pages)
        assert all(np.any(r != 0) for r in rows)

        first_1, blocks_1, pages_1, rows_1 = _prefill_only(one_shot, prompt)
        assert one_shot.prefill_rounds == 1 and blocks_1 == blocks
        first_h, pages_h, rows_h = _host_side_prefill(host, prompt, blocks)
        for other in ((first_1, pages_1, rows_1), (first_h, pages_h, rows_h)):
            assert other[0] == first
            for got, want in zip(pages + rows, other[1] + other[2]):
                np.testing.assert_array_equal(got, want)
    finally:
        for engine in (chunked, one_shot, host):
            engine.close()


# -- (d) the rng advances where a prompt finishes, and nowhere else -----------

@pytest.mark.parametrize("kind", KINDS)
def test_sampled_first_tokens_survive_interleaving(models, kind):
    """Three sampled requests of 2, 3 and 4 chunks. Submitted together
    their prefill rounds rotate (A B C A B C B C C) and they finish in the
    order they came; submitted one after another nothing interleaves. The
    rng is split once a finished prompt, so both give the same tokens, and
    the engine's key ends three splits from its seed."""
    model = models(kind)
    vocab = model[0].vocab_size
    prompts = [_tokens(20 + i, n, vocab) for i, n in enumerate((20, 37, 60))]
    kw = dict(temperature=0.9, top_k=8, seed=11)
    together, apart = _engine(model, **kw), _engine(model, **kw)
    try:
        reqs = [together.submit(p, max_new_tokens=1) for p in prompts]
        _drain(together, reqs)
        assert together.prefill_rounds == 9
        alone = []
        for p in prompts:
            alone.append(apart.submit(p, max_new_tokens=1))
            _drain(apart, alone[-1:])
        assert [r.tokens for r in reqs] == [r.tokens for r in alone]
        key = jax.random.PRNGKey(11)
        for _ in prompts:
            key = jax.random.split(key)[0]
        for engine in (together, apart):
            np.testing.assert_array_equal(np.asarray(engine._rng),
                                          np.asarray(key))
        # a greedy row of a sampling engine takes the best logit, and
        # still costs the stream its one split
        unseen = _tokens(30, 30, vocab)
        greedy = apart.submit(unseen, max_new_tokens=1, greedy=True)
        _drain(apart, [greedy])
        cold = _engine(model)
        try:
            want = cold.submit(unseen, max_new_tokens=1)
            _drain(cold, [want])
        finally:
            cold.close()
        assert greedy.tokens == want.tokens
        np.testing.assert_array_equal(
            np.asarray(apart._rng), np.asarray(jax.random.split(key)[0]))
    finally:
        together.close()
        apart.close()


# -- (e) warm-up compiles no width; the first request at a width compiles it ---

@pytest.mark.parametrize("kind", KINDS + ("gang",))
def test_warmup_compiles_no_prefill_width(models, compiles, kind):
    """``warmup()`` is decode's (and the state splice's): it neither traces
    nor lowers nor compiles ``prefill_step``, whose widths cost seconds each
    on the chip whatever the compile cache holds. The first request that
    reaches a width compiles exactly that program, host buffer on its first
    round and device buffer after it; a request at widths already seen
    compiles nothing."""
    engine = _engine(models(kind), gang=kind == "gang", prefill_budget=32,
                     temperature=0.7)
    cfg = engine.cfg
    try:
        with compiles:
            engine.warmup()
        assert "decode_step" in compiles.compiled
        for stage in (compiles.traced, compiles.lowered, compiles.compiled):
            assert "prefill_step" not in stage
        # tails of 8; of 32 then 16 (two rounds: the job's buffer reaches
        # the second from the device); then 32 and 32, both seen
        for n, new in ((5, 1), (44, 2), (64, 0)):
            with compiles:
                _drain(engine, [engine.submit(_tokens(n, n, cfg.vocab_size),
                                              max_new_tokens=3)])
            assert compiles.compiled.count("prefill_step") == new
            assert compiles.lowered.count("prefill_step") == new
        assert sorted(engine._dispatch_paths) == [1, 8, 16, 32]
    finally:
        engine.close()


# -- (f) greedy decoding, chunked and interleaved or alone ---------------------

@pytest.mark.parametrize("kind", KINDS)
def test_greedy_tokens_survive_chunking_and_interleaving(models, kind):
    """Three greedy requests of 2, 3 and 4 chunks and six tokens each.
    Submitted together, the later prompts' prefill rounds run between the
    earlier requests' decode rounds; served one at a time by an engine that
    prefills in one round, nothing interleaves. Same tokens."""
    model = models(kind)
    cfg, params = model
    prompts = [_tokens(40 + i, n, cfg.vocab_size)
               for i, n in enumerate((20, 37, 60))]
    together = _engine(model)
    alone = _engine(model, prefill_budget=None, prefill_chunk=16)
    try:
        reqs = [together.submit(p, max_new_tokens=6) for p in prompts]
        _drain(together, reqs)
        assert together.prefill_rounds == 9
        for req, prompt in zip(reqs, prompts):
            want = alone.submit(prompt, max_new_tokens=6)
            _drain(alone, [want])
            assert req.tokens == want.tokens
            if kind == "llama":
                ref = generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                               max_new_tokens=6, prefill_chunk=16)
                assert req.tokens == np.asarray(ref)[0, len(prompt):].tolist()
    finally:
        together.close()
        alone.close()

"""``serving/prefill.py``: the prefill object at its seam.

The object is built here with no engine (a module that echoes what it is
told, a real ``RadixCache``, callbacks that record), which is what the seam
is for: the buffer's writer against its traced reader, the round-robin
cursor, what a job that ends in each of its ways gives back. And inside real
engines over the three kinds of cache (a pool, a pool with window pages,
state a slot and no pool), a job's resources over its life.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import brumby, cohere2_moe, llama, serving, unbox
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving import prefill as prefill_mod
from lzy_tpu.serving.engine import PoolCorruption
from lzy_tpu.serving.kv_cache import RadixCache
from lzy_tpu.serving.prefill import Prefill, ProgramBuild
from lzy_tpu.serving.scheduler import Request
from lzy_tpu.utils import trace
from lzy_tpu.utils.clock import SYSTEM_CLOCK

PAGE, CHUNK, SEQ, VOCAB = 16, 16, 128, 64


class _Echo:
    """The one module a ``Prefill`` needs: ``apply`` and the class
    attributes it asks for. A program's logits are its tokens one-hot (so
    the first token picked is the prompt's last real token), and a row of
    the ``log`` leaf keeps what the program was told: one row a program, at
    the count the state leaf ``acc`` has reached."""

    STATS = ()
    TOLD_PROMPT_LEN = True

    def apply(self, variables, tokens, *, page_table, mutable, valid_len,
              prompt_len):
        cache = variables["cache"]
        n = cache["acc"][0, 0]
        row = jnp.stack([cache["index"][0], valid_len[0], prompt_len[0],
                         page_table[0, 0], tokens[0, 0], tokens[0, -1], n,
                         jnp.int32(tokens.shape[1])])
        return jax.nn.one_hot(tokens, VOCAB), {"cache": {
            "acc": cache["acc"] + 1,
            "index": cache["index"] + tokens.shape[1],
            "log": cache["log"].at[n].set(row)}}


class _Fatal(RuntimeError):
    pass


class _Bench:
    """A ``Prefill`` and everything it is handed, with no engine."""

    def __init__(self, *, budget=CHUNK, max_jobs=None, finished=None):
        tree = {"acc": jnp.zeros((3, 1), jnp.int32),        # state a slot
                "index": jnp.zeros((3,), jnp.int32),
                "log": jnp.full((16, 8), -1, jnp.int32)}    # a "pool"
        treedef = jax.tree_util.tree_structure(tree)
        kinds = [serving.STATE, serving.INDEX, serving.PAGED]
        self.payload = [tree["acc"], tree["log"]]
        self.rng = jax.random.PRNGKey(0)
        self.kv = RadixCache(32, PAGE)
        self.done, self.failed, self.cancelled = [], [], []
        self.widths, self.entered = [], []
        self.prefill = Prefill(
            types.SimpleNamespace(max_seq_len=SEQ), _Echo(), {},
            leaf_kinds=kinds, treedef=treedef, state_at=[0], pool_at=[1],
            build=ProgramBuild(), kv=self.kv,
            kv_io=types.SimpleNamespace(promote=lambda tokens: 0), win=None,
            page_size=PAGE, pooled=True, chunk=CHUNK, budget=budget,
            max_jobs=max_jobs, sampling=(0.0, None, None), tells_real=True,
            clock=SYSTEM_CLOCK, payload=lambda: self.payload,
            rng=lambda: self.rng,
            set_rng=lambda key: setattr(self, "rng", key),
            row_greedy=lambda req: bool(req.greedy),
            count_dispatch=self.widths.append,
            first=lambda *a, **kw: trace.NOOP,
            enter=lambda: self.entered.append(1),
            finished=finished or (lambda job, tok: self.done.append(
                (job.req, job.slot, tok))),
            failed=lambda req, e, what: self.failed.append((req, e, what)),
            cancelled=self.cancelled.append, fatal=_Fatal)

    def request(self, n, *, first=1, greedy=True):
        return Request(list(range(first, first + n)), 4, greedy=greedy)

    def log(self):
        rows = np.asarray(self.payload[1])
        return rows[rows[:, 0] >= 0]


def _held(kv):
    """What of a ``RadixCache`` a leak would move."""
    return (list(kv.pool._ref), sorted(kv.pool._free), kv.available())


def test_each_program_sees_the_row_that_was_written():
    """A plan of three chunks with a padded tail: the writer's rows are
    what the traced reader hands each program, and the cursor moves by
    one a program."""
    bench = _Bench()
    p = bench.prefill
    req = bench.request(40, first=3, greedy=True)
    job = p.stage(2, req)
    assert job.plan == [(0, 16, 16), (16, 16, 16), (32, 8, 8)]
    plan_at, table_at, prompt_at, length = p.layout
    for n, (start, take, width) in enumerate(job.plan):
        assert p.advance()
        buf = np.asarray(job.inputs)
        assert buf.shape == (1, length) and buf[0, 0] == n + 1   # cursor
        # the row this program read, still where the writer put it
        row = buf[0, plan_at + 5 * n:plan_at + 5 * (n + 1)]
        assert list(row) == [start, take, 1, int(n == 0), 40]
        # what the module was told: start (the index leaf), valid_len,
        # prompt_len, the table's first page, the chunk's two ends (a
        # padded tail reads the pad id), the state's count (zeroed once, by
        # the fresh program, and carried since), the width
        last = req.prompt[start + width - 1] if take == width else 0
        assert list(bench.log()[n]) == [
            start, take, 40, job.table[0], req.prompt[start], last, n, width]
    # the traced reader on the buffer itself, eagerly, at a cursor of 2
    buf = jnp.asarray(np.asarray(job.inputs)).at[0, 0].set(2)
    ctl, table, prompt = p._read_row(buf)
    assert list(np.asarray(ctl)) == [32, 8, 1, 0, 40]
    assert list(np.asarray(table)[0, :3]) == job.table
    assert table.shape == (1, prompt_at - table_at) == (1, SEQ // PAGE)
    chunk = p._read_chunk(prompt, ctl[0], ctl[1], 16)
    assert list(np.asarray(chunk)[0]) == req.prompt[32:40] + [0] * 8
    # the scheduler was handed the first token where the program left it,
    # and nothing waited for the program: the fence is the scheduler's call
    (done, slot, first), = bench.done
    assert done is req and slot == 2
    assert isinstance(first, jax.Array) and first.shape == (1,)
    assert p.fence_wait == 0.0
    # the prompt's last real token comes back as the first token
    assert p.fence(first) == req.prompt[-1] and p.fence_wait > 0.0
    assert bench.widths == [16, 16, 8]
    assert p.rounds == 3 and not p.jobs and len(bench.entered) == 3


def test_the_fence_is_timed_over_a_turn_and_spanned():
    """``fence`` is where the loop waits for a prompt's programs: its
    seconds add up over the fences of a turn (``advance`` opens the turn)
    and each is an ``engine.prefill.fence`` record."""
    bench = _Bench(budget=None)
    p = bench.prefill
    reqs = [bench.request(20, first=1), bench.request(9, first=30)]
    for slot, req in enumerate(reqs):
        p.stage(slot, req)
    assert p.advance() and p.advance() and not p.jobs
    assert p.fence_wait == 0.0
    with trace.recording() as rec:
        tokens = []
        for _, _, first in bench.done:
            before = p.fence_wait
            tokens.append(p.fence(first))
            assert p.fence_wait > before
        recs = rec.drain()
    assert tokens == [20, 38]
    assert [r.name for r in recs if r.name.startswith("engine.")] == \
        [trace.ENGINE_PREFILL_FENCE] * 2
    p.stage(0, bench.request(5))
    assert p.advance() and p.fence_wait == 0.0


def test_a_sampled_rows_flag_is_written_too():
    bench = _Bench()
    job = bench.prefill.stage(0, bench.request(20, greedy=False))
    buf = bench.prefill._write(job)
    plan_at = bench.prefill.layout[0]
    assert list(buf[0, plan_at:plan_at + 10]) == [
        0, 16, 0, 1, 20, 16, 4, 0, 0, 20]


def test_the_cursor_survives_a_drop_in_front_of_it():
    """Three jobs of three rounds each, budget one chunk a round. After a
    round each of the first two the cursor stands at the third; the first
    is aborted: the third still runs next, and nobody is skipped."""
    bench = _Bench()
    p = bench.prefill
    reqs = [bench.request(40, first=1 + 3 * i) for i in range(3)]
    jobs = [p.stage(i, r) for i, r in enumerate(reqs)]
    assert p.advance() and p.advance()
    assert [j.next_chunk for j in jobs] == [1, 1, 0]
    p.abort(jobs[0])
    assert p.jobs == jobs[1:]
    assert p.advance()
    assert [j.next_chunk for j in jobs] == [1, 1, 1]
    for _ in range(4):
        assert p.advance()
    assert not p.jobs and not p.advance()
    assert [(r, slot) for r, slot, _ in bench.done] == [
        (reqs[1], 1), (reqs[2], 2)]


def test_max_jobs_says_full_and_slots_are_the_jobs():
    bench = _Bench(max_jobs=2)
    p = bench.prefill
    p.stage(0, bench.request(5))
    assert not p.full and p.slots() == {0}
    p.stage(2, bench.request(5))
    assert p.full and p.slots() == {0, 2}


def test_a_reapable_job_goes_as_cancelled_and_gives_everything_back():
    bench = _Bench()
    p = bench.prefill
    before = _held(bench.kv)
    keep, gone = bench.request(40), bench.request(40, first=9)
    p.stage(0, keep)
    p.stage(1, gone)
    assert p.advance() and p.advance()
    gone.cancel()
    p.reap()
    assert bench.cancelled == [gone] and [j.req for j in p.jobs] == [keep]
    assert len(p.spare_state) == 1      # its rows wait for the next job
    keep.cancel()
    assert p.advance()                  # the round's own check
    assert bench.cancelled == [gone, keep] and not p.jobs
    assert _held(bench.kv) == before and len(p.spare_state) == 2


def test_a_failure_after_the_device_section_drops_and_does_not_free():
    """What the error path does, pinned: ``finished`` raising is
    request-scoped, the job goes, the scheduler is told, and the job's
    blocks are NOT released here (they may be the slot's by then)."""
    def finished(job, tok):
        raise ValueError("the slot would not take it")

    bench = _Bench(budget=None, finished=finished)
    p = bench.prefill
    before = _held(bench.kv)
    req = bench.request(20)
    job = p.stage(0, req)
    assert p.advance() and not p.jobs
    (failed, error, what), = bench.failed
    assert failed is req and isinstance(error, ValueError)
    assert what == "prefill"
    assert _held(bench.kv) != before and len(job.table) == 2
    bench.kv.release(job.table)
    assert _held(bench.kv) == before


def test_a_failure_inside_the_device_section_is_fatal_and_keeps_the_job():
    bench = _Bench()
    p = bench.prefill
    p.stage(0, bench.request(20))
    p._enter = lambda: (_ for _ in ()).throw(OSError("device lost"))
    with pytest.raises(_Fatal, match="paged prefill died mid-flight"):
        p.advance()
    assert len(p.jobs) == 1 and not bench.failed


def test_staging_rolls_back_what_it_took():
    bench = _Bench()
    before = _held(bench.kv)
    bench.kv.allocate(bench.kv.available() - 1)     # one block left
    squeezed = _held(bench.kv)
    with pytest.raises(Exception):
        bench.prefill.stage(0, bench.request(40))   # needs three
    assert _held(bench.kv) == squeezed and not bench.prefill.jobs
    assert before != squeezed


# -- a job's resources over its life, inside real engines ---------------------


def _llama():
    cfg = LlamaConfig.tiny(vocab_size=VOCAB)
    return cfg, unbox(llama.init_params(cfg, jax.random.PRNGKey(0))[0])


def _cohere2():
    cfg = cohere2_moe.Cohere2MoeConfig.tiny()
    return cfg, cohere2_moe.init_params(cfg, jax.random.PRNGKey(1))


def _brumby():
    cfg = brumby.BrumbyConfig.tiny()
    return cfg, brumby.init_params(cfg, jax.random.PRNGKey(1))


_MODELS = {"pool": _llama, "window": _cohere2, "state": _brumby}


@pytest.fixture(scope="module", params=sorted(_MODELS))
def engine(request):
    cfg, params = _MODELS[request.param]()
    eng = PagedInferenceEngine(cfg, params, slots=3, page_size=PAGE,
                               kernel="lax", prefill_budget=16)
    yield eng
    eng.close()


def _resources(eng):
    win = eng._win
    return {"kv": _held(eng.kv),
            "window": None if win is None else (
                win.live(), win.available(), win.pool.free_count()),
            "tables": eng._tables.copy().tolist(),
            "slot_blocks": [list(b) for b in eng._slot_blocks]}


def _stage_one(eng, seed, n=40):
    rng = np.random.RandomState(seed)
    prompt = [int(t) for t in rng.randint(1, eng.cfg.vocab_size, n)]
    req = eng.submit(prompt, max_new_tokens=3, greedy=True)
    assert eng._admit()
    job, = eng.prefill.jobs
    assert job.req is req
    return req, job


def test_stage_then_abort_gives_everything_back(engine):
    before = _resources(engine)
    req, job = _stage_one(engine, 1)
    assert engine.prefill.advance()         # a chunk in: pages, rows taken
    assert job.next_chunk == 1 and engine.prefill.jobs
    if engine._win is not None:
        assert job.window.held
    engine.prefill.abort(job)
    engine._finish_cancelled(req)
    assert not engine.prefill.jobs and req.done
    assert _resources(engine) == before
    assert len(engine.prefill.spare_state) == (1 if engine._has_state else 0)


def test_a_failed_round_keeps_the_job_and_close_gives_it_back(engine):
    """A device call dying mid-prefill is the engine's death
    (``PoolCorruption``), not the request's: the job stays staged, holding
    what it held, and the sweep that follows gives it all back."""
    before = _resources(engine)
    spare = len(engine.prefill.spare_state)
    req, job = _stage_one(engine, 2)
    assert engine.prefill.advance()
    held = _resources(engine)
    enter, engine.prefill._enter = engine.prefill._enter, \
        lambda: (_ for _ in ()).throw(OSError("injected"))
    try:
        with pytest.raises(PoolCorruption, match=req.id):
            engine.prefill.advance()
    finally:
        engine.prefill._enter = enter
    assert engine.prefill.jobs == [job] and not req.done
    assert _resources(engine) == held
    engine.prefill.close()                  # what engine.close() calls
    engine._finish_cancelled(req)
    assert _resources(engine) == before
    assert spare <= len(engine.prefill.spare_state) <= 2


def test_a_finished_prompt_leaves_nothing_staged(engine):
    req, job = _stage_one(engine, 3)
    for _ in range(200):
        if req.done:
            break
        engine.step()
    assert req.done and req.error is None and len(req.tokens) == 3
    assert not engine.prefill.jobs and engine.prefill.rounds >= 3
    assert engine.prefill_rounds == engine.prefill.rounds
    assert engine.prefill_chunk == engine.prefill.chunk == 16
    assert engine.prefill_budget == engine.prefill.budget == 16
    assert len(engine.prefill.spare_state) <= 2
    counters = (prefill_mod._PREFILL_PROGRAMS, prefill_mod._PREFILL_TOKENS,
                prefill_mod._PREFILL_POSITIONS, prefill_mod.PREFILL_CALLS)
    assert all(sum(c._values.values()) > 0 for c in counters)

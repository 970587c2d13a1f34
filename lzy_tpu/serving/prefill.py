"""The engine's prefill: a staged prompt from its admission to its first token.

One object, :class:`Prefill`, which ``PagedInferenceEngine`` owns as it owns
``KvIO`` and which reaches the scheduler through what it is built from
alone. It holds the job (:class:`Job`), the one buffer a job's programs are
told everything in (layout, host writer and traced reader side by side), the
programs (``prefill_step``, the rng's split, the splice of a state model's
rows), and the life of a job: staging, one budgeted round a scheduling round
over the jobs in turn, the spare state rows, abort, and the fence that
fetches the first token. The scheduler decides who is admitted and when, and
is told how a job ended: ``finished(job, first)``, with the first token still
a ``[1]`` array on the device (what that does to the slot, and when
``fence(first)`` brings the token to the host, is the scheduler's),
``failed(request, error, what)``, ``cancelled(request)``.

:class:`ProgramBuild` is where the engine's programs (these, and the decode
and verify steps of ``serving/engine.py``) learn what a gang changes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from lzy_tpu.models import serving
from lzy_tpu.models.generate import draw_token, prefill_plan
from lzy_tpu.serving.kv_cache import blocks_for
from lzy_tpu.serving.scheduler import Request
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

_PREFILL_ROUNDS = REGISTRY.counter(
    "lzy_inference_prefill_rounds_total",
    "bounded prefill rounds run between decode steps (chunked prefill)")

# what prefill programs carry: a program costs a read of the weights
# whatever its width, so tokens / programs says how well a round's budget
# is spent, and positions - tokens what the last chunk's pad costs
_PREFILL_TOKENS = REGISTRY.counter(
    "lzy_engine_prefill_tokens_total",
    "prompt tokens forwarded by prefill programs (real tokens, no pads)")
_PREFILL_PROGRAMS = REGISTRY.counter(
    "lzy_engine_prefill_programs_total",
    "prefill programs dispatched (one a chunk of a plan)")
_PREFILL_POSITIONS = REGISTRY.counter(
    "lzy_engine_prefill_positions_total",
    "positions prefill programs ran over: their widths, pads included")
# counted where they are made, in the prefill phase: a round is one (the
# program; a job's buffer rides in its first dispatch), a prompt's last adds
# the rng's split and a state model's splice of its rows
PREFILL_CALLS = REGISTRY.counter(
    "lzy_engine_prefill_device_calls_total",
    "device calls of the prefill phase: programs, the rng's split a "
    "finished prompt, state rows made or spliced, an upload made apart "
    "from a program (a gang's)")

# per-slot state (models/serving.py, cache-leaf kind ``state``): a prefill
# job of a model with state leaves starts from a zeroed batch-1 row, which
# is spliced into the slot's row when the prompt is done
_STATE_RESETS = REGISTRY.counter(
    "lzy_state_slots_reset_total",
    "per-slot state rows started from zero for a newly admitted request "
    "(models with state cache leaves)")


@dataclasses.dataclass(frozen=True)
class ProgramBuild:
    """What a gang changes about the engine's programs, supplied by the
    subclass from what it can observe (``serving/sharded/engine.py``): the
    keywords ``paged_model`` and every ``apply`` get (its rules, ``mesh=``),
    whether a program donates what it is handed (off on a CPU mesh), and
    the sharding that what is not sharded is committed to (``replicated``;
    None off a mesh, where nothing is placed by hand)."""

    model_kw: dict = dataclasses.field(default_factory=dict)
    apply_kw: dict = dataclasses.field(default_factory=dict)
    donate: bool = True
    replicated: Any = None

    def jit(self, fn, donate: tuple = (), **kw):
        if self.donate and donate:
            kw["donate_argnums"] = donate
        return jax.jit(fn, **kw)

    def upload(self, host):
        """A round's input from its host mirror: an explicit copy (the
        mirror goes on changing), committed where the gang says."""
        if self.replicated is None:
            return jnp.array(host)
        return jax.device_put(np.array(host), self.replicated)

    def aval(self, shape, dtype, sharding=None):
        """What ``warmup()`` lowers from: placed on a mesh alone."""
        if self.replicated is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sharding or self.replicated)


@dataclasses.dataclass
class Job:
    """One admitted request's in-progress prefill. With a
    ``prefill_budget`` the engine advances jobs at most ``budget``
    prompt tokens per scheduling round, interleaved with decode steps,
    so a 32k-token prompt can never freeze resident rows' token streams.
    The chunk *plan* is fixed at staging (identical to the one-shot
    path at the same chunk width, which follows the budget), so pausing
    between chunks changes scheduling, never numerics — greedy output
    stays bit-identical to an uncontended run."""

    req: Request
    slot: int                       # reserved; activates on completion
    plan: list                      # [(start, take, width)] over suffix
    next_chunk: int = 0
    done: int = 0                   # suffix tokens already prefilled
    matched: int = 0                # radix-matched prompt prefix
    table: list = dataclasses.field(default_factory=list)  # pool blocks
    # everything the job's programs are told, in ONE int32 array of one
    # shape whatever the prompt's length (a 32k prompt at budget 256 runs
    # ~128 rounds, and no program or transfer may follow the length): the
    # buffer whose format stands below. A host array until the job's first
    # program, in whose dispatch it rides; every program hands it back on
    # the device with the cursor moved on, so the rounds after the first
    # upload nothing
    inputs: Any = None
    # state leaves (models with per-slot state): the job's
    # own batch-1 rows, carried from program to program — the slot's rows
    # in the decode tree are not touched until the prompt is done. None
    # until the job's first program, which starts them from zero
    state: Any = None
    # window leaves (models/serving.py): the job's own row of window pages
    # (``kv_cache.WindowRow``), grown and shed chunk by chunk; the slot's
    # once the prompt is done
    window: Any = None


# THE BUFFER'S FORMAT. ``[1, n]`` int32: the cursor (which chunk runs next),
# a ``_CTL_*`` row a chunk of the plan, the page table, and the whole prompt
# from position 0 followed by the pad id as far as ``max_seq_len`` plus the
# widest chunk. ``Prefill.layout`` has the offsets, ``_write`` fills it on
# the host, ``_read_row`` and ``_read_chunk`` read it inside the program.
#
# What a prefill program is told about its chunk, one row of five int32 a
# chunk of the job's plan: where the chunk starts (the position of its first
# token in the prompt, which is also the cache index it writes from), how
# many of its positions are real, the row's sampling mode, whether the job's
# state rows start from zero, and the length of the prompt the request was
# admitted with (handed to a model that asks for it: ``TOLD_PROMPT_LEN``)
_CTL_START, _CTL_TAKE, _CTL_GREEDY, _CTL_FRESH, _CTL_PROMPT, _CTL_LEN = \
    range(6)


class Prefill:
    """``model`` is the engine's one module and ``leaf_kinds`` / ``treedef``
    its cache tree's (``models/serving.py``); ``payload`` reads the tree's
    leaves less the index leaves (a list, written in place) and ``rng`` /
    ``set_rng`` the engine's key; ``kv``, ``win`` (None with one kind of
    page) and ``kv_io`` are the pools a job's pages come from and the tiers
    a prompt's prefix is promoted through; ``first(key, site, **attrs)`` is
    the build context around a program's first call, ``count_dispatch`` the
    kernel-path counter a program of a width; ``sampling`` is ``(temperature,
    top_k, top_p)`` and ``row_greedy(request)`` a request's own mode.
    ``enter`` is called at the head of a round's device section and
    ``fatal`` is the error a failure inside it is raised as: the shared
    pool was donated into it, so it fails the engine, not the request."""

    def __init__(self, cfg, model, params, *, leaf_kinds, treedef,
                 state_at, pool_at, build: ProgramBuild, kv, kv_io, win,
                 page_size: int, pooled: bool, chunk: int,
                 budget: Optional[int], max_jobs: Optional[int],
                 sampling: tuple, tells_real: bool, clock, payload, rng,
                 set_rng, row_greedy, count_dispatch, first, enter,
                 finished: Callable[[Job, Any], None],
                 failed: Callable[[Request, Exception, str], None],
                 cancelled: Callable[[Request], None], fatal: type):
        self._cfg, self._model, self._params = cfg, model, params
        self._leaf_kinds, self._treedef = list(leaf_kinds), treedef
        self._build = build
        self.kv, self._kv_io, self._win = kv, kv_io, win
        self._page, self._pooled = page_size, pooled
        self._pages_per_seq = cfg.max_seq_len // page_size if pooled else 0
        # the widest program, and the prompt tokens a round advances at
        # most (None = whole prompt in one round, the pre-tenancy behavior)
        self.chunk, self.budget = chunk, budget
        # prompts staged at once at most (None: one a free slot). A job of a
        # model with state leaves holds its own batch-1 row of every one
        # until it is spliced in, so a burst of prompts holds as many copies
        # of a slot's state as it has jobs: where a slot's state is large, a
        # deployment bounds them here and the rest wait in the queue
        self.max_jobs = max_jobs
        self._temperature, self._top_k, self._top_p = sampling
        self._clock = clock
        self._payload, self._rng, self._set_rng = payload, rng, set_rng
        self._row_greedy, self._count_dispatch = row_greedy, count_dispatch
        self._first, self._enter = first, enter
        self._finished, self._failed = finished, failed
        self._cancelled, self._fatal = cancelled, fatal
        self.jobs: List[Job] = []
        self._next = 0                  # the round-robin cursor into jobs
        self.rounds = 0                 # public: interleave observability
        self.fence_wait = 0.0           # this turn's fences, in seconds
        # which payload leaves are per-slot state, by their place in the
        # payload; the rest are keys and values
        self._state_at, self._pool_at = list(state_at), list(pool_at)
        self._has_state = bool(self._state_at)
        # a model with state leaves, or one that counts (``STATS``), is
        # told which positions of a program are real (``valid_len``)
        self._tells_real = tells_real
        # a model whose read of a request follows the length it was admitted
        # with is told it in every prefill program (``prompt_len``)
        self._tells_prompt_len = getattr(type(model), "TOLD_PROMPT_LEN",
                                         False)
        # the batch-1 state rows finished (or abandoned) prefill jobs no
        # longer need: the next job's first program starts from them
        self.spare_state: List[list] = []
        self._build_programs()

    # -- the buffer: layout, host writer, traced reader ----------------------

    @property
    def layout(self) -> tuple:
        """Where a job's buffer (``Job.inputs``) keeps what: the offsets of
        its plan rows, its page table and its prompt, and its length. A
        plan has at most ``max_seq_len / chunk`` chunks; the prompt's part
        runs a chunk past ``max_seq_len`` so that a chunk cut anywhere
        inside the prompt never runs off the end."""
        chunk = max(1, self.chunk)
        table = 1 + _CTL_LEN * -(-self._cfg.max_seq_len // chunk)
        prompt = table + self._pages_per_seq
        return 1, table, prompt, prompt + self._cfg.max_seq_len + chunk

    def _write(self, job: Job):
        """The job's buffer, written once: the cursor at 0, a ``_CTL_*``
        row a chunk of the plan, the page table, the prompt. The host array
        as it is, so that it rides in the dispatch of the job's first
        program; a gang commits it replicated by a call of its own (unplaced
        it would reach that program as another program than the one its
        later rounds compile)."""
        prompt = job.req.prompt
        plan_at, table_at, prompt_at, length = self.layout
        buf = np.zeros((1, length), np.int32)
        greedy = self._row_greedy(job.req)
        rows = [(job.matched + start, take, greedy,
                 n == 0 and self._has_state,
                 len(prompt))                      # in the order of _CTL_*
                for n, (start, take, _) in enumerate(job.plan)]
        buf[0, plan_at:plan_at + _CTL_LEN * len(rows)] = \
            np.asarray(rows, np.int32).ravel()
        buf[0, table_at:table_at + len(job.table)] = job.table
        buf[0, prompt_at:prompt_at + len(prompt)] = prompt
        if self._build.replicated is None:
            return buf
        PREFILL_CALLS.inc()
        return jax.device_put(buf, self._build.replicated)

    def _read_row(self, buf):
        """(traced) The ``_CTL_*`` row the buffer's cursor points at, the
        page table and the prompt's part."""
        plan_at, table_at, prompt_at, _ = self.layout
        ctl = jax.lax.dynamic_slice_in_dim(
            buf[0], plan_at + _CTL_LEN * buf[0, 0], _CTL_LEN)
        return ctl, buf[:, table_at:prompt_at], buf[:, prompt_at:]

    @staticmethod
    def _read_chunk(prompt, start, take, width: int):
        """(traced) ``width`` ids of the prompt from ``start``, positions
        at or past ``take`` set to the pad id 0 (the buffer holds 0 there
        already; the program does not lean on it), so a padded tail sees
        exactly what a padded upload held."""
        chunk = jax.lax.dynamic_slice_in_dim(prompt, start, width, axis=1)
        return jnp.where(jnp.arange(width, dtype=jnp.int32) < take, chunk, 0)

    # -- the programs --------------------------------------------------------

    def _build_programs(self) -> None:
        build = self._build
        # the device trace knows the program by the function's name
        self.step = build.jit(self.prefill_step, donate=(0, 1, 2),
                              static_argnames=("width",))
        # the rng's one split a finished prompt: (what the stream goes on
        # from, what the first token's draw spends), as one program; a
        # gang's halves leave replicated, as its decode program was warmed
        # to take the rng
        self.split_rng = jax.jit(
            lambda rng: tuple(jax.random.split(rng)),
            **({} if build.replicated is None else
               {"out_shardings": (build.replicated, build.replicated)}))
        if self._has_state:
            def splice_state(rows, job_rows, slot):
                """Each state leaf's row ``slot`` becomes the job's
                batch-1 row, in place."""
                return [jax.lax.dynamic_update_slice_in_dim(
                    big, small, slot, axis=0)
                    for big, small in zip(rows, job_rows)]

            self.splice = build.jit(splice_state, donate=(0,))

    def prefill_step(self, pool, state, job, params, key, window_table=None,
                     *, width):
        """(traced: ``step`` is its jit) One batch-1 chunk of ``width``
        positions against the shared pool, everything a round needs
        computed here from the job's buffer, as ``decode_step`` computes
        its own from ``pos``. ``window_table``: a model with window leaves
        only (the job's row of the second kind of page, as this chunk
        needs it).

        - The chunk's ``_CTL_*`` row is the one the buffer's cursor
          points at; the cursor moves on in the buffer handed back.
        - The cache tree: the pool's paged leaves as they are, ONE index
          value ``[start]`` placed at every index leaf, a state leaf the
          JOB's own batch-1 row (a state row belongs to one slot, and the
          decode rounds interleaved with this prefill see that slot as
          idle), zeroed on the job's first program.
        - The chunk: ``_read_chunk``.
        - The first token: picked from the logits of the last real
          position with the engine's sampling parameters, the row's
          greedy override and ``key``, which on the chunk that finishes a
          prompt is the spent half of the rng's split; on the others the
          pick is computed and dropped, the price of one program a width.

        Returns the pool leaves, the job's state rows, its buffer (on the
        device from here on) and the ``[1]`` token."""
        second = {} if window_table is None \
            else {"window_table": window_table}
        with trace.part(trace.EMBED):
            ctl, page_table, prompt = self._read_row(job)
            start, take = ctl[_CTL_START], ctl[_CTL_TAKE]
        index = jnp.reshape(start, (1,))
        leaves, paged, rows = [], iter(pool), iter(state)
        for kind in self._leaf_kinds:
            if kind == serving.INDEX:
                leaves.append(index)
            elif kind == serving.STATE:
                row = next(rows)
                with trace.part(trace.STATE):
                    leaves.append(jnp.where(ctl[_CTL_FRESH] != 0,
                                            jnp.zeros_like(row), row))
            else:
                leaves.append(next(paged))
        cache = jax.tree_util.tree_unflatten(self._treedef, leaves)
        with trace.part(trace.EMBED):
            tokens = self._read_chunk(prompt, start, take, width)
        real = {"valid_len": jnp.reshape(take, (1,))} \
            if self._tells_real else {}
        if self._tells_prompt_len:
            real["prompt_len"] = jnp.reshape(ctl[_CTL_PROMPT], (1,))
        logits, updated = self._model.apply(
            {"params": params, "cache": cache}, tokens,
            page_table=page_table, mutable=["cache"], **real, **second,
            **self._build.apply_kw)
        with trace.part(trace.SAMPLE):
            last = jax.lax.dynamic_index_in_dim(
                logits, take - 1, axis=1, keepdims=False)
            first = self._pick_first(last, ctl[_CTL_GREEDY] != 0, key)
        out = jax.tree_util.tree_leaves(updated["cache"])
        return ([leaf for leaf, kind in zip(out, self._leaf_kinds)
                 if kind in serving.POOLS],
                [leaf for leaf, kind in zip(out, self._leaf_kinds)
                 if kind == serving.STATE],
                job.at[0, 0].add(1), first)

    def _pick_first(self, logits, row_greedy, key):
        """First-token pick after prefill, inside ``prefill_step``, over
        the one row of a prefill program. ``key`` is the spent half of the
        rng's one split a finished prompt (the same discipline as the
        decode step's pick; the split itself is ``split_rng``, outside
        the program); ``row_greedy`` is the request's own sampling mode, a
        traced flag."""
        tok = draw_token(logits, self._temperature, key,
                         top_k=self._top_k, top_p=self._top_p)
        if self._temperature > 0.0:
            tok = jnp.where(
                row_greedy, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                tok)
        return tok

    def compile_splice(self, payload) -> None:
        """``warmup()``'s: the splice of a finished prefill's state rows,
        one program for every slot, compiled from the payload's avals."""
        rows = [payload[i] for i in self._state_at]
        self.splice.lower(
            rows, [jax.ShapeDtypeStruct((1,) + r.shape[1:], r.dtype)
                   for r in rows],
            jax.ShapeDtypeStruct((), jnp.int32)).compile()

    # -- the life of a job ---------------------------------------------------

    def slots(self) -> set:
        """The slots staged jobs hold reserved."""
        return {job.slot for job in self.jobs}

    @property
    def full(self) -> bool:
        """True while ``max_jobs`` prompts are staged."""
        return self.max_jobs is not None and len(self.jobs) >= self.max_jobs

    def stage(self, slot: int, req: Request) -> Job:
        """Everything a prompt holds before its first program, or nothing:
        a failure gives back what was taken and raises (request-scoped:
        nothing shared was touched)."""
        prompt = req.prompt
        t0 = len(prompt)
        # tier promotion FIRST: chains that aged out of HBM (or arrived
        # via the shared storage tier) re-enter the radix tree here, so
        # the match below hits them like any locally-cached prefix — and
        # counts them in prefill_tokens_saved, which is the honest
        # accounting (the prefill really is skipped)
        self._kv_io.promote(prompt[:-1])
        # longest cached whole-block prefix; capped at prompt[:-1] so at
        # least one real token remains to forward (logits for the first
        # generated token must come from an actual prefill position)
        table, matched = self.kv.match(prompt[:-1])
        table, row = list(table), None
        try:
            # provenance: if any matched block arrived via a KV import, the
            # prefill pool that produced it really served this prefix — the
            # disagg gateway reports it as `prefilled_by` (used, not staged)
            req.kv_prefilled_by = (
                self.kv.chain_origin(prompt[:matched]) if matched else None)
            plan = prefill_plan(t0 - matched, self.chunk,
                                self._cfg.max_seq_len - matched)
            # blocks for the REAL prompt positions only: a padded final
            # chunk's pad positions (>= t0) fall past the table's allocated
            # prefix, map to the scratch block, and are masked garbage by
            # construction — allocating coverage for them would waste up to
            # bucket_width/page blocks per short request
            evicted = self.kv.evictions
            need = blocks_for(t0, self._page) if self._pooled else 0
            table += self.kv.allocate(need - len(table))
            if trace.ON:
                trace.note(evicted=self.kv.evictions - evicted)
            if self._win is not None:
                # the job's row of window pages: what it will hold at its
                # most is set aside now, taken and shed chunk by chunk
                row = self._win.row()
                self._win.reserve(row, t0)
        except Exception:
            self.kv.release(table)   # the match's refs, and what was taken
            raise
        # NOTE: the slot's row of the engine's tables stays scratch until
        # the job completes — decode rounds interleaved with this prefill
        # must see the reserved slot as idle (its garbage writes land on
        # block 0), never on the job's half-written real blocks
        if self._has_state:
            # a reused slot starts from zero state: the job's first
            # program zeroes the rows it is handed
            _STATE_RESETS.inc()
        job = Job(req=req, slot=slot, plan=plan, matched=matched,
                  table=table, window=row)
        self.jobs.append(job)
        return job

    def _drop(self, job: Job) -> None:
        idx = self.jobs.index(job)
        del self.jobs[idx]
        if self._next > idx:
            self._next -= 1

    def _leave_state(self, job: Job) -> None:
        """A finished or abandoned job's state rows stay for the next job
        to start from; no more than two sets are kept (a set is as large
        as a slot's state), so a burst of prompts does not hold the
        memory of its widest moment."""
        if job.state is not None and len(self.spare_state) < 2:
            self.spare_state.append(job.state)
        job.state = None

    def abort(self, job: Job) -> None:
        """Release a job's staged resources without finishing its
        request (the caller decides the terminal status)."""
        self._drop(job)
        # drop the staged refs: matched prefix blocks fall back to
        # cached, freshly-owned ones return to the free list (their
        # half-written K/V is dead weight a future holder overwrites
        # during its own prefill, same as any freed slot's blocks)
        self.kv.release(job.table)
        job.table = []
        if job.window is not None:
            self._win.release(job.window)
        self._leave_state(job)

    def reap(self, jobs: Optional[List[Job]] = None) -> bool:
        """A mid-prefill abandon releases everything staged (the job's
        blocks go back to the pool) and ends as ``cancelled``; True if
        any of ``jobs`` (all of them, by default) went."""
        gone = [job for job in (self.jobs if jobs is None else jobs)
                if job.req.reapable]
        for job in gone:
            self.abort(job)
            self._cancelled(job.req)
        return bool(gone)

    def close(self) -> None:
        """Staged prefills release their resources (blocks back to the
        pool); their requests are the caller's to fail."""
        for job in list(self.jobs):
            self.abort(job)

    def advance(self) -> bool:
        """Advance ONE pending job by at most ``budget`` prompt tokens (all
        of them when the budget is None), rotating round-robin across jobs
        so a short prompt staged behind a long one still reaches its first
        token in O(1) rounds. False when no job is staged."""
        self.fence_wait = 0.0
        if not self.jobs:
            return False
        if self._next >= len(self.jobs):
            self._next = 0
        job = self.jobs[self._next]
        req = job.req
        if self.reap([job]):
            return True
        chunks0, tokens0 = job.next_chunk, job.done
        try:
            finished = self._round(job)
        except self._fatal:
            raise            # engine-fatal: the shared pool was donated
        except Exception as e:  # noqa: BLE001 — request-scoped
            # past the device section: the job's blocks and window row may
            # be the slot's by now, so they are dropped with it, not freed
            self._drop(job)
            self._failed(req, e, "prefill")
            return True
        self.rounds += 1
        _PREFILL_ROUNDS.inc()
        if trace.ON:
            trace.note(request=req.id, chunks=job.next_chunk - chunks0,
                       start=job.matched + tokens0,
                       tokens=job.done - tokens0, finished=finished,
                       width=sum(w for _, _, w
                                 in job.plan[chunks0:job.next_chunk]))
        if finished:
            self._drop(job)
        else:
            self._next += 1
        return True

    def _round(self, job: Job) -> bool:
        """One budgeted round of a prefill; True when the job finished
        (the scheduler was told, and activated its slot). A round is ONE
        device call a chunk: one program a chunk of ``job.plan`` until the
        plan ends or the budget is spent (a budget of one bucket's width
        is one program a round). The jitted ``prefill_step``
        takes the pool's leaves, advances them by the chunk and hands them
        back, so decode steps between rounds run against a fully consistent
        tree (the job's slot reads as idle: index 0, scratch page table).
        Resuming at ``matched + done`` reproduces the one-shot index
        exactly (interior chunks are unpadded), so chunking never changes
        the device math — only its interleaving. The round that finishes a
        prompt splices, on a model with state leaves, the job's rows into
        the slot's, and hands the scheduler the first token as the program
        left it, on the device: nothing here waits for the prompt's
        programs (``fence`` does, when the scheduler asks)."""
        # everything device-side below donates the SHARED pool: a failure
        # here poisons every request, not just this one
        try:
            # the engine's chaos boundary: an injected error here is
            # exactly a device call dying mid-prefill — engine-fatal by
            # construction
            self._enter()
            if job.inputs is None:
                job.inputs = self._write(job)
            spent, first = 0, None
            while job.next_chunk < len(job.plan):
                _, take, width = job.plan[job.next_chunk]
                first = self._run_program(job, take, width)
                job.next_chunk += 1
                job.done += take
                spent += take
                if self.budget is not None and spent >= self.budget \
                        and job.next_chunk < len(job.plan):
                    return False
            if self._has_state:
                self._splice(job)
        except Exception as e:  # noqa: BLE001 — see ``fatal``
            raise self._fatal(
                f"paged prefill died mid-flight for {job.req.id}: "
                f"{type(e).__name__}: {e}") from e
        self._finished(job, first)
        return True

    def _run_program(self, job: Job, take: int, width: int):
        """ONE device call: ``prefill_step`` over the next ``width``
        positions of the job's prompt, ``take`` of them real. Where the
        chunk starts and the rest of what the program is told stand in the
        job's buffer, which rides in the dispatch of the job's first
        program and stays on the device, so nothing is uploaded, sliced,
        padded or picked by a call of its own. Everything the program is
        handed but the parameters and the key is donated and comes back:
        the pool leaves, the job's buffer, a state model's job rows (a
        job's first program zeroes what it is given).

        The chunk that finishes a prompt is preceded by the rng's one
        split a finished prompt (``split_rng``, compiled once), whose
        spent half the program draws the first token from; the other
        chunks are handed the rng as it is and their pick is dropped. The
        split is not inside the program because a Threefry split is a
        third of what a width costs to lower, at every width, whatever
        the compile cache holds (PERF.md section 6, PR 38)."""
        # one program dispatch per CHUNK (a budgeted round may run
        # several) — the dispatch counter must agree with the
        # decode/verify paths' one-inc-per-program rule
        self._count_dispatch(width)
        _PREFILL_PROGRAMS.inc()
        _PREFILL_TOKENS.inc(take)
        _PREFILL_POSITIONS.inc(width)
        PREFILL_CALLS.inc()
        payload = self._payload()
        if self._has_state and job.state is None:
            job.state = self.spare_state.pop() if self.spare_state \
                else self._new_state_rows(payload)
        key = self._rng()
        finishing = job.next_chunk == len(job.plan) - 1
        if finishing:
            PREFILL_CALLS.inc()
            with self._first("split_rng", trace.SITE_AUX, phase="prefill"):
                rng, key = self.split_rng(key)
            self._set_rng(rng)
        tables = ()
        if job.window is not None:
            # the chunk's own pages are taken and the pages wholly behind
            # its first query's window go back, before the dispatch; the
            # table rides in it (a copy: the row's array changes under the
            # next chunk)
            start = job.matched + job.plan[job.next_chunk][0]
            self._win.cover(job.window, start - self._win.window,
                            start + take)
            tables = (job.window.table[None].copy(),)
        # the first program of a width is traced, lowered and compiled (or
        # read from the cache) here, while every resident row waits
        with self._first(width, trace.SITE_PREFILL, phase="prefill",
                         width=width):
            pool, state, job.inputs, first = self.step(
                [payload[i] for i in self._pool_at], job.state or [],
                job.inputs, self._params, key, *tables, width=width)
        for i, leaf in zip(self._pool_at, pool):
            payload[i] = leaf
        if self._has_state:
            job.state = state
        if finishing:
            # the finished prompt's first token is asked for now, in front
            # of whatever is queued next: ``fence`` then waits for this
            # program, not for a copy behind a later one
            first.copy_to_host_async()
        return first

    def _new_state_rows(self, payload) -> list:
        """Batch-1 rows of every state leaf, for a prefill job when no
        finished job has left its own behind."""
        PREFILL_CALLS.inc(len(self._state_at))
        with self._first("state_rows", trace.SITE_AUX, phase="prefill"):
            return [jnp.zeros((1,) + payload[i].shape[1:], payload[i].dtype)
                    for i in self._state_at]

    def _splice(self, job: Job) -> None:
        """A finished prompt's state: the job's batch-1 rows are spliced
        into the slot's rows of the decode tree (one device call), and
        left for the next job to start from. Index state needs no splice
        — the scheduler's host mirror of positions (set when the slot is
        activated; 0 while the job is mid-flight) is the single source of
        truth for positions."""
        payload = self._payload()
        with trace.span(trace.ENGINE_PREFILL_STATE), \
                self._first("splice", trace.SITE_SPLICE, phase="prefill"):
            PREFILL_CALLS.inc()
            rows = self.splice([payload[i] for i in self._state_at],
                               job.state, np.int32(job.slot))
        for i, row in zip(self._state_at, rows):
            payload[i] = row
        self._leave_state(job)

    def fence(self, first) -> int:
        """The prefill's one blocking transfer: the first token of a
        finished prompt (``finished``'s ``first``, whose copy to the host
        the finishing program's dispatch asked for), and with it the wait
        for whichever of the prompt's programs the device has not run yet.
        Called by the scheduler when it wants the token: behind the next
        decode round's dispatch, so that the device has that round to run
        while the loop waits here, or at once where the turn cannot go on
        without it. Timed apart from the ``prefill`` phase (``fence_wait``,
        summed over a turn): here the loop waits for the device, not the
        device for the loop."""
        t0 = self._clock.now()
        with trace.span(trace.ENGINE_PREFILL_FENCE):
            token = int(np.asarray(first)[0])
        self.fence_wait += self._clock.now() - t0
        return token

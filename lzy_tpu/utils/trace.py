"""Tracing: one span recorder for the serving path, the names of the device's
work, and the JAX profiler.

Two questions, one module. "Where did the step time go on the chip" is
answered by the XLA profiler; "what was the host doing meanwhile, and where
did a request wait" by spans the program records itself. Both are switched
on the same way, so a profile carries the spans with no new switch.

**The recorder.** :func:`span` (a context manager), :func:`event` (an
instant), :func:`emit` (a span written afterwards from stamps the program
already holds) and :func:`note` (attributes for the thread's open span).
Off, which is the default, each is one test of the module's ``ON`` and a
return: ``span()`` hands back one shared no-op object (falsy, so
``if sp:`` guards work done only for the record). On, a span records ``(name, start, end,
thread, id, parent, request, attrs)`` on ``time.monotonic()`` (the clock of
``SYSTEM_CLOCK.now()`` and of ``Request``'s stamps) into a bounded buffer
in memory; what the bound dropped is counted, and nothing is written until
the holder drains it. The parent is the thread's open span; across a
thread hop it is carried: :func:`context` on one side, ``parent=`` on the
other. Spans of one request share ``request``, the id of their root.

- :func:`recording` turns the recorder on for a block and yields it;
- :func:`profiled` captures a profiler trace around a block, turns the
  recorder on too (so does ``LZY_PROFILE=1`` on an op, below), and on the
  way out drains it into ``<logdir>/spans.jsonl``: a header line (the
  clock, the count, what the bound dropped), then one record a line. The
  file is uploaded with the trace's other artifacts.

**One clock with the device trace.** The engine loop's spans
(``LOOP_SPANS``) and ``program.build`` also open a
``jax.profiler.TraceAnnotation`` of the same name, so they land in the
trace's ``/host:CPU`` plane on the profiler's clock, where a reader names
the device's idle gaps by them. Names are
constants of ``[a-z0-9_.]``, at most 40 characters: ids and sizes are
attributes. Request-scoped spans are never annotations (they last seconds
and would cover every gap). When the recorder starts, and at the first
``engine.round`` of every second, an instant annotation
``lzy.clock.<time.monotonic_ns()>`` is emitted: its start on the
profiler's clock less the number in its name is what to add to a record's
stamp (``spans.jsonl``) to place it in the trace. Each anchor is also an
event ``lzy.clock`` among the records (``monotonic_ns``: the same number),
so a reader can tell a trace that holds no anchor from anchors it did not
find.

**A program's build.** :func:`building` (beside :func:`span`) is opened
where the program knows that JAX is about to trace, lower and compile for
it: ``with building(ENGINE_PREFILL, width=256) as b``. It names the *site*
of every build JAX reports on that thread while it is open
(``utils/jaxenv.py`` listens to ``jax.monitoring``; a build under no
context is ``other``), which is all it does while the recorder is off: the
counters ``lzy_program_build_seconds{site,stage}`` and
``lzy_program_builds_total{site,cache}`` are always on. With the recorder
on it is also the span ``program.build`` (an annotation too, so a device
gap during a build has a name), whose attributes are the same seconds the
counters took; a context in which JAX built nothing leaves no record.

**The device's side: parts.** A span names what the host was doing; what
the *device* was doing is named by :func:`part`: ``with part(PROJ):`` (or
``@part(STATE)`` on an op's public entry point) puts every operation traced
inside under ``part.<name>`` in its ``op_name``. The names are the constants
of ``PARTS`` below, a closed set beside the span names (``[a-z0-9_]``, no
ids, no sizes): ``embed``, ``norm``, ``proj``, ``attn_read``,
``cache_write``, ``ffn``, ``router``, ``experts``, ``state``, ``mix``,
``head``, ``sample``, ``loss``, ``optimizer`` and seven model-specific ones.
Every served program, the engine's step functions and the train step carry
them (``tests/test_zz_parts.py`` holds each product, custom call, gather,
scatter, sort and loop to exactly one). A device trace's ``XLA Ops`` events
hold the ``op_name`` as ``tf_op``, so XProf's trace viewer and op profile
group by them, and ``benchmark/readers/part_share.py`` and
``tools/part_table.py`` file a program's device time under them. Always on:
a scope is metadata of the compiled program (the persistent compile cache
keys a program without it) and costs the device nothing.

**The profiler.**

- :func:`profiled` — capture a trace around any code region, optionally
  uploading the TensorBoard-ready artifacts to workflow storage, so traces
  from remote workers land next to the run's logs;
- :func:`annotate_step` — mark train-loop steps so the trace viewer groups
  device work per step;
- worker integration: set ``LZY_PROFILE=1`` on an op's env
  (``op.with_env_vars({"LZY_PROFILE": "1"})``) and the worker wraps the op
  body in a trace whose artifacts are uploaded under the execution's
  ``traces/`` prefix — retrieve with any storage client and open in
  TensorBoard/XProf.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import tempfile
import threading
import time
from typing import Any, Iterator, List, NamedTuple, Optional, Tuple

from lzy_tpu.utils.log import get_logger

_LOG = get_logger(__name__)

PROFILE_ENV = "LZY_PROFILE"


# -- span names: constants, never built from ids or sizes ---------------------

ENGINE_ROUND = "engine.round"
ENGINE_KV_IO = "engine.kv_io"
ENGINE_REAP = "engine.reap"
ENGINE_ADMIT = "engine.admit"
ENGINE_PREFILL = "engine.prefill"
ENGINE_PREFILL_FENCE = "engine.prefill.fence"
# a child of engine.prefill, not a phase of its own (no phase label, no
# profiler annotation): the splice of a finished prefill's per-slot state
# rows into the decode tree (models with state cache leaves)
ENGINE_PREFILL_STATE = "engine.prefill.state"
ENGINE_DECODE_PLAN = "engine.decode.plan"
ENGINE_DECODE_DISPATCH = "engine.decode.dispatch"
ENGINE_DECODE_OVERLAP = "engine.decode.overlap"
ENGINE_DECODE_FENCE = "engine.decode.fence"
ENGINE_DECODE_EMIT = "engine.decode.emit"
ENGINE_PARK = "engine.park"
# a build of a program (trace, lowering, compile or cache read), wherever
# it happens: in warm-up, or on the loop thread under ``engine.prefill``
PROGRAM_BUILD = "program.build"
#: the engine loop's spans: with ``program.build`` the only ones that are
#: profiler annotations too
LOOP_SPANS = frozenset({
    ENGINE_ROUND, ENGINE_KV_IO, ENGINE_REAP, ENGINE_ADMIT, ENGINE_PREFILL,
    ENGINE_PREFILL_FENCE, ENGINE_DECODE_PLAN, ENGINE_DECODE_DISPATCH, ENGINE_DECODE_OVERLAP,
    ENGINE_DECODE_FENCE, ENGINE_DECODE_EMIT, ENGINE_PARK})
_ANNOTATED = LOOP_SPANS | {PROGRAM_BUILD}
# set-up's two parents: the engine's constructor and ``warmup()``
ENGINE_INIT = "engine.init"
ENGINE_WARMUP = "engine.warmup"
ENGINE_PREEMPT = "engine.preempt"           # event
KV_EVICT = "kv.evict"                       # event
ENGINE_REQUEST = "engine.request"
ENGINE_REQUEST_QUEUED = "engine.request.queued"
ENGINE_REQUEST_PREFILL = "engine.request.prefill"
ENGINE_REQUEST_DECODE = "engine.request.decode"
GATEWAY_GENERATE = "gateway.generate"
GATEWAY_ADMIT = "gateway.admit"
GATEWAY_ROUTE = "gateway.route"
GATEWAY_ATTEMPT = "gateway.attempt"
LLM_BATCH = "llm.batch"
LLM_ROW = "llm.row"
LLM_ROW_POOL_WAIT = "llm.row.pool_wait"
LLM_DISPATCH = "llm.dispatch"

# -- build sites: the ``site`` label of the build counters --------------------

SITE_DECODE = "engine.decode"
SITE_VERIFY = "engine.verify"
SITE_SPLICE = "engine.splice"
SITE_PREFILL = "engine.prefill"
# the engine's small programs: the constructor's allocations, the rng's
# split, a state model's row maker, the round inputs' first uploads
SITE_AUX = "engine.aux"
SITE_TRAIN_STEP = "train.step"
# no context open: eager stragglers, and whatever the library's caller
# compiles for itself
SITE_OTHER = "other"

# -- device parts: what :func:`part` may name a program's work ---------------
# the device-side counterpart of the span names above: constants of
# ``[a-z0-9_]``, no ids, no sizes. A closed set: a trace reader, XProf's op
# profile and PERF.md's tables file a step's device time under these

EMBED = "embed"
NORM = "norm"
# the products around a mixer: q/k/v/o, a latent's down- and up-projections
# with their relayouts, rotary embeddings, head norms, gates
PROJ = "proj"
ATTN_READ = "attn_read"           # a read over a cache of any kind
CACHE_WRITE = "cache_write"
FFN = "ffn"                       # a dense feed-forward: gate, up and down
ROUTER = "router"
EXPERTS = "experts"               # routed and shared experts' products
# a recurrent mixer's scan or update: Mamba, KDA, lightning, retention, CCA
STATE = "state"
MIX = "mix"                       # residual-stream mixing (mHC)
HEAD = "head"                     # final norm and logits
SAMPLE = "sample"
LOSS = "loss"
OPTIMIZER = "optimizer"
LATENT_INDEX = "latent_index"
LATENT_CHOICE = "latent_choice"
LATENT_GATHER = "latent_gather"
LATENT_CHOSEN_READ = "latent_chosen_read"
LATENT_WINDOW_READ = "latent_window_read"
DIFF_EPILOGUE = "diff_epilogue"   # Motif: the difference, W_vb, the gate
LOOP_EXIT = "loop_exit"           # Ouro's exit gate
PARTS = frozenset({
    EMBED, NORM, PROJ, ATTN_READ, CACHE_WRITE, FFN, ROUTER, EXPERTS, STATE,
    MIX, HEAD, SAMPLE, LOSS, OPTIMIZER, LATENT_INDEX, LATENT_CHOICE,
    LATENT_GATHER, LATENT_CHOSEN_READ, LATENT_WINDOW_READ, DIFF_EPILOGUE,
    LOOP_EXIT})
#: how a part is spelt inside an operation's ``op_name``: ``part.<name>``
PART_PREFIX = "part."

CLOCK = "lzy.clock"                         # event: one for each anchor
CLOCK_ANCHOR = CLOCK + "."
#: what ``profiled()`` leaves beside the trace: the recorder's records
SPANS_FILE = "spans.jsonl"

#: the recorder's one switch: read it as ``trace.ON``, never import it by
#: name (``recording()`` rebinds it)
ON = False

DEFAULT_MAXLEN = 1 << 17


class Record(NamedTuple):
    """One closed span (``end > start``) or one event (``end == start``).
    ``parent`` is a span id or None; ``request`` the id of the root of the
    tree it belongs to (None on the engine loop's spans)."""
    name: str
    start: float
    end: float
    thread: str
    id: int
    parent: Optional[int]
    request: Optional[int]
    attrs: dict


class Recorder:
    """The bounded buffer. ``drain()`` hands out what is held and empties
    it; ``dropped`` counts what the bound has pushed out."""

    def __init__(self, maxlen: int = DEFAULT_MAXLEN):
        self._buf: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.dropped = 0

    def _add(self, record: Record) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(record)

    def drain(self) -> List[Record]:
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
        return out


class _Noop:
    """What ``span()`` returns while the recorder is off: one object for
    every call, falsy, inert."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


NOOP = _Noop()
#: the recorder's clock, for stamps handed to ``span(start=)`` and ``emit``
now = time.monotonic
_ids = itertools.count(1)
_tls = threading.local()
_recorder: Optional[Recorder] = None
_holders = 0
_switch = threading.Lock()
_next_anchor = 0.0


class _Span:
    __slots__ = ("name", "attrs", "start", "id", "parent", "request",
                 "_outer", "_annotation", "_recorder")

    def __init__(self, name: str, parent, start, attrs: dict):
        self.name, self.attrs, self.start = name, attrs, start
        self.parent, self.request = parent if parent else (None, None)
        self._annotation = None

    def __enter__(self):
        self._recorder = _recorder
        self.id = next(_ids)
        self._outer = getattr(_tls, "open", None)
        if self.parent is None and self._outer is not None:
            self.parent, self.request = self._outer.id, self._outer.request
        loop = self.name in _ANNOTATED
        if self.request is None and not loop:
            self.request = self.id            # the root of a request's tree
        _tls.open = self
        if self.start is None:
            self.start = time.monotonic()
        if loop:
            if self.name is ENGINE_ROUND and self.start >= _next_anchor:
                anchor()
            self._annotation = _annotation(self.name)
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        end = time.monotonic()
        _tls.open = self._outer
        rec = self._recorder
        if rec is not None:
            rec._add(Record(self.name, self.start, end,
                            threading.current_thread().name, self.id,
                            self.parent, self.request, self.attrs))
        return False

    def drop(self) -> None:
        """Close without a record (a build context in which nothing was
        built)."""
        self._recorder = None
        self.__exit__()

    def __bool__(self):
        return True


class Build:
    """What :func:`building` yields: the site of the thread's builds while
    it is open, and what JAX built meanwhile, by stage. ``built`` says
    whether anything was; ``seconds`` is trace + lower + compile."""
    __slots__ = ("site", "attrs", "trace_s", "lower_s", "compile_s",
                 "cache_read_s", "compile_requests", "hits", "misses",
                 "_outer", "_span")

    def __init__(self, site: str, attrs: dict):
        self.site, self.attrs = site, attrs
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.cache_read_s = 0.0
        self.compile_requests = self.hits = self.misses = 0
        self._span = None

    def __enter__(self):
        from lzy_tpu.utils import jaxenv

        jaxenv.install_build_meter()      # once a process
        self._outer = getattr(_tls, "build", None)
        _tls.build = self
        if ON:
            self._span = _Span(PROGRAM_BUILD, None, None, self.attrs)
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        _tls.build = self._outer
        sp = self._span
        if sp is not None:
            if self.built:
                sp.attrs.update(
                    site=self.site, trace_s=self.trace_s,
                    lower_s=self.lower_s, compile_s=self.compile_s,
                    cache_read_s=self.cache_read_s, cache=self.cache,
                    compile_requests=self.compile_requests)
                sp.__exit__()
            else:
                sp.drop()
        return False

    def add(self, stage: str, seconds: float) -> None:
        """The listener's: ``seconds`` of ``trace``, ``lower``, ``compile``
        or ``cache_read`` reported inside this context."""
        setattr(self, stage + "_s", getattr(self, stage + "_s") + seconds)

    def add_request(self, cache: str) -> None:
        """The listener's: one backend compile request, and what the
        persistent cache did with it (``hit``, ``miss``, ``off``)."""
        self.compile_requests += 1
        self.hits += cache == "hit"
        self.misses += cache == "miss"

    @property
    def built(self) -> bool:
        # a compile request, a lowering, or a trace of some length (an
        # ``eval_shape`` of a model is one): JAX also reports a trace, of
        # some microseconds, where it finds the jaxpr it traced before
        return bool(self.compile_requests or self.lower_s
                    or self.trace_s >= 1e-3)

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s

    @property
    def cache(self) -> str:
        """``hit`` where every compile request that asked the persistent
        cache was answered by it, ``miss`` where one was not, ``off``
        where none asked."""
        if self.misses:
            return "miss"
        return "hit" if self.hits else "off"

    def describe(self) -> str:
        """``prefill width=256 (trace 0.47 s, lower 0.29 s, compile 1.2 s,
        cache hit)``: the words of the engine's slow-phase log line."""
        what = self.site.rpartition(".")[2]
        if "width" in self.attrs:
            what += f" width={self.attrs['width']}"
        return (f"{what} (trace {self.trace_s:.2f} s, lower "
                f"{self.lower_s:.2f} s, compile {self.compile_s:.2f} s, "
                f"cache {self.cache})")


def building(site: str, **attrs) -> Build:
    """``with building(SITE, width=...) as b:`` around code that may make
    JAX build a program. Always on (one thread-local write): the builds
    JAX reports on this thread inside the block are counted under
    ``site``, the innermost context's. With the recorder on the block is
    the span ``program.build`` with ``attrs`` and the stage seconds, if
    anything was built."""
    return Build(site, attrs)


def open_build() -> Optional[Build]:
    """The thread's innermost open build context (for the listener)."""
    return getattr(_tls, "build", None)



_annotation_cls = None


def _annotation(name: str):
    """An entered ``TraceAnnotation`` or None: tracing never fails the
    traced computation."""
    global _annotation_cls
    try:
        if _annotation_cls is None:
            import jax

            _annotation_cls = jax.profiler.TraceAnnotation
        a = _annotation_cls(name)
        a.__enter__()
        return a
    except Exception:  # noqa: BLE001 — observability is best-effort
        return None


def span(name: str, parent: Optional[Tuple[int, Any]] = None,
         start: Optional[float] = None, **attrs):
    """``with span(NAME) as sp:``: a span around the block, a child of the
    thread's open span (or of ``parent``, a :func:`context` carried from
    another thread). ``start`` backdates it to a stamp already taken."""
    if not ON:
        return NOOP
    return _Span(name, parent, start, attrs)


def part(name: str):
    """``with part(PROJ):`` (or ``@part(PROJ)`` on a function whose whole
    body is one part): the device-side counterpart of :func:`span`. Every
    operation traced inside carries ``part.<name>`` in its ``op_name`` (a
    ``jax.named_scope``), forward and under ``transpose(jvp(...))``, which is
    what a device trace's ``tf_op``, XProf's trace viewer and op profile, and
    ``benchmark/readers/part_share.py`` file its device time by. ``name`` is
    one of ``PARTS``; any other is refused here, before anything is traced.

    Always on: a scope is metadata of the compiled program and costs the
    device nothing, so there is no switch. **Parts do not nest**: inside an
    open part a second one names nothing (the outermost wins), so an op's
    public entry point can name itself and still be called from a block its
    caller has named, and an operation's ``op_name`` holds one ``part.``
    component. A fusion goes to the part XLA's ``op_name`` gives it: a norm's
    reduction fused into the product that follows is the product's."""
    if name not in PARTS:
        raise ValueError(f"no device part {name!r}; known: {sorted(PARTS)} "
                         f"(lzy_tpu/utils/trace.py)")
    return _part(name)


@contextlib.contextmanager
def _part(name: str) -> Iterator[None]:
    if getattr(_tls, "part", None) is not None:
        yield
        return
    import jax

    _tls.part = name
    try:
        with jax.named_scope(PART_PREFIX + name):
            yield
    finally:
        _tls.part = None


def _here() -> Tuple[Optional[int], Any]:
    outer = getattr(_tls, "open", None)
    return (outer.id, outer.request) if outer is not None else (None, None)


def emit(name: str, start: float, end: float,
         parent: Optional[Tuple[int, Any]] = None, **attrs):
    """A span written afterwards from two stamps of ``time.monotonic()``,
    under ``parent`` or the thread's open span. Returns its
    :func:`context` (for children written the same way)."""
    if not ON:
        return None
    rec = _recorder
    up, request = parent if parent is not None else _here()
    sid = next(_ids)
    if request is None:
        request = sid
    if rec is not None:
        rec._add(Record(name, start, end, threading.current_thread().name,
                        sid, up, request, attrs))
    return sid, request


def event(name: str, **attrs) -> None:
    """An instant, under the thread's open span."""
    if ON:
        now = time.monotonic()
        emit(name, now, now, **attrs)


def note(**attrs) -> None:
    """Attributes for the thread's open span (call under ``if trace.ON``
    on a hot path: the keyword dict is built before the test here)."""
    outer = getattr(_tls, "open", None) if ON else None
    if outer is not None:
        outer.attrs.update(attrs)


def context() -> Optional[Tuple[int, Any]]:
    """``(span id, request)`` of the thread's open span, to hand to
    another thread's ``span(..., parent=)``; None when off or outside."""
    here = _here() if ON else (None, None)
    return here if here[0] is not None else None


def anchor() -> None:
    """Ties the two clocks: an instant annotation whose name holds
    ``time.monotonic_ns()`` as read where the profiler stamps it, and an
    event ``lzy.clock`` with the same number (``monotonic_ns``), so the
    records list the anchors a reader has to find in the host plane."""
    global _next_anchor
    ns = time.monotonic_ns()
    _next_anchor = ns / 1e9 + 1.0
    a = _annotation(CLOCK_ANCHOR + str(ns))
    if a is not None:
        a.__exit__(None, None, None)
    emit(CLOCK, ns / 1e9, ns / 1e9, monotonic_ns=ns)


def name_thread(name: str) -> None:
    """Give the calling thread an OS-level name (Linux; 15 bytes are
    kept). A profile's host plane has one line a thread, named by this:
    Python's threads are all ``python3`` otherwise, and whoever opens the
    profile has to guess which of them is the engine's loop.
    Best-effort."""
    try:
        import ctypes

        prctl = ctypes.CDLL(None).prctl
        prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                          ctypes.c_ulong, ctypes.c_ulong]
        prctl.restype = ctypes.c_int
        prctl(15, name.encode()[:15], 0, 0, 0)       # PR_SET_NAME
    except (OSError, AttributeError):   # not Linux: a name is not worth
        pass                            # a failure


@contextlib.contextmanager
def recording(maxlen: int = DEFAULT_MAXLEN) -> Iterator[Recorder]:
    """Turn the recorder on for the block; yields it. Nested holders
    share one recorder, and the last one out turns it off."""
    global ON, _recorder, _holders
    with _switch:
        if _holders == 0:
            _recorder = Recorder(maxlen)
        _holders += 1
        rec = _recorder
        ON = True
    anchor()
    try:
        yield rec
    finally:
        with _switch:
            _holders -= 1
            if _holders == 0:
                ON = False
                _recorder = None


def profile_enabled(env_vars) -> bool:
    """True only for conventional truthy values — ``LZY_PROFILE=0``/"false"
    must DISABLE profiling, not enable it via string truthiness."""
    value = (env_vars or {}).get(PROFILE_ENV, "")
    return str(value).strip().lower() in ("1", "true", "yes", "on")


@contextlib.contextmanager
def profiled(logdir: Optional[str] = None, *,
             upload_prefix: Optional[str] = None,
             storage=None) -> Iterator[str]:
    """Capture a JAX/XLA profiler trace around the block.

    Yields the local trace directory; the span recorder is on inside the
    block (:func:`recording`) and drained into ``spans.jsonl`` there when
    the block ends. With ``upload_prefix`` + ``storage`` (a StorageClient),
    every produced artifact is uploaded under that prefix after capture —
    profiling must never fail the traced computation, so capture/upload
    errors are logged and swallowed.
    """
    import jax

    logdir = logdir or tempfile.mkdtemp(prefix="lzy_trace_")
    started = False
    try:
        jax.profiler.start_trace(logdir)
        started = True
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        _LOG.warning("profiler start failed: %r", e)
    try:
        # the program's spans ride along: the engine loop's as annotations
        # in this trace's host plane, all of them in spans.jsonl beside it
        with recording() as rec:
            yield logdir
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                _LOG.warning("profiler stop failed: %r", e)
        _write_spans(rec, logdir)
        if upload_prefix and storage is not None:
            _upload_dir(storage, logdir, upload_prefix)


def annotate_step(step: int, name: str = "train"):
    """Step marker for the trace viewer's per-step grouping:
    ``with annotate_step(i): state, _ = train_step(state, batch)``."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def _write_spans(rec: Recorder, logdir: str) -> None:
    """Drain ``rec`` into ``<logdir>/spans.jsonl``: one header object
    (``clock``, ``anchor``, ``records``, ``dropped``), then one object a
    record with :class:`Record`'s fields. Stamps are seconds of
    ``time.monotonic()``; an anchor annotation in the trace's host plane
    places them on the profiler's clock."""
    records = rec.drain()
    try:
        os.makedirs(logdir, exist_ok=True)
        with open(os.path.join(logdir, SPANS_FILE), "w") as f:
            f.write(json.dumps({
                "clock": "time.monotonic", "anchor": CLOCK_ANCHOR,
                "records": len(records), "dropped": rec.dropped}) + "\n")
            for r in records:
                f.write(json.dumps(r._asdict(), default=str) + "\n")
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        _LOG.warning("writing %s failed: %r", SPANS_FILE, e)


def _upload_dir(storage, local_dir: str, prefix: str) -> int:
    from lzy_tpu.storage.api import join_uri

    n = 0
    for root, _, files in os.walk(local_dir):
        for fname in files:
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, local_dir)
            try:
                with open(path, "rb") as f:
                    storage.write_bytes(join_uri(prefix, rel), f.read())
                n += 1
            except Exception as e:  # noqa: BLE001
                _LOG.warning("trace upload of %s failed: %r", rel, e)
    _LOG.info("uploaded %d trace artifacts to %s", n, prefix)
    return n

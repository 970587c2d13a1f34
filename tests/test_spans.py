"""The span recorder (``lzy_tpu/utils/trace.py``) and the spans the serving
path records with it: the engine loop's round and its phases, a request from
the gateway through the engine, the ``llm`` entry pool's rows."""

import json
import re
import threading
import time

import jax
import pytest

from lzy_tpu import llm
from lzy_tpu.gateway import GatewayService, PrefixAffinityRouter, ReplicaFleet
from lzy_tpu.llm.sched import WorkflowScheduler
from lzy_tpu.models import llama, unbox
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

PAGE = 8
OLD_PHASES = ("plan", "overlap", "fence", "emit")
NEW_PHASES = ("kv_io", "reap", "admit", "prefill", "prefill_fence",
              "dispatch", "park")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


@pytest.fixture
def gateway(tiny_model):
    cfg, params = tiny_model
    fleet = ReplicaFleet(lambda: PagedInferenceEngine(
        cfg, params, slots=2, page_size=PAGE))
    gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                        model_name="tiny")
    fleet.add_replica()
    yield gw
    llm.configure(None)
    gw.close()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _phase_sums():
    return {phase: float(v) for phase, v in re.findall(
        r'lzy_engine_round_phase_seconds_sum\{phase="(\w+)"\} (\S+)',
        REGISTRY.exposition())}


class TestRecorder:
    def test_off_records_nothing_and_span_is_the_shared_noop(self):
        assert trace.ON is False
        a, b = trace.span(trace.ENGINE_ADMIT), trace.span("anything", k=1)
        assert a is b is trace.NOOP and not a
        with a as sp:
            assert sp is a
        trace.event(trace.KV_EVICT, blocks=1)
        trace.note(ignored=1)
        assert trace.emit("x", 0.0, 1.0) is None
        assert trace.context() is None
        with trace.recording() as rec:
            # its own anchor, and nothing leaked in from before
            assert [r.name for r in rec.drain()] == [trace.CLOCK]

    def test_nesting_sets_parent_request_and_attrs(self):
        with trace.recording() as rec:
            with trace.span(trace.GATEWAY_GENERATE, tenant="t") as root:
                with trace.span(trace.GATEWAY_ATTEMPT) as child:
                    trace.note(replica="r1")
                    trace.event(trace.KV_EVICT, blocks=2)
                    leaf = trace.emit(trace.GATEWAY_ROUTE, 1.0, 2.0)
            with trace.span(trace.GATEWAY_GENERATE):
                pass
            recs = _by_name(rec.drain())
        first, second = recs[trace.GATEWAY_GENERATE]
        attempt, = recs[trace.GATEWAY_ATTEMPT]
        evict, = recs[trace.KV_EVICT]
        route, = recs[trace.GATEWAY_ROUTE]
        assert first.parent is None and first.request == first.id == root.id
        assert first.attrs == {"tenant": "t"}
        assert attempt.parent == first.id and attempt.request == first.id
        assert attempt.attrs == {"replica": "r1"} and attempt.id == child.id
        assert evict.parent == attempt.id and evict.start == evict.end
        assert (route.id, route.request) == leaf
        assert route.parent == attempt.id and (route.start, route.end) == (1, 2)
        assert second.request == second.id != first.id
        assert first.start <= attempt.start <= attempt.end <= first.end
        assert first.thread == threading.current_thread().name

    def test_buffer_is_bounded_and_counts_what_it_dropped(self):
        with trace.recording(maxlen=4) as rec:
            for i in range(10):
                trace.event(trace.KV_EVICT, i=i)
            assert rec.dropped == 7       # six events and the anchor
            kept = rec.drain()
            assert [r.attrs["i"] for r in kept] == [6, 7, 8, 9]
            assert rec.drain() == [] and rec.dropped == 7

    def test_nested_holders_share_one_recorder(self):
        with trace.recording() as outer:
            with trace.recording() as inner:
                assert inner is outer
            assert trace.ON is True
            trace.event(trace.KV_EVICT)
            # every holder that comes in anchors the clocks anew
            assert [r.name for r in outer.drain()] == [
                trace.CLOCK, trace.CLOCK, trace.KV_EVICT]
        assert trace.ON is False

    def test_threads_share_the_recorder_without_losing_a_record(self):
        """More threads than cores on a shortened switch interval: every
        span is kept, ids are unique, and a thread's nesting is its own."""
        import os
        import sys

        workers, each = 4 * (os.cpu_count() or 2), 200
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with trace.recording(maxlen=4 * workers * each) as rec:
                def work():
                    for _ in range(each):
                        with trace.span(trace.GATEWAY_GENERATE) as outer:
                            with trace.span(trace.GATEWAY_ATTEMPT) as inner:
                                assert trace.context() == (inner.id,
                                                           outer.id)

                threads = [threading.Thread(target=work)
                           for _ in range(workers)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
                assert not any(t.is_alive() for t in threads)
                recs = [r for r in rec.drain() if r.name != trace.CLOCK]
                assert rec.dropped == 0
        finally:
            sys.setswitchinterval(interval)
        assert len(recs) == 2 * workers * each
        assert len({r.id for r in recs}) == len(recs)
        by_id = {r.id: r for r in recs}
        for r in recs:
            if r.name == trace.GATEWAY_ATTEMPT:
                assert by_id[r.parent].thread == r.thread
                assert r.request == r.parent
            else:
                assert r.parent is None and r.request == r.id

    def test_profiled_leaves_the_records_beside_the_trace(self, tmp_path):
        """What ``profiled()`` does with the recorder on its way out: a
        header with the count and what the bound dropped, then a record a
        line, request-scoped spans included."""
        with trace.recording(maxlen=3) as rec:
            with trace.span(trace.GATEWAY_GENERATE, tenant="a") as root:
                trace.event(trace.ENGINE_PREEMPT, request="r1", blocks=2)
                for _ in range(2):
                    with trace.span(trace.GATEWAY_ATTEMPT, replica=object()):
                        pass
            trace._write_spans(rec, str(tmp_path / "t"))
            assert rec.drain() == []
        head, *lines = [json.loads(line) for line in
                        open(tmp_path / "t" / trace.SPANS_FILE)]
        assert head == {"clock": "time.monotonic", "records": 3,
                        "anchor": "lzy.clock.", "dropped": 2}
        assert [r["name"] for r in lines] == [
            trace.GATEWAY_ATTEMPT, trace.GATEWAY_ATTEMPT,
            trace.GATEWAY_GENERATE]
        assert set(lines[0]) == set(trace.Record._fields)
        assert lines[0]["parent"] == lines[2]["id"] == root.id
        assert lines[2]["attrs"] == {"tenant": "a"}
        assert isinstance(lines[0]["attrs"]["replica"], str)

    def test_loop_span_names_fit_a_gap_label(self):
        names = {v for k, v in vars(trace).items()
                 if k.isupper() and isinstance(v, str) and k != "PROFILE_ENV"}
        assert trace.LOOP_SPANS < names
        for name in names - {trace.CLOCK_ANCHOR}:
            assert re.fullmatch(r"[a-z0-9_.]{1,40}", name), name


class TestThreadHops:
    def test_parent_is_carried_across_the_workflow_pool(self):
        # a window of two, as a reply gives it, and four rows held on an
        # event: two of them wait for the window, and no clock is asked
        sched = WorkflowScheduler(backend=None)
        sched._follow({"plane_slots": 2, "plane_admits": 2})
        assert sched.stats()["row_window"] == 2
        seen, inside = [], threading.Semaphore(0)
        hold = threading.Event()

        def row(x):
            seen.append((threading.current_thread().name, trace.context()))
            inside.release()
            assert hold.wait(30)
            return x * 2

        out = []
        try:
            with trace.recording() as rec:
                def batch():
                    with trace.span(trace.LLM_BATCH, rows=4) as sp:
                        out.append((sp.id, sched.map(row, [1, 2, 3, 4])))

                t = threading.Thread(target=batch)
                t.start()
                assert inside.acquire(timeout=30)
                assert inside.acquire(timeout=30)
                # two rows are inside and held; the other two wait
                assert sched.stats()["rows_waiting"] == 2
                assert not inside.acquire(blocking=False)
                hold.set()
                t.join(30)
                recs = _by_name(rec.drain())
        finally:
            hold.set()
            sched.close()
        (batch_id, results), = out
        assert results == [2, 4, 6, 8]
        rows, waits = recs[trace.LLM_ROW], recs[trace.LLM_ROW_POOL_WAIT]
        assert len(rows) == len(waits) == 4
        by_id = {r.id: r for r in rows}
        for r in rows:
            assert r.parent == batch_id and r.request == batch_id
            assert r.thread.startswith("lzy-wfsched")
        for w in waits:
            owner = by_id[w.parent]
            assert w.start == owner.start and w.end <= owner.end
        # four rows through a window of two: two of them waited, and
        # entered only once a row had left (one clock, no threshold)
        first_out = min(r.end for r in rows)
        assert sum(w.end >= first_out for w in waits) == 2
        assert sum(w.end < first_out for w in waits) == 2
        assert all(ctx[0] in by_id for _, ctx in seen)
        # off: the function runs with no span around it
        sched2 = WorkflowScheduler(backend=None)
        try:
            assert sched2.map(lambda x: trace.context(), [1]) == [None]
        finally:
            sched2.close()

    def test_parent_is_carried_from_the_client_thread_to_the_loop(
            self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        eng.start()
        try:
            with trace.recording() as rec:
                with trace.span(trace.GATEWAY_ATTEMPT) as attempt:
                    req = eng.submit([5, 9, 3, 7], max_new_tokens=3)
                    assert req.wait(60)
                recs = _by_name(rec.drain())
        finally:
            eng.close()
        request, = recs[trace.ENGINE_REQUEST]
        assert request.parent == attempt.id
        assert request.request == attempt.request
        assert request.thread == "inference-engine"
        assert request.attrs["request"] == req.id
        assert req.submitted_at <= req.admitted_at <= req.first_token_at


class TestRequestPath:
    def test_gateway_attempt_and_engine_request_nest_and_add_up(
            self, gateway):
        gateway.generate([1, 2, 3], max_new_tokens=2, greedy=True)  # compile
        with trace.recording() as rec:
            reply = gateway.generate([5, 9, 3, 7, 1, 2, 8, 4, 6],
                                     max_new_tokens=5, greedy=True)
            recs = _by_name(rec.drain())
        assert len(reply["tokens"]) == 5
        root, = recs[trace.GATEWAY_GENERATE]
        admit, = recs[trace.GATEWAY_ADMIT]
        route, = recs[trace.GATEWAY_ROUTE]
        attempt, = recs[trace.GATEWAY_ATTEMPT]
        request, = recs[trace.ENGINE_REQUEST]
        assert root.attrs == {"tenant": "default", "prompt_tokens": 9}
        assert admit.parent == root.id and attempt.parent == root.id
        assert route.parent == attempt.id and request.parent == attempt.id
        assert attempt.attrs["request"] == reply["request_id"]
        for inner, outer in ((admit, root), (route, attempt),
                             (request, attempt), (attempt, root)):
            assert outer.start <= inner.start <= inner.end <= outer.end
            assert inner.request == root.id
        parts = [recs[n][0] for n in (trace.ENGINE_REQUEST_QUEUED,
                                      trace.ENGINE_REQUEST_PREFILL,
                                      trace.ENGINE_REQUEST_DECODE)]
        assert all(p.parent == request.id for p in parts)
        assert parts[0].start == request.start
        assert parts[0].end == parts[1].start
        assert parts[1].end == parts[2].start and parts[2].end == request.end
        assert sum(p.end - p.start for p in parts) == pytest.approx(
            request.end - request.start, abs=1e-9)
        # the loop's own spans are not request-scoped
        assert all(r.request is None for r in recs[trace.ENGINE_ROUND])
        staged = [r for r in recs[trace.ENGINE_ADMIT] if r.attrs]
        assert [r.attrs["prompt_tokens"] for r in staged] == [9]
        assert staged[0].attrs["request"] == reply["request_id"]

    def test_batch_rows_run_under_llm_batch(self, gateway):
        llm.configure(gateway)
        prompts = [[5, 9, 3], [7, 2, 8, 1], [5, 9, 3]]
        with trace.recording() as rec:
            out = llm.generate_batch(prompts, max_new_tokens=3, greedy=True,
                                     cache=False)
            recs = _by_name(rec.drain())
        assert [len(g.tokens) for g in out] == [3, 3, 3]
        batch, = recs[trace.LLM_BATCH]
        assert batch.attrs == {"rows": 3, "deduplicated": 1}
        rows = recs[trace.LLM_ROW]
        assert len(rows) == 2 and all(r.parent == batch.id for r in rows)
        assert len(recs[trace.LLM_ROW_POOL_WAIT]) == 2
        row_ids = {r.id for r in rows}
        dispatches = recs[trace.LLM_DISPATCH]
        assert {d.parent for d in dispatches} == row_ids
        # each row's gateway call hangs under its dispatch, in its tree
        for g in recs[trace.GATEWAY_GENERATE]:
            assert g.parent in {d.id for d in dispatches}
            assert g.request == batch.id


class TestEngineLoop:
    def test_children_tile_the_round(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        warm = eng.submit([1, 2, 3], max_new_tokens=2)
        while not warm.done:
            eng.step()
        reqs = [eng.submit([5, 9, 3, 7, 1, 2, 8, 4, 6], max_new_tokens=120),
                eng.submit([11, 12, 13, 14, 15], max_new_tokens=120)]
        with trace.recording() as rec:
            for _ in range(50):
                eng.step()
            recs = rec.drain()
        assert rec.dropped == 0
        rounds = [r for r in recs if r.name == trace.ENGINE_ROUND]
        assert len(rounds) == 50 and not any(r.done for r in reqs)
        assert {r.attrs["kind"] for r in rounds} == {"decode"}
        assert all(r.attrs["rows"] >= 1 for r in rounds)
        covered = sum(r.end - r.start for r in recs
                      if r.parent in {x.id for x in rounds})
        whole = sum(r.end - r.start for r in rounds)
        assert covered >= 0.95 * whole
        names = {r.name for r in recs if r.parent == rounds[-1].id}
        assert names == trace.LOOP_SPANS - {
            trace.ENGINE_ROUND, trace.ENGINE_PARK,
            trace.ENGINE_PREFILL_FENCE}
        eng.close()

    def test_idle_round_and_park_are_named(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE)
        before = _phase_sums().get("park", 0.0)
        with trace.recording() as rec:
            assert eng.step() is False
            eng.start()
            deadline = time.monotonic() + 10
            while _phase_sums().get("park", 0.0) == before:
                assert time.monotonic() < deadline
                eng.queue.work_available.set()
                time.sleep(0.01)
            eng.close()
            recs = _by_name(rec.drain())
        assert recs[trace.ENGINE_ROUND][0].attrs == {"kind": "idle"}
        assert recs[trace.ENGINE_PARK]
        assert all(r.parent is None for r in recs[trace.ENGINE_PARK])

    def test_new_phase_labels_and_the_old_four_unchanged(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        req = eng.submit([5, 9, 3, 7], max_new_tokens=6)
        before = _phase_sums()
        t0 = time.monotonic()
        with trace.recording() as rec:
            while not req.done:
                eng.step()
            recs = _by_name(rec.drain())
        elapsed = time.monotonic() - t0
        after = _phase_sums()
        text = REGISTRY.exposition()
        for phase in NEW_PHASES[:-1] + OLD_PHASES:
            assert f'lzy_engine_round_phase_seconds_count{{phase="{phase}"}}' \
                in text
            assert after[phase] > before.get(phase, 0.0)
        # each phase's sum is its spans' time, give or take the two clock
        # reads that bracket a span: the four old labels as before
        span_of = {"plan": trace.ENGINE_DECODE_PLAN,
                   "dispatch": trace.ENGINE_DECODE_DISPATCH,
                   "overlap": trace.ENGINE_DECODE_OVERLAP,
                   "fence": trace.ENGINE_DECODE_FENCE,
                   "prefill_fence": trace.ENGINE_PREFILL_FENCE,
                   "admit": trace.ENGINE_ADMIT}
        grown = {p: after[p] - before.get(p, 0.0) for p in after}
        for phase, name in span_of.items():
            spans = sum(r.end - r.start for r in recs[name])
            assert grown[phase] == pytest.approx(spans, abs=2e-3), phase
        # the wait for the first token is a label of its own and no part
        # of the advance: it is taken behind the decode half's dispatch, a
        # child of the round, and ``prefill`` is the advance whole
        fence, = recs[trace.ENGINE_PREFILL_FENCE]
        assert fence.parent in {r.id for r in recs[trace.ENGINE_ROUND]}
        dispatch = next(r for r in recs[trace.ENGINE_DECODE_DISPATCH]
                        if r.parent == fence.parent)
        assert dispatch.end <= fence.start
        assert grown["prefill"] == pytest.approx(
            sum(r.end - r.start for r in recs[trace.ENGINE_PREFILL]),
            abs=2e-3)
        total = sum(after[p] - before.get(p, 0.0)
                    for p in NEW_PHASES[:-1] + OLD_PHASES)
        assert 0.5 * elapsed < total <= elapsed
        eng.close()

    def test_preemption_fires_one_event(self, tiny_model):
        """The pool-exhaustion scenario of ``test_kv_cache``: 7 usable
        blocks, two growing requests, the younger is preempted."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   kv_blocks=8)
        a = eng.submit([5, 9, 3, 7, 1, 2, 8, 4, 6], max_new_tokens=30)
        b = eng.submit([11, 12, 13, 14, 15, 16, 17], max_new_tokens=30)
        with trace.recording() as rec:
            for _ in range(120):
                if a.done and b.done:
                    break
                eng.step()
            recs = _by_name(rec.drain())
        assert a.error is None and "preempted" in b.error
        event, = recs[trace.ENGINE_PREEMPT]
        assert event.attrs["request"] == b.id and event.attrs["blocks"] > 0
        plan = {r.id for r in recs[trace.ENGINE_DECODE_PLAN]}
        assert event.parent in plan          # during block growth
        status = {r.attrs["request"]: r.attrs["status"]
                  for r in recs[trace.ENGINE_REQUEST]}
        assert status == {a.id: "ok", b.id: "error"}
        eng.close()

    def test_eviction_fires_an_event_under_admit(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   kv_blocks=8)              # 7 usable
        with trace.recording() as rec:
            for lo in (0, 16, 32, 48):
                # 24 tokens: three full blocks stay cached when it ends
                r = eng.submit(list(range(lo, lo + 24)), max_new_tokens=2)
                while not r.done:
                    eng.step()
            recs = _by_name(rec.drain())
        evictions = recs[trace.KV_EVICT]
        assert evictions and eng.kv.evictions == sum(
            e.attrs["blocks"] for e in evictions)
        # a prompt's blocks are evicted for at admission, a row's next
        # block during the decode plan's growth
        admits = {r.id: r for r in recs[trace.ENGINE_ADMIT]}
        plans = {r.id for r in recs[trace.ENGINE_DECODE_PLAN]}
        at_admit = [e for e in evictions if e.parent in admits]
        assert at_admit and all(e.parent in plans for e in evictions
                                if e not in at_admit)
        for e in at_admit:
            assert admits[e.parent].attrs["evicted"] == e.attrs["blocks"]
        eng.close()

    def test_every_anchor_is_a_record_and_a_parked_loop_keeps_anchoring(
            self, tiny_model):
        """The anchors a reader has to find in a profile's host plane are
        listed among the records. A parked loop wakes every half second
        into an idle round, so two seconds of park still anchor the
        clocks, one anchor a second."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE)
        with trace.recording() as rec:
            eng.start()
            time.sleep(2.2)
            eng.close()
            recs = _by_name(rec.drain())
        anchors = recs[trace.CLOCK]
        assert len(anchors) >= 2 and recs[trace.ENGINE_PARK]
        for a in anchors:
            assert a.start == a.end
            assert a.attrs == {"monotonic_ns": round(a.start * 1e9)}
        first, *later = anchors
        assert first.thread == threading.current_thread().name
        rounds = {r.id for r in recs[trace.ENGINE_ROUND]}
        assert all(a.parent in rounds for a in later)
        gaps = [b.start - a.start for a, b in zip(later, later[1:])]
        assert all(gap >= 1.0 for gap in gaps)

    def test_a_round_says_what_it_moved(self, tiny_model):
        """``uploads`` on the dispatch span counts the inputs the round
        uploaded from the host (the round after an admission: the greedy
        mask and the page table, never the tokens or positions, which the
        device keeps; none in the rounds that follow), ``bytes`` on the
        fence span is the size of the one array the round fetched."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        first = eng.submit([5, 9, 3], max_new_tokens=4)
        with trace.recording() as rec:
            while not first.done:
                eng.step()
            one = _by_name(rec.drain())
            second = eng.submit([7, 1], max_new_tokens=3)
            while not second.done:
                eng.step()
            two = _by_name(rec.drain())
        for recs in (one, two):
            uploads = [r.attrs["uploads"]
                       for r in recs[trace.ENGINE_DECODE_DISPATCH]]
            assert uploads[0] == 2          # the mask and the page table
            assert len(uploads) > 1 and set(uploads[1:]) == {0}
            assert {r.attrs["bytes"] for r in recs[
                trace.ENGINE_DECODE_FENCE]} == {eng.slots * 4}
        eng.close()

    def test_off_a_round_makes_no_span_and_no_note(self, tiny_model,
                                                   monkeypatch):
        """The recorder off, which is how every end-to-end number is
        taken: a round builds no ``_Span`` and reaches no ``note``, the
        new call sites included."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)

        def refuse(*args, **kwargs):
            raise AssertionError("reached with the recorder off")

        assert trace.ON is False
        monkeypatch.setattr(trace, "_Span", refuse)
        monkeypatch.setattr(trace, "note", refuse)
        monkeypatch.setattr(trace, "anchor", refuse)
        req = eng.submit([5, 9, 3, 7], max_new_tokens=12)
        while not req.done:
            eng.step()
        assert eng.step() is False and len(req.tokens) == 12
        eng.close()


class _SkewedClock:
    """The system's clock plus what a test has added to it, so a test can
    make one phase of the loop as long as it likes."""

    def __init__(self, park_s=0.0):
        from lzy_tpu.utils.clock import SYSTEM_CLOCK

        self._real, self.skew, self.park_s = SYSTEM_CLOCK, 0.0, park_s

    def now(self):
        return self._real.now() + self.skew

    def wait(self, event, timeout=None):
        self.skew += self.park_s          # the loop's park: a long one
        return self._real.wait(event, 0.01)

    def __getattr__(self, name):
        return getattr(self._real, name)


def _slow_phases():
    return {phase: float(v) for phase, v in re.findall(
        r'lzy_engine_slow_phase_total\{phase="(\w+)"\} (\S+)',
        REGISTRY.exposition())}


class TestSlowPhase:
    def test_a_slow_phase_is_counted_and_logged_once(self, tiny_model,
                                                     caplog):
        cfg, params = tiny_model
        clock = _SkewedClock()
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   clock=clock)
        req = eng.submit([5, 9, 3, 7], max_new_tokens=6)
        while len(req.tokens) < 2:
            eng.step()
        before = _slow_phases()
        reap = eng._reap_cancelled

        def slow_reap():
            reap()
            clock.skew += 0.3

        eng._reap_cancelled = slow_reap
        caplog.clear()          # a compile in the rounds above may be slow
        with caplog.at_level("WARNING"):
            eng.step()
            eng._reap_cancelled = reap
            while not req.done:
                eng.step()
        after = _slow_phases()
        grown = {p: after[p] - before.get(p, 0.0) for p in after
                 if after[p] != before.get(p, 0.0)}
        assert grown == {"reap": 1.0}
        lines = [r.getMessage() for r in caplog.records
                 if "engine loop: phase" in r.getMessage()]
        assert len(lines) == 1
        assert re.fullmatch(r"engine loop: phase reap took 0\.3\d\d s "
                            r"\(round kind=decode rows=1\)", lines[0])
        eng.close()

    def test_a_long_park_is_not_a_slow_phase(self, tiny_model):
        cfg, params = tiny_model
        clock = _SkewedClock(park_s=1.0)
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                   clock=clock)
        before, parked = _slow_phases(), _phase_sums().get("park", 0.0)
        eng.start()
        deadline = time.monotonic() + 10
        while _phase_sums().get("park", 0.0) < parked + 1.0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        eng.close()
        assert _slow_phases() == before


# -- a program's build: the meter, the span, the engine's sites ---------------


def _samples(name):
    """``{label string: value}`` of one series of the registry."""
    return {labels: float(v) for labels, v in re.findall(
        rf"^{name}\{{([^}}]*)\}} (\S+)$", REGISTRY.exposition(), re.M)}


def _grown(name, before):
    after = _samples(name)
    return {k: after[k] - before.get(k, 0.0) for k in after
            if after[k] != before.get(k, 0.0)}


def _builds(records, site=None):
    return [r for r in records if r.name == trace.PROGRAM_BUILD
            and site in (None, r.attrs["site"])]


STAGE_ATTRS = {"trace_s", "lower_s", "compile_s", "cache_read_s"}


class TestBuildMeter:
    def test_the_listeners_turn_jaxs_events_into_stage_seconds(self):
        """Fed by hand: a jit traced inside a trace is taken out of the
        outer trace's seconds, the compile request is closed by its
        duration with what the cache said, and all of it lands on the
        open context and under its site."""
        from lzy_tpu.utils import jaxenv

        tr, lo, co = (e for e, _ in sorted(
            jaxenv._STAGE.items(), key=lambda kv: ("trace", "lower",
                                                   "compile").index(kv[1])))
        secs = _samples("lzy_program_build_seconds_sum")
        reqs = _samples("lzy_program_builds_total")
        with trace.building(trace.SITE_VERIFY) as b:
            jaxenv._on_stage_start(tr, 0.0)
            jaxenv._on_stage_start(tr, 0.0)
            jaxenv._on_duration(tr, 0.25)              # the inner jit
            jaxenv._on_duration(tr, 1.0)               # holds the 0.25
            jaxenv._on_stage_start(lo, 0.0)
            jaxenv._on_duration(lo, 0.5)
            jaxenv._on_stage_start(co, 0.0)
            jaxenv._on_event(jaxenv._CACHE_ASKED)
            jaxenv._on_event(jaxenv._CACHE_HIT)
            jaxenv._on_duration(jaxenv._CACHE_READ, 0.125)
            jaxenv._on_duration(co, 2.0)
            jaxenv._on_stage_start(co, 0.0)
            jaxenv._on_duration(co, 4.0)               # the cache not asked
            jaxenv._on_duration("/jax/some/other_duration", 9.0)
        assert (b.trace_s, b.lower_s, b.compile_s, b.cache_read_s) == (
            1.0, 0.5, 6.0, 0.125)
        assert (b.compile_requests, b.hits, b.misses) == (2, 1, 0)
        assert b.built and b.seconds == 7.5 and b.cache == "hit"
        site = f'site="{trace.SITE_VERIFY}"'
        assert _grown("lzy_program_build_seconds_sum", secs) == {
            f'{site},stage="trace"': 1.0, f'{site},stage="lower"': 0.5,
            f'{site},stage="compile"': 6.0,
            f'{site},stage="cache_read"': 0.125}
        assert _grown("lzy_program_builds_total", reqs) == {
            f'cache="hit",{site}': 1.0, f'cache="off",{site}': 1.0}
        assert b.describe() == ("verify (trace 1.00 s, lower 0.50 s, "
                                "compile 6.00 s, cache hit)")

    def test_a_compile_outside_any_context_lands_under_other(self):
        from lzy_tpu.utils import jaxenv

        x = jax.numpy.ones((3,))
        reqs = _samples("lzy_program_builds_total")
        totals = jaxenv.build_totals()
        assert trace.open_build() is None
        jax.jit(lambda v: v * 7 - 2)(x)
        grown = _grown("lzy_program_builds_total", reqs)
        assert sum(grown.values()) == 1
        assert all('site="other"' in k for k in grown)
        assert jaxenv.build_totals()["requests"] == totals["requests"] + 1

    def test_off_the_counters_move_and_no_record_is_made(self):
        x = jax.numpy.ones((3,))
        reqs = _samples("lzy_program_builds_total")
        assert trace.ON is False
        with trace.building(trace.SITE_SPLICE) as b:
            jax.jit(lambda v: v * 11 - 3)(x)
        assert b.built and b.compile_requests == 1 and b._span is None
        assert sum(_grown("lzy_program_builds_total", reqs).values()) == 1
        with trace.recording() as rec:
            assert _builds(rec.drain()) == []

    def test_a_context_in_which_nothing_was_built_leaves_no_record(self):
        f = jax.jit(lambda v: v * 13 - 4)
        x = jax.numpy.ones((3,))
        f(x)
        with trace.recording() as rec:
            with trace.span(trace.LLM_BATCH) as outer:
                with trace.building(trace.SITE_DECODE, width=8) as b:
                    f(x)
                # the thread's open span is the outer one again
                trace.note(after=True)
            records = rec.drain()
        assert not b.built and _builds(records) == []
        assert [r.attrs for r in records if r.id == outer.id] == [
            {"after": True}]

    def test_the_train_steps_first_call_is_one_build_its_second_none(self):
        import optax

        from lzy_tpu.parallel import TrainState, fsdp_mesh, make_train_step

        tx = optax.sgd(0.1)
        step, shard_state, _ = make_train_step(
            lambda p, batch: jax.numpy.mean((batch["x"] @ p["w"]) ** 2), tx,
            mesh=fsdp_mesh(), param_logical_axes={"w": (None, None)},
            batch_logical_axes=("batch",))
        batch = {"x": jax.numpy.ones((8, 4))}
        state = shard_state(TrainState.create(
            {"w": jax.numpy.ones((4, 2))}, tx))
        reqs = _samples("lzy_program_builds_total")
        with trace.recording() as rec:
            state, _ = step(state, batch)
            first = _builds(rec.drain())
            grown = _grown("lzy_program_builds_total", reqs)
            state, _ = step(state, batch)
            assert _builds(rec.drain()) == []
        assert len(first) == 1
        assert first[0].attrs["site"] == trace.SITE_TRAIN_STEP
        # the step's program (and a helper's, where placing the batch
        # needs one): all of it under the site, none of it later
        assert first[0].attrs["compile_requests"] >= 1
        assert all(f'site="{trace.SITE_TRAIN_STEP}"' in k for k in grown)
        assert sum(grown.values()) == first[0].attrs["compile_requests"]
        assert _grown("lzy_program_builds_total", reqs) == grown


class TestEngineBuilds:
    def test_set_up_is_two_parents_and_warmup_builds_decode(
            self, tiny_model):
        cfg, params = tiny_model
        setup = _samples("lzy_engine_setup_seconds_total")
        inside = _samples("lzy_engine_setup_build_seconds_total")
        with trace.recording() as rec:
            eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
            eng.warmup()
            recs = rec.drain()
        named = _by_name(recs)
        init, = named[trace.ENGINE_INIT]
        warm, = named[trace.ENGINE_WARMUP]
        decode, = _builds(recs, trace.SITE_DECODE)
        assert decode.parent == warm.id
        assert decode.attrs["warm"] is True and decode.attrs["rows"] == 0
        assert STAGE_ATTRS <= set(decode.attrs)
        assert decode.attrs["compile_requests"] >= 1
        assert decode.attrs["cache"] in ("hit", "miss", "off")
        assert decode.attrs["lower_s"] > 0 and decode.attrs["compile_s"] > 0
        # the constructor's builds are its own context's, engine.aux
        # (none where the process has built its small programs before)
        assert {r.attrs["site"] for r in _builds(recs)
                if r.parent == init.id} <= {trace.SITE_AUX}
        # always on: each phase's seconds, and the builds inside them
        grown = _grown("lzy_engine_setup_seconds_total", setup)
        built = _grown("lzy_engine_setup_build_seconds_total", inside)
        assert set(grown) == {'phase="init"', 'phase="warmup"'}
        assert built.get('phase="warmup"', 0.0) > 0
        for phase, parent in (("init", init), ("warmup", warm)):
            took = grown[f'phase="{phase}"']
            assert took == pytest.approx(parent.end - parent.start, abs=0.05)
            assert built.get(f'phase="{phase}"', 0.0) <= took
            # (to a millisecond: a trace JAX answers from its own cache
            # is counted and makes no span)
            assert built.get(f'phase="{phase}"', 0.0) == pytest.approx(sum(
                r.attrs["trace_s"] + r.attrs["lower_s"]
                + r.attrs["compile_s"] for r in _builds(recs)
                if r.parent == parent.id), abs=1e-3)
        eng.close()

    def test_a_width_is_built_once_by_the_first_request_that_reaches_it(
            self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        eng.warmup()
        secs = _samples("lzy_program_build_seconds_sum")
        reqs = _samples("lzy_program_builds_total")
        serving = _samples("lzy_engine_serving_builds_total")

        def run(prompt):
            req = eng.submit(prompt, max_new_tokens=2)
            while not req.done:
                eng.step()

        with trace.recording() as rec:
            run([5, 9, 3])                              # width 8
            first = rec.drain()
            run(list(range(1, 41)))                     # width 64
            second = rec.drain()
            run([7, 1, 2, 6])                           # width 8 again
            third = rec.drain()
        for recs, width in ((first, 8), (second, 64)):
            build, = _builds(recs, trace.SITE_PREFILL)
            assert build.attrs["width"] == width
            assert build.attrs["warm"] is False
            assert build.attrs["phase"] == "prefill"
            under, = [r for r in recs if r.id == build.parent]
            assert under.name == trace.ENGINE_PREFILL
        assert _builds(third) == []
        # no row was in decode while a width was built: nothing waited,
        # and the alert's counter names no prefill build
        assert all(r.attrs["rows"] == 0 for r in _builds(
            first + second, trace.SITE_PREFILL))
        assert f'site="{trace.SITE_PREFILL}"' not in _grown(
            "lzy_engine_serving_builds_total", serving)
        # the counters took the numbers the spans carry
        builds = _builds(first + second)
        for stage in ("trace", "lower", "compile", "cache_read"):
            by_site = {}
            for r in builds:
                by_site[r.attrs["site"]] = by_site.get(
                    r.attrs["site"], 0.0) + r.attrs[stage + "_s"]
            grown = _grown("lzy_program_build_seconds_sum", secs)
            for site, seconds in by_site.items():
                assert grown.get(f'site="{site}",stage="{stage}"', 0.0) \
                    == pytest.approx(seconds)
        assert sum(v for k, v in _grown(
            "lzy_program_builds_total", reqs).items()
            if 'site="other"' not in k) == sum(
            r.attrs["compile_requests"] for r in builds)
        eng.close()

    def test_a_build_under_a_resident_row_is_counted_spanned_and_named(
            self, tiny_model, caplog):
        cfg, params = tiny_model
        clock = _SkewedClock()
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   clock=clock)
        eng.warmup()
        resident = eng.submit([5, 9, 3], max_new_tokens=200)
        while len(resident.tokens) < 2:
            eng.step()
        serving = _samples("lzy_engine_serving_builds_total")
        stalled = _stalled_row_seconds()
        step = eng.prefill.step

        def slow_first_program(*args, **kwargs):
            clock.skew += 0.3          # the build, as the loop's clock sees
            return step(*args, **kwargs)

        eng.prefill.step = slow_first_program
        caplog.clear()
        with trace.recording() as rec, caplog.at_level("WARNING"):
            wide = eng.submit(list(range(1, 41)), max_new_tokens=2)
            while not wide.done:
                eng.step()
            recs = rec.drain()
        build, = _builds(recs, trace.SITE_PREFILL)
        assert build.attrs["rows"] == 1 and build.attrs["width"] == 64
        assert build.attrs["warm"] is False
        assert _grown("lzy_engine_serving_builds_total", serving) == {
            f'site="{trace.SITE_PREFILL}"': 1.0}
        seconds = sum(build.attrs[k] for k in
                      ("trace_s", "lower_s", "compile_s"))
        assert _stalled_row_seconds() - stalled == pytest.approx(seconds)
        line, = [r.getMessage() for r in caplog.records
                 if "engine loop: phase prefill" in r.getMessage()]
        assert re.search(
            r"\(round kind=\w+ rows=\d\); built prefill width=64 "
            r"\(trace \d+\.\d\d s, lower \d+\.\d\d s, compile \d+\.\d\d s, "
            r"cache (hit|miss|off)\)$", line)
        eng.close()


def _stalled_row_seconds():
    m = re.search(r"^lzy_engine_build_stalled_row_seconds_total (\S+)$",
                  REGISTRY.exposition(), re.M)
    return float(m.group(1)) if m else 0.0

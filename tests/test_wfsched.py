"""Workflow-aware serving scheduler (``lzy_tpu/llm/sched.py``): the
acceptance properties and failure paths.

- **In-flight dedup**: N identical in-flight greedy calls reach the
  fleet as exactly ONE engine request whose reply fans out to every
  waiter; sampled/streaming calls never dedup; a cancelled or failed
  leader is its own outcome — followers re-dispatch, they do not
  inherit it.
- **Fused op chains**: step 2 of a ``generate → tool-op → generate``
  chain hard-pins to the replica holding the parked KV and re-prefills
  NOTHING of the shared prefix (asserted via ``prefill_tokens_saved``),
  bit-identical to the unfused oracle.
- **Failure paths**: replica death mid-tool-gap drops the lease and
  the chain falls back to the routed path (still bit-identical); a
  parked chain's TTL expiry releases it at the next engine round; KV
  pressure sheds parked chains BEFORE any resident request suffers.
"""

import threading
import time

import jax
import pytest

from lzy_tpu import Lzy, llm
from lzy_tpu.llm.sched import WorkflowScheduler
from lzy_tpu.gateway import GatewayService, PrefixAffinityRouter, ReplicaFleet
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate as oracle_generate
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig
from lzy_tpu.utils.clock import SYSTEM_CLOCK

import jax.numpy as jnp
import numpy as np

PAGE = 8


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


@pytest.fixture(autouse=True)
def _clean_backend():
    yield
    llm.configure(None)


def _oracle_tokens(cfg, params, prompt_ids, n, **kw):
    out = oracle_generate(cfg, params,
                          jnp.asarray([prompt_ids], jnp.int32),
                          max_new_tokens=n, **kw)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _make_gateway(cfg, params, *, replicas=2, slots=2, **engine_kw):
    def factory():
        return PagedInferenceEngine(cfg, params, slots=slots,
                                    page_size=PAGE, **engine_kw)

    fleet = ReplicaFleet(factory)
    gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                        model_name="tiny")
    for _ in range(replicas):
        fleet.add_replica()
    return gw, fleet


def _local_lzy(uri: str) -> Lzy:
    reg = DefaultStorageRegistry()
    reg.register_storage("default", StorageConfig(uri=uri), default=True)
    return Lzy(storage_registry=reg)


def _wait_until(pred, timeout=15.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


def _prefill_saved(fleet) -> int:
    return sum(r.engine.stats().prefill_tokens_saved or 0
               for r in fleet.replicas())


def _parked_released(reason: str) -> float:
    from lzy_tpu.serving.kv_io import PARKED_RELEASED

    return sum(v for k, v in PARKED_RELEASED._values.items()
               if reason in str(k))


# -- in-flight dedup (the admission fan-in plane) -----------------------------

class _GatedBackend:
    """Fake serving plane: every generate blocks on ``gate`` (so the
    test controls overlap) and is counted."""

    def __init__(self, replies=None):
        self.calls = 0
        self.gate = threading.Event()
        self._lock = threading.Lock()
        self._replies = replies

    def model_digest(self):
        return "fake-digest"

    def generate(self, prompt, **kw):
        with self._lock:
            self.calls += 1
            n = self.calls
        if not self.gate.wait(30):
            raise TimeoutError("test gate never opened")
        if self._replies is not None:
            return self._replies(n)
        return {"tokens": [100 + n], "status": "ok"}


def _dispatch_into(sched, results, i, prompt, **kw):
    def run():
        try:
            results[i] = sched.dispatch(prompt, **kw)
        except BaseException as e:  # noqa: BLE001 — asserted by the test
            results[i] = e

    t = threading.Thread(target=run)
    t.start()
    return t


class TestInflightDedup:
    def test_identical_greedy_calls_collapse_to_one_request(self):
        """Acceptance: N identical in-flight greedy calls reach the
        plane as exactly 1 request; every waiter gets the reply, with
        its OWN token list."""
        be = _GatedBackend()
        sched = WorkflowScheduler(be, dedup=True, fuse=False)
        try:
            results = {}
            threads = [_dispatch_into(sched, results, 0, [1, 2, 3],
                                      max_new_tokens=4, greedy=True)]
            assert _wait_until(lambda: be.calls == 1)
            threads += [_dispatch_into(sched, results, i, [1, 2, 3],
                                       max_new_tokens=4, greedy=True)
                        for i in (1, 2, 3)]
            assert _wait_until(
                lambda: sched.stats()["dedup_waiting"] == 3)
            be.gate.set()
            for t in threads:
                t.join(30)
            assert be.calls == 1
            assert all(results[i] == {"tokens": [101], "status": "ok"}
                       for i in range(4))
            # fan-out copies, never aliases: a waiter mutating its
            # Generation's tokens must not corrupt a sibling's
            lists = [results[i]["tokens"] for i in range(4)]
            for i in range(4):
                for j in range(i + 1, 4):
                    assert lists[i] is not lists[j]
            s = sched.stats()
            assert s["dispatches"] == 4
            assert s["dedup_hits"] == 3
            assert s["dedup_waiting"] == 0
        finally:
            sched.close()

    def test_different_slo_identity_never_dedups(self):
        """Same prompt, different tenant: a follower must not ride a
        reply another tenant's quota paid for."""
        be = _GatedBackend()
        sched = WorkflowScheduler(be, dedup=True, fuse=False)
        try:
            results = {}
            t1 = _dispatch_into(sched, results, 0, [1, 2], greedy=True,
                                max_new_tokens=4, tenant="a")
            assert _wait_until(lambda: be.calls == 1)
            t2 = _dispatch_into(sched, results, 1, [1, 2], greedy=True,
                                max_new_tokens=4, tenant="b")
            assert _wait_until(lambda: be.calls == 2)
            be.gate.set()
            t1.join(30)
            t2.join(30)
            assert sched.stats()["dedup_hits"] == 0
        finally:
            sched.close()

    @pytest.mark.parametrize("kw", [
        {"greedy": None},                       # sampled: a draw, not a
        {"greedy": False},                      # function of the inputs
        {"greedy": True, "stream": object()},   # stream: one channel
    ])
    def test_sampled_and_streaming_calls_never_dedup(self, kw):
        be = _GatedBackend()
        sched = WorkflowScheduler(be, dedup=True, fuse=False)
        try:
            results = {}
            t1 = _dispatch_into(sched, results, 0, [7, 8],
                                max_new_tokens=4, **kw)
            assert _wait_until(lambda: be.calls == 1)
            t2 = _dispatch_into(sched, results, 1, [7, 8],
                                max_new_tokens=4, **kw)
            # both are IN the backend concurrently — no rendezvous
            assert _wait_until(lambda: be.calls == 2)
            be.gate.set()
            t1.join(30)
            t2.join(30)
            assert sched.stats()["dedup_hits"] == 0
        finally:
            sched.close()

    def test_cancelled_leader_does_not_fail_followers(self):
        """A deadline-truncated leader reply (status 'cancelled') is the
        LEADER's outcome: the follower re-dispatches and completes."""
        be = _GatedBackend(replies=lambda n: (
            {"tokens": [1], "status": "cancelled"} if n == 1
            else {"tokens": [7, 8], "status": "ok"}))
        sched = WorkflowScheduler(be, dedup=True, fuse=False)
        try:
            results = {}
            t1 = _dispatch_into(sched, results, 0, [5, 5],
                                max_new_tokens=4, greedy=True)
            assert _wait_until(lambda: be.calls == 1)
            t2 = _dispatch_into(sched, results, 1, [5, 5],
                                max_new_tokens=4, greedy=True)
            assert _wait_until(
                lambda: sched.stats()["dedup_waiting"] == 1)
            be.gate.set()
            t1.join(30)
            t2.join(30)
            assert results[0] == {"tokens": [1], "status": "cancelled"}
            assert results[1] == {"tokens": [7, 8], "status": "ok"}
            assert be.calls == 2
            assert sched.stats()["dedup_hits"] == 0
        finally:
            sched.close()

    def test_failed_leader_does_not_fail_followers(self):
        """A leader that RAISES fails only its own caller — the
        follower becomes the new leader and succeeds."""
        calls = {"n": 0}
        gate = threading.Event()

        class RaiseThenOk:
            def model_digest(self):
                return "d"

            def generate(self, prompt, **kw):
                calls["n"] += 1
                if calls["n"] == 1:
                    gate.wait(30)
                    raise RuntimeError("leader replica on fire")
                return {"tokens": [9], "status": "ok"}

        sched = WorkflowScheduler(RaiseThenOk(), dedup=True, fuse=False)
        try:
            results = {}
            t1 = _dispatch_into(sched, results, 0, [3, 3],
                                max_new_tokens=2, greedy=True)
            assert _wait_until(lambda: calls["n"] == 1)
            t2 = _dispatch_into(sched, results, 1, [3, 3],
                                max_new_tokens=2, greedy=True)
            assert _wait_until(
                lambda: sched.stats()["dedup_waiting"] == 1)
            gate.set()
            t1.join(30)
            t2.join(30)
            assert isinstance(results[0], RuntimeError)
            assert results[1] == {"tokens": [9], "status": "ok"}
            assert calls["n"] == 2
        finally:
            sched.close()

    def test_follower_timeout_falls_back_to_its_own_dispatch(self):
        """A leader that outlives the follower's budget must not hold
        the follower hostage: past ``timeout_s`` it dispatches for
        itself (no dedup credit)."""
        calls = {"n": 0}
        gate = threading.Event()

        class SlowLeader:
            def model_digest(self):
                return "d"

            def generate(self, prompt, **kw):
                calls["n"] += 1
                n = calls["n"]
                if n == 1:
                    gate.wait(30)        # the leader, wedged
                return {"tokens": [n], "status": "ok"}

        sched = WorkflowScheduler(SlowLeader(), dedup=True, fuse=False)
        try:
            results = {}
            t1 = _dispatch_into(sched, results, 0, [4, 4],
                                max_new_tokens=2, greedy=True)
            assert _wait_until(lambda: calls["n"] == 1)
            t2 = _dispatch_into(sched, results, 1, [4, 4],
                                max_new_tokens=2, greedy=True,
                                timeout_s=0.3)
            t2.join(30)                  # returns while leader is stuck
            assert results[1] == {"tokens": [2], "status": "ok"}
            assert calls["n"] == 2
            assert sched.stats()["dedup_hits"] == 0
            gate.set()
            t1.join(30)
            assert results[0] == {"tokens": [1], "status": "ok"}
        finally:
            sched.close()

    def test_batch_rows_dedup_through_the_real_fleet(self, tiny_model):
        """`llm.generate_batch` with identical greedy rows: the fleet
        serves exactly the UNIQUE rows; every duplicate adopts a copy.
        Sampled rows never collapse."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://wfsched-batch")
            pa, pb = [5, 9, 3, 1], [7, 2, 8, 1, 4]
            base = gw.stats()["requests_finished"]
            with lzy.workflow("fanin"):
                outs = llm.generate_batch([pa, pa, pb, pa],
                                          max_new_tokens=4, greedy=True)
            outs = list(outs)
            assert gw.stats()["requests_finished"] - base == 2
            ea = _oracle_tokens(cfg, params, pa, 4)
            eb = _oracle_tokens(cfg, params, pb, 4)
            assert [g.tokens for g in outs] == [ea, ea, eb, ea]
            assert outs[0].status == outs[1].status == "ok"
            sched = llm.current_scheduler()
            assert sched.stats()["dedup_hits"] >= 2
            # sampled rows: each is its own draw — no collapse
            base = gw.stats()["requests_finished"]
            with lzy.workflow("fanin-sampled"):
                outs = llm.generate_batch([pa, pa, pa], max_new_tokens=4)
            assert len(list(outs)) == 3
            assert gw.stats()["requests_finished"] - base == 3
        finally:
            gw.close()


# -- fused op chains against the real fleet -----------------------------------

class TestFusedChain:
    P1 = [5, 9, 3, 1, 2, 6, 7, 4, 11, 12, 13, 14]      # 12 tokens

    def _run_chain(self, cfg, params, uri):
        """One generate → tool-gap → generate conversation; returns
        (g1, g2, step2_prefill_saved, sched_stats, gateway)."""
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy(uri)
            conv = llm.Conversation(f"chain-{uri[-6:]}")
            with lzy.workflow("step1"):
                g1 = llm.generate(self.P1, max_new_tokens=5, greedy=True,
                                  conversation=conv)
            sched = llm.current_scheduler()
            sched.drain()                 # park + speculation settled
            saved0 = _prefill_saved(fleet)
            p2 = list(g1.full_tokens()) + [41, 42]
            with lzy.workflow("step2"):
                g2 = llm.generate(p2, max_new_tokens=5, greedy=True,
                                  conversation=conv)
            return (g1, g2, _prefill_saved(fleet) - saved0,
                    sched.stats())
        finally:
            gw.close()
            llm.configure(None)

    def test_fused_step_skips_the_whole_shared_prefix(
            self, tiny_model, monkeypatch):
        """Acceptance: with fusion on, step 2 routes 'fused' to the
        pinned replica and its prefill matches EVERY whole page of the
        parked + speculated chain — step-1 prompt AND reply pages (16
        of 19 prompt tokens; 8-token pages) — where the unfused path
        re-prefills the reply positions (8 matched). Greedy output is
        bit-identical to the unfused oracle either way."""
        cfg, params = tiny_model
        monkeypatch.delenv("LZY_WFSCHED_FUSE", raising=False)
        g1f, g2f, saved_fused, stats_f = self._run_chain(
            cfg, params, "mem://wfsched-fused")
        monkeypatch.setenv("LZY_WFSCHED_FUSE", "0")
        g1u, g2u, saved_unfused, stats_u = self._run_chain(
            cfg, params, "mem://wfsched-plain")
        # bit-identity vs the monolithic oracle, fused and unfused
        e1 = _oracle_tokens(cfg, params, self.P1, 5)
        p2 = self.P1 + e1 + [41, 42]
        e2 = _oracle_tokens(cfg, params, p2, 5)
        assert g1f.tokens == g1u.tokens == e1
        assert g2f.tokens == g2u.tokens == e2
        # the fused chain pinned step 2 to the replica holding the KV
        assert g2f.routed_by == "fused"
        assert g2f.replica == g1f.replica
        assert stats_f["parks"] >= 1 and stats_f["speculations"] >= 1
        # ...and re-prefilled nothing of the shared prefix: the step-1
        # prompt page came from the ordinary radix cache, the reply
        # page ONLY exists because the speculation prefilled it
        assert saved_fused == 16
        # unfused: session affinity still finds the prompt page, but
        # the reply positions are decode output — never tree-cached —
        # so the shared prefix IS re-prefilled past the first page
        assert g2u.routed_by == "session"
        assert saved_unfused == 8
        assert stats_u["parks"] == 0 and stats_u["speculations"] == 0

    def test_replica_death_mid_gap_drops_lease_and_falls_back(
            self, tiny_model):
        """The pinned replica dies during the tool gap: the health tick
        retires it, the fusion lease (and its parked KV) dies with it,
        and step 2 serves bit-identically over the routed path."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://wfsched-kill")
            conv = llm.Conversation("killed-gap")
            p1 = TestFusedChain.P1
            with lzy.workflow("step1"):
                g1 = llm.generate(p1, max_new_tokens=5, greedy=True,
                                  conversation=conv)
            llm.current_scheduler().drain()
            assert gw.stats()["wf_parked_sessions"] == 1
            rid = gw.router.session_replica(conv.id)
            victim = fleet.get(rid)
            assert victim.engine.stats().kv_parked_chains == 1
            released0 = _parked_released("shutdown")
            victim.engine.close()         # mid-gap death
            gw.tick()                     # health check reaps it...
            # ...dropping the lease AND the engine-side pins
            assert gw.stats()["wf_parked_sessions"] == 0
            assert _parked_released("shutdown") == released0 + 1
            assert rid not in [r.id for r in fleet.replicas()]
            p2 = list(g1.full_tokens()) + [41]
            with lzy.workflow("step2"):
                g2 = llm.generate(p2, max_new_tokens=5, greedy=True,
                                  conversation=conv)
            assert g2.status == "ok"
            assert g2.tokens == _oracle_tokens(cfg, params, p2, 5)
            assert g2.replica != rid
            assert g2.routed_by != "fused"
        finally:
            gw.close()

    def test_replica_death_without_health_tick_still_serves(
            self, tiny_model):
        """No tick between the death and step 2: the stale lease points
        at a corpse. The routed loop consumes the pin, finds the
        replica unroutable, and degrades to ordinary routing — one
        re-prefill, never a wrong token."""
        cfg, params = tiny_model
        gw, fleet = _make_gateway(cfg, params, replicas=2)
        try:
            llm.configure(gw)
            lzy = _local_lzy("mem://wfsched-kill-lazy")
            conv = llm.Conversation("killed-gap-lazy")
            p1 = TestFusedChain.P1
            with lzy.workflow("step1"):
                g1 = llm.generate(p1, max_new_tokens=5, greedy=True,
                                  conversation=conv)
            llm.current_scheduler().drain()
            rid = gw.router.session_replica(conv.id)
            fleet.get(rid).engine.close()
            p2 = list(g1.full_tokens()) + [41]
            with lzy.workflow("step2"):
                g2 = llm.generate(p2, max_new_tokens=5, greedy=True,
                                  conversation=conv)
            assert g2.status == "ok"
            assert g2.tokens == _oracle_tokens(cfg, params, p2, 5)
            assert g2.replica != rid
            assert g2.routed_by != "fused"
        finally:
            gw.close()


# -- engine-side park lifecycle (TTL, pressure) -------------------------------

class _OffsetClock:
    """System clock plus a test-advanced offset — park TTLs observe the
    jump without the test sleeping through them."""

    def __init__(self):
        self.offset = 0.0

    def now(self):
        return SYSTEM_CLOCK.now() + self.offset

    def time(self):
        return SYSTEM_CLOCK.time() + self.offset

    def sleep(self, seconds):
        SYSTEM_CLOCK.sleep(seconds)

    def wait(self, event, timeout=None):
        return SYSTEM_CLOCK.wait(event, timeout)

    def event(self):
        return SYSTEM_CLOCK.event()


def _run_to_done(eng, req, rounds=200):
    for _ in range(rounds):
        if req.done:
            return
        eng.step()
    raise AssertionError(f"request {req.id} never finished")


class TestEnginePark:
    def test_park_ttl_expiry_sweeps_the_chain(self, tiny_model):
        cfg, params = tiny_model
        clk = _OffsetClock()
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   clock=clk)
        prompt = list(range(1, 13))
        a = eng.submit(prompt, max_new_tokens=2, greedy=True)
        _run_to_done(eng, a)
        assert eng.park_chain("conv:ttl", prompt, ttl_s=5.0)
        s = eng.stats()
        assert s.kv_parked_chains == 1
        assert s.kv_parked_blocks == 1       # one whole 8-token page
        # re-park refreshes the one pin, never duplicates it
        assert eng.park_chain("conv:ttl", prompt, ttl_s=5.0)
        assert eng.stats().kv_parked_chains == 1
        released0 = _parked_released("ttl")
        clk.offset += 10.0                   # the tool gap overran
        eng.step()                           # next round sweeps
        assert eng.stats().kv_parked_chains == 0
        assert _parked_released("ttl") == released0 + 1
        assert not eng.unpark_chain("conv:ttl")   # double-release: no-op

    def test_park_declines_when_nothing_is_cached(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE)
        assert not eng.park_chain("conv:none", [60] * 12, ttl_s=5.0)
        assert eng.stats().kv_parked_chains == 0

    def test_pressure_sheds_parked_before_any_resident_request(
            self, tiny_model):
        """KV pressure: a parked tool-gap chain is strictly cheaper to
        lose than resident work — the admission gate sheds it (reason
        'pressure') and BOTH live requests finish ok, bit-identical,
        with nobody preempted."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                                   kv_blocks=12)
        pa = list(range(1, 13))              # 12 tokens, 2 blocks
        a = eng.submit(pa, max_new_tokens=2, greedy=True)
        _run_to_done(eng, a)
        assert eng.park_chain("conv:gap", pa, ttl_s=300.0)
        # b occupies 6 of the 12 blocks and stays resident (41 + 7
        # tokens fit its 6 pages exactly — no decode growth)
        pb = [(i * 7) % 60 + 1 for i in range(41)]
        b = eng.submit(pb, max_new_tokens=7, greedy=True)
        for _ in range(200):
            if b.tokens:
                break
            eng.step()
        assert b.tokens and not b.done
        # c needs 6 blocks; free pool is 5 with the pin held — the gate
        # must shed the parked chain, not queue c behind the tool gap
        released0 = _parked_released("pressure")
        pc = [(i * 11) % 60 + 1 for i in range(47)]
        c = eng.submit(pc, max_new_tokens=1, greedy=True)
        _run_to_done(eng, b)
        _run_to_done(eng, c)
        assert _parked_released("pressure") == released0 + 1
        assert eng.stats().kv_parked_chains == 0
        # the residents never paid for it: no preemption, exact output
        assert b.error is None and c.error is None
        assert b.result(0) == _oracle_tokens(cfg, params, pb, 7)
        assert c.result(0) == _oracle_tokens(cfg, params, pc, 1)


# -- the window of rows in flight (WorkflowScheduler.map) ---------------------

class _WindowBackend:
    """Fake serving plane for the window: counts the rows inside
    ``generate`` and holds each on the condition until the test lets its
    prompt go (no sleeps). Replies carry the capacity fields it is given;
    a prompt in ``shed`` is refused once, as a full gateway refuses."""

    def __init__(self, slots=None, admits=None):
        self.slots, self.admits = slots, admits
        self.inside, self.peak = 0, 0
        self.entered = []            # first token of each prompt, in order
        self.shed = set()
        self.open_all = False
        self._let_go = set()
        self._cv = threading.Condition()

    def model_digest(self):
        return "window-digest"

    def generate(self, prompt, **kw):
        key = prompt[0]
        with self._cv:
            if key in self.shed:
                self.shed.discard(key)
                from lzy_tpu.serving.scheduler import AdmissionError

                raise AdmissionError("waiters_busy")
            self.inside += 1
            self.peak = max(self.peak, self.inside)
            self.entered.append(key)
            self._cv.notify_all()
            ok = self._cv.wait_for(
                lambda: self.open_all or key in self._let_go, 30)
            self.inside -= 1
            reply = {"tokens": [key], "status": "ok"}
            if self.slots is not None:
                reply["plane_slots"] = self.slots
            if self.admits is not None:
                reply["plane_admits"] = self.admits
            self._cv.notify_all()
        if not ok:
            raise TimeoutError("the test never let the row go")
        return reply

    def let_go(self, *keys):
        with self._cv:
            self._let_go.update(keys)
            self._cv.notify_all()

    def let_all_go(self):
        with self._cv:
            self.open_all = True
            self._cv.notify_all()

    def wait_entered(self, n):
        with self._cv:
            assert self._cv.wait_for(lambda: len(self.entered) >= n, 30), \
                f"{len(self.entered)} rows entered, {n} expected"


def _map_in_thread(sched, keys, out):
    """``sched.map`` over one-token prompts, on a thread of its own."""
    def run():
        try:
            out.append(sched.map(
                lambda k: sched.dispatch([k], max_new_tokens=1)["tokens"],
                list(keys)))
        except BaseException as e:  # noqa: BLE001 — asserted by the test
            out.append(e)

    t = threading.Thread(target=run)
    t.start()
    return t


def _width_for(slots):
    from lzy_tpu.llm import sched as sched_mod

    return slots + int(sched_mod._BACKLOG_PER_SLOT * slots)


class TestRowWindow:
    def test_a_plane_that_reports_nothing_gets_sixteen(self):
        backend = _WindowBackend()
        sched = WorkflowScheduler(backend)
        out = []
        t = _map_in_thread(sched, range(20), out)
        backend.wait_entered(16)
        st = sched.stats()
        assert (st["row_window"], st["rows_in_flight"],
                st["rows_waiting"]) == (16, 16, 4)
        backend.let_all_go()
        t.join(30)
        assert out == [[[k] for k in range(20)]]
        assert backend.peak == 16 and sched.stats()["row_window"] == 16

    def test_width_follows_the_first_reply_that_reports_and_shrinks(self):
        backend = _WindowBackend(slots=16)
        sched = WorkflowScheduler(backend)
        wide = _width_for(16)
        out = []
        t = _map_in_thread(sched, range(wide + 8), out)
        backend.wait_entered(16)
        assert sched.stats()["row_window"] == 16
        backend.let_go(0)                      # the first reply: 16 slots
        backend.wait_entered(wide + 1)         # the row left, width entered
        assert _wait_until(
            lambda: sched.stats()["rows_in_flight"] == wide)
        assert sched.stats()["row_window"] == wide
        assert sched.stats()["rows_waiting"] == 7 and backend.peak <= wide
        # a replica is lost: the next reply reports 2 slots. Rows inside
        # stay inside, new ones wait until the window has room again
        backend.slots = 2
        narrow = _width_for(2)
        backend.let_go(1)
        assert _wait_until(lambda: sched.stats()["row_window"] == narrow)
        backend.let_go(*range(2, 10))
        assert _wait_until(
            lambda: sched.stats()["rows_in_flight"] == wide - 9)
        assert len(backend.entered) == wide + 1
        assert backend.inside == wide - 9
        assert sched.stats()["rows_waiting"] == 7
        backend.let_all_go()
        t.join(30)
        assert out == [[[k] for k in range(wide + 8)]]

    @pytest.mark.parametrize("slots,admits,width", [
        (32, 16, 16),         # the default gateway: gains nothing, sheds
        (8, 8, 8),            # nothing; a small front narrows the window
        (4, 256, None),       # None: slots plus their backlog
    ])
    def test_width_stays_within_what_the_plane_admits(self, slots, admits,
                                                      width):
        backend = _WindowBackend(slots=slots, admits=admits)
        sched = WorkflowScheduler(backend)
        backend.let_all_go()
        assert sched.map(lambda k: sched.dispatch(
            [k], max_new_tokens=1)["tokens"], [7]) == [[7]]
        assert sched.stats()["row_window"] == (
            _width_for(slots) if width is None else width)

    @pytest.mark.parametrize("bad", [0, -3, True, "32", 2.5, None])
    def test_a_field_that_is_no_count_changes_nothing(self, bad):
        backend = _WindowBackend(slots=bad)
        sched = WorkflowScheduler(backend)
        backend.let_all_go()
        sched.dispatch([1], max_new_tokens=1)
        assert sched.stats()["row_window"] == 16

    def test_a_shed_row_is_retried_and_never_widens_the_window(self):
        from lzy_tpu.llm import metrics

        backend = _WindowBackend()
        backend.shed = {3, 5}
        backend.let_all_go()
        llm.configure(backend)
        retries = metrics.DISPATCH_RETRIES._values.get((), 0.0)
        gens = llm.generate_batch([[k] for k in range(8)],
                                  max_new_tokens=1, cache=False)
        assert [g.tokens for g in gens] == [[k] for k in range(8)]
        assert metrics.DISPATCH_RETRIES._values[()] == retries + 2
        from lzy_tpu.llm.sched import current_scheduler

        assert current_scheduler().stats()["row_window"] == 16
        assert backend.peak <= 16

    def test_rows_of_concurrent_batches_enter_in_hand_over_order(self):
        backend = _WindowBackend()
        sched = WorkflowScheduler(backend)
        a, b, c = range(10), range(100, 110), range(200, 204)
        outs = [[], [], []]
        ta = _map_in_thread(sched, a, outs[0])
        backend.wait_entered(10)
        tb = _map_in_thread(sched, b, outs[1])
        backend.wait_entered(16)               # b is handed over whole
        tc = _map_in_thread(sched, c, outs[2])
        assert _wait_until(lambda: sched.stats()["rows_waiting"] == 8)
        assert set(backend.entered[:10]) == set(a)
        assert set(backend.entered[10:]) == set(b[:6])
        for i, key in enumerate(list(a[:8])):  # one leaves, one enters
            backend.let_go(key)
            backend.wait_entered(17 + i)
        assert backend.entered[16:] == list(b[6:]) + list(c)
        assert backend.peak == 16
        backend.let_all_go()
        for t in (ta, tb, tc):
            t.join(30)
        assert [o[0] for o in outs] == [[[k] for k in keys]
                                        for keys in (a, b, c)]

    def test_first_exception_propagates_after_all_rows_settle(self):
        sched = WorkflowScheduler(backend=None)
        hold, failed, settled = threading.Event(), threading.Event(), []

        def row(i):
            try:
                if i == 1:
                    raise ValueError("row 1")
                if i == 3:
                    failed.set()
                    raise KeyError("row 3")
                assert hold.wait(30)
                return i
            finally:
                settled.append(i)

        out = []

        def run():
            try:
                out.append(sched.map(row, list(range(5))))
            except BaseException as e:  # noqa: BLE001
                out.append((e, sorted(settled)))

        t = threading.Thread(target=run)
        t.start()
        assert failed.wait(30)
        assert t.is_alive() and not out        # rows 0, 2, 4 are held
        hold.set()
        t.join(30)
        (err, seen), = out
        assert isinstance(err, ValueError) and seen == [0, 1, 2, 3, 4]

    def test_close_releases_waiting_rows(self):
        backend = _WindowBackend()
        sched = WorkflowScheduler(backend)
        out = []
        t = _map_in_thread(sched, range(20), out)
        backend.wait_entered(16)
        assert sched.stats()["rows_waiting"] == 4
        sched.close()
        backend.wait_entered(20)               # over the width: released
        assert backend.inside == 20 and sched.stats()["rows_waiting"] == 0
        backend.let_all_go()
        t.join(30)
        assert out == [[[k] for k in range(20)]]

    def test_window_holds_under_many_batches_and_a_moving_width(self):
        """Time-bounded stress: more batches than cores, a short switch
        interval, the reported slots changing between replies. No row
        is lost, none runs twice, and once the width has settled no more
        rows are inside than it allows."""
        import sys

        lock = threading.Lock()
        state = {"inside": 0, "peak_settled": 0, "calls": 0}

        class Plane:
            slots = 2

            def model_digest(self):
                return "stress"

            def generate(self, prompt, **kw):
                with lock:
                    state["inside"] += 1
                    state["calls"] += 1
                    if state["calls"] > 400:      # the width is 4 by now
                        state["peak_settled"] = max(state["peak_settled"],
                                                    state["inside"])
                    slots = 2 if state["calls"] > 300 else \
                        (1, 3, 5)[state["calls"] % 3]
                with lock:
                    state["inside"] -= 1
                return {"tokens": [prompt[0]], "status": "ok",
                        "plane_slots": slots}

        sched = WorkflowScheduler(Plane())
        outs, old = {}, sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            def batch(b):
                keys = [b * 100 + i for i in range(25)]
                outs[b] = (keys, sched.map(lambda k: sched.dispatch(
                    [k], max_new_tokens=1)["tokens"][0], keys))

            threads = [threading.Thread(target=batch, args=(b,))
                       for b in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert len(outs) == 24
        assert all(keys == got for keys, got in outs.values())
        assert state["calls"] == 600
        assert state["peak_settled"] <= _width_for(2)
        st = sched.stats()
        assert st["row_window"] == _width_for(2)
        assert st["rows_waiting"] == 0
        assert _wait_until(lambda: sched.stats()["rows_in_flight"] == 0)

    def test_counters_follow_the_window(self):
        from lzy_tpu.llm import metrics

        def count(window):
            key = (("window", str(window)),)
            counts = metrics.ROW_WINDOW_WAIT._counts.get(key)
            # (rows, rows that entered at once)
            return (counts[-1], counts[0]) if counts else (0, 0)

        backend = _WindowBackend()
        sched = WorkflowScheduler(backend)
        assert metrics.ROW_WINDOW._values[()] == 16
        before, in_flight = count(16), \
            metrics.ROWS_IN_FLIGHT._values.get((), 0.0)
        out = []
        t = _map_in_thread(sched, range(18), out)
        backend.wait_entered(16)
        assert _wait_until(lambda: metrics.ROWS_IN_FLIGHT._values[()]
                           == in_flight + 16)
        backend.let_all_go()
        t.join(30)
        rows, at_once = count(16)
        # 18 rows through a window of 16: two of them waited
        assert rows - before[0] == 18 and at_once - before[1] == 16
        assert _wait_until(lambda: metrics.ROWS_IN_FLIGHT._values[()]
                           == in_flight)
        backend.slots = 4
        sched.dispatch([1], max_new_tokens=1)
        assert metrics.ROW_WINDOW._values[()] == _width_for(4)


def _reply_surface(kind, cfg, params):
    """(generate, close, ready slots, waiter cap or None) of one surface
    that answers ``llm`` calls."""
    if kind == "gateway":
        def factory():
            return PagedInferenceEngine(cfg, params, slots=2,
                                        page_size=PAGE)

        fleet = ReplicaFleet(factory)
        gw = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                            model_name="tiny", max_waiters=3)
        for _ in range(2):
            fleet.add_replica()
        return gw.generate, gw.close, 4, 3
    if kind == "disagg":
        from lzy_tpu.gateway.disagg import DisaggGatewayService
        from lzy_tpu.serving.disagg import DecodeEngine, PrefillEngine

        decode = ReplicaFleet(
            lambda: DecodeEngine(cfg, params, slots=2, page_size=PAGE),
            replica_prefix="decode")
        prefill = ReplicaFleet(
            lambda: PrefillEngine(cfg, params, slots=2, page_size=PAGE),
            replica_prefix="prefill")
        gw = DisaggGatewayService(
            decode, prefill, page_size=PAGE,
            router=PrefixAffinityRouter(PAGE),
            prefill_router=PrefixAffinityRouter(PAGE),
            prefill_replicas=1, model_name="tiny", max_waiters=3)
        for _ in range(2):
            decode.add_replica()
        prefill.add_replica()
        return gw.generate, gw.close, 4, 3
    engine = PagedInferenceEngine(cfg, params, slots=3, page_size=PAGE)
    engine.start()
    if kind == "inference_service":
        from lzy_tpu.service.inference import InferenceService

        svc = InferenceService(engine, model_name="tiny", max_waiters=2)
        return svc.generate, svc.close, 3, 2
    backend = llm.EngineBackend(engine, model_name="tiny")
    return backend.generate, engine.close, 3, None


class TestPlaneCapacityInReplies:
    @pytest.mark.parametrize("kind", ["gateway", "disagg",
                                      "inference_service",
                                      "engine_backend"])
    def test_every_reply_says_what_the_plane_holds(self, tiny_model, kind):
        cfg, params = tiny_model
        generate, close, slots, waiters = _reply_surface(kind, cfg, params)
        try:
            reply = generate([5, 9, 3, 7], max_new_tokens=2, greedy=True)
            assert reply["status"] == "ok" and len(reply["tokens"]) == 2
            if waiters is None:
                assert reply["plane_slots"] == slots
                assert "plane_admits" not in reply
                return
            # a gated call: the ready replicas' slots, bounded by the
            # waiter cap, and the cap itself
            assert reply["plane_slots"] == min(slots, waiters)
            assert reply["plane_admits"] == waiters
            # a caller with a liveness probe holds no waiter
            reply = generate([5, 9, 3, 7], max_new_tokens=2, greedy=True,
                             liveness=lambda: True)
            assert reply["plane_slots"] == slots
            assert "plane_admits" not in reply
            # the window takes it from the reply, through a proxy that
            # only forwards generate
            sched = WorkflowScheduler(_ForwardOnly(generate))
            sched.dispatch([5, 9, 3, 7], max_new_tokens=2, greedy=True)
            assert sched.stats()["row_window"] == min(
                _width_for(min(slots, waiters)), waiters)
        finally:
            close()


class _ForwardOnly:
    """What the benchmark's proxy is to ``llm.configure``: ``generate``
    and a digest, no ``stats()``, ``fleet`` or ``engine``."""

    def __init__(self, generate):
        self._generate = generate

    def model_digest(self):
        return "forwarded"

    def generate(self, prompt, **kw):
        return self._generate(prompt, **{k: v for k, v in kw.items()
                                         if v is not None})

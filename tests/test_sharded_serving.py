"""Sharded gang replicas: bit-identity, fence contract, gang failover.

The acceptance bar for ``lzy_tpu/serving/sharded``: a 1×2 CPU-mesh gang
must be indistinguishable from the single-device ``PagedInferenceEngine``
through every contract the serving stack pins —

- **bit-identity** against both the ``generate()`` oracle and a
  single-device engine: greedy, sampled (same rng draw order), spec
  verify under forced full-acceptance/full-rejection, and chunked
  prefill. These strict bitwise tests run with ``dtype=float32``: the
  no-sharded-contractions placement keeps operand order exact, but under
  bf16 compute the differently-partitioned program fuses (and therefore
  rounds) at different points — 1-ULP logit noise that can flip argmax
  on near-ties. bf16 streams are pinned by the fixed-seed determinism
  test instead (see the ``partition`` module docstring);
- **one fence per round**: ``host_fetches`` advances by exactly 1 per
  steady-state decode round and the counting-``np`` shim sees no
  device→host conversion outside ``_fetch`` — the emit matrix is
  replicated before it crosses, so the gang pays the same single sync;
- **sharded pool, shared table**: per-shard occupancy is uniform by
  construction and the skew gauge reads 0;
- **cross-replica KV**: a gang's export stamps its mesh shape, imports
  are geometry-exact (fail closed into a differently-shaped pool),
  unsharded exports still import anywhere;
- **gang failure is whole-gang failure**: one dead host mid-stream fails
  the replica over with fenced tokens kept, through a mixed fleet of one
  gang and one single-device replica.
"""

import dataclasses
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.gateway import (
    GatewayService, PrefixAffinityRouter, ReplicaFleet, RoundRobinRouter)
from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving import engine as engine_mod
from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine
from lzy_tpu.serving.sharded import metrics as _m

# the module: ``lzy_tpu.ops`` exports the function under the same name
paged_attention = importlib.import_module("lzy_tpu.ops.paged_attention")

VOCAB = 64
PAGE = 16


PROMPTS = [
    [5, 9, 3, 7, 2],
    [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3, 4],
]


@pytest.fixture(scope="module")
def tiny_model():
    """f32 compute: the strict bitwise fixture (see module docstring).
    param_dtype is float32 either way, so the same param tree also
    drives the bf16-compute determinism test."""
    if len(jax.devices()) < 2:
        pytest.skip("sharded serving needs >= 2 devices")
    cfg = dataclasses.replace(LlamaConfig.tiny(vocab_size=VOCAB),
                              dtype=jnp.float32)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


def _oracle(cfg, params, prompt_ids, n, **kw):
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _drain(engine, reqs, rounds=800):
    for _ in range(rounds):
        if all(r.done for r in reqs):
            return
        engine.step()
    raise AssertionError("engine did not finish its requests")


def _run(engine, prompt, n):
    req = engine.submit(prompt, max_new_tokens=n)
    _drain(engine, [req])
    return req.result()


def _reach_steady_decode(eng, reqs, rounds=200):
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
    """Transfer shim: counts ``asarray``/``array`` calls whose argument
    is a device array — every device→host conversion in engine code."""

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


def _gang(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", PAGE)
    return ShardedPagedInferenceEngine(cfg, params, tp=2, **kw)


@pytest.fixture(scope="module")
def gang(tiny_model):
    """The shared 1×2 gang. prefill_chunk=8 so every prompt here takes
    the chunked-prefill path — chunking must change scheduling only."""
    cfg, params = tiny_model
    eng = _gang(cfg, params, prefill_chunk=8)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def baseline(tiny_model):
    """The single-device twin of ``gang`` (same slots/page/chunking)."""
    cfg, params = tiny_model
    eng = PagedInferenceEngine(cfg, params, slots=2, page_size=PAGE,
                               prefill_chunk=8)
    yield eng
    eng.close()


class TestConstruction:
    def test_tp_divisibility_gate(self, tiny_model):
        # tiny has n_kv_heads=2: a 1×4 gang would need padded kv-head
        # shards, which changes reduction extents — refused up front
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="not divisible by tp=4"):
            ShardedPagedInferenceEngine(cfg, params, tp=4)

    def test_gang_needs_tp_at_least_2(self, tiny_model):
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="tp >= 2"):
            ShardedPagedInferenceEngine(cfg, params, tp=1)

    def test_pallas_kernel_rejected(self, tiny_model):
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="pallas"):
            ShardedPagedInferenceEngine(cfg, params, tp=2,
                                        kernel="pallas")

    def test_auto_kernel_is_lax_in_a_gang(self, tiny_model, monkeypatch):
        """The Pallas decode kernel is a custom call GSPMD cannot
        partition: a gang's ``"auto"`` is the lax read, where a solo
        engine's is the kernel on a TPU."""
        cfg, params = tiny_model
        monkeypatch.setattr(paged_attention, "default_kernel",
                            lambda: "pallas")
        eng = ShardedPagedInferenceEngine(
            cfg, params, tp=2, slots=2, page_size=PAGE, kernel="auto")
        try:
            assert eng.kernel_path == "lax"
            assert eng.stats().kernel_path == "lax"
        finally:
            eng.close()

    def test_default_arguments_count_dispatches_under_lax(
            self, tiny_model, monkeypatch):
        """No keyword at all, on a platform whose ``"auto"`` is the
        kernel: every program a gang dispatches (prefill chunk, decode
        steps) is counted under ``lax``, none under ``pallas``."""
        DISPATCHES = paged_attention.DISPATCHES

        def count(path):
            return sum(v for key, v in DISPATCHES._values.items()
                       if ("path", path) in key)

        cfg, params = tiny_model
        monkeypatch.setattr(paged_attention, "default_kernel",
                            lambda: "pallas")
        eng = ShardedPagedInferenceEngine(cfg, params, slots=2)
        try:
            before = {p: count(p) for p in ("lax", "pallas")}
            assert _run(eng, PROMPTS[0], 6) == _oracle(
                cfg, params, PROMPTS[0], 6)
            assert count("lax") - before["lax"] >= 1 + 5
            assert count("pallas") == before["pallas"]
        finally:
            eng.close()


class TestBitIdentity:
    def test_greedy_matches_oracle_and_single_engine(
            self, tiny_model, gang, baseline):
        cfg, params = tiny_model
        for prompt in PROMPTS:
            exp = _oracle(cfg, params, prompt, 24)
            assert _run(baseline, prompt, 24) == exp
            assert _run(gang, prompt, 24) == exp

    def test_chunked_prefill_long_prompt(self, tiny_model, gang,
                                         baseline):
        # 20 prompt tokens through prefill_chunk=8 → a 3-chunk plan on
        # both engines; the oracle prefills one-shot — all three equal
        cfg, params = tiny_model
        prompt = list(range(1, 21))
        exp = _oracle(cfg, params, prompt, 12)
        assert _run(baseline, prompt, 12) == exp
        assert _run(gang, prompt, 12) == exp

    def test_sampled_rng_draw_order_matches_single_engine(
            self, tiny_model):
        cfg, params = tiny_model
        kw = dict(temperature=0.8, top_k=20, seed=7)
        solo = PagedInferenceEngine(cfg, params, slots=2,
                                    page_size=PAGE, **kw)
        eng = _gang(cfg, params, **kw)
        try:
            for prompt in ([5, 9, 3], [2, 4, 6, 8]):
                assert _run(eng, prompt, 12) == _run(solo, prompt, 12)
        finally:
            solo.close()
            eng.close()

    @pytest.mark.parametrize("accept", [True, False])
    def test_spec_verify_matches_oracle(self, tiny_model, accept):
        cfg, params = tiny_model
        n, gamma = 24, 3
        prompt = PROMPTS[1]
        exp = _oracle(cfg, params, prompt, n)
        cls = _OracleProposer if accept else _AdversarialProposer
        eng = _gang(cfg, params, spec_tokens=gamma,
                    proposer=cls([prompt + exp], gamma))
        try:
            req = eng.submit(prompt, max_new_tokens=n)
            _drain(eng, [req])
            assert req.result() == exp
            s = eng.stats()
            if accept:
                assert s.spec_acceptance_rate == 1.0
                assert eng.decode_steps < n - 1
            else:
                assert s.spec_proposed_tokens > 0
                assert s.spec_accepted_tokens == 0
        finally:
            eng.close()

    def test_bf16_stream_fixed_seed_deterministic(self, tiny_model):
        """The bf16 half of the contract: strict cross-program identity
        is out of reach (fusion-boundary rounding), but one gang's
        stream is deterministic — a re-run of the same prompt (now on
        the radix-cached prefix path) reproduces it bit-for-bit."""
        _, params = tiny_model
        cfg = LlamaConfig.tiny(vocab_size=VOCAB)   # bf16 compute
        eng = _gang(cfg, params)
        try:
            first = _run(eng, PROMPTS[0], 16)
            assert _run(eng, PROMPTS[0], 16) == first
        finally:
            eng.close()


class TestOneFencePerRound:
    def test_one_fetch_per_steady_decode_round(self, gang):
        reqs = [gang.submit(p, max_new_tokens=40) for p in PROMPTS]
        _reach_steady_decode(gang, reqs)
        for _ in range(8):
            before = gang.host_fetches
            assert gang.step()
            assert gang.host_fetches == before + 1
        _drain(gang, reqs)

    def test_shim_sees_no_fetch_outside_the_fence(self, gang,
                                                  monkeypatch):
        reqs = [gang.submit(p, max_new_tokens=40) for p in PROMPTS]
        _reach_steady_decode(gang, reqs)
        shim = _CountingNp(np)
        monkeypatch.setattr(engine_mod, "np", shim)
        rounds = 8
        before = gang.host_fetches
        for _ in range(rounds):
            assert gang.step()
        assert gang.host_fetches - before == rounds
        assert shim.device_fetches == rounds
        monkeypatch.undo()
        _drain(gang, reqs)

    def test_shard_occupancy_uniform_and_skew_zero(self, gang):
        reqs = [gang.submit(p, max_new_tokens=8) for p in PROMPTS]
        _reach_steady_decode(gang, reqs)
        occ = gang.shard_occupancy()
        assert len(occ) == 2
        assert occ[0] == occ[1] > 0
        gang.stats()                       # refreshes the gauges
        key = (("mesh", "1x2"),)
        assert _m.SHARD_SKEW._values[key] == 0.0
        assert _m.SHARD_KV_BLOCKS._values[key + (("shard", "0"),)] \
            == float(occ[0])
        _drain(gang, reqs)


class TestShardedKVTransfer:
    def test_gang_export_is_geometry_stamped_and_exact(
            self, tiny_model, gang, baseline):
        """A gang's KV export names its pool geometry; a same-shape gang
        imports it and serves the continuation bit-identically, while a
        differently-shaped pool fails closed (import skipped, local
        re-prefill — never garbage)."""
        cfg, params = tiny_model
        prompt = [3, 1, 4, 1, 5, 9, 2, 6] * 4          # 2 full pages
        out = _run(gang, prompt, 8)
        export = gang.kv_io.export_kv(prompt)
        assert export is not None
        assert tuple(export.mesh_shape) == (1, 2)
        assert export.n_blocks == 2

        # geometry-exact import into a fresh 1×2 gang
        sibling = _gang(cfg, params)
        try:
            assert sibling.kv_io.import_kv(export) == 2
            assert _run(sibling, prompt, 8) == out
        finally:
            sibling.close()

        # fail closed into the single-device pool (mesh (1,2) ≠ none)
        assert baseline.kv_io.import_kv(export) == 0
        # ...which costs nothing but a local re-prefill
        assert _run(baseline, prompt, 8) == out

    def test_unsharded_export_imports_into_a_gang(self, tiny_model,
                                                  baseline):
        """mesh_shape=None manifests predate gangs and import anywhere:
        the scatter follows the destination pool's placement."""
        cfg, params = tiny_model
        prompt = [7, 7, 2, 9, 1, 8, 3, 5] * 4
        out = _run(baseline, prompt, 8)
        export = baseline.kv_io.export_kv(prompt)
        assert export is not None and export.mesh_shape is None
        eng = _gang(cfg, params)
        try:
            assert eng.kv_io.import_kv(export) == 2
            assert _run(eng, prompt, 8) == out
        finally:
            eng.close()


def _mixed_gateway(cfg, params, *, kinds, router=None, **engine_kw):
    """A fleet mixing gang and single-device replicas: ``kinds`` is the
    factory schedule, one entry per ``add_replica`` in order."""
    schedule = iter(kinds)

    def factory():
        if next(schedule) == "gang":
            return _gang(cfg, params, **engine_kw)
        return PagedInferenceEngine(cfg, params, slots=2,
                                    page_size=PAGE, **engine_kw)

    fleet = ReplicaFleet(factory, start_engines=True)
    gw = GatewayService(fleet, router=router or RoundRobinRouter(),
                        model_name="tiny")
    for _ in kinds:
        fleet.add_replica()
    return gw, fleet


class TestMixedFleet:
    def test_routing_across_gang_and_single_device(self, tiny_model):
        """One gang + one single-device replica behind one gateway:
        round-robin routing lands requests on both, and every reply is
        bit-identical to the oracle regardless of which served it."""
        cfg, params = tiny_model
        gw, fleet = _mixed_gateway(cfg, params, kinds=("gang", "single"))
        try:
            gangs = {r.id for r in fleet.replicas()
                     if getattr(r.engine, "gang_size", 1) > 1}
            assert len(gangs) == 1
            served = set()
            for i in range(4):
                prompt = [3 + i, 5, 7]
                res = gw.generate(prompt, max_new_tokens=6,
                                  timeout_s=120)
                assert res["status"] == "ok" and res["failovers"] == 0
                assert res["tokens"] == _oracle(cfg, params, prompt, 6)
                served.add(res["replica"])
            assert len(served) == 2        # both replica kinds served
        finally:
            gw.close()

    def test_gang_host_death_mid_stream_fails_over_whole(
            self, tiny_model):
        """Kill ONE shard host of the gang mid-decode: the whole gang
        dies (no partial-gang mode), the stream fails over to the
        single-device sibling with the fenced tokens kept, and the
        gang-failover counter ticks."""
        cfg, params = tiny_model
        gw, fleet = _mixed_gateway(cfg, params, kinds=("gang", "single"))
        result = {}

        def run():
            try:
                result["res"] = gw.generate([7, 2, 8, 1],
                                            max_new_tokens=24,
                                            timeout_s=120)
            except BaseException as e:
                result["err"] = e

        failovers_before = _m.GANG_FAILOVERS._values.get((), 0.0)
        try:
            t = threading.Thread(target=run)
            t.start()
            victim = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                for replica in fleet.replicas():
                    if getattr(replica.engine, "gang_size", 1) <= 1:
                        continue
                    live = [r for r in replica.engine._active
                            if r is not None]
                    if live and len(live[0].tokens) >= 3:
                        victim = replica
                        break
                if victim:
                    break
                time.sleep(0.005)
            assert victim is not None, \
                "request never reached mid-decode on the gang"

            victim.engine.mark_host_dead(0, "host unreachable")
            assert victim.engine.gang_intact is False
            t.join(120)
            assert "err" not in result, result.get("err")
            res = result["res"]
            assert res["tokens"] == _oracle(cfg, params, [7, 2, 8, 1], 24)
            assert res["failovers"] == 1 and res["status"] == "ok"
            # the whole gang retired; only the single-device replica is
            # left routing
            ids = [r.id for r in fleet.replicas()]
            assert victim.id not in ids and len(ids) == 1
            assert _m.GANG_FAILOVERS._values.get((), 0.0) == \
                failovers_before + 1.0
        finally:
            gw.close()

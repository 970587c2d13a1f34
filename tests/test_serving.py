"""Serving-path tests: batched prefill parity and continuous batching.

The batched prefill is an optimization with an in-tree oracle — the
original one-device-call-per-token loop is kept as ``prefill="sequential"``
— so parity is asserted token-for-token, greedy AND sampled (the batched
path must advance the rng stream in lockstep with the oracle's per-token
sample-and-discard). The engine tests drive ``PagedInferenceEngine.step()``
synchronously so admission order is deterministic: requests join a LIVE
decode batch mid-flight, leave on completion, and each one's tokens must
match a solo ``generate()`` run bit-for-bit (any cross-request leakage
through the shared slot cache would break that).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import generate, prefill_plan
from lzy_tpu.models.llama import LlamaConfig
from lzy_tpu.serving import AdmissionError, PagedInferenceEngine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


class TestPrefillPlan:
    def test_pass_count_and_coverage(self):
        for t0 in (1, 5, 8, 13, 64, 200):
            plan = prefill_plan(t0, chunk=64, max_seq_len=256)
            assert len(plan) <= math.ceil(t0 / 64)
            assert sum(take for _, take, _ in plan) == t0
            starts = [start for start, _, _ in plan]
            assert starts == sorted(starts)

    def test_padded_write_never_spills_past_max_seq_len(self):
        # a prompt ending near the cache edge must not pad past it:
        # dynamic_update_slice would clamp the start and clobber real rows
        plan = prefill_plan(250, chunk=64, max_seq_len=256)
        for start, take, width in plan:
            assert take <= width
            assert start + width <= 256

    def test_bounded_shape_set(self):
        widths = {w for t0 in range(1, 200)
                  for _, _, w in prefill_plan(t0, chunk=64, max_seq_len=512)}
        assert len(widths) <= 5  # buckets 8/16/32/64 — not one shape per t0


class TestBatchedPrefillParity:
    @pytest.mark.parametrize("t0", [1, 3, 11, 40])
    def test_greedy_matches_sequential_oracle(self, tiny_model, t0):
        cfg, params = tiny_model
        prompt = jax.random.randint(
            jax.random.PRNGKey(3), (2, t0), 0, cfg.vocab_size)
        ref = generate(cfg, params, prompt, max_new_tokens=6,
                       prefill="sequential")
        out = generate(cfg, params, prompt, max_new_tokens=6,
                       prefill="batched", prefill_chunk=8)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("t0", [3, 11])
    def test_sampled_matches_sequential_oracle(self, tiny_model, t0):
        cfg, params = tiny_model
        prompt = jax.random.randint(
            jax.random.PRNGKey(4), (2, t0), 0, cfg.vocab_size)
        kw = dict(max_new_tokens=6, temperature=0.8, top_k=20)
        ref = generate(cfg, params, prompt, rng=jax.random.PRNGKey(7),
                       prefill="sequential", **kw)
        out = generate(cfg, params, prompt, rng=jax.random.PRNGKey(7),
                       prefill="batched", prefill_chunk=8, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_rejects_unknown_prefill_mode(self, tiny_model):
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="prefill"):
            generate(cfg, params, jnp.zeros((1, 2), jnp.int32),
                     max_new_tokens=1, prefill="turbo")


class TestEarlyExit:
    def test_early_exit_output_identical(self, tiny_model):
        """Pick whatever token greedy decode emits first and declare it
        eos: every sequence is then done after one step, and the
        early-exit path must still return the exact padded output the
        full-length loop does."""
        cfg, params = tiny_model
        prompt = jnp.array([[5, 9, 3], [7, 2, 8]], jnp.int32)
        probe = generate(cfg, params, prompt, max_new_tokens=1)
        eos = int(probe[0, -1])
        full = generate(cfg, params, prompt, max_new_tokens=32,
                        eos_token=eos, eos_check_every=0)
        early = generate(cfg, params, prompt, max_new_tokens=32,
                         eos_token=eos, eos_check_every=4)
        np.testing.assert_array_equal(np.asarray(early), np.asarray(full))
        # row 0 hit eos immediately, so its tail is pure eos padding
        assert np.all(np.asarray(early)[0, 3:] == eos)

    def test_early_exit_skips_device_steps(self, tiny_model, monkeypatch):
        cfg, params = tiny_model
        prompt = jnp.array([[5, 9, 3]], jnp.int32)
        eos = int(generate(cfg, params, prompt, max_new_tokens=1)[0, -1])
        calls = {"n": 0}
        orig = jax.jit

        def counting_jit(fn, **kw):
            jitted = orig(fn, **kw)

            def wrapper(*a, **k):
                calls["n"] += 1
                return jitted(*a, **k)

            return wrapper

        monkeypatch.setattr(jax, "jit", counting_jit)
        out = generate(cfg, params, prompt, max_new_tokens=64,
                       eos_token=eos, eos_check_every=2)
        assert out.shape == (1, 3 + 64)
        # prefill chunk + the eos check window — nowhere near 64 steps
        assert calls["n"] < 16


def _oracle_tokens(cfg, params, prompt_ids, n):
    """Solo greedy generate() continuation for one prompt (generated ids
    only) — what the engine must reproduce for that request regardless of
    what else shares the decode batch."""
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


class TestInferenceEngine:
    def test_staggered_requests_share_the_decode_batch(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2)
        a = eng.submit([5, 9, 3], max_new_tokens=12)
        eng.step()            # admits A (prefill emits token 1) + 1 decode
        eng.step()
        assert not a.done and len(a.tokens) >= 2
        # B arrives mid-decode: it must start generating on the very next
        # step, not after A drains
        b = eng.submit([7, 2, 8, 1, 4], max_new_tokens=4)
        eng.step()
        assert len(b.tokens) >= 1, "B waited for the running batch to drain"
        assert not a.done, "A should still be mid-flight when B joins"
        for _ in range(40):
            if a.done and b.done:
                break
            eng.step()
        assert a.result(0) == _oracle_tokens(cfg, params, a.prompt, 12)
        assert b.result(0) == _oracle_tokens(cfg, params, b.prompt, 4)

    def test_freed_slot_is_reused_without_leakage(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        a = eng.submit([5, 9, 3], max_new_tokens=3)
        for _ in range(10):
            if a.done:
                break
            eng.step()
        assert a.done
        # C lands in the slot A just vacated; a stale index or unmasked
        # cache row from A would corrupt C's continuation
        c = eng.submit([7, 2, 8, 1], max_new_tokens=5)
        for _ in range(10):
            if c.done:
                break
            eng.step()
        assert c.result(0) == _oracle_tokens(cfg, params, c.prompt, 5)

    def test_eos_frees_the_slot(self, tiny_model):
        cfg, params = tiny_model
        prompt = [5, 9, 3]
        first = _oracle_tokens(cfg, params, prompt, 1)[0]
        eng = PagedInferenceEngine(cfg, params, slots=2, eos_token=first)
        r = eng.submit(prompt, max_new_tokens=16)
        eng.step()
        assert r.done and r.result(0) == [first]
        assert eng.stats().busy == 0

    def test_cancelled_request_frees_its_slot(self, tiny_model):
        """An abandoned waiter (client timeout) must not keep burning
        decode steps: a cancelled slot-resident request is reaped at the
        next scheduling round, a cancelled queued one is dropped at pop."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        a = eng.submit([5, 9, 3], max_new_tokens=50)
        queued = eng.submit([1, 2], max_new_tokens=50)
        eng.step()
        assert eng.stats().busy == 1
        a.cancel()
        queued.cancel()
        live = eng.submit([7, 2, 8], max_new_tokens=4)
        for _ in range(10):
            if live.done:
                break
            eng.step()
        assert a.done and a.error == "cancelled"
        assert queued.done and queued.error == "cancelled"
        n_before = len(a.tokens)
        eng.step()
        assert len(a.tokens) == n_before  # no tokens after cancellation
        assert live.result(0) == _oracle_tokens(cfg, params, live.prompt, 4)

    def test_admission_backpressure(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, max_queue=1)
        eng.submit([1, 2], max_new_tokens=2)
        with pytest.raises(AdmissionError):
            eng.submit([3, 4], max_new_tokens=2)

    def test_invalid_requests_rejected(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        with pytest.raises(ValueError, match="non-empty|empty"):
            eng.submit([], max_new_tokens=2)
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit([1] * 10, max_new_tokens=cfg.max_seq_len)

    def test_background_loop_and_stats(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2).start()
        try:
            reqs = [eng.submit([3 + i, 5, 7], max_new_tokens=4)
                    for i in range(3)]
            outs = [r.result(timeout=60) for r in reqs]
        finally:
            eng.close()
        for i, out in enumerate(outs):
            assert out == _oracle_tokens(cfg, params, [3 + i, 5, 7], 4)
        s = eng.stats()
        assert s.requests_finished == 3
        assert s.tokens_generated == 12

    def test_submit_after_close_fails_fast(self, tiny_model):
        """Shutdown stops the engine before the RPC server, so a submit can
        arrive in the gap: it must get retryable backpressure immediately,
        not sit in a queue no loop will ever drain until the RPC timeout."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1).start()
        eng.close()
        with pytest.raises(AdmissionError, match="shut down"):
            eng.submit([1, 2], max_new_tokens=2)

    def test_loop_death_fails_outstanding_requests(self, tiny_model,
                                                   monkeypatch):
        """An engine-fatal step() error (device OOM, poisoned compile) must
        fail every outstanding request and refuse new admissions — not die
        silently while waiters burn their full timeouts."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1)
        req = eng.submit([5, 9, 3], max_new_tokens=8)
        monkeypatch.setattr(
            eng, "step",
            lambda: (_ for _ in ()).throw(RuntimeError("device on fire")))
        eng.start()
        with pytest.raises(RuntimeError, match="engine loop died"):
            req.result(timeout=30)
        with pytest.raises(AdmissionError):
            eng.submit([1], max_new_tokens=1)

    def test_metrics_exported_in_registry(self, tiny_model):
        from lzy_tpu.utils.metrics import REGISTRY

        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2)
        r = eng.submit([5, 9], max_new_tokens=3)
        while not r.done:
            eng.step()
        text = REGISTRY.exposition()
        for name in ("lzy_inference_ttft_seconds",
                     "lzy_inference_tokens_total",
                     "lzy_inference_slots_busy",
                     "lzy_inference_queue_depth",
                     "lzy_inference_tokens_per_s"):
            assert name in text


class TestInferenceRpc:
    def test_generate_and_stats_over_the_control_plane(
            self, tiny_model, tmp_path):
        from lzy_tpu.rpc import RpcInferenceClient
        from lzy_tpu.service import InProcessCluster
        from lzy_tpu.service.inference import InferenceService

        cfg, params = tiny_model
        engine = PagedInferenceEngine(cfg, params, slots=2).start()
        cluster = InProcessCluster(
            db_path=str(tmp_path / "meta.db"),
            storage_uri=f"file://{tmp_path}/storage",
            worker_mode="process",
            inference_service=InferenceService(engine, model_name="tiny"),
        )
        try:
            client = RpcInferenceClient(cluster.rpc_server.address)
            try:
                res = client.generate([5, 9, 3], max_new_tokens=4,
                                      timeout_s=60)
                assert res["model"] == "tiny"
                assert res["tokens"] == _oracle_tokens(
                    cfg, params, [5, 9, 3], 4)
                assert res["ttft_ms"] is not None
                stats = client.stats()
                assert stats["requests_finished"] >= 1
            finally:
                client.close()
        finally:
            cluster.shutdown()


# -- the model seam (models/serving.py) ---------------------------------------


def _documented_names():
    """Every name ``models/serving.py`` documents as a bullet of its
    protocol (``- ``name(...)``: ...``) or in its opening sentence on the
    configuration object's plain fields."""
    import re

    from lzy_tpu.models import serving

    doc = serving.__doc__.split("**The module class**")[0]
    fields = re.search(r"gives (.*?) \(the paged pool", doc, re.S).group(1)
    names = re.findall(r"``(\w+)``", fields)
    names += re.findall(r"^- ``(\w+)", doc, re.M)
    return names


class TestModelSeam:
    @pytest.mark.parametrize("family", ["llama", "nemotron_h"])
    def test_every_documented_name_is_answered(self, family):
        """The protocol asks nothing its implementers refuse: every name
        the module documents for the configuration object is an attribute
        of both families' (a method, a property or a field), and each
        builds its paged module and declares its cache kinds."""
        from lzy_tpu.models import nemotron_h, serving

        cfg = (LlamaConfig.tiny() if family == "llama"
               else nemotron_h.NemotronHConfig.tiny())
        names = _documented_names()
        assert {"serving_config", "paged_model", "kv_layers", "kernel_paths",
                "check_kernels", "max_seq_len", "head_dim"} <= set(names)
        assert "dense_models" not in names
        for name in names:
            assert hasattr(cfg, name), f"{type(cfg).__name__}.{name}"
        module = cfg.serving_config().paged_model(
            page_size=16, kv_pages=4, kernel="lax", kv_quant=None)
        assert isinstance(type(module).CACHE_KINDS, dict)
        assert isinstance(tuple(type(module).STATS), tuple)
        assert set(type(module).CACHE_KINDS.values()) <= {
            serving.INDEX, serving.PAGED, serving.STATE}

    def test_the_engine_names_no_model(self):
        """``serving/engine.py`` holds one engine class and imports no
        model family: what it needs it asks of the configuration."""
        import ast
        import inspect

        from lzy_tpu.serving import engine

        tree = ast.parse(inspect.getsource(engine))
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                imported.add(node.module)
                imported.update(f"{node.module}.{a.name}"
                                for a in node.names)
            elif isinstance(node, ast.Import):
                imported.update(a.name for a in node.names)
        for family in ("llama", "nemotron_h", "moe", "t5", "bert"):
            assert f"lzy_tpu.models.{family}" not in imported
        engines = [n.name for n in tree.body
                   if isinstance(n, ast.ClassDef)
                   and n.name.endswith("InferenceEngine")]
        assert engines == ["PagedInferenceEngine"]
        assert engine.PagedInferenceEngine.__mro__[1] is object

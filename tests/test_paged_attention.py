"""Native paged-attention kernels + int8 KV quantization (PR 9).

Three layers of coverage, matching the module's correctness contract
(``lzy_tpu/ops/paged_attention.py``, docs/serving.md "Native paged
attention & KV quantization"):

- **Op-level bit-exactness sweeps**: the lax gather-attention fallback
  and the Pallas kernel (interpret mode on CPU) must produce EXACTLY the
  same bytes across page sizes, ragged per-row lengths, scratch-block
  idle rows, chunk widths (1-token decode, gamma+1 verify windows,
  prefill chunks), dtypes, and quantization on/off. "Close" is not a
  pass: the serving stack's oracle chain (paged == dense == generate())
  is built on bit-identity, and the native path joins that chain.
- **Model/engine-level oracle tests**: a ``PagedInferenceEngine`` with
  ``native_attention=True`` must be bit-identical to the solo
  ``generate()`` oracle — greedy and sampled, speculation on and off —
  because the lax kernel reproduces the legacy gather math op for op.
- **int8 bounded divergence**: quantized output is intentionally NOT
  bit-identical; what IS asserted: the per-element dequantization error
  bound (one optimal-scale quantization step), kernel-independence of
  quantized output (legacy == lax == pallas on the same int8 pool),
  greedy-match rate against the fp oracle over long continuations, pool
  integrity (no leaked/corrupted blocks under quantization), and the 2x
  block-count win at a fixed pool byte budget.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.models import llama, unbox
from lzy_tpu.models.generate import decode_config, generate, init_cache
from lzy_tpu.models.llama import Llama, LlamaConfig
from lzy_tpu.ops.paged_attention import (
    DEQUANT_ERROR_EWMA, KVQuant, default_kernel, dequantize_kv,
    note_dequant_error, paged_attention, quantize_kv)
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.kv_cache import (
    blocks_for_bytes, kv_block_bytes, kv_quant_sidecar_bytes)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=64)
    boxed, _ = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, unbox(boxed)


def _oracle_tokens(cfg, params, prompt_ids, n, **kw):
    out = generate(cfg, params, jnp.asarray([prompt_ids], jnp.int32),
                   max_new_tokens=n, **kw)
    return np.asarray(out)[0, len(prompt_ids):].tolist()


def _drive(eng, *reqs, rounds=400):
    for _ in range(rounds):
        if all(r.done for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _metric_value(metric) -> float:
    """Sum over all label combinations of a process-registry metric."""
    return sum(metric._values.values())


# -- quantizer units ---------------------------------------------------------


class TestQuantizeKV:
    def test_error_bounded_by_one_step(self):
        rng = np.random.default_rng(0)
        for scale_exp in (-3, 0, 4):          # tiny, unit, large ranges
            x = jnp.asarray(
                rng.standard_normal((64, 3, 16)) * 10.0 ** scale_exp,
                jnp.float32)
            q, s, z = quantize_kv(x)
            deq = dequantize_kv(q, s, z, jnp.float32)
            span = (jnp.max(x, -1) - jnp.min(x, -1))[..., None]
            # one exactly-representable step of the OPTIMAL scale (the
            # pow2 rounding costs at most a factor 2 over half a step)
            bound = span / 254.0 + 1e-6
            assert bool(jnp.all(jnp.abs(deq - x) <= bound))

    def test_scales_are_powers_of_two(self):
        x = jnp.asarray(
            np.random.default_rng(1).standard_normal((32, 2, 8)),
            jnp.float32)
        _, s, _ = quantize_kv(x)
        log = np.log2(np.asarray(s))
        assert np.allclose(log, np.round(log)), \
            "pow2 scales are what make dequantization FMA-invariant"

    def test_constant_vectors_near_exact(self):
        x = jnp.full((4, 2, 8), 3.25, jnp.float32)
        q, s, z = quantize_kv(x)
        deq = dequantize_kv(q, s, z, jnp.float32)
        assert bool(jnp.all(jnp.abs(deq - x) <= 1e-6))
        assert bool(jnp.all(q == 0))

    def test_ewma_gauge_updates(self):
        v1 = note_dequant_error(0.5)
        v2 = note_dequant_error(0.1)
        assert v2 < v1
        assert _metric_value(DEQUANT_ERROR_EWMA) == pytest.approx(v2)


# -- op-level bit-exactness sweeps -------------------------------------------


def _random_case(rng, *, page, pages, b, t, kv, g, d, dtype, quant):
    """One randomized paged-attention problem with the serving stack's
    real shapes: shuffled block ownership, a row parked on the scratch
    block at position 0 (the idle-slot case), ragged per-row positions,
    and tables whose tail entries are scratch (partially-grown rows)."""
    n = b * pages + 3
    L = pages * page
    q = jnp.asarray(rng.standard_normal((b, t, kv * g, d)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((n, page, kv, d)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((n, page, kv, d)), dtype)
    ids = rng.permutation(np.arange(1, n))[: b * pages]
    pt = ids.reshape(b, pages).astype(np.int32)
    pt[0, pages // 2:] = 0                    # partially-grown row
    starts = rng.integers(0, L - t, size=(b,)).astype(np.int32)
    starts[0] = 0                             # idle row on scratch
    pos = jnp.asarray(starts[:, None] + np.arange(t)[None, :], jnp.int32)
    quant_side = None
    if quant:
        k_pool, ks, kz = quantize_kv(k_pool)
        v_pool, vs, vz = quantize_kv(v_pool)
        quant_side = KVQuant(ks, kz, vs, vz)
    return q, k_pool, v_pool, jnp.asarray(pt), pos, quant_side


class TestKernelBitExactness:
    @pytest.mark.parametrize("page,pages", [(4, 8), (8, 4), (16, 3)])
    @pytest.mark.parametrize("t", [1, 5])
    @pytest.mark.parametrize("quant", [False, True])
    def test_pallas_interpret_equals_lax(self, page, pages, t, quant):
        rng = np.random.default_rng(page * 100 + t)
        for dtype in (jnp.bfloat16, jnp.float32):
            q, kp, vp, pt, pos, side = _random_case(
                rng, page=page, pages=pages, b=3, t=t, kv=2, g=2, d=16,
                dtype=dtype, quant=quant)
            a = paged_attention(q, kp, vp, pt, pos, kernel="lax",
                                dtype=dtype, quant=side)
            p = paged_attention(q, kp, vp, pt, pos, kernel="pallas",
                                dtype=dtype, quant=side, interpret=True)
            if quant and dtype == jnp.float32:
                # the one case that is not bitwise: the dequantised float32
                # values go through a [T*G, L] x [L, D] contraction whose
                # summation order the CPU backend picks per program, and
                # interpreted Pallas and op-by-op lax are two programs
                # (seen: 1.2e-7 on outputs of order 1). Four float32 ulps
                # at the output's scale; bf16 rounds the difference away.
                a, p = np.asarray(a), np.asarray(p)
                atol = 4 * np.finfo(np.float32).eps * max(
                    1.0, float(np.abs(a).max()))
                np.testing.assert_allclose(p, a, rtol=0, atol=atol)
                continue
            assert bool(jnp.array_equal(a, p)), \
                f"pallas != lax at dtype={dtype} quant={quant}"

    def test_exact_under_jit_and_odd_head_dim(self):
        # the engine runs the op inside jitted programs; fusion must not
        # perturb the identity (d=24: a head dim whose softmax scale is
        # not a power of two)
        import functools

        rng = np.random.default_rng(7)
        q, kp, vp, pt, pos, side = _random_case(
            rng, page=8, pages=4, b=2, t=3, kv=2, g=3, d=24,
            dtype=jnp.bfloat16, quant=True)
        f_lax = jax.jit(functools.partial(
            paged_attention, kernel="lax", dtype=jnp.bfloat16, quant=side))
        f_pal = jax.jit(functools.partial(
            paged_attention, kernel="pallas", dtype=jnp.bfloat16,
            quant=side, interpret=True))
        assert bool(jnp.array_equal(f_lax(q, kp, vp, pt, pos),
                                    f_pal(q, kp, vp, pt, pos)))

    def test_pallas_rejects_vmem_oversized_pools(self):
        """An HBM-sized pool must fail the pallas path at TRACE time
        with an actionable error (warmup AOT-compiles, so this lands at
        boot), not as a Mosaic compile failure mid-serving."""
        big = jax.ShapeDtypeStruct((200_000, 64, 2, 128), jnp.bfloat16)
        q = jax.ShapeDtypeStruct((1, 1, 4, 128), jnp.bfloat16)
        pt = jax.ShapeDtypeStruct((1, 16), jnp.int32)
        pos = jax.ShapeDtypeStruct((1, 1), jnp.int32)
        with pytest.raises(ValueError, match="VMEM"):
            jax.eval_shape(
                lambda *a: paged_attention(*a, kernel="pallas",
                                           interpret=False),
                q, big, big, pt, pos)

    def test_unknown_kernel_and_missing_dtype_rejected(self):
        rng = np.random.default_rng(3)
        q, kp, vp, pt, pos, side = _random_case(
            rng, page=4, pages=2, b=1, t=1, kv=1, g=1, d=8,
            dtype=jnp.float32, quant=True)
        with pytest.raises(ValueError, match="unknown"):
            paged_attention(q, kp, vp, pt, pos, kernel="cuda",
                            dtype=jnp.float32, quant=side)
        with pytest.raises(ValueError, match="dtype"):
            paged_attention(q, kp, vp, pt, pos, quant=side)


class TestModelPathBitExactness:
    """The three read paths of ``Attention._decode_step`` — legacy
    gather, native lax, native pallas — through the REAL model forward:
    prefill chunks, 1-token decode, and a gamma+1 verify window over
    interleaved per-row positions."""

    def _run_path(self, tiny_model, **over):
        cfg0, params = tiny_model
        B, page = 3, 8
        pages = cfg0.max_seq_len // page
        n = B * pages + 1
        pt = jnp.arange(1, B * pages + 1, dtype=jnp.int32).reshape(
            B, pages)
        dcfg = dataclasses.replace(
            decode_config(cfg0), decode_paged=True, kv_page_size=page,
            kv_pages=n, **over)
        model = Llama(dcfg)
        cache = init_cache(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32),
            page_table=pt))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            1, cfg0.vocab_size, (B, 6)), jnp.int32)
        outs = []
        # prefill chunk (t=6) → decode step (t=1) → verify window (t=6)
        for chunk in (toks, toks[:, :1], toks):
            logits, upd = model.apply(
                {"params": params, "cache": cache}, chunk,
                page_table=pt, mutable=["cache"])
            cache = upd["cache"]
            outs.append(logits)
        return outs

    def test_native_lax_bit_identical_to_legacy(self, tiny_model):
        legacy = self._run_path(tiny_model)
        native = self._run_path(tiny_model, paged_attention_native=True,
                                paged_kernel="lax")
        for a, b in zip(legacy, native):
            assert bool(jnp.array_equal(a, b))

    def test_native_pallas_bit_identical_to_legacy(self, tiny_model):
        legacy = self._run_path(tiny_model)
        native = self._run_path(tiny_model, paged_attention_native=True,
                                paged_kernel="pallas")
        for a, b in zip(legacy, native):
            assert bool(jnp.array_equal(a, b))

    def test_quantized_output_is_kernel_independent(self, tiny_model):
        """int8 output diverges boundedly from fp but must NOT depend on
        which kernel read the pool — legacy gather+dequant, lax, and
        pallas all dequantize with the same (FMA-invariant) formula."""
        ql = self._run_path(tiny_model, kv_quant="int8")
        qn = self._run_path(tiny_model, kv_quant="int8",
                            paged_attention_native=True,
                            paged_kernel="lax")
        qp = self._run_path(tiny_model, kv_quant="int8",
                            paged_attention_native=True,
                            paged_kernel="pallas")
        for a, b, c in zip(ql, qn, qp):
            assert bool(jnp.array_equal(a, b))
            assert bool(jnp.array_equal(a, c))

    def test_quant_diverges_boundedly_from_fp(self, tiny_model):
        fp = self._run_path(tiny_model)
        q8 = self._run_path(tiny_model, kv_quant="int8")
        worst = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(fp, q8))
        assert 0.0 < worst < 0.5, \
            f"int8 logits should differ from fp, boundedly (got {worst})"

    def test_quant_requires_paged(self, tiny_model):
        cfg0, params = tiny_model
        dcfg = dataclasses.replace(decode_config(cfg0), kv_quant="int8")
        model = Llama(dcfg)
        with pytest.raises(ValueError, match="decode_paged"):
            model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 1), jnp.int32))


# -- engine-level oracle ------------------------------------------------------


class TestNativeEngineOracle:
    PROMPTS = [[5, 9, 3, 11, 7], [2, 4, 2, 4, 2, 4, 2], [31, 9]]
    N = 24

    def test_native_lax_greedy_matches_oracle(self, tiny_model):
        cfg, params = tiny_model
        want = [_oracle_tokens(cfg, params, p, self.N)
                for p in self.PROMPTS]
        eng = PagedInferenceEngine(cfg, params, slots=4, page_size=8,
                                   native_attention=True, kernel="lax")
        try:
            reqs = [eng.submit(p, max_new_tokens=self.N)
                    for p in self.PROMPTS]
            _drive(eng, *reqs)
            assert [r.tokens for r in reqs] == want
            assert eng.stats().kernel_path == "lax"
        finally:
            eng.close()

    def test_native_lax_spec_greedy_matches_oracle(self, tiny_model):
        cfg, params = tiny_model
        want = [_oracle_tokens(cfg, params, p, self.N)
                for p in self.PROMPTS]
        eng = PagedInferenceEngine(cfg, params, slots=4, page_size=8,
                                   native_attention=True, kernel="lax",
                                   spec_tokens=4)
        try:
            reqs = [eng.submit(p, max_new_tokens=self.N)
                    for p in self.PROMPTS]
            _drive(eng, *reqs)
            assert [r.tokens for r in reqs] == want
        finally:
            eng.close()

    def test_native_pallas_spec_greedy_matches_oracle(self, tiny_model):
        cfg, params = tiny_model
        want = [_oracle_tokens(cfg, params, p, 12) for p in self.PROMPTS]
        eng = PagedInferenceEngine(cfg, params, slots=4, page_size=8,
                                   native_attention=True, kernel="pallas",
                                   spec_tokens=3)
        try:
            reqs = [eng.submit(p, max_new_tokens=12)
                    for p in self.PROMPTS]
            _drive(eng, *reqs)
            assert [r.tokens for r in reqs] == want
            assert eng.stats().kernel_path == "pallas"
        finally:
            eng.close()

    def test_native_sampled_matches_legacy_engine(self, tiny_model):
        """Sampled rows share the engine-wide rng stream; the native
        path must not perturb a single draw."""
        cfg, params = tiny_model

        def sample_with(native):
            eng = PagedInferenceEngine(
                cfg, params, slots=3, page_size=8, temperature=0.8,
                seed=11, native_attention=native)
            try:
                reqs = [eng.submit(p, max_new_tokens=10)
                        for p in self.PROMPTS]
                _drive(eng, *reqs)
                return [r.tokens for r in reqs]
            finally:
                eng.close()

        assert sample_with(True) == sample_with(False)

    def test_dispatch_counter_counts_each_prefill_chunk(self, tiny_model):
        """One inc per PROGRAM, on every path: a multi-chunk prefill
        must move the counter by its chunk count, like decode/verify."""
        from lzy_tpu.ops.paged_attention import DISPATCHES

        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=8,
                                   prefill_chunk=4,
                                   native_attention=True)
        try:
            before = _metric_value(DISPATCHES)
            r = eng.submit(list(range(1, 21)), max_new_tokens=3)
            _drive(eng, r)
            # 20-token prompt at chunk 4 = 5 prefill programs, plus the
            # decode steps after it
            assert _metric_value(DISPATCHES) - before >= 5 + 2
        finally:
            eng.close()

    def test_auto_kernel_resolves_by_platform(self, tiny_model):
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=8,
                                   native_attention=True, kernel="auto")
        try:
            assert eng.kernel_path == default_kernel()
        finally:
            eng.close()
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=8)
        try:
            assert eng.kernel_path == "legacy"
            assert eng.stats().kernel_path == "legacy"
        finally:
            eng.close()

    def test_bad_engine_kwargs_rejected(self, tiny_model):
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="kv_quant"):
            PagedInferenceEngine(cfg, params, kv_quant="fp4")
        with pytest.raises(ValueError, match="kernel"):
            PagedInferenceEngine(cfg, params, kernel="cuda")
        with pytest.raises(ValueError, match="not both"):
            PagedInferenceEngine(cfg, params, kv_blocks=8,
                                 kv_pool_bytes=1 << 20)
        # an explicit kernel the legacy path would silently ignore is a
        # misconfiguration, not a preference
        with pytest.raises(ValueError, match="native_attention"):
            PagedInferenceEngine(cfg, params, kernel="pallas")

    def test_serve_flags_validated(self):
        from lzy_tpu.service.serve import main

        for flags in (["--serve-kernel", "pallas"],
                      ["--serve-kv-quant", "int8"],
                      ["--serve-kv-pool-mb", "64"],
                      ["--serve-paged", "--serve-kernel", "pallas"],
                      ["--serve-paged", "--serve-kv-blocks", "8",
                       "--serve-kv-pool-mb", "64",
                       "--serve-native-attention"]):
            with pytest.raises(SystemExit):
                main(["--storage-uri", "file:///tmp/x",
                      "--serve-model", "tiny"] + flags)


# -- int8 engine: bounded divergence + pool integrity -------------------------


class TestQuantEngine:
    def test_greedy_match_rate_vs_fp_oracle(self, tiny_model):
        """The bounded-divergence regime: int8 greedy decode follows the
        fp oracle's continuation closely over LONG continuations. The
        floor is deliberately below 1.0 — quantized decode is allowed to
        diverge (once the argmax flips, continuations legitimately go
        elsewhere) — but a collapse below it would mean the quantizer is
        destroying the signal, not perturbing it."""
        cfg, params = tiny_model
        prompts = [[5, 9, 3, 11, 7], [2, 4, 2, 4, 2, 4, 2],
                   [31, 9, 17, 1], [8, 8, 40]]
        n = 48
        want = [_oracle_tokens(cfg, params, p, n) for p in prompts]
        eng = PagedInferenceEngine(cfg, params, slots=4, page_size=8,
                                   kv_quant="int8",
                                   native_attention=True)
        try:
            reqs = [eng.submit(p, max_new_tokens=n) for p in prompts]
            _drive(eng, *reqs, rounds=600)
            total = sum(len(w) for w in want)
            matched = sum(
                sum(a == b for a, b in zip(r.tokens, w))
                for r, w in zip(reqs, want))
            rate = matched / total
            assert rate >= 0.8, \
                f"greedy-match rate {rate:.3f} vs fp oracle collapsed"
            st = eng.stats()
            assert st.kv_quant == "int8"
        finally:
            eng.close()

    def test_pool_integrity_under_quantization(self, tiny_model):
        """Quantization must be invisible to the block pool's
        accounting: drive admissions past capacity (evictions), finish
        everything, and assert every non-cached block returned to the
        free list with zero refcounts — int8 payloads and sidecars ride
        the same block ids, so a leak here would mean the quant path
        forked the bookkeeping."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                   kv_blocks=9, kv_quant="int8",
                                   native_attention=True)
        try:
            prompts = [[i, i + 1, i + 2] * 3 for i in range(1, 11, 2)]
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            _drive(eng, *reqs, rounds=800)
            assert all(r.error is None or "preempted" in r.error
                       for r in reqs)
            pool = eng.kv.pool
            stats = eng.kv.stats()
            assert stats.blocks_free + stats.blocks_cached \
                == stats.blocks_total
            for block in range(pool.n_blocks):
                assert pool.refcount(block) == 0
        finally:
            eng.close()

    def test_quant_prefix_reuse_stays_consistent(self, tiny_model):
        """A second request hitting the radix cache reads blocks the
        FIRST request quantized — the sidecars must describe those
        bytes. Both continuations must equal a fresh quantized run
        (cache reuse can never change quantized output)."""
        cfg, params = tiny_model
        prompt = [7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 5]
        outs = []
        for _ in range(2):
            eng = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                       kv_quant="int8",
                                       native_attention=True)
            try:
                r1 = eng.submit(prompt, max_new_tokens=10)
                _drive(eng, r1)
                r2 = eng.submit(prompt, max_new_tokens=10)
                _drive(eng, r2)
                assert eng.kv.stats().prefix_hit_tokens > 0, \
                    "second request should hit the radix cache"
                assert r2.tokens == r1.tokens
                outs.append(r1.tokens)
            finally:
                eng.close()
        assert outs[0] == outs[1]

    def test_pool_bytes_budget_doubles_blocks_under_int8(self, tiny_model):
        """The capacity claim, end to end: at a FIXED payload byte
        budget an int8 engine owns at least 2x the blocks of the bf16
        engine (sidecars are metadata outside the payload budget, like
        page tables — kv_quant_sidecar_bytes reports them)."""
        cfg, params = tiny_model
        budget = 512 * 1024
        sizes = {}
        for quant in (None, "int8"):
            eng = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                       kv_pool_bytes=budget,
                                       kv_quant=quant,
                                       native_attention=True)
            try:
                sizes[quant] = eng.stats().kv_blocks_total
            finally:
                eng.close()
        assert sizes["int8"] >= 2 * sizes[None], sizes


class TestBlockBytes:
    def test_int8_halves_payload_and_doubles_blocks(self):
        kw = dict(page_size=16, n_kv_heads=8, head_dim=128, n_layers=32)
        fp = kv_block_bytes(dtype="bfloat16", **kw)
        q8 = kv_block_bytes(dtype="bfloat16", kv_quant="int8", **kw)
        assert q8 * 2 == fp
        budget = 1 << 30
        assert blocks_for_bytes(budget, dtype="bfloat16",
                                kv_quant="int8", **kw) \
            == 2 * blocks_for_bytes(budget, dtype="bfloat16", **kw)

    def test_sidecar_accounting(self):
        kw = dict(page_size=16, n_kv_heads=8, n_layers=32)
        assert kv_quant_sidecar_bytes(**kw) == 0
        side = kv_quant_sidecar_bytes(kv_quant="int8", **kw)
        assert side == 2 * 32 * 16 * 8 * 2 * 4
        # sidecars stay a small fraction of the int8 payload they ride
        payload = kv_block_bytes(head_dim=128, kv_quant="int8",
                                 dtype="bfloat16", **kw)
        assert side / payload < 0.07


class TestQuantMismatchFailsClosed:
    def test_quant_export_into_fp_pool_is_refused(self, tiny_model):
        """A quantized export imported into an fp pool must FAIL CLOSED
        (local re-prefill), never scatter int8 quantization codes into a
        pool that reads them as KV values — the decode replica's output
        must stay the fp oracle's."""
        from lzy_tpu.serving import DecodeEngine, PrefillEngine
        from lzy_tpu.serving.disagg.kv_export import import_kv

        cfg, params = tiny_model
        prompt = list(range(16)) + [40]
        pf = PrefillEngine(cfg, params, slots=1, page_size=8,
                           kv_quant="int8")
        try:
            req = pf.submit(prompt)
            _drive(pf, req)
            export = req.kv_export
        finally:
            pf.close()
        de = DecodeEngine(cfg, params, slots=1, page_size=8)
        try:
            free_before = de.kv.pool.free_count()
            assert import_kv(de, export) == 0
            assert de.kv.match_len(prompt) == 0, \
                "a refused import must not register the prefix"
            assert de.kv.pool.free_count() == free_before
            r = de.submit(prompt, max_new_tokens=8)
            _drive(de, r)
            assert r.tokens == _oracle_tokens(cfg, params, prompt, 8)
        finally:
            de.close()

    def test_fp_export_into_quant_pool_is_refused(self, tiny_model):
        from lzy_tpu.serving import DecodeEngine, PrefillEngine
        from lzy_tpu.serving.disagg.kv_export import import_kv

        cfg, params = tiny_model
        prompt = list(range(16)) + [40]
        pf = PrefillEngine(cfg, params, slots=1, page_size=8)
        try:
            req = pf.submit(prompt)
            _drive(pf, req)
            export = req.kv_export
        finally:
            pf.close()
        de = DecodeEngine(cfg, params, slots=1, page_size=8,
                          kv_quant="int8")
        try:
            assert import_kv(de, export) == 0
            assert de.kv.match_len(prompt) == 0
        finally:
            de.close()

    def test_builders_reject_native_knobs_without_paged(self):
        from lzy_tpu.service.inference import (
            build_gateway_service, build_inference_service)

        for kw in ({"kv_quant": "int8"}, {"native_attention": True},
                   {"kernel": "lax"}):
            with pytest.raises(ValueError, match="paged"):
                build_inference_service("tiny", **kw)
            with pytest.raises(ValueError, match="paged"):
                build_gateway_service("tiny", **kw)

    def test_resident_gauge_sums_engines_and_clears_on_close(
            self, tiny_model):
        from lzy_tpu.ops.paged_attention import QUANT_BLOCKS_RESIDENT

        cfg, params = tiny_model
        base = _metric_value(QUANT_BLOCKS_RESIDENT)
        engines = []
        try:
            for i in range(2):
                eng = PagedInferenceEngine(
                    cfg, params, slots=1, page_size=8, kv_quant="int8")
                engines.append(eng)
                # a 2-block prompt: its full blocks stay radix-cached
                # (resident, unreferenced) after the request finishes
                r = eng.submit(list(range(16)) + [5 + i],
                               max_new_tokens=2)
                _drive(eng, r)
                eng.stats()
            per = [e._quant_resident_seen for e in engines]
            assert all(v > 0 for v in per)
            assert _metric_value(QUANT_BLOCKS_RESIDENT) - base \
                == pytest.approx(sum(per))
        finally:
            for eng in engines:
                eng.close()
        assert _metric_value(QUANT_BLOCKS_RESIDENT) - base \
            == pytest.approx(0)


class TestQuantDisaggTransfer:
    def test_quantized_blocks_travel_export_import(self, tiny_model):
        """Disaggregation moves every cache leaf by name — int8 payloads
        AND their scale/zero-point sidecars must arrive together, and a
        decode continuation over imported quantized blocks must equal
        the monolithic quantized engine's (quantization is deterministic,
        so identical fp inputs produce identical int8 bytes)."""
        from lzy_tpu.serving import DecodeEngine, PrefillEngine
        from lzy_tpu.serving.disagg.kv_export import import_kv

        cfg, params = tiny_model
        prompt = list(range(16)) + [40]      # 2 full blocks at page 8
        kw = dict(page_size=8, kv_quant="int8", native_attention=True)
        pf = PrefillEngine(cfg, params, slots=1, **kw)
        try:
            req = pf.submit(prompt)
            _drive(pf, req)
            assert req.error is None, req.error
            export = req.kv_export
        finally:
            pf.close()
        assert export is not None
        assert any("k_scale" in key for key in export.leaves), \
            "quant sidecars must ride the transfer payload"
        de = DecodeEngine(cfg, params, slots=1, **kw)
        try:
            assert import_kv(de, export) == 2
            r = de.submit(prompt, max_new_tokens=8)
            _drive(de, r)
            assert r.error is None, r.error
            assert de.kv.stats().prefix_hit_tokens >= 16
            got = r.tokens
        finally:
            de.close()
        mono = PagedInferenceEngine(cfg, params, slots=1, **kw)
        try:
            m = mono.submit(prompt, max_new_tokens=8)
            _drive(mono, m)
            assert got == m.tokens
        finally:
            mono.close()


# -- spec draft truncation counter (satellite) --------------------------------


class _WindowProposer:
    """Always proposes a fixed draft — forces spec growth every round."""

    def __init__(self, gamma):
        self.gamma = gamma

    def propose(self, tokens):
        return [3] * self.gamma


class TestSpecDraftTruncation:
    def test_truncation_is_counted(self, tiny_model):
        """A pool with a dry free list truncates drafts instead of
        evicting cached blocks (PR 5's backstop); since PR 9 that event
        is COUNTED — EngineStats.spec_draft_truncated and
        lzy_spec_draft_truncated_total — instead of silently reading as
        a low tokens-per-step."""
        from lzy_tpu.serving.spec import DRAFT_TRUNCATED

        cfg, params = tiny_model
        page = 4
        # prompt fills 2 blocks + growth block; pool sized so that once
        # both slots are resident the free list is EMPTY, so every
        # verify round's _grow_for_spec comes up short
        eng = PagedInferenceEngine(
            cfg, params, slots=2, page_size=page, kv_blocks=7,
            spec_tokens=6, proposer=_WindowProposer(6),
            native_attention=True)
        try:
            before = _metric_value(DRAFT_TRUNCATED)
            reqs = [eng.submit([1 + i, 2, 3, 4, 5, 6, 7], max_new_tokens=12)
                    for i in range(2)]
            _drive(eng, *reqs, rounds=600)
            st = eng.stats()
            assert st.spec_draft_truncated > 0
            assert _metric_value(DRAFT_TRUNCATED) > before
        finally:
            eng.close()

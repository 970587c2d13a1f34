"""The paged-attention read paths + int8 KV quantization (PR 9).

Three layers of coverage, matching the module's correctness contract
(``lzy_tpu/ops/paged_attention.py``, docs/serving.md "Paged attention & KV
quantization"):

- **Op-level sweeps**: the lax gather-attention reproduces the dense
  cache's read bit for bit (same ops in the same order), and stays the
  portable oracle. The Pallas decode kernel (TPU interpreter on the CPU: DMAs,
  semaphores, scratch memory that starts as NaN) reorders the sums
  (online softmax), so it is judged against a float32 reference within
  the module's written tolerance, across page sizes, ragged per-row
  lengths, scratch-block idle rows, decode and verify windows and
  dtypes, and at the lengths that break such kernels.
- **Model/engine-level oracle tests**: a ``PagedInferenceEngine`` with
  ``kernel="lax"`` must be bit-identical to the solo ``generate()``
  oracle — greedy and sampled, speculation on and off. Through the
  kernel (``"pallas"``, interpreted here), logits lie within the
  tolerance and greedy tokens are the oracle's except at a logit tie.
- **int8 bounded divergence**: quantized output is intentionally NOT
  bit-identical; what IS asserted: the per-element dequantization error
  bound (one optimal-scale quantization step), kernel-independence of
  quantized output (the kernel leaves int8 pools to lax),
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
    CHUNK_PATH, DEQUANT_ERROR_EWMA, DISPATCHES, MAX_Q_TOKENS, TOLERANCE,
    KVQuant, default_kernel, dequantize_kv, kernel_path, note_dequant_error,
    paged_attention, quantize_kv)
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


def _metric_value(metric, **labels) -> float:
    """Sum over the label combinations of a process-registry metric
    that carry ``labels`` (all of them when none is given)."""
    want = set(labels.items())
    return sum(v for key, v in metric._values.items()
               if want <= set(key))


def _module():
    """``lzy_tpu.ops.paged_attention``, the module (``lzy_tpu.ops`` exports
    the function under the same name)."""
    import importlib

    return importlib.import_module("lzy_tpu.ops.paged_attention")


def _reference(q, k_pool, v_pool, pt, pos):
    """Float32 attention over each row's gathered pages, one (row,
    position, head) at a time: nothing shared with the code under test.
    A query at position -1 sees nothing and reads as zeros."""
    q, k_pool, v_pool = (np.asarray(x, np.float32)
                         for x in (q, k_pool, v_pool))
    pt, pos = np.asarray(pt), np.asarray(pos)
    b, t, h, d = q.shape
    kv = k_pool.shape[2]
    g = h // kv
    out = np.zeros((b, t, kv, g, d), np.float32)
    for bi in range(b):
        keys = k_pool[pt[bi]].reshape(-1, kv, d)
        vals = v_pool[pt[bi]].reshape(-1, kv, d)
        for ti in range(t):
            n = pos[bi, ti] + 1
            for head in range(h if n > 0 else 0):
                s = keys[:n, head // g] @ q[bi, ti, head] * d ** -0.5
                p = np.exp(s - s.max())
                out[bi, ti, head // g, head % g] = \
                    (p / p.sum()) @ vals[:n, head // g]
    return out


def _assert_within_tolerance(got, want, dtype, what=""):
    """The written tolerance (``ops.paged_attention.TOLERANCE``): the
    largest absolute difference, relative to the reference's largest
    magnitude or 1."""
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all(), f"non-finite output {what}"
    err = np.abs(got - want).max() / max(1.0, np.abs(want).max())
    assert err <= TOLERANCE[jnp.dtype(dtype).name], \
        f"{what}: error {err:.3g} at {jnp.dtype(dtype).name}"


def _assert_same_or_tie(cfg, params, prompt, got, want, tol=0.05):
    """Greedy tokens equal, or at the first difference the two tokens'
    logits under the full (uncached) forward are within ``tol``: a tie,
    after which the continuations legitimately part."""
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        if a == b:
            continue
        seen = jnp.asarray([list(prompt) + list(want[:i])], jnp.int32)
        logits = np.asarray(
            Llama(cfg).apply({"params": params}, seen)[0, -1], np.float32)
        assert abs(logits[a] - logits[b]) <= tol, \
            f"token {i}: {a} != {b}, logits {logits[a]} vs {logits[b]}"
        return


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


def _edge_case(name, *, page=8, pages=6, block_pages=4):
    """Per-row context lengths at which such kernels break; a length of
    n puts the query at position n - 1 and owns ceil(n / page) pages."""
    L = pages * page
    return {
        "idle_slot": [1, 2 * page + 3, 1],       # position 0, zeroed table
        "length_0": [0, 5, 0],                   # a query that sees nothing
        "length_1": [1, 2, 1],
        "page_boundary": [page, 2 * page, 3 * page],
        "first_of_a_page": [page + 1, 2 * page + 1, 1],
        "block_boundary": [block_pages * page, block_pages * page + 1,
                           block_pages * page - 1],
        "full_table": [L, L, L],
        "very_different": [1, L, 3],
    }[name]


class TestDecodeKernel:
    @pytest.mark.parametrize("page,pages", [(4, 8), (8, 4), (16, 3)])
    @pytest.mark.parametrize("t", [1, 5])
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_pallas_interpret_matches_float32_reference(
            self, page, pages, t, dtype):
        rng = np.random.default_rng(page * 100 + t)
        dtype = jnp.dtype(dtype)
        q, kp, vp, pt, pos, _ = _random_case(
            rng, page=page, pages=pages, b=3, t=t, kv=2, g=2, d=16,
            dtype=dtype, quant=False)
        want = _reference(q, kp, vp, pt, pos)
        p = paged_attention(q, kp, vp, pt, pos, kernel="pallas",
                            dtype=dtype, interpret=True)
        assert p.shape == want.shape and p.dtype == dtype
        _assert_within_tolerance(p, want, dtype, "pallas")
        # the oracle of the rest of the suite is held to the same judge
        a = paged_attention(q, kp, vp, pt, pos, kernel="lax", dtype=dtype)
        _assert_within_tolerance(a, want, dtype, "lax")

    @pytest.mark.parametrize("case", [
        "idle_slot", "length_0", "length_1", "page_boundary",
        "first_of_a_page", "block_boundary", "full_table",
        "very_different"])
    def test_lengths_that_break_such_kernels(self, case):
        """kv=2 and page=8 make a page 16 pool rows; a compute block of 64
        rows is 4 pages, so 6-page tables run one full and one partial
        block, double-buffered."""
        kernel = _module()._pallas_paged_attention
        page, pages, kv, g, d = 8, 6, 2, 2, 16
        lens = np.asarray(_edge_case(case, page=page, pages=pages))
        rng = np.random.default_rng(len(case))
        b = len(lens)
        n = b * pages + 1
        dtype = jnp.bfloat16
        q = jnp.asarray(rng.standard_normal((b, 1, kv * g, d)), dtype)
        kp = jnp.asarray(rng.standard_normal((n, page, kv, d)), dtype)
        vp = jnp.asarray(rng.standard_normal((n, page, kv, d)), dtype)
        pt = np.zeros((b, pages), np.int32)
        ids = rng.permutation(np.arange(1, n))
        # an idle slot keeps the zeroed table and a stale position 0; a live
        # row of one position owns a page like any other
        idle = (lens == 1) if case == "idle_slot" else (lens == 0)
        for row, owned in enumerate(-(-lens // page)):
            if not idle[row]:
                pt[row, :owned], ids = ids[:owned], ids[owned:]
        pos = jnp.asarray(lens[:, None] - 1, jnp.int32)
        got = kernel(q, kp, vp, jnp.asarray(pt), pos, dtype=dtype,
                     interpret=True, block_rows=64)
        want = _reference(q, kp, vp, pt, pos)
        want[idle] = 0.0
        _assert_within_tolerance(got, want, dtype, case)
        assert not np.asarray(got, np.float32)[idle].any()
        assert np.asarray(got, np.float32)[~idle].any(axis=(1, 2, 3, 4)).all()

    @staticmethod
    def _live_case(live, *, t, page=8, pages=6, kv=2, g=2, d=16,
                   dtype=jnp.bfloat16):
        """Rows by ``live``: a live row owns the pages under its positions
        (the tail of its table is scratch, as a row not yet grown); an idle
        one keeps the zeroed table and a stale position, as the engine
        leaves a slot. The pools are float32 arrays of numpy, for a test
        to poison."""
        b = len(live)
        rng = np.random.default_rng(1000 * t + int(live.sum()) + b)
        n = b * pages + 1
        q = jnp.asarray(rng.standard_normal((b, t, kv * g, d)), dtype)
        k_pool = rng.standard_normal((n, page, kv, d)).astype(np.float32)
        v_pool = rng.standard_normal((n, page, kv, d)).astype(np.float32)
        starts = rng.integers(0, pages * page - t + 1, size=b)
        starts[~live] = rng.integers(0, 3, size=int((~live).sum()))
        ids = rng.permutation(np.arange(1, n))
        pt = np.zeros((b, pages), np.int32)
        for row in np.flatnonzero(live):
            owned = -(-(starts[row] + t) // page)
            pt[row, :owned], ids = ids[:owned], ids[owned:]
        pos = starts[:, None] + np.arange(t)[None, :]
        return (q, k_pool, v_pool, jnp.asarray(pt),
                jnp.asarray(pos, jnp.int32))

    @pytest.mark.parametrize("cell_rows", [2048, 16],
                             ids=["one_cell", "cells"])
    @pytest.mark.parametrize("t", [1, 5], ids=["decode", "verify"])
    @pytest.mark.parametrize("pattern", [
        "none_live", "first_only", "last_only", "alternating", "all_live"])
    def test_idle_rows_are_zero_and_live_rows_the_reference(
            self, pattern, t, cell_rows, monkeypatch):
        """A row whose table starts with the scratch block is an idle slot:
        exactly 0, whatever its stale position; the others are the float32
        reference's, in one grid cell and in several (six rows under a q
        block of 16 rows a cell: two cells of three rows for decode, six of
        one for the window)."""
        b = 6
        live = {"none_live": np.zeros(b, bool),
                "first_only": np.arange(b) == 0,
                "last_only": np.arange(b) == b - 1,
                "alternating": np.arange(b) % 2 == 0,
                "all_live": np.ones(b, bool)}[pattern]
        q, kp, vp, pt, pos = self._live_case(live, t=t)
        dtype = jnp.bfloat16
        kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
        want = _reference(q, kp, vp, pt, pos)
        want[~live] = 0.0
        monkeypatch.setattr(_module(), "_CELL_ROWS", cell_rows)
        # the jitted wrapper's cache does not know the module's constant
        got = _module()._pallas_paged_attention.__wrapped__(
            q, kp, vp, pt, pos, dtype=dtype, interpret=True, block_rows=64)
        _assert_within_tolerance(got, want, dtype, pattern)
        got = np.asarray(got, np.float32)
        assert not got[~live].any()
        assert got[live].any(axis=(1, 2, 3, 4)).all()

    @pytest.mark.parametrize("t", [1, 5], ids=["decode", "verify"])
    def test_a_live_row_reads_its_own_blocks_and_no_other(self, t):
        """Every block a live row does not own, the scratch block too, is
        NaN: the live rows' results are the clean pool's bit for bit, the
        idle rows' exactly 0."""
        live = np.asarray([True, False, True, False, False, True])
        q, kp, vp, pt, pos = self._live_case(live, t=t)
        dtype = jnp.bfloat16
        owned = np.unique(np.asarray(pt)[np.asarray(pt) != 0])
        k_bad = np.full_like(kp, np.nan)
        v_bad = np.full_like(vp, np.nan)
        k_bad[owned], v_bad[owned] = kp[owned], vp[owned]
        clean, poisoned = (
            np.asarray(paged_attention(
                q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), pt, pos,
                kernel="pallas", interpret=True).astype(jnp.float32))
            for k, v in ((kp, vp), (k_bad, v_bad)))
        assert np.isfinite(poisoned).all()
        assert np.array_equal(clean[live], poisoned[live])
        assert not poisoned[~live].any() and clean[live].any()

    def test_a_live_row_at_position_0_on_a_page_of_its_own_is_read(self):
        """Position 0 is a live row's first, not an idle slot's mark: what
        says idle is the table."""
        page, pages, kv, g, d = 8, 3, 2, 2, 16
        rng = np.random.default_rng(46)
        dtype = jnp.bfloat16
        q = jnp.asarray(rng.standard_normal((2, 1, kv * g, d)), dtype)
        kp = jnp.asarray(rng.standard_normal((5, page, kv, d)), dtype)
        vp = jnp.asarray(rng.standard_normal((5, page, kv, d)), dtype)
        pt = jnp.asarray([[3, 0, 0], [0, 0, 0]], jnp.int32)
        pos = jnp.zeros((2, 1), jnp.int32)
        got = np.asarray(paged_attention(
            q, kp, vp, pt, pos, kernel="pallas",
            interpret=True).astype(jnp.float32))
        # one visible key: the result is that position's values, a head
        want = np.asarray(vp.astype(jnp.float32))[3, 0]      # [kv, d]
        assert np.array_equal(got[0, 0], np.broadcast_to(
            want[:, None, :], (kv, g, d)))
        assert not got[1].any()

    def test_within_tolerance_under_jit_and_odd_head_dim(self):
        # the engine runs the op inside jitted programs (d=24: a head dim
        # whose softmax scale is not a power of two; g=3: a group size
        # that is not one either)
        import functools

        rng = np.random.default_rng(7)
        q, kp, vp, pt, pos, _ = _random_case(
            rng, page=8, pages=4, b=2, t=3, kv=2, g=3, d=24,
            dtype=jnp.bfloat16, quant=False)
        f_pal = jax.jit(functools.partial(
            paged_attention, kernel="pallas", dtype=jnp.bfloat16,
            interpret=True))
        _assert_within_tolerance(f_pal(q, kp, vp, pt, pos),
                                 _reference(q, kp, vp, pt, pos),
                                 jnp.bfloat16)

    def test_pool_far_larger_than_the_vmem(self):
        """The pool stays in HBM and only the live pages move: 48 MiB of
        K and V (three times a core's VMEM) behind rows that own 1, 3 and
        40 pages at the far end of the pool."""
        page, pages, kv, g, d = 16, 40, 2, 2, 128
        n = 3072
        rng = np.random.default_rng(11)
        dtype = jnp.bfloat16
        kp = jnp.asarray(rng.standard_normal((n, page, kv, d)), dtype)
        vp = jnp.asarray(rng.standard_normal((n, page, kv, d)), dtype)
        assert kp.nbytes + vp.nbytes >= 48 << 20
        q = jnp.asarray(rng.standard_normal((3, 1, kv * g, d)), dtype)
        lens = np.asarray([1, 3 * page - 2, pages * page])
        pt = np.zeros((3, pages), np.int32)
        pt[0, 0] = 5
        pt[1, :3] = [n - 1, 7, n - 2]
        pt[2] = n - 3 - np.arange(pages)
        pos = jnp.asarray(lens[:, None] - 1, jnp.int32)
        got = paged_attention(q, kp, vp, jnp.asarray(pt), pos,
                              kernel="pallas", interpret=True)
        _assert_within_tolerance(got, _reference(q, kp, vp, pt, pos), dtype)

    def test_kernel_takes_decode_and_chunk_shapes_and_leaves_int8_to_lax(
            self):
        assert kernel_path("pallas", t=1, quantized=False) == "pallas"
        assert kernel_path("pallas", t=MAX_Q_TOKENS, quantized=False) \
            == "pallas"
        assert kernel_path("pallas", t=MAX_Q_TOKENS + 1,
                           quantized=False) == CHUNK_PATH == "chunk_pallas"
        assert kernel_path("pallas", t=256, quantized=False) == CHUNK_PATH
        for t in (1, MAX_Q_TOKENS + 1, 256):
            assert kernel_path("pallas", t=t, quantized=True) == "lax"
            assert kernel_path("lax", t=t, quantized=False) == "lax"
        # and the call does as the label says: an int8 pool comes back as
        # the lax read's very bytes at either width; a prefill-wide window
        # over a float pool does not (the chunk kernel reorders the sums)
        # and lies within the tolerance all the same
        rng = np.random.default_rng(5)
        for t, quant in ((MAX_Q_TOKENS + 1, True), (1, True),
                         (MAX_Q_TOKENS + 1, False)):
            q, kp, vp, pt, pos, side = _random_case(
                rng, page=4, pages=8, b=2, t=t, kv=2, g=2, d=16,
                dtype=jnp.bfloat16, quant=quant)
            a, p = (paged_attention(q, kp, vp, pt, pos, kernel=k,
                                    dtype=jnp.bfloat16, quant=side,
                                    interpret=True)
                    for k in ("lax", "pallas"))
            assert bool(jnp.array_equal(a, p)) == quant
            if not quant:
                _assert_within_tolerance(
                    p, _reference(q, kp, vp, pt, pos), jnp.bfloat16)

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


#: heads / key-value heads of the three configurations that read a pool
#: ``[n_blocks, page, KV, D]`` (head size 128, pages of 16)
_CHUNK_SHAPES = {"mistral": (32, 8), "nemotron": (32, 2), "solar": (64, 8)}


def _chunk_case(rng, *, h, kv, t, starts, pages, live=None, page=16, d=128,
                dtype=jnp.bfloat16):
    """A prefill chunk of ``t`` consecutive positions a row from
    ``starts``. A row owns the pages under its first ``live`` positions
    (default: up to its chunk's end, within the table); the table's entries
    behind them name pages of NaN, as another row's would be to this one: a
    read that touches one shows in the output."""
    b = len(starts)
    n = b * pages + 1
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
    k_pool = rng.standard_normal((n, page, kv, d)).astype(np.float32)
    v_pool = rng.standard_normal((n, page, kv, d)).astype(np.float32)
    pt = rng.permutation(np.arange(1, n)).reshape(b, pages).astype(np.int32)
    for row, start in enumerate(starts):
        upto = start + t if live is None else live[row]
        owned = min(-(-upto // page), pages)
        k_pool[pt[row, owned:]] = np.nan
        v_pool[pt[row, owned:]] = np.nan
        if live is not None:
            pt[row, owned:] = 0           # not grown yet: scratch
    pos = jnp.asarray(np.asarray(starts)[:, None] + np.arange(t)[None, :],
                      jnp.int32)
    return (q, jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype),
            jnp.asarray(pt), pos)


class TestChunkKernel:
    """The chunk kernel under the Pallas TPU interpreter against float32
    attention, at the three configurations' head shapes."""

    @pytest.mark.parametrize("t", [16, 32, 256])
    @pytest.mark.parametrize("shape", sorted(_CHUNK_SHAPES))
    def test_two_rows_at_different_starts_read_their_own_pages_only(
            self, shape, t):
        """Batch 2, one start inside a page and one past a block of pages;
        every page behind a row's chunk is NaN (a table wider than the live
        prefix, filled with another row's pages): the kernel stops at the
        page of a tile's last query."""
        h, kv = _CHUNK_SHAPES[shape]
        rng = np.random.default_rng(t + h + kv)
        pages = 24 + t // 16
        q, kp, vp, pt, pos = _chunk_case(
            rng, h=h, kv=kv, t=t, starts=[5, 8 * 16 + 16 + 3], pages=pages)
        got = paged_attention(q, kp, vp, pt, pos, kernel="pallas",
                              interpret=True)
        assert got.shape == (2, t, kv, h // kv, 128)
        assert got.dtype == jnp.bfloat16
        _assert_within_tolerance(got, _reference(q, kp, vp, pt, pos),
                                 jnp.bfloat16, f"{shape} t={t}")

    @pytest.mark.parametrize("case", [
        "start_0", "inside_a_page", "ends_on_the_last_page",
        "pads_past_the_allocated_pages", "pads_past_the_table"])
    @pytest.mark.parametrize("shape", sorted(_CHUNK_SHAPES))
    def test_starts_and_pads_that_break_such_kernels(self, shape, case):
        h, kv = _CHUNK_SHAPES[shape]
        t, pages, page = 32, 20, 16
        L = pages * page
        # start, and how many of the chunk's positions are real tokens
        start, real = {
            "start_0": (0, t),
            "inside_a_page": (page + 5, t),
            "ends_on_the_last_page": (L - t, t),
            # the row's pages end with its prompt; the pads' positions read
            # scratch and what they return is garbage that nothing keeps
            "pads_past_the_allocated_pages": (9 * page + 2, 9),
            # a pad's position may lie past max_seq_len: the page index is
            # clamped to the table's width
            "pads_past_the_table": (L - t // 2, t // 2),
        }[case]
        rng = np.random.default_rng(len(case) + h)
        q, kp, vp, pt, pos = _chunk_case(
            rng, h=h, kv=kv, t=t, starts=[start], pages=pages,
            live=None if real == t else [start + real])
        got = paged_attention(q, kp, vp, pt, pos, kernel="pallas",
                              interpret=True)
        want = _reference(q[:, :real], kp, vp, pt, pos[:, :real])
        _assert_within_tolerance(got[:, :real], want, jnp.bfloat16,
                                 f"{shape} {case}")

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_small_tiles_and_blocks_under_jit_at_odd_shapes(
            self, dtype, monkeypatch):
        """A group of 3, a head size of 24, a width that is no power of
        two, q tiles of 2 positions and blocks of 2 pages: every tile walks
        several blocks, the last of them partial."""
        pa = _module()
        monkeypatch.setattr(pa, "_CHUNK_ROWS", 8)
        monkeypatch.setattr(pa, "_CHUNK_BLOCK_ROWS", 16)
        rng = np.random.default_rng(9)
        dtype = jnp.dtype(dtype)
        q, kp, vp, pt, pos = _chunk_case(
            rng, h=6, kv=2, t=12, starts=[0, 7, 30], pages=12, page=4,
            d=24, dtype=dtype)
        # the wrapper reads the two sizes when it is traced: nothing else
        # traces it at this shape, before or after
        pa._pallas_chunk_attention.clear_cache()
        try:
            got = paged_attention(q, kp, vp, pt, pos, kernel="pallas",
                                  interpret=True)
        finally:
            pa._pallas_chunk_attention.clear_cache()
        _assert_within_tolerance(got, _reference(q, kp, vp, pt, pos), dtype)

    @pytest.mark.parametrize("shape", sorted(_CHUNK_SHAPES))
    def test_verify_window_wider_than_the_decode_kernel_takes(self, shape):
        """``spec_tokens >= 8`` makes a verify window ``[slots, 9+]``, past
        ``MAX_Q_TOKENS``: it is the chunk kernel's, a grid cell a slot, each
        at its own start (one slot fresh at 0, one inside a page, one past
        a block of pages), a q tile of 9 positions."""
        h, kv = _CHUNK_SHAPES[shape]
        rng = np.random.default_rng(h * kv)
        q, kp, vp, pt, pos = _chunk_case(
            rng, h=h, kv=kv, t=MAX_Q_TOKENS + 1,
            starts=[0, 21, 8 * 16 + 7, 300], pages=24)
        assert kernel_path("pallas", t=q.shape[1], quantized=False) \
            == CHUNK_PATH
        got = paged_attention(q, kp, vp, pt, pos, kernel="pallas",
                              interpret=True)
        _assert_within_tolerance(got, _reference(q, kp, vp, pt, pos),
                                 jnp.bfloat16, shape)

    def test_positions_that_are_not_consecutive_are_refused(self):
        """The chunk kernel reads ``positions[:, 0]`` and takes the rest as
        ``start + t``; the lax read and the decode kernel honour any
        positions. An eager call with others is refused, by name, at every
        width past the decode kernel's."""
        rng = np.random.default_rng(2)
        q, kp, vp, pt, pos = _chunk_case(
            rng, h=4, kv=2, t=MAX_Q_TOKENS + 1, starts=[3, 9], pages=6,
            page=4, d=16)
        gap = pos.at[1, 5:].add(2)
        for bad in (gap, pos[:, ::-1], jnp.zeros_like(pos)):
            with pytest.raises(ValueError, match="consecutive"):
                paged_attention(q, kp, vp, pt, bad, kernel="pallas",
                                interpret=True)
            # the reads that honour every position take them
            paged_attention(q, kp, vp, pt, bad, kernel="lax")
        paged_attention(q[:, :MAX_Q_TOKENS], kp, vp, pt,
                        gap[:, :MAX_Q_TOKENS], kernel="pallas",
                        interpret=True)


class TestModelPathBitExactness:
    """The read paths of ``Attention._decode_step`` through the REAL
    model forward: prefill chunks, 1-token decode, and a gamma+1 verify
    window. The reference is the dense scalar-index cache that
    ``models/generate.py``, the oracle, runs: lax is that read bit for
    bit; the kernel (all three windows are decode-sized here) within the
    written tolerance."""

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

    def _run_dense(self, tiny_model):
        """The same three chunks over the dense ``[B, L, KV, D]`` cache
        under one scalar index (every row of ``_run_path`` sits at the
        same positions)."""
        cfg0, params = tiny_model
        model = Llama(decode_config(cfg0))
        cache = init_cache(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32)))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            1, cfg0.vocab_size, (3, 6)), jnp.int32)
        outs = []
        for chunk in (toks, toks[:, :1], toks):
            logits, upd = model.apply(
                {"params": params, "cache": cache}, chunk,
                mutable=["cache"])
            cache = upd["cache"]
            outs.append(logits)
        return outs

    def test_lax_bit_identical_to_the_dense_cache_read(self, tiny_model):
        dense = self._run_dense(tiny_model)
        lax = self._run_path(tiny_model, paged_kernel="lax")
        for a, b in zip(dense, lax):
            assert bool(jnp.array_equal(a, b))

    def test_pallas_logits_within_tolerance_of_the_dense_cache_read(
            self, tiny_model):
        dense = self._run_dense(tiny_model)
        kernel = self._run_path(tiny_model, paged_kernel="pallas")
        for a, b in zip(dense, kernel):
            _assert_within_tolerance(b, np.asarray(a, np.float32),
                                     tiny_model[0].dtype, "logits")

    def test_quantized_output_is_kernel_independent(self, tiny_model):
        """int8 output diverges boundedly from fp but must NOT depend on
        which kernel the model was asked for: a model asked for the
        kernel reads an int8 pool through lax."""
        qn = self._run_path(tiny_model, kv_quant="int8",
                            paged_kernel="lax")
        qp = self._run_path(tiny_model, kv_quant="int8",
                            paged_kernel="pallas")
        for a, b in zip(qn, qp):
            assert bool(jnp.array_equal(a, b))

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
                                   kernel="lax")
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
                                   kernel="lax", spec_tokens=4)
        try:
            reqs = [eng.submit(p, max_new_tokens=self.N)
                    for p in self.PROMPTS]
            _drive(eng, *reqs)
            assert [r.tokens for r in reqs] == want
        finally:
            eng.close()

    def test_native_pallas_spec_greedy_matches_oracle(self, tiny_model):
        """The verify window (gamma + 1 = 4 positions a row) is the same
        q tile as plain decode: tokens are the oracle's, or part from it
        at a logit tie."""
        cfg, params = tiny_model
        want = [_oracle_tokens(cfg, params, p, 12) for p in self.PROMPTS]
        eng = PagedInferenceEngine(cfg, params, slots=4, page_size=8,
                                   kernel="pallas", spec_tokens=3)
        try:
            before = _metric_value(DISPATCHES, path="pallas")
            reqs = [eng.submit(p, max_new_tokens=12)
                    for p in self.PROMPTS]
            _drive(eng, *reqs)
            for p, r, w in zip(self.PROMPTS, reqs, want):
                _assert_same_or_tie(cfg, params, p, r.tokens, w)
            assert eng.stats().kernel_path == "pallas"
            assert _metric_value(DISPATCHES, path="pallas") > before
        finally:
            eng.close()

    def test_the_kernel_counts_dispatches_by_path(self, tiny_model):
        """``"pallas"`` (what ``"auto"`` is on a TPU) serves decode
        through the decode kernel and prefill chunks through the chunk
        kernel, and ``lzy_kernel_dispatch_total`` says so: a silent
        fall-back of either to lax would show under ``lax``. Greedy tokens
        are ``kernel="lax"``'s except at a logit tie."""
        cfg, params = tiny_model
        prompts = [list(range(1, 21)), [31, 9] * 9]

        def run(kernel):
            eng = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                       kernel=kernel)
            try:
                seen = {path: _metric_value(DISPATCHES, path=path)
                        for path in ("pallas", CHUNK_PATH, "lax")}
                reqs = [eng.submit(p, max_new_tokens=self.N)
                        for p in prompts]
                _drive(eng, *reqs)
                counts = {path: _metric_value(DISPATCHES, path=path) - n
                          for path, n in seen.items()}
                return eng.stats().kernel_path, counts, \
                    [r.tokens for r in reqs]
            finally:
                eng.close()

        path, counts, kernel = run("pallas")
        assert path == "pallas"
        # two prompts of 20 and 18 tokens: one 32-wide chunk each
        assert counts[CHUNK_PATH] == 2 and counts["lax"] == 0
        assert counts["pallas"] >= self.N - 1
        path, counts, lax = run("lax")
        assert path == "lax"
        assert counts["pallas"] == counts[CHUNK_PATH] == 0
        for p, a, b in zip(prompts, kernel, lax):
            _assert_same_or_tie(cfg, params, p, a, b)

    def test_sampled_draws_do_not_depend_on_the_kernel(self, tiny_model):
        """Sampled rows share the engine-wide rng stream; which kernel
        reads the pool must not perturb a single draw (float32 compute:
        the kernel's reordered sums stay far below what moves a draw)."""
        cfg, params = tiny_model
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)

        def sample_with(kernel):
            eng = PagedInferenceEngine(
                cfg, params, slots=3, page_size=8, temperature=0.8,
                seed=11, kernel=kernel)
            try:
                reqs = [eng.submit(p, max_new_tokens=6)
                        for p in self.PROMPTS]
                _drive(eng, *reqs)
                return [r.tokens for r in reqs]
            finally:
                eng.close()

        sampled = sample_with("lax")
        assert sampled == sample_with("pallas")
        assert sampled != [_oracle_tokens(cfg, params, p, 6)
                           for p in self.PROMPTS]

    def test_dispatch_counter_counts_each_prefill_chunk(self, tiny_model):
        """One inc per PROGRAM, on every path: a multi-chunk prefill
        must move the counter by its chunk count, like decode/verify, and
        under the label of the read each program's width gets."""
        cfg, params = tiny_model
        eng = PagedInferenceEngine(cfg, params, slots=1, page_size=8,
                                   prefill_chunk=16, kernel="pallas")
        try:
            before = {path: _metric_value(DISPATCHES, path=path)
                      for path in ("pallas", CHUNK_PATH, "lax")}
            r = eng.submit(list(range(1, 39)), max_new_tokens=3)
            _drive(eng, r)
            moved = {path: _metric_value(DISPATCHES, path=path) - n
                     for path, n in before.items()}
            # a 38-token prompt at chunk 16: two 16-wide programs through
            # the chunk kernel, a tail of 6 padded to 8 (a window the
            # decode kernel takes), plus the decode steps after it
            assert moved[CHUNK_PATH] == 2 and moved["lax"] == 0
            assert moved["pallas"] >= 1 + 2
        finally:
            eng.close()

    def test_chunked_prefill_through_the_chunk_kernel_matches_lax(
            self, tiny_model, chunk=64, prompt_len=150):
        """A prompt cut into three programs, the last narrower (64, 64,
        32), each reading the prefix the ones before it wrote: greedy
        tokens are the lax engine's, or part from them at a logit tie."""
        cfg, params = tiny_model
        rng = np.random.default_rng(prompt_len)
        prompt = rng.integers(1, cfg.vocab_size, prompt_len).tolist()

        def run(kernel):
            eng = PagedInferenceEngine(cfg, params, slots=2, page_size=8,
                                       prefill_chunk=chunk, kernel=kernel)
            try:
                before = _metric_value(DISPATCHES, path=CHUNK_PATH)
                r = eng.submit(prompt, max_new_tokens=12)
                _drive(eng, r)
                return r.tokens, \
                    _metric_value(DISPATCHES, path=CHUNK_PATH) - before
            finally:
                eng.close()

        got, programs = run("pallas")
        assert programs == 3
        want, programs = run("lax")
        assert programs == 0
        _assert_same_or_tie(cfg, params, prompt, got, want)

    def test_auto_kernel_resolves_by_platform(self, tiny_model, monkeypatch):
        """``"auto"`` is the code's choice from the platform it observes:
        lax on this CPU, the kernel on a TPU; a pool the kernel does not
        read (int8) is lax there too. No keyword is the same as
        ``"auto"``."""
        cfg, params = tiny_model
        assert jax.default_backend() == "cpu" and default_kernel() == "lax"
        for kw in ({}, {"kernel": "auto"}):
            eng = PagedInferenceEngine(cfg, params, slots=1, page_size=8,
                                       **kw)
            try:
                assert eng.kernel_path == "lax"
                assert eng.stats().kernel_path == "lax"
            finally:
                eng.close()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert default_kernel() == "pallas"
        for kw, path in (({}, "pallas"), ({"kv_quant": "int8"}, "lax")):
            eng = PagedInferenceEngine(cfg, params, slots=1, page_size=8,
                                       **kw)
            try:
                assert eng.kernel_path == path
            finally:
                eng.close()

    @pytest.mark.parametrize("family", ["llama", "nemotron_h"])
    def test_the_gather_read_is_refused_by_name(self, tiny_model, family):
        """The two tombstones (``benchmark/`` still passes the keywords,
        as ``True``): ``False`` is refused, naming what went and what
        reads in its place."""
        cfg, params = tiny_model
        if family == "nemotron_h":
            from lzy_tpu.models import nemotron_h

            cfg = nemotron_h.NemotronHConfig.tiny()
            params = None           # refused before a weight is touched
        with pytest.raises(ValueError, match="gather read.*kernel='lax'"):
            PagedInferenceEngine(cfg, params, native_attention=False)
        with pytest.raises(ValueError, match="gather read.*kernel='lax'"):
            cfg.paged_model(page_size=8, kv_pages=4, kernel="lax",
                            kv_quant=None, native=False)
        model = cfg.paged_model(page_size=8, kv_pages=4, kernel="lax",
                                kv_quant=None, native=True)
        assert model.cfg.decode_paged

    def test_bad_engine_kwargs_rejected(self, tiny_model):
        cfg, params = tiny_model
        with pytest.raises(ValueError, match="kv_quant"):
            PagedInferenceEngine(cfg, params, kv_quant="fp4")
        with pytest.raises(ValueError, match="kernel"):
            PagedInferenceEngine(cfg, params, kernel="cuda")
        with pytest.raises(ValueError, match="not both"):
            PagedInferenceEngine(cfg, params, kv_blocks=8,
                                 kv_pool_bytes=1 << 20)
        # the kernel over a pool it does not read is a misconfiguration,
        # not a preference
        with pytest.raises(ValueError, match="int8"):
            PagedInferenceEngine(cfg, params, kernel="pallas",
                                 kv_quant="int8")

    def test_serve_flags_validated(self):
        from lzy_tpu.service.serve import main

        for flags in (["--serve-kernel", "cuda"],
                      ["--serve-kv-blocks", "8", "--serve-kv-pool-mb", "64"],
                      ["--serve-mesh", "2", "--serve-kernel", "pallas"],
                      # the flags that chose the engine and the read are
                      # gone: unknown, not ignored
                      ["--serve-paged"],
                      ["--serve-native-attention"]):
            with pytest.raises(SystemExit) as exit_:
                main(["--storage-uri", "file:///tmp/x",
                      "--serve-model", "tiny"] + flags)
            assert exit_.value.code == 2


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
                                   kv_quant="int8")
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
                                   kv_blocks=9, kv_quant="int8")
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
                                       kv_quant="int8")
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
                                       kv_quant=quant)
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
            assert de.kv_io.import_kv(export) == 0
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
            assert de.kv_io.import_kv(export) == 0
            assert de.kv.match_len(prompt) == 0
        finally:
            de.close()

    def test_builders_serve_the_paged_engine_with_no_knob(self):
        """What ``serve.py --serve-model X`` builds with no further flag:
        the paged engine, its read chosen by the code; the keywords that
        used to choose are unknown to every builder."""
        from lzy_tpu.service.inference import (
            build_disagg_gateway_service, build_gateway_service,
            build_inference_service)

        svc = build_inference_service("tiny", start=False)
        try:
            assert type(svc.engine) is PagedInferenceEngine
            assert svc.engine.kernel_path == default_kernel()
            assert svc.engine.stats().kv_blocks_total > 0
        finally:
            svc.engine.close()
        for build in (build_inference_service, build_gateway_service,
                      build_disagg_gateway_service):
            for kw in ({"paged": True}, {"native_attention": True}):
                with pytest.raises(TypeError, match="unexpected keyword"):
                    build("tiny", **kw)

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

        cfg, params = tiny_model
        prompt = list(range(16)) + [40]      # 2 full blocks at page 8
        kw = dict(page_size=8, kv_quant="int8")
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
            assert de.kv_io.import_kv(export) == 2
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
            spec_tokens=6, proposer=_WindowProposer(6))
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

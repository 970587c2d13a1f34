"""Native paged-attention decode kernel + int8 KV-block quantization.

The paged serving path (``serving/kv_cache.py`` + ``models/llama.py``)
historically paid for its bit-identity guarantee twice per decode step:
K/V writes scatter through the page table, and then every row's blocks
are gathered BACK into the dense ``[B, L, kv, d]`` layout before the
dense attention code runs — doubling HBM traffic on a path that is
memory-bound to begin with. This module is the native read path:

- :func:`paged_attention` — attention computed *through* the page table.
  Two kernels behind one signature:

  * ``kernel="lax"`` — a pure ``jax.lax`` gather-attention whose op
    sequence reproduces the legacy gather→dense math EXACTLY (same
    einsums, same mask, same softmax, same dtypes), so its output is
    bit-identical to the legacy path and, transitively, to the dense
    engine and the ``generate()`` oracle. It is kept forever as the
    portable oracle the Pallas kernel is tested against.
  * ``kernel="pallas"`` — a fused Pallas program (one grid cell per
    ``(batch row, kv head)``, following ``ops/flash_attention.py``
    structure) that walks the row's blocks with dynamic page-table
    loads: the ``[B, L, kv, d]`` dense copy of the pool never exists,
    and dequantization of int8 blocks happens inside the block loop —
    the fusion GPUOS argues transparent runtimes owe their users
    (PAPERS.md). It runs under the Pallas interpreter only. The TPU
    lowering refuses its layout: the pool is blocked ``(n, page, 1, d)``
    out of ``(n, page, kv, d)``, and a block's last two dimensions must
    be multiples of (8, 128) or the array's own; the pool's per-head
    slice is also staged whole into VMEM per grid cell
    (:data:`VMEM_BUDGET_BYTES`). A legal layout needs the pool left in
    HBM and its blocks fetched by DMA — the kernel ROADMAP S2 writes in
    this one's place. Until then ``"auto"`` resolves to ``"lax"``
    (:func:`default_kernel`) and an engine asked for ``"pallas"``
    outside the interpreter fails at construction with the lowering's
    own message (:func:`lower_pallas_for_tpu`).

  The speculative verify forward (``serving/spec.py``) is the same call
  with ``T = gamma+1`` query positions — proposal scoring, cache write
  and attention run as ONE program per round.

- :func:`quantize_kv` / :func:`dequantize_kv` — per-position, per-head
  asymmetric int8 quantization of KV vectors (scale/zero-point sidecars
  stored per block row alongside the pool, ``models/llama.py`` owns the
  cache variables). int8 halves the pool's payload bytes, roughly
  doubling resident block count at fixed HBM — which multiplies radix
  prefix-cache hit rate and batch occupancy. Quantized output is
  intentionally NOT bit-identical; the contract is *bounded divergence*
  (per-element dequant error ≤ one optimal-scale quantization step,
  greedy-match rate vs the fp oracle asserted in
  tests/test_paged_attention.py).

Dispatch counts by kernel path, quantized blocks resident, and the
dequant-error EWMA are exported via ``lzy_tpu.utils.metrics.REGISTRY``
(``lzy_kernel_*``) and surfaced through ``EngineStats`` and ``bench.py``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils.metrics import REGISTRY

_NEG_INF = -1e30

DISPATCHES = REGISTRY.counter(
    "lzy_kernel_dispatch_total",
    "paged-attention dispatches by kernel path (pallas/lax/legacy)")
QUANT_BLOCKS_RESIDENT = REGISTRY.gauge(
    "lzy_kernel_kv_quant_blocks_resident",
    "int8-quantized KV blocks currently holding live data (summed over "
    "this process's quantized pools; engines withdraw their share on "
    "close)")
DEQUANT_ERROR_EWMA = REGISTRY.gauge(
    "lzy_kernel_dequant_error_ewma",
    "EWMA of observed KV dequantization error (mean |deq - fp|)")

_ewma_state = {"value": None}


def note_dequant_error(err: float, alpha: float = 0.2) -> float:
    """Fold one observed dequantization error (mean absolute, host-side)
    into the exported EWMA. Callers are the bench quant probes and tests
    — the hot path never reads quantized values back to the host."""
    prev = _ewma_state["value"]
    cur = float(err) if prev is None else (1 - alpha) * prev + alpha * err
    _ewma_state["value"] = cur
    DEQUANT_ERROR_EWMA.set(cur)
    return cur


def default_kernel() -> str:
    """The kernel ``"auto"`` resolves to: the one that compiles for a TPU
    at serving shapes. That is the lax gather-attention on every platform
    — the Pallas kernel's block layout does not lower (module docstring),
    and a kernel that cannot serve is never picked for the caller."""
    return "lax"


class KVQuant(NamedTuple):
    """Per-block quantization sidecars riding next to the int8 pools.

    Every array is indexed ``[n_blocks, page_size, kv_heads]`` — one
    scale/zero-point pair per written KV vector (the granularity a
    scatter-write can maintain without requantizing its whole block)."""

    k_scale: Any
    k_zp: Any
    v_scale: Any
    v_zp: Any


def quantize_kv(x: jax.Array):
    """Asymmetric int8 quantization of KV vectors over the head dim.

    ``x``: ``[..., d]`` float → ``(q int8 [..., d], scale [...],
    zp [...])`` with ``deq = q * scale + zp``. The range is mapped
    symmetrically around the vector's midpoint, and the scale is rounded
    UP to a power of two: ``q * scale`` is then EXACT in f32 (integer
    times 2^k), so dequantization carries exactly one rounding (the zp
    add) and FMA-fusing and non-fusing lowerings produce bit-identical
    values — without it, "which kernel compiled this" would leak a ulp
    into the output (XLA fuses the multiply-add inside the Pallas kernel
    body but not on the op-by-op path). The power-of-two rounding costs
    at most one bit of precision: worst-case per-element error stays
    under ``(max - min) / 254`` — one exactly-representable
    quantization step of the optimal scale (the bound tests assert).
    Constant vectors quantize to zeros with the midpoint as zero-point
    (near-exact)."""
    x32 = x.astype(jnp.float32)
    hi = jnp.max(x32, axis=-1)
    lo = jnp.min(x32, axis=-1)
    zp = (hi + lo) * 0.5
    step = jnp.maximum((hi - lo) / 254.0, 1e-30)
    scale = jnp.exp2(jnp.ceil(jnp.log2(step)))
    q = jnp.clip(
        jnp.round((x32 - zp[..., None]) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale, zp


def dequantize_kv(q: jax.Array, scale: jax.Array, zp: jax.Array,
                  dtype: Any) -> jax.Array:
    """Inverse of :func:`quantize_kv`; ``scale``/``zp`` broadcast over
    the trailing head dim. One formula shared by every read path (legacy
    gather, lax oracle, Pallas block loop), and — because the scale is a
    power of two — one whose value is independent of how the compiler
    fuses it, so the quantized paths can never diverge from EACH OTHER,
    only boundedly from fp."""
    return (q.astype(jnp.float32) * scale[..., None]
            + zp[..., None]).astype(dtype)


# -- lax oracle ------------------------------------------------------------------


def _lax_paged_attention(q, k_pool, v_pool, page_table, positions, *,
                         dtype, quant: Optional[KVQuant]):
    """Gather-attention in EXACTLY the legacy op sequence. This is the
    bit-exactness anchor: ``models/llama.py``'s legacy branch runs these
    same ops inline against the dense engine's shared math, so any
    change here must keep the einsum forms, mask constant, softmax call
    and dtype casts literally identical."""
    b, t, h, d = q.shape
    kv_heads = k_pool.shape[2]
    pages = page_table.shape[1]
    page = k_pool.shape[1]
    L = pages * page
    keys = k_pool[page_table]              # [B, P, page, KV, D]
    vals = v_pool[page_table]
    if quant is not None:
        keys = dequantize_kv(keys, quant.k_scale[page_table],
                             quant.k_zp[page_table], dtype)
        vals = dequantize_kv(vals, quant.v_scale[page_table],
                             quant.v_zp[page_table], dtype)
    keys = keys.reshape(b, L, kv_heads, d)
    vals = vals.reshape(b, L, kv_heads, d)
    reps = h // kv_heads
    qg = q.reshape(b, t, kv_heads, reps, d)
    s = jnp.einsum(
        "btkgd,blkd->bkgtl", qg, keys,
        preferred_element_type=jnp.float32,
    ) * (d ** -0.5)                                   # [B, KV, G, T, L]
    visible = (jnp.arange(L)[None, None, None, None, :]
               <= positions[:, None, None, :, None])
    s = jnp.where(visible, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("bkgtl,blkd->btkgd", p, vals)


# -- pallas kernel ---------------------------------------------------------------


def _pallas_kernel(*refs, page, pages, t, g, d, scale, dtype, quant):
    """One ``(batch row, kv head)`` grid cell: walk the row's page table,
    score every pooled position against the cell's ``[T, G, D]`` query
    tile, softmax over the full visible row, and contract with the
    gathered values — K/V are read straight out of the pool by block id
    (dynamic ``pl.ds`` loads), never materialized in the dense layout.
    int8 pools dequantize per block inside the loop.

    Numerics discipline: scores accumulate in f32 (``dot_general`` with
    ``preferred_element_type``), the softmax is the max-shift/exp/sum
    sequence ``jax.nn.softmax`` lowers to, and the value contraction
    runs on ``dtype`` operands over the full L axis — the same op
    shapes-modulo-batching as the lax oracle, which is what keeps
    interpret-mode output bit-identical to it (asserted by
    tests/test_paged_attention.py)."""
    if quant:
        (q_ref, k_ref, v_ref, ks_ref, kz_ref, vs_ref, vz_ref, pt_ref,
         pos_ref, o_ref) = refs
    else:
        q_ref, k_ref, v_ref, pt_ref, pos_ref, o_ref = refs
        ks_ref = kz_ref = vs_ref = vz_ref = None
    L = pages * page
    qf = q_ref[0, :, 0].astype(jnp.float32).reshape(t * g, d)

    def load_block(ref, s_ref, z_ref, j):
        row = pt_ref[0, j]
        blk = ref[pl.ds(row, 1), :, 0, :][0]            # [page, D]
        if s_ref is None:
            return blk
        sc = s_ref[pl.ds(row, 1), :, 0][0]              # [page]
        zp = z_ref[pl.ds(row, 1), :, 0][0]
        return dequantize_kv(blk, sc, zp, dtype)

    def score_body(j, carry):
        k_blk = load_block(k_ref, ks_ref, kz_ref, j).astype(jnp.float32)
        # scale AFTER the dot, exactly where the lax oracle applies it
        # (d**-0.5 is not a power of two for every head dim, so the
        # placement is visible in the last ulp)
        s_j = lax.dot_general(
            qf, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [T*G, page]
        return lax.dynamic_update_slice(carry, s_j, (0, j * page))

    s = lax.fori_loop(0, pages, score_body,
                      jnp.zeros((t * g, L), jnp.float32))

    # causal visibility: query at (row position) sees pooled slots
    # l <= its absolute position; rows of the tile are t-major over g
    pos_row = jnp.repeat(pos_ref[0, :], g)              # [T*G]
    cols = lax.broadcasted_iota(jnp.int32, (t * g, L), 1)
    s = jnp.where(cols <= pos_row[:, None], s, _NEG_INF)
    # jax.nn.softmax's exact op order: max-shift, exp, normalize
    m = jnp.max(s, axis=-1, keepdims=True)
    unnorm = jnp.exp(s - m)
    p = (unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)).astype(dtype)

    def gather_body(j, carry):
        v_blk = load_block(v_ref, vs_ref, vz_ref, j)
        return lax.dynamic_update_slice(carry, v_blk, (j * page, 0))

    vals = lax.fori_loop(
        0, pages, gather_body, jnp.zeros((L, d), dtype))
    out = lax.dot_general(p, vals, (((1,), (0,)), ((), ())))
    o_ref[0, :, 0] = out.reshape(t, g, d).astype(o_ref.dtype)


#: per-grid-cell VMEM budget the staged operands must fit (conservative
#: for every current TPU generation). The kernel stages the pool's
#: PER-HEAD slice into VMEM per (batch row, kv head) cell — fine at
#: bench/test scale, but an HBM-sized pool (--serve-kv-pool-mb) would
#: either fail Mosaic compilation or move more bytes than the legacy
#: gather; until the scalar-prefetch DMA variant lands (ROADMAP S2)
#: the guard turns that into a clear boot-time error (warmup AOT-compiles
#: the decode program) instead of a mid-serving engine death.
VMEM_BUDGET_BYTES = 48 << 20


def _pallas_paged_attention(q, k_pool, v_pool, page_table, positions, *,
                            dtype, quant: Optional[KVQuant],
                            interpret: Optional[bool]):
    b, t, h, d = q.shape
    n, page, kv_heads, _ = k_pool.shape
    pages = page_table.shape[1]
    g = h // kv_heads
    interpret = _interpret.resolve(interpret)
    L = pages * page
    staged = 2 * n * page * d * k_pool.dtype.itemsize      # k+v head slice
    if quant is not None:
        staged += 4 * n * page * 4                         # f32 sidecars
    staged += (t * g * L + L * d + t * g * d) * 4          # scores/vals/q
    if not interpret and staged > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"paged-attention pallas kernel would stage ~{staged >> 20} "
            f"MiB per grid cell (pool of {n} blocks x page {page} x head "
            f"dim {d}) — beyond the {VMEM_BUDGET_BYTES >> 20} MiB VMEM "
            f"budget. Shrink the pool or use kernel='lax' until the "
            f"HBM-resident DMA variant lands (ROADMAP S2).")
    qg = q.reshape(b, t, kv_heads, g, d)

    pool_spec = pl.BlockSpec((n, page, 1, d), lambda bi, ki: (0, 0, ki, 0))
    side_spec = pl.BlockSpec((n, page, 1), lambda bi, ki: (0, 0, ki))
    in_specs = [
        pl.BlockSpec((1, t, 1, g, d), lambda bi, ki: (bi, 0, ki, 0, 0)),
        pool_spec, pool_spec,
    ]
    operands = [qg, k_pool, v_pool]
    if quant is not None:
        in_specs += [side_spec] * 4
        operands += [quant.k_scale, quant.k_zp, quant.v_scale, quant.v_zp]
    in_specs += [
        pl.BlockSpec((1, pages), lambda bi, ki: (bi, 0)),
        pl.BlockSpec((1, t), lambda bi, ki: (bi, 0)),
    ]
    operands += [page_table.astype(jnp.int32), positions.astype(jnp.int32)]
    kernel = functools.partial(
        _pallas_kernel, page=page, pages=pages, t=t, g=g, d=d,
        scale=d ** -0.5, dtype=dtype, quant=quant is not None)
    out = pl.pallas_call(
        kernel,
        grid=(b, kv_heads),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, t, 1, g, d),
                               lambda bi, ki: (bi, 0, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, kv_heads, g, d), dtype),
        interpret=interpret,
    )(*operands)
    return out


def lower_pallas_for_tpu(*, batch: int, n_heads: int, n_kv_heads: int,
                         head_dim: int, n_blocks: int, page_size: int,
                         pages_per_seq: int, dtype: Any,
                         quantized: bool = False, t: int = 1) -> None:
    """Lower the Pallas kernel for a TPU at these shapes, with no device
    and no compile, and let the lowering's error out. An engine asked for
    ``kernel="pallas"`` calls this when it is built: what the TPU would
    refuse at the first request is refused at construction, in the
    lowering's own words."""
    sds = jax.ShapeDtypeStruct
    pool = sds((n_blocks, page_size, n_kv_heads, head_dim),
               jnp.int8 if quantized else dtype)
    quant = None
    if quantized:
        side = sds((n_blocks, page_size, n_kv_heads), jnp.float32)
        quant = KVQuant(side, side, side, side)

    def read(q, k_pool, v_pool, page_table, positions, quant):
        return paged_attention(q, k_pool, v_pool, page_table, positions,
                               kernel="pallas", dtype=dtype, quant=quant,
                               interpret=False)

    jax.jit(read).trace(
        sds((batch, t, n_heads, head_dim), dtype), pool, pool,
        sds((batch, pages_per_seq), jnp.int32), sds((batch, t), jnp.int32),
        quant,
    ).lower(lowering_platforms=("tpu",))


# -- public op -------------------------------------------------------------------


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    *,
    kernel: str = "lax",
    dtype: Any = None,
    quant: Optional[KVQuant] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode attention read directly through the page table.

    - ``q``: ``[B, T, H, D]`` post-RoPE queries (T=1 plain decode,
      T=gamma+1 the speculative verify chunk, T=chunk prefill);
    - ``k_pool``/``v_pool``: ``[n_blocks, page_size, KV, D]`` pooled
      cache (float, or int8 with ``quant`` sidecars);
    - ``page_table``: ``[B, P]`` int32 block ids in position order
      (id 0 = the reserved scratch block);
    - ``positions``: ``[B, T]`` int32 absolute positions of the queries
      (the causal mask: pooled slot ``l`` is visible iff
      ``l <= position``);
    - ``kernel``: ``"lax"`` (the path that serves, bit-identical to the
      legacy gather path) or ``"pallas"`` (fused; interpreter only, see
      the module docstring — ``interpret=None`` takes the process's
      ``ops.interpret`` setting);
    - ``dtype``: compute/output dtype (defaults to the pool dtype; int8
      pools must pass the model's activation dtype).

    Returns ``[B, T, KV, G, D]`` — the grouped-query layout the caller's
    output projection consumes (``reshape(b, t, h * d)``).
    """
    if dtype is None:
        if quant is not None:
            raise ValueError("quantized pools need an explicit dtype")
        dtype = k_pool.dtype
    if kernel == "lax":
        return _lax_paged_attention(
            q, k_pool, v_pool, page_table, positions, dtype=dtype,
            quant=quant)
    if kernel == "pallas":
        return _pallas_paged_attention(
            q, k_pool, v_pool, page_table, positions, dtype=dtype,
            quant=quant, interpret=interpret)
    raise ValueError(
        f"unknown paged-attention kernel {kernel!r}; known: lax, pallas")

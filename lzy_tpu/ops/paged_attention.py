"""The paged-attention read + int8 KV-block quantization.

The serving path (``serving/kv_cache.py`` + the models) writes K/V by
scattering through the page table; this module is how attention reads them
back, the one read path of the serving engine:

- :func:`paged_attention` — attention computed *through* the page table.
  Three reads behind one signature:

  * ``kernel="lax"`` — a pure ``jax.lax`` gather-attention whose op
    sequence reproduces the dense cache's read in ``models/llama.py``
    EXACTLY (same einsums, same mask, same softmax, same dtypes), so its
    output is bit-identical to the ``generate()`` oracle's. It is kept as
    the portable path, the sharded gang's read, the read of an int8 pool
    and the tests' bit-exact reference. Its cost is the table's width: it
    gathers ``pages_per_seq`` pages for every row, live or not, and scores
    every query against all of them.
  * ``kernel="pallas"`` — the Pallas kernels (ROADMAP S2), what
    ``"auto"`` resolves to on a TPU (:func:`default_kernel`). The pools
    stay in HBM in their own ``[n_blocks, page, KV, D]`` layout
    (``memory_space=ANY``; a page of all KV heads is one contiguous
    ``[page * KV, D]`` tile-aligned slab, a free reshape); the page
    table and the positions arrive by scalar prefetch; a grid cell
    copies the pages its queries can see, and no more, into VMEM by DMA,
    a block of pages in flight while the block before it is scored, and
    folds them into an online softmax. The trip count is dynamic: one
    compiled program serves every context length. What a read costs
    follows the live context, not ``max_seq_len``. Which kernel a program
    gets follows its shape (:func:`kernel_path`):

    - the **decode kernel** (``paged_decode_attention``): ``T <=
      MAX_Q_TOKENS`` query positions a row, which is plain decode and the
      speculative verify window (``T = gamma + 1``, the same q tile ``T``
      times taller); one grid cell walks the batch rows (a decode batch
      whole, a wide window's in groups); every query head scored
      against every row of a page behind a block-diagonal mask, ``KV``
      times the arithmetic on a read that the HBM bounds. **A row whose
      table starts with the scratch block is an idle slot** (block 0 is
      no row's; the engine zeroes a freed slot's table and leaves its
      position stale): its result is exactly 0 and it costs a scalar
      compare, no page, no block of scores and no wait. What a call costs
      follows the rows that are live, not the slots.
    - the **chunk kernel** (``paged_chunk_attention``): a wider window
      at the consecutive positions ``start + t``, which is a prefill chunk
      (or the verify window of ``gamma >= 8``, a grid cell a slot); a grid
      cell a tile of query positions, pages 0 to the tile's own; a block
      of pages is de-interleaved by key-value head once (a float32
      staging copy read with a stride of ``KV`` rows) and each head's
      query rows are scored against their own keys only.

    An int8 pool is read by lax under the same ``kernel="pallas"``; the
    engine labels ``lzy_kernel_dispatch_total{path}`` with the path each
    program took (``pallas``, ``chunk_pallas``, ``lax``). The sharded
    engine (GSPMD cannot partition the custom call) resolves ``"auto"``
    to ``"lax"`` itself.

    Online softmax reorders the sums, so the kernels are not bit-identical
    to lax: all are judged against float32 attention within
    :data:`TOLERANCE` (ROADMAP D4). Off the TPU they run under the Pallas
    TPU interpreter (``ops/interpret.py``), which models the DMAs and
    semaphores; an engine built for them outside the interpreter lowers
    them for a TPU at construction (:func:`lower_pallas_for_tpu`) and fails
    there, in the lowering's own words, if the shapes cannot be served.

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
(``lzy_kernel_*``) and surfaced through ``EngineStats``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

_NEG_INF = -1e30

#: the written tolerance of the read paths against float32 attention over
#: the same pool values (ROADMAP D4): the largest absolute difference,
#: relative to the reference's largest magnitude or 1, by compute dtype.
#: bfloat16 carries one rounding of the probabilities and one of the
#: output (seen on the chip at serving shapes: 0.003 for T = 5, 0.0006
#: for T = 1); float32 only the order of the sums.
TOLERANCE = {"bfloat16": 2e-2, "float32": 1e-5}

DISPATCHES = REGISTRY.counter(
    "lzy_kernel_dispatch_total",
    "paged-attention dispatches by kernel path (pallas/chunk_pallas/lax)")
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
    into the exported EWMA. Callers are quantization probes and tests
    — the hot path never reads quantized values back to the host."""
    prev = _ewma_state["value"]
    cur = float(err) if prev is None else (1 - alpha) * prev + alpha * err
    _ewma_state["value"] = cur
    DEQUANT_ERROR_EWMA.set(cur)
    return cur


def default_kernel() -> str:
    """The kernel ``"auto"`` resolves to, by the platform JAX runs on. On
    a TPU: the Pallas kernels, which compile there at serving shapes and
    read no more than the live context. Anywhere else: lax,
    the portable read. Never the interpreter (``ops/interpret.py``): a
    test that wants the kernel off the TPU asks for ``"pallas"`` by name."""
    return "pallas" if jax.default_backend() == "tpu" else "lax"


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
    the trailing head dim. One formula for every read of an int8 pool,
    and — because the scale is a power of two — one whose value is
    independent of how the compiler fuses it, so quantized reads can
    never diverge from EACH OTHER, only boundedly from fp."""
    return (q.astype(jnp.float32) * scale[..., None]
            + zp[..., None]).astype(dtype)


# -- lax oracle ------------------------------------------------------------------


def paged_scatter_index(page_table: jax.Array, positions: jax.Array,
                        page_size: int):
    """Where each ``(row, position)`` of a ``[B, T]`` chunk lands in a
    pool: ``(block ids, offsets)``, both ``[B * T]``, for
    ``pool.at[blocks, offsets].set(values)``. Rows own their tail blocks
    exclusively, so real positions never collide; an idle row (position 0,
    zeroed table) and a position past a row's allocated blocks land on the
    reserved scratch block 0 and write garbage over garbage. The one cache
    write every model with a paged pool shares."""
    blocks = jnp.take_along_axis(page_table, positions // page_size, axis=1)
    return blocks.reshape(-1), (positions % page_size).reshape(-1)


def _lax_paged_attention(q, k_pool, v_pool, page_table, positions, *,
                         dtype, quant: Optional[KVQuant],
                         window: Optional[int] = None):
    """Gather-attention in EXACTLY the op sequence of the dense cache's
    read. This is the bit-exactness anchor: ``models/llama.py``'s dense
    decode branch (what ``models/generate.py``, the oracle, runs) makes
    these same ops over its ``[B, L, KV, D]`` rows, so any change here
    must keep the einsum forms, mask constant, softmax call and dtype
    casts literally identical."""
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
    if window is not None:
        # a windowed layer: query i sees keys j with i - window < j <= i
        visible &= (jnp.arange(L)[None, None, None, None, :]
                    > positions[:, None, None, :, None] - window)
    s = jnp.where(visible, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("bkgtl,blkd->btkgd", p, vals)


# -- pallas decode kernel ----------------------------------------------------------

#: the widest query window the decode kernel takes: plain decode (T == 1)
#: and the speculative verify window (T == gamma + 1) share one q tile of
#: ``T * H`` rows. Prefill chunks are wider: the chunk kernel's.
MAX_Q_TOKENS = 8

#: ``lzy_kernel_dispatch_total{path}`` label of a prefill chunk's read
#: through the chunk kernel
CHUNK_PATH = "chunk_pallas"

#: pool rows, ``(position, kv head)`` pairs, scored per compute block:
#: 8 pages of 16 positions x 8 kv heads. One block is two ``[1024, D]``
#: buffers (K and V), double-buffered.
_BLOCK_ROWS = 1024

#: q rows (batch rows x window x heads) of the batch rows one grid cell of
#: the decode kernel walks: every decode batch the engine runs is one cell
#: (32 slots of 32 or 64 heads, 64 slots of 32: 262 or 524 KB of q and as
#: much of result in VMEM); a verify window of 5 over 32 slots of 32 heads
#: is 4 cells of 8 slots. Decode on a v5e chip, 32 slots of 32 / 8 heads of
#: 128, bfloat16, page 16, us a call by the rows that are live at 1,024 of
#: context, the rest idle (PERF.md section 6, PR 46; device time, median of
#: 48 calls; "before": one grid cell a slot, an idle slot reading one page
#: and scoring one block):
#:
#:     live rows at 1,024     0      1      2      4      8      32
#:     before               31.7   38.2   44.6   57.3   82.6  236.0
#:     now                   2.0    9.3   16.6   31.2   60.4  235.5
#:
#: 30 rows at 370: 101.1 -> 99.0; the verify window of 5, 11 slots of 32
#: live: 143.0 -> 114.3; a prefill tail (batch 1, 8 positions): 13.74 ->
#: 13.78; 64 slots of 32 / 2 heads, 21 live: 117.8 -> 68.9. A live row's
#: result is the same to the bit in every case.
_CELL_ROWS = 2048


def kernel_path(kernel: str, *, t: int, quantized: bool) -> str:
    """The path a ``paged_attention(kernel=kernel)`` call takes at this
    shape: asking for ``"pallas"`` over a float pool gets the decode kernel
    for a decode-sized query window and the chunk kernel
    (:data:`CHUNK_PATH`) for a wider one, a prefill chunk; an int8 pool is
    read by lax at any width. The engine labels
    ``lzy_kernel_dispatch_total`` with the same answer."""
    if kernel != "pallas":
        return kernel
    if quantized:
        return "lax"
    return "pallas" if t <= MAX_Q_TOKENS else CHUNK_PATH


def _decode_kernel(pos_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, *, group, t, heads, kv_heads, page,
                   pages_per_seq, block_pages, scale):
    """One grid cell walks ``group`` batch rows in order (all of a decode
    batch: one cell a call). A row none of whose positions reaches 0 is an
    idle slot: it is given 0 and costs a scalar compare, no page, no block
    of scores, no wait. A live row's ``[T*H, D]`` query tile is scored
    against the row's live pages only: ``ceil(len / page)`` pages are copied
    from the HBM pool by DMA, ``block_pages`` at a time into one of two VMEM
    buffers (the next block's copies are in flight while this one is
    scored), and folded into a running max / sum / weighted value (online
    softmax).

    A page arrives as ``[page * KV, D]``: the pool's own layout, rows
    ordered ``(position, kv head)``. Every query head is scored against
    every row and a block-diagonal mask keeps its own kv head's: the
    contraction stays one ``[T*H, D] x [D, rows]`` matmul per block with
    no relayout of the page, at ``KV`` times the arithmetic, on a read
    that the HBM bounds.

    Numerics: scores and the running max / sum in float32, scaled after
    the dot, probabilities cast to the pool's dtype before the value
    contraction (as the lax path does), the sum of the float32
    probabilities divides the float32 accumulator once at the end."""
    cell = pl.program_id(0)
    _, m_rows, d = q_ref.shape
    rows = page * kv_heads
    cols = block_pages * rows
    g = heads // kv_heads

    # what no row changes, built once a call: each query row's place in
    # the window (t-major over heads) and the block-diagonal mask
    row_t = lax.div(lax.broadcasted_iota(jnp.int32, (m_rows, 1), 0), heads)
    col = lax.broadcasted_iota(jnp.int32, (m_rows, cols), 1)
    row = lax.broadcasted_iota(jnp.int32, (m_rows, cols), 0)
    own_head = lax.rem(col, kv_heads) == lax.div(lax.rem(row, heads), g)
    col_pos = lax.div(col, kv_heads)

    @pl.when(cell == 0)
    def _():
        # a partial block leaves rows of the buffer unwritten; their
        # probabilities are 0, and 0 x whatever VMEM held must be 0
        v_buf[...] = jnp.zeros_like(v_buf)

    def one_row(rl, _):
        b = cell * group + rl
        # the last position any of the row's queries sees
        last = pos_ref[b * t]
        for ti in range(1, t):
            last = jnp.maximum(last, pos_ref[b * t + ti])

        @pl.when(last < 0)
        def _():
            o_ref[rl] = jnp.zeros((m_rows, d), o_ref.dtype)

        @pl.when(last >= 0)
        def _():
            # per query row: the last position it sees
            pos_rows = jnp.full((m_rows, 1), pos_ref[b * t], jnp.int32)
            for ti in range(1, t):
                pos_rows = jnp.where(row_t == ti, pos_ref[b * t + ti],
                                     pos_rows)
            n_pages = lax.div(last + page, page)
            n_blocks = lax.div(n_pages + block_pages - 1, block_pages)

            def for_pages(j, slot, op):
                for i in range(block_pages):
                    @pl.when(j * block_pages + i < n_pages)
                    def _():
                        pid = pt_ref[b * pages_per_seq + j * block_pages + i]
                        dst = pl.ds(i * rows, rows)
                        op(pltpu.make_async_copy(
                            k_hbm.at[pid], k_buf.at[slot, dst],
                            sems.at[0, slot]))
                        op(pltpu.make_async_copy(
                            v_hbm.at[pid], v_buf.at[slot, dst],
                            sems.at[1, slot]))

            for_pages(0, 0, lambda c: c.start())

            def body(j, carry):
                m, l, acc = carry
                slot = lax.rem(j, 2)

                @pl.when(j + 1 < n_blocks)
                def _():
                    for_pages(j + 1, 1 - slot, lambda c: c.start())

                for_pages(j, slot, lambda c: c.wait())
                s = lax.dot_general(
                    q_ref[rl], k_buf[slot], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [T*H, cols]
                visible = own_head & (
                    col_pos <= pos_rows - j * (block_pages * page))
                s = jnp.where(visible, s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = alpha * acc + lax.dot_general(
                    p.astype(v_buf.dtype), v_buf[slot],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l, acc

            _, l, acc = lax.fori_loop(
                0, n_blocks, body,
                (jnp.full((m_rows, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((m_rows, 1), jnp.float32),
                 jnp.zeros((m_rows, d), jnp.float32)))
            # a query of a live row that sees nothing (position -1) is 0
            o_ref[rl] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)

        return 0

    lax.fori_loop(0, group, one_row, 0)


def cell_group(b: int, m_rows: int, limit: int) -> int:
    """Batch rows one grid cell of a decode read walks (this kernel's and
    ``ops/mla.py``'s): the most that divide the batch and keep the cell's q
    block, ``m_rows`` rows a batch row, within ``limit`` rows."""
    return max(g for g in range(1, b + 1)
               if b % g == 0 and (g == 1 or g * m_rows <= limit))


@functools.partial(jax.jit, static_argnames=(
    "dtype", "interpret", "block_rows", "name"))
def _pallas_paged_attention(q, k_pool, v_pool, page_table, positions, *,
                            dtype, interpret: bool,
                            block_rows: int = _BLOCK_ROWS,
                            name: str = "paged_decode_attention"):
    """jitted so that a model's layers, which all make this call at one
    shape, trace and lower the kernel once a program, not once a layer
    (a second of Python each: set-up time on every start of a replica).
    ``name``: what a device trace calls the kernel (a read of chosen pages,
    ``ops/sparse_attention.py``, runs this body under a name of its own)."""
    b, t, h, d = q.shape
    n, page, kv_heads, _ = k_pool.shape
    pages = page_table.shape[1]
    rows = page * kv_heads
    block_pages = max(1, min(pages, block_rows // rows))
    group = cell_group(b, t * h, _CELL_ROWS)
    kernel = functools.partial(
        _decode_kernel, group=group, t=t, heads=h, kv_heads=kv_heads,
        page=page, pages_per_seq=pages, block_pages=block_pages,
        scale=d ** -0.5)
    # a row whose table starts with the scratch block, which no row owns,
    # is an idle slot: it has no real position, whatever its index says
    positions = jnp.where(page_table[:, :1] != 0,
                          positions.astype(jnp.int32), -1)
    tile = pl.BlockSpec((group, t * h, d), lambda c, *_: (c, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // group,),
            in_specs=[tile, pool, pool],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * rows, d), k_pool.dtype),
                pltpu.VMEM((2, block_pages * rows, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, t * h, d), dtype),
        # the V buffer is zeroed by the first cell and kept by the rest
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret.tpu_params(interpret),
        name=name,
    )(positions.reshape(-1),
      page_table.astype(jnp.int32).reshape(-1),
      q.astype(k_pool.dtype).reshape(b, t * h, d),
      # a page is contiguous in the pool: [page, KV, D] -> [page*KV, D]
      k_pool.reshape(n, rows, d), v_pool.reshape(n, rows, d))
    return out.reshape(b, t, kv_heads, h // kv_heads, d)


# -- pallas chunk kernel -----------------------------------------------------------

#: rows of a chunk read's q tile a key-value head (query positions x the
#: group's heads: what one de-interleaved block of keys is scored against),
#: and pool rows, ``(position, kv head)`` pairs, fetched and scored a block:
#: 256 positions at 8 key-value heads, 1,024 at 2. Chosen on a v5e chip
#: (PERF.md section 6, PR 42; ms a layer for a 256-wide chunk at a start of
#: 384 / 3,840, q rows x positions a block): 32 / 8 heads 256 x 128 0.181 /
#: 0.610, 512 x 128 0.160 / 0.509, 1024 x 256 0.153 / 0.366, 1024 x 512
#: 0.168 / 0.359 (the lax read 0.68); 32 / 2 heads 1024 x 256 0.185 / 0.587,
#: 1024 x 512 0.159 / 0.378, 1024 x 1024 0.139 / 0.276: a block's fixed
#: cost (the waits, the row reductions across lanes, the accumulator's
#: rescale) favours large steps, and VMEM ends them (1024 positions of 8
#: heads do not fit).
_CHUNK_ROWS = 1024
_CHUNK_BLOCK_ROWS = 2048


def _chunk_kernel(start_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                  v_buf, k_f32, v_f32, sems, m_ref, l_ref, acc_ref, *,
                  tq, group, kv_heads, page, pages_per_seq, block_pages,
                  scale):
    """One grid cell: ``tq`` consecutive query positions of batch row ``b``
    against the pages they can see, page 0 to the page of the cell's last
    query (clamped to the table's width: a pad's position may lie past it),
    a block of pages in flight while the block before it is scored, folded
    into a running max / sum / weighted value a key-value head. Scores are
    ``[tq x group, block]`` a head and never the whole context.

    A page arrives as ``[page * KV, D]``, the pool's own layout, rows
    ordered ``(position, kv head)``, as in the decode kernel. A block is
    copied once into a float32 staging buffer (a 16-bit pool packs two rows,
    two heads, into one word, and a strided read is of whole words), from
    which head ``g``'s rows, every ``KV``-th, are a strided read. A head's
    ``tq x group`` query rows (the q tile is ``[KV, tq x group, D]``, rows
    ordered (position, head in the group)) are scored against their own
    keys only: none of the decode kernel's ``KV``-fold masked arithmetic, at
    two passes over the block, which ``tq x group`` rows amortise.

    The loops over a block's pages and over the heads are ``fori_loop``s,
    not Python's: every prefill width traces and lowers this body anew when
    its first request arrives, and a body unrolled in Python (a thousand
    equations: a ``cond`` a page a call site) cost 2 s a width of set-up on
    the serving host (PERF.md section 6, PR 42). The heads' loop is unrolled
    when it is lowered (``unroll=True``: one head's matrix work overlaps the
    next one's exponentials, and ``g`` is static to the strided read); the
    pages' has a dynamic trip count, the pages the tile can see.

    Numerics as the decode kernel's: float32 scores, max, sum and
    accumulator, scaled after the dot, probabilities cast to the pool's
    dtype before the value contraction, one division at the end."""
    b, i = pl.program_id(0), pl.program_id(1)
    rows = tq * group
    cols = block_pages * page
    slab = page * kv_heads
    first = start_ref[b] + i * tq
    n_pages = jnp.minimum(lax.div(first + tq - 1 + page, page), pages_per_seq)
    n_blocks = lax.div(n_pages + block_pages - 1, block_pages)
    row_pos = first + lax.div(
        lax.broadcasted_iota(jnp.int32, (rows, 1), 0), group)
    col = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)

    @pl.when((b == 0) & (i == 0))
    def _():
        # a partial block leaves rows of the buffer unwritten; their
        # probabilities are 0, and 0 x whatever VMEM held must be 0
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def for_pages(j, slot, op):
        # the block's pages the tile can see: none past the last block
        def one(k, _):
            pid = pt_ref[b * pages_per_seq + j * block_pages + k]
            dst = pl.ds(pl.multiple_of(k * slab, slab), slab)
            op(pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, dst], sems.at[0, slot]))
            op(pltpu.make_async_copy(
                v_hbm.at[pid], v_buf.at[slot, dst], sems.at[1, slot]))
            return 0

        lax.fori_loop(
            0, jnp.clip(n_pages - j * block_pages, 0, block_pages), one, 0)

    for_pages(0, 0, lambda c: c.start())

    def body(j, _):
        slot = lax.rem(j, 2)
        for_pages(j + 1, 1 - slot, lambda c: c.start())
        for_pages(j, slot, lambda c: c.wait())
        k_f32[...] = k_buf[slot].astype(jnp.float32)
        v_f32[...] = v_buf[slot].astype(jnp.float32)
        visible = col <= row_pos - j * cols

        def head(g, _):
            own = pl.ds(g, cols, stride=kv_heads)
            keys = k_f32[own, :].astype(k_buf.dtype)
            vals = v_f32[own, :].astype(v_buf.dtype)
            s = lax.dot_general(
                q_ref[g], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [rows, cols]
            s = jnp.where(visible, s, _NEG_INF)
            m = m_ref[g]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            # position 0 is in block 0 and every query sees it, so m_new is
            # a real score from the first block on and a masked column's
            # probability is exp(-1e30 - m_new) = 0 with no second mask
            p = jnp.exp(s - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + lax.dot_general(
                p.astype(v_buf.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new
            return 0

        lax.fori_loop(0, kv_heads, head, 0, unroll=True)
        return 0

    lax.fori_loop(0, n_blocks, body, 0)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_chunk_attention(q, k_pool, v_pool, page_table, start, *,
                            interpret: bool):
    """jitted so that a model's layers, which all make this call at one
    shape, trace and lower the kernel once a program."""
    b, t, h, d = q.shape
    n, page, kv_heads, _ = k_pool.shape
    pages = page_table.shape[1]
    group = h // kv_heads
    tq = t
    while tq * group > _CHUNK_ROWS and tq % 2 == 0:
        tq //= 2
    rows = tq * group
    slab = page * kv_heads
    block_pages = max(1, min(pages, _CHUNK_BLOCK_ROWS // slab))
    cols = block_pages * page
    size = jnp.dtype(k_pool.dtype).itemsize
    # what the kernel keeps in VMEM: the q and output tiles twice, the two
    # page buffers twice and their float32 copies, the accumulator, the max
    # and the sum (a row of lanes each), a block's scores and probabilities
    vmem = (4 * kv_heads * rows * d * size + 4 * cols * kv_heads * d * size
            + 2 * cols * kv_heads * d * 4
            + kv_heads * rows * (d + 2 * 128) * 4 + 4 * rows * cols * 4)
    kernel = functools.partial(
        _chunk_kernel, tq=tq, group=group, kv_heads=kv_heads, page=page,
        pages_per_seq=pages, block_pages=block_pages, scale=d ** -0.5)
    tile = pl.BlockSpec((None, kv_heads, rows, d),
                        lambda bi, i, *_: (bi, 0, i, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    # [B, T, KV, G, D] -> [B, KV, T x G, D]: a head's rows together
    qt = q.astype(k_pool.dtype).reshape(b, t, kv_heads, group, d).transpose(
        0, 2, 1, 3, 4).reshape(b, kv_heads, t * group, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, t // tq),
            in_specs=[tile, pool, pool],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, cols * kv_heads, d), k_pool.dtype),
                pltpu.VMEM((2, cols * kv_heads, d), v_pool.dtype),
                pltpu.VMEM((cols * kv_heads, d), jnp.float32),
                pltpu.VMEM((cols * kv_heads, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, t * group, d),
                                       k_pool.dtype),
        # the V buffer is zeroed by the first cell and kept by the rest
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=_interpret.tpu_params(interpret),
        name="paged_chunk_attention",
    )(start.astype(jnp.int32).reshape(-1),
      page_table.astype(jnp.int32).reshape(-1), qt,
      # a page is contiguous in the pool: [page, KV, D] -> [page*KV, D]
      k_pool.reshape(n, slab, d), v_pool.reshape(n, slab, d))
    return out.reshape(b, kv_heads, t, group, d).transpose(0, 2, 1, 3, 4)


def _require_consecutive(positions) -> None:
    """The chunk kernel's contract: a row's positions are ``start + t``
    (``models/llama.py`` and ``models/paged_blocks.py`` build them so, for
    a prefill chunk and a verify window alike). Checked where the values
    are at hand, in an eager call; inside a traced program they are not,
    and the contract is the caller's word."""
    if isinstance(positions, jax.core.Tracer):
        return
    steps = np.diff(np.asarray(positions), axis=1)
    if (steps != 1).any():
        raise ValueError(
            "the chunk kernel reads a window of consecutive positions "
            "(start + t) a row; got steps of "
            f"{sorted(set(steps.ravel().tolist()))}: read such a window "
            "with kernel='lax'")


def lower_pallas_for_tpu(*, batch: int, n_heads: int, n_kv_heads: int,
                         head_dim: int, n_blocks: int, page_size: int,
                         pages_per_seq: int, dtype: Any, t: int = 1) -> None:
    """Lower the Pallas kernel that reads ``t`` query positions a row (the
    decode kernel, or the chunk kernel past ``MAX_Q_TOKENS``) for a TPU at
    these shapes, with no device and no compile, and let the lowering's
    error out. An engine whose programs take the kernels calls this when
    it is built: what the TPU would refuse at the first request is refused
    at construction, in the lowering's own words."""
    sds = jax.ShapeDtypeStruct
    pool = sds((n_blocks, page_size, n_kv_heads, head_dim), dtype)

    def read(q, k_pool, v_pool, page_table, positions):
        return paged_attention(
            q, k_pool, v_pool, page_table, positions, kernel="pallas",
            dtype=jnp.dtype(dtype), interpret=False)

    jax.jit(read).trace(
        sds((batch, t, n_heads, head_dim), dtype), pool, pool,
        sds((batch, pages_per_seq), jnp.int32), sds((batch, t), jnp.int32),
    ).lower(lowering_platforms=("tpu",))


# -- pallas kernel for wide groups, decode and chunk -----------------------------

#: ``lzy_kernel_dispatch_total{path}`` labels of the reads of a pool laid out
#: ``[n_blocks, page, KV x D]`` (:func:`paged_group_attention`)
GROUP_DECODE_PATH = "group_decode_pallas"
GROUP_CHUNK_PATH = "group_prefill_pallas"

#: query positions a grid cell of the chunk read takes (x the group's heads:
#: the rows of a q tile a key-value head) and pooled positions scored a block
_CHUNK_TILE = 32
_GROUP_BLOCK = 512


def group_path(kernel: str, *, t: int) -> str:
    """The label of a program over ``t`` query positions a row that reads
    through :func:`paged_group_attention`."""
    if kernel != "pallas":
        return kernel
    return GROUP_DECODE_PATH if t <= MAX_Q_TOKENS else GROUP_CHUNK_PATH


def _group_kernel(start_ref, pt_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                  v_buf, sems, m_ref, l_ref, acc_ref, *, tq, group, kv_heads,
                  d, page, pages_per_seq, block_pages, window, scale):
    """One grid cell: ``tq`` consecutive query positions of batch row ``b``
    against the pages its queries can see, from the first page that can hold
    a visible key (``window``: a query at ``p`` sees ``p - window < j <= p``;
    page 0 where there is none) to the page of the cell's last query, a
    block of pages in flight while the block before it is scored, folded
    into a running max / sum / weighted value a key-value head. Scores are
    ``[tq x group, block]`` a head and never the whole context.

    A page arrives as ``[page, KV x D]``, the pool's own layout: head
    ``g``'s keys are the lanes ``g x D .. (g + 1) x D`` of every row, so a
    head's ``group`` query heads are scored against their own keys only,
    with no relayout of the page and no masked arithmetic (the decode kernel
    above scores every head against every row: at a group of 16 that is
    eight times the exponentials). The q tile is ``[KV, tq x group, D]``,
    rows ordered (position, head in the group).

    Numerics as the decode kernel's: float32 scores, max, sum and
    accumulator, scaled after the dot, probabilities cast to the pool's
    dtype before the value contraction."""
    b, i = pl.program_id(0), pl.program_id(1)
    rows = tq * group
    cols = block_pages * page
    first = start_ref[b] + i * tq
    n_pages = lax.div(jnp.maximum(first + tq - 1 + page, 0), page)
    lo = 0 if window is None else lax.div(
        jnp.maximum(first - window + 1, 0), page)
    n_blocks = lax.div(n_pages - lo + block_pages - 1, block_pages)
    row_pos = first + lax.div(
        lax.broadcasted_iota(jnp.int32, (rows, 1), 0), group)
    col = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)

    @pl.when((b == 0) & (i == 0))
    def _():
        # a partial block leaves rows of the buffer unwritten; their
        # probabilities are 0, and 0 x whatever VMEM held must be 0
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def for_pages(j, slot, op):
        for k in range(block_pages):
            at = lo + j * block_pages + k

            @pl.when(at < n_pages)
            def _():
                pid = pt_ref[b * pages_per_seq + at]
                dst = pl.ds(k * page, page)
                op(pltpu.make_async_copy(
                    k_hbm.at[pid], k_buf.at[slot, dst], sems.at[0, slot]))
                op(pltpu.make_async_copy(
                    v_hbm.at[pid], v_buf.at[slot, dst], sems.at[1, slot]))

    for_pages(0, 0, lambda c: c.start())

    def body(j, _):
        slot = lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _():
            for_pages(j + 1, 1 - slot, lambda c: c.start())

        for_pages(j, slot, lambda c: c.wait())
        seen = row_pos - (lo + j * block_pages) * page
        visible = col <= seen
        if window is not None:
            visible &= col > seen - window
        for g in range(kv_heads):
            lanes = slice(g * d, (g + 1) * d)
            s = lax.dot_general(
                q_ref[g], k_buf[slot, :, lanes], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [rows, cols]
            s = jnp.where(visible, s, _NEG_INF)
            m = m_ref[g]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot, :, lanes],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[g] = m_new
        return 0

    lax.fori_loop(0, n_blocks, body, 0)
    l = l_ref[...]
    # a row that sees nothing (position -1) reads nothing and returns 0
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _pallas_group_attention(q, k_pool, v_pool, page_table, start, *,
                            window: Optional[int], interpret: bool):
    """jitted so that the layers of one kind, which all make this call at one
    shape, trace and lower the kernel once a program."""
    b, t, h, d = q.shape
    n, page, width = k_pool.shape
    kv_heads = width // d
    pages = page_table.shape[1]
    group = h // kv_heads
    decode = t <= MAX_Q_TOKENS
    tq = t if decode else min(t, _CHUNK_TILE)
    if t % tq:
        raise ValueError(
            f"a prefill chunk of {t} positions is not whole tiles of {tq}")
    rows = tq * group
    block_pages = max(1, min(pages, _GROUP_BLOCK // page))
    cols = block_pages * page
    size = jnp.dtype(k_pool.dtype).itemsize
    # what the kernel keeps in VMEM: the q and output tiles twice, the two
    # page buffers twice, the accumulator, the max and the sum (a row of
    # lanes each), a block's scores and probabilities
    vmem = (4 * kv_heads * rows * d * size + 4 * cols * width * size
            + kv_heads * rows * (d + 2 * 128) * 4 + 4 * rows * cols * 4)
    kernel = functools.partial(
        _group_kernel, tq=tq, group=group, kv_heads=kv_heads, d=d, page=page,
        pages_per_seq=pages, block_pages=block_pages, window=window,
        scale=d ** -0.5)
    tile = pl.BlockSpec((None, kv_heads, rows, d),
                        lambda bi, i, *_: (bi, 0, i, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    # [B, T, KV, G, D] -> [B, KV, T x G, D]: a head's rows together
    qt = q.astype(k_pool.dtype).reshape(b, t, kv_heads, group, d).transpose(
        0, 2, 1, 3, 4).reshape(b, kv_heads, t * group, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, t // tq),
            in_specs=[tile, pool, pool],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, cols, width), k_pool.dtype),
                pltpu.VMEM((2, cols, width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, t * group, d),
                                       k_pool.dtype),
        # the V buffer is zeroed by the first cell and kept by the rest
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=_interpret.tpu_params(interpret),
        name="paged_group_decode" if decode else "paged_group_prefill",
    )(start.astype(jnp.int32).reshape(-1),
      page_table.astype(jnp.int32).reshape(-1), qt, k_pool, v_pool)
    return out.reshape(b, kv_heads, t, group, d).transpose(0, 2, 1, 3, 4)


@trace.part(trace.ATTN_READ)
def paged_group_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          page_table: jax.Array, start: jax.Array, *,
                          window: Optional[int] = None, kernel: str = "lax",
                          interpret: Optional[bool] = None) -> jax.Array:
    """Attention through the page table for a model with many query heads a
    key-value head (a group of 16: ``models/cohere2_moe.py``), whose pools
    are ``[n_blocks, page, KV x D]``: a token's keys (or values) of all
    heads side by side in one row, so that a head's are a slice of lanes.
    (Why another layout than ``[n_blocks, page, KV, D]``: the compiler
    copies the whole pool to turn one into the other, 287 MB a leaf a call
    at the Command A+ widths; one layout has to serve decode and chunk.)

    ``q`` ``[B, T, H, D]`` post-RoPE queries at the consecutive positions
    ``start[b] + t`` (``start`` ``[B]`` int32); ``page_table`` ``[B, P]``;
    ``window`` (static): a query at ``p`` sees keys ``p - window < j <= p``
    (None: every key up to itself), and pages wholly behind the window are
    never named: the engine has returned them and the table reads scratch
    there. ``"pallas"``: one kernel body under two names of a device trace,
    ``paged_group_decode`` (``T <= MAX_Q_TOKENS``: a grid cell a row) and
    ``paged_group_prefill`` (a chunk cut into tiles of ``_CHUNK_TILE``
    positions), which walks the pages from the first visible one to the
    cell's own and keeps a running softmax: what it costs follows the
    visible context, never the table's width, and no ``[heads, chunk,
    context]`` scores exist. ``"lax"``: the gathered table scored whole, its
    oracle. Both are judged against float32 attention within
    :data:`TOLERANCE`. Returns ``[B, T, KV, G, D]``."""
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown paged-attention kernel {kernel!r}; known: lax, pallas")
    if kernel == "pallas":
        return _pallas_group_attention(
            q, k_pool, v_pool, page_table, start, window=window,
            interpret=_interpret.resolve(interpret))
    n, page, width = k_pool.shape
    d = q.shape[-1]
    positions = start[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)
    return _lax_paged_attention(
        q, k_pool.reshape(n, page, width // d, d),
        v_pool.reshape(n, page, width // d, d), page_table, positions,
        dtype=k_pool.dtype, quant=None, window=window)


def lower_group_for_tpu(*, batch: int, t: int, n_heads: int, n_kv_heads: int,
                        head_dim: int, n_blocks: int, page_size: int,
                        pages_per_seq: int, dtype: Any,
                        window: Optional[int]) -> None:
    """Lower :func:`paged_group_attention`'s kernel for a TPU at these
    shapes, with no device and no compile, and let the lowering's error
    out."""
    sds = jax.ShapeDtypeStruct
    pool = sds((n_blocks, page_size, n_kv_heads * head_dim), dtype)

    def read(q, k_pool, v_pool, page_table, start):
        return _pallas_group_attention(
            q, k_pool, v_pool, page_table, start, window=window,
            interpret=False)

    jax.jit(read).trace(
        sds((batch, t, n_heads, head_dim), dtype), pool, pool,
        sds((batch, pages_per_seq), jnp.int32), sds((batch,), jnp.int32),
    ).lower(lowering_platforms=("tpu",))


# -- public op -------------------------------------------------------------------


@trace.part(trace.ATTN_READ)
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
      (id 0 = the reserved scratch block; a row whose first entry is 0 is
      an idle slot to the decode kernel, which gives it 0 and reads no
      page for it: the lax read and the chunk kernel score what its
      positions say, and nobody reads an idle slot's result);
    - ``positions``: ``[B, T]`` int32 absolute positions of the queries
      (the causal mask: pooled slot ``l`` is visible iff
      ``l <= position``);
    - ``kernel``: ``"lax"`` (portable, bit-identical to the dense
      cache's read) or ``"pallas"`` (the decode kernel or the chunk kernel
      by the window's width, lax for an int8 pool: :func:`kernel_path`;
      the chunk kernel takes ``positions[:, 0]`` and the positions after
      it as consecutive, and an eager call with others is refused;
      ``interpret=None`` takes the process's ``ops.interpret`` setting);
    - ``dtype``: compute/output dtype (defaults to the pool dtype; int8
      pools must pass the model's activation dtype).

    Returns ``[B, T, KV, G, D]`` — the grouped-query layout the caller's
    output projection consumes (``reshape(b, t, h * d)``).
    """
    if dtype is None:
        if quant is not None:
            raise ValueError("quantized pools need an explicit dtype")
        dtype = k_pool.dtype
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown paged-attention kernel {kernel!r}; known: lax, pallas")
    path = kernel_path(kernel, t=q.shape[1], quantized=quant is not None)
    if path == "pallas":
        return _pallas_paged_attention(
            q, k_pool, v_pool, page_table, positions, dtype=jnp.dtype(dtype),
            interpret=_interpret.resolve(interpret))
    if path == CHUNK_PATH:
        # a chunk's positions are consecutive: the row's first names them
        _require_consecutive(positions)
        return _pallas_chunk_attention(
            q, k_pool, v_pool, page_table, positions[:, 0],
            interpret=_interpret.resolve(interpret)).astype(dtype)
    return _lax_paged_attention(
        q, k_pool, v_pool, page_table, positions, dtype=dtype, quant=quant)

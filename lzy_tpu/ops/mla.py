"""Reads of a paged *latent* cache: multi-head latent attention (DeepSeek-V2 /
V3, ``models/deepseek_v3.py``) in its absorbed form.

A latent-attention layer caches, a token, one vector with no head axis:
``[c ; k_rope]``, the normalised compressed key-value ``c`` (``value_dim``
values, 512) and the one rotary key all heads share (64). The pool is
``[n_blocks, page, width]`` (``width`` 576), addressed through the same page
table as a key/value pool (``ops/paged_attention.py``). In the absorbed form
the key's up-projection is folded into the query
(``q~_h = W_kvb,K,h^T q_nope_h``) and the value's is applied after the sum, so
the read is attention with **one** "key" of ``width`` shared by every query
head, whose first ``value_dim`` values are also the "value":

    s_h(l) = scale * <[q~_h ; q_rope_h], pool(l)>     l <= the query's position
    u_h    = sum_l softmax(s_h)(l) * pool(l)[:value_dim]

Equal in exact arithmetic to the published form that expands ``c`` into
per-head keys and values; a page is read once and serves scores and values of
every head.

- :func:`mla_attention` with ``kernel="pallas"``: one kernel body under two
  names a device trace shows, ``mla_paged_decode`` (plain decode and the
  speculative verify window: a grid cell walks as many batch rows as keep
  its q block within ``_CELL_ROWS`` rows, all 32 slots of 16 heads in one
  cell) and ``mla_paged_prefill`` (a batch-1 chunk cut into tiles of
  ``_PREFILL_TILE`` query positions, one grid cell a tile). The pool stays in
  HBM (``memory_space=ANY``); the page table and each row's first position
  arrive by scalar prefetch; a live row copies the pages its last query can
  see, and no more, into VMEM by DMA, a block of pages in flight while the
  block before it is scored, and folds them into an online softmax (float32
  scores, running max and sum). What it costs follows the live rows and
  their live context, not the slots and not ``max_seq_len``: both are the
  full softmax over the whole prefix.
- ``kernel="lax"``: the same sum in plain ``jax.numpy`` over the gathered
  table (every page of the table, live or not): the portable path and the
  kernel's oracle.

Query positions of a row are consecutive (``start + t``), as every program of
the engine makes them (decode ``T = 1``, verify ``T = gamma + 1``, a prefill
chunk), so the causal mask needs each row's first position only. **A row
whose ``start`` is below 0 is idle** (a slot with no request in it; position
0 is a live row with one visible key): under both kernels its result is 0,
and the Pallas read spends a scalar compare on it, no page, no block of
scores, no wait.

**Under a window** (a static ``window``: ``ops/latent_select.py``
``latent_window_attention``, the sliding layers of ``models/dots3_note.py``
and ``models/motif.py``) a query sees its ``window`` newest positions. It is
the same walk begun at the window's first page instead of page 0, with one
more compare in the mask and a block no wider than a window can fill; the
table may read scratch behind the window, and those pages are not touched.
``window=None`` traces the program this module traced before it knew of
windows: the window is a Python branch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.ops.paged_attention import cell_group
from lzy_tpu.utils import trace

_NEG_INF = -1e30

#: ``lzy_kernel_dispatch_total{path}`` labels of the reads
DECODE_PATH = "mla_decode_pallas"
PREFILL_PATH = "mla_prefill_pallas"
LAX_PATH = "mla_lax"

#: the widest query window one grid cell takes whole (decode, verify); a
#: wider window is a prefill chunk and is cut into tiles
MAX_DECODE_TOKENS = 8
#: query positions a grid cell of the prefill read (x heads rows of the q
#: tile) and pooled positions scored a block (two such buffers are in VMEM).
#: On a v5e chip, 16 heads, 640 lanes, a chunk of 256 against a live prefix of
#: 2048 / 4096 / 7680 (ms a layer, 27 reads a program; PERF.md section 6,
#: PR 36): tile 32 block 128 0.31 / 0.58 / 1.05 (the float32 accumulator is
#: rescaled once a block: at 128 positions a block that is as long as the
#: block's matrix products); tile 32 block 512 0.18 / 0.31 / 0.55; tile 64
#: block 512 0.17 / 0.29 / 0.50; the lax form 0.68 whatever is live. Decode,
#: 32 rows at 7936: block 128 229 GB/s, block 512 420, block 1024 494 with
#: 8 rows at 1024 the slower for it. Tile 128 and block 1024 at tile 64 pass
#: the kernel's VMEM.
_PREFILL_TILE = 64
_BLOCK_POSITIONS = 512
#: q rows (batch rows x window x heads) of the batch rows one grid cell of
#: the decode read walks: 32 slots of 16 heads in one cell (655 KB of q and
#: 524 KB of result in VMEM), 4 slots a cell for a verify window of 5.
#: Decode on a v5e chip, 32 slots of 16 heads, page 16, us a call by the
#: rows that are live, the rest idle (PERF.md section 6, PR 45; device time,
#: median of 81 calls; "before": one grid cell a slot, an idle slot reading
#: one page and scoring one block):
#:
#:     live rows at 4,096     0      1      2      4      8      32
#:     before               40.4   49.8   59.1   78.0  116.6  342.9
#:     now                   2.6   13.2   23.6   45.2   87.7  342.8
#:
#: 32 rows at 7,936: 666.5 -> 665.7 (488 GB/s); 8 rows at 1,024: 55.5 ->
#: 27.0; a verify window of 5 at 4,096, 11 slots of 32 live: 202.1 -> 169.3;
#: a prefill chunk of 256 at 3,840: 255.3 -> 255.0. A live row's result is
#: the same to the bit in every case. Two forms that were measured and did
#: not stay: row ids sorted live-first by scalar prefetch over a grid of 32
#: cells, the idle ones standing still (14.6 / 25.6 at 1 / 2 live rows, and
#: 1.6 us a call of sort and select outside the kernel); a live row's last
#: block starting the next live row's first (25.0 at 2 live rows, 353.8 at
#: 32: the loop body that can start another row's pages is 0.07 us a block
#: slower, which is more than the wait it saves).
_CELL_ROWS = 512
#: **Under a window** (``latent_window_decode``: ``ops/latent_select.py``
#: ``latent_window_attention``) a live row's walk begins at its window's
#: first page and a block is no wider than a window can fill
#: (:func:`window_block_pages`). On a v5e chip, pages of 64, us a call (a
#: layer) by the rows that are live, the rest idle (PERF.md section 6, PR
#: 66; device time, 5 calls; "XLA": the two gathers of every slot's window
#: and the plain sums, what a decode round ran before):
#:
#:     16 slots, 64 heads, 1,152 lanes, window 513, rows at 16,384
#:     live rows               0      1      4     16
#:     XLA                   167.1  167.1  167.1  167.1
#:     two blocks of 5 pages   9.0   12.1   22.9   66.2
#:     blocks of 8 and 1       8.8   13.0   26.2   79.2
#:     one block of 9          8.6   12.2   23.3   67.4
#:
#:     64 slots, 80 heads, 640 lanes, window 128, rows at 3,000
#:     live rows               0      8     34     64
#:     XLA                   115.8  115.9  115.9  115.8
#:     one block of 3 pages   20.8   33.2   74.5  121.5
#:     blocks of 2 and 1      20.8   37.0   90.0  150.8
#:     a block of 8 (512)     21.0   35.2   82.3  136.1
#:
#: 3.6 and 1.6 us a live row; with none live the call moves its q and result
#: blocks (11.7 MB at 64 slots of 80 heads: 20 us). A full engine is within
#: a tenth of XLA's (and XLA's copies the 37 MB pool besides: 61 us a layer
#: in a traced round), so a decode program takes the kernel whatever is
#: live: no ``lax.cond``.


def read_path(kernel: str, *, t: int) -> str:
    """The label of a program with ``t`` query positions a row."""
    if kernel != "pallas":
        return LAX_PATH
    return DECODE_PATH if t <= MAX_DECODE_TOKENS else PREFILL_PATH


def _absorbed(q, lat, pos, *, value_dim: int, scale: float):
    """``q`` [B, T, H, W] at positions ``pos`` [B, T] against ``lat``
    [B, L, W], whose row ``l`` is position ``l``: scores and softmax in
    float32, probabilities cast to ``lat``'s dtype before the value
    contraction (as the kernel does). Returns [B, T, H, value_dim]."""
    s = jnp.einsum("bthw,blw->bhtl", q.astype(lat.dtype), lat,
                   preferred_element_type=jnp.float32) * scale
    visible = (jnp.arange(lat.shape[1])[None, None, None, :]
               <= pos[:, None, :, None])
    p = jax.nn.softmax(jnp.where(visible, s, _NEG_INF), axis=-1)
    return jnp.einsum("bhtl,blv->bthv", p.astype(lat.dtype),
                      lat[..., :value_dim])


@trace.part(trace.ATTN_READ)
def lax_mla_attention(q, pool, page_table, start, *, value_dim: int,
                      scale: float):
    """The absorbed sum over the whole table: ``pool`` [n_blocks, page, W]
    gathered through ``page_table`` [B, P], every page of it, live or not;
    ``start`` [B], below 0 for an idle row, whose result is 0."""
    b, t, _, w = q.shape
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
    out = _absorbed(q, pool[page_table].reshape(b, -1, w), pos,
                    value_dim=value_dim, scale=scale)
    return jnp.where((start >= 0)[:, None, None, None], out, 0)


@trace.part(trace.ATTN_READ)
def causal_mla_attention(q, lat, *, value_dim: int, scale: float):
    """The absorbed sum with no cache: ``q`` [B, T, H, W] against the chunk's
    own ``lat`` [B, T, W], causal."""
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return _absorbed(q, lat, pos, value_dim=value_dim, scale=scale)


def _kernel(start_ref, pt_ref, q_ref, pool_hbm, o_ref, buf, sems, m_ref,
            l_ref, acc_ref, *, group, tq, heads, page, pages_per_seq,
            block_pages, value_dim, scale, window):
    """One grid cell: ``group`` batch rows at tile ``i`` of their query
    window, walked in order. A live row is ``tq`` consecutive query
    positions (``tq * heads`` rows of q, position-major) against the pages
    the last of them can see; an idle row (``start`` below 0) is given 0
    and costs a scalar compare: no page, no block of scores, no wait.
    Under a ``window`` the walk begins at the page that holds the first
    position the tile's first query sees (the table may read scratch before
    it), column 0 of the first block is that page's first position, and a
    query sees the ``window`` newest positions up to its own; ``None``
    traces what the kernel traced before it knew of windows.
    Numerics: scores, the running max and sum and the accumulator in
    float32, scaled after the dot; probabilities cast to the pool's dtype
    before the value contraction; the sum of the float32 probabilities
    divides the accumulator once at the end."""
    g, i = pl.program_id(0), pl.program_id(1)
    rows = tq * heads
    cols = block_pages * page
    # per q row: its position past the window's first
    row_off = i * tq + lax.div(
        lax.broadcasted_iota(jnp.int32, (rows, 1), 0), heads)
    col = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)

    @pl.when((g == 0) & (i == 0))
    def _():
        # a partial block leaves rows of the buffer unwritten; their
        # probabilities are 0, and 0 x whatever VMEM held must be 0
        buf[...] = jnp.zeros_like(buf)

    def row(rl, _):
        r = g * group + rl
        first = start_ref[r]

        @pl.when(first < 0)
        def _():
            o_ref[rl] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(first >= 0)
        def _():
            # per q row: the last pooled position it sees
            row_pos = first + row_off
            n_pages = lax.div(first + (i + 1) * tq - 1 + page, page)
            if window is not None:
                # the pages to walk and the positions, counted from the
                # window's first page
                page0 = lax.div(
                    jnp.maximum(first + i * tq - (window - 1), 0), page)
                n_pages = n_pages - page0
                row_pos = row_pos - page0 * page
            n_blocks = lax.div(n_pages + block_pages - 1, block_pages)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def for_pages(j, slot, op):
                for k in range(block_pages):
                    @pl.when(j * block_pages + k < n_pages)
                    def _():
                        at = r * pages_per_seq + j * block_pages + k
                        pid = pt_ref[at if window is None else at + page0]
                        op(pltpu.make_async_copy(
                            pool_hbm.at[pid],
                            buf.at[slot, pl.ds(k * page, page)],
                            sems.at[slot]))

            for_pages(0, 0, lambda c: c.start())

            def body(j, _):
                slot = lax.rem(j, 2)

                @pl.when(j + 1 < n_blocks)
                def _():
                    for_pages(j + 1, 1 - slot, lambda c: c.start())

                for_pages(j, slot, lambda c: c.wait())
                lat = buf[slot]                               # [cols, W]
                s = lax.dot_general(
                    q_ref[rl], lat, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                last = row_pos - j * cols
                visible = col <= last                         # [rows, cols]
                if window is not None:
                    visible &= col > last - window
                s = jnp.where(visible, s, _NEG_INF)
                m = m_ref[...]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
                l_ref[...] = alpha * l_ref[...] + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
                    p.astype(lat.dtype), lat[:, :value_dim],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[...] = m_new
                return 0

            lax.fori_loop(0, n_blocks, body, 0)
            # a live row sees its own position at least: the sum is not 0
            o_ref[rl] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return 0

    lax.fori_loop(0, group, row, 0)


def window_block_pages(window: int, tq: int, page: int) -> int:
    """Pages a block of a read under ``window`` holds. The ``window + tq -
    1`` positions a tile of ``tq`` queries sees touch ``touch`` pages at
    most; they go in as few blocks as ``_BLOCK_POSITIONS`` allows, of equal
    size, so no block is wider than a window can fill."""
    touch = -(-(window + tq - 2) // page) + 1
    blocks = -(-touch // max(1, _BLOCK_POSITIONS // page))
    return -(-touch // blocks)


@functools.partial(jax.jit, static_argnames=(
    "value_dim", "scale", "interpret", "window", "name", "block_pages"))
def _pallas_mla_attention(q, pool, page_table, start, *, value_dim: int,
                          scale: float, interpret: bool,
                          window: Optional[int] = None,
                          name: Optional[str] = None,
                          block_pages: Optional[int] = None):
    """jitted so that the layers, which all make this call at one shape,
    trace and lower the kernel once a program. ``name``: what a device
    trace calls the kernel (a read under a window runs this body under a
    name of its own); ``block_pages``: a tool's override of the block."""
    b, t, h, w = q.shape
    n, page, _ = pool.shape
    pages = page_table.shape[1]
    decode = t <= MAX_DECODE_TOKENS
    tq = t if decode else min(t, _PREFILL_TILE)
    if t % tq:
        raise ValueError(
            f"a prefill chunk of {t} positions is not whole tiles of {tq}")
    rows = tq * h
    group = cell_group(b, rows, _CELL_ROWS)
    if block_pages is None:
        block_pages = max(1, min(pages, _BLOCK_POSITIONS // page))
        if window is not None:
            block_pages = min(block_pages,
                              window_block_pages(window, tq, page))
    kernel = functools.partial(
        _kernel, group=group, tq=tq, heads=h, page=page, pages_per_seq=pages,
        block_pages=block_pages, value_dim=value_dim, scale=scale,
        window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // group, t // tq),
            in_specs=[
                pl.BlockSpec((group, rows, w), lambda g, i, *_: (g, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((group, rows, value_dim),
                                   lambda g, i, *_: (g, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * page, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, value_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, t * h, value_dim), pool.dtype),
        # the page buffer is zeroed by the first cell and kept by the rest
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret.tpu_params(interpret),
        name=name or ("mla_paged_decode" if decode else "mla_paged_prefill"),
    )(start.astype(jnp.int32).reshape(-1),
      page_table.astype(jnp.int32).reshape(-1),
      q.astype(pool.dtype).reshape(b, t * h, w), pool)
    return out.reshape(b, t, h, value_dim)


@trace.part(trace.ATTN_READ)
def mla_attention(q: jax.Array, pool: jax.Array, page_table: jax.Array,
                  start: jax.Array, *, value_dim: int, scale: float,
                  kernel: str = "lax", interpret: Optional[bool] = None,
                  window: Optional[int] = None,
                  name: Optional[str] = None) -> jax.Array:
    """The absorbed latent read through the page table.

    - ``q``: ``[B, T, H, W]`` absorbed queries ``[q~ ; q_rope]`` (rotary
      applied), ``W = value_dim + rope width``;
    - ``pool``: ``[n_blocks, page, W]`` cached ``[c ; k_rope]`` (id 0 = the
      reserved scratch block);
    - ``page_table``: ``[B, P]`` int32 block ids in position order;
    - ``start``: ``[B]`` int32, the position of each row's first query; query
      ``t`` sits at ``start + t`` and sees pooled positions up to itself;
      below 0 for an idle row, whose result is 0 and whose pages (the
      kernel's) are not read;
    - ``kernel``: ``"lax"`` or ``"pallas"`` (``interpret=None`` takes the
      process's ``ops.interpret`` setting);
    - ``window`` (static; ``kernel="pallas"`` alone: the ``lax`` form of a
      read under a window is ``latent_select.latent_window_attention``'s):
      a query sees its ``window`` newest positions, and the walk begins at
      the window's first page: the table may read scratch behind it.
      ``None``: the whole prefix;
    - ``name`` (static): the kernel's name in a device trace, where a caller
      wants its read told from ``mla_paged_decode`` / ``mla_paged_prefill``.

    Returns ``[B, T, H, value_dim]`` in the pool's dtype: each head's
    weighted sum of ``c``, for the value up-projection to finish."""
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown latent-read kernel {kernel!r}; known: lax, pallas")
    if kernel == "pallas":
        return _pallas_mla_attention(
            q, pool, page_table, start, value_dim=value_dim,
            scale=float(scale), interpret=_interpret.resolve(interpret),
            window=window, name=name)
    if window is not None:
        raise ValueError(
            "the lax read under a window gathers the window alone: "
            "latent_select.latent_window_attention")
    return lax_mla_attention(q, pool, page_table, start,
                             value_dim=value_dim, scale=scale)


def lower_for_tpu(*, batch: int, t: int, heads: int, width: int,
                  value_dim: int, n_blocks: int, page_size: int,
                  pages_per_seq: int, dtype) -> None:
    """Lower the kernel for a TPU at these shapes, with no device and no
    compile, and let the lowering's error out."""
    sds = jax.ShapeDtypeStruct

    def read(q, pool, page_table, start):
        return _pallas_mla_attention(
            q, pool, page_table, start, value_dim=value_dim,
            scale=1.0, interpret=False)

    jax.jit(read).trace(
        sds((batch, t, heads, width), dtype),
        sds((n_blocks, page_size, width), dtype),
        sds((batch, pages_per_seq), jnp.int32), sds((batch,), jnp.int32),
    ).lower(lowering_platforms=("tpu",))

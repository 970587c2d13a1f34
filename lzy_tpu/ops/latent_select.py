"""Learned sparse attention over a paged *latent* cache: an indexer with
weights of its own scores every cached token, each query keeps the
``index_topk`` best, and the absorbed latent read (``ops/mla.py``) runs over
the chosen *tokens* alone (``models/dots3_note.py``; DeepSeek-V3.2's indexer).
Beside it, the absorbed read of a latent pool under a sliding window.

A selecting layer caches, a token, two vectors under one page table: the
latent ``[c ; k_rope]`` (``[pages, page, W]``, as ``ops/mla.py``'s) and the
indexer's key ``k^I`` (``[pages, page, D_I]``). Four steps, each with a
``lax`` form that is its oracle:

1. **the index** (:func:`index_scores`): ``I(t, s) = sum_j w_j(t) *
   relu(q^I_j(t) . k^I(s))`` for every cached position ``s <= t`` of the
   row, ``-1e30`` elsewhere. Products in the pool's dtype with float32
   sums; the ReLU, the head weights and the sum over heads in float32.
   ``kernel="pallas"``: ``latent_index_decode`` (one grid step walks the
   rows; a live row's pages of ``k^I`` come in by DMA, a block in flight
   while the block before it is scored: all 64 heads against a block in one
   product) and ``latent_index_prefill`` (a batch-1 chunk in tiles of 128
   queries, one grid step a (tile, block of keys); the heads one after
   another, each a ``[128, D_I] x [D_I, block]`` product into a float32
   accumulator). **A row at ``index_topk`` positions or fewer needs no
   score** (everything it sees is chosen): both kernels skip it, as they
   skip an idle slot (``start`` below 0) and a block of keys past the tile's
   last query, at a scalar compare each. What they skip they do not write:
   the scores of positions past a query's own are whatever memory held, and
   :func:`latent_topk` masks by position, not by value.
2. **the choice** (:func:`latent_topk`): the exact ``k`` largest a query, a
   tie to the lower position (``jax.lax.top_k``: XLA's, which breaks ties
   so), as positions; a query that sees ``k`` positions or fewer takes them
   all. Exact, never ``approx_max_k``: an approximate choice is another
   function. ``jax.lax.top_k`` costs by the columns it is handed (16.9 ms a
   chunk's 256 queries and 6.0 ms a decode round's 16 slots over the
   table's 50,176 on a v5e chip, whatever the context), so it is run over
   the narrowest of a few widths that holds the program's positions: one
   path, no other method (a choice by bisection on the scores' bit
   patterns was tried and taken out: PERF.md section 6, PR 62).
3. **the read of the chosen** (:func:`latent_chosen_attention`): the chosen
   positions' latent vectors are gathered through the page table
   (``block = table[s // page]``, ``s % page``) into ``[queries, k, W]``,
   each once a query a layer, and read by ``ops/mla.py``'s own kernel
   (``mla_paged_decode``: every query a row of one position over its own
   ``k`` tokens, online softmax in float32), not a copy of it. **Why a
   gather and not a kernel that walks the choices**: a chosen token is 1,280
   bytes, and a DMA a token is issue-bound (2,048 a query, 524 thousand a
   256-wide chunk a layer, some 25 ms at the 0.04 us a DMA ``ops/mla.py``
   measured); **why not the union of a tile's choices**
   (``ops/sparse_attention.py`` ``sparse_prefill_attention``'s design):
   with random weights two neighbouring queries share ``k / visible`` of
   their tokens and no more, so the union of 128 queries' choices is every
   visible token and the read degenerates to the dense one, 16 times the
   arithmetic at 32 thousand tokens.
4. **the read under a window** (:func:`latent_window_attention`): the
   absorbed sum over the ``window`` newest positions of a pool whose pages
   behind the window have gone back (``serving/kv_cache.py``
   ``WindowPages``: the table reads scratch there), gathered from the first
   position a query of the program can see and no earlier: ``window - 1 +
   T`` vectors a row, whatever the context. Plain XLA on the chip too: 513
   keys are a product of a few GFLOP a layer.
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
from lzy_tpu.ops import mla

_NEG_INF = -1e30

#: ``lzy_kernel_dispatch_total{path}`` labels
INDEX_DECODE_PATH = "latent_index_decode"
INDEX_PREFILL_PATH = "latent_index_prefill"
CHOSEN_DECODE_PATH = "latent_chosen_decode_pallas"
CHOSEN_PREFILL_PATH = "latent_chosen_prefill_pallas"
CHOSEN_LAX_PATH = "latent_chosen_lax"

#: query positions a grid step of the prefill index scores (a head's product
#: is ``[tile, D_I] x [D_I, block]``: 128 rows fill the matrix unit) and the
#: cached positions a block holds, prefill and decode
_PREFILL_TILE = 128
_PREFILL_BLOCK = 512
_DECODE_BLOCK = 1024
#: the widest pseudo-page the chosen tokens are handed to ``ops/mla.py`` in
_CHOSEN_PAGE = 128


def index_path(t: int) -> str:
    """The index's label in a program with ``t`` query positions a row
    (whichever form runs it: the read's own label says which)."""
    return INDEX_DECODE_PATH if t == 1 else INDEX_PREFILL_PATH


def chosen_path(kernel: str, *, t: int) -> str:
    """The chosen read's label in a program with ``t`` positions a row."""
    if kernel != "pallas":
        return CHOSEN_LAX_PATH
    return CHOSEN_DECODE_PATH if t <= mla.MAX_DECODE_TOKENS \
        else CHOSEN_PREFILL_PATH


def _block_pages(pages: int, page: int, positions: int) -> int:
    """Pages a block of about ``positions`` cached positions holds: the most
    that divide the table, so that the blocks tile it, and of those a count
    whose positions are whole tiles of 128 lanes where there is one (the
    TPU lowering takes no other block of scores; a table of 98 pages of 64
    has none under 1,024 positions: size a table in multiples of eight
    pages)."""
    most = max(1, positions // page)
    fits = [g for g in range(1, min(pages, most) + 1) if pages % g == 0]
    whole = [g for g in fits if g * page % 128 == 0]
    return max(whole or fits)


# -- 1. the index --------------------------------------------------------------

def lax_index_scores(q, w, pool, page_table, start):
    """The oracle: ``q`` [B, J, T, D], ``w`` [B, T, J] float32, ``pool``
    [n_blocks, page, D] gathered through ``page_table`` [B, P], every page
    of it; ``start`` [B], below 0 for an idle row. Returns [B, T, P x page]
    float32, ``-1e30`` where a query does not see."""
    b, _, t, d = q.shape
    keys = pool[page_table].reshape(b, -1, d)
    s = jnp.einsum("bjtd,bld->bjtl", q.astype(pool.dtype), keys,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bjtl,btj->btl", jnp.maximum(s, 0.0),
                   w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
    seen = (jnp.arange(keys.shape[1])[None, None, :] <= pos[:, :, None]) \
        & (start >= 0)[:, None, None]
    return jnp.where(seen, s, _NEG_INF)


def _page_copies(pt_ref, pool_hbm, buf, sem, *, base, n_pages, block_pages,
                 page, op):
    """``op`` on the DMA of each page of a block that the row holds: table
    entries ``base ..`` into ``buf``'s rows, a page after a page."""
    for k in range(block_pages):
        @pl.when(k < n_pages)
        def _():
            op(pltpu.make_async_copy(
                pool_hbm.at[pt_ref[base + k]],
                buf.at[pl.ds(k * page, page)], sem))


def _index_decode_kernel(start_ref, pt_ref, q_ref, w_ref, pool_hbm, o_ref,
                         buf, sems, *, rows, page, pages_per_seq,
                         block_pages, topk):
    """The one grid step: every row in turn. A row that selects (its
    position is ``topk`` or more) scores the pages it sees, a block in
    flight while the block before it is scored; an idle row and a row that
    does not select cost a scalar compare."""
    cols = block_pages * page
    col = lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    # a partial block leaves rows of the buffer unwritten: they are masked,
    # and must hold numbers for the product all the same
    buf[...] = jnp.zeros_like(buf)

    def row(r, _):
        pos = start_ref[r]

        @pl.when(pos >= topk)
        def _():
            n_pages = lax.div(pos, page) + 1
            n_blocks = lax.div(n_pages + block_pages - 1, block_pages)

            def copies(j, slot, op):
                _page_copies(
                    pt_ref, pool_hbm, buf.at[slot], sems.at[slot],
                    base=r * pages_per_seq + j * block_pages,
                    n_pages=n_pages - j * block_pages,
                    block_pages=block_pages, page=page, op=op)

            copies(0, 0, lambda c: c.start())

            def body(j, _):
                slot = lax.rem(j, 2)

                @pl.when(j + 1 < n_blocks)
                def _():
                    copies(j + 1, 1 - slot, lambda c: c.start())

                copies(j, slot, lambda c: c.wait())
                s = lax.dot_general(
                    q_ref[r], buf[slot], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [J, cols]
                s = jnp.sum(jnp.maximum(s, 0.0) * w_ref[r], axis=0,
                            keepdims=True)
                s = jnp.where(col <= pos - j * cols, s, _NEG_INF)
                o_ref[pl.ds(r, 1),
                      pl.ds(pl.multiple_of(j * cols, cols), cols)] = s
                return 0

            lax.fori_loop(0, n_blocks, body, 0)

        return 0

    lax.fori_loop(0, rows, row, 0)


def _index_prefill_kernel(start_ref, pt_ref, q_ref, w_ref, pool_hbm, o_ref,
                          buf, sem, acc_ref, *, tq, heads, page,
                          pages_per_seq, block_pages, topk):
    """One grid step: tile ``i`` of row ``b``'s queries against block ``kb``
    of its cached keys. Skipped, and nothing written, where the row is idle,
    where no query of the tile selects, and where the block lies past the
    tile's last query."""
    b, i, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cols = block_pages * page
    first = start_ref[b]
    last = first + (i + 1) * tq - 1

    @pl.when((b == 0) & (i == 0) & (kb == 0))
    def _():
        buf[...] = jnp.zeros_like(buf)

    @pl.when((first >= 0) & (last >= topk) & (kb * cols <= last))
    def _():
        def copies(op):
            _page_copies(
                pt_ref, pool_hbm, buf, sem.at[0],
                base=b * pages_per_seq + kb * block_pages,
                n_pages=lax.div(last, page) + 1 - kb * block_pages,
                block_pages=block_pages, page=page, op=op)

        copies(lambda c: c.start())
        copies(lambda c: c.wait())
        keys = buf[...]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for j in range(heads):
            s = lax.dot_general(
                q_ref[0, j], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [tq, cols]
            acc_ref[...] += jnp.maximum(s, 0.0) * w_ref[0, :, j:j + 1]
        pos = first + i * tq + lax.broadcasted_iota(
            jnp.int32, (tq, cols), 0)
        col = kb * cols + lax.broadcasted_iota(jnp.int32, (tq, cols), 1)
        o_ref[0] = jnp.where(col <= pos, acc_ref[...], _NEG_INF)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _pallas_index_scores(q, w, pool, page_table, start, *, topk: int,
                         interpret: bool):
    """jitted so that the layers, which all make this call at one shape,
    trace and lower the kernel once a program."""
    b, heads, t, d = q.shape
    _, page, _ = pool.shape
    pages = page_table.shape[1]
    width = pages * page
    start = start.astype(jnp.int32).reshape(-1)
    table = page_table.astype(jnp.int32).reshape(-1)
    q = q.astype(pool.dtype)
    w = w.astype(jnp.float32)
    if t == 1:
        block_pages = _block_pages(pages, page, _DECODE_BLOCK)
        kernel = functools.partial(
            _index_decode_kernel, rows=b, page=page, pages_per_seq=pages,
            block_pages=block_pages, topk=topk)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,),
                in_specs=[
                    pl.BlockSpec((b, heads, d), lambda g, *_: (0, 0, 0)),
                    pl.BlockSpec((b, heads, 1), lambda g, *_: (0, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((b, width), lambda g, *_: (0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, block_pages * page, d), pool.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                ]),
            out_shape=jax.ShapeDtypeStruct((b, width), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret.tpu_params(interpret),
            name="latent_index_decode",
        )(start, table, q.reshape(b, heads, d),
          w.reshape(b, heads, 1), pool)
        return out.reshape(b, 1, width)
    tq = min(t, _PREFILL_TILE)
    if t % tq:
        raise ValueError(
            f"a prefill chunk of {t} positions is not whole tiles of {tq}")
    block_pages = _block_pages(pages, page, _PREFILL_BLOCK)
    cols = block_pages * page
    kernel = functools.partial(
        _index_prefill_kernel, tq=tq, heads=heads, page=page,
        pages_per_seq=pages, block_pages=block_pages, topk=topk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, t // tq, width // cols),
            in_specs=[
                pl.BlockSpec((1, heads, tq, d),
                             lambda g, i, k, *_: (g, 0, i, 0)),
                pl.BlockSpec((1, tq, heads), lambda g, i, k, *_: (g, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, tq, cols),
                                   lambda g, i, k, *_: (g, i, k)),
            scratch_shapes=[
                pltpu.VMEM((cols, d), pool.dtype),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.VMEM((tq, cols), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, t, width), jnp.float32),
        # the key buffer is zeroed by the first step and kept by the rest
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=_interpret.tpu_params(interpret),
        name="latent_index_prefill",
    )(start, table, q, w, pool)


def index_scores(q: jax.Array, w: jax.Array, pool: jax.Array,
                 page_table: jax.Array, start: jax.Array, *, topk: int,
                 kernel: str = "lax",
                 interpret: Optional[bool] = None) -> jax.Array:
    """The indexer's scores of a program's queries against each row's cached
    keys.

    - ``q``: ``[B, J, T, D]`` index queries, head-major (rotary applied);
    - ``w``: ``[B, T, J]`` float32 head weights;
    - ``pool``: ``[n_blocks, page, D]`` cached index keys (id 0 = scratch);
    - ``page_table``: ``[B, P]`` int32 block ids in position order;
    - ``start``: ``[B]`` the position of each row's first query, below 0
      for an idle row;
    - ``topk``: a query at a position under it selects nothing (it reads all
      it sees) and the kernels leave its scores unwritten.

    Returns ``[B, T, P x page]`` float32: ``I(t, s)`` where ``s`` is at or
    before query ``t``; **whatever memory held** past it under
    ``kernel="pallas"`` (``-1e30`` under ``"lax"``), and for a row the
    kernels skip: :func:`latent_topk` masks by position."""
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown index kernel {kernel!r}; known: lax, pallas")
    if kernel == "pallas":
        return _pallas_index_scores(
            q, w, pool, page_table, start, topk=int(topk),
            interpret=_interpret.resolve(interpret))
    return lax_index_scores(q, w, pool, page_table, start)


# -- 2. the choice --------------------------------------------------------------

#: the widths ``jax.lax.top_k`` is run over, by the positions the program's
#: queries reach: its cost follows the columns it is handed, not the context
#: (a chunk of 256 queries: 1.2 / 5.8 / 16.6 ms over 8,192 / 32,768 / 50,176
#: columns on a v5e chip), so a program picks the narrowest that holds every
#: position it can see
_TOPK_WIDTHS = (8192, 16384, 32768)


def _top_k_over_context(key, pos, k: int):
    """``jax.lax.top_k(key, k)``'s positions, run over the narrowest of
    ``_TOPK_WIDTHS`` (and the whole width) that holds every position the
    program's queries see: one branch of a ``lax.switch`` runs."""
    width = key.shape[-1]
    widths = [w for w in _TOPK_WIDTHS if k <= w < width] + [width]
    if len(widths) == 1:
        return lax.top_k(key, k)[1]
    reach = jnp.max(pos) + 1
    branch = sum((reach > w).astype(jnp.int32) for w in widths[:-1])
    return lax.switch(
        branch,
        [lambda key, w=w: lax.top_k(key[..., :w], k)[1] for w in widths],
        key)


def latent_topk(scores: jax.Array, pos: jax.Array, k: int):
    """The exact ``k`` best cached positions a query: ``scores`` [B, T, L]
    float32 (read only at ``s <= pos``), ``pos`` [B, T] each query's own
    position, below 0 for a query that is not real. Returns ``(idx [B, T,
    k] int32, n [B, T] int32)``: the first ``n`` entries of ``idx`` are the
    chosen positions. A query that sees more than ``k`` positions takes the
    ``k`` of largest score, a tie to the lower position; one that sees ``k``
    or fewer takes them all (``0 .. pos``, whatever ``scores`` holds);
    one that is not real takes none. The chosen come in order of score
    (``jax.lax.top_k``'s)."""
    width = scores.shape[-1]
    if width < k:
        raise ValueError(f"a choice of {k} among {width} cached positions")
    selects = (pos >= k)[..., None]
    seen = jnp.arange(width, dtype=jnp.int32) <= pos[..., None]
    key = jnp.where(seen & selects, scores, -jnp.inf)
    idx = jnp.where(selects, _top_k_over_context(key, pos, k),
                    jnp.arange(k, dtype=jnp.int32))
    return idx.astype(jnp.int32), jnp.clip(pos + 1, 0, k).astype(jnp.int32)


# -- 3. the read of the chosen ---------------------------------------------------

def gather_tokens(pool, page_table, positions):
    """``pool`` [n_blocks, page, W] at ``positions`` [B, S] of each row,
    through ``page_table`` [B, P]: ``[B, S, W]``."""
    page = pool.shape[1]
    blocks = jnp.take_along_axis(page_table, positions // page, axis=1)
    flat = blocks * page + positions % page
    return pool.reshape(-1, pool.shape[-1])[flat]


def latent_chosen_attention(q: jax.Array, pool: jax.Array,
                            page_table: jax.Array, idx: jax.Array,
                            n: jax.Array, *, value_dim: int, scale: float,
                            kernel: str = "lax",
                            interpret: Optional[bool] = None) -> jax.Array:
    """The absorbed latent read over each query's chosen tokens.

    ``q`` ``[B, T, H, W]`` absorbed queries, ``pool`` ``[n_blocks, page,
    W]``, ``page_table`` ``[B, P]``, ``idx`` / ``n`` as :func:`latent_topk`
    gives them. The chosen vectors are gathered once a query and handed to
    ``ops/mla.py`` as a pool of their own: every query a row of one position
    that sees the first ``n`` of its ``k`` tokens (a query with ``n`` 0 is
    an idle row there: 0, nothing read). Returns ``[B, T, H, value_dim]``."""
    b, t, h, w = q.shape
    k = idx.shape[-1]
    pp = min(k, _CHOSEN_PAGE)
    if k % pp:
        raise ValueError(f"{k} chosen tokens are not whole pages of {pp}")
    got = gather_tokens(pool, page_table, idx.reshape(b, t * k))
    out = mla.mla_attention(
        q.reshape(b * t, 1, h, w), got.reshape(b * t * k // pp, pp, w),
        jnp.arange(b * t * k // pp, dtype=jnp.int32).reshape(b * t, k // pp),
        n.reshape(b * t) - 1, value_dim=value_dim, scale=scale,
        kernel=kernel, interpret=interpret)
    return out.reshape(b, t, h, value_dim)


# -- 4. the read under a window -------------------------------------------------

def latent_window_attention(q: jax.Array, pool: jax.Array,
                            window_table: jax.Array, start: jax.Array, *,
                            window: int, value_dim: int,
                            scale: float) -> jax.Array:
    """The absorbed latent read of the ``window`` newest positions: ``q``
    ``[B, T, H, W]`` at positions ``start + t`` (``start`` below 0: an idle
    row, result 0) against ``pool`` ``[n_blocks, page, W]`` through
    ``window_table`` ``[B, P]``. A query at ``p`` sees ``p - window < s <=
    p``; the program gathers positions ``start - window + 1 .. start + T -
    1`` and nothing before them, so the table may read scratch behind the
    window. Returns ``[B, T, H, value_dim]``."""
    b, t, _, _ = q.shape
    page = pool.shape[1]
    span = window - 1 + t
    held = start[:, None] - (window - 1) + jnp.arange(span, dtype=jnp.int32)
    lat = gather_tokens(pool, window_table, jnp.clip(
        held, 0, window_table.shape[1] * page - 1))           # [B, span, W]
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
    seen = (held >= 0)[:, None, :] & (held[:, None, :] <= pos[:, :, None]) \
        & (held[:, None, :] > pos[:, :, None] - window)
    s = jnp.einsum("bthw,bsw->bhts", q.astype(lat.dtype), lat,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None], s, _NEG_INF), axis=-1)
    out = jnp.einsum("bhts,bsv->bthv", p.astype(lat.dtype),
                     lat[..., :value_dim])
    return jnp.where((start >= 0)[:, None, None, None], out, 0)


def causal_latent_attention(q, lat, *, value_dim: int, scale: float,
                            window: Optional[int] = None, scores=None,
                            topk: Optional[int] = None):
    """The same sums with no cache, for the uncached forward: ``q`` [B, T,
    H, W] against the chunk's own ``lat`` [B, T, W], causal; under a
    ``window``, or over the ``topk`` best of ``scores`` [B, T, T] a query
    (every visible position while there are ``topk`` or fewer)."""
    b, t = q.shape[:2]
    at = jnp.arange(t, dtype=jnp.int32)
    seen = jnp.broadcast_to(at[None, :] <= at[:, None], (b, t, t))
    if window is not None:
        seen &= at[None, :] > at[:, None] - window
    if topk is not None and t > topk:
        idx, n = latent_topk(scores, jnp.broadcast_to(at, (b, t)), topk)
        taken = jnp.arange(topk)[None, None, :] < n[..., None]
        seen = jnp.any((idx[..., None] == at) & taken[..., None], axis=2)
    s = jnp.einsum("bthw,blw->bhtl", q.astype(lat.dtype), lat,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None], s, _NEG_INF), axis=-1)
    return jnp.einsum("bhtl,blv->bthv", p.astype(lat.dtype),
                      lat[..., :value_dim])


def lower_for_tpu(*, batch: int, t: int, heads: int, index_heads: int,
                  index_dim: int, width: int, value_dim: int, topk: int,
                  n_blocks: int, page_size: int, pages_per_seq: int,
                  dtype) -> None:
    """Lower the index kernel and the chosen read for a TPU at these
    shapes, with no device and no compile, and let the lowering's error
    out."""
    sds = jax.ShapeDtypeStruct

    def program(qi, w, ik, q, pool, page_table, start):
        scores = _pallas_index_scores(qi, w, ik, page_table, start,
                                      topk=topk, interpret=False)
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
        idx, n = latent_topk(scores, pos, topk)
        return latent_chosen_attention(
            q, pool, page_table, idx, n, value_dim=value_dim, scale=1.0,
            kernel="pallas", interpret=False)

    jax.jit(program).trace(
        sds((batch, index_heads, t, index_dim), dtype),
        sds((batch, t, index_heads), jnp.float32),
        sds((n_blocks, page_size, index_dim), dtype),
        sds((batch, t, heads, width), dtype),
        sds((n_blocks, page_size, width), dtype),
        sds((batch, pages_per_seq), jnp.int32), sds((batch,), jnp.int32),
    ).lower(lowering_platforms=("tpu",))

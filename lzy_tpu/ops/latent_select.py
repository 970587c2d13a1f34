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
   tie to the lower position (``jax.lax.top_k``'s contract), as positions; a
   query that sees ``k`` positions or fewer takes them all. Exact, never
   ``approx_max_k``: an approximate choice is another function.
   ``kernel="pallas"``: ``latent_choice_decode`` (a grid step a slot) and
   ``latent_choice_prefill`` (a grid step eight queries of a batch-1
   chunk), one body: a row's scores come into VMEM as chunks of 128
   positions, become int32 keys in float order masked by position, the
   ``k``-th largest is found by bisection over the key's 32 bits (a compare
   and a count a bit, over the groups of 1,024 positions the furthest query
   reaches and no further) and the chosen are compacted in two levels
   (counts a chunk, their running sum, and for every output place the chunk
   that holds it and the lane within it): no sort, and a position is
   ``chunk * 128 + lane`` in int32, never the result of a product. An idle
   slot and a query under ``k`` cost a scalar compare. ``"lax"`` is
   ``jax.lax.top_k`` over the whole width (it costs by the columns it is
   handed: 17 ms a chunk's 256 queries, 6 ms a decode round's 16 slots over
   50,176 on a v5e chip): the oracle, and what a CPU runs.
3. **the read of the chosen** (:func:`latent_chosen_attention`): the chosen
   positions' latent vectors are copied through the page table (``block =
   table[s // page]``, ``s % page``) into pseudo-pages of 128 tokens,
   ``[queries x k / 128, 128, W]``, each once a query a layer
   (:func:`latent_gather`), and read by ``ops/mla.py``'s own kernel
   (``mla_paged_decode``: every query a row of one position over its own
   ``k`` tokens, online softmax in float32), not a copy of it. **What
   copies them where.** ``kernel="pallas"``, a program of up to
   ``mla.MAX_DECODE_TOKENS`` positions a row (decode, the verify window):
   ``latent_gather_decode``. A query with chosen tokens walks its row's
   pages up to its furthest chosen position in blocks of 512 positions (a
   page a DMA, a block in flight while the block before it is picked
   from); a tile of 128 places against a block is the product of their 0 /
   1 matrix with the block, summed in float32: a place's vector is 1 x
   itself plus zeros, so the copy is exact (a ``-0.0`` comes out ``+0.0``).
   In order of position, as the kernel choice hands them out, a tile meets
   the one or two blocks its positions lie in; a query with none chosen
   costs a scalar compare and nothing of it is written; a query under ``k``
   gets zeros to the end of the last pseudo-page it touches (the read
   multiplies what lies there by a weight of exactly 0). On a v5e chip, 16
   slots, us a layer by the live rows and their context (PERF.md section
   6, PR 64): 2.5 with none live, 36 / 87 / 122 with one at 8 / 32 / 49
   thousand tokens, 2.1 ns a walked position and 19 us a live query, where
   XLA's gather is 836 whatever is live (32,768 vectors at 105 GB/s and
   their addresses). The walk costs by the reaches, XLA's gather by the
   places, so the program takes the walk while the live queries' reaches,
   summed, are under ``_GATHER_WALK_RATIO`` positions a place and
   ``gather_tokens`` past that (16 rows at 32 thousand: 1.36 ms against
   0.84), by a ``lax.cond`` on what ``idx`` and ``n`` say. **A prefill
   chunk keeps** ``gather_tokens``: its 256 queries are one row's, and the
   walk a query reads the row 256 times (8.5 / 21.8 / 30.6 ms at 8 / 32 /
   49 thousand against 10.8). **Why not a DMA a chosen token**: Mosaic
   refuses a one-row slice of a bfloat16 ``(page, 640)`` tile ("Slice shape
   along dimension 1 must be aligned to tiling (8), but is 1": a row is
   half a packed sublane), and at the 0.04 us a DMA's issue ``ops/mla.py``
   measured 2,048 of them would be 82 us a live row besides; **why not the
   union of a tile's choices** (``ops/sparse_attention.py``
   ``sparse_prefill_attention``'s design): with random weights two
   neighbouring queries share ``k / visible`` of their tokens and no more,
   so the union of 128 queries' choices is every visible token and the
   read degenerates to the dense one, 16 times the arithmetic at 32
   thousand tokens. ``kernel="lax"`` is ``gather_tokens`` everywhere: the
   oracle, and what a CPU runs.
4. **the read under a window** (:func:`latent_window_attention`): the
   absorbed sum over the ``window`` newest positions of a pool whose pages
   behind the window have gone back (``serving/kv_cache.py``
   ``WindowPages``: the table reads scratch there), read from the first
   position a query of the program can see and no earlier: ``window - 1 +
   T`` positions a row, whatever the context. ``kernel="pallas"``, a
   program of up to ``mla.MAX_DECODE_TOKENS`` positions a row (decode, the
   verify window): ``ops/mla.py``'s kernel under the name
   ``latent_window_decode``, its walk of a live row begun at the window's
   first page, one more compare in its mask, a block no wider than a window
   can fill (three pages of 64 under a window of 128: one block; nine
   under 513: two of five); an idle slot is a scalar compare. A prefill
   chunk and ``kernel="lax"`` gather the ``window - 1 + T`` vectors of
   every row, live or not, by two XLA gathers and score them in plain XLA:
   the oracle, what a CPU runs, and until PR 66 what a decode round ran
   too (on a v5e chip, 16 slots under a window of 513 at 1,152 lanes: 167
   us a layer whatever is live, where the kernel is 12 with one row live
   and 66 with all sixteen; ``ops/mla.py`` has the table beside
   ``_CELL_ROWS``).
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
from lzy_tpu.utils import trace

_NEG_INF = -1e30

#: ``lzy_kernel_dispatch_total{path}`` labels
INDEX_DECODE_PATH = "latent_index_decode"
INDEX_PREFILL_PATH = "latent_index_prefill"
CHOSEN_DECODE_PATH = "latent_chosen_decode_pallas"
CHOSEN_PREFILL_PATH = "latent_chosen_prefill_pallas"
CHOSEN_LAX_PATH = "latent_chosen_lax"
GATHER_DECODE_PATH = "latent_gather_decode"
CHOICE_DECODE_PATH = "latent_choice_decode"
CHOICE_PREFILL_PATH = "latent_choice_prefill"
CHOICE_LAX_PATH = "latent_choice_lax"
WINDOW_DECODE_PATH = "latent_window_decode"

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

@trace.part(trace.LATENT_INDEX)
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


@trace.part(trace.LATENT_INDEX)
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

#: cached positions a chunk holds (the lanes of a vector register), the
#: chunks a block of the compaction holds (one matrix-unit tile of chunks),
#: the query rows a grid step of the prefill kernel bisects side by side
_CHUNK = 128
_CHUNK_BLOCK = 128
_CHOICE_ROWS = 8
_INT_MIN = -2 ** 31


def choice_path(kernel: str, *, t: int) -> str:
    """The choice's label in a program with ``t`` positions a row."""
    if kernel != "pallas":
        return CHOICE_LAX_PATH
    return CHOICE_DECODE_PATH if t == 1 else CHOICE_PREFILL_PATH


def _ordered_keys(x, seen):
    """float32 ``x`` as int32 keys whose integer order is the float order
    (``-0.0`` as ``+0.0``); the least key where ``seen`` is false, and
    nowhere else."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    b = jnp.where(b == _INT_MIN, 0, b)
    key = b ^ ((b >> 31) & 0x7fffffff)
    return jnp.where(seen, jnp.maximum(key, _INT_MIN + 1), _INT_MIN)


def _splat_sum(x):
    """The sum of a float32 ``[8, 128]`` in every element of one."""
    s = jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)
    return jnp.broadcast_to(s, x.shape)


def _choice_kernel(pos_ref, x_ref, o_ref, keys_ref, thr_ref, need_ref,
                   sel_ref, s_ref, hi_ref, acc_ref, *, rows, chunks, k):
    """One grid step: ``rows`` queries, each a row of ``chunks`` x 128
    scores. Every step of it is a count or a compare; nothing is sorted.

    1. the scores become ordered int32 keys, masked by position;
    2. the ``k``-th largest key of each row by bisection over its 32 bits
       (the rows side by side, over the groups of 1,024 positions that the
       furthest query reaches);
    3. a row at a time: what is above the threshold and the lowest
       positions of what equals it are marked, counted a chunk, summed over
       the chunks, and every output place finds its chunk (a compare against
       the running sums) and its lane (a compare against the chunk's
       running count, fetched by a product with the one-hot of the chunk);
       the position is ``chunk * 128 + lane`` in int32.

    Every product multiplies 0 / 1 (or -1) by counts of 128 or less and
    sums in float32: exact under any pass of the matrix unit."""
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    padded = keys_ref.shape[1]
    n_blocks = padded // _CHUNK_BLOCK
    kp = o_ref.shape[-1]
    r0 = pl.program_id(0) * rows
    places = lax.broadcasted_iota(i32, (1, kp), 1)
    at = [pos_ref[r0 + g] for g in range(rows)]
    last = functools.reduce(jnp.maximum, at)

    @pl.when(last < k)
    def _():
        for g in range(rows):
            o_ref[g] = places

    @pl.when(last >= k)
    def _():
        sub = lax.broadcasted_iota(i32, (8, _CHUNK), 0)
        lane = lax.broadcasted_iota(i32, (8, _CHUNK), 1)
        least = jnp.full((8, _CHUNK), _INT_MIN, i32)

        # 1. keys, whole rows: what the bisection leaves out is still read
        # by the compaction's blocks
        def keys_of(i, _):
            at_i = (i * 8 + sub) * _CHUNK + lane
            rows8 = pl.ds(pl.multiple_of(i * 8, 8), 8)
            for g in range(rows):
                keys_ref[g, rows8, :] = _ordered_keys(
                    x_ref[g, rows8, :], at_i <= at[g])
            return 0

        lax.fori_loop(0, chunks // 8, keys_of, 0)
        if padded > chunks:
            for g in range(rows):
                keys_ref[g, chunks:, :] = jnp.full(
                    (padded - chunks, _CHUNK), _INT_MIN, i32)

        # 2. the threshold: the greatest t with k keys or more at or above it
        groups = lax.div(last, 8 * _CHUNK) + 1

        def count(test, bounds):
            """How many keys of each row pass ``test`` against its bound,
            in every element of an ``[8, 128]`` float32."""
            def body(i, accs):
                rows8 = pl.ds(pl.multiple_of(i * 8, 8), 8)
                return tuple(
                    acc + jnp.where(test(keys_ref[g, rows8, :], bound), 1, 0)
                    for g, (acc, bound) in enumerate(zip(accs, bounds)))

            accs = lax.fori_loop(
                0, groups, body,
                tuple(jnp.zeros((8, _CHUNK), i32) for _ in range(rows)))
            return [_splat_sum(acc.astype(f32)) for acc in accs]

        def bit(i, bases):
            step = jnp.left_shift(jnp.int32(1), 31 - i)
            tries = [base + step for base in bases]
            got = count(lambda key, t: key >= t, tries)
            return tuple(jnp.where(n >= k, t, base)
                         for n, t, base in zip(got, tries, bases))

        thrs = lax.fori_loop(0, 32, bit, (least,) * rows)
        above = count(lambda key, t: key > t, thrs)
        for g in range(rows):
            thr_ref[g] = thrs[g]
            need_ref[g] = k - above[g]

        # constants of the compaction: 0 / 1 matrices over a tile
        ri = lax.broadcasted_iota(i32, (_CHUNK, _CHUNK), 0)
        ci = lax.broadcasted_iota(i32, (_CHUNK, _CHUNK), 1)

        def one(mask):
            return jnp.where(mask, 1.0, 0.0).astype(bf16)

        ones = jnp.ones((_CHUNK, _CHUNK), bf16)
        ones8 = jnp.ones((8, _CHUNK), bf16)
        before = one(ri < ci)           # [l', l]: l' before l
        upto_t = one(ci <= ri)          # [l, l']: l' at or before l
        below = one(ci < ri)            # [m, m']: chunk m' before chunk m
        places_f = lax.broadcasted_iota(i32, (_CHUNK, kp), 1).astype(f32)
        nt = (((1,), (1,)), ((), ()))

        def dot(a, b):
            return jnp.dot(a, b, preferred_element_type=f32)

        def chunks_of(b):
            """The rows of block ``b`` in a ``[chunks, 128]`` scratch."""
            return pl.ds(pl.multiple_of(b * _CHUNK_BLOCK, _CHUNK_BLOCK),
                         _CHUNK_BLOCK)

        def end_of(b):
            """The chosen up to block ``b``'s end, ``[1, 128]`` alike."""
            return hi_ref[pl.ds(pl.multiple_of(b * 8, 8), 8), :][:1]

        def wide(x):
            """``x`` [., 128] alike along its lanes, over the ``kp``
            output places."""
            return jnp.concatenate([x] * (kp // _CHUNK), axis=1)

        def row(g, _):
            reach = pos_ref[r0 + g]

            @pl.when(reach < k)
            def _():
                o_ref[g] = places

            @pl.when(reach >= k)
            def _():
                live = jnp.minimum(
                    lax.div(reach, _CHUNK_BLOCK * _CHUNK) + 1, n_blocks)
                thr = jnp.broadcast_to(thr_ref[g][:1], (_CHUNK, _CHUNK))
                need = jnp.broadcast_to(need_ref[g][:1], (_CHUNK, _CHUNK))
                hi_ref[...] = jnp.full(hi_ref.shape, 2.0 ** 30, f32)

                # marks, a block of chunks at a time: above the threshold,
                # and the first `need` of what equals it, in position order
                def mark(b, carry):
                    tied_before, chosen_before = carry
                    blk = chunks_of(b)
                    key = keys_ref[g, blk, :]
                    tied = key == thr
                    tied_b = one(tied)
                    in_chunk = dot(tied_b, before)
                    a_chunk = dot(tied_b, ones)
                    rank = dot(below, a_chunk.astype(bf16)) + in_chunk \
                        + tied_before
                    chosen = (key > thr) | (tied & (rank < need))
                    chosen_b = one(chosen)
                    sel_ref[blk, :] = chosen_b.astype(f32)
                    a_chunk_c = dot(chosen_b, ones).astype(bf16)
                    s_ref[blk, :] = dot(below, a_chunk_c) + chosen_before
                    chosen_before = chosen_before + dot(ones, a_chunk_c)
                    hi_ref[pl.ds(pl.multiple_of(b * 8, 8), 8), :] = \
                        chosen_before[:8]
                    return (tied_before + dot(ones, a_chunk.astype(bf16)),
                            chosen_before)

                zero = jnp.zeros((_CHUNK, _CHUNK), f32)
                lax.fori_loop(0, live, mark, (zero, zero))

                # every output place against a block's chunks: the first 128
                # rows the running count at each lane of the place's chunk,
                # the next eight the chunks of the block up to it, the last
                # eight the block's chosen before it
                acc_ref[...] = jnp.zeros_like(acc_ref)

                def place(b, _):
                    chosen = sel_ref[chunks_of(b), :]
                    prev = jnp.where(ri == 0, 0.0,
                                     pltpu.roll(chosen, 1, 0))
                    steps = lax.dot_general(
                        upto_t, (chosen - prev).astype(bf16), nt,
                        preferred_element_type=f32)          # [l, m]
                    chosen_prev = lax.dot_general(
                        ones8, prev.astype(bf16), nt,
                        preferred_element_type=f32)          # [8, m]
                    lhs = jnp.concatenate(
                        [steps, jnp.ones((8, _CHUNK), f32), chosen_prev],
                        axis=0).astype(bf16)
                    holds = one((wide(s_ref[chunks_of(b), :]) <= places_f)
                                & (places_f < wide(end_of(b))))
                    acc_ref[...] += dot(lhs, holds)
                    return 0

                lax.fori_loop(0, live, place, 0)

                # the block a place falls in and the chosen before it
                pf = places.astype(f32)
                block = jnp.zeros((1, kp), f32)
                before_block = jnp.zeros((1, kp), f32)
                for b in range(n_blocks):
                    end = wide(end_of(b))
                    block += jnp.where(end <= pf, 1.0, 0.0)
                    before_block = jnp.maximum(
                        before_block, jnp.where(end <= pf, end, 0.0))
                chunk = block * _CHUNK_BLOCK \
                    + acc_ref[_CHUNK:_CHUNK + 1, :] - 1.0
                nth = pf - before_block - acc_ref[_CHUNK + 8:_CHUNK + 9, :]
                lane_of = jnp.sum(
                    jnp.where(acc_ref[:_CHUNK, :] <= nth, 1.0, 0.0),
                    axis=0, keepdims=True)
                o_ref[g] = chunk.astype(i32) * _CHUNK + lane_of.astype(i32)

            return 0

        lax.fori_loop(0, rows, row, 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _pallas_latent_choice(scores, pos, *, k: int, interpret: bool):
    """``scores`` [B, T, L] float32 and ``pos`` [B, T] as
    :func:`latent_topk` takes them: ``[B, T, k]`` int32, a selecting
    query's chosen positions in position order, ``0 .. k - 1`` for every
    other query."""
    b, t, width = scores.shape
    n = b * t
    rows = 1 if t == 1 else _CHOICE_ROWS
    n_pad = -n % rows
    # whole groups of eight chunks of 128 positions; a padded position lies
    # past every query's own and is masked with the rest
    w_pad = -width % (8 * _CHUNK)
    chunks = (width + w_pad) // _CHUNK
    padded = -(-chunks // _CHUNK_BLOCK) * _CHUNK_BLOCK
    kp = -(-k // _CHUNK) * _CHUNK
    x = jnp.pad(scores.reshape(n, width), ((0, n_pad), (0, w_pad)))
    at = jnp.pad(pos.astype(jnp.int32).reshape(n), (0, n_pad),
                 constant_values=-1)
    kernel = functools.partial(_choice_kernel, rows=rows, chunks=chunks, k=k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=((n + n_pad) // rows,),
            in_specs=[pl.BlockSpec((rows, chunks, _CHUNK),
                                   lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((rows, 1, kp), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, padded, _CHUNK), jnp.int32),    # keys
                pltpu.VMEM((rows, 8, _CHUNK), jnp.int32),         # threshold
                pltpu.VMEM((rows, 8, _CHUNK), jnp.float32),       # ties due
                pltpu.VMEM((padded, _CHUNK), jnp.float32),        # marks
                pltpu.VMEM((padded, _CHUNK), jnp.float32),        # starts
                pltpu.VMEM((padded // _CHUNK_BLOCK * 8, _CHUNK),
                           jnp.float32),                          # block ends
                pltpu.VMEM((_CHUNK + 16, kp), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, 1, kp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret.tpu_params(interpret),
        name="latent_choice_decode" if t == 1 else "latent_choice_prefill",
    )(at, x.reshape(n + n_pad, chunks, _CHUNK))
    return out[:n, 0, :k].reshape(b, t, k)


@trace.part(trace.LATENT_CHOICE)
def latent_topk(scores: jax.Array, pos: jax.Array, k: int, *,
                kernel: str = "lax", interpret: Optional[bool] = None):
    """The exact ``k`` best cached positions a query: ``scores`` [B, T, L]
    float32 (read only at ``s <= pos``), ``pos`` [B, T] each query's own
    position, below 0 for a query that is not real. Returns ``(idx [B, T,
    k] int32, n [B, T] int32)``: the first ``n`` entries of ``idx`` are the
    chosen positions. A query that sees more than ``k`` positions takes the
    ``k`` of largest score, a tie to the lower position; one that sees ``k``
    or fewer takes them all (``0 .. pos``, whatever ``scores`` holds);
    one that is not real takes none.

    **The chosen are a set**: ``kernel="pallas"`` hands them out in order
    of position, ``"lax"`` (``jax.lax.top_k`` over the whole width: the
    oracle, and what a CPU runs) in order of score, and every reader (a
    softmax over them, a mask) takes either."""
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown choice kernel {kernel!r}; known: lax, pallas")
    width = scores.shape[-1]
    if width < k:
        raise ValueError(f"a choice of {k} among {width} cached positions")
    n = jnp.clip(pos + 1, 0, k).astype(jnp.int32)
    if kernel == "pallas":
        return _pallas_latent_choice(
            scores.astype(jnp.float32), pos, k=int(k),
            interpret=_interpret.resolve(interpret)), n
    selects = (pos >= k)[..., None]
    seen = jnp.arange(width, dtype=jnp.int32) <= pos[..., None]
    key = jnp.where(seen & selects, scores, -jnp.inf)
    idx = jnp.where(selects, lax.top_k(key, k)[1],
                    jnp.arange(k, dtype=jnp.int32))
    return idx.astype(jnp.int32), n


# -- 3. the read of the chosen ---------------------------------------------------

#: cached positions a block of the gather's walk holds
_GATHER_BLOCK = 512
#: the walk is taken while the live queries' reaches, summed, stay under
#: this many cached positions a gathered vector; past it XLA's gather, which
#: costs by the vectors it is asked for and not by what is live, is the
#: faster (PERF.md section 6, PR 64: the tool's table)
_GATHER_WALK_RATIO = 8


def gather_path(kernel: str, *, t: int) -> Optional[str]:
    """The gather's label in a program with ``t`` positions a row: the
    kernel's, or None where ``gather_tokens`` (plain XLA, no label of its
    own) copies the chosen."""
    if kernel == "pallas" and t <= mla.MAX_DECODE_TOKENS:
        return GATHER_DECODE_PATH
    return None


@trace.part(trace.LATENT_GATHER)
def gather_tokens(pool, page_table, positions):
    """``pool`` [n_blocks, page, W] at ``positions`` [B, S] of each row,
    through ``page_table`` [B, P]: ``[B, S, W]``."""
    page = pool.shape[1]
    blocks = jnp.take_along_axis(page_table, positions // page, axis=1)
    flat = blocks * page + positions % page
    return pool.reshape(-1, pool.shape[-1])[flat]


def _gather_decode_kernel(n_ref, reach_ref, lo_ref, hi_ref, pt_ref, idx_ref,
                          pool_hbm, out_hbm, col_ref, buf, stage, sems,
                          out_sem, *, queries, t, tiles, pp, page,
                          pages_per_seq, block_pages, precision):
    """The one grid step: every query in turn. A query with chosen tokens
    walks the pages of its row up to its furthest chosen position, a block
    in flight while the block before it is picked from: a tile of ``pp``
    output places against a block is a product of their 0 / 1 matrix
    (place x position) with the block, summed in float32, so a place's
    vector is 1 x itself plus zeros: a copy. A tile meets the blocks
    between its lowest and its highest position and no other; a query with
    none chosen costs a scalar compare."""
    f32 = jnp.float32
    cols = block_pages * page
    dtype = buf.dtype
    lane = lax.broadcasted_iota(jnp.int32, (pp, cols), 1)
    sub = lax.broadcasted_iota(jnp.int32, (pp, 1), 0)
    # a partial block leaves rows of the buffer unwritten: no place picks
    # them, and 0 x whatever VMEM held must be 0
    buf[...] = jnp.zeros_like(buf)

    def query(q, _):
        n = n_ref[q]

        @pl.when(n > 0)
        def _():
            used = lax.div(n + pp - 1, pp)
            # each tile's chosen positions down a column, -1 past ``n``:
            # those places match no position and stay 0, to the end of the
            # last pseudo-page the query touches
            for j in range(tiles):
                @pl.when(j < used)
                def _():
                    across = jnp.broadcast_to(idx_ref[q, j:j + 1, :],
                                              (pp, pp))
                    col_ref[j] = jnp.where(
                        j * pp + sub < n, jnp.transpose(across)[:, :1], -1)
                    stage[j] = jnp.zeros(stage.shape[1:], dtype)

            n_pages = lax.div(reach_ref[q], page) + 1
            n_blocks = lax.div(n_pages + block_pages - 1, block_pages)

            def copies(c, slot, op):
                _page_copies(
                    pt_ref, pool_hbm, buf.at[slot], sems.at[slot],
                    base=lax.div(q, t) * pages_per_seq + c * block_pages,
                    n_pages=n_pages - c * block_pages,
                    block_pages=block_pages, page=page, op=op)

            copies(0, 0, lambda c: c.start())

            def block(c, _):
                slot = lax.rem(c, 2)

                @pl.when(c + 1 < n_blocks)
                def _():
                    copies(c + 1, 1 - slot, lambda c: c.start())

                copies(c, slot, lambda c: c.wait())
                base = c * cols

                def tile(j, _):
                    @pl.when((lo_ref[q * tiles + j] < base + cols)
                             & (hi_ref[q * tiles + j] >= base))
                    def _():
                        picks = jnp.where(col_ref[j] - base == lane,
                                          1.0, 0.0).astype(dtype)
                        got = jnp.dot(picks, buf[slot], precision=precision,
                                      preferred_element_type=f32)
                        stage[j] = (stage[j].astype(f32) + got).astype(dtype)

                    return 0

                lax.fori_loop(0, used, tile, 0)
                return 0

            lax.fori_loop(0, n_blocks, block, 0)

            def tiles_out(op):
                for j in range(tiles):
                    @pl.when(j < used)
                    def _():
                        op(pltpu.make_async_copy(
                            stage.at[j], out_hbm.at[q * tiles + j],
                            out_sem.at[0]))

            tiles_out(lambda c: c.start())
            tiles_out(lambda c: c.wait())

        return 0

    lax.fori_loop(0, queries, query, 0)


def _tile_bounds(idx, n):
    """``lo`` / ``hi`` [B x T, tiles] the lowest and the highest chosen
    position of each tile of ``pp`` places (an empty tile: ``lo`` above
    ``hi``) and ``reach`` [B x T] the highest of a query, -1 for none."""
    b, t, k = idx.shape
    pp = min(k, _CHOSEN_PAGE)
    taken = jnp.arange(k, dtype=jnp.int32) < n[..., None]
    by_tile = (b * t, k // pp, pp)
    lo = jnp.min(jnp.where(taken, idx, 2 ** 30).reshape(by_tile), axis=-1)
    hi = jnp.max(jnp.where(taken, idx, -1).reshape(by_tile), axis=-1)
    return lo, hi, jnp.max(hi, axis=-1)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _pallas_latent_gather(pool, page_table, idx, n, lo, hi, reach, *,
                          block: int = _GATHER_BLOCK, interpret: bool):
    """The kernel's call, bounds as :func:`_tile_bounds` gives them."""
    b, t, k = idx.shape
    _, page, w = pool.shape
    pages = page_table.shape[1]
    pp = min(k, _CHOSEN_PAGE)
    tiles = k // pp
    queries = b * t
    block_pages = _block_pages(pages, page, block)
    kernel = functools.partial(
        _gather_decode_kernel, queries=queries, t=t, tiles=tiles, pp=pp,
        page=page, pages_per_seq=pages, block_pages=block_pages,
        precision=(lax.Precision.HIGHEST if pool.dtype == jnp.float32
                   else None))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,),
            in_specs=[
                pl.BlockSpec((queries, tiles, pp), lambda g, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((tiles, pp, 1), jnp.int32),
                pltpu.VMEM((2, block_pages * page, w), pool.dtype),
                pltpu.VMEM((tiles, pp, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((queries * tiles, pp, w), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=_interpret.tpu_params(interpret),
        name="latent_gather_decode",
    )(n.astype(jnp.int32).reshape(-1), reach.reshape(-1), lo.reshape(-1),
      hi.reshape(-1), page_table.astype(jnp.int32).reshape(-1),
      idx.astype(jnp.int32).reshape(queries, tiles, pp), pool)


@trace.part(trace.LATENT_GATHER)
def latent_gather(pool: jax.Array, page_table: jax.Array, idx: jax.Array,
                  n: jax.Array, *, kernel: str = "lax",
                  interpret: Optional[bool] = None) -> jax.Array:
    """Each query's chosen latent vectors as a pool of their own: ``pool``
    ``[n_blocks, page, W]`` through ``page_table`` ``[B, P]`` at ``idx``
    ``[B, T, k]``, the first ``n`` ``[B, T]`` of them: ``[B x T x k / pp,
    pp, W]`` in pseudo-pages of ``pp = min(k, 128)`` tokens, query ``q``'s
    place ``i`` at ``[q x k / pp + i // pp, i % pp]``.

    ``kernel="lax"`` (and any program of more than
    ``mla.MAX_DECODE_TOKENS`` positions a row) is :func:`gather_tokens`:
    every place of every query, live or not. ``kernel="pallas"`` is
    ``latent_gather_decode``: a query's first ``n`` places hold
    ``gather_tokens``'s vectors bit for bit (a ``-0.0`` comes out ``+0.0``:
    the copy is a sum with zeros), the places from ``n`` to the end of that
    pseudo-page hold 0, and **everything past it, and every place of a
    query with ``n`` 0, is whatever memory held**: ``ops/mla.py``'s kernel
    reads the pages its query sees and no more. The chosen may come in any
    order; in order of position (the kernel choice's) a tile of places
    meets the one or two blocks of pages its positions lie in, in order of
    score (the ``lax`` choice's) every block its query walks, sixteen times
    the products.

    The walk costs by the live queries' reaches, XLA's gather by the
    places: the program takes the walk while the reaches, summed, are
    under ``_GATHER_WALK_RATIO`` cached positions a place
    (``lax.cond`` on what ``idx`` and ``n`` say, both branches compiled)."""
    b, t, k = idx.shape
    pp = min(k, _CHOSEN_PAGE)
    if k % pp:
        raise ValueError(f"{k} chosen tokens are not whole pages of {pp}")
    w = pool.shape[-1]

    def plain(pool, page_table, idx):
        return gather_tokens(pool, page_table, idx.reshape(b, t * k)) \
            .reshape(b * t * k // pp, pp, w)

    if gather_path(kernel, t=t) is None:
        return plain(pool, page_table, idx)
    lo, hi, reach = _tile_bounds(idx, n)

    def walk(pool, page_table, idx):
        return _pallas_latent_gather(
            pool, page_table, idx, n, lo, hi, reach,
            interpret=_interpret.resolve(interpret))

    return lax.cond(jnp.sum(reach + 1) <= _GATHER_WALK_RATIO * b * t * k,
                    walk, plain, pool, page_table, idx)


def latent_chosen_attention(q: jax.Array, pool: jax.Array,
                            page_table: jax.Array, idx: jax.Array,
                            n: jax.Array, *, value_dim: int, scale: float,
                            kernel: str = "lax",
                            interpret: Optional[bool] = None) -> jax.Array:
    """The absorbed latent read over each query's chosen tokens.

    ``q`` ``[B, T, H, W]`` absorbed queries, ``pool`` ``[n_blocks, page,
    W]``, ``page_table`` ``[B, P]``, ``idx`` / ``n`` as :func:`latent_topk`
    gives them. The chosen vectors are copied once a query
    (:func:`latent_gather`) and handed to ``ops/mla.py`` as a pool of their
    own: every query a row of one position that sees the first ``n`` of its
    ``k`` tokens (a query with ``n`` 0 is an idle row there: 0, nothing
    read). Returns ``[B, T, H, value_dim]``."""
    b, t, h, w = q.shape
    k = idx.shape[-1]
    got = latent_gather(pool, page_table, idx, n, kernel=kernel,
                        interpret=interpret)
    # two parts: the copy is ``latent_gather``'s, the read this one
    with trace.part(trace.LATENT_CHOSEN_READ):
        out = mla.mla_attention(
            q.reshape(b * t, 1, h, w), got,
            jnp.arange(got.shape[0], dtype=jnp.int32).reshape(b * t, -1),
            n.reshape(b * t) - 1, value_dim=value_dim, scale=scale,
            kernel=kernel, interpret=interpret)
        return out.reshape(b, t, h, value_dim)


# -- 4. the read under a window -------------------------------------------------

def window_path(kernel: str, *, t: int) -> Optional[str]:
    """The window read's label in a program with ``t`` positions a row: the
    kernel's, or None where the gathers and the sums are plain XLA (no
    label of their own)."""
    if kernel == "pallas" and t <= mla.MAX_DECODE_TOKENS:
        return WINDOW_DECODE_PATH
    return None


@trace.part(trace.LATENT_WINDOW_READ)
def latent_window_attention(q: jax.Array, pool: jax.Array,
                            window_table: jax.Array, start: jax.Array, *,
                            window: int, value_dim: int, scale: float,
                            kernel: str = "lax",
                            interpret: Optional[bool] = None) -> jax.Array:
    """The absorbed latent read of the ``window`` newest positions: ``q``
    ``[B, T, H, W]`` at positions ``start + t`` (``start`` below 0: an idle
    row, result 0) against ``pool`` ``[n_blocks, page, W]`` through
    ``window_table`` ``[B, P]``. A query at ``p`` sees ``p - window < s <=
    p``; the program reads positions ``start - window + 1 .. start + T -
    1`` and no page before theirs, so the table may read scratch behind the
    window. ``kernel="pallas"`` in a program of up to
    ``mla.MAX_DECODE_TOKENS`` positions a row is ``ops/mla.py``'s kernel
    under the name ``latent_window_decode``: live rows' pages alone. A
    wider program and ``kernel="lax"`` gather every row's positions.
    Returns ``[B, T, H, value_dim]``."""
    b, t, _, _ = q.shape
    if window_path(kernel, t=t) is not None:
        return mla.mla_attention(
            q, pool, window_table, start, value_dim=value_dim, scale=scale,
            kernel=kernel, interpret=interpret, window=window,
            name=WINDOW_DECODE_PATH)
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


@trace.part(trace.ATTN_READ)
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
    """Lower the index kernel, the choice, the gather (a decode program's)
    and the chosen read for a TPU at these shapes, with no device and no
    compile, and let the lowering's error out."""
    sds = jax.ShapeDtypeStruct

    def program(qi, w, ik, q, pool, page_table, start):
        scores = _pallas_index_scores(qi, w, ik, page_table, start,
                                      topk=topk, interpret=False)
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
        idx, n = latent_topk(scores, pos, topk, kernel="pallas",
                             interpret=False)
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


def lower_window_for_tpu(*, batch: int, t: int, heads: int, width: int,
                         value_dim: int, window: int, n_blocks: int,
                         page_size: int, pages_per_seq: int, dtype):
    """Lower the read under a window as a program of ``t`` positions a row
    takes it under ``kernel="pallas"`` (``latent_window_decode`` up to
    ``mla.MAX_DECODE_TOKENS``) for a TPU at these shapes, with no device
    and no compile, and let the lowering's error out. Returns what was
    lowered, for whoever wants its text."""
    sds = jax.ShapeDtypeStruct

    def read(q, pool, window_table, start):
        return latent_window_attention(
            q, pool, window_table, start, window=window,
            value_dim=value_dim, scale=1.0, kernel="pallas", interpret=False)

    return jax.jit(read).trace(
        sds((batch, t, heads, width), dtype),
        sds((n_blocks, page_size, width), dtype),
        sds((batch, pages_per_seq), jnp.int32), sds((batch,), jnp.int32),
    ).lower(lowering_platforms=("tpu",))

"""Block-sparse attention through the page table: a query chooses which
pages of its row it reads (InfLLM-v2, MiniCPM4's ``sparse_config``;
arXiv:2506.07900 section 2.2).

A layer keeps three paged leaves, all addressed by the one page table:
keys and values ``[pages, KV, page, D]`` (a page of one key-value head is a
contiguous ``[page, D]`` slab: the choice is a group's own, so a read moves
one head's slab and never both) and **compressed keys** ``[pages, page /
stride, KV, D]`` float32: entry ``j`` of a row is the mean of the keys at
``stride x j .. stride x j + kernel - 1`` and lives on the page that holds
its first position (``j // (page / stride)``); the last entry of a page
straddles into the next one and is written when that one's first positions
arrive. A selector block is a page (``page == block_size``).

What a query at position ``t`` of a long request reads (:class:`SparseSpec`
has the sizes):

1. ``p = softmax_j(q . c_j / sqrt(D))`` a query head over the compressed
   keys whose positions are all ``<= t``, summed over the heads of the
   key-value group;
2. a block's score is the largest ``p`` of the compressed keys that overlap
   it;
3. the first ``init_blocks`` blocks and the ``window_size / block_size``
   blocks that end at the query's own are always read; of the others the
   ``topk`` best (all, if fewer; a tie goes to the lower block);
4. causal softmax attention over the positions ``<= t`` of those blocks.

- :func:`compress_keys`: the compressed keys a chunk completes, from the
  pool (plain XLA: a gather of ``kernel`` keys an entry).
- :func:`select_blocks`: steps 1-3, ``[B, KV, T, pages]`` bool. ``"pallas"``:
  one kernel body under two names of a device trace,
  ``sparse_select_decode`` (one position a row; a row that does not select,
  idle or dense, costs a scalar compare) and ``sparse_select_prefill`` (a
  chunk of one row); it copies the row's live pages of compressed keys into
  VMEM by DMA and scores, pools and picks there. ``"lax"``: the gathered
  table scored whole, its oracle.
- :func:`sparse_decode_attention`: step 4 for one position a row. The chosen
  pages are packed into a table of their own (ascending, the row's own page
  last) by a running count of the mask (:func:`pack_chosen`; nothing is
  sorted) and read by ``ops/paged_attention.py``'s decode kernel under the
  name ``sparse_decode_attention``, a (row, group) pair as a row of one
  key-value head at the position ``(chosen - 1) x page + t % page``: the
  layers carry no rotary embedding, so a position is only the causal mask's.
- :func:`sparse_prefill_attention`: step 4 for a chunk, each query with its
  own choice: a tile of queries reads the union of its queries' pages and
  masks a page per query (``sparse_prefill_attention`` in a device trace).

A row served densely (a short request) is a row whose choice is everything
visible: the same reads, no selection.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.ops.paged_attention import (
    _pallas_paged_attention, paged_attention, paged_scatter_index)
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` labels
SELECT_DECODE_PATH = "sparse_select_decode"
SELECT_PREFILL_PATH = "sparse_select_prefill"
DECODE_PATH = "sparse_decode_pallas"
PREFILL_PATH = "sparse_prefill_pallas"

_NEG_INF = -1e30
_HI = lax.Precision.HIGHEST
_LANES = 128


class SparseSpec(NamedTuple):
    """MiniCPM4's published ``sparse_config`` (the defaults) less
    ``dense_len``, which is the model's: it decides which rows select."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048

    def check(self) -> "SparseSpec":
        if self.block_size % self.kernel_stride \
                or self.window_size % self.block_size \
                or not self.kernel_stride <= self.kernel_size \
                <= self.kernel_stride + self.block_size:
            raise ValueError(
                f"{self}: a block is whole strides, the window whole "
                f"blocks, and a compressed key reaches into the next block "
                f"at most")
        return self

    @property
    def per_block(self) -> int:
        """Compressed keys that start on one block."""
        return self.block_size // self.kernel_stride

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def straddlers(self) -> int:
        """The first of a block's compressed keys that reaches into the
        next block (``per_block`` where none does)."""
        return next((r for r in range(self.per_block)
                     if self.kernel_stride * r + self.kernel_size
                     > self.block_size), self.per_block)

    @property
    def most_read(self) -> int:
        """Blocks a query reads at most."""
        return self.init_blocks + self.window_blocks + self.topk


def pool_shapes(n_blocks: int, kv_heads: int, head_dim: int,
                spec: SparseSpec):
    """``(keys or values, compressed keys)`` leaf shapes."""
    return ((n_blocks, kv_heads, spec.block_size, head_dim),
            (n_blocks, spec.per_block, kv_heads, head_dim))


# -- writes -------------------------------------------------------------------

def _rows(blocks, offsets, kv: int, page: int):
    """Rows of a ``[pages, KV, page, D]`` pool seen as ``[pages x KV x page,
    D]`` that hold the positions ``(blocks, offsets)``, every key-value
    head's: ``[..., KV]``."""
    return (blocks[..., None] * kv + jnp.arange(kv, dtype=jnp.int32)) * page \
        + offsets[..., None]


@trace.part(trace.CACHE_WRITE)
def scatter_kv(pool, page_table, positions, values):
    """``values`` ``[B, T, KV, D]`` written at ``positions`` ``[B, T]`` of a
    ``[pages, KV, page, D]`` pool (an idle row and a position past a row's
    pages land on the scratch block). Written as rows of the pool seen as
    ``[pages x KV x page, D]``: a scatter along the two outer axes with the
    heads between them made the compiler copy the whole pool into a layout
    of its own and back, twice a leaf a layer a program (126 MB each at the
    cell's pool: 3 ms of a 22 ms decode round; PERF.md section 6, PR 51)."""
    n, kv, page, d = pool.shape
    blocks, offs = paged_scatter_index(page_table, positions, page)
    rows = _rows(blocks, offs, kv, page).reshape(-1)
    return pool.reshape(n * kv * page, d).at[rows].set(
        values.astype(pool.dtype).reshape(-1, d)).reshape(pool.shape)


@trace.part(trace.CACHE_WRITE)
def compress_keys(k_pool, ck_pool, page_table, start, n_real, *, t: int,
                  spec: SparseSpec):
    """The compressed keys that a chunk of ``t`` positions from ``start``
    ``[B]`` completes (those whose last position is one of the chunk's first
    ``n_real`` ``[B]``), written to ``ck_pool``; the chunk's keys are in
    ``k_pool`` already. A kernel of 32 at stride 16 straddles chunk and page
    edges: its keys are read back through the page table, wherever they
    lie. Entries that complete nothing are written to the scratch block."""
    ks, st, page, cpp = (spec.kernel_size, spec.kernel_stride,
                         spec.block_size, spec.per_block)
    b, pages = page_table.shape
    kv, d = k_pool.shape[1], k_pool.shape[3]
    m = -(-t // st)
    start = start.astype(jnp.int32)
    ends = (start + jnp.mod(st - 1 - start, st))[:, None] \
        + st * jnp.arange(m, dtype=jnp.int32)                    # [B, M]
    ok = (ends < (start + n_real.astype(jnp.int32))[:, None]) \
        & (ends >= ks - 1)
    pos = jnp.maximum(
        ends[..., None] - (ks - 1) + jnp.arange(ks, dtype=jnp.int32), 0)
    blocks = jnp.take_along_axis(
        page_table, jnp.minimum(pos // page, pages - 1).reshape(b, -1),
        axis=1).reshape(pos.shape)
    n = k_pool.shape[0]
    keys = k_pool.reshape(n * kv * page, d)[
        _rows(blocks, pos % page, kv, page)]             # [B, M, ks, KV, D]
    mean = jnp.mean(keys.astype(jnp.float32), axis=2)
    j = jnp.maximum(ends - (ks - 1), 0) // st
    dst = jnp.take_along_axis(
        page_table, jnp.minimum(j // cpp, pages - 1), axis=1)
    return ck_pool.at[jnp.where(ok, dst, 0).reshape(-1),
                      jnp.where(ok, j % cpp, 0).reshape(-1)].set(
        mean.reshape(b * m, kv, d).astype(ck_pool.dtype))


def _check_kernel(kernel: str) -> None:
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown sparse-attention kernel {kernel!r}; known: lax, "
            f"pallas")


# -- the choice: lax ----------------------------------------------------------

def block_scores(ps, spec: SparseSpec):
    """``ps`` ``[..., pages x per_block]`` (a group's summed probabilities a
    compressed key) -> ``[..., pages]``: the largest over the compressed
    keys that overlap a block, its own and the previous block's
    straddlers."""
    cpp = spec.per_block
    per = ps.reshape(ps.shape[:-1] + (ps.shape[-1] // cpp, cpp))
    own = per.max(axis=-1)
    if spec.straddlers >= cpp:
        return own
    late = per[..., spec.straddlers:].max(axis=-1)
    return jnp.maximum(own, jnp.concatenate(
        [jnp.zeros_like(late[..., :1]), late[..., :-1]], axis=-1))


def forced_blocks(positions, pages: int, spec: SparseSpec):
    """``(seen, forced)`` ``[B, 1, T, pages]`` bool: the blocks that hold a
    position ``<= t``, and those of them read whatever their score."""
    cur = (positions // spec.block_size)[:, None, :, None]
    blk = jnp.arange(pages, dtype=jnp.int32)
    seen = blk <= cur
    return seen, seen & ((blk < spec.init_blocks)
                         | (cur - blk < spec.window_blocks))


@trace.part(trace.LATENT_CHOICE)
def choose(scores, positions, spec: SparseSpec):
    """``scores`` ``[B, KV, T, pages]`` -> the chosen blocks, bool."""
    pages = scores.shape[-1]
    seen, forced = forced_blocks(positions, pages, spec)
    sc = jnp.where(seen & ~forced, scores, -1.0)
    vals, idx = lax.top_k(sc, min(spec.topk, pages))
    hit = (idx[..., None] == jnp.arange(pages)) & (vals >= 0)[..., None]
    return forced | hit.any(axis=-2)


def _lax_select(q, ck_pool, page_table, positions, spec: SparseSpec):
    b, t, h, d = q.shape
    _, cpp, kv, _ = ck_pool.shape
    pages = page_table.shape[1]
    ck = ck_pool[page_table].reshape(b, pages * cpp, kv, d)
    qg = q.astype(jnp.float32).reshape(b, t, kv, h // kv, d)
    s = jnp.einsum("btkgd,bjkd->bkgtj", qg, ck.astype(jnp.float32),
                   precision=_HI) * d ** -0.5
    last = spec.kernel_stride * jnp.arange(pages * cpp) \
        + spec.kernel_size - 1
    vis = (last[None, None, :] <= positions[:, :, None])[:, None, None]
    s = jnp.where(vis, s, _NEG_INF)
    e = jnp.where(vis, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    z = e.sum(axis=-1, keepdims=True)
    ps = (e / jnp.where(z == 0.0, 1.0, z)).sum(axis=2)     # [B, KV, T, J]
    return choose(block_scores(ps, spec), positions, spec)


# -- the choice: pallas -------------------------------------------------------

def _select_kernel(pos_ref, pt_ref, q_ref, ck_hbm, o_ref, ck_buf, sem,
                   acc_ref, *, t, kv, loops, pages, pages_pad, spec, scale):
    """One grid cell: one batch row. ``pos_ref[b]`` is the position of the
    row's first query, negative for a row that does not select (its result
    is 0 and it reads nothing). The row's live pages of compressed keys are
    copied whole into ``ck_buf`` (``[pages x per_block x KV, D]``, a page's
    entries in the pool's own order), a residue's keys of one group are a
    strided read of it, and scores are ``[query rows, pages]``: a block's
    score is an elementwise maximum over the residues and one shift by a
    lane. Query rows are a group's heads (decode, summed at the end) or a
    chunk's positions (prefill, one head a turn of a loop, summed in
    ``acc_ref``)."""
    b = pl.program_id(0)
    first = pos_ref[b]
    page, cpp = spec.block_size, spec.per_block
    slab = cpp * kv
    rows = q_ref.shape[3]

    @pl.when(first < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first >= 0)
    def _():
        n_pages = jnp.minimum(lax.div(first + t - 1, page) + 1, pages)

        def copy(i):
            return pltpu.make_async_copy(
                ck_hbm.at[pt_ref[b * pages + i]],
                ck_buf.at[pl.ds(pl.multiple_of(i * slab, slab), slab)],
                sem.at[0])

        lax.fori_loop(0, n_pages, lambda i, _: (copy(i).start(), 0)[1], 0)
        lax.fori_loop(0, n_pages, lambda i, _: (copy(i).wait(), 0)[1], 0)

        lane = lax.broadcasted_iota(jnp.int32, (rows, pages_pad), 1)
        out_rows = o_ref.shape[2]
        lane_out = lax.broadcasted_iota(jnp.int32, (out_rows, pages_pad), 1)
        # the picks compare lanes as float32 (exact: a table is far under
        # 2^24 wide), the type the row reductions are sure to take
        lane_f = lane_out.astype(jnp.float32)
        if t == 1:
            row_pos = first
            pos_out = first
        else:
            row_pos = first + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            pos_out = row_pos

        def probabilities(gi, q_tile):
            ss = []
            for r in range(cpp):
                keys = ck_buf[pl.ds(r * kv + gi, pages_pad, stride=slab), :]
                s = lax.dot_general(
                    q_tile, keys, (((1,), (1,)), ((), ())), precision=_HI,
                    preferred_element_type=jnp.float32) * scale
                vis = lane * page + (r * spec.kernel_stride
                                     + spec.kernel_size - 1) <= row_pos
                ss.append(jnp.where(vis, s, _NEG_INF))
            m = functools.reduce(
                jnp.maximum, [s.max(axis=1, keepdims=True) for s in ss])
            es = [jnp.where(s > _NEG_INF / 2, jnp.exp(s - m), 0.0)
                  for s in ss]
            z = functools.reduce(
                jnp.add, [e.sum(axis=1, keepdims=True) for e in es])
            inv = 1.0 / jnp.where(z == 0.0, 1.0, z)
            return [e * inv for e in es]

        for gi in range(kv):
            if loops == 1:
                ps = [p.sum(axis=0, keepdims=True)
                      for p in probabilities(gi, q_ref[0, gi, 0])]
            else:
                acc_ref[...] = jnp.zeros_like(acc_ref)

                def head(hi, _, gi=gi):
                    for r, p in enumerate(
                            probabilities(gi, q_ref[0, gi, hi])):
                        acc_ref[r] += p
                    return 0

                lax.fori_loop(0, loops, head, 0)
                ps = [acc_ref[r] for r in range(cpp)]
            own = functools.reduce(jnp.maximum, ps)
            if spec.straddlers < cpp:
                late = functools.reduce(jnp.maximum, ps[spec.straddlers:])
                own = jnp.maximum(own, jnp.where(
                    lane_out >= 1, pltpu.roll(late, 1, 1), 0.0))
            cur = lax.div(pos_out, page)
            seen = lane_out <= cur
            forced = seen & ((lane_out < spec.init_blocks)
                             | (cur - lane_out < spec.window_blocks))
            sc = jnp.where(seen & ~forced, own, -1.0)

            def pick(_, carry):
                sc, ch = carry
                best = sc.max(axis=1, keepdims=True)
                at = jnp.min(jnp.where(sc == best, lane_f, float(pages_pad)),
                             axis=1, keepdims=True)
                take = (lane_f == at) & (best >= 0.0)
                return jnp.where(take, -1.0, sc), jnp.where(take, 1, ch)

            _, ch = lax.fori_loop(0, min(spec.topk, pages), pick,
                                  (sc, forced.astype(jnp.int32)))
            o_ref[0, gi] = ch


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def _pallas_select(q, ck_pool, page_table, first, *, spec: SparseSpec,
                   interpret: bool):
    """``first`` ``[B]``: the position of each row's first query, negative
    for a row that does not select."""
    b, t, h, d = q.shape
    n, cpp, kv, _ = ck_pool.shape
    pages = page_table.shape[1]
    pages_pad = -(-pages // _LANES) * _LANES
    g = h // kv
    qg = q.astype(jnp.float32).reshape(b, t, kv, g, d)
    if t == 1:
        # a group's heads are the rows: [B, KV, 1, G, D]
        qt, loops, rows = qg.transpose(0, 2, 1, 3, 4), 1, g
    else:
        # a head a turn, the chunk's positions the rows: [B, KV, G, T, D]
        qt, loops, rows = qg.transpose(0, 2, 3, 1, 4), g, t
    out_rows = 1 if t == 1 else t
    kernel = functools.partial(
        _select_kernel, t=t, kv=kv, loops=loops, pages=pages,
        pages_pad=pages_pad, spec=spec, scale=d ** -0.5)
    vmem = 4 * (pages_pad * cpp * kv * d + (cpp + 12) * out_rows * pages_pad
                + 8 * rows * pages_pad + 4 * kv * loops * rows * d)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, kv, loops, rows, d),
                             lambda bi, *_: (bi, 0, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, kv, out_rows, pages_pad),
                                   lambda bi, *_: (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((pages_pad * cpp * kv, d), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.VMEM((cpp, out_rows, pages_pad), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv, out_rows, pages_pad),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=_interpret.tpu_params(interpret),
        name="sparse_select_decode" if t == 1 else "sparse_select_prefill",
    )(first.astype(jnp.int32), page_table.astype(jnp.int32).reshape(-1),
      qt, ck_pool.reshape(n, cpp * kv, d))
    return out[..., :pages] != 0


@trace.part(trace.LATENT_CHOICE)
def select_blocks(q, ck_pool, page_table, positions, selects, *,
                  spec: SparseSpec, kernel: str = "lax",
                  interpret: Optional[bool] = None):
    """The blocks each query reads: ``q`` ``[B, T, H, D]`` at the
    consecutive positions ``positions`` ``[B, T]``, ``ck_pool`` ``[pages,
    page / stride, KV, D]`` float32, ``selects`` ``[B]`` bool (a row that
    does not select reads everything visible: a dense row; an idle one's
    result nobody reads). Returns ``[B, KV, T, pages]`` bool."""
    _check_kernel(kernel)
    pages = page_table.shape[1]
    if kernel == "pallas":
        chosen = _pallas_select(
            q, ck_pool, page_table,
            jnp.where(selects, positions[:, 0], -1), spec=spec,
            interpret=_interpret.resolve(interpret))
    else:
        chosen = _lax_select(q, ck_pool, page_table, positions, spec)
    seen, _ = forced_blocks(positions, pages, spec)
    return jnp.where(selects[:, None, None, None], chosen, seen)


# -- the reads ----------------------------------------------------------------

@trace.part(trace.LATENT_CHOICE)
def pack_chosen(chosen, page_table):
    """The chosen pages first, in position order, a table of their own:
    ``chosen`` ``[B, KV, pages]`` bool, ``page_table`` ``[B, pages]``.
    Returns ``(table [B, KV, pages] int32, count [B, KV] int32)``: entry
    ``j < count`` is the id of the ``j``-th chosen page, 0 behind. A page's
    place is the running count of the mask before it, and an entry is a
    masked sum over the one page with that place: compares and adds on the
    vector unit, exact in int32. Nothing is sorted or gathered (XLA's
    gather moves an element in 12 ns on the chip, a scatter likewise)."""
    pages = chosen.shape[-1]
    cum = jnp.cumsum(chosen, axis=-1, dtype=jnp.int32)
    place = jnp.where(chosen, cum - 1, -1)                # [B, KV, pages]
    ids = page_table.astype(jnp.int32)[:, None, :, None]
    table = jnp.sum(
        jnp.where(place[..., None] == jnp.arange(pages), ids, 0), axis=-2)
    return table, cum[..., -1]


@trace.part(trace.ATTN_READ)
def sparse_decode_attention(q, k_pool, v_pool, page_table, positions,
                            chosen, live, *, kernel: str = "lax",
                            dtype: Any = None,
                            interpret: Optional[bool] = None):
    """One position a row over its chosen pages: ``q`` ``[B, 1, H, D]``,
    pools ``[pages, KV, page, D]``, ``chosen`` ``[B, KV, pages]`` bool,
    ``live`` ``[B]`` bool (an idle row reads nothing and gets 0 from the
    kernel). Returns ``[B, 1, KV, G, D]``."""
    b, _, h, d = q.shape
    n, kv, page, _ = k_pool.shape
    pages = page_table.shape[1]
    dtype = k_pool.dtype if dtype is None else dtype
    table, count = pack_chosen(chosen & live[:, None, None], page_table)
    # a (row, group) is a row of one key-value head of the pools seen as
    # [pages x KV, page, 1, D]: block ``pid x KV + g``
    flat = jnp.where(count[..., None] > 0,
                     table * kv + jnp.arange(kv)[None, :, None], 0)
    at = jnp.where(count > 0,
                   (count - 1) * page + positions[:, :1] % page, -1)
    args = (q.reshape(b * kv, 1, h // kv, d),
            k_pool.reshape(n * kv, page, 1, d),
            v_pool.reshape(n * kv, page, 1, d),
            flat.reshape(b * kv, pages), at.reshape(b * kv, 1))
    if kernel == "pallas":
        # the decode kernel's body under a name of this read's own
        out = _pallas_paged_attention(
            *args, dtype=jnp.dtype(dtype),
            interpret=_interpret.resolve(interpret),
            name="sparse_decode_attention")
    else:
        out = paged_attention(*args, kernel=kernel, dtype=dtype)
    return out.reshape(b, 1, kv, h // kv, d)


def _lax_prefill(q, k_pool, v_pool, page_table, start, chosen, dtype):
    b, t, h, d = q.shape
    n, kv, page, _ = k_pool.shape
    pages = page_table.shape[1]
    keys = k_pool[page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, kv, pages * page, d)
    vals = v_pool[page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, kv, pages * page, d)
    qg = q.reshape(b, t, kv, h // kv, d)
    s = jnp.einsum("btkgd,bkld->bkgtl", qg, keys,
                   preferred_element_type=jnp.float32) * d ** -0.5
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
    visible = (jnp.arange(pages * page) <= pos[..., None])[:, None] \
        & jnp.repeat(chosen, page, axis=-1)                 # [B, KV, T, L]
    s = jnp.where(visible[:, :, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("bkgtl,bkld->btkgd", p, vals)


#: query positions a grid cell of the prefill read takes (x the group's
#: heads: the rows of its q tile) and pages fetched and scored a block (a
#: divisor of 128: a block's pages lie in one lane group of the mask)
_PREFILL_TILE = 64
_PREFILL_BLOCK_PAGES = 8


def _prefill_kernel(start_ref, pt_ref, any_ref, q_ref, sel_ref, k_hbm, v_hbm,
                    o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *, tq,
                    group, kv, tiles, page, pages, block_pages, scale):
    """One grid cell: ``tq`` consecutive query positions of batch row ``b``,
    the ``group`` heads of key-value head ``g`` (rows ordered (head,
    position)), against the pages any of its queries chose
    (``any_ref``: a flag a (row, group, tile, page)), from page 0 to the
    tile's own, a block of pages in flight while the block before it is
    scored. A page none of the tile's queries chose is not copied, and a
    block of such pages is not scored. ``sel_ref`` ``[pages_pad / 128, tq,
    128]``: query x page, 1 where the query chose the page; a block's
    ``[tq, block_pages x page]`` mask is one small product of it with a 0/1
    matrix that spreads a page's flag over its positions (the matrix unit
    is idle here), laid over the heads. Numerics as
    ``ops/paged_attention.py``'s chunk kernel."""
    b, g, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows = tq * group
    cols = block_pages * page
    first = start_ref[b] + i * tq
    n_pages = jnp.minimum(lax.div(first + tq - 1, page) + 1, pages)
    n_blocks = lax.div(n_pages + block_pages - 1, block_pages)
    flags = ((b * kv + g) * tiles + i) * pages
    row_pos = first + lax.rem(
        lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tq)
    col = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    spread_row = lax.broadcasted_iota(jnp.int32, (_LANES, cols), 0)
    spread_page = lax.div(
        lax.broadcasted_iota(jnp.int32, (_LANES, cols), 1), page)

    @pl.when((b == 0) & (g == 0) & (i == 0))
    def _():
        # a page that was not copied leaves rows of the buffer unwritten;
        # their probabilities are 0, and 0 x whatever VMEM held must be 0
        v_buf[...] = jnp.zeros_like(v_buf)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def in_block(j):
        return jnp.clip(n_pages - j * block_pages, 0, block_pages)

    def for_pages(j, slot, op):
        def one(k, _):
            at = j * block_pages + k

            @pl.when(any_ref[flags + at] != 0)
            def _():
                pid = pt_ref[b * pages + at]
                dst = pl.ds(pl.multiple_of(k * page, page), page)
                op(pltpu.make_async_copy(
                    k_hbm.at[pid, g], k_buf.at[slot, dst], sems.at[0, slot]))
                op(pltpu.make_async_copy(
                    v_hbm.at[pid, g], v_buf.at[slot, dst], sems.at[1, slot]))
            return 0

        lax.fori_loop(0, in_block(j), one, 0)

    for_pages(0, 0, lambda c: c.start())

    def body(j, _):
        slot = lax.rem(j, 2)
        for_pages(j + 1, 1 - slot, lambda c: c.start())
        for_pages(j, slot, lambda c: c.wait())
        wanted = lax.fori_loop(
            0, in_block(j),
            lambda k, n: n + any_ref[flags + j * block_pages + k], 0)

        @pl.when(wanted > 0)
        def _():
            at = j * block_pages
            # which of the 128 pages of this lane group a column belongs to
            spread = (spread_row == lax.rem(at, _LANES) + spread_page
                      ).astype(jnp.bfloat16)
            mine = lax.dot_general(
                sel_ref[lax.div(at, _LANES)].astype(jnp.bfloat16), spread,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [tq, cols]
            mine = jnp.concatenate([mine] * group, axis=0)   # [rows, cols]
            visible = (col <= row_pos - j * cols) & (mine > 0.5)
            s = lax.dot_general(
                q_ref[...], k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rows, cols]
            s = jnp.where(visible, s, _NEG_INF)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        return 0

    lax.fori_loop(0, n_blocks, body, 0)
    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_prefill(q, k_pool, v_pool, page_table, start, chosen, *,
                    interpret: bool):
    b, t, h, d = q.shape
    n, kv, page, _ = k_pool.shape
    pages = page_table.shape[1]
    group = h // kv
    tq = min(t, _PREFILL_TILE)
    if t % tq:
        raise ValueError(
            f"a prefill chunk of {t} positions is not whole tiles of {tq}")
    tiles = t // tq
    rows = tq * group
    block_pages = _PREFILL_BLOCK_PAGES
    cols = block_pages * page
    pages_pad = -(-pages // _LANES) * _LANES
    lane_groups = pages_pad // _LANES
    # [B, T, KV, G, D] -> [B, KV, tiles, G x tq, D]: rows (head, position)
    qt = q.astype(k_pool.dtype).reshape(b, tiles, tq, kv, group, d).transpose(
        0, 3, 1, 4, 2, 5).reshape(b, kv, tiles, rows, d)
    # [B, KV, T, pages] -> [B, KV, tiles, lane groups, tq, 128]
    sel = jnp.pad(chosen, ((0, 0),) * 3 + ((0, pages_pad - pages),)).astype(
        jnp.float32).reshape(b, kv, tiles, tq, lane_groups, _LANES).transpose(
        0, 1, 2, 4, 3, 5)
    wanted = chosen.reshape(b, kv, tiles, tq, pages).any(axis=3)
    size = jnp.dtype(k_pool.dtype).itemsize
    vmem = (4 * rows * d * size + 4 * cols * d * size
            + 2 * lane_groups * tq * _LANES * 4
            + rows * (d + 2 * _LANES) * 4 + 6 * rows * cols * 4)
    kernel = functools.partial(
        _prefill_kernel, tq=tq, group=group, kv=kv, tiles=tiles, page=page,
        pages=pages, block_pages=block_pages, scale=d ** -0.5)
    tile = pl.BlockSpec((None, None, None, rows, d),
                        lambda bi, gi, i, *_: (bi, gi, i, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, kv, tiles),
            in_specs=[
                tile,
                pl.BlockSpec((None, None, None, lane_groups, tq, _LANES),
                             lambda bi, gi, i, *_: (bi, gi, i, 0, 0, 0)),
                pool, pool],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, cols, d), k_pool.dtype),
                pltpu.VMEM((2, cols, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv, tiles, rows, d),
                                       k_pool.dtype),
        # the V buffer is zeroed by the first cell and kept by the rest
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=_interpret.tpu_params(interpret),
        name="sparse_prefill_attention",
    )(start.astype(jnp.int32).reshape(-1),
      page_table.astype(jnp.int32).reshape(-1),
      wanted.astype(jnp.int32).reshape(-1), qt, sel, k_pool, v_pool)
    return out.reshape(b, kv, tiles, group, tq, d).transpose(
        0, 2, 4, 1, 3, 5).reshape(b, t, kv, group, d)


@trace.part(trace.ATTN_READ)
def sparse_prefill_attention(q, k_pool, v_pool, page_table, start, chosen, *,
                             kernel: str = "lax", dtype: Any = None,
                             interpret: Optional[bool] = None):
    """A chunk over each query's own chosen pages: ``q`` ``[B, T, H, D]`` at
    the consecutive positions ``start[b] + t``, pools ``[pages, KV, page,
    D]``, ``chosen`` ``[B, KV, T, pages]`` bool. Returns ``[B, T, KV, G,
    D]``."""
    _check_kernel(kernel)
    dtype = k_pool.dtype if dtype is None else dtype
    if kernel == "pallas":
        return _pallas_prefill(
            q, k_pool, v_pool, page_table, start, chosen,
            interpret=_interpret.resolve(interpret)).astype(dtype)
    return _lax_prefill(q, k_pool, v_pool, page_table, start, chosen, dtype)


def read_path(kernel: str, *, t: int) -> str:
    """The ``lzy_kernel_dispatch_total{path}`` label of the read of a
    program over ``t`` positions a row."""
    if kernel != "pallas":
        return kernel
    return DECODE_PATH if t == 1 else PREFILL_PATH


def lower_for_tpu(*, batch: int, t: int, n_heads: int, n_kv_heads: int,
                  head_dim: int, n_blocks: int, pages_per_seq: int,
                  dtype: Any, spec: SparseSpec) -> None:
    """Lower the selector and the read of a program over ``t`` positions a
    row for a TPU at these shapes, with no device and no compile, and let
    the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    kv_shape, ck_shape = pool_shapes(n_blocks, n_kv_heads, head_dim, spec)

    def read(q, k_pool, v_pool, ck_pool, page_table, positions):
        live = jnp.ones((batch,), bool)
        chosen = select_blocks(q, ck_pool, page_table, positions, live,
                               spec=spec, kernel="pallas", interpret=False)
        if t == 1:
            return sparse_decode_attention(
                q, k_pool, v_pool, page_table, positions, chosen[:, :, 0],
                live, kernel="pallas", interpret=False)
        return sparse_prefill_attention(
            q, k_pool, v_pool, page_table, positions[:, 0], chosen,
            kernel="pallas", interpret=False)

    jax.jit(read).trace(
        sds((batch, t, n_heads, head_dim), dtype), sds(kv_shape, dtype),
        sds(kv_shape, dtype), sds(ck_shape, jnp.float32),
        sds((batch, pages_per_seq), jnp.int32), sds((batch, t), jnp.int32),
    ).lower(lowering_platforms=("tpu",))

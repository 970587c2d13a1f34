"""Pallas TPU flash attention (forward + backward).

The hot op of every transformer in this framework. FlashAttention-2 structure
mapped to the TPU memory hierarchy (``/opt/skills/guides/pallas_guide.md``):

- grid over (batch x heads, query blocks); K/V for one (b,h) live in VMEM and
  are walked blockwise with the online-softmax recurrence: the T x T score
  matrix never exists, activations are O(T x D);
- every product takes its operands in the input dtype (bfloat16 in,
  bfloat16 on the matrix unit) and accumulates in float32
  (``preferred_element_type``): blocks of Q, K, V and dO are multiplied as
  they were loaded, ``p`` and ``ds`` are cast to the input dtype once, and the
  softmax scale goes into the scores through Q alone, rounded to the input
  dtype: once to the resident block in the forward and dQ kernels, to the
  block of a turn in the dK/dV kernel, so that all three see the same
  scores to the bit and ``p`` in the backward is the forward's softmax; dQ
  and dK are made from the unscaled K and Q and scaled once in float32.
  (At Mosaic's default precision a float32 operand is rounded to bfloat16
  inside the product anyway: the casts make that rounding visible, they do
  not add one.) Softmax statistics and accumulators are float32;
- causal programs stop their KV loop at the diagonal (no wasted FLOPs on
  masked blocks);
- packed documents (``segment_ids``) confine attention to equal ids AND
  tighten the KV loop to the blocks the query block's documents span:
  data-dependent ``fori_loop`` bounds read from a precomputed per-position
  (id, doc start, doc end) slab, so cross-document blocks cost nothing
  (for fully packed batches the FLOPs drop from O(T^2/2) toward
  O(sum_doc len^2/2));
- a visited (query block, key block) pair's mask is two compares against
  its own block's (start, end) lanes, since documents are contiguous: one
  iota a program, no read of the other side's ids. What a call cannot need
  is not traced: no compare without ``causal`` or segments, no bias without
  a ``kv_mask``. (A second, unmasked body for the pairs that lie wholly
  under the diagonal and inside one document was measured and lost in the
  train step: PERF.md section 6, PR 59.) :func:`block_pair_census` counts,
  for a batch's segment ids, the pairs the documents need and the pairs
  visited;
- the forward and dK/dV kernels work on transposed tiles (``[bkv, bq]``):
  the softmax's maximum and sum run down a tile's rows and the running
  statistics are one value a lane, so nothing is reduced across lanes; the
  dQ kernel's tiles are ``[bq, bkv]``;
- backward is two Pallas kernels (dK/dV over KV blocks, dQ over Q blocks)
  using the saved per-row logsumexp, wrapped in ``jax.custom_vjp``.

The row statistics (logsumexp, delta) are one float32 a row: the forward
writes the logsumexp as ``[bh, 1, t]`` (a ``(1, 1, block_q)`` block is legal
where the second-to-last dim is the whole of a dim of 1), the dQ kernel reads
that array, turns a block's lanes into rows once a program, and writes delta
= sum(dO * O) of its rows the same way (no XLA operation makes, cuts or
broadcasts a statistic), and the dK/dV kernel reads both as
``[bh, t / block_q, block_q]``, a query block a row.
The segment slab rides a 128-lane dim: lane 0 = segment id,
lane 1 = document start, lane 2 = document end (exclusive); every kernel
reads its own block's rows of it.

VMEM: every kernel keeps one head's full-length operands resident (K and V,
and the ``kv_mask`` bias slab when there is one, in the forward and dQ
kernels; Q, dO and the two statistics in the dK/dV kernel), so its footprint
grows with T. Each ``pallas_call`` asks Mosaic for the scoped VMEM its shapes
need (:func:`_vmem_limit`; the default 16 MiB runs out in the backward at
T = 8192, head size 128), and a length whose kernels would not fit
:data:`VMEM_CAP_BYTES` is refused by :func:`flash_attention` before anything
is traced (:func:`max_seq_len`: 92,672 for bfloat16 at head size 128, 47,104
with a ``kv_mask``).

Callers without a TPU ask for the Pallas interpreter (``interpret=True``, or
``lzy_tpu.ops.interpret.set_interpret`` for the process); it is never chosen
from the device.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils import trace

_NEG_INF = -1e30
_LANE = 128

#: the names the forward kernel's two results carry for ``jax.checkpoint``:
#: a policy that saves them (``save_only_these_names(*SAVED_NAMES)``) spares
#: a rematerialised caller the forward kernel in its backward. They are a
#: custom call's results, so no policy that looks for matmuls finds them.
SAVED_NAMES = ("flash_attention_out", "flash_attention_lse")

#: most scoped VMEM a kernel here asks for: a v5e core has 128 MiB, and the
#: rest is left to the program around the kernel
VMEM_CAP_BYTES = 100 << 20
#: Mosaic's own default; smaller kernels are left at it
_VMEM_DEFAULT_BYTES = 16 << 20
#: blocks, accumulators and Mosaic's internal scratch on top of the
#: full-length operands
_VMEM_SLACK_BYTES = 8 << 20


def _vmem_need(resident_bytes: int) -> int:
    """Scoped VMEM of a kernel holding ``resident_bytes`` of full-length
    operands (Pallas double-buffers every blocked operand)."""
    return 2 * resident_bytes + _VMEM_SLACK_BYTES


def _vmem_limit(resident_bytes: int) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(vmem_limit_bytes=max(
        _vmem_need(resident_bytes), _VMEM_DEFAULT_BYTES))


def _kv_resident_bytes(t: int, d: int, itemsize: int, bias) -> int:
    """What the forward and dQ kernels keep per head: K and V, and the
    ``kv_mask`` bias as a float32 ``[t, LANE]`` slab when there is one."""
    return 2 * t * d * itemsize + (0 if bias is None else t * _LANE * 4)


def _dkv_resident_bytes(t: int, d: int, itemsize: int) -> int:
    """What the dK/dV kernel keeps per head: Q and dO in the input dtype,
    and logsumexp and delta, one float32 a row each."""
    return 2 * t * d * itemsize + 2 * t * 4


def _resident_bytes(t: int, d: int, itemsize: int, bias) -> int:
    """The largest footprint of the three kernels: it decides which lengths
    :func:`flash_attention` accepts. (The segment slab is read a block at a
    time by every kernel, and counts as a block.)"""
    return max(_kv_resident_bytes(t, d, itemsize, bias),
               _dkv_resident_bytes(t, d, itemsize))


def max_seq_len(d: int, dtype, masked: bool = False) -> int:
    """Longest sequence (a multiple of 128) whose kernels fit
    :data:`VMEM_CAP_BYTES` at head size ``d``; ``masked`` when a
    ``kv_mask`` is given."""
    per_pos = _vmem_need(_resident_bytes(
        1, d, jnp.dtype(dtype).itemsize, masked or None)) \
        - _VMEM_SLACK_BYTES
    return (VMEM_CAP_BYTES - _VMEM_SLACK_BYTES) // per_pos // _LANE * _LANE


def _pick_block(t: int, requested: int) -> int:
    """Largest multiple of 128 that divides t and is ≤ max(requested, 128),
    so any lane-aligned sequence gets a valid block (t=384 → 128)."""
    b = max(min(requested, t), _LANE)
    b -= b % _LANE
    while b > _LANE:
        if t % b == 0:
            return b
        b -= _LANE
    return _LANE  # t is a multiple of 128 (checked by caller)


def _split_in_refs(refs, masked, segmented, n_out):
    """(base_inputs, bias_ref, seg_ref, outputs) for a kernel's ref list —
    optional operands appear in bias, seg order."""
    refs = list(refs)
    ins, outs = refs[:len(refs) - n_out], refs[len(refs) - n_out:]
    n_base = len(ins) - int(masked) - int(segmented)
    base = ins[:n_base]
    bias_ref = ins[n_base] if masked else None
    seg_ref = ins[n_base + int(masked)] if segmented else None
    return base, bias_ref, seg_ref, outs


# -- which pairs a block visits, and what masks them ---------------------------


class _Ops(NamedTuple):
    """The integer arithmetic :func:`_pair_ranges` is written in: traced
    scalars inside a kernel, whole numpy arrays in :func:`block_pair_census`.
    Every quantity is non-negative, so truncating and flooring division
    agree."""
    minimum: Callable
    maximum: Callable
    div: Callable


_KERNEL_OPS = _Ops(jnp.minimum, jnp.maximum, lax.div)
_HOST_OPS = _Ops(np.minimum, np.maximum, np.floor_divide)


def _pair_ranges(own_start, own, other, n_other, docs, *, causal, q_major,
                 ops):
    """The blocks ``[lo, hi)`` of the other side that one block meets.

    ``own_start`` is the first position of this block of ``own`` rows: a
    query block when ``q_major`` (forward, dQ: the others are ``n_other``
    key blocks of ``other`` rows), else a key block (dK/dV: the others are
    query blocks). ``docs`` is None or (start of the first row's document,
    end of the last row's): documents are contiguous, so the block's rows
    keep nothing outside that span."""
    def ceil_div(a):
        return ops.div(a + (other - 1), other)

    lo, hi = 0, n_other
    if causal and q_major:
        hi = ceil_div(own_start + own)        # keys up to the last query
    elif causal:
        lo = ops.div(own_start, other)        # queries from the first key on
    if docs is not None:
        first_start, last_end = docs
        lo = ops.maximum(lo, ops.div(first_start, other))
        hi = ops.minimum(hi, ceil_div(last_end))
    return lo, ops.minimum(hi, n_other)


class PairCensus(NamedTuple):
    """(query block, key block) pairs of a batch, counted over its rows."""
    needed: int     #: pairs that hold a (query, key) some document keeps
    visited: int    #: pairs the kernels' loops run


def block_pair_census(segment_ids, block_q: int, block_kv: int,
                      causal: bool = True) -> PairCensus:
    """What the kernels do with a batch's packing, counted on the host:
    ``segment_ids`` [B, T] (a document is a contiguous run of equal ids, as
    in :func:`flash_attention`; all zeros is one document a row). The
    visited count comes from :func:`_pair_ranges` over the (start, end)
    lanes of :func:`segment_slab`, the arithmetic and the numbers of the
    kernels' loop bounds; the needed count from the documents alone. The
    forward, dQ and dK/dV kernels visit the same pairs, so one count stands
    for all three."""
    slab = np.asarray(segment_slab(document_starts(jnp.asarray(segment_ids))))
    start, end = (slab[:, :, lane].astype(np.int64) for lane in (1, 2))
    b, t = start.shape
    block_q, block_kv = _pick_block(t, block_q), _pick_block(t, block_kv)
    n_q, n_kv = t // block_q, t // block_kv
    q_first = np.arange(n_q) * block_q
    q_last = q_first + block_q - 1
    lo, hi = _pair_ranges(
        q_first[None, :], block_q, block_kv, n_kv,
        (start[:, q_first], end[:, q_last]), causal=causal, q_major=True,
        ops=_HOST_OPS)
    needed = np.zeros((b, n_q, n_kv), bool)
    for row in range(b):
        for s in np.flatnonzero(start[row] == np.arange(t)):
            e = int(end[row, s])
            for i in range(s // block_q, (e - 1) // block_q + 1):
                reach = min(e, (i + 1) * block_q) if causal else e
                needed[row, i, s // block_kv:(reach - 1) // block_kv + 1] = 1
    return PairCensus(int(needed.sum()), int((hi - lo).sum()))


def _kernel_ranges(seg_ref, own_start, own, other, n_other, *, causal,
                   q_major):
    """:func:`_pair_ranges` on the scalars of this block's slab rows."""
    docs = None
    if seg_ref is not None:
        first, last = seg_ref[0, 0:1, :], seg_ref[0, own - 1:own, :]
        docs = (first[0, 1].astype(jnp.int32), last[0, 2].astype(jnp.int32))
    return _pair_ranges(own_start, own, other, n_other, docs, causal=causal,
                        q_major=q_major, ops=_KERNEL_OPS)


def _own_bounds(seg_ref, own_start, own, *, causal, q_major):
    """What a pair's mask compares: int32 ``[own, 1]`` columns
    (lo, hi), either of which may be None, such that a position ``x`` of the
    other side is kept by this block's row when ``lo <= x < hi``. Documents
    are contiguous, so a query keeps the keys from its document's start up
    to itself, and a key the queries from itself up to its document's end:
    one side's (start, end) lanes say it all, and the other side's ids are
    never read."""
    pos = own_start + lax.broadcasted_iota(jnp.int32, (own, 1), 0)
    start = end = None
    if seg_ref is not None:
        start = seg_ref[0, :, 1:2].astype(jnp.int32)
        end = seg_ref[0, :, 2:3].astype(jnp.int32)
    if not causal:
        return start, end
    return (start, pos + 1) if q_major else (pos, end)


def _keep(idx, lo, hi, other_start):
    """The mask of one pair from :func:`_own_bounds`: ``idx`` is the
    tile's index along the other side, ``other_start`` the first position
    of the other side's block. None when nothing is masked."""
    keep = None
    if lo is not None:
        keep = idx >= lo - other_start
    if hi is not None:
        below = idx < hi - other_start
        keep = below if keep is None else jnp.logical_and(keep, below)
    return keep


def _to_lanes(col):
    """float32 [n, 1] (a value a row) -> [1, n] (a value a lane)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANE)).T[0:1, :]


def _to_sublanes(row):
    """float32 [1, n] -> [n, 1]."""
    return jnp.broadcast_to(row, (_LANE, row.shape[1])).T[:, 0:1]


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scaled(x, scale):
    """``x * scale`` in the dtype of ``x``: every kernel multiplies the
    same rounded ``q * scale``, so all three see the same scores."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


# -- forward --------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, masked, segmented, block_q, block_kv,
                seq_len):
    """A query block against the key blocks it reaches, transposed: the
    tile is ``[bkv, bq]``, so the online softmax's maximum and sum run down
    the sublanes (elementwise over the tile's rows, no reduction across
    lanes), the running statistics are one value a lane (four registers a
    512 rows, not 64), and the log-sum-exp leaves as it is stored."""
    (q_ref, k_ref, v_ref), bias_ref, seg_ref, (o_ref, lse_ref) = \
        _split_in_refs(refs, masked, segmented, 2)
    q_start = pl.program_id(1) * block_q
    dtype = q_ref.dtype
    q = _scaled(q_ref[0], scale)                          # [bq, d]
    ranges = _kernel_ranges(seg_ref, q_start, block_q, block_kv,
                            seq_len // block_kv, causal=causal, q_major=True)
    # the queries' bounds, a value a lane: turned once a program
    keep_lo, keep_hi = (
        x if x is None else _to_lanes(x.astype(jnp.float32)).astype(jnp.int32)
        for x in _own_bounds(seg_ref, q_start, block_q, causal=causal,
                             q_major=True))
    key = lax.broadcasted_iota(jnp.int32, (block_kv, block_q), 0)

    def body(j, carry):
        acc, m, l = carry
        kv_start = j * block_kv
        k_blk = k_ref[0, pl.ds(kv_start, block_kv), :]
        v_blk = v_ref[0, pl.ds(kv_start, block_kv), :]
        st = _dot(k_blk, q, _NT)                          # [bkv, bq]
        if bias_ref is not None:
            # additive KV bias (0 keep / -inf drop), a row a position
            st = st + bias_ref[0, pl.ds(kv_start, block_kv), 0:1]
        keep = _keep(key, keep_lo, keep_hi, kv_start)
        if keep is not None:
            st = jnp.where(keep, st, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
        if bias_ref is not None:
            # a query may see no key at all: its maximum stays at -inf, its
            # sum at 0, and the output and the gradients come out as 0
            m_ref = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
            alpha = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - m_ref))
        else:
            # every query keeps itself (causal or not), so one that has
            # seen only dropped keys so far (maximum -inf, p = 1 for each)
            # is wiped by alpha = exp(-inf) = 0 when its own block comes
            m_ref = m_new
            alpha = jnp.exp(m - m_new)
        pt = jnp.exp(st - m_ref)
        l_new = l * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc_new = acc * alpha + _dot(v_blk, pt.astype(dtype), _TN)
        return acc_new, m_new, l_new

    d = q.shape[-1]
    carry = (jnp.zeros((d, block_q), jnp.float32),
             jnp.full((1, block_q), _NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32))
    acc, m, l = lax.fori_loop(*ranges, body, carry)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).T.astype(o_ref.dtype)
    lse_ref[0] = jnp.where(
        l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF)


def _aux_specs(bias, seg, n_heads, *, bias_block=None, seg_block=None):
    """(in_specs, operands) of the optional slabs, in bias, seg order. Both
    are a batch row's, ``[b, t, LANE]``, where grid dim 0 walks batch x
    heads; a slab is handed over whole, or ``block`` rows at grid dim 1."""
    specs, operands = [], []
    for slab, block in ((bias, bias_block), (seg, seg_block)):
        if slab is None:
            continue
        if block is None:
            specs.append(pl.BlockSpec(
                (1, slab.shape[1], _LANE),
                lambda b, i: (b // n_heads, 0, 0)))
        else:
            specs.append(pl.BlockSpec(
                (1, block, _LANE), lambda b, i: (b // n_heads, i, 0)))
        operands.append(slab)
    return specs, operands


def _fwd(q, k, v, bias, seg, *, scale, causal, block_q, block_kv, interpret,
         n_heads):
    """The output and the log-sum-exp, one float32 a row as ``[bh, 1, t]``:
    the backward kernels read that array as it is."""
    bh, t, d = q.shape
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, masked=bias is not None,
        segmented=seg is not None, block_q=block_q, block_kv=block_kv,
        seq_len=t,
    )
    aux_specs, aux = _aux_specs(bias, seg, n_heads, seg_block=block_q)
    return pl.pallas_call(
        kernel,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
        ] + aux_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_vmem_limit(
            _kv_resident_bytes(t, d, q.dtype.itemsize, bias)),
    )(q, k, v, *aux)


# -- backward -------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale, causal, masked, segmented, block_q,
                   block_kv, seq_len):
    """dQ of a query block, and its rows' delta = sum(dO * O) on the way:
    the dK/dV kernel reads what this one wrote."""
    ((q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref), bias_ref, seg_ref,
     (dq_ref, delta_ref)) = _split_in_refs(refs, masked, segmented, 2)
    q_start = pl.program_id(1) * block_q
    dtype = q_ref.dtype
    q = _scaled(q_ref[0], scale)
    do = do_ref[0]
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)               # [bq, 1]
    delta_ref[0] = _to_lanes(delta)
    # the log-sum-exp arrives a value a lane; against a [bq, bkv] tile it
    # is wanted a value a row: turned once a program
    lse = _to_sublanes(lse_ref[0])                        # [bq, 1]
    if bias_ref is not None:
        # a fully masked row stored lse = -inf, which would cancel the -inf
        # bias (s - (-inf) + (-inf) = s) and resurrect p; its softmax had
        # no mass, so its gradient is exactly zero: exp(s - inf) = 0
        lse = jnp.where(lse > _NEG_INF / 2, lse, -_NEG_INF)
    ranges = _kernel_ranges(seg_ref, q_start, block_q, block_kv,
                            seq_len // block_kv, causal=causal, q_major=True)
    keep_lo, keep_hi = _own_bounds(seg_ref, q_start, block_q, causal=causal,
                                   q_major=True)
    lane = lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    def body(j, dq):
        kv_start = j * block_kv
        k_blk = k_ref[0, pl.ds(kv_start, block_kv), :]
        v_blk = v_ref[0, pl.ds(kv_start, block_kv), :]
        s = _dot(q, k_blk, _NT)
        if bias_ref is not None:
            s = s + bias_ref[0, pl.ds(kv_start, block_kv), 0][None, :]
        keep = _keep(lane, keep_lo, keep_hi, kv_start)
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v_blk, _NT)
        ds = p * (dp - delta)
        return dq + _dot(ds.astype(dtype), k_blk, _NN)

    dq = lax.fori_loop(*ranges, body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, masked, segmented, block_q,
                    block_kv, seq_len):
    """A key block against the query blocks that reach it, transposed: the
    tile is ``[bkv, bq]``, so the queries' statistics are read a value a
    lane as they are stored, the bias and the segment bounds a value a row
    from this block's own slab rows, and both accumulating products are
    plain ``a @ b``."""
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, seg_ref,
     (dk_ref, dv_ref)) = _split_in_refs(refs, masked, segmented, 2)
    kv_start = pl.program_id(1) * block_kv
    dtype = k_ref.dtype
    k = k_ref[0]                                          # [bkv, d]
    v = v_ref[0]
    ranges = _kernel_ranges(seg_ref, kv_start, block_kv, block_q,
                            seq_len // block_q, causal=causal, q_major=False)
    keep_lo, keep_hi = _own_bounds(seg_ref, kv_start, block_kv,
                                   causal=causal, q_major=False)
    lane = lax.broadcasted_iota(jnp.int32, (block_kv, block_q), 1)

    def body(i, carry):
        dk, dv = carry
        q_start = i * block_q
        q_blk = q_ref[0, pl.ds(q_start, block_q), :]
        do_blk = do_ref[0, pl.ds(q_start, block_q), :]
        lse = lse_ref[0, pl.ds(i, 1), :]                  # [1, bq]
        delta = delta_ref[0, pl.ds(i, 1), :]
        # the forward's q * scale, to the bit: the scores here are its
        # scores, and p its softmax
        st = _dot(k, _scaled(q_blk, scale), _NT)          # [bkv, bq]
        if bias_ref is not None:
            st = st + bias_ref[0, :, 0:1]
            # same empty-row guard as the dQ kernel (see comment there)
            lse = jnp.where(lse > _NEG_INF / 2, lse, -_NEG_INF)
        keep = _keep(lane, keep_lo, keep_hi, q_start)
        if keep is not None:
            st = jnp.where(keep, st, _NEG_INF)
        pt = jnp.exp(st - lse)
        dv_new = dv + _dot(pt.astype(dtype), do_blk, _NN)
        dpt = _dot(v, do_blk, _NT)
        dst = pt * (dpt - delta)
        dk_new = dk + _dot(dst.astype(dtype), q_blk, _NN)
        return dk_new, dv_new

    d = k.shape[-1]
    zeros = jnp.zeros((block_kv, d), jnp.float32)
    dk, dv = lax.fori_loop(*ranges, body, (zeros, zeros))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, bias, seg, o, lse, do, *, scale, causal, block_q, block_kv,
         interpret, n_heads):
    bh, t, d = q.shape
    n_q = t // block_q
    masked = bias is not None
    segmented = seg is not None
    static = dict(scale=scale, causal=causal, masked=masked,
                  segmented=segmented, block_q=block_q, block_kv=block_kv,
                  seq_len=t)

    q_block = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    whole = pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0))
    q_stat = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
    aux_specs, aux = _aux_specs(bias, seg, n_heads, seg_block=block_q)
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid=(bh, n_q),
        in_specs=[q_block, whole, whole, q_block, q_block, q_stat]
        + aux_specs,
        out_specs=[q_block, q_stat],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)],
        interpret=interpret,
        compiler_params=_vmem_limit(
            _kv_resident_bytes(t, d, q.dtype.itemsize, bias)),
    )(q, k, v, do, o, lse, *aux)

    kv_block = pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0))
    # a head's statistics whole, a query block a row: the loop picks a row
    stats = pl.BlockSpec((1, n_q, block_q), lambda b, j: (b, 0, 0))
    aux_specs, aux = _aux_specs(bias, seg, n_heads, bias_block=block_kv,
                                seg_block=block_kv)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid=(bh, t // block_kv),
        in_specs=[whole, kv_block, kv_block, whole, stats, stats]
        + aux_specs,
        out_specs=[kv_block, kv_block],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=interpret,
        compiler_params=_vmem_limit(
            _dkv_resident_bytes(t, d, q.dtype.itemsize)),
    )(q, k, v, do, lse.reshape(bh, n_q, block_q),
      delta.reshape(bh, n_q, block_q), *aux)
    return dq, dk, dv


# -- public op -------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, bias, seg, scale, causal, block_q, block_kv, interpret,
           n_heads):
    o, _ = _fwd(q, k, v, bias, seg, scale=scale, causal=causal,
                block_q=block_q, block_kv=block_kv, interpret=interpret,
                n_heads=n_heads)
    return o


def _flash_fwd(q, k, v, bias, seg, scale, causal, block_q, block_kv,
               interpret, n_heads):
    o, lse = _fwd(q, k, v, bias, seg, scale=scale, causal=causal,
                  block_q=block_q, block_kv=block_kv, interpret=interpret,
                  n_heads=n_heads)
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q, k, v, bias, seg, o, lse)


def _flash_bwd(scale, causal, block_q, block_kv, interpret, n_heads, res,
               do):
    q, k, v, bias, seg, o, lse = res
    dq, dk, dv = _bwd(q, k, v, bias, seg, o, lse, do, scale=scale,
                      causal=causal, block_q=block_q, block_kv=block_kv,
                      interpret=interpret, n_heads=n_heads)
    # bias/seg encode boolean structure; their cotangents are structurally 0
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseg = None if seg is None else jnp.zeros_like(seg)
    return dq, dk, dv, dbias, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


def document_starts(segment_ids: jax.Array) -> jax.Array:
    """[B, T] document ids → [B, T] int32 start index of each position's
    document, where a document is a CONTIGUOUS RUN of equal ids (cummax over
    change points). The start index uniquely identifies the run, so every
    attention path normalizes ids through this before comparing — repeated
    ids in non-adjacent runs are distinct documents everywhere, and the
    kernel's run-based block skipping can never disagree with its mask.
    Also shared by per-document RoPE positions in the models. Idempotent."""
    b, t = segment_ids.shape
    seg = segment_ids.astype(jnp.int32)
    idx = jnp.arange(t, dtype=jnp.int32)
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1
    )
    return lax.cummax(jnp.where(first, idx[None, :], 0), axis=1)


def segment_slab(segment_ids: jax.Array, lane: int = _LANE) -> jax.Array:
    """[B, T] non-decreasing document ids → the [B, T, lane] float32 slab the
    kernels read: lane 0 = id, lane 1 = document start, lane 2 = document end
    (exclusive). Positions of the SAME document share start/end, which is
    what turns the mask into loop bounds."""
    b, t = segment_ids.shape
    seg = segment_ids.astype(jnp.int32)
    idx = jnp.arange(t, dtype=jnp.int32)
    start = document_starts(seg)
    last = jnp.concatenate(
        [seg[:, 1:] != seg[:, :-1], jnp.ones((b, 1), bool)], axis=1
    )
    end = lax.cummin(
        jnp.where(last, idx[None, :] + 1, t)[:, ::-1], axis=1
    )[:, ::-1]
    aux = jnp.stack([seg, start, end], axis=-1).astype(jnp.float32)
    return jnp.pad(aux, ((0, 0), (0, 0), (0, lane - 3)))


@trace.part(trace.ATTN_READ)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_mask: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q/k/v: [B, H, T, D] → [B, H, T, D]. T must be a multiple of 128 (TPU
    lane tiling) and of the block sizes, and short enough for the backward
    kernels' VMEM (:func:`max_seq_len`; 92,672 for bf16 at head size 128,
    47,104 with a ``kv_mask``).

    ``kv_mask``: optional [B, T] boolean — True = attend to that KV position
    (padding masks for encoder models). Carried into the kernels as an
    additive 0/-inf bias, one 128-lane slab per batch row; fully-masked
    query rows produce zero output and zero gradients.

    ``segment_ids``: optional [B, T] ints — a document is a contiguous run
    of equal ids (repeating an id later starts a NEW document). Attention is
    confined within documents, and the KV loops skip blocks entirely outside
    the query block's documents, so packing N short documents costs ~the sum
    of their individual attention FLOPs, not the full T² triangle.
    """
    b, h, t, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if t % _LANE:
        raise ValueError(f"seq len {t} must be divisible by {_LANE}")
    need = _vmem_need(_resident_bytes(t, d, q.dtype.itemsize, kv_mask))
    if need > VMEM_CAP_BYTES:
        raise ValueError(
            f"flash_attention: seq len {t} at head size {d} needs "
            f"~{need >> 20} MiB of VMEM (every kernel keeps one head's "
            f"full-length operands resident: K and V, or Q and dO), over "
            f"the {VMEM_CAP_BYTES >> 20} MiB this kernel may ask for; the "
            f"longest accepted length is "
            f"{max_seq_len(d, q.dtype, kv_mask is not None)}. Shard "
            f"the sequence (ring/Ulysses attention) or use "
            f"ops.attention.chunked_attention.")
    block_q = _pick_block(t, block_q)
    block_kv = _pick_block(t, block_kv)
    interpret = _interpret.resolve(interpret)

    bias = None
    if kv_mask is not None:
        if kv_mask.shape != (b, t):
            raise ValueError(
                f"kv_mask shape {kv_mask.shape} != (batch, seq) = {(b, t)}"
            )
        bias = jnp.where(kv_mask, 0.0, _NEG_INF).astype(jnp.float32)
        bias = jnp.broadcast_to(bias[:, :, None], (b, t, _LANE))

    seg = None
    if segment_ids is not None:
        if segment_ids.shape != (b, t):
            raise ValueError(
                f"segment_ids shape {segment_ids.shape} != {(b, t)}"
            )
        # normalize to run starts: the id the kernels compare IS the run
        # identity, so the mask and the block-skip bounds agree by
        # construction whatever ids the caller passed
        seg = segment_slab(document_starts(segment_ids))

    flat = lambda x: x.reshape(b * h, t, d)  # noqa: E731
    o = _flash(flat(q), flat(k), flat(v), bias, seg, scale, causal, block_q,
               block_kv, interpret, h)
    return o.reshape(b, h, t, d)

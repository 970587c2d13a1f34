"""Pallas TPU flash attention (forward + backward).

The hot op of every transformer in this framework. FlashAttention-2 structure
mapped to the TPU memory hierarchy (``/opt/skills/guides/pallas_guide.md``):

- grid over (batch·heads, query blocks); K/V for one (b,h) live in VMEM and
  are walked blockwise with the online-softmax recurrence — the T×T score
  matrix never exists, activations are O(T·D);
- matmuls hit the MXU with float32 accumulation (``preferred_element_type``),
  inputs stay bfloat16;
- causal programs stop their KV loop at the diagonal (no wasted FLOPs on
  masked blocks);
- packed documents (``segment_ids``) confine attention to equal ids AND
  tighten the KV loop to the blocks the query block's documents span —
  data-dependent ``fori_loop`` bounds read from a precomputed per-position
  (id, doc start, doc end) slab, so cross-document blocks cost nothing
  (for fully packed batches the FLOPs drop from O(T²/2) toward
  O(sum_doc len²/2));
- backward is two Pallas kernels (dK/dV over KV blocks, dQ over Q blocks)
  using the saved per-row logsumexp, wrapped in ``jax.custom_vjp``.

TPU tiling note: auxiliary row vectors (logsumexp, delta) cannot use
``(1, block)`` blocks — the last two block dims must be (8k, 128k) or
full-dim. Both directions therefore carry lse/delta broadcast across the head
dim (the same layout jax's reference TPU flash kernel uses for l/m residuals).
The segment slab likewise rides a 128-lane dim: lane 0 = segment id,
lane 1 = document start, lane 2 = document end (exclusive).

VMEM: every kernel keeps one head's full-length operands resident (K and V
in the forward and dQ kernels; Q, dO and the two broadcast row vectors in the
dK/dV kernel), so its footprint grows with T. Each ``pallas_call`` asks Mosaic
for the scoped VMEM its shapes need (:func:`_vmem_limit`; the default 16 MiB
runs out in the backward at T = 8192, head size 128), and a length whose
backward would not fit :data:`VMEM_CAP_BYTES` is refused by
:func:`flash_attention` before anything is traced.

Callers without a TPU ask for the Pallas interpreter (``interpret=True``, or
``lzy_tpu.ops.interpret.set_interpret`` for the process); it is never chosen
from the device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret

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


def _aux_bytes(t: int, *aux) -> int:
    """Bytes of the full-length float32 ``[t, LANE]`` slabs (bias, segments)
    that are present."""
    return sum(t * _LANE * 4 for a in aux if a is not None)


def _kv_resident_bytes(t: int, d: int, itemsize: int, bias, seg) -> int:
    """What the forward and dQ kernels keep per head: K and V."""
    return 2 * t * d * itemsize + _aux_bytes(t, bias, seg)


def _dkv_resident_bytes(t: int, d: int, itemsize: int, seg) -> int:
    """What the dK/dV kernel keeps per head: Q and dO in the input dtype,
    logsumexp and delta broadcast to float32 ``[t, d]``, and the segment
    slab. The largest footprint of the three kernels, so it decides which
    lengths :func:`flash_attention` accepts."""
    return t * d * (2 * itemsize + 8) + _aux_bytes(t, seg)


def max_seq_len(d: int, dtype, segmented: bool = False) -> int:
    """Longest sequence (a multiple of 128) whose backward fits
    :data:`VMEM_CAP_BYTES` at head size ``d``."""
    per_pos = _vmem_need(_dkv_resident_bytes(
        1, d, jnp.dtype(dtype).itemsize, segmented or None)) \
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


# -- forward --------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, masked, segmented, block_q, block_kv,
                seq_len):
    (q_ref, k_ref, v_ref), bias_ref, seg_ref, (o_ref, lse_ref) = \
        _split_in_refs(refs, masked, segmented, 2)
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # [bq, d]
    q_start = iq * block_q
    n_kv = seq_len // block_kv
    hi = jnp.minimum(
        lax.div(q_start + block_q + block_kv - 1, block_kv), n_kv
    ) if causal else n_kv
    lo = 0
    seg_q = None
    if seg_ref is not None:
        seg_rows = seg_ref[0, pl.ds(q_start, block_q), :]   # [bq, LANE]
        seg_q = seg_rows[:, 0]
        # ids are non-decreasing (packed layout): the block's documents span
        # [start of first row's doc, end of last row's doc) — KV blocks
        # outside that range are entirely cross-document, skip them
        lo = lax.div(seg_rows[0, 1].astype(jnp.int32), block_kv)
        seg_hi = lax.div(
            seg_rows[block_q - 1, 2].astype(jnp.int32) + block_kv - 1,
            block_kv,
        )
        hi = jnp.minimum(hi, seg_hi)

    d = q.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [bq, bkv]
        if bias_ref is not None:
            # additive KV bias (0 keep / -inf drop), one lane per position
            b_col = bias_ref[0, pl.ds(j * block_kv, block_kv), 0]
            s = s + b_col[None, :]
        keep = None
        if causal:
            rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * block_kv + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = rows >= cols
        if seg_q is not None:
            seg_kv = seg_ref[0, pl.ds(j * block_kv, block_kv), 0]
            same = seg_q[:, None] == seg_kv[None, :]
            keep = same if keep is None else jnp.logical_and(keep, same)
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc, m, l = lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF)
    lse_ref[0] = jnp.broadcast_to(lse[:, None], (block_q, d))


def _fwd(q, k, v, bias, seg, *, scale, causal, block_q, block_kv, interpret,
         n_heads):
    bh, t, d = q.shape
    n_q = t // block_q
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, masked=bias is not None,
        segmented=seg is not None, block_q=block_q, block_kv=block_kv,
        seq_len=t,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
    ]
    operands = [q, k, v]
    # bias/seg are per-BATCH [b, t, LANE]; grid dim 0 walks batch·heads
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (1, t, _LANE), lambda b, i: (b // n_heads, 0, 0)))
        operands.append(bias)
    if seg is not None:
        in_specs.append(pl.BlockSpec(
            (1, t, _LANE), lambda b, i: (b // n_heads, 0, 0)))
        operands.append(seg)
    o, lse_bcast = pl.pallas_call(
        kernel,
        grid=(bh, n_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_vmem_limit(
            _kv_resident_bytes(t, d, q.dtype.itemsize, bias, seg)),
    )(*operands)
    return o, lse_bcast[:, :, 0]                          # [bh, t]


# -- backward -------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale, causal, masked, segmented, block_q,
                   block_kv, seq_len):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, seg_ref,
     (dq_ref,)) = _split_in_refs(refs, masked, segmented, 1)
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    q_start = iq * block_q
    # lse/delta arrive broadcast over the head dim (TPU lane tiling); keep the
    # per-row column as 2D [block_q, 1] for clean broadcasting
    lse = lse_ref[0, :, 0:1]
    delta = delta_ref[0, :, 0:1]
    n_kv = seq_len // block_kv
    hi = jnp.minimum(
        lax.div(q_start + block_q + block_kv - 1, block_kv), n_kv
    ) if causal else n_kv
    lo = 0
    seg_q = None
    if seg_ref is not None:
        seg_rows = seg_ref[0, pl.ds(q_start, block_q), :]
        seg_q = seg_rows[:, 0]
        lo = lax.div(seg_rows[0, 1].astype(jnp.int32), block_kv)
        hi = jnp.minimum(hi, lax.div(
            seg_rows[block_q - 1, 2].astype(jnp.int32) + block_kv - 1,
            block_kv,
        ))

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q * scale, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if bias_ref is not None:
            b_col = bias_ref[0, pl.ds(j * block_kv, block_kv), 0]
            s = s + b_col[None, :]
        rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = j * block_kv + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.exp(s - lse)
        # fully-masked rows store lse = -inf, which would cancel the -inf
        # bias (s - (-inf) + (-inf) = s) and resurrect p; their softmax had
        # no mass, so their gradient is exactly zero
        p = jnp.where(lse > _NEG_INF / 2, p, 0.0)
        if causal:
            p = jnp.where(rows >= cols, p, 0.0)
        if seg_q is not None:
            seg_kv = seg_ref[0, pl.ds(j * block_kv, block_kv), 0]
            p = jnp.where(seg_q[:, None] == seg_kv[None, :], p, 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = lax.fori_loop(lo, hi, body, jnp.zeros_like(q))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, masked, segmented, block_q,
                    block_kv, seq_len):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, seg_ref,
     (dk_ref, dv_ref)) = _split_in_refs(refs, masked, segmented, 2)
    jkv = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)                  # [bkv, d]
    v_blk = v_ref[0].astype(jnp.float32)
    kv_start = jkv * block_kv
    n_q = seq_len // block_q
    lo = lax.div(kv_start, block_q) if causal else 0
    hi = n_q
    seg_kv = None
    if seg_ref is not None:
        seg_rows = seg_ref[0, pl.ds(kv_start, block_kv), :]
        seg_kv = seg_rows[:, 0]
        # mirror of the forward skip: only q rows inside this KV block's
        # documents can reach it
        if not causal:
            lo = jnp.maximum(
                lo, lax.div(seg_rows[0, 1].astype(jnp.int32), block_q)
            )
        hi = jnp.minimum(hi, lax.div(
            seg_rows[block_kv - 1, 2].astype(jnp.int32) + block_q - 1,
            block_q,
        ))

    d = k_blk.shape[-1]

    def body(i, carry):
        dk, dv = carry
        q_start = i * block_q
        q_blk = q_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        lse_blk = lse_ref[0, pl.ds(q_start, block_q), 0:1]      # [bq, 1]
        delta_blk = delta_ref[0, pl.ds(q_start, block_q), 0:1]  # [bq, 1]
        s = jax.lax.dot_general(
            q_blk * scale, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [bq, bkv]
        if bias_ref is not None:
            # this kernel's whole KV block shares one bias slice
            s = s + bias_ref[0, :, 0][None, :]
        rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kv_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.exp(s - lse_blk)
        # same empty-row guard as the dQ kernel (see comment there)
        p = jnp.where(lse_blk > _NEG_INF / 2, p, 0.0)
        if causal:
            p = jnp.where(rows >= cols, p, 0.0)
        if seg_kv is not None:
            seg_q = seg_ref[0, pl.ds(q_start, block_q), 0]
            p = jnp.where(seg_q[:, None] == seg_kv[None, :], p, 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_blk) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    dk0 = jnp.zeros((block_kv, d), jnp.float32)
    dv0 = jnp.zeros((block_kv, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, hi, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, bias, seg, o, lse, do, *, scale, causal, block_q, block_kv,
         interpret, n_heads):
    bh, t, d = q.shape
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )                                                     # [bh, t]
    # broadcast row vectors over the head dim to satisfy TPU lane tiling
    # (same layout jax's reference TPU flash kernel uses for l/m residuals)
    lse_t = jnp.broadcast_to(lse[:, :, None], (bh, t, d))
    delta_t = jnp.broadcast_to(delta[:, :, None], (bh, t, d))
    masked = bias is not None
    segmented = seg is not None

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
        pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),          # k
        pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),          # v
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # do
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # lse
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # delta
    ]
    dq_operands = [q, k, v, do, lse_t, delta_t]
    if masked:
        dq_specs.append(pl.BlockSpec(
            (1, t, _LANE), lambda b, i: (b // n_heads, 0, 0)))
        dq_operands.append(bias)
    if segmented:
        dq_specs.append(pl.BlockSpec(
            (1, t, _LANE), lambda b, i: (b // n_heads, 0, 0)))
        dq_operands.append(seg)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, masked=masked,
            segmented=segmented, block_q=block_q, block_kv=block_kv,
            seq_len=t,
        ),
        grid=(bh, t // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        compiler_params=_vmem_limit(
            _kv_resident_bytes(t, d, q.dtype.itemsize, bias, seg)),
    )(*dq_operands)

    dkv_specs = [
        pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),          # q
        pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0)),  # k
        pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0)),  # v
        pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),          # do
        pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),          # lse
        pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),          # delta
    ]
    dkv_operands = [q, k, v, do, lse_t, delta_t]
    if masked:
        dkv_specs.append(pl.BlockSpec(
            (1, block_kv, _LANE), lambda b, j: (b // n_heads, j, 0)))
        dkv_operands.append(bias)
    if segmented:
        # the dKV kernel needs BOTH its own KV rows and arbitrary q rows of
        # the slab: pass it full-length
        dkv_specs.append(pl.BlockSpec(
            (1, t, _LANE), lambda b, j: (b // n_heads, 0, 0)))
        dkv_operands.append(seg)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, masked=masked,
            segmented=segmented, block_q=block_q, block_kv=block_kv,
            seq_len=t,
        ),
        grid=(bh, t // block_kv),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=interpret,
        compiler_params=_vmem_limit(
            _dkv_resident_bytes(t, d, q.dtype.itemsize, seg)),
    )(*dkv_operands)
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
    # the kernel writes the log-sum-exp over all 128 lanes (the module's
    # tiling note) and ``_fwd`` cuts one out. Left alone, XLA sinks that cut
    # to the backward and keeps the float32 [bh, t, 128] block, twice the
    # output's bytes, for as long as the residual lives; tied to the output,
    # the cut is made before anything reads the output
    o, lse = lax.optimization_barrier((o, lse))
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
    kernels' VMEM (:func:`max_seq_len`; 31,360 for bf16 at head size 128).

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
    need = _vmem_need(
        _dkv_resident_bytes(t, d, q.dtype.itemsize, segment_ids))
    if need > VMEM_CAP_BYTES:
        raise ValueError(
            f"flash_attention: seq len {t} at head size {d} needs "
            f"~{need >> 20} MiB of VMEM in the backward (one head's Q, dO "
            f"and row statistics stay resident), over the "
            f"{VMEM_CAP_BYTES >> 20} MiB this kernel may ask for; the "
            f"longest accepted length is "
            f"{max_seq_len(d, q.dtype, segment_ids is not None)}. Shard "
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

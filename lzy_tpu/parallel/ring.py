"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Absent from the reference (SURVEY.md §5.7 — it scales *sequence of ops*, not
sequence length); first-class here. The sequence is sharded over ``sp``; each
device holds its Q block and streams K/V blocks around the ring with
``lax.ppermute`` (ICI neighbor exchange), accumulating attention with the
online-softmax (flash) recurrence so the full sequence is never materialized
on one chip. Communication overlaps compute: while block i is processed, XLA
schedules the permute of block i+1 (double-buffered carry).

Causal masking across ring steps uses the block-position trick: a block from
source rank r is fully visible if r < my_rank, fully masked if r > my_rank,
and diagonally masked if r == my_rank.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from lzy_tpu.parallel.sharding import inside_manual

_NEG_INF = -1e30


def _block_attn(q, k, v, *, scale, mask):
    """One flash block: returns (unnormalized out, row max, row sumexp).

    q: [B, H, Tq, D], k/v: [B, H, Tk, D]; mask: None or any shape
    broadcastable to [B, H, Tq, Tk] (the segmented ring path passes
    [B, 1, Tq, Tk]).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Tq]
    # guard fully-masked rows (all -inf): exp(-inf - -inf) would NaN
    m_safe = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,H,Tq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m_safe, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Online-softmax merge of two partial attention results."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    q_spec: P = P(("dp", "fsdp"), None, "sp", None),
    segment_ids: Optional[jax.Array] = None,
    seg_spec: P = P(("dp", "fsdp"), "sp"),
) -> jax.Array:
    """Attention over a sequence sharded on ``axis``.

    Shapes (per global array): q/k/v ``[batch, heads, seq, head_dim]`` with
    ``seq`` sharded over ``axis``. Returns the same layout as q.

    ``segment_ids``: optional global ``[batch, seq]`` packed-document ids
    (seq sharded like q; a document = a contiguous run of equal ids). Each
    rank's id chunk rides the ring alongside its K/V chunk, so attention
    stays confined within documents across rank boundaries too — documents
    may straddle ring shards.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[axis]
    if segment_ids is not None:
        # normalize to GLOBAL run starts before sharding (same run semantics
        # as the flash kernel); a local normalization inside shard_map would
        # renumber each shard from zero and glue runs at shard boundaries
        from lzy_tpu.ops.flash_attention import document_starts

        segment_ids = document_starts(segment_ids)

    def local_fn(q_blk, k_blk, v_blk, seg_blk):
        my_rank = lax.axis_index(axis)
        tq = q_blk.shape[2]
        tk = k_blk.shape[2]

        def diag_mask():
            rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            return rows >= cols

        def body(carry, step):
            o, m, l, k_cur, v_cur, seg_cur = carry
            src_rank = (my_rank - step) % n          # who produced this block
            mask = None
            if causal:
                keep_all = src_rank < my_rank
                keep_none = src_rank > my_rank
                mask = jnp.where(
                    keep_all, True,
                    jnp.where(keep_none, False, diag_mask()),
                )
            if seg_cur is not None:
                # [B, 1, Tq, Tk]: this rank's q ids vs the ids that arrived
                # with the current K/V chunk
                same = seg_blk[:, None, :, None] == seg_cur[:, None, None, :]
                mask = same if mask is None else jnp.logical_and(mask, same)
            o_b, m_b, l_b = _block_attn(q_blk, k_cur, v_cur, scale=scale, mask=mask)
            o, m, l = _merge(o, m, l, o_b, m_b, l_b)
            # rotate K/V (and their ids) to the next rank; overlaps with the
            # next block's math
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            seg_nxt = None if seg_cur is None \
                else lax.ppermute(seg_cur, axis, perm)
            return (o, m, l, k_nxt, v_nxt, seg_nxt), None

        b, h, _, d = q_blk.shape
        # zero that carries q's varying-manual-axes type: when this body
        # runs inside an outer manual region (the pp pipeline), the scan's
        # carry inits must match the (pp, sp)-varying outputs or the scan
        # type check rejects the mix (standalone shard_map sets
        # check_vma=False, but the pipeline's region checks)
        zv = (q_blk[0, 0, 0, 0] * 0).astype(jnp.float32)
        o0 = jnp.zeros((b, h, tq, d), jnp.float32) + zv
        m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32) + zv
        l0 = jnp.zeros((b, h, tq), jnp.float32) + zv
        (o, m, l, _, _, _), _ = lax.scan(
            body, (o0, m0, l0, k_blk, v_blk, seg_blk), jnp.arange(n)
        )
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q_blk.dtype)

    if segment_ids is None:
        fn, in_specs, args = (functools.partial(local_fn, seg_blk=None),
                              (q_spec, q_spec, q_spec), (q, k, v))
    else:
        fn, in_specs, args = (local_fn, (q_spec, q_spec, q_spec, seg_spec),
                              (q, k, v, segment_ids))
    if inside_manual(axis):
        if segment_ids is not None:
            raise ValueError(
                "packed segments do not compose with ring attention inside "
                "an already-manual region (document_starts would renumber "
                "per-chunk); unpack or drop sp from the pipeline mesh")
        # Composition with the pp pipeline: we are ALREADY inside a manual
        # region that includes the ring axis (pipeline_apply manualizes
        # {pp, sp} when the stages ring — see its seq_axis param), so the
        # inputs are the per-rank chunks and the ring recurrence runs
        # directly. A nested shard_map here is not an option: both
        # partitioners reject re-binding an axis a parent manual region
        # holds (sdy verifier error; GSPMD crash).
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=q_spec, check_vma=False,
    )(*args)

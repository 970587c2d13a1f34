"""Memory-efficient chunked attention (XLA path).

Online-softmax attention computed blockwise over keys with ``lax.scan``:
activation memory is O(T·block) instead of O(T²), so long sequences train
without materializing the score matrix. Fully differentiable through the scan;
``jax.checkpoint`` on the block body bounds backward memory too. This is the
portable fallback for the Pallas flash kernel (``lzy_tpu/ops/flash_attention``)
— same math, same masking semantics, works on CPU/virtual meshes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from lzy_tpu.utils import trace

_NEG_INF = -1e30


def _match_vma(init, *refs):
    """Align a zero-init scan carry's varying-over-manual-axes type with the
    data it will accumulate. Inside a partial-manual ``shard_map`` (e.g. the
    pipeline's pp axis with fsdp/tp auto), q/k/v are device-varying over the
    manual axes while a plain ``jnp.zeros`` is invariant — the scan's vma
    type check rejects that mix unless the init is pcast up front."""
    vma = frozenset().union(*(jax.typeof(r).vma for r in refs))
    missing = vma - jax.typeof(init).vma
    if missing:
        init = lax.pcast(init, tuple(missing), to="varying")
    return init


def auto_block(t: int, requested: int = 512) -> int:
    """Largest divisor of ``t`` that is ≤ requested — any sequence length gets
    a valid block without callers hand-rolling divisor hunts."""
    b = min(requested, t)
    while t % b:
        b -= 1
    return b


@trace.part(trace.ATTN_READ)
def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_size: int = 512,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """q/k/v: [B, H, T, D] → [B, H, T, D]. Keys/values are processed in
    blocks with the flash merge recurrence; ``block_size`` is clamped to the
    largest divisor of T.

    ``segment_ids``: optional [B, T] ints — a document is a contiguous run
    of equal ids; attention never crosses documents (same run semantics as
    the flash kernel: ids are normalized to run starts before comparing)."""
    b, h, t, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    block = auto_block(t, block_size)
    n_blocks = t // block

    q32 = q.astype(jnp.float32) * scale
    k_blocks = k.reshape(b, h, n_blocks, block, d)
    v_blocks = v.reshape(b, h, n_blocks, block, d)
    q_pos = lax.broadcasted_iota(jnp.int32, (t, block), 0)
    seg_q = None
    seg_blocks = None
    if segment_ids is not None:
        if segment_ids.shape != (b, t):
            raise ValueError(
                f"segment_ids shape {segment_ids.shape} != {(b, t)}"
            )
        from lzy_tpu.ops.flash_attention import document_starts

        runs = document_starts(segment_ids)
        seg_q = runs.reshape(b, 1, t, 1)
        seg_blocks = jnp.moveaxis(runs.reshape(b, n_blocks, block), 1, 0)

    def body(carry, inputs):
        o, m, l = carry
        if seg_blocks is not None:
            blk_idx, k_blk, v_blk, seg_blk = inputs
        else:
            (blk_idx, k_blk, v_blk), seg_blk = inputs, None
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk.astype(jnp.float32))
        keep = None
        if causal:
            k_pos = blk_idx * block + lax.broadcasted_iota(
                jnp.int32, (t, block), 1
            )
            keep = (q_pos >= k_pos)[None, None]
        if seg_blk is not None:
            same = seg_q == seg_blk[:, None, None, :]
            keep = same if keep is None else jnp.logical_and(keep, same)
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # fully-masked rows keep m at -inf; shift by 0 there to avoid NaN
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(jnp.where(m <= _NEG_INF / 2, _NEG_INF, m) - m_safe)
        alpha = jnp.where(m <= _NEG_INF / 2, 0.0, alpha)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32)
        )
        return (o_new, m_new, l_new), None

    o0 = _match_vma(jnp.zeros((b, h, t, d), jnp.float32), q, k, v)
    m0 = _match_vma(jnp.full((b, h, t), _NEG_INF, jnp.float32), q, k, v)
    l0 = _match_vma(jnp.zeros((b, h, t), jnp.float32), q, k, v)
    idxs = jnp.arange(n_blocks)
    xs = (idxs, jnp.moveaxis(k_blocks, 2, 0), jnp.moveaxis(v_blocks, 2, 0))
    if seg_blocks is not None:
        xs = xs + (seg_blocks,)
    (o, m, l), _ = lax.scan(jax.checkpoint(body), (o0, m0, l0), xs)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

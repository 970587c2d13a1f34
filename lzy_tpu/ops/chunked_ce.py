"""Fused (logits-free) causal-LM cross-entropy that makes its gradients
while it holds the logits.

The lm-head logits are the largest activation of a train step: for the
benchmark's training cell (8 x 4096 tokens a chip-step over four chips,
vocab 32768) the f32 ``[N, V]`` tensor is 1 GB a chip, written in forward
and re-read in backward. This op never holds it. One ``lax.scan`` walks
**blocks of tokens**; a block has its rows' whole logits at once, so the
log-sum-exp, the softmax, ``dlogits = (softmax - onehot) * weight``, the
block's ``dx`` and its share of ``dW`` are all made while the block's logits
are there. The head is multiplied three times a token (logits, dx, dW), as
a loss and its gradient need, and nothing is computed again in the
backward: the residuals *are* the gradients, and the backward multiplies
them by the scalar cotangent.

What a block holds in memory: its f32 logits (``rows x V x 4`` bytes, held
under ``_LOGITS_BYTES``: 1024 rows at vocab 32768), ``dlogits`` in the
features' dtype (half of that in bf16), and across blocks the f32 ``dW``
accumulator (``V x D x 4``: 537 MB at 32768 x 4096) and ``dx`` in the
features' dtype.

On a mesh that shards the batch (``shard=``) the loop runs per batch shard
inside ``jax.shard_map``: each device walks its own tokens with its own
``dW``, and the cross-device sum is one f32 ``psum_scatter`` onto the head's
own sharding after the loop. Left to the partitioner, a ``dW`` carry is
all-reduced inside the loop, every block.

No reference counterpart (the reference has no tensor math at all;
SURVEY.md §2.4); the blockwise-loss idea follows the public blockwise
attention/CE literature (see PAPERS.md), implemented here as a
``jax.custom_vjp`` over ``lax.scan``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from lzy_tpu.utils import trace

#: temp memory a block's f32 logits may take: what fixes the rows a block
_LOGITS_BYTES = 128 << 20


class BatchShard(NamedTuple):
    """Where the loss runs per batch shard: the mesh, the mesh axes that
    shard the leading (batch) dim of the features, and for each dim of the
    head those of them its parameter is laid over (where its gradient is
    scattered; over the rest it is summed whole)."""

    mesh: Mesh
    batch_axes: Tuple[str, ...]
    head_axes: Tuple[Tuple[str, ...], ...]


def _rows_per_block(n: int, v: int) -> int:
    """Tokens a block, read off the operands: as many rows (a multiple of
    8) as keep the block's f32 logits under ``_LOGITS_BYTES``, and no more
    than the tokens there are. At 1024 rows the block's ``dW`` product
    (``rows / 4`` FLOP a byte of its f32 accumulator) hides the accumulator;
    more rows bought nothing on the chip and cost their logits' memory
    (PERF.md section 6, PR 54, has the table)."""
    return min(max(_LOGITS_BYTES // (4 * v) // 8 * 8, 8), n)


def _block_sums(x, head, labels, w, rows):
    """``(sum of w * nll, dx [N, D], dW [V, D] f32)`` of the weighted nll
    sum over this device's tokens: one scan over blocks of ``rows`` tokens
    (None: what the shapes say; the tail is padded with weight-0 rows)."""
    n, d = x.shape
    v = head.shape[0]
    rows = _rows_per_block(n, v) if rows is None else min(rows, n)
    blocks = -(-n // rows)
    pad = blocks * rows - n
    if pad:
        x, labels, w = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                        for a in (x, labels, w))

    def block(carry, inputs):
        loss, dw = carry
        xb, lb, wb = inputs
        # bf16 MXU operands, f32 accumulation: the dense head einsum's
        # dtype discipline, in all three products
        logits = jnp.einsum("nd,vd->nv", xb, head,
                            preferred_element_type=jnp.float32)
        m = logits.max(axis=-1)
        logz = m + jnp.log(jnp.exp(logits - m[:, None]).sum(axis=-1))
        onehot = jnp.arange(v)[None, :] == lb[:, None]
        picked = jnp.where(onehot, logits, 0.0).sum(axis=-1)
        loss = loss + ((logz - picked) * wb).sum()
        dlogits = (jnp.exp(logits - logz[:, None])
                   - onehot.astype(jnp.float32)) * wb[:, None]
        # held as one array for both products: fused into them as a
        # producer, the exponentials are run again for every output tile
        # (one chip, 8192 rows: 47.5 ms a loss where this reads 41.9)
        dl = lax.optimization_barrier(dlogits.astype(xb.dtype))
        dx = jnp.einsum("nv,vd->nd", dl, head,
                        preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("nv,nd->vd", dl, xb,
                             preferred_element_type=jnp.float32)
        return (loss, dw), dx.astype(xb.dtype)

    (loss, dw), dx = lax.scan(
        block, (jnp.zeros((), jnp.float32), jnp.zeros((v, d), jnp.float32)),
        (x.reshape(blocks, rows, d), labels.reshape(blocks, rows),
         w.reshape(blocks, rows)))
    return loss, dx.reshape(blocks * rows, d)[:n], dw


def _mean_and_grads(x, head, labels, w, rows, shard):
    """The mask-weighted mean nll with its gradients by ``x`` and ``head``.
    ``shard``: a :class:`BatchShard`, or None: one shard under plain
    ``jit``, whose sums over no axes are the values themselves."""
    mesh, axes, scatter = shard or (None, (), ())
    rest = tuple(a for a in axes if not any(a in over for over in scatter))

    def per_shard(x, head, labels, w):
        # divided by their sum over every shard, the weights make the
        # block sums the mean's
        w = w / jnp.maximum(lax.psum(w.sum(), axes), 1.0)
        loss, dx, dw = _block_sums(x, head, labels, w, rows)
        # ONE cross-device sum of the head's gradient, in f32, after the loop
        for dim, over in enumerate(scatter):
            if over:
                dw = lax.psum_scatter(dw, over, scatter_dimension=dim,
                                      tiled=True)
        return lax.psum(loss, axes), dx, lax.psum(dw, rest)

    if shard is None:
        return per_shard(x, head, labels, w)
    tokens = P(axes)
    return jax.shard_map(
        per_shard, mesh=mesh, in_specs=(tokens, P(), tokens, tokens),
        out_specs=(P(), tokens, P(*(over or None for over in scatter))),
        check_vma=False,
    )(x, head, labels, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_nll(x, head, labels, w, rows, shard):
    return _mean_and_grads(x, head, labels, w, rows, shard)[0]


def _fwd(x, head, labels, w, rows, shard):
    loss, dx, dw = _mean_and_grads(x, head, labels, w, rows, shard)
    return loss, (dx, dw)


def _bwd(rows, shard, residuals, g):
    dx, dw = residuals
    # head arrives in x's dtype (chunked_cross_entropy), so dx's is both's
    return dx * g.astype(dx.dtype), (dw * g).astype(dx.dtype), None, None


_fused_nll.defvjp(_fwd, _bwd)


@trace.part(trace.LOSS)
def chunked_cross_entropy(
    features: jax.Array,            # [B, T, D] or [N, D] (bf16 ok)
    head: jax.Array,                # [V, D]
    labels: jax.Array,              # [B, T] or [N] int
    *,
    chunk: Optional[int] = None,
    mask: Optional[jax.Array] = None,
    shard: Optional[BatchShard] = None,
) -> jax.Array:
    """Mask-weighted mean nll, numerically identical to
    ``cross_entropy_loss(features @ head.T, labels, mask)`` but without the
    [N, V] intermediate. ``chunk``: tokens a block (any count: the last
    block is padded); by default what ``_rows_per_block`` reads off the
    shapes. ``shard``: run per batch shard (the leading dim of ``features``
    must divide over ``shard.batch_axes``)."""
    d = features.shape[-1]
    x, lf = features.reshape(-1, d), labels.reshape(-1)
    w = jnp.ones(lf.shape, jnp.float32) if mask is None \
        else mask.reshape(-1).astype(jnp.float32)
    return _fused_nll(x, head.astype(x.dtype), lf, w, chunk, shard)

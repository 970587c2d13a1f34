"""Blocks that more than one served model is built from
(``models/nemotron_h.py``, ``models/solar_open2.py``): the bias-free linear
layer and its initialiser, and grouped-query attention without a positional
embedding over the shared paged pool. ``cfg`` is the model's configuration
object: of it these read ``dtype``, ``param_dtype`` and, for the attention
block, ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``attn_gate``
and the paging fields ``paged_model`` sets (``decode_paged``, ``kv_pages``,
``kv_page_size``, ``paged_kernel``). A configuration that says
``group_read`` (``models/jamba.py``: 20 query heads over one key-value head)
has the same pools read as ``[pages, page, KV x D]`` by
``ops/paged_attention.py`` ``paged_group_attention``; one that does not has
the programs it had. The two counters of a full attention layer's reads live
here, for every family that sows them."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

ATTN_FULL_KEYS = REGISTRY.counter(
    "lzy_attn_full_keys_total",
    "cached keys the real rows of decode rounds read in layers that see "
    "everything (a row at position p reads p + 1), a layer")
ATTN_ROWS = REGISTRY.counter(
    "lzy_attn_rows_total",
    "real rows of decode rounds that read a paged attention layer, a layer")


def normal(std: float = 0.02):
    """``normal(std)`` drawn in float32 and then cast: drawn in bfloat16
    directly, a normal variate takes a few hundred distinct values."""
    def init(key, shape, dtype=jnp.float32):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            dtype)

    return init


class Linear(nn.Module):
    """``x @ kernel`` with no bias; ``out_dtype`` is what leaves the
    accumulator (float32 where the result steers an exponential)."""
    features: int
    dtype: Any
    param_dtype: Any
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", normal(),
                            (x.shape[-1], self.features), self.param_dtype)
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=self.out_dtype or self.dtype)


def dense(features, name, cfg, out_dtype=None):
    return Linear(features, cfg.dtype, cfg.param_dtype, out_dtype, name=name)


def into_heads(y, *shape):
    """A projection's result ``y`` ``[.., out]`` split into heads:
    ``y.reshape(shape)`` behind an ``optimization_barrier``. The head norms,
    the rotary embedding and the attention kernels want the result a head a
    row, and without the barrier the compiler gets it there by carrying that
    layout back through the matmul onto the *weight*: it transposes the
    whole ``[in, out]`` matrix in every program (``copy`` of
    ``bf16[4096,4096]``: 32 MB, 41 us on a v5e chip, 46 of them a
    MiniCPM-SALA decode round, 1.87 ms of 16.72; ``bf16[8192,4096]`` 154 us
    twice a Solar-Open2 round; ``bf16[2048,3072]`` 10.7 us in each of
    Moonlight's 27 layers: ledger, PR 51) to spare a relayout of ``[16,
    4096]`` activations. Behind the barrier the matmul reads the weight as it
    is stored and the relayout falls on ``y``. The copy was also the weight's
    one read from HBM (its result lay near the core), so what went with it is
    the transposition, not the read: a 4096 x 4096 projection of a decode
    round 49-53 us -> 28-29, a round 16.72 -> 15.80 ms (PERF.md section 6,
    PR 52).
    ``models/cohere2_moe.py`` ``HeadMajorLinear`` reached the same program by
    storing its weight ``[out, in]``; this leaves the parameter tree as it
    is. The barrier is the identity, with a transpose rule: gradients pass."""
    return jax.lax.optimization_barrier(y).reshape(shape)


def inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class PagedAttention(nn.Module):
    """Grouped-query attention, no rotary embedding, over the shared paged
    pool (or, uncached, causal over the chunk). A configuration whose
    ``attn_gate`` is true has the heads' output multiplied by
    ``sigmoid(gate_proj(u))`` before ``o_proj``. ``stats`` ``(at, of)``: the
    block sows the cached keys its real rows read (a row at position p reads
    p + 1) and those rows into places ``at`` and ``at + 1`` of a ``stats``
    vector of ``of`` (``models/serving.py``: the module's ``STATS``); only a
    block told ``valid_len`` whose configuration says ``group_read``
    counts."""
    cfg: Any
    stats: Any = None

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        from lzy_tpu.ops.paged_attention import (
            paged_attention, paged_scatter_index)

        cfg = self.cfg
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        with trace.part(trace.PROJ):
            q = into_heads(dense(h * d, "q_proj", cfg)(u), b, t, h, d)
            k = into_heads(dense(kv * d, "k_proj", cfg)(u), b, t, kv, d)
            v = into_heads(dense(kv * d, "v_proj", cfg)(u), b, t, kv, d)
        if not cfg.decode_paged:
            with trace.part(trace.ATTN_READ):
                qg = q.reshape(b, t, kv, h // kv, d)
                s = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                               preferred_element_type=jnp.float32) * d ** -0.5
                keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
                out = jnp.einsum("bkgtl,blkd->btkgd", pr.astype(cfg.dtype),
                                 v)
            return self._project(out, u)
        shape = (cfg.kv_pages, cfg.kv_page_size, kv, d)
        pool_k = self.variable("cache", "k", jnp.zeros, shape, cfg.dtype)
        pool_v = self.variable("cache", "v", jnp.zeros, shape, cfg.dtype)
        index = self.variable("cache", "index",
                              lambda: jnp.zeros((b,), jnp.int32))
        start = index.value
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
        if not self.is_initializing():
            if page_table is None:
                raise ValueError("a paged forward needs page_table")
            with trace.part(trace.CACHE_WRITE):
                rows, offs = paged_scatter_index(page_table, pos,
                                                 cfg.kv_page_size)
                pool_k.value = pool_k.value.at[rows, offs].set(
                    k.astype(cfg.dtype).reshape(b * t, kv, d))
                pool_v.value = pool_v.value.at[rows, offs].set(
                    v.astype(cfg.dtype).reshape(b * t, kv, d))
                index.value = index.value + t
        with trace.part(trace.ATTN_READ):
            if getattr(cfg, "group_read", False):
                out = self._group_read(q, pool_k.value, pool_v.value,
                                       page_table, start, valid_len)
            else:
                out = paged_attention(q, pool_k.value, pool_v.value,
                                      page_table, pos,
                                      kernel=cfg.paged_kernel,
                                      dtype=cfg.dtype)
        return self._project(out, u)

    def _group_read(self, q, pool_k, pool_v, page_table, start, valid_len):
        """The read of the pools as ``[pages, page, KV x D]`` (a token's keys
        of all heads side by side: the same bytes) from the first page to
        the row's own by ``paged_group_attention`` (decode rounds and prefill
        chunks alike; what it costs follows the context, not the table's
        width), and the block's counts."""
        from lzy_tpu.ops.paged_attention import paged_group_attention

        b = q.shape[0]
        side_by_side = pool_k.shape[:2] + (-1,)
        real = jnp.ones((b,), bool) if valid_len is None else valid_len > 0
        # an idle slot (no real position) reads one page, whatever its
        # stale position says
        out = paged_group_attention(
            q, pool_k.reshape(side_by_side), pool_v.reshape(side_by_side),
            page_table, jnp.where(real, start, 0),
            kernel=self.cfg.paged_kernel)
        if self.stats is not None and valid_len is not None:
            at, of = self.stats
            seen = jnp.where(real, start + valid_len.astype(jnp.int32), 0)
            counts = jnp.zeros((of,), jnp.int32).at[at].set(
                jnp.sum(seen)).at[at + 1].set(jnp.sum(real))
            self.sow("stats", "attn", counts,
                     reduce_fn=lambda a, x: a + x,
                     init_fn=lambda: jnp.zeros((of,), jnp.int32))
        return out.astype(self.cfg.dtype)

    @trace.part(trace.PROJ)
    def _project(self, out, u):
        cfg = self.cfg
        b, t, _ = u.shape
        out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
        if cfg.attn_gate:
            gate = dense(out.shape[-1], "gate_proj", cfg, jnp.float32)(u)
            out = (out * jax.nn.sigmoid(gate)).astype(cfg.dtype)
        return dense(cfg.d_model, "o_proj", cfg)(out)

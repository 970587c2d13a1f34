"""Blocks that more than one served model is built from
(``models/nemotron_h.py``, ``models/solar_open2.py``): the bias-free linear
layer and its initialiser, and grouped-query attention without a positional
embedding over the shared paged pool. ``cfg`` is the model's configuration
object: of it these read ``dtype``, ``param_dtype`` and, for the attention
block, ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``attn_gate``
and the paging fields ``paged_model`` sets (``decode_paged``, ``kv_pages``,
``kv_page_size``, ``paged_kernel``)."""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


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


def inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class PagedAttention(nn.Module):
    """Grouped-query attention, no rotary embedding, over the shared paged
    pool (or, uncached, causal over the chunk). A configuration whose
    ``attn_gate`` is true has the heads' output multiplied by
    ``sigmoid(gate_proj(u))`` before ``o_proj``."""
    cfg: Any

    @nn.compact
    def __call__(self, u, page_table=None):
        from lzy_tpu.ops.paged_attention import (
            paged_attention, paged_scatter_index)

        cfg = self.cfg
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = dense(h * d, "q_proj", cfg)(u).reshape(b, t, h, d)
        k = dense(kv * d, "k_proj", cfg)(u).reshape(b, t, kv, d)
        v = dense(kv * d, "v_proj", cfg)(u).reshape(b, t, kv, d)
        if not cfg.decode_paged:
            qg = q.reshape(b, t, kv, h // kv, d)
            s = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                           preferred_element_type=jnp.float32) * d ** -0.5
            keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
            pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
            out = jnp.einsum("bkgtl,blkd->btkgd", pr.astype(cfg.dtype), v)
            return self._project(out, u)
        shape = (cfg.kv_pages, cfg.kv_page_size, kv, d)
        pool_k = self.variable("cache", "k", jnp.zeros, shape, cfg.dtype)
        pool_v = self.variable("cache", "v", jnp.zeros, shape, cfg.dtype)
        index = self.variable("cache", "index",
                              lambda: jnp.zeros((b,), jnp.int32))
        pos = index.value[:, None] + jnp.arange(t, dtype=jnp.int32)
        if not self.is_initializing():
            if page_table is None:
                raise ValueError("a paged forward needs page_table")
            rows, offs = paged_scatter_index(page_table, pos,
                                             cfg.kv_page_size)
            pool_k.value = pool_k.value.at[rows, offs].set(
                k.astype(cfg.dtype).reshape(b * t, kv, d))
            pool_v.value = pool_v.value.at[rows, offs].set(
                v.astype(cfg.dtype).reshape(b * t, kv, d))
            index.value = index.value + t
        out = paged_attention(q, pool_k.value, pool_v.value, page_table,
                              pos, kernel=cfg.paged_kernel, dtype=cfg.dtype)
        return self._project(out, u)

    def _project(self, out, u):
        cfg = self.cfg
        b, t, _ = u.shape
        out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
        if cfg.attn_gate:
            gate = dense(out.shape[-1], "gate_proj", cfg, jnp.float32)(u)
            out = (out * jax.nn.sigmoid(gate)).astype(cfg.dtype)
        return dense(cfg.d_model, "o_proj", cfg)(out)

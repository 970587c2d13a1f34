"""Command A+ (``model_type`` ``cohere2_moe``; ``command-a-plus-05-2026``: 32
layers at 4096, 128 query heads over 8 key-value heads of 128): layers whose
attention sees only the last ``window`` positions ("sliding", with rotary
embedding) and, every fourth, a layer that sees everything with no positional
embedding, each beside a mixture of gated experts in a **parallel block**:
one LayerNorm (mean removed, no bias), attention and the expert layer both
from the normed input, both added to the residual,
``x' = x + attn(LN(x)) + moe(LN(x))``. Embeddings are tied.

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **paged leaves of two lifetimes** (:attr:`Cohere2Moe.CACHE_KINDS`). A full
  layer keeps ``k`` / ``v`` (kind ``paged``): every token of the context for
  as long as the row lives. A window layer keeps ``wk`` / ``wv`` (kind
  ``window``): a query at position ``p`` reads keys ``p - window < j <= p``
  and nothing behind them ever again, so the engine returns pages wholly
  behind the window to a pool of their own and the row's *window table*
  reads scratch there (``kv_window`` says how wide; the module takes the
  second table as ``window_table``). All leaves are ``[pages, page,
  KV x D]``, a token's keys of all heads side by side in one row:
  ``ops/paged_attention.py`` ``paged_group_attention`` reads a head's as a
  slice of lanes, in decode rounds and prefill chunks alike, from the first
  page that can hold a visible key.
- **no state leaf**, but the mechanisms that move pages by a prefix's
  tokens (the radix cache, parking, speculation's rewind, export / import,
  the tiers) know one kind of page: the engine refuses them for a model with
  window leaves, by name (``docs/serving.md``); ``kv_quant`` is refused here.
- **an expert layer that is told which experts it holds** (``experts_held``;
  ``models/experts.py`` :class:`GatedExperts`): sigmoid scores over
  ``n_routed_experts`` in float32, the ``top_k`` largest chosen with no
  correction bias, renormalised over all the chosen (held here or not), no
  scaling; ``n_shared`` shared experts of the routed width whose outputs are
  **averaged** (one gated MLP of ``n_shared x expert_width`` times
  ``1 / n_shared``: the same sum) and added to the routed sum.
- **counts** a round carries out with its tokens (:attr:`Cohere2Moe.STATS`):
  the experts' four and, beside them, the keys the round's real rows read in
  window layers, in full layers, and the rows that read them, a layer.

Read from the published config where it gives only a flag (the benchmark's
configuration file lists them as ``assumed``): the rotary pairs value ``i``
with ``i + d/2`` (``models/llama.py`` ``_rope``; the published
``rope_gptj`` interleaves, the same map under a fixed permutation of
``W_q``'s and ``W_k``'s columns); "shared experts averaged" is the mean of
the shared outputs added to the routed sum; the width of one expert is
``intermediate_size``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models import experts
from lzy_tpu.models.experts import GatedExperts, row_mask
from lzy_tpu.models.llama import _rope
from lzy_tpu.models.paged_blocks import (
    ATTN_FULL_KEYS, ATTN_ROWS, dense, normal)
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops.paged_attention import (
    group_path, lower_group_for_tpu, paged_group_attention,
    paged_scatter_index)
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

ATTN_WINDOW_KEYS = REGISTRY.counter(
    "lzy_attn_window_keys_total",
    "cached keys the real rows of decode rounds read in layers with a "
    "window (a row at position p reads min(p + 1, window)), a layer")

SLIDING, FULL = "sliding_attention", "full_attention"


class WindowPoolUnsupported(ValueError):
    """A mechanism that pools of the ``[pages, page, KV x D]`` layout, or of
    two lifetimes, cannot serve, by name."""


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    #: each layer's attention, ``sliding_attention`` or ``full_attention``
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 4096
    rope_theta: float = 50000.0
    # experts
    n_routed_experts: int = 128          # the router's width
    experts_held: Tuple[int, int] = (0, 128)   # [lo, hi) held here
    top_k: int = 8
    expert_width: int = 4096
    n_shared: int = 4
    routed_scaling: float = 1.0
    router_bias: bool = False
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 200000
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: keys and values in two shared paged pools
    decode_paged: bool = False
    kv_page_size: int = 32
    kv_pages: int = 0
    window_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers or not set(
                self.layer_types) <= {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, each "
                f"{SLIDING!r} or {FULL!r}; got {self.layer_types}")
        if FULL not in self.layer_types:
            raise ValueError(
                "no full_attention layer: the engine tells a live row from "
                "an idle one by the pages that are never returned")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} outside the router's "
                f"{self.n_routed_experts}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads must divide into their groups, and the "
                             "rotary width must be even")
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "Cohere2MoeConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``router_width`` and
        ``experts_held_from`` (a deployment's, not published) say which of
        the router's experts are held here."""
        refused = {
            "expert_selection_fn": ("sigmoid",), "norm_topk_prob": (True,),
            "shared_expert_combination_strategy": ("average",),
            "use_parallel_block": (True,), "use_qk_norm": (False, None),
            "attention_bias": (False, None), "hidden_act": ("silu", None),
            "use_gated_activation": (True,), "rotary_pct": (1, 1.0),
            "tie_word_embeddings": (True,), "first_k_dense_replace": (0,),
            "position_embedding_type": ("rope_gptj",),
        }
        for key, served in refused.items():
            if doc.get(key) not in served:
                raise ValueError(
                    f"Cohere2MoeConfig serves {key} in {served!r} (a "
                    f"parallel block, sigmoid selection renormalised, "
                    f"shared experts averaged, plain rotary over the whole "
                    f"head, tied embeddings, no dense prefix); the "
                    f"configuration says {key} = {doc.get(key)!r}")
        width = doc.get("router_width", doc["num_experts"])
        lo = doc.get("experts_held_from", 0)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=doc["num_hidden_layers"],
            layer_types=tuple(doc["layer_types"]),
            n_heads=doc["num_attention_heads"],
            n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
            window=doc["sliding_window"],
            rope_theta=float(doc["rope_theta"]),
            n_routed_experts=width,
            experts_held=(lo, lo + doc["num_experts"]),
            top_k=doc["num_experts_per_tok"],
            expert_width=doc["intermediate_size"],
            n_shared=doc["num_shared_experts"],
            norm_eps=float(doc["layer_norm_eps"]),
            logit_scale=float(doc["logit_scale"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def kv_layers(self) -> int:
        """Layers whose pages keep every token: what sizes the ``paged``
        pool."""
        return self.layer_types.count(FULL)

    @property
    def window_layers(self) -> int:
        """Layers whose pages go back behind the window: what sizes the
        ``window`` pool."""
        return self.layer_types.count(SLIDING)

    @property
    def kv_window(self) -> int:
        """Positions a ``window`` leaf keeps readable behind the newest."""
        return self.window

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def shared_width(self) -> int:
        """The shared experts side by side as one gated MLP."""
        return self.n_shared * self.expert_width

    @property
    def shared_scale(self) -> float:
        """Their outputs are averaged."""
        return 1.0 / self.n_shared

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "Cohere2MoeConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise WindowPoolUnsupported(
                f"kv_quant={kv_quant!r}: int8 pools and their sidecars are "
                f"laid out a head (ops/paged_attention.py quantize_kv); "
                f"this model's pools are [pages, page, KV x D] in "
                f"{jnp.dtype(self.dtype).name}")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str], window_pages: int):
        self._refuse_quant(kv_quant)
        return Cohere2Moe(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, window_pages=window_pages,
            paged_kernel=kernel))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one layer, of either kind: keys and
        values of every key-value head (4096 at the published widths)."""
        self._refuse_quant(kv_quant)
        return 2 * self.n_kv_heads * self.head_dim \
            * jnp.dtype(self.dtype).itemsize

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the attention reads
        of a program over ``t`` positions a row."""
        return group_path(kernel, t=t)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket. A program
        reads every held expert (9.5 GB of weights at the Command A+ cut)
        whatever its width, and the chunk read's arithmetic grows with its
        rows as the dense products do."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        return (gexp.PATH,)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None,
                      window_blocks: Optional[int] = None) -> None:
        """Lower this model's kernels for a TPU (no device, no compile): the
        expert product at the decode step's rows and at the widest chunk's
        and, with pools named, both reads over each (a decode round's, whose
        page table is the widest the scalar prefetch carries, and the
        widest prefill chunk's; with the window and without)."""
        self._refuse_quant(kv_quant)
        if kv_blocks is not None:
            for blocks, window in ((kv_blocks, None),
                                   (window_blocks, self.window)):
                for batch, t in ((slots, 1), (1, self.widest_prefill)):
                    lower_group_for_tpu(
                        batch=batch, t=t, n_heads=self.n_heads,
                        n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                        n_blocks=blocks, page_size=page_size,
                        pages_per_seq=pages_per_seq, dtype=self.dtype,
                        window=window)
        for rows in (slots, self.widest_prefill):
            gexp.lower_for_tpu(rows=rows, experts=self.n_held,
                               latent=self.d_model, width=self.expert_width,
                               dtype=self.dtype, gated=True)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Cohere2MoeConfig":
        """Every mechanism at a size the CPU tests run: one period of four
        layers, 32 heads over 2 (a group of 16) of 16, a window of 24, 16
        routed experts of which 4 a token, 2 shared."""
        return Cohere2MoeConfig(
            vocab_size=vocab_size, d_model=64, n_layers=4,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL), n_heads=32,
            n_kv_heads=2, head_dim=16, window=24, n_routed_experts=16,
            experts_held=(0, 16), top_k=4, expert_width=32, n_shared=2,
            max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32,
            kv_page_size=8)


class LayerNorm(nn.Module):
    """``(x - mean) * rsqrt(var + eps) * weight`` in float32, no bias."""
    eps: float
    param_dtype: Any

    @nn.compact
    @trace.part(trace.NORM)
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


class HeadMajorLinear(nn.Module):
    """``x @ kernel_t.T`` with the kernel stored ``[out, in]``. The query
    projection's result goes to the attention kernel a head a row
    (``[.., heads, head_dim]`` tiles), and the compiler gets it there by
    contracting against the weight with its output features major: stored
    ``[in, out]`` it transposes the whole matrix in every program (134 MB
    at 4096 x 16384: 0.41 ms a layer a decode round on a v5e chip, PERF.md
    section 6, PR 41); stored ``[out, in]`` it reads it as it lies."""
    features: int
    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        kernel = self.param("kernel_t", normal(),
                            (self.features, x.shape[-1]), cfg.param_dtype)
        return jnp.einsum("...e,fe->...f", x.astype(cfg.dtype),
                          kernel.astype(cfg.dtype),
                          preferred_element_type=cfg.dtype)


class GroupAttention(nn.Module):
    """Grouped-query attention of either kind: ``windowed`` layers rotate
    queries and keys and see the last ``window`` positions, the others use
    no positional embedding and see everything."""
    cfg: Cohere2MoeConfig
    windowed: bool

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        window = cfg.window if self.windowed else None
        with trace.part(trace.PROJ):
            q = HeadMajorLinear(h * d, cfg, name="q_proj")(u).reshape(
                b, t, h, d)
            k = dense(kv * d, "k_proj", cfg)(u).reshape(b, t, kv, d)
            v = dense(kv * d, "v_proj", cfg)(u)
        if cfg.decode_paged:
            pages = cfg.window_pages if self.windowed else cfg.kv_pages
            names = ("wk", "wv") if self.windowed else ("k", "v")
            shape = (pages, cfg.kv_page_size, kv * d)
            pool_k = self.variable("cache", names[0], jnp.zeros, shape,
                                   cfg.dtype)
            pool_v = self.variable("cache", names[1], jnp.zeros, shape,
                                   cfg.dtype)
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start = index.value
        else:
            start = jnp.zeros((b,), jnp.int32)
        with trace.part(trace.PROJ):
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
            if self.windowed:
                q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos,
                                                            cfg.rope_theta)
        if not cfg.decode_paged:
            with trace.part(trace.ATTN_READ):
                qg = q.reshape(b, t, kv, h // kv, d)
                s = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                               preferred_element_type=jnp.float32) * d ** -0.5
                at = jnp.arange(t)
                keep = at[:, None] >= at[None, :]
                if window is not None:
                    keep &= at[None, :] > at[:, None] - window
                pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
                out = jnp.einsum("bkgtl,blkd->btkgd", pr.astype(cfg.dtype),
                                 v.reshape(b, t, kv, d))
        else:
            real = row_mask(valid_len, b, t)
            if not self.is_initializing():
                if page_table is None:
                    raise ValueError("a paged forward needs its page table")
                with trace.part(trace.CACHE_WRITE):
                    rows, offs = paged_scatter_index(page_table, pos,
                                                     cfg.kv_page_size)
                    pool_k.value = pool_k.value.at[rows, offs].set(
                        k.astype(cfg.dtype).reshape(b * t, kv * d))
                    pool_v.value = pool_v.value.at[rows, offs].set(
                        v.astype(cfg.dtype).reshape(b * t, kv * d))
                    index.value = index.value + t
            # an idle slot (no real position) reads one page, whatever its
            # stale position says
            out = paged_group_attention(
                q, pool_k.value, pool_v.value, page_table,
                jnp.where(real[:, 0], start, 0), window=window,
                kernel=cfg.paged_kernel)
            with trace.part(trace.ATTN_READ):
                # the last real query of a row at position p reads p + 1 keys,
                # or the window's worth of them
                seen = jnp.where(real[:, 0], start + jnp.sum(real, axis=1), 0)
                if window is not None:
                    seen = jnp.minimum(seen, window)
                keys = jnp.sum(seen)
                by_kind = [keys, 0] if self.windowed else [0, keys]
                other = len(experts.STATS)
                self.sow("stats", "attn", jnp.concatenate([
                    jnp.zeros((other,), jnp.int32),
                    jnp.stack([*map(jnp.asarray, by_kind),
                               jnp.sum(real[:, 0])]).astype(jnp.int32)]),
                    reduce_fn=lambda a, x: a + x,
                    init_fn=lambda: jnp.zeros((other + 3,), jnp.int32))
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "o_proj", cfg)(
                out.astype(cfg.dtype).reshape(b, t, h * d))


class Cohere2Moe(nn.Module):
    cfg: Cohere2MoeConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"k": "paged", "v": "paged", "wk": "window",
                   "wv": "window", "index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = experts.STATS + (ATTN_WINDOW_KEYS, ATTN_FULL_KEYS, ATTN_ROWS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None,
                 window_table=None):
        cfg = self.cfg
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens]
        for i, kind in enumerate(cfg.layer_types):
            windowed = kind == SLIDING
            u = LayerNorm(cfg.norm_eps, cfg.param_dtype,
                          name=f"layer_{i}_norm")(x)
            a = GroupAttention(cfg, windowed, name=f"layer_{i}")(
                u, window_table if windowed else page_table, valid_len)
            m = GatedExperts(cfg, other_stats=3, name=f"layer_{i}_moe")(
                u, valid_len)
            # the parallel block's sum is filed with the larger summand
            with trace.part(trace.EXPERTS):
                x = x + a + m
        with trace.part(trace.HEAD):
            x = LayerNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            logits = jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                                emb.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            return logits if cfg.logit_scale == 1.0 \
                else logits * cfg.logit_scale


def init_params(cfg: Cohere2MoeConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Cohere2Moe(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

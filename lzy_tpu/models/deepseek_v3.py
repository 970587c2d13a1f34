"""The DeepSeek-V3 family (``model_type`` ``deepseek_v3``; ``Moonlight-16B-A3B``:
27 layers at 2048, 16 heads): multi-head **latent** attention and, after a
first dense layer, a mixture of gated experts with a shared pair. Every block
is ``h + attn(RMSNorm(h))`` then ``h + ffn(RMSNorm(h))``.

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **one latent leaf a layer, with no head axis** (:attr:`DeepseekV3.CACHE_KINDS`:
  ``latent``, kind ``paged``, and the ``index``). A token caches
  ``[c ; k_rope]``: the normalised compressed key-value (``kv_lora_rank``,
  512) and the one rotary key every head shares (``qk_rope_head_dim``, 64):
  576 values where keys and values a head would be ``2 x KV x D``. The leaf
  is ``[pages, page, latent_width]`` with ``latent_width`` 640: the TPU lays
  the minor dimension out in tiles of 128 lanes, so a leaf of 576 occupies
  640 in HBM all the same (the compiler's own memref says so) and a DMA may
  not slice it at 576; the leaf is declared at what it occupies, its last 64
  lanes zero. ``c`` and ``k_rope`` as two leaves (512 + 64) would occupy
  512 + 128 and cost a second DMA a page. ``kv_token_bytes`` answers 1280.
- **two reads of it, both the absorbed form** (``ops/mla.py``): the key's
  up-projection is folded into the query and the value's applied after the
  sum, so every head scores against the one cached vector and a page is read
  once. Decode rounds and the verify window: ``mla_paged_decode``; a batch-1
  prefill chunk: ``mla_paged_prefill`` (the same kernel body over tiles of
  64 query positions); ``kernel="lax"``: the same sum over the gathered
  table. Why absorbed in prefill too: a chunk of 256 positions through the
  expanded form pays ``W_kvb`` for every cached position (4.2 MFLOP a
  position a layer) before 2.6 MFLOP of scores and values, against 8.9 MFLOP
  absorbed, and needs the expanded keys and values of the whole prefix in
  HBM; the absorbed kernel reads only the live pages and keeps scores in
  VMEM (PERF.md section 6, PR 36, has the timings).
- **no state leaf**: the radix cache, parking, speculation's rewind, KV
  export / import and the tiers all work over the latent leaf as over keys
  and values (``docs/serving.md``); ``kv_quant`` is refused by name.
- **an expert layer that is told which experts it holds**
  (``experts_held``; ``models/experts.py`` :class:`GatedExperts`, the layer
  Solar-Open2 shares): sigmoid scores over ``n_routed_experts`` in float32,
  the ``top_k`` largest of ``scores + bias`` (``noaux_tc`` with one group),
  renormalised and scaled by ``routed_scaling``; the shared pair is one
  SwiGLU MLP of twice the expert width, whole.
- **counts** a round carries out with its tokens (:attr:`DeepseekV3.STATS`):
  the experts' four and, beside them, the context tokens the round's rows read
  through the latent pool and the rows that read them, a layer.

Read from the published config where it gives only a flag (the benchmark's
configuration file lists them as ``assumed``): the rotary pairs value ``i``
with ``i + d/2`` (``models/llama.py`` ``_rope``); the softmax scale is
``(qk_nope_head_dim + qk_rope_head_dim)^-1/2`` with no ``mscale`` (no
``rope_scaling``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models import experts
from lzy_tpu.models.experts import GatedExperts, row_mask
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.paged_blocks import dense, into_heads, normal
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mla
from lzy_tpu.utils import trace
from lzy_tpu.ops.paged_attention import paged_scatter_index
from lzy_tpu.utils.metrics import REGISTRY

MLA_CONTEXT_TOKENS = REGISTRY.counter(
    "lzy_mla_context_tokens_total",
    "cached positions the real rows of decode rounds read through the "
    "latent pool (a row at position p reads p + 1), a layer")
MLA_ROWS = REGISTRY.counter(
    "lzy_mla_rows_total",
    "real rows of decode rounds that read the latent pool, a layer")

_LANES = 128


class LatentPoolUnsupported(ValueError):
    """A mechanism that a latent pool (one vector a token, no head axis)
    cannot serve, by name."""


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 163840
    d_model: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    # latent attention
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    # the first layers' dense MLP
    first_dense: int = 1
    dense_width: int = 11264
    # experts
    n_routed_experts: int = 64           # the router's width
    experts_held: Tuple[int, int] = (0, 64)    # [lo, hi) held here
    top_k: int = 6
    expert_width: int = 1408
    shared_width: int = 2816             # n_shared_experts x expert_width
    routed_scaling: float = 2.446
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: one latent vector a token in a shared paged pool
    decode_paged: bool = False
    kv_page_size: int = 16
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} outside the router's "
                f"{self.n_routed_experts}")
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError("first_dense outside the layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary width must be even")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "DeepseekV3Config":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``router_width`` and
        ``experts_held_from`` (a deployment's, not published) say which of
        the router's experts are held here."""
        refused = {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "n_group": (1, None), "topk_group": (1, None),
            "norm_topk_prob": (True,), "tie_word_embeddings": (False, None),
            "attention_bias": (False, None), "hidden_act": ("silu", None),
            "moe_layer_freq": (1, None),
        }
        for key, served in refused.items():
            if doc.get(key) not in served:
                raise ValueError(
                    f"DeepseekV3Config serves {key} in {served!r} (a direct "
                    f"query, plain rotary, sigmoid scores with one group, "
                    f"renormalised); the configuration says "
                    f"{key} = {doc.get(key)!r}")
        width = doc.get("router_width", doc["n_routed_experts"])
        lo = doc.get("experts_held_from", 0)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=doc["num_hidden_layers"],
            n_heads=doc["num_attention_heads"],
            kv_lora_rank=doc["kv_lora_rank"],
            qk_nope_head_dim=doc["qk_nope_head_dim"],
            qk_rope_head_dim=doc["qk_rope_head_dim"],
            v_head_dim=doc["v_head_dim"],
            rope_theta=float(doc["rope_theta"]),
            first_dense=doc["first_k_dense_replace"],
            dense_width=doc["intermediate_size"],
            n_routed_experts=width,
            experts_held=(lo, lo + doc["n_routed_experts"]),
            top_k=doc["num_experts_per_tok"],
            expert_width=doc["moe_intermediate_size"],
            shared_width=doc["n_shared_experts"]
            * doc["moe_intermediate_size"],
            routed_scaling=float(doc["routed_scaling_factor"]),
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: every one."""
        return self.n_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_values(self) -> int:
        """What a token caches a layer: ``c`` and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The cached vector as the pool lays it out: whole tiles of 128
        lanes (576 values in 640)."""
        return -(-self.latent_values // _LANES) * _LANES

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "DeepseekV3Config":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise LatentPoolUnsupported(
                f"kv_quant={kv_quant!r}: int8 pools quantise keys and values "
                f"a head (ops/paged_attention.py quantize_kv); this model's "
                f"pool is one latent vector a token with no head axis, kept "
                f"in {jnp.dtype(self.dtype).name}")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str]):
        self._refuse_quant(kv_quant)
        return DeepseekV3(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one pool layer: the latent vector as
        the pool lays it out (1280 at the published widths in bfloat16, of
        which 1152 are values)."""
        self._refuse_quant(kv_quant)
        return self.latent_width * jnp.dtype(self.dtype).itemsize

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the latent read of a
        program over ``t`` positions a row."""
        return mla.read_path(kernel, t=t)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program this model's kernels take: 256. At
        the Moonlight-16B-A3B widths (27 layers, 16 of 64 experts held) the
        engine's own ``prefill_step`` of 16 / 64 / 128 / 256 positions,
        continuing a prompt at position 2048, takes 30.0 / 33.3 / 34.7 /
        40.3 ms on a v5e chip (host clock around the dispatch, median of
        seven; at 6144: 30.6 / 35.3 / 38.8 / 47.2): 0.16 ms a position at
        256 against 0.27 at 128. A program of 16 is already the read of
        every held expert (9.3 GB of weights); what 256 adds is the expert
        kernel's arithmetic and the latent read (PERF.md section 6,
        PR 36)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the latent read's own."""
        return (gexp.PATH,) if self.expert_layers else ()

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower this model's kernels for a TPU (no device, no compile):
        the expert product at the decode step's rows and, with a pool named,
        both latent reads over it (the decode step's, whose page table is
        the widest the scalar prefetch carries, and the widest prefill
        chunk's). Refused here, not at the first request."""
        self._refuse_quant(kv_quant)
        if kv_blocks is not None:
            for batch, t in ((slots, 1), (1, self.widest_prefill)):
                mla.lower_for_tpu(
                    batch=batch, t=t, heads=self.n_heads,
                    width=self.latent_width, value_dim=self.kv_lora_rank,
                    n_blocks=kv_blocks, page_size=page_size,
                    pages_per_seq=pages_per_seq, dtype=self.dtype)
        if self.expert_layers:
            gexp.lower_for_tpu(rows=slots, experts=self.n_held,
                               latent=self.d_model, width=self.expert_width,
                               dtype=self.dtype, gated=True)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "DeepseekV3Config":
        """Every mechanism at a size the CPU tests run: a dense layer and
        two expert layers, 4 heads over a latent of 32 + 8, 16 routed
        experts of which 4 a token."""
        return DeepseekV3Config(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, first_dense=1, dense_width=128,
            n_routed_experts=16, experts_held=(0, 16), top_k=4,
            expert_width=32, shared_width=64, max_seq_len=128,
            dtype=jnp.float32, param_dtype=jnp.float32)


class LatentAttention(nn.Module):
    """Multi-head latent attention, absorbed: the cached vector is scored by
    every head and summed by every head, ``W_kvb`` on either side."""
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        w = cfg.latent_width
        with trace.part(trace.PROJ):
            q = into_heads(dense(h * (dn + dr), "q_proj", cfg)(u),
                           b, t, h, dn + dr)
            kva = dense(r + dr, "kv_a_proj", cfg)(u)
            c = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="kv_a_norm")(
                kva[..., :r])
            # [rank, head, nope + value]: the keys' and the values'
            # up-projection
            w_kvb = self.param("kv_b_proj", normal(), (r, h, dn + dv),
                               cfg.param_dtype).astype(cfg.dtype)

        cached = cfg.decode_paged
        if cached:
            pool = self.variable(
                "cache", "latent", jnp.zeros,
                (cfg.kv_pages, cfg.kv_page_size, w), cfg.dtype)
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start = index.value
        else:
            start = jnp.zeros((b,), jnp.int32)
        with trace.part(trace.PROJ):
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
            q_rope = _rope(q[..., dn:], pos, cfg.rope_theta)
            k_rope = _rope(kva[:, :, None, r:], pos, cfg.rope_theta)[:, :, 0]
            # absorb the keys' up-projection into the query
            q_abs = jnp.einsum("bthn,rhn->bthr", q[..., :dn], w_kvb[..., :dn],
                               preferred_element_type=jnp.float32)
            pad = w - r - dr
            q_full = jnp.concatenate(
                [q_abs.astype(cfg.dtype), q_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, h, pad), cfg.dtype)], axis=-1)
            lat = jnp.concatenate(
                [c.astype(cfg.dtype), k_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, pad), cfg.dtype)], axis=-1)       # [B, T, W]

        if not cached:
            summed = mla.causal_mla_attention(
                q_full, lat, value_dim=r, scale=cfg.softmax_scale)
        else:
            real = row_mask(valid_len, b, t)
            if not self.is_initializing():
                if page_table is None:
                    raise ValueError("a paged forward needs page_table")
                with trace.part(trace.CACHE_WRITE):
                    rows, offs = paged_scatter_index(page_table, pos,
                                                     cfg.kv_page_size)
                    pool.value = pool.value.at[rows, offs].set(
                        lat.reshape(b * t, w))
                    index.value = index.value + t
            # an idle slot (no real position) is told so, whatever its stale
            # position says: the read skips it and gives it 0
            summed = mla.mla_attention(
                q_full, pool.value, page_table,
                jnp.where(real[:, 0], start, -1), value_dim=r,
                scale=cfg.softmax_scale, kernel=cfg.paged_kernel)
            with trace.part(trace.ATTN_READ):
                # the last real query of a row at position p reads p + 1
                last = pos[:, 0] + jnp.sum(real, axis=1)
                other = len(experts.STATS)
                self.sow("stats", "mla", jnp.concatenate([
                    jnp.zeros((other,), jnp.int32),
                    jnp.stack([jnp.sum(jnp.where(real[:, 0], last, 0)),
                               jnp.sum(real[:, 0])]).astype(jnp.int32)]),
                    reduce_fn=lambda a, x: a + x,
                    init_fn=lambda: jnp.zeros((other + 2,), jnp.int32))
        with trace.part(trace.PROJ):
            out = jnp.einsum("bthr,rhv->bthv", summed.astype(cfg.dtype),
                             w_kvb[..., dn:],
                             preferred_element_type=jnp.float32)
            return dense(cfg.d_model, "o_proj", cfg)(
                out.astype(cfg.dtype).reshape(b, t, h * dv))


class GatedMlp(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    @trace.part(trace.FFN)
    def __call__(self, u):
        cfg = self.cfg
        f32 = jnp.float32
        hid = jax.nn.silu(dense(cfg.dense_width, "gate_proj", cfg, f32)(u)) \
            * dense(cfg.dense_width, "up_proj", cfg, f32)(u)
        return dense(cfg.d_model, "down_proj", cfg)(hid.astype(cfg.dtype))


class DeepseekV3(nn.Module):
    cfg: DeepseekV3Config

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"latent": "paged", "index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = experts.STATS + (MLA_CONTEXT_TOKENS, MLA_ROWS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None):
        cfg = self.cfg
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens]

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        for i in range(cfg.n_layers):
            y = LatentAttention(cfg, name=f"layer_{i}")(
                norm(f"layer_{i}_norm")(x), page_table, valid_len)
            # a residual sum is filed with the block it closes
            with trace.part(trace.PROJ):
                x = x + y
            u = norm(f"layer_{i}_ffn_norm")(x)
            if i < cfg.first_dense:
                with trace.part(trace.FFN):
                    x = x + GatedMlp(cfg, name=f"layer_{i}_mlp")(u)
            else:
                y = GatedExperts(cfg, other_stats=2,
                                 name=f"layer_{i}_moe")(u, valid_len)
                with trace.part(trace.EXPERTS):
                    x = x + y
        with trace.part(trace.HEAD):
            x = norm("final_norm")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: DeepseekV3Config, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(DeepseekV3(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

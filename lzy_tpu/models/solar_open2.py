"""Solar-Open2: a hybrid decoder of gated delta-rule linear attention
("KDA") and gated softmax attention with no positional embedding, every
layer followed by a mixture of gated experts (``Solar-Open2-250B``: 48
layers of period 4, layer ``4i`` attention at 64 query / 8 key-value heads,
layers ``4i+1..4i+3`` KDA at 64 heads of 128; 320 routed experts of width
1280, 8 a token, and one shared expert). Every block is
``h + mixer(RMSNorm(h))`` then ``h + moe(RMSNorm(h))``.

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **cache leaves of three kinds** (:attr:`SolarOpen2.CACHE_KINDS`). The
  attention layers keep keys and values in the shared paged pool (``k``,
  ``v``: ``paged``, through ``models/paged_blocks.py``'s attention block) and
  an ``index``. A KDA layer keeps **per-slot state**: ``conv``
  ``[slots, kernel - 1, 3 x heads x head_dim]``, the last inputs of the
  causal convolution over ``q``, ``k`` and ``v``, and ``kda``
  ``[slots, heads, head_dim, head_dim]`` in float32, the delta rule's state,
  value-major (``ops/kda.py``); kind ``state``: a row belongs to one slot,
  cannot be shared through a page table and cannot be rewound.
- ``valid_len`` ``[B]``: how many of a row's ``T`` positions are real. A
  KDA layer freezes its state past it (decay 1, write 0, the convolution's
  window taken at the last real position); an expert layer leaves those rows
  out of its product and of its counts.
- **an expert layer that is told which experts it holds**
  (``experts_held``; ``models/experts.py``): the router keeps its published
  width and its experts per token, the layer computes the part of the
  result its own experts give (``ops/grouped_experts.py``, the gated form),
  and a chosen expert held elsewhere adds nothing here. The shared expert
  is whole.
- **counts** a round carries out with its tokens (``models/experts.py``
  ``STATS``).

Read from the published config where it gives only a flag (the
benchmark's configuration file lists them as ``assumed``): the attention's
output gate is elementwise, ``sigmoid(W_gate x)`` on the heads' output
before ``o_proj`` (arXiv 2505.06708); ``kda_use_full_proj`` false makes the
decay's and the output gate's projections low-rank (rank ``gate_rank``);
the router is sigmoid scores with a correction bias for the choice only,
the chosen scores renormalised.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models.experts import STATS, GatedExperts, row_mask
from lzy_tpu.models.llama import RMSNorm
from lzy_tpu.models.paged_blocks import (
    PagedAttention, dense, inv_softplus)
from lzy_tpu.models.serving import HeadPool
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import kda
from lzy_tpu.utils import trace


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(HeadPool):
    vocab_size: int = 196608
    d_model: int = 4096
    n_layers: int = 48
    #: the layers whose mixer is softmax attention; the others are KDA
    attn_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    # gated attention, no positional embedding
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    attn_gate: bool = True
    # KDA
    kda_heads: int = 64
    kda_head_dim: int = 128
    conv_kernel: int = 4
    gate_rank: int = 128
    chunk_size: int = 16
    # experts
    n_routed_experts: int = 320          # the router's width
    experts_held: Tuple[int, int] = (0, 320)   # [lo, hi) held here
    top_k: int = 8
    expert_width: int = 1280
    shared_width: int = 1280
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4608
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: keys and values in a shared paged pool, state a slot
    decode_paged: bool = False
    kv_page_size: int = 16
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        if not self.attn_layers or not all(
                0 <= i < self.n_layers for i in self.attn_layers):
            raise ValueError(
                f"attn_layers {self.attn_layers} outside the "
                f"{self.n_layers} layers (the paged pool needs one)")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} outside the router's "
                f"{self.n_routed_experts}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into their groups")

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: what sizes it."""
        return len(self.attn_layers)

    @property
    def kda_layers(self) -> int:
        return self.n_layers - len(self.attn_layers)

    @property
    def kda_dim(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "SolarOpen2Config":
        """No training-only feature to clear."""
        return self

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str]):
        if kv_quant is not None:
            raise ValueError(
                "kv_quant: this model's paged pool is float (int8 pools "
                "are models/llama.py's)")
        return SolarOpen2(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program this model's kernels take: 256. At
        the Solar-Open2-250B widths (8 layers, 40 of 320 experts held) the
        engine's own ``prefill_step`` of 16 / 64 / 128 / 256 positions,
        continuing a prompt at position 2048, takes 9.4 / 14.9 / 19.8 /
        25.8 ms on a v5e chip (host clock around the dispatch, median of
        seven): 0.101 ms a position at 256 against 0.155 at 128. The
        experts' read is most of it up to 128 rows; at 256 their arithmetic
        (every row through every touched expert) is as long as the read
        (PERF.md section 6, PR 33)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the attention read's own."""
        mixer = () if not self.kda_layers else (
            kda.UPDATE_PATH if t == 1 else kda.SCAN_PATH,)
        return mixer + (gexp.PATH,)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower this model's own kernels for a TPU at the decode step's
        shapes (no device, no compile): refused here, not at the first
        request. With a pool named, the attention read over it too."""
        self.lower_read(
            slots=slots, kv_blocks=kv_blocks, page_size=page_size,
            pages_per_seq=pages_per_seq, kv_quant=kv_quant)
        if self.kda_layers:
            kda.lower_for_tpu(batch=slots, heads=self.kda_heads,
                              key_dim=self.kda_head_dim,
                              value_dim=self.kda_head_dim)
        gexp.lower_for_tpu(rows=slots, experts=self.n_held,
                           latent=self.d_model, width=self.expert_width,
                           dtype=self.dtype, gated=True)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "SolarOpen2Config":
        """Every mechanism at a size the CPU tests run: one period (an
        attention layer, three KDA layers of 8 heads), 16 routed experts of
        which 4 a token."""
        return SolarOpen2Config(
            vocab_size=vocab_size, d_model=64, n_layers=4, attn_layers=(0,),
            n_heads=4, n_kv_heads=2, head_dim=16, kda_heads=8,
            kda_head_dim=16, conv_kernel=4, gate_rank=8, chunk_size=8,
            n_routed_experts=16, experts_held=(0, 16), top_k=4,
            expert_width=32, shared_width=32, max_seq_len=128,
            dtype=jnp.float32, param_dtype=jnp.float32)


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


class KdaMixer(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, d, k, r = (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel,
                      cfg.gate_rank)
        hd = cfg.kda_dim
        f32 = jnp.float32

        # the convolution's inputs are kept a row (the conv state), in the
        # activations' dtype: round them before use, in prefill and decode
        with trace.part(trace.PROJ):
            qkv = dense(3 * hd, "qkv_proj", cfg)(u).astype(cfg.dtype)
        conv_w = self.param("conv_kernel", nn.initializers.normal(0.3),
                            (k, 3 * hd), f32)
        # float32 out of the accumulator: these steer an exponential
        with trace.part(trace.PROJ):
            decay_in = dense(hd, "decay_up", cfg, f32)(
                dense(r, "decay_down", cfg)(u))
        # alpha = exp(-exp(A_log) softplus(. + dt_bias)) starts with a step
        # log-uniform in [0.001, 0.1] and a rate uniform in [1, 16]
        dt_bias = self.param(
            "dt_bias", lambda key, shape: inv_softplus(jnp.exp(
                jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                   jnp.log(1e-1)))), (hd,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, f32, 1.0, 16.0)), (h,))
        with trace.part(trace.PROJ):
            beta = 2.0 * jax.nn.sigmoid(dense(h, "beta_proj", cfg, f32)(u))
            gate = dense(hd, "gate_up", cfg, f32)(
                dense(r, "gate_down", cfg)(u))

        cached = cfg.decode_paged
        if cached:
            conv_state = self.variable("cache", "conv", jnp.zeros,
                                       (b, k - 1, 3 * hd), cfg.dtype)
            kda_state = self.variable("cache", "kda", jnp.zeros,
                                      (b, h, d, d), f32)
            prev, state = conv_state.value, kda_state.value
        else:
            prev = jnp.zeros((b, k - 1, 3 * hd), cfg.dtype)
            state = jnp.zeros((b, h, d, d), f32)

        with trace.part(trace.STATE):
            real = row_mask(valid_len, b, t)                         # [B, T]
            seq = jnp.concatenate([prev, qkv], axis=1)             # [B, T+k-1]
            conv = jax.nn.silu(sum(conv_w[i] * seq[:, i:i + t].astype(f32)
                                   for i in range(k)))
            q, kk, v = (conv[..., i * hd:(i + 1) * hd].reshape(b, t, h, d)
                        for i in range(3))
            q = _l2_normalise(q) * d ** -0.5
            kk = _l2_normalise(kk)
            log_alpha = (-jnp.exp(a_log)[:, None] * jax.nn.softplus(
                decay_in + dt_bias).reshape(b, t, h, d))
            # a pad position and an idle slot: decay 1, no write
            log_alpha = jnp.where(real[:, :, None, None], log_alpha, 0.0)
            beta = jnp.where(real[:, :, None], beta, 0.0)

            if cached and t == 1:
                o, new_state = kda.kda_state_update(
                    state, q[:, 0], kk[:, 0], v[:, 0],
                    jnp.exp(log_alpha[:, 0]), beta[:, 0], real[:, 0])
                o = o[:, None]
            else:
                o, new_state = kda.kda_chunk_scan(
                    q, kk, v, log_alpha, beta, state, chunk=cfg.chunk_size)
            if cached and not self.is_initializing():
                kda_state.value = new_state
                # the window that ends at the last real position
                ends = jnp.full((b,), t, jnp.int32) if valid_len is None \
                    else valid_len.astype(jnp.int32)
                conv_state.value = jax.vmap(
                    lambda s, e: jax.lax.dynamic_slice_in_dim(s, e, k - 1, 0)
                )(seq, ends)

            # RMSNorm over each head's channels, with weight, then the gate
            norm_w = self.param("out_norm", nn.initializers.ones, (d,), f32)
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
            o = (o * norm_w).reshape(b, t, hd) * jax.nn.sigmoid(gate)
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "o_proj", cfg)(o.astype(cfg.dtype))


class SolarOpen2(nn.Module):
    cfg: SolarOpen2Config

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"k": "paged", "v": "paged", "index": "index",
                   "conv": "state", "kda": "state"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = STATS

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
            u = norm(f"layer_{i}_norm")(x)
            if i in cfg.attn_layers:
                y = PagedAttention(cfg, name=f"layer_{i}")(u, page_table)
            else:
                y = KdaMixer(cfg, name=f"layer_{i}")(u, valid_len)
            with trace.part(trace.PROJ):
                x = x + y
            with trace.part(trace.EXPERTS):
                x = x + GatedExperts(cfg, name=f"layer_{i}_moe")(
                    norm(f"layer_{i}_moe_norm")(x), valid_len)
        with trace.part(trace.HEAD):
            x = norm("final_norm")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: SolarOpen2Config, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(SolarOpen2(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

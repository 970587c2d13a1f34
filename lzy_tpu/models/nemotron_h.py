"""Nemotron-H: a hybrid decoder whose blocks are Mamba-2 mixers (``M``),
latent mixture-of-experts layers (``E``) and grouped-query attention
(``*``), in the order a pattern string gives
(``NVIDIA-Nemotron-3-Super-120B-A12B``: 88 blocks, 40 / 40 / 8). Every
block is ``x + mixer(RMSNorm(x))``.

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **cache leaves of three kinds** (:attr:`NemotronH.CACHE_KINDS`). The
  attention layers keep keys and values in the shared paged pool (``k``,
  ``v``: kind ``paged``, written and read through the page table exactly as
  ``models/llama.py`` does, by ``ops/paged_attention.py``), and an ``index``
  of tokens resident a row. A Mamba layer keeps **per-slot state**: ``conv``
  ``[slots, kernel - 1, channels]``, the last inputs of its causal
  convolution, and ``ssm`` ``[slots, heads, head_dim, state]`` in float32,
  the recurrence's state (kind ``state``). A state row belongs to one slot,
  cannot be shared through a page table and cannot be rewound.
- ``valid_len`` ``[B]``: how many of a row's ``T`` positions are real. A
  Mamba layer freezes its state past it (``dt`` = 0, the convolution's
  window taken at the last real position), so neither a padded prefill chunk
  nor an idle decode slot advances a recurrence; an expert layer leaves
  those rows out of its product and of its counts.
- **an expert layer that is told which experts it holds**
  (``experts_held``): the router keeps its published width and its experts
  per token, the layer computes the part of the result its own experts give
  (``ops/grouped_experts.py``: dropless), and a chosen expert held elsewhere
  adds nothing here. The shared expert is whole.
- **counts** a round carries out with its tokens (:data:`STATS`, the
  ``stats`` collection: one vector a layer, summed by the engine).

Departures from the published implementation, for serving: no rotary
embedding in attention (Nemotron-H's attention layers carry none);
``time_step_limit`` unbounded; the multi-token-prediction module is not
built (it drafts for speculative decoding and touches no next-token logit).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models.experts import (
    STATS, held_weights, row_mask, sigmoid_scores)
from lzy_tpu.models.llama import RMSNorm
from lzy_tpu.models.paged_blocks import (
    PagedAttention, dense, inv_softplus, normal)
from lzy_tpu.models.serving import HeadPool
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mamba2
from lzy_tpu.utils import trace


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(HeadPool):
    vocab_size: int = 131072
    d_model: int = 4096
    #: one character a block: M (Mamba-2), E (experts), * (attention)
    pattern: str = "MEMEMEM*EME"
    # attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    #: no output gate on the heads (``models/paged_blocks.py`` reads it)
    attn_gate: bool = False
    # Mamba-2
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # experts
    n_routed_experts: int = 512          # the router's width
    experts_held: Tuple[int, int] = (0, 512)   # [lo, hi) held here
    top_k: int = 22
    expert_width: int = 2688
    latent: int = 1024
    shared_width: int = 5376
    routed_scaling: float = 5.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: keys and values in a shared paged pool, state a slot
    decode_paged: bool = False
    kv_page_size: int = 16
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        if set(self.pattern) - set("ME*") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: blocks are M, E, *")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} outside the router's "
                f"{self.n_routed_experts}")
        if self.n_heads % self.n_kv_heads \
                or self.mamba_heads % self.n_groups:
            raise ValueError("heads must divide into their groups")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: what sizes it."""
        return self.pattern.count("*")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "NemotronHConfig":
        """No training-only feature to clear."""
        return self

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str], native: bool = True):
        if kv_quant is not None:
            raise ValueError(
                "kv_quant: this model's paged pool is float (int8 pools "
                "are models/llama.py's)")
        # tombstone: benchmark/models/nemotron_h.py passes ``native=True``;
        # the benchmark issue that takes it out there (ROADMAP B2) removes
        # the keyword here
        if not native:
            raise ValueError(
                "native=False: the gather read (the pool copied back into "
                "a dense [B, L, KV, D] layout) is gone; kernel='lax' reads "
                "through the page table")
        return NemotronH(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program this model's kernels take: 256.
        ``ops/grouped_experts.py``'s arithmetic grows with rows x touched
        experts while the read of the experts it amortises does not, and
        at the Nemotron-3-Super widths the read still hides most of it at
        256 rows: a program of 64 / 128 / 256 positions takes 12.7 / 14.1 /
        16.8 ms on a v5e chip (PERF.md section 6, PR 32)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the attention read's own."""
        paths = []
        if "M" in self.pattern:
            paths.append(mamba2.UPDATE_PATH if t == 1 else mamba2.SCAN_PATH)
        if "E" in self.pattern:
            paths.append(gexp.PATH)
        return tuple(paths)

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
        if "M" in self.pattern:
            mamba2.lower_update_for_tpu(
                batch=slots, heads=self.mamba_heads,
                head_dim=self.mamba_head_dim, state_size=self.ssm_state,
                groups=self.n_groups)
        if "E" in self.pattern:
            gexp.lower_for_tpu(rows=slots, experts=self.n_held,
                               latent=self.latent, width=self.expert_width,
                               dtype=self.dtype)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "NemotronHConfig":
        """Every mechanism at a size the CPU tests run: two groups of eight
        Mamba heads, 16 routed experts of which 4 a token."""
        return NemotronHConfig(
            vocab_size=vocab_size, d_model=64, pattern="ME*EM",
            n_heads=4, n_kv_heads=2, head_dim=16, mamba_heads=16,
            mamba_head_dim=8, ssm_state=128, n_groups=2, conv_kernel=4,
            chunk_size=16, n_routed_experts=16, experts_held=(0, 16),
            top_k=4, expert_width=128, latent=32, shared_width=128,
            max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32)


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, p, n, g = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
                      cfg.n_groups)
        di, cd, k = cfg.d_inner, cfg.conv_dim, cfg.conv_kernel
        f32 = jnp.float32

        # [z, xBC, dt] in one projection; float32 out of the accumulator:
        # dt steers an exponential
        with trace.part(trace.PROJ):
            zxbcdt = dense(di + cd + h, "in_proj", cfg, f32)(u)
            z = zxbcdt[..., :di].astype(f32)
            # the convolution's inputs are kept a row (the conv state), in
            # the activations' dtype: round them before use, in prefill and
            # decode
            xbc = zxbcdt[..., di:di + cd].astype(cfg.dtype)
            dt_raw = zxbcdt[..., di + cd:].astype(f32)

        conv_w = self.param("conv_kernel", nn.initializers.normal(0.3),
                            (k, cd), f32)
        conv_b = self.param("conv_bias", nn.initializers.normal(0.1),
                            (cd,), f32)
        # dt = softplus(dt_raw + dt_bias) starts log-uniform in
        # [time_step_min, time_step_max] = [0.001, 0.1]
        dt_bias = self.param(
            "dt_bias", lambda key, shape: inv_softplus(jnp.exp(
                jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                   jnp.log(1e-1)))), (h,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, f32, 1.0, 16.0)), (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,), f32)

        cached = cfg.decode_paged
        if cached:
            conv_state = self.variable("cache", "conv", jnp.zeros,
                                       (b, k - 1, cd), cfg.dtype)
            ssm_state = self.variable("cache", "ssm", jnp.zeros,
                                      (b, h, p, n), f32)
            prev, state = conv_state.value, ssm_state.value
        else:
            prev = jnp.zeros((b, k - 1, cd), cfg.dtype)
            state = jnp.zeros((b, h, p, n), f32)

        with trace.part(trace.STATE):
            real = row_mask(valid_len, b, t)                         # [B, T]
            seq = jnp.concatenate([prev, xbc], axis=1)             # [B, T+k-1]
            conv = conv_b + sum(conv_w[i] * seq[:, i:i + t].astype(f32)
                                for i in range(k))
            xbc_act = jax.nn.silu(conv)
            x = xbc_act[..., :di].reshape(b, t, h, p)
            bm = xbc_act[..., di:di + g * n].reshape(b, t, g, n)
            cm = xbc_act[..., di + g * n:].reshape(b, t, g, n)
            dt = jnp.where(real[..., None],
                           jax.nn.softplus(dt_raw + dt_bias), 0.0)  # [B, T, H]
            a = -jnp.exp(a_log)

            if cached and t == 1:
                y, new_state = mamba2.ssm_state_update(
                    state, x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
                y = y[:, None]
            else:
                y, new_state = mamba2.ssd_chunk_scan(
                    x, dt, a, bm, cm, state, chunk=cfg.chunk_size)
            if cached and not self.is_initializing():
                ssm_state.value = new_state
                # the window that ends at the last real position
                ends = jnp.full((b,), t, jnp.int32) if valid_len is None \
                    else valid_len.astype(jnp.int32)
                conv_state.value = jax.vmap(
                    lambda s, e: jax.lax.dynamic_slice_in_dim(s, e, k - 1, 0)
                )(seq, ends)

            y = y + d_skip[:, None] * x
            y = y.reshape(b, t, di) * jax.nn.silu(z)
            # RMSNorm over each group's channels, with weight
            gate_w = self.param("gate_norm", nn.initializers.ones, (di,), f32)
            yg = y.reshape(b, t, g, di // g)
            yg = yg * jax.lax.rsqrt(
                jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                + cfg.norm_eps)
            y = (yg.reshape(b, t, di) * gate_w).astype(cfg.dtype)
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "out_proj", cfg)(y)


class LatentExperts(nn.Module):
    """Sigmoid router over all the routed experts, the held experts'
    product in a latent space, a shared expert at hidden width."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, dm = u.shape
        m = b * t
        f32 = jnp.float32
        um = u.reshape(m, dm)
        with trace.part(trace.ROUTER):
            real = row_mask(valid_len, b, t).reshape(m)
            scores, bias = sigmoid_scores(self, um, cfg.n_routed_experts)
            weights = held_weights(
                self, scores, real, top_k=cfg.top_k, held=cfg.experts_held,
                bias=bias, scaling=cfg.routed_scaling)
        with trace.part(trace.EXPERTS):
            v = dense(cfg.latent, "latent_down", cfg)(um)
        w1 = self.param("experts_w1", nn.initializers.normal(0.02),
                        (cfg.n_held, cfg.latent, cfg.expert_width),
                        cfg.param_dtype)
        w2 = self.param("experts_w2", normal(),
                        (cfg.n_held, cfg.expert_width, cfg.latent),
                        cfg.param_dtype)
        with trace.part(trace.EXPERTS):
            if self.is_initializing():
                routed = jnp.zeros((m, cfg.latent), f32)  # no kernel at init
            else:
                routed = gexp.grouped_experts(v, w1.astype(cfg.dtype),
                                              w2.astype(cfg.dtype), weights)
            out = dense(dm, "latent_up", cfg)(routed.astype(cfg.dtype))
            hid = dense(cfg.shared_width, "shared_w1", cfg)(um)
            hid = jnp.square(jax.nn.relu(hid.astype(f32))).astype(cfg.dtype)
            out = out + dense(dm, "shared_w2", cfg)(hid)
            return out.reshape(b, t, dm)


class NemotronH(nn.Module):
    cfg: NemotronHConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"k": "paged", "v": "paged", "index": "index",
                   "conv": "state", "ssm": "state"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = STATS

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None):
        cfg = self.cfg
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens]
        for i, kind in enumerate(cfg.pattern):
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_norm")(x)
            if kind == "M":
                y = Mamba2Mixer(cfg, name=f"layer_{i}")(u, valid_len)
            elif kind == "E":
                y = LatentExperts(cfg, name=f"layer_{i}")(u, valid_len)
            else:
                y = PagedAttention(cfg, name=f"layer_{i}")(u, page_table)
            # a residual sum is filed with the block it closes
            with trace.part(trace.EXPERTS if kind == "E" else trace.PROJ):
                x = x + y
        with trace.part(trace.HEAD):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: NemotronHConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(NemotronH(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

"""The language model of LongCat-Flash (``LongCat-Flash-Omni``: 28 layers at
6144, 64 heads): **shortcut-connected** layers. A layer holds two latent
attentions, two dense feed-forward parts, four block norms and one expert
layer whose result leaves the residual path at the first sublayer and
rejoins it three sublayers later. With ``N`` an RMSNorm:

    for i in (0, 1):
        a = h + MLA_i(N_in,i(h))
        u = N_post,i(a)
        if i == 0:  s = MoE(u)            # the shortcut
        h = a + FFN_i(u)
    h = h + s

- ``MLA(x)``: a low-rank query with its own norm, ``c_q = a_q N_q(x W_qa)``,
  ``q_h = W_qb,h c_q = [q_nope ; q_rope]``; ``[c' ; k'] = x W_kva``,
  ``c = a_kv N_kv(c')``, ``k_rope = rope(k')``, one rotary key for all heads
  that passes through no norm and is not scaled. **The two scale
  corrections** (``mla_scale_q_lora`` / ``mla_scale_kv_lora``) are
  ``a = sqrt(hidden / rank)`` on the normed latents (2 and 3.4641 at the
  published widths), **applied before ``c`` is cached**, so the absorbed
  read (``ops/mla.py``) is the DeepSeek family's as it is.
- ``FFN``: a SwiGLU MLP of ``dense_width``.
- ``MoE(u)``: ``p = softmax(u W_r)`` over ``n_routed_experts`` outputs in
  float32 at the highest precision: **the experts with weights first, then
  ``zero_experts`` identity ("zero-compute") experts**. The ``top_k`` largest
  of ``p + bias`` are chosen (the bias steers the choice only); a chosen
  output's weight is ``routed_scaling x p``, **not renormalised**;
  ``MoE(u) = sum over chosen experts of w_e E_e(u) + (sum over chosen zero
  experts of w_e) u``. No shared expert: the dense parts play it.

What a serving engine has to know, and reads from here without naming the
model (``models/serving.py``):

- **two latent leaves a layer** (:attr:`LongcatFlash.CACHE_KINDS`: ``latent``,
  kind ``paged``, and the ``index``; the two attentions' are told apart by
  their modules, ``layer_<i>_attn_0`` and ``layer_<i>_attn_1``): ``kv_layers``
  is twice the layers, by two attentions of different weights a layer (where
  ``models/ouro.py`` gets there by passes). Each is ``[pages, page, 640]``
  (576 values), 1,280 bytes a token, under the row's one page table.
- **no state leaf**: the radix cache, parking, speculation's rewind, KV
  export / import and the tiers work over the leaves; ``kv_quant`` is
  refused by name.
- **an expert layer that is told which experts it holds**, whose router is
  wider than the experts that have weights. The choice, the held experts'
  weights and the experts' four counts are ``experts.held_weights``'s; **the
  identity term belongs to no chip's share of the experts**: the chip a row
  lives on computes it, so here it is computed for every real row, whole, as
  a shared expert is. A row reaches 0 to ``top_k`` experts with weights, so
  the work a row asks of the expert product varies.
- **counts** (:attr:`LongcatFlash.STATS`): the experts' four, the latent
  reads' two (a row at ``p`` reads ``p + 1``, an attention), and an expert
  layer's choices that fell on a zero expert, their weight and the weight of
  all the choices, in thousandths.

Read from the published config where it gives a flag or nothing (the
benchmark's configuration file lists each under ``assumed``): the sublayers'
order and where the shortcut leaves and rejoins; the scale corrections'
form; no renormalisation; the softmax over the whole router and the bias on
the choice only; rotary pairing ``i`` with ``i + d/2`` (``models/llama.py``
``_rope``); softmax scale ``(d_nope + d_rope)^-1/2`` with no ``mscale``;
untied embeddings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models import experts
from lzy_tpu.models.deepseek_v3 import (
    MLA_CONTEXT_TOKENS, MLA_ROWS, GatedMlp, LatentPoolUnsupported)
from lzy_tpu.models.experts import row_mask
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.paged_blocks import dense, into_heads, normal
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mla
from lzy_tpu.ops.paged_attention import paged_scatter_index
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

MOE_ZERO_ASSIGNMENTS = REGISTRY.counter(
    "lzy_moe_zero_assignments_total",
    "of lzy_moe_assignments_total, those that fell on an identity "
    "(zero-compute) expert")
MOE_ZERO_WEIGHT_MILLI = REGISTRY.counter(
    "lzy_moe_zero_weight_milli_total",
    "1000 x the routing weight the real rows of decode rounds gave identity "
    "experts, a layer")
MOE_WEIGHT_MILLI = REGISTRY.counter(
    "lzy_moe_weight_milli_total",
    "1000 x the routing weight of all the choices of the real rows of decode "
    "rounds, a layer")

_LANES = 128
#: query rows (positions x heads) a grid cell of ``ops/mla.py``'s prefill
#: read may hold: its tile of 64 positions x 20 heads compiles for a v5e
#: core's VMEM, x 40 does not (``models/motif.py`` found the limit; the
#: kernel was sized for 16 heads)
_PREFILL_CELL_ROWS = 1280
#: places of the ``stats`` vector: the experts' four, the reads' two, then
#: the identity experts' three
_MLA_AT = len(experts.STATS)
_ZERO_AT = _MLA_AT + 2
_N_STATS = _ZERO_AT + 3


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    d_model: int = 6144
    n_layers: int = 28
    n_heads: int = 64
    # latent attention, twice a layer
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e7
    # the dense feed-forward parts, twice a layer
    dense_width: int = 12288
    # experts: the router's outputs are the experts with weights, then the
    # identity experts
    n_routed_experts: int = 768              # the router's width
    zero_experts: int = 256
    experts_held: Tuple[int, int] = (0, 512)     # [lo, hi) held here
    top_k: int = 12
    expert_width: int = 2048
    routed_scaling: float = 6.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: two latent vectors a token a layer in a shared paged pool
    decode_paged: bool = False
    kv_page_size: int = 16
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        lo, hi = self.experts_held
        if not 0 <= self.zero_experts < self.n_routed_experts:
            raise ValueError("zero_experts outside the router")
        if not 0 <= lo < hi <= self.n_weighted:
            raise ValueError(
                f"experts_held {self.experts_held} outside the "
                f"{self.n_weighted} experts with weights of a router "
                f"{self.n_routed_experts} wide")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary width must be even")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "LongcatFlashConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``router_width`` (the
        published ``n_routed_experts + zero_expert_num``) and
        ``experts_held_from`` (a deployment's, not published) say which of
        the router's experts with weights are held here:
        ``n_routed_experts`` of them."""
        refused = {
            "attention_method": ("MLA",), "zero_expert_type": ("identity",),
            "mla_scale_q_lora": (True,), "mla_scale_kv_lora": (True,),
            "attention_bias": (False, None), "rope_scaling": (None,),
            "tie_word_embeddings": (False, None),
        }
        for key, served in refused.items():
            if doc.get(key) not in served:
                raise ValueError(
                    f"LongcatFlashConfig serves {key} in {served!r} (latent "
                    f"attention with both scale corrections and no bias, "
                    f"identity zero experts, plain rotary, untied "
                    f"embeddings); the configuration says "
                    f"{key} = {doc.get(key)!r}")
        if doc.get("q_lora_rank") is None:
            raise ValueError(
                "LongcatFlashConfig serves a low-rank query: q_lora_rank "
                "is None (models/deepseek_v3.py serves the direct one)")
        zero = doc["zero_expert_num"]
        width = doc.get("router_width", doc["n_routed_experts"] + zero)
        lo = doc.get("experts_held_from", 0)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=doc["num_layers"], n_heads=doc["num_attention_heads"],
            q_lora_rank=doc["q_lora_rank"], kv_lora_rank=doc["kv_lora_rank"],
            qk_nope_head_dim=doc["qk_nope_head_dim"],
            qk_rope_head_dim=doc["qk_rope_head_dim"],
            v_head_dim=doc["v_head_dim"],
            rope_theta=float(doc["rope_theta"]),
            dense_width=doc["ffn_hidden_size"],
            n_routed_experts=width, zero_experts=zero,
            experts_held=(lo, lo + doc["n_routed_experts"]),
            top_k=doc["moe_topk"],
            expert_width=doc["expert_ffn_hidden_size"],
            routed_scaling=float(doc["routed_scaling_factor"]),
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def n_weighted(self) -> int:
        """The router's outputs that are experts with weights."""
        return self.n_routed_experts - self.zero_experts

    @property
    def kv_layers(self) -> int:
        """Entries a token keeps in the paged pool: two attentions a layer."""
        return 2 * self.n_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layers

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_values(self) -> int:
        """What a token caches an attention: ``c`` and the shared rotary
        key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The cached vector as the pool lays it out: whole tiles of 128
        lanes (576 values in 640)."""
        return -(-self.latent_values // _LANES) * _LANES

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def prefill_read_heads(self) -> int:
        """Heads one call of ``ops/mla.py``'s prefill read takes: the most
        that divide the heads and keep a tile's q rows within
        ``_PREFILL_CELL_ROWS`` (16 of 64: four calls an attention)."""
        most = max(1, _PREFILL_CELL_ROWS // mla._PREFILL_TILE)
        return max(h for h in range(1, self.n_heads + 1)
                   if self.n_heads % h == 0 and h <= most)

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "LongcatFlashConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise LatentPoolUnsupported(
                f"kv_quant={kv_quant!r}: int8 pools quantise keys and values "
                f"a head (ops/paged_attention.py quantize_kv); this model's "
                f"pool is two latent vectors a token a layer with no head "
                f"axis, kept in {jnp.dtype(self.dtype).name}")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str]):
        self._refuse_quant(kv_quant)
        return LongcatFlash(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one attention's leaf: the latent
        vector as the pool lays it out (1280 at the published widths in
        bfloat16, of which 1152 are values)."""
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
        the benchmark's cut (4 layers, 16 of 512 experts held, all of them
        touched) the four-layer program of 64 / 128 / 256 positions behind
        a prefix of 1,024 takes 10.23 / 12.06 / 15.34 ms of device time on a
        v5e chip (``tools/longcat_bench.py chunk``; PERF.md section 6, PR
        67): 0.160 / 0.094 / 0.060 ms a position. A program of 64 is already
        the read of every weight (10.2 GB); what 256 adds is arithmetic, the
        expert kernel's and the latent read's."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the latent read's own."""
        return (gexp.PATH,)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower this model's kernels for a TPU (no device, no compile):
        the expert product at the decode step's rows and at the widest
        prefill chunk's and, with a pool named, both latent reads over it
        (every head in a decode round, ``prefill_read_heads`` a call in a
        chunk). Refused here, not at the first request."""
        self._refuse_quant(kv_quant)
        if kv_blocks is not None:
            for batch, t, heads in ((slots, 1, self.n_heads),
                                    (1, self.widest_prefill,
                                     self.prefill_read_heads)):
                mla.lower_for_tpu(
                    batch=batch, t=t, heads=heads,
                    width=self.latent_width, value_dim=self.kv_lora_rank,
                    n_blocks=kv_blocks, page_size=page_size,
                    pages_per_seq=pages_per_seq, dtype=self.dtype)
        for rows in (slots, self.widest_prefill):
            gexp.lower_for_tpu(rows=rows, experts=self.n_held,
                               latent=self.d_model, width=self.expert_width,
                               dtype=self.dtype, gated=True)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LongcatFlashConfig":
        """Every mechanism at a size the CPU tests run: two layers (four
        attentions), 4 heads over latents of 24 (query) and 32 + 8, 16
        experts with weights of which 4 held and 8 identity experts, 5 a
        token, an expert width of three tiles."""
        return LongcatFlashConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
            dense_width=128, n_routed_experts=24, zero_experts=8,
            experts_held=(0, 4), top_k=5, expert_width=384,
            max_seq_len=128, kv_page_size=4, dtype=jnp.float32,
            param_dtype=jnp.float32)


class LatentAttention(nn.Module):
    """Multi-head latent attention with a low-rank query and both scale
    corrections, absorbed: the cached vector is scored by every head and
    summed by every head, ``W_kvb`` on either side."""
    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        w = cfg.latent_width
        f32 = jnp.float32

        def latent(x, name, rank):
            """A low-rank projection's float32 result normed, scaled back to
            a full-width activation's variance and rounded once."""
            y = RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)(x)
            return (y * (cfg.d_model / rank) ** 0.5).astype(cfg.dtype)

        with trace.part(trace.PROJ):
            c_q = latent(dense(cfg.q_lora_rank, "q_a_proj", cfg, f32)(u),
                         "q_a_norm", cfg.q_lora_rank)
            q = into_heads(dense(h * (dn + dr), "q_b_proj", cfg)(c_q),
                           b, t, h, dn + dr)
            kva = dense(r + dr, "kv_a_proj", cfg, f32)(u)
            c = latent(kva[..., :r], "kv_a_norm", r)
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
                               preferred_element_type=f32)
            pad = w - r - dr
            q_full = jnp.concatenate(
                [q_abs.astype(cfg.dtype), q_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, h, pad), cfg.dtype)], axis=-1)
            lat = jnp.concatenate(
                [c, k_rope.astype(cfg.dtype),
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
            live = jnp.where(real[:, 0], start, -1)
            # ``ops/mla.py``'s read as it is: a decode program's heads in
            # one call, a prefill chunk's ``prefill_read_heads`` a call (the
            # kernel's tile of 64 positions x 64 heads does not fit a core's
            # VMEM)
            with trace.part(trace.ATTN_READ):
                step = h if t <= mla.MAX_DECODE_TOKENS \
                    or cfg.paged_kernel != "pallas" else cfg.prefill_read_heads
                summed = jnp.concatenate([
                    mla.mla_attention(
                        q_full[:, :, at:at + step], pool.value, page_table,
                        live, value_dim=r, scale=cfg.softmax_scale,
                        kernel=cfg.paged_kernel)
                    for at in range(0, h, step)], axis=2)
                # the last real query of a row at position p reads p + 1
                last = pos[:, 0] + jnp.sum(real, axis=1)
                self.sow("stats", "mla", jnp.zeros((_N_STATS,), jnp.int32).at[
                    _MLA_AT:_ZERO_AT].set(jnp.stack([
                        jnp.sum(jnp.where(real[:, 0], last, 0)),
                        jnp.sum(real[:, 0])]).astype(jnp.int32)),
                    reduce_fn=lambda a, x: a + x,
                    init_fn=lambda: jnp.zeros((_N_STATS,), jnp.int32))
        with trace.part(trace.PROJ):
            out = jnp.einsum("bthr,rhv->bthv", summed.astype(cfg.dtype),
                             w_kvb[..., dn:], preferred_element_type=f32)
            return dense(cfg.d_model, "o_proj", cfg)(
                out.astype(cfg.dtype).reshape(b, t, h * dv))


def softmax_scores(layer: nn.Module, um, n_routed: int):
    """``([M, n_routed] float32 softmax over the whole router, the choice's
    correction bias)``: float32 at the highest precision (a near-tie among
    the scores decides which expert a row reaches). Called from the expert
    layer's compact method: the parameters are the layer's own."""
    f32 = jnp.float32
    wr = layer.param("router", nn.initializers.normal(0.02),
                     (um.shape[-1], n_routed), f32)
    scores = jax.nn.softmax(jnp.dot(
        um.astype(f32), wr, precision=jax.lax.Precision.HIGHEST), axis=-1)
    # a softmax's scores over n outputs are of order 1 / n (the twelfth
    # largest of 768 under this router's initial logits: 0.011), so a bias of
    # the sigmoid routers' normal(0.02) would make every row's choice the
    # bias's own (on the chip: 17% of the held experts touched where
    # uniform routing touches 70%; PERF.md section 6, PR 67): it is drawn
    # an order under the chosen scores, where it settles near-ties alone
    bias = layer.param("router_bias",
                       nn.initializers.normal(0.02 * n_routed ** -0.5),
                       (n_routed,), f32)
    return scores, bias


def zero_weight(scores, bias, real, cfg: LongcatFlashConfig):
    """``([M] float32, [3] int32)``: each real row's summed weight for the
    identity experts it chose (0 for a pad or an idle slot), and the layer's
    three counts: choices that fell on an identity expert, 1000 x their
    weight, 1000 x the weight of all the choices. The choice is
    ``experts.held_weights``'s own (the same ``top_k`` of the same operand:
    one program computes it once)."""
    _, chosen = jax.lax.top_k(scores + bias, cfg.top_k)            # [M, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1) \
        * cfg.routed_scaling
    on_zero = (chosen >= cfg.n_weighted) & real[:, None]
    z = jnp.sum(jnp.where(on_zero, picked, 0.0), axis=-1)
    every = jnp.sum(jnp.where(real[:, None], picked, 0.0))
    counts = jnp.stack([
        jnp.sum(on_zero).astype(jnp.float32),
        jnp.round(1000.0 * jnp.sum(z)),
        jnp.round(1000.0 * every)]).astype(jnp.int32)
    return z, counts


class ShortcutExperts(nn.Module):
    """The expert layer of a shortcut block: a softmax router over the
    experts with weights and the identity experts, the held experts' product
    at hidden width, and the identity term ``z u`` for every real row."""
    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, dm = u.shape
        m = b * t
        f32 = jnp.float32
        um = u.reshape(m, dm)
        with trace.part(trace.ROUTER):
            real = row_mask(valid_len, b, t).reshape(m)
            scores, bias = softmax_scores(self, um, cfg.n_routed_experts)
            weights = experts.held_weights(
                self, scores, real, top_k=cfg.top_k, held=cfg.experts_held,
                bias=bias, renormalise=False, scaling=cfg.routed_scaling,
                other_stats=_N_STATS - len(experts.STATS))
            z, counts = zero_weight(scores, bias, real, cfg)
            self.sow("stats", "zero", jnp.zeros((_N_STATS,), jnp.int32).at[
                _ZERO_AT:].set(counts),
                reduce_fn=lambda a, c: a + c,
                init_fn=lambda: jnp.zeros((_N_STATS,), jnp.int32))
        up_shape = (cfg.n_held, dm, cfg.expert_width)
        wg = self.param("experts_gate", normal(), up_shape,
                        cfg.param_dtype)
        wu = self.param("experts_up", normal(), up_shape,
                        cfg.param_dtype)
        wd = self.param("experts_down", normal(),
                        (cfg.n_held, cfg.expert_width, dm),
                        cfg.param_dtype)
        with trace.part(trace.EXPERTS):
            if self.is_initializing():
                routed = jnp.zeros((m, dm), f32)        # no kernel at init
            else:
                routed = gexp.grouped_experts(
                    um, wu.astype(cfg.dtype), wd.astype(cfg.dtype), weights,
                    gate=wg.astype(cfg.dtype))
            out = routed + z[:, None] * um.astype(f32)
            return out.astype(cfg.dtype).reshape(b, t, dm)


class LongcatFlash(nn.Module):
    cfg: LongcatFlashConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"latent": "paged", "index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = experts.STATS + (MLA_CONTEXT_TOKENS, MLA_ROWS) + (
        MOE_ZERO_ASSIGNMENTS, MOE_ZERO_WEIGHT_MILLI, MOE_WEIGHT_MILLI)

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
            for j in (0, 1):
                y = LatentAttention(cfg, name=f"layer_{i}_attn_{j}")(
                    norm(f"layer_{i}_norm_{j}")(x), page_table, valid_len)
                # a residual sum is filed with the block it closes
                with trace.part(trace.PROJ):
                    x = x + y
                u = norm(f"layer_{i}_ffn_norm_{j}")(x)
                if j == 0:
                    # the shortcut: computed here, added three sublayers on
                    s = ShortcutExperts(cfg, name=f"layer_{i}_moe")(
                        u, valid_len)
                with trace.part(trace.FFN):
                    x = x + GatedMlp(cfg, name=f"layer_{i}_mlp_{j}")(u)
            with trace.part(trace.EXPERTS):
                x = x + s
        with trace.part(trace.HEAD):
            x = norm("final_norm")(x)
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: LongcatFlashConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(LongcatFlash(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

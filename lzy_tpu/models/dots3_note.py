"""The language model of ``dots3-note-prev`` (``model_type`` ``dots3_note``;
46 layers at 5120): latent attention of **two kinds**, a learned indexer on
the full layers, a gate a head, and after a first dense layer a mixture of
gated experts with one shared expert. Every block is ``x + attn(RMSNorm(x))``
then ``x + ffn(RMSNorm(x))``. With ``u = RMSNorm(x)``:

- **both kinds** make a low-rank query, ``c_q = a_q * RMSNorm(W_qa u)``,
  ``q_h = W_qb,h c_q = [q_nope ; q_rope]``, and cache, a token, ``[c ;
  k_rope]`` with ``[c' ; k'] = W_kva u``, ``c = a_kv * RMSNorm(c')``,
  ``k_rope = rope(k')``: one rotary key for all heads. The rescale
  (``apply_mla_qkv_lora_rescale``) is ``a = sqrt(hidden / rank)`` on the
  normed latents, **applied before ``c`` is cached**. The read is the
  absorbed form (``ops/mla.py``): ``W_kvb``'s key half folded into the
  query, its value half applied after the sum. The heads' outputs are
  multiplied by ``sigmoid(W_g u)``, one number a head, before ``W_o``.
- **full layers** (128 heads, ``c`` of 512, theta 8e7) keep a second cached
  vector a token, the indexer's key ``k^I = rope_64(LayerNorm(W_kI u))``
  (128 values), and read only the ``index_topk`` (2,048) cached tokens of
  largest ``I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s))``, with ``q^I_j
  = rope_64(W_qI,j c_q)`` over 64 heads and ``w = W_w u`` in float32
  (``ops/latent_select.py``: the index, the exact choice, the read of the
  chosen). A query that sees ``index_topk`` positions or fewer reads them
  all.
- **sliding layers** (64 heads, ``c`` of 1024, nope width 192, theta 5e4)
  read the ``window`` (513) newest positions: itself and the 512 before.

What a serving engine has to know, and reads from here without naming the
model (``models/serving.py``):

- **three pool leaves, two lifetimes, two prices.** A full layer keeps
  ``latent`` ``[pages, page, 640]`` (576 values) and ``ik`` ``[pages, page,
  128]`` under ONE page table (kind ``paged``: ``kv_token_bytes`` answers
  both, 1,536 bytes); a sliding layer keeps ``wlatent`` ``[pages, page,
  1152]`` (1,088 values in nine tiles of 128 lanes) in the ``window`` pool,
  whose pages go back behind the window (``window_token_bytes``: 2,304).
  The window kind's vector is the **wider** of the two: the protocol's one
  price a layer of either kind did not fit.
- **no state leaf, but window leaves**: the radix cache is off and the
  mechanisms that move pages by tokens refuse the model by name, as for
  ``models/cohere2_moe.py``; ``kv_quant`` is refused here.
- **an expert layer that is told which experts it holds**
  (``models/experts.py`` :class:`GatedExperts`, as it is): sigmoid scores
  over ``n_routed_experts`` in float32, the 8 largest of ``scores + bias``,
  renormalised, times ``routed_scaling`` (1).
- **counts** a round carries out with its tokens (:attr:`Dots3Note.STATS`):
  the experts' four, and what the selecting layers' real decode rows could
  have read, chose and read, how many selected and how many read
  everything, and what the window layers read.

Read from the published config where it gives only a flag (the benchmark's
configuration file lists each under ``assumed``): the rescale's form; the
indexer's form (DeepSeek-V3.2's, without its Hadamard rotation, an
orthogonal map on both sides of a dot product, and without its constant
scales, which move no top-k; bfloat16 where the published kernels use fp8);
rotary pairing ``i`` with ``i + d/2`` (``models/llama.py`` ``_rope``);
softmax scales ``(d_nope + d_rope)^-1/2`` with no ``mscale``; the gate's
input (``u``) and place; no bias anywhere but the indexer's LayerNorm.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models import experts
from lzy_tpu.models.deepseek_v3 import GatedMlp
from lzy_tpu.models.experts import GatedExperts, row_mask
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.paged_blocks import dense, into_heads, normal
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import latent_select as lsel
from lzy_tpu.ops.paged_attention import paged_scatter_index
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

LATENT_VISIBLE = REGISTRY.counter(
    "lzy_latent_visible_tokens_total",
    "cached positions the real rows of decode rounds could have read in "
    "layers that select (a row at position p sees p + 1), a layer")
LATENT_CHOSEN = REGISTRY.counter(
    "lzy_latent_chosen_tokens_total",
    "cached positions those rows chose and read (min(p + 1, index_topk)), "
    "a layer")
LATENT_SELECT_ROWS = REGISTRY.counter(
    "lzy_latent_select_rows_total",
    "real rows of decode rounds past index_topk positions, whose indexer "
    "scored their context, a layer")
LATENT_DENSE_ROWS = REGISTRY.counter(
    "lzy_latent_dense_rows_total",
    "real rows of decode rounds at index_topk positions or fewer, which "
    "read everything, a layer")
LATENT_WINDOW_TOKENS = REGISTRY.counter(
    "lzy_latent_window_tokens_total",
    "cached positions the real rows of decode rounds read in window layers "
    "(a row at position p reads min(p + 1, window)), a layer")
LATENT_ROWS = REGISTRY.counter(
    "lzy_latent_rows_total",
    "real rows of decode rounds that read a selecting layer, a layer")

SLIDING, FULL = "sliding_attention", "full_attention"
_LANES = 128
_OWN_STATS = 6


class LatentWindowUnsupported(ValueError):
    """A mechanism that latent pools of two lifetimes cannot serve, by
    name."""


def _tiles(values: int) -> int:
    """``values`` as the pool lays them out: whole tiles of 128 lanes."""
    return -(-values // _LANES) * _LANES


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    d_model: int = 5120
    n_layers: int = 46
    #: each layer's attention, ``full_attention`` or ``sliding_attention``
    layer_types: Tuple[str, ...] = (FULL, FULL) + (
        SLIDING, SLIDING, SLIDING, FULL) * 11
    # full layers: latent attention over the tokens an indexer picks
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding layers: latent attention of their own ranks and head count
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    window: int = 513
    #: ``apply_mla_qkv_lora_rescale``: the normed latents times
    #: ``sqrt(d_model / rank)``
    lora_rescale: bool = True
    # the first layers' dense MLP
    first_dense: int = 1
    dense_width: int = 13824
    # experts
    n_routed_experts: int = 256          # the router's width
    experts_held: Tuple[int, int] = (0, 256)   # [lo, hi) held here
    top_k: int = 8
    expert_width: int = 1536
    shared_width: int = 1536             # n_shared_experts x expert_width
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 524288
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: latent vectors in two shared paged pools
    decode_paged: bool = False
    kv_page_size: int = 64
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
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError("first_dense outside the layers")
        if self.qk_rope_head_dim % 2 or self.swa_qk_rope_head_dim % 2 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the rotary widths must be even, and the "
                             "indexer's within its head")
        if self.window < 1 or self.index_topk < 1:
            raise ValueError("window and index_topk must be >= 1")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "Dots3NoteConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``router_width`` and
        ``experts_held_from`` (a deployment's, not published) say which of
        the router's experts are held here."""
        refused = {
            "rope_scaling": (None,), "scoring_func": ("sigmoid",),
            "topk_method": ("noaux_tc",), "norm_topk_prob": (True,),
            "tie_word_embeddings": (False, None),
            "attention_bias": (False, None), "hidden_act": ("silu", None),
            "attention_gate_type": ("headwise",),
            "swa_attention_gate_type": ("headwise",),
            "moe_layer_freq": (1, None),
        }
        for key, served in refused.items():
            if doc.get(key) not in served:
                raise ValueError(
                    f"Dots3NoteConfig serves {key} in {served!r} (plain "
                    f"rotary, sigmoid scores renormalised, untied "
                    f"embeddings, no bias, a gate a head, experts in every "
                    f"layer after the dense ones); the configuration says "
                    f"{key} = {doc.get(key)!r}")
        kinds = tuple(doc["layer_types"])
        if not set(kinds) <= {SLIDING, FULL}:
            raise ValueError(
                f"Dots3NoteConfig serves layer_types of {FULL!r} and "
                f"{SLIDING!r}; the configuration says {sorted(set(kinds))}")
        width = doc.get("router_width", doc["n_routed_experts"])
        lo = doc.get("experts_held_from", 0)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=doc["num_hidden_layers"], layer_types=kinds,
            n_heads=doc["num_attention_heads"],
            q_lora_rank=doc["q_lora_rank"],
            kv_lora_rank=doc["kv_lora_rank"],
            qk_nope_head_dim=doc["qk_nope_head_dim"],
            qk_rope_head_dim=doc["qk_rope_head_dim"],
            v_head_dim=doc["v_head_dim"],
            rope_theta=float(doc["rope_theta"]),
            index_n_heads=doc["index_n_heads"],
            index_head_dim=doc["index_head_dim"],
            index_topk=doc["index_topk"],
            swa_n_heads=doc["swa_num_attention_heads"],
            swa_q_lora_rank=doc["swa_q_lora_rank"],
            swa_kv_lora_rank=doc["swa_kv_lora_rank"],
            swa_qk_nope_head_dim=doc["swa_qk_nope_head_dim"],
            swa_qk_rope_head_dim=doc["swa_qk_rope_head_dim"],
            swa_v_head_dim=doc["swa_v_head_dim"],
            swa_rope_theta=float(doc["swa_rope_theta"]),
            window=doc["sliding_window_size"],
            lora_rescale=bool(doc["apply_mla_qkv_lora_rescale"]),
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

    def kind(self, windowed: bool) -> "LatentKind":
        """The widths of one of the two kinds of attention layer."""
        if windowed:
            return LatentKind(
                self.swa_n_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta)
        return LatentKind(
            self.n_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta)

    @property
    def kv_layers(self) -> int:
        """Layers whose pages keep every token: the full ones."""
        return self.layer_types.count(FULL)

    @property
    def window_layers(self) -> int:
        """Layers whose pages go back behind the window."""
        return self.layer_types.count(SLIDING)

    @property
    def kv_window(self) -> int:
        """Positions a ``window`` leaf keeps readable behind the newest."""
        return self.window

    @property
    def expert_layers(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "Dots3NoteConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise LatentWindowUnsupported(
                f"kv_quant={kv_quant!r}: int8 pools quantise keys and values "
                f"a head (ops/paged_attention.py quantize_kv); this model's "
                f"pools are latent vectors and index keys with no head "
                f"axis, kept in {jnp.dtype(self.dtype).name}")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str], window_pages: int):
        self._refuse_quant(kv_quant)
        return Dots3Note(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, window_pages=window_pages,
            paged_kernel=kernel))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one full layer: the latent vector
        and the indexer's key as the pool lays them out (640 + 128 lanes:
        1,536 at the published widths in bfloat16)."""
        self._refuse_quant(kv_quant)
        return (self.kind(False).latent_width + _tiles(self.index_head_dim)) \
            * jnp.dtype(self.dtype).itemsize

    def window_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one sliding layer: its own, wider
        latent vector (1,088 values in 1,152 lanes: 2,304)."""
        self._refuse_quant(kv_quant)
        return self.kind(True).latent_width * jnp.dtype(self.dtype).itemsize

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the read of the
        chosen tokens by a program over ``t`` positions a row."""
        return lsel.chosen_path(kernel, t=t)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket. A program
        reads 1.7 GB of weights outside the routed experts and the experts
        its rows reach whatever its width; the index's and the chosen
        read's arithmetic grow with the rows as the dense products do, and
        the choice (a threshold and a compaction a query, eight queries a
        grid step) costs a query the same at any width (PERF.md section 6,
        PRs 62-63)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the chosen read's own (asked of the
        paged model's configuration, which knows its kernel)."""
        paths = [lsel.index_path(t),
                 lsel.choice_path(self.paged_kernel, t=t),
                 lsel.gather_path(self.paged_kernel, t=t)]
        if SLIDING in self.layer_types:
            paths.append(lsel.window_path(self.paged_kernel, t=t))
        if self.expert_layers:
            paths.append(gexp.PATH)
        return tuple(path for path in paths if path)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None,
                      window_blocks: Optional[int] = None) -> None:
        """Lower this model's kernels for a TPU (no device, no compile): the
        expert product at the decode step's rows and at the widest chunk's
        and, with pools named, the index and the read of the chosen at both
        (the decode step's page table is the widest the scalar prefetch
        carries) and, with ``window_blocks`` named, the read under the
        window at the decode step's rows (a chunk's is plain XLA)."""
        self._refuse_quant(kv_quant)
        full = self.kind(False)
        if window_blocks is not None and SLIDING in self.layer_types:
            swa = self.kind(True)
            lsel.lower_window_for_tpu(
                batch=slots, t=1, heads=swa.heads, width=swa.latent_width,
                value_dim=swa.rank, window=self.window,
                n_blocks=window_blocks, page_size=page_size,
                pages_per_seq=pages_per_seq, dtype=self.dtype)
        if kv_blocks is not None:
            for batch, t in ((slots, 1), (1, self.widest_prefill)):
                lsel.lower_for_tpu(
                    batch=batch, t=t, heads=full.heads,
                    index_heads=self.index_n_heads,
                    index_dim=self.index_head_dim, width=full.latent_width,
                    value_dim=full.rank, topk=self.index_topk,
                    n_blocks=kv_blocks, page_size=page_size,
                    pages_per_seq=pages_per_seq, dtype=self.dtype)
        if self.expert_layers:
            for rows in (slots, self.widest_prefill):
                gexp.lower_for_tpu(
                    rows=rows, experts=self.n_held, latent=self.d_model,
                    width=self.expert_width, dtype=self.dtype, gated=True)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Dots3NoteConfig":
        """Every mechanism at a size the CPU tests run: a dense full layer,
        then full, sliding, sliding; 4 heads over a latent of 32 + 8 with an
        indexer of 2 heads that keeps 8 positions, 2 heads over a latent of
        48 + 8 under a window of 5; 16 routed experts of which 4 held, 4 a
        token."""
        return Dots3NoteConfig(
            vocab_size=vocab_size, d_model=64, n_layers=4,
            layer_types=(FULL, FULL, SLIDING, SLIDING), n_heads=4,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
            index_head_dim=16, index_topk=8, swa_n_heads=2,
            swa_q_lora_rank=24, swa_kv_lora_rank=48,
            swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
            swa_v_head_dim=16, window=5, first_dense=1, dense_width=128,
            n_routed_experts=16, experts_held=(0, 4), top_k=4,
            expert_width=32, shared_width=32, max_seq_len=128,
            dtype=jnp.float32, param_dtype=jnp.float32, kv_page_size=8)


@dataclasses.dataclass(frozen=True)
class LatentKind:
    """One kind of attention layer's widths."""
    heads: int
    q_rank: int
    rank: int
    nope: int
    rope: int
    value: int
    theta: float

    @property
    def latent_values(self) -> int:
        """What a token caches a layer: ``c`` and the shared rotary key."""
        return self.rank + self.rope

    @property
    def latent_width(self) -> int:
        return _tiles(self.latent_values)

    @property
    def softmax_scale(self) -> float:
        return (self.nope + self.rope) ** -0.5


class BiasedLayerNorm(nn.Module):
    """``(x - mean) * rsqrt(var + eps) * scale + bias`` in float32: the
    indexer's key norm, the model's one bias."""
    eps: float
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,),
                           self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (d,),
                          self.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)


def _rope_head(x, pos, theta: float, width: int):
    """``x`` [B, T, H, D] with its first ``width`` values rotated."""
    if width == x.shape[-1]:
        return _rope(x, pos, theta)
    return jnp.concatenate(
        [_rope(x[..., :width], pos, theta), x[..., width:]], axis=-1)


class LatentAttention(nn.Module):
    """Latent attention of either kind, absorbed. ``windowed`` layers read
    the window's positions of their own pool; the others score the row's
    cached index keys, keep the ``index_topk`` best and read those."""
    cfg: Dots3NoteConfig
    windowed: bool

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        cfg, k = self.cfg, self.cfg.kind(self.windowed)
        b, t, _ = u.shape
        h, r, dn, dr, dv = k.heads, k.rank, k.nope, k.rope, k.value
        w = k.latent_width
        f32 = jnp.float32

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        def latent(x, name, rank):
            """A low-rank projection's float32 result normed, rescaled and
            rounded once: the projection's sums, the norm and the rescale
            stay in float32 between them."""
            y = norm(name)(x)
            if cfg.lora_rescale:
                y = y * (cfg.d_model / rank) ** 0.5
            return y.astype(cfg.dtype)

        with trace.part(trace.PROJ):
            c_q = latent(dense(k.q_rank, "q_a_proj", cfg, f32)(u), "q_a_norm",
                         k.q_rank)
            q = into_heads(dense(h * (dn + dr), "q_b_proj", cfg)(c_q),
                           b, t, h, dn + dr)
            kva = dense(r + dr, "kv_a_proj", cfg, f32)(u)
            c = latent(kva[..., :r], "kv_a_norm", r)
            # [rank, head, nope + value]: the keys' and the values'
            # up-projection
            w_kvb = self.param("kv_b_proj", normal(), (r, h, dn + dv),
                               cfg.param_dtype).astype(cfg.dtype)
            gate = jax.nn.sigmoid(dense(h, "gate_proj", cfg, f32)(u))

        cached = cfg.decode_paged
        if cached:
            pages = cfg.window_pages if self.windowed else cfg.kv_pages
            pool = self.variable(
                "cache", "wlatent" if self.windowed else "latent",
                jnp.zeros, (pages, cfg.kv_page_size, w), cfg.dtype)
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start = index.value
        else:
            start = jnp.zeros((b,), jnp.int32)
        with trace.part(trace.PROJ):
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
            q_rope = _rope(q[..., dn:], pos, k.theta)
            k_rope = _rope(kva[:, :, None, r:], pos, k.theta)[:, :, 0]
            # absorb the keys' up-projection into the query
            q_abs = jnp.einsum("bthn,rhn->bthr", q[..., :dn], w_kvb[..., :dn],
                               preferred_element_type=f32)
            pad = w - r - dr
            q_full = jnp.concatenate(
                [q_abs.astype(cfg.dtype), q_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, h, pad), cfg.dtype)], axis=-1)
            lat = jnp.concatenate(
                [c.astype(cfg.dtype), k_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, pad), cfg.dtype)], axis=-1)       # [B, T, W]

        if not self.windowed:
            # the indexer: queries from c_q, one key a token from u, head
            # weights from u; head-major queries, as the kernels take them
            with trace.part(trace.PROJ):
                j, di = cfg.index_n_heads, cfg.index_head_dim
                qi = into_heads(dense(j * di, "index_q_proj", cfg)(c_q),
                                b, t, j, di)
                qi = _rope_head(qi, pos, k.theta, dr).transpose(0, 2, 1, 3)
                ki = BiasedLayerNorm(1e-6, cfg.param_dtype,
                                     name="index_k_norm")(
                    dense(di, "index_k_proj", cfg)(u))
                ki = _rope_head(ki[:, :, None], pos, k.theta, dr)[:, :, 0]
                wi = dense(j, "index_w_proj", cfg, f32)(u)

        if not cached:
            if self.windowed:
                summed = lsel.causal_latent_attention(
                    q_full, lat, value_dim=r, scale=k.softmax_scale,
                    window=cfg.window)
            else:
                scores = None
                if t > cfg.index_topk:
                    with trace.part(trace.ATTN_READ):
                        scores = jnp.einsum(
                            "bjtl,btj->btl", jnp.maximum(jnp.einsum(
                                "bjtd,bld->bjtl", qi.astype(cfg.dtype),
                                ki.astype(cfg.dtype),
                                preferred_element_type=f32), 0.0), wi,
                            precision=jax.lax.Precision.HIGHEST)
                summed = lsel.causal_latent_attention(
                    q_full, lat, value_dim=r, scale=k.softmax_scale,
                    scores=scores, topk=cfg.index_topk)
        else:
            real = row_mask(valid_len, b, t)
            if not self.is_initializing():
                if page_table is None:
                    raise ValueError("a paged forward needs its page table")
                with trace.part(trace.CACHE_WRITE):
                    rows, offs = paged_scatter_index(page_table, pos,
                                                     cfg.kv_page_size)
                    pool.value = pool.value.at[rows, offs].set(
                        lat.reshape(b * t, w))
                    index.value = index.value + t
            # an idle slot (no real position) is told so, whatever its stale
            # position says: the reads skip it and give it 0
            live = jnp.where(real[:, 0], start, -1)
            seen = jnp.where(real[:, 0], start + jnp.sum(real, axis=1), 0)
            n_rows = jnp.sum(real[:, 0])
            if self.windowed:
                summed = lsel.latent_window_attention(
                    q_full, pool.value, page_table, live,
                    window=cfg.window, value_dim=r,
                    scale=k.softmax_scale, kernel=cfg.paged_kernel)
                counts = [0, 0, 0, 0,
                          jnp.sum(jnp.minimum(seen, cfg.window)), 0]
            else:
                # the index key in whole tiles of lanes too (128 as published)
                pad_i = _tiles(di) - di
                ik_pool = self.variable(
                    "cache", "ik", jnp.zeros,
                    (cfg.kv_pages, cfg.kv_page_size, di + pad_i), cfg.dtype)
                if not self.is_initializing():
                    with trace.part(trace.CACHE_WRITE):
                        ik_pool.value = ik_pool.value.at[rows, offs].set(
                            jnp.pad(ki.astype(cfg.dtype).reshape(b * t, di),
                                    ((0, 0), (0, pad_i))))
                # the four steps name themselves (``ops/latent_select.py``:
                # latent_index, latent_choice, latent_gather and
                # latent_chosen_read; latent_window_read above)
                with trace.part(trace.LATENT_INDEX):
                    qi = jnp.pad(qi, ((0, 0),) * 3 + ((0, pad_i),))
                    scores = lsel.index_scores(
                        qi, wi, ik_pool.value, page_table, live,
                        topk=cfg.index_topk, kernel=cfg.paged_kernel)
                idx, n = lsel.latent_topk(
                    scores, jnp.where(real, pos, -1), cfg.index_topk,
                    kernel=cfg.paged_kernel)
                # for whoever asks (``mutable=["choices"]``): what every
                # query chose, and how many of them
                self.sow("choices", "chosen", (idx, n))
                summed = lsel.latent_chosen_attention(
                    q_full, pool.value, page_table, idx, n,
                    value_dim=r, scale=k.softmax_scale,
                    kernel=cfg.paged_kernel)
                selects = real[:, 0] & (seen > cfg.index_topk)
                counts = [jnp.sum(seen),
                          jnp.sum(jnp.minimum(seen, cfg.index_topk)),
                          jnp.sum(selects), n_rows - jnp.sum(selects), 0,
                          n_rows]
            with trace.part(trace.ATTN_READ):
                other = len(experts.STATS)
                self.sow("stats", "latent", jnp.concatenate([
                    jnp.zeros((other,), jnp.int32),
                    jnp.stack([*map(jnp.asarray, counts)]).astype(jnp.int32)]),
                    reduce_fn=lambda a, x: a + x,
                    init_fn=lambda: jnp.zeros((other + _OWN_STATS,),
                                              jnp.int32))
        with trace.part(trace.PROJ):
            out = jnp.einsum("bthr,rhv->bthv", summed.astype(cfg.dtype),
                             w_kvb[..., dn:], preferred_element_type=f32)
            out = out * gate[..., None]
            return dense(cfg.d_model, "o_proj", cfg)(
                out.astype(cfg.dtype).reshape(b, t, h * dv))


class Dots3Note(nn.Module):
    cfg: Dots3NoteConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"latent": "paged", "ik": "paged", "wlatent": "window",
                   "index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = experts.STATS + (
        LATENT_VISIBLE, LATENT_CHOSEN, LATENT_SELECT_ROWS, LATENT_DENSE_ROWS,
        LATENT_WINDOW_TOKENS, LATENT_ROWS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None,
                 window_table=None):
        cfg = self.cfg
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens]

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        for i, kind in enumerate(cfg.layer_types):
            windowed = kind == SLIDING
            y = LatentAttention(cfg, windowed, name=f"layer_{i}")(
                norm(f"layer_{i}_norm")(x),
                window_table if windowed else page_table, valid_len)
            # a residual sum is filed with the block it closes
            with trace.part(trace.PROJ):
                x = x + y
            u = norm(f"layer_{i}_ffn_norm")(x)
            if i < cfg.first_dense:
                with trace.part(trace.FFN):
                    x = x + GatedMlp(cfg, name=f"layer_{i}_mlp")(u)
            else:
                y = GatedExperts(cfg, other_stats=_OWN_STATS,
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


def init_params(cfg: Dots3NoteConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Dots3Note(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

"""Motif-3-Beta (``model_type`` ``Motif``; 53 layers at 4096): **four residual
streams a token** mixed by matrices computed from the token (mHC), **grouped
differential attention over a latent cache** in window and full layers, and
gated MLPs and experts under **PolyNorm**. ``N(.)`` is RMSNorm, ``sigma`` the
logistic function. A token's residual is ``X`` in ``R^{4 x 4096}``; the
embedding is copied into the four streams, and after the last layer they are
summed: ``logits = W_head N_f(sum_i X_i)``.

- **every sublayer** ``F`` (attention or feed-forward; ``ops/mhc.py``), with
  its own ``phi``, ``alpha``, ``b``: from the flattened, normalised streams
  three mixes, ``Hpre = sigma(.)`` ``[4]``, ``Hpost = 2 sigma(.)`` ``[4]``,
  ``Hres`` ``[4 x 4]`` made doubly stochastic by ``mhc_sinkhorn_iters``
  Sinkhorn-Knopp sweeps; ``h = sum_i Hpre_i X_i``; ``y = clamp(F(N(h)),
  +-hidden_clamp)``; ``X_i <- sum_j Hres_ij X_j + Hpost_i y``.
- **attention**, both kinds: 80 query heads = 16 groups (one a key-value
  head) of 4 *signal* heads and 1 *noise* head, **signal heads first**
  (head ``4 g + i`` is signal head ``i`` of group ``g``, head ``64 + g`` the
  group's noise head). ``c_q = N_q(W_qa u)``, ``q_h = W_qb,h c_q = [q_nope
  (128) ; q_rope (64)]``; a token caches ``[c ; k_rope]``, ``[c' ; k'] =
  W_kva u``, ``c = N_kv(c')`` (512), ``k_rope = rope(k')`` (64): one latent
  vector a token, 16 key-value heads expanded from it by ``W_kvb``. Each head
  is a softmax read of its group's keys and values; **a signal head's output
  is its read less ``lam`` times its group's noise head's**, ``lam =
  sigma(W_lam u)`` one a signal head in float32 (Differential Transformer V2
  over Grouped Differential Attention's unbalanced heads); the 64 outputs
  are gated a channel, ``sigma(W_g u)``, before ``W_o``. A full layer reads
  every position, a window layer itself and the 127 before it.
- **the read is the absorbed form** (``ops/mla.py``): ``W_kvb``'s key half
  folded into the query, its value half applied after the sum. Because
  ``W_vb,g`` is linear and shared by a group, **the difference is taken in
  the latent**: ``o_g,i = W_vb,g (a_(g,i) - lam_(g,i) a_(g,n))``, 80 reads
  of one cached vector a token, 64 up-projections.
- **feed-forward**: ``W_down(P(W_gate n) * W_up n)`` with ``P`` PolyNorm,
  normalised over the whole intermediate width (``ops/polynorm_experts.py``);
  the first ``first_dense`` layers one MLP of 12,288, the others a sigmoid
  router over ``n_routed_experts`` (384), 8 a token renormalised and scaled
  by 2, experts of 1,280, and one shared expert of the same form; each MLP
  its own four PolyNorm scalars.

What a serving engine has to know, and reads from here without naming the
model (``models/serving.py``):

- **the layer's carry is four streams**, ``[B, T, 4 x 4096]`` float32, inside
  ``__call__`` only: the engine hands ids in and takes logits out, and no
  cache leaf holds a stream. Float32 because the streams are what every
  sublayer's three mixes are computed from and what ten sublayers add into
  (``models/jamba.py`` carries its one stream in float32 for the same
  reason); what a sublayer reads (``N(h)``) and returns is the weights' type.
- **two latent leaves of one price in two kinds**: ``latent`` ``[pages,
  page, 640]`` (576 values) of kind ``paged`` in full layers, ``wlatent``
  the same shape of kind ``window`` in window layers: 1,280 bytes a token a
  layer either way, so ``kv_token_bytes`` answers both.
- **no state leaf, but window leaves**: the radix cache is off and the
  mechanisms that move pages by tokens refuse the model by name, as for
  ``models/cohere2_moe.py``; ``kv_quant`` is refused here.
- **an expert layer that is told which experts it holds**: the router, its
  choice, the weights and the counts are ``models/experts.py``'s as they
  are; the product is :func:`ops.polynorm_experts.polynorm_experts`.
- **counts** a round carries out with its tokens (:attr:`Motif.STATS`).

Float32: the streams, the connections' norm, projections, sigmoids and
sweeps; the router; the softmaxes; ``lam`` and the difference; PolyNorm's
powers and norms.

Read from the published config where it gives only a flag (the benchmark's
configuration file lists each under ``assumed``): the residual as DeepSeek's
mHC (arXiv:2512.24880), one connection a sublayer; the differential read as
Differential Transformer V2 with no norm a head and no ``lambda_init``; the
window as "itself and the ``window - 1`` before it"; layer ``i`` full where
``i % sliding_window_period == sliding_window_period - 1``; the gate's input
(``u``) and place; PolyNorm as PolyCom and Motif-2.6B's modelling code define
it; plain rotary (``apply_yarn_scaling`` false) pairing ``i`` with ``i +
d/2``; a softmax scale of ``head_dim^-1/2``; no bias anywhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models import experts
from lzy_tpu.models.deepseek_v3 import MLA_CONTEXT_TOKENS, MLA_ROWS
from lzy_tpu.models.dots3_note import LATENT_WINDOW_TOKENS
from lzy_tpu.models.experts import held_weights, row_mask, sigmoid_scores
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.paged_blocks import dense, into_heads, normal
from lzy_tpu.ops import latent_select as lsel
from lzy_tpu.ops import mhc, mla
from lzy_tpu.ops import polynorm_experts as pne
from lzy_tpu.ops.paged_attention import paged_scatter_index
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

MHC_MIXED_ROWS = REGISTRY.counter(
    "lzy_mhc_mixed_rows_total",
    "real rows of decode rounds whose residual streams a sublayer mixed "
    "(rows x sublayers a round)")
DIFF_NOISE_WEIGHT_MILLI = REGISTRY.counter(
    "lzy_diff_noise_weight_milli_total",
    "1000 x the weight lam the real rows of decode rounds gave their "
    "group's noise head, summed over signal heads, a layer (over "
    "lzy_diff_signal_reads_total: the mean lam; 0 says nothing is "
    "subtracted)")
DIFF_SIGNAL_READS = REGISTRY.counter(
    "lzy_diff_signal_reads_total",
    "signal heads' reads by the real rows of decode rounds (rows x signal "
    "heads), a layer")

SLIDING, FULL = "sliding_attention", "full_attention"
_LANES = 128
_OWN_STATS = 6
#: the four streams' type (the module's docstring says why)
STREAM_DTYPE = jnp.float32
#: query rows (positions x heads) a grid cell of ``ops/mla.py``'s prefill
#: read may hold: its tile of 64 positions x 20 heads compiles for a v5e
#: core's VMEM, x 40 does not (the kernel was sized for 16 heads)
_PREFILL_CELL_ROWS = 1280


class LatentWindowUnsupported(ValueError):
    """A mechanism that latent pools of two lifetimes cannot serve, by
    name."""


@dataclasses.dataclass(frozen=True)
class MotifConfig:
    vocab_size: int = 220160
    d_model: int = 4096
    n_layers: int = 53
    #: each layer's attention, ``full_attention`` or ``sliding_attention``
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 13 \
        + (SLIDING,)
    # grouped differential attention over a latent cache
    n_heads: int = 80                    # query heads, signal and noise
    n_noise_heads: int = 16              # one a key-value head
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    swa_rope_theta: float = 1e4
    window: int = 128
    # the residual: mHC
    mhc_streams: int = 4
    mhc_sweeps: int = 20
    hidden_clamp: float = 1e6
    # the first layers' dense MLP
    first_dense: int = 2
    dense_width: int = 12288
    # experts
    n_routed_experts: int = 384          # the router's width
    experts_held: Tuple[int, int] = (0, 384)   # [lo, hi) held here
    top_k: int = 8
    expert_width: int = 1280
    shared_width: int = 1280             # num_shared_experts x expert_width
    routed_scaling: float = 2.0
    polynorm_scale: float = 0.5
    polynorm_clamp: float = 0.5
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
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
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary width must be even")
        if self.n_noise_heads < 1 \
                or self.n_signal_heads % self.n_noise_heads:
            raise ValueError(
                f"{self.n_signal_heads} signal heads are not whole groups "
                f"over {self.n_noise_heads} noise heads")
        if self.window < 1 or self.mhc_streams < 1 or self.mhc_sweeps < 1:
            raise ValueError("window, mhc_streams and mhc_sweeps must be "
                             ">= 1")
        if mhc.mix_rows(self.mhc_streams) > mhc.MIX_WIDTH:
            raise ValueError(f"{self.mhc_streams} streams' mix does not fit "
                             f"its {mhc.MIX_WIDTH} columns")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "MotifConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``router_width`` and
        ``experts_held_from`` (a deployment's, not published) say which of
        the router's experts are held here; ``layer_types``, where given,
        stands for the published period."""
        refused = {
            "attention_cls": ("gdla",), "diff_v2": (True,),
            "mhc_enabled": (True,), "hidden_act": ("poly_norm",),
            "score_func": ("sigmoid",), "route_norm": (True,),
            "score_before_experts": (False, None),
            "headwise_attn_output_gate": (False, None),
            "elementwise_attn_output_gate": (True,),
            "tie_word_embeddings": (False, None),
            "interleave_moe_layer_step": (1, None),
            "sliding_window_pattern": ("interleave",),
            "use_sliding_window": (True,),
        }
        for key, served in refused.items():
            if doc.get(key) not in served:
                raise ValueError(
                    f"MotifConfig serves {key} in {served!r} (grouped "
                    f"differential latent attention, four mixed residual "
                    f"streams, PolyNorm, sigmoid scores renormalised and "
                    f"applied after the experts, a gate a channel, untied "
                    f"embeddings, experts in every layer after the dense "
                    f"ones, window and full layers interleaved); the "
                    f"configuration says {key} = {doc.get(key)!r}")
        if (doc.get("rope_scaling") or {}).get("apply_yarn_scaling"):
            raise ValueError(
                "MotifConfig serves plain rotary; the configuration says "
                "rope_scaling.apply_yarn_scaling = True")
        heads, noise = doc["num_attention_heads"], doc["num_noise_heads"]
        if noise != doc["num_key_value_heads"]:
            raise ValueError(
                f"MotifConfig serves one noise head a key-value head; the "
                f"configuration says num_noise_heads = {noise}, "
                f"num_key_value_heads = {doc['num_key_value_heads']}")
        if noise < 1 or (heads - noise) % noise:
            raise ValueError(
                f"MotifConfig serves signal heads in whole groups: "
                f"num_attention_heads - num_noise_heads = {heads - noise} "
                f"is not a multiple of num_noise_heads = {noise}")
        n = doc["num_hidden_layers"]
        period = doc["sliding_window_period"]
        kinds = tuple(doc.get("layer_types") or (
            FULL if i % period == period - 1 else SLIDING for i in range(n)))
        width = doc.get("router_width", doc["num_experts"])
        lo = doc.get("experts_held_from", 0)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=n, layer_types=kinds, n_heads=heads,
            n_noise_heads=noise, q_lora_rank=doc["q_lora_rank"],
            kv_lora_rank=doc["kv_lora_rank"],
            qk_nope_head_dim=doc["head_dim"] - doc["qk_rope_head_dim"],
            qk_rope_head_dim=doc["qk_rope_head_dim"],
            v_head_dim=doc["v_head_dim"],
            rope_theta=float(doc["rope_theta"]),
            swa_rope_theta=float(doc["swa_rope_theta"]),
            window=doc["sliding_window"],
            mhc_streams=doc["mhc_expansion_rate"],
            mhc_sweeps=doc["mhc_sinkhorn_iters"],
            hidden_clamp=float(doc["hidden_clamp"]),
            first_dense=doc["n_dense_first_layers"],
            dense_width=doc["intermediate_size"],
            n_routed_experts=width,
            experts_held=(lo, lo + doc["num_experts"]),
            top_k=doc["experts_top_k"],
            expert_width=doc["moe_intermediate_size"],
            shared_width=doc["num_shared_experts"]
            * doc["moe_intermediate_size"],
            routed_scaling=float(doc["route_scale"]),
            polynorm_scale=float(doc["polynorm_output_scale"]),
            polynorm_clamp=float(doc["polynorm_bias_clamp"]),
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def n_signal_heads(self) -> int:
        return self.n_heads - self.n_noise_heads

    @property
    def group_size(self) -> int:
        """Signal heads a group (a key-value head, a noise head)."""
        return self.n_signal_heads // self.n_noise_heads

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

    @property
    def latent_values(self) -> int:
        """What a token caches a layer: ``c`` and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """The cached vector as the pools lay it out: whole tiles of 128
        lanes (576 values in 640)."""
        return -(-self.latent_values // _LANES) * _LANES

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def prefill_read_heads(self) -> int:
        """Heads one call of ``ops/mla.py``'s prefill read takes: the most
        that divide the heads and keep a tile's q rows within
        ``_PREFILL_CELL_ROWS`` (20 of 80: four calls a full layer)."""
        most = max(1, _PREFILL_CELL_ROWS // mla._PREFILL_TILE)
        return max(h for h in range(1, self.n_heads + 1)
                   if self.n_heads % h == 0 and h <= most)

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "MotifConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise LatentWindowUnsupported(
                f"kv_quant={kv_quant!r}: int8 pools quantise keys and values "
                f"a head (ops/paged_attention.py quantize_kv); this model's "
                f"pools are latent vectors with no head axis, kept in "
                f"{jnp.dtype(self.dtype).name}")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str], window_pages: int):
        self._refuse_quant(kv_quant)
        return Motif(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, window_pages=window_pages,
            paged_kernel=kernel))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one layer of either kind: the latent
        vector as the pools lay it out (1,280 at the published widths in
        bfloat16, of which 1,152 are values)."""
        self._refuse_quant(kv_quant)
        return self.latent_width * jnp.dtype(self.dtype).itemsize

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the full layers'
        latent read by a program over ``t`` positions a row."""
        return mla.read_path(kernel, t=t)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket. A program
        reads 1.6 GB of weights outside the routed experts and the experts
        its rows reach whatever its width (256 rows reach nearly all 48 a
        layer: 6 GB); the connections', the reads' and the expert kernel's
        arithmetic grow with the rows as the dense products do (PERF.md
        section 6, PR 65, has the program's timings)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the full layers' read's own (asked of
        the paged model's configuration, which knows its kernel)."""
        paths = [mhc.path(self.paged_kernel)]
        if SLIDING in self.layer_types:
            paths.append(lsel.window_path(self.paged_kernel, t=t))
        if self.expert_layers:
            paths.append(pne.path(self.paged_kernel))
        return tuple(path for path in paths if path)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None,
                      window_blocks: Optional[int] = None) -> None:
        """Lower this model's kernels for a TPU (no device, no compile) at
        the decode step's rows and at the widest chunk's: the connections'
        two, the expert product and, with a pool named, ``ops/mla.py``'s
        read at this model's heads (all of them in a decode round,
        ``prefill_read_heads`` a call in a chunk) and, with
        ``window_blocks`` named, the read under the window at the decode
        step's rows (a chunk's is plain XLA)."""
        self._refuse_quant(kv_quant)
        if window_blocks is not None and SLIDING in self.layer_types:
            lsel.lower_window_for_tpu(
                batch=slots, t=1, heads=self.n_heads,
                width=self.latent_width, value_dim=self.kv_lora_rank,
                window=self.window, n_blocks=window_blocks,
                page_size=page_size, pages_per_seq=pages_per_seq,
                dtype=self.dtype)
        if kv_blocks is not None:
            for batch, t, heads in ((slots, 1, self.n_heads),
                                    (1, self.widest_prefill,
                                     self.prefill_read_heads)):
                mla.lower_for_tpu(
                    batch=batch, t=t, heads=heads, width=self.latent_width,
                    value_dim=self.kv_lora_rank, n_blocks=kv_blocks,
                    page_size=page_size, pages_per_seq=pages_per_seq,
                    dtype=self.dtype)
        for rows in (slots, self.widest_prefill):
            mhc.lower_for_tpu(
                rows=rows, streams=self.mhc_streams, width=self.d_model,
                sweeps=self.mhc_sweeps, dtype=STREAM_DTYPE,
                y_dtype=self.dtype)
            if self.expert_layers:
                pne.lower_for_tpu(
                    rows=rows, experts=self.n_held, latent=self.d_model,
                    width=self.expert_width, dtype=self.dtype)

    @staticmethod
    def tiny(vocab_size: int = 256, *, sweeps: int = 20) -> "MotifConfig":
        """Every mechanism at a size the CPU tests run: a dense window
        layer, then window, window, full, window; a window of 5; 4 streams;
        10 heads of which 2 noise over 2 key-value heads and a latent of 32
        + 8; 16 routed experts of which 4 held, 4 a token, an expert width
        of three tiles."""
        return MotifConfig(
            vocab_size=vocab_size, d_model=64, n_layers=5,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
            n_heads=10, n_noise_heads=2, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            window=5, mhc_streams=4, mhc_sweeps=sweeps, first_dense=1,
            dense_width=256, n_routed_experts=16, experts_held=(0, 4),
            top_k=4, expert_width=384, shared_width=384, max_seq_len=128,
            dtype=jnp.float32, param_dtype=jnp.float32, kv_page_size=8)


class HyperConnection(nn.Module):
    """One sublayer's connection: its ``phi`` (a quantity a row, ``[n + n +
    n x n, n x D]``), ``alpha`` and ``b``, float32. ``pre`` gives what the
    sublayer reads and the token's mix, ``post`` the new streams."""
    cfg: MotifConfig

    def setup(self):
        cfg = self.cfg
        n, k = cfg.mhc_streams, mhc.mix_rows(cfg.mhc_streams)
        f32 = jnp.float32
        # a projection of the unit-rms streams with unit spread
        self.phi = self.param(
            "phi", nn.initializers.normal((n * cfg.d_model) ** -0.5),
            (k, n * cfg.d_model), f32)
        self.alpha = self.param("alpha", nn.initializers.ones, (3,), f32)
        self.b = self.param("b", nn.initializers.normal(0.5), (k,), f32)

    def _kernel(self) -> str:
        # the uncached forward and the initialiser take the portable form
        cfg = self.cfg
        return cfg.paged_kernel if cfg.decode_paged \
            and not self.is_initializing() else "lax"

    def pre(self, x):
        cfg = self.cfg
        b, t, nd = x.shape
        h, mix = mhc.mhc_pre(
            x.reshape(b * t, nd), self.phi, self.alpha, self.b,
            streams=cfg.mhc_streams, sweeps=cfg.mhc_sweeps,
            eps=cfg.norm_eps, kernel=self._kernel())
        return h.reshape(b, t, cfg.d_model), mix

    def post(self, x, y, mix):
        cfg = self.cfg
        b, t, nd = x.shape
        y = jnp.clip(y, -cfg.hidden_clamp, cfg.hidden_clamp)
        return mhc.mhc_post(
            x.reshape(b * t, nd), y.reshape(b * t, cfg.d_model), mix,
            streams=cfg.mhc_streams, kernel=self._kernel()
        ).reshape(b, t, nd)


def _own_stats(layer: nn.Module, name: str, counts) -> None:
    """Sow this model's six counts (the places after the experts' four)."""
    other = len(experts.STATS)
    layer.sow("stats", name, jnp.concatenate([
        jnp.zeros((other,), jnp.int32),
        jnp.stack([*map(jnp.asarray, counts)]).astype(jnp.int32)]),
        reduce_fn=lambda a, x: a + x,
        init_fn=lambda: jnp.zeros((other + _OWN_STATS,), jnp.int32))


class DifferentialLatentAttention(nn.Module):
    """Grouped differential attention over the latent cache, absorbed, the
    difference taken in the latent. ``windowed`` layers read the window's
    positions of their own pool, the others every position of theirs."""
    cfg: MotifConfig
    windowed: bool

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, g, per = cfg.n_heads, cfg.n_noise_heads, cfg.group_size
        hs = cfg.n_signal_heads
        r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        w = cfg.latent_width
        theta = cfg.swa_rope_theta if self.windowed else cfg.rope_theta
        f32 = jnp.float32

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        with trace.part(trace.PROJ):
            c_q = norm("q_a_norm")(
                dense(cfg.q_lora_rank, "q_a_proj", cfg, f32)(u)).astype(
                cfg.dtype)
            q = into_heads(dense(h * (dn + dr), "q_b_proj", cfg)(c_q),
                           b, t, h, dn + dr)
            kva = dense(r + dr, "kv_a_proj", cfg, f32)(u)
            c = norm("kv_a_norm")(kva[..., :r]).astype(cfg.dtype)
            # [rank, key-value head, nope + value]: the 16 heads' keys and
            # values
            w_kvb = self.param("kv_b_proj", normal(), (r, g, dn + dv),
                               cfg.param_dtype).astype(cfg.dtype)
            lam = jax.nn.sigmoid(dense(hs, "lambda_proj", cfg, f32)(u))
            gate = jax.nn.sigmoid(dense(hs * dv, "gate_proj", cfg, f32)(u))

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
            q_rope = _rope(q[..., dn:], pos, theta)
            k_rope = _rope(kva[:, :, None, r:], pos, theta)[:, :, 0]
            # absorb the keys' up-projection into the queries of its group:
            # the signal heads (group-major), then the noise heads
            w_k = w_kvb[..., :dn]
            q_abs = jnp.concatenate([
                jnp.einsum("btgin,rgn->btgir",
                           q[:, :, :hs, :dn].reshape(b, t, g, per, dn), w_k,
                           preferred_element_type=f32).reshape(b, t, hs, r),
                jnp.einsum("btgn,rgn->btgr", q[:, :, hs:, :dn], w_k,
                           preferred_element_type=f32)], axis=2)
            pad = w - r - dr
            q_full = jnp.concatenate(
                [q_abs.astype(cfg.dtype), q_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, h, pad), cfg.dtype)], axis=-1)
            lat = jnp.concatenate(
                [c, k_rope.astype(cfg.dtype),
                 jnp.zeros((b, t, pad), cfg.dtype)], axis=-1)       # [B, T, W]

        if not cached:
            summed = lsel.causal_latent_attention(
                q_full, lat, value_dim=r, scale=cfg.softmax_scale,
                window=cfg.window if self.windowed else None)
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
            # an idle slot (no real position) is told so, whatever its
            # stale position says: the reads skip it and give it 0
            live = jnp.where(real[:, 0], start, -1)
            seen = jnp.where(real[:, 0], start + jnp.sum(real, axis=1), 0)
            n_rows = jnp.sum(real[:, 0])
            if self.windowed:
                summed = lsel.latent_window_attention(
                    q_full, pool.value, page_table, live,
                    window=cfg.window, value_dim=r,
                    scale=cfg.softmax_scale, kernel=cfg.paged_kernel)
                read = [0, 0, jnp.sum(jnp.minimum(seen, cfg.window))]
            else:
                with trace.part(trace.ATTN_READ):
                    summed = self._full_read(q_full, pool.value, page_table,
                                             live)
                read = [jnp.sum(seen), n_rows, 0]
            with trace.part(trace.ATTN_READ):
                noise = jnp.sum(jnp.where(real[:, 0, None], lam[:, 0], 0.0))
                _own_stats(self, "diff", read + [
                    0, jnp.round(1000.0 * noise), n_rows * hs])
        with trace.part(trace.DIFF_EPILOGUE):
            # the difference, in the latent: a signal head's read less lam
            # times its group's noise head's
            a = summed.astype(f32)
            o_lat = a[:, :, :hs].reshape(b, t, g, per, r) \
                - lam.reshape(b, t, g, per, 1) * a[:, :, hs:, None, :]
            out = jnp.einsum("btgir,rgv->btgiv", o_lat.astype(cfg.dtype),
                             w_kvb[..., dn:], preferred_element_type=f32)
            out = out.reshape(b, t, hs * dv) * gate
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "o_proj", cfg)(out.astype(cfg.dtype))

    def _full_read(self, q_full, pool, page_table, live):
        """``ops/mla.py``'s read as it is: a decode program's heads in one
        call, a prefill chunk's ``prefill_read_heads`` a call (the kernel's
        tile of 64 positions x 80 heads does not fit a core's VMEM)."""
        cfg = self.cfg
        t, h = q_full.shape[1], q_full.shape[2]
        step = h if t <= mla.MAX_DECODE_TOKENS or cfg.paged_kernel != "pallas" \
            else cfg.prefill_read_heads
        return jnp.concatenate([
            mla.mla_attention(
                q_full[:, :, at:at + step], pool, page_table, live,
                value_dim=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                kernel=cfg.paged_kernel)
            for at in range(0, h, step)], axis=2)


class PolyNormMlp(nn.Module):
    """``W_down(P(W_gate n) * W_up n)``, PolyNorm over the whole width:
    the dense layers' MLP and the shared expert (plain XLA)."""
    cfg: MotifConfig
    width: int

    @nn.compact
    @trace.part(trace.FFN)
    def __call__(self, u):
        cfg = self.cfg
        f32 = jnp.float32
        params = self.param("polynorm", _polynorm_init, (4,), f32)
        act = pne.polynorm(dense(self.width, "gate_proj", cfg, f32)(u),
                           params, scale=cfg.polynorm_scale,
                           clamp=cfg.polynorm_clamp)
        hid = act * dense(self.width, "up_proj", cfg, f32)(u)
        return dense(cfg.d_model, "down_proj", cfg, f32)(
            hid.astype(cfg.dtype))


def _polynorm_init(key, shape, dtype=jnp.float32):
    """``(w_1, w_2, w_3, b)`` an MLP: PolyCom's thirds, and each MLP's own
    small departure from them (so that no two experts are alike)."""
    base = jnp.asarray([1 / 3, 1 / 3, 1 / 3, 0.0], jnp.float32)
    return (base + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(
        dtype)


class PolyNormExperts(nn.Module):
    """Sigmoid router over all the routed experts (no bias on the choice),
    the held experts' product under PolyNorm, a shared expert of the same
    form."""
    cfg: MotifConfig

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, dm = u.shape
        m = b * t
        um = u.reshape(m, dm)
        with trace.part(trace.ROUTER):
            real = row_mask(valid_len, b, t).reshape(m)
            scores, _ = sigmoid_scores(self, um, cfg.n_routed_experts,
                                       choice_bias=False)
            weights = held_weights(
                self, scores, real, top_k=cfg.top_k, held=cfg.experts_held,
                scaling=cfg.routed_scaling, other_stats=_OWN_STATS)
        up_shape = (cfg.n_held, dm, cfg.expert_width)
        wg = self.param("experts_gate", normal(), up_shape, cfg.param_dtype)
        wu = self.param("experts_up", normal(), up_shape, cfg.param_dtype)
        wd = self.param("experts_down", normal(),
                        (cfg.n_held, cfg.expert_width, dm), cfg.param_dtype)
        pn = self.param("experts_polynorm", _polynorm_init, (cfg.n_held, 4),
                        jnp.float32)
        with trace.part(trace.EXPERTS):
            if self.is_initializing():
                routed = jnp.zeros((m, dm), jnp.float32)    # no kernel at init
            else:
                routed = pne.polynorm_experts(
                    um, wg.astype(cfg.dtype), wu.astype(cfg.dtype),
                    wd.astype(cfg.dtype), pn, weights,
                    scale=cfg.polynorm_scale, clamp=cfg.polynorm_clamp,
                    kernel=cfg.paged_kernel if cfg.decode_paged else "lax")
            shared = PolyNormMlp(cfg, cfg.shared_width, name="shared")(um)
            return (routed + shared).astype(cfg.dtype).reshape(b, t, dm)


class Motif(nn.Module):
    cfg: MotifConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"latent": "paged", "wlatent": "window", "index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = experts.STATS + (
        MLA_CONTEXT_TOKENS, MLA_ROWS, LATENT_WINDOW_TOKENS, MHC_MIXED_ROWS,
        DIFF_NOISE_WEIGHT_MILLI, DIFF_SIGNAL_READS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None,
                 window_table=None):
        cfg = self.cfg
        b, t = tokens.shape
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        # the embedding, copied into the streams, which lie side by side:
        # [B, T, n x D] (a [.., 4, 4096] array pads its 4 to 8 sublanes on
        # the chip, and every reshape of it is a copy)
        with trace.part(trace.EMBED):
            x = jnp.tile(emb[tokens].astype(STREAM_DTYPE),
                         (1, 1, cfg.mhc_streams))
        n_rows = jnp.sum(row_mask(valid_len, b, t)[:, 0])

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        def sublayer(x, name, norm_name, fn):
            """``fn`` behind its connection: the mix in, the sublayer on
            ``N(h)`` in the weights' type, the mix out."""
            hc = HyperConnection(cfg, name=name)
            with trace.part(trace.MIX):
                h, mix = hc.pre(x)
            with trace.part(trace.NORM):
                u = norm(norm_name)(h).astype(cfg.dtype)
            y = fn(u)
            with trace.part(trace.MIX):
                x = hc.post(x, y.astype(cfg.dtype), mix)
            if cfg.decode_paged:
                _own_stats(self, name + "_rows", [0, 0, 0, n_rows, 0, 0])
            return x

        for i, kind in enumerate(cfg.layer_types):
            windowed = kind == SLIDING
            attn = DifferentialLatentAttention(cfg, windowed,
                                               name=f"layer_{i}")
            x = sublayer(
                x, f"layer_{i}_hc", f"layer_{i}_norm", functools.partial(
                    attn, page_table=window_table if windowed else page_table,
                    valid_len=valid_len))
            if i < cfg.first_dense:
                ffn = PolyNormMlp(cfg, cfg.dense_width, name=f"layer_{i}_mlp")
            else:
                ffn = functools.partial(
                    PolyNormExperts(cfg, name=f"layer_{i}_moe"),
                    valid_len=valid_len)
            x = sublayer(x, f"layer_{i}_ffn_hc", f"layer_{i}_ffn_norm", ffn)
        # the streams, summed out
        with trace.part(trace.HEAD):
            d = cfg.d_model
            out = norm("final_norm")(sum(
                x[..., i * d:(i + 1) * d] for i in range(cfg.mhc_streams)))
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            return jnp.einsum("bte,ve->btv", out.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: MotifConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Motif(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

"""ZAYA1 (``model_type`` ``zaya``; ``ZAYA1-8B``: 40 layers at 2048): a decoder
whose every layer is an attention sublayer and an expert sublayer. The
attention is **compressed convolutional attention** (CCA, arXiv:2510.04476):
8 query heads over 2 key-value heads of 128 *in a compressed latent* (queries
1024 wide, keys and values 256: half and an eighth of the hidden width), and
its queries and keys are mixed **along the sequence** before they are cached.
The expert sublayer is 16 SiLU-gated experts, **one a token**, behind a
router that is a small MLP and **carries its state from layer to layer**.

Attention sublayer, ``u_t = RMSNorm(x_t)`` (``ops/cca.py`` has the mix)::

    [q~ ; k~ ; v2 ; v1]_t = mix_proj(u_t)            2048 -> 1024+256+128+128
    q_t, k_t  = the two causal convolutions over [q~ ; k~] at t-2 .. t, the
                query-key mean, L2 norms x sqrt(d), the key's temperature
    v_t       = [v1_t ; v2_{t-1}]       head 0 this position's, head 1 the last
    rotary on the first half of each head of q and k; causal grouped-query
    softmax attention over the cached k, v at 1 / sqrt(d); o_proj 1024 -> 2048

Expert sublayer, ``u_t = RMSNorm(x_t)``, layer ``l``::

    r^l   = router_down(u_t) + bias  (256);   l > 0:  r^l += s^l * r^(l-1)
    z     = MLP(RMSNorm(r^l))        256 -> 256 -> 256 -> 16, GELU between
    pi    = softmax(z);   e* = argmax(pi + beta);   y_t = pi[e*] E_e*(u_t)

all of the router in float32 at the highest precision, from the normalised
stream before it is rounded for the experts' products: a near-tie decides
which expert a row's whole output comes from. What is carried to the next
layer is ``r^l``, the sum before its norm. Both sublayers join the stream
through a learned scale and bias a channel, on the stream and on the
sublayer's output (the model's first sublayer: the output's pair only). The
stream is float32 (the products take it rounded to their type).

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **cache leaves of three kinds** (:attr:`Zaya.CACHE_KINDS`). ``k``, ``v``:
  the mixed, normalised, rotated keys and the shifted values in the shared
  paged pool, ``[pages, page, 2, 128]`` (1,024 bytes a token a layer), read
  by ``ops/paged_attention.py`` as Mistral's are; an ``index``; and
  ``window`` (kind ``state``): **the last two positions' latent projections**
  ``[slots, 2 x 1408]`` float32, which the next position's convolutions and
  value read. It is a window and not a recurrence: a fresh slot's is zeros,
  which is the sequence's padded start.
- ``valid_len`` ``[B]``: how many of a row's ``T`` positions are real. The
  window after a program ends at the row's last real position, so neither a
  padded prefill chunk nor an idle decode slot moves it; the expert layer
  leaves pad rows out of its product and of its counts.
- **an expert layer that is told which experts it holds**
  (``experts_held``; ``models/experts.py`` ``held_weights``).
- **counts** a round carries out with its tokens (:attr:`Zaya.STATS`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models import experts
from lzy_tpu.models.experts import held_weights, row_mask
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.paged_blocks import (
    ATTN_FULL_KEYS, ATTN_ROWS, dense, normal)
from lzy_tpu.models.serving import HeadPool
from lzy_tpu.ops import cca
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

CCA_ROWS = REGISTRY.counter(
    "lzy_cca_rows_total",
    "real rows of decode rounds whose carried window a CCA layer moved, a "
    "layer")

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig(HeadPool):
    vocab_size: int = 262272
    d_model: int = 2048
    n_layers: int = 40
    # compressed convolutional attention
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    rotary_fraction: float = 0.5
    rope_theta: float = 5e6
    # experts
    n_routed_experts: int = 16           # the router's width
    experts_held: Tuple[int, int] = (0, 16)    # [lo, hi) held here
    top_k: int = 1
    expert_width: int = 2048
    router_width: int = 256
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    #: the carried window's type (``ops/cca.py`` takes float32 and no other)
    window_dtype: Any = jnp.float32
    # serving: keys and values in a shared paged pool, the window a slot
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
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(
                "query heads divide into their groups, and the key-value "
                "heads into this position's values and the last one's")
        if self.top_k != 1:
            raise ValueError(
                f"one expert a token (the weight is the chosen probability "
                f"itself), got top_k {self.top_k}")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "ZayaConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``router_width`` and
        ``experts_held_from`` (a deployment's, not published) say which of
        the router's experts are held here."""
        served = {
            "num_experts_per_tok": (1,), "sliding_window": (None,),
            "cca_time0": (2,), "cca_time1": (2,), "hidden_act": ("silu",),
            "attention_bias": (False,), "lm_head_bias": (False,),
            "tie_word_embeddings": (True,),
        }
        for key, values in served.items():
            if doc.get(key) not in values:
                raise ValueError(
                    f"ZayaConfig serves {key} in {values!r} (one expert a "
                    f"token, attention over everything, two convolutions of "
                    f"two taps over a carried window of two positions, "
                    f"SiLU-gated experts, no bias, tied embeddings); the "
                    f"configuration says {key} = {doc.get(key)!r}")
        layers = doc["num_hidden_layers"]
        if list(doc["layer_types"]) != ["hybrid"] * layers:
            raise ValueError(
                f"ZayaConfig serves {layers} layers of type 'hybrid' (CCA "
                f"over everything, then experts); layer_types is "
                f"{sorted(set(doc['layer_types']))} x "
                f"{len(doc['layer_types'])}")
        rope = doc["rope_parameters"]["hybrid"]
        width = doc.get("router_width", doc["num_experts"])
        lo = doc.get("experts_held_from", 0)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=layers, n_heads=doc["num_attention_heads"],
            n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
            rotary_fraction=rope["partial_rotary_factor"],
            rope_theta=float(rope["rope_theta"]), n_routed_experts=width,
            experts_held=(lo, lo + doc["num_experts"]),
            top_k=doc["num_experts_per_tok"],
            expert_width=doc["moe_intermediate_size"],
            router_width=doc["router_hidden_size"],
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: every one."""
        return self.n_layers

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def window_width(self) -> int:
        """What one position leaves in the carried window (1408)."""
        return cca.window_width(self.n_heads, self.n_kv_heads, self.head_dim)

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "ZayaConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise ValueError(
                "kv_quant: this model's paged pool is float (int8 pools "
                "are models/llama.py's)")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str]):
        self._refuse_quant(kv_quant)
        return Zaya(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket. At one expert
        a token of 16 the expert kernel multiplies every row by every touched
        expert: at 256 rows its arithmetic (103 GFLOP a layer, 0.52 ms at a
        v5e's peak) meets the read of the 16 experts (403 MB, 0.49 ms), and
        past it the arithmetic alone grows (PERF.md section 6, PR 48)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the attention read's own."""
        return (cca.UPDATE_PATH if t == 1 else cca.MIX_PATH, gexp.PATH)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower this model's kernels for a TPU at the decode step's shapes
        (no device, no compile): refused here, not at the first request.
        With a pool named, the attention reads over it too."""
        self._refuse_quant(kv_quant)
        self.lower_read(
            slots=slots, kv_blocks=kv_blocks, page_size=page_size,
            pages_per_seq=pages_per_seq, kv_quant=kv_quant)
        cca.lower_update_for_tpu(
            batch=slots, heads=self.n_heads, groups=self.n_kv_heads,
            head_dim=self.head_dim, dtype=self.dtype)
        gexp.lower_for_tpu(rows=slots, experts=self.n_held,
                           latent=self.d_model, width=self.expert_width,
                           dtype=self.dtype, gated=True)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "ZayaConfig":
        """Every mechanism at a size the CPU tests run: three layers (the
        first without the stream's pair and without a carry, two with), 4
        query heads over 2 of 16, 16 experts of which one a token."""
        return ZayaConfig(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            n_kv_heads=2, head_dim=16, rope_theta=1e4, n_routed_experts=16,
            experts_held=(0, 16), expert_width=32, router_width=16,
            max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32,
            kv_page_size=8)


def _uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    return init


def _around_one(std: float):
    """``1 + std N``: a learned scale that is not 1, so that a program
    without it fails the comparison."""
    def init(key, shape, dtype=jnp.float32):
        return (1.0 + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    return init


def _partial_rope(x, positions, theta: float, rot: int):
    """Rotary embedding on the first ``rot`` entries of each head."""
    return jnp.concatenate(
        [_rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)


class CcaAttention(nn.Module):
    cfg: ZayaConfig
    #: where this layer's counts go in the ``stats`` vector, and its length
    stats: Tuple[int, int] = (0, 3)

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None):
        from lzy_tpu.ops.paged_attention import (
            paged_attention, paged_scatter_index)

        cfg = self.cfg
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        lq, lk, width = h * d, kv * d, cfg.window_width
        f32 = jnp.float32

        # float32 out of the accumulator: two positions of it are carried,
        # and it is summed, multiplied and normalised before it is rounded
        with trace.part(trace.PROJ):
            mixed = dense(width + lk // 2, "mix_proj", cfg, f32)(u)
            new, v1 = mixed[..., :width], mixed[..., width:]
        # a convolution's taps and bias start uniform in +-1 / sqrt(fan in)
        # (torch's Conv1d): 2 taps a channel, then 2 x d inputs a head
        mixer = cca.Mixer(
            self.param("conv0_kernel", _uniform(2 ** -0.5), (2, lq + lk),
                       f32),
            self.param("conv0_bias", _uniform(2 ** -0.5), (lq + lk,), f32),
            self.param("conv1_kernel", _uniform((2 * d) ** -0.5),
                       (h + kv, 2 * d, d), cfg.param_dtype),
            self.param("conv1_bias", _uniform((2 * d) ** -0.5), (lq + lk,),
                       f32),
            self.param("temperature", lambda key, shape: jnp.exp(
                0.25 * jax.random.normal(key, shape, f32)), (kv,)))
        sizes = dict(heads=h, groups=kv, dtype=cfg.dtype)

        cached = cfg.decode_paged
        if cached:
            window = self.variable("cache", "window", jnp.zeros,
                                   (b, 2 * width), cfg.window_dtype)
            carried = window.value
        else:
            carried = jnp.zeros((b, 2 * width), f32)
        real = row_mask(valid_len, b, t)                         # [B, T]
        if cached and t == 1 and not self.is_initializing():
            q, k, v, moved = cca.cca_mix_update(
                carried, new[:, 0], v1[:, 0], real[:, 0], mixer, **sizes)
        else:
            q, k, v, moved = cca.cca_mix(carried, new, v1, valid_len, mixer,
                                         **sizes)

        if cached:
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start = index.value
        else:
            start = jnp.zeros((b,), jnp.int32)
        with trace.part(trace.PROJ):
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
            rot = int(d * cfg.rotary_fraction)
            q = _partial_rope(q.reshape(b, t, h, d), pos, cfg.rope_theta,
                              rot).astype(cfg.dtype)
            k = _partial_rope(k.reshape(b, t, kv, d), pos, cfg.rope_theta,
                              rot).astype(cfg.dtype)
            v = v.reshape(b, t, kv, d).astype(cfg.dtype)

        if not cached:
            with trace.part(trace.ATTN_READ):
                qg = q.reshape(b, t, kv, h // kv, d)
                s = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                               preferred_element_type=f32) * d ** -0.5
                keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
                out = jnp.einsum("bkgtl,blkd->btkgd", pr.astype(cfg.dtype), v)
        else:
            shape = (cfg.kv_pages, cfg.kv_page_size, kv, d)
            pool_k = self.variable("cache", "k", jnp.zeros, shape, cfg.dtype)
            pool_v = self.variable("cache", "v", jnp.zeros, shape, cfg.dtype)
            if not self.is_initializing():
                if page_table is None:
                    raise ValueError("a paged forward needs page_table")
                with trace.part(trace.CACHE_WRITE):
                    rows, offs = paged_scatter_index(page_table, pos,
                                                     cfg.kv_page_size)
                    pool_k.value = pool_k.value.at[rows, offs].set(
                        k.reshape(b * t, kv, d))
                    pool_v.value = pool_v.value.at[rows, offs].set(
                        v.reshape(b * t, kv, d))
                    index.value = index.value + t
                    window.value = moved.astype(cfg.window_dtype)
                    self._count(real, start, valid_len)
            out = paged_attention(q, pool_k.value, pool_v.value, page_table,
                                  pos, kernel=cfg.paged_kernel,
                                  dtype=cfg.dtype)
        # float32 out of the accumulator: it joins the residual stream
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "o_proj", cfg, f32)(
                out.reshape(b, t, lq).astype(cfg.dtype))

    def _count(self, real, start, valid_len):
        """The layer's counts: the cached keys its real rows read (a row at
        position p reads p + 1), those rows, and the rows whose window
        moved."""
        at, of = self.stats
        live = real[:, 0]
        ends = real.shape[1] if valid_len is None \
            else valid_len.astype(jnp.int32)
        rows = jnp.sum(live)
        counts = jnp.zeros((of,), jnp.int32).at[at].set(
            jnp.sum(jnp.where(live, start + ends, 0))).at[at + 1].set(
            rows).at[at + 2].set(rows)
        self.sow("stats", "attn", counts, reduce_fn=lambda a, x: a + x,
                 init_fn=lambda: jnp.zeros((of,), jnp.int32))


class RoutedExperts(nn.Module):
    """The router (an MLP over a 256-wide projection that adds the previous
    layer's, softmax, one expert a token by ``pi + beta``) and the held
    experts' product. Returns the layer's output and ``r``, what the next
    layer's router adds."""
    cfg: ZayaConfig
    other_stats: int = 0

    @nn.compact
    def __call__(self, u, carry=None, valid_len=None):
        cfg = self.cfg
        b, t, dm = u.shape
        m, rw, f32 = b * t, cfg.router_width, jnp.float32
        um = u.reshape(m, dm)
        with trace.part(trace.ROUTER):
            real = row_mask(valid_len, b, t).reshape(m)

            def weight(name, shape, std):
                return self.param(name, nn.initializers.normal(std), shape,
                                  f32)

            def product(x, w):
                return jnp.dot(x, w, precision=_HIGHEST)

            r = product(um.astype(f32),
                        weight("router_down", (dm, rw), 0.02)) \
                + weight("router_down_bias", (rw,), 0.02)
            if carry is not None:
                r = r + self.param("carry_scale", _around_one(0.1), (rw,),
                                   f32) * carry
            hid = RMSNorm(cfg.norm_eps, f32, name="router_norm")(r)
            # the MLP's weights keep a unit input at unit scale (1 / sqrt(fan
            # in), and twice that behind a GELU, which halves it), so that the
            # probabilities differ by tenths and not by thousandths
            for i, gain in ((0, 1.0), (1, 2.0)):
                hid = jax.nn.gelu(
                    product(hid, weight(f"router_mlp_{i}", (rw, rw),
                                        gain * rw ** -0.5))
                    + weight(f"router_mlp_{i}_bias", (rw,), 0.02),
                    approximate=False)
            scores = jax.nn.softmax(product(hid, weight(
                "router_out", (rw, cfg.n_routed_experts), 2.0 * rw ** -0.5)),
                axis=-1)
            weights = held_weights(
                self, scores, real, top_k=cfg.top_k, held=cfg.experts_held,
                bias=weight("router_bias", (cfg.n_routed_experts,), 0.02),
                renormalise=False, other_stats=self.other_stats)

        up_shape = (cfg.n_held, dm, cfg.expert_width)
        wg = self.param("experts_gate", normal(), up_shape, cfg.param_dtype)
        wu = self.param("experts_up", normal(), up_shape, cfg.param_dtype)
        wd = self.param("experts_down", normal(),
                        (cfg.n_held, cfg.expert_width, dm), cfg.param_dtype)
        with trace.part(trace.EXPERTS):
            if self.is_initializing():
                routed = jnp.zeros((m, dm), f32)            # no kernel at init
            else:
                routed = gexp.grouped_experts(
                    um, wu.astype(cfg.dtype), wd.astype(cfg.dtype), weights,
                    gate=wg.astype(cfg.dtype))
            return routed.reshape(b, t, dm), r


class ZayaLayer(nn.Module):
    """One layer over the float32 stream ``x``: the attention sublayer, the
    expert sublayer, each joined through its scales and biases. ``first``:
    the model's first layer, whose attention sublayer has the output's pair
    only and whose router is handed no ``carry``."""
    cfg: ZayaConfig
    first: bool = False

    @nn.compact
    def __call__(self, x, carry=None, page_table=None, valid_len=None):
        cfg = self.cfg
        f32 = jnp.float32
        of = len(Zaya.STATS)

        def scaled(name, value):
            # the scale is drawn around 1 and the bias around 0, neither at
            # it: a program without them is not this one. The bias is small
            # beside a sublayer's output (0.3-0.6 a channel at the published
            # widths): at 0.1 the 48 biases are most of the stream by the
            # last layer, every row's logits favour the same few tokens,
            # and served rows reach fewer experts than any real text would
            return value * self.param(
                f"{name}_scale", _around_one(0.1), (cfg.d_model,), f32) \
                + self.param(f"{name}_bias", nn.initializers.normal(0.02),
                             (cfg.d_model,), f32)

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        y = CcaAttention(cfg, (len(experts.STATS), of), name="attn")(
            norm("attn_norm")(x).astype(cfg.dtype), page_table, valid_len)
        # a sublayer's join is filed with the block it closes
        with trace.part(trace.PROJ):
            x = (x if self.first else scaled("attn_stream", x)) \
                + scaled("attn_out", y)
        # float32 as the norm leaves it: the router reads it unrounded (a
        # rounded input flips near-ties), the experts' products round it
        y, carry = RoutedExperts(
            cfg, of - len(experts.STATS), name="moe")(
            norm("moe_norm")(x), carry, valid_len)
        with trace.part(trace.EXPERTS):
            return scaled("moe_stream", x) + scaled("moe_out", y), carry


class Zaya(nn.Module):
    cfg: ZayaConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"k": "paged", "v": "paged", "index": "index",
                   "window": "state"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = experts.STATS + (ATTN_FULL_KEYS, ATTN_ROWS, CCA_ROWS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None):
        cfg = self.cfg
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        # the stream is float32: 2 x layers sums, each behind a scale and a
        # bias, in bfloat16 would round it as many times
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens].astype(jnp.float32)
        carry = None
        for i in range(cfg.n_layers):
            x, carry = ZayaLayer(cfg, i == 0, name=f"layer_{i}")(
                x, carry, page_table, valid_len)
        with trace.part(trace.HEAD):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              emb.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: ZayaConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Zaya(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

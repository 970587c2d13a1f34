"""Brumby-14B-Base (``model_type`` ``brumby``, Manifest AI; 40 layers at 5120):
Qwen3-14B's block with the softmax attention of **every** layer replaced by
**power retention** (arXiv:2507.04239; ``ops/power_retention.py`` has the
equations, the feature map's layout and both programs). A layer, ``u =
RMSNorm(x)``::

    q = rope(norm_d(W_q u)),  k = rope(norm_d(W_k u)),  v = W_v u
    l = log sigmoid(W_G u + gate_bias)            one decay a key-value head
    S <- exp(l) S + phi(k) v^T,   z <- exp(l) z + phi(k)
    y_h = phi(q_h)^T S / (phi(q_h)^T z + d eps)   five query heads read one state
    x <- x + W_o y;   x <- x + W_down(silu(W_gate n) * W_up n),  n = RMSNorm(x)

40 query heads over 8 key-value heads of 128, RMSNorm a head on q and k before
the rotary embedding (rotate-half over the whole head), a SiLU-gated MLP of
17408, untied embeddings, no bias anywhere. **Assumed** (``config.json`` fixes
every width and is silent on the retention's own constants): degree 2, chunks
of 128, the gate as ``logsigmoid`` of one linear map with one output a
key-value head (a gate and a state a query head would be five times the
state), scale ``1 / sqrt(128)``, ``eps`` 1e-6, no norm and no gate on the
layer's output, the state and the normaliser float32. ``gate_bias`` is a
constant of the program, 0 as published (a trained gate reaches long
memories through its weights; a random one is given them by this constant:
``benchmark/models/brumby.py`` says which and why).

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **cache leaves**, a layer: ``S`` ``[slots, 8, 65, 128, 128]`` and ``z``
  ``[slots, 8, 5, 13, 128]`` float32, both ``state``, and an ``index``. **No
  leaf is a pool of pages**: ``kv_layers`` and ``kv_token_bytes`` are 0, and
  the engine builds no pool, keeps no page table and admits by a free slot.
- ``valid_len`` ``[B]``: the real positions of a program. A padded chunk and
  an idle slot advance no state.
- **counts** a round carries out with its tokens (:attr:`Brumby.STATS`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models.experts import row_mask
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.minicpm_sala import GatedMlp, HeadNorm, _sow_counts
from lzy_tpu.models.paged_blocks import dense, into_heads, normal
from lzy_tpu.ops import power_retention as retention
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

RETENTION_ROWS = REGISTRY.counter(
    "lzy_retention_rows_total",
    "real rows of decode rounds whose power-retention state a layer moved, "
    "a layer")


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    d_model: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    #: assumed: the retention's constants (the module's docstring)
    degree: int = 2
    chunk_size: int = 128
    retention_eps: float = 1e-6
    gate_bias: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    #: the state's and the normaliser's type (``ops/power_retention.py``
    #: takes float32)
    state_dtype: Any = jnp.float32
    # serving: a state a slot, no pool
    decode_paged: bool = False

    def __post_init__(self):
        if self.degree != 2:
            raise ValueError(
                f"power retention of degree {self.degree}: the feature map "
                f"of ops/power_retention.py is the symmetric square")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                "query heads divide into their key-value heads, and a head "
                "into two halves (the rotary embedding, the feature tiles)")
        if jnp.dtype(self.state_dtype) != jnp.float32:
            raise ValueError(
                f"the state and the normaliser are float32; a "
                f"{jnp.dtype(self.state_dtype)} state is a different "
                f"configuration")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "BrumbyConfig":
        """The published ``config.json`` keys as this configuration. What the
        program cannot honour is refused by name."""
        served = {
            "attention_bias": (False, None), "hidden_act": ("silu",),
            "rope_scaling": (None,), "sliding_window": (None,),
            "use_sliding_window": (False, None),
            "tie_word_embeddings": (False,),
        }
        for key, values in served.items():
            if doc.get(key) not in values:
                raise ValueError(
                    f"BrumbyConfig serves {key} in {values!r} (no bias, no "
                    f"window, no rotary scaling, SiLU, untied embeddings); "
                    f"the configuration says {key} = {doc.get(key)!r}")
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=doc["num_hidden_layers"],
            n_heads=doc["num_attention_heads"],
            n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
            d_ff=doc["intermediate_size"],
            rope_theta=float(doc["rope_theta"]),
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def kv_layers(self) -> int:
        """Layers that write a paged pool: none."""
        return 0

    @property
    def state_bytes(self) -> int:
        """Bytes of ``S`` and ``z`` a slot, all layers."""
        s, z = retention.state_shapes(1, self.n_kv_heads, self.head_dim)
        return self.n_layers * 4 * (math.prod(s) + math.prod(z))

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "BrumbyConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise ValueError(
                "kv_quant: this model keeps no paged pool to quantise (its "
                "cache is a float32 state a slot)")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str]):
        """``page_size``, ``kv_pages`` and ``kernel`` size and read a pool
        this model does not have."""
        self._refuse_quant(kv_quant)
        return Brumby(dataclasses.replace(self, decode_paged=True))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """A cached token costs no page: 0."""
        self._refuse_quant(kv_quant)
        return 0

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of a program over ``t``
        positions a row. There is no pool to read: the label is the
        retention's own program, the model's one access to its cache."""
        return retention.UPDATE_PATH if t == 1 else retention.SCAN_PATH

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket (two chunks of
        the scan; dense products beside it)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """Labels beside ``read_path``'s: none, the retention is counted
        there."""
        return ()

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower both kernels for a TPU (no device, no compile), the update
        at the decode step's shapes and the chunk scan at one row of the
        widest program: refused here, not at the first request."""
        self._refuse_quant(kv_quant)
        heads = dict(heads=self.n_heads, kv_heads=self.n_kv_heads,
                     head_dim=self.head_dim, dtype=self.dtype)
        retention.lower_update_for_tpu(batch=slots, **heads)
        retention.lower_chunk_for_tpu(
            batch=1, t=self.widest_prefill, chunk=self.chunk_size, **heads)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "BrumbyConfig":
        """Every mechanism at a size the CPU tests run: three layers, 6 query
        heads over 2 key-value heads of 16 (9 tiles of 16 features, one step
        of the update's grid), chunks of 16."""
        return BrumbyConfig(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=6,
            n_kv_heads=2, head_dim=16, d_ff=128, rope_theta=1e4,
            max_seq_len=512, chunk_size=16, dtype=jnp.float32,
            param_dtype=jnp.float32)


class PowerRetention(nn.Module):
    """A layer's mixer. ``stats`` ``(at, of)``: where the layer's count of
    the rows it moved goes in the ``stats`` vector, and its length."""
    cfg: BrumbyConfig
    stats: Tuple[int, int] = (0, 1)

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        f32 = jnp.float32
        # float32 out of the accumulator: the norms a head read it
        with trace.part(trace.PROJ):
            q = into_heads(dense(h * d, "q_proj", cfg, f32)(u), b, t, h, d)
            k = into_heads(dense(kv * d, "k_proj", cfg, f32)(u), b, t, kv, d)
            v = into_heads(dense(kv * d, "v_proj", cfg)(u), b, t, kv, d)
            # one decay a key-value head: the group's query heads read one
            # state
            log_g = jax.nn.log_sigmoid(
                dense(kv, "g_proj", cfg, f32)(u) + cfg.gate_bias)
            q = HeadNorm(cfg.norm_eps, name="q_norm")(q)
            k = HeadNorm(cfg.norm_eps, name="k_norm")(k)
        cached = cfg.decode_paged
        s_shape, z_shape = retention.state_shapes(b, kv, d)
        if cached:
            s = self.variable("cache", "S", jnp.zeros, s_shape,
                              cfg.state_dtype)
            z = self.variable("cache", "z", jnp.zeros, z_shape,
                              cfg.state_dtype)
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start, carried = index.value, (s.value, z.value)
        else:
            start = jnp.zeros((b,), jnp.int32)
            carried = (jnp.zeros(s_shape, f32), jnp.zeros(z_shape, f32))
        with trace.part(trace.PROJ):
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
            # the products take q, k and v rounded to the activations' type
            q = _rope(q, pos, cfg.rope_theta).astype(cfg.dtype)
            k = _rope(k, pos, cfg.rope_theta).astype(cfg.dtype)
        with trace.part(trace.STATE):
            real = row_mask(valid_len, b, t)                         # [B, T]
            if cached and t == 1 and not self.is_initializing():
                y, *new = retention.retention_state_update(
                    *carried, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                    real[:, 0], eps=cfg.retention_eps)
                y = y[:, None]
                self._count(real[:, 0])
            else:
                y, *new = retention.retention_chunk_scan(
                    q, k, v, log_g, *carried, real, chunk=cfg.chunk_size,
                    eps=cfg.retention_eps)
            if cached and not self.is_initializing():
                s.value, z.value = new
                index.value = index.value + t
        # float32 out of the accumulator: it joins the residual stream
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "o_proj", cfg, f32)(
                y.astype(cfg.dtype).reshape(b, t, h * d))

    def _count(self, live):
        at, of = self.stats
        _sow_counts(self, "retention",
                    jnp.zeros((of,), jnp.int32).at[at].set(jnp.sum(live)))


class Brumby(nn.Module):
    cfg: BrumbyConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"S": "state", "z": "state", "index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = (RETENTION_ROWS,)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None):
        """``page_table`` is what an engine hands every model; there is no
        pool for it to address, and it is not read."""
        cfg = self.cfg
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        # the stream is float32: 2 x layers sums in bfloat16 would round it
        # as many times (the products take it rounded to their type)
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens].astype(jnp.float32)
        for i in range(cfg.n_layers):
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_norm")(x).astype(cfg.dtype)
            y = PowerRetention(cfg, (0, len(self.STATS)),
                               name=f"layer_{i}")(u, valid_len)
            # a residual sum is filed with the block it closes
            with trace.part(trace.PROJ):
                x = x + y
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_mlp_norm")(x).astype(cfg.dtype)
            with trace.part(trace.FFN):
                x = x + GatedMlp(cfg, name=f"layer_{i}_mlp")(u)
        with trace.part(trace.HEAD):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            head = self.param("lm_head", normal(0.02),
                              (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: BrumbyConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Brumby(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

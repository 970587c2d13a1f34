"""Jamba (``model_type`` ``jamba``; ``AI21-Jamba2-3B``: 28 blocks at 2560): a
hybrid decoder whose mixers are **Mamba-1** selective state-space layers and,
one block in ``attn_period`` (at ``attn_offset``), grouped-query attention
with no positional embedding (20 query heads over one key-value head of
128). Every block is ``h = x + mixer(RMSNorm(x))``, ``h + MLP(RMSNorm(h))``
with a SwiGLU MLP (``num_experts`` 1: no sparse experts at this size);
embeddings are tied. The residual stream ``x`` is float32 whatever the
activations are (the products take it rounded to their type).

The Mamba-1 mixer (``D`` hidden, ``Di = expand x D`` channels, ``N`` state
entries a channel, ``R`` the step's rank, ``K`` the convolution's taps)::

    [xs, z]    = in_proj(u)                         D -> 2 Di
    xc         = silu(causal depthwise conv1d(xs, K) + bias)
    [dt, B, C] = x_proj(xc)                         Di -> R + 2N
    dt, B, C   = RMSNorm(dt), RMSNorm(B), RMSNorm(C)    (Jamba's addition)
    dt         = softplus(dt_proj(dt) + dt_bias)    R -> Di
    S_t[n, c]  = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] xc_t[c]
    y_t[c]     = sum_n S_t[n, c] C_t[n] + D[c] xc_t[c]
    out        = out_proj(y * silu(z))              Di -> D

with ``A = -exp(A_log)`` a decay for each of ``Di x N`` state entries: there
is no matrix-product form of that recurrence (``ops/mamba1.py``), where
Mamba-2's scalar a head has one (``models/nemotron_h.py``).

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **cache leaves of three kinds** (:attr:`Jamba.CACHE_KINDS`). The attention
  layers keep keys and values in the shared paged pool (``k``, ``v``: kind
  ``paged``, ``[pages, page, KV, D]``, read as ``[pages, page, KV x D]`` by
  ``ops/paged_attention.py`` ``paged_group_attention``: the block is
  ``models/paged_blocks.py``'s, told ``group_read``), and an ``index`` of
  tokens resident a row. A Mamba layer keeps **per-slot state**: ``conv``
  ``[slots, K - 1, Di]``, the last inputs of its convolution in the
  activations' type, and ``ssm`` ``[slots, N, Di]`` in float32, the
  recurrence's state with the channels on the lanes (kind ``state``: two
  leaves a Mamba layer, 52 at the published depth).
- ``valid_len`` ``[B]``: how many of a row's ``T`` positions are real. A
  Mamba layer freezes its state past it (``dt`` = 0, the convolution's
  window taken at the last real position), so neither a padded prefill chunk
  nor an idle decode slot advances a recurrence.
- **counts** a round carries out with its tokens (:attr:`Jamba.STATS`): the
  live rows whose state a Mamba layer moved, the cached keys the real rows
  read in the attention layers, and those rows, a layer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models.experts import row_mask
from lzy_tpu.models.llama import RMSNorm
from lzy_tpu.models.paged_blocks import (
    ATTN_FULL_KEYS, ATTN_ROWS, PagedAttention, dense, inv_softplus)
from lzy_tpu.models.serving import HeadPool
from lzy_tpu.ops import mamba1
from lzy_tpu.ops.paged_attention import group_path, lower_group_for_tpu
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

SSM_ROWS = REGISTRY.counter(
    "lzy_ssm_rows_total",
    "live rows of decode rounds whose recurrence state a Mamba layer moved, "
    "a layer")

ATTENTION, MAMBA = "attention", "mamba"


@dataclasses.dataclass(frozen=True)
class JambaConfig(HeadPool):
    vocab_size: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    #: layer ``i`` is attention where ``i % attn_period == attn_offset``
    attn_period: int = 14
    attn_offset: int = 7
    # attention: no positional embedding, no bias
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    #: no output gate on the heads; the pools read by
    #: ``paged_group_attention`` (``models/paged_blocks.py`` reads both)
    attn_gate: bool = False
    group_read: bool = True
    d_ff: int = 8192
    # Mamba-1
    mamba_expand: int = 2
    ssm_state: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: keys and values in a shared paged pool, state a slot
    decode_paged: bool = False
    kv_page_size: int = 128
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError(
                f"attn_offset {self.attn_offset} outside a period of "
                f"{self.attn_period}")
        if ATTENTION not in self.layer_kinds:
            raise ValueError(
                f"no attention layer among {self.n_layers} (period "
                f"{self.attn_period}, offset {self.attn_offset}): the "
                f"engine tells a live row from an idle one by its pages")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into their groups")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "JambaConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name. ``head_dim`` is not
        published: ``hidden_size / num_attention_heads`` unless the
        document gives one."""
        served = {
            "num_experts": (1,), "num_experts_per_tok": (1,),
            "hidden_act": ("silu",), "mamba_conv_bias": (True,),
            "mamba_proj_bias": (False,), "sliding_window": (None,),
            "tie_word_embeddings": (True,),
        }
        for key, values in served.items():
            if doc.get(key) not in values:
                raise ValueError(
                    f"JambaConfig serves {key} in {values!r} (one SwiGLU "
                    f"MLP a block, a biased convolution, bias-free "
                    f"projections, attention over everything, tied "
                    f"embeddings); the configuration says {key} = "
                    f"{doc.get(key)!r}")
        heads = doc["num_attention_heads"]
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=doc["num_hidden_layers"],
            attn_period=doc["attn_layer_period"],
            attn_offset=doc["attn_layer_offset"], n_heads=heads,
            n_kv_heads=doc["num_key_value_heads"],
            head_dim=doc.get("head_dim") or doc["hidden_size"] // heads,
            d_ff=doc["intermediate_size"],
            mamba_expand=doc["mamba_expand"],
            ssm_state=doc["mamba_d_state"], dt_rank=doc["mamba_dt_rank"],
            conv_kernel=doc["mamba_d_conv"],
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer, :data:`ATTENTION` or :data:`MAMBA`."""
        return tuple(
            ATTENTION if i % self.attn_period == self.attn_offset else MAMBA
            for i in range(self.n_layers))

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: what sizes it."""
        return self.layer_kinds.count(ATTENTION)

    @property
    def mamba_layers(self) -> int:
        return self.layer_kinds.count(MAMBA)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "JambaConfig":
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
        return Jamba(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the attention read
        of a program over ``t`` positions a row: the group read's."""
        return group_path(kernel, t=t)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket. A program
        reads every weight (6 GB at the Jamba2-3B widths) whatever its
        width, and the scan's and the chunk read's work grow with its rows
        as the dense products do."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the attention read's own."""
        if not self.mamba_layers:
            return ()
        return (mamba1.UPDATE_PATH if t == 1 else mamba1.SCAN_PATH,)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower this model's kernels for a TPU (no device, no compile) at
        the decode step's shapes and the widest chunk's: the state update
        over every slot, the scan of one row and, with a pool named, the
        attention read of each."""
        self._refuse_quant(kv_quant)
        if kv_blocks is not None:
            for batch, t in ((slots, 1), (1, self.widest_prefill)):
                lower_group_for_tpu(
                    batch=batch, t=t, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                    n_blocks=kv_blocks, page_size=page_size,
                    pages_per_seq=pages_per_seq, dtype=self.dtype,
                    window=None)
        if self.mamba_layers:
            mamba1.lower_update_for_tpu(
                batch=slots, channels=self.d_inner,
                state_size=self.ssm_state)
            mamba1.lower_scan_for_tpu(
                batch=1, t=self.widest_prefill, channels=self.d_inner,
                state_size=self.ssm_state)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "JambaConfig":
        """Every mechanism at a size the CPU tests run: one period of 14
        layers with attention at offset 7, 5 query heads over 1 (a group
        that is no multiple of 8), 128 channels of 16 state entries."""
        return JambaConfig(
            vocab_size=vocab_size, d_model=64, n_layers=14, attn_period=14,
            attn_offset=7, n_heads=5, n_kv_heads=1, head_dim=16, d_ff=128,
            mamba_expand=2, ssm_state=16, dt_rank=8, conv_kernel=4,
            max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32,
            kv_page_size=8)


def _uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    return init


class Mamba1Mixer(nn.Module):
    cfg: JambaConfig
    #: where this layer's count goes in the ``stats`` vector, and its length
    stats: Tuple[int, int] = (0, 1)

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        di, n, r, k = (cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                       cfg.conv_kernel)
        f32 = jnp.float32

        with trace.part(trace.PROJ):
            xz = dense(2 * di, "in_proj", cfg)(u)
            # the convolution's inputs are kept a row (the conv state), in the
            # activations' dtype: round them before use, in prefill and decode
            xs = xz[..., :di].astype(cfg.dtype)
            z = xz[..., di:].astype(f32)

        # a depthwise convolution of K taps starts uniform in
        # +-1 / sqrt(K), weight and bias (the published Mamba's Conv1d)
        conv_w = self.param("conv_kernel", _uniform(k ** -0.5), (k, di), f32)
        conv_b = self.param("conv_bias", _uniform(k ** -0.5), (di,), f32)
        # dt = softplus(dt_proj(.) + dt_bias) starts log-uniform in
        # [0.001, 0.1]
        dt_bias = self.param(
            "dt_bias", lambda key, shape: inv_softplus(jnp.exp(
                jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                   jnp.log(1e-1)))), (di,))
        # A = -(1 .. N) a channel, stored with the channels on the lanes
        a_log = self.param(
            "A_log", lambda key, shape: jnp.broadcast_to(jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=f32))[:, None], shape),
            (n, di))
        d_skip = self.param("D", nn.initializers.ones, (di,), f32)

        cached = cfg.decode_paged
        if cached:
            conv_state = self.variable("cache", "conv", jnp.zeros,
                                       (b, k - 1, di), cfg.dtype)
            ssm_state = self.variable("cache", "ssm", jnp.zeros,
                                      (b, n, di), f32)
            prev, state = conv_state.value, ssm_state.value
        else:
            prev = jnp.zeros((b, k - 1, di), cfg.dtype)
            state = jnp.zeros((b, n, di), f32)

        with trace.part(trace.STATE):
            real = row_mask(valid_len, b, t)                         # [B, T]
            seq = jnp.concatenate([prev, xs], axis=1)              # [B, T+k-1]
            xc = jax.nn.silu(conv_b + sum(
                conv_w[i] * seq[:, i:i + t].astype(f32) for i in range(k)))

        # float32 out of the accumulator: dt steers an exponential, and B
        # and C weigh a state carried over thousands of positions
        with trace.part(trace.PROJ):
            dbc = dense(r + 2 * n, "x_proj", cfg, f32)(xc)
            norm = lambda name: RMSNorm(cfg.norm_eps, cfg.param_dtype,
                                        name=name)
            dt_r = norm("dt_norm")(dbc[..., :r])
            bm = norm("b_norm")(dbc[..., r:r + n])
            cm = norm("c_norm")(dbc[..., r + n:])
            dt = jnp.where(
                real[..., None],
                jax.nn.softplus(
                    dense(di, "dt_proj", cfg, f32)(dt_r) + dt_bias),
                0.0)                                               # [B, T, Di]
            a = -jnp.exp(a_log)

        with trace.part(trace.STATE):
            if cached and t == 1:
                y, new_state = mamba1.selective_state_update(
                    state, xc[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
                y = y[:, None]
            else:
                # a served chunk through the kernel (on the CPU under the
                # interpreter, as the update is); the uncached forward by lax
                y, new_state = mamba1.selective_scan(
                    xc, dt, a, bm, cm, state,
                    kernel="pallas" if cached else "lax")
            if cached and not self.is_initializing():
                ssm_state.value = new_state
                # the window that ends at the last real position
                ends = jnp.full((b,), t, jnp.int32) if valid_len is None \
                    else valid_len.astype(jnp.int32)
                conv_state.value = jax.vmap(
                    lambda s, e: jax.lax.dynamic_slice_in_dim(s, e, k - 1, 0)
                )(seq, ends)
                at, of = self.stats
                self.sow("stats", "ssm",
                         jnp.zeros((of,), jnp.int32).at[at].set(
                             jnp.sum(real[:, 0])),
                         reduce_fn=lambda acc, x: acc + x,
                         init_fn=lambda: jnp.zeros((of,), jnp.int32))

            y = (y + d_skip * xc) * jax.nn.silu(z)
        # float32 out of the accumulator: it joins the residual stream
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "out_proj", cfg, f32)(
                y.astype(cfg.dtype))


class GatedMlp(nn.Module):
    """``down(silu(gate(h)) * up(h))``, no bias."""
    cfg: JambaConfig

    @nn.compact
    @trace.part(trace.FFN)
    def __call__(self, h):
        cfg = self.cfg
        f32 = jnp.float32
        hid = jax.nn.silu(dense(cfg.d_ff, "gate_proj", cfg, f32)(h)) \
            * dense(cfg.d_ff, "up_proj", cfg, f32)(h)
        return dense(cfg.d_model, "down_proj", cfg, f32)(
            hid.astype(cfg.dtype))


class Jamba(nn.Module):
    cfg: JambaConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"k": "paged", "v": "paged", "index": "index",
                   "conv": "state", "ssm": "state"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = (SSM_ROWS, ATTN_FULL_KEYS, ATTN_ROWS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None):
        cfg = self.cfg
        of = len(self.STATS)
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        # the residual stream is float32 (Mamba's reference keeps it so,
        # ``residual_in_fp32``): 56 sums in bfloat16 would round a stream
        # that grows with depth 56 times, more error than everything else
        # the activations' type costs; the products take it rounded once
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens].astype(jnp.float32)
        for i, kind in enumerate(cfg.layer_kinds):
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_norm")(x)
            if kind == MAMBA:
                y = Mamba1Mixer(cfg, (0, of), name=f"layer_{i}")(
                    u, valid_len)
            else:
                y = PagedAttention(cfg, (1, of), name=f"layer_{i}")(
                    u, page_table, valid_len)
            # a residual sum is filed with the block it closes
            with trace.part(trace.PROJ):
                x = x + y.astype(jnp.float32)
            h = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_mlp_norm")(x)
            with trace.part(trace.FFN):
                x = x + GatedMlp(cfg, name=f"layer_{i}_mlp")(h)
        with trace.part(trace.HEAD):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            return jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                              emb.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)


def init_params(cfg: JambaConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Jamba(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

"""MiniCPM-SALA (``model_type`` ``minicpm_sala``; 32 layers at 4096): a
decoder whose mixers are **block-sparse attention** (``minicpm4``: MiniCPM4's
InfLLM-v2, arXiv:2506.07900 section 2.2; 8 of 32 layers) and **lightning
linear attention** (``lightning-attn``: arXiv:2401.04658; 24 of 32), in the
order ``mixer_types`` gives, each followed by a SiLU-gated MLP. With ``s =
scale_depth / sqrt(L)`` (``L`` the published depth, whatever is built)::

    h0 = scale_emb * E[token]
    h  = h + s * mixer(RMSNorm(h));   h = h + s * MLP(RMSNorm(h))
    logits = head(RMSNorm(h)) / (hidden_size / dim_model_base)

``minicpm4``, ``u = RMSNorm(h)``: 32 query heads of 128 in 2 key-value groups
of 16, RMSNorm a head on q and k, **no rotary embedding**, softmax at
``128^-1/2``, ``o * sigmoid(gate(u))``, ``o_proj``. A request admitted with a
prompt of ``dense_len`` tokens or more is **sparse** for as long as it lives:
each of its queries reads the first block, the 32 blocks that end at its own
and the 64 best of the rest by the compressed keys' scores, a group's own
choice (``ops/sparse_attention.py`` has the rule and the reads). A shorter
request reads everything.

``lightning-attn``: 32 heads of 128, as many key-value heads; RMSNorm a head
on q and k, rotary embedding on both (the whole head), ``S_t = lambda_h
S_{t-1} + k_t v_t^T``, ``o_t = q_t^T S_t / sqrt(128)``, RMSNorm on ``o``, ``o
* sigmoid(gate(u))``, ``o_proj``. **Assumed** (the published config has no key
for them): ``lambda_h = exp(-2^(-8 (h + 1) / H))``, the same in every layer;
no activation on q, k, v; a full-width gate. The recurrence is Mamba-2's with
a constant decay a head, no ``dt``, no skip and a ``B`` and ``C`` a head, and
runs on ``ops/mamba2.py``: ``ssd_chunk_scan`` over a chunk, its update kernel
(a head's own ``B`` and ``C``, under the name ``lightning_state_update``) for
a decode round.

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **cache leaves.** A sparse layer: ``k``, ``v`` ``[pages, KV, page, D]`` and
  ``ck`` ``[pages, page / 16, KV, D]`` float32, the compressed keys, all
  ``paged``: one page table addresses the three (the engine moves pages by
  block id and reads no shape past the page axis), and ``kv_token_bytes``
  counts the compressed keys' share; an ``index``. A lightning layer:
  ``state`` ``[slots, 32, 128, 128]`` float32 (kind ``state``) and an
  ``index``. The model: ``sparse`` ``[slots]`` int32 (kind ``state``): 1 for a
  slot whose request selects.
- ``valid_len`` ``[B]``: the real positions of a program. A padded chunk and
  an idle slot advance no state and complete no compressed key.
- ``prompt_len`` ``[B]`` (``TOLD_PROMPT_LEN``): told to prefill programs, the
  length of the prompt the request was admitted with: what fixes its mode. A
  decode round reads the mode from the slot's ``sparse`` row.
- **counts** a round carries out with its tokens (:attr:`MiniCPMSala.STATS`).
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
from lzy_tpu.models.paged_blocks import dense, into_heads, normal
from lzy_tpu.ops import mamba2
from lzy_tpu.ops import sparse_attention as sparse
from lzy_tpu.ops.sparse_attention import SparseSpec
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

SPARSE_VISIBLE = REGISTRY.counter(
    "lzy_sparse_blocks_visible_total",
    "key blocks the selecting rows of decode rounds could have read (a row "
    "at position p sees p // block + 1), a key-value group a layer")
SPARSE_READ = REGISTRY.counter(
    "lzy_sparse_blocks_read_total",
    "key blocks the selecting rows of decode rounds chose and read, a "
    "key-value group a layer")
SPARSE_ROWS = REGISTRY.counter(
    "lzy_sparse_rows_total",
    "real rows of decode rounds that chose their key blocks, a layer")
SPARSE_DENSE_ROWS = REGISTRY.counter(
    "lzy_sparse_dense_rows_total",
    "real rows of decode rounds that a sparse layer served densely (a "
    "request admitted under dense_len), a layer")
LIGHTNING_ROWS = REGISTRY.counter(
    "lzy_lightning_rows_total",
    "real rows of decode rounds whose decayed state a lightning layer "
    "moved, a layer")

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    d_model: int = 4096
    #: a mixer a layer, in order
    mixer_types: Tuple[str, ...] = (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,)
    #: the published depth: the residual scale is ``scale_depth / sqrt`` of
    #: it, whatever ``mixer_types`` keeps
    depth: int = 32
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    d_ff: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    #: MiniCPM4's published ``sparse_config``
    sparse: SparseSpec = SparseSpec()
    dense_len: int = 8192
    max_seq_len: int = 524288
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    #: the recurrence state's type (``ops/mamba2.py`` takes float32)
    state_dtype: Any = jnp.float32
    chunk_size: int = 128
    # serving: keys, values and compressed keys in a shared paged pool
    decode_paged: bool = False
    kv_page_size: int = 64
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        if set(self.mixer_types) - {SPARSE, LIGHTNING} \
                or not self.mixer_types:
            raise ValueError(
                f"mixer_types {sorted(set(self.mixer_types))}: a layer's "
                f"mixer is {SPARSE!r} or {LIGHTNING!r}")
        if self.n_heads % self.n_kv_heads or self.lightning_heads % 8:
            raise ValueError(
                "query heads divide into their groups, and the lightning "
                "heads into eights (ops/mamba2.py's update walks eight a "
                "cell)")
        self.sparse.check()
        if self.decode_paged and self.kv_page_size != self.sparse.block_size:
            raise ValueError(
                f"a selector block is a page: page_size "
                f"{self.kv_page_size} against block_size "
                f"{self.sparse.block_size}")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "MiniCPMSalaConfig":
        """The published ``config.json`` keys as this configuration. What the
        program cannot honour is refused by name. ``sparse_config`` (a
        deployment's: MiniCPM4's published one where absent) and
        ``published_depth`` (the depth the residual scale is reckoned from,
        where ``num_hidden_layers`` was cut) are not published keys."""
        served = {
            "attention_bias": (False,), "attn_use_rope": (False,),
            "hidden_act": ("silu",), "lightning_scale": ("1/sqrt(d)",),
            "lightning_use_rope": (True,), "qk_norm": (True,),
            "tie_word_embeddings": (False,), "use_output_gate": (True,),
            "use_output_norm": (True,), "attn_use_output_gate": (True,),
        }
        for key, values in served.items():
            if doc.get(key) not in values:
                raise ValueError(
                    f"MiniCPMSalaConfig serves {key} in {values!r} (no "
                    f"bias, no rotary embedding in the sparse layers and "
                    f"one in the lightning layers, norms on q and k, a "
                    f"norm and a gate on a mixer's output, SiLU, untied "
                    f"embeddings); the configuration says {key} = "
                    f"{doc.get(key)!r}")
        if doc["lightning_nkv"] != doc["lightning_nh"]:
            raise ValueError(
                f"a lightning head has its own keys and values: "
                f"lightning_nkv {doc['lightning_nkv']} against lightning_nh "
                f"{doc['lightning_nh']}")
        if len(doc["mixer_types"]) != doc["num_hidden_layers"]:
            raise ValueError(
                f"{len(doc['mixer_types'])} mixer_types for "
                f"{doc['num_hidden_layers']} layers")
        sc = dict(doc.get("sparse_config") or {})
        dense_len = sc.pop("dense_len", cls.dense_len)
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            mixer_types=tuple(doc["mixer_types"]),
            depth=doc.get("published_depth", doc["num_hidden_layers"]),
            n_heads=doc["num_attention_heads"],
            n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
            lightning_heads=doc["lightning_nh"],
            lightning_head_dim=doc["lightning_head_dim"],
            d_ff=doc["intermediate_size"], scale_emb=float(doc["scale_emb"]),
            scale_depth=float(doc["scale_depth"]),
            dim_model_base=doc["dim_model_base"],
            rope_theta=float(doc["rope_theta"]),
            norm_eps=float(doc["rms_norm_eps"]), sparse=SparseSpec(**sc),
            dense_len=dense_len,
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: the sparse ones."""
        return self.mixer_types.count(SPARSE)

    @property
    def lightning_layers(self) -> int:
        return self.mixer_types.count(LIGHTNING)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth)

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "MiniCPMSalaConfig":
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
        return MiniCPMSala(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    def kv_token_bytes(self, kv_quant: Optional[str] = None) -> int:
        """Bytes one cached token costs one sparse layer: keys and values a
        head, and its sixteenth of a float32 compressed key a head."""
        self._refuse_quant(kv_quant)
        each = self.n_kv_heads * self.head_dim
        return 2 * each * jnp.dtype(self.dtype).itemsize \
            + each * 4 // self.sparse.kernel_stride

    def read_path(self, kernel: str, *, t: int,
                  kv_quant: Optional[str] = None) -> str:
        """``lzy_kernel_dispatch_total{path}`` label of the sparse layers'
        read of a program over ``t`` positions a row."""
        return sparse.read_path(kernel, t=t)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket (dense
        products, and reads whose arithmetic grows with their rows)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """``lzy_kernel_dispatch_total{path}`` labels of a program over
        ``t`` positions a row, beside the read's own: the selector and the
        lightning recurrence."""
        paths = []
        if self.kv_layers:
            paths.append(sparse.SELECT_DECODE_PATH if t == 1
                         else sparse.SELECT_PREFILL_PATH)
        if self.lightning_layers:
            paths.append(mamba2.UPDATE_PATH if t == 1 else mamba2.SCAN_PATH)
        return tuple(paths)

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower this model's kernels for a TPU at the decode step's shapes
        (no device, no compile): refused here, not at the first request.
        With a pool named, the selector and both reads over it too."""
        self._refuse_quant(kv_quant)
        if kv_blocks is not None and self.kv_layers:
            for batch, t in ((slots, 1), (1, self.widest_prefill)):
                sparse.lower_for_tpu(
                    batch=batch, t=t, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                    n_blocks=kv_blocks, pages_per_seq=pages_per_seq,
                    dtype=self.dtype, spec=self.sparse)
        if self.lightning_layers:
            mamba2.lower_update_for_tpu(
                batch=slots, heads=self.lightning_heads,
                head_dim=self.lightning_head_dim,
                state_size=self.lightning_head_dim,
                groups=self.lightning_heads)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MiniCPMSalaConfig":
        """Every mechanism at a size the CPU tests run: five layers (sparse
        first and last, three lightning between), 4 query heads over 2 of
        16, 8 lightning heads of 16; compressed keys of 8 positions at
        stride 4, blocks and pages of 16, the first block, a window of 2
        blocks and the 4 best of the rest, ``dense_len`` 128: a 300-token
        prompt sees 19 blocks and reads 7."""
        return MiniCPMSalaConfig(
            vocab_size=vocab_size, d_model=64,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING, LIGHTNING, SPARSE),
            depth=8, n_heads=4, n_kv_heads=2, head_dim=16,
            lightning_heads=8, lightning_head_dim=16, d_ff=128,
            dim_model_base=32, rope_theta=1e4,
            sparse=SparseSpec(kernel_size=8, kernel_stride=4, block_size=16,
                              topk=4, init_blocks=1, window_size=32),
            dense_len=128, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32, chunk_size=16, kv_page_size=16)


def lightning_decay(heads: int) -> jax.Array:
    """``log lambda_h = -2^(-8 (h + 1) / H)``: the slopes of Lightning
    Attention, the same in every layer (assumed: the published
    configuration has no decay key)."""
    return -jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                     / heads)


class HeadNorm(nn.Module):
    """RMSNorm over a head's entries with a learned scale, in float32. The
    scale is drawn around 1 (1 + 0.1 N), not at 1: a program without it is
    not this one."""
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", lambda key, shape: 1.0 + 0.1 * jax.random.normal(
                key, shape, jnp.float32), (x.shape[-1],))
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale


def _head_norm(cfg, name: str):
    return HeadNorm(cfg.norm_eps, name=name)


@trace.part(trace.PROJ)
def _gated_out(cfg, out, u):
    """``o * sigmoid(gate(u))``, then ``o_proj``; float32 out of the
    accumulator: it joins the residual stream."""
    gate = dense(out.shape[-1], "gate_proj", cfg, jnp.float32)(u)
    out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(cfg.dtype)
    return dense(cfg.d_model, "o_proj", cfg, jnp.float32)(out)


def _sow_counts(module, name: str, counts):
    """A layer's places of the ``stats`` vector, summed over its calls."""
    module.sow("stats", name, counts, reduce_fn=lambda a, x: a + x,
               init_fn=lambda: jnp.zeros(counts.shape, counts.dtype))


class SparseAttention(nn.Module):
    """A ``minicpm4`` layer's mixer. ``selects`` ``[B]`` bool: the rows
    whose requests choose their blocks. ``stats`` ``(at, of)``: where this
    layer's four counts go in the ``stats`` vector, and its length."""
    cfg: MiniCPMSalaConfig
    stats: Tuple[int, int] = (0, 5)

    @nn.compact
    def __call__(self, u, page_table=None, valid_len=None, selects=None):
        cfg = self.cfg
        spec = cfg.sparse
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        # float32 out of the accumulator: the norms a head read it
        with trace.part(trace.PROJ):
            q = into_heads(dense(h * d, "q_proj", cfg, jnp.float32)(u),
                           b, t, h, d)
            k = into_heads(dense(kv * d, "k_proj", cfg, jnp.float32)(u),
                           b, t, kv, d)
            v = into_heads(dense(kv * d, "v_proj", cfg)(u), b, t, kv, d)
            # the selector reads the query as the norm leaves it: rounded to
            # the products' type it flips near-ties between block scores
            q32 = _head_norm(cfg, "q_norm")(q)
            q = q32.astype(cfg.dtype)
            k = _head_norm(cfg, "k_norm")(k).astype(cfg.dtype)
        if not cfg.decode_paged:
            if t >= cfg.dense_len:
                raise ValueError(
                    f"the uncached forward reads everything: {t} positions "
                    f"reach dense_len {cfg.dense_len}")
            with trace.part(trace.ATTN_READ):
                qg = q.reshape(b, t, kv, h // kv, d)
                s = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                               preferred_element_type=jnp.float32) * d ** -0.5
                keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
                out = jnp.einsum("bkgtl,blkd->btkgd", pr.astype(cfg.dtype), v)
            return _gated_out(cfg, out.reshape(b, t, h * d), u)

        kv_shape, ck_shape = sparse.pool_shapes(cfg.kv_pages, kv, d, spec)
        pool_k = self.variable("cache", "k", jnp.zeros, kv_shape, cfg.dtype)
        pool_v = self.variable("cache", "v", jnp.zeros, kv_shape, cfg.dtype)
        pool_ck = self.variable("cache", "ck", jnp.zeros, ck_shape,
                                jnp.float32)
        index = self.variable("cache", "index",
                              lambda: jnp.zeros((b,), jnp.int32))
        start = index.value
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
        if self.is_initializing():
            return _gated_out(cfg, jnp.zeros((b, t, h * d), cfg.dtype),
                              u)
        if page_table is None:
            raise ValueError("a paged forward needs page_table")
        with trace.part(trace.CACHE_WRITE):
            n_real = jnp.full((b,), t, jnp.int32) if valid_len is None \
                else valid_len.astype(jnp.int32)
            live = n_real > 0
            selects = jnp.zeros((b,), bool) if selects is None \
                else selects & live
            pool_k.value = sparse.scatter_kv(pool_k.value, page_table, pos, k)
            pool_v.value = sparse.scatter_kv(pool_v.value, page_table, pos, v)
            pool_ck.value = sparse.compress_keys(
                pool_k.value, pool_ck.value, page_table, start, n_real, t=t,
                spec=spec)
            index.value = index.value + t
        chosen = sparse.select_blocks(
            q32, pool_ck.value, page_table, pos, selects, spec=spec,
            kernel=cfg.paged_kernel)
        # what a (query, group) chose: read by tests, dropped by a program
        self.sow("choices", "chosen", chosen)
        if t == 1:
            out = sparse.sparse_decode_attention(
                q, pool_k.value, pool_v.value, page_table, pos,
                chosen[:, :, 0], live, kernel=cfg.paged_kernel,
                dtype=cfg.dtype)
            with trace.part(trace.ATTN_READ):
                self._count(chosen[:, :, 0], pos[:, 0], selects, live)
        else:
            out = sparse.sparse_prefill_attention(
                q, pool_k.value, pool_v.value, page_table, start, chosen,
                kernel=cfg.paged_kernel, dtype=cfg.dtype)
        return _gated_out(cfg, out.reshape(b, t, h * d), u)

    def _count(self, chosen, pos, selects, live):
        """The layer's counts of a decode round: the blocks its selecting
        rows could have read and did (a group each), those rows, and the
        rows it served densely."""
        at, of = self.stats
        seen = (pos // self.cfg.sparse.block_size + 1) * self.cfg.n_kv_heads
        counts = jnp.zeros((of,), jnp.int32).at[at].set(
            jnp.sum(jnp.where(selects, seen, 0))).at[at + 1].set(
            jnp.sum(chosen & selects[:, None, None])).at[at + 2].set(
            jnp.sum(selects)).at[at + 3].set(jnp.sum(live & ~selects))
        _sow_counts(self, "attn", counts)


class LightningAttention(nn.Module):
    """A ``lightning-attn`` layer's mixer: the decayed outer-product state a
    head, on ``ops/mamba2.py``'s scan and update (``x = v``, ``B = k``, ``C =
    q``, a group a head, ``dt`` 1 at a real position and 0 at a pad or an
    idle slot, ``A = log lambda``)."""
    cfg: MiniCPMSalaConfig
    stats: Tuple[int, int] = (4, 5)

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, _ = u.shape
        h, d = cfg.lightning_heads, cfg.lightning_head_dim
        f32 = jnp.float32
        with trace.part(trace.PROJ):
            q = into_heads(dense(h * d, "q_proj", cfg, f32)(u), b, t, h, d)
            k = into_heads(dense(h * d, "k_proj", cfg, f32)(u), b, t, h, d)
            v = into_heads(dense(h * d, "v_proj", cfg)(u), b, t, h, d)
            q = _head_norm(cfg, "q_norm")(q)
            k = _head_norm(cfg, "k_norm")(k)
        cached = cfg.decode_paged
        if cached:
            state = self.variable("cache", "state", jnp.zeros, (b, h, d, d),
                                  cfg.state_dtype)
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start, carried = index.value, state.value
        else:
            start, carried = jnp.zeros((b,), jnp.int32), \
                jnp.zeros((b, h, d, d), f32)
        with trace.part(trace.PROJ):
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
            # the products take q, k and v rounded to the activations' type
            q = _rope(q, pos, cfg.rope_theta).astype(cfg.dtype)
            k = _rope(k, pos, cfg.rope_theta).astype(cfg.dtype)
        with trace.part(trace.STATE):
            real = row_mask(valid_len, b, t)                         # [B, T]
            dt = jnp.broadcast_to(real[..., None].astype(f32), (b, t, h))
            decay = lightning_decay(h)
            if cached and t == 1 and not self.is_initializing():
                y, new = mamba2.ssm_state_update(
                    carried, v[:, 0], dt[:, 0], decay, k[:, 0], q[:, 0])
                y = y[:, None]
                self._count(real[:, 0])
            else:
                y, new = mamba2.ssd_chunk_scan(
                    v, dt, decay, k, q, carried, chunk=cfg.chunk_size)
            if cached and not self.is_initializing():
                state.value = new.astype(cfg.state_dtype)
                index.value = index.value + t
            y = y * d ** -0.5
            y = _head_norm(cfg, "o_norm")(y).astype(cfg.dtype)
        return _gated_out(cfg, y.reshape(b, t, h * d), u)

    def _count(self, live):
        at, of = self.stats
        counts = jnp.zeros((of,), jnp.int32).at[at].set(jnp.sum(live))
        _sow_counts(self, "lightning", counts)


class GatedMlp(nn.Module):
    cfg: MiniCPMSalaConfig

    @nn.compact
    @trace.part(trace.FFN)
    def __call__(self, u):
        cfg = self.cfg
        gate = dense(cfg.d_ff, "gate_proj", cfg, jnp.float32)(u)
        up = dense(cfg.d_ff, "up_proj", cfg, jnp.float32)(u)
        return dense(cfg.d_model, "down_proj", cfg, jnp.float32)(
            (jax.nn.silu(gate) * up).astype(cfg.dtype))


class MiniCPMSala(nn.Module):
    cfg: MiniCPMSalaConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``)
    CACHE_KINDS = {"k": "paged", "v": "paged", "ck": "paged",
                   "index": "index", "state": "state", "sparse": "state"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = (SPARSE_VISIBLE, SPARSE_READ, SPARSE_ROWS, SPARSE_DENSE_ROWS,
             LIGHTNING_ROWS)
    #: prefill programs are told the admitted prompt's length
    TOLD_PROMPT_LEN = True

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None,
                 prompt_len=None):
        cfg = self.cfg
        b = tokens.shape[0]
        emb = self.param("embed_tokens", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        # the stream is float32: 2 x layers sums in bfloat16 would round it
        # as many times (the products take it rounded to their type)
        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens].astype(jnp.float32) \
                * cfg.scale_emb
            selects = None
            if cfg.decode_paged:
                # a request's mode is fixed when it is admitted, by its
                # prompt's length: a prefill program is told it and writes the
                # slot's row, a decode round reads the row
                mode = self.variable("cache", "sparse",
                                     lambda: jnp.zeros((b,), jnp.int32))
                if prompt_len is not None and not self.is_initializing():
                    mode.value = (prompt_len >= cfg.dense_len).astype(
                        jnp.int32)
                selects = mode.value != 0
        s = cfg.residual_scale
        of = len(self.STATS)
        for i, kind in enumerate(cfg.mixer_types):
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_norm")(x).astype(cfg.dtype)
            if kind == SPARSE:
                y = SparseAttention(cfg, (0, of), name=f"layer_{i}")(
                    u, page_table, valid_len, selects)
            else:
                y = LightningAttention(cfg, (of - 1, of), name=f"layer_{i}")(
                    u, valid_len)
            # a residual sum is filed with the block it closes
            with trace.part(trace.PROJ):
                x = x + s * y
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                        name=f"layer_{i}_mlp_norm")(x).astype(cfg.dtype)
            with trace.part(trace.FFN):
                x = x + s * GatedMlp(cfg, name=f"layer_{i}_mlp")(u)
        with trace.part(trace.HEAD):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            # drawn wider by what the logits are divided by, so that a random
            # model's logits spread as the other families' do
            head = self.param(
                "lm_head", normal(0.02 * cfg.d_model / cfg.dim_model_base),
                (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            logits = jnp.einsum("bte,ve->btv", x.astype(cfg.dtype),
                                head.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            return logits / (cfg.d_model / cfg.dim_model_base)


def init_params(cfg: MiniCPMSalaConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(MiniCPMSala(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

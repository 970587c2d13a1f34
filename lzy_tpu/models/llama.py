"""Llama-family decoder (RMSNorm + RoPE + GQA + SwiGLU), TPU-first.

The flagship model for BASELINE config 4 (Llama-3-8B FSDP on v5e-64). Design
for the MXU/HBM (SURVEY.md §6 north star):

- bfloat16 activations and matmuls (``dtype``), float32 master params
  (``param_dtype``) — the MXU's native mixed precision;
- every parameter carries logical axes via ``nn.with_logical_partitioning``,
  so one rule table (``lzy_tpu.parallel.sharding.DEFAULT_RULES``) lays the
  model out for FSDP/TP/SP and XLA inserts the collectives;
- optional per-layer remat (``jax.checkpoint``) trades FLOPs for HBM at long
  sequence lengths;
- attention switches to ring attention over the ``sp`` axis for
  sequence-parallel long-context training (``lzy_tpu.parallel.ring``), and to
  the fused Pallas flash kernel where ``use_flash_kernel`` asks for it
  (``lzy_tpu.ops.flash_attention``).

No reference counterpart exists (the reference is a workflow platform, not a
tensor framework — SURVEY.md §2.4); architecture follows the public Llama-3
configuration.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from lzy_tpu.models.common import cross_entropy_loss
from lzy_tpu.models.serving import HeadPool
from lzy_tpu.utils import trace


@dataclasses.dataclass(frozen=True)
class LlamaConfig(HeadPool):
    vocab_size: int = 128_256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14_336
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # what a rematerialised decoder layer KEEPS for its backward
    # (docs/architecture.md, "Training a LlamaConfig"):
    # "dots" (the default): the results of its matmuls and, under
    #   use_flash_kernel, the attention kernel's output and log-sum-exp, so
    #   the backward runs no projection and no attention forward again;
    #   norms, RoPE, silu(gate) * up, casts and residual adds are recomputed
    #   from what is kept. In bf16 that is at most 2 x (q + k + v + o +
    #   gate + up + down widths) + 2 x d_model (+ 4 a head for the
    #   log-sum-exp) bytes a token a layer: 94,336 at Mistral-7B's widths.
    # "nothing": the layer's input alone (2 x d_model bytes a token a
    #   layer); the backward runs the whole layer's forward again, a third
    #   more matmul work. For a model that cannot hold the results.
    # Keeping everything is spelled remat=False.
    remat_policy: str = "dots"
    tie_embeddings: bool = False         # Llama-3 uses an untied lm_head
    use_ring_attention: bool = False     # SP via ppermute ring over 'sp'
    use_ulysses_attention: bool = False  # SP via all-to-all head resharding
    use_flash_kernel: bool = False       # Pallas kernel (TPU only)
    # Mixtral-style sparse MLP: >0 replaces dense MLPs with MoE (ep-shardable)
    n_experts: int = 0
    moe_top_k: int = 2
    # autoregressive decoding with a KV cache (see generate()); the decode
    # step accepts token chunks [B, T>=1], so prefill writes a whole prompt
    # chunk into the cache per forward pass instead of one position at a
    # time. The same chunked forward is the speculative VERIFY step
    # (serving/spec.py): a [B, gamma+1] chunk of proposed tokens scores
    # every position in one call, and the engine rolls the cache index
    # back over rejected positions afterwards
    decode: bool = False
    # paged KV cache: k/v live in a SHARED pool of [kv_pages, kv_page_size,
    # heads, dim] blocks instead of a dense [B, max_seq_len, ...] row per
    # batch slot; each forward pass takes a per-row page table (block ids in
    # position order) and gathers/scatters through it. Block allocation,
    # prefix reuse and eviction live in lzy_tpu/serving/kv_cache.py; the
    # index is per-row [B] (continuous batching is the only paged caller).
    decode_paged: bool = False
    kv_page_size: int = 16
    kv_pages: int = 0
    # how attention reads the pool through the page table
    # (ops/paged_attention.py): "lax" (portable gather-attention, the same
    # sums in the same order as the dense cache's read below) or "pallas"
    # (the kernels: live pages by DMA, online softmax; the decode kernel
    # for decode-sized windows and the chunk kernel for prefill chunks
    # over float pools, lax for an int8 pool)
    paged_kernel: str = "lax"
    # int8 per-block KV quantization (paged cache only): pooled K/V are
    # stored int8 with per-position/per-head scale+zero-point sidecars
    # riding next to the pool — half the payload bytes, so ~2x resident
    # blocks at fixed HBM. Output is intentionally NOT bit-identical to
    # fp (bounded divergence; see ops/paged_attention.quantize_kv).
    kv_quant: Optional[str] = None
    # logits-free loss: the model returns (features, head) and the loss uses
    # chunked_cross_entropy — saves the [B,T,V] activation (ops/chunked_ce.py)
    fused_ce: bool = False
    # GPipe pipeline parallelism: >1 partitions the decoder stack into that
    # many stages streamed over the mesh's 'pp' axis (parallel/pipeline.py);
    # composes with dp/fsdp/tp, ring/Ulysses sp, MoE, and packed segments.
    # Decode from staged params with models.generate.pp_generate (or
    # unstack_pp_params + the dense generate).
    pp_stages: int = 0
    pp_microbatches: int = 0  # 0 → pp_stages (the minimum that fills the pipe)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # -- what a serving engine asks of a configuration (models/serving.py):
    # methods and no field --------------------------------------------------

    def serving_config(self) -> "LlamaConfig":
        """This configuration with every training-only feature cleared."""
        from lzy_tpu.models.generate import decode_config

        return decode_config(self)

    @property
    def kv_layers(self) -> int:
        """Layers that write the paged pool: what sizes it."""
        return self.n_layers

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str], native: bool = True,
                    **module_kw):
        """The module the engine runs, for decode rounds and batch-1
        prefill alike. ``module_kw`` goes to the module (the sharded
        engine's rule table)."""
        # tombstone: benchmark/models/nemotron_h.py passes ``native=True``
        # to this protocol method; the benchmark issue that takes it out
        # there (ROADMAP B2) removes the keyword here
        if not native:
            raise ValueError(
                "native=False: the gather read (the pool copied back into "
                "a dense [B, L, KV, D] layout) is gone; kernel='lax' reads "
                "through the page table and gives the same bits")
        return Llama(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel, kv_quant=kv_quant),
            **module_kw)

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program this family's kernels take: dense
        matmuls and a chunk read that tiles the width, which take any, so
        the widest bucket."""
        from lzy_tpu.models.generate import PREFILL_BUCKETS

        return PREFILL_BUCKETS[-1]

    def kernel_paths(self, t: int) -> tuple:
        """``lzy_kernel_dispatch_total{path}`` labels beside the attention
        read's own: this family has no other kernel."""
        return ()

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """The attention read over a pool of these shapes (where the caller
        names one); nothing else to lower."""
        self.lower_read(
            slots=slots, kv_blocks=kv_blocks, page_size=page_size,
            pages_per_seq=pages_per_seq, kv_quant=kv_quant)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(
            d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
            d_ff=28_672,
        )

    @staticmethod
    def moe_8x(base: "LlamaConfig" = None) -> "LlamaConfig":
        """Mixtral-style sparse variant: 8 experts, top-2 routing."""
        base = base or LlamaConfig()
        return dataclasses.replace(base, n_experts=8, moe_top_k=2)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """Test/dryrun shape: same code paths, toy dims."""
        return LlamaConfig(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=128, max_seq_len=256, remat=False,
            tie_embeddings=True,
        )


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, T, H, D]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # [B,T,1,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _remat_policy(name: str):
    """Checkpoint policy by name (LlamaConfig.remat_policy). ``"dots"``
    keeps the matmuls' results and the flash kernel's two (a custom call's
    results are no dot's: they are kept by the names the kernel gives
    them), so that nothing the matrix unit did is done again."""
    from lzy_tpu.ops.flash_attention import SAVED_NAMES

    cp = jax.checkpoint_policies
    policies = {
        "nothing": cp.nothing_saveable,
        "dots": cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names(*SAVED_NAMES)),
    }
    try:
        return policies[name]
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {name!r}; known: {sorted(policies)}"
        ) from None


class RMSNorm(nn.Module):
    eps: float
    param_dtype: Any

    @nn.compact
    @trace.part(trace.NORM)
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
            (x.shape[-1],), self.param_dtype,
        )
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig
    #: mesh for activation anchors (dense path only; None inside the
    #: pipeline's manual region, where constraints on the full mesh are
    #: not expressible — LlamaStage manages its own boundaries)
    anchor_mesh: Any = None
    #: frozen sharding-rule overrides (parallel.sharding.freeze_rules);
    #: None = the canonical DEFAULT_RULES table
    rules: Any = None

    @nn.compact
    def __call__(self, x, positions, mesh=None, segments=None,
                 page_table=None):
        cfg = self.cfg
        dense = lambda features, name, axes: nn.DenseGeneral(  # noqa: E731
            features=features, axis=-1, use_bias=False, name=name,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes
            ),
        )
        b, t, _ = x.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        with trace.part(trace.PROJ):
            q = dense((h, d), "q_proj", ("embed", "heads", "head_dim"))(x)
            k = dense((kv, d), "k_proj", ("embed", "kv", "head_dim"))(x)
            v = dense((kv, d), "v_proj", ("embed", "kv", "head_dim"))(x)
            # in-layer anchors (see Mlp): keep batch sharded through the
            # projections so fsdp gathers weights, not [D,T,B] activations
            q = _anchor(q, self.anchor_mesh, "batch", "seq", "act_heads",
                        None, rules=self.rules)
            k = _anchor(k, self.anchor_mesh, "batch", "seq", None, None,
                        rules=self.rules)
            v = _anchor(v, self.anchor_mesh, "batch", "seq", None, None,
                        rules=self.rules)

        if cfg.decode:
            return self._decode_step(q, k, v, b, page_table)

        with trace.part(trace.PROJ):
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)

            # GQA: repeat kv groups up to full heads
            reps = h // kv
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)

            # [B, H, T, D] layout for attention
            q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))

        with trace.part(trace.ATTN_READ):
            out = self._attend(q, k, v, mesh, segments, t)
        with trace.part(trace.PROJ):
            out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * d)
            return _anchor(self._o_proj(out), self.anchor_mesh,
                           "batch", "seq", "act_embed", rules=self.rules)

    def _attend(self, q, k, v, mesh, segments, t):
        """The uncached causal attention over ``[B, H, T, D]``, by the
        path the configuration asks for."""
        cfg = self.cfg
        if cfg.use_ring_attention and mesh is not None:
            from lzy_tpu.parallel.ring import ring_attention

            return ring_attention(q, k, v, mesh=mesh, causal=True,
                                  segment_ids=segments)
        if cfg.use_ulysses_attention and mesh is not None:
            # all-to-all SP: reshard seq→heads so each device sees the FULL
            # sequence for its head slice (better when heads ≥ sp and the
            # ring's ppermute latency dominates)
            from lzy_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention(q, k, v, mesh=mesh, causal=True,
                                     segment_ids=segments)
        if cfg.use_flash_kernel and not (
                self.is_initializing() and t % 128):
            # asked for, so taken: a length the kernel cannot serve
            # (t % 128, VMEM) is refused by flash_attention itself, never
            # dropped to the reference path in silence. Only init()'s
            # short dummy trace, whose output is thrown away, goes below.
            from lzy_tpu.ops.flash_attention import flash_attention

            return _batch_sharded_attention(
                flash_attention, q, k, v, segments, self.anchor_mesh,
                rules=self.rules)
        # portable fallback: chunked online-softmax attention — O(T·block)
        # activations, never the T×T score matrix (lzy_tpu/ops/attention)
        from lzy_tpu.ops.attention import chunked_attention

        return _batch_sharded_attention(
            chunked_attention, q, k, v, segments, self.anchor_mesh,
            rules=self.rules)

    def _o_proj(self, out):
        cfg = self.cfg
        return nn.DenseGeneral(
            features=cfg.d_model, use_bias=False, name="o_proj",
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads_merged", "embed")
            ),
        )(out)

    def _decode_step(self, q, k, v, b, page_table=None):
        """Autoregressive step against the KV cache (flax cache collection);
        q/k/v: [B, T, heads|kv, D] pre-RoPE. T=1 is token-by-token decode;
        T>1 is a batched chunk — prefill, or the speculative VERIFY
        forward (``serving/spec.py``): proposed tokens are written and
        scored in one pass, logits come back for every position, and the
        caller rewinds the per-row index over rejected positions (the
        garbage K/V they wrote sits beyond the rewound index, invisible
        to the causal mask and overwritten before it could surface).
        Caller contract for chunks: ``index + T`` must stay within
        ``max_seq_len`` for every live row — the dense write is a
        ``dynamic_update_slice`` (clamps the start, overwriting real
        positions) and the paged scatter clamps the page lookup into the
        row's last block; the serving engine falls back to 1-token steps
        when any row is that close to the edge.

        With ``cfg.decode_paged`` the k/v caches are a SHARED pool of
        ``[kv_pages, kv_page_size, ...]`` blocks, the index is ``[B]``
        (every row reads and writes at its own position: continuous
        batching) and ``page_table`` (``[B, max_seq_len //
        kv_page_size]`` block ids) maps each row's positions onto pool
        rows: writes scatter to ``(table[b, pos//page], pos%page)``, and
        attention is computed THROUGH the page table
        (``ops/paged_attention``; kernel per ``cfg.paged_kernel``) — no
        dense copy of the pool exists. With ``cfg.kv_quant`` the pools
        store int8 with scale/zero-point sidecar cache leaves (quantize
        on scatter-write, dequantize on read — every read path uses the
        same formula). Without ``cfg.decode_paged`` the cache is a dense
        ``[B, L, ...]`` row a batch row under one scalar index: what
        ``models/generate.py``, the oracle, runs."""
        cfg = self.cfg
        h, kv_heads, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        L = cfg.max_seq_len
        t = q.shape[1]
        quant = cfg.kv_quant is not None
        if quant and cfg.kv_quant != "int8":
            raise ValueError(
                f"unknown kv_quant {cfg.kv_quant!r}; known: int8")
        if quant and not cfg.decode_paged:
            raise ValueError(
                "kv_quant requires decode_paged (the dense cache has no "
                "pool to quantize)")
        quant_side = None
        if cfg.decode_paged:
            if cfg.kv_pages < 2 or L % cfg.kv_page_size:
                raise ValueError(
                    f"decode_paged needs kv_pages >= 2 and max_seq_len "
                    f"({L}) divisible by kv_page_size ({cfg.kv_page_size})")
            page = cfg.kv_page_size
            kv_store = jnp.int8 if quant else cfg.dtype
            cache_k = self.variable(
                "cache", "k", jnp.zeros,
                (cfg.kv_pages, page, kv_heads, d), kv_store)
            cache_v = self.variable(
                "cache", "v", jnp.zeros,
                (cfg.kv_pages, page, kv_heads, d), kv_store)
            if quant:
                # per-position/per-head scale+zero-point sidecars riding
                # next to the int8 pools, scattered through the SAME
                # (block row, offset) addressing as the payload
                quant_side = [
                    self.variable("cache", name, jnp.zeros,
                                  (cfg.kv_pages, page, kv_heads),
                                  jnp.float32)
                    for name in ("k_scale", "k_zp", "v_scale", "v_zp")]
            index = self.variable(
                "cache", "index", lambda: jnp.zeros((b,), jnp.int32))
        else:
            cache_k = self.variable(
                "cache", "k", jnp.zeros, (b, L, kv_heads, d), cfg.dtype
            )
            cache_v = self.variable(
                "cache", "v", jnp.zeros, (b, L, kv_heads, d), cfg.dtype
            )
            index = self.variable(
                "cache", "index", lambda: jnp.zeros((), jnp.int32)
            )
        i = index.value
        starts = i if i.ndim else jnp.broadcast_to(i, (b,))      # [B]
        pos = starts[:, None] + jnp.arange(t, dtype=jnp.int32)   # [B, T]
        with trace.part(trace.PROJ):
            q = _rope(q, pos, cfg.rope_theta)
            k = _rope(k, pos, cfg.rope_theta)
        if not self.is_initializing():
            # init() RUNS the module; writing during init would pre-populate
            # the cache with the dummy token and shift every real position
            with trace.part(trace.CACHE_WRITE):
                if cfg.decode_paged:
                    if page_table is None:
                        raise ValueError(
                            "decode_paged forward needs page_table")
                    from lzy_tpu.ops.paged_attention import (
                        paged_scatter_index)

                    # scatter each (row, position) into its pool block
                    rows, offs = paged_scatter_index(page_table, pos,
                                                     cfg.kv_page_size)
                    flat_k = k.astype(cfg.dtype).reshape(b * t, kv_heads, d)
                    flat_v = v.astype(cfg.dtype).reshape(b * t, kv_heads, d)
                    if quant:
                        # quantize on scatter-write: the pool stores int8 of
                        # EXACTLY what the fp path would have stored (the
                        # cfg.dtype-rounded K/V), so divergence is purely the
                        # int8 step, never a dtype-path difference
                        from lzy_tpu.ops.paged_attention import quantize_kv

                        qk, sk, zk = quantize_kv(flat_k)
                        qv, sv, zv = quantize_kv(flat_v)
                        cache_k.value = cache_k.value.at[rows, offs].set(qk)
                        cache_v.value = cache_v.value.at[rows, offs].set(qv)
                        for var, vals in zip(quant_side, (sk, zk, sv, zv)):
                            var.value = var.value.at[rows, offs].set(vals)
                    else:
                        cache_k.value = cache_k.value.at[rows, offs].set(
                            flat_k)
                        cache_v.value = cache_v.value.at[rows, offs].set(
                            flat_v)
                else:
                    cache_k.value = jax.lax.dynamic_update_slice(
                        cache_k.value, k.astype(cfg.dtype), (0, i, 0, 0)
                    )
                    cache_v.value = jax.lax.dynamic_update_slice(
                        cache_v.value, v.astype(cfg.dtype), (0, i, 0, 0)
                    )
                index.value = i + t

        if cfg.decode_paged:
            from lzy_tpu.ops.paged_attention import (
                KVQuant, paged_attention)

            kvq = None
            if quant:
                kvq = KVQuant(*(var.value for var in quant_side))
            # attention computed THROUGH the page table
            # (ops/paged_attention): decode, prefill chunks and the
            # [B, gamma+1] speculative verify all make this one call.
            # "lax" makes the dense read's sums below in the same order
            # (bit-identical to it); "pallas" is the decode kernel for
            # decode and verify windows and the chunk kernel for prefill
            # chunks over float pools (within a written tolerance of
            # float32 attention), and lax for int8 pools.
            out = paged_attention(
                q, cache_k.value, cache_v.value, page_table, pos,
                kernel=cfg.paged_kernel, dtype=cfg.dtype, quant=kvq)
            # gather head-sharded attention output BEFORE o_proj: the
            # merged head dim is o_proj's contraction dim, and letting
            # the partitioner keep it sharded would psum partial
            # matmul products (a float reduction-order change — the
            # sharded engine's bit-identity contract forbids it)
            with trace.part(trace.PROJ):
                out = _anchor(out.reshape(b, t, h * d), self.anchor_mesh,
                              "batch", "seq", "act_attn_out",
                              rules=self.rules)
                return self._o_proj(out)
        keys, vals = cache_k.value, cache_v.value

        # GQA without jnp.repeat: grouping q as [B, T, KV, G, D] lets the
        # einsum broadcast the shared KV head instead of materializing a
        # G-times larger cache copy every step — decode is HBM-bound, and
        # the repeat was pure wasted bandwidth
        with trace.part(trace.ATTN_READ):
            reps = h // kv_heads
            qg = q.reshape(b, t, kv_heads, reps, d)
            s = jnp.einsum(
                "btkgd,blkd->bkgtl", qg, keys,
                preferred_element_type=jnp.float32,
            ) * (d ** -0.5)                               # [B, KV, G, T, L]
            # query at (row, chunk offset tq) sees cache slots l <= start + tq:
            # everything already cached plus the chunk's own causal prefix (the
            # chunk was written above, so "future" chunk positions ARE in the
            # cache and must be masked; -1e30 underflows to exactly 0 after
            # softmax, so masked garbage contributes nothing)
            visible = (jnp.arange(L)[None, None, None, None, :]
                       <= pos[:, None, None, :, None])
            s = jnp.where(visible, s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bkgtl,blkd->btkgd", p, vals)
        # same contraction-dim gather as the paged read above: replicate
        # the merged head dim before o_proj so no psum-of-partials ever
        # enters the decode forward
        with trace.part(trace.PROJ):
            out = _anchor(out.reshape(b, t, h * d), self.anchor_mesh,
                          "batch", "seq", "act_attn_out", rules=self.rules)
            return self._o_proj(out)


class Mlp(nn.Module):
    cfg: LlamaConfig
    mesh: Any = None
    rules: Any = None

    @nn.compact
    @trace.part(trace.FFN)
    def __call__(self, x):
        cfg = self.cfg

        def dense(features, name, axes):
            return nn.DenseGeneral(
                features=features, use_bias=False, name=name,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes
                ),
            )

        # in-layer anchors: with fsdp-sharded kernels the partitioner
        # otherwise re-shards the hidden activations onto the model dim
        # and all-gathers [D,T,B] per matmul — 14 gathers/layer, 150 GB
        # per step at flagship v5e-16 scale (a deviceless compile, now
        # tests/test_aot_topology.py); anchoring the intermediates keeps
        # batch sharded so only WEIGHTS are gathered
        gate = dense(cfg.d_ff, "gate_proj", ("embed", "mlp"))(x)
        up = dense(cfg.d_ff, "up_proj", ("embed", "mlp"))(x)
        h = _anchor(nn.silu(gate) * up, self.mesh, "batch", "seq", "act_mlp",
                    rules=self.rules)
        out = dense(cfg.d_model, "down_proj", ("mlp", "embed"))(h)
        return _anchor(out, self.mesh, "batch", "seq", "act_embed",
                       rules=self.rules)


class DecoderLayer(nn.Module):
    """One decoder block. ``mesh`` is a module FIELD, not a call argument:
    under ``nn.remat`` every call argument is traced, and a Mesh object
    cannot be interpreted as an abstract array — remat=True with a mesh
    crashed until the mesh moved to construction time (caught by the AOT
    compile of the seq-4k bench variant; tests/test_aot_topology.py keeps
    such compiles)."""

    cfg: LlamaConfig
    mesh: Any = None
    #: dense-path activation anchors; False inside the pipeline's manual
    #: region (LlamaStage), where full-mesh constraints don't apply
    anchor: bool = False
    rules: Any = None

    @nn.compact
    def __call__(self, x, positions, segments=None, page_table=None):
        cfg, mesh = self.cfg, self.mesh
        amesh = mesh if self.anchor else None
        attn = Attention(cfg, anchor_mesh=amesh, rules=self.rules,
                         name="attn")(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="attn_norm")(x),
            positions, mesh, segments, page_table,
        )
        # a residual sum is filed with the block it closes
        with trace.part(trace.PROJ):
            x = x + attn
        h = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="mlp_norm")(x)
        if cfg.n_experts > 0:
            from lzy_tpu.models.moe import MoeConfig, MoeMlp

            moe_out, aux = MoeMlp(MoeConfig(
                d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
                top_k=cfg.moe_top_k, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
            ), name="moe")(h)
            self.sow("losses", "moe_aux", aux)
            with trace.part(trace.EXPERTS):
                return x + moe_out
        with trace.part(trace.FFN):
            return x + Mlp(cfg, mesh=amesh, rules=self.rules, name="mlp")(h)


def _mesh_axes_for(rules, name, mesh):
    """Mesh axes a logical axis maps to under the ACTIVE rule table,
    filtered to axes the mesh actually has (a remapped deployment may
    drop dp/tp entirely). ``rules`` is a frozen override tuple or None."""
    from lzy_tpu.parallel.sharding import DEFAULT_RULES

    table = dict(DEFAULT_RULES)
    if rules:
        table.update(dict(rules))
    entry = table.get(name)
    if entry is None:
        return ()
    names = entry if isinstance(entry, tuple) else (entry,)
    return tuple(a for a in names if a in mesh.shape)


def _batch_sharded_attention(fn, q, k, v, segments, mesh, rules=None):
    """Run a non-ring attention body per batch/head shard via shard_map.

    The SPMD partitioner cannot see inside the Pallas flash custom call
    (and shards the chunked-attention while loop poorly): without this
    wrapper it REPLICATES the attention operands — at flagship v5e-16
    scale that was 280 all-gathers / 150 GB per step of [B*H, T, D]
    tensors, every chip then computing attention for the full global
    batch (deviceless compile, op_name attn/while/body; the traffic bound
    in tests/test_aot_topology.py pins it).
    Attention is independent per (batch, head), so mapping those dims is
    exact. Dense path only (``anchor_mesh``); the ring/Ulysses paths and
    the pipeline's manual region do their own thing. The batch/head mesh
    axes come from the ACTIVE rule table (``rules``), not hardcoded
    dp/fsdp/tp names, so remapped deployments shard instead of crashing
    on a missing mesh axis."""
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, causal=True, segment_ids=segments)
    import math

    batch_axes = _mesh_axes_for(rules, "batch", mesh)
    head_axes = _mesh_axes_for(rules, "heads", mesh)
    bs = math.prod(mesh.shape[a] for a in batch_axes)
    hs = math.prod(mesh.shape[a] for a in head_axes)
    # shard_map demands exact divisibility where GSPMD would pad; odd
    # batch/head counts (eval smoke runs, unusual head configs) and rule
    # tables that shard neither dim keep the old replicated path —
    # correct, just not bandwidth-optimal
    if bs * hs == 1 or q.shape[0] % bs or q.shape[1] % hs:
        return fn(q, k, v, causal=True, segment_ids=segments)
    from jax.sharding import PartitionSpec as P

    qkv_spec = P(batch_axes or None, head_axes or None, None, None)
    if segments is None:
        return jax.shard_map(
            lambda a, b, c: fn(a, b, c, causal=True),
            mesh=mesh, in_specs=(qkv_spec,) * 3, out_specs=qkv_spec,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        lambda a, b, c, s: fn(a, b, c, causal=True, segment_ids=s),
        mesh=mesh,
        in_specs=(qkv_spec,) * 3 + (P(batch_axes or None, None),),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, segments)


def _anchor(x, mesh, *logical_axes, rules=None):
    """Pin an activation's sharding to the logical rules (maxtext-style
    anchor). Without this the TPU partitioner may resolve a
    param-vs-activation axis conflict by un-sharding the *batch* — on an
    fsdp mesh the embed table is (vocab, embed->fsdp), and propagating
    that into the residual stream makes XLA batch-all-gather every
    [B,T,V]-shaped intermediate (33 MB each at test size, 34 GB at
    flagship scale; tests/test_aot_topology.py pins the traffic bound).
    ``rules`` is a frozen override tuple
    (``parallel.sharding.freeze_rules``) so anchors follow the SAME table
    the params were laid out with instead of silently assuming
    DEFAULT_RULES."""
    if mesh is None or mesh.size == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    from lzy_tpu.parallel.sharding import manual_axes, spec_for

    spec = spec_for(logical_axes, dict(rules) if rules else None)
    # a rule may name axes the mesh doesn't have (remapped deployments);
    # constraints on absent axes are rejected, so keep only real ones
    def present(entry):
        if entry is None:
            return None
        names = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in names if a in mesh.shape)
        return kept if kept else None

    spec = PartitionSpec(*(present(e) for e in spec))
    manual = manual_axes()
    if manual:
        # inside a manual region (the pp pipeline runs the stage body under
        # shard_map): a constraint naming a manual axis is rejected by both
        # partitioners, so anchor only the still-auto axes
        def strip(entry):
            if entry is None:
                return None
            names = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in names if a not in manual)
            return kept if kept else None

        spec = PartitionSpec(*(strip(e) for e in spec))
        if all(e is None for e in spec):
            return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def _embed_lookup(table, tokens, *, one_hot: bool):
    """Token embedding lookup.

    On sharded meshes the gather's transpose (scatter-add into the
    vocab/embed-sharded table) forces SPMD into an 'Involuntary full
    rematerialization' of the cotangent (MULTICHIP_r03 warnings); the
    TPU-native form is a one-hot einsum (maxtext's iota-embed trick):
    both directions are then plain dots the partitioner shards with
    clean collectives, and XLA fuses the iota-compare operand so the
    [B,T,V] one-hot is never materialized. Plain gather stays for the
    meshless path (single-chip decode), where it's strictly cheaper."""
    if not one_hot:
        return table[tokens]
    hot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
    return jnp.einsum("btv,vd->btd", hot, table)


class Llama(nn.Module):
    cfg: LlamaConfig
    #: the kind of each cache leaf, by its name (``models/serving.py``):
    #: every leaf but ``index`` is keys and values (or their int8 scales),
    #: paged under the paged engine, a row a slot under the dense one
    CACHE_KINDS = {"index": "index"}
    #: the counters a ``stats`` collection would feed: this model sows none
    STATS = ()
    #: frozen sharding-rule overrides (``parallel.sharding.freeze_rules``);
    #: threads the ACTIVE rule table into every activation anchor so a
    #: deployment with remapped rules doesn't get DEFAULT_RULES anchors
    #: fighting its custom param shardings
    rules: Any = None

    @nn.compact
    def __call__(self, tokens, mesh=None, segments=None, page_table=None):
        cfg = self.cfg
        emb = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype,
        )
        with trace.part(trace.EMBED):
            x = _embed_lookup(emb.astype(cfg.dtype), tokens,
                              one_hot=mesh is not None)
            x = _anchor(x, mesh, "batch", "seq", "act_embed",
                        rules=self.rules)
        if segments is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        else:
            # packed documents: RoPE positions restart at every document so
            # each one sees the same positional geometry it would unpacked
            from lzy_tpu.ops.flash_attention import document_starts

            idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            positions = idx[None, :] - document_starts(segments)
        layer = DecoderLayer
        if cfg.remat:
            layer = nn.remat(
                DecoderLayer, static_argnums=(),
                policy=_remat_policy(cfg.remat_policy),
            )
        for i in range(cfg.n_layers):
            # anchor=True: in-layer activation anchors (Attention/Mlp) —
            # one anchor at the embed is not enough; at flagship scale
            # the partitioner re-shards activations onto the model dim
            # mid-layer and all-gathers [D,T,B] for every matmul (280
            # gathers / 150 GB per step on v5e-16 in a deviceless compile). The
            # pp path (LlamaStage) manages its own boundaries.
            x = layer(cfg, mesh=mesh, anchor=True, rules=self.rules,
                      name=f"layer_{i}")(x, positions, segments, page_table)
            x = _anchor(x, mesh, "batch", "seq", "act_embed",
                        rules=self.rules)
        with trace.part(trace.HEAD):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            if cfg.tie_embeddings:
                head = emb
            else:
                head = self.param(
                    "lm_head",
                    nn.with_logical_partitioning(
                        nn.initializers.normal(0.02), ("vocab", "embed")
                    ),
                    (cfg.vocab_size, cfg.d_model), cfg.param_dtype,
                )
            if cfg.fused_ce and not cfg.decode:
                # the loss computes chunked CE straight from features + head
                # and never materializes [B,T,V] logits (decode always needs
                # real logits for sampling, whatever the training config said)
                return x.astype(cfg.dtype), head.astype(cfg.dtype)
            # bf16 operands on the MXU, f32 accumulation — an f32×f32 head
            # matmul would run ~4x slower for no useful precision (loss is
            # f32 anyway)
            logits = jnp.einsum(
                "bte,ve->btv", x.astype(cfg.dtype), head.astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
            return _anchor(logits, mesh, "batch", "seq", "act_vocab",
                           rules=self.rules)


class LlamaStage(nn.Module):
    """One pipeline stage: ``n_layers`` consecutive decoded layers.

    Every stage runs the same module shape with per-stage weights — the
    constraint ``parallel.pipeline.pipeline_apply`` streams microbatches
    through (stage i holds layers [i*k, (i+1)*k)). ``mesh`` (static)
    flows to the layers so sequence-parallel attention composes with the
    pipeline: the ring's shard_map nests partial-manual over ``sp``
    inside the pipeline's partial-manual ``pp`` region (ring.py handles
    the nested case against the context mesh)."""

    cfg: LlamaConfig
    n_layers: int
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, segments=None):
        cfg = self.cfg
        layer = DecoderLayer
        if cfg.remat:
            layer = nn.remat(
                DecoderLayer, static_argnums=(),
                policy=_remat_policy(cfg.remat_policy),
            )
        for i in range(self.n_layers):
            x = layer(cfg, mesh=self.mesh, name=f"layer_{i}")(
                x, positions, segments)
        return x


def _check_pp_config(cfg: LlamaConfig) -> int:
    """Validate a pipeline config; returns layers-per-stage."""
    if cfg.pp_stages < 2:
        raise ValueError(
            f"pipeline entry points need pp_stages >= 2, got "
            f"{cfg.pp_stages} (dense configs use the non-pp forward)"
        )
    if cfg.n_layers % cfg.pp_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp_stages={cfg.pp_stages}"
        )
    if cfg.decode:
        raise ValueError(
            "pp_stages>1 training entries do not take decode configs; "
            "decode from staged params with models.generate.pp_generate "
            "(or unstack_pp_params + the dense generate). Ring/Ulysses "
            "sequence parallelism and MoE DO compose with pp."
        )
    return cfg.n_layers // cfg.pp_stages


def _init_pp_params(cfg: LlamaConfig, rng: jax.Array, seq_len: int):
    """Pipeline layout: the decoder stack lives under ``"stages"`` with every
    leaf stacked ``[pp_stages, ...]`` (logical axis ``"stage"`` → mesh ``pp``);
    embed/final-norm/head stay top-level exactly as in the dense tree.
    Returned params are plain arrays (``unbox`` is a no-op on them), so the
    ``boxed, axes = init_params(...); params = unbox(boxed)`` call pattern
    works unchanged."""
    from lzy_tpu.models.common import param_logical_axes, unbox as _unbox

    k = _check_pp_config(cfg)
    r_trunk, r_stages = jax.random.split(rng)

    trunk_cfg = dataclasses.replace(cfg, n_layers=0, pp_stages=0)
    tokens = jnp.zeros((1, seq_len), jnp.int32)
    trunk_boxed = Llama(trunk_cfg).init(r_trunk, tokens)["params"]

    stage = LlamaStage(cfg, k)
    dummy_x = jnp.zeros((1, seq_len, cfg.d_model), cfg.dtype)
    dummy_pos = jnp.zeros((1, seq_len), jnp.int32)
    one_boxed = stage.init(jax.random.PRNGKey(0), dummy_x, dummy_pos)["params"]
    stage_axes = jax.tree_util.tree_map(
        lambda axes: ("stage",) + axes,
        param_logical_axes(one_boxed),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    stacked = jax.vmap(
        lambda r: _unbox(stage.init(r, dummy_x, dummy_pos)["params"])
    )(jax.random.split(r_stages, cfg.pp_stages))

    params = dict(_unbox(trunk_boxed))
    params["stages"] = stacked
    axes = dict(param_logical_axes(trunk_boxed))
    axes["stages"] = stage_axes
    return params, axes


def pp_forward(params, tokens: jax.Array, cfg: LlamaConfig, mesh,
               axis: str = "pp", segments=None):
    """Pipelined forward: embed → GPipe over the decoder stack → norm + head.

    Embedding/norm/head run outside the pipeline (replicated over ``pp``,
    sharded over the remaining mesh axes as usual); only the decoder stack
    streams microbatches stage-to-stage over ``ppermute`` neighbor hops.

    ``segments``: optional ``[B, T]`` packed-document ids; each stage
    looks up its current microbatch's segment chunk by index (the
    pipeline passes ``micro_idx``) so attention masking and per-document
    RoPE restarts follow their microbatch through the stages. Not yet
    composable with sequence parallelism inside the pipeline."""
    from lzy_tpu.parallel.pipeline import pipeline_apply

    k = _check_pp_config(cfg)
    if mesh.shape[axis] != cfg.pp_stages:
        raise ValueError(
            f"mesh {axis}={mesh.shape[axis]} != pp_stages={cfg.pp_stages}"
        )
    b, t = tokens.shape
    n_micro = cfg.pp_microbatches or cfg.pp_stages
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")

    # one-hot, not gather: same resharding-cliff avoidance as the dense
    # path (_embed_lookup) — the gather's scatter-add transpose forces an
    # involuntary full rematerialization on pp x fsdp meshes
    x = _embed_lookup(params["embed_tokens"].astype(cfg.dtype), tokens,
                      one_hot=True)
    mb = b // n_micro
    xm = x.reshape(n_micro, mb, t, x.shape[-1])

    # pp × sp: with ring attention on an sp-bearing mesh, the pipeline's
    # manual region covers {pp, sp} and activations enter seq-sharded —
    # the stage then computes its chunk's ABSOLUTE positions from its sp
    # rank (RoPE must see global offsets, not per-chunk zeros)
    from jax.sharding import NamedSharding, PartitionSpec as P

    seq_axis = None
    if segments is not None and (cfg.use_ring_attention
                                 or cfg.use_ulysses_attention):
        raise ValueError(
            "packed segments do not compose with sequence parallelism "
            "inside the pipeline yet (drop sp or unpack)")
    if cfg.use_ring_attention or cfg.use_ulysses_attention:
        which = ("use_ring_attention" if cfg.use_ring_attention
                 else "use_ulysses_attention")
        if "sp" not in mesh.shape or mesh.shape["sp"] < 2:
            raise ValueError(
                f"pp_stages>1 with {which} needs an 'sp' axis of size >= 2 "
                f"on the mesh (sequence parallelism runs against the manual "
                f"sp axis inside the pipeline); add sp to the mesh or drop "
                f"{which}")
        seq_axis = "sp"
        if t % mesh.shape["sp"]:
            raise ValueError(
                f"seq {t} not divisible by sp={mesh.shape['sp']}")
        if cfg.use_ulysses_attention and cfg.n_heads % mesh.shape["sp"]:
            raise ValueError(
                f"ulysses needs n_heads={cfg.n_heads} divisible by "
                f"sp={mesh.shape['sp']}")
    # The microbatch reshape mangles the tokens' batch sharding into a 2D
    # split of the leading dims; SPMD can't convert that to the layout it
    # wants at the pipeline boundary without an 'Involuntary full
    # rematerialization'. The activations cross that boundary (replicated
    # except for the manual sp chunking) regardless, so lay them out
    # explicitly — a voluntary all-gather instead of an involuntary one.
    boundary = P(None, None, seq_axis, None)
    xm = jax.lax.with_sharding_constraint(xm, NamedSharding(mesh, boundary))

    stage = LlamaStage(cfg, k, mesh=mesh)
    with_aux = cfg.n_experts > 0
    segs_m = None
    if segments is not None:
        segs_m = segments.reshape(n_micro, mb, t)

    def stage_fn(p, h, micro_idx=None):
        seg = None
        t_local = h.shape[1]
        if seq_axis is not None:
            start = jax.lax.axis_index(seq_axis) * t_local
            positions = jnp.broadcast_to(start + jnp.arange(t_local),
                                         (h.shape[0], t_local))
        elif segs_m is not None:
            # packed docs: this microbatch's ids ride along by index, and
            # RoPE restarts at every document (dense-path semantics)
            from lzy_tpu.ops.flash_attention import document_starts

            seg = segs_m[micro_idx]
            idx = jnp.arange(t_local, dtype=jnp.int32)
            positions = idx[None, :] - document_starts(seg)
        else:
            positions = jnp.broadcast_to(jnp.arange(t_local),
                                         (h.shape[0], t_local))
        if with_aux:
            y, sown = stage.apply({"params": p}, h, positions, seg,
                                  mutable=["losses"])
            aux = sum(jax.tree_util.tree_leaves(sown.get("losses", {})),
                      jnp.zeros((), jnp.float32))
            return y, aux
        return stage.apply({"params": p}, h, positions, seg)

    aux = jnp.zeros((), jnp.float32)
    out = pipeline_apply(stage_fn, params["stages"], xm, mesh=mesh, axis=axis,
                         seq_axis=seq_axis, with_aux=with_aux,
                         pass_micro_index=segs_m is not None)
    if with_aux:
        x, aux = out
    else:
        x = out
    # same voluntary trick on the way out: the constraint transposes to
    # itself, so the BACKWARD cotangent (embed-sharded by the head matmul)
    # is gathered explicitly at the boundary instead of via SPMD's
    # last-resort full rematerialization
    x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, boundary))
    x = x.reshape(b, t, -1)
    x = RMSNorm(cfg.norm_eps, cfg.param_dtype).apply(
        {"params": params["final_norm"]}, x
    )
    head = params["embed_tokens"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.fused_ce:
        out = (x.astype(cfg.dtype), head.astype(cfg.dtype))
    else:
        out = jnp.einsum(
            "bte,ve->btv", x.astype(cfg.dtype), head.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    # MoE configs also return the stages' summed load-balancing aux loss
    # (accumulated bubble-masked inside the pipeline)
    return (out, aux) if with_aux else out


def unstack_pp_params(cfg: LlamaConfig, params):
    """Pipeline-stacked params → the standard dense Llama tree (so a
    pp-trained model can run ``generate``/eval, which don't pipeline)."""
    k = _check_pp_config(cfg)
    dense = {key: val for key, val in params.items() if key != "stages"}
    for s in range(cfg.pp_stages):
        for j in range(k):
            dense[f"layer_{s * k + j}"] = jax.tree_util.tree_map(
                lambda a, s=s: a[s], params["stages"][f"layer_{j}"]
            )
    return dense


def init_params(cfg: LlamaConfig, rng: jax.Array, seq_len: int = 8):
    """Returns (boxed_params, logical_axes). Unbox with models.common.unbox."""
    from lzy_tpu.models.common import param_logical_axes

    if cfg.pp_stages > 1:
        return _init_pp_params(cfg, rng, seq_len)
    model = Llama(cfg)
    tokens = jnp.zeros((1, seq_len), jnp.int32)
    boxed = model.init(rng, tokens)["params"]
    return boxed, param_logical_axes(boxed)


def make_loss_fn(cfg: LlamaConfig, mesh=None, rules=None):
    """Causal-LM loss: predict tokens[t+1] from tokens[:t]. MoE configs add
    the routers' load-balancing aux losses. ``pp_stages>1`` streams the
    decoder stack over the mesh's pp axis (mesh required). ``rules``
    (a ``parallel.sharding.Rules`` override dict) threads the active rule
    table into the model's activation anchors — pass the SAME table you
    give ``make_train_step`` or anchors will pin default-rule layouts
    against custom param shardings."""
    from lzy_tpu.parallel.sharding import freeze_rules

    frozen = freeze_rules(rules)
    if cfg.pp_stages > 1:
        _check_pp_config(cfg)
        if mesh is None:
            raise ValueError("pp_stages>1 requires make_loss_fn(cfg, mesh=...)")

        def pp_loss_fn(params, batch):
            tokens = batch["tokens"]
            segments = batch.get("segments")
            out = pp_forward(params, tokens, cfg, mesh, segments=segments)
            aux = 0.0
            if cfg.n_experts > 0:
                out, aux = out
            mask = batch.get("mask")
            shifted_mask = mask[:, 1:] if mask is not None else None
            if segments is not None:
                shifted_mask = _segment_shift_mask(segments, shifted_mask)
            return _lm_loss(cfg, out, tokens, shifted_mask, mesh,
                            rules=frozen) + aux

        return pp_loss_fn
    model = Llama(cfg, rules=frozen)

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        segments = batch.get("segments")
        if cfg.n_experts > 0:
            logits, sown = model.apply(
                {"params": params}, tokens, mesh, segments,
                mutable=["losses"],
            )
            aux = sum(
                jax.tree_util.tree_leaves(sown.get("losses", {})),
                jnp.zeros((), jnp.float32),
            )
        else:
            logits = model.apply({"params": params}, tokens, mesh, segments)
            aux = 0.0
        mask = batch.get("mask")
        shifted_mask = mask[:, 1:] if mask is not None else None
        if segments is not None:
            shifted_mask = _segment_shift_mask(segments, shifted_mask)
        return _lm_loss(cfg, logits, tokens, shifted_mask, mesh,
                        rules=frozen) + aux

    return loss_fn


def _segment_shift_mask(segments, shifted_mask):
    """Cross-document next-token rule shared by the dense and pp losses: a
    position whose next token belongs to a different document must not be
    asked to predict it."""
    same_doc = segments[:, 1:] == segments[:, :-1]
    return same_doc if shifted_mask is None \
        else jnp.logical_and(shifted_mask, same_doc)


@trace.part(trace.LOSS)
def _lm_loss(cfg: LlamaConfig, out, tokens, shifted_mask, mesh=None,
             rules=None):
    """Shared next-token loss tail: ``out`` is logits, or (features, head)
    when ``cfg.fused_ce`` (both the dense and pipelined paths end here)."""
    if cfg.fused_ce:
        features, head = out
        from lzy_tpu.ops.chunked_ce import chunked_cross_entropy

        # anchor the CE operands: features keep the batch sharded
        features = _anchor(features, mesh, "batch", "seq", "act_embed",
                           rules=rules)
        shard = _loss_batch_shard(features.shape[0], mesh, rules)
        if shard is None:
            # the head is gathered whole ONCE (vocab x embed, ~67 MB bf16
            # at flagship size) instead of the partitioner keeping its
            # embed dim fsdp-sharded and batch-all-gathering every block of
            # the scan — the 193 GB/step pathology a deviceless v5e-16
            # compile showed. (vocab, None): "act_embed" here would map to
            # the same mesh axis as "vocab" (both tp) and P("tp","tp") is
            # illegal. Per batch shard the ``shard_map`` takes the head
            # whole itself, and an anchor would only pin its gradient
            # replicated on the way back: a gather of what was scattered
            head = _anchor(head, mesh, "vocab", None, rules=rules)
        # the last position predicts nothing: a weight-0 row, not a slice
        # (a slice copies the features and leaves 4095 rows a sequence,
        # which no block of 8 divides)
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        weights = jnp.ones(tokens[:, 1:].shape, jnp.float32) \
            if shifted_mask is None else shifted_mask.astype(jnp.float32)
        return chunked_cross_entropy(
            features, head, labels, mask=jnp.pad(weights, ((0, 0), (0, 1))),
            shard=shard,
        )
    return cross_entropy_loss(out[:, :-1], tokens[:, 1:], shifted_mask)


def _loss_batch_shard(batch, mesh, rules):
    """Where the fused loss runs per batch shard (``ops.chunked_ce``
    ``BatchShard``): the mesh shards the batch over more than one device
    and the batch divides, the rule ``_batch_sharded_attention`` uses. None
    elsewhere (one device, a mesh that shards no batch, an odd batch, a
    manual region, which a nested ``shard_map`` cannot re-bind): the same
    loss under plain ``jit``, whose partitioner sums the head's gradient
    inside the loop."""
    import math

    from lzy_tpu.ops.chunked_ce import BatchShard
    from lzy_tpu.parallel.sharding import manual_axes

    if mesh is None or mesh.size == 1 or manual_axes():
        return None
    batch_axes = _mesh_axes_for(rules, "batch", mesh)
    shards = math.prod(mesh.shape[a] for a in batch_axes)
    if shards == 1 or batch % shards:
        return None
    # the head's own layout: its parameter's logical axes under the rules
    return BatchShard(mesh, batch_axes, tuple(
        tuple(a for a in _mesh_axes_for(rules, name, mesh) if a in batch_axes)
        for name in ("vocab", "embed")))

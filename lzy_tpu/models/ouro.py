"""Ouro (``model_type`` ``ouro``; ``Ouro-2.6B``: 48 layers at 2048), the
looped language model of "Scaling Latent Reasoning via Looped Language
Models" (arXiv:2510.25741): **one stack of layers run ``total_ut_steps``
times a token with the same weights**, a key/value cache of its own for every
(pass, layer), and a learned **exit gate** that says which pass's state the
head reads. ``N`` is RMSNorm; ``x^(t)_l`` the residual entering layer ``l`` in
pass ``t`` of ``T``::

    x^(1)_1 = E[token]
    layer l, pass t (sandwich norm: four norms a layer):
      a  = x + N2_l( Attn_l( N1_l(x) ; pass t ) )
      x' = a + N4_l( W_down_l( silu(W_gate_l n) * W_up_l n ) ),  n = N3_l(a)
    Attn_l(u ; t): 16 heads of 128 over 16 key-value heads, no bias, rotary
      embedding (rotate-half, the whole head, position p in every pass),
      causal softmax over the keys and values **pass t of layer l** cached
    end of pass t:  h^(t) = N_f( x^(t)_last )    g^(t) = w_g . h^(t) + b_g
                    x^(t+1)_1 = h^(t)             (the normed state goes on)
    lam_t = sigmoid(g^(t));  p_t = lam_t prod_{j<t} (1 - lam_j) for t < T,
    p_T = prod_{j<T} (1 - lam_j);  t* = the first t whose p_1 + .. + p_t
    reaches ``early_exit_threshold``, else T;  logits = W_head h^(t*)

Every pass runs for every token whatever the gate says (as the published
modelling code does: the gate chooses which state is read, it skips nothing).
The stream inside a pass is float32 (96 sums a pass; the products take it
rounded to their type), and so are the gate's product and the exit
distribution: a rounding there changes which state a token is read from.

What a serving engine has to know about it, and reads from here without
naming the model (``models/serving.py``):

- **the stack is traced once and looped** (``nn.scan`` over the pass with
  the parameters broadcast and the cache carried): the parameter tree holds
  ``n_layers`` layers, a program's build costs ``n_layers`` layers, and the
  pass index is a traced scalar.
- **a paged leaf holds the passes side by side in a block**: ``k`` / ``v``
  ``[kv_pages, T, page, KV, D]`` a layer. The first axis is the engine's block
  id, so the radix cache, copy, export / import, tiers and rewind move a
  block's ``T`` passes together and one page table a row serves every pass.
  Inside pass ``t`` the leaf is read as ``[kv_pages x T, page, KV, D]`` (the
  same memory) through ``page_table x T + t``: the scatter and both reads of
  ``ops/paged_attention.py`` see a pool of ``kv_pages x T`` blocks and do not
  change. ``kv_layers`` is ``n_layers x T`` (192 for 48 layers).
- an idle slot's table is zeros, so after the product it starts with ``t``
  and not with the scratch block: idle rows are told to the decode read by
  position (``-1``), from the table before the product.
- ``valid_len`` ``[B]``: how many of a row's positions are real. A padded
  chunk position and an idle slot write the scratch block (block 0) of the
  pass and nothing else.
- **counts** a round carries out with its tokens (:attr:`Ouro.STATS`): the
  real rows, the sum of ``t*`` over them, and the two counts of a full
  attention layer's reads, here a (pass, layer).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models.experts import row_mask
from lzy_tpu.models.llama import RMSNorm, _rope
from lzy_tpu.models.paged_blocks import (
    ATTN_FULL_KEYS, ATTN_ROWS, dense, into_heads, normal)
from lzy_tpu.models.serving import HeadPool
from lzy_tpu.ops.paged_attention import MAX_Q_TOKENS
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

LOOP_ROWS = REGISTRY.counter(
    "lzy_loop_rows_total",
    "real rows of decode rounds of a model that runs its layers several "
    "times a token")
LOOP_EXIT_PASS = REGISTRY.counter(
    "lzy_loop_exit_pass_total",
    "the pass whose state the head read (1 .. total_ut_steps), summed over "
    "lzy_loop_rows_total")

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class OuroConfig(HeadPool):
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    #: passes over the stack a token (``T``)
    total_ut_steps: int = 4
    #: the exit distribution's mass at which the head stops looking further
    early_exit_threshold: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 65536
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # serving: keys and values a (pass, layer) in a shared paged pool
    decode_paged: bool = False
    kv_page_size: int = 16
    kv_pages: int = 0
    paged_kernel: str = "lax"

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps: a token passes the stack at least once, "
                f"got {self.total_ut_steps}")
        if not 0.0 < self.early_exit_threshold <= 1.0:
            raise ValueError(
                f"early_exit_threshold: a mass of the exit distribution, in "
                f"(0, 1], got {self.early_exit_threshold}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("query heads divide into their groups")

    @classmethod
    def from_published(cls, doc: dict, **over) -> "OuroConfig":
        """The published ``config.json`` keys as this configuration. What
        the program cannot honour is refused by name."""
        served = {
            "sliding_window": (None,), "use_sliding_window": (False, None),
            "rope_scaling": (None,), "tie_word_embeddings": (False,),
            "hidden_act": ("silu",),
        }
        for key, values in served.items():
            if doc.get(key) not in values:
                raise ValueError(
                    f"OuroConfig serves {key} in {values!r} (attention over "
                    f"everything in every layer, plain rotary positions, an "
                    f"output head of its own, a SiLU-gated MLP); the "
                    f"configuration says {key} = {doc.get(key)!r}")
        layers = doc["num_hidden_layers"]
        kinds = list(doc.get("layer_types") or ["full_attention"] * layers)
        if kinds != ["full_attention"] * layers:
            raise ValueError(
                f"OuroConfig serves {layers} layers of type "
                f"'full_attention'; layer_types is {sorted(set(kinds))} x "
                f"{len(kinds)}")
        return cls(
            vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
            n_layers=layers, n_heads=doc["num_attention_heads"],
            n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
            d_ff=doc["intermediate_size"],
            total_ut_steps=doc["total_ut_steps"],
            early_exit_threshold=float(doc["early_exit_threshold"]),
            rope_theta=float(doc["rope_theta"]),
            norm_eps=float(doc["rms_norm_eps"]),
            max_seq_len=doc["max_position_embeddings"], **over)

    @property
    def kv_layers(self) -> int:
        """Entries a token keeps in the paged pool: one a (pass, layer), so
        more than the model has layers."""
        return self.n_layers * self.total_ut_steps

    # -- what models/serving.py asks of a configuration -----------------------

    def serving_config(self) -> "OuroConfig":
        """No training-only feature to clear."""
        return self

    def _refuse_quant(self, kv_quant: Optional[str]) -> None:
        if kv_quant is not None:
            raise ValueError(
                "kv_quant: this model's paged pool is float (an int8 pool "
                "with a pass axis does not exist; int8 pools are "
                "models/llama.py's)")

    def paged_model(self, *, page_size: int, kv_pages: int, kernel: str,
                    kv_quant: Optional[str]):
        self._refuse_quant(kv_quant)
        return Ouro(dataclasses.replace(
            self, decode_paged=True, kv_page_size=page_size,
            kv_pages=kv_pages, paged_kernel=kernel))

    @property
    def widest_prefill(self) -> int:
        """The widest prefill program: 256, the widest bucket (dense
        products: a program reads the stack's weights ``T`` times whatever
        its width)."""
        return 256

    def kernel_paths(self, t: int) -> Tuple[str, ...]:
        """No kernel beside the attention read's own."""
        return ()

    def check_kernels(self, *, slots: int, kv_blocks: Optional[int] = None,
                      page_size: Optional[int] = None,
                      pages_per_seq: Optional[int] = None,
                      kv_quant: Optional[str] = None) -> None:
        """Lower the two reads for a TPU at the shapes the programs give
        them: a pool of ``kv_blocks x T`` blocks (a pass's share of a block
        is a block to the kernels)."""
        self._refuse_quant(kv_quant)
        self.lower_read(
            slots=slots,
            kv_blocks=None if kv_blocks is None
            else kv_blocks * self.total_ut_steps,
            page_size=page_size, pages_per_seq=pages_per_seq,
            kv_quant=kv_quant)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "OuroConfig":
        """Every mechanism at a size the CPU tests run: three layers, three
        passes, 4 heads over 4 of 16."""
        return OuroConfig(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            n_kv_heads=4, head_dim=16, d_ff=96, total_ut_steps=3,
            rope_theta=1e4, max_seq_len=128, dtype=jnp.float32,
            param_dtype=jnp.float32, kv_page_size=8)


@dataclasses.dataclass(frozen=True)
class _Places:
    """Where a program's positions are, the same in every pass and layer:
    ``pos`` ``[B, T]`` the positions written and rotated by; ``read``
    ``[B, T]`` the positions the read is told (``-1`` a row of an idle
    slot); ``page_table`` ``[B, P]`` as the engine keeps it (block ids, the
    same for every pass); ``real`` ``[B, T]`` which positions are real."""
    pos: Any
    read: Any
    page_table: Any
    real: Any


jax.tree_util.register_dataclass(
    _Places, data_fields=["pos", "read", "page_table", "real"],
    meta_fields=[])


class OuroAttention(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, u, step, at: _Places):
        from lzy_tpu.ops.paged_attention import (
            paged_attention, paged_scatter_index)

        cfg = self.cfg
        b, t, _ = u.shape
        h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        passes = cfg.total_ut_steps
        with trace.part(trace.PROJ):
            q = into_heads(dense(h * d, "q_proj", cfg)(u), b, t, h, d)
            k = into_heads(dense(kv * d, "k_proj", cfg)(u), b, t, kv, d)
            v = into_heads(dense(kv * d, "v_proj", cfg)(u), b, t, kv, d)
            q = _rope(q, at.pos, cfg.rope_theta)
            k = _rope(k, at.pos, cfg.rope_theta)
        if not cfg.decode_paged:
            with trace.part(trace.ATTN_READ):
                qg = q.reshape(b, t, kv, h // kv, d)
                s = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                               preferred_element_type=jnp.float32) * d ** -0.5
                keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
                out = jnp.einsum("bkgtl,blkd->btkgd", pr.astype(cfg.dtype), v)
        else:
            # a block holds the passes side by side; a pass's share of it is
            # a block of the pool the kernels see (the same memory)
            shape = (cfg.kv_pages, passes, cfg.kv_page_size, kv, d)
            flat = (cfg.kv_pages * passes,) + shape[2:]
            pool_k = self.variable("cache", "k", jnp.zeros, shape, cfg.dtype)
            pool_v = self.variable("cache", "v", jnp.zeros, shape, cfg.dtype)
            if at.page_table is None:
                raise ValueError("a paged forward needs page_table")
            table = at.page_table * passes + step
            keys, values = pool_k.value.reshape(flat), \
                pool_v.value.reshape(flat)
            if not self.is_initializing():
                with trace.part(trace.CACHE_WRITE):
                    blocks, offs = paged_scatter_index(table, at.pos,
                                                       cfg.kv_page_size)
                    # a pad and an idle slot write the pass's scratch block
                    blocks = jnp.where(at.real.reshape(-1), blocks, step)
                    keys = keys.at[blocks, offs].set(
                        k.astype(cfg.dtype).reshape(b * t, kv, d))
                    values = values.at[blocks, offs].set(
                        v.astype(cfg.dtype).reshape(b * t, kv, d))
                    pool_k.value = keys.reshape(shape)
                    pool_v.value = values.reshape(shape)
            out = paged_attention(q, keys, values, table, at.read,
                                  kernel=cfg.paged_kernel, dtype=cfg.dtype)
        # float32 out of the accumulator: it is normed and joins the stream
        with trace.part(trace.PROJ):
            return dense(cfg.d_model, "o_proj", cfg, jnp.float32)(
                out.reshape(b, t, h * d).astype(cfg.dtype))


class OuroLayer(nn.Module):
    """One layer over the float32 stream: each sublayer normed on both
    sides."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x, step, at: _Places):
        cfg = self.cfg

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        y = OuroAttention(cfg, name="attn")(
            norm("attn_norm")(x).astype(cfg.dtype), step, at)
        # a residual sum (and the norm in front of it) is filed with the
        # block it closes
        with trace.part(trace.PROJ):
            a = x + norm("attn_post_norm")(y)
        n = norm("mlp_norm")(a).astype(cfg.dtype)
        with trace.part(trace.FFN):
            hid = jax.nn.silu(dense(cfg.d_ff, "gate_proj", cfg)(n)) \
                * dense(cfg.d_ff, "up_proj", cfg)(n)
            y = dense(cfg.d_model, "down_proj", cfg, jnp.float32)(hid)
            return a + norm("mlp_post_norm")(y)


class OuroPass(nn.Module):
    """One pass: the stack, the final norm, the gate, and the exit rule's
    step. The carry is ``(x, chosen, survive, mass, exit_pass)``: the state
    entering the pass, the state the head will read, ``prod (1 - lam)`` so
    far, the exit distribution's mass so far, and ``t*`` (0: not yet
    chosen)."""
    cfg: OuroConfig

    @nn.compact
    def __call__(self, carry, step, at: _Places):
        cfg = self.cfg
        x, chosen, survive, mass, exit_pass = carry
        # a plain scope, not a part: a pass holds every layer's parts
        with jax.named_scope("loop_pass"):
            for i in range(cfg.n_layers):
                x = OuroLayer(cfg, name=f"layer_{i}")(x, step, at)
            hidden = RMSNorm(cfg.norm_eps, cfg.param_dtype,
                             name="final_norm")(x)
        with trace.part(trace.LOOP_EXIT):
            w = self.param("exit_gate", normal(), (cfg.d_model,),
                           jnp.float32)
            bias = self.param("exit_gate_bias", normal(), (), jnp.float32)
            lam = jax.nn.sigmoid(jnp.einsum(
                "bte,e->bt", hidden, w, precision=_HIGHEST) + bias)
            last = step == cfg.total_ut_steps - 1
            mass = mass + jnp.where(last, survive, lam * survive)
            take = (exit_pass == 0) & (
                (mass >= cfg.early_exit_threshold) | last)
            chosen = jnp.where(take[..., None], hidden, chosen)
            exit_pass = jnp.where(take, step + 1, exit_pass)
            survive = survive * (1.0 - lam)
        return (hidden, chosen, survive, mass, exit_pass), None


class Ouro(nn.Module):
    cfg: OuroConfig

    #: the kind of each cache leaf, by its name (``models/serving.py``);
    #: ``k`` and ``v`` are ``paged``, a block of them ``T`` passes
    CACHE_KINDS = {"index": "index"}
    #: the counters the ``stats`` collection's vector feeds, in its order
    STATS = (LOOP_ROWS, LOOP_EXIT_PASS, ATTN_FULL_KEYS, ATTN_ROWS)

    @nn.compact
    def __call__(self, tokens, page_table=None, valid_len=None):
        cfg = self.cfg
        b, t = tokens.shape
        f32 = jnp.float32
        emb = self.param("embed_tokens", normal(),
                         (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        head = self.param("lm_head", normal(),
                          (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        cached = cfg.decode_paged
        if cached:
            # one index for the model: a layer's entries are at the same
            # positions in every pass
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((b,), jnp.int32))
            start = index.value
        else:
            start = jnp.zeros((b,), jnp.int32)
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
        # a row whose table starts with the scratch block is an idle slot:
        # the decode read is told by position, since a pass's table starts
        # with ``t`` there (the chunk read and lax score what the positions
        # say, and nobody reads an idle slot's result)
        read = pos if page_table is None or t > MAX_Q_TOKENS \
            else jnp.where(page_table[:, :1] != 0, pos, -1)
        at = _Places(pos, read, page_table, row_mask(valid_len, b, t))

        with trace.part(trace.EMBED):
            x = emb.astype(cfg.dtype)[tokens].astype(f32)
            carry = (x, jnp.zeros_like(x), jnp.ones((b, t), f32),
                     jnp.zeros((b, t), f32), jnp.zeros((b, t), jnp.int32))
        if self.is_initializing():
            # the loop's body once: the parameters and the cache are the
            # same tree whatever the trip count
            carry, _ = OuroPass(cfg, name="stack")(carry, jnp.int32(0), at)
        else:
            loop = nn.scan(
                OuroPass, variable_broadcast="params",
                variable_carry="cache", split_rngs={"params": False},
                in_axes=(0, nn.broadcast), length=cfg.total_ut_steps)
            carry, _ = loop(cfg, name="stack")(
                carry, jnp.arange(cfg.total_ut_steps, dtype=jnp.int32), at)
        _, chosen, _, _, exit_pass = carry
        self.sow("intermediates", "exit_pass", exit_pass)
        with trace.part(trace.HEAD):
            if cached and not self.is_initializing():
                index.value = start + t
                if valid_len is not None:
                    self._count(start, valid_len.astype(jnp.int32),
                                exit_pass)
            return jnp.einsum("bte,ve->btv", chosen.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=f32)

    def _count(self, start, ends, exit_pass):
        """The round's counts: real rows, the pass each was read from (at
        its last real position), and the cached keys the real rows read (a
        row at position p reads p + 1) with those rows, a (pass, layer)."""
        live = ends > 0
        rows = jnp.sum(live)
        at_end = jnp.take_along_axis(
            exit_pass, jnp.maximum(ends - 1, 0)[:, None], axis=1)[:, 0]
        reads = self.cfg.kv_layers
        counts = jnp.stack([
            rows, jnp.sum(jnp.where(live, at_end, 0)),
            reads * jnp.sum(jnp.where(live, start + ends, 0)),
            reads * rows]).astype(jnp.int32)
        self.sow("stats", "loop", counts, reduce_fn=lambda a, x: a + x,
                 init_fn=lambda: jnp.zeros((len(Ouro.STATS),), jnp.int32))


def init_params(cfg: OuroConfig, rng: jax.Array):
    """The parameter tree (plain arrays), from an uncached forward over a
    few positions: ``n_layers`` layers, whatever ``total_ut_steps``."""
    plain = dataclasses.replace(cfg, decode_paged=False)
    return nn.meta.unbox(Ouro(plain).init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])

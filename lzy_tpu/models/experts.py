"""What the served models with sparse experts share: the counters their
expert layers feed, the part of the router that tells a layer which of the
experts it holds each row reaches (``models/nemotron_h.py``,
``models/solar_open2.py``, ``models/deepseek_v3.py``,
``models/cohere2_moe.py``, ``models/zaya.py``), and the gated expert layer
three of them are built from (:class:`GatedExperts`).

**The scoring is the family's.** Four of the five score with a sigmoid over
all the routed experts (:func:`sigmoid_scores`: ``sigmoid(u W)``, float32 at
the highest precision: a near-tie among its scores decides which expert a row
reaches; a correction bias of auxiliary-loss-free load balancing for the
choice); ZAYA's scores are a softmax over an MLP that adds the previous
layer's router state (``models/zaya.py``).

**The choice, the weights and the counts are shared**
(:func:`held_weights`): the ``top_k`` largest of ``score + bias`` are chosen
(the bias steers the choice only), the chosen scores are the weights,
renormalised and scaled where the family says so (with one expert a token the
weight is the score itself). **The layer is told which experts it holds**
(``held = (lo, hi)``): the router keeps its published width and its experts
per token, and a chosen expert held elsewhere gets no weight here. Pad
positions and idle slots (``real`` False) are left out of the weights and of
the counts.

**The counts** (:data:`STATS`): one vector a layer, sown into the ``stats``
collection in this order, summed over layers by the engine and carried out
of a decode round with its tokens (``models/serving.py``).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.models.paged_blocks import dense, normal
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

MOE_ASSIGNMENTS = REGISTRY.counter(
    "lzy_moe_assignments_total",
    "(row, chosen expert) pairs of decode rounds, real rows only, a layer")
MOE_HELD_ASSIGNMENTS = REGISTRY.counter(
    "lzy_moe_held_assignments_total",
    "of lzy_moe_assignments_total, those that fell on an expert held here")
MOE_EXPERTS_TOUCHED = REGISTRY.counter(
    "lzy_moe_experts_touched_total",
    "held experts that a decode round's rows reached, a layer a round")
MOE_EXPERTS_HELD = REGISTRY.counter(
    "lzy_moe_experts_held_total",
    "held experts, a layer a round (the denominator of the touched share)")

#: what an expert layer sows into the ``stats`` collection, in this order
STATS = (MOE_ASSIGNMENTS, MOE_HELD_ASSIGNMENTS, MOE_EXPERTS_TOUCHED,
         MOE_EXPERTS_HELD)


def row_mask(valid_len, b: int, t: int):
    """``[B, T]`` bool: which positions are real."""
    if valid_len is None:
        return jnp.ones((b, t), bool)
    return jnp.arange(t)[None, :] < valid_len[:, None]


def sigmoid_scores(layer: nn.Module, um, n_routed: int, *,
                   choice_bias: bool = True):
    """The scoring of the four families whose router is ``sigmoid(u W)``:
    ``([M, n_routed] float32 scores, the choice's correction bias or None)``.
    Called from the expert layer's compact method: the parameters ``router``
    ``[D, n_routed]`` and ``router_bias`` are the layer's own."""
    f32 = jnp.float32
    wr = layer.param("router", nn.initializers.normal(0.02),
                     (um.shape[-1], n_routed), f32)
    scores = jax.nn.sigmoid(jnp.dot(
        um.astype(f32), wr, precision=jax.lax.Precision.HIGHEST))
    bias = layer.param("router_bias", nn.initializers.normal(0.02),
                       (n_routed,), f32) if choice_bias else None
    return scores, bias


def held_weights(layer: nn.Module, scores, real, *, top_k: int,
                 held: Tuple[int, int], bias=None, renormalise: bool = True,
                 scaling: float = 1.0, other_stats: int = 0):
    """``[M, held]`` float32: each row's weight for each expert held here
    (0 where it did not choose it, or is not real), from the family's own
    ``scores`` ``[M, n_routed]`` float32. The ``top_k`` largest of
    ``scores + bias`` are chosen (``bias`` steers the choice only; None for
    none); a row's weight for a chosen expert is its score, renormalised
    over the row's choices where ``renormalise`` says so, times ``scaling``.
    Called from the expert layer's compact method: the row's choices are sown
    as ``intermediates/chosen`` and the layer's counts as ``stats/moe``
    (followed by ``other_stats`` zeros: the places of the counts the model's
    other layers sow, every ``stats`` leaf being one vector of ``STATS``)."""
    lo, hi = held
    n_held = hi - lo
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias,
                              top_k)                             # [M, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalise:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    if scaling != 1.0:
        picked = picked * scaling
    # for whoever asks (``mutable=["intermediates"]``): a row's choices
    layer.sow("intermediates", "chosen", chosen)
    on_held = (chosen >= lo) & (chosen < hi) & real[:, None]
    # [M, k, held]: which held expert each of a row's choices is
    onehot = on_held[:, :, None] & (
        chosen[:, :, None] - lo == jnp.arange(n_held)[None, None, :])
    weights = jnp.sum(jnp.where(onehot, picked[:, :, None], 0.0), axis=1)
    reached = jnp.any(onehot, axis=(0, 1))
    layer.sow("stats", "moe", jnp.stack([
        jnp.sum(real) * top_k, jnp.sum(on_held), jnp.sum(reached),
        jnp.asarray(n_held)] + [jnp.asarray(0)] * other_stats
        ).astype(jnp.int32),
        reduce_fn=lambda a, c: a + c,
        init_fn=lambda: jnp.zeros((4 + other_stats,), jnp.int32))
    return weights


class GatedExperts(nn.Module):
    """Sigmoid router over all the routed experts, the held experts'
    product at hidden width (gated three-matrix experts), a shared expert of
    the same form: Solar-Open2's expert layer and the DeepSeek-V3 family's.
    ``cfg`` gives ``n_routed_experts``, ``experts_held``, ``n_held``,
    ``top_k``, ``routed_scaling``, ``expert_width``, ``shared_width``,
    ``dtype`` and ``param_dtype``; ``other_stats`` as :func:`held_weights`.
    Two answers only some configurations give: ``router_bias`` False (no
    correction bias on the choice) and ``shared_scale`` (the shared part is
    several experts side by side, ``shared_width`` their widths together,
    and their outputs are averaged, not summed: 1 / how many; Command A+)."""
    cfg: Any
    other_stats: int = 0

    @nn.compact
    def __call__(self, u, valid_len=None):
        cfg = self.cfg
        b, t, dm = u.shape
        m = b * t
        f32 = jnp.float32
        um = u.reshape(m, dm)
        with trace.part(trace.ROUTER):
            real = row_mask(valid_len, b, t).reshape(m)
            scores, bias = sigmoid_scores(
                self, um, cfg.n_routed_experts,
                choice_bias=getattr(cfg, "router_bias", True))
            weights = held_weights(
                self, scores, real, top_k=cfg.top_k, held=cfg.experts_held,
                bias=bias, scaling=cfg.routed_scaling,
                other_stats=self.other_stats)
        up_shape = (cfg.n_held, dm, cfg.expert_width)
        wg = self.param("experts_gate", normal(), up_shape, cfg.param_dtype)
        wu = self.param("experts_up", normal(), up_shape, cfg.param_dtype)
        wd = self.param("experts_down", normal(),
                        (cfg.n_held, cfg.expert_width, dm), cfg.param_dtype)
        with trace.part(trace.EXPERTS):
            if self.is_initializing():
                routed = jnp.zeros((m, dm), f32)        # no kernel at init
            else:
                routed = gexp.grouped_experts(
                    um, wu.astype(cfg.dtype), wd.astype(cfg.dtype), weights,
                    gate=wg.astype(cfg.dtype))
            hid = jax.nn.silu(dense(cfg.shared_width, "shared_gate", cfg,
                                     f32)(um)) \
                * dense(cfg.shared_width, "shared_up", cfg, f32)(um)
            shared = dense(dm, "shared_down", cfg, f32)(
                hid.astype(cfg.dtype))
            scale = getattr(cfg, "shared_scale", 1.0)
            out = routed + (shared if scale == 1.0 else shared * scale)
            return out.astype(cfg.dtype).reshape(b, t, dm)

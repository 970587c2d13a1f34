"""Mixture-of-experts MLP with expert parallelism.

Switch/GShard-style top-k routing implemented the XLA way: dispatch and
combine are einsums over one-hot masks, expert weights carry the ``expert``
logical axis (→ ``ep`` mesh axis), and sharding the dispatched tensor over
``ep`` makes XLA insert the token all-to-alls — no hand-written routing
collectives. Capacity-bounded: tokens beyond ``capacity_factor × T/E`` per
expert are dropped (residual passes them through), the standard behavior.

No reference counterpart (the reference has no tensor parallelism at all,
SURVEY.md §2.4); this is part of the TPU build's distributed-first mandate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.utils import trace


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_aux_weight: float = 0.01


class MoeMlp(nn.Module):
    """Drop-in MLP block: [B, T, D] → ([B, T, D], aux_loss)."""

    cfg: MoeConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        b, t, d = x.shape
        n_tokens = b * t
        e = cfg.n_experts
        capacity = max(1, int(cfg.capacity_factor * n_tokens * cfg.top_k / e))

        router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "expert")
            ),
            (d, e), cfg.param_dtype,
        )
        w_in = self.param(
            "w_in",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")
            ),
            (e, d, cfg.d_ff), cfg.param_dtype,
        )
        w_out = self.param(
            "w_out",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")
            ),
            (e, cfg.d_ff, d), cfg.param_dtype,
        )

        with trace.part(trace.ROUTER):
            tokens = x.reshape(n_tokens, d)
            # routing in f32: tiny matmul, numerics matter
            logits = tokens.astype(jnp.float32) @ router.astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)                    # [N, E]

            # top-k choice per token
            gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)    # [N, K]
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(axis=-1, keepdims=True), 1e-9
            )

            # capacity assignment per (token, choice): position within the
            # chosen expert's buffer via a cumulative count in token order
            onehot = jax.nn.one_hot(expert_idx, e,
                                    dtype=jnp.float32)             # [N, K, E]
            flat_choice = onehot.reshape(n_tokens * cfg.top_k, e)
            position = (jnp.cumsum(flat_choice, axis=0) - flat_choice).reshape(
                n_tokens, cfg.top_k, e
            )
            position = (position * onehot).sum(-1)                     # [N, K]
            within = position < capacity
            gate_vals = gate_vals * within

            # dispatch [N, E, C] / combine [N, E, C]
            pos_onehot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)
            dispatch = jnp.einsum("nke,nkc->nec", onehot,
                                  pos_onehot * within[..., None])
            combine = jnp.einsum("nke,nkc->nec", onehot * gate_vals[..., None],
                                 pos_onehot)

        # expert compute: [E, C, D] — sharding 'expert'→ep makes this the
        # all-to-all boundary
        with trace.part(trace.EXPERTS):
            expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                                   tokens.astype(jnp.float32)).astype(
                cfg.dtype)
            h = jnp.einsum("ecd,edf->ecf", expert_in, w_in.astype(cfg.dtype))
            h = nn.gelu(h)
            expert_out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(cfg.dtype))

            out = jnp.einsum("nec,ecd->nd", combine,
                             expert_out.astype(jnp.float32))

        # load-balancing auxiliary loss (Switch §2.2): mean gate prob × mean
        # token fraction per expert, scaled by E
        with trace.part(trace.ROUTER):
            token_frac = onehot[:, 0, :].mean(axis=0)          # top-1 share
            prob_frac = probs.mean(axis=0)
            aux = cfg.router_aux_weight * e * jnp.sum(token_frac * prob_frac)

        return out.reshape(b, t, d).astype(x.dtype), aux

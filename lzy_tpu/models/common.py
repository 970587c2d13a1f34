"""Shared model utilities: losses, flax logical-partitioning glue."""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from lzy_tpu.utils import trace


@trace.part(trace.LOSS)
def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-level CE in float32 regardless of compute dtype (numerics)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )[..., 0]
    nll = logz - label_logit
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def param_logical_axes(boxed_params: Any) -> Any:
    """Extract logical-axis tuples from a flax param tree initialized with
    ``nn.with_logical_partitioning``. Leaves without metadata get fully
    replicated axes. The result plugs into
    ``lzy_tpu.parallel.make_train_step(param_logical_axes=...)``."""

    def axes(leaf):
        if isinstance(leaf, nn.LogicallyPartitioned):
            return tuple(leaf.names)
        return (None,) * jnp.ndim(leaf)

    return jax.tree_util.tree_map(
        axes, boxed_params,
        is_leaf=lambda x: isinstance(x, nn.LogicallyPartitioned),
    )


def unbox(boxed_params: Any) -> Any:
    return nn.meta.unbox(boxed_params)


def count_params(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))

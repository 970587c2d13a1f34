"""Logical-axis sharding rules.

The t5x/flax "logical axes" recipe, implemented natively: model code annotates
parameters with logical names (``("embed", "mlp")``), a rule table maps logical
names to mesh axes, and XLA inserts the collectives. This is the idiomatic
TPU answer to what GPU frameworks do with hand-written NCCL calls
(scaling-book recipe: pick a mesh, annotate shardings, let XLA do the rest).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Optional[Union[str, Tuple[str, ...]]]]

# default rule table: batch splits over (dp, fsdp); params shard over fsdp on
# their largest axis; tp splits heads/mlp; sp splits sequence for long context
DEFAULT_RULES: Rules = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "mlp": "tp",
    "heads": "tp",
    "heads_merged": "tp",
    "kv": None,
    "head_dim": None,
    "vocab": "tp",
    "expert": "ep",
    "norm": None,
    "embed_out": None,
    # activation anchors: the residual stream and logits shard over tp,
    # NEVER fsdp — fsdp shards *params* on model dims and *batch* on the
    # batch dim; letting the partitioner put an activation's model dim on
    # fsdp instead makes it batch-all-gather [B,T,V]-sized intermediates
    # (the 377 MB pred gathers tests/test_aot_topology.py pins)
    "act_embed": "tp",
    "act_vocab": "tp",
    "act_mlp": "tp",
    "act_heads": "tp",
    # merged attention output entering o_proj: replicated by default so a
    # head-sharded decode forward all-gathers BEFORE the o_proj matmul —
    # sharding the contraction dim would make GSPMD psum partial products
    # and break bit-identity with the single-device engine
    "act_attn_out": None,
    "stage": "pp",
    # conv models
    "conv_spatial": None,
    "channels_in": None,
    "channels_out": "fsdp",
}


def freeze_rules(rules: Optional[Rules]):
    """A hashable form of a rule-override table, for threading through
    flax module fields (``models.llama.Llama(cfg, rules=...)``) — module
    attributes must stay hashable for jit/remat static handling. Thaw
    with ``dict(frozen)``; None/empty stays None (= DEFAULT_RULES)."""
    if not rules:
        return None
    return tuple(sorted(rules.items()))


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Rules] = None) -> P:
    rules = {**DEFAULT_RULES, **(rules or {})}
    parts = []
    for name in logical_axes:
        if name is None:
            parts.append(None)
            continue
        if name not in rules:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        parts.append(rules[name])
    return P(*parts)


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   rules: Optional[Rules] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules))


def tree_shardings(mesh: Mesh, logical_tree: Any,
                   rules: Optional[Rules] = None) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: named_sharding(mesh, *axes, rules=rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def infer_param_logical_axes(params: Any) -> Any:
    """Heuristic logical axes for an un-annotated param tree: shard the
    LARGEST dimension of every ≥2D tensor over fsdp, replicate the rest.
    Correct-by-construction for FSDP (any consistent choice works); models
    with explicit annotations (lzy_tpu.models) override this."""

    def axes_for(x):
        if x.ndim < 2:
            return (None,) * x.ndim
        largest = int(max(range(x.ndim), key=lambda i: x.shape[i]))
        return tuple("embed" if i == largest else None for i in range(x.ndim))

    return jax.tree_util.tree_map(axes_for, params)


def shard_tree(tree: Any, mesh: Mesh, logical_tree: Any,
               rules: Optional[Rules] = None) -> Any:
    """Device-put a pytree with shardings derived from logical axes."""
    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.device_put(tree, shardings)


def manual_axes() -> set:
    """Mesh axes bound as manual (``shard_map``) at this trace point. A
    sharding constraint naming one is rejected by the partitioner, and a
    nested ``shard_map`` cannot re-bind it, so anchors strip these axes
    and ring/Ulysses attention run their per-shard body directly."""
    ctx = jax.sharding.get_abstract_mesh()
    return set() if ctx.empty else set(ctx.manual_axes)


def inside_manual(axis: str) -> bool:
    """True when tracing inside a manual region that binds ``axis``."""
    return axis in manual_axes()

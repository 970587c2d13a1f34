"""Pipeline parallelism: GPipe-style microbatch streaming over the ``pp`` axis.

Stage parameters are stacked on a leading stage dimension sharded over ``pp``
(logical axis ``"stage"``); ``shard_map`` gives each device its own stage, and
activations flow stage→stage with ``lax.ppermute`` (neighbor ICI hops — the
reason ``pp`` is the outermost mesh axis: it needs the least bandwidth).
The schedule is the classic GPipe fill-drain loop: ``n_micro + n_stages - 1``
ticks, stage 0 injecting a fresh microbatch each tick while real work ripples
down the ring; bubbles shrink as ``n_micro`` grows.

Constraint (standard for this pattern): every stage runs the same ``stage_fn``
shape — e.g. "k transformer layers" — with per-stage weights.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pp",
    seq_axis: str = None,
    with_aux: bool = False,
    pass_micro_index: bool = False,
):
    """Run ``x`` through ``n_stages`` sequential applications of ``stage_fn``.

    - ``stage_params``: pytree whose leaves have leading dim ``n_stages``
      (sharded over ``axis``); stage ``i`` uses leaf ``[i]``.
    - ``x``: ``[n_micro, micro_batch, ...]`` microbatched input (replicated).
    - ``seq_axis``: composes the pipeline with ring sequence parallelism:
      the manual region covers ``{axis, seq_axis}`` and ``x``'s dim 2 (the
      sequence) enters sharded over ``seq_axis``, so a ring-attention body
      inside ``stage_fn`` runs directly against the manual axis (nested
      shard_maps cannot re-bind an axis — both partitioners reject it).
    - ``with_aux``: ``stage_fn`` returns ``(y, aux_scalar)`` (e.g. MoE
      load-balancing losses); the pipeline sums aux over stages and
      AVERAGES over microbatches, masking out the fill/drain bubble ticks
      where a stage chews on garbage (their aux must not leak into the
      loss). Returns ``(outs, aux)``.
    - ``pass_micro_index``: ``stage_fn`` is called as ``stage_fn(params,
      h, micro_idx)`` where ``micro_idx`` is the (traced, clamped) index
      of the microbatch this stage is processing this tick — the hook
      for per-microbatch side inputs closed over by the caller (packed
      segment ids, masks) that must follow their microbatch through the
      stages.

    Returns ``[n_micro, micro_batch, ...]`` outputs, equal to applying the
    stages sequentially to each microbatch (plus aux when ``with_aux``).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    dtype = x.dtype

    param_specs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def local(params_local, x_all):
        # params_local leaves: [1, ...] — this device's stage
        params = jax.tree_util.tree_map(lambda a: a[0], params_local)
        rank = lax.axis_index(axis)
        if seq_axis is not None:
            # params are pp-varying but the activations are (pp, sp)-
            # varying; the implicit pvary that unifies them would happen
            # AFTER the model's bf16 cast, and its psum transpose on bf16
            # grads crashes XLA:CPU's AllReducePromotion (same bug as the
            # f32 boundary note below). Pre-vary in param dtype (f32)
            # so the backward's sp-psum of param grads stays f32.
            sp_vary = lax.axis_index(seq_axis) * 0
            params = jax.tree_util.tree_map(
                lambda a: a + sp_vary.astype(a.dtype), params)
        total = n_micro + n_stages - 1

        # the carry is device-varying over pp (each rank banks different
        # values), so the zero-init must carry that vma type too or the
        # cond/scan type checks reject the mix. Derive the zeros from the
        # (varying) rank index instead of lax.pcast: a bf16 pcast lowers to
        # a copy-computation all-reduce that crashes XLA:CPU's
        # AllReducePromotion pass (hlo_instruction.cc "Invalid binary
        # instruction opcode copy"), while this arithmetic form lowers to
        # plain elementwise ops on every backend.
        # x_all enters f32 (see the boundary note below) and becomes the
        # compute dtype here; adding zero_v also makes it pp-varying so the
        # tick's where(rank==0, inject, buf) needs no implicit pvary.
        vary = rank * 0
        if seq_axis is not None:
            # the seq-sharded input is seq_axis-varying; the zero-inits and
            # injected microbatches must carry the same vma type
            vary = vary + lax.axis_index(seq_axis) * 0
        zero_v = vary.astype(dtype)
        # varying-making add BEFORE the downcast: the implicit pvary (and
        # its psum transpose in the backward) must see f32, not bf16
        x_all = (x_all + vary.astype(x_all.dtype)).astype(dtype)
        micro_shape = x_all.shape[1:]
        outs0 = jnp.zeros((n_micro,) + micro_shape, dtype) + zero_v
        buf0 = jnp.zeros(micro_shape, dtype) + zero_v
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        # shape (1,), not scalar: legacy shard_map's partial-eval stamps
        # residuals with a dim-0 sharding, which is ill-formed for rank-0
        # arrays — any scalar crossing the forward/backward split aborts
        # grad tracing. Kept 1-D through the region, squeezed outside.
        aux0 = jnp.zeros((1,), jnp.float32) + zero_v.astype(jnp.float32)

        def tick(carry, t):
            buf_in, outs, aux_acc = carry
            # stage 0 injects microbatch t (clamped; masked out past the end)
            inject = x_all[jnp.minimum(t, n_micro - 1)]
            cur = jnp.where(rank == 0, inject, buf_in)
            # this rank processes microbatch t-rank (clamped into range:
            # fill/drain ticks chew on garbage and their outputs/aux are
            # masked out downstream)
            micro_idx = jnp.clip(t - rank, 0, n_micro - 1)
            call = ((lambda p, h: stage_fn(p, h, micro_idx))
                    if pass_micro_index else stage_fn)
            if with_aux:
                y, aux = call(params, cur)
                working = (t >= rank) & (t - rank < n_micro)
                aux_acc = aux_acc + jnp.where(
                    working, aux.astype(jnp.float32), 0.0)
            else:
                y = call(params, cur)
            # last stage banks finished microbatch t-(n_stages-1)
            out_idx = t - (n_stages - 1)
            valid = (rank == n_stages - 1) & (out_idx >= 0)
            outs = lax.cond(
                valid,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
                lambda o: o,
                outs,
            )
            buf_next = lax.ppermute(y, axis, perm)
            return (buf_next, outs, aux_acc), None

        (_, outs, aux_acc), _ = lax.scan(
            tick, (buf0, outs0, aux0), jnp.arange(total))
        # only the last stage banked real outputs (every other rank kept
        # zeros), so a psum replicates them to all ranks in one collective
        outs = lax.psum(outs.astype(jnp.float32), axis)
        if with_aux:
            # sum over stages (each rank accumulated its own layers' aux),
            # mean over microbatches — equal micro sizes make this exactly
            # the dense full-batch aux; still (1,) at the boundary (see
            # the aux0 note)
            aux_out = lax.psum(aux_acc, axis) / n_micro
            if seq_axis is not None:
                # each sp rank's MoE routers scored only its sequence
                # chunk, so its aux is a chunk-local estimate; the sp-mean
                # replicates one consistent value (NOT the exact dense
                # full-sequence aux — the balancing loss is nonlinear in
                # the routing stats — but an unbiased per-chunk average,
                # which is what matters for the gradient pressure). The
                # replication also makes the P() out_spec truthful.
                aux_out = lax.pmean(aux_out, seq_axis)
            return outs, aux_out
        return outs

    # only ``pp`` is manual: the other mesh axes (dp/fsdp/tp) stay auto, so
    # the stage body's matmuls are sharded by XLA from the params' own
    # shardings — pipeline composes with fsdp/tp instead of forcing stage
    # params replicated onto every device.
    # The boundary (x in, outs out, and their grad transposes) is f32: the
    # partial-manual lowering wraps boundary all-reduces' reduction bodies
    # in a sharding constraint, and XLA:CPU's AllReducePromotion pass
    # crashes cloning that body for promoted (bf16) types — f32 is never
    # promoted. Inside, compute stays in x.dtype; one boundary-sized f32
    # collective is noise next to the pipeline itself.
    manual = {axis}
    x_spec = P()
    if seq_axis is not None:
        manual = {axis, seq_axis}
        x_spec = P(None, None, seq_axis)
    out_specs = (x_spec, P()) if with_aux else x_spec
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=out_specs,
        axis_names=manual,
    )(stage_params, x.astype(jnp.float32))
    if with_aux:
        y, aux = out
        return y.astype(dtype), aux[0]
    return out.astype(dtype)

"""Ulysses-style sequence parallelism: all-to-all attention.

The complement to ring attention (``lzy_tpu/parallel/ring.py``) for long
sequences: instead of streaming K/V blocks around a ring, two all-to-alls
re-shard the problem — heads gather the FULL sequence while the head dimension
splits across ``sp``:

    [B, H, T/n, D] --all-to-all--> [B, H/n, T, D]   (exact local attention)
                   --all-to-all--> [B, H, T/n, D]

Each device then runs an exact (flash/chunked) attention over the whole
sequence for its head shard. Ring wins when T is huge and H is small;
Ulysses wins when H ≥ n and the two all-to-alls are cheaper than n ppermute
rounds. Requires ``n_heads % sp == 0``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from lzy_tpu.parallel.sharding import inside_manual


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    q_spec: P = P(("dp", "fsdp"), None, "sp", None),
    segment_ids: Optional[jax.Array] = None,
    seg_spec: P = P(("dp", "fsdp"), "sp"),
) -> jax.Array:
    """q/k/v: global ``[B, H, T, D]`` with T sharded over ``axis``; returns the
    same layout. Exact attention (computed via the chunked online-softmax
    kernel on each device's full-sequence head shard).

    ``segment_ids``: optional global ``[B, T]`` packed-document ids (T
    sharded like q; a document = a contiguous run of equal ids);
    all-gathered over ``axis`` so each head shard masks against the full
    sequence (ids are int32 — the gather is negligible next to the K/V
    all-to-alls)."""
    n = mesh.shape[axis]
    h = q.shape[1]
    if h % n:
        raise ValueError(f"n_heads={h} must be divisible by {axis}={n}")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if segment_ids is not None:
        # global run starts BEFORE sharding (see ring.py for the rationale)
        from lzy_tpu.ops.flash_attention import document_starts

        segment_ids = document_starts(segment_ids)

    def local_fn(q_blk, k_blk, v_blk, seg_blk):
        # local: [B, H, T/n, D] → heads scatter, sequence gathers
        def seq_to_head(x):
            # split_axis=1 (heads), concat_axis=2 (sequence)
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        def head_to_seq(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        qg, kg, vg = (seq_to_head(x) for x in (q_blk, k_blk, v_blk))
        seg_full = None
        if seg_blk is not None:
            seg_full = lax.all_gather(seg_blk, axis, axis=1, tiled=True)
        # [B, H/n, T, D]: exact attention over the full sequence
        from lzy_tpu.ops.attention import chunked_attention

        out = chunked_attention(qg, kg, vg, causal=causal, scale=scale,
                                segment_ids=seg_full)
        return head_to_seq(out)

    if segment_ids is None:
        fn, in_specs, args = (functools.partial(local_fn, seg_blk=None),
                              (q_spec, q_spec, q_spec), (q, k, v))
    else:
        fn, in_specs, args = (local_fn, (q_spec, q_spec, q_spec, seg_spec),
                              (q, k, v, segment_ids))
    if inside_manual(axis):
        # Composition with the pp pipeline (same shape as ring.py): we are
        # already inside a manual region holding the sp axis, the inputs
        # are per-rank chunks, and the all-to-alls run directly against
        # the manual axis — a nested shard_map cannot re-bind it.
        if segment_ids is not None:
            raise ValueError(
                "packed segments do not compose with ulysses attention "
                "inside an already-manual region (document_starts would "
                "renumber per-chunk); unpack or drop sp from the pipeline "
                "mesh")
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=q_spec, check_vma=False,
    )(*args)

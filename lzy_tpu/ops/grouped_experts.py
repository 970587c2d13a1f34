"""A dropless product over the experts a chip holds: every row reaches
every held expert it chose, whatever the others chose (ROADMAP S4; the
one-hot dispatch of ``models/moe.py`` drops rows over a capacity).

The experts are ungated two-matrix MLPs with a squared ReLU,
``E_e(v) = relu(v W1_e)^2 W2_e``, and the result is
``sum_e w[m, e] * E_e(v[m])`` over the held experts, ``w`` being 0 where row
``m`` did not choose ``e`` (or is a pad row, or an idle slot).

:func:`grouped_experts` is written for the serving batch, where the rows are
few (a decode round's slots, a prefill chunk's positions: 8 to 256) and the
experts many: the cost is the read of each touched expert's weights from HBM
(11 MB at the Nemotron-3-Super widths), not arithmetic; at 256 rows the
arithmetic is as long as the read and the pipeline still overlaps the two
(``NemotronHConfig.widest_prefill`` has the timings). So the kernel
(``grouped_experts`` in a device trace) walks the **touched** experts, reads
each one's two matrices once, in tiles of the intermediate width, multiplies
all the rows by it (one pass of the matrix unit whatever the row count) and
adds each row's share weighted by ``w``: an expert nobody chose is never
read, a row that did not choose the expert adds 0. It sorts nothing and pads
nothing, and it is exact in which rows reach which experts. Its arithmetic
grows with rows x touched experts, so a batch of thousands of rows (training)
wants a sorted, ragged product instead; no cell has one.

:func:`lax_grouped_experts` is the same sum in plain ``jax.numpy`` (a scan
over the held experts, reading all of them): the oracle of the kernel's
tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret

#: ``lzy_kernel_dispatch_total{path}`` label of a program that holds the kernel
PATH = "experts_pallas"


def _tile(width: int) -> int:
    """The widest tile of the intermediate width that is a multiple of 128,
    divides it and keeps a pair of double-buffered weight tiles inside the
    default VMEM budget (a tile of 1024 x 896 bfloat16 is 1.8 MB)."""
    for lanes in range(min(width, 1024) // 128 * 128, 0, -128):
        if width % lanes == 0:
            return lanes
    return width


def _kernel(ids_ref, n_ref, x_ref, w1_ref, w2_ref, wc_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _():
        h = jnp.dot(x_ref[...], w1_ref[0],
                    preferred_element_type=jnp.float32)      # [M, tile]
        h = jnp.square(jnp.maximum(h, 0.0)) * wc_ref[0]      # [M, 1] weights
        o_ref[...] += jnp.dot(h.astype(w2_ref.dtype), w2_ref[0],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_grouped(x, w1, w2, weights, *, interpret: bool):
    m, latent = x.shape
    e, _, width = w1.shape
    tile = _tile(width)
    tiles = width // tile
    touched = jnp.any(weights != 0.0, axis=0)                # [E]
    n = jnp.sum(touched).astype(jnp.int32)
    # the touched experts first, in their own order
    ids = jnp.argsort(~touched, stable=True).astype(jnp.int32)

    def expert(i, ids, n):
        return ids[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    def tile_of(i, j, n):
        # past the last touched expert every step names the block the last
        # real step held: nothing more is fetched
        return jnp.where(i < n[0], j, tiles - 1)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, tiles),
            in_specs=[
                pl.BlockSpec((m, latent), lambda i, j, ids, n: (0, 0)),
                pl.BlockSpec((1, latent, tile), lambda i, j, ids, n:
                             (expert(i, ids, n), 0, tile_of(i, j, n))),
                pl.BlockSpec((1, tile, latent), lambda i, j, ids, n:
                             (expert(i, ids, n), tile_of(i, j, n), 0)),
                pl.BlockSpec((1, m, 1), lambda i, j, ids, n:
                             (expert(i, ids, n), 0, 0)),
            ],
            out_specs=pl.BlockSpec((m, latent), lambda i, j, ids, n: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_experts",
    )(ids, n.reshape(1), x, w1, w2,
      weights.astype(jnp.float32).T[:, :, None])


def grouped_experts(x: jax.Array, w1: jax.Array, w2: jax.Array,
                    weights: jax.Array, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """``x`` [M, L] (the experts' input, in the weights' dtype), ``w1``
    [E, L, F], ``w2`` [E, F, L], ``weights`` [M, E] float32 (0 where the row
    does not reach the expert). Returns ``[M, L]`` float32."""
    return _pallas_grouped(x.astype(w1.dtype), w1, w2, weights,
                           interpret=_interpret.resolve(interpret))


def lax_grouped_experts(x, w1, w2, weights):
    """The same sum over every held expert, one after another."""
    x = x.astype(w1.dtype)

    def one(acc, ew):
        a, b, col = ew
        h = jnp.dot(x, a, preferred_element_type=jnp.float32)
        h = jnp.square(jnp.maximum(h, 0.0)) * col[:, None]
        return acc + jnp.dot(h.astype(b.dtype), b,
                             preferred_element_type=jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (w1, w2, weights.astype(jnp.float32).T))
    return out


def lower_for_tpu(*, rows: int, experts: int, latent: int, width: int,
                  dtype) -> None:
    """Lower the kernel for a TPU at these shapes with no device, and let
    the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    jax.jit(functools.partial(_pallas_grouped.__wrapped__, interpret=False)
            ).trace(
        sds((rows, latent), dtype), sds((experts, latent, width), dtype),
        sds((experts, width, latent), dtype),
        sds((rows, experts), jnp.float32),
    ).lower(lowering_platforms=("tpu",))

"""A dropless product over the experts a chip holds: every row reaches
every held expert it chose, whatever the others chose (ROADMAP S4; the
one-hot dispatch of ``models/moe.py`` drops rows over a capacity).

An expert takes one of two forms, and one kernel computes both: ungated
two-matrix MLPs with a squared ReLU, ``E_e(v) = relu(v W1_e)^2 W2_e``
(Nemotron-3-Super's, in a latent space of 1024), or, where the caller gives
a third matrix ``gate``, gated three-matrix MLPs,
``E_e(v) = (silu(v Wg_e) * (v W1_e)) W2_e`` (Solar-Open2's, at the hidden
width of 4096). The result is ``sum_e w[m, e] * E_e(v[m])`` over the held
experts, ``w`` being 0 where row ``m`` did not choose ``e`` (or is a pad
row, or an idle slot).

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
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` label of a program that holds the kernel
PATH = "experts_pallas"


#: bytes of one weight tile: two or three of them, double-buffered, and the
#: rows' input and float32 result stay inside the kernel's VMEM
_TILE_BYTES = 2 << 20
#: above this the kernel asks for its VMEM by name (the compiler's default
#: scoped limit is 16 MiB of a v5e core's 128)
_DEFAULT_VMEM = 14 << 20


def _tile(width: int, latent: int, itemsize: int) -> int:
    """The widest tile of the intermediate width that is a multiple of 128,
    divides it and is at most ``_TILE_BYTES`` (1024 x 896 bfloat16, 1.8 MB;
    at an input of 4096, 4096 x 256)."""
    most = max(128, _TILE_BYTES // (latent * itemsize))
    for lanes in range(min(width, most, 1024) // 128 * 128, 0, -128):
        if width % lanes == 0:
            return lanes
    return width


def _kernel(ids_ref, n_ref, x_ref, *refs, gated: bool):
    *w_refs, w2_ref, wc_ref, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _():
        h = jnp.dot(x_ref[...], w_refs[-1][0],
                    preferred_element_type=jnp.float32)      # [M, tile]
        if gated:
            h = h * jax.nn.silu(jnp.dot(
                x_ref[...], w_refs[0][0],
                preferred_element_type=jnp.float32))
        else:
            h = jnp.square(jnp.maximum(h, 0.0))
        h = h * wc_ref[0]                                    # [M, 1] weights
        o_ref[...] += jnp.dot(h.astype(w2_ref.dtype), w2_ref[0],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_grouped(x, w1, w2, weights, gate=None, *, interpret: bool):
    m, latent = x.shape
    e, _, width = w1.shape
    size = jnp.dtype(w1.dtype).itemsize
    tile = _tile(width, latent, size)
    tiles = width // tile
    ups = (w1,) if gate is None else (gate, w1)
    # what the pipeline keeps in VMEM: every block twice
    vmem = 2 * ((len(ups) + 1) * latent * tile * size
                + m * latent * (size + 4) + m * 128 * 4)
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

    up_spec = pl.BlockSpec((1, latent, tile), lambda i, j, ids, n:
                           (expert(i, ids, n), 0, tile_of(i, j, n)))
    return pl.pallas_call(
        functools.partial(_kernel, gated=gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, tiles),
            in_specs=[
                pl.BlockSpec((m, latent), lambda i, j, ids, n: (0, 0)),
                *[up_spec] * len(ups),
                pl.BlockSpec((1, tile, latent), lambda i, j, ids, n:
                             (expert(i, ids, n), tile_of(i, j, n), 0)),
                pl.BlockSpec((1, m, 1), lambda i, j, ids, n:
                             (expert(i, ids, n), 0, 0)),
            ],
            out_specs=pl.BlockSpec((m, latent), lambda i, j, ids, n: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=None if vmem <= _DEFAULT_VMEM
            else vmem + (8 << 20)),
        interpret=interpret,
        name="grouped_experts",
    )(ids, n.reshape(1), x, *ups, w2,
      weights.astype(jnp.float32).T[:, :, None])


@trace.part(trace.EXPERTS)
def grouped_experts(x: jax.Array, w1: jax.Array, w2: jax.Array,
                    weights: jax.Array, *, gate: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """``x`` [M, L] (the experts' input, in the weights' dtype), ``w1``
    [E, L, F], ``w2`` [E, F, L], ``weights`` [M, E] float32 (0 where the row
    does not reach the expert); ``gate`` [E, L, F] makes the experts gated
    (``silu(x gate) * (x w1)`` where there is none ``relu(x w1)^2``).
    Returns ``[M, L]`` float32."""
    return _pallas_grouped(x.astype(w1.dtype), w1, w2, weights, gate,
                           interpret=_interpret.resolve(interpret))


@trace.part(trace.EXPERTS)
def lax_grouped_experts(x, w1, w2, weights, gate=None):
    """The same sum over every held expert, one after another."""
    x = x.astype(w1.dtype)
    gated = gate is not None

    def one(acc, ew):
        a, b, col, *g = ew
        h = jnp.dot(x, a, preferred_element_type=jnp.float32)
        if gated:
            h = h * jax.nn.silu(jnp.dot(
                x, g[0], preferred_element_type=jnp.float32))
        else:
            h = jnp.square(jnp.maximum(h, 0.0))
        h = h * col[:, None]
        return acc + jnp.dot(h.astype(b.dtype), b,
                             preferred_element_type=jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (w1, w2, weights.astype(jnp.float32).T) + ((gate,) if gated else ()))
    return out


def lower_for_tpu(*, rows: int, experts: int, latent: int, width: int,
                  dtype, gated: bool = False) -> None:
    """Lower the kernel for a TPU at these shapes with no device, and let
    the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    up = sds((experts, latent, width), dtype)
    jax.jit(functools.partial(_pallas_grouped.__wrapped__, interpret=False)
            ).trace(
        sds((rows, latent), dtype), up,
        sds((experts, width, latent), dtype),
        sds((rows, experts), jnp.float32), up if gated else None,
    ).lower(lowering_platforms=("tpu",))

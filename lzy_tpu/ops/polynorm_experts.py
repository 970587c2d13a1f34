"""The dropless product over the experts a chip holds, for experts whose
activation is **normalised over their width**: gated three-matrix MLPs under
PolyNorm (PolyCom, arXiv:2411.03884; ``models/motif.py``),

    E_e(v) = (P_e(v Wg_e) * (v Wu_e)) Wd_e
    P(z)   = scale * ( w_3 z^3 / rms(z^3) + w_2 z^2 / rms(z^2) + w_1 z / rms(z)
                       + clamp(b, -clamp, clamp) ),        rms over the width m

with one ``(w_1, w_2, w_3, b)`` an expert. The result is ``sum_e w[m, e] *
E_e(v[m])`` over the held experts, ``w`` being 0 where row ``m`` did not
choose ``e`` (or is a pad row, or an idle slot), as
``ops/grouped_experts.py``, whose experts (SiLU, squared ReLU) act a value at
a time: that kernel walks an expert's width in tiles and is done with a tile
when it leaves it. Here a row's ``rms(z^k)`` needs all of ``z`` before any of
``P(z) * up`` can be formed.

:func:`polynorm_experts` (``polynorm_experts`` in a device trace) walks the
**touched** experts by scalar prefetch (an expert nobody chose is never read)
and gives each **two phases over its tiles**: the gate product, a tile at a
time, into a ``[rows, width]`` float32 scratch in VMEM (1.3 MB at 256 rows of
1,280) with the rows' sums of ``z^2``, ``z^4`` and ``z^6``; then up and down
tile by tile, the activation taken from the scratch. Every weight byte is
read once, as in the other kernel: while the gate's tiles arrive the up and
down specs stand on the expert's first tile, and while those walk, the
gate's stands on its last. **The other exact form**, four accumulators
``sum_tiles (z^k * up) Wd`` scaled by ``w_k / rms_k`` at the end, needs no
scratch and one phase, and costs four down products for one: at 256 rows the
arithmetic is already as long as the read, so it was not taken. The powers,
the sums and the norms are float32 (a bfloat16 ``z^3`` loses the norm); the
activation times ``up`` times the row's weight is rounded once, to the
weights' type, for the down product.

:func:`lax_polynorm_experts` is the same sum in plain ``jax.numpy`` (a scan
over the held experts, reading all of them): the portable path and the
kernel's oracle. :func:`polynorm` is the activation alone, which the shared
expert and the dense MLP (plain XLA matmuls) use too.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.ops.grouped_experts import _DEFAULT_VMEM, _tile
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` labels of a program with the product
PATH = "polynorm_experts_pallas"
LAX_PATH = "polynorm_experts_lax"

#: PolyCom's epsilon under each root
EPS = 1e-6


def path(kernel: str) -> str:
    return PATH if kernel == "pallas" else LAX_PATH


def polynorm(z, params, *, scale: float, clamp: float, eps: float = EPS):
    """``P(z)`` over the last axis of ``z`` (float32 inside), ``params``
    ``[..., 4]`` = ``(w_1, w_2, w_3, b)`` broadcast against ``z``'s leading
    axes. Returns float32."""
    z = z.astype(jnp.float32)
    p = params.astype(jnp.float32)
    z2 = z * z

    def normed(v):
        return v * jax.lax.rsqrt(
            jnp.mean(v * v, axis=-1, keepdims=True) + eps)

    return scale * (p[..., 2:3] * normed(z2 * z) + p[..., 1:2] * normed(z2)
                    + p[..., 0:1] * normed(z)
                    + jnp.clip(p[..., 3:4], -clamp, clamp))


def _kernel(ids_ref, n_ref, pn_ref, x_ref, g_ref, u_ref, d_ref, wc_ref,
            o_ref, z_ref, s_ref, *, tiles: int, width: int, scale: float,
            clamp: float, eps: float):
    f32 = jnp.float32
    i, s = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (s == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    live = i < n_ref[0]

    @pl.when(live & (s < tiles))
    def _():
        z = jnp.dot(x_ref[...], g_ref[0], preferred_element_type=f32)
        z_ref[s] = z
        z2 = z * z
        z4 = z2 * z2
        sums = [jnp.sum(v, axis=-1, keepdims=True)
                for v in (z2, z4, z4 * z2)]

        @pl.when(s == 0)
        def _():
            for k in range(3):
                s_ref[k] = sums[k]

        @pl.when(s > 0)
        def _():
            for k in range(3):
                s_ref[k] += sums[k]

    @pl.when(live & (s >= tiles))
    def _():
        e = ids_ref[i]
        z = z_ref[s - tiles]
        z2 = z * z
        inv = [jax.lax.rsqrt(s_ref[k] / width + eps) for k in range(3)]
        bias = jnp.clip(pn_ref[e, 3], -clamp, clamp)
        act = scale * (pn_ref[e, 2] * (z2 * z * inv[2])
                       + pn_ref[e, 1] * (z2 * inv[1])
                       + pn_ref[e, 0] * (z * inv[0]) + bias)
        h = act * jnp.dot(x_ref[...], u_ref[0], preferred_element_type=f32)
        h = h * wc_ref[0]                                    # [M, 1] weights
        o_ref[...] += jnp.dot(h.astype(d_ref.dtype), d_ref[0],
                              preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("scale", "clamp", "interpret"))
def _pallas_polynorm(x, gate, up, down, params, weights, *, scale: float,
                     clamp: float, interpret: bool):
    m, latent = x.shape
    e, _, width = up.shape
    size = jnp.dtype(up.dtype).itemsize
    tile = _tile(width, latent, size)
    tiles = width // tile
    # what stays in VMEM: every block twice, and the gate's scratch
    vmem = 2 * (3 * latent * tile * size + m * latent * (size + 4)
                + m * 128 * 4) + m * width * 4 + 3 * m * 128 * 4
    touched = jnp.any(weights != 0.0, axis=0)                # [E]
    n = jnp.sum(touched).astype(jnp.int32)
    # the touched experts first, in their own order
    ids = jnp.argsort(~touched, stable=True).astype(jnp.int32)

    def expert(i, ids, n):
        return ids[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    def at(i, s, n, phase):
        """The tile a spec stands on at step ``s`` of expert ``i``: phase 0
        (the gate) walks ``s < tiles`` and waits on its last tile, phase 1
        (up and down) waits on its first and walks after; past the last
        touched expert every step names what the last real step held."""
        j = jnp.clip(s - phase * tiles, 0, tiles - 1)
        return jnp.where(i < n[0], j, tiles - 1)

    def gate_at(i, s, ids, n):
        return (expert(i, ids, n), 0, at(i, s, n, 0))

    def up_at(i, s, ids, n):
        return (expert(i, ids, n), 0, at(i, s, n, 1))

    return pl.pallas_call(
        functools.partial(_kernel, tiles=tiles, width=width, scale=scale,
                          clamp=clamp, eps=EPS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, 2 * tiles),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((m, latent), lambda i, s, ids, n: (0, 0)),
                pl.BlockSpec((1, latent, tile), gate_at),
                pl.BlockSpec((1, latent, tile), up_at),
                pl.BlockSpec((1, tile, latent), lambda i, s, ids, n:
                             (expert(i, ids, n), at(i, s, n, 1), 0)),
                pl.BlockSpec((1, m, 1), lambda i, s, ids, n:
                             (expert(i, ids, n), 0, 0)),
            ],
            out_specs=pl.BlockSpec((m, latent), lambda i, s, ids, n: (0, 0)),
            scratch_shapes=[pltpu.VMEM((tiles, m, tile), jnp.float32),
                            pltpu.VMEM((3, m, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=None if vmem <= _DEFAULT_VMEM
            else vmem + (8 << 20)),
        interpret=interpret,
        name="polynorm_experts",
    )(ids, n.reshape(1), params.astype(jnp.float32), x, gate, up, down,
      weights.astype(jnp.float32).T[:, :, None])


@trace.part(trace.EXPERTS)
def polynorm_experts(x: jax.Array, gate: jax.Array, up: jax.Array,
                     down: jax.Array, params: jax.Array, weights: jax.Array,
                     *, scale: float, clamp: float, kernel: str = "pallas",
                     interpret: Optional[bool] = None) -> jax.Array:
    """``x`` [M, L] (the experts' input, in the weights' dtype), ``gate`` /
    ``up`` [E, L, F], ``down`` [E, F, L], ``params`` [E, 4] float32 (``w_1,
    w_2, w_3, b`` an expert), ``weights`` [M, E] float32 (0 where the row
    does not reach the expert). Returns ``[M, L]`` float32."""
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown expert kernel {kernel!r}; known: lax, pallas")
    if kernel == "lax":
        return lax_polynorm_experts(x, gate, up, down, params, weights,
                                    scale=scale, clamp=clamp)
    return _pallas_polynorm(
        x.astype(up.dtype), gate, up, down, params, weights,
        scale=float(scale), clamp=float(clamp),
        interpret=_interpret.resolve(interpret))


@trace.part(trace.EXPERTS)
def lax_polynorm_experts(x, gate, up, down, params, weights, *,
                         scale: float, clamp: float):
    """The same sum over every held expert, one after another."""
    x = x.astype(up.dtype)
    f32 = jnp.float32

    def one(acc, ew):
        g, u, d, p, col = ew
        act = polynorm(jnp.dot(x, g, preferred_element_type=f32), p,
                       scale=scale, clamp=clamp)
        h = act * jnp.dot(x, u, preferred_element_type=f32) * col[:, None]
        return acc + jnp.dot(h.astype(d.dtype), d,
                             preferred_element_type=f32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, f32),
        (gate, up, down, params.astype(f32), weights.astype(f32).T))
    return out


def lower_for_tpu(*, rows: int, experts: int, latent: int, width: int,
                  dtype) -> None:
    """Lower the kernel for a TPU at these shapes with no device, and let
    the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    up = sds((experts, latent, width), dtype)
    jax.jit(functools.partial(
        _pallas_polynorm.__wrapped__, scale=0.5, clamp=0.5, interpret=False)
    ).trace(
        sds((rows, latent), dtype), up, up,
        sds((experts, width, latent), dtype),
        sds((experts, 4), jnp.float32), sds((rows, experts), jnp.float32),
    ).lower(lowering_platforms=("tpu",))

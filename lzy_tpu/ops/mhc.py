"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a residual
of ``n`` streams a token, mixed into and out of every sublayer by matrices
computed from the token itself (``models/motif.py``).

A token's residual is ``X`` in ``R^{n x D}`` (``n`` 4, ``D`` 4096), **carried
flat**, ``[rows, n x D]`` with the streams side by side (a ``[rows, 4, 4096]``
array pads its 4 to 8 sublanes on the chip, and every reshape of it is a
copy). Around a
sublayer ``F`` with its own ``phi`` ``[n + n + n x n, n x D]``, ``alpha`` ``[3]``
and ``b`` ``[n + n + n x n]``:

    x~     = vec(X) / rms(vec(X))                      float32, no learned scale
    H~     = alpha_k * (phi x~) + b                    [n | n | n x n]
    Hpre   = sigmoid(H~[:n]);  Hpost = 2 sigmoid(H~[n:2n])
    Hres   = SK(H~[2n:]):  M = exp(.), ``sweeps`` times: each row divided by its
             sum, then each column by its sum          (doubly stochastic in the limit)
    h      = sum_i Hpre_i X_i                          what the sublayer reads
    X_i   <- sum_j Hres_ij X_j + Hpost_i y             y = F(norm(h))

:func:`mhc_pre` gives ``h`` and the token's mix, :func:`mhc_post` the new
streams. **The mix is handed from one to the other packed**, ``[M, 32]``
float32 a row: columns ``0..n`` ``Hpre``, ``n..2n`` ``Hpost``, ``2n..2n + n
x n`` ``Hres`` row-major, zeros after (:func:`split_mix` unpacks it).
``phi`` is stored ``[n + n + n x n, n x D]``, a quantity a row: the
projection's 24 outputs are then 24 sublanes of a tile and not 24 of 128
lanes (a ``[16384, 24]`` operand occupies 8 MB of VMEM, this one 1.5).

Each has two forms. ``kernel="pallas"``: ``mhc_pre`` / ``mhc_post`` in a
device trace, a grid step a tile of ``_TILE_ROWS`` rows that stays in VMEM
for the norm, the projection, the sweeps and the mix; the streams are read
once by each and written once by ``mhc_post``, in place
(``input_output_aliases``). In plain XLA the twenty sweeps are some eighty
small reductions a sublayer, a microsecond or two each. Inside the kernel a
token is a lane: the projection is taken as ``phi x~^T`` (``[24, rows]``),
so a sweep is 16 divisions and 24 additions of one vector register
whatever the rows, and the finished mix is transposed once (``[32, rows]
-> [rows, 32]``) to meet the streams, whose rows are sublanes.
``kernel="lax"``: the same arithmetic in ``jax.numpy``, the portable path
and the kernel's oracle. Everything is float32 at the highest matmul
precision in both. ``exp`` is taken of ``H~res`` less its largest entry a
token: the first row division cancels the factor, and a trained ``b`` may
be large.

No tile is skipped for its rows being pads or idle slots: a decode round's
slots are one tile, and a prefill chunk's pads are its last tile's tail.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` labels of a program that mixes streams
PATH = "mhc_pallas"
LAX_PATH = "mhc_lax"

#: columns of the packed mix (a quarter of a tile of lanes)
MIX_WIDTH = 32
#: rows a grid step keeps in VMEM: 64 rows of 4 float32 streams of 4096 are
#: 4 MB, twice for the pipeline, and ``mhc_post`` holds its result beside them
_TILE_ROWS = 64
_VMEM_LIMIT = 48 << 20
#: lanes of the streams one step of the mix touches at a time
_LANES = 512

_HIGHEST = lax.Precision.HIGHEST


def path(kernel: str) -> str:
    return PATH if kernel == "pallas" else LAX_PATH


def mix_rows(n: int) -> int:
    """Quantities a token's mix holds: ``n + n + n x n``."""
    return 2 * n + n * n


def _sweeps(m, n: int, sweeps: int):
    """Sinkhorn-Knopp on ``m``, a list of ``n x n`` arrays (row-major):
    ``sweeps`` times each row by its sum, then each column by its sum. One
    sweep's operations in the program, run ``sweeps`` times."""
    def sweep(_, m):
        m = list(m)
        for i in range(n):
            s = sum(m[n * i + j] for j in range(n))
            for j in range(n):
                m[n * i + j] = m[n * i + j] / s
        for j in range(n):
            s = sum(m[n * i + j] for i in range(n))
            for i in range(n):
                m[n * i + j] = m[n * i + j] / s
        return tuple(m)

    return list(lax.fori_loop(0, sweeps, sweep, tuple(m)))


def _mix_of(raw, n: int, sweeps: int):
    """``raw``: the ``n + n + n x n`` quantities ``H~`` as a list of arrays
    of one shape. Returns the finished mix in the same order."""
    pre = [jax.nn.sigmoid(r) for r in raw[:n]]
    post = [2.0 * jax.nn.sigmoid(r) for r in raw[n:2 * n]]
    res = raw[2 * n:]
    top = functools.reduce(jnp.maximum, res)
    return pre + post + _sweeps([jnp.exp(r - top) for r in res], n, sweeps)


def split_mix(mix, n: int):
    """The packed mix ``[M, 32]`` as ``(Hpre [M, n], Hpost [M, n], Hres [M,
    n, n])``."""
    return (mix[:, :n], mix[:, n:2 * n],
            mix[:, 2 * n:mix_rows(n)].reshape(-1, n, n))


def _scales(alpha, n: int):
    """``alpha`` ``[3]`` as one factor a quantity, ``[n + n + n x n]``."""
    return jnp.repeat(alpha.astype(jnp.float32), jnp.asarray([n, n, n * n]),
                      total_repeat_length=mix_rows(n))


# -- the lax forms ------------------------------------------------------------

@trace.part(trace.MIX)
def lax_mhc_pre(x, phi, alpha, b, *, streams: int, sweeps: int, eps: float):
    n, (m, nd) = streams, x.shape
    flat = x.astype(jnp.float32)
    x32 = flat.reshape(m, n, nd // n)
    inv = lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    proj = jnp.dot(flat, phi.astype(jnp.float32).T, precision=_HIGHEST) * inv
    raw = proj * _scales(alpha, n) + b.astype(jnp.float32)
    cols = _mix_of([raw[:, k] for k in range(mix_rows(n))], n, sweeps)
    mix = jnp.stack(cols + [jnp.zeros_like(cols[0])]
                    * (MIX_WIDTH - len(cols)), axis=-1)
    h = jnp.einsum("mn,mnd->md", mix[:, :n], x32, precision=_HIGHEST)
    return h, mix


@trace.part(trace.MIX)
def lax_mhc_post(x, y, mix, *, streams: int):
    n, (m, nd) = streams, x.shape
    _, post, res = split_mix(mix, n)
    out = jnp.einsum("mij,mjd->mid", res,
                     x.astype(jnp.float32).reshape(m, n, nd // n),
                     precision=_HIGHEST) \
        + post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.reshape(m, nd).astype(x.dtype)


# -- the kernels --------------------------------------------------------------

def _pre_kernel(alpha_ref, x_ref, phi_ref, b_ref, h_ref, mix_ref, *, n: int,
                d: int, sweeps: int, eps: float):
    f32 = jnp.float32
    rows = x_ref.shape[0]
    x = x_ref[...].astype(f32)                              # [rows, n x d]
    nt = (((1,), (1,)), ((), ()))
    # a token a lane: [24, rows] and the sum of squares [8, rows]
    proj = lax.dot_general(phi_ref[...], x, nt, preferred_element_type=f32,
                           precision=_HIGHEST)
    sq = lax.dot_general(jnp.ones((8, n * d), f32), x * x, nt,
                         preferred_element_type=f32, precision=_HIGHEST)
    inv = lax.rsqrt(sq[0:1] / (n * d) + eps)                # [1, rows]
    raw = []
    for k in range(mix_rows(n)):
        a = alpha_ref[0 if k < n else 1 if k < 2 * n else 2]
        raw.append(proj[k:k + 1] * inv * a + b_ref[k:k + 1, 0:1])
    done = _mix_of(raw, n, sweeps)
    packed = jnp.concatenate(
        done + [jnp.zeros((MIX_WIDTH - len(done), rows), f32)], axis=0)
    mix = packed.T                                          # [rows, 32]
    mix_ref[...] = mix
    for c in range(0, d, _LANES):
        w = min(_LANES, d - c)
        h_ref[:, c:c + w] = sum(
            mix[:, i:i + 1] * x_ref[:, i * d + c:i * d + c + w].astype(f32)
            for i in range(n))


def _post_kernel(x_ref, y_ref, mix_ref, o_ref, *, n: int, d: int):
    f32 = jnp.float32
    mix = mix_ref[...]
    for c in range(0, d, _LANES):
        w = min(_LANES, d - c)
        y = y_ref[:, c:c + w].astype(f32)
        xs = [x_ref[:, j * d + c:j * d + c + w].astype(f32)
              for j in range(n)]
        for i in range(n):
            at = 2 * n + n * i
            acc = mix[:, n + i:n + i + 1] * y
            for j in range(n):
                acc = acc + mix[:, at + j:at + j + 1] * xs[j]
            o_ref[:, i * d + c:i * d + c + w] = acc.astype(o_ref.dtype)


def _tile_rows(m: int) -> int:
    """Rows a grid step takes: all of them up to ``_TILE_ROWS``, else the
    most that are whole sublane tiles of 8 and divide ``m``."""
    if m <= _TILE_ROWS:
        return m
    for rows in range(_TILE_ROWS, 7, -8):
        if m % rows == 0:
            return rows
    raise ValueError(
        f"{m} rows are not whole tiles of 8 to {_TILE_ROWS}: a program's "
        f"rows are a decode round's slots or a prefill chunk's positions")


@functools.partial(jax.jit, static_argnames=("streams", "sweeps", "eps",
                                             "interpret"))
def _pallas_pre(x, phi, alpha, b, *, streams: int, sweeps: int, eps: float,
                interpret: bool):
    n, (m, nd) = streams, x.shape
    d = nd // n
    k = mix_rows(n)
    tm = _tile_rows(m)
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, d=d, sweeps=sweeps, eps=eps),
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tm, n * d), lambda i: (i, 0)),
            pl.BlockSpec((k, n * d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((tm, MIX_WIDTH), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, d), jnp.float32),
                   jax.ShapeDtypeStruct((m, MIX_WIDTH), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mhc_pre",
    )(alpha.astype(jnp.float32), x, phi.astype(jnp.float32),
      b.astype(jnp.float32).reshape(k, 1))


@functools.partial(jax.jit, static_argnames=("streams", "interpret"))
def _pallas_post(x, y, mix, *, streams: int, interpret: bool):
    n, (m, nd) = streams, x.shape
    d = nd // n
    tm = _tile_rows(m)
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, d=d),
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, n * d), lambda i: (i, 0)),
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((tm, MIX_WIDTH), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tm, n * d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nd), x.dtype),
        # the new streams take the old ones' place where the caller lets go
        # of them (inside a step program they always do)
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mhc_post",
    )(x, y, mix)


# -- the calls ----------------------------------------------------------------

def _check_kernel(kernel: str) -> None:
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown stream-mix kernel {kernel!r}; known: lax, pallas")


@trace.part(trace.MIX)
def mhc_pre(x: jax.Array, phi: jax.Array, alpha: jax.Array, b: jax.Array, *,
            streams: int, sweeps: int, eps: float = 1e-5,
            kernel: str = "lax",
            interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """The mix into a sublayer. ``x`` ``[M, n x D]`` the ``streams`` (n)
    side by side, ``phi`` ``[n + n + n x n, n x D]``, ``alpha`` ``[3]``,
    ``b`` ``[n + n + n x n]``. Returns ``(h [M, D] float32, mix [M, 32]
    float32)``: what the sublayer reads, and the token's packed mix for
    :func:`mhc_post`."""
    _check_kernel(kernel)
    if kernel == "pallas":
        return _pallas_pre(x, phi, alpha, b, streams=streams, sweeps=sweeps,
                           eps=float(eps),
                           interpret=_interpret.resolve(interpret))
    return lax_mhc_pre(x, phi, alpha, b, streams=streams, sweeps=sweeps,
                       eps=eps)


@trace.part(trace.MIX)
def mhc_post(x: jax.Array, y: jax.Array, mix: jax.Array, *, streams: int,
             kernel: str = "lax",
             interpret: Optional[bool] = None) -> jax.Array:
    """The mix out of a sublayer: ``X_i <- sum_j Hres_ij X_j + Hpost_i y``.
    ``x`` ``[M, n x D]``, ``y`` ``[M, D]`` the sublayer's result, ``mix`` as
    :func:`mhc_pre` gave it. Returns the new streams, ``x``'s shape and
    dtype."""
    _check_kernel(kernel)
    if kernel == "pallas":
        return _pallas_post(x, y, mix, streams=streams,
                            interpret=_interpret.resolve(interpret))
    return lax_mhc_post(x, y, mix, streams=streams)


def lower_for_tpu(*, rows: int, streams: int, width: int, sweeps: int,
                  dtype, y_dtype) -> None:
    """Lower both kernels for a TPU at these shapes with no device, and let
    the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    k = mix_rows(streams)

    def both(x, phi, alpha, b, y):
        h, mix = _pallas_pre(x, phi, alpha, b, streams=streams,
                             sweeps=sweeps, eps=1e-5, interpret=False)
        return h, _pallas_post(x, y, mix, streams=streams, interpret=False)

    jax.jit(both).trace(
        sds((rows, streams * width), dtype),
        sds((k, streams * width), jnp.float32), sds((3,), jnp.float32),
        sds((k,), jnp.float32), sds((rows, width), y_dtype),
    ).lower(lowering_platforms=("tpu",))

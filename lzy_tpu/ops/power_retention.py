"""Power retention at degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): linear attention whose feature map
is the symmetric square of the key, with a decay a token and a normaliser
carried beside the state. A chunked scan for prefill and a one-token state
update for decode.

For a key-value head with ``G`` query heads of ``d`` numbers, ``l_t <= 0`` the
token's log decay::

    attention form, s <= t:   a_ts = exp(l_{s+1} + .. + l_t) (q_t . k_s)^2
                              y_t  = sum_s a_ts v_s / (sum_s a_ts + d eps)
    state form:               S_t = exp(l_t) S_{t-1} + phi(k_t) v_t^T
                              z_t = exp(l_t) z_{t-1} + phi(k_t)
                              y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + d eps)

``phi(x)`` holds ``x_i x_j`` once a pair, ``sqrt(2)`` times where ``i != j``,
so that ``phi(a) . phi(b) = (a . b)^2``: ``d (d + 1) / 2`` features (8,256 at
``d`` = 128). The model's ``1 / sqrt(d)`` on the scores cancels between the
numerator and the normaliser (both carry ``1 / d``); what is left of it is the
``d`` beside ``eps``.

**The layout of the features is this file's.** They lie as ``d / 2 + 1``
*tiles* of ``d``: entry ``i`` of tile ``m`` is ``w_m x_i x_{(i - m) mod d}``
(``w_0`` = 1, the squares; ``w_m`` = ``sqrt(2)`` past it: every pair at
circular distance ``m`` once). In the last tile (``m = d / 2``) the entries
``i >= d / 2`` would name their pairs a second time and **are zeros**: 64 of
8,320 at ``d`` = 128, the padded tail. So a tile is the vector times a
rotation of itself: a kernel makes it from ``d`` numbers with one lane
rotation, and never reads ``phi`` from HBM (nor writes it). The state of a key-value head is
``S`` ``[tiles, d (of v), d (of the tile)]`` and ``z`` ``[tiles, d]``, both
float32 whatever the activations are: they are carried over thousands of
tokens, and a bfloat16 state is a different configuration
(``ops/mamba2.py``). ``z`` is stored ``[steps, tiles a step, d]``
(:func:`state_shapes`): both kernels walk a head's tiles a step at a
time.

**The products** of ``phi(q)`` with ``S`` take both rounded to the type ``q``
arrives in (bfloat16 served: as attention rounds its probabilities and
values), summed in float32; the update of ``S`` and ``z``, the normaliser's
product and every sum are float32 (a product of two bfloat16 numbers is exact
there).

- :func:`retention_chunk_scan`: ``T`` positions in chunks of ``chunk``
  positions, a Pallas kernel (:data:`CHUNK_KERNEL` in a device trace) that
  takes :data:`CHUNKS_A_CALL` chunks a call: 256 positions, the widest
  prefill program. Its grid walks (row, key-value head, step of
  :func:`tiles_a_step` tiles); ``S`` and ``z`` pass through VMEM once a
  call, in place, and while a tile is resident every chunk of the call is
  served: the chunk's queries read the tile (``phi(q) S`` and ``phi(q) z``,
  the group's query heads folded into the rows of one product), then its
  keys move it (``exp(cs_C) S + phi(k)^T (tail v)``, one float32 product at
  full precision whose last row is ``z``'s). ``phi`` of the queries and the
  keys is made a tile at a time, the rows times a lane rotation of
  themselves, and never written. The last step adds the chunk's own masked,
  decay-weighted squared scores and divides. What a chunk's decays make
  (``cs``, the mask, ``grow``, ``tail``, ``total``: a few numbers a
  position) is plain ``jax.numpy`` before the kernel. A position that is
  not ``real`` leaves ``S`` and ``z`` as they were.
- :func:`retention_state_update`: one position for every row of a decode
  batch, a Pallas kernel (:data:`UPDATE_KERNEL` in a device trace) that reads
  and writes each live row's ``S`` and ``z`` once, in place, and returns the
  query heads' numerators and normalisers from the new state. A row that is
  not live is skipped: its state stays bit for bit.

Both count in ``lzy_kernel_dispatch_total`` under :data:`SCAN_PATH` and
:data:`UPDATE_PATH`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` labels of the two programs
SCAN_PATH = "retention_chunk_pallas"
UPDATE_PATH = "retention_update_pallas"
#: the two kernels' names in a device trace
UPDATE_KERNEL = "power_retention_update"
CHUNK_KERNEL = "power_retention_chunk"

_HI = lax.Precision.HIGHEST
_SQRT2 = math.sqrt(2.0)


def n_tiles(d: int) -> int:
    """Tiles of ``d`` features a head's ``phi`` lies in."""
    return d // 2 + 1


def n_features(d: int) -> int:
    """The features that are not the padded tail: ``d (d + 1) / 2``."""
    return d * (d + 1) // 2


def tiles_a_step(d: int) -> int:
    """Tiles a kernel takes a grid step: the largest divisor of
    :func:`n_tiles` up to 16 (13 of 65 at ``d`` = 128: 832 KiB of ``S``)."""
    m = n_tiles(d)
    return max(t for t in range(1, min(m, 16) + 1) if m % t == 0)


def state_shapes(batch: int, kv_heads: int, d: int) -> tuple:
    """``(S's shape, z's shape)`` for ``batch`` rows."""
    m, t = n_tiles(d), tiles_a_step(d)
    return (batch, kv_heads, m, d, d), (batch, kv_heads, m // t, t, d)


@functools.lru_cache(maxsize=None)
def _weights(d: int) -> np.ndarray:
    """``[tiles, d]``: 1 on the squares, ``sqrt(2)`` on the pairs, 0 on the
    padded tail."""
    m = np.arange(n_tiles(d))[:, None]
    i = np.arange(d)[None, :]
    return (np.where(m == 0, 1.0, _SQRT2)
            * ((m < d // 2) | (i < d // 2))).astype(np.float32)


def phi(x: jax.Array) -> jax.Array:
    """``[..., d]`` -> ``[..., tiles, d]`` float32, in this file's layout:
    tile ``m`` is ``x`` times ``x`` rotated by ``m``, weighted. The layout
    written out for the tests' oracles and the benchmark's reference to be
    held to; the kernels make a tile from the rows they hold and never call
    this."""
    x = x.astype(jnp.float32)
    return jnp.stack([x * jnp.roll(x, m, -1)
                      for m in range(n_tiles(x.shape[-1]))],
                     axis=-2) * _weights(x.shape[-1])


# -- prefill: T positions in chunks, the state through VMEM once a call -------

#: chunks one call of the chunk kernel serves while a tile of the state is
#: resident (256 positions at chunks of 128: the widest prefill program)
CHUNKS_A_CALL = 2
#: rows under ``v^T`` in the update's left operand: the decays' own row (it
#: makes ``z``'s update), up to a whole sublane tile
_Z_ROWS = 8
_NT = (((1,), (1,)), ((), ()))


def _chunk_kernel(q_ref, k_ref, v_ref, vt_ref, w_ref, grow_ref, total_ref,
                  wt_ref, s_ref, z_ref, o_y, o_s, o_z, num_ref, den_ref, *,
                  tiles: int, group: int, pd, eps: float):
    """One (row, key-value head, step of ``tiles`` tiles), every chunk of the
    call. ``q`` [chunks, G x C, d] (the group's heads folded into the rows,
    head-major) and ``k`` [chunks, C, d], float32 copies of numbers of type
    ``pd``: ``x * roll(x, m) * w_m`` is tile ``m`` of ``phi`` of every row at
    once (``wt`` holds ``w_m``: 1, ``sqrt(2)``, and the zeros of the padded
    tail). While a tile of ``S`` is resident each chunk's queries read it and
    its keys then move it; ``num`` / ``den`` [chunks, G x C, d] carry the
    queries' sums over the steps (``den`` a lane: summed at the end). The
    last step adds the chunk's own masked, decay-weighted squared scores
    (``w`` [chunks, C, C]) and divides."""
    step = pl.program_id(2)
    chunks, c, d = k_ref.shape[2:]
    f32 = jnp.float32
    prec = _HI if pd == f32 else None

    @pl.when(step == 0)
    def _():
        num_ref[...] = jnp.zeros(num_ref.shape, f32)
        den_ref[...] = jnp.zeros(den_ref.shape, f32)

    for t in range(tiles):
        m = step * tiles + t
        w_m = wt_ref[0, t:t + 1, :]                    # [1, d]
        s_m, z_m = s_ref[0, 0, t], z_ref[0, 0, 0, t:t + 1, :]
        for ch in range(chunks):
            q32, k32 = q_ref[0, 0, ch], k_ref[0, 0, ch]
            pq = (q32 * pltpu.roll(q32, m, 1) * w_m).astype(pd)
            num_ref[ch] += lax.dot_general(
                pq, s_m.astype(pd), _NT, precision=prec,
                preferred_element_type=f32)
            # the normaliser's product reads the same rounded phi(q)
            den_ref[ch] += pq.astype(f32) * z_m
            pk = k32 * pltpu.roll(k32, m, 1) * w_m
            moved = jnp.dot(vt_ref[0, 0, ch], pk, precision=_HI,
                            preferred_element_type=f32)    # [d + 8, d]
            total = total_ref[0, 0, ch]                # [1, d]
            s_m = total * s_m + moved[:d]
            z_m = total * z_m + moved[d:d + 1]
        o_s[0, 0, t] = s_m
        o_z[0, 0, 0, t:t + 1, :] = z_m

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        for ch in range(chunks):
            keys, grow = k_ref[0, 0, ch].astype(pd), grow_ref[0, 0, ch]
            for g in range(group):
                rows = slice(g * c, (g + 1) * c)
                sc = lax.dot_general(
                    q_ref[0, 0, ch, rows, :].astype(pd), keys, _NT,
                    precision=prec, preferred_element_type=f32)
                a = w_ref[0, 0, ch] * sc * sc          # [C of t, C of s]
                num = jnp.dot(a.astype(pd), v_ref[0, 0, ch], precision=prec,
                              preferred_element_type=f32) \
                    + grow * num_ref[ch, rows, :]
                den = jnp.sum(a, -1, keepdims=True) + grow * jnp.sum(
                    den_ref[ch, rows, :], -1, keepdims=True)
                o_y[0, 0, ch, rows, :] = num / (den + d * eps)


@functools.partial(jax.jit, static_argnames=("c", "eps", "interpret"))
def _pallas_chunk(s, z, q, k, v, log_g, real, wt, *, c: int, eps: float,
                  interpret: bool):
    """Whole chunks of ``c`` positions, :data:`CHUNKS_A_CALL` at most:
    ``q`` [B, chunks x c, H, d] and the rest as :func:`retention_chunk_scan`
    takes them, ``wt`` the tiles' weights (:func:`_weights`). What a chunk's
    decays make (its mask ``w``, ``grow``, ``tail`` and ``total`` of the
    module's equations) is a few numbers a position and made here;
    everything with a feature in it is the kernel's."""
    b, t, h, d = q.shape
    kv, g, chunks = k.shape[2], h // k.shape[2], t // c
    steps, tiles = z.shape[2], z.shape[3]
    f32, pd = jnp.float32, jnp.dtype(q.dtype)

    def heads_first(x):                # [B, T, KV, ..] -> [B, KV, chunks, c, ..]
        x = x.reshape(b, chunks, c, kv, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    on = real.reshape(b, 1, chunks, c)
    cs = jnp.cumsum(heads_first(jnp.where(
        real[..., None], log_g.astype(f32), 0.0)), axis=-1)    # through t
    # a_ts carries exp(cs_t - cs_s), s <= t; the masked entries have a
    # positive exponent: zero them before exp
    keep = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]) \
        & on[..., None, :]
    w = jnp.where(keep, jnp.exp(jnp.where(
        keep, cs[..., :, None] - cs[..., None, :], 0.0)), 0.0)
    grow = jnp.exp(cs)[..., None]
    tail = jnp.where(on, jnp.exp(cs[..., -1:] - cs), 0.0)[..., None, :]
    total = jnp.broadcast_to(jnp.exp(cs[..., -1])[..., None, None],
                             (b, kv, chunks, 1, d))
    vs = heads_first(v)
    vt = jnp.concatenate([
        jnp.swapaxes(vs.astype(f32), -1, -2) * tail, tail,
        jnp.zeros((b, kv, chunks, _Z_ROWS - 1, c), f32)], axis=-2)
    qs = jnp.moveaxis(heads_first(q.astype(f32).reshape(b, t, kv, g, d)),
                      4, 3).reshape(b, kv, chunks, g * c, d)
    ks = heads_first(k.astype(f32))

    def a_head(*block):
        return pl.BlockSpec((1, 1) + block,
                            lambda i, j, n: (i, j) + (0,) * len(block))

    s_spec = pl.BlockSpec((1, 1, tiles, d, d), lambda i, j, n: (i, j, n, 0, 0))
    z_spec = pl.BlockSpec((1, 1, 1, tiles, d), lambda i, j, n: (i, j, n, 0, 0))
    y, s, z = pl.pallas_call(
        functools.partial(_chunk_kernel, tiles=tiles, group=g, pd=pd,
                          eps=eps),
        grid=(b, kv, steps),
        in_specs=[a_head(chunks, g * c, d), a_head(chunks, c, d),
                  a_head(chunks, c, d), a_head(chunks, d + _Z_ROWS, c),
                  a_head(chunks, c, c), a_head(chunks, c, 1),
                  a_head(chunks, 1, d),
                  pl.BlockSpec((1, tiles, d), lambda i, j, n: (n, 0, 0)),
                  s_spec, z_spec],
        out_specs=[a_head(chunks, g * c, d), s_spec, z_spec],
        out_shape=[jax.ShapeDtypeStruct((b, kv, chunks, g * c, d), f32),
                   jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct(z.shape, f32)],
        scratch_shapes=[pltpu.VMEM((chunks, g * c, d), f32)] * 2,
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=CHUNK_KERNEL,
    )(qs, ks, vs, vt, w, grow, total, wt.reshape(steps, tiles, d), s, z)
    y = jnp.moveaxis(y.reshape(b, kv, chunks, g, c, d), (1, 3), (3, 4))
    return y.reshape(b, t, h, d), s, z


@trace.part(trace.STATE)
def retention_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                         log_g: jax.Array, s: jax.Array, z: jax.Array,
                         real: Optional[jax.Array] = None, *,
                         chunk: int = 128, eps: float = 1e-6,
                         interpret: Optional[bool] = None):
    """``q`` [B, T, H, d], ``k`` / ``v`` [B, T, KV, d] (the products take
    them in ``q``'s type), ``log_g`` [B, T, KV] (<= 0), ``s`` / ``z`` as
    :func:`state_shapes` says (float32), ``real`` [B, T] bool (a position
    that is not leaves the state as it was and adds nothing to a later
    query). Returns ``(y [B, T, H, d] float32, S, z)``. ``T`` is cut into
    chunks of ``chunk`` positions, :data:`CHUNKS_A_CALL` a call of the
    kernel; fewer than ``chunk`` are one short chunk, and the last chunk is
    filled up with positions that are not real."""
    b, t = q.shape[:2]
    if real is None:
        real = jnp.ones((b, t), bool)
    c = chunk if t > chunk else -(-t // 8) * 8
    # made here: inside the jitted call the table would be a constant of
    # its cached trace
    wt = jnp.asarray(_weights(q.shape[-1]))
    q, k, v, log_g, real = (
        jnp.pad(x, ((0, 0), (0, -t % c)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, log_g, real))
    ys = []
    for start in range(0, q.shape[1], CHUNKS_A_CALL * c):
        sl = slice(start, start + CHUNKS_A_CALL * c)
        y, s, z = _pallas_chunk(
            s, z, q[:, sl], k[:, sl], v[:, sl], log_g[:, sl], real[:, sl],
            wt, c=c, eps=eps, interpret=_interpret.resolve(interpret))
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    return y[:, :t], s, z


# -- decode: one position a row, in place -------------------------------------

def _side_rows(group: int) -> int:
    """Rows of the kernel's small operand: the key, the decay, the group's
    queries, up to a whole number of sublane tiles."""
    return -(-(2 + group) // 8) * 8


def _update_kernel(rows_ref, n_ref, s_ref, z_ref, side_ref, v_ref,
                   o_s, o_z, o_num, o_den, *, tiles: int, pd):
    """One (row, key-value head, step of ``tiles`` tiles). ``side`` [R, d]:
    row 0 the key, row 1 the decay on every lane, rows 2.. the group's
    queries: ``side * roll(side, m)`` is tile ``m`` of ``phi`` of the key and
    of every query at once (row 1 and the rows of padding ride along and are
    dropped outside)."""
    step = pl.program_id(2)
    d = side_ref.shape[-1]
    prec = _HI if pd == jnp.float32 else None

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        side = side_ref[0, 0]
        decay = side[1:2, :]
        vb = jnp.broadcast_to(v_ref[0, 0], (d, d))     # [d of v, d]
        lane = lax.broadcasted_iota(jnp.int32, side.shape, 1)

        @pl.when(step == 0)
        def _():
            o_num[0, 0] = jnp.zeros(side.shape, jnp.float32)
            o_den[0, 0] = jnp.zeros(side.shape, jnp.float32)

        num, den = o_num[0, 0], o_den[0, 0]
        for t in range(tiles):
            m = step * tiles + t
            both = side * pltpu.roll(side, m, 1) \
                * jnp.where(m == 0, 1.0, _SQRT2)
            both = jnp.where((m < d // 2) | (lane < d // 2), both, 0.0)
            pk = both[0:1, :]
            new = decay * s_ref[0, 0, t] + vb * pk     # [d of v, d]
            o_s[0, 0, t] = new
            zn = decay * z_ref[0, 0, 0, t:t + 1, :] + pk
            o_z[0, 0, 0, t:t + 1, :] = zn
            num = num + lax.dot_general(
                both.astype(pd), new.astype(pd), (((1,), (1,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)
            den = den + both * zn
        o_num[0, 0] = num
        o_den[0, 0] = den


@functools.partial(jax.jit, static_argnames=("pd", "interpret"),
                   donate_argnums=(0, 1))
def _pallas_update(s, z, side, vcol, live, *, pd, interpret: bool):
    """``live`` [B] bool: the rows whose state moves. The grid walks the
    live rows first (their ids arrive by scalar prefetch) and then stands
    still on the last one's last block, so an idle slot's state is neither
    read nor written (``ops/mamba2.py``'s discipline). ``side`` [B, KV, R,
    d], ``vcol`` [B, KV, d, 1]. Returns ``(S, z, num [B, KV, R, d], den [B,
    KV, R, d])``: a row of ``num`` is that row of ``side``'s ``phi`` against
    the new ``S``, ``den``'s summed over its lanes against the new ``z``."""
    b, kv, m, d, _ = s.shape
    steps, tiles = z.shape[2], z.shape[3]
    r = side.shape[2]
    # with no live row at all the grid would write back a block it never
    # filled: walk row 0 then (the caller zeroes a dead row's key and decay:
    # its state is multiplied by 1 and added 0)
    live = live.at[0].set(live[0] | ~jnp.any(live))
    count = jnp.sum(live).astype(jnp.int32).reshape(1)
    rows = jnp.argsort(~live, stable=True).astype(jnp.int32)

    def at(i, j, c, rows, count):
        last = jnp.maximum(count[0] - 1, 0)
        on = i < count[0]
        return (rows[jnp.minimum(i, last)], jnp.where(on, j, kv - 1),
                jnp.where(on, c, steps - 1))

    def walked(block):
        return pl.BlockSpec(block, lambda i, j, c, rows, count:
                            at(i, j, c, rows, count) + (0,) * (len(block) - 3))

    def a_head(block):
        return pl.BlockSpec(block, lambda i, j, c, rows, count:
                            at(i, j, c, rows, count)[:2]
                            + (0,) * (len(block) - 2))

    s_spec, z_spec = walked((1, 1, tiles, d, d)), walked((1, 1, 1, tiles, d))
    out = a_head((1, 1, r, d))
    return pl.pallas_call(
        functools.partial(_update_kernel, tiles=tiles, pd=pd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, steps),
            in_specs=[s_spec, z_spec, a_head((1, 1, r, d)),
                      a_head((1, 1, d, 1))],
            out_specs=[s_spec, z_spec, out, out]),
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(z.shape, jnp.float32),
                   jax.ShapeDtypeStruct(side.shape, jnp.float32),
                   jax.ShapeDtypeStruct(side.shape, jnp.float32)],
        # the state operands follow the two prefetched scalars
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=UPDATE_KERNEL,
    )(rows, count, s, z, side, vcol)


@trace.part(trace.STATE)
def retention_state_update(s: jax.Array, z: jax.Array, q: jax.Array,
                           k: jax.Array, v: jax.Array, log_g: jax.Array,
                           live: jax.Array, *, eps: float = 1e-6,
                           interpret: Optional[bool] = None):
    """One decode position: ``s`` / ``z`` as :func:`state_shapes` says
    (float32, donated and updated in place), ``q`` [B, H, d], ``k`` / ``v``
    [B, KV, d] (the products take them in ``q``'s type), ``log_g`` [B, KV],
    ``live`` [B] bool. Returns ``(y [B, H, d] float32, S, z)``; a row that
    is not live gets 0 and its state is not touched."""
    b, h, d = q.shape
    kv = k.shape[1]
    g = h // kv
    f32 = jnp.float32
    on = live[:, None, None]
    # a row the grid walks without its being live (row 0 of a round with no
    # live row) multiplies its state by 1 and adds 0
    key = jnp.where(on, k.astype(f32), 0.0)
    decay = jnp.where(live[:, None], jnp.exp(log_g.astype(f32)), 1.0)
    side = jnp.concatenate([
        key[:, :, None], jnp.broadcast_to(decay[:, :, None, None],
                                          (b, kv, 1, d)),
        q.astype(f32).reshape(b, kv, g, d),
        jnp.zeros((b, kv, _side_rows(g) - 2 - g, d), f32)], axis=2)
    s, z, num, den = _pallas_update(
        s, z, side, v.astype(f32)[..., None], live, pd=jnp.dtype(q.dtype),
        interpret=_interpret.resolve(interpret))
    y = num[:, :, 2:2 + g] / (
        jnp.sum(den[:, :, 2:2 + g], axis=-1, keepdims=True) + d * eps)
    return jnp.where(on, y.reshape(b, h, d), 0.0), s, z


def lower_chunk_for_tpu(*, batch: int, t: int, heads: int, kv_heads: int,
                        head_dim: int, chunk: int, dtype) -> None:
    """Lower the chunk kernel for a TPU at a program of ``t`` positions a
    row with no device, and let the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    s_shape, z_shape = state_shapes(batch, kv_heads, head_dim)
    jax.jit(functools.partial(retention_chunk_scan, chunk=chunk,
                              interpret=False)).trace(
        sds((batch, t, heads, head_dim), dtype),
        sds((batch, t, kv_heads, head_dim), dtype),
        sds((batch, t, kv_heads, head_dim), dtype),
        sds((batch, t, kv_heads), f32), sds(s_shape, f32), sds(z_shape, f32),
        sds((batch, t), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))


def lower_update_for_tpu(*, batch: int, heads: int, kv_heads: int,
                         head_dim: int, dtype) -> None:
    """Lower the update kernel for a TPU at these shapes with no device, and
    let the lowering's error out (as ``mamba2.lower_update_for_tpu``)."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    s_shape, z_shape = state_shapes(batch, kv_heads, head_dim)
    jax.jit(functools.partial(_pallas_update.__wrapped__,
                              pd=jnp.dtype(dtype), interpret=False)).trace(
        sds(s_shape, f32), sds(z_shape, f32),
        sds((batch, kv_heads, _side_rows(heads // kv_heads), head_dim), f32),
        sds((batch, kv_heads, head_dim, 1), f32),
        sds((batch,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))

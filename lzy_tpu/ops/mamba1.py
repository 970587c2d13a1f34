"""Mamba-1 (selective state space) recurrences: a scan over a prefill chunk
and a one-position state update for decode.

The layer's recurrence, a channel ``c`` of ``Di`` with a state of ``N``
numbers, every one with a decay of its own (``A`` is ``[N, Di]``, negative;
``dt`` a step a channel a position; ``B`` and ``C`` shared by the channels)::

    S_t[n, c] = exp(dt_t[c] * A[n, c]) * S_{t-1}[n, c] + dt_t[c] * B_t[n] * x_t[c]
    y_t[c]    = sum_n S_t[n, c] * C_t[n]

(the ``D * x`` skip, the gate and the projections belong to the model).
Mamba-2's decay is one scalar a head, which lets a chunk be written as masked
matrix products (``ops/mamba2.py``); here it differs for each of ``Di x N``
state entries, and there is no such form: ``Di x N`` multiply-adds and as many
exponentials a position, on the vector unit, one position after another. The
state is float32 whatever the activations are and is held **with the channels
on the lanes**, ``[B, N, Di]`` (16 sublanes x 5120 lanes at the Jamba widths);
a state of another type is another configuration.

- :func:`selective_scan`: ``T`` positions of every row, the state carried in
  and out once. ``kernel="pallas"``: a Pallas kernel (``selective_scan`` in a
  device trace), a grid cell a row and a block of channels, the loop over
  time inside it with the block's state in registers: no ``[T, N, Di]`` array
  ever exists (328 KB a position a layer at the Jamba widths).
  ``kernel="lax"``: ``lax.scan`` over time in float32, its oracle. A position
  whose ``dt`` is 0 (at or past ``valid_len``: a padded tail) leaves the
  state as it was, bit for bit (decay ``exp(0) = 1``, no input).
- :func:`selective_state_update`: one position for every row of a decode
  batch, as a Pallas kernel (``selective_state_update`` in a device trace)
  that reads and writes each live row's state once, in place. A row whose
  ``dt`` is 0 everywhere (an idle slot) is skipped: its state is neither read
  nor written.

``B`` and ``C`` reach the kernels spread over a vector register's 128 lanes
(``[.., N, 128]``: the kernel needs ``B_t[n]`` on sublane ``n`` of every
lane, the projection yields it on lane ``n``, and turning one into the other
is a transpose the compiler does well outside and Mosaic does not inside).

The two kernels count in ``lzy_kernel_dispatch_total`` under
:data:`SCAN_PATH` and :data:`UPDATE_PATH` (the engine counts one for each
program it dispatches); no served program scans by the oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` labels of the programs
SCAN_PATH = "ssm1_scan_pallas"
UPDATE_PATH = "ssm1_update_pallas"

_LANES = 128
#: positions a turn of the scan's loop takes: one aligned ``[8, block]`` tile
#: of ``x``, ``dt`` and ``y``
_TURN = 8
#: channels a grid cell of the scan keeps in registers: ``[16, 512]`` float32
#: of state is 8 vector registers, the decay and the products as many again
_SCAN_BLOCK = 512
#: bytes of one row's state a grid cell of the update moves at most
_UPDATE_BLOCK_BYTES = 512 << 10


def _refuse_another_state(state: jax.Array) -> None:
    if state.dtype != jnp.float32:
        raise ValueError(
            f"the recurrence state is float32, got {state.dtype}: a state "
            f"of another type is another configuration")


def _block(di: int, most: int) -> int:
    """The widest block of channels, whole vector registers of 128 lanes,
    that divides ``di`` and is at most ``most``; ``di`` itself where none
    does (a tiny size: the block is the whole dimension)."""
    best = 0
    for lanes in range(_LANES, min(di, most) + 1, _LANES):
        if di % lanes == 0:
            best = lanes
    return best or di


def _over_lanes(m: jax.Array) -> jax.Array:
    """``[..., N]`` -> ``[..., N, 128]`` float32: entry ``n`` on sublane
    ``n`` of every lane."""
    return jnp.broadcast_to(m.astype(jnp.float32)[..., None],
                            m.shape + (_LANES,))


def _wide(m: jax.Array, width: int) -> jax.Array:
    """``[N, 128]`` (one value a sublane) -> ``[N, width]``: the same
    registers, side by side."""
    reps = width // _LANES
    if width % _LANES:                  # a tiny size: one value a sublane
        return jnp.broadcast_to(m[:, :1], (m.shape[0], width))
    return m if reps == 1 else jnp.concatenate([m] * reps, axis=1)


# -- prefill: a chunk's positions, the state carried in and out ---------------

def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s_ref, o_y, o_s, *,
                 turns):
    a = a_ref[...]                                     # [N, bd]
    width = a.shape[1]

    def turn(i, s):
        t0 = pl.multiple_of(i * _TURN, _TURN)
        x8 = x_ref[0, pl.ds(t0, _TURN), :]             # [8, bd]
        dt8 = dt_ref[0, pl.ds(t0, _TURN), :]
        ys = []
        for k in range(_TURN):
            dt_t = dt8[k:k + 1, :]                     # [1, bd]
            s = jnp.exp(dt_t * a) * s \
                + _wide(b_ref[0, t0 + k], width) * (dt_t * x8[k:k + 1, :])
            ys.append(jnp.sum(s * _wide(c_ref[0, t0 + k], width), axis=0,
                              keepdims=True))
        o_y[0, pl.ds(t0, _TURN), :] = jnp.concatenate(ys, axis=0)
        return s

    o_s[0] = lax.fori_loop(0, turns, turn, s_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def _pallas_scan(x, dt, a, b, c, state, *, interpret: bool):
    """jitted so that a model's layers, which all make this call at one
    shape, trace and lower the kernel once a program. ``T`` is whole turns
    of :data:`_TURN` positions (the wrapper pads)."""
    bsz, t, di = x.shape
    n = a.shape[0]
    bd = _block(di, _SCAN_BLOCK)
    seq = pl.BlockSpec((1, t, bd), lambda i, j: (i, 0, j))
    spread = pl.BlockSpec((1, t, n, _LANES), lambda i, j: (i, 0, 0, 0))
    st = pl.BlockSpec((1, n, bd), lambda i, j: (i, 0, j))
    # the sequence tiles and the spread B and C, each twice (the pipeline's
    # two buffers), the state and A
    vmem = 2 * (3 * t * bd + 2 * t * n * _LANES + 3 * n * bd) * 4
    return pl.pallas_call(
        functools.partial(_scan_kernel, turns=t // _TURN),
        grid=(bsz, di // bd),
        in_specs=[seq, seq, pl.BlockSpec((n, bd), lambda i, j: (0, j)),
                  spread, spread, st],
        out_specs=[seq, st],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, di), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=interpret,
        name="selective_scan",
    )(x, dt, a, b, c, state)


def _lax_scan(x, dt, a, b, c, state):
    def one(s, inp):
        x_t, dt_t, b_t, c_t = inp          # [B, Di], [B, Di], [B, N], [B, N]
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    new, y = lax.scan(one, state, tuple(
        jnp.swapaxes(m, 0, 1) for m in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1), new


@trace.part(trace.STATE)
def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, state: jax.Array, *, kernel: str = "pallas",
                   interpret: Optional[bool] = None):
    """``x`` [B, T, Di], ``dt`` [B, T, Di] (after softplus; 0 freezes the
    state at that position), ``a`` [N, Di] (negative), ``b`` / ``c``
    [B, T, N], ``state`` [B, N, Di] float32 (donated by the kernel and
    updated in place). Returns ``(y [B, T, Di] float32, new state)``."""
    if kernel not in ("lax", "pallas"):
        raise ValueError(
            f"unknown selective-scan kernel {kernel!r}; known: lax, pallas")
    _refuse_another_state(state)
    f32 = jnp.float32
    x, dt, a, b, c = (m.astype(f32) for m in (x, dt, a, b, c))
    if kernel == "lax":
        return _lax_scan(x, dt, a, b, c, state)
    t = x.shape[1]
    pad = -t % _TURN
    if pad:
        # whole turns: a padded position has dt 0 and moves nothing
        x, dt, b, c = (jnp.pad(m, ((0, 0), (0, pad), (0, 0)))
                       for m in (x, dt, b, c))
    y, new = _pallas_scan(x, dt, a, _over_lanes(b), _over_lanes(c), state,
                          interpret=_interpret.resolve(interpret))
    return (y[:, :t] if pad else y), new


def lower_scan_for_tpu(*, batch: int, t: int, channels: int,
                       state_size: int) -> None:
    """Lower the scan kernel for a TPU at these shapes with no device, and
    let the lowering's error out (as ``mamba2.lower_update_for_tpu``)."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    seq = sds((batch, t, channels), f32)
    spread = sds((batch, t, state_size, _LANES), f32)
    jax.jit(functools.partial(_pallas_scan.__wrapped__, interpret=False)
            ).trace(
        seq, seq, sds((state_size, channels), f32), spread, spread,
        sds((batch, state_size, channels), f32),
    ).lower(lowering_platforms=("tpu",))


# -- decode: one position a row, in place -------------------------------------

def _update_kernel(rows_ref, n_ref, s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
                   o_s, o_y):
    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        dt = dt_ref[0]                                 # [1, bd]
        width = dt.shape[1]
        new = jnp.exp(dt * a_ref[...]) * s_ref[0] \
            + _wide(b_ref[0], width) * (dt * x_ref[0])
        o_s[0] = new
        o_y[0] = jnp.sum(new * _wide(c_ref[0], width), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _pallas_update(state, x, dt, a, b, c, live, *, interpret: bool):
    """``live`` [B] bool: the rows whose state moves. The grid walks the
    live rows first (their ids arrive by scalar prefetch) and then stands
    still on the last one's last block, so an idle slot's state is neither
    read nor written: it stays where it is, bit for bit (the state is
    updated in place). ``x`` / ``dt`` are ``[B, 1, Di]``, ``b`` / ``c``
    ``[B, N, 128]``."""
    bsz, n, di = state.shape
    bd = _block(di, max(_LANES, _UPDATE_BLOCK_BYTES // (4 * n)))
    blocks = di // bd
    # with no live row at all the grid would write back a block it never
    # filled: walk row 0 then, whose dt of 0 leaves its state as it is
    walked = live.at[0].set(live[0] | ~jnp.any(live))
    count = jnp.sum(walked).astype(jnp.int32).reshape(1)
    rows = jnp.argsort(~walked, stable=True).astype(jnp.int32)

    def at(i, j, rows, count):
        last = jnp.maximum(count[0] - 1, 0)
        return (rows[jnp.minimum(i, last)],
                jnp.where(i < count[0], j, blocks - 1))

    def row(i, j, rows, count):
        r, k = at(i, j, rows, count)
        return r, 0, k

    st = pl.BlockSpec((1, n, bd), row)
    vec = pl.BlockSpec((1, 1, bd), row)
    spread = pl.BlockSpec(
        (1, n, _LANES), lambda i, j, rows, count: (at(i, j, rows, count)[0],
                                                   0, 0))
    new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, blocks),
            in_specs=[st, vec, vec,
                      pl.BlockSpec((n, bd), lambda i, j, rows, count: (
                          0, at(i, j, rows, count)[1])),
                      spread, spread],
            out_specs=[st, vec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, 1, di), jnp.float32)],
        # the state operand follows the two prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_state_update",
    )(rows, count, state, x, dt, a, b, c)
    # an idle row's y was never written: whatever the buffer held
    return new, jnp.where(live[:, None], y[:, 0], 0.0)


@trace.part(trace.STATE)
def selective_state_update(state: jax.Array, x: jax.Array, dt: jax.Array,
                           a: jax.Array, b: jax.Array, c: jax.Array, *,
                           interpret: Optional[bool] = None):
    """One decode position: ``state`` [B, N, Di] float32 (donated and
    updated in place), ``x`` / ``dt`` [B, Di], ``a`` [N, Di], ``b`` / ``c``
    [B, N]. Returns ``(y [B, Di] float32, new state)``. A row whose ``dt``
    is 0 in every channel is idle: its state is not moved and its ``y`` is
    0."""
    _refuse_another_state(state)
    f32 = jnp.float32
    dt = dt.astype(f32)
    new, y = _pallas_update(
        state, x.astype(f32)[:, None], dt[:, None], a.astype(f32),
        _over_lanes(b), _over_lanes(c), jnp.any(dt != 0.0, axis=1),
        interpret=_interpret.resolve(interpret))
    return y, new


def lower_update_for_tpu(*, batch: int, channels: int,
                         state_size: int) -> None:
    """Lower the update kernel for a TPU at these shapes with no device, and
    let the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    vec = sds((batch, 1, channels), f32)
    spread = sds((batch, state_size, _LANES), f32)
    jax.jit(functools.partial(_pallas_update.__wrapped__, interpret=False)
            ).trace(
        sds((batch, state_size, channels), f32), vec, vec,
        sds((state_size, channels), f32), spread, spread,
        sds((batch,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))

"""Mamba-2 (state-space duality) recurrences: a chunked scan for prefill
and a one-token state update for decode.

The layer's recurrence, per head ``h`` with a scalar decay (heads of ``P``
channels, a state of ``N`` numbers a channel, ``B`` and ``C`` shared by the
heads of a group)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t      [P, N]
    y_t = S_t . C_t                                             [P]

(the ``D * x`` skip, the gate and the norm belong to the model). The state
is float32 whatever the activations are: it is carried over thousands of
tokens, and a bfloat16 state is a different configuration.

- :func:`ssd_chunk_scan`: ``T`` positions at once in chunks of ``chunk``
  positions, carrying the state from chunk to chunk (the "SSD" form: inside
  a chunk the recurrence is a masked, decay-weighted attention-like product;
  between chunks it is the recurrence itself). Plain ``jax.numpy`` at the
  highest matmul precision: a prefill chunk of 64 positions is a few MFLOPs a
  head. A position at or past ``valid_len`` leaves the state as it was
  (``dt`` = 0 there: decay 1, no input), so a padded last chunk does not
  advance the recurrence.
- :func:`ssm_state_update`: one position for every row of a decode batch,
  as a Pallas kernel (``ssm_state_update`` in a device trace) that reads and
  writes each live row's state once, in place. A row whose ``dt`` is 0 (an
  idle slot) is skipped: its state is not moved and stays bit for bit.

Both count in ``lzy_kernel_dispatch_total`` under the path names
:data:`SCAN_PATH` and :data:`UPDATE_PATH` (the engine counts one for each
program it dispatches).
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

#: ``lzy_kernel_dispatch_total{path}`` labels of the two programs
SCAN_PATH = "ssm_scan_lax"
UPDATE_PATH = "ssm_update_pallas"
#: the update kernel's name in a device trace where every head brings its own
#: ``B`` and ``C`` (a grid cell's layout differs from the grouped program's,
#: ``ssm_state_update``, and a metric anchors on one of the two)
PER_HEAD_UPDATE = "lightning_state_update"

_HI = lax.Precision.HIGHEST


def _expand_groups(m: jax.Array, heads: int) -> jax.Array:
    """``[..., G, N]`` -> ``[..., H, N]``: head ``h`` reads group
    ``h // (H // G)``."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


@trace.part(trace.STATE)
def ssd_chunk_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, state: jax.Array, *, chunk: int = 128):
    """``x`` [B, T, H, P], ``dt`` [B, T, H] (after softplus; 0 freezes the
    state at that position), ``a`` [H] (negative), ``b``/``c`` [B, T, G, N],
    ``state`` [B, H, P, N] float32. Returns ``(y [B, T, H, P] float32,
    new state)``. ``T`` is cut into chunks of ``chunk`` positions (the last
    one may be shorter)."""
    bsz, t, h, p = x.shape
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    b = _expand_groups(b.astype(jnp.float32), h)       # [B, T, H, N]
    c = _expand_groups(c.astype(jnp.float32), h)
    ys = []
    for start in range(0, t, chunk):
        sl = slice(start, min(start + chunk, t))
        y, state = _one_chunk(x[:, sl], dt[:, sl], a, b[:, sl], c[:, sl],
                              state)
        ys.append(y)
    return (ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)), state


def _one_chunk(x, dt, a, b, c, state):
    q = x.shape[1]
    la = dt * a.astype(jnp.float32)                    # [B, Q, H] log decay
    cs = jnp.cumsum(la, axis=1)                        # through position t
    # inside the chunk: y_t += sum_{s<=t} (C_t.B_s) exp(cs_t - cs_s) dt_s x_s
    cb = jnp.einsum("bthn,bshn->bhts", c, b, precision=_HI)
    seg = cs.transpose(0, 2, 1)[:, :, :, None] \
        - cs.transpose(0, 2, 1)[:, :, None, :]         # [B, H, t, s]
    keep = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    # the masked entries have a positive exponent: zero them before exp
    w = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0) * cb
    dtx = dt[..., None] * x                            # [B, Q, H, P]
    y = jnp.einsum("bhts,bshp->bthp", w, dtx, precision=_HI)
    # what the carried state adds: y_t += exp(cs_t) C_t . S_in
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bthn,bhpn->bthp", c, state, precision=_HI)
    # the state after the chunk
    tail = jnp.exp(cs[:, -1:, :] - cs)                 # [B, Q, H]
    new = jnp.exp(cs[:, -1])[:, :, None, None] * state + jnp.einsum(
        "bshp,bshn->bhpn", dtx * tail[..., None], b, precision=_HI)
    return y, new


# -- decode: one position a row, in place -------------------------------------

def _update_kernel(rows_ref, n_ref, s_ref, x_ref, da_ref, dtb_ref, c_ref,
                   o_s, o_y):
    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        s = s_ref[0]                                   # [hb, P, N]
        new = da_ref[0] * s + x_ref[0][:, :, None] * dtb_ref[0]
        o_s[0] = new
        o_y[0] = jnp.sum(new * c_ref[0], axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _pallas_update(state, x, da, dtb, c, live, *, interpret: bool):
    """``live`` [B] bool: the rows whose state moves. The grid walks the
    live rows first (their ids arrive by scalar prefetch) and then stands
    still on the last one's last block, so an idle slot's state is neither
    read nor written: it stays where it is, bit for bit (the state is
    updated in place). ``c`` ``[B, G, 1, N]``: a ``C`` a group, or a head
    (``G == H``: a recurrence whose every head has its own ``B`` and ``C``,
    linear attention with a decay: a device trace calls that program
    ``PER_HEAD_UPDATE``, the grouped one ``ssm_state_update``)."""
    bsz, h, p, n = state.shape
    # a grid cell: one group's heads, one ``B``, one ``C``; or eight heads
    # with a ``C`` each
    g, hc = (c.shape[1], 1) if c.shape[1] != h else (h // 8, 8)
    hb = h // g
    # with no live row at all the grid would write back a block it never
    # filled: walk row 0 then, whose dt of 0 leaves its state as it is
    live = live.at[0].set(live[0] | ~jnp.any(live))
    count = jnp.sum(live).astype(jnp.int32).reshape(1)
    rows = jnp.argsort(~live, stable=True).astype(jnp.int32)

    def at(i, j, rows, count):
        last = jnp.maximum(count[0] - 1, 0)
        return (rows[jnp.minimum(i, last)],
                jnp.where(i < count[0], j, g - 1))

    def spec(block):
        return pl.BlockSpec(
            block, lambda i, j, rows, count: at(i, j, rows, count)
            + (0,) * (len(block) - 2))

    y_spec = spec((1, hb, p))
    new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, g),
            in_specs=[spec((1, hb, p, n)), y_spec, spec((1, hb, 1, n)),
                      spec((1, hb, 1, n)), spec((1, hc, 1, n))],
            out_specs=[spec((1, hb, p, n)), y_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h, p), jnp.float32)],
        # the state operand follows the two prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=PER_HEAD_UPDATE if hc > 1 else "ssm_state_update",
    )(rows, count, state, x, da, dtb, c)
    # an idle row's y was never written: whatever the buffer held
    return new, jnp.where(live[:, None, None], y, 0.0)


@trace.part(trace.STATE)
def ssm_state_update(state: jax.Array, x: jax.Array, dt: jax.Array,
                     a: jax.Array, b: jax.Array, c: jax.Array, *,
                     interpret: Optional[bool] = None):
    """One decode position: ``state`` [B, H, P, N] float32 (donated and
    updated in place), ``x`` [B, H, P], ``dt`` [B, H], ``a`` [H], ``b`` /
    ``c`` [B, G, N] (``G == H``: a ``B`` and a ``C`` a head). Returns ``(y
    [B, H, P] float32, new state)``. The per-head scalars arrive spread over
    the state's lanes (``exp(dt A)`` and ``dt B`` as ``[B, H, 1, N]``: 1/64
    of the state's bytes), so the kernel is one multiply-add over each state
    tile and one reduction."""
    bsz, h, p, n = state.shape
    g = b.shape[1]
    per_head = g == h and h % 8 == 0
    if h % g or ((h // g) % 8 and not per_head):
        raise ValueError(
            f"heads a group ({h}/{g}) must be a multiple of 8, or every "
            f"head its own group and the heads a multiple of 8")
    dt = dt.astype(jnp.float32)
    da = jnp.exp(dt * a.astype(jnp.float32))           # [B, H]
    da = jnp.broadcast_to(da[:, :, None, None], (bsz, h, 1, n))
    dtb = dt[:, :, None, None] * _expand_groups(
        b.astype(jnp.float32), h)[:, :, None, :]       # [B, H, 1, N]
    new, y = _pallas_update(
        state, x.astype(jnp.float32), da, dtb,
        c.astype(jnp.float32)[:, :, None, :], jnp.any(dt != 0.0, axis=1),
        interpret=_interpret.resolve(interpret))
    return y, new


def lower_update_for_tpu(*, batch: int, heads: int, head_dim: int,
                         state_size: int, groups: int) -> None:
    """Lower the update kernel for a TPU at these shapes with no device, and
    let the lowering's error out (as ``paged_attention.lower_pallas_for_tpu``
    does for the decode kernel)."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    jax.jit(functools.partial(_pallas_update.__wrapped__, interpret=False)
            ).trace(
        sds((batch, heads, head_dim, state_size), f32),
        sds((batch, heads, head_dim), f32),
        sds((batch, heads, 1, state_size), f32),
        sds((batch, heads, 1, state_size), f32),
        sds((batch, groups, 1, state_size), f32),
        sds((batch,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))

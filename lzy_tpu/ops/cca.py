"""Compressed convolutional attention's mix (CCA; arXiv:2510.04476, the
ZAYA1 family): what turns a position's latent projections into the query,
key and value its attention layer caches and reads. Every other attention in
the tree projects a position alone; here position ``t`` reads ``t-1`` and
``t-2`` too.

``H`` query heads and ``G`` key-value heads of ``d``, ``Lq = H d``,
``Lk = G d``, ``C = Lq + Lk``, ``g = H / G``. A position hands over
``w_t = [q~_t ; k~_t ; v2_t]`` (``W = C + Lk / 2`` wide: its latent query and
key, and the half of the *next* position's value that this position
projects) and ``v1_t`` (``Lk / 2``: the half of its own value it projects
itself). With ``p_t = w_t[:C]``, ``p_t = 0`` before the sequence::

    a_t[c]  = w0[0, c] p_{t-1}[c] + w0[1, c] p_t[c] + b0[c]       depthwise
    c_t[h]  = [a_{t-1}[h] ; a_t[h]] W1[h] + b1[h]     a head of d, 2d x d each
    m_t[h]  = (q~_t[h] + k~_t[h // g]) / 2          mk_t[j] = mean of its g
    q_t[h]  = sqrt(d) n(c_t[h] + m_t[h])
    k_t[j]  = tau[j] sqrt(d) n(c_t[H + j] + mk_t[j])       n(x) = x / |x|
    v_t     = [v1_t ; v2_{t-1}]

so ``a_{-1} = b0``, not 0: the sequence is padded once, on the left, by two
zeros of ``p``. **What is carried between programs is a window of two
positions**, ``[w_{t-2} ; w_{t-1}]`` (``[rows, 2 W]`` float32, the older
first; zeros for a fresh row, which *is* the padded start: a cached ``a``
would have to start at ``b0``).

- :func:`cca_mix_update`: one position for every row of a decode batch, as
  one Pallas kernel (``cca_mix_update`` in a device trace): both
  convolutions, the mean, both normalisations with the temperature, the value
  shift, and the window moved on by one position, in place. The grid walks
  blocks of :data:`_ROW_BLOCK` rows, those with a live row first, and stands
  still after them, so a block of idle slots is neither read nor written; an
  idle row inside a walked block keeps its window bit for bit (its results
  are not zeroed: nobody reads an idle row's).
- :func:`cca_mix`: the same arithmetic over a chunk of ``T`` positions a
  row, by XLA (the work is elementwise but for ten ``[T, 2d] x [2d, d]``
  products, which it takes as one batched ``dot_general``; PERF.md section
  6, PR 48 has what it costs in a prefill program). The window after the
  chunk ends at the row's last *real* position (``valid_len``), so pads
  behind it and a row with no real position move nothing.
- :func:`lax_mix_update`: the update in plain ``jax.numpy``, the kernel's
  oracle.

The products take their operands in ``dtype`` (the activations' type) and
accumulate in float32; everything else is float32. Programs that hold them
count in ``lzy_kernel_dispatch_total`` under :data:`UPDATE_PATH` and
:data:`MIX_PATH`.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lzy_tpu.ops import interpret as _interpret
from lzy_tpu.utils import trace

#: ``lzy_kernel_dispatch_total{path}`` labels of the programs
UPDATE_PATH = "cca_update_pallas"
MIX_PATH = "cca_mix_lax"

#: rows a grid cell of the update takes: the rows of its twenty products
_ROW_BLOCK = 16
_NORM_EPS = 1e-6


class Mixer(NamedTuple):
    """A layer's weights, as the functions here take them: ``w0`` [2, C] and
    ``b0`` [C] (the depthwise taps, the older position's first), ``w1``
    [H + G, 2 d, d] (a head's two taps stacked on the input axis, the older
    position's first) and ``b1`` [C], ``tau`` [G]."""
    w0: Any
    b0: Any
    w1: Any
    b1: Any
    tau: Any


def window_width(heads: int, groups: int, head_dim: int) -> int:
    """``W``: what one position leaves in the window."""
    return (heads + groups) * head_dim + groups * head_dim // 2


def _refuse_another_window(window: jax.Array) -> None:
    if window.dtype != jnp.float32:
        raise ValueError(
            f"the carried window is float32, got {window.dtype}: a window "
            f"of another type is another configuration")


def _normalise(x, d: int):
    return x * (jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _NORM_EPS)
        * d ** 0.5)


def _mix(old, mid, new, v1, w0, b0, w1, b1, tau_wide, *, heads: int,
         groups: int, dtype):
    """``q`` [.., Lq], ``k`` [.., Lk], ``v`` [.., Lk], float32, of the
    positions whose ``w`` is ``new`` [.., W], behind ``mid`` (one position
    back) and ``old`` (two back); ``w0`` / ``b0`` / ``tau_wide`` broadcast
    against ``[.., C]`` / ``[.., Lk]``, ``w1`` and ``b1`` index a head first
    (arrays or the kernel's references: a head's bias is read as a row of its
    own, Mosaic refuses to spread a lane slice of one row over the rows).
    The arithmetic of the kernel's body and of its oracle, a head after
    another on lane-aligned slices, which is what Mosaic takes;
    :func:`cca_mix` says the same over whole arrays."""
    c = b0.shape[-1]
    d = c // (heads + groups)
    g = heads // groups
    p_old, p_mid, p_new = old[..., :c], mid[..., :c], new[..., :c]
    a_prev = w0[0] * p_old + w0[1] * p_mid + b0
    a_cur = w0[0] * p_mid + w0[1] * p_new + b0

    def head(x, h):
        return x[..., h * d:(h + 1) * d]

    def conv(h):
        taps = jnp.concatenate([head(a_prev, h), head(a_cur, h)], axis=-1)
        return jnp.dot(taps.astype(dtype), w1[h].astype(dtype),
                       preferred_element_type=jnp.float32) + b1[h]

    q, k = [], []
    for j in range(groups):
        k_lat = head(p_new, heads + j)
        q_sum = 0.0
        for h in range(j * g, (j + 1) * g):
            q_lat = head(p_new, h)
            q_sum = q_sum + q_lat
            q.append(_normalise(conv(h) + (q_lat + k_lat) * 0.5, d))
        k.append(_normalise(conv(heads + j) + (q_sum * (1.0 / g) + k_lat)
                            * 0.5, d))
    k = jnp.concatenate(k, axis=-1) * tau_wide
    v = jnp.concatenate([v1, mid[..., c:]], axis=-1)
    return jnp.concatenate(q, axis=-1), k, v


def _wide(mixer: Mixer):
    """The weights as ``_mix`` broadcasts them: float32, ``b1`` a head a
    row ``[H + G, 1, d]``, ``tau`` a head's value over its ``d`` lanes,
    ``[1, Lk]``."""
    f32 = jnp.float32
    heads, _, d = mixer.w1.shape
    return (mixer.w0.astype(f32), mixer.b0.astype(f32)[None], mixer.w1,
            mixer.b1.astype(f32).reshape(heads, 1, d),
            jnp.repeat(mixer.tau.astype(f32), d)[None])


# -- decode: one position a row, the window moved in place --------------------

def _update_kernel(order_ref, n_ref, win_ref, new_ref, v1_ref, live_ref,
                   w0_ref, b0_ref, w1_ref, b1_ref, tau_ref, o_win, o_q, o_k,
                   o_v, *, heads, groups, dtype):
    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        width = new_ref.shape[1]
        old, mid = win_ref[:, :width], win_ref[:, width:]
        new = new_ref[...]
        q, k, v = _mix(old, mid, new, v1_ref[...], w0_ref[...], b0_ref[...],
                       w1_ref, b1_ref, tau_ref[...], heads=heads,
                       groups=groups, dtype=dtype)
        o_q[...], o_k[...], o_v[...] = q, k, v
        live = live_ref[...] > 0                       # [rows, 1]
        o_win[:, :width] = jnp.where(live, mid, old)
        o_win[:, width:] = jnp.where(live, new, mid)


@functools.partial(jax.jit,
                   static_argnames=("heads", "groups", "dtype", "interpret"),
                   donate_argnums=(0,))
def _pallas_update(window, new, v1, live, w0, b0, w1, b1, tau_wide, *, heads,
                   groups, dtype, interpret: bool):
    """``live`` [B] bool. ``B`` is whole blocks of :data:`_ROW_BLOCK` rows
    (the wrapper pads). The blocks with a live row are walked first (their
    ids arrive by scalar prefetch); after them the grid stands still on the
    last one, so a block of idle rows is neither read nor written."""
    bsz, two_w = window.shape
    width = two_w // 2
    c = b0.shape[-1]
    lq = c * heads // (heads + groups)
    lk = c - lq
    rb = min(_ROW_BLOCK, bsz)
    blocks = bsz // rb
    touched = jnp.any(live.reshape(blocks, rb), axis=1)
    # with no live row at all the grid would write back a block it never
    # filled: walk block 0 then, whose rows all keep their windows
    walked = touched.at[0].set(touched[0] | ~jnp.any(touched))
    count = jnp.sum(walked).astype(jnp.int32).reshape(1)
    order = jnp.argsort(~walked, stable=True).astype(jnp.int32)

    def rows(i, order, count):
        return order[jnp.minimum(i, jnp.maximum(count[0] - 1, 0))], 0

    def fixed(*shape):
        return pl.BlockSpec(shape, lambda i, order, count: (0,) * len(shape))

    out_win, q, k, v = pl.pallas_call(
        functools.partial(_update_kernel, heads=heads, groups=groups,
                          dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks,),
            in_specs=[pl.BlockSpec((rb, two_w), rows),
                      pl.BlockSpec((rb, width), rows),
                      pl.BlockSpec((rb, lk // 2), rows),
                      pl.BlockSpec((rb, 1), rows),
                      fixed(*w0.shape), fixed(*b0.shape), fixed(*w1.shape),
                      fixed(*b1.shape), fixed(*tau_wide.shape)],
            out_specs=[pl.BlockSpec((rb, two_w), rows),
                       pl.BlockSpec((rb, lq), rows),
                       pl.BlockSpec((rb, lk), rows),
                       pl.BlockSpec((rb, lk), rows)]),
        out_shape=[jax.ShapeDtypeStruct(window.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, lq), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, lk), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, lk), jnp.float32)],
        # the window operand follows the two prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="cca_mix_update",
    )(order, count, window, new, v1, live.astype(jnp.int32)[:, None], w0, b0,
      w1, b1, tau_wide)
    return q, k, v, out_win


@trace.part(trace.STATE)
def cca_mix_update(window: jax.Array, new: jax.Array, v1: jax.Array,
                   live: jax.Array, mixer: Mixer, *, heads: int, groups: int,
                   dtype: Any, interpret: Optional[bool] = None):
    """One decode position: ``window`` [B, 2 W] float32 (donated and moved
    in place), ``new`` [B, W] (``w_t``), ``v1`` [B, Lk / 2], ``live`` [B]
    bool. Returns ``(q [B, Lq], k [B, Lk], v [B, Lk], new window)``, float32.
    An idle row's window is not moved; its ``q``, ``k``, ``v`` are whatever
    (nobody reads them)."""
    _refuse_another_window(window)
    f32 = jnp.float32
    bsz = window.shape[0]
    pad = -bsz % min(_ROW_BLOCK, bsz)
    new, v1 = new.astype(f32), v1.astype(f32)
    if pad:
        window, new, v1 = (jnp.pad(m, ((0, pad), (0, 0)))
                           for m in (window, new, v1))
        live = jnp.pad(live, (0, pad))
    q, k, v, out = _pallas_update(
        window, new, v1, live, *_wide(mixer), heads=heads, groups=groups,
        dtype=jnp.dtype(dtype), interpret=_interpret.resolve(interpret))
    if pad:
        q, k, v, out = (m[:bsz] for m in (q, k, v, out))
    return q, k, v, out


@trace.part(trace.STATE)
def lax_mix_update(window, new, v1, live, mixer: Mixer, *, heads: int,
                   groups: int, dtype: Any):
    """:func:`cca_mix_update` in plain ``jax.numpy``: its oracle."""
    _refuse_another_window(window)
    f32 = jnp.float32
    width = new.shape[-1]
    old, mid = window[:, :width], window[:, width:]
    new = new.astype(f32)
    q, k, v = _mix(old, mid, new, v1.astype(f32), *_wide(mixer), heads=heads,
                   groups=groups, dtype=jnp.dtype(dtype))
    moved = jnp.concatenate([mid, new], axis=-1)
    return q, k, v, jnp.where(live[:, None], moved, window)


def lower_update_for_tpu(*, batch: int, heads: int, groups: int,
                         head_dim: int, dtype: Any) -> None:
    """Lower the update kernel for a TPU at these shapes with no device, and
    let the lowering's error out (as ``mamba1.lower_update_for_tpu``)."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    batch += -batch % min(_ROW_BLOCK, batch)
    c = (heads + groups) * head_dim
    lk = groups * head_dim
    width = window_width(heads, groups, head_dim)
    jax.jit(functools.partial(
        _pallas_update.__wrapped__, heads=heads, groups=groups,
        dtype=jnp.dtype(dtype), interpret=False)).trace(
        sds((batch, 2 * width), f32), sds((batch, width), f32),
        sds((batch, lk // 2), f32), sds((batch,), jnp.bool_),
        sds((2, c), f32), sds((1, c), f32),
        sds((heads + groups, 2 * head_dim, head_dim), jnp.dtype(dtype)),
        sds((heads + groups, 1, head_dim), f32), sds((1, lk), f32),
    ).lower(lowering_platforms=("tpu",))


# -- prefill: a chunk's positions, the window carried in and out --------------

@trace.part(trace.STATE)
def cca_mix(window: jax.Array, new: jax.Array, v1: jax.Array,
            valid_len: Optional[jax.Array], mixer: Mixer, *, heads: int,
            groups: int, dtype: Any):
    """A chunk: ``window`` [B, 2 W] float32, ``new`` [B, T, W], ``v1``
    [B, T, Lk / 2], ``valid_len`` [B] (how many of the ``T`` positions are
    real; None for all). Returns ``(q [B, T, Lq], k [B, T, Lk], v [B, T,
    Lk], new window)``, float32; the new window ends at the last real
    position (a row with none keeps the one it had)."""
    _refuse_another_window(window)
    f32 = jnp.float32
    b, t, width = new.shape
    dtype = jnp.dtype(dtype)
    w0, b0, w1, b1 = (mixer.w0.astype(f32), mixer.b0.astype(f32), mixer.w1,
                      mixer.b1.astype(f32))
    n, _, d = w1.shape
    c, g = n * d, heads // groups
    seq = jnp.concatenate([window.reshape(b, 2, width), new.astype(f32)],
                          axis=1)                              # [B, T+2, W]
    p = seq[..., :c]
    # a_{-1} .. a_{T-1}, a head a row; the heads' products as one batched dot
    a = (w0[0] * p[:, :-1] + w0[1] * p[:, 1:] + b0).reshape(b, t + 1, n, d)
    taps = jnp.concatenate([a[:, :-1], a[:, 1:]], axis=-1).astype(dtype)
    conv = jnp.einsum("bthi,hio->btho", taps, w1.astype(dtype),
                      preferred_element_type=f32) + b1.reshape(n, d)
    lat = p[:, 2:].reshape(b, t, n, d)
    q_lat = lat[:, :, :heads].reshape(b, t, groups, g, d)
    k_lat = lat[:, :, heads:]
    mean = (q_lat + k_lat[:, :, :, None]) * 0.5
    q = _normalise(conv[:, :, :heads] + mean.reshape(b, t, heads, d), d)
    k = _normalise(conv[:, :, heads:]
                   + (jnp.sum(q_lat, axis=3) * (1.0 / g) + k_lat) * 0.5, d) \
        * mixer.tau.astype(f32)[:, None]
    v = jnp.concatenate([v1.astype(f32), seq[:, 1:t + 1, c:]], axis=-1)
    ends = jnp.full((b,), t, jnp.int32) if valid_len is None \
        else valid_len.astype(jnp.int32)
    out = jax.vmap(lambda s, e: jax.lax.dynamic_slice_in_dim(s, e, 2, 0))(
        seq, ends)
    return (q.reshape(b, t, heads * d), k.reshape(b, t, groups * d), v,
            out.reshape(b, 2 * width))

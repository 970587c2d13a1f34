"""The gated delta rule with a decay a channel ("KDA", Kimi delta
attention): a chunked scan for prefill and a one-token state update for
decode.

The layer's recurrence, per head, with keys of ``K`` and values of ``V``
channels (``q`` and ``k`` arrive L2-normalised, ``q`` scaled)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``alpha_t`` in ``(0, 1]^K`` decays each key channel by itself, ``beta_t`` in
``[0, 2)`` is the write's strength (above 1 the transition has a negative
eigenvalue). This is not ``ops/mamba2.py``'s recurrence: the transition is a
matrix, not a scalar, so the chunked form needs a triangular solve and the
decode step a product of the state with ``k`` before the rank-1 write.

**The state is kept value-major**, ``[..., V, K]`` (the transpose of ``S``
above), in float32 whatever the activations are: the vectors a step
multiplies it by (``alpha``, ``k``, ``q``) then lie along its lanes, and its
two reductions run along them. It is carried over thousands of tokens; a
bfloat16 state is a different configuration.

- :func:`kda_chunk_scan`: ``T`` positions at once, in chunks of ``chunk``
  positions, carrying the state from chunk to chunk (the WY / UT-transform
  form). With ``g_r`` the running sum of ``log alpha`` inside a chunk and
  ``u_r = beta_r (v_r - (Diag(alpha_r) S_{r-1})^T k_r)`` the write at ``r``::

      (I + Diag(beta) A) U = Diag(beta) (V - (K exp(g)) S_0)
      A[r, i] = sum_c k_r[c] k_i[c] exp(g_r[c] - g_i[c])        i < r
      O = (Q exp(g)) S_0 + P U,   P[r, i] = the same with q_r,  i <= r
      S_C = Diag(exp(g_C)) S_0 + (K exp(g_C - g))^T U

  The inverse of the unit triangular matrix is a product of ``log2 chunk``
  matrices (``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)...``, ``N`` nilpotent),
  computed for every chunk at once; what is left between chunks is four
  matrix products a chunk. The scores ``A`` and ``P`` take the difference of
  the exponents before the exponential, so nothing overflows however strong
  the decay (a product of ``k exp(g)`` with ``k exp(-g)`` would): that is
  elementwise work of ``chunk x K`` a position, which a kernel would keep to
  the diagonal blocks (ROADMAP). Plain ``jax.numpy``, float32 at the highest
  matmul precision; no loop over positions. A position at or past
  ``valid_len`` has ``alpha`` 1 and ``beta`` 0 (the caller's mask): it
  leaves the state as it was.
- :func:`kda_state_update`: one position for every row of a decode batch,
  as a Pallas kernel (``kda_state_update`` in a device trace) that reads and
  writes each live row's state once, in place; a row that is not ``live``
  (an idle slot) is skipped: its state is not moved and stays bit for bit.

Both count in ``lzy_kernel_dispatch_total`` under :data:`SCAN_PATH` and
:data:`UPDATE_PATH` (the engine counts one for each program it dispatches).
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
SCAN_PATH = "kda_chunk_lax"
UPDATE_PATH = "kda_update_pallas"

_HI = lax.Precision.HIGHEST
#: heads of one grid cell of the update kernel: 8 x 128 x 128 float32, 512 KB
_HEAD_BLOCK = 8


@trace.part(trace.STATE)
def kda_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                   log_alpha: jax.Array, beta: jax.Array, state: jax.Array,
                   *, chunk: int = 32):
    """``q``/``k`` [B, T, H, K], ``v`` [B, T, H, V], ``log_alpha``
    [B, T, H, K] (<= 0; 0 freezes the decay), ``beta`` [B, T, H] (0: no
    write), ``state`` [B, H, V, K] float32. Returns ``(o [B, T, H, V]
    float32, new state)``. ``T`` is padded up to whole chunks with frozen
    positions."""
    f32 = jnp.float32
    bsz, t, h, dk = k.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    pad = n * chunk - t

    def chunks(x):
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        # [B, T, H, ...] -> [N, B, H, C, ...]
        x = x.reshape((bsz, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    q, k, v, la = chunks(q), chunks(k), chunks(v), chunks(log_alpha)
    beta = chunks(beta[..., None])                      # [N, B, H, C, 1]
    g = jnp.cumsum(la, axis=-2)                         # through position r
    # the scores inside a chunk: the exponents' difference first
    diff = g[..., :, None, :] - g[..., None, :, :]      # [.., r, i, K]
    r_i = jnp.arange(chunk)
    lower = (r_i[:, None] >= r_i[None, :])[..., None]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kd = k[..., None, :, :] * decay                     # k_i exp(g_r - g_i)
    a = jnp.sum(k[..., :, None, :] * kd, axis=-1)       # [.., r, i]
    p = jnp.sum(q[..., :, None, :] * kd, axis=-1)       # i <= r
    eye = jnp.eye(chunk, dtype=f32)
    nil = beta * a * (r_i[:, None] > r_i[None, :])      # strictly lower
    inv = eye - nil
    power = nil
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        power = jnp.matmul(power, power, precision=_HI)
        inv = jnp.matmul(inv, eye + power, precision=_HI)
    eg = jnp.exp(g)
    u0 = jnp.matmul(inv, beta * v, precision=_HI)              # [.., C, V]
    w = jnp.matmul(inv, beta * (k * eg), precision=_HI)        # [.., C, K]
    q_in = q * eg
    tail = jnp.exp(g[..., -1:, :] - g)
    k_out = k * tail
    last = eg[..., -1, :]                                      # [.., K]

    def one(st, xs):
        u0_c, w_c, q_c, p_c, k_c, last_c = xs
        u = u0_c - jnp.einsum("bhrk,bhvk->bhrv", w_c, st, precision=_HI)
        o = jnp.einsum("bhrk,bhvk->bhrv", q_c, st, precision=_HI) \
            + jnp.matmul(p_c, u, precision=_HI)
        st = st * last_c[:, :, None, :] \
            + jnp.einsum("bhrv,bhrk->bhvk", u, k_c, precision=_HI)
        return st, o

    state, o = lax.scan(one, state.astype(f32),
                        (u0, w, q_in, p, k_out, last))
    # [N, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1).reshape(
        bsz, n * chunk, h, dv)
    return o[:, :t], state


@trace.part(trace.STATE)
def kda_step(state, q, k, v, alpha, beta):
    """One position of the recurrence in plain ``jax.numpy``: ``state``
    [B, H, V, K], ``q``/``k``/``alpha`` [B, H, K], ``v`` [B, H, V], ``beta``
    [B, H]. Returns ``(o [B, H, V], new state)``: the update kernel's
    oracle."""
    f32 = jnp.float32
    st = state.astype(f32) * alpha.astype(f32)[:, :, None, :]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - jnp.einsum(
        "bhvk,bhk->bhv", st, k.astype(f32), precision=_HI))
    st = st + u[..., None] * k.astype(f32)[:, :, None, :]
    return jnp.einsum("bhvk,bhk->bhv", st, q.astype(f32), precision=_HI), st


# -- decode: one position a row, in place -------------------------------------

def _update_kernel(rows_ref, n_ref, s_ref, r_ref, v_ref, o_s, o_y):
    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        r = r_ref[0]                                   # [hb, 4, K]
        alpha, k, bk, q = (r[:, i:i + 1, :] for i in range(4))
        s = s_ref[0] * alpha                           # [hb, V, K]
        u = v_ref[0] - jnp.sum(s * k, axis=-1)         # [hb, V]
        new = s + u[:, :, None] * bk
        o_s[0] = new
        o_y[0] = jnp.sum(new * q, axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _pallas_update(state, rowvecs, v, live, *, interpret: bool):
    """``rowvecs`` [B, H, 4, K]: ``alpha``, ``k``, ``beta k`` and ``q`` of
    each head, one array so that a grid cell fetches them together;
    ``live`` [B] bool: the rows whose state moves. The grid walks the live
    rows first (their ids arrive by scalar prefetch) and then stands still
    on the last one's last block, so an idle slot's state is neither read
    nor written: it stays where it is, bit for bit (the state is updated in
    place)."""
    bsz, h, dv, dk = state.shape
    hb = min(_HEAD_BLOCK, h)
    blocks = h // hb
    # with no live row at all the grid would write back a block it never
    # filled: walk row 0 then, which the caller's alpha of 1 and beta of 0
    # leave as it is
    asked = live
    live = live.at[0].set(live[0] | ~jnp.any(live))
    count = jnp.sum(live).astype(jnp.int32).reshape(1)
    rows = jnp.argsort(~live, stable=True).astype(jnp.int32)

    def at(i, j, rows, count):
        last = jnp.maximum(count[0] - 1, 0)
        return (rows[jnp.minimum(i, last)],
                jnp.where(i < count[0], j, blocks - 1))

    def spec(block):
        return pl.BlockSpec(
            block, lambda i, j, rows, count: at(i, j, rows, count)
            + (0,) * (len(block) - 2))

    new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, blocks),
            in_specs=[spec((1, hb, dv, dk)), spec((1, hb, 4, dk)),
                      spec((1, hb, dv))],
            out_specs=[spec((1, hb, dv, dk)), spec((1, hb, dv))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h, dv), jnp.float32)],
        # the state operand follows the two prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_state_update",
    )(rows, count, state, rowvecs, v)
    # an idle row's o was never written: whatever the buffer held
    return new, jnp.where(asked[:, None, None], y, 0.0)


@trace.part(trace.STATE)
def kda_state_update(state: jax.Array, q: jax.Array, k: jax.Array,
                     v: jax.Array, alpha: jax.Array, beta: jax.Array,
                     live: Optional[jax.Array] = None, *,
                     interpret: Optional[bool] = None):
    """One decode position: ``state`` [B, H, V, K] float32 (donated and
    updated in place), ``q``/``k``/``alpha`` [B, H, K], ``v`` [B, H, V],
    ``beta`` [B, H], ``live`` [B] bool (every row where None). Returns
    ``(o [B, H, V] float32, new state)``; an idle row's ``o`` is 0."""
    bsz, h, _, _ = state.shape
    if h % min(_HEAD_BLOCK, h):
        raise ValueError(f"{h} heads do not divide into blocks of "
                         f"{_HEAD_BLOCK}")
    f32 = jnp.float32
    k = k.astype(f32)
    rowvecs = jnp.stack(
        [alpha.astype(f32), k, beta.astype(f32)[..., None] * k,
         q.astype(f32)], axis=2)
    if live is None:
        live = jnp.ones((bsz,), bool)
    new, o = _pallas_update(state, rowvecs, v.astype(f32), live,
                            interpret=_interpret.resolve(interpret))
    return o, new


def lower_for_tpu(*, batch: int, heads: int, key_dim: int,
                  value_dim: int) -> None:
    """Lower the update kernel for a TPU at these shapes with no device, and
    let the lowering's error out."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    jax.jit(functools.partial(_pallas_update.__wrapped__, interpret=False)
            ).trace(
        sds((batch, heads, value_dim, key_dim), f32),
        sds((batch, heads, 4, key_dim), f32),
        sds((batch, heads, value_dim), f32),
        sds((batch,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))

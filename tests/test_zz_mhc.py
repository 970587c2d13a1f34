"""The residual streams' mix (``ops/mhc.py``): each kernel against its ``lax``
oracle at a decode round's rows and a chunk's, the mix against the
benchmark's plain reference (twenty explicit Sinkhorn sweeps), how far from
doubly stochastic twenty sweeps leave it, and the lowering for a TPU at the
published widths. CPU, Pallas kernels interpreted (``tests/conftest.py``).

The file's name sorts last on purpose (as ``test_zz_deepseek_v3.py``'s)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import motif as ref
from lzy_tpu.ops import mhc

N, D = 4, 128
#: float32 on both sides: the order of the sums alone
TOL = 5e-6


def _inputs(rows, seed=0, spread=1.0):
    """Streams of unequal scale, a projection of unit spread (as the
    model's initialiser draws it), a mix of large and small biases."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, N, D)) * rng.uniform(0.2, 3.0, (rows, N, 1))
    phi = rng.normal(size=(mhc.mix_rows(N), N * D)) * spread * (N * D) ** -0.5
    alpha = np.asarray([0.7, 1.3, 0.9])
    b = rng.normal(size=(mhc.mix_rows(N),)) * 0.5
    y = rng.normal(size=(rows, D))
    return tuple(jnp.asarray(a, jnp.float32) for a in (x, phi, alpha, b, y))


def _reference(x, phi, alpha, b, sweeps=20):
    cfg = types.SimpleNamespace(norm_eps=1e-5, mhc_sweeps=sweeps)
    with jax.default_matmul_precision("highest"):
        return ref.connection(x, {"phi": phi, "alpha": alpha, "b": b}, cfg)


@pytest.mark.parametrize("rows", [8, 40, 64, 256])
def test_the_kernels_are_their_oracles(rows):
    x, phi, alpha, b, y = _inputs(rows, seed=rows)
    flat = x.reshape(rows, N * D)
    kw = dict(streams=N, sweeps=20)
    h0, mix0 = mhc.mhc_pre(flat, phi, alpha, b, kernel="lax", **kw)
    h1, mix1 = mhc.mhc_pre(flat, phi, alpha, b, kernel="pallas", **kw)
    assert h1.shape == (rows, D) and mix1.shape == (rows, mhc.MIX_WIDTH)
    assert np.abs(np.asarray(h0 - h1)).max() < TOL * 10
    assert np.abs(np.asarray(mix0 - mix1)).max() < TOL
    assert not np.asarray(mix1[:, mhc.mix_rows(N):]).any()
    out0 = mhc.mhc_post(flat, y, mix0, streams=N, kernel="lax")
    out1 = mhc.mhc_post(flat, y, mix0, streams=N, kernel="pallas")
    assert out1.shape == flat.shape and out1.dtype == flat.dtype
    assert np.abs(np.asarray(out0 - out1)).max() < TOL * 10


def test_the_mix_is_the_references_explicit_sweeps():
    """``Hpre``, ``Hpost`` and ``Hres`` as the benchmark's reference makes
    them (``exp`` and twenty explicit sweeps, no subtraction of the largest
    entry), and ``h`` and the new streams as its sums."""
    x, phi, alpha, b, y = _inputs(40, seed=3)
    pre, post, res = _reference(x, phi, alpha, b)
    for kernel in ("lax", "pallas"):
        h, mix = mhc.mhc_pre(x.reshape(40, N * D), phi, alpha, b, streams=N,
                             sweeps=20, kernel=kernel)
        mine = mhc.split_mix(mix, N)
        for a, want in zip(mine, (pre, post, res)):
            assert np.abs(np.asarray(a - want)).max() < TOL
        assert np.abs(np.asarray(
            h - jnp.einsum("tn,tnd->td", pre, x))).max() < TOL * 10
        out = mhc.mhc_post(x.reshape(40, N * D), y, mix, streams=N,
                           kernel=kernel).reshape(40, N, D)
        want = jnp.einsum("tij,tjd->tid", res, x) \
            + post[:, :, None] * y[:, None]
        assert np.abs(np.asarray(out - want)).max() < TOL * 10
    assert float(post.max()) > 1.0            # the factor 2 is there


def test_twenty_sweeps_leave_the_mix_doubly_stochastic_to_a_thousandth():
    """The last sweep ends on the columns: their sums are 1 to rounding.
    The rows' sums stand within 1e-3 of 1 at the initialiser's spread (the
    stated distance; Sinkhorn-Knopp converges geometrically, and 3 sweeps
    leave them 30 times further), and the entries are positive."""
    x, phi, alpha, b, _ = _inputs(256, seed=5)
    flat = x.reshape(256, N * D)
    _, mix = mhc.mhc_pre(flat, phi, alpha, b, streams=N, sweeps=20)
    res = np.asarray(mhc.split_mix(mix, N)[2])
    assert (res > 0).all()
    assert np.abs(res.sum(axis=1) - 1).max() < 1e-6        # columns
    rows20 = np.abs(res.sum(axis=2) - 1).max()
    assert rows20 < 1e-3
    _, mix3 = mhc.mhc_pre(flat, phi, alpha, b, streams=N, sweeps=3)
    rows3 = np.abs(np.asarray(mhc.split_mix(mix3, N)[2]).sum(axis=2)
                   - 1).max()
    assert rows3 > 30 * rows20
    # and three sweeps are the reference's three
    _, _, want = _reference(x, phi, alpha, b, sweeps=3)
    assert np.abs(np.asarray(mhc.split_mix(mix3, N)[2]) - want).max() < TOL


def test_a_large_bias_does_not_overflow_the_exponent():
    """``exp`` is taken of ``H~res`` less its largest entry a token: a
    trained ``b`` of 100 gives the mix the shifted one gives, not NaN."""
    x, phi, alpha, b, _ = _inputs(8, seed=7)
    shifted = b.at[2 * N:].add(100.0)
    for kernel in ("lax", "pallas"):
        _, a = mhc.mhc_pre(x.reshape(8, N * D), phi, alpha, b, streams=N,
                           sweeps=20, kernel=kernel)
        _, c = mhc.mhc_pre(x.reshape(8, N * D), phi, alpha, shifted,
                           streams=N, sweeps=20, kernel=kernel)
        assert np.isfinite(np.asarray(c)).all()
        assert np.abs(np.asarray(a - c)).max() < 1e-4


def test_the_streams_keep_their_sum_but_for_what_the_sublayer_adds():
    """Columns that sum to 1 carry the streams' sum through: the new sum is
    the old one plus ``sum(Hpost) y``."""
    x, phi, alpha, b, y = _inputs(16, seed=9)
    flat = x.reshape(16, N * D)
    _, mix = mhc.mhc_pre(flat, phi, alpha, b, streams=N, sweeps=20)
    out = mhc.mhc_post(flat, y, mix, streams=N).reshape(16, N, D)
    post = mhc.split_mix(mix, N)[1]
    want = x.sum(axis=1) + post.sum(axis=1, keepdims=True) * y
    assert np.abs(np.asarray(out.sum(axis=1) - want)).max() < 1e-4


@pytest.mark.parametrize("what", ["kernel", "rows"])
def test_what_the_kernels_cannot_take_is_refused(what):
    x, phi, alpha, b, y = _inputs(100 if what == "rows" else 8)
    flat = x.reshape(x.shape[0], N * D)
    if what == "kernel":
        with pytest.raises(ValueError, match="unknown stream-mix kernel"):
            mhc.mhc_pre(flat, phi, alpha, b, streams=N, sweeps=20,
                        kernel="triton")
        with pytest.raises(ValueError, match="unknown stream-mix kernel"):
            mhc.mhc_post(flat, y, jnp.zeros((8, mhc.MIX_WIDTH)), streams=N,
                         kernel="triton")
    else:
        with pytest.raises(ValueError, match="whole tiles"):
            mhc.mhc_pre(flat, phi, alpha, b, streams=N, sweeps=20,
                        kernel="pallas")


@pytest.mark.parametrize("rows", [64, 256])
def test_the_kernels_lower_for_a_tpu_at_published_widths(rows):
    mhc.lower_for_tpu(rows=rows, streams=4, width=4096, sweeps=20,
                      dtype=jnp.float32, y_dtype=jnp.bfloat16)
    assert (mhc.path("pallas"), mhc.path("lax")) == ("mhc_pallas", "mhc_lax")

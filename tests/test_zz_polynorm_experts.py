"""The dropless product under PolyNorm (``ops/polynorm_experts.py``): the
kernel against its ``lax`` oracle at a decode round's rows and a chunk's and
at a width of several tiles, the activation against a direct sum, an expert
nobody chose never read, and the lowering for a TPU at the published widths.
CPU, Pallas kernels interpreted (``tests/conftest.py``).

The file's name sorts last on purpose (as ``test_zz_deepseek_v3.py``'s)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import polynorm_experts as pne

SCALE, CLAMP = 0.5, 0.5


def _inputs(rows, experts=6, latent=64, width=384, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def draw(*shape, std=1.0):
        return jnp.asarray(rng.normal(size=shape) * std, dtype)

    x = draw(rows, latent)
    gate, up = (draw(experts, latent, width, std=latent ** -0.5)
                for _ in range(2))
    down = draw(experts, width, latent, std=width ** -0.5)
    params = jnp.asarray(np.concatenate([
        1 / 3 + 0.2 * rng.normal(size=(experts, 3)),
        rng.normal(size=(experts, 1))], axis=-1), jnp.float32)
    # a third of the (row, expert) pairs, weights of either sign
    weights = jnp.asarray(
        rng.normal(size=(rows, experts))
        * (rng.uniform(size=(rows, experts)) < 0.34), jnp.float32)
    return x, gate, up, down, params, weights


def _both(args, **kw):
    return (pne.polynorm_experts(*args, scale=SCALE, clamp=CLAMP,
                                 kernel="pallas", **kw),
            pne.lax_polynorm_experts(*args, scale=SCALE, clamp=CLAMP))


@pytest.mark.parametrize("rows", [8, 40, 256])
def test_the_kernel_is_its_oracle(rows):
    """One tile of 384 (``_tile`` takes the widest that fits 2 MB)."""
    mine, want = _both(_inputs(rows, seed=rows))
    assert mine.shape == (rows, 64) and mine.dtype == jnp.float32
    assert np.abs(np.asarray(mine - want)).max() < 2e-5
    assert float(jnp.abs(want).max()) > 0.5


@pytest.mark.parametrize("rows,width,tiles", [(8, 2304, 3), (40, 1536, 2)])
def test_the_norms_are_over_the_width_at_several_tiles(rows, width, tiles):
    """A width the kernel walks in several tiles (768 the widest multiple of
    128 under 1,024 that divides these): a row's three norms are over all of
    it, so the two phases and the float32 scratch are exercised."""
    assert width // gexp._tile(width, 64, 4) == tiles
    mine, want = _both(_inputs(rows, width=width, seed=width))
    assert np.abs(np.asarray(mine - want)).max() < 5e-5
    # norms taken a tile at a time would be another function
    x, gate, up, down, params, weights = _inputs(rows, width=width,
                                                 seed=width)
    tile = width // tiles
    act = jnp.concatenate([
        pne.polynorm(jnp.dot(x, gate[0][:, i:i + tile]), params[0],
                     scale=SCALE, clamp=CLAMP)
        for i in range(0, width, tile)], axis=-1)
    whole = pne.polynorm(jnp.dot(x, gate[0]), params[0], scale=SCALE,
                         clamp=CLAMP)
    assert np.abs(np.asarray(act - whole)).max() > 1e-2


def test_an_expert_nobody_chose_is_never_read():
    """Expert 2's matrices are NaN and no row reaches it: the kernel walks
    the touched experts only, and the result is the oracle's over zeros
    there. Touched by one row, the NaN comes through."""
    x, gate, up, down, params, weights = _inputs(16, seed=3)
    weights = weights.at[:, 2].set(0.0)
    bad = (x, gate.at[2].set(jnp.nan), up.at[2].set(jnp.nan),
           down.at[2].set(jnp.nan), params, weights)
    mine = pne.polynorm_experts(*bad, scale=SCALE, clamp=CLAMP)
    want = pne.lax_polynorm_experts(x, gate, up, down, params, weights,
                                    scale=SCALE, clamp=CLAMP)
    assert np.isfinite(np.asarray(mine)).all()
    assert np.abs(np.asarray(mine - want)).max() < 2e-5
    reached = pne.polynorm_experts(
        *bad[:5], weights.at[5, 2].set(0.5), scale=SCALE, clamp=CLAMP)
    assert not np.isfinite(np.asarray(reached)).all()


def test_no_row_reaching_any_expert_gives_zeros():
    args = _inputs(8, seed=4)
    mine = pne.polynorm_experts(*args[:5], jnp.zeros_like(args[5]),
                                scale=SCALE, clamp=CLAMP)
    assert not np.asarray(mine).any()


def test_the_activation_is_the_published_sum():
    """``P(z) = scale (w_3 z^3 / rms(z^3) + w_2 z^2 / rms(z^2) + w_1 z /
    rms(z) + clamp(b))``, each root over the whole width with eps 1e-6, by
    hand in float64; the clamp binds a bias of 2; a bfloat16 ``z`` is
    raised to its powers in float32."""
    rng = np.random.default_rng(5)
    z = rng.normal(size=(7, 96)) * 3.0
    p = np.asarray([0.4, -0.3, 0.25, 2.0])

    def rms(v):
        return np.sqrt((v * v).mean(axis=-1, keepdims=True) + 1e-6)

    want = SCALE * (p[2] * z ** 3 / rms(z ** 3) + p[1] * z ** 2
                    / rms(z ** 2) + p[0] * z / rms(z) + CLAMP)
    got = pne.polynorm(jnp.asarray(z, jnp.float32), jnp.asarray(p),
                       scale=SCALE, clamp=CLAMP)
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    rough = pne.polynorm(jnp.asarray(z, jnp.bfloat16), jnp.asarray(p),
                         scale=SCALE, clamp=CLAMP)
    exact = pne.polynorm(jnp.asarray(z, jnp.bfloat16).astype(jnp.float32),
                         jnp.asarray(p), scale=SCALE, clamp=CLAMP)
    assert rough.dtype == jnp.float32 and (rough == exact).all()


def test_bfloat16_weights_keep_the_norm_in_float32():
    """bfloat16 operands, as served: the kernel and the oracle round at the
    same places (the products' float32 results feed the powers; the
    activation times ``up`` times the weight is rounded once)."""
    args = _inputs(40, seed=6, dtype=jnp.bfloat16)
    mine, want = _both(args)
    assert np.abs(np.asarray(mine - want)).max() \
        < 2e-2 * float(jnp.abs(want).max())


def test_an_unknown_kernel_is_refused():
    with pytest.raises(ValueError, match="unknown expert kernel"):
        pne.polynorm_experts(*_inputs(8), scale=SCALE, clamp=CLAMP,
                             kernel="triton")
    assert (pne.path("pallas"), pne.path("lax")) == (
        "polynorm_experts_pallas", "polynorm_experts_lax")


@pytest.mark.parametrize("rows", [8, 64, 256])
def test_the_kernel_lowers_for_a_tpu_at_published_widths(rows):
    """48 experts of 4096 x 1280 in bfloat16: five tiles of 256."""
    assert gexp._tile(1280, 4096, 2) == 256
    pne.lower_for_tpu(rows=rows, experts=48, latent=4096, width=1280,
                      dtype=jnp.bfloat16)

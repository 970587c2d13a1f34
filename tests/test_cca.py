"""``ops/cca.py``: the decode round's mix as a Pallas kernel (interpreted)
against its ``lax`` form, the chunk form against the update one position at
a time and against the written definition, idle rows and pads untouched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import cca

H, G, D = 4, 2, 16
C, LK, W = (H + G) * D, G * D, cca.window_width(H, G, D)
SIZES = dict(heads=H, groups=G, dtype=jnp.float32)


def _mixer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return cca.Mixer(
        jax.random.normal(ks[0], (2, C)), jax.random.normal(ks[1], (C,)),
        jax.random.normal(ks[2], (H + G, 2 * D, D)) * 0.2,
        jax.random.normal(ks[3], (C,)),
        jnp.exp(0.25 * jax.random.normal(ks[4], (G,))))


def _rows(seed, b):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, 2 * W)),
            jax.random.normal(ks[1], (b, W)),
            jax.random.normal(ks[2], (b, LK // 2)),
            jax.random.bernoulli(ks[3], 0.6, (b,)))


@pytest.mark.parametrize("b", [1, 7, 16, 37, 64])
def test_the_update_kernel_is_its_lax_form_and_idle_rows_keep_their_window(
        b):
    window, new, v1, live = _rows(b, b)
    mixer = _mixer()
    want = cca.lax_mix_update(window, new, v1, live, mixer, **SIZES)
    got = cca.cca_mix_update(window + 0, new, v1, live, mixer,
                             interpret=True, **SIZES)
    on = np.asarray(live)
    for a, e in zip(got[:3], want[:3]):
        if on.any():
            assert np.abs(np.asarray(a)[on] - np.asarray(e)[on]).max() < 1e-5
    assert (np.asarray(got[3]) == np.asarray(want[3])).all()
    # an idle row's window is the one it had, bit for bit; a live row's has
    # moved on by one position
    assert (np.asarray(got[3])[~on] == np.asarray(window)[~on]).all()
    if on.any():
        assert (np.asarray(got[3])[on, :W] == np.asarray(window)[on, W:]
                ).all()
        assert (np.asarray(got[3])[on, W:] == np.asarray(new)[on]).all()


def test_a_batch_with_no_live_row_moves_nothing():
    window, new, v1, _ = _rows(5, 32)
    out = cca.cca_mix_update(window + 0, new, v1, jnp.zeros((32,), bool),
                             _mixer(), interpret=True, **SIZES)
    assert (np.asarray(out[3]) == np.asarray(window)).all()


def test_a_block_of_idle_rows_is_not_walked():
    """Rows 16-31 idle: the grid stands still after the blocks with a live
    row, so their block is neither read nor written (NaN there would
    poison a block that was computed and selected away)."""
    window, new, v1, _ = _rows(6, 48)
    live = jnp.arange(48) // 16 != 1
    window = window.at[16:32].set(jnp.nan)
    out = cca.cca_mix_update(window + 0, new, v1, live, _mixer(),
                             interpret=True, **SIZES)
    assert np.isnan(np.asarray(out[3])[16:32]).all()
    assert np.isfinite(np.asarray(out[3])[np.asarray(live)]).all()
    assert np.isfinite(np.asarray(out[0])[np.asarray(live)]).all()


def _written(window, news, v1s, mixer):
    """The definition, one position and one head after another, float64."""
    w0, b0, w1, b1, tau = (np.asarray(m, np.float64) for m in mixer)
    seq = np.concatenate([np.asarray(window, np.float64).reshape(2, W),
                          np.asarray(news, np.float64)])
    p = seq[:, :C]
    t = len(news)
    q, k, v = np.zeros((t, H, D)), np.zeros((t, G, D)), np.zeros((t, LK))
    a = lambda i: w0[0] * p[i - 1] + w0[1] * p[i] + b0       # needs i >= 1
    for i in range(t):
        cur, prev = a(i + 2), a(i + 1)
        lat = p[i + 2]
        for h in range(H + G):
            sl = slice(h * D, (h + 1) * D)
            c = prev[sl] @ w1[h, :D] + cur[sl] @ w1[h, D:] + b1[sl]
            if h < H:
                j = h // (H // G)
                x = c + (lat[sl] + lat[(H + j) * D:(H + j + 1) * D]) / 2
                q[i, h] = D ** 0.5 * x / np.sqrt((x * x).sum() + 1e-6)
            else:
                j = h - H
                heads = lat[:H * D].reshape(H, D)[j * (H // G):(j + 1)
                                                  * (H // G)]
                x = c + (heads.mean(0) + lat[sl]) / 2
                k[i, j] = tau[j] * D ** 0.5 * x / np.sqrt(
                    (x * x).sum() + 1e-6)
        v[i] = np.concatenate([np.asarray(v1s[i], np.float64),
                               seq[i + 1, C:]])
    return q.reshape(t, -1), k.reshape(t, -1), v


def test_the_chunk_form_is_the_written_definition_from_a_fresh_window():
    """Zeros are the padded start: the first position's older tap reads
    convolution 0's bias, not 0."""
    mixer = _mixer(3)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    news = jax.random.normal(ks[0], (1, 6, W))
    v1s = jax.random.normal(ks[1], (1, 6, LK // 2))
    fresh = jnp.zeros((1, 2 * W))
    q, k, v, window = cca.cca_mix(fresh, news, v1s, None, mixer, **SIZES)
    wq, wk, wv = _written(fresh[0], news[0], v1s[0], mixer)
    assert np.abs(np.asarray(q[0]) - wq).max() < 1e-4
    assert np.abs(np.asarray(k[0]) - wk).max() < 1e-4
    assert np.abs(np.asarray(v[0]) - wv).max() < 1e-6
    assert (np.asarray(window[0]) == np.asarray(news[0, 4:]).reshape(-1)
            ).all()
    # a_{-1} = b0: with the bias gone the first position moves
    q0, _, _, _ = cca.cca_mix(fresh, news, v1s, None, mixer._replace(
        b0=jnp.zeros_like(mixer.b0)), **SIZES)
    assert np.abs(np.asarray(q0[0, 0] - q[0, 0])).max() > 1e-2


@pytest.mark.parametrize("t", [1, 2, 5, 16])
def test_a_chunk_is_its_positions_one_after_another_and_pads_move_nothing(t):
    b = 9
    window, _, _, _ = _rows(11, b)
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    news = jax.random.normal(ks[0], (b, t, W))
    v1s = jax.random.normal(ks[1], (b, t, LK // 2))
    valid = jax.random.randint(ks[2], (b,), 0, t + 1).at[0].set(0).at[1].set(t)
    mixer = _mixer(1)
    q, k, v, out = cca.cca_mix(window, news, v1s, valid, mixer, **SIZES)
    carried = window
    for i in range(t):
        on = i < valid
        qi, ki, vi, carried = cca.lax_mix_update(
            carried, news[:, i], v1s[:, i], on, mixer, **SIZES)
        on = np.asarray(on)
        if on.any():
            for a, e in ((q, qi), (k, ki), (v, vi)):
                assert np.abs(np.asarray(a[:, i])[on]
                              - np.asarray(e)[on]).max() < 1e-5
    assert (np.asarray(out) == np.asarray(carried)).all()
    assert (np.asarray(out[0]) == np.asarray(window[0])).all()


def test_a_window_of_another_type_is_refused():
    window, new, v1, live = _rows(2, 4)
    rough = window.astype(jnp.bfloat16)
    for call in (
            lambda: cca.cca_mix_update(rough, new, v1, live, _mixer(),
                                       interpret=True, **SIZES),
            lambda: cca.lax_mix_update(rough, new, v1, live, _mixer(),
                                       **SIZES),
            lambda: cca.cca_mix(rough, new[:, None], v1[:, None], None,
                                _mixer(), **SIZES)):
        with pytest.raises(ValueError, match="float32"):
            call()


def test_the_update_lowers_for_a_tpu_at_the_published_widths():
    for batch in (1, 64):
        cca.lower_update_for_tpu(batch=batch, heads=8, groups=2,
                                 head_dim=128, dtype=jnp.bfloat16)
    assert cca.window_width(8, 2, 128) == 1408

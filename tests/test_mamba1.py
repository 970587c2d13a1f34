"""The Mamba-1 recurrences (``lzy_tpu/ops/mamba1.py``): the scan of a prefill
chunk and the one-position update of a decode round, both Pallas kernels
interpreted on the CPU (``tests/conftest.py``), against their ``lax`` oracle
and against the recurrence written out as a loop over positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import mamba1

TOL = 2e-5


def _inputs(seed=0, bsz=2, t=40, di=256, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        size=(bsz, t, di))), jnp.float32)
    a = -jnp.asarray(np.exp(rng.uniform(0.0, np.log(16.0), size=(n, di))),
                     jnp.float32)
    return (f(bsz, t, di), dt, a, f(bsz, t, n), f(bsz, t, n),
            f(bsz, n, di))


def _loop(x, dt, a, b, c, state):
    """The recurrence as it is written: one position after another, a row,
    a channel and a state entry at a time (numpy, float64)."""
    x, dt, a, b, c = (np.asarray(m, np.float64) for m in (x, dt, a, b, c))
    s = np.asarray(state, np.float64).copy()
    ys = np.zeros(x.shape)
    for i in range(x.shape[1]):
        s = np.exp(dt[:, i, None, :] * a) * s \
            + b[:, i, :, None] * (dt[:, i] * x[:, i])[:, None, :]
        ys[:, i] = np.einsum("bnc,bn->bc", s, c[:, i])
    return ys, s


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("t,di", [(40, 256), (8, 128), (19, 48), (64, 640)],
                         ids=["t40", "t8", "t19_narrow", "t64_five_tiles"])
def test_scan_is_the_recurrence(kernel, t, di):
    x, dt, a, b, c, s0 = _inputs(t=t, di=di)
    want_y, want_s = _loop(x, dt, a, b, c, s0)
    y, s = mamba1.selective_scan(x, dt, a, b, c, jnp.array(s0),
                                 kernel=kernel)
    assert y.dtype == s.dtype == jnp.float32
    assert np.abs(np.asarray(y) - want_y).max() < TOL * np.abs(want_y).max()
    assert np.abs(np.asarray(s) - want_s).max() < TOL * np.abs(want_s).max()


def test_the_kernel_is_its_oracle():
    x, dt, a, b, c, s0 = _inputs(seed=3, t=32)
    y0, n0 = mamba1.selective_scan(x, dt, a, b, c, s0, kernel="lax")
    y1, n1 = mamba1.selective_scan(x, dt, a, b, c, jnp.array(s0),
                                   kernel="pallas")
    assert np.abs(np.asarray(y0 - y1)).max() < 1e-5
    assert np.abs(np.asarray(n0 - n1)).max() < 1e-5


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("cuts", [(16, 16, 8), (7, 20, 13), (1, 39)],
                         ids=["even", "uneven", "one_then_rest"])
def test_chunks_with_carried_state_are_the_whole_prompt(kernel, cuts):
    x, dt, a, b, c, s0 = _inputs(seed=1, t=sum(cuts))
    whole_y, whole_s = mamba1.selective_scan(x, dt, a, b, c, jnp.array(s0),
                                             kernel=kernel)
    ys, s, at = [], jnp.array(s0), 0
    for n in cuts:
        sl = slice(at, at + n)
        y, s = mamba1.selective_scan(x[:, sl], dt[:, sl], a, b[:, sl],
                                     c[:, sl], s, kernel=kernel)
        ys.append(y)
        at += n
    assert np.abs(np.asarray(jnp.concatenate(ys, 1) - whole_y)).max() < 1e-5
    assert np.abs(np.asarray(s - whole_s)).max() < 1e-5


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_a_padded_tail_leaves_the_state_bit_for_bit(kernel):
    """Positions at or past ``valid_len`` have ``dt`` 0: a chunk of 24 of
    which 13 are real ends in the state the 13 alone end in."""
    x, dt, a, b, c, s0 = _inputs(seed=2, t=24)
    real = jnp.arange(24)[None, :, None] < jnp.asarray([13, 24])[:, None,
                                                                 None]
    _, padded = mamba1.selective_scan(x, jnp.where(real, dt, 0.0), a, b, c,
                                      jnp.array(s0), kernel=kernel)
    _, alone = mamba1.selective_scan(x[:1, :13], dt[:1, :13], a, b[:1, :13],
                                     c[:1, :13], jnp.array(s0[:1]),
                                     kernel=kernel)
    assert np.array_equal(np.asarray(padded[0]), np.asarray(alone[0]))
    # and a chunk of nothing but pads moves nothing at all
    _, still = mamba1.selective_scan(x, jnp.zeros_like(dt), a, b, c,
                                     jnp.array(s0), kernel=kernel)
    assert np.array_equal(np.asarray(still), np.asarray(s0))


def test_update_is_the_scan_one_position_at_a_time():
    x, dt, a, b, c, s0 = _inputs(seed=4, bsz=3, t=6, di=256)
    want_y, want_s = _loop(x, dt, a, b, c, s0)
    s, ys = jnp.array(s0), []
    for i in range(6):
        y, s = mamba1.selective_state_update(s, x[:, i], dt[:, i], a,
                                             b[:, i], c[:, i])
        ys.append(np.asarray(y))
    got = np.stack(ys, axis=1)
    assert np.abs(got - want_y).max() < TOL * np.abs(want_y).max()
    assert np.abs(np.asarray(s) - want_s).max() < TOL * np.abs(want_s).max()


@pytest.mark.parametrize("live", [(True, False, True, False),
                                  (False, False, False, True),
                                  (False, True, True, True)],
                         ids=["first_and_third", "last_only", "all_but_first"])
def test_update_leaves_an_idle_slot_bit_for_bit(live):
    x, dt, a, b, c, s0 = _inputs(seed=5, bsz=4, t=1, di=128)
    live = np.asarray(live)
    dt1 = dt[:, 0] * live[:, None]
    y, s = mamba1.selective_state_update(jnp.array(s0), x[:, 0], dt1, a,
                                         b[:, 0], c[:, 0])
    want_y, want_s = _loop(x, dt1[:, None], a, b, c, s0)
    y, s = np.asarray(y), np.asarray(s)
    for row in range(4):
        if live[row]:
            assert np.abs(y[row] - want_y[row, 0]).max() < 1e-4
            assert np.abs(s[row] - want_s[row]).max() < 1e-4
        else:
            assert np.array_equal(s[row], np.asarray(s0[row]))
            assert not y[row].any()


def test_update_with_no_live_row_moves_nothing():
    x, dt, a, b, c, s0 = _inputs(seed=6, bsz=3, t=1, di=128)
    y, s = mamba1.selective_state_update(
        jnp.array(s0), x[:, 0], jnp.zeros_like(dt[:, 0]), a, b[:, 0],
        c[:, 0])
    assert np.array_equal(np.asarray(s), np.asarray(s0))
    assert not np.asarray(y).any()


def test_a_state_of_another_type_is_refused():
    x, dt, a, b, c, s0 = _inputs(t=8, di=128)
    with pytest.raises(ValueError, match="another configuration"):
        mamba1.selective_scan(x, dt, a, b, c, s0.astype(jnp.bfloat16))
    with pytest.raises(ValueError, match="another configuration"):
        mamba1.selective_state_update(s0.astype(jnp.bfloat16), x[:, 0],
                                      dt[:, 0], a, b[:, 0], c[:, 0])
    with pytest.raises(ValueError, match="unknown selective-scan kernel"):
        mamba1.selective_scan(x, dt, a, b, c, s0, kernel="auto")


def test_the_state_is_float32_over_a_long_slow_recurrence():
    """A slow channel (decay 0.999 a position) adding small inputs for 2,000
    positions: float32 holds the sum to 1e-5 of the written recurrence, a
    state rounded to bfloat16 after every position is percent off. The
    configuration states float32, and this is what that buys."""
    t, di, n = 2000, 128, 16
    rng = np.random.default_rng(7)
    x = jnp.asarray(1.0 + 0.1 * rng.normal(size=(1, t, di)), jnp.float32)
    dt = jnp.full((1, t, di), 1e-3, jnp.float32)
    a = -jnp.ones((n, di), jnp.float32)
    b = jnp.ones((1, t, n), jnp.float32)
    c = jnp.ones((1, t, n), jnp.float32) / n
    s0 = jnp.zeros((1, n, di), jnp.float32)
    want_y, want_s = _loop(x, dt, a, b, c, s0)
    _, s = mamba1.selective_scan(x, dt, a, b, c, jnp.array(s0),
                                 kernel="pallas")
    assert np.abs(np.asarray(s) - want_s).max() < 1e-5 * np.abs(want_s).max()
    rough = jnp.array(s0)
    for i in range(0, t, 8):
        sl = slice(i, i + 8)
        _, rough = mamba1.selective_scan(x[:, sl], dt[:, sl], a, b[:, sl],
                                         c[:, sl], rough, kernel="lax")
        rough = rough.astype(jnp.bfloat16).astype(jnp.float32)
    off = np.abs(np.asarray(rough) - want_s).max() / np.abs(want_s).max()
    assert off > 3e-3


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: what Mosaic's lowering would refuse at the
    first request is refused here (the engine asks at construction;
    ``tests/test_aot_topology.py`` compiles them for v5e)."""
    mamba1.lower_update_for_tpu(batch=32, channels=5120, state_size=16)
    mamba1.lower_scan_for_tpu(batch=1, t=256, channels=5120, state_size=16)


def test_path_labels():
    assert mamba1.SCAN_PATH == "ssm1_scan_pallas"
    assert mamba1.UPDATE_PATH == "ssm1_update_pallas"

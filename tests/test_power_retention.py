"""Power retention (``ops/power_retention.py``) at tiny sizes on the CPU: the
token-by-token recurrence, the chunked scan and the attention form are one
function; the feature map's layout keeps ``phi(a) . phi(b) = (a . b)^2``; a
padded chunk and an idle slot leave the state bit for bit; the chunk kernel
and the update kernel (both interpreted) against ``jax.numpy``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import power_retention as pr

EPS = 1e-6


def _inputs(seed, b, t, h, kv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, d), jnp.float32).astype(dtype)
    log_g = jax.nn.log_sigmoid(
        2.0 + 1.5 * jax.random.normal(ks[3], (b, t, kv), jnp.float32))
    return q, k, v, log_g


def _zeros(b, kv, d):
    s_shape, z_shape = pr.state_shapes(b, kv, d)
    return jnp.zeros(s_shape, jnp.float32), jnp.zeros(z_shape, jnp.float32)


def attention_form(q, k, v, log_g, real=None):
    """The other formula of the same function: no state, no chunk."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if real is None:
        real = jnp.ones((b, t), bool)
    lg = jnp.where(real[..., None], log_g, 0.0)
    cs = jnp.cumsum(lg, axis=1).transpose(0, 2, 1)         # [B, KV, T]
    sc = jnp.einsum("btkgd,bskd->bkgts", q.reshape(b, t, kv, h // kv, d), k,
                    precision="highest") / np.sqrt(d)
    keep = (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]) \
        & real[:, None, None, :]
    seg = cs[:, :, :, None] - cs[:, :, None, :]
    a = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0)[:, :, None] \
        * sc * sc
    y = jnp.einsum("bkgts,bskd->btkgd", a, v, precision="highest") \
        / (a.sum(-1).transpose(0, 3, 1, 2)[..., None] + EPS)
    return y.reshape(b, t, h, d)


def recurrence(q, k, v, log_g, s, z, real=None):
    """Token by token in ``jax.numpy``, the state form as it is written."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    flat = z.reshape(b, kv, pr.n_tiles(d), d)
    ys = []
    for i in range(t):
        on = jnp.ones((b,), bool) if real is None else real[:, i]
        pk, pq = pr.phi(k[:, i]), pr.phi(q[:, i].reshape(b, kv, h // kv, d))
        decay = jnp.exp(log_g[:, i])
        s_new = decay[:, :, None, None, None] * s + \
            pk[:, :, :, None, :] * v[:, i].astype(jnp.float32)[:, :, None, :,
                                                               None]
        z_new = decay[:, :, None, None] * flat + pk
        s = jnp.where(on[:, None, None, None, None], s_new, s)
        flat = jnp.where(on[:, None, None, None], z_new, flat)
        num = jnp.einsum("bkgmi,bkmvi->bkgv", pq, s, precision="highest")
        den = jnp.einsum("bkgmi,bkmi->bkg", pq, flat, precision="highest")
        ys.append((num / (den[..., None] + d * EPS)).reshape(b, h, d))
    return jnp.stack(ys, axis=1), s, flat.reshape(z.shape)


@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_keeps_the_squared_product_in_its_layout(d):
    a, b = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d), jnp.float32)
    pa, pb = pr.phi(a), pr.phi(b)
    assert pa.shape == (7, pr.n_tiles(d), d)
    np.testing.assert_allclose(
        (pa * pb).sum((-1, -2)), jnp.square((a * b).sum(-1)), rtol=1e-4,
        atol=1e-6 * d * d)      # d^2: the size of the terms that cancel
    # d (d + 1) / 2 features and a padded tail of zeros
    filled = np.asarray(pr.phi(jnp.ones((d,)))) != 0
    assert filled.sum() == pr.n_features(d)
    assert not filled[-1, d // 2:].any() and filled[:-1].all()


@pytest.mark.parametrize("d,h,kv", [(8, 4, 2), (16, 6, 2)])
def test_recurrence_chunked_scan_and_attention_form_agree(d, h, kv):
    q, k, v, log_g = _inputs(0, 2, 37, h, kv, d)
    s0, z0 = _zeros(2, kv, d)
    exact = attention_form(q, k, v, log_g)
    y_r, s_r, z_r = recurrence(q, k, v, log_g, s0, z0)
    y_c, s_c, z_c = pr.retention_chunk_scan(q, k, v, log_g, s0, z0,
                                            chunk=16, eps=EPS)
    np.testing.assert_allclose(y_r, exact, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(y_c, exact, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(s_c, s_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z_c, z_r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cut", [1, 5, 16, 23])
def test_a_chunk_boundary_anywhere_gives_the_same_state(cut):
    d, h, kv = 8, 4, 2
    q, k, v, log_g = _inputs(1, 1, 29, h, kv, d)
    s0, z0 = _zeros(1, kv, d)
    y, s, z = pr.retention_chunk_scan(q, k, v, log_g, s0, z0, chunk=64)
    y1, s1, z1 = pr.retention_chunk_scan(
        q[:, :cut], k[:, :cut], v[:, :cut], log_g[:, :cut], s0, z0, chunk=8)
    y2, s2, z2 = pr.retention_chunk_scan(
        q[:, cut:], k[:, cut:], v[:, cut:], log_g[:, cut:], s1, z1, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(s2, s, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z2, z, rtol=1e-4, atol=1e-4)


def test_a_padded_chunk_leaves_the_state_bit_for_bit():
    d, h, kv = 8, 4, 2
    q, k, v, log_g = _inputs(2, 1, 16, h, kv, d)
    s0, z0 = _zeros(1, kv, d)
    _, s, z = pr.retention_chunk_scan(q, k, v, log_g, s0, z0, chunk=8)
    # a chunk of pads alone, and a chunk whose tail is pads
    none = jnp.zeros((1, 16), bool)
    _, s1, z1 = pr.retention_chunk_scan(q, k, v, log_g, s, z, none, chunk=8)
    assert (s1 == s).all() and (z1 == z).all()
    some = jnp.arange(16)[None, :] < 5
    y2, s2, z2 = pr.retention_chunk_scan(q, k, v, log_g, s, z, some, chunk=8)
    y3, s3, z3 = pr.retention_chunk_scan(
        q[:, :5], k[:, :5], v[:, :5], log_g[:, :5], s, z, chunk=8)
    np.testing.assert_allclose(s2, s3, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(z2, z3, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y2[:, :5], y3, rtol=1e-5, atol=1e-6)


def _scan_counting_calls(q, k, v, log_g, s, z, chunk):
    """The scan's results, and how many calls of the kernel it traced."""
    scan = jax.jit(pr.retention_chunk_scan, static_argnames=("chunk",))
    traced = scan.trace(q, k, v, log_g, s, z, chunk=chunk)
    return scan(q, k, v, log_g, s, z, chunk=chunk), \
        str(traced.jaxpr).count(pr.CHUNK_KERNEL)


@pytest.mark.parametrize("d,h,kv,chunk", [(8, 4, 2, 128), (16, 10, 2, 16)])
def test_two_chunks_in_one_call_are_two_calls_of_one_chunk(d, h, kv, chunk):
    """A program of two chunks passes the state through the kernel once
    (:data:`CHUNKS_A_CALL`), a tile serving both chunks while it is
    resident: the same numbers as a call a chunk, to float32 rounding."""
    x = _inputs(6, 2, 2 * chunk, h, kv, d)

    def part(sl):
        return [a[:, sl] for a in x]

    # over a state that is not zeros
    _, s0, z0 = pr.retention_chunk_scan(*part(slice(9)), *_zeros(2, kv, d),
                                        chunk=chunk)
    (y, s, z), calls = _scan_counting_calls(*x, s0, z0, chunk)
    assert calls == 1
    y1, s1, z1 = pr.retention_chunk_scan(*part(slice(chunk)), s0, z0,
                                         chunk=chunk)
    y2, s2, z2 = pr.retention_chunk_scan(*part(slice(chunk, None)), s1, z1,
                                         chunk=chunk)
    np.testing.assert_allclose(y, jnp.concatenate([y1, y2], 1), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(s, s2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z, z2, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,calls", [(3, 1), (21, 1), (37, 2), (70, 3)])
def test_a_short_last_chunk_is_filled_with_positions_that_are_not_real(
        t, calls):
    """Fewer positions than a chunk are one short chunk, and a last chunk
    that is short is filled up: the recurrence over the real positions."""
    d, h, kv = 16, 10, 2                       # five query heads a state
    q, k, v, log_g = _inputs(7, 1, t, h, kv, d)
    s0, z0 = _zeros(1, kv, d)
    (y, s, z), traced = _scan_counting_calls(q, k, v, log_g, s0, z0, 16)
    assert traced == calls and y.shape == q.shape
    y_r, s_r, z_r = recurrence(q, k, v, log_g, s0, z0)
    np.testing.assert_allclose(y, y_r, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(s, s_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z, z_r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("d,h,kv", [(8, 4, 2), (16, 6, 2)])
def test_update_kernel_interpreted_against_jax_numpy(d, h, kv):
    b = 3
    q, k, v, log_g = _inputs(3, b, 6, h, kv, d)
    s0, z0 = _zeros(b, kv, d)
    y_r, s_r, z_r = recurrence(q, k, v, log_g, s0, z0)
    s, z = s0, z0
    live = jnp.ones((b,), bool)
    for i in range(6):
        y, s, z = pr.retention_state_update(
            s, z, q[:, i], k[:, i], v[:, i], log_g[:, i], live, eps=EPS,
            interpret=True)
        np.testing.assert_allclose(y, y_r[:, i], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(s, s_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z, z_r, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("live", [(True, False, True, False),
                                  (False, False, False, True),
                                  (False, False, False, False)])
def test_an_idle_slot_is_skipped_and_its_state_stays_bit_for_bit(live):
    d, h, kv, b = 8, 4, 2, 4
    q, k, v, log_g = _inputs(4, b, 3, h, kv, d)
    s0, z0 = _zeros(b, kv, d)
    _, s, z = pr.retention_chunk_scan(q, k, v, log_g, s0, z0)
    before = np.asarray(s), np.asarray(z)
    live = jnp.asarray(live)
    y, s1, z1 = pr.retention_state_update(
        s, z, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], live, interpret=True)
    y_r, s_r, z_r = recurrence(
        q[:, :1], k[:, :1], v[:, :1], log_g[:, :1], jnp.asarray(before[0]),
        jnp.asarray(before[1]), real=live[:, None])
    for row, on in enumerate(np.asarray(live)):
        if on:
            np.testing.assert_allclose(y[row], y_r[row, 0], rtol=2e-4,
                                       atol=2e-5)
            np.testing.assert_allclose(s1[row], s_r[row], rtol=1e-5,
                                       atol=1e-5)
        else:
            assert (np.asarray(s1[row]) == before[0][row]).all()
            assert (np.asarray(z1[row]) == before[1][row]).all()
            assert not np.asarray(y[row]).any()


def test_bfloat16_products_stay_near_the_float32_function():
    """The served types: q, k, v and the products of phi(q) with S in
    bfloat16, the state and every sum float32."""
    d, h, kv = 16, 6, 2
    q, k, v, log_g = _inputs(5, 1, 40, h, kv, d, jnp.bfloat16)
    s0, z0 = _zeros(1, kv, d)
    exact = attention_form(q, k, v, log_g)
    y, s, z = pr.retention_chunk_scan(q[:, :32], k[:, :32], v[:, :32],
                                      log_g[:, :32], s0, z0, chunk=16)
    assert s.dtype == z.dtype == jnp.float32
    live = jnp.ones((1,), bool)
    for i in range(32, 40):
        y_i, s, z = pr.retention_state_update(
            s, z, q[:, i], k[:, i], v[:, i], log_g[:, i], live,
            interpret=True)
        np.testing.assert_allclose(y_i, exact[:, i], rtol=0.05, atol=0.05)
    np.testing.assert_allclose(y, exact[:, :32], rtol=0.05, atol=0.05)


def test_bfloat16_chunks_over_a_carried_state_stay_near_the_function():
    """Two chunks a call and a short third over the state the first two
    left, five query heads a state, every operand bfloat16."""
    d, h, kv = 16, 10, 2
    q, k, v, log_g = _inputs(8, 2, 40, h, kv, d, jnp.bfloat16)
    s0, z0 = _zeros(2, kv, d)
    exact = attention_form(q, k, v, log_g)
    y, s, z = pr.retention_chunk_scan(q, k, v, log_g, s0, z0, chunk=16)
    assert y.dtype == s.dtype == z.dtype == jnp.float32
    np.testing.assert_allclose(y, exact, rtol=0.05, atol=0.05)
    _, s_r, z_r = recurrence(q, k, v, log_g, s0, z0)
    np.testing.assert_allclose(s, s_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z, z_r, rtol=1e-4, atol=1e-4)

"""The reads of a paged latent cache (``ops/mla.py``): the Pallas kernel
under both its names against the lax form of the same sum and against
float32, across page and block boundaries, idle slots and the verify
window; and its lowering for a TPU at the published shapes. Interpreted
here (``tests/conftest.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import mla
from lzy_tpu.ops.paged_attention import TOLERANCE


def _case(b, t, h, w, n, page, pages, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k[0], (b, t, h, w), jnp.float32).astype(dtype)
    pool = jax.random.normal(k[1], (n, page, w), jnp.float32).astype(dtype)
    table = jax.random.permutation(k[2], jnp.arange(1, n))[:b * pages] \
        .reshape(b, pages).astype(jnp.int32)
    return q, pool, table


def _float32(q, pool, table, start, v, scale):
    return np.asarray(mla.lax_mla_attention(
        q.astype(jnp.float32), pool.astype(jnp.float32), table, start,
        value_dim=v, scale=scale))


#: (batch, query positions, first positions): decode over a page boundary
#: (15 -> 16), a block boundary (the kernel scores 512 positions a block: 32
#: pages of 16) and a row at 0; the verify window across the block
#: boundary (510, 511, 512); prefill chunks cut into tiles (128 = 2 x 64, the
#: second across the block boundary) and one narrower than a tile (16).
#: Pages of 16 here: 36 of them reach past the block's 512 positions
_SHAPES = [
    (4, 1, [0, 15, 16, 530]),
    (2, 3, [5, 510]),
    (1, 128, [400]),
    (1, 16, [0]),
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,starts", _SHAPES)
def test_kernel_is_its_lax_oracle_and_float32(b, t, starts, dtype):
    h, w, v, page, pages = 2, 128, 64, 16, 36
    dt = jnp.dtype(dtype)
    q, pool, table = _case(b, t, h, w, 1 + b * pages, page, pages, dt)
    start = jnp.asarray(starts, jnp.int32)
    kw = dict(value_dim=v, scale=w ** -0.5)
    lax = mla.mla_attention(q, pool, table, start, kernel="lax", **kw)
    got = mla.mla_attention(q, pool, table, start, kernel="pallas", **kw)
    assert got.shape == lax.shape == (b, t, h, v) and got.dtype == dt
    exact = _float32(q, pool, table, start, v, w ** -0.5)
    scale = max(1.0, float(np.abs(exact).max()))
    for out in (lax, got):
        err = np.abs(np.asarray(out, np.float32) - exact).max() / scale
        assert err < TOLERANCE[dtype], err


def test_softmax_is_float32_where_bfloat16_scores_would_tie():
    """Two cached vectors whose scores differ by less than a bfloat16 step
    at 100: float32 scores weigh them 0.62 / 0.38; scores rounded to
    bfloat16 would weigh them alike."""
    w, v = 128, 128
    pool = jnp.zeros((2, 8, w), jnp.bfloat16)
    pool = pool.at[1, 0, 0].set(1.0).at[1, 1, 1].set(1.0)
    q = jnp.zeros((1, 1, 1, w), jnp.bfloat16).at[0, 0, 0, 0].set(100.0) \
        .at[0, 0, 0, 1].set(99.5)
    table = jnp.asarray([[1]], jnp.int32)
    for kernel in ("lax", "pallas"):
        out = np.asarray(mla.mla_attention(
            q, pool, table, jnp.asarray([1], jnp.int32), value_dim=v,
            scale=1.0, kernel=kernel), np.float32)[0, 0, 0]
        assert abs(out[0] - 0.6225) < 5e-3 and abs(out[1] - 0.3775) < 5e-3


def test_an_idle_slot_and_pages_past_the_context_change_nothing():
    """A row reads the pages its position reaches and no more, and an idle
    row (``start`` below 0) reads nothing at all: with NaN in every block of
    the pool but the live row's pages, the scratch block 0 too, the live
    row's result is the clean pool's bit for bit and the idle row's is 0."""
    h, w, v, page, pages = 2, 128, 64, 8, 6
    q, pool, table = _case(2, 1, h, w, 14, page, pages, jnp.float32)
    table = table.at[1].set(0)                       # row 1 idle
    start = jnp.asarray([11, -1], jnp.int32)         # row 0: two pages
    live = np.asarray(table[0, :2])
    dirty = jnp.full_like(pool, jnp.nan).at[live].set(pool[live])
    want = mla.mla_attention(q, pool, table, start, value_dim=v,
                             scale=0.1, kernel="pallas")
    got = mla.mla_attention(q, dirty, table, start, value_dim=v, scale=0.1,
                            kernel="pallas")
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.isfinite(np.asarray(want[0])).all()
    assert not np.asarray(got[1]).any()


#: which of four slots hold a row
_LIVE = {
    "none": [False, False, False, False],
    "first": [True, False, False, False],
    "last": [False, False, False, True],
    "alternating": [False, True, False, True],
    "all": [True, True, True, True],
}


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("pattern", list(_LIVE))
def test_live_rows_are_read_and_idle_rows_are_zero(pattern, t):
    """Decode and the verify window over every pattern of live slots: a live
    row is the lax oracle's and float32's at the file's tolerances, whatever
    its neighbours are (bit for bit what it is when all four are live); an
    idle row is 0 under both kernels. The first positions cross a page (15)
    and the block boundary (509-512 with four queries)."""
    h, w, v, page, pages, dtype = 2, 128, 64, 16, 36, "bfloat16"
    q, pool, whole = _case(4, t, h, w, 1 + 4 * pages, page, pages,
                           jnp.dtype(dtype), seed=5)
    live = np.asarray(_LIVE[pattern])
    full = jnp.asarray([15, 509, 3, 530], jnp.int32)
    start = jnp.where(live, full, -1)
    table = jnp.where(live[:, None], whole, 0)
    kw = dict(value_dim=v, scale=w ** -0.5)
    got = np.asarray(mla.mla_attention(
        q, pool, table, start, kernel="pallas", **kw), np.float32)
    lax = np.asarray(mla.mla_attention(
        q, pool, table, start, kernel="lax", **kw), np.float32)
    assert not got[~live].any() and not lax[~live].any()
    if live.any():
        exact = _float32(q, pool, table, start, v, w ** -0.5)[live]
        scale = max(1.0, float(np.abs(exact).max()))
        for out in (lax, got):
            assert np.abs(out[live] - exact).max() / scale < TOLERANCE[dtype]
        every = np.asarray(mla.mla_attention(
            q, pool, whole, full, kernel="pallas", **kw), np.float32)
        assert np.array_equal(got[live], every[live])


def test_a_live_row_at_position_0_is_still_read():
    """Position 0 is a row with one visible key, not an idle slot: its
    result is that key's own values, beside an idle row's 0."""
    h, w, v, page, pages = 2, 128, 64, 8, 3
    q, pool, table = _case(2, 1, h, w, 7, page, pages, jnp.float32, seed=2)
    start = jnp.asarray([0, -1], jnp.int32)
    own = np.asarray(pool[table[0, 0], 0, :v])
    for kernel in ("lax", "pallas"):
        got = np.asarray(mla.mla_attention(
            q, pool, table, start, value_dim=v, scale=0.1, kernel=kernel))
        assert np.abs(got[0, 0] - own[None, :]).max() < 1e-6, kernel
        assert not got[1].any(), kernel


def test_the_uncached_form_is_the_paged_form():
    b, t, h, w, v, page = 1, 24, 2, 128, 64, 8
    q, pool, table = _case(b, t, h, w, 4, page, 3, jnp.float32, seed=3)
    lat = pool[table].reshape(b, t, w)
    paged = mla.mla_attention(q, pool, table, jnp.zeros((1,), jnp.int32),
                              value_dim=v, scale=0.2, kernel="lax")
    plain = mla.causal_mla_attention(q, lat, value_dim=v, scale=0.2)
    assert np.abs(np.asarray(paged) - np.asarray(plain)).max() < 1e-5


def test_read_paths_and_unknown_kernel():
    assert mla.read_path("pallas", t=1) == mla.DECODE_PATH
    assert mla.read_path("pallas", t=mla.MAX_DECODE_TOKENS) == mla.DECODE_PATH
    assert mla.read_path("pallas", t=16) == mla.PREFILL_PATH
    assert mla.read_path("lax", t=1) == mla.read_path("lax", t=256) \
        == mla.LAX_PATH
    with pytest.raises(ValueError, match="unknown latent-read kernel"):
        mla.mla_attention(None, None, None, None, value_dim=1, scale=1.0,
                          kernel="gather")


@pytest.mark.parametrize("batch,t", [(32, 1), (32, 5), (1, 16), (1, 256)])
def test_both_reads_lower_for_a_tpu_at_the_published_shapes(batch, t):
    """No device and no compile: 16 heads over a latent of 576 in 640 lanes,
    a pool of 7,000 pages of 16, a table of 512 pages a slot (the widest the
    repo lowers: 64 KiB of scalar prefetch at 32 slots)."""
    mla.lower_for_tpu(batch=batch, t=t, heads=16, width=640, value_dim=512,
                      n_blocks=7000, page_size=16, pages_per_seq=512,
                      dtype=jnp.bfloat16)


def test_a_latent_of_576_lanes_is_refused_by_name_before_the_chip():
    """Why the leaf is 640 wide: the kernel copies whole pages, and a page
    of 576 lanes is not whole tiles of 128."""
    from lzy_tpu.models.deepseek_v3 import DeepseekV3Config

    cfg = DeepseekV3Config()
    assert (cfg.latent_values, cfg.latent_width) == (576, 640)
    assert cfg.kv_token_bytes() == 1280

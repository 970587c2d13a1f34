"""``ops/latent_select.py``: the indexer's scores, the exact choice, the read
of the chosen tokens and the read under a window, each against its ``lax``
oracle or a float32 computation by hand; idle rows, rows that do not select,
padded positions; the lowering for a TPU at the published widths. Tiny
shapes, CPU, Pallas kernels interpreted (``tests/conftest.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import latent_select as ls
from lzy_tpu.ops import mla

PAGE = 8


def _pool(rng, blocks, width, dtype="float32"):
    return jnp.asarray(rng.normal(size=(blocks, PAGE, width)), dtype)


def _table(rng, rows, pages, blocks):
    return jnp.asarray(np.stack([
        rng.choice(np.arange(1, blocks), pages, False)
        for _ in range(rows)]).astype(np.int32))


# -- the index ------------------------------------------------------------------

def _index_by_hand(q, w, pool, table, start):
    keys = np.asarray(pool, np.float64)[np.asarray(table)]
    keys = keys.reshape(len(start), -1, keys.shape[-1])
    s = np.einsum("bjtd,bld->bjtl", np.asarray(q, np.float64), keys)
    return np.einsum("bjtl,btj->btl", np.maximum(s, 0.0),
                     np.asarray(w, np.float64))


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("t", [1, 16])
def test_index_scores_are_the_sum_over_heads_by_hand(kernel, t):
    """A decode round (a row that selects, one past it, a row under
    ``topk``, an idle slot) and a chunk of 16 that starts past ``topk``:
    every visible position of a selecting query scores ``sum_j w_j relu(q_j
    . k)``."""
    rng = np.random.default_rng(0)
    heads, d, topk, pages = 3, 16, 8, 12
    pool = _pool(rng, 40, d)
    starts = [20, 70, 5, -1] if t == 1 else [33]
    table = _table(rng, len(starts), pages, 40)
    q = jnp.asarray(rng.normal(size=(len(starts), heads, t, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(starts), t, heads)), jnp.float32)
    start = jnp.asarray(starts, jnp.int32)
    got = np.asarray(ls.index_scores(q, w, pool, table, start, topk=topk,
                                     kernel=kernel))
    want = _index_by_hand(q, w, pool, table, starts)
    assert got.shape == (len(starts), t, pages * PAGE)
    for r, first in enumerate(starts):
        for i in range(t):
            p = first + i
            if first < 0 or (kernel == "pallas" and first + t - 1 < topk):
                continue       # an idle row; a tile the kernel skips
            assert np.abs(got[r, i, :p + 1] - want[r, i, :p + 1]).max() \
                < 1e-4, (r, i)
            if kernel == "lax":
                assert (got[r, i, p + 1:] == -1e30).all()


def test_the_index_kernels_are_their_oracle_in_bfloat16():
    """Products in the pool's dtype, sums in float32: kernel and oracle read
    the same rounded operands, so they differ by the order of their sums."""
    rng = np.random.default_rng(1)
    pool = _pool(rng, 30, 16, "bfloat16")
    table = _table(rng, 2, 10, 30)
    for t, starts in ((1, [40, 61]), (8, [30, 50])):
        q = jnp.asarray(rng.normal(size=(2, 4, t, 16)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(2, t, 4)), jnp.float32)
        start = jnp.asarray(starts, jnp.int32)
        a = np.asarray(ls.index_scores(q, w, pool, table, start, topk=8,
                                       kernel="pallas"))
        b = np.asarray(ls.index_scores(q, w, pool, table, start, topk=8,
                                       kernel="lax"))
        for r, first in enumerate(starts):
            for i in range(t):
                n = first + i + 1
                assert np.abs(a[r, i, :n] - b[r, i, :n]).max() < 2e-3


def test_an_unknown_kernel_is_refused_by_name():
    z = jnp.zeros
    with pytest.raises(ValueError, match="mosaic"):
        ls.index_scores(z((1, 1, 1, 8)), z((1, 1, 1)), z((2, PAGE, 8)),
                        z((1, 2), jnp.int32), z((1,), jnp.int32), topk=4,
                        kernel="mosaic")


# -- the choice -----------------------------------------------------------------

def _sorted_choice(scores, pos, k):
    """By a sort: score descending, position ascending among equals."""
    seen = scores[:pos + 1]
    order = np.lexsort((np.arange(pos + 1), -seen))
    return set(order[:k].tolist())


def _assert_the_sorted_choice(scores, pos, k, idx, n, in_order):
    for b in range(pos.shape[0]):
        for t in range(pos.shape[1]):
            p = pos[b, t]
            assert n[b, t] == max(0, min(p + 1, k))
            if p < 0:
                continue
            got = idx[b, t, :n[b, t]].tolist()
            assert len(set(got)) == len(got)
            assert set(got) == _sorted_choice(scores[b, t], p, k), (b, t)
            if in_order:
                assert got == sorted(got), (b, t)


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("width,k", [(300, 8), (1000, 128), (640, 64),
                                     (130, 128), (512, 1), (2304, 256)])
def test_the_choice_is_a_sort_ties_to_the_lower_position(width, k, kernel):
    """Scores rounded to quarters (many ties, both zeros among them), a row
    of one value, queries past ``k``, at ``k``, under it and not real;
    widths that are not whole chunks of 128. The kernel hands the chosen
    out in order of position."""
    rng = np.random.default_rng(width)
    scores = np.round(rng.normal(size=(2, 5, width)) * 4).astype(
        np.float32) / 4
    scores[0, 0, :50] = -0.0
    scores[0, 1, :] = 1.0
    # what lies past a query's position is never read, whatever it holds
    scores[1, 0, width // 2 + 1:] = np.nan
    pos = np.array([[width - 1, width - 2, k, k - 1, -1],
                    [width // 2, k + 1, 3, 0, width - 1]], np.int32)
    idx, n = ls.latent_topk(jnp.asarray(scores), jnp.asarray(pos), k,
                            kernel=kernel)
    idx, n = np.asarray(idx), np.asarray(n)
    assert idx.shape == (2, 5, k) and idx.dtype == np.int32
    _assert_the_sorted_choice(scores, pos, k, idx, n, kernel == "pallas")


#: 260 chunks of 128: chunk numbers and running counts past what 8 bits hold
_WIDE, _WIDE_K = 33280, 2048


@pytest.mark.parametrize("reach", [
    _WIDE,                # the table's last position
    20 * 128 + 77,        # ends inside a chunk, inside the first block
    128 * 128,            # on a chunk's edge, the first block's
    200 * 128 + 1,        # the first lane of a chunk of the second block
    256 * 128 + 5])       # past 256 chunks
def test_the_kernel_chooses_as_a_sort_at_any_reach_of_a_wide_table(reach):
    """A decode round over a table of more than 256 chunks, ``k`` 2,048: a
    live row at ``reach``, an idle slot, a row far short of it and a row
    under ``k``; each row's work stops at its own reach."""
    rng = np.random.default_rng(reach)
    scores = rng.normal(size=(4, 1, _WIDE)).astype(np.float32)
    scores[:, :, reach:] = np.nan
    pos = np.array([[reach - 1], [-1], [_WIDE_K + 300], [_WIDE_K - 1]],
                   np.int32)
    idx, n = ls.latent_topk(jnp.asarray(scores), jnp.asarray(pos), _WIDE_K,
                            kernel="pallas")
    _assert_the_sorted_choice(scores, pos, _WIDE_K, np.asarray(idx),
                              np.asarray(n), True)


@pytest.mark.parametrize("case", ["one value", "ties across a chunk edge"])
def test_the_kernel_breaks_ties_by_position_across_chunks(case):
    """A row that is one value throughout takes its first ``k`` positions;
    a run of the threshold's value that straddles chunk boundaries gives
    its lowest positions and no other. A chunk of 16 queries (two grid
    steps of eight) with a padded query and queries short of ``k``."""
    rng = np.random.default_rng(9)
    width, k, t = 4096, 300, 16
    scores = rng.normal(size=(1, t, width)).astype(np.float32)
    if case == "one value":
        scores[:] = -2.5
    else:
        # 200 above, then 250 equal ones from lane 100 of chunk 5 on
        scores = np.minimum(scores, 0.5)
        scores[:, :, 3000:3200] = 3.0
        scores[:, :, 5 * 128 + 100:5 * 128 + 350] = 1.0
    pos = (3600 + np.arange(t, dtype=np.int32))[None]
    pos[0, 3], pos[0, 9], pos[0, 12] = -1, k - 1, k
    idx, n = ls.latent_topk(jnp.asarray(scores), jnp.asarray(pos), k,
                            kernel="pallas")
    _assert_the_sorted_choice(scores, pos, k, np.asarray(idx),
                              np.asarray(n), True)
    if case == "one value":
        assert (np.asarray(idx)[0, 0] == np.arange(k)).all()


def test_an_unknown_choice_kernel_is_refused_by_name():
    with pytest.raises(ValueError, match="mosaic"):
        ls.latent_topk(jnp.zeros((1, 1, 8)), jnp.zeros((1, 1), jnp.int32),
                       4, kernel="mosaic")


def test_a_choice_wider_than_the_scores_is_refused():
    with pytest.raises(ValueError, match="16 among 8"):
        ls.latent_topk(jnp.zeros((1, 1, 8)), jnp.zeros((1, 1), jnp.int32),
                       16)


# -- the read of the chosen -----------------------------------------------------

def _attention_by_hand(q, vectors, value_dim, scale):
    """``q`` [H, W] over ``vectors`` [S, W] in float64."""
    s = np.asarray(q, np.float64) @ np.asarray(vectors, np.float64).T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ np.asarray(vectors, np.float64)[:, :value_dim]


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("t", [1, 16])
def test_the_chosen_read_is_attention_over_the_chosen_alone(kernel, t):
    rng = np.random.default_rng(3)
    h, w, r, k, pages = 4, 128, 96, 8, 12
    pool = _pool(rng, 40, w)
    starts = [20, 70, 5] if t == 1 else [33]
    b = len(starts)
    table = _table(rng, b, pages, 40)
    q = jnp.asarray(rng.normal(size=(b, t, h, w)), jnp.float32)
    pos = np.asarray(starts)[:, None] + np.arange(t)
    scores = jnp.asarray(rng.normal(size=(b, t, pages * PAGE)), jnp.float32)
    idx, n = ls.latent_topk(scores, jnp.asarray(pos, jnp.int32), k)
    got = np.asarray(ls.latent_chosen_attention(
        q, pool, table, idx, n, value_dim=r, scale=0.1, kernel=kernel))
    flat = np.asarray(pool)[np.asarray(table)].reshape(b, -1, w)
    for row in range(b):
        for i in range(t):
            chosen = np.asarray(idx)[row, i, :int(n[row, i])]
            want = _attention_by_hand(q[row, i], flat[row, chosen], r, 0.1)
            assert np.abs(got[row, i] - want).max() < 1e-5, (row, i)


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_a_row_under_topk_reads_everything_as_the_unselected_read(kernel):
    """Positions 3 and 7 of a choice of 8: every visible position is chosen,
    and the result is ``ops/mla.py``'s full read of the same pool."""
    rng = np.random.default_rng(4)
    h, w, r = 4, 128, 96
    pool = _pool(rng, 20, w)
    table = _table(rng, 2, 6, 20)
    q = jnp.asarray(rng.normal(size=(2, 1, h, w)), jnp.float32)
    start = jnp.asarray([3, 7], jnp.int32)
    # garbage scores: a row that does not select never reads them
    scores = jnp.full((2, 1, 6 * PAGE), jnp.nan, jnp.float32)
    idx, n = ls.latent_topk(scores, start[:, None], 8)
    assert list(np.asarray(n)[:, 0]) == [4, 8]
    got = ls.latent_chosen_attention(q, pool, table, idx, n, value_dim=r,
                                     scale=0.1, kernel=kernel)
    want = mla.mla_attention(q, pool, table, start, value_dim=r, scale=0.1,
                             kernel="lax")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_an_idle_slot_and_a_padded_position_read_nothing_and_give_zero(
        kernel):
    rng = np.random.default_rng(5)
    pool = _pool(rng, 20, 128)
    table = _table(rng, 2, 6, 20)
    q = jnp.asarray(rng.normal(size=(2, 4, 4, 128)), jnp.float32)
    # row 0: two real positions of four; row 1: idle
    pos = jnp.asarray([[30, 31, -1, -1], [-1, -1, -1, -1]], jnp.int32)
    scores = jnp.asarray(rng.normal(size=(2, 4, 6 * PAGE)), jnp.float32)
    idx, n = ls.latent_topk(scores, pos, 8)
    assert np.asarray(n).tolist() == [[8, 8, 0, 0], [0, 0, 0, 0]]
    got = np.asarray(ls.latent_chosen_attention(
        q, pool, table, idx, n, value_dim=96, scale=0.1, kernel=kernel))
    assert np.abs(got[0, :2]).max() > 0
    assert (got[0, 2:] == 0).all() and (got[1] == 0).all()


# -- the gather of the chosen ---------------------------------------------------

def _chosen(rng, at, k, in_order=True):
    """``idx`` [Q, k] and ``n`` [Q] for queries at positions ``at`` (below
    0: not real): ``k`` random positions of ``0 .. p``, every position
    while there are ``k`` or fewer; in order of position or shuffled."""
    idx = np.tile(np.arange(k, dtype=np.int32), (len(at), 1))
    for q, p in enumerate(at):
        if p >= k:
            idx[q] = np.sort(rng.choice(p + 1, k, replace=False))
            if not in_order:
                rng.shuffle(idx[q])
    return idx, np.clip(np.asarray(at) + 1, 0, k).astype(np.int32)


def _assert_the_gather_is_gather_tokens(pool, table, idx, n, t, block=None):
    """Every live query's first ``n`` places bit for bit, zeros from there
    to the end of the pseudo-page it touches last."""
    b, k = table.shape[0], idx.shape[-1]
    idx = jnp.asarray(idx.reshape(b, t, k))
    n = jnp.asarray(n.reshape(b, t))
    want = np.asarray(ls.gather_tokens(
        pool, table, idx.reshape(b, t * k))).reshape(b * t, k, -1)
    if block is None:
        got = ls.latent_gather(pool, table, idx, n, kernel="pallas")
    else:
        got = ls._pallas_latent_gather(
            pool, table, idx, n, *ls._tile_bounds(idx, n), block=block,
            interpret=True)
    pp = min(k, 128)
    assert got.shape == (b * t * k // pp, pp, pool.shape[-1])
    got = np.asarray(got).reshape(b * t, k, -1)
    bits = {2: np.uint16, 4: np.uint32}[want.dtype.itemsize]
    for q, m in enumerate(np.asarray(n).reshape(-1)):
        assert (got[q, :m].view(bits) == want[q, :m].view(bits)).all(), q
        assert (got[q, m:-(-m // pp) * pp] == 0).all(), q
    return got


_GATHER_CASES = {
    # an idle slot between live ones, a query at position 700 of k 256 and
    # one under k; pages of 8, blocks of 128 positions
    "idle between live": dict(at=[900, -1, 700, 100, -1, 1023], k=256,
                              pages=128),
    "n == k at the table's end": dict(at=[1023, 1023], k=128, pages=128),
    # a query at position 700 of k 2,048: 701 places, the last pseudo-page
    # a part of one
    "n < k": dict(at=[700, 5, 0], k=2048, pages=384),
    # the chosen of one tile on both sides of a page's edge (8) and of a
    # block's (128 positions a block here)
    "across page and block edges": dict(
        at=[300], k=8, pages=64, block=128,
        chosen=[[7, 8, 127, 128, 129, 255, 256, 300]]),
    "16 slots, one live": dict(at=[-1] * 9 + [2000] + [-1] * 6, k=128,
                               pages=256),
    "the verify window": dict(at=[500 + i for i in range(8)]
                              + [-1] * 8 + [90 + i for i in range(5)]
                              + [-1] * 3, k=128, pages=128, t=8),
    "in order of score": dict(at=[900, -1, 333], k=128, pages=128,
                              in_order=False),
    "bfloat16": dict(at=[900, 40], k=128, pages=128, dtype="bfloat16"),
    "a block that is not the default": dict(at=[1000, -1, 513], k=128,
                                            pages=128, block=64),
}


@pytest.mark.parametrize("case", list(_GATHER_CASES))
def test_the_gather_kernel_copies_what_gather_tokens_gives(case):
    """``latent_gather_decode`` against ``gather_tokens``: the first ``n``
    places of every live query bit for bit, in either order of the chosen;
    a query with none chosen is not written at all."""
    spec = dict(_GATHER_CASES[case])
    rng = np.random.default_rng(len(case))
    t, k, pages = spec.get("t", 1), spec["k"], spec["pages"]
    at = spec["at"]
    b = len(at) // t
    blocks = b * pages + 1
    pool = np.array(_pool(rng, blocks, 128, spec.get("dtype", "float32")))
    pool[0] = np.nan                       # the scratch block is never read
    table = jnp.asarray(1 + rng.permutation(b * pages).reshape(
        b, pages).astype(np.int32))
    idx, n = _chosen(rng, at, k, spec.get("in_order", True))
    if "chosen" in spec:
        idx = np.asarray(spec["chosen"], np.int32)
    got = _assert_the_gather_is_gather_tokens(
        jnp.asarray(pool), table, idx, n, t, spec.get("block"))
    # what no query chose is not written: the interpreter's memory starts
    # as NaN, as the chip's may
    for q, m in enumerate(n):
        if m == 0:
            assert np.isnan(got[q].astype(np.float32)).all(), q


def test_the_gather_walks_a_table_of_260_chunks():
    """A table of 33,280 positions (520 pages of 64, blocks of 512): a live
    row at its end, an idle slot, a row far short of it."""
    rng = np.random.default_rng(11)
    pages, k = 520, 2048
    pool = jnp.asarray(rng.normal(size=(2 * pages + 1, 64, 128)),
                       jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(3 * pages).reshape(
        3, pages).astype(np.int32) % (2 * pages))
    idx, n = _chosen(rng, [33279, -1, 2348], k)
    _assert_the_gather_is_gather_tokens(pool, table, idx, n, 1)


def test_the_gather_takes_xlas_when_the_reaches_are_long(monkeypatch):
    """The program adapts on what ``idx`` and ``n`` say: past
    ``_GATHER_WALK_RATIO`` cached positions a gathered place the branch is
    ``gather_tokens``, which writes every place of every query."""
    rng = np.random.default_rng(12)
    pool = _pool(rng, 129, 128)
    table = jnp.asarray(1 + rng.permutation(128).reshape(1, 128).astype(
        np.int32))
    idx, n = _chosen(rng, [1000], 128)
    idx, n = jnp.asarray(idx.reshape(1, 1, 128)), jnp.asarray([[100]])
    walked = np.asarray(ls.latent_gather(pool, table, idx, n,
                                         kernel="pallas"))
    assert (walked[0, 100:] == 0).all()
    monkeypatch.setattr(ls, "_GATHER_WALK_RATIO", 0)
    plain = np.asarray(ls.latent_gather(pool, table, idx, n,
                                        kernel="pallas"))
    assert (plain[0, :100] == walked[0, :100]).all()
    assert np.abs(plain[0, 100:]).min() > 0
    # and a program wider than the decode kernel's, or the lax form, is
    # gather_tokens outright
    assert ls.gather_path("pallas", t=1) == ls.gather_path("pallas", t=8) \
        == ls.GATHER_DECODE_PATH
    assert ls.gather_path("pallas", t=16) is None
    assert ls.gather_path("lax", t=1) is None


def test_nothing_unwritten_reaches_the_chosen_read():
    """The gather's buffer and the pool's scratch block hold NaN (the
    interpreter's memory starts so; block 0 is poisoned here) and idle
    slots' tables read scratch: the read of the chosen is finite and the
    ``lax`` path's, a query at 700 of ``k`` 256 (its last pseudo-page a
    part of one), one past ``k`` and idle slots between."""
    rng = np.random.default_rng(13)
    h, w, r, k, pages = 4, 128, 96, 256, 128
    pool = np.array(_pool(rng, 2 * pages + 1, w))
    pool[0] = np.nan
    table = np.zeros((4, pages), np.int32)
    table[0] = 1 + rng.permutation(pages)
    table[2] = 1 + pages + rng.permutation(pages)
    at = [300, -1, 1000, -1]
    idx, n = _chosen(rng, at, k)
    idx, n = jnp.asarray(idx.reshape(4, 1, k)), jnp.asarray(n.reshape(4, 1))
    q = jnp.asarray(rng.normal(size=(4, 1, h, w)), jnp.float32)
    got = np.asarray(ls.latent_chosen_attention(
        q, jnp.asarray(pool), jnp.asarray(table), idx, n, value_dim=r,
        scale=0.1, kernel="pallas"))
    assert np.isfinite(got).all()
    clean = np.where(np.isnan(pool), 0, pool)
    want = np.asarray(ls.latent_chosen_attention(
        q, jnp.asarray(clean), jnp.asarray(table), idx, n, value_dim=r,
        scale=0.1, kernel="lax"))
    assert np.abs(got - want).max() < 1e-5
    assert (got[1] == 0).all() and (got[3] == 0).all()


# -- the read under a window ----------------------------------------------------

def _window_table(rng, starts, t, window, page, pages, blocks):
    """Each live row's pages from its window's first to its last query's,
    drawn without a repeat; block 0, the scratch, everywhere else."""
    table = np.zeros((len(starts), pages), np.int32)
    free = list(rng.permutation(np.arange(1, blocks)))
    for row, first in enumerate(starts):
        if first < 0:
            continue
        lo = max(0, first - window + 1) // page
        hi = (first + t - 1) // page + 1
        table[row, lo:hi] = [free.pop() for _ in range(hi - lo)]
    return table


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("t", [1, 16])
@pytest.mark.parametrize("window", [5, 24])
def test_the_window_read_sees_the_window_and_no_page_behind_it(
        t, window, kernel):
    """Rows at the sequence's start, across the window's edge and far past
    it, an idle one; the table reads scratch behind the window, as the
    engine leaves it, and block 0 holds NaN: a read that touched a page
    behind the window would show it. Under ``kernel="pallas"`` the round
    of one position is the kernel's, the chunk of 16 the ``lax`` body's."""
    rng = np.random.default_rng(6)
    h, w, r, pages = 2, 128, 96, 16
    pool = np.array(_pool(rng, 40, w))
    pool[0] = np.nan
    starts = [2, window - 1, 90, -1] if t == 1 else [0, 61]
    b = len(starts)
    table = _window_table(rng, starts, t, window, PAGE, pages, 40)
    q = jnp.asarray(rng.normal(size=(b, t, h, w)), jnp.float32)
    got = np.asarray(ls.latent_window_attention(
        q, jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(starts, jnp.int32), window=window, value_dim=r,
        scale=0.1, kernel=kernel))
    flat = pool[table].reshape(b, -1, w)
    for row, first in enumerate(starts):
        if first < 0:
            assert (got[row] == 0).all()
            continue
        for i in range(t):
            p = first + i
            seen = np.arange(max(0, p - window + 1), p + 1)
            want = _attention_by_hand(q[row, i], flat[row, seen], r, 0.1)
            assert np.abs(got[row, i] - want).max() < 1e-5, (row, i)


@pytest.mark.parametrize("t", [1, 4, 8])
@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("window", [5, 24, 128, 513])
def test_the_window_kernel_is_the_lax_body(window, page, t):
    """``latent_window_decode`` (interpreted) against the ``lax`` body: rows
    at the sequence's start, on both sides of the window's edge, far past
    it, with the last query on a page's last position and on the next
    page's first (a window that straddles page edges, a verify window that
    crosses one), idle rows between live ones; the table reads scratch
    behind every window, and the scratch holds NaN."""
    rng = np.random.default_rng(window * page + t)
    h, w, r = 2, 128, 96
    far = 3 * window + 7 * page
    edge = (far // page + 2) * page
    starts = [0, -1, window - 1, window, -1, far, edge - t, edge]
    b = len(starts)
    pages = (edge + t) // page + 2
    blocks = 1 + b * (-(-(window + t) // page) + 2)
    pool = np.array(rng.normal(size=(blocks, page, w)), np.float32)
    pool[0] = np.nan
    table = _window_table(rng, starts, t, window, page, pages, blocks)
    args = (jnp.asarray(rng.normal(size=(b, t, h, w)), jnp.float32),
            jnp.asarray(pool), jnp.asarray(table),
            jnp.asarray(starts, jnp.int32))
    got, want = (np.asarray(ls.latent_window_attention(
        *args, window=window, value_dim=r, scale=0.1, kernel=kernel))
        for kernel in ("pallas", "lax"))
    live = np.asarray(starts) >= 0
    assert np.isfinite(got).all()
    assert np.abs(got[live] - want[live]).max() < 1e-5
    assert (got[~live] == 0).all() and (want[~live] == 0).all()


def test_the_window_kernel_is_the_lax_body_in_bfloat16():
    """bfloat16 operands, float32 scores and sums, probabilities cast to
    the pool's dtype before the value product: both forms read the same
    rounded operands, so they differ by the order of their sums."""
    rng = np.random.default_rng(11)
    window, page, t, h, w, r = 24, 16, 2, 4, 128, 96
    starts = [5, -1, 100, 31]
    pool = jnp.asarray(rng.normal(size=(20, page, w)), jnp.bfloat16)
    table = _window_table(rng, starts, t, window, page, 8, 20)
    q = jnp.asarray(rng.normal(size=(4, t, h, w)), jnp.bfloat16)
    a, b = (np.asarray(ls.latent_window_attention(
        q, pool, jnp.asarray(table), jnp.asarray(starts, jnp.int32),
        window=window, value_dim=r, scale=0.1, kernel=kernel), np.float32)
        for kernel in ("pallas", "lax"))
    assert a.dtype == b.dtype and np.abs(a - b).max() < 2e-2
    assert (a[1] == 0).all()


@pytest.mark.parametrize("t", [16, 128])
def test_mlas_own_read_takes_a_window_at_any_width(t):
    """``mla.mla_attention(window=)`` itself past the decode read's widths,
    as tiles of its prefill read (one of 16 positions, two of 64: the
    second tile's walk begins at its own first query's window), against
    the window read's ``lax`` body; the ``lax`` form of ``ops/mla.py``
    gathers every page and knows no window."""
    rng = np.random.default_rng(12)
    h, w, r, window = 2, 128, 96, 24
    starts = [3, 70]
    pool = np.array(_pool(rng, 60, w))
    pool[0] = np.nan
    table = jnp.asarray(_window_table(rng, starts, t, window, PAGE, 26, 60))
    q = jnp.asarray(rng.normal(size=(2, t, h, w)), jnp.float32)
    start = jnp.asarray(starts, jnp.int32)
    got = mla.mla_attention(q, jnp.asarray(pool), table, start, value_dim=r,
                            scale=0.1, kernel="pallas", window=window)
    want = ls.latent_window_attention(q, jnp.asarray(pool), table, start,
                                      window=window, value_dim=r, scale=0.1)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    with pytest.raises(ValueError, match="latent_window_attention"):
        mla.mla_attention(q, jnp.asarray(pool), table, start, value_dim=r,
                          scale=0.1, kernel="lax", window=window)


def test_the_uncached_forms_are_the_cached_ones():
    """``causal_latent_attention`` over a chunk of its own is the window
    read and the chosen read of a pool that holds the same vectors."""
    rng = np.random.default_rng(7)
    h, w, r, t, k = 2, 128, 96, 24, 8
    lat = jnp.asarray(rng.normal(size=(1, t, w)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, t, h, w)), jnp.float32)
    pool = jnp.concatenate([jnp.zeros((1, PAGE, w)),
                            lat.reshape(t // PAGE, PAGE, w)])
    table = jnp.arange(1, t // PAGE + 1, dtype=jnp.int32)[None]
    start = jnp.zeros((1,), jnp.int32)
    a = ls.causal_latent_attention(q, lat, value_dim=r, scale=0.1, window=5)
    b = ls.latent_window_attention(q, pool, table, start, window=5,
                                   value_dim=r, scale=0.1)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
    scores = jnp.asarray(rng.normal(size=(1, t, t)), jnp.float32)
    a = ls.causal_latent_attention(q, lat, value_dim=r, scale=0.1,
                                   scores=scores, topk=k)
    idx, n = ls.latent_topk(scores, jnp.arange(t, dtype=jnp.int32)[None], k)
    b = ls.latent_chosen_attention(q, pool, table, idx, n, value_dim=r,
                                   scale=0.1)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5


# -- labels, and the lowering at the published widths ---------------------------

def test_the_labels_say_which_form_ran():
    assert ls.index_path(1) == ls.INDEX_DECODE_PATH
    assert ls.index_path(256) == ls.INDEX_PREFILL_PATH
    assert ls.chosen_path("pallas", t=1) == ls.CHOSEN_DECODE_PATH
    assert ls.chosen_path("pallas", t=256) == ls.CHOSEN_PREFILL_PATH
    assert ls.chosen_path("lax", t=1) == ls.chosen_path("lax", t=256) \
        == ls.CHOSEN_LAX_PATH
    assert ls.choice_path("pallas", t=1) == ls.CHOICE_DECODE_PATH
    assert ls.choice_path("pallas", t=256) == ls.CHOICE_PREFILL_PATH
    assert ls.choice_path("lax", t=1) == ls.choice_path("lax", t=256) \
        == ls.CHOICE_LAX_PATH
    assert ls.GATHER_DECODE_PATH == "latent_gather_decode"
    assert ls.window_path("pallas", t=1) == ls.window_path("pallas", t=8) \
        == ls.WINDOW_DECODE_PATH == "latent_window_decode"
    assert ls.window_path("pallas", t=256) is None
    assert ls.window_path("lax", t=1) is None


@pytest.mark.parametrize("batch,t", [(16, 1), (1, 256)])
def test_the_kernels_lower_for_a_tpu_at_published_widths(batch, t):
    """No device and no compile: the index at 64 heads of 128, the choice
    of 2,048 among 50,176 and the read of the chosen at 128 heads over 640
    lanes, through a table of 784 pages of 64 (50,176 positions): the
    decode round of 16 slots and the prefill chunk of 256."""
    ls.lower_for_tpu(batch=batch, t=t, heads=128, index_heads=64,
                     index_dim=128, width=640, value_dim=512, topk=2048,
                     n_blocks=12545, page_size=64, pages_per_seq=784,
                     dtype=jnp.bfloat16)


@pytest.mark.parametrize("window,t,page,block", [
    (128, 1, 64, 3), (513, 1, 64, 5), (513, 8, 64, 5), (513, 1, 16, 17),
    (5, 1, 8, 2), (24, 8, 8, 5)])
def test_a_windows_block_is_no_wider_than_it_can_fill(window, t, page,
                                                      block):
    """``window + t - 1`` positions touch at most one page more than they
    fill; they go in the fewest blocks of 512 positions, of equal size:
    one block of three pages of 64 under a window of 128, two of five under
    513 (nine pages: eight and one would score 1,024 columns for 513)."""
    assert mla.window_block_pages(window, t, page) == block


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("shape", [
    dict(batch=16, heads=64, width=1152, value_dim=1024, window=513,
         n_blocks=225, pages_per_seq=784),
    dict(batch=64, heads=80, width=640, value_dim=512, window=128,
         n_blocks=449, pages_per_seq=192)], ids=["dots3", "motif"])
def test_the_window_read_lowers_for_a_tpu_at_both_cells_shapes(shape, t):
    """No device and no compile: ``latent_window_decode`` at
    ``indexed-steady``'s decode round (16 slots, 64 heads over 1,152 lanes
    under a window of 513, a table of 784 pages of 64) and at
    ``hyper-steady``'s (64 slots, 80 heads over 640 lanes under 128, a
    pool of 449 pages), and at a verify window of 5; under its own name,
    not the full layers' read's."""
    text = ls.lower_window_for_tpu(t=t, page_size=64, dtype=jnp.bfloat16,
                                   **shape).as_text()
    assert 'kernel_name = "latent_window_decode"' in text
    assert "mla_paged" not in text


def _mosaic_modules(lowered_text):
    """The Mosaic modules of a text lowered for a TPU, each printed with
    no locations: a custom call carries its module as MLIR bytecode, whose
    debug information holds the line every operation was traced from."""
    import base64
    import json
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    out = []
    for config in re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"',
                             lowered_text):
        config = json.loads(config.replace("\\22", '"'))
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            out.append(ir.Module.parse(base64.b64decode(
                config["custom_call_config"]["body"])).operation.get_asm(
                    enable_debug_info=False))
    return out


@pytest.mark.parametrize("shape,digest", [
    ((4, 1, 16, 8), "4154c920b57e90dbbf4e4db1b38f04f26ac198f18f76b8fa6462f8"
                     "0be62a9586"),
    ((1, 128, 16, 24), "f5f8c4c43eee93791f7fc14fbf4d9a5f7ff8102c82965a815e01"
                       "ea63c1b929ad")], ids=["decode", "prefill"])
def test_no_window_lowers_to_what_it_lowered_to_before(shape, digest):
    """``mla_attention(window=None)`` is the program the kernel was before
    it knew of windows: the window is a Python branch, not a traced one.
    The digests are of the Mosaic module lowered for a TPU from PR 65's
    ``ops/mla.py`` at these shapes (16 heads over 640 lanes, 33 pages of
    16), printed with no locations."""
    import hashlib

    b, t, h, pages = shape
    sds = jax.ShapeDtypeStruct
    text = jax.jit(lambda q, pool, table, start: mla.mla_attention(
        q, pool, table, start, value_dim=512, scale=0.125, kernel="pallas",
        interpret=False)).trace(
        sds((b, t, h, 640), jnp.bfloat16), sds((33, 16, 640), jnp.bfloat16),
        sds((b, pages), jnp.int32), sds((b,), jnp.int32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    module, = _mosaic_modules(text)
    assert hashlib.sha256(module.encode()).hexdigest() == digest

"""``ops/sparse_attention.py``: the compressed keys a chunk completes, the
selector and the two reads of the chosen pages, each Pallas kernel
(interpreted: ``tests/conftest.py``) against its lax form and both against a
plain computation over the unpaged keys; and ``ops/mamba2.py``'s update with
a ``B`` and a ``C`` a head, which the lightning layers run. Small shapes:
heads of 16, pages of 16, compressed keys of 8 positions at stride 4."""

import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import mamba2
from lzy_tpu.ops import sparse_attention as sp

SPEC = sp.SparseSpec(kernel_size=8, kernel_stride=4, block_size=16, topk=3,
                     init_blocks=1, window_size=32)
KV, G, D, PAGES = 2, 2, 16, 12


def _row(seed, length, *, table_offset=1):
    """One row's keys and values ``[length, KV, D]`` written into pools
    through a shuffled page table, with their compressed keys."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((length, KV, D)).astype(np.float32)
    v = rng.standard_normal((length, KV, D)).astype(np.float32)
    n_blocks = 2 * PAGES + 2
    table = (rng.permutation(n_blocks - 1)[:PAGES] + table_offset).astype(
        np.int32)[None]
    kv_shape, ck_shape = sp.pool_shapes(n_blocks, KV, D, SPEC)
    pos = jnp.arange(length, dtype=jnp.int32)[None]
    pool_k = sp.scatter_kv(jnp.zeros(kv_shape), jnp.asarray(table), pos,
                           jnp.asarray(k)[None])
    pool_v = sp.scatter_kv(jnp.zeros(kv_shape), jnp.asarray(table), pos,
                           jnp.asarray(v)[None])
    pool_ck = sp.compress_keys(
        pool_k, jnp.zeros(ck_shape), jnp.asarray(table),
        jnp.zeros((1,), jnp.int32), jnp.asarray([length]), t=length,
        spec=SPEC)
    return k, v, jnp.asarray(table), pool_k, pool_v, pool_ck


def _means(k):
    n = (len(k) - SPEC.kernel_size) // SPEC.kernel_stride + 1
    return np.stack([k[4 * j:4 * j + 8].mean(axis=0) for j in range(n)])


def _plain_choice(q, k, pos):
    """The published rule for one query ``q`` [KV, G, D] at ``pos`` over the
    keys ``k`` [L, KV, D], by loops: ``[KV, blocks]`` bool."""
    ck = _means(k[:pos + 1]) if pos + 1 >= SPEC.kernel_size \
        else np.zeros((0, KV, D), np.float32)
    cur = pos // SPEC.block_size
    out = np.zeros((KV, PAGES), bool)
    for g in range(KV):
        p = np.zeros(len(ck))
        for h in range(G):
            s = ck[:, g] @ q[g, h] * D ** -0.5
            e = np.exp(s - s.max()) if len(s) else s
            p += e / e.sum() if len(s) else 0
        score = np.zeros(cur + 1)
        for b in range(cur + 1):
            over = [j for j in range(len(ck))
                    if 4 * j + 7 >= 16 * b and 4 * j <= 16 * b + 15]
            score[b] = max([p[j] for j in over], default=0.0)
        forced = [b for b in range(cur + 1) if b < 1 or cur - b < 2]
        rest = sorted((b for b in range(cur + 1) if b not in forced),
                      key=lambda b: (-score[b], b))[:SPEC.topk]
        out[g, forced + rest] = True
    return out


def _plain_attention(q, k, v, pos, chosen):
    """``q`` [KV, G, D] at ``pos`` over the chosen blocks' keys ``<= pos``."""
    keep = np.repeat(chosen, SPEC.block_size, axis=-1)[:, :pos + 1] \
        & (np.arange(pos + 1) <= pos)
    out = np.zeros((KV, G, D), np.float32)
    for g in range(KV):
        s = np.einsum("hd,ld->hl", q[g], k[:pos + 1, g]) * D ** -0.5
        s = np.where(keep[g][None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[g] = (p / p.sum(-1, keepdims=True)) @ v[:pos + 1, g]
    return out


def test_a_spec_that_cannot_be_served_is_refused():
    with pytest.raises(ValueError, match="whole strides"):
        sp.SparseSpec(block_size=60).check()
    with pytest.raises(ValueError, match="next block"):
        sp.SparseSpec(kernel_size=128).check()
    spec = sp.SparseSpec().check()
    assert (spec.per_block, spec.window_blocks, spec.straddlers,
            spec.most_read) == (4, 32, 3, 97)
    assert (SPEC.per_block, SPEC.window_blocks, SPEC.straddlers,
            SPEC.most_read) == (4, 2, 3, 6)


@pytest.mark.parametrize("length", [7, 8, 19, 20, 100, 163])
def test_compressed_keys_are_the_means_of_their_windows(length):
    k, _, table, _, _, pool_ck = _row(length, length)
    want = _means(k) if length >= 8 else np.zeros((0, KV, D))
    got = np.asarray(pool_ck[table[0]]).reshape(-1, KV, D)
    assert np.abs(got[:len(want)] - want).max(initial=0.0) < 1e-6
    # nothing past the last complete window was written on the row's pages
    assert np.abs(got[len(want):]).max() == 0


@pytest.mark.parametrize("width", [1, 8, 12, 32])
def test_a_chunk_edge_and_a_page_edge_inside_a_compression_kernel(width):
    """Written chunk by chunk (a kernel of 8 at stride 4 straddles every
    chunk edge at a width of 12 and every page edge at any), with a padded
    tail that completes nothing: the same compressed keys as at once."""
    length = 100
    k, _, table, pool_k, _, want = _row(3, length)
    ck = jnp.zeros_like(want)
    for start in range(0, length, width):
        real = min(width, length - start)
        ck = sp.compress_keys(
            pool_k, ck, table, jnp.asarray([start]), jnp.asarray([real]),
            t=width, spec=SPEC)
    live = np.asarray(table[0])
    assert np.abs(np.asarray(ck)[live] - np.asarray(want)[live]).max() < 1e-6


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("pos", [5, 40, 111, 190])
def test_the_selector_chooses_what_the_published_rule_does(kernel, pos):
    """One position a row, three rows: one that selects, one that does not
    (everything visible) and an idle one."""
    k, _, table, _, _, pool_ck = _row(pos, 191)
    q = np.random.default_rng(pos).standard_normal(
        (3, 1, KV * G, D)).astype(np.float32) * 2
    tables = jnp.concatenate([table, table, jnp.zeros_like(table)])
    positions = jnp.asarray([[pos], [pos], [0]], jnp.int32)
    chosen = np.asarray(sp.select_blocks(
        jnp.asarray(q), pool_ck, tables, positions,
        jnp.asarray([True, False, False]), spec=SPEC, kernel=kernel))
    assert chosen.shape == (3, KV, 1, PAGES)
    want = _plain_choice(q[0, 0].reshape(KV, G, D), k, pos)
    assert (chosen[0, :, 0] == want).all()
    cur = pos // 16
    assert want.sum(-1).max() <= min(cur + 1, SPEC.most_read)
    assert (chosen[1, :, 0] == (np.arange(PAGES) <= cur)).all()


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("start,t", [(0, 32), (96, 32), (150, 8)])
def test_a_chunks_queries_each_choose_their_own(kernel, start, t):
    k, _, table, _, _, pool_ck = _row(start + t, start + t)
    q = np.random.default_rng(start).standard_normal(
        (1, t, KV * G, D)).astype(np.float32) * 2
    positions = start + jnp.arange(t, dtype=jnp.int32)[None]
    chosen = np.asarray(sp.select_blocks(
        jnp.asarray(q), pool_ck, table, positions, jnp.asarray([True]),
        spec=SPEC, kernel=kernel))
    for i in range(t):
        want = _plain_choice(q[0, i].reshape(KV, G, D), k, start + i)
        assert (chosen[0, :, i] == want).all(), i
    if start:
        # the choice is a query's own: the chunk's queries do not agree
        assert (chosen[0, :, 0] != chosen[0, :, -1]).any()


def test_fewer_candidates_than_topk_are_all_read_and_a_tie_goes_low():
    scores = jnp.zeros((1, 1, 1, PAGES)).at[0, 0, 0, 5].set(0.5)
    at = lambda pos: np.asarray(sp.choose(  # noqa: E731
        scores, jnp.asarray([[pos]]), SPEC))[0, 0, 0]
    # 5 blocks seen: the first and the last two forced, both others read
    assert at(16 * 4 + 3).tolist() == [True] * 5 + [False] * 7
    # 9 seen: blocks 1-6 are candidates, 5 is best, the tie at 0 goes to 1, 2
    assert np.flatnonzero(at(16 * 8)).tolist() == [0, 1, 2, 5, 7, 8]


def _sorted_table(chosen, page_table):
    """The table as a stable sort and a gather make it (what
    ``sparse_decode_attention`` ran before :func:`sp.pack_chosen`): the
    chosen pages' ids in position order, zeros behind their count."""
    order = np.argsort(~chosen, axis=-1, kind="stable")
    count = chosen.sum(axis=-1).astype(np.int32)
    table = np.take_along_axis(
        np.broadcast_to(page_table[:, None, :], chosen.shape), order,
        axis=-1)
    return np.where(np.arange(chosen.shape[-1]) < count[..., None], table,
                    0), count


def _mask(case):
    """``chosen`` ``[B, KV, pages]`` of a named case; the two seeded ones
    are ``sparse-steady``'s shape, 16 slots of 528 pages."""
    b, pages = (16, 528) if case.startswith("seeded") else (3, 41)
    chosen = np.zeros((b, KV, pages), bool)
    if case == "everything":
        chosen[:] = True
    elif case == "the_first_page":
        chosen[..., 0] = True
    elif case == "the_last_page":
        chosen[..., -1] = True
    elif case == "alternating":
        chosen[..., ::2] = True
        chosen[1] = ~chosen[1]
    elif case == "a_group_its_own":
        chosen[:, 0, 3:9] = True
        chosen[:, 1, [0, 7, 40]] = True
    elif case == "an_idle_row_beside_a_live_one":
        chosen[1, :, [0, 5, 6, 30]] = True
    elif case == "a_dense_row_beside_a_selecting_one":
        chosen[0, :, :29] = True                  # everything visible
        chosen[2, :, [0, 4, 9, 17, 18, 19]] = True
    elif case.startswith("seeded"):
        rng = np.random.default_rng(int(case[-1]))
        chosen = rng.random((b, KV, pages)) < rng.random((b, 1, 1))
    else:
        assert case == "nothing"
    return chosen


@pytest.mark.parametrize("case", [
    "nothing", "everything", "the_first_page", "the_last_page",
    "alternating", "a_group_its_own", "an_idle_row_beside_a_live_one",
    "a_dense_row_beside_a_selecting_one", "seeded_0", "seeded_1"])
def test_the_running_count_packs_the_table_a_stable_sort_would(case):
    chosen = _mask(case)
    b, _, pages = chosen.shape
    # ids as a pool's: shuffled, none of them the scratch block 0
    page_table = (np.random.default_rng(5).permutation(b * pages) + 1
                  ).astype(np.int32).reshape(b, pages)
    want_table, want_count = _sorted_table(chosen, page_table)
    table, count = sp.pack_chosen(jnp.asarray(chosen),
                                  jnp.asarray(page_table))
    assert table.dtype == jnp.int32 and count.dtype == jnp.int32
    assert (np.asarray(count) == want_count).all()
    assert (np.asarray(table) == want_table).all()


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_the_decode_read_reads_the_chosen_pages_and_no_other(kernel):
    pos = 175
    k, v, table, pool_k, pool_v, pool_ck = _row(9, pos + 1)
    rng = np.random.default_rng(1)
    q = rng.standard_normal((3, 1, KV * G, D)).astype(np.float32)
    tables = jnp.concatenate([table, jnp.zeros_like(table), table])
    positions = jnp.asarray([[pos], [0], [pos]], jnp.int32)
    live = jnp.asarray([True, False, True])
    chosen = sp.select_blocks(
        jnp.asarray(q), pool_ck, tables, positions,
        jnp.asarray([True, False, False]), spec=SPEC, kernel=kernel)
    # what was not chosen is poisoned: a read that touches it shows
    mask = np.asarray(chosen)[0, :, 0]                     # [KV, pages]
    unread = np.asarray(table[0])[~mask.any(axis=0)]
    poisoned_k = pool_k.at[unread].set(jnp.nan)
    out = np.asarray(sp.sparse_decode_attention(
        jnp.asarray(q), poisoned_k, pool_v, tables, positions,
        chosen[:, :, 0], live, kernel=kernel))
    assert out.shape == (3, 1, KV, G, D)
    want = _plain_attention(q[0, 0].reshape(KV, G, D), k, v, pos, mask)
    assert np.abs(out[0, 0] - want).max() < 1e-5
    # the row that does not select reads everything visible
    dense = np.asarray(sp.sparse_decode_attention(
        jnp.asarray(q), pool_k, pool_v, tables, positions, chosen[:, :, 0],
        live, kernel=kernel))[2, 0]
    everything = np.ones((KV, PAGES), bool)
    assert np.abs(dense - _plain_attention(
        q[2, 0].reshape(KV, G, D), k, v, pos, everything)).max() < 1e-5
    if kernel == "pallas":
        assert np.abs(out[1]).max() == 0           # the idle row: exactly 0


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
@pytest.mark.parametrize("start,t", [(96, 32), (144, 8), (0, 64)])
def test_the_prefill_read_masks_a_page_per_query(kernel, start, t):
    k, v, table, pool_k, pool_v, pool_ck = _row(start + 5, start + t)
    rng = np.random.default_rng(2)
    q = rng.standard_normal((1, t, KV * G, D)).astype(np.float32)
    positions = start + jnp.arange(t, dtype=jnp.int32)[None]
    chosen = sp.select_blocks(
        jnp.asarray(q) * 2, pool_ck, table, positions, jnp.asarray([True]),
        spec=SPEC, kernel="lax")
    mask = np.asarray(chosen)[0]                           # [KV, T, pages]
    # a page no query of either group chose is poisoned
    unread = np.asarray(table[0])[~mask.any(axis=(0, 1))]
    out = np.asarray(sp.sparse_prefill_attention(
        jnp.asarray(q), pool_k.at[unread].set(jnp.nan), pool_v, table,
        jnp.asarray([start]), chosen, kernel=kernel))
    assert out.shape == (1, t, KV, G, D)
    for i in range(t):
        want = _plain_attention(q[0, i].reshape(KV, G, D), k, v, start + i,
                                mask[:, i])
        assert np.abs(out[0, i] - want).max() < 1e-5, i


def test_the_reads_refuse_a_kernel_they_do_not_have():
    with pytest.raises(ValueError, match="unknown sparse-attention kernel"):
        sp.select_blocks(None, None, jnp.zeros((1, 2)), None, None,
                         spec=SPEC, kernel="triton")
    assert sp.read_path("pallas", t=1) == sp.DECODE_PATH
    assert sp.read_path("pallas", t=256) == sp.PREFILL_PATH
    assert sp.read_path("lax", t=1) == "lax"


def test_the_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: the selector and both reads at 32 / 2 heads
    of 128 over a table 528 wide."""
    for batch, t in ((16, 1), (1, 256)):
        sp.lower_for_tpu(batch=batch, t=t, n_heads=32, n_kv_heads=2,
                         head_dim=128, n_blocks=3855, pages_per_seq=528,
                         dtype=jnp.bfloat16, spec=sp.SparseSpec())


# -- ops/mamba2.py with a B and a C a head ------------------------------------

def test_the_state_update_takes_a_b_and_a_c_a_head():
    """The lightning recurrence on the shared kernel: 8 heads, each with its
    own ``B`` and ``C``; an idle row's state stays bit for bit; the name in a
    device trace is the caller's."""
    rng = np.random.default_rng(0)
    b, h, p, n = 3, 8, 16, 16
    state = rng.standard_normal((b, h, p, n)).astype(np.float32)
    x = rng.standard_normal((b, h, p)).astype(np.float32)
    bm = rng.standard_normal((b, h, n)).astype(np.float32)
    cm = rng.standard_normal((b, h, n)).astype(np.float32)
    a = -np.exp2(-8.0 * np.arange(1, h + 1) / h).astype(np.float32)
    dt = np.ones((b, h), np.float32)
    dt[1] = 0.0
    y, new = mamba2.ssm_state_update(
        jnp.asarray(state), jnp.asarray(x), jnp.asarray(dt), jnp.asarray(a),
        jnp.asarray(bm), jnp.asarray(cm))
    want = np.exp(a)[None, :, None, None] * state \
        + x[..., None] * bm[:, :, None, :]
    assert np.abs(np.asarray(new)[[0, 2]] - want[[0, 2]]).max() < 1e-6
    assert (np.asarray(new)[1] == state[1]).all()
    assert np.abs(np.asarray(y)[[0, 2]] - np.einsum(
        "bhpn,bhn->bhp", want, cm)[[0, 2]]).max() < 1e-5
    assert np.abs(np.asarray(y)[1]).max() == 0
    # the chunk scan from the same state, one position: the same numbers
    ys, scanned = mamba2.ssd_chunk_scan(
        jnp.asarray(x)[:, None], jnp.asarray(dt)[:, None], jnp.asarray(a),
        jnp.asarray(bm)[:, None], jnp.asarray(cm)[:, None],
        jnp.asarray(state))
    assert np.abs(np.asarray(scanned) - np.asarray(new)).max() < 1e-5
    assert np.abs(np.asarray(ys)[[0, 2], 0]
                  - np.asarray(y)[[0, 2]]).max() < 1e-4
    with pytest.raises(ValueError, match="multiple of 8"):
        mamba2.ssm_state_update(
            jnp.zeros((1, 4, 8, 8)), jnp.zeros((1, 4, 8)), jnp.zeros((1, 4)),
            jnp.zeros((4,)), jnp.zeros((1, 4, 8)), jnp.zeros((1, 4, 8)))
    mamba2.lower_update_for_tpu(batch=16, heads=32, head_dim=128,
                                state_size=128, groups=32)

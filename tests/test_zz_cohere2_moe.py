"""Command A+ (``cohere2_moe``) on the serving path: window and full attention
layers through two kinds of page against the benchmark's plain float32
reference (logits, not tokens), across the window's edge in prefill and in
decode; the expert shares; the precision guards; the two reads against their
``lax`` oracles; the allocator of pages that go back behind the window; and
the model through ``PagedInferenceEngine`` with every mechanism it is served
by or refused by. Tiny widths (a window of 24 over pages of 8), seeded
weights, CPU, Pallas kernels interpreted (``tests/conftest.py``).

The file's name sorts last on purpose (as ``test_zz_deepseek_v3.py``'s): the
tier runs ``--dist loadfile``, which hands files to workers in their order;
run last it shifts no earlier file, so ``tests/test_load.py``'s wall-clock
smoke test keeps the neighbours it had."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import cohere2_moe as ref
from lzy_tpu.models import cohere2_moe as c2
from lzy_tpu.models import experts, serving
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import WindowLeavesUnsupported
from lzy_tpu.serving.kv_cache import NoFreeBlocks, WindowPages
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

pa = importlib.import_module("lzy_tpu.ops.paged_attention")

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4
PAGE = 8


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths; at the tiny ones it would shrink every layer's output to nothing
    and a wrong expert or a lost page would hide under the tolerance.
    Rescale each matrix to fan_in ** -0.5."""
    def fix(path, leaf):
        if path[-1].key in ("kernel", "experts_gate", "experts_up",
                            "experts_down", "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if path[-1].key == "kernel_t":          # stored [out, in]
            return leaf * (leaf.shape[-1] ** -0.5 / 0.02)
        if path[-1].key == "embed_tokens":
            return leaf / 0.02
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = c2.Cohere2MoeConfig.tiny()
    return cfg, _unit_scale(c2.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    """Uncached, 60 positions: past the window of 24 in the three window
    layers."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    got, seen = c2.Cohere2Moe(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.reference_logits(params, toks, jnp.arange(64), cfg)
    assert np.abs(got[0] - want).max() < TOL
    assert seen["intermediates"]["layer_1_moe"]["chosen"][0].shape \
        == (64, cfg.top_k)
    total = np.asarray(sum(jax.tree_util.tree_leaves(seen["stats"])))
    assert total.shape == (len(c2.Cohere2Moe.STATS),)
    # uncached, the attention sows nothing; the four expert layers do
    assert list(total[[0, 3, 4, 5, 6]]) == [
        64 * cfg.top_k * 4, cfg.n_held * 4, 0, 0, 0]


def test_the_window_is_seen_by_the_reference(tiny):
    """A reference that ignored the window would pass the test above only
    if the program ignored it too: cut to a window wider than the sequence
    the logits move."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    wide = dataclasses.replace(cfg, window=64)
    a = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), cfg))
    b = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), wide))
    assert np.abs(a[:24] - b[:24]).max() < TOL
    assert np.abs(a[40:] - b[40:]).max() > 100 * TOL


def test_the_references_rotary_is_the_programs():
    from lzy_tpu.models.llama import _rope

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(20, 29)
    assert np.abs(np.asarray(_rope(x, pos[None], 50000.0)[0])
                  - np.asarray(ref.rotary(x[0], pos, 50000.0))).max() < 1e-6


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        tiny, kernel):
    """Logits, not tokens. Chunks of 16: the second crosses the window's
    edge (24), the third is padded; then one position at a time from 41 to
    59, over page boundaries, with the pages behind the window returned
    before every program as the engine returns them (``WindowPages``): the
    window table reads scratch there."""
    cfg, params = tiny
    pages = cfg.max_seq_len // PAGE
    model = cfg.paged_model(page_size=PAGE, kv_pages=12, kernel=kernel,
                            kv_quant=None, window_pages=9)
    toks = _tokens(3, 60, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks + [0] * 4]), jnp.arange(60), cfg))
    full = np.zeros((1, pages), np.int32)
    full[0, :8] = [5, 2, 7, 1, 9, 3, 11, 4]
    win = WindowPages(9, PAGE, cfg.window, pages, 16)
    row = win.row()
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32),
                               page_table=jnp.asarray(full),
                               window_table=jnp.asarray(full)))["cache"])

    def run(cache, start, chunk, real):
        win.cover(row, start - cfg.window, start + real)
        assert row.held <= win.bound
        pad = chunk + [0] * (16 - len(chunk)) if len(chunk) > 1 else chunk
        logits, upd = model.apply(
            {"params": params, "cache": cache}, jnp.asarray([pad]),
            page_table=jnp.asarray(full),
            window_table=jnp.asarray(row.table[None]),
            valid_len=jnp.asarray([real], jnp.int32),
            mutable=["cache", "stats"])
        cache = upd["cache"]
        if len(pad) != real:            # the engine rewinds a padded index
            cache = jax.tree_util.tree_map_with_path(
                lambda p, leaf: leaf - (len(pad) - real)
                if p[-1].key == "index" else leaf, cache)
        counts = np.asarray(sum(jax.tree_util.tree_leaves(upd["stats"])))
        return cache, np.asarray(logits[0, :real]), counts

    got = []
    cache, out, _ = run(cache, 0, toks[:16], 16)
    got.append(out)
    cache, out, _ = run(cache, 16, toks[16:32], 16)
    got.append(out)
    cache, out, counts = run(cache, 32, toks[32:41], 9)   # padded to 16
    got.append(out)
    # the last real query sits at 40: it reads 41 keys in the full layer
    # and the window's 24 in each of the three window layers
    assert list(counts[-3:]) == [24 * 3, 41, 4]
    for at in range(41, 60):
        cache, out, counts = run(cache, at, [toks[at]], 1)
        got.append(out)
        assert list(counts[-3:]) == [24 * 3, at + 1, 4]
    assert np.abs(np.concatenate(got) - want).max() < TOL
    # pages 0-3 (positions 0-31) lie wholly behind 59 - 24 and went back
    assert win.released == 4 and list(row.table[:4]) == [0] * 4
    assert row.held == 4 and win.live() == 4


def test_the_shares_add_up(tiny):
    """Eight chips hold 2 of the 16 routed experts each (the deployment's
    eight, 16 of 128). What each computes for the layer, with attention and
    the shared mean (which every chip computes alike) counted once, adds up
    to the uncut layer: in the program, and to the reference's uncut
    layer."""
    cfg, params = tiny
    layer = params["layer_1_moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 24, cfg.d_model)).astype(np.float32))
    big = ("experts_gate", "experts_up", "experts_down")

    def cut(lo, hi):
        c = dataclasses.replace(cfg, experts_held=(lo, hi))
        return c, dict(layer, **{n: layer[n][lo:hi] for n in big})

    def program(lo, hi):
        c, w = cut(lo, hi)
        out, _ = experts.GatedExperts(c, other_stats=3).apply(
            {"params": w}, u, mutable=["stats"])
        return np.asarray(out[0])

    def reference(lo, hi):
        c, w = cut(lo, hi)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.routed_experts(u[0], w, c)
                              + ref.shared_mean(u[0], w, c))

    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared_mean(u[0], layer, cfg))
        attn = np.asarray(ref._attention(
            u[0], params["layer_1"], cfg, jnp.float32, True))
    uncut = reference(0, 16)
    for layer_fn in (program, reference):
        shares = [layer_fn(lo, lo + 2) for lo in range(0, 16, 2)]
        # the whole layer's addition to the residual, attention once
        summed = sum(s - shared for s in shares) + shared + attn
        assert np.abs(summed - (uncut + attn)).max() < TOL
        # a share alone is not the layer: the cut is real
        assert np.abs(shares[0] - uncut).max() > 10 * TOL
    assert np.abs(program(4, 6) - reference(4, 6)).max() < TOL


def test_the_shared_experts_are_averaged_not_summed(tiny):
    """The program's one gated MLP of ``n_shared x width`` times
    ``1 / n_shared`` is the mean of the reference's separate experts; their
    sum is twice that here (two shared experts)."""
    cfg, params = tiny
    layer = params["layer_0_moe"]
    u = jnp.asarray(np.random.default_rng(6).normal(
        size=(1, 8, cfg.d_model)).astype(np.float32))
    none = dataclasses.replace(cfg, top_k=1, experts_held=(0, 1))
    zeroed = dict(layer, experts_down=jnp.zeros_like(
        layer["experts_down"][:1]), experts_gate=layer["experts_gate"][:1],
        experts_up=layer["experts_up"][:1])
    out, _ = experts.GatedExperts(none, other_stats=3).apply(
        {"params": zeroed}, u, mutable=["stats"])
    with jax.default_matmul_precision("highest"):
        mean = np.asarray(ref.shared_mean(u[0], layer, cfg))
    assert np.abs(np.asarray(out[0]) - mean).max() < TOL
    assert np.abs(mean).max() > 100 * TOL


# -- the precision guards -----------------------------------------------------

def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_router_tells_apart_scores_that_tie_in_bfloat16():
    """Two experts whose sigmoid scores differ by 1e-4 at the edge of the
    choice: float32 scores pick the larger; scores rounded to bfloat16 tie.
    The activations' dtype is bfloat16 here, as it is served; the router
    has no correction bias (no such parameter)."""
    cfg = dataclasses.replace(c2.Cohere2MoeConfig.tiny(), dtype=jnp.bfloat16)
    layer = experts.GatedExperts(cfg, other_stats=3)
    u = jnp.zeros((1, 1, cfg.d_model), jnp.float32).at[0, 0, 0].set(1.0)
    params = dict(layer.init(jax.random.PRNGKey(0), u)["params"])
    assert "router_bias" not in params
    logits = np.linspace(-3.0, -2.0, cfg.n_routed_experts).astype(np.float32)
    logits[[0, 1, 2]] = 2.0, 1.5, 1.0          # three clear choices
    logits[3], logits[4] = 0.1000, 0.1004      # the fourth: expert 4, by 1e-4
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    assert _bf16(scores[3]) == _bf16(scores[4])
    params["router"] = jnp.zeros_like(params["router"]).at[0].set(logits)
    _, seen = layer.apply({"params": params}, u.astype(cfg.dtype),
                          mutable=["intermediates", "stats"])
    chosen = set(np.asarray(seen["intermediates"]["chosen"][0]).ravel())
    assert chosen == {0, 1, 2, 4}


def test_a_bfloat16_reference_fails_the_tolerance(tiny):
    """The control (the reference wholly in bfloat16) is not within the
    tolerance the program is held to."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(4, 64, cfg.vocab_size)])
    exact = np.asarray(ref.reference_logits(params, toks, jnp.arange(64),
                                            cfg))
    control = np.asarray(ref.reference_logits(params, toks, jnp.arange(64),
                                              cfg, jnp.bfloat16))
    assert np.abs(control - exact).max() > 20 * TOL


# -- the two reads against their lax oracles, a group of 16 -------------------

def _float32_attention(q, kp, vp, table, pos, window):
    b, t, h, d = q.shape
    n, page, width = kp.shape
    kv = width // d
    keys = np.asarray(kp, np.float32)[np.asarray(table)].reshape(b, -1, kv, d)
    vals = np.asarray(vp, np.float32)[np.asarray(table)].reshape(b, -1, kv, d)
    qg = np.asarray(q, np.float32).reshape(b, t, kv, h // kv, d)
    s = np.einsum("btkgd,blkd->bkgtl", qg, keys) * d ** -0.5
    at = np.arange(keys.shape[1])[None, None, None, None, :]
    p = np.asarray(pos)[:, None, None, :, None]
    keep = at <= p
    if window is not None:
        keep &= at > p - window
    s = np.where(keep, s, -1e30)
    s = np.exp(s - s.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    return np.einsum("bkgtl,blkd->btkgd", s, vals)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [1, 16])
def test_the_reads_are_within_tolerance_of_float32_attention(window, dtype,
                                                             t):
    """A decode round (three rows, one idle at position 0 of a zeroed
    table) and a chunk of 16 (batch 1, starting past the window), kernel
    and oracle alike, 32 heads over 2; the table reads scratch behind the
    window where there is one."""
    rng = np.random.default_rng(7)
    kv, g, d, blocks, pages = 2, 16, 16, 40, 12
    dt = jnp.dtype(dtype)
    kp = jnp.asarray(rng.normal(size=(blocks, PAGE, kv * d)), dt)
    vp = jnp.asarray(rng.normal(size=(blocks, PAGE, kv * d)), dt)
    starts = [5, 60, 0] if t == 1 else [44]
    table = np.zeros((len(starts), pages), np.int32)
    for r, start in enumerate(starts):
        if t == 1 and r == 2:
            continue                     # the idle slot
        hi = (start + t - 1) // PAGE + 1
        lo = 0 if window is None else max(0, start - window + 1) // PAGE
        table[r, lo:hi] = rng.choice(np.arange(1, blocks), hi - lo, False)
    q = jnp.asarray(rng.normal(size=(len(starts), t, kv * g, d)), dt)
    start = jnp.asarray(starts, jnp.int32)
    pos = np.asarray(starts)[:, None] + np.arange(t)
    want = _float32_attention(q, kp, vp, table, pos, window)
    live = slice(0, 2) if t == 1 else slice(None)
    for kernel in ("lax", "pallas"):
        got = pa.paged_group_attention(q, kp, vp, jnp.asarray(table), start,
                                       window=window, kernel=kernel)
        err = np.abs(np.asarray(got, np.float32)[live] - want[live]).max()
        assert err / max(1.0, np.abs(want).max()) < pa.TOLERANCE[dtype], (
            kernel, err)


@pytest.mark.parametrize("at", [23, 24])
def test_the_group_decode_read_at_the_windows_edge(at):
    """A decode round whose rows stand at the window's last whole position
    (23: nothing is hidden yet), at the first that hides a key (24) and a
    page further, where the first page has gone back and the table reads
    scratch there: kernel and oracle against float32 attention."""
    window = 24
    rng = np.random.default_rng(8)
    kv, g, d, blocks, pages = 2, 16, 16, 40, 12
    kp = jnp.asarray(rng.normal(size=(blocks, PAGE, kv * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(blocks, PAGE, kv * d)), jnp.float32)
    starts = [at, at + PAGE, at + 3 * PAGE]
    table = np.zeros((3, pages), np.int32)
    for r, p in enumerate(starts):
        lo = max(0, p - window + 1) // PAGE
        table[r, lo:p // PAGE + 1] = rng.choice(
            np.arange(1, blocks), p // PAGE + 1 - lo, False)
    q = jnp.asarray(rng.normal(size=(3, 1, kv * g, d)), jnp.float32)
    pos = np.asarray(starts)[:, None]
    want = _float32_attention(q, kp, vp, table, pos, window)
    for kernel in ("lax", "pallas"):
        got = pa.paged_group_attention(
            q, kp, vp, jnp.asarray(table), jnp.asarray(starts, jnp.int32),
            window=window, kernel=kernel)
        assert np.abs(np.asarray(got) - want).max() < 1e-5, kernel


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: both reads with the window and without,
    over pools of 512 pages of 32 a slot at 32 slots, 128 heads over 8 of
    128; the gated experts at 4096 x 4096 (tiles of 4096 x 256) at 32 and
    at 256 rows."""
    cfg = dataclasses.replace(c2.Cohere2MoeConfig(), experts_held=(0, 16))
    cfg.check_kernels(slots=32, kv_blocks=16385, page_size=32,
                      pages_per_seq=512, window_blocks=4385)
    assert gexp._tile(4096, 4096, 2) == 256
    with pytest.raises(c2.WindowPoolUnsupported, match="kv_quant"):
        cfg.check_kernels(slots=32, kv_quant="int8")


# -- the seam -----------------------------------------------------------------

def _published():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "command-a-plus-serve-l4-ep8.json")
    with open(path) as f:
        doc = json.load(f)
    doc = dict(doc, **doc["published"])
    del doc["router_width"], doc["experts_held_from"]
    return doc


def test_the_published_keys_give_the_name_its_count():
    """218 B parameters, 25 B of them active a token (shapes only), from
    the benchmark's configuration file with its cuts undone."""
    cfg = c2.Cohere2MoeConfig.from_published(_published())
    assert cfg == c2.Cohere2MoeConfig()
    shapes = jax.eval_shape(lambda: c2.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 1e9 - 218.2) < 0.5
    active = total - 32 * (128 - 8) * 3 * 4096 * 4096
    assert abs(active / 1e9 - 24.9) < 0.5


@pytest.mark.parametrize("key,value", [
    ("expert_selection_fn", "softmax"), ("norm_topk_prob", False),
    ("shared_expert_combination_strategy", "sum"),
    ("use_parallel_block", False), ("use_qk_norm", True),
    ("tie_word_embeddings", False), ("first_k_dense_replace", 2),
    ("rotary_pct", 0.5)])
def test_what_the_program_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        c2.Cohere2MoeConfig.from_published(dict(_published(),
                                                **{key: value}))


def test_every_documented_name_is_answered():
    import re

    doc = serving.__doc__.split("**The module class**")[0]
    names = re.findall(r"^- ``(\w+)", doc, re.M)
    cfg = c2.Cohere2MoeConfig.tiny()
    for name in names + ["max_seq_len", "vocab_size", "dtype", "n_heads",
                         "kv_window", "window_layers"]:
        assert hasattr(cfg, name), name
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (1, 3, 24)
    assert serving.WINDOW in serving.__doc__ and "window_table" \
        in serving.__doc__


# -- the allocator of pages that go back --------------------------------------

def test_a_rows_window_pages_stay_under_the_bound():
    win = WindowPages(40, PAGE, 24, 64, 16)
    assert win.bound == (24 + 16 + PAGE - 1) // PAGE + 1 == 6
    row = win.row()
    win.reserve(row, 100)
    assert win.available() == 39 - 6
    for start in range(0, 96, 16):                     # prefill, chunks of 16
        win.cover(row, start - 24, start + 16)
        assert row.held <= win.bound
        assert win.available() == 39 - 6               # the job's promise
    win.unreserve(row)
    for pos in range(96, 400):                         # a long decode
        win.cover(row, pos - 24, pos + 1)
        assert row.held <= 24 // PAGE + 1
    assert win.released == (399 - 24) // PAGE
    assert np.count_nonzero(row.table) == row.held
    win.release(row)
    assert win.live() == 0 and win.available() == 39


def test_a_job_is_promised_its_pages_and_others_wait():
    win = WindowPages(10, PAGE, 24, 64, 16)             # 9 usable
    a, b = win.row(), win.row()
    win.reserve(a, 200)                                 # 6
    with pytest.raises(NoFreeBlocks):
        win.reserve(b, 200)
    win.reserve(b, 20)                                  # 3
    win.cover(b, -24, 16)
    with pytest.raises(NoFreeBlocks):                   # b decodes on
        win.unreserve(b) or win.cover(b, 0, 40)
    win.cover(a, -24, 16)
    assert (a.held, b.held, win.available()) == (2, 2, 1)
    win.release(a)
    win.cover(b, 0, 40)
    assert b.held == 5


def test_the_pool_is_divided_by_the_rule(tiny):
    """Each kind gets what ``slots`` rows at ``max_seq_len`` come to at
    their most, if the budget covers both; else its share of the budget."""
    cfg, params = tiny
    token = cfg.kv_token_bytes()
    pages = cfg.max_seq_len // PAGE                     # 16
    most_w, most_p = 3 * 6 + 1, 3 * pages + 1
    roomy = PagedInferenceEngine(
        cfg, params, slots=3, page_size=PAGE, kernel="lax",
        prefill_chunk=16, kv_pool_bytes=1 << 20)
    assert roomy._win.pool.n_blocks == most_w
    assert roomy._kv_blocks == most_p
    want = PAGE * token * (3 * most_w + most_p)
    tight = PagedInferenceEngine(
        cfg, params, slots=3, page_size=PAGE, kernel="lax",
        prefill_chunk=16, kv_pool_bytes=want // 2)
    assert abs(tight._win.pool.n_blocks - most_w / 2) <= 1
    assert abs(tight._kv_blocks - most_p / 2) <= 1
    s = tight.stats()
    assert s.kv_window_blocks_total == tight._win.pool.n_blocks - 1
    assert s.kv_token_bytes == token                    # the paged kind's


# -- through the engine -------------------------------------------------------

def _engine(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("slots", 3)
    kw.setdefault("kernel", "lax")
    kw.setdefault("prefill_chunk", 16)
    return PagedInferenceEngine(cfg, params, page_size=PAGE, **kw)


def _gap(tiny, prompt, tokens):
    """How far below the reference's best logit each served token sits."""
    cfg, params = tiny
    full = list(prompt) + list(tokens)
    pad = -len(full) % 64
    logits = np.asarray(ref.reference_logits(
        params, jnp.asarray([full + [0] * pad]),
        jnp.arange(len(prompt) - 1, len(full) - 1), cfg))
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


def _counter(name):
    for line in REGISTRY.exposition().splitlines():
        if line.split(" ")[0] == name:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


#: under the window and staying there; crossing it in decode; past it in
#: prefill; a padded last chunk; more requests than slots
_LENGTHS, _BUDGETS = (5, 20, 61, 37, 9, 48), (12, 30, 40, 6, 15, 4)
_COUNTED = tuple(c.name for c in c2.Cohere2Moe.STATS) + (
    "lzy_kv_window_pages_released_total",)


@pytest.fixture(scope="module")
def served(tiny):
    cfg, _ = tiny
    engine = _engine(tiny, prefill_budget=16, kernel="pallas")
    engine.warmup()
    before = {n: _counter(n) for n in _COUNTED}
    prompts = [_tokens(10 + i, n, cfg.vocab_size)
               for i, n in enumerate(_LENGTHS)]
    held = []
    with trace.recording() as rec:
        reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
                for p, m in zip(prompts, _BUDGETS)]
        for _ in range(900):
            if not engine.step():
                break
            held.append(max(
                [r.held for r in engine._win_rows]
                + [j.window.held for j in engine.prefill.jobs]))
        spans = rec.drain()
    after = {n: _counter(n) for n in before}
    yield {"engine": engine, "prompts": prompts, "reqs": reqs,
           "spans": spans, "held": held,
           "counted": {n: after[n] - before[n] for n in before}}
    engine.close()


@pytest.mark.parametrize("i", range(6))
def test_engine_serves_the_references_tokens(tiny, served, i):
    req, prompt = served["reqs"][i], served["prompts"][i]
    assert req.done and req.error is None
    assert len(req.tokens) == _BUDGETS[i]
    assert _gap(tiny, prompt, req.tokens) < TOL


def test_window_pages_are_bounded_returned_and_all_come_back(served):
    engine = served["engine"]
    win = engine._win
    assert max(served["held"]) <= win.bound == 6
    # a row at position p has returned the pages wholly behind p - 1 - 24
    want = sum(max(0, n + m - 2 - 24) // PAGE
               for n, m in zip(_LENGTHS, _BUDGETS))
    assert served["counted"]["lzy_kv_window_pages_released_total"] == want
    s = engine.stats()
    assert s.kv_window_pages_released == win.released == want
    assert s.kv_window_blocks_live == 0 and win.reserved == 0
    assert s.kv_window_blocks_free == s.kv_window_blocks_total
    assert s.kv_blocks_free == s.kv_blocks_total
    assert not engine._win_tables.any() and not engine._tables.any()


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    assert counted["lzy_moe_assignments_total"] \
        == engine.decode_rows * cfg.top_k * cfg.n_layers
    assert counted["lzy_attn_rows_total"] \
        == engine.decode_rows * cfg.n_layers
    # a decoded token at position p read p + 1 keys in the full layer and
    # min(p + 1, 24) in each window layer: a request of n prompt and m
    # answer tokens decodes at n .. n + m - 2
    spans_ = [range(n + 1, n + m) for n, m in zip(_LENGTHS, _BUDGETS)]
    assert counted["lzy_attn_full_keys_total"] == sum(map(sum, spans_))
    assert counted["lzy_attn_window_keys_total"] == 3 * sum(
        min(p, 24) for r in spans_ for p in r)
    assert counted["lzy_attn_window_keys_total"] \
        < 3 * counted["lzy_attn_full_keys_total"]
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        set(s.attrs["model_stats"]) == set(_COUNTED[:-1]) for s in emits)
    starts = [s.attrs["start"] for s in served["spans"]
              if s.name == "engine.prefill" and "start" in s.attrs]
    assert 0 in starts and 48 in starts


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (pa.GROUP_DECODE_PATH, pa.GROUP_CHUNK_PATH, gexp.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].stats().kernel_path == pa.GROUP_DECODE_PATH


def test_cache_leaves_are_declared_by_kind(served):
    engine = served["engine"]
    kinds = engine._leaf_kinds
    assert kinds.count(serving.PAGED) == 2 and kinds.count(
        serving.WINDOW) == 6 and not engine._has_state
    shapes = sorted({leaf.shape for leaf in engine._payload})
    assert shapes == sorted({(engine._kv_blocks, PAGE, 32),
                             (engine._win.pool.n_blocks, PAGE, 32)})


def test_returned_pages_are_reused_while_a_round_is_in_flight(tiny):
    """A window pool of nine pages, two rows: the long row's context needs
    more pages over its life than the pool has, and both rows' tokens are
    the reference's: pages returned behind the long row's window are taken
    again (by either row) with a decode round in flight."""
    cfg, _ = tiny
    engine = _engine(tiny, slots=2, kv_window_blocks=10, prefill_budget=16)
    engine._loop_ident = __import__("threading").get_ident()
    prompts = [_tokens(30, 40, cfg.vocab_size),
               _tokens(31, 10, cfg.vocab_size)]
    reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
            for p, m in zip(prompts, (70, 30))]
    seen, in_flight_takes = set(), 0
    for _ in range(900):
        before = engine._win.released
        busy = engine.step()
        tables = engine._win_tables
        now = set(tables[tables != 0].tolist())
        if engine._inflight is not None and now - seen \
                and engine._win.released > before:
            in_flight_takes += 1
        seen |= now
        if not busy:
            break
    assert [r.error for r in reqs] == [None, None] and all(
        r.done for r in reqs)
    for p, r in zip(prompts, reqs):
        assert _gap(tiny, p, r.tokens) < TOL
    assert engine._win.released > 9         # more than the pool ever held
    assert len(seen) <= 9 and in_flight_takes > 0
    assert engine.stats().kv_window_blocks_live == 0
    engine.close()


def test_a_request_that_fits_one_kind_of_page_and_not_the_other_waits(tiny):
    """Plenty of paged blocks, seven window pages: the second long prompt
    waits in the queue until the first row ends, then runs."""
    cfg, _ = tiny
    engine = _engine(tiny, slots=2, kv_window_blocks=8, prefill_budget=16)
    prompts = [_tokens(40 + i, 50, cfg.vocab_size) for i in range(2)]
    reqs = [engine.submit(p, max_new_tokens=8, greedy=True) for p in prompts]
    for _ in range(6):
        engine.step()
    assert engine.stats().queue_depth == 1 and not reqs[1].done
    assert engine.kv.available() > 20 > engine._win.available()
    for _ in range(200):       # a turn with nothing to do is not the end:
        engine.step()          # the queue still holds the second prompt
        if all(r.done for r in reqs):
            break
    assert [(r.error, len(r.tokens)) for r in reqs] == [(None, 8)] * 2
    assert _gap(tiny, prompts[1], reqs[1].tokens) < TOL
    engine.close()


def test_llm_generate_through_the_gateway(tiny):
    from lzy_tpu import llm
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)

    cfg, _ = tiny
    fleet = ReplicaFleet(lambda: _engine(tiny, slots=2))
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                             model_name="command-a-plus-tiny",
                             page_size=PAGE)
    try:
        fleet.add_replica()
        llm.configure(gateway)
        prompt = _tokens(40, 35, cfg.vocab_size)
        gen = llm.generate(prompt, max_new_tokens=7, greedy=True,
                           cache=False)
        assert gen.status == "ok" and len(gen.tokens) == 7
        assert _gap(tiny, prompt, list(gen.tokens)) < TOL
        agg = fleet.aggregate()
        assert agg["kv_window_pages_released"] == 2
        assert agg["kv_window_blocks_live"] == 0
        assert agg["kv_window_blocks_free"] > 0
    finally:
        llm.configure(None)
        gateway.close()


@pytest.mark.parametrize("mechanism", [
    "int8 pool", "speculation", "host tier", "parking", "export", "import",
    "sharded engine"])
def test_each_refusal_names_its_mechanism(tiny, mechanism):
    cfg, params = tiny
    if mechanism == "int8 pool":
        with pytest.raises(c2.WindowPoolUnsupported, match="kv_quant"):
            _engine(tiny, kv_quant="int8")
    elif mechanism == "speculation":
        with pytest.raises(WindowLeavesUnsupported, match="spec_tokens"):
            _engine(tiny, spec_tokens=2)
    elif mechanism == "host tier":
        with pytest.raises(WindowLeavesUnsupported, match="tiered KV"):
            _engine(tiny, kv_host_tier_bytes=1 << 20)
    elif mechanism == "sharded engine":
        from lzy_tpu.serving.sharded import (
            NoPartitionRules, ShardedPagedInferenceEngine)

        with pytest.raises(NoPartitionRules, match="sharded engine"):
            ShardedPagedInferenceEngine(cfg, params, tp=2, slots=2)
    else:
        engine = _engine(tiny)
        assert engine.kv.reuse is False
        call = {"parking": lambda: engine.park_chain("k", [1] * 16),
                "export": lambda: engine.request_kv_export([1] * 16),
                "import": lambda: engine.queue_kv_import(object())}
        with pytest.raises(WindowLeavesUnsupported, match="window leaves"):
            call[mechanism]()
        engine.close()


def test_the_engine_names_no_model():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "lzy_tpu", "serving", "engine.py")) as f:
        text = f.read().lower()
    for word in ("cohere", "command", "sliding"):
        assert word not in text, word

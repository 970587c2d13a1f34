"""dots3-note-prev's language model (``dots3_note``) on the serving path:
latent attention of two kinds, the full layers reading the tokens a learned
indexer picks, the sliding layers a latent pool of their own under a window,
against the benchmark's plain float32 reference (logits, not tokens) past
``index_topk`` and past the window, in prefill and in decode; the expert
shares; the protocol's second price; the model through
``PagedInferenceEngine`` with every mechanism it is served by or refused by.
Tiny widths (a choice of 8, a window of 5, pages of 8), seeded weights, CPU,
Pallas kernels interpreted (``tests/conftest.py``).

The file's name sorts last on purpose (as ``test_zz_deepseek_v3.py``'s)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import dots3_note as ref
from lzy_tpu.models import cohere2_moe as c2
from lzy_tpu.models import dots3_note as dn
from lzy_tpu.models import experts, serving
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import latent_select as ls
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import WindowLeavesUnsupported
from lzy_tpu.serving.kv_cache import WindowPages, divide_pool
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4
PAGE = 8


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths; at the tiny ones it would shrink every layer's output to nothing
    and a wrong choice or a lost page would hide under the tolerance.
    Rescale each matrix to fan_in ** -0.5."""
    def fix(path, leaf):
        if path[-1].key in ("kernel", "experts_gate", "experts_up",
                            "experts_down", "router"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if path[-1].key == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        if path[-1].key == "embed_tokens":
            return leaf / 0.02
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = dn.Dots3NoteConfig.tiny()
    return cfg, _unit_scale(dn.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    """Uncached, 64 positions: past ``index_topk`` (8) in the two full
    layers and past the window (5) in the two sliding ones."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    got, seen = dn.Dots3Note(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.reference_logits(params, toks, jnp.arange(64), cfg)
    assert np.abs(got[0] - want).max() < TOL
    total = np.asarray(sum(jax.tree_util.tree_leaves(seen["stats"])))
    assert total.shape == (len(dn.Dots3Note.STATS),) == (10,)
    # uncached, the attention sows nothing; the three expert layers do
    assert list(total[[0, 3]]) == [64 * cfg.top_k * 3, cfg.n_held * 3]
    assert not total[4:].any()


#: a reference that ignored a mechanism would pass the test above only if
#: the program ignored it too: each variant moves the reference's logits
_VARIANTS = {
    "no selection": {"index_topk": 64},
    "a narrower choice": {"index_topk": 4},
    "a window of one less": {"window": 4},
    "a window of one more": {"window": 6},
    "no rescale": {"lora_rescale": False},
}


@pytest.mark.parametrize("name", sorted(_VARIANTS))
def test_the_reference_sees_each_mechanism(tiny, name):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    other = dataclasses.replace(cfg, **_VARIANTS[name])
    a = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), cfg))
    b = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), other))
    # the first positions see neither the window's edge nor a choice
    assert np.abs(a[:4] - b[:4]).max() < TOL or name == "no rescale"
    assert np.abs(a[40:] - b[40:]).max() > 100 * TOL


@pytest.mark.parametrize("leaf", ["gate_proj", "index_w_proj",
                                  "index_k_proj"])
def test_the_reference_reads_the_gate_and_the_indexer(tiny, leaf):
    """Another gate, other head weights or other index keys move the
    reference as they move the program: both read the weights they share by
    name."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    layer = dict(params["layer_1"])
    layer[leaf] = {"kernel": -params["layer_1"][leaf]["kernel"]}
    other = dict(params, layer_1=layer)
    a = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), cfg))
    b = np.asarray(ref.reference_logits(other, toks, jnp.arange(64), cfg))
    assert np.abs(a[40:] - b[40:]).max() > 100 * TOL
    got = dn.Dots3Note(cfg).apply({"params": other}, toks)
    assert np.abs(np.asarray(got[0]) - b).max() < TOL


def test_the_references_rotary_is_the_programs():
    from lzy_tpu.models.llama import _rope

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 3, 16)),
                    jnp.float32)
    pos = jnp.arange(20, 29)
    assert np.abs(np.asarray(_rope(x, pos[None], 8e7)[0])
                  - np.asarray(ref.rotary(x[0], pos, 8e7))).max() < 1e-6
    # the indexer's: the first 8 of 16 rotated, the rest as they are
    half = np.asarray(dn._rope_head(x, pos[None], 8e7, 8)[0])
    assert np.abs(half - np.asarray(ref.rotary(x[0], pos, 8e7, 8))).max() \
        < 1e-6
    assert (half[..., 8:] == np.asarray(x[0, ..., 8:])).all()


def _zero_cache(model, table):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32),
                               page_table=table, window_table=table))[
                                   "cache"])


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        tiny, kernel):
    """Logits, not tokens. Chunks of 16: the first crosses ``index_topk``
    (8) and the window (5), the third is padded; then one position at a time
    from 41 to 59, over page boundaries, with the pages behind the window
    returned before every program as the engine returns them
    (``WindowPages``): the window table reads scratch there. The choices of
    every query are the reference's."""
    cfg, params = tiny
    pages = cfg.max_seq_len // PAGE
    model = cfg.paged_model(page_size=PAGE, kv_pages=12, kernel=kernel,
                            kv_quant=None, window_pages=6)
    toks = _tokens(3, 60, cfg.vocab_size)
    want, chose = ref.reference(params, jnp.asarray([toks + [0] * 4]),
                                jnp.arange(60), cfg)
    want = np.asarray(want)
    full = np.zeros((1, pages), np.int32)
    full[0, :8] = [5, 2, 7, 1, 9, 3, 11, 4]
    win = WindowPages(6, PAGE, cfg.window, pages, 16)
    row = win.row()
    cache = _zero_cache(model, jnp.asarray(full))
    picked = [np.zeros((60, 64), bool) for _ in range(2)]

    def run(cache, start, chunk, real):
        win.cover(row, start - cfg.window, start + real)
        assert row.held <= win.bound
        pad = chunk + [0] * (16 - len(chunk)) if len(chunk) > 1 else chunk
        logits, upd = model.apply(
            {"params": params, "cache": cache}, jnp.asarray([pad]),
            page_table=jnp.asarray(full),
            window_table=jnp.asarray(row.table[None]),
            valid_len=jnp.asarray([real], jnp.int32),
            mutable=["cache", "stats", "choices"])
        cache = upd["cache"]
        if len(pad) != real:            # the engine rewinds a padded index
            cache = jax.tree_util.tree_map_with_path(
                lambda p, leaf: leaf - (len(pad) - real)
                if p[-1].key == "index" else leaf, cache)
        for mask, layer in zip(picked, ("layer_0", "layer_1")):
            idx, n = (np.asarray(x[0]) for x in
                      upd["choices"][layer]["chosen"][0])
            assert (n[real:] == 0).all()          # a pad chooses nothing
            for i in range(real):
                mask[start + i, idx[i, :n[i]]] = True
        counts = np.asarray(sum(jax.tree_util.tree_leaves(upd["stats"])))
        return cache, np.asarray(logits[0, :real]), counts

    got = []
    cache, out, _ = run(cache, 0, toks[:16], 16)
    got.append(out)
    cache, out, _ = run(cache, 16, toks[16:32], 16)
    got.append(out)
    cache, out, counts = run(cache, 32, toks[32:41], 9)   # padded to 16
    got.append(out)
    # the last real query sits at 40: 41 visible and 8 chosen in each full
    # layer, one selecting row a layer, the window's 5 in each sliding layer
    assert list(counts[4:]) == [41 * 2, 8 * 2, 2, 0, 5 * 2, 2]
    for at in range(41, 60):
        cache, out, counts = run(cache, at, [toks[at]], 1)
        got.append(out)
        assert list(counts[4:]) == [(at + 1) * 2, 8 * 2, 2, 0, 5 * 2, 2]
    assert np.abs(np.concatenate(got) - want).max() < TOL
    for mine, exact in zip(picked, chose):
        assert (mine == np.asarray(exact)[:, :64]).all()
        assert mine.sum(axis=1).tolist() == [min(p + 1, 8)
                                             for p in range(60)]
    # pages 0-5 (positions 0-47) lie wholly behind 59 - 5 and went back
    assert win.released == 6 and list(row.table[:6]) == [0] * 6
    assert row.held <= 2 and win.live() == row.held


@pytest.mark.parametrize("program", ["prefill chunk", "decode round"])
def test_the_kernels_choose_what_the_sort_chooses(tiny, program):
    """Both full layers, through ``mutable=["choices"]``: the module with
    ``paged_kernel="pallas"`` (``latent_choice_prefill`` /
    ``latent_choice_decode``) chooses the sets it chooses with ``"lax"``
    (``jax.lax.top_k``): a chunk of 16 whose queries cross ``index_topk``
    behind 24 cached positions, and a round of three slots (one past
    ``index_topk``, one idle, one under it)."""
    cfg, params = tiny
    pages = cfg.max_seq_len // PAGE
    rows = 1 if program == "prefill chunk" else 3
    table = np.zeros((rows, pages), np.int32)
    table[0, :6] = [5, 2, 7, 1, 9, 3]
    table[-1, :6] = [4, 6, 8, 10, 11, 12]
    table = jnp.asarray(table)
    toks = jnp.asarray([_tokens(5, 24, cfg.vocab_size)] * rows)
    chosen = {}
    for kernel in ("lax", "pallas"):
        model = cfg.paged_model(page_size=PAGE, kv_pages=13, kernel=kernel,
                                kv_quant=None, window_pages=13)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
                lambda: model.init(
                    jax.random.PRNGKey(0), jnp.zeros((rows, 1), jnp.int32),
                    page_table=table, window_table=table))["cache"])
        _, upd = model.apply(
            {"params": params, "cache": cache}, toks, page_table=table,
            window_table=table, mutable=["cache"])
        if program == "prefill chunk":
            ids, real = jnp.asarray([_tokens(6, 16, cfg.vocab_size)]), [16]
            at = [24]
        else:
            ids, real, at = jnp.asarray([[7], [8], [9]]), [1, 0, 1], [24, 9, 3]
        cache = jax.tree_util.tree_map_with_path(
            lambda p, leaf: jnp.asarray(at, jnp.int32)
            if p[-1].key == "index" else leaf, upd["cache"])
        _, out = model.apply(
            {"params": params, "cache": cache}, ids, page_table=table,
            window_table=table, valid_len=jnp.asarray(real, jnp.int32),
            mutable=["cache", "choices"])
        chosen[kernel] = [
            [np.asarray(x) for x in out["choices"][layer]["chosen"][0]]
            for layer in ("layer_0", "layer_1")]
    for (idx_a, n_a), (idx_b, n_b) in zip(chosen["lax"], chosen["pallas"]):
        assert (n_a == n_b).all() and n_a.max() == cfg.index_topk
        for r in range(idx_a.shape[0]):
            for t in range(idx_a.shape[1]):
                n = int(n_a[r, t])
                assert set(idx_a[r, t, :n].tolist()) \
                    == set(idx_b[r, t, :n].tolist()), (r, t)


def test_an_idle_slot_and_a_padded_position_write_only_scratch(tiny):
    """A decode round of three slots, the middle one idle (a zeroed table,
    ``valid_len`` 0), and a chunk padded from 3 to 8: in all three kinds of
    leaf nothing but block 0 and the real positions' places changes."""
    cfg, params = tiny
    pages = cfg.max_seq_len // PAGE
    model = cfg.paged_model(page_size=PAGE, kv_pages=8, kernel="lax",
                            kv_quant=None, window_pages=8)
    table = np.zeros((3, pages), np.int32)
    table[0, :2], table[2, :2] = [3, 4], [5, 6]
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32),
                page_table=jnp.asarray(table),
                window_table=jnp.asarray(table)))["cache"])
    cache = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.asarray([9, 77, 2], jnp.int32)
        if p[-1].key == "index" else leaf, cache)
    logits, upd = model.apply(
        {"params": params, "cache": cache},
        jnp.asarray([[7], [8], [9]]), page_table=jnp.asarray(table),
        window_table=jnp.asarray(table),
        valid_len=jnp.asarray([1, 0, 1], jnp.int32),
        mutable=["cache", "stats"])
    assert np.isfinite(np.asarray(logits)).all()
    names = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(upd["cache"]):
        name = path[-1].key
        if name == "index":
            continue
        names.add(name)
        leaf = np.asarray(leaf)
        written = {int(b) for b in np.nonzero(
            leaf.reshape(leaf.shape[0], -1).any(axis=1))[0]}
        # row 0 at position 9: block 4; row 2 at position 2: block 5
        assert written <= {0, 4, 5} and {4, 5} <= written, (name, written)
    assert names == {"latent", "ik", "wlatent"}
    # a chunk of 8 with 3 real positions, batch 1
    one = jnp.asarray(table[:1])
    cache = _zero_cache(model, one)
    _, upd = model.apply(
        {"params": params, "cache": cache},
        jnp.asarray([[7, 8, 9, 0, 0, 0, 0, 0]]), page_table=one,
        window_table=one, valid_len=jnp.asarray([3], jnp.int32),
        mutable=["cache", "stats"])
    for path, leaf in jax.tree_util.tree_leaves_with_path(upd["cache"]):
        if path[-1].key != "index":
            leaf = np.asarray(leaf)
            # the pads land in the row's own page past the real positions:
            # garbage the next program overwrites, and nothing elsewhere
            assert not leaf[[i for i in range(8) if i != 3]].any()


def test_the_shares_add_up(tiny):
    """Eight chips hold 2 of the 16 routed experts each. What each computes
    for the layer, with the shared expert (which every chip computes alike)
    counted once, adds up to the uncut layer: in the program, and to the
    reference's uncut layer."""
    cfg, params = tiny
    rng = jax.random.PRNGKey(7)
    whole = dataclasses.replace(cfg, experts_held=(0, 16))
    layer = _unit_scale(dn.init_params(whole, rng))["layer_1_moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 24, cfg.d_model)).astype(np.float32))
    big = ("experts_gate", "experts_up", "experts_down")

    def cut(lo, hi):
        c = dataclasses.replace(cfg, experts_held=(lo, hi))
        return c, dict(layer, **{n: layer[n][lo:hi] for n in big})

    def program(lo, hi):
        c, w = cut(lo, hi)
        out, _ = experts.GatedExperts(c, other_stats=6).apply(
            {"params": w}, u, mutable=["stats"])
        return np.asarray(out[0])

    def reference(lo, hi):
        c, w = cut(lo, hi)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.routed_experts(u[0], w, c)
                              + ref.shared_expert(u[0], w))

    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared_expert(u[0], layer))
    uncut = reference(0, 16)
    for layer_fn in (program, reference):
        shares = [layer_fn(lo, lo + 2) for lo in range(0, 16, 2)]
        summed = sum(s - shared for s in shares) + shared
        assert np.abs(summed - uncut).max() < TOL
        # a share alone is not the layer: the cut is real
        assert np.abs(shares[0] - uncut).max() > 10 * TOL
    assert np.abs(program(4, 6) - reference(4, 6)).max() < TOL


def test_a_bfloat16_reference_fails_the_tolerance(tiny):
    """The control (the reference wholly in bfloat16) is not within the
    tolerance the program is held to, and some of its choices are not the
    reference's."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(4, 64, cfg.vocab_size)])
    exact, chose = ref.reference(params, toks, jnp.arange(64), cfg)
    control, rough = ref.reference(params, toks, jnp.arange(64), cfg,
                                   jnp.bfloat16)
    assert np.abs(np.asarray(control) - np.asarray(exact)).max() > 20 * TOL
    counts = ref.choices_differ(
        [np.asarray(c) for c in rough], [np.asarray(c) for c in chose])
    assert len(counts) == cfg.kv_layers          # a pair a full layer
    differing, chosen = (sum(x) for x in zip(*counts))
    assert 0 < differing < chosen // 2
    assert ref.differ_shares([counts, counts]) == [d / c for d, c in counts]
    assert 0 < max(ref.differ_shares([counts])) < 0.5
    assert ref.choices_differ(chose, chose) == [(0, c) for _, c in counts]


# -- the seam -----------------------------------------------------------------

def _published():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "dots3-note-prev-serve-l5-ep8.json")
    with open(path) as f:
        doc = json.load(f)
    doc = dict(doc, **doc["published"])
    del doc["router_width"], doc["experts_held_from"]
    return doc


def test_the_published_keys_give_the_widths_and_the_counts():
    """Shapes only: a full layer's attention is 144.05 M parameters, a
    sliding layer's 90.83 M, a routed expert 23.59 M (ISSUE 62's
    reckoning), from the benchmark's configuration file with its cuts
    undone."""
    cfg = dn.Dots3NoteConfig.from_published(_published())
    assert cfg == dn.Dots3NoteConfig()
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (13, 33, 513)
    small = dataclasses.replace(
        cfg, n_layers=3, layer_types=(dn.FULL, dn.FULL, dn.SLIDING),
        experts_held=(0, 1), vocab_size=8)
    shapes = jax.eval_shape(lambda: dn.init_params(small,
                                                   jax.random.PRNGKey(0)))

    def count(name):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(shapes[name]))

    assert abs(count("layer_1") / 1e6 - 144.05) < 0.01
    assert abs(count("layer_2") / 1e6 - 90.83) < 0.01
    assert count("layer_0_mlp") == 3 * 5120 * 13824
    moe = count("layer_1_moe")
    assert moe == 2 * 3 * 5120 * 1536 + 5120 * 256 + 256


def test_the_cells_cut_is_the_chips_share_of_eight():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "dots3-note-prev-serve-l5-ep8.json")
    with open(path) as f:
        doc = json.load(f)
    cfg = ref.program_config(doc)
    assert cfg.layer_types == (dn.FULL, dn.FULL) + (dn.SLIDING,) * 3
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, (0, 32))
    assert (cfg.vocab_size, cfg.max_seq_len) == (19008, 50176)
    assert (cfg.kv_token_bytes(), cfg.window_token_bytes()) == (1536, 2304)
    shapes = jax.eval_shape(lambda: dn.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 1e9 - 4.087) < 0.002


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn"}), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("attention_gate_type", "elementwise"),
    ("swa_attention_gate_type", "none"), ("moe_layer_freq", 2),
    ("layer_types", ["full_attention", "linear_attention"])])
def test_what_the_program_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        dn.Dots3NoteConfig.from_published(dict(_published(),
                                               **{key: value}))


def test_kv_quant_is_refused_by_name(tiny):
    cfg, _ = tiny
    with pytest.raises(dn.LatentWindowUnsupported, match="kv_quant"):
        cfg.paged_model(page_size=PAGE, kv_pages=4, kernel="lax",
                        kv_quant="int8", window_pages=4)
    with pytest.raises(dn.LatentWindowUnsupported, match="kv_quant"):
        cfg.kv_token_bytes("int8")
    with pytest.raises(dn.LatentWindowUnsupported, match="kv_quant"):
        cfg.check_kernels(slots=4, kv_quant="int8")


def test_every_documented_name_is_answered():
    import re

    doc = serving.__doc__.split("**The module class**")[0]
    names = re.findall(r"^- ``(\w+)", doc, re.M)
    cfg = dn.Dots3NoteConfig.tiny()
    for name in names + ["max_seq_len", "vocab_size", "dtype", "n_heads",
                         "kv_window", "window_layers",
                         "window_token_bytes"]:
        assert hasattr(cfg, name), name
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (2, 2, 5)
    assert "window_token_bytes" in serving.__doc__
    assert "thirteen families" in serving.__doc__


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: the index and the read of the chosen at
    the decode round's shapes and the widest chunk's, the read under the
    window at the decode round's over the 225-page window pool, the gated
    experts at 5120 x 1536 at 16 and at 256 rows."""
    cfg = dataclasses.replace(
        dn.Dots3NoteConfig(), n_layers=5,
        layer_types=(dn.FULL, dn.FULL) + (dn.SLIDING,) * 3,
        experts_held=(0, 32), max_seq_len=50176)
    cfg.check_kernels(slots=16, kv_blocks=12545, page_size=64,
                      pages_per_seq=784, window_blocks=225)


# -- the protocol's second price ------------------------------------------------

def test_each_kind_of_page_is_charged_its_own_price(tiny):
    """A byte budget that covers both kinds gives each its most; one that
    does not is divided in proportion to what each kind's most costs at its
    own bytes a token."""
    cfg, params = tiny
    assert cfg.kv_token_bytes() == (128 + 128) * 4
    assert cfg.window_token_bytes() == 128 * 4
    pages = cfg.max_seq_len // PAGE                     # 16
    bound = (cfg.window + 16 + PAGE - 1) // PAGE + 1    # 4
    most_w, most_p = 3 * bound + 1, 3 * pages + 1
    roomy = PagedInferenceEngine(
        cfg, params, slots=3, page_size=PAGE, kernel="lax",
        prefill_chunk=16, kv_pool_bytes=1 << 22)
    assert roomy._win.pool.n_blocks == most_w
    assert roomy._kv_blocks == most_p
    want_w = most_w * PAGE * 2 * cfg.window_token_bytes()
    want_p = most_p * PAGE * 2 * cfg.kv_token_bytes()
    tight = PagedInferenceEngine(
        cfg, params, slots=3, page_size=PAGE, kernel="lax",
        prefill_chunk=16, kv_pool_bytes=(want_w + want_p) // 2)
    assert abs(tight._win.pool.n_blocks - most_w / 2) <= 1
    assert abs(tight._kv_blocks - most_p / 2) <= 1
    s = tight.stats()
    assert s.kv_window_blocks_total == tight._win.pool.n_blocks - 1
    assert s.kv_token_bytes == 2 * cfg.kv_token_bytes()   # the paged kind's
    roomy.close(), tight.close()


@pytest.mark.parametrize("budget", [1 << 14, 1 << 17, 1 << 20])
def test_a_model_with_one_price_is_divided_as_before(budget):
    """``cohere2_moe`` answers no ``window_token_bytes``: its two block
    counts from a byte budget are what the rule gave before the window kind
    had a price of its own (both kinds at ``kv_token_bytes``)."""
    cfg = c2.Cohere2MoeConfig.tiny()
    assert not hasattr(cfg, "window_token_bytes")
    page, most_w, most_p = 8, 19, 49
    token = cfg.kv_token_bytes(None)
    per_window = page * cfg.window_layers * token
    want_w, want_p = most_w * per_window, most_p * page * cfg.kv_layers \
        * token
    if want_w + want_p <= budget:
        before = (most_w, want_p)
    else:
        for_w = budget * want_w // (want_w + want_p)
        before = (max(2, for_w // per_window), budget - for_w)
    assert divide_pool(budget, cfg, most_w, most_p, page) == before


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


#: under the choice and the window and staying there; crossing both in
#: decode; past both in prefill; a padded last chunk; more requests than
#: slots
_LENGTHS, _BUDGETS = (3, 6, 61, 37, 9, 48), (4, 30, 40, 6, 15, 4)
_COUNTED = tuple(c.name for c in dn.Dots3Note.STATS) + (
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


def test_window_pages_are_bounded_returned_and_all_come_back(tiny, served):
    cfg, _ = tiny
    engine = served["engine"]
    win = engine._win
    assert win.window == cfg.window == 5
    assert max(served["held"]) <= win.bound == 4
    # a row at position p has returned the pages wholly behind p - 1 - 5
    want = sum(max(0, n + m - 2 - 5) // PAGE
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
        == engine.decode_rows * cfg.top_k * cfg.expert_layers
    assert counted["lzy_latent_rows_total"] == engine.decode_rows * 2
    # a decoded token at position p saw p + 1 positions and read min(p + 1,
    # 8) in each full layer, and min(p + 1, 5) in each sliding layer: a
    # request of n prompt and m answer tokens decodes at n .. n + m - 2
    seen = [p for n, m in zip(_LENGTHS, _BUDGETS)
            for p in range(n + 1, n + m)]
    assert counted["lzy_latent_visible_tokens_total"] == 2 * sum(seen)
    assert counted["lzy_latent_chosen_tokens_total"] == 2 * sum(
        min(p, 8) for p in seen)
    assert counted["lzy_latent_select_rows_total"] == 2 * sum(
        p > 8 for p in seen)
    assert counted["lzy_latent_dense_rows_total"] == 2 * sum(
        p <= 8 for p in seen)
    assert counted["lzy_latent_window_tokens_total"] == 2 * sum(
        min(p, 5) for p in seen)
    assert counted["lzy_latent_chosen_tokens_total"] \
        < counted["lzy_latent_visible_tokens_total"]
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        set(s.attrs["model_stats"]) == set(_COUNTED[:-1]) for s in emits)
    starts = [s.attrs["start"] for s in served["spans"]
              if s.name == "engine.prefill" and "start" in s.attrs]
    assert 0 in starts and 48 in starts


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (ls.CHOSEN_DECODE_PATH, ls.CHOSEN_PREFILL_PATH,
                 ls.INDEX_DECODE_PATH, ls.INDEX_PREFILL_PATH,
                 ls.CHOICE_DECODE_PATH, ls.CHOICE_PREFILL_PATH,
                 ls.GATHER_DECODE_PATH, ls.WINDOW_DECODE_PATH, gexp.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].stats().kernel_path == ls.CHOSEN_DECODE_PATH


@pytest.mark.parametrize("kernel,t,there", [
    ("pallas", 1, True), ("pallas", 8, True), ("pallas", 256, False),
    ("lax", 1, False)])
def test_the_gathers_label_is_a_decode_programs_under_the_kernel(
        tiny, kernel, t, there):
    """``latent_gather_decode`` copies the chosen, and
    ``latent_window_decode`` reads the window layers, in programs of up to
    ``mla.MAX_DECODE_TOKENS`` positions a row; a prefill chunk and the
    ``lax`` form keep XLA's gathers, which have no label."""
    cfg, _ = tiny
    paths = dataclasses.replace(cfg, paged_kernel=kernel).kernel_paths(t)
    assert (ls.GATHER_DECODE_PATH in paths) == there
    assert (ls.WINDOW_DECODE_PATH in paths) == there
    assert ls.index_path(t) in paths and gexp.PATH in paths


def test_cache_leaves_are_declared_by_kind(served):
    engine = served["engine"]
    kinds = engine._leaf_kinds
    assert kinds.count(serving.PAGED) == 4 and kinds.count(
        serving.WINDOW) == 2 and not engine._has_state
    shapes = sorted({leaf.shape for leaf in engine._payload})
    assert shapes == sorted({
        (engine._kv_blocks, PAGE, 128),                 # latent and ik
        (engine._win.pool.n_blocks, PAGE, 128)})        # wlatent
    assert engine.kv.reuse is False                     # window leaves


@pytest.mark.parametrize("mechanism", [
    "int8 pool", "speculation", "host tier", "sharded engine", "parking",
    "export", "import"])
def test_each_refusal_names_its_mechanism(tiny, mechanism):
    cfg, params = tiny
    if mechanism == "int8 pool":
        with pytest.raises(dn.LatentWindowUnsupported, match="kv_quant"):
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
    for name in ("engine.py", "prefill.py", "kv_cache.py", "kv_io.py"):
        with open(os.path.join(root, "lzy_tpu", "serving", name)) as f:
            text = f.read().lower()
        assert "dots3" not in text and "dots3_note" not in text, name


def test_the_lax_engine_serves_the_pallas_engines_tokens(tiny, served):
    """``kernel="lax"`` (the CPU's ``auto``) through the same engine: the
    same greedy tokens, float32."""
    engine = _engine(tiny, prefill_budget=16)
    reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
            for p, m in zip(served["prompts"][:4], _BUDGETS[:4])]
    for _ in range(600):
        if not engine.step():
            break
    for mine, theirs in zip(reqs, served["reqs"]):
        assert mine.tokens == theirs.tokens
    assert engine.stats().kernel_path == ls.CHOSEN_LAX_PATH
    engine.close()

"""Motif-3-Beta (``Motif``) on the serving path: four residual streams mixed
around every sublayer, grouped differential attention over a latent cache in
window and full layers (absorbed, the difference taken in the latent),
PolyNorm experts, against the benchmark's plain float32 reference (the
published non-absorbed form: logits, not tokens) past the window and over
several pages, in prefill and in decode; the expert shares; the model through
``PagedInferenceEngine`` with every mechanism it is served by or refused by.
Tiny widths (a window of 5, pages of 8, 10 heads of which 2 noise), seeded
weights, CPU, Pallas kernels interpreted (``tests/conftest.py``).

The file's name sorts last on purpose (as ``test_zz_deepseek_v3.py``'s)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import motif as ref
from lzy_tpu.models import motif as mt
from lzy_tpu.models import serving
from lzy_tpu.ops import latent_select as lsel
from lzy_tpu.ops import mhc, mla
from lzy_tpu.ops import polynorm_experts as pne
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.serving.engine import WindowLeavesUnsupported
from lzy_tpu.serving.kv_cache import WindowPages
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums alone
TOL = 2e-4
PAGE = 8
CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs",
    "motif-3-beta-serve-l5-ep8.json")


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths; at the tiny ones it would shrink every layer's output to nothing
    and a lost page or an unsubtracted head would hide under the tolerance.
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
    cfg = mt.MotifConfig.tiny()
    return cfg, _unit_scale(mt.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    """Uncached, 64 positions: past the window (5) in the four window
    layers; the absorbed read with the difference in the latent against the
    expanded read with the difference on the heads."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    got, seen = mt.Motif(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.reference_logits(params, toks, jnp.arange(64), cfg)
    assert np.abs(got[0] - want).max() < TOL
    total = np.asarray(sum(jax.tree_util.tree_leaves(seen["stats"])))
    assert total.shape == (len(mt.Motif.STATS),) == (10,)
    # uncached, attention and the connections sow nothing; the four expert
    # layers do
    assert list(total[[0, 3]]) == [64 * cfg.top_k * 4, cfg.n_held * 4]
    assert not total[4:].any()


#: a reference that ignored a mechanism would pass the test above only if
#: the program ignored it too: each variant moves the reference's logits
_VARIANTS = {
    "a window of one less": {"window": 4},
    "a window of one more": {"window": 6},
    "one sweep for twenty": {"mhc_sweeps": 1},
    "no output scale on PolyNorm": {"polynorm_scale": 1.0},
    "no clamp on PolyNorm's bias": {"polynorm_clamp": 1e9},
    "no scale on the routed weights": {"routed_scaling": 1.0},
}


@pytest.mark.parametrize("name", sorted(_VARIANTS))
def test_the_reference_sees_each_mechanism(tiny, name):
    cfg, params = tiny
    if "clamp" in name:
        # a bias the clamp binds, in every MLP
        params = jax.tree_util.tree_map_with_path(
            lambda p, x: x.at[..., 3].set(2.0)
            if "polynorm" in p[-1].key else x, params)
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    other = dataclasses.replace(cfg, **_VARIANTS[name])
    a = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), cfg))
    b = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), other))
    if "window" in name:
        # the first positions do not see the window's edge
        assert np.abs(a[:4] - b[:4]).max() < TOL
    # one sweep leaves the mix a few thousandths from twenty's (Sinkhorn-
    # Knopp converges fast at the initialiser's spread): ten tolerances
    assert np.abs(a[40:] - b[40:]).max() > (10 if "sweep" in name
                                            else 100) * TOL
    got = mt.Motif(other).apply({"params": params}, toks)
    assert np.abs(np.asarray(got[0]) - b).max() < TOL


@pytest.mark.parametrize("leaf", ["gate_proj", "lambda_proj"])
def test_the_reference_reads_the_gate_and_the_noise_weight(tiny, leaf):
    """Another gate or another ``lam`` moves the reference as it moves the
    program: both read the weights they share by name."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    layer = dict(params["layer_3"])
    layer[leaf] = {"kernel": -params["layer_3"][leaf]["kernel"]}
    other = dict(params, layer_3=layer)
    a = np.asarray(ref.reference_logits(params, toks, jnp.arange(64), cfg))
    b = np.asarray(ref.reference_logits(other, toks, jnp.arange(64), cfg))
    assert np.abs(a[40:] - b[40:]).max() > 100 * TOL
    got = mt.Motif(cfg).apply({"params": other}, toks)
    assert np.abs(np.asarray(got[0]) - b).max() < TOL


@pytest.mark.parametrize("fault", [
    "the noise heads not subtracted", "lam of one for all",
    "a noise head paired with the wrong group", "the gate left out",
    "Hres the identity", "Hpost without its factor 2",
    "the streams averaged on the way in", "the cubic term left out"])
def test_a_planted_fault_moves_the_reference(tiny, fault, monkeypatch):
    """The reference with one mechanism broken stands far from the program:
    the comparison sees each (ISSUE 65's list; the others are configuration
    variants above, and PolyNorm's norms a tile at a time and its bfloat16
    powers are held by ``tests/test_zz_polynorm_experts.py``)."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 64, cfg.vocab_size)])
    rows = jnp.arange(64)
    sound = np.asarray(ref.reference_logits(params, toks, rows, cfg))

    def flip(name, leaf, fn):
        return {k: (dict(v, **{leaf: {"kernel": fn(v[leaf]["kernel"])}})
                    if k == name else v) for k, v in params.items()}

    broken = params
    if fault in ("the noise heads not subtracted", "lam of one for all"):
        # lam has no bias to move: hold the one sigmoid of its width
        plain, hs = jax.nn.sigmoid, cfg.n_signal_heads
        value = 0.0 if "not subtracted" in fault else 1.0
        monkeypatch.setattr(
            jax.nn, "sigmoid", lambda x: jnp.full_like(x, value)
            if x.shape[-1] == hs else plain(x))
    elif fault == "a noise head paired with the wrong group":
        # the two noise heads' queries change places
        hs, dq = cfg.n_signal_heads, cfg.qk_nope_head_dim \
            + cfg.qk_rope_head_dim

        def swap(w):
            w = w.reshape(w.shape[0], cfg.n_heads, dq)
            return jnp.concatenate(
                [w[:, :hs], w[:, hs:][:, ::-1]], axis=1).reshape(
                    w.shape[0], -1)
        broken = flip("layer_3", "q_b_proj", swap)
    elif fault == "the gate left out":
        broken = {k: (dict(v, gate_proj={"kernel": jnp.zeros_like(
            v["gate_proj"]["kernel"])}) if k.startswith("layer_")
            and "lambda_proj" in v else v) for k, v in params.items()}
    elif fault == "Hres the identity":
        monkeypatch.setattr(
            ref, "sinkhorn", lambda raw, sweeps: jnp.broadcast_to(
                jnp.eye(raw.shape[-1]), raw.shape))
    elif fault == "Hpost without its factor 2":
        real = ref.connection
        monkeypatch.setattr(ref, "connection", lambda x, w, c: (
            lambda pre, post, res: (pre, post / 2.0, res))(*real(x, w, c)))
    elif fault == "the streams averaged on the way in":
        real = ref.connection
        monkeypatch.setattr(ref, "connection", lambda x, w, c: (
            lambda pre, post, res: (jnp.full_like(pre, 0.25), post, res))(
                *real(x, w, c)))
    elif fault == "the cubic term left out":
        broken = jax.tree_util.tree_map_with_path(
            lambda p, x: x.at[..., 2].set(0.0)
            if "polynorm" in p[-1].key else x, params)
    jax.clear_caches()
    try:
        wrong = np.asarray(ref.reference_logits(broken, toks, rows, cfg))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.abs(wrong[40:] - sound[40:]).max() > 100 * TOL


def test_the_references_rotary_is_the_programs():
    from lzy_tpu.models.llama import _rope

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 3, 16)),
                    jnp.float32)
    pos = jnp.arange(20, 29)
    assert np.abs(np.asarray(_rope(x, pos[None], 1e4)[0])
                  - np.asarray(ref.rotary(x[0], pos, 1e4))).max() < 1e-6


def _zero_cache(model, table):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((table.shape[0], 1), jnp.int32),
                               page_table=table, window_table=table))[
                                   "cache"])


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        tiny, kernel):
    """Logits, not tokens. Chunks of 16: the first crosses the window (5),
    the third is padded; then one position at a time from 41 to 59, over
    page boundaries, with the pages behind the window returned before every
    program as the engine returns them (``WindowPages``): the window table
    reads scratch there."""
    cfg, params = tiny
    pages = cfg.max_seq_len // PAGE
    model = cfg.paged_model(page_size=PAGE, kv_pages=12, kernel=kernel,
                            kv_quant=None, window_pages=6)
    toks = _tokens(3, 60, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks + [0] * 4]), jnp.arange(60), cfg))
    full = np.zeros((1, pages), np.int32)
    full[0, :8] = [5, 2, 7, 1, 9, 3, 11, 4]
    win = WindowPages(6, PAGE, cfg.window, pages, 16)
    row = win.row()
    cache = _zero_cache(model, jnp.asarray(full))

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
    # the last real query sits at 40: 41 positions in the full layer, one
    # row; the window's 5 in each of four window layers; ten sublayers
    # mixed; 8 signal heads a layer, lam summed in thousandths
    assert list(counts[4:8]) == [41, 1, 5 * 4, 10]
    assert counts[9] == 8 * 5 and 0 < counts[8] < 1000 * counts[9]
    for at in range(41, 60):
        cache, out, counts = run(cache, at, [toks[at]], 1)
        got.append(out)
        assert list(counts[4:8]) == [at + 1, 1, 5 * 4, 10]
    assert np.abs(np.concatenate(got) - want).max() < TOL
    # pages 0-5 (positions 0-47) lie wholly behind 59 - 5 and went back
    assert win.released == 6 and list(row.table[:6]) == [0] * 6
    assert row.held <= 2 and win.live() == row.held


def test_an_idle_slot_and_a_padded_position_write_only_scratch(tiny):
    """A decode round of three slots, the middle one idle (a zeroed table,
    ``valid_len`` 0), and a chunk padded from 3 to 8: in both kinds of leaf
    nothing but block 0 and the real positions' places changes, and the idle
    slot moves no count."""
    cfg, params = tiny
    pages = cfg.max_seq_len // PAGE
    model = cfg.paged_model(page_size=PAGE, kv_pages=8, kernel="lax",
                            kv_quant=None, window_pages=8)
    table = np.zeros((3, pages), np.int32)
    table[0, :2], table[2, :2] = [3, 4], [5, 6]
    table = jnp.asarray(table)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.asarray([9, 77, 2], jnp.int32)
        if p[-1].key == "index" else leaf, _zero_cache(model, table))
    logits, upd = model.apply(
        {"params": params, "cache": cache},
        jnp.asarray([[7], [8], [9]]), page_table=table, window_table=table,
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
    assert names == {"latent", "wlatent"}
    counts = np.asarray(sum(jax.tree_util.tree_leaves(upd["stats"])))
    # two real rows: at positions 9 and 2 they read 10 + 3 in the full
    # layer and 5 + 3 in each window layer
    assert list(counts[4:8]) == [13, 2, (5 + 3) * 4, 2 * 10]
    assert counts[0] == 2 * cfg.top_k * 4 and counts[9] == 2 * 8 * 5
    # a chunk of 8 with 3 real positions, batch 1
    one = table[:1]
    _, upd = model.apply(
        {"params": params, "cache": _zero_cache(model, one)},
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
    whole = dataclasses.replace(cfg, experts_held=(0, 16))
    layer = _unit_scale(mt.init_params(whole, jax.random.PRNGKey(7)))[
        "layer_1_moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 24, cfg.d_model)).astype(np.float32))
    big = ("experts_gate", "experts_up", "experts_down", "experts_polynorm")

    def cut(lo, hi):
        c = dataclasses.replace(cfg, experts_held=(lo, hi))
        return c, dict(layer, **{n: layer[n][lo:hi] for n in big})

    def program(lo, hi):
        c, w = cut(lo, hi)
        out, _ = mt.PolyNormExperts(c).apply(
            {"params": w}, u, mutable=["stats", "intermediates"])
        return np.asarray(out[0])

    def reference(lo, hi):
        c, w = cut(lo, hi)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.routed_experts(u[0], w, c)
                              + ref.shared_expert(u[0], w, c))

    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared_expert(u[0], layer, cfg))
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
    tolerance the program is held to."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(4, 64, cfg.vocab_size)])
    exact = ref.reference_logits(params, toks, jnp.arange(64), cfg)
    control = ref.reference_logits(params, toks, jnp.arange(64), cfg,
                                   jnp.bfloat16)
    assert np.abs(np.asarray(control) - np.asarray(exact)).max() > 20 * TOL


# -- the seam -----------------------------------------------------------------

def _published():
    with open(CONFIG) as f:
        doc = json.load(f)
    doc = dict(doc, **doc["published"])
    del doc["router_width"], doc["experts_held_from"]
    return doc


def test_the_published_keys_give_the_widths_and_the_counts():
    """Shapes only: attention is 91.75 M parameters a layer, a sublayer's
    connection 0.39 M, a routed expert 15.73 M, the dense MLP 151.0 M
    (ISSUE 65's reckoning), from the benchmark's configuration file with its
    cuts undone."""
    cfg = mt.MotifConfig.from_published(_published())
    assert cfg == mt.MotifConfig()
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (13, 40, 128)
    assert (cfg.n_signal_heads, cfg.group_size) == (64, 4)
    small = dataclasses.replace(
        cfg, n_layers=2, layer_types=(mt.FULL, mt.SLIDING), first_dense=1,
        experts_held=(0, 1), vocab_size=8)
    shapes = jax.eval_shape(lambda: mt.init_params(small,
                                                   jax.random.PRNGKey(0)))

    def count(name):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(shapes[name]))

    assert abs(count("layer_0") / 1e6 - 91.75) < 0.01
    assert count("layer_0") == count("layer_1")
    assert count("layer_0_hc") == count("layer_1_ffn_hc") \
        == 24 * 16384 + 3 + 24
    assert count("layer_0_mlp") == 3 * 4096 * 12288 + 4
    assert count("layer_1_moe") == 2 * (3 * 4096 * 1280 + 4) + 4096 * 384


def test_the_cells_cut_is_the_chips_share_of_eight():
    with open(CONFIG) as f:
        doc = json.load(f)
    cfg = ref.program_config(doc)
    assert cfg.layer_types == (mt.SLIDING,) * 3 + (mt.FULL, mt.SLIDING)
    assert (cfg.n_routed_experts, cfg.experts_held) == (384, (0, 48))
    assert (cfg.first_dense, cfg.top_k, cfg.routed_scaling) == (1, 8, 2.0)
    assert (cfg.vocab_size, cfg.max_seq_len) == (27520, 12288)
    assert cfg.kv_token_bytes() == 1280
    assert not hasattr(cfg, "window_token_bytes")     # one price
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (1, 4, 128)
    assert cfg.widest_prefill == 256 and cfg.prefill_read_heads == 20
    shapes = jax.eval_shape(lambda: mt.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 1e9 - 3.928) < 0.002


@pytest.mark.parametrize("key,value", [
    ("attention_cls", "mla"), ("diff_v2", False), ("mhc_enabled", False),
    ("hidden_act", "silu"), ("score_func", "softmax"), ("route_norm", False),
    ("score_before_experts", True), ("headwise_attn_output_gate", True),
    ("tie_word_embeddings", True), ("interleave_moe_layer_step", 2),
    ("sliding_window_pattern", "alternate"), ("num_noise_heads", 8),
    ("num_attention_heads", 81), ("layer_types", ["full_attention", "x"]),
    ("rope_scaling", {"apply_yarn_scaling": True})])
def test_what_the_program_cannot_honour_is_refused_by_name(key, value):
    doc = dict(_published(), **{key: value})
    name = "apply_yarn_scaling" if key == "rope_scaling" else key
    if key == "num_attention_heads":
        doc["num_noise_heads"] = doc["num_key_value_heads"] = 16
        name = "num_attention_heads - num_noise_heads"
    if key == "layer_types":
        doc["num_hidden_layers"] = 2
    with pytest.raises(ValueError, match=name):
        mt.MotifConfig.from_published(doc)


def test_kv_quant_is_refused_by_name(tiny):
    cfg, _ = tiny
    with pytest.raises(mt.LatentWindowUnsupported, match="kv_quant"):
        cfg.paged_model(page_size=PAGE, kv_pages=4, kernel="lax",
                        kv_quant="int8", window_pages=4)
    with pytest.raises(mt.LatentWindowUnsupported, match="kv_quant"):
        cfg.kv_token_bytes("int8")
    with pytest.raises(mt.LatentWindowUnsupported, match="kv_quant"):
        cfg.check_kernels(slots=4, kv_quant="int8")


def test_every_documented_name_is_answered():
    import re

    doc = serving.__doc__.split("**The module class**")[0]
    names = re.findall(r"^- ``(\w+)", doc, re.M)
    cfg = mt.MotifConfig.tiny()
    for name in names + ["max_seq_len", "vocab_size", "dtype", "n_heads",
                         "kv_window", "window_layers"]:
        assert hasattr(cfg, name), name
    assert (cfg.kv_layers, cfg.window_layers, cfg.kv_window) == (1, 4, 5)
    assert "thirteen families" in serving.__doc__
    assert "models/motif.py" in serving.__doc__
    assert mt.Motif.CACHE_KINDS == {"latent": "paged", "wlatent": "window",
                                    "index": "index"}


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: ``ops/mla.py``'s read at 80 heads at the
    decode round's shapes and at 20 a call at the widest chunk's, the read
    under the window at the decode round's over the 449-page window pool,
    both connections' kernels and the expert product at 64 and at 256
    rows."""
    with open(CONFIG) as f:
        cfg = ref.program_config(json.load(f))
    cfg.check_kernels(slots=64, kv_blocks=12289, page_size=64,
                      pages_per_seq=192, window_blocks=449)


@pytest.mark.parametrize("kernel,t,paths", [
    ("pallas", 1, (mhc.PATH, lsel.WINDOW_DECODE_PATH, pne.PATH)),
    ("pallas", 8, (mhc.PATH, lsel.WINDOW_DECODE_PATH, pne.PATH)),
    ("pallas", 256, (mhc.PATH, pne.PATH)),
    ("lax", 1, (mhc.LAX_PATH, pne.LAX_PATH))])
def test_a_programs_kernel_labels(tiny, kernel, t, paths):
    """``latent_window_decode`` reads the window layers in programs of up
    to ``mla.MAX_DECODE_TOKENS`` positions a row under the kernel; a
    prefill chunk and the ``lax`` form keep the gathers, which have no
    label."""
    cfg, _ = tiny
    assert dataclasses.replace(cfg, paged_kernel=kernel).kernel_paths(t) \
        == paths
    assert cfg.read_path(kernel, t=t) == mla.read_path(kernel, t=t)


def test_one_price_buys_both_kinds_of_page(tiny):
    """A byte budget that covers both kinds gives each its most; one that
    does not is divided in proportion to what each kind's most costs, both
    at ``kv_token_bytes`` (the model answers no ``window_token_bytes``)."""
    cfg, params = tiny
    assert cfg.kv_token_bytes() == 128 * 4       # 40 values in one tile
    pages = cfg.max_seq_len // PAGE                     # 16
    bound = (cfg.window + 16 + PAGE - 1) // PAGE + 1    # 4
    most_w, most_p = 3 * bound + 1, 3 * pages + 1
    roomy = PagedInferenceEngine(
        cfg, params, slots=3, page_size=PAGE, kernel="lax",
        prefill_chunk=16, kv_pool_bytes=1 << 22)
    assert roomy._win.pool.n_blocks == most_w
    assert roomy._kv_blocks == most_p
    want_w = most_w * PAGE * cfg.window_layers * cfg.kv_token_bytes()
    want_p = most_p * PAGE * cfg.kv_layers * cfg.kv_token_bytes()
    tight = PagedInferenceEngine(
        cfg, params, slots=3, page_size=PAGE, kernel="lax",
        prefill_chunk=16, kv_pool_bytes=(want_w + want_p) // 2)
    assert abs(tight._win.pool.n_blocks - most_w / 2) <= 1
    assert abs(tight._kv_blocks - most_p / 2) <= 1
    s = tight.stats()
    assert s.kv_window_blocks_total == tight._win.pool.n_blocks - 1
    assert s.kv_token_bytes == cfg.kv_layers * cfg.kv_token_bytes()
    assert roomy._win.bound == bound
    roomy.close(), tight.close()


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
#: prefill and over several pages; a padded last chunk; more requests than
#: slots
_LENGTHS, _BUDGETS = (3, 6, 61, 37, 9), (4, 24, 20, 6, 12)
_COUNTED = tuple(c.name for c in mt.Motif.STATS) + (
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


@pytest.mark.parametrize("i", range(5))
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
    assert engine.kv.reuse is False                 # the radix cache is off


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    rows = engine.decode_rows
    assert counted["lzy_moe_assignments_total"] \
        == rows * cfg.top_k * cfg.expert_layers
    assert counted["lzy_mla_rows_total"] == rows            # one full layer
    assert counted["lzy_mhc_mixed_rows_total"] == rows * 2 * cfg.n_layers
    assert counted["lzy_diff_signal_reads_total"] \
        == rows * cfg.n_signal_heads * cfg.n_layers
    # the mean lam, over every layer's signal heads: about a half, never 0
    mean = counted["lzy_diff_noise_weight_milli_total"] / 1000.0 \
        / counted["lzy_diff_signal_reads_total"]
    assert 0.3 < mean < 0.7
    # a decoded token at position p saw p + 1 positions in the full layer
    # and min(p + 1, 5) in each window layer: a request of n prompt and m
    # answer tokens decodes at n .. n + m - 2
    seen = [p for n, m in zip(_LENGTHS, _BUDGETS)
            for p in range(n + 1, n + m)]
    assert counted["lzy_mla_context_tokens_total"] == sum(seen)
    assert counted["lzy_latent_window_tokens_total"] == 4 * sum(
        min(p, 5) for p in seen)
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        set(s.attrs["model_stats"]) == set(_COUNTED[:-1]) for s in emits)
    starts = [s.attrs["start"] for s in served["spans"]
              if s.name == "engine.prefill" and "start" in s.attrs]
    assert 0 in starts and 48 in starts


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (mla.DECODE_PATH, mla.PREFILL_PATH, mhc.PATH,
                 lsel.WINDOW_DECODE_PATH, pne.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].stats().kernel_path == mla.DECODE_PATH


@pytest.mark.parametrize("mechanism", [
    "speculation", "host tier", "sharded engine", "parking", "export",
    "import"])
def test_mechanisms_that_move_pages_by_tokens_refuse_the_model(
        tiny, mechanism):
    cfg, params = tiny
    if mechanism == "speculation":
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
    for word in ("motif", "polynorm", "sinkhorn", "mhc"):
        assert word not in text, word


def test_the_lax_engine_serves_the_same_tokens(tiny, served):
    """``kernel="lax"`` (what ``"auto"`` is on the CPU) takes the portable
    forms of all three kernels: the same greedy tokens at float32."""
    cfg, _ = tiny
    engine = _engine(tiny, prefill_budget=16)
    try:
        req = engine.submit(served["prompts"][3], max_new_tokens=6,
                            greedy=True)
        for _ in range(200):
            if not engine.step():
                break
        assert req.tokens == served["reqs"][3].tokens
        assert engine.stats().kernel_path == mla.LAX_PATH
    finally:
        engine.close()

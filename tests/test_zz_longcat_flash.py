"""LongCat-Flash's language model (``LongCat-Flash-Omni``) on the serving
path: shortcut layers of two absorbed latent attentions over two paged
leaves, two dense parts and an expert layer whose router is wider than the
experts with weights, against the benchmark's plain float32 reference in the
published, non-absorbed form (logits, not tokens); the identity term, the
counts, the expert shares, the seam the engine sizes its pool through, and
the model through ``PagedInferenceEngine`` with every mechanism a latent
leaf serves or refuses. Tiny widths, seeded weights, CPU, Pallas kernels
interpreted (``tests/conftest.py``). The file's name sorts last on purpose
(``tests/test_zz_deepseek_v3.py`` says why)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import longcat_flash as ref
from lzy_tpu.models import deepseek_v3 as ds
from lzy_tpu.models import longcat_flash as lc
from lzy_tpu.models import serving
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mla
from lzy_tpu.serving import PagedInferenceEngine
from lzy_tpu.utils import trace
from lzy_tpu.utils.metrics import REGISTRY

#: float32 everywhere at the tiny size: program and reference differ by the
#: order of their sums and by the algebraic form of the attentions alone
TOL = 2e-4
PAGE = 4


def _unit_scale(params):
    """The initialiser's normal(0.02) preserves variance at the published
    widths; at the tiny ones it would shrink every sublayer's output to
    nothing and a wrong expert or a lost page would hide under the
    tolerance. Rescale each matrix to fan_in ** -0.5, and the router to
    three times that (softmax logits of deviation 3: the five chosen carry
    most of the mass, so the weights are worth seeing)."""
    def fix(path, leaf):
        name = path[-1].key
        if name in ("kernel", "experts_gate", "experts_up", "experts_down"):
            return leaf * (leaf.shape[-2] ** -0.5 / 0.02)
        if name == "router":
            return leaf * (3.0 * leaf.shape[-2] ** -0.5 / 0.02)
        if name == "kv_b_proj":
            return leaf * (leaf.shape[0] ** -0.5 / 0.02)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = lc.LongcatFlashConfig.tiny()
    return cfg, _unit_scale(lc.init_params(cfg, jax.random.PRNGKey(1)))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _counts(stats):
    return np.asarray(sum(jax.tree_util.tree_leaves(stats)))


# -- the model against the reference ------------------------------------------

def test_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = jnp.asarray([_tokens(2, 40, cfg.vocab_size)])
    got, seen = lc.LongcatFlash(cfg).apply(
        {"params": params}, toks, mutable=["stats", "intermediates"])
    want = ref.reference_logits(params, toks, jnp.arange(40), cfg)
    assert np.abs(got[0] - want).max() < TOL
    chosen = np.asarray(seen["intermediates"]["layer_1_moe"]["chosen"][0])
    assert chosen.shape == (40, cfg.top_k)
    # uncached, the attentions sow nothing; the two expert layers do
    total = _counts(seen["stats"])
    assert total.shape == (len(lc.LongcatFlash.STATS),) == (9,)
    assert list(total[[0, 3, 4, 5]]) == [40 * cfg.top_k * 2,
                                         cfg.n_held * 2, 0, 0]
    first = np.asarray(seen["intermediates"]["layer_0_moe"]["chosen"][0])
    assert total[6] == (chosen >= 16).sum() + (first >= 16).sum() > 0


def test_the_references_rotary_is_the_programs():
    from lzy_tpu.models.llama import _rope

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(20, 29)
    assert np.abs(np.asarray(_rope(x, pos[None], 1e7)[0])
                  - np.asarray(ref.rotary(x[0], pos, 1e7))).max() < 1e-6


def _fresh_cache(model, table):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((table.shape[0], 1), jnp.int32),
                               page_table=table))["cache"])


def _rewind(cache, by):
    """The engine rewinds a padded index."""
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: leaf - by if p[-1].key == "index" else leaf, cache)


@pytest.mark.parametrize("kernel", ["pallas", "lax"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        tiny, kernel):
    """Logits, not tokens: a prefill chunk over two pages, a padded chunk
    across a page boundary, then one position at a time over three more
    page boundaries, each through the two paged latent leaves a layer."""
    cfg, params = tiny
    model = cfg.paged_model(page_size=PAGE, kv_pages=12, kernel=kernel,
                            kv_quant=None)
    toks = _tokens(3, 27, cfg.vocab_size)
    want = np.asarray(ref.reference_logits(
        params, jnp.asarray([toks + [0]]), jnp.arange(27), cfg))
    table = jnp.asarray([[5, 2, 7, 9, 3, 11, 1, 0]], jnp.int32)
    cache = _fresh_cache(model, table)

    def run(cache, chunk, real):
        pad = chunk + [0] * (8 - len(chunk)) if len(chunk) > 1 else chunk
        logits, upd = model.apply(
            {"params": params, "cache": cache}, jnp.asarray([pad]),
            page_table=table, valid_len=jnp.asarray([real], jnp.int32),
            mutable=["cache", "stats"])
        cache = _rewind(upd["cache"], len(pad) - real)
        return cache, np.asarray(logits[0, :real]), _counts(upd["stats"])

    got = []
    cache, out, _ = run(cache, toks[:8], 8)
    got.append(out)
    cache, out, counts = run(cache, toks[8:13], 5)           # padded to 8
    got.append(out)
    # the last real query of the chunk sits at 12 and reads 13, an attention
    assert list(counts[4:6]) == [13 * cfg.kv_layers, cfg.kv_layers]
    for tok in toks[13:]:
        cache, out, _ = run(cache, [tok], 1)
        got.append(out)
    assert np.abs(np.concatenate(got) - want).max() < TOL
    # the two leaves of a layer are written apart: different vectors at the
    # same (page, offset), and each attention reads its own
    first = np.asarray(cache["layer_0_attn_0"]["latent"])
    second = np.asarray(cache["layer_0_attn_1"]["latent"])
    assert np.abs(first[5] - second[5]).max() > 0.1
    swapped = dict(cache, layer_0_attn_0=cache["layer_0_attn_1"],
                   layer_0_attn_1=cache["layer_0_attn_0"])
    a, _ = model.apply({"params": params, "cache": cache},
                       jnp.asarray([[7]]), page_table=table,
                       valid_len=jnp.asarray([1]), mutable=["cache", "stats"])
    b, _ = model.apply({"params": params, "cache": swapped},
                       jnp.asarray([[7]]), page_table=table,
                       valid_len=jnp.asarray([1]), mutable=["cache", "stats"])
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 100 * TOL


def test_absorbed_is_expanded_with_both_scale_corrections(tiny):
    """One attention alone: the program's absorbed form, uncached, against
    the reference's expanded one; and neither is itself without a
    correction (the variance the corrections restore is not 1)."""
    cfg, params = tiny
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(1, 16, cfg.d_model)).astype(np.float32))
    w = params["layer_1_attn_1"]
    got = lc.LatentAttention(cfg).apply({"params": w}, x)[0]
    one = jnp.ones((cfg.d_model,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        # the reference norms its input first: a norm of scale 1 and eps 0
        # over rows scaled to unit mean square is the identity
        xn = x[0] / jnp.sqrt(jnp.mean(x[0] ** 2, -1, keepdims=True))
        want = ref.attention(xn, one, w, cfg=dataclasses.replace(
            cfg, norm_eps=0.0), dt=jnp.dtype(jnp.float32)) - xn
        got_n = lc.LatentAttention(cfg).apply({"params": w}, xn[None])[0]
    assert np.abs(got_n - want).max() < TOL
    assert (cfg.d_model / cfg.q_lora_rank) ** 0.5 > 1.5
    assert (cfg.d_model / cfg.kv_lora_rank) ** 0.5 > 1.4
    assert got.shape == (16, cfg.d_model)


# -- the expert layer ---------------------------------------------------------

def _expert_layer(cfg, w, u, valid_len=None):
    out, seen = lc.ShortcutExperts(cfg).apply(
        {"params": w}, u, valid_len, mutable=["stats", "intermediates"])
    return np.asarray(out), _counts(seen["stats"]), np.asarray(
        seen["intermediates"]["chosen"][0])


def test_the_three_counts_are_the_counts_by_hand(tiny):
    cfg, params = tiny
    w = params["layer_0_moe"]
    u = jnp.asarray(np.random.default_rng(6).normal(
        size=(3, 5, cfg.d_model)).astype(np.float32))
    valid = jnp.asarray([5, 2, 0], jnp.int32)      # pads, and an idle row
    out, counts, chosen = _expert_layer(cfg, w, u, valid)
    real = (np.arange(5)[None] < np.asarray(valid)[:, None]).reshape(-1)
    logits = np.asarray(u, np.float64).reshape(15, -1) @ np.asarray(
        w["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    by_hand = np.argsort(-(p + np.asarray(w["router_bias"])), axis=-1)[
        :, :cfg.top_k]
    assert (np.sort(by_hand, -1) == np.sort(chosen, -1)).all()
    picked = np.take_along_axis(p, by_hand, -1) * cfg.routed_scaling
    on_zero = (by_hand >= cfg.n_weighted) & real[:, None]
    held = (by_hand < cfg.experts_held[1]) & real[:, None]
    assert counts[0] == real.sum() * cfg.top_k == 35
    assert counts[1] == held.sum()
    assert counts[6] == on_zero.sum() > 0
    assert abs(counts[7] - 1000 * picked[on_zero].sum()) <= 1
    assert abs(counts[8] - 1000 * picked[real].sum()) <= 1
    # not renormalised: a row's weights do not add up to the scale
    assert np.abs(picked.sum(-1) - cfg.routed_scaling).min() > 0.05
    # pads and the idle row: no routed part, no identity term
    assert np.abs(out.reshape(15, -1)[~real]).max() == 0.0


def test_a_row_of_identity_choices_gets_z_u_and_reads_no_expert(tiny):
    """A router whose identity outputs outscore every expert with weights:
    every choice is an identity expert, the layer's result is ``z u`` with
    ``z`` the chosen weights' sum, no held expert is touched, and the
    kernel's walk is empty."""
    cfg, params = tiny
    w = dict(params["layer_0_moe"])
    tilt = jnp.where(jnp.arange(cfg.n_routed_experts) >= cfg.n_weighted,
                     4.0, -4.0)
    w["router_bias"] = w["router_bias"] + tilt
    u = jnp.asarray(np.random.default_rng(7).normal(
        size=(2, 3, cfg.d_model)).astype(np.float32))
    out, counts, chosen = _expert_layer(cfg, w, u)
    assert (chosen >= cfg.n_weighted).all()
    assert list(counts[[1, 2, 6]]) == [0, 0, 6 * cfg.top_k]
    p = np.asarray(jax.nn.softmax(
        np.asarray(u).reshape(6, -1) @ np.asarray(w["router"]), axis=-1))
    z = cfg.routed_scaling * np.take_along_axis(p, chosen, -1).sum(-1)
    assert np.abs(out.reshape(6, -1)
                  - z[:, None] * np.asarray(u).reshape(6, -1)).max() < 1e-5
    assert abs(counts[7] - counts[8]) <= 1 and counts[7] > 0
    # the other way about: no identity choice, no identity term
    w["router_bias"] = params["layer_0_moe"]["router_bias"] - tilt
    _, counts, chosen = _expert_layer(cfg, w, u)
    assert (chosen < cfg.n_weighted).all() and list(counts[6:8]) == [0, 0]


def test_the_shares_add_up(tiny):
    """Four chips hold 4 of the 16 experts with weights each. What each
    computes for the layer, **with the identity term (which the chip a row
    lives on computes, whole) counted once**, adds up to the uncut layer: in
    the program, and to the reference's uncut layer."""
    cfg, params = tiny
    layer = params["layer_1_moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 24, cfg.d_model)).astype(np.float32))
    big = ("experts_gate", "experts_up", "experts_down")
    whole = lc.init_params(
        dataclasses.replace(cfg, experts_held=(0, 16), n_layers=1),
        jax.random.PRNGKey(9))["layer_0_moe"]
    layer = dict(layer, **{n: whole[n] * (whole[n].shape[-2] ** -0.5 / 0.02)
                           for n in big})

    def cut(lo, hi):
        c = dataclasses.replace(cfg, experts_held=(lo, hi))
        return c, dict(layer, **{n: layer[n][lo:hi] for n in big})

    def program(lo, hi):
        c, w = cut(lo, hi)
        return _expert_layer(c, w, u)[0][0]

    def reference(lo, hi):
        c, w = cut(lo, hi)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.shortcut_experts(u[0], w, cfg=c))

    with jax.default_matmul_precision("highest"):
        identity = np.asarray(ref.route(u[0], layer, cfg)[1])[:, None] \
            * np.asarray(u[0])
    assert np.abs(identity).max() > 0.05
    uncut = reference(0, 16)
    for layer_fn in (program, reference):
        shares = [layer_fn(lo, lo + 4) for lo in range(0, 16, 4)]
        summed = sum(s - identity for s in shares) + identity
        assert np.abs(summed - uncut).max() < TOL
        # a share alone is not the layer: the cut is real
        assert np.abs(shares[0] - uncut).max() > 10 * TOL
    assert np.abs(program(4, 8) - reference(4, 8)).max() < TOL


def test_an_idle_slot_and_a_pad_write_only_scratch(tiny):
    """A decode round of three slots, one idle (table all scratch, no real
    position), and a padded chunk: the pages rows own are untouched by the
    idle slot and the pads, the identity term and the counts leave them
    out."""
    cfg, params = tiny
    model = cfg.paged_model(page_size=PAGE, kv_pages=8, kernel="pallas",
                            kv_quant=None)
    table = jnp.asarray([[1, 2, 0], [0, 0, 0], [3, 4, 0]], jnp.int32)
    cache = _fresh_cache(model, table)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.asarray([2, 5, 6], jnp.int32)
        if p[-1].key == "index" else leaf, cache)
    toks = jnp.asarray([[3], [9], [4]])
    _, upd = model.apply(
        {"params": params, "cache": cache}, toks, page_table=table,
        valid_len=jnp.asarray([1, 0, 1], jnp.int32),
        mutable=["cache", "stats"])
    counts = _counts(upd["stats"])
    assert counts[0] == 2 * cfg.top_k * cfg.n_layers
    assert list(counts[4:6]) == [(3 + 7) * cfg.kv_layers, 2 * cfg.kv_layers]
    for name in ("layer_0_attn_0", "layer_1_attn_1"):
        pool = np.asarray(upd["cache"][name]["latent"])
        written = {(int(b), int(o)) for b, o in zip(*np.nonzero(
            np.abs(pool).sum(-1)))}
        # row 0 at position 2 (page 1, offset 2), row 2 at 6 (page 4, 2);
        # the idle slot's position 5 falls in the scratch block
        assert written - {(0, 1)} == {(1, 2), (4, 2)}
    # the same round with the idle slot live counts it
    _, upd = model.apply(
        {"params": params, "cache": cache}, toks,
        page_table=table.at[1].set(jnp.asarray([5, 6, 0])),
        valid_len=jnp.asarray([1, 1, 1], jnp.int32),
        mutable=["cache", "stats"])
    assert _counts(upd["stats"])[0] == 3 * cfg.top_k * cfg.n_layers
    assert _counts(upd["stats"])[8] > counts[8]


# -- the precision guards -----------------------------------------------------

def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_router_tells_apart_scores_that_tie_in_bfloat16():
    """Two outputs whose softmax scores differ in the fifth digit at the
    edge of the choice: float32 scores pick the larger; rounded to bfloat16
    they tie. The activations' dtype is bfloat16 here, as it is served."""
    cfg = dataclasses.replace(lc.LongcatFlashConfig.tiny(),
                              dtype=jnp.bfloat16)
    layer = lc.ShortcutExperts(cfg)
    u = jnp.zeros((1, 1, cfg.d_model), jnp.float32).at[0, 0, 0].set(1.0)
    params = dict(layer.init(jax.random.PRNGKey(0), u)["params"])
    logits = np.linspace(-3.0, -2.0, cfg.n_routed_experts).astype(np.float32)
    logits[[0, 1, 2, 20]] = 2.0, 1.5, 1.0, 0.5     # four clear choices
    logits[3], logits[4] = 0.1000, 0.1004          # the fifth: output 4
    p = np.exp(logits.astype(np.float64))
    p /= p.sum()
    assert _bf16(p[3]) == _bf16(p[4])
    params["router"] = jnp.zeros_like(params["router"]).at[0].set(logits)
    params["router_bias"] = jnp.zeros_like(params["router_bias"])
    _, seen = layer.apply({"params": params}, u.astype(cfg.dtype),
                          mutable=["intermediates", "stats"])
    chosen = set(np.asarray(seen["intermediates"]["chosen"][0]).ravel())
    assert chosen == {0, 1, 2, 4, 20}


def test_a_bfloat16_reference_fails_the_tolerance(tiny):
    """The control (the reference wholly in bfloat16) is not within the
    tolerance the program is held to."""
    cfg, params = tiny
    toks = jnp.asarray([_tokens(4, 40, cfg.vocab_size)])
    exact = np.asarray(ref.reference_logits(params, toks, jnp.arange(40),
                                            cfg))
    control = np.asarray(ref.reference_logits(params, toks, jnp.arange(40),
                                              cfg, jnp.bfloat16))
    assert np.abs(control - exact).max() > 20 * TOL


# -- the seam -----------------------------------------------------------------

def test_every_documented_name_is_answered():
    import re

    doc = serving.__doc__.split("**The module class**")[0]
    names = re.findall(r"^- ``(\w+)", doc, re.M)
    cfg = lc.LongcatFlashConfig.tiny()
    for name in names + ["max_seq_len", "vocab_size", "dtype", "n_heads"]:
        assert hasattr(cfg, name), name
    assert not hasattr(cfg, "n_kv_heads") and not hasattr(cfg, "head_dim")
    assert "longcat_flash" in serving.__doc__


def test_two_leaves_a_layer_are_counted_and_priced(tiny):
    cfg, params = tiny
    full = lc.LongcatFlashConfig()
    assert full.kv_layers == 56 and full.kv_token_bytes() == 1280
    assert dataclasses.replace(full, n_layers=4).kv_layers == 8
    # 4 leaves x 128 lanes x 4 bytes: 2048 bytes a token, 8192 a page of 4
    assert cfg.kv_layers == 4 and cfg.kv_layers * cfg.kv_token_bytes() \
        == 2048
    engine = PagedInferenceEngine(cfg, params, slots=1, page_size=PAGE,
                                  kernel="lax", kv_pool_bytes=10 * 8192 + 5)
    try:
        assert engine._kv_blocks == 10
        assert engine.stats().kv_token_bytes == 2048
        assert engine.kernel_path == mla.LAX_PATH
        kinds = engine._leaf_kinds
        assert kinds.count(serving.PAGED) == 4 and not engine._has_state
        assert all(leaf.shape == (10, PAGE, 128)
                   for leaf in engine._payload)
    finally:
        engine.close()


def _published():
    return {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}


def test_the_published_keys_give_the_name_its_count():
    """560.7 B parameters, 27 B of them active a token under uniform routing
    (8 of a token's 12 choices are experts with weights), shapes only."""
    cfg = lc.LongcatFlashConfig.from_published(_published())
    assert cfg == lc.LongcatFlashConfig()
    assert cfg.n_routed_experts == 768 and cfg.n_weighted == 512
    shapes = jax.eval_shape(lambda: lc.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 1e9 - 560.7) < 0.1
    expert = 3 * 6144 * 2048
    active = total - 28 * (512 - 8) * expert - 131072 * 6144
    assert abs(active / 1e9 - 27.2) < 0.1
    # the benchmark's cut: 16 of the 512 held under the published router
    cut = lc.LongcatFlashConfig.from_published(dict(
        _published(), n_routed_experts=16, router_width=768,
        experts_held_from=32, num_layers=4, vocab_size=16384))
    assert cut.experts_held == (32, 48) and cut.n_routed_experts == 768
    assert cut.n_weighted == 512 and cut.kv_layers == 8


@pytest.mark.parametrize("key,value", [
    ("attention_method", "MHA"), ("zero_expert_type", "copy"),
    ("mla_scale_q_lora", False), ("mla_scale_kv_lora", False),
    ("attention_bias", True),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("tie_word_embeddings", True), ("q_lora_rank", None)])
def test_what_the_program_cannot_honour_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        lc.LongcatFlashConfig.from_published(dict(_published(),
                                                  **{key: value}))


def test_kernels_lower_for_a_tpu_at_published_widths():
    """No device and no compile: both latent reads at 64 heads over a pool
    of 128 pages a slot at 128 slots, and the gated experts at 6144 x 2048
    at 128 and 256 rows (tiles of 6144 x 128, the narrowest)."""
    cfg = dataclasses.replace(lc.LongcatFlashConfig(), experts_held=(0, 16),
                              n_layers=4)
    cfg.check_kernels(slots=128, kv_blocks=4096, page_size=64,
                      pages_per_seq=128)
    assert gexp._tile(2048, 6144, 2) == 128
    with pytest.raises(ds.LatentPoolUnsupported, match="kv_quant"):
        cfg.check_kernels(slots=128, kv_quant="int8")
    with pytest.raises(ds.LatentPoolUnsupported, match="kv_quant"):
        cfg.paged_model(page_size=64, kv_pages=8, kernel="lax",
                        kv_quant="int8")


# -- through the engine -------------------------------------------------------

def _engine(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("slots", 3)
    kw.setdefault("kernel", "lax")
    kw.setdefault("prefill_chunk", 8)
    return PagedInferenceEngine(cfg, params, page_size=PAGE, **kw)


def _drain(engine, limit=600):
    for _ in range(limit):
        if not engine.step():
            return
    raise AssertionError("the engine did not go idle")


def _gap(tiny, prompt, tokens):
    """How far below the reference's best logit each served token sits."""
    cfg, params = tiny
    full = list(prompt) + list(tokens)
    pad = -len(full) % 8
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


_LENGTHS, _BUDGETS = (21, 5, 30, 13, 9, 18), (9, 12, 6, 10, 8, 4)
_COUNTED = tuple(c.name for c in lc.LongcatFlash.STATS)


@pytest.fixture(scope="module")
def served(tiny):
    """One engine, one mixed run: prompts whose last chunk is padded and
    not, a budget that splits the long prompts over rounds while the short
    ones already decode, more requests than slots, several pages a row."""
    cfg, _ = tiny
    engine = _engine(tiny, prefill_chunk=16, prefill_budget=16,
                     kernel="pallas")
    engine.warmup()
    before = {n: _counter(n) for n in _COUNTED}
    prompts = [_tokens(10 + i, n, cfg.vocab_size)
               for i, n in enumerate(_LENGTHS)]
    with trace.recording() as rec:
        reqs = [engine.submit(p, max_new_tokens=m, greedy=True)
                for p, m in zip(prompts, _BUDGETS)]
        _drain(engine)
        spans = rec.drain()
    after = {n: _counter(n) for n in before}
    yield {"engine": engine, "prompts": prompts, "reqs": reqs,
           "spans": spans,
           "counted": {n: after[n] - before[n] for n in before}}
    engine.close()


@pytest.mark.parametrize("i", range(6))
def test_engine_serves_the_references_tokens(tiny, served, i):
    req, prompt = served["reqs"][i], served["prompts"][i]
    assert req.done and req.error is None
    assert len(req.tokens) == _BUDGETS[i]
    assert _gap(tiny, prompt, req.tokens) < TOL


def test_one_fence_a_round_carries_the_counts(tiny, served):
    cfg, _ = tiny
    engine, counted = served["engine"], served["counted"]
    assert engine.host_fetches == engine.decode_steps
    assert counted["lzy_moe_assignments_total"] \
        == engine.decode_rows * cfg.top_k * cfg.n_layers
    assert counted["lzy_moe_experts_held_total"] \
        == engine.decode_steps * cfg.n_held * cfg.n_layers
    # four reads a round at the tiny size: two attentions a layer
    assert counted["lzy_mla_rows_total"] \
        == engine.decode_rows * cfg.kv_layers
    want = sum(sum(range(n + 1, n + m)) for n, m in zip(_LENGTHS, _BUDGETS))
    assert counted["lzy_mla_context_tokens_total"] == want * cfg.kv_layers
    assert 0 < counted["lzy_moe_zero_assignments_total"] \
        < counted["lzy_moe_assignments_total"]
    assert 0 < counted["lzy_moe_zero_weight_milli_total"] \
        < counted["lzy_moe_weight_milli_total"]
    emits = [s for s in served["spans"] if s.name == "engine.decode.emit"]
    assert emits and all(
        "rows" in s.attrs and set(s.attrs["model_stats"]) == set(_COUNTED)
        for s in emits)


def test_kernel_paths_are_counted(served):
    text = REGISTRY.exposition()
    for path in (mla.DECODE_PATH, mla.PREFILL_PATH, gexp.PATH):
        assert f'lzy_kernel_dispatch_total{{path="{path}"}}' in text
    assert served["engine"].stats().kernel_path == mla.DECODE_PATH


def test_a_radix_hit_gives_the_logits_of_a_cold_prefill(tiny):
    """The family takes the prefix cache: a prompt that shares 16 tokens
    (four pages of each of the four leaves) with a finished one skips their
    prefill, and what it serves sits as close to the reference as a cold
    engine's."""
    cfg, _ = tiny
    shared = _tokens(60, 16, cfg.vocab_size)
    first = shared + _tokens(61, 5, cfg.vocab_size)
    second = shared + _tokens(62, 7, cfg.vocab_size)
    warm = _engine(tiny, slots=1)
    a = warm.submit(first, max_new_tokens=6, greedy=True)
    _drain(warm)
    assert warm.kv.reuse and warm.kv.hit_tokens == 0
    b = warm.submit(second, max_new_tokens=8, greedy=True)
    _drain(warm)
    assert warm.kv.hit_tokens == 16
    assert warm.stats().prefill_tokens_saved == 16
    cold = _engine(tiny, slots=1)
    c = cold.submit(second, max_new_tokens=8, greedy=True)
    _drain(cold)
    assert cold.kv.hit_tokens == 0
    assert b.tokens == c.tokens
    assert _gap(tiny, first, a.tokens) < TOL
    assert _gap(tiny, second, b.tokens) < TOL
    warm.close()
    cold.close()


def test_llm_generate_through_the_gateway(tiny):
    from lzy_tpu import llm
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)

    cfg, _ = tiny
    fleet = ReplicaFleet(lambda: _engine(tiny, slots=2))
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(PAGE),
                             model_name="longcat-tiny", page_size=PAGE)
    try:
        fleet.add_replica()
        llm.configure(gateway)
        prompt = _tokens(40, 19, cfg.vocab_size)
        gen = llm.generate(prompt, max_new_tokens=7, greedy=True,
                           cache=False)
        assert gen.status == "ok" and len(gen.tokens) == 7
        assert _gap(tiny, prompt, list(gen.tokens)) < TOL
    finally:
        llm.configure(None)
        gateway.close()


# -- each mechanism works over the two leaves, or refuses by name -------------

@pytest.mark.parametrize("mechanism", [
    "speculation", "parking", "export", "host tier"])
def test_each_mechanism_a_paged_leaf_serves_is_taken(tiny, mechanism):
    cfg, _ = tiny
    if mechanism == "speculation":
        engine = _engine(tiny, slots=2, spec_tokens=2, kernel="pallas")
        prompt = (_tokens(70, 6, cfg.vocab_size) * 4)[:21]
        req = engine.submit(prompt, max_new_tokens=10, greedy=True)
        _drain(engine)
        assert engine.spec_steps > 0 and len(req.tokens) == 10
        assert _gap(tiny, prompt, req.tokens) < TOL
        engine.close()
    elif mechanism == "parking":
        engine = _engine(tiny, slots=1)
        prompt = _tokens(71, 20, cfg.vocab_size)
        req = engine.submit(prompt, max_new_tokens=4, greedy=True)
        _drain(engine)
        assert engine.park_chain("conv:1", prompt + list(req.tokens))
        _drain(engine, limit=5)
        assert engine.stats().kv_parked_chains == 1
        assert engine.stats().kv_parked_blocks >= 2
        engine.close()
    elif mechanism == "export":
        prompt = _tokens(72, 19, cfg.vocab_size)
        source = _engine(tiny, slots=1)
        a = source.submit(prompt, max_new_tokens=5, greedy=True)
        _drain(source)
        export = source.kv_io.export_kv(prompt)
        assert export is not None and len(export.tokens) == 16
        # the two attentions' leaves of each layer, by block id
        assert len(export.leaves) == 4 and all(
            leaf.shape == (4, PAGE, 128) for leaf in export.leaves.values())
        target = _engine(tiny, slots=1)
        assert target.kv_io.import_kv(export) == 4
        b = target.submit(prompt, max_new_tokens=5, greedy=True)
        _drain(target)
        assert target.stats().prefill_tokens_saved == 16
        assert b.tokens == a.tokens
        source.close()
        target.close()
    else:
        engine = _engine(tiny, slots=1, kv_blocks=9,
                         kv_host_tier_bytes=1 << 20)
        a = _tokens(73, 25, cfg.vocab_size)
        b = _tokens(74, 21, cfg.vocab_size)
        first = engine.submit(a, max_new_tokens=6, greedy=True)
        _drain(engine)
        engine.submit(b, max_new_tokens=6, greedy=True)  # evicts a's pages
        _drain(engine)
        assert engine.kv_tier.stats()["demotions"] > 0
        again = engine.submit(a, max_new_tokens=6, greedy=True)
        _drain(engine)
        assert engine.kv_tier.stats()["promotions"] > 0
        assert again.tokens == first.tokens
        assert _gap(tiny, a, again.tokens) < TOL
        engine.close()


@pytest.mark.parametrize("mechanism", ["int8 pool", "sharded engine"])
def test_each_refusal_names_its_mechanism(tiny, mechanism):
    cfg, params = tiny
    if mechanism == "int8 pool":
        with pytest.raises(ds.LatentPoolUnsupported, match="kv_quant"):
            _engine(tiny, kv_quant="int8")
    else:
        from lzy_tpu.serving.sharded import (
            NoPartitionRules, ShardedPagedInferenceEngine)

        with pytest.raises(NoPartitionRules, match="sharded engine"):
            ShardedPagedInferenceEngine(cfg, params, tp=2, slots=2)


def test_the_engine_names_no_model():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "lzy_tpu", "serving", "engine.py")) as f:
        text = f.read().lower()
    for word in ("longcat", "shortcut", "zero_expert", "identity expert"):
        assert word not in text, word

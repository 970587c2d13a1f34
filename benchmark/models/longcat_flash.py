"""LongCat-Flash-Omni's language model (LongCat-Flash: shortcut-connected
layers) as the benchmark has to know it: the program's side, the plain
reference, the counts. A configuration file says ``"model": "longcat_flash"``
(``benchmark/models/__init__.py`` lists the names a model file gives).

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision, in the published,
**non-absorbed** form, with no cache: the program serves the absorbed form
through two paged latent leaves a layer, so the comparison is between two
algebraic forms of each attention. It imports nothing from ``lzy_tpu.models``
or ``lzy_tpu.ops``: it reads the weights from the program's parameter tree by
name and does its own arithmetic. ``N`` is RMSNorm (eps 1e-5, learned scale);
no projection has a bias. A layer takes ``h`` ``[T, 6144]``:

    for i in (0, 1):
        a = h + MLA_i(N_in,i(h))
        u = N_post,i(a)
        if i == 0:  s = MoE(u)          # the shortcut
        h = a + FFN_i(u)
    h = h + s

- ``MLA(x)``, 64 heads: ``c_q = 2 N_q(x W_qa)`` (1536 wide; 2 =
  ``sqrt(6144 / 1536)``), ``q = c_q W_qb`` in ``64 x [q_nope (128) ; q_rope
  (64)]``; ``[c' ; k'] = x W_kva`` (512 + 64); ``c = 3.4641 N_kv(c')``
  (``sqrt(6144 / 512)``; ``k'`` passes through no norm and is not scaled);
  ``[k_nope_h ; v_h] = c W_kvb,h`` (**expanded**: every position's keys and
  values a head, 128 + 128); rotary (theta 1e7, value ``i`` paired with ``i
  + 32``) on ``q_rope`` and on ``k'``, one rotary key for all heads; scores
  ``(q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(192)``, causal softmax;
  ``W_o [o_1 .. o_64]``.
- ``FFN(u) = (silu(u W_g) * (u W_u)) W_d`` at width 12288.
- ``MoE(u)``: ``p = softmax(u W_r)`` over **768** outputs (512 experts with
  weights, then 256 identity experts); the 12 largest of ``p + bias``;
  ``w_e = 6 p_e``, not renormalised; ``sum over chosen e < 512 of w_e E_e(u)
  + (sum over chosen e >= 512 of w_e) u``, ``E_e`` a SwiGLU MLP of width
  2048. Dropless. **The share**: of the 512 experts with weights this chip
  holds ``experts_held``; a chosen expert outside it adds nothing, here as in
  the program; **the identity term is whole** (it belongs to no chip's share:
  the chip a row lives on computes it).
- final ``RMSNorm``, untied head over the vocabulary slice held.

Departures from the published implementation, all for memory or for the cut:
weights are upcast a matrix (an expert) at a time, never a layer; attention
runs over blocks of queries; the experts are a loop over the held ones, every
position through each (weight 0 where it did not choose it).
``reference_logits(..., dtype=bfloat16)`` is the **control**: the same
arithmetic with weights, activations, norms, router and softmaxes in bfloat16
at the default precision.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

#: **Two limits**, both on how far below the float32 reference's best logit
#: the served tokens sit (their *gap*; 0 where the program chose what the
#: reference would). A run's correctness requests are 4 x 128 decoded tokens
#: behind prompts of 379 / 651 / 1,018 / 1,932 tokens (``shortcut-steady``'s):
#: 512 judged positions behind 2 to 8 prefill chunks and 6 to 33 pages of
#: each of the eight latent leaves. ``CALIBRATION`` has the readings (my chip
#: runs, PR 67, one v5e chip, the published widths: fifteen runs, each its
#: own seed, weights and prompts, 7,680 tokens; PERF.md section 6).
#:
#: 1. ``GAP_RATIO``: over a run's judged tokens the program's mean gap may be
#:    at most 0.65 of the **control's own mean gap at the same positions**
#:    (the control: this reference wholly in bfloat16, weights, activations,
#:    router, norms and softmaxes, its choices judged behind the same served
#:    sequence). **This is the precision limit**, paired because a seed that
#:    is hard for one is hard for the other. The program keeps the norms, the
#:    router's product and softmax, the attention's softmax and the identity
#:    weights in float32 and rounds what a sublayer reads and returns; the
#:    control rounds all of it. The ratio of the two mean gaps, a run:
#:    0.117-0.369 over the fifteen (mean 0.240, deviation 0.065; thirteen
#:    runs under this PR's first initialiser, whose routing had collapsed,
#:    read 0.176-0.342). The control through the same comparison is 1 by
#:    construction, with no spread, and comes out not correct
#:    (``control_correct`` false in all twenty-eight); 0.65 stands 0.28 over
#:    the program's largest reading, 6 deviations over its mean, and 0.35
#:    under the control. On the CPU at the tiny size eleven of ISSUE 67's
#:    thirteen planted faults read 4.8 to 1,571 (PERF.md section 6 has the
#:    table); a bias added to the weights reads 13.6-28.5 where the bias is
#:    of the scores' order and 0.10-0.81 under this initialiser's, an order
#:    under them; a bfloat16 router moves hardly a choice there and read
#:    0.201 on the chip, inside the program's own range: no limit on logits
#:    separates those two, and tier 1 pins both (counts by hand, a planted
#:    near-tie).
#: 2. ``LOGIT_TIE_TOL``: no single token more than 4.7 below the best. The
#:    guard for what a mean cannot see: a token that is simply wrong (a
#:    chunk boundary, a page boundary, the second attention reading the
#:    first's leaf at one position, a slot's first position). **Derived from
#:    this model's own logits**: their deviation over the 16,384 rows reads
#:    1.566 on the chip (every run, to three digits), and the best of 16,384
#:    normal draws sits about 3.9 deviations, 6.1, over a token taken
#:    blindly; the limit is 3 deviations, which catches a blind token about
#:    eight times in ten, and three wrong tokens in a run move the mean gap
#:    (6.1 each over 512: 0.036 against the control's 0.015-0.026) past the
#:    first limit. It cannot sit much lower: the control's largest of 14,336
#:    tokens is 0.62 and the program's 0.28, but a near-tie in the router's
#:    12th place that falls the other way on a rounded input changes a
#:    layer's result, and in the other latent or routed models of this
#:    benchmark that tail reached 1.6 to 3.6 within a few dozen runs; one
#:    run over the limit refuses a check. This limit the control passes, as
#:    it may: it has to fail one of the cell's limits, not each.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``); ``held_to_both_limits`` says how the first limit
#: reaches it all the same (as ``benchmark/models/deepseek_v3.py``).
LOGIT_TIE_TOL = 4.7
GAP_RATIO = 0.65
GAP_RATIO_MIN_TOKENS = 500

#: the readings the limits were set from (my chip runs, PR 67, the corrected
#: initialiser; a run a place, in the order they were made): the judged
#: tokens that are not the reference's choice, the program's and the
#: control's; the ratio of the mean gaps; the largest gaps; the logits'
#: deviation
CALIBRATION = {
    "differ": [34, 33, 43, 28, 39, 40, 29, 42, 38, 41, 41, 46, 47, 34, 39],
    "control_differ": [69, 67, 67, 85, 92, 82, 63, 73, 80, 80, 83, 85, 92,
                       80, 81],
    "gap_ratio": [0.257, 0.226, 0.255, 0.117, 0.178, 0.211, 0.261, 0.350,
                  0.189, 0.176, 0.248, 0.369, 0.249, 0.274, 0.247],
    "worst_gap": [0.139, 0.140, 0.140, 0.130, 0.179, 0.142, 0.163, 0.210,
                  0.184, 0.107, 0.148, 0.227, 0.162, 0.179, 0.182],
    "control_worst_gap": [0.352, 0.341, 0.308, 0.394, 0.423, 0.372, 0.327,
                          0.302, 0.445, 0.344, 0.382, 0.324, 0.324, 0.320,
                          0.327],
    "logit_std": 1.566,
}

_QUERY_BLOCK = 128


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``LongcatFlashConfig``. A key the program cannot honour is refused (by
    the program's own ``from_published``)."""
    from lzy_tpu.models.longcat_flash import LongcatFlashConfig

    return LongcatFlashConfig.from_published(
        doc, dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in the type they are served in:
    the program's initialiser as it is, **a layer at a time** (one program
    that initialises every layer takes the chip's compiler minutes:
    ``benchmark/models/deepseek_v3.py``). The layers are alike, so one
    program over a model of one layer is compiled once and run under one key
    a layer: the first call gives the embedding, the head, the final norm
    and layer 0, every further call one more layer, renamed to its place."""
    from lzy_tpu.models import longcat_flash

    short = dataclasses.replace(cfg, n_layers=1)
    mine = re.compile(r"^layer_0(?=$|_)")
    whole = jax.jit(lambda key: longcat_flash.init_params(short, key))
    layer = jax.jit(lambda key: {
        k: v for k, v in longcat_flash.init_params(short, key).items()
        if mine.match(k)})
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)),
                            cfg.n_layers)
    params = dict(whole(keys[0]))
    for i in range(1, cfg.n_layers):
        for name, leaf in layer(keys[i]).items():
            params[mine.sub(f"layer_{i}", name)] = leaf
    if out_shardings is not None:
        params = jax.device_put(params, out_shardings)
    return jax.block_until_ready(params)


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(x.dtype)


def rotary(x, positions, theta):
    """``x`` [T, ..., D] rotated by its position: value ``i`` pairs with
    ``i + D/2``, frequencies ``theta^(-2i/D)``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs       # [T, D/2]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos],
                           axis=-1).astype(x.dtype)


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


@functools.partial(jax.jit, static_argnames=("cfg", "dt"))
def attention(x, norm, w, *, cfg, dt):
    """``x + MLA(N(x))`` over one sequence ``[T, hidden]``, the published
    form: the latent expanded into keys and values a head at every
    position. One attention's five matrices are upcast here (0.36 GB in
    float32 at the published widths)."""
    w = jax.tree_util.tree_map(lambda a: a.astype(dt), w)
    t = x.shape[0]
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pos = jnp.arange(t)
    u = _rms_norm(x, norm, cfg.norm_eps)
    c_q = _rms_norm(u @ w["q_a_proj"]["kernel"], w["q_a_norm"]["scale"],
                    cfg.norm_eps) * jnp.asarray(
                        (cfg.d_model / cfg.q_lora_rank) ** 0.5, dt)
    q = (c_q @ w["q_b_proj"]["kernel"]).reshape(t, h, dn + dr)
    kva = u @ w["kv_a_proj"]["kernel"]
    c = _rms_norm(kva[:, :r], w["kv_a_norm"]["scale"],
                  cfg.norm_eps) * jnp.asarray((cfg.d_model / r) ** 0.5, dt)
    kv = jnp.einsum("tr,rhx->thx", c, w["kv_b_proj"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_rope = q[..., :dn], rotary(q[..., dn:], pos, cfg.rope_theta)
    k_rope = rotary(kva[:, r:], pos, cfg.rope_theta)              # [T, dr]
    block = min(_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")

    def one(qs):
        """One block of queries against every position before them."""
        qn, qr, first = qs
        s = (jnp.einsum("qhn,lhn->hql", qn, k_nope)
             + jnp.einsum("qhr,lr->hql", qr, k_rope)) * (dn + dr) ** -0.5
        keep = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.einsum("hql,lhv->qhv", pr, v)

    out = jax.lax.map(one, (q_nope.reshape(-1, block, h, dn),
                            q_rope.reshape(-1, block, h, dr),
                            jnp.arange(0, t, block)))
    return (x + out.reshape(t, h * dv) @ w["o_proj"]["kernel"]).astype(dt)


@jax.jit
def _product(x, w):
    """One matrix, upcast alone."""
    return x @ w.astype(x.dtype)


def dense_ffn(u, w):
    """``(silu(u W_g) * (u W_u)) W_d``, a matrix at a time (one is 0.3 GB in
    float32 at the published widths)."""
    hid = jax.nn.silu(_product(u, w["gate_proj"]["kernel"])) \
        * _product(u, w["up_proj"]["kernel"])
    return _product(hid, w["down_proj"]["kernel"])


def route(u, w, cfg):
    """``([T, held], [T])``: each position's weight for each held expert (0
    where it did not choose it) and its summed weight for the identity
    experts it chose."""
    lo, hi = cfg.experts_held
    p = jax.nn.softmax(u @ w["router"].astype(u.dtype), axis=-1)
    _, chosen = jax.lax.top_k(p + w["router_bias"].astype(u.dtype),
                              cfg.top_k)
    picked = jnp.take_along_axis(p, chosen, axis=-1) * cfg.routed_scaling
    held = jnp.arange(lo, hi)
    weights = jnp.sum(jnp.where(chosen[:, :, None] == held[None, None, :],
                                picked[:, :, None], 0.0), axis=1)
    z = jnp.sum(jnp.where(chosen >= cfg.n_routed_experts - cfg.zero_experts,
                          picked, 0.0), axis=-1)
    return weights, z


def routed_experts(u, w, weights):
    """The held experts' part of the layer's result, ``[T, hidden]``: one
    expert's three matrices upcast at a time."""
    dt = u.dtype

    def one(acc, ew):
        wg, wu, wd, col = ew
        hid = jax.nn.silu(u @ wg.astype(dt)) * (u @ wu.astype(dt))
        return acc + (hid * col[:, None]) @ wd.astype(dt), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["experts_gate"], w["experts_up"], w["experts_down"],
         weights.astype(dt).T))
    return routed


@functools.partial(jax.jit, static_argnames=("cfg",))
def shortcut_experts(u, w, *, cfg):
    """``MoE(u)``: the held experts' weighted sum and the identity term."""
    weights, z = route(u, w, cfg)
    return routed_experts(u, w, weights) + z.astype(u.dtype)[:, None] * u


def layer(x, params, i, cfg, dt):
    """One shortcut layer over one sequence ``[T, hidden]``."""
    for j in (0, 1):
        a = attention(x, params[f"layer_{i}_norm_{j}"]["scale"],
                      params[f"layer_{i}_attn_{j}"], cfg=cfg, dt=dt)
        u = _rms_norm(a, params[f"layer_{i}_ffn_norm_{j}"]["scale"],
                      cfg.norm_eps)
        if j == 0:
            s = shortcut_experts(u, params[f"layer_{i}_moe"], cfg=cfg)
        x = (a + dense_ffn(u, params[f"layer_{i}_mlp_{j}"])).astype(dt)
    return (x + s).astype(dt)


def features(params, tokens, cfg, dtype=jnp.float32):
    """Hidden states before the final norm, ``[T, hidden]``, of one sequence
    ``tokens`` [1, T]."""
    dt = jnp.dtype(dtype)
    with _precision(dt):
        x = params["embed_tokens"][tokens[0]].astype(dt)
        for i in range(cfg.n_layers):
            x = layer(x, params, i, cfg, dt)
    return x


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    dt = jnp.dtype(dtype)
    x = features(params, tokens, cfg, dtype)[rows]
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
        return _product(x, params["lm_head"].T).astype(jnp.float32)


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def held_to_both_limits(exact, chosen, judged, judged_control) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings two limits, the
    first over all of a run's judged tokens. ``judged`` holds the gaps of
    the run's correctness requests so far, this one's among them, and
    ``judged_control`` the control's at the same positions. Where they are
    at least ``GAP_RATIO_MIN_TOKENS`` and the program's mean gap is over
    ``GAP_RATIO`` of the control's, every chosen token's logit is set ``2 x
    LOGIT_TIE_TOL`` under its row's best (as ``benchmark/models/motif.py``:
    lowering it by ``LOGIT_TIE_TOL`` alone would leave a row whose token is
    the reference's own choice just inside the limit): the largest gap the
    harness then reads is over its limit and the run comes out not correct.
    So a ``worst_logit_gap`` near ``2 x LOGIT_TIE_TOL`` in a result's notes
    means the program sat no closer to the reference than ``GAP_RATIO`` of
    its bfloat16 control."""
    exact = np.array(exact, np.float32)
    chosen, judged = np.asarray(chosen), np.asarray(judged)
    if len(judged) >= GAP_RATIO_MIN_TOKENS \
            and np.mean(judged) > GAP_RATIO * np.mean(judged_control):
        exact[np.arange(len(chosen)), chosen] = \
            exact.max(axis=-1) - 2.0 * LOGIT_TIE_TOL
    return exact


def harness_says_correct(exact, chosen) -> bool:
    """The harness's own comparison of one request."""
    return bool(gaps(exact, chosen).max() <= LOGIT_TIE_TOL)


#: the gaps of this process's correctness requests so far, the program's and
#: the control's, one pair of arrays a request (a run is one process, and
#: the harness's only calls of ``logits_at`` are its correctness requests,
#: one after another)
_JUDGED: list = []


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request: ``tokens`` [1, T]
    is the prompt and the served tokens (padded), ``rows`` the positions
    whose logits chose them, so the served tokens are ``tokens[0, rows +
    1]``. The float32 reference's logits there, held to both limits over
    the run's requests so far. **The control is put through the same
    comparison** (its own tokens): ``control_correct`` on stderr says what
    the harness would have said of it."""
    rows = np.asarray(rows)
    exact = np.asarray(reference_logits(params, tokens, rows, cfg))
    served = np.asarray(tokens)[0, rows + 1]
    control = np.asarray(reference_logits(
        params, tokens, rows, cfg, jnp.bfloat16)).argmax(axis=-1)
    _JUDGED.append((gaps(exact, served), gaps(exact, control)))
    mine, ctrl = (np.concatenate(x) for x in zip(*_JUDGED))
    control_correct = harness_says_correct(
        held_to_both_limits(exact, control, ctrl, ctrl), control)
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"longcat_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((ctrl > 0).sum()),
        "worst_gap": float(mine.max()),
        "control_worst_gap": float(ctrl.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(ctrl.mean()),
        "gap_ratio": float(mine.mean() / max(ctrl.mean(), 1e-30)),
        "logit_std": float(exact.std(axis=-1).mean()),
        "control_correct": control_correct}}),
        file=sys.stderr, flush=True)
    return held_to_both_limits(exact, served, mine, ctrl)


# -- the counts: bytes a decode round must move, from shapes ------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg) -> int:
    """The latent vectors of one token of context, every leaf (two a layer):
    the 576 values (``c`` and the shared rotary key) a read needs, not the
    640 lanes the pool lays them out in."""
    return 2 * cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
        * _itemsize(cfg)


def latent_step_bytes(cfg, rows: float, mean_context: float) -> float:
    """What the latent reads of one decode round must move: the cached
    vectors of the context its rows read (``rows`` rows of ``mean_context``
    tokens each, both as the program counted them:
    ``lzy_mla_context_tokens_total / lzy_mla_rows_total`` a traced round),
    each once an attention, two attentions a layer. The rows' queries and
    results (128 KB a row an attention) are left out."""
    return rows * mean_context * kv_bytes_per_token(cfg)


def expert_bytes(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg.d_model * cfg.expert_width * _itemsize(cfg)


def _held(cfg) -> int:
    return cfg.experts_held[1] - cfg.experts_held[0]


def experts_step_bytes(cfg, rows: float, share: float) -> float:
    """What the grouped expert product of one decode round must read: the
    weights of the held experts its rows reached, an expert layer a layer.
    ``share`` is the share of the held experts reached as the program
    counted it over the traced rounds (``readers/counted_rows.py``): never
    the expectation under uniform routing. The identity term reads no
    weight."""
    return cfg.n_layers * _held(cfg) * share * expert_bytes(cfg)


def routed_param_bytes(cfg) -> int:
    return cfg.n_layers * _held(cfg) * expert_bytes(cfg)


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float, share: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight
    outside the routed experts once (the head's slice among them; the
    embedding table is a lookup of ``rows`` rows and is left out), the
    routed experts those rows reached (``share`` of the held ones, as the
    program counted it: ``readers/decode_counted_roofline.py``), and the
    latent vectors of the resident context, once an attention (a row at
    ``p``: ``2 x layers x 1,152 x (p + 1)`` bytes)."""
    embed = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    outside = param_bytes - routed_param_bytes(cfg) - embed
    return outside + experts_step_bytes(cfg, rows, share) \
        + kv_bytes_per_token(cfg) * resident_tokens

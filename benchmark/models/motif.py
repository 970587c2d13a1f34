"""Motif-3-Beta (``model_type`` ``Motif``) as the benchmark has to know it:
the program's side, the plain reference, the counts. A configuration file
says ``"model": "motif"``.

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision, in the published,
**non-absorbed** form, with no cache: the program serves the absorbed form
through two paged latent pools and takes the heads' difference in the latent,
so the comparison is between two algebraic forms. It imports nothing from
``lzy_tpu.models`` or ``lzy_tpu.ops``: it reads the weights from the
program's parameter tree by name and does its own arithmetic. A token's
residual is ``X`` in ``R^{4 x 4096}``, the embedding copied into the four
streams; ``N`` is RMSNorm, ``sigma`` the logistic function.

- **every sublayer** ``F``, with its own ``phi`` ``[24, 16384]`` (a quantity
  a row), ``alpha`` ``[3]``, ``b`` ``[24]``: ``x~ = vec(X) / rms(vec(X))``;
  ``H~ = alpha * (phi x~) + b``; ``Hpre = sigma(H~[:4])``, ``Hpost = 2
  sigma(H~[4:8])``, ``Hres`` = ``exp(H~[8:])`` as a 4 x 4 matrix under
  **twenty explicit sweeps** (each row divided by its sum, then each column
  by its sum); ``h = sum_i Hpre_i X_i``; ``y = clamp(F(N(h)), +-1e6)``;
  ``X_i <- sum_j Hres_ij X_j + Hpost_i y``.
- **attention**, both kinds: ``c_q = N_q(W_qa u)``, ``q_h = W_qb,h c_q =
  [q_nope ; q_rope]`` for 80 heads; ``[c' ; k'] = W_kva u``, ``c =
  N_kv(c')``; **expanded**: ``[k_nope_g ; v_g] = W_kvb,g c`` at every
  position, 16 key-value heads; rotary (value ``i`` paired with ``i + 32``,
  theta 1e4) on ``q_rope`` and ``k'``, one rotary key for all heads; **80
  softmaxes** of ``(q_nope_h . k_nope_g(h) + q_rope_h . k_rope) /
  sqrt(192)`` over the positions the layer's kind lets the query see (the
  window as a mask: itself and the 127 before it); heads ``4 g + i`` are
  group ``g``'s signal heads, head ``64 + g`` its noise head; **the
  difference is taken on the 128-wide head outputs**, ``o_g,i = A_(g,i) -
  sigma(W_lam u)_(g,i) A_(g,n)``; the 64 outputs times ``sigma(W_g u)``, one
  a channel; ``W_o``.
- **PolyNorm** over a width ``m``: ``P(z) = 0.5 (w_3 z^3 / rms(z^3) + w_2
  z^2 / rms(z^2) + w_1 z / rms(z) + clamp(b, +-0.5))``, eps 1e-6, each root
  over all ``m`` values. **Layer 0**: ``W_down(P(W_gate n) * W_up n)`` at
  12,288. **Layers >= 1**: ``s = sigmoid(W_r n)`` over 384 experts, the 8
  largest, weights ``s[chosen] / (sum + 1e-20)`` times 2; expert ``e`` the
  same gated MLP at 1,280 with its own four scalars; plus the shared expert.
  Dropless. **The share**: of the router's experts this chip holds
  ``experts_held``; a chosen expert outside it adds nothing, here as in the
  program.
- the streams summed, final ``RMSNorm``, untied head over the vocabulary
  slice held.

Departures from the published implementation, for memory or for the cut:
weights are upcast one layer (one expert) at a time; attention runs over
blocks of queries; the experts are a loop over the held ones.
``reference_logits(..., dtype=bfloat16)`` is the **control**: the same
arithmetic wholly in bfloat16 at the default precision: weights,
activations and streams, and also what the program keeps in float32 by
ISSUE 65's word (the connections' norm, projections, sigmoids and sweeps,
the router, the softmaxes, ``lam``, PolyNorm's powers and norms).
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

#: **Three limits** on the served tokens against the float32 reference, over
#: a run's correctness requests: 4 x 128 decoded tokens behind prompts of 611
#: to 2,645 tokens, 512 judged positions past the window of 128, behind 3 to
#: 11 prefill chunks and 10 to 44 latent pages. A token's *gap* is how far
#: below the reference's best logit it sits (0 where the program chose what
#: the reference would). **The control** is this reference wholly in
#: bfloat16 (weights, activations and streams, and also what the program
#: keeps in float32: the connections' norm, projections, sigmoids and
#: sweeps, the router, the softmaxes, ``lam``, PolyNorm's powers and norms),
#: its choices judged behind the same served sequence. ``CALIBRATION`` has
#: the readings (my chip runs, PR 65, one v5e chip, the published widths:
#: fourteen runs, each its own seed, weights and prompts, 7,168 tokens;
#: PERF.md section 6).
#:
#: 1. ``DIFFER_RATIO``: of a run's judged tokens, those that are **not the
#:    reference's own choice** may number at most 0.9 of **the control's at
#:    the same positions**. **This is the precision limit**, paired because
#:    nothing unpaired separates the two (a seed that is hard for one is hard
#:    for the other), and **a count because the mean gap is one token's
#:    matter**: a run's 512 gaps add up to about 1.0 and its largest single
#:    gap is 0.1-0.6 of that, so the ratio of mean gaps reads 0.17-0.79 over
#:    the fourteen runs (two runs at 0.78, both on one token of 0.54 and
#:    0.62) where the ratio of counts reads 0.31-0.60 (mean 0.46, deviation
#:    0.10: 12-29 tokens of 512 against the control's 28-56). The program
#:    keeps its four streams and every mix in float32 and rounds what a
#:    sublayer reads and returns; the control rounds the streams too, ten
#:    times a token. The control through the same comparison is 1 by
#:    construction, with no spread, and comes out not correct
#:    (``control_correct`` false in all fourteen); 0.9 stands 0.30 over the
#:    program's largest reading, 4.4 deviations over its mean, and 0.10
#:    under the control.
#: 2. ``GAP_RATIO``: the program's mean gap at most 2.0 times the control's.
#:    **Not a precision limit** (the readings above say why it cannot be):
#:    the guard for a program that is wrong and not merely rounded, which
#:    the count sees late (a wrong program that still picks the reference's
#:    token most of the time). On the CPU at the tiny size the planted
#:    faults of ISSUE 65 read 3.5 to 390 (PERF.md section 6 has the table);
#:    the program's largest reading is 0.79.
#: 3. ``LOGIT_TIE_TOL``: no single token more than 4.5 below the best. The
#:    guard for what neither ratio can see: one token that is simply wrong
#:    (a chunk boundary, a page boundary, the window's edge, a slot's first
#:    position). **Derived from this model's own logits**: their deviation
#:    over the 27,520 rows is 1.278 on the chip (every run, to three
#:    digits), so the best sits about 4 deviations, 5.1, above a token taken
#:    blindly; 4.5 is 3.5 deviations, which catches a blind token seven
#:    times in ten, and three wrong tokens in a run move the mean gap (5.1
#:    each over 512: 0.03 against the control's 0.003-0.014) past the second
#:    limit. It cannot sit much lower: the control's largest of 7,168 tokens
#:    is 1.03 and the program's 0.62 (a router's 8th place changing hands on
#:    a rounded input moves a logit that far, and in four other latent or
#:    routed models of this benchmark the tail reached 1.6 to 3.6 within a
#:    few dozen runs); one run over the limit refuses a check. This limit
#:    the control passes, as it may: it has to fail one of the cell's
#:    limits, not each.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``); ``held_to_the_limits`` says how the first two reach
#: it all the same (as ``benchmark/models/deepseek_v3.py``).
LOGIT_TIE_TOL = 4.5
DIFFER_RATIO = 0.9
GAP_RATIO = 2.0
RATIO_MIN_TOKENS = 500

#: the readings the limits were set from (my chip runs, PR 65; a run a
#: place, in the order they were made): the judged tokens that are not the
#: reference's choice, the program's and the control's; the ratio of the
#: mean gaps; the largest gaps; the logits' deviation
CALIBRATION = {
    "differ": [19, 21, 18, 21, 17, 26, 14, 17, 19, 13, 12, 29, 20, 14],
    "control_differ": [37, 40, 37, 35, 52, 45, 44, 43, 32, 28, 39, 56, 46,
                       36],
    "gap_ratio": [0.276, 0.165, 0.296, 0.357, 0.265, 0.238, 0.281, 0.372,
                  0.784, 0.786, 0.177, 0.402, 0.208, 0.186],
    "worst_gap": [0.18, 0.10, 0.48, 0.27, 0.55, 0.20, 0.62, 0.39, 0.54,
                  0.62, 0.11, 0.40, 0.26, 0.22],
    "control_worst_gap": [0.71, 0.38, 0.46, 0.33, 0.64, 0.52, 0.62, 0.25,
                          0.29, 0.39, 0.56, 1.03, 0.73, 0.49],
    "logit_std": 1.278,
}

_QUERY_BLOCK = 128
SLIDING = "sliding_attention"
POLYNORM_EPS = 1e-6


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``MotifConfig``. A key the program cannot honour is refused (by the
    program's own ``from_published``)."""
    from lzy_tpu.models.motif import MotifConfig

    return MotifConfig.from_published(
        doc, dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in the type they are served in:
    the program's initialiser as it is, **a layer at a time** (one program
    that initialises every layer takes the chip's compiler minutes:
    ``benchmark/models/deepseek_v3.py``). One call a layer, each under its
    own key, over a model of that layer alone (its kind, its feed-forward),
    renamed to its place; the embedding, the head and the final norm come
    with the first."""
    from lzy_tpu.models import motif

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)),
                            cfg.n_layers)
    params: dict = {}
    made: dict = {}
    for i, kind in enumerate(cfg.layer_types):
        dense = i < cfg.first_dense
        # a layer alone, first in a model of one or two layers: a model needs
        # a full layer, so a window layer stands second behind one
        full = kind == motif.FULL
        at = 0 if full else 1
        short = dataclasses.replace(
            cfg, n_layers=at + 1,
            layer_types=(kind,) if full else (motif.FULL, kind),
            first_dense=(at + 1 if dense else 0))
        mine = re.compile(rf"^layer_{at}(?=$|_)")
        build = made.get((kind, dense))
        if build is None:
            build = made[(kind, dense)] = jax.jit(functools.partial(
                lambda key, short: {
                    k: v for k, v in motif.init_params(short, key).items()
                    if mine.match(k) or not k.startswith("layer_")},
                short=short))
        for name, leaf in build(keys[i]).items():
            if mine.match(name):
                params[mine.sub(f"layer_{i}", name)] = leaf
            elif i == 0:
                params[name] = leaf
    if out_shardings is not None:
        params = jax.device_put(params, out_shardings)
    return jax.block_until_ready(params)


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, positions, theta):
    """``x`` [T, ..., D] rotated by its position: value ``i`` pairs with
    ``i + D/2``, frequencies ``theta^(-2i/D)``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs       # [T, D/2]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf1 = x[..., :d // 2].astype(jnp.float32)
    xf2 = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos],
                           axis=-1).astype(x.dtype)


def sinkhorn(raw, sweeps: int):
    """``raw`` [T, n, n] -> ``exp(raw)`` under ``sweeps`` explicit sweeps:
    each row divided by its sum, then each column by its sum."""
    m = jnp.exp(raw)
    for _ in range(sweeps):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def connection(x, w, cfg):
    """One sublayer's three mixes from the streams ``x`` [T, n, D] (in the
    type they are to be computed in): ``(Hpre [T, n], Hpost [T, n], Hres [T,
    n, n])``."""
    t, n, d = x.shape
    flat = x.reshape(t, n * d)
    unit = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + cfg.norm_eps)
    proj = unit @ w["phi"].astype(x.dtype).T                     # [T, 24]
    alpha = w["alpha"].astype(x.dtype)
    b = w["b"].astype(x.dtype)
    pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn((alpha[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(t, n, n),
                   cfg.mhc_sweeps)
    return pre, post, res


def sublayer(x, hc, norm_scale, fn, cfg, dt):
    """``fn`` behind its connection. ``x`` [T, n, D] the streams (float32 in
    the reference, the control's own type in the control)."""
    pre, post, res = connection(x, hc, cfg)
    h = jnp.einsum("tn,tnd->td", pre, x)
    y = fn(_rms_norm(h, norm_scale.astype(x.dtype), cfg.norm_eps).astype(dt))
    y = jnp.clip(y, -cfg.hidden_clamp, cfg.hidden_clamp).astype(x.dtype)
    return jnp.einsum("tij,tjd->tid", res, x) + post[:, :, None] * y[:, None]


def polynorm(z, p, cfg):
    """``P(z)`` over the last axis; ``p`` = ``(w_1, w_2, w_3, b)``."""
    p = p.astype(z.dtype)
    z2 = z * z

    def normed(v):
        return v * jax.lax.rsqrt(
            jnp.mean(v * v, axis=-1, keepdims=True) + POLYNORM_EPS)

    return cfg.polynorm_scale * (
        p[2] * normed(z2 * z) + p[1] * normed(z2) + p[0] * normed(z)
        + jnp.clip(p[3], -cfg.polynorm_clamp, cfg.polynorm_clamp))


def gated_mlp(u, w, cfg, acc):
    """``W_down(P(W_gate u) * W_up u)``; PolyNorm in ``acc`` (float32 in the
    reference, whatever the weights' type)."""
    act = polynorm((u @ w["gate_proj"]["kernel"]).astype(acc), w["polynorm"],
                   cfg)
    hid = act * (u @ w["up_proj"]["kernel"]).astype(acc)
    return hid.astype(u.dtype) @ w["down_proj"]["kernel"]


def _attention(u, w, cfg, dt, *, windowed: bool):
    """The published form: the latent expanded into 16 heads' keys and
    values at every position, 80 softmaxes, the difference on the heads'
    128-wide outputs."""
    t = u.shape[0]
    g, hs = cfg.n_noise_heads, cfg.n_heads - cfg.n_noise_heads
    per = hs // g
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    theta = cfg.swa_rope_theta if windowed else cfg.rope_theta
    pos = jnp.arange(t)
    # the softmaxes', lam's and the gate's type: float32 in the reference
    # whatever the weights' type; the control's own type
    acc = jnp.float32 if dt == jnp.float32 else dt
    c_q = _rms_norm(u @ w["q_a_proj"]["kernel"], w["q_a_norm"]["scale"],
                    cfg.norm_eps).astype(dt)
    q = (c_q @ w["q_b_proj"]["kernel"]).reshape(t, cfg.n_heads, dn + dr)
    kva = u @ w["kv_a_proj"]["kernel"]
    c = _rms_norm(kva[:, :r], w["kv_a_norm"]["scale"], cfg.norm_eps
                  ).astype(dt)
    kv = jnp.einsum("tr,rgx->tgx", c, w["kv_b_proj"])           # [T, 16, .]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = rotary(q[..., dn:], pos, theta)
    k_rope = rotary(kva[:, r:], pos, theta)                       # [T, dr]
    lam = jax.nn.sigmoid((u @ w["lambda_proj"]["kernel"]).astype(acc))
    gate = jax.nn.sigmoid((u @ w["gate_proj"]["kernel"]).astype(acc))
    block = min(_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")
    scale = (dn + dr) ** -0.5

    def one(qs):
        """One block of queries against every position before them."""
        qn, qr, first = qs
        at = first + jnp.arange(block)[:, None]
        keep = jnp.arange(t)[None, :] <= at
        if windowed:
            keep &= jnp.arange(t)[None, :] > at - cfg.window

        def read(qn, qr, heads):
            """``heads`` a group: [Q, 16, heads, .] -> [Q, 16, heads, dv]."""
            s = (jnp.einsum("qgin,lgn->giql", qn, k_nope)
                 + jnp.einsum("qgir,lr->giql", qr, k_rope)) * scale
            pr = jax.nn.softmax(
                jnp.where(keep, s.astype(acc), -1e30), axis=-1)
            return jnp.einsum("giql,lgv->qgiv", pr.astype(dt), v)

        signal = read(qn[:, :hs].reshape(block, g, per, dn),
                      qr[:, :hs].reshape(block, g, per, dr), per)
        noise = read(qn[:, hs:, None], qr[:, hs:, None], 1)
        return signal, noise

    signal, noise = jax.lax.map(one, (
        q[..., :dn].reshape(-1, block, cfg.n_heads, dn),
        q_rope.reshape(-1, block, cfg.n_heads, dr),
        jnp.arange(0, t, block)))
    signal = signal.reshape(t, g, per, dv).astype(acc)
    noise = noise.reshape(t, g, 1, dv).astype(acc)
    out = signal - lam.reshape(t, g, per, 1) * noise
    out = (out.reshape(t, hs * dv) * gate).astype(dt)
    return out @ w["o_proj"]["kernel"]


def route(u, w, cfg):
    """``[T, held]``: each position's weight for each held expert (0 where
    it did not choose it)."""
    lo, hi = cfg.experts_held
    scores = jax.nn.sigmoid(u @ w["router"].astype(u.dtype))
    _, chosen = jax.lax.top_k(scores, cfg.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling
    held = jnp.arange(lo, hi)
    return jnp.sum(jnp.where(chosen[:, :, None] == held[None, None, :],
                             picked[:, :, None], 0.0), axis=1)


def routed_experts(u, w, cfg, dt=jnp.float32):
    """The held experts' part of the layer's result, ``[T, hidden]``."""
    acc = jnp.float32 if dt == jnp.float32 else dt
    weights = route(u.astype(acc), w, cfg).astype(dt)

    def one(total, ew):
        wg, wu, wd, p, col = ew
        act = polynorm((u @ wg.astype(dt)).astype(acc), p, cfg)
        hid = (act * (u @ wu.astype(dt)).astype(acc)
               * col[:, None].astype(acc)).astype(dt)
        return total + hid @ wd.astype(dt), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["experts_gate"], w["experts_up"], w["experts_down"],
         w["experts_polynorm"], weights.T))
    return routed


def shared_expert(u, w, cfg, dt=jnp.float32):
    acc = jnp.float32 if dt == jnp.float32 else dt
    return gated_mlp(u, w["shared"], cfg, acc)


_BIG = ("experts_gate", "experts_up", "experts_down")


def _cast(w, dt):
    """The routed experts' weights stay as they are stored and are upcast
    one expert at a time."""
    return {k: v if k in _BIG else jax.tree_util.tree_map(
        lambda a: a.astype(dt), v) for k, v in w.items()}


@functools.partial(jax.jit,
                   static_argnames=("dense", "windowed", "cfg", "dt"))
def _layer(x, hc, norm, w, ffn_hc, ffn_norm, ffn, *, dense, windowed, cfg,
           dt):
    """One layer over one sequence's streams ``[T, n, hidden]``."""
    w, ffn = _cast(w, dt), _cast(ffn, dt)
    acc = jnp.float32 if dt == jnp.float32 else dt
    x = sublayer(
        x, hc, norm,
        lambda u: _attention(u, w, cfg, dt, windowed=windowed), cfg, dt)
    if dense:
        return sublayer(x, ffn_hc, ffn_norm,
                        lambda u: gated_mlp(u, ffn, cfg, acc), cfg, dt)
    return sublayer(
        x, ffn_hc, ffn_norm,
        lambda u: routed_experts(u, ffn, cfg, dt)
        + shared_expert(u, ffn, cfg, dt), cfg, dt)


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def features(params, tokens, cfg, dtype=jnp.float32):
    """The streams' sum before the final norm, ``[T, hidden]``, of one
    sequence ``tokens`` [1, T]."""
    dt = jnp.dtype(dtype)
    with _precision(dt):
        emb = params["embed_tokens"][tokens[0]].astype(dt)
        x = jnp.broadcast_to(emb[:, None, :],
                             (emb.shape[0], cfg.mhc_streams, emb.shape[1]))
        for i, kind in enumerate(cfg.layer_types):
            dense = i < cfg.first_dense
            x = _layer(
                x, params[f"layer_{i}_hc"], params[f"layer_{i}_norm"]["scale"],
                params[f"layer_{i}"], params[f"layer_{i}_ffn_hc"],
                params[f"layer_{i}_ffn_norm"]["scale"],
                params[f"layer_{i}_mlp" if dense else f"layer_{i}_moe"],
                dense=dense, windowed=kind == SLIDING, cfg=cfg, dt=dt)
        return jnp.sum(x, axis=1)


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    dt = jnp.dtype(dtype)
    x = features(params, tokens, cfg, dtype)[jnp.asarray(rows)]
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"].astype(dt),
                      cfg.norm_eps)
        return (x @ params["lm_head"].astype(dt).T).astype(jnp.float32)


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def held_to_the_limits(exact, chosen, judged, judged_control) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings two limits more,
    both over all of a run's judged tokens. ``judged`` holds the gaps of the
    run's correctness requests so far, this one's among them, and
    ``judged_control`` the control's at the same positions. Where they are
    at least ``RATIO_MIN_TOKENS`` and the program's tokens that are not the
    reference's choice number over ``DIFFER_RATIO`` of the control's, or its
    mean gap is over ``GAP_RATIO`` of the control's, the chosen tokens'
    logits are set ``2 x LOGIT_TIE_TOL`` below the reference's best
    (``benchmark/models/minicpm_sala.py``): the largest gap the harness then
    reads is over its limit, and the run comes out not correct. So a
    ``worst_logit_gap`` over ``LOGIT_TIE_TOL`` in a result's notes beside
    gaps of a few tenths in the run's ``motif_judged`` lines on stderr means:
    one of the two ratios, and those lines say which."""
    exact = np.array(exact, np.float32)
    chosen, judged = np.asarray(chosen), np.asarray(judged)
    control = np.asarray(judged_control)
    if len(judged) >= RATIO_MIN_TOKENS and (
            np.sum(judged > 0) > DIFFER_RATIO * np.sum(control > 0)
            or np.mean(judged) > GAP_RATIO * np.mean(control)):
        exact[np.arange(len(chosen)), chosen] = \
            exact.max(axis=-1) - 2.0 * LOGIT_TIE_TOL
    return exact


def harness_says_correct(exact, tokens) -> bool:
    """The harness's one comparison, as ``warm_and_check`` makes it."""
    return float(gaps(exact, tokens).max()) <= LOGIT_TIE_TOL


#: the gaps of this process's correctness requests so far, the program's and
#: the control's, one pair of arrays a request (a run is one process, and
#: the harness's only calls of ``logits_at`` are its correctness requests,
#: one after another)
_JUDGED: list = []


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request: ``tokens`` [1, T]
    is the prompt and the served tokens (padded), ``rows`` the positions
    whose logits chose them, so the served tokens are ``tokens[0, rows +
    1]``. The float32 reference's logits there, held to the limits over
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
        held_to_the_limits(exact, control, ctrl, ctrl), control)
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"motif_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((ctrl > 0).sum()),
        "worst_gap": float(mine.max()),
        "control_worst_gap": float(ctrl.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(ctrl.mean()),
        "differ_ratio": float((mine > 0).sum() / max((ctrl > 0).sum(), 1)),
        "gap_ratio": float(mine.mean() / max(ctrl.mean(), 1e-30)),
        "logit_std": float(exact.std(axis=-1).mean()),
        "control_correct": control_correct}}),
        file=sys.stderr, flush=True)
    return held_to_the_limits(exact, served, mine, ctrl)


# -- the counts: bytes the kernels must move, from shapes ---------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def _layers(cfg) -> tuple:
    """``(full, window)`` layers."""
    window = sum(kind == SLIDING for kind in cfg.layer_types)
    return cfg.n_layers - window, window


def kv_bytes_per_token(cfg) -> int:
    """One token of context as the pools hold it: the latent vector in each
    full layer (``kv_token_bytes``: 1,280 bytes a layer) and, while it is
    inside the window, in each window layer: the ``paged`` pool's share of a
    token, plus the ``window`` pool's spread over the context a row holds
    there (128 of its positions, however long: for a row of
    ``max_seq_len``)."""
    full, window = _layers(cfg)
    return full * cfg.kv_token_bytes() + window \
        * cfg.kv_token_bytes() * cfg.window // cfg.max_seq_len


def latent_step_bytes(cfg, rows: float, mean_context: float) -> float:
    """What the full layers' latent read of one decode round must move: the
    576 values of every position its rows read (``rows`` rows of
    ``mean_context`` tokens each, both as the program counted them:
    ``lzy_mla_context_tokens_total / lzy_mla_rows_total`` a traced round),
    each once a full layer. The rows' queries and results (80 heads x 640 +
    512 values a row) are left out."""
    full, _ = _layers(cfg)
    return rows * mean_context * full * cfg.latent_values * _itemsize(cfg)


def expert_bytes(cfg) -> int:
    """One routed expert's three matrices (its four PolyNorm scalars are
    16 bytes)."""
    return 3 * cfg.d_model * cfg.expert_width * _itemsize(cfg)


def experts_step_bytes(cfg, rows: float, share: float) -> float:
    """What the expert product of one decode round must read: the weights
    of the held experts its rows reached, over the expert layers. ``share``
    is the share of the held experts reached as the program counted it over
    the traced rounds (``readers/counted_rows.py``): never the expectation
    under uniform routing."""
    return cfg.expert_layers * cfg.n_held * share * expert_bytes(cfg)


def routed_param_bytes(cfg) -> int:
    return cfg.expert_layers * cfg.n_held * expert_bytes(cfg)


def _mhc_phi_bytes(cfg) -> int:
    """One sublayer's ``phi`` (float32), read once a program."""
    n = cfg.mhc_streams
    return (2 * n + n * n) * n * cfg.d_model * 4


def mhc_step_bytes(cfg, rows: float) -> float:
    """What the connections of one decode round must read from HBM: each of
    the ``2 x layers`` sublayers' ``phi`` (24 x 16,384 float32, 1.5 MB)
    once, whatever the ``rows``. **The streams are not charged**: ISSUE 65
    reckoned three passes over them a row a sublayer (read by ``mhc_pre``,
    read and written by ``mhc_post``), and on the chip they never leave the
    core's memory between a sublayer's kernels (64 rows of four float32
    streams are 4 MB of its 128: ``mhc_post`` moved 5.2 MB in 1.9 us, 2.7
    TB/s, and with the streams charged the prefill share read 119%: PERF.md
    section 6), nor do ``h`` and ``y``. **Expected low**: a call is bound by
    its latency (a projection onto 24 numbers at the highest precision,
    twenty sweeps, a transposition, the mixing out of VMEM), not by these
    bytes."""
    del rows
    return 2 * cfg.n_layers * _mhc_phi_bytes(cfg)


def mhc_prefill_bytes(cfg, tokens: float, programs: float) -> float:
    """The same for ``programs`` prefill programs: ``phi`` once a program a
    sublayer, whatever the ``tokens`` they carried."""
    del tokens
    return programs * 2 * cfg.n_layers * _mhc_phi_bytes(cfg)


def row_context_bytes(cfg, p: float) -> float:
    """What a decode round reads of the cache for a row at position ``p``:
    every position in the full layers, the window's in the window layers,
    576 values each."""
    full, window = _layers(cfg)
    return cfg.latent_values * _itemsize(cfg) * (
        full * (p + 1) + window * min(p + 1, cfg.window))


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float, share: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight
    outside the routed experts once (the head's slice and the connections'
    ``phi`` among them; the embedding table is a lookup of ``rows`` rows and
    is left out), the routed experts those rows reached (``share`` of the
    held ones, as the program counted it), and the rows' context as
    ``row_context_bytes`` charges it, every row at the mean position
    ``resident_tokens / rows`` (the full layers' part is linear in the
    position and the window's is capped, so the mean position charges no
    more than the rows' own). The streams' round trips are not charged."""
    embed = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    outside = param_bytes - routed_param_bytes(cfg) - embed
    context = rows * row_context_bytes(cfg, resident_tokens / rows - 1) \
        if rows else 0.0
    return outside + experts_step_bytes(cfg, rows, share) + context

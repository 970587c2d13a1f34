"""dots3-note-prev's language model (``model_type`` ``dots3_note``) as the
benchmark has to know it: the program's side, the plain reference, the
counts. A configuration file says ``"model": "dots3_note"``.

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision, in the published,
**non-absorbed** form, with no cache: the program serves the absorbed form
through two paged latent pools and reads the chosen tokens by a gather, so
the comparison is between two algebraic forms and two ways of choosing. It
imports nothing from ``lzy_tpu.models`` or ``lzy_tpu.ops``: it reads the
weights from the program's parameter tree by name and does its own
arithmetic. With ``u = RMSNorm(x)``, every layer ``x + attn(u)`` then ``x +
ffn(RMSNorm(x))``:

- **both kinds of attention**: ``c_q = a_q RMSNorm(W_qa u)``, ``q_h = W_qb,h
  c_q = [q_nope ; q_rope]``; ``[c' ; k'] = W_kva u``, ``c = a_kv
  RMSNorm(c')``, ``a = sqrt(hidden / rank)``; **expanded**: ``[k_nope_h ;
  v_h] = W_kvb,h c`` at every position; rotary (value ``i`` paired with ``i
  + d/2``) on ``q_rope`` and on ``k'``, one rotary key for all heads; scores
  ``(q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(d_nope + d_rope)``,
  softmax over the positions the layer's kind lets the query see; ``o_h``
  times ``sigmoid(W_g u)_h``; ``W_o``.
- **full layers** (128 heads, rank 512, 128 + 64 / 128, theta 8e7): a query
  sees the ``index_topk`` positions ``s <= t`` of largest ``I(t, s) = sum_j
  w_j(t) relu(q^I_j(t) . k^I(s))`` (all of them while there are
  ``index_topk`` or fewer; a tie to the lower position), ``q^I_j =
  rope_64(W_qI,j c_q)``, ``k^I = rope_64(LayerNorm(W_kI u))``, ``w = W_w
  u``: **an exact top-k by a sort, a query**.
- **sliding layers** (64 heads, rank 1024, 192 + 64 / 128, theta 5e4): a
  query at ``t`` sees ``t - 513 < s <= t``: the window as a mask.
- **layer 0**: a SwiGLU MLP of width 13,824. **Layers >= 1**: ``s =
  sigmoid(W_r u)`` over 256 experts; the 8 largest of ``s + bias``; weights
  ``s[chosen] / (sum + 1e-20)`` times 1; expert ``e``: ``(silu(u Wg_e) * (u
  Wu_e)) Wd_e`` at width 1,536; plus the shared expert of the same form.
  Dropless. **The share**: of the router's experts this chip holds
  ``experts_held``; a chosen expert outside it adds nothing, here as in the
  program.
- final ``RMSNorm``, untied head over the vocabulary slice held.

Departures from the published implementation, for memory or for the cut:
weights are upcast one layer (one expert) at a time; attention runs over
blocks of queries; the experts are a loop over the held ones.
``reference(..., dtype=bfloat16)`` is the **control**: the same arithmetic
wholly in bfloat16 at the default precision: weights and activations, and
also what the program keeps in float32 by ISSUE 62's word (the router, the
softmaxes, the indexer's ReLU, head weights and sum over heads, the gate,
the norms).
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

#: **Four limits**, over a run's correctness requests (4 x 128 decoded
#: tokens behind prompts of 5,881 and 7,936 tokens, every judged position
#: past ``index_topk``): 512 judged positions and, for each and each of the
#: two full layers, the 2,048 positions chosen (1.05 M chosen positions a
#: layer). The choices and the program's own logits are read from
#: :func:`program_replay`: the program's paged module run again over the
#: served sequence **in the engine's two programs' shapes** (the decode
#: program at the engine's 16 slots, the batch-1 prefill program at its
#: chunk of 256, both through a table of its 784 pages a row).
#: ``CALIBRATION`` has the readings the limits were set from (my chip runs,
#: PR 62: twelve runs at those shapes, each its own seed, weights and
#: prompts; eleven earlier runs whose replay was batch 1 over a table the
#: sequence's size read the same totals).
#:
#: 1. ``CHOICE_DIFFER_TOL``: of the positions the program's indexer chose
#:    **in the first full layer** at the judged positions, at most 0.00288
#:    are not the float32 reference's. **The precision limit.** The first
#:    full layer's input is the embedding's rows, the same on both sides,
#:    so what moves a token across the 2,048th place there is the indexer's
#:    own rounding and nothing upstream of it: the program, whose indexer
#:    multiplies bfloat16 operands and keeps its ReLU, head weights and sum
#:    over 64 heads in float32, reads 0.00256-0.00270 over the twelve runs
#:    (mean 0.00262, deviation 0.00004); **the control**, this reference
#:    wholly in bfloat16 (weights and activations, and also what the
#:    program keeps in float32: the norms, the indexer's sums, the
#:    softmaxes, the gate, the router), reads 0.00306-0.00318 (mean
#:    0.00313, deviation 0.00004) and **comes out not correct, through the
#:    harness's own comparison** (``control_correct`` false in all twelve):
#:    the limit stands 6.7% over the program's largest and 5.9% under the
#:    control's smallest, 5.5 and 6.9 deviations from the means. **No more
#:    room exists by this model's own definition**: ISSUE 62 has the index
#:    multiply bfloat16 operands, as the control does, so 84% of the
#:    control's reading is the program's too (their ratio is 0.83-0.85 in
#:    every run); over both full layers together the two stand closer
#:    (0.0314-0.0336 against 0.0367-0.0379: the second full layer's
#:    reading, 0.060-0.065 against 0.070-0.073, is mostly the first's moved
#:    tokens arriving in its input), which is why the first layer is held.
#: 2. ``CHOICE_DRIFT_TOL``: in every full layer at most 0.1 of the chosen
#:    positions are not the reference's. **The guard for an indexer that is
#:    wrong** in a layer the first limit does not read: the program's
#:    largest is 0.0645 (the control's 0.0727: this limit the control may
#:    pass); the first 2,048 positions in place of the best read 1 - 2,048
#:    / context, 0.65-0.74 at these prompts; on the CPU at the tiny size
#:    rotary left off the index 0.39, head weights of ones 0.84, the rescale
#:    left out 0.17 (PERF.md section 6).
#: 3. ``REPLAY_TIE_TOL``: no served token more than 0.3 below **the
#:    replay's own best logit**. What ties the replay to the timed rounds:
#:    the engine's tokens are the replay's largest at 125-128 of a
#:    request's 128 positions and at most 0.068 under it elsewhere (two
#:    programs compiled apart, of the same shapes but for the pools'), and
#:    the control's tokens stand 0.65-1.70 under it; an engine whose rounds
#:    chose or read otherwise than the replay serves tokens the replay does
#:    not rank first.
#: 4. ``GAP_RATIO`` and ``LOGIT_TIE_TOL``, on the served tokens against the
#:    float32 reference: the mean gap below its best logit at most 2.0 times
#:    **the control's mean gap at the same positions**, and no token more
#:    than 4.5 below. **Not precision limits, and they cannot be**: the
#:    program reads 0.495-1.125 of the control's mean gap (23 runs; the
#:    control 1 by construction) and 0.69-3.63 at the largest (the control
#:    1.11-3.63): with random weights the tokens that change sides at the
#:    2,048th place are most of both, and 512 judged tokens a run leave the
#:    ratio a spread of 0.2. They are the guards for a program that is wrong
#:    and not merely rounded: the miscompiled choice of PERF.md section 6
#:    read 4.5 and 5.5-7.2, the planted faults on the CPU 5.8-59 (a window
#:    of one more or one less 0.55-1.16: no run-level limit sees that; tier
#:    1's 2e-4 on the logits does). ``LOGIT_TIE_TOL`` is derived from this
#:    model's own logits: their deviation over the 19,008 rows is 1.429 on
#:    the chip, so the best sits about 4 deviations, 5.7, above a token
#:    taken blindly; it stands midway between the sound runs' largest
#:    (3.63, one token of 11,776, which the control chose too: a router's
#:    8th place changing hands on a rounded input moves a logit that far;
#:    the requests' largest gaps have a tail of 0.6 a factor of e past
#:    2.0, which puts a sound run over 4.0 once in a hundred and over 4.5
#:    once in two hundred) and the wrong program's smallest (5.5). It was
#:    4.0 until the seventeenth run read 3.63.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``); ``held_to_the_limits`` says how the others reach it
#: all the same (as ``benchmark/models/minicpm_sala.py``).
LOGIT_TIE_TOL = 4.5
GAP_RATIO = 2.0
GAP_RATIO_MIN_TOKENS = 500
CHOICE_DIFFER_TOL = 0.00288
CHOICE_DRIFT_TOL = 0.1
REPLAY_TIE_TOL = 0.3

#: the readings the limits were set from (my chip runs, PR 62, one v5e chip,
#: the published widths, each run its own seed; PERF.md section 6): the
#: twelve runs whose replay had the engine's shapes, a full layer; the
#: served tokens' numbers over those and the eleven runs before them
CALIBRATION = {
    "first_layer_differ_share":
        [0.00258, 0.00261, 0.00256, 0.0027, 0.00264, 0.00268, 0.00262,
         0.00267, 0.00257, 0.00264, 0.0026, 0.00268],
    "control_first_layer_differ_share":
        [0.00306, 0.00315, 0.00309, 0.00317, 0.00318, 0.00314, 0.00316,
         0.00314, 0.0031, 0.00313, 0.00312, 0.00315],
    "second_layer_differ_share":
        [0.06143, 0.0626, 0.06214, 0.06102, 0.06453, 0.06005, 0.06107,
         0.06389, 0.06459, 0.06168, 0.06147, 0.06159],
    "control_second_layer_differ_share":
        [0.07061, 0.07151, 0.07188, 0.07025, 0.07269, 0.07076, 0.07206,
         0.0733, 0.0722, 0.0719, 0.07196, 0.07241],
    "replay_worst_gap": [0.023, 0.009, 0.011, 0.043, 0.004, 0.058, 0.008,
                         0.003, 0.022, 0.015, 0.01, 0.068],
    "control_replay_worst_gap": [1.18, 1.69, 1.49, 1.07, 1.21, 0.65, 0.65,
                                 1.7, 1.2, 1.29, 1.34, 1.26],
    "gap_ratio": [1.125, 1.048, 1.037, 0.495, 0.696, 1.0, 0.787, 0.897,
                  0.869, 0.782, 0.79, 0.755, 0.915, 0.654, 0.638, 0.907,
                  0.898, 0.743, 0.637, 0.864, 0.799, 0.728, 0.686],
    "worst_gap": [1.64, 2.42, 1.01, 0.69, 2.38, 1.56, 2.32, 1.01, 2.73,
                  1.74, 0.75, 0.74, 3.63, 0.74, 1.0, 2.1, 1.48, 0.84, 1.23,
                  1.66, 1.52, 1.36, 0.93],
    "control_worst_gap": [1.64, 1.61, 1.31, 1.16, 1.41, 1.19, 1.5, 1.27,
                          2.73, 1.74, 1.24, 1.19, 3.63, 1.87, 2.0, 3.18,
                          1.11, 1.79, 1.79, 1.23, 1.31, 1.14, 1.55],
    "logit_std": 1.429,
}

_QUERY_BLOCK = 128


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``Dots3NoteConfig``. A key the program cannot honour is refused (by the
    program's own ``from_published``)."""
    from lzy_tpu.models.dots3_note import Dots3NoteConfig

    return Dots3NoteConfig.from_published(
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
    from lzy_tpu.models import dots3_note

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)),
                            cfg.n_layers)
    params: dict = {}
    made: dict = {}
    for i, kind in enumerate(cfg.layer_types):
        dense = i < cfg.first_dense
        # a layer alone, first in a model of one or two layers: a model needs
        # a full layer, so a sliding layer stands second behind one
        full = kind == dots3_note.FULL
        short = dataclasses.replace(
            cfg, n_layers=1 if full else 2,
            layer_types=(kind,) if full else (dots3_note.FULL, kind),
            first_dense=(1 if dense else 0))
        at = 0 if full else 1
        mine = re.compile(rf"^layer_{at}(?=$|_)")
        build = made.get((kind, dense))
        if build is None:
            build = made[(kind, dense)] = jax.jit(functools.partial(
                lambda key, short: dots3_note.init_params(short, key),
                short=short))
        tree = build(keys[i])
        for name, leaf in tree.items():
            if mine.match(name):
                params[mine.sub(f"layer_{i}", name)] = leaf
            elif i == 0 and not name.startswith("layer_"):
                params[name] = leaf
    if out_shardings is not None:
        params = jax.device_put(params, out_shardings)
    return jax.block_until_ready(params)


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _layer_norm(x, scale, bias, eps=1e-6):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale + bias


def rotary(x, positions, theta, width=None):
    """``x`` [T, ..., D] with its first ``width`` values (all of them by
    default) rotated by its position: value ``i`` pairs with ``i +
    width/2``, frequencies ``theta^(-2i/width)``."""
    d = x.shape[-1] if width is None else width
    rest = x[..., d:]
    x = x[..., :d]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs       # [T, d/2]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf1 = x[..., :d // 2].astype(jnp.float32)
    xf2 = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate(
        [(xf1 * cos - xf2 * sin).astype(x.dtype),
         (xf1 * sin + xf2 * cos).astype(x.dtype), rest], axis=-1)


def exact_topk_mask(scores, seen, k: int):
    """``[Q, T]`` bool: of the positions ``seen`` [Q, T], the ``k`` of
    largest ``scores`` a query, a tie to the lower position; all that are
    seen where there are ``k`` or fewer. By a sort of the values: the
    ``k``-th largest, everything above it, and the first of its equals."""
    if scores.shape[-1] <= k:
        return seen
    s = jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)
    kth = jnp.sort(s, axis=-1)[:, -k][:, None]
    above, equal = s > kth, s == kth
    spare = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= spare))) & seen


def _widths(cfg, windowed: bool):
    if windowed:
        return (cfg.swa_n_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
                cfg.swa_qk_nope_head_dim, cfg.swa_qk_rope_head_dim,
                cfg.swa_v_head_dim, cfg.swa_rope_theta)
    return (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.rope_theta)


def _attention(u, w, cfg, dt, *, windowed: bool, rows=None):
    """The published form: the latent expanded into keys and values a head
    at every position. Returns the layer's output ``[T, hidden]`` and, a
    full layer asked for ``rows``, the ``[rows, T]`` bool of what each of
    those queries chose."""
    t = u.shape[0]
    h, rq, r, dn, dr, dv, theta = _widths(cfg, windowed)
    pos = jnp.arange(t)
    a_q = (cfg.d_model / rq) ** 0.5 if cfg.lora_rescale else 1.0
    a_kv = (cfg.d_model / r) ** 0.5 if cfg.lora_rescale else 1.0
    c_q = (_rms_norm(u @ w["q_a_proj"]["kernel"], w["q_a_norm"]["scale"],
                     cfg.norm_eps) * a_q).astype(dt)
    q = (c_q @ w["q_b_proj"]["kernel"]).reshape(t, h, dn + dr)
    kva = u @ w["kv_a_proj"]["kernel"]
    c = (_rms_norm(kva[:, :r], w["kv_a_norm"]["scale"], cfg.norm_eps)
         * a_kv).astype(dt)
    kv = jnp.einsum("tr,rhx->thx", c, w["kv_b_proj"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_rope = q[..., :dn], rotary(q[..., dn:], pos, theta)
    k_rope = rotary(kva[:, r:], pos, theta)                       # [T, dr]
    # the softmaxes', the indexer's and the gate's type: float32 in the
    # reference whatever the weights' type; the control's own type
    acc = jnp.float32 if dt == jnp.float32 else dt
    gate = jax.nn.sigmoid((u @ w["gate_proj"]["kernel"]).astype(acc))
    block = min(_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")
    if windowed:
        qi = ki = wi = None
    else:
        j, di = cfg.index_n_heads, cfg.index_head_dim
        qi = rotary((c_q @ w["index_q_proj"]["kernel"]).reshape(t, j, di),
                    pos, theta, dr)
        ki = rotary(_layer_norm(
            u @ w["index_k_proj"]["kernel"], w["index_k_norm"]["scale"],
            w["index_k_norm"]["bias"]).astype(dt), pos, theta, dr)
        wi = (u @ w["index_w_proj"]["kernel"]).astype(acc)

    def one(qs):
        """One block of queries against every position before them."""
        qn, qr, first, qib, wib = qs
        at = first + jnp.arange(block)[:, None]
        keep = jnp.arange(t)[None, :] <= at
        if windowed:
            keep &= jnp.arange(t)[None, :] > at - cfg.window
        else:
            scored = jnp.maximum(jnp.einsum(
                "qjd,ld->qjl", qib, ki).astype(acc), 0.0)
            keep = exact_topk_mask(
                jnp.einsum("qjl,qj->ql", scored, wib), keep,
                cfg.index_topk)
        s = (jnp.einsum("qhn,lhn->hql", qn, k_nope)
             + jnp.einsum("qhr,lr->hql", qr, k_rope)) * (dn + dr) ** -0.5
        pr = jax.nn.softmax(
            jnp.where(keep, s.astype(acc), -1e30), axis=-1)
        return jnp.einsum("hql,lhv->qhv", pr.astype(dt), v), keep

    blocks = jnp.arange(0, t, block)
    nothing = jnp.zeros((t // block, block, 1), dt)
    out, keep = jax.lax.map(one, (
        q_nope.reshape(-1, block, h, dn), q_rope.reshape(-1, block, h, dr),
        blocks,
        nothing if windowed else qi.reshape(-1, block, *qi.shape[1:]),
        nothing if windowed else wi.reshape(-1, block, wi.shape[-1])))
    out = (out.reshape(t, h, dv) * gate[:, :, None].astype(dt)).astype(dt)
    out = out.reshape(t, h * dv) @ w["o_proj"]["kernel"]
    chose = None
    if not windowed and rows is not None:
        chose = keep.reshape(t, t)[rows]
    return out, chose


def route(u, w, cfg):
    """``[T, held]``: each position's weight for each held expert (0 where
    it did not choose it)."""
    lo, hi = cfg.experts_held
    scores = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(scores + w["router_bias"], cfg.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling
    held = jnp.arange(lo, hi)
    return jnp.sum(jnp.where(chosen[:, :, None] == held[None, None, :],
                             picked[:, :, None], 0.0), axis=1)


def routed_experts(u, w, cfg, dt=jnp.float32):
    """The held experts' part of the layer's result, ``[T, hidden]``."""
    weights = route(u, w, cfg).astype(dt)

    def one(acc, ew):
        wg, wu, wd, col = ew
        hid = jax.nn.silu(u @ wg.astype(dt)) * (u @ wu.astype(dt))
        return acc + (hid * col[:, None]) @ wd.astype(dt), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["experts_gate"], w["experts_up"], w["experts_down"], weights.T))
    return routed


def shared_expert(u, w):
    return (jax.nn.silu(u @ w["shared_gate"]["kernel"])
            * (u @ w["shared_up"]["kernel"])) @ w["shared_down"]["kernel"]


def dense_mlp(u, w):
    return (jax.nn.silu(u @ w["gate_proj"]["kernel"])
            * (u @ w["up_proj"]["kernel"])) @ w["down_proj"]["kernel"]


_BIG = ("experts_gate", "experts_up", "experts_down")


def _cast(w, dt):
    """The routed experts' weights stay as they are stored and are upcast
    one expert at a time."""
    return {k: v if k in _BIG else jax.tree_util.tree_map(
        lambda a: a.astype(dt), v) for k, v in w.items()}


@functools.partial(jax.jit,
                   static_argnames=("dense", "windowed", "cfg", "dt"))
def _layer(x, norm, w, ffn_norm, ffn, rows, *, dense, windowed, cfg, dt):
    """One layer over one sequence ``[T, hidden]``."""
    w, ffn = _cast(w, dt), _cast(ffn, dt)
    u = _rms_norm(x, norm.astype(dt), cfg.norm_eps)
    a, chose = _attention(u, w, cfg, dt, windowed=windowed, rows=rows)
    x = (x + a).astype(dt)
    u = _rms_norm(x, ffn_norm.astype(dt), cfg.norm_eps)
    if dense:
        return (x + dense_mlp(u, ffn)).astype(dt), chose
    return (x + routed_experts(u, ffn, cfg, dt)
            + shared_expert(u, ffn)).astype(dt), chose


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def features(params, tokens, cfg, dtype=jnp.float32, rows=None):
    """Hidden states before the final norm, ``[T, hidden]``, of one sequence
    ``tokens`` [1, T], and what the queries at ``rows`` chose in each full
    layer (``[rows, T]`` bool, in layer order)."""
    dt = jnp.dtype(dtype)
    chosen = []
    with _precision(dt):
        x = params["embed_tokens"][tokens[0]].astype(dt)
        for i, kind in enumerate(cfg.layer_types):
            dense = i < cfg.first_dense
            windowed = kind == "sliding_attention"
            x, chose = _layer(
                x, params[f"layer_{i}_norm"]["scale"], params[f"layer_{i}"],
                params[f"layer_{i}_ffn_norm"]["scale"],
                params[f"layer_{i}_mlp" if dense else f"layer_{i}_moe"],
                None if rows is None else jnp.asarray(rows),
                dense=dense, windowed=windowed, cfg=cfg, dt=dt)
            if chose is not None:
                chosen.append(chose)
    return x, chosen


def head_logits(params, x, cfg, dtype=jnp.float32):
    dt = jnp.dtype(dtype)
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"].astype(dt),
                      cfg.norm_eps)
        return (x @ params["lm_head"].astype(dt).T).astype(jnp.float32)


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    x, _ = features(params, tokens, cfg, dtype)
    return head_logits(params, x[jnp.asarray(rows)], cfg, dtype)


def reference(params, tokens, rows, cfg, dtype=jnp.float32):
    """``(logits [rows, vocab], chosen)``: as :func:`reference_logits`, and
    what the queries at ``rows`` chose in each full layer."""
    x, chosen = features(params, tokens, cfg, dtype, rows=rows)
    return head_logits(params, x[jnp.asarray(rows)], cfg, dtype), chosen


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def engine_shapes(params) -> tuple:
    """The shapes of the timed programs, as :func:`program_replay` takes
    them: the ``slots``, the ``pages_per_seq`` of a row's table, the
    ``page_size``, the ``chunk`` of a prefill program and the ``kernel``
    (``lax`` or ``pallas``) of the engine that serves these weights. The harness hands a model file its weights and no
    engine, so it is looked for among the process's objects, by the
    identity of ``params`` (``benchmark/models/minicpm_sala.py``
    ``_serving_engine``)."""
    import gc

    from lzy_tpu.serving import PagedInferenceEngine

    found = [o for o in gc.get_objects()
             if isinstance(o, PagedInferenceEngine) and o.params is params]
    if len(found) != 1:
        raise LookupError(
            f"{len(found)} engines serve these weights: the replay takes "
            f"the shapes of the one engine of a run")
    engine = found[0]
    return {"slots": engine.slots, "pages_per_seq": engine._pages_per_seq,
            "page_size": engine._page, "chunk": engine.prefill_chunk,
            "kernel": engine._paged_kernel}


def program_replay(params, tokens, cfg, *, rows, slots: int,
                   pages_per_seq: int, page_size: int, chunk: int,
                   kernel: str):
    """The program's paged module run again over ``tokens[0, :rows[-1] +
    1]``, the sequence the engine read, **in the engine's two programs'
    shapes**: the prompt (``rows[0] + 1`` tokens) ``chunk`` positions a
    program, batch 1 (``latent_index_prefill``), and every served token
    after the first as a decode round of ``slots`` rows of which one is
    live (``latent_index_decode``, the choice and the read of the chosen at
    the decode step's shapes), both through a table of ``pages_per_seq``
    pages of ``page_size`` a row as the engine's is and with its
    ``kernel``, over pools of its own that hold this one sequence. Returns ``(chosen, logits)``: what the
    queries at ``rows`` chose, ``[rows, last + 1]`` bool a full layer in
    layer order, and the program's own logits there ``[rows, vocab]``: the
    served tokens are their largest but at a tie, which is what ties this
    replay to the timed rounds (``REPLAY_TIE_TOL``)."""
    rows = np.asarray(rows)
    prompt, last = int(rows[0]) + 1, int(rows[-1])
    if (np.diff(rows) != 1).any():
        raise ValueError("the judged positions are consecutive")
    held = -(-(last + 1) // page_size)
    if held > pages_per_seq:
        raise ValueError(f"{last + 1} positions need {held} pages of a "
                         f"table of {pages_per_seq}")
    module = cfg.paged_model(
        page_size=page_size, kv_pages=held + 1, window_pages=held + 1,
        kv_quant=None, kernel=kernel)
    # one live row, first in the table; block 0 is scratch: the pages past
    # the sequence's (a last chunk's pads land there, as in the engine) and
    # every page of an idle row
    row = np.zeros((pages_per_seq,), np.int32)
    row[:held] = np.arange(1, held + 1)
    tables = {1: jnp.asarray(row[None])}
    tables[slots] = jnp.zeros((slots, pages_per_seq), jnp.int32).at[0].set(
        tables[1][0])
    # the pools alone, from their shapes: ``init`` would draw the weights
    pools = {layer: {name: jnp.zeros(leaf.shape, leaf.dtype)
                     for name, leaf in leaves.items() if name != "index"}
             for layer, leaves in jax.eval_shape(lambda: module.init(
                 jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                 page_table=tables[1], window_table=tables[1]))[
                     "cache"].items()}
    full = [i for i, kind in enumerate(cfg.layer_types)
            if kind == "full_attention"]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, pools, ids, real, at, table, pick):
        """One program: ``ids`` [B, T] at positions ``at + t`` of which
        ``real`` a row are real; of row 0's query ``pick``, the logits and
        what each full layer chose."""
        cache = {layer: dict(leaves, index=at)
                 for layer, leaves in pools.items()}
        logits, out = module.apply(
            {"params": params, "cache": cache}, ids, page_table=table,
            window_table=table, valid_len=real,
            mutable=["cache", "choices"])
        pools = {layer: {name: leaf for name, leaf in leaves.items()
                         if name != "index"}
                 for layer, leaves in out["cache"].items()}
        return pools, logits[0, pick], [
            tuple(x[0, pick] for x in out["choices"][f"layer_{i}"][
                "chosen"][0]) for i in full]         # ([k], []) a full layer

    ids = np.asarray(tokens)[0]
    judged = []
    for at in range(0, prompt, chunk):
        take = min(chunk, prompt - at)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :take] = ids[at:at + take]
        pools, logits, chosen = step(
            params, pools, jnp.asarray(padded), jnp.asarray([take], jnp.int32),
            jnp.asarray([at], jnp.int32), tables[1], jnp.int32(take - 1))
    judged.append((logits, chosen))            # the prompt's last position
    live = jnp.zeros((slots,), jnp.int32).at[0].set(1)
    for at in range(prompt, last + 1):
        pools, logits, chosen = step(
            params, pools, jnp.zeros((slots, 1), jnp.int32).at[0, 0].set(
                int(ids[at])), live, live * at, tables[slots], jnp.int32(0))
        judged.append((logits, chosen))
    masks = [np.zeros((len(rows), last + 1), bool) for _ in full]
    for r, (_, chosen) in enumerate(judged):
        for mask, (idx, n) in zip(masks, chosen):
            mask[r, np.asarray(idx)[:int(n)]] = True
    return masks, np.stack([np.asarray(logits) for logits, _ in judged])


def choices_differ(mine: list, exact: list) -> list:
    """``(differing, chosen)`` a full layer, in layer order: over the judged
    positions, the positions the program chose that the reference did not,
    and all it chose."""
    counts = []
    for a, b in zip(mine, exact):
        a, b = np.asarray(a), np.asarray(b)
        n = min(a.shape[-1], b.shape[-1])
        counts.append((int((a[:, :n] & ~b[:, :n]).sum())
                       + int(a[:, n:].sum()), int(a.sum())))
    return counts


def differ_shares(tally: list) -> list:
    """A run's tally (a :func:`choices_differ` a request) as the share of
    chosen positions that differ, a full layer in layer order."""
    return [sum(d for d, _ in layer) / max(1, sum(c for _, c in layer))
            for layer in zip(*tally)]


def held_to_the_limits(exact, chosen, judged, judged_control,
                       differ_shares, replay_gap=0.0) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings four more.
    Where the run's judged tokens so far are at least
    ``GAP_RATIO_MIN_TOKENS`` and their mean gap is over ``GAP_RATIO`` of the
    control's at the same positions, where the share of the chosen
    positions that the reference did not choose (``differ_shares``, a full
    layer) is over ``CHOICE_DIFFER_TOL`` in the first full layer or over
    ``CHOICE_DRIFT_TOL`` in any, or where a served token sits more than
    ``REPLAY_TIE_TOL`` below the replay's own best, the chosen tokens'
    logits are set ``2 x LOGIT_TIE_TOL`` below the reference's best
    (``benchmark/models/minicpm_sala.py``; a token that is the reference's
    best too, which a lowering by a fixed step would leave under the limit):
    the largest gap the harness then reads is over its limit, and the run
    comes out not correct. So a ``worst_logit_gap`` of exactly ``2 x
    LOGIT_TIE_TOL`` in a result's notes means: the run's
    ``dots3_note_judged`` lines on stderr say which limit."""
    exact = np.array(exact, np.float32)
    chosen, judged = np.asarray(chosen), np.asarray(judged)
    slow = len(judged) >= GAP_RATIO_MIN_TOKENS \
        and np.mean(judged) > GAP_RATIO * np.mean(judged_control)
    if slow or differ_shares[0] > CHOICE_DIFFER_TOL \
            or max(differ_shares) > CHOICE_DRIFT_TOL \
            or replay_gap > REPLAY_TIE_TOL:
        exact[np.arange(len(chosen)), chosen] = \
            exact.max(axis=-1) - 2.0 * LOGIT_TIE_TOL
    return exact


def harness_says_correct(exact, tokens) -> bool:
    """The harness's one comparison, as ``warm_and_check`` makes it."""
    return float(gaps(exact, tokens).max()) <= LOGIT_TIE_TOL


#: this process's correctness requests so far, one entry a request: the
#: gaps (the program's and the control's), the choices' counts a full
#: layer (the program's and the control's), the served tokens' largest gap
#: below the replay's best
_JUDGED: list = []
_CHOICES: list = []
_CONTROL_CHOICES: list = []
_REPLAY_GAPS: list = []


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request: ``tokens`` [1, T]
    is the prompt and the served tokens (padded), ``rows`` the positions
    whose logits chose them, so the served tokens are ``tokens[0, rows +
    1]``. The float32 reference's logits there, held to the limits over the
    run's requests so far. **The control is put through the same
    comparison** (its own tokens, its own choices): ``control_correct`` on
    stderr says what the harness would have said of it."""
    rows = np.asarray(rows)
    exact, chose = reference(params, tokens, rows, cfg)
    exact = np.asarray(exact)
    chose = [np.asarray(c) for c in chose]
    served = np.asarray(tokens)[0, rows + 1]
    rough, rough_chose = reference(params, tokens, rows, cfg, jnp.bfloat16)
    control = np.asarray(rough).argmax(axis=-1)
    _JUDGED.append((gaps(exact, served), gaps(exact, control)))
    mine, ctrl = (np.concatenate(x) for x in zip(*_JUDGED))
    shapes = engine_shapes(params)
    replayed, own = program_replay(params, tokens, cfg, rows=rows, **shapes)
    _REPLAY_GAPS.append(float(gaps(own, served).max()))
    _CHOICES.append(choices_differ(replayed, chose))
    _CONTROL_CHOICES.append(choices_differ(
        [np.asarray(c) for c in rough_chose], chose))
    shares, c_shares = differ_shares(_CHOICES), differ_shares(_CONTROL_CHOICES)
    control_correct = harness_says_correct(held_to_the_limits(
        exact, control, ctrl, ctrl, c_shares), control)
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"dots3_note_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((ctrl > 0).sum()),
        "worst_gap": float(mine.max()),
        "control_worst_gap": float(ctrl.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(ctrl.mean()),
        "logit_std": float(exact.std(axis=-1).mean()),
        "replay_shapes": shapes,
        "replay_worst_gap": max(_REPLAY_GAPS),
        "replay_same_token": int((own.argmax(axis=-1) == served).sum()),
        "control_replay_worst_gap": float(gaps(own, control).max()),
        "choices_differ_share_by_layer": shares,
        "control_choices_differ_share_by_layer": c_shares,
        "control_correct": control_correct}}),
        file=sys.stderr, flush=True)
    return held_to_the_limits(exact, served, mine, ctrl, shares,
                              max(_REPLAY_GAPS))


# -- the counts: bytes and operations the kernels must move, from shapes ------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def _layers(cfg) -> tuple:
    """``(full, sliding)`` layers."""
    full = sum(kind == "full_attention" for kind in cfg.layer_types)
    return full, cfg.n_layers - full


def latent_values(cfg, windowed: bool) -> int:
    """What a read needs of a cached token a layer: ``c`` and the shared
    rotary key (576 full, 1,088 sliding), not the lanes a page occupies."""
    if windowed:
        return cfg.swa_kv_lora_rank + cfg.swa_qk_rope_head_dim
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def kv_bytes_per_token(cfg) -> int:
    """One token of context as the pools hold it: the latent vector and the
    indexer's key in each full layer (``kv_token_bytes``: 1,536 bytes a
    layer, 3,072) and, while it is inside the window, the sliding layers'
    wider vector (2,304 a layer): the ``paged`` pool's share of a token,
    plus the ``window`` pool's spread over the context a row holds there
    (513 of its positions, however long: for a row of ``max_seq_len``)."""
    full, sliding = _layers(cfg)
    return full * cfg.kv_token_bytes() + sliding \
        * cfg.window_token_bytes() * cfg.window // cfg.max_seq_len


def index_step_bytes(cfg, rows: float, mean_visible: float) -> float:
    """What the index of one decode round must read: the cached index key
    (128 values) of every position its selecting rows see, a full layer.
    ``mean_visible`` is ``lzy_latent_visible_tokens_total /
    lzy_latent_select_rows_total`` as the traced rounds counted it."""
    full, _ = _layers(cfg)
    return rows * mean_visible * full * cfg.index_head_dim * _itemsize(cfg)


def chosen_step_bytes(cfg, rows: float, mean_chosen: float) -> float:
    """What the read of the chosen of one decode round must move: the 576
    values of each chosen token, a full layer. ``mean_chosen`` is
    ``lzy_latent_chosen_tokens_total / lzy_latent_rows_total``."""
    full, _ = _layers(cfg)
    return rows * mean_chosen * full * latent_values(cfg, False) \
        * _itemsize(cfg)


def index_prefill_flops(cfg, start: int, tokens: int) -> float:
    """The index's arithmetic for the prompt positions ``start .. start +
    tokens - 1``: ``2 x index_n_heads x index_head_dim`` (16,384) a (query,
    visible position) pair a full layer, a query past ``index_topk`` seeing
    ``p + 1``; a query that does not select scores nothing."""
    full, _ = _layers(cfg)
    p = np.arange(start, start + tokens, dtype=np.float64)
    pairs = np.where(p >= cfg.index_topk, p + 1, 0.0).sum()
    return float(pairs) * full * 2 * cfg.index_n_heads * cfg.index_head_dim


def chosen_prefill_flops(cfg, start: int, tokens: int) -> float:
    """The chosen read's arithmetic for those positions: ``2 x heads x (576
    + 512)`` (278,528) a (query, chosen position) pair a full layer, a query
    at ``p`` reading ``min(p + 1, index_topk)``. **Bound: compute, by a
    hair**: a query's 128 heads do 278,528 operations on the 1,152 bytes of
    a chosen token, 242 a byte against the chip's 240."""
    full, _ = _layers(cfg)
    p = np.arange(start, start + tokens, dtype=np.float64)
    pairs = np.minimum(p + 1, cfg.index_topk).sum()
    return float(pairs) * full * 2 * cfg.n_heads * (
        latent_values(cfg, False) + cfg.kv_lora_rank)


def expert_bytes(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg.d_model * cfg.expert_width * _itemsize(cfg)


def experts_step_bytes(cfg, rows: float, share: float) -> float:
    """What the grouped expert product of one decode round must read: the
    weights of the held experts its rows reached, over the expert layers.
    ``share`` is the share of the held experts reached as the program
    counted it over the traced rounds (``readers/counted_rows.py``)."""
    return cfg.expert_layers * cfg.n_held * share * expert_bytes(cfg)


def routed_param_bytes(cfg) -> int:
    return cfg.expert_layers * cfg.n_held * expert_bytes(cfg)


def row_context_bytes(cfg, p: float) -> float:
    """What a decode round reads of the cache for a row at position ``p``:
    every visible index key in the full layers, the chosen tokens' latent
    vectors, the window's in the sliding layers."""
    full, sliding = _layers(cfg)
    b = _itemsize(cfg)
    return full * cfg.index_head_dim * b * (p + 1) \
        + full * latent_values(cfg, False) * b * min(p + 1, cfg.index_topk) \
        + sliding * latent_values(cfg, True) * b * min(p + 1, cfg.window)


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float, share: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight
    outside the routed experts once (the head's slice among them; the
    embedding table is a lookup of ``rows`` rows and is left out), the
    routed experts those rows reached (``share`` of the held ones, as the
    program counted it), and the rows' context as ``row_context_bytes``
    charges it, every row at the mean position ``resident_tokens / rows``
    (the index's part is linear in the position and the other two are
    capped, so the mean position charges no more than the rows' own)."""
    embed = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    outside = param_bytes - routed_param_bytes(cfg) - embed
    context = rows * row_context_bytes(cfg, resident_tokens / rows - 1) \
        if rows else 0.0
    return outside + experts_step_bytes(cfg, rows, share) + context

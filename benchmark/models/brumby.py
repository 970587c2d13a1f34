"""Brumby-14B-Base for the benchmark: the program's configuration and weights
(``lzy_tpu/models/brumby.py``), and a plain reference that shares nothing
with the program's layers or kernels.

**The reference is the other formula of the same function.** The program
carries a state (``S``, ``z``) and reads it through a feature map
(``ops/power_retention.py``: chunks in prefill, a Pallas update in decode).
The reference never builds the state to answer a query: a layer's output is
the *attention form*, over the whole sequence, float32 at the highest matmul
precision, in blocks of queries::

    l_t = log sigmoid(W_G u_t + gate_bias)          cs_t = l_0 + .. + l_t
    a_ts = exp(cs_t - cs_s) (q_t . k_s / sqrt(d))^2,  s <= t
    y_t = sum_s a_ts v_s / (sum_s a_ts + eps)

and, for the state a request leaves in its slot, the direct sum ``S_T = sum_s
exp(cs_T - cs_s) phi(k_s) v_s^T``, ``z_T`` likewise, in blocks of positions.
``phi`` is written out again here, in the layout the program's state leaves
have (the contract between the two, stated in both files): ``d / 2 + 1`` tiles
of ``d``; entry ``i`` of tile ``m`` is ``w_m x_i x_{(i - m) mod d}``, ``w_0``
= 1 and ``sqrt(2)`` past it, and the entries ``i >= d / 2`` of the last tile
are zeros (64 of 8,320 at ``d`` = 128); ``S`` is ``[KV, tiles, d of v, d]``.

**Weights.** The program's own initialiser from ``--seed``. The gate's
constant (``RETENTION_GATE_BIAS``) is the one thing added, and it is a
constant of the program's configuration, not a weight.
"""

from __future__ import annotations

import contextlib
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

#: **Four limits**, over a run's correctness requests (4 x 256 decoded tokens
#: behind prompts of 1,070, 1,651, 2,541 and 3,271 tokens, the cell's own
#: levels under ``pad_to`` 4,352): 1,024 judged positions and four final
#: states. ``CALIBRATION`` has the readings they were set from (my chip runs,
#: PR 56, one v5e chip, fifteen runs, each its own seed): the sound program's,
#: and beside each the all-bfloat16 control's. The logits of this random
#: model spread by 1.4, and a rounding moves a token by hundredths: the
#: limits are a tenth of the other cells'.
#:
#: 1. ``LOGIT_TIE_TOL``: no served token more than 0.5 below the float32
#:    reference's best logit. The harness's one comparison, a backstop: the
#:    sound program's largest gap reads 0.067-0.125 (some 58 of 1,024 tokens
#:    are not the reference's, at near-ties), the control's 0.135-0.228, so
#:    the control passes it; a program that dropped the normaliser read 0.91
#:    at the tiny size (``benchmark/tests/test_brumby_model_file.py``), a
#:    token drawn blind sits 3 to 4 below.
#: 2. ``GAP_RATIO``: the served tokens' mean gap at most 0.65 of **the
#:    all-bfloat16 control's mean gap at the same positions** (paired: a seed
#:    that is hard for one is hard for the other), once
#:    ``GAP_RATIO_MIN_TOKENS`` tokens are judged. Sound 0.19-0.40 (mean gap
#:    0.0009-0.0017 against 0.0039-0.0063); the control 1 by construction.
#:    The control is this file's reference with every activation, product
#:    and sum in bfloat16 (the running sum of the log decays alone stays
#:    float32: in bfloat16 it stops moving after a few hundred tokens and
#:    every weight is 0 or 1, which is no program anyone would serve). What
#:    a program that dropped the normaliser, the gate or the ``sqrt(2)`` of
#:    ``phi`` fails first (140 to 1,400 times the control's at the tiny
#:    size).
#: 3. ``STATE_REL_TOL``: the ``S`` and the ``z`` each request leaves in its
#:    slot against the reference's direct sum after the same positions,
#:    relative to the reference's norm, the mean over the layers, the larger
#:    of the two, the largest of a run's requests: at most 0.03. Sound
#:    0.0217-0.0224 over fifteen seeds (0.4% in the first layer, 3.3% in the
#:    eighth: the activations that make k and v are rounded to bfloat16 and
#:    the layers carry it along; the reading hardly moves with the seed);
#:    the control 0.0399-0.0415, so the limit stands 1.34 over the one and
#:    1.33 under the other. A pad that advanced the state read 0.55 at the
#:    tiny size, a dropped ``sqrt(2)`` 0.37, a dropped gate 1.00.
#: 4. ``STATE_COARSE_TOL``: the share of the slot's ``S`` entries (zeros
#:    left out: the padded tail is zeros) that a bfloat16 holds exactly, at
#:    most 0.01: a state summed in float32 reads 0.000035-0.000037 (2^-16 is
#:    0.000015), one kept or rounded in bfloat16 reads 1.0 (its state gap,
#:    0.009 at the tiny size, would pass the third limit).
#:    ``program_config`` also refuses a state leaf of another type than the
#:    configuration states.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``); ``held_to_the_limits`` says how the other three reach
#: it all the same (as ``benchmark/models/minicpm_sala.py``).
LOGIT_TIE_TOL = 0.5
GAP_RATIO = 0.65
GAP_RATIO_MIN_TOKENS = 1000
STATE_REL_TOL = 0.03
STATE_COARSE_TOL = 0.01

#: a run's readings after its fourth request: seed, requests/s, the mean gap
#: over the control's, largest gap (the control's), the largest state gap of
#: the four (the control's), the largest share of state entries a bfloat16
#: holds; ``broken_tiny``: the program broken one way each, on the CPU at the
#: tiny size in float32 (``benchmark/tests/test_brumby_model_file.py``): the
#: limits it failed, its mean gap (the control's), largest gap, state gap,
#: bfloat16-exact share
CALIBRATION = {
    "sound": [
        (4300000057, 0.4, 0.35, 0.081, 0.199, 0.0222, 0.0400, 0.000036),
        (4300000058, 0.4, 0.40, 0.092, 0.155, 0.0220, 0.0404, 0.000036),
        (4300000061, 0.4, 0.38, 0.089, 0.184, 0.0220, 0.0403, 0.000036),
        (4300000062, 0.5, 0.25, 0.075, 0.218, 0.0221, 0.0408, 0.000036),
        (4300000066, 0.55, 0.37, 0.121, 0.218, 0.0217, 0.0399, 0.000036),
        (4300000063, 0.6, 0.19, 0.067, 0.176, 0.0220, 0.0415, 0.000036),
        (4300000064, 0.7, 0.27, 0.125, 0.176, 0.0219, 0.0410, 0.000036),
        (4300000065, 0.8, 0.24, 0.080, 0.187, 0.0219, 0.0406, 0.000037),
        (4300000071, 0.44, 0.27, 0.072, 0.200, 0.0220, 0.0404, 0.000035),
        (4300000072, 0.44, 0.21, 0.079, 0.135, 0.0219, 0.0409, 0.000035),
        (4300000073, 0.44, 0.36, 0.077, 0.152, 0.0222, 0.0399, 0.000035),
        (4300000074, 0.44, 0.31, 0.079, 0.177, 0.0219, 0.0399, 0.000036),
        (4300000075, 0.44, 0.33, 0.092, 0.190, 0.0222, 0.0403, 0.000037),
        (4300000076, 0.44, 0.31, 0.092, 0.170, 0.0224, 0.0405, 0.000035),
        (4300000077, 0.44, 0.19, 0.118, 0.228, 0.0220, 0.0402, 0.000035),
    ],
    "broken_tiny": {
        "sound": ((), 0.00000, 0.00054, 0.0000, 0.00000, 0.00000),
        "bfloat16_state": (("STATE_COARSE_TOL",),
                           0.00003, 0.00056, 0.0012, 0.00905, 1.00000),
        "no_normaliser": (("LOGIT_TIE_TOL", "GAP_RATIO", "STATE_REL_TOL"),
                          0.46884, 0.00032, 0.9097, 0.99433, 0.00000),
        "no_gate": (("GAP_RATIO", "STATE_REL_TOL"),
                    0.12403, 0.00009, 0.4838, 1.00000, 0.00000),
        "pad_advances_the_state": (("GAP_RATIO", "STATE_REL_TOL"),
                                   0.07518, 0.00008, 0.4186, 0.55317, 0.00000),
        "no_sqrt2": (("GAP_RATIO", "STATE_REL_TOL"),
                     0.04322, 0.00030, 0.3339, 0.37243, 0.00008),
    },
}

#: what ``program_config`` sets the program's ``gate_bias`` to (the
#: configuration file's ``assumed`` names it). The program's initialiser
#: draws ``W_G`` as every projection, ``0.02 N``: ``W_G u`` is then ``N(0,
#: 1.4^2)`` and a token's decay ``sigmoid`` of it, 0.5 on average: a state
#: that forgets in two tokens, which no trained retention model keeps 260
#: MiB a slot for, and under which a padded position, a dropped gate or a
#: bfloat16 state would leave hardly a trace to judge. With 4 added nine
#: decays in ten lie between 0.84 and 0.998 (a memory of 6 to 500 tokens, a
#: head and a token their own); over the correctness requests the mean reads
#: 0.958-0.959 and the range 0.04-0.99999 (my chip runs, PR 56: every run
#: prints them, ``decay_mean`` / ``decay_min`` / ``decay_max``).
RETENTION_GATE_BIAS = 4.0

#: positions the MLP, the projections and a block of queries take at a time
_ROW_BLOCK = 1024
_QUERY_BLOCK = 512
#: rows of the head the logits take at a time
_VOCAB_BLOCK = 8192


def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``BrumbyConfig``. A key the program cannot honour is refused (by the
    program's own ``from_published``), and so is a state or a product of
    another type than the configuration states."""
    from lzy_tpu.models.brumby import BrumbyConfig

    kw = {"gate_bias": RETENTION_GATE_BIAS, **doc.get("program", {}), **over}
    cfg = BrumbyConfig.from_published(
        doc, dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]), **kw)
    stated = doc.get("retention_state_dtype", "float32")
    if jnp.dtype(cfg.state_dtype) != jnp.dtype(stated):
        raise ValueError(
            f"the configuration states retention_state_dtype {stated}; the "
            f"program keeps its state in {jnp.dtype(cfg.state_dtype)}: a "
            f"different configuration")
    product = doc.get("retention_product_dtype", doc["param_dtype"])
    if jnp.dtype(cfg.dtype) != jnp.dtype(product):
        raise ValueError(
            f"the configuration states retention_product_dtype {product}; "
            f"the program's products take q, k, v and phi in "
            f"{jnp.dtype(cfg.dtype)}")
    return cfg


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in one program, in the type
    they are served in: the program's initialiser as it is."""
    from lzy_tpu.models import brumby

    return jax.block_until_ready(jax.jit(
        lambda key: brumby.init_params(cfg, key),
        out_shardings=out_shardings)(jax.random.PRNGKey(seed % (2 ** 31))))


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole head; ``x`` [T, H, D] at
    positions 0 .. T - 1, angles in float32 whatever ``x`` is."""
    t, _, d = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def phi(x):
    """``[..., d]`` -> ``[..., d / 2 + 1, d]`` in ``x``'s type: the feature
    map in the layout of the program's state leaves (the module's
    docstring), so that ``sum(phi(a) * phi(b)) = (a . b)^2``."""
    d = x.shape[-1]
    m = np.arange(d // 2 + 1)[:, None]
    i = np.arange(d)[None, :]
    weight = (np.where(m == 0, 1.0, math.sqrt(2.0))
              * ((m < d // 2) | (i < d // 2))).astype(np.float32)
    return x[..., None, :] * x[..., (i - m) % d] * weight.astype(x.dtype)


@jax.jit
def _attend(qb, k, v, cs_q, cs_k, first, eps):
    """A block of queries ``qb`` [B, G, D] at positions ``first ..`` against
    the keys up to the block's end: ``[B, G, D]``."""
    d = qb.shape[-1]
    s = jnp.einsum("bgd,sd->gbs", qb, k) / math.sqrt(d)
    at = first + jnp.arange(qb.shape[0])
    keep = at[:, None] >= jnp.arange(k.shape[0])[None, :]
    seg = cs_q[:, None] - cs_k[None, :]
    w = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0)
    a = (w.astype(qb.dtype)[None] * s * s).astype(qb.dtype)
    num = jnp.einsum("gbs,sd->bgd", a, v)
    den = a.sum(-1).T[..., None]
    return (num / (den + eps.astype(qb.dtype))).astype(qb.dtype)


@jax.jit
def _state_after(k, v, cs, last):
    """``S`` [tiles, D of v, D] and ``z`` [tiles, D] after position
    ``last``, the direct sum over a block of positions (``k``, ``v`` [B, D],
    ``cs`` [B] the running log decay less its value at ``last``; a position
    past ``last`` has weight 0)."""
    w = jnp.where(jnp.arange(k.shape[0]) <= last, jnp.exp(jnp.minimum(
        -cs, 0.0)), 0.0).astype(k.dtype)
    pk = phi(k) * w[:, None, None]
    return (jnp.einsum("smi,sv->mvi", pk, v).astype(k.dtype),
            pk.sum(0).astype(k.dtype))


def _retention(u, w, cfg, dt, last):
    """A layer's mixer over the whole sequence ``u`` [T, hidden]: its output
    ``[T, hidden]``, the state after position ``last`` (``S`` [KV, tiles, D,
    D], ``z`` [KV, tiles, D]) and the decays ``exp(l)`` [T, KV]."""
    t = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv

    def project(name, heads):
        return jnp.concatenate([
            (u[first:first + _ROW_BLOCK]
             @ w[name]["kernel"].astype(dt)).astype(dt)
            for first in range(0, t, _ROW_BLOCK)]).reshape(t, heads, -1)

    q, k, v = project("q_proj", h), project("k_proj", kv), \
        project("v_proj", kv)
    log_g = jax.nn.log_sigmoid(
        project("g_proj", kv)[..., 0].astype(jnp.float32) + cfg.gate_bias)
    q = _rope(_rms_norm(q, w["q_norm"]["scale"].astype(dt),
                        cfg.norm_eps).astype(dt), cfg.rope_theta)
    k = _rope(_rms_norm(k, w["k_norm"]["scale"].astype(dt),
                        cfg.norm_eps).astype(dt), cfg.rope_theta)
    # the running log decay is float32 in the control too (the limits'
    # comment says why)
    cs = jnp.cumsum(log_g, axis=0)
    outs, ss, zs = [], [], []
    for j in range(kv):
        qg, kg, vg, cg = q[:, j * g:(j + 1) * g], k[:, j], v[:, j], cs[:, j]
        ys = []
        for first in range(0, t, _QUERY_BLOCK):
            end = min(first + _QUERY_BLOCK, t)
            ys.append(_attend(qg[first:end], kg[:end], vg[:end],
                              cg[first:end], cg[:end], first,
                              jnp.float32(cfg.retention_eps)))
        outs.append(jnp.concatenate(ys))
        s_sum = z_sum = 0.0
        for first in range(0, last + 1, _ROW_BLOCK):
            sl = slice(first, first + _ROW_BLOCK)
            s_blk, z_blk = _state_after(kg[sl], vg[sl], cg[sl] - cg[last],
                                        jnp.int32(last - first))
            s_sum, z_sum = s_sum + s_blk, z_sum + z_blk
        ss.append(s_sum)
        zs.append(z_sum)
    y = jnp.concatenate(outs, axis=1).reshape(t, h * d)
    out = jnp.concatenate([
        (y[first:first + _ROW_BLOCK]
         @ w["o_proj"]["kernel"].astype(dt)).astype(dt)
        for first in range(0, t, _ROW_BLOCK)])
    return out, (jnp.stack(ss), jnp.stack(zs)), jnp.exp(log_g)


@jax.jit
def _mlp_rows(ub, wg, wu, wd):
    """A block of rows through the MLP; the weights arrive as they are kept
    and are upcast inside the program."""
    dt = ub.dtype
    hid = jax.nn.silu((ub @ wg.astype(dt)).astype(dt)) \
        * (ub @ wu.astype(dt)).astype(dt)
    return (hid.astype(dt) @ wd.astype(dt)).astype(dt)


def _mlp(u, w):
    wg, wu, wd = (w[n]["kernel"]
                  for n in ("gate_proj", "up_proj", "down_proj"))
    # one block in flight: a queued program holds its temporaries (three
    # upcast weights, 1 GB) from the moment it is queued
    return jnp.concatenate([
        jax.block_until_ready(
            _mlp_rows(u[first:first + _ROW_BLOCK], wg, wu, wd))
        for first in range(0, u.shape[0], _ROW_BLOCK)])


def features(params, tokens, cfg, dtype=jnp.float32, last=None):
    """Hidden states before the final norm ``[T, hidden]`` of one sequence
    ``tokens`` [1, T]; a layer's ``(S, z)`` after position ``last`` (the
    sequence's end unless given), in layer order; and the decays ``exp(l)``
    ``[layers, T, KV]``."""
    dt = jnp.dtype(dtype)
    t = tokens.shape[1]
    last = t - 1 if last is None else int(last)
    states, decays = [], []
    with _precision(dt):
        x = params["embed_tokens"][tokens[0]].astype(dt)
        for i in range(cfg.n_layers):
            u = _rms_norm(x, params[f"layer_{i}_norm"]["scale"].astype(dt),
                          cfg.norm_eps).astype(dt)
            y, state, decay = _retention(u, params[f"layer_{i}"], cfg, dt,
                                         last)
            states.append(jax.block_until_ready(state))
            decays.append(decay)
            x = (x + y).astype(dt)
            u = _rms_norm(x, params[f"layer_{i}_mlp_norm"]["scale"].astype(
                dt), cfg.norm_eps).astype(dt)
            x = (x + _mlp(u, params[f"layer_{i}_mlp"])).astype(dt)
    return x, states, jnp.stack(decays)


def head_logits(params, x, cfg, dtype=jnp.float32):
    """The final norm and the head over hidden states ``x`` [R, hidden]."""
    dt = jnp.dtype(dtype)
    head = params["lm_head"]
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"].astype(dt),
                      cfg.norm_eps).astype(dt)
        # a block of the vocabulary's rows at a time: the whole head upcast
        # and transposed is 3.1 GB beside the engine
        return jnp.concatenate([
            (x @ head[first:first + _VOCAB_BLOCK].astype(dt).T).astype(
                jnp.float32)
            for first in range(0, head.shape[0], _VOCAB_BLOCK)], axis=-1)


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    x, _, _ = features(params, tokens, cfg, dtype)
    return head_logits(params, x[jnp.asarray(rows)], cfg, dtype)


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def held_to_the_limits(exact, chosen, judged, judged_control,
                       state_gap: float = 0.0,
                       coarse_share: float = 0.0) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings four limits.
    Where the judged tokens are at least ``GAP_RATIO_MIN_TOKENS`` and their
    mean gap is over ``GAP_RATIO`` of the control's, or ``state_gap`` (the
    run's largest so far) is over ``STATE_REL_TOL``, or ``coarse_share``
    (the run's largest) over ``STATE_COARSE_TOL``, every chosen token's
    logit is set ``2 x LOGIT_TIE_TOL`` below the reference's best: the
    largest gap the harness then reads is over its limit, and the run comes
    out not correct. So a ``worst_logit_gap`` of exactly ``2 x
    LOGIT_TIE_TOL`` in a result's notes means: the run's ``brumby_judged``
    lines on stderr say which limit."""
    exact = np.array(exact, np.float32)
    chosen = np.asarray(chosen)
    # the first limit is the harness's own to judge
    if set(failed_limits(judged, judged_control, state_gap,
                         coarse_share)) - {"LOGIT_TIE_TOL"}:
        exact[np.arange(len(chosen)), chosen] = \
            exact.max(axis=-1) - 2.0 * LOGIT_TIE_TOL
    return exact


def failed_limits(judged, judged_control, state_gap: float = 0.0,
                  coarse_share: float = 0.0) -> list:
    """The names of the limits these readings are over (the first is the
    harness's own and is judged by it all the same)."""
    mine = float(np.mean(judged)) if len(judged) else 0.0
    control = float(np.mean(judged_control)) if len(judged) else 0.0
    out = []
    if len(judged) and float(np.max(judged)) > LOGIT_TIE_TOL:
        out.append("LOGIT_TIE_TOL")
    if len(judged) >= GAP_RATIO_MIN_TOKENS and mine > GAP_RATIO * control:
        out.append("GAP_RATIO")
    if state_gap > STATE_REL_TOL:
        out.append("STATE_REL_TOL")
    if coarse_share > STATE_COARSE_TOL:
        out.append("STATE_COARSE_TOL")
    return out


#: this process's correctness requests so far: the program's gaps and the
#: control's, a pair of arrays a request; and the requests' state readings
#: (a run is one process, and the harness's only calls of ``logits_at`` are
#: its correctness requests)
_JUDGED: list = []
_STATE_GAPS: list = []
_COARSE: list = []


# -- the state the program leaves ------------------------------------------------

def coarse_share(state) -> float:
    """The share of a float32 state's entries, zeros left out, that a
    bfloat16 holds exactly (the low 16 bits of the pattern are 0): 2^-16 of
    a state that was summed in float32, all of one that was kept or rounded
    in bfloat16."""
    bits = jax.lax.bitcast_convert_type(state.astype(jnp.float32), jnp.uint32)
    filled = state != 0
    return float(jnp.sum(((bits & 0xFFFF) == 0) & filled)
                 / jnp.maximum(jnp.sum(filled), 1))


def state_gaps(leaves: dict, states: list, cfg) -> dict:
    """The states a finished request left in the engine (``leaves``:
    ``PagedInferenceEngine.state_leaves()``, ``S`` ``[slots, KV, tiles, D,
    D]`` and ``z`` ``[slots, KV, steps, tiles a step, D]`` a layer) against
    the reference's after the same positions (``states``: ``(S, z)`` in layer
    order). The request's slot is not told: it is the one whose rows lie
    nearest the reference's over all layers. ``S`` and ``z``: a layer each,
    in layer order; ``gap``: the larger of their means."""
    def layer_of(name):
        return int(name.split("layer_")[1].split("'")[0])

    by_layer = {which: {layer_of(name): leaf for name, leaf in leaves.items()
                        if name.endswith(f"['{which}']")}
                for which in ("S", "z")}
    want = list(range(cfg.n_layers))
    if sorted(by_layer["S"]) != want or sorted(by_layer["z"]) != want \
            or len(states) != cfg.n_layers:
        raise LookupError(
            f"the engine's state leaves are of layers "
            f"{sorted(by_layer['S'])} (S) and {sorted(by_layer['z'])} (z); "
            f"the reference has {len(states)} states, of layers {want}")

    def one(leaf, exact):
        exact = exact.astype(jnp.float32).reshape(leaf.shape[1:])
        off = jnp.square(leaf.astype(jnp.float32) - exact[None])
        return jnp.sqrt(off.reshape(leaf.shape[0], -1).sum(1)
                        / jnp.square(exact).sum())

    s_gap = np.stack([one(by_layer["S"][i], states[i][0]) for i in want])
    z_gap = np.stack([one(by_layer["z"][i], states[i][1]) for i in want])
    slot = int((s_gap + z_gap).mean(axis=0).argmin())
    return {"slot": slot, "S": s_gap[:, slot].tolist(),
            "z": z_gap[:, slot].tolist(),
            "gap": float(max(s_gap[:, slot].mean(), z_gap[:, slot].mean())),
            "coarse": float(np.mean([coarse_share(by_layer["S"][i][slot])
                                     for i in want]))}


def control_state_gap(control: list, states: list) -> float:
    """The control's ``S`` against the reference's, the mean over the
    layers: what ``state_gaps`` reads of a program that is bfloat16
    throughout."""
    return float(np.mean([
        jnp.sqrt(jnp.square(c[0].astype(jnp.float32) - e[0]).sum()
                 / jnp.square(e[0]).sum())
        for c, e in zip(control, states)]))


def _serving_engine(params):
    """The engine that serves these weights. The harness hands a model file
    its weights and no engine (PERF.md section 7), so it is looked for among
    the process's objects, by the identity of ``params``."""
    import gc

    from lzy_tpu.serving import PagedInferenceEngine

    found = [o for o in gc.get_objects()
             if isinstance(o, PagedInferenceEngine) and o.params is params]
    if len(found) != 1:
        raise LookupError(
            f"{len(found)} engines serve these weights: the state limits "
            f"read the one engine of a run")
    return found[0]


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request, once it is
    answered: ``tokens`` [1, T] is the prompt and the served tokens
    (padded), ``rows`` the positions whose logits chose them, so the served
    tokens are ``tokens[0, rows + 1]``. The float32 reference's logits
    there, held to the four limits over the run's requests so far. The
    engine has read ``tokens[0, :rows[-1] + 1]`` (the last served token was
    emitted and never fed), so that is where the state is taken."""
    rows = np.asarray(rows)
    last = int(rows[-1])
    x, states, decays = features(params, tokens, cfg, last=last)
    exact = head_logits(params, x[jnp.asarray(rows)], cfg)
    del x
    served = np.asarray(tokens)[0, rows + 1]
    x, rough_states, _ = features(params, tokens, cfg, jnp.bfloat16,
                                  last=last)
    control = np.asarray(head_logits(
        params, x[jnp.asarray(rows)], cfg, jnp.bfloat16)).argmax(axis=-1)
    del x
    _JUDGED.append((gaps(exact, served), gaps(exact, control)))
    mine, ctrl = (np.concatenate(x) for x in zip(*_JUDGED))
    state = state_gaps(_serving_engine(params).state_leaves(), states, cfg)
    _STATE_GAPS.append(state["gap"])
    _COARSE.append(state["coarse"])
    read = np.asarray(decays[:, :last + 1])
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"brumby_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((ctrl > 0).sum()),
        "worst_gap": float(mine.max()),
        "control_worst_gap": float(ctrl.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(ctrl.mean()),
        "state_slot": state["slot"], "state_gap": state["gap"],
        "state_gap_S_by_layer": [round(g, 5) for g in state["S"]],
        "state_gap_z_by_layer": [round(g, 5) for g in state["z"]],
        "control_state_gap": control_state_gap(rough_states, states),
        "state_coarse_share": state["coarse"],
        "decay_mean": float(read.mean()), "decay_min": float(read.min()),
        "decay_max": float(read.max()),
        "failed": failed_limits(mine, ctrl, max(_STATE_GAPS),
                                max(_COARSE))}}),
        file=sys.stderr, flush=True)
    return held_to_the_limits(exact, served, mine, ctrl, max(_STATE_GAPS),
                              max(_COARSE))


# -- the counts: bytes and operations, from shapes ----------------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def _features(cfg) -> int:
    """The features of ``phi`` that are not the padded tail: 8,256."""
    return cfg.head_dim * (cfg.head_dim + 1) // 2


def kv_bytes_per_token(cfg) -> int:
    """A token of context keeps nothing: there is no pool."""
    return 0


def retention_state_bytes(cfg) -> int:
    """One slot's ``S`` and ``z`` over the layers, float32, the 8,256
    features and not the 64 zeros of the padded tail (272,646,144 bytes at 8
    layers: 8 key-value heads of 8,256 x 128 and 8,256)."""
    return cfg.n_layers * cfg.n_kv_heads * _features(cfg) \
        * (cfg.head_dim + 1) * 4


def retention_step_bytes(cfg, rows: float) -> float:
    """What ``power_retention_update`` of one decode round must move: a live
    row's state read and written, every layer; an idle slot's state is not
    moved. The padded tail (0.8% more) is moved and not charged, so the
    share reads low by that and never over 100%."""
    return 2.0 * rows * retention_state_bytes(cfg)


def retention_chunk_flops(cfg, start: int, tokens: int) -> float:
    """Arithmetic of the retention over ``tokens`` prefill positions in
    chunks of ``chunk_size`` (wherever ``start`` is: the state's size does
    not follow the context): a position's five-to-one query of the state and
    of the normaliser (``2 x 40 x 8,256 x 129``), the state's update (``2 x 8
    x 8,256 x 129``) and the chunk's own masked products (``4 x 40 x chunk x
    128``), every layer: 104.9 M a position a layer."""
    f, d = _features(cfg), cfg.head_dim
    return cfg.n_layers * float(tokens) * (
        2.0 * (cfg.n_heads + cfg.n_kv_heads) * f * (d + 1)
        + 4.0 * cfg.n_heads * cfg.chunk_size * d)


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight once
    (the embedding table is not read: a round gathers ``rows`` rows of it)
    and the rows' states read and written; nothing follows the context's
    length."""
    table = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    return param_bytes - table + retention_step_bytes(cfg, rows)

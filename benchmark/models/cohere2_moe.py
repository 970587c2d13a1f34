"""Command A+ (``model_type`` ``cohere2_moe``; ``command-a-plus-05-2026``) as
the benchmark has to know it: the program's side, the plain reference, the
counts. A configuration file says ``"model": "cohere2_moe"``
(``benchmark/models/__init__.py`` lists the names a model file gives).

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision, with no cache, no
page table, no kernel and no batching: one sequence, every position against
every position it may see. It imports nothing from ``lzy_tpu.models``: it
reads the weights from the program's parameter tree by name and does its own
arithmetic. For layer ``l`` with input ``x`` (the language model only: the
catalog gives no sizes for the image tower, so none is built):

- ``h = LN(x)``: ``(x - mean) * rsqrt(var + 1e-5) * weight``, no bias.
- attention, 128 query heads over 8 key-value heads of 128, no bias, no
  q/k norm, scale ``128^-1/2``. ``layer_types[l] == "sliding_attention"``:
  rotary over the whole head (theta 50000, value ``i`` paired with
  ``i + 64``) and query ``i`` sees keys ``i - 4096 < j <= i``;
  ``"full_attention"``: no positional embedding, causal over everything.
- experts: ``s = sigmoid(h W_r)`` over 128; the 8 largest; weights
  ``s[chosen] / (sum of the 8 + 1e-20)``; expert ``e``:
  ``(silu(h Wg_e) * (h Wu_e)) Wd_e`` at width 4096; plus **the mean of the
  4 shared experts' outputs** (each the same form at width 4096: the
  program's one gated MLP of width 16384 cut into its four). Dropless.
  **The share**: of the router's experts this chip holds ``experts_held``;
  a chosen expert outside it adds nothing, here as in the program, and that
  partial result goes on.
- ``x' = x + attention + experts``: the parallel block.
- final ``LN``, logits ``= logit_scale x h E^T`` over the held rows of the
  tied embedding.

Departures from the published implementation, all for memory or for the cut:
weights are upcast one layer (one expert) at a time; attention runs over
blocks of queries, each block projecting its own queries (12,288 positions
at 128 heads fit beside the engine); the experts are a loop over the held
ones, every position through each (weight 0 where it did not choose it).
``reference_logits(..., dtype=bfloat16)`` is the **control**: the same
arithmetic with weights, activations, router, norms and softmax in bfloat16
at the default precision.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: **Two limits**, both on how far below the float32 reference's best logit
#: the served tokens sit (their *gap*; 0 where the program chose what the
#: reference would). A run's correctness requests are 4 x 256 decoded
#: tokens behind prompts of 1,096 / 2,739 / 4,430 / 8,903 tokens: 1,024
#: judged positions, half of them behind a context the window has cut. All
#: readings on the chip at the published widths (my chip runs, PR 41: twelve
#: seeds, each its own weights and prompts, 12,288 tokens; PERF.md section
#: 6).
#:
#: 1. ``DIFFER_RATIO``: over a run's judged tokens, the served tokens that
#:    are not the reference's own choice may number at most 0.75 of **the
#:    control's choices that are not, at the same positions** (the control:
#:    this reference wholly in bfloat16, weights, activations, router, norms
#:    and softmax, its choices judged behind the same served sequence).
#:    **This is the precision limit**, and it is paired because nothing
#:    unpaired separates the two over 1,024 tokens: four layers leave both
#:    close to the reference (the program's choice differs at 6-24 of a
#:    run's 1,024 positions, 1.31% of the 12,288; the control's at 18-63,
#:    3.72%; mean gap a run 0.00009-0.00105 against 0.00075-0.00464; largest
#:    gap a run 0.043-0.355 against 0.125-0.661: each pair of ranges
#:    overlaps), and a seed that is hard for one is hard for the other. The
#:    ratio of the two counts, a run: 0.238-0.511 over the twelve seeds
#:    (mean 0.357, deviation 0.078). The control read through the same
#:    comparison is 1, by construction and with no spread, and comes out
#:    not correct; 0.75 is five deviations above the program's mean, 0.24
#:    above its largest reading. (The ratio of the mean gaps reads
#:    0.058-0.445, deviation 0.139: one near-tie that falls the other way
#:    is a third of a run's mean, so the count is held, not the mean.)
#:    **It is also the limit that sees a fault one page wide.** Planted in
#:    the program at the cell's own sizes, two seeds each (seeds whose sound
#:    ratios are 0.41 and 0.27): a window page returned one page early, so
#:    that a live row's table reads scratch under its oldest 32 visible
#:    keys, reads 1.267 and 0.818; a window read that starts a page late
#:    0.917 and 0.556. Three of the four come out not correct; a window
#:    layer's attention over 4,096 keys of random weights is nearly flat,
#:    so 32 of them move a choice about as often as bfloat16 does.
#: 2. ``LOGIT_TIE_TOL``: no single token more than 2.0 below the best. The
#:    guard for what a count cannot see: a token that is simply wrong (a
#:    chunk boundary, the window's edge, a returned page read again). The
#:    logits' standard deviation is 1.28 over 32,768 rows, so the best sits
#:    ~5 above a row taken blindly. The program's largest of the 12,288
#:    calibration tokens is 0.355 and of the 14 runs of the cell since
#:    (14,336 tokens) 0.552: a near-tie among the 128 router scores that
#:    falls the other way changes a layer's result; 2.0 is over three times
#:    that. The control's largest: 0.661. This limit the control passes, as
#:    it may: it has to fail one of the cell's limits, not each. **From
#:    above it is held by a planted fault**: the window layers reading from
#:    page 0, over the pages they returned (the table reads scratch there),
#:    leaves a run's worst token 6.18 and 6.10 below the best (2.32 and
#:    1.74 in the request 334 positions past the window, 6.18 and 6.10 in
#:    the one 4,807 past; 288 and 335 of 1,024 choices differ): 2.0 has 3.6
#:    times of room over the largest sound reading and 3.05 under the
#:    fault's. It does not see the faults one page wide above (their worst
#:    token: 0.144-0.369, inside the sound runs' range): no value of this
#:    limit would, and the first limit is there for them.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``) and hands this file no verdict to give:
#: ``held_to_both_limits`` says how the first limit reaches it all the same
#: (as ``benchmark/models/deepseek_v3.py``; PERF.md section 7, row 10).
LOGIT_TIE_TOL = 2.0
DIFFER_RATIO = 0.75
DIFFER_RATIO_MIN_TOKENS = 1000

_QUERY_BLOCK = 64
SLIDING = "sliding_attention"


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``Cohere2MoeConfig``. A key the program cannot honour is refused (by the
    program's own ``from_published``)."""
    from lzy_tpu.models.cohere2_moe import Cohere2MoeConfig

    return Cohere2MoeConfig.from_published(
        doc, dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in the type they are served in:
    the program's initialiser as it is, in one jitted call (four layers)."""
    from lzy_tpu.models import cohere2_moe

    params = jax.jit(lambda key: cohere2_moe.init_params(cfg, key))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    if out_shardings is not None:
        params = jax.device_put(params, out_shardings)
    return jax.block_until_ready(params)


# -- the plain reference ------------------------------------------------------

def _layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotary(x, positions, theta):
    """``x`` [T, H, D] rotated by its position: value ``i`` pairs with
    ``i + D/2``, frequencies ``theta^(-2i/D)``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1 = x[..., :d // 2].astype(jnp.float32)
    x2 = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _attention(u, w, cfg, dt, windowed: bool):
    """Every query against every key it may see, a block of queries at a
    time (each block projects its own queries)."""
    t = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(t)
    # the program stores the query projection [out, in] (its kernel_t)
    k = (u @ w["k_proj"]["kernel"]).reshape(t, kv, d)
    v = (u @ w["v_proj"]["kernel"]).reshape(t, kv, d)
    if windowed:
        k = rotary(k, pos, cfg.rope_theta)
    block = min(_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")

    def one(xs):
        ub, first = xs
        at = first + jnp.arange(block)
        q = (ub @ w["q_proj"]["kernel_t"].T).reshape(block, h, d)
        if windowed:
            q = rotary(q, at, cfg.rope_theta)
        s = jnp.einsum("qkgd,lkd->kgql", q.reshape(block, kv, h // kv, d),
                       k) * d ** -0.5
        keep = pos[None, :] <= at[:, None]
        if windowed:
            keep &= pos[None, :] > at[:, None] - cfg.window
        pr = jax.nn.softmax(
            jnp.where(keep, s.astype(jnp.float32), -1e30), axis=-1)
        out = jnp.einsum("kgql,lkd->qkgd", pr.astype(dt), v)
        return out.reshape(block, h * d) @ w["o_proj"]["kernel"]

    out = jax.lax.map(one, (u.reshape(-1, block, u.shape[-1]),
                            jnp.arange(0, t, block)))
    return out.reshape(t, -1)


def route(u, w, cfg):
    """``[T, held]``: each position's weight for each held expert (0 where
    it did not choose it). No correction bias, no scaling; the chosen
    scores are renormalised over all of them, held here or not."""
    lo, hi = cfg.experts_held
    scores = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(scores, cfg.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
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


def shared_mean(u, w, cfg, dt=jnp.float32):
    """The mean of the shared experts' outputs: the program's one gated MLP
    of ``n_shared x expert_width`` is the shared experts side by side, so
    expert ``k`` is columns ``k x width .. (k + 1) x width`` of its gate and
    up matrices and the same rows of its down matrix."""
    width = cfg.expert_width
    total = jnp.zeros_like(u)
    for k in range(cfg.n_shared):
        cols = slice(k * width, (k + 1) * width)
        hid = jax.nn.silu(u @ w["shared_gate"]["kernel"][:, cols].astype(dt)) \
            * (u @ w["shared_up"]["kernel"][:, cols].astype(dt))
        total = total + hid @ w["shared_down"]["kernel"][cols].astype(dt)
    return total / cfg.n_shared


_BIG = ("experts_gate", "experts_up", "experts_down", "shared_gate",
        "shared_up", "shared_down")


def _cast(w, dt):
    """The experts' weights stay as they are stored and are upcast one
    expert at a time."""
    return {k: v if k in _BIG else jax.tree_util.tree_map(
        lambda a: a.astype(dt), v) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("windowed", "cfg", "dt"))
def _layer(x, norm, w, moe, *, windowed, cfg, dt):
    """One layer over one sequence ``[T, hidden]``: the parallel block."""
    w, moe = _cast(w, dt), _cast(moe, dt)
    u = _layer_norm(x, norm.astype(dt), cfg.norm_eps).astype(dt)
    return (x + _attention(u, w, cfg, dt, windowed)
            + routed_experts(u, moe, cfg, dt)
            + shared_mean(u, moe, cfg, dt)).astype(dt)


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def features(params, tokens, cfg, dtype=jnp.float32):
    """Hidden states before the final norm, ``[T, hidden]``, of one sequence
    ``tokens`` [1, T]."""
    dt = jnp.dtype(dtype)
    with _precision(dt):
        x = params["embed_tokens"][tokens[0]].astype(dt)
        for i, kind in enumerate(cfg.layer_types):
            x = _layer(x, params[f"layer_{i}_norm"]["scale"],
                       params[f"layer_{i}"], params[f"layer_{i}_moe"],
                       windowed=kind == SLIDING, cfg=cfg, dt=dt)
    return x


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    dt = jnp.dtype(dtype)
    x = features(params, tokens, cfg, dtype)[rows]
    with _precision(dt):
        x = _layer_norm(x, params["final_norm"]["scale"].astype(dt),
                        cfg.norm_eps).astype(dt)
        logits = x @ params["embed_tokens"].astype(dt).T
        return (logits * cfg.logit_scale).astype(jnp.float32)


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
    at least ``DIFFER_RATIO_MIN_TOKENS`` and the served tokens that are not
    the reference's choice (gap over 0) number more than ``DIFFER_RATIO`` of
    the control's that are not, the chosen tokens' logits are lowered by
    ``LOGIT_TIE_TOL``: the largest gap the harness then reads is the true
    one plus ``LOGIT_TIE_TOL``, over its limit, and the run comes out not
    correct. So a ``worst_logit_gap`` above ``LOGIT_TIE_TOL`` in a result's
    notes means: take ``LOGIT_TIE_TOL`` off; if what is left is under it,
    the program chose no closer to the reference than its bfloat16
    control."""
    exact = np.array(exact, np.float32)
    chosen = np.asarray(chosen)
    mine = np.count_nonzero(np.asarray(judged) > 0)
    control = np.count_nonzero(np.asarray(judged_control) > 0)
    if len(judged) >= DIFFER_RATIO_MIN_TOKENS \
            and mine > DIFFER_RATIO * control:
        exact[np.arange(len(chosen)), chosen] -= LOGIT_TIE_TOL
    return exact


#: the gaps of this process's correctness requests so far, the program's and
#: the control's, one pair of arrays a request (a run is one process, and
#: the harness's only calls of ``logits_at`` are its correctness requests,
#: one after another)
_JUDGED: list = []


def control_choices(params, tokens, rows, cfg) -> np.ndarray:
    """The control's reading: what the bfloat16 reference chooses at the
    positions the served tokens are judged at (the same sequence before
    each)."""
    return np.asarray(reference_logits(params, tokens, rows, cfg,
                                       jnp.bfloat16)).argmax(axis=-1)


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request: ``tokens`` [1, T]
    is the prompt and the served tokens (padded), ``rows`` the positions
    whose logits chose them, so the served tokens are ``tokens[0, rows +
    1]``. The float32 reference's logits there, held to both limits over
    the run's requests so far."""
    exact = reference_logits(params, tokens, rows, cfg)
    served = np.asarray(tokens)[0, np.asarray(rows) + 1]
    _JUDGED.append((gaps(exact, served),
                    gaps(exact, control_choices(params, tokens, rows, cfg))))
    mine, control = (np.concatenate(x) for x in zip(*_JUDGED))
    return held_to_both_limits(exact, served, mine, control)


# -- the counts: bytes and operations, from shapes ----------------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def layer_token_bytes(cfg) -> int:
    """Keys and values of one token in one layer, of either kind."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * _itemsize(cfg)


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token of context a decode round reads where
    no window cuts it: every layer's."""
    return cfg.n_layers * layer_token_bytes(cfg)


def attention_step_bytes(cfg, keys: float) -> float:
    """What the attention reads of one decode round must move: ``keys`` is
    the cached keys its rows read, summed over the layers, as the program
    counted them (``lzy_attn_window_keys_total + lzy_attn_full_keys_total``
    of a traced round: a row at position p reads p + 1 in a full layer and
    min(p + 1, window) in a window layer), each with its value, once. The
    kernel moves whole pages, so up to a page more a row a layer than this
    charges; the rows' queries and results are left out."""
    return keys * layer_token_bytes(cfg)


def chunk_read_flops(cfg, start: int, tokens: int) -> float:
    """The arithmetic of the attention reads of prefill programs that carry
    positions ``start .. start + tokens - 1`` of a prompt: scores and
    weighted values, ``4 x heads x head_dim`` operations a (query, visible
    key) pair, the query at position p seeing p + 1 keys in a full layer
    and min(p + 1, window) in a window layer. Bound: compute. A tile of 32
    queries reads a visible key's 4096 bytes once for 4 x 128 x 128 x 32
    operations: 512 operations a byte against the chip's 240, so the
    arithmetic binds, not the bytes."""
    p = np.arange(start, start + tokens, dtype=np.float64) + 1
    pairs = cfg.kv_layers * p.sum() \
        + cfg.window_layers * np.minimum(p, cfg.window).sum()
    return 4.0 * cfg.n_heads * cfg.head_dim * pairs


def expert_bytes(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg.d_model * cfg.expert_width * _itemsize(cfg)


def experts_step_bytes(cfg, rows: float, share: float) -> float:
    """What the grouped expert product of one decode round must read: the
    weights of the held experts its rows reached, over the layers (every one
    has experts). ``share`` is the share of the held experts reached as the
    program counted it over the traced rounds (``readers/counted_rows.py``):
    never the expectation under uniform routing."""
    return cfg.n_layers * cfg.n_held * share * expert_bytes(cfg)


def routed_param_bytes(cfg) -> int:
    return cfg.n_layers * cfg.n_held * expert_bytes(cfg)


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float, share: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight
    outside the routed experts once (the tied embedding is the head: read
    whole), the routed experts those rows reached (``share`` of the held
    ones, as the program counted it: ``readers/decode_counted_roofline.py``)
    and the keys and values of the resident context: every token in the
    full layers, and in the window layers no more than the rows' windows
    (``min(resident_tokens, rows x window)``: the clients' side gives the
    sum over the rows, not each row's, so rows under the window beside rows
    over it are charged up to the window each; that is at most a hundredth
    of a round's bytes here)."""
    outside = param_bytes - routed_param_bytes(cfg)
    token = layer_token_bytes(cfg)
    return outside + experts_step_bytes(cfg, rows, share) \
        + cfg.kv_layers * token * resident_tokens \
        + cfg.window_layers * token * min(resident_tokens, rows * cfg.window)

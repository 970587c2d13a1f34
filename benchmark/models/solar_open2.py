"""Solar-Open2-250B (``model_type`` ``solar_open2``) as the benchmark has to
know it: the program's side, the plain reference, the counts. A
configuration file says ``"model": "solar_open2"``
(``benchmark/models/__init__.py`` lists the names a model file gives).

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision. It imports nothing
from ``lzy_tpu.models``: it reads the weights from the program's parameter
tree by name and does its own arithmetic. 48 layers in the published model
(8 in the benchmark's cut), each ``h + mixer(RMSNorm(h))`` then
``h + moe(RMSNorm(h))``:

- **KDA** (every layer not in ``gqa_layers``), 64 heads of 128:
  ``[q, k, v] = silu(causal depthwise conv1d(W_qkv x, 4))`` (no bias);
  ``q``, ``k`` L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q``
  times ``128^-1/2``; ``alpha = exp(-exp(A_log_h) softplus(W_up W_down x +
  dt_bias))`` a channel; ``beta = 2 sigmoid(W_beta x)`` a head;
  ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
  ``o_t = S_t^T q_t``; ``W_o (RMSNorm_head(o) * sigmoid(W_gup W_gdown x))``.
  **The plain recurrence**, one position after another (``lax.scan``): no
  chunks, no triangular solve. The state is ``[K, V]`` here (the program
  keeps its transpose).
- **gated attention** (``gqa_layers``): 64 query and 8 key/value heads of
  128, no bias, **no positional embedding** (``use_rope`` false), causal
  softmax at ``128^-1/2``, the heads' output times ``sigmoid(W_gate x)``
  elementwise, ``o_proj``.
- **experts** (every layer): ``s = sigmoid(W_r x)``; the 8 largest of
  ``s + bias``; weights ``s[chosen] / (sum + 1e-20)`` times
  ``routed_scaling_factor``; expert ``e``: ``(silu(x Wg_e) * (x Wu_e)) Wd_e``;
  plus the shared expert, the same form. Dropless. **The share**: of the
  router's experts this chip holds ``experts_held``; a chosen expert outside
  it adds nothing, here as in the program, and that partial result goes on
  to the next layer. The shared expert is whole.
- final ``RMSNorm``, untied head over the vocabulary slice held.

Departures from the published implementation, all for memory or for the cut:
weights are upcast one layer (one expert) at a time; attention runs over
blocks of queries; the experts are a loop over the held ones, every position
through each (weight 0 where it did not choose it).
``reference_logits(..., dtype=bfloat16)`` is the **control**: the same arithmetic
with weights, activations and recurrence state in bfloat16 at the default
precision.
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
#: tokens, 1,024 judged positions. All readings on the chip at the
#: published widths (my chip runs, PR 33: twenty-two seeds, each its own
#: weights and prompts, twelve of them at 384 tokens a request for the
#: per-token figures; PERF.md section 6).
#:
#: 1. ``WIDE_GAP_SHARE``: of a run's judged tokens at most 1.5% may sit more
#:    than ``WIDE_GAP`` = 0.5 below the best. **This is the precision
#:    limit.** The program: 0-10 of 1,024 over twenty-two seeds (at most
#:    0.98%; mean 4.2; 56 of 12,288 in the twelve of the calibration). The
#:    control (this reference wholly in bfloat16: weights, activations,
#:    delta-rule state, router and sums, its choices judged at the same
#:    positions): 21-79 of 1,024 over the same seeds (2.1-7.7%; mean 51; the
#:    21: one of its four requests had no token over 0.5, the program's
#:    largest gap there 0.28; about one request in ten is as easy). The
#:    limit, 15 of 1,024, is half again the program's largest reading and
#:    under three quarters of the control's smallest; as a count (4.2 +- 2.4
#:    for the program) it is four and a half deviations out. The mean gap
#:    separates them too, less widely (0.023-0.043 a run against
#:    0.076-0.120); one request's 256 tokens do not (a request of the
#:    program read up to 5 tokens over 0.5, one of the control as few as
#:    0), which is why the limit is held over the run and not a request.
#:    Gross faults, injected into the reference and judged the same way
#:    (three seeds x four requests of 256): the shared expert of one layer
#:    dropped, 134-156 of 256 over 0.5; the last layer (its mixer and
#:    experts) dropped, 80-98 of 256. Both far over.
#: 2. ``LOGIT_TIE_TOL``: no single token more than 2.0 below the best. The
#:    guard for what a share cannot see: one token that is simply wrong (a
#:    chunk boundary, a slot's first position). The logits' standard
#:    deviation is 1.28 over 24,576 rows, so the best sits ~5 above a row
#:    taken blindly. The program's largest of 18,432 tokens: 1.008 (one
#:    over 1.0, five over 0.75; beyond 0.5 the tail falls off like
#:    exp(-gap / 0.09), so 2.0 is ten such lengths past the largest), and
#:    1.107 over the twenty runs of the cell since (20,480 tokens); the
#:    control's: 1.555 (26 of 12,288 over 1.0). This limit the control
#:    passes, as it may: it has to fail one of the cell's limits, not each.
#:
#: Why the program is off the reference at all: per judged token its choice
#: differs in 23.1% of positions and then sits 0.148 below in the mean (the
#: control's: 38.7% and 0.265; root-mean-square logit error 0.135-0.144
#: against 0.225-0.282). That is the bfloat16 residual stream's error, which
#: the published model has too: each of the sixteen sublayers, fed the
#: reference's own input, is within 0.4-1.4% of the reference's output (the
#: expert layers at the upper end: a near-tie among the 320 router scores
#: swaps a held expert in 0.2-1.1% of rows), and the chain compounds that to
#: 0.14 on logits of standard deviation 1.28. ``program_config`` still
#: refuses a program whose ``kda`` cache leaf is not the configuration's
#: ``kda_state_dtype``, and tier 1 pins the float32 state and router
#: arithmetic (``tests/test_kda.py``, ``tests/test_solar_open2.py``): a
#: state in bfloat16 alone was not measured against these limits.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``) and hands this file no verdict to give:
#: ``held_to_both_limits`` says how the first limit reaches it all the same,
#: and PERF.md section 7 which edit to ``harness/serve.py`` would let that
#: go.
LOGIT_TIE_TOL = 2.0
WIDE_GAP = 0.5
WIDE_GAP_SHARE = 0.015
WIDE_GAP_MIN_TOKENS = 1000

_QUERY_BLOCK = 512


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``SolarOpen2Config``. A key the program cannot honour is refused."""
    from lzy_tpu.models.solar_open2 import SolarOpen2Config

    want = {"use_rope": False, "use_gqa_gate": True,
            "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
            "first_k_dense_replace": 0, "n_shared_experts": 1,
            "norm_topk_prob": True, "tie_word_embeddings": False}
    for key, value in want.items():
        if doc.get(key) != value:
            raise ValueError(f"the program serves {key} = {value!r}, the "
                             f"configuration says {doc.get(key)!r}")
    lin = doc["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("the program's KDA heads are not grouped")
    layers = doc["num_hidden_layers"]
    period = doc["gqa_interval"] + 1
    if list(doc["gqa_layers"]) != list(range(0, layers, period)):
        raise ValueError("gqa_layers is not every (gqa_interval + 1)-th "
                         "layer of num_hidden_layers")
    lo = doc.get("experts_held_from", 0)
    cfg = SolarOpen2Config(
        vocab_size=doc["vocab_size"], d_model=doc["hidden_size"],
        n_layers=layers, attn_layers=tuple(doc["gqa_layers"]),
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        gate_rank=doc["kda_gate_rank"],
        n_routed_experts=doc["router_width"],
        experts_held=(lo, lo + doc["n_routed_experts"]),
        top_k=doc["num_experts_per_tok"],
        expert_width=doc["moe_intermediate_size"],
        shared_width=doc["n_shared_experts"] * doc["moe_intermediate_size"],
        routed_scaling=float(doc["routed_scaling_factor"]),
        norm_eps=float(doc["rms_norm_eps"]),
        max_seq_len=doc["max_position_embeddings"],
        dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)
    _refuse_another_state_dtype(cfg, doc.get("kda_state_dtype", "float32"))
    return cfg


def _refuse_another_state_dtype(cfg, stated: str) -> None:
    """The configuration states the recurrence state's type, and served
    tokens cannot tell a lower one (``LOGIT_TIE_TOL``): look at the cache
    leaf the program would keep (shapes only, nothing is computed)."""
    module = cfg.paged_model(page_size=16, kv_pages=2, kernel="lax",
                             kv_quant=None)
    cache = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        page_table=jnp.zeros((1, 1), jnp.int32)))["cache"]
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if getattr(path[-1], "key", None) == "kda" \
                and leaf.dtype != jnp.dtype(stated):
            raise ValueError(
                f"the configuration states kda_state_dtype {stated}; the "
                f"program keeps its recurrence state in {leaf.dtype}: a "
                f"different configuration")


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in one program, in the type
    they are served in: the program's initialiser as it is."""
    from lzy_tpu.models import solar_open2

    make = jax.jit(lambda key: solar_open2.init_params(cfg, key),
                   out_shardings=out_shardings)
    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (2 ** 31))))


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def delta_rule(q, k, v, alpha, beta, dt=jnp.float32):
    """The recurrence one position after another: ``q``/``k``/``alpha``
    [T, H, K], ``v`` [T, H, V], ``beta`` [T, H]; the state ``[H, K, V]``
    starts at 0. Returns ``o`` [T, H, V]."""
    def one(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[:, :, None] * state
        write = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = (state + k_t[:, :, None] * write[:, None, :]).astype(dt)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    h, dk, dv = k.shape[1], k.shape[2], v.shape[2]
    _, o = jax.lax.scan(one, jnp.zeros((h, dk, dv), dt),
                        (q, k, v, alpha, beta))
    return o


def _kda(u, w, cfg, dt):
    t = u.shape[0]
    h, d, kk = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel
    hd = h * d
    qkv = u @ w["qkv_proj"]["kernel"]
    # causal depthwise convolution: position t sees t - 3 .. t
    padded = jnp.concatenate([jnp.zeros((kk - 1, 3 * hd), dt), qkv])
    conv = jax.nn.silu(sum(w["conv_kernel"][i] * padded[i:i + t]
                           for i in range(kk)))
    q, k, v = (conv[:, i * hd:(i + 1) * hd].reshape(t, h, d)
               for i in range(3))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    step = jax.nn.softplus(
        (u @ w["decay_down"]["kernel"]) @ w["decay_up"]["kernel"]
        + w["dt_bias"]).reshape(t, h, d)
    alpha = jnp.exp(-jnp.exp(w["A_log"])[:, None] * step)
    beta = 2.0 * jax.nn.sigmoid(u @ w["beta_proj"]["kernel"])
    o = delta_rule(q, k, v, alpha, beta, dt)
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
    gate = jax.nn.sigmoid(
        (u @ w["gate_down"]["kernel"]) @ w["gate_up"]["kernel"])
    return ((o * w["out_norm"]).reshape(t, hd) * gate) @ w["o_proj"]["kernel"]


def _attention(u, w, cfg):
    t = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ w["q_proj"]["kernel"]).reshape(t, kv, h // kv, d)
    k = (u @ w["k_proj"]["kernel"]).reshape(t, kv, d)
    v = (u @ w["v_proj"]["kernel"]).reshape(t, kv, d)
    outs = []
    for start in range(0, t, _QUERY_BLOCK):
        stop = min(start + _QUERY_BLOCK, t)
        s = jnp.einsum("qkgd,lkd->kgql", q[start:stop], k) * d ** -0.5
        keep = jnp.arange(t)[None, :] <= jnp.arange(start, stop)[:, None]
        pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        outs.append(jnp.einsum("kgql,lkd->qkgd", pr, v))
    out = jnp.concatenate(outs).reshape(t, h * d)
    out = out * jax.nn.sigmoid(u @ w["gate_proj"]["kernel"])
    return out @ w["o_proj"]["kernel"]


def route(u, w, cfg):
    """``[T, held]`` float32: each position's weight for each held expert
    (0 where it did not choose it)."""
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


_BIG = ("experts_gate", "experts_up", "experts_down")


def _cast(w, dt):
    """The routed experts' weights stay as they are stored and are upcast
    one expert at a time."""
    return {k: v if k in _BIG else jax.tree_util.tree_map(
        lambda a: a.astype(dt), v) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("attention", "cfg", "dt"))
def _layer(x, norm, w, moe_norm, moe, *, attention, cfg, dt):
    """One layer over one sequence ``[T, hidden]``."""
    w, moe = _cast(w, dt), _cast(moe, dt)
    u = _rms_norm(x, norm.astype(dt), cfg.norm_eps)
    x = (x + (_attention(u, w, cfg) if attention
              else _kda(u, w, cfg, dt))).astype(dt)
    u = _rms_norm(x, moe_norm.astype(dt), cfg.norm_eps)
    return (x + routed_experts(u, moe, cfg, dt)
            + shared_expert(u, moe)).astype(dt)


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
        for i in range(cfg.n_layers):
            x = _layer(x, params[f"layer_{i}_norm"]["scale"],
                       params[f"layer_{i}"],
                       params[f"layer_{i}_moe_norm"]["scale"],
                       params[f"layer_{i}_moe"],
                       attention=i in cfg.attn_layers, cfg=cfg, dt=dt)
    return x


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    dt = jnp.dtype(dtype)
    x = features(params, tokens, cfg, dtype)[rows]
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"].astype(dt),
                      cfg.norm_eps)
        return (x @ params["lm_head"].astype(dt).T).astype(jnp.float32)


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def held_to_both_limits(exact, chosen, judged) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings two limits, the
    second over all of a run's judged tokens. ``judged`` holds the gaps of
    the run's correctness requests so far, this one's among them. Where
    they are at least ``WIDE_GAP_MIN_TOKENS`` and more than
    ``WIDE_GAP_SHARE`` of them are over ``WIDE_GAP``, the chosen tokens'
    logits are lowered by ``LOGIT_TIE_TOL``: the largest gap the harness
    then reads is the true one plus ``LOGIT_TIE_TOL``, over its limit, and
    the run comes out not correct. So a ``worst_logit_gap`` above
    ``LOGIT_TIE_TOL`` in a result's notes means: take ``LOGIT_TIE_TOL``
    off; if what is left is under it, too many tokens sat far below."""
    exact = np.array(exact, np.float32)
    chosen, judged = np.asarray(chosen), np.asarray(judged)
    if len(judged) >= WIDE_GAP_MIN_TOKENS \
            and np.mean(judged > WIDE_GAP) > WIDE_GAP_SHARE:
        exact[np.arange(len(chosen)), chosen] -= LOGIT_TIE_TOL
    return exact


#: the gaps of this process's correctness requests so far, one array a
#: request (a run is one process, and the harness's only calls of
#: ``logits_at`` are its correctness requests, one after another)
_JUDGED: list = []


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request: ``tokens`` [1, T]
    is the prompt and the served tokens (padded), ``rows`` the positions
    whose logits chose them, so the served tokens are ``tokens[0, rows +
    1]``. The float32 reference's logits there, held to both limits over
    the run's requests so far."""
    exact = reference_logits(params, tokens, rows, cfg)
    served = np.asarray(tokens)[0, np.asarray(rows) + 1]
    _JUDGED.append(gaps(exact, served))
    return held_to_both_limits(exact, served, np.concatenate(_JUDGED))


def control_choices(params, tokens, rows, cfg) -> np.ndarray:
    """The control's reading: what the bfloat16 reference chooses at the
    positions the served tokens are judged at (the same sequence before
    each)."""
    return np.asarray(reference_logits(params, tokens, rows, cfg,
                                       jnp.bfloat16)).argmax(axis=-1)


# -- the counts: bytes a decode round must move, from shapes ------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token of context: the attention layers only."""
    return 2 * cfg.kv_layers * cfg.n_kv_heads * cfg.head_dim * _itemsize(cfg)


def expert_bytes(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg.d_model * cfg.expert_width * _itemsize(cfg)


def experts_step_bytes(cfg, rows: float, share: float) -> float:
    """What the grouped expert product of one decode round must read: the
    weights of the held experts its rows reached, over the layers (every one
    has experts). ``share`` is the share of the held experts reached as the
    program counted it over the traced rounds (``readers/counted_rows.py``);
    ``rows`` is not needed for it. **Never the expectation under uniform
    routing**: skewed routing reaches fewer, and the share of the roofline
    then reads too high (PR 29's 143%)."""
    return cfg.n_layers * cfg.n_held * share * expert_bytes(cfg)


def kda_state_bytes(cfg) -> int:
    """One slot's delta-rule state over the KDA layers, float32."""
    return cfg.kda_layers * cfg.kda_heads * cfg.kda_head_dim ** 2 * 4


def conv_state_bytes(cfg) -> int:
    return cfg.kda_layers * (cfg.conv_kernel - 1) * 3 * cfg.kda_dim \
        * _itemsize(cfg)


def state_step_bytes(cfg, rows: float) -> float:
    """What the state update of one decode round must move: the delta-rule
    state of the rows that decoded, read and written (the kernel moves no
    idle slot's; the convolution's window is the model step's, not the
    kernel's, and is left out)."""
    return 2.0 * rows * kda_state_bytes(cfg)


def routed_param_bytes(cfg) -> int:
    return cfg.n_layers * cfg.n_held * expert_bytes(cfg)


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float, share: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight
    outside the routed experts once (the head's slice among them; the
    embedding table is a lookup of ``rows`` rows and is left out), the
    routed experts those rows reached (``share`` of the held ones, as the
    program counted it: ``readers/decode_counted_roofline.py``), the keys
    and values of the resident context, and the rows' state read and
    written."""
    embed = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    outside = param_bytes - routed_param_bytes(cfg) - embed
    return outside + experts_step_bytes(cfg, rows, share) \
        + kv_bytes_per_token(cfg) * resident_tokens \
        + 2.0 * rows * (kda_state_bytes(cfg) + conv_state_bytes(cfg))

"""AI21-Jamba2-3B (``model_type`` ``jamba``) as the benchmark has to know it:
the program's side, the plain reference, the counts. A configuration file
says ``"model": "jamba"`` (``benchmark/models/__init__.py`` lists the names a
model file gives).

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision, with no cache, no
page table, no kernel and no batching: one sequence. It imports nothing from
``lzy_tpu.models``: it reads the weights from the program's parameter tree by
name and does its own arithmetic. 28 blocks (HF ``modeling_jamba.py``), block
``i`` with input ``x``::

    h   = x + mixer_i(RMSNorm(x))
    out = h + down(silu(gate(RMSNorm(h))) * up(RMSNorm(h)))      2560 -> 8192

- the mixer is **attention** where ``i % attn_layer_period ==
  attn_layer_offset`` (layers 7 and 21): 20 query heads over 1 key-value head
  of 128, no positional embedding, no bias, causal softmax at ``128^-1/2``;
- and **Mamba-1** in the other 26: ``[xs, z] = in_proj(u)``; ``xc =
  silu(causal depthwise conv1d(xs, 4) + bias)``; ``[dt, B, C] = x_proj(xc)``
  (160 + 16 + 16); **each through an RMSNorm with its own weight** (Jamba's
  addition to Mamba); ``dt = softplus(dt_proj(dt) + dt_bias)``; ``A =
  -exp(A_log)``; ``S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c]
  B_t[n] xc_t[c]``; ``y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] xc_t[c]``;
  ``out_proj(y * silu(z))``. **The plain recurrence**, one position after
  another (``lax.scan``, a few positions a turn so that 8,448 of them take
  seconds and not minutes);
- final ``RMSNorm``, logits over the tied embedding.

Departures from the published implementation, all for memory: weights are
upcast one block at a time; attention runs over blocks of queries. The
program keeps ``A_log`` as ``[state, channels]`` (the published tensor
transposed: its kernels hold the channels on the lanes), and the reference
reads it so. ``reference_logits(..., dtype=bfloat16)`` is the **control**:
the same arithmetic with weights, activations, norms, softmax and the
recurrence state in bfloat16 at the default precision.
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

#: **Three limits.** The first two are on how far below the float32
#: reference's best logit the served tokens sit (their *gap*; 0 where the
#: program chose what the reference would), the third on the recurrence
#: state itself. A run's correctness requests are 4 x 256 decoded tokens
#: behind prompts of 3,256 / 4,757 / 6,182 / 7,754 tokens (the harness picks
#: them from the cell's own levels under ``pad_to`` 8448): 1,024 judged
#: positions and four final states. All readings on the chip at the
#: published widths and full depth (my chip runs, PR 44; ``CALIBRATION`` and
#: ``STATE_CALIBRATION`` below, PERF.md section 6).
#:
#: 1. ``GAP_RATIO``: over a run's judged tokens, the served tokens' mean gap
#:    may be at most 0.7 of **the control's mean gap at the same positions**
#:    (the control: this reference wholly in bfloat16, weights, activations,
#:    the residual stream, norms, softmax and the recurrence state, its
#:    choices judged behind the same served sequence). It is paired because
#:    nothing unpaired separates the two over 1,024 tokens: 28 layers leave
#:    both well off the reference (the program's choice differs at 60-89 of
#:    a run's 1,024 positions, the control's at 95-135), and a seed that is
#:    hard for one is hard for the other. The mean is held and not the count
#:    of differing choices: here a run has some seventy of them, so no
#:    single near-tie is more than a fourteenth of the sum, and the two
#:    means stand further apart (the ratio of the counts reads 0.54-0.75
#:    over the seeds, the ratio of the means 0.23-0.44 over twelve, and
#:    0.28-0.47 in the seven final runs of the cell). The
#:    control read through the same comparison is 1, by construction and
#:    with no spread, and comes out not correct; so the limit cannot stand
#:    three times over the largest sound reading (that would be 1.32, past
#:    anything the comparison reads): it lies between the two, 0.26 over
#:    0.44 and 0.30 under 1.
#:    **What the program's side of the ratio rests on**: the residual stream
#:    in float32 (56 sums a position; in bfloat16 the program read 0.72 and
#:    0.83 of the control's mean, two seeds: no limit under 1 would have
#:    held) and dt, B, C and the scan in float32.
#:    **It sees a mixer without its three inner norms** (planted in the
#:    program at the cell's own sizes: 986 of 1,024 choices differ, mean gap
#:    2.05 against the control's 0.0055) **and a recurrence state rounded to
#:    bfloat16 after every program in four runs of six** (planted the same
#:    way: 1.25 / 0.93 / 0.65 / 1.47 / 0.50 / 3.91 of the control's mean
#:    where the sound program read 0.34-0.42 on the same seeds): not a limit
#:    to hold the state's precision by. The third one is.
#: 2. ``LOGIT_TIE_TOL``: no single token more than 1.0 below the best. The
#:    guard for what a mean cannot see: a token that is simply wrong (a
#:    state spliced into the wrong slot, a chunk boundary, a convolution
#:    window taken at a pad). The logits' standard deviation is 1.0 over
#:    65,536 rows, so the best sits about 4.5 above a row taken blindly. The
#:    program's largest of 12,288 calibration tokens is 0.153, the control's
#:    0.231: this limit the control passes, as it may (it has to fail one of
#:    the cell's limits, not each). **From above it is held by the planted
#:    fault**: without the inner norms a run's worst token sits 5.47 below
#:    the best and 927 of 1,024 more than 0.5 below.
#: 3. ``STATE_REL_TOL``: **the precision of the recurrence state, read from
#:    the state.** A freed slot keeps what its last round left
#:    (``PagedInferenceEngine.state_leaves()``), so after each correctness
#:    request the slot's 26 ``ssm`` leaves are compared with this
#:    reference's states after the same positions (the prompt and every
#:    served token but the last, which was emitted and never fed). A leaf's
#:    reading is the distance over its **slow entries**, those that remember
#:    more than ``STATE_SLOW_POSITIONS`` positions (``softplus(dt_bias) x -A
#:    x 256 < 1``: about 3% of a leaf), relative to the reference's norm
#:    there; a request's is the mean over the 26 leaves, a run's the largest
#:    of its four, and it may be at most 0.02. The sound program reads
#:    0.0048-0.0081 over six seeds and 0.0051-0.0068 in the seven final
#:    runs of the cell (its state is float32, its inputs are
#:    bfloat16 products and its stream drifts from the reference's with
#:    depth: the first leaf alone reads 0.0004-0.0020, the deepest leaves
#:    0.01-0.04, which is why the worst leaf is not the number held: it
#:    reads 0.015-0.037 sound and 0.077-0.092 faulty, a factor of two). The
#:    state rounded to bfloat16 after every program reads **0.048-0.061** on
#:    the same seeds (first leaf 0.046-0.087): the limit stands 2.5 times
#:    over the largest sound run and 2.4 times under the smallest faulty
#:    one. Over all entries the two stand closer (0.016-0.019 against
#:    0.031-0.127): an entry that forgets in a few positions is remade from
#:    its inputs before its roundings add up, and carries most of a leaf's
#:    norm. **The fault has to be planted with** ``lax.reduce_precision``:
#:    the TPU compiler drops an ``astype(bfloat16).astype(float32)`` pair
#:    (it only loses precision), which is what this PR's first calibration
#:    planted and why it found a bfloat16 state invisible (it had planted
#:    nothing: the slot's state was not bfloat16-representable). Beside
#:    this limit ``program_config`` refuses a program whose ``ssm`` leaf is
#:    not the configuration's ``ssm_state_dtype`` and the kernels refuse a
#:    state that is not float32; this limit is what would catch one that
#:    stores float32 and rounds inside.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``) and hands this file no verdict to give and no engine:
#: ``held_to_the_limits`` says how the other two limits reach it all the
#: same (as ``benchmark/models/cohere2_moe.py``; PERF.md section 7, row 10),
#: ``_serving_engine`` how the state is found.
LOGIT_TIE_TOL = 1.0
GAP_RATIO = 0.7
GAP_RATIO_MIN_TOKENS = 1000
STATE_SLOW_POSITIONS = 256
STATE_REL_TOL = 0.02

#: what the first two limits were set from: a run's 1,024 judged tokens, the
#: program's reading and the control's (choices that differ from the
#: reference's, mean gap, largest gap), twelve seeds of
#: ``_chip_checkout``-style calibration runs (the correctness requests
#: alone) and the planted faults
CALIBRATION = {
    "program": [
        # seed, differ, control differ, mean gap, control's, worst, control's
        (2440000101, 74, 135, 0.002022, 0.005663, 0.1531, 0.1836),
        (2440000102, 71, 95, 0.002067, 0.004709, 0.0908, 0.1468),
        (2440000103, 72, 133, 0.001914, 0.005739, 0.0819, 0.1512),
        (2440000104, 66, 119, 0.001455, 0.006323, 0.0796, 0.1914),
        (2440000105, 60, 110, 0.001485, 0.005912, 0.0674, 0.1923),
        (2440000106, 89, 134, 0.002467, 0.006342, 0.1190, 0.1706),
        (2440000501, 80, 123, 0.002180, 0.005897, 0.0795, 0.1946),
        (2440000502, 77, 107, 0.001918, 0.004728, 0.1312, 0.1635),
        (2440000503, 78, 132, 0.002181, 0.005589, 0.0737, 0.1423),
        (2440000504, 80, 126, 0.002183, 0.005214, 0.0890, 0.1615),
        (2440000505, 70, 122, 0.001786, 0.004819, 0.0711, 0.1742),
        (2440000506, 68, 122, 0.002237, 0.006502, 0.0840, 0.2312)],
    "state_rounded_to_bfloat16": [
        (2440000501, 118, 111, 0.006604, 0.005288, 0.3113, 0.1996),
        (2440000502, 100, 114, 0.004758, 0.005106, 0.2160, 0.1752),
        (2440000503, 97, 122, 0.003601, 0.005574, 0.1886, 0.1768),
        (2440000504, 138, 131, 0.008288, 0.005625, 0.3216, 0.2118),
        (2440000505, 93, 132, 0.002692, 0.005424, 0.1129, 0.2369),
        (2440000506, 232, 141, 0.030188, 0.007726, 0.6313, 0.1928)],
    "no_inner_norms": [
        (2440000101, 986, 117, 2.052868, 0.005453, 5.4712, 0.1505)],
    "residual_stream_in_bfloat16": [
        (2440000001, 98, 132, 0.003897, 0.005420, 0.1319, 0.1824),
        (2440000002, 99, 106, 0.003362, 0.004062, 0.1363, 0.1425)],
}

#: the third limit's readings, the largest of a run's four requests: seed,
#: the mean over the leaves (the number held), the first leaf, the worst leaf
STATE_CALIBRATION = {
    "program": [
        (2440000501, 0.00546, 0.00052, 0.01535),
        (2440000502, 0.00591, 0.00055, 0.02812),
        (2440000503, 0.00810, 0.00075, 0.03658),
        (2440000504, 0.00480, 0.00038, 0.01546),
        (2440000505, 0.00682, 0.00094, 0.02023),
        (2440000506, 0.00663, 0.00037, 0.02299)],
    "state_rounded_to_bfloat16": [
        (2440000501, 0.05499, 0.06565, 0.08774),
        (2440000502, 0.05047, 0.06632, 0.07727),
        (2440000503, 0.04930, 0.06602, 0.08274),
        (2440000504, 0.06076, 0.08748, 0.08748),
        (2440000505, 0.04773, 0.04589, 0.07926),
        (2440000506, 0.05752, 0.08338, 0.09212)],
}

_QUERY_BLOCK = 256
#: positions a turn of the reference's scan takes (unrolled: the recurrence
#: is the same, a turn's dispatch is paid once for all of them)
_SCAN_TURN = 16


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``JambaConfig``. A key the program cannot honour is refused (by the
    program's own ``from_published``), and so is a recurrence state of
    another type than the configuration states."""
    from lzy_tpu.models.jamba import JambaConfig

    cfg = JambaConfig.from_published(
        doc, dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)
    _refuse_another_state_dtype(cfg, doc.get("ssm_state_dtype", "float32"))
    if doc.get("residual_dtype", "float32") != "float32":
        raise ValueError(
            f"the program keeps its residual stream in float32, the "
            f"configuration says residual_dtype {doc['residual_dtype']!r}")
    return cfg


def _refuse_another_state_dtype(cfg, stated: str) -> None:
    """The configuration states the recurrence state's type: look at the
    cache leaf the program would keep (shapes only, nothing is computed)."""
    module = cfg.paged_model(page_size=16, kv_pages=2, kernel="lax",
                             kv_quant=None)
    cache = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
        page_table=jnp.zeros((1, 1), jnp.int32)))["cache"]
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if getattr(path[-1], "key", None) == "ssm" \
                and leaf.dtype != jnp.dtype(stated):
            raise ValueError(
                f"the configuration states ssm_state_dtype {stated}; the "
                f"program keeps its recurrence state in {leaf.dtype}: a "
                f"different configuration")


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in one program, in the type
    they are served in: the program's initialiser as it is."""
    from lzy_tpu.models import jamba

    make = jax.jit(lambda key: jamba.init_params(cfg, key),
                   out_shardings=out_shardings)
    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (2 ** 31))))


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def selective_recurrence(x, step, a, bm, cm, dt):
    """``y_t[c] = sum_n S_t[n, c] C_t[n]`` with ``S_t = exp(step_t A) S_{t-1}
    + step_t B_t x_t``, one position after another from a zero state: ``x``,
    ``step`` [T, Di], ``a`` [N, Di], ``bm`` / ``cm`` [T, N]. The state is
    kept in ``dt`` (float32; the control's bfloat16). Returns ``y`` and the
    state after the last position (a position whose ``step`` is 0 leaves
    it as it was)."""
    t, di = x.shape
    turn = _SCAN_TURN if t % _SCAN_TURN == 0 else 1

    def one(state, inp):
        x_t, s_t, b_t, c_t = inp
        state = (jnp.exp(s_t * a) * state
                 + b_t[:, None] * (s_t * x_t)).astype(dt)
        return state, jnp.sum(state * c_t[:, None], axis=0)

    state, y = jax.lax.scan(one, jnp.zeros(a.shape, dt), (x, step, bm, cm),
                            unroll=turn)
    return y, state


def _mamba(u, w, cfg, dt, last):
    """The mixer's output and its recurrence state after position ``last``
    (the steps behind it are 0: nothing behind ``last`` is read by a
    caller that asks for the state there)."""
    t = u.shape[0]
    di, n, r, k = cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.conv_kernel
    xz = u @ w["in_proj"]["kernel"]
    xs, z = xz[:, :di], xz[:, di:]
    # causal depthwise convolution: position t sees t - 3 .. t
    padded = jnp.concatenate([jnp.zeros((k - 1, di), dt), xs])
    xc = jax.nn.silu(w["conv_bias"] + sum(
        w["conv_kernel"][i] * padded[i:i + t] for i in range(k)))
    dbc = xc @ w["x_proj"]["kernel"]
    eps = cfg.norm_eps
    dt_r = _rms_norm(dbc[:, :r], w["dt_norm"]["scale"], eps)
    bm = _rms_norm(dbc[:, r:r + n], w["b_norm"]["scale"], eps)
    cm = _rms_norm(dbc[:, r + n:], w["c_norm"]["scale"], eps)
    step = jax.nn.softplus(dt_r @ w["dt_proj"]["kernel"] + w["dt_bias"])
    step = jnp.where(jnp.arange(t)[:, None] <= last, step, 0).astype(dt)
    y, state = selective_recurrence(xc, step, -jnp.exp(w["A_log"]), bm, cm,
                                    dt)
    y = (y + w["D"] * xc) * jax.nn.silu(z)
    return y.astype(dt) @ w["out_proj"]["kernel"], state


def _attention(u, w, cfg, dt):
    """Every query against every key before it, a block of queries at a
    time."""
    t = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(t)
    k = (u @ w["k_proj"]["kernel"]).reshape(t, kv, d)
    v = (u @ w["v_proj"]["kernel"]).reshape(t, kv, d)
    block = _QUERY_BLOCK if t % _QUERY_BLOCK == 0 else t

    def one(xs):
        ub, first = xs
        at = first + jnp.arange(block)
        q = (ub @ w["q_proj"]["kernel"]).reshape(block, kv, h // kv, d)
        s = jnp.einsum("qkgd,lkd->kgql", q, k) * d ** -0.5
        keep = pos[None, :] <= at[:, None]
        pr = jax.nn.softmax(
            jnp.where(keep, s.astype(jnp.float32), -1e30), axis=-1)
        out = jnp.einsum("kgql,lkd->qkgd", pr.astype(dt), v)
        return out.reshape(block, h * d) @ w["o_proj"]["kernel"]

    out = jax.lax.map(one, (u.reshape(-1, block, u.shape[-1]),
                            jnp.arange(0, t, block)))
    return out.reshape(t, -1)


def _mlp(h, w):
    return (jax.nn.silu(h @ w["gate_proj"]["kernel"])
            * (h @ w["up_proj"]["kernel"])) @ w["down_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("attention", "cfg", "dt"))
def _block(x, norm, w, mlp_norm, mlp, last, *, attention, cfg, dt):
    """One block over one sequence ``[T, hidden]``, and a Mamba block's
    recurrence state after position ``last`` (None for attention)."""
    cast = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(dt), tree)
    w, mlp = cast(w), cast(mlp)
    u = _rms_norm(x, norm.astype(dt), cfg.norm_eps).astype(dt)
    mixed, state = (_attention(u, w, cfg, dt), None) if attention \
        else _mamba(u, w, cfg, dt, last)
    h = (x + mixed).astype(dt)
    v = _rms_norm(h, mlp_norm.astype(dt), cfg.norm_eps).astype(dt)
    return (h + _mlp(v, mlp)).astype(dt), state


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def layer_is_attention(cfg, i: int) -> bool:
    """The order of the layer types: the two ``attn_layer_*`` keys."""
    return i % cfg.attn_period == cfg.attn_offset


def features(params, tokens, cfg, dtype=jnp.float32, last=None):
    """Hidden states before the final norm, ``[T, hidden]``, of one sequence
    ``tokens`` [1, T], and the Mamba layers' recurrence states ``[N, Di]``
    after position ``last`` (the sequence's end unless given; the hidden
    states behind ``last`` are then not the sequence's), in layer order."""
    dt = jnp.dtype(dtype)
    last = tokens.shape[1] - 1 if last is None else last
    states = []
    with _precision(dt):
        x = params["embed_tokens"][tokens[0]].astype(dt)
        for i in range(cfg.n_layers):
            x, state = _block(
                x, params[f"layer_{i}_norm"]["scale"], params[f"layer_{i}"],
                params[f"layer_{i}_mlp_norm"]["scale"],
                params[f"layer_{i}_mlp"], last,
                attention=layer_is_attention(cfg, i), cfg=cfg, dt=dt)
            if state is not None:
                states.append(state)
    return x, states


def head_logits(params, x, cfg, dtype=jnp.float32):
    """The final norm and the tied head over hidden states ``x`` [R,
    hidden]."""
    dt = jnp.dtype(dtype)
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"].astype(dt),
                      cfg.norm_eps).astype(dt)
        return (x @ params["embed_tokens"].astype(dt).T).astype(jnp.float32)


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    x, _ = features(params, tokens, cfg, dtype)
    return head_logits(params, x[rows], cfg, dtype)


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def held_to_the_limits(exact, chosen, judged, judged_control,
                       state_gap: float = 0.0) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings three limits.
    ``judged`` holds the gaps of the run's correctness requests so far, this
    one's among them, ``judged_control`` the control's at the same
    positions, ``state_gap`` the run's largest state reading so far. Where
    the tokens are at least ``GAP_RATIO_MIN_TOKENS`` and the served tokens'
    mean gap is more than ``GAP_RATIO`` of the control's, or where
    ``state_gap`` is over ``STATE_REL_TOL``, the chosen tokens' logits are
    lowered by ``LOGIT_TIE_TOL``: the largest gap the harness then reads is
    the true one plus ``LOGIT_TIE_TOL``, over its limit, and the run comes
    out not correct (through a served token that is not the reference's
    choice, whose gap only grows: a run has some seventy). So a
    ``worst_logit_gap`` above ``LOGIT_TIE_TOL`` in a result's notes means: take ``LOGIT_TIE_TOL`` off; if what is left is
    under it, one of the other two limits failed, and the run's
    ``jamba_judged`` lines on stderr say which."""
    exact = np.array(exact, np.float32)
    chosen = np.asarray(chosen)
    mine = float(np.mean(judged)) if len(judged) else 0.0
    control = float(np.mean(judged_control)) if len(judged) else 0.0
    if (len(judged) >= GAP_RATIO_MIN_TOKENS and mine > GAP_RATIO * control) \
            or state_gap > STATE_REL_TOL:
        exact[np.arange(len(chosen)), chosen] -= LOGIT_TIE_TOL
    return exact


#: the gaps of this process's correctness requests so far, the program's and
#: the control's, one pair of arrays a request, and the requests' state
#: readings (a run is one process, and the harness's only calls of
#: ``logits_at`` are its correctness requests, one after another)
_JUDGED: list = []
_STATE_GAPS: list = []


def control_choices(params, tokens, rows, cfg) -> np.ndarray:
    """The control's reading: what the bfloat16 reference chooses at the
    positions the served tokens are judged at (the same sequence before
    each)."""
    return np.asarray(reference_logits(params, tokens, rows, cfg,
                                       jnp.bfloat16)).argmax(axis=-1)


# -- the state itself ---------------------------------------------------------

def slow_entries(w):
    """Which of a Mamba layer's ``[N, Di]`` state entries remember more than
    ``STATE_SLOW_POSITIONS`` positions: at the step its bias alone gives a
    channel, ``exp(step A)`` of one position is above ``exp(-1 /
    STATE_SLOW_POSITIONS)``. These are the entries a coarser state loses
    first (an entry that forgets in a few positions is remade from its
    inputs before its rounding adds up)."""
    step = jax.nn.softplus(w["dt_bias"].astype(jnp.float32))
    return step[None, :] * jnp.exp(w["A_log"].astype(jnp.float32)) \
        * STATE_SLOW_POSITIONS < 1.0


@jax.jit
def _leaf_gaps(leaf, exact, slow):
    """Every slot's row of one state leaf ``[slots, N, Di]`` against the
    reference's state ``[N, Di]``: the distance over all entries and over
    the slow ones, each relative to the reference's own norm there."""
    off, size = jnp.square(leaf - exact[None]), jnp.square(exact)
    return (jnp.sqrt(off.sum((1, 2)) / size.sum()),
            jnp.sqrt((off * slow).sum((1, 2)) / (size * slow).sum()))


def state_gaps(leaves: dict, states: list, params, cfg) -> dict:
    """The recurrence state a finished request left in the engine
    (``leaves``: ``PagedInferenceEngine.state_leaves()``) against the
    reference's after the same positions (``states``, in layer order). The
    request's slot is not told: it is the one whose rows lie nearest the
    reference's over all layers (any other slot holds another sequence's
    state, or none: a distance near 1 or over it). ``all`` and ``slow``: a
    Mamba layer each, in layer order."""
    mamba = [i for i in range(cfg.n_layers) if not layer_is_attention(cfg, i)]
    by_layer = {int(name.split("layer_")[1].split("'")[0]): leaf
                for name, leaf in leaves.items() if name.endswith("['ssm']")}
    if sorted(by_layer) != mamba or len(states) != len(mamba):
        raise LookupError(
            f"the engine's ssm leaves are of layers {sorted(by_layer)}; "
            f"the reference has {len(states)} states, of layers {mamba}")
    whole, slowly = (np.stack(x) for x in zip(*(
        _leaf_gaps(by_layer[i], exact, slow_entries(params[f"layer_{i}"]))
        for i, exact in zip(mamba, states))))        # [layers, slots] each
    if not np.isfinite(slowly).all():
        raise ValueError(
            f"a layer has no state entry that remembers "
            f"{STATE_SLOW_POSITIONS} positions: the state limit has nothing "
            f"to read")
    slot = int(whole.mean(axis=0).argmin())
    return {"slot": slot, "all": whole[:, slot].tolist(),
            "slow": slowly[:, slot].tolist()}


def _serving_engine(params):
    """The engine that serves these weights. The harness hands a model file
    its weights and no engine (PERF.md section 7, row 10), so it is looked
    for among the process's objects, by the identity of ``params``."""
    import gc

    from lzy_tpu.serving import PagedInferenceEngine

    found = [o for o in gc.get_objects()
             if isinstance(o, PagedInferenceEngine) and o.params is params]
    if len(found) != 1:
        raise LookupError(
            f"{len(found)} engines serve these weights: the state limit "
            f"reads the one engine of a run")
    return found[0]


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request, once it is
    answered: ``tokens`` [1, T] is the prompt and the served tokens
    (padded), ``rows`` the positions whose logits chose them, so the served
    tokens are ``tokens[0, rows + 1]``. The float32 reference's logits
    there, held to the three limits over the run's requests so far. The
    engine has read ``tokens[0, :rows[-1] + 1]`` into the request's state
    (the last served token was emitted and never fed), so that is where the
    reference's state is taken."""
    rows = np.asarray(rows)
    x, states = features(params, tokens, cfg, last=int(rows[-1]))
    exact = head_logits(params, x[rows], cfg)
    served = np.asarray(tokens)[0, rows + 1]
    _JUDGED.append((gaps(exact, served),
                    gaps(exact, control_choices(params, tokens, rows, cfg))))
    mine, control = (np.concatenate(x) for x in zip(*_JUDGED))
    state = state_gaps(_serving_engine(params).state_leaves(), states,
                       params, cfg)
    _STATE_GAPS.append(float(np.mean(state["slow"])))
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"jamba_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((control > 0).sum()),
        "worst_gap": float(mine.max()),
        "control_worst_gap": float(control.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(control.mean()),
        "state_slot": state["slot"], "state_gap": _STATE_GAPS[-1],
        "state_gap_first_leaf": state["slow"][0],
        "state_gap_worst_leaf": max(state["slow"]),
        "state_gap_all_entries": float(np.mean(state["all"]))}}),
        file=sys.stderr, flush=True)
    return held_to_the_limits(exact, served, mine, control,
                              max(_STATE_GAPS))


# -- the counts: bytes and operations, from shapes ----------------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token of context: the attention layers only
    (1,024 bytes at the published widths: two layers of one head of 128)."""
    return 2 * cfg.kv_layers * cfg.n_kv_heads * cfg.head_dim * _itemsize(cfg)


def ssm_state_bytes(cfg) -> int:
    """One slot's recurrence state over the Mamba layers, float32."""
    return cfg.mamba_layers * cfg.ssm_state * cfg.d_inner * 4


def conv_state_bytes(cfg) -> int:
    """One slot's convolution windows over the Mamba layers."""
    return cfg.mamba_layers * (cfg.conv_kernel - 1) * cfg.d_inner \
        * _itemsize(cfg)


def slot_state_bytes(cfg) -> int:
    """What one slot keeps outside the pool (9,318,400 bytes at the
    published widths: 26 x (327,680 + 30,720))."""
    return ssm_state_bytes(cfg) + conv_state_bytes(cfg)


def state_step_bytes(cfg, rows: float) -> float:
    """What the state update of one decode round must move: the recurrence
    state of the live rows, read and written, every Mamba layer. (Its other
    operands, a row's ``x``, ``dt``, ``B``, ``C`` and ``y``, are a
    twenty-fifth of that and are left out: the share reads low by them,
    never high.)"""
    return 2.0 * rows * ssm_state_bytes(cfg)


def scan_bytes(cfg, tokens: float, programs: float = 1) -> float:
    """What ``selective_scan`` must move for ``programs`` prefill programs
    that carry ``tokens`` real positions, over the Mamba layers: its inputs
    (``x`` and ``dt`` a channel, ``B`` and ``C`` a state entry) and its
    output once, in the float32 the program hands them over in; the carried
    state in and out and ``A`` once. **Never the ``[T, Di, N]`` products**:
    an implementation that writes them out moves sixteen times this and
    reads a few per cent. The program's pads (a chunk narrower than its
    program's width) and the kernel's copies of ``B`` and ``C`` over 128
    lanes are not needed and not charged: the share reads low by them."""
    di, n = cfg.d_inner, cfg.ssm_state
    a_position = (3 * di + 2 * n) * 4
    a_program = 3 * n * di * 4
    return cfg.mamba_layers * (tokens * a_position + programs * a_program)


def attention_step_bytes(cfg, keys: float) -> float:
    """What the attention reads of one decode round must move: ``keys`` is
    the cached keys its rows read, summed over the attention layers, as the
    program counted them (``lzy_attn_full_keys_total`` of a traced round: a
    row at position p reads p + 1 in each), each with its value, once. The
    kernel moves whole pages, so up to a page more a row a layer than this
    charges; the rows' queries and results are left out."""
    return keys * 2 * cfg.n_kv_heads * cfg.head_dim * _itemsize(cfg)


def chunk_read_flops(cfg, start: int, tokens: int) -> float:
    """The arithmetic of the attention reads of prefill programs that carry
    positions ``start .. start + tokens - 1`` of a prompt: scores and
    weighted values, ``4 x heads x head_dim`` operations a (query, visible
    key) pair, the query at position p seeing p + 1 keys, in each attention
    layer. Bound: compute. A tile of 32 queries reads a visible key's 512
    bytes once for 4 x 20 x 128 x 32 operations: 640 operations a byte
    against the chip's 240."""
    p = np.arange(start, start + tokens, dtype=np.float64) + 1
    return 4.0 * cfg.n_heads * cfg.head_dim * cfg.kv_layers * p.sum()


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float) -> float:
    """What one decode round of ``rows`` resident rows has to move: every
    weight once (the tied embedding is the head: read whole), the keys and
    values of the resident context (1 KiB a token), and the rows' state,
    recurrence and convolution windows, read and written."""
    return param_bytes + kv_bytes_per_token(cfg) * resident_tokens \
        + 2.0 * rows * slot_state_bytes(cfg)

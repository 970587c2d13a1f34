"""Ouro-2.6B for the benchmark: the program's configuration and weights
(``lzy_tpu/models/ouro.py``), and a plain reference that shares nothing with
the program's layers, loop, cache or kernels.

**The reference has no cache at all.** The program runs its stack four times
inside a loop and keeps a key/value cache for every (pass, layer), 192 of
them, read through one page table a row. The reference is the published
equations over the whole sequence at once, float32 at the highest matmul
precision: the passes are a Python loop, a layer's attention is a causal
softmax over the keys and values that the same layer made *in the same pass*
(computed there and then, in blocks of queries), and what enters the next
pass is the final norm's result::

    layer l, pass t:  a  = x + N2_l( Attn_l( N1_l(x) ) )
                      x' = a + N4_l( W_down_l( silu(W_gate_l n) * W_up_l n ) ),
                      n = N3_l(a)
    end of pass t:    h^(t) = N_f(x);  g^(t) = w_g . h^(t) + b_g;  x <- h^(t)
    lam_t = sigmoid(g^(t));  p_t = lam_t prod_{j<t}(1 - lam_j), t < T;
    p_T = prod_{j<T}(1 - lam_j);  t* = first t with p_1 + .. + p_t >=
    early_exit_threshold, else T;  logits = W_head h^(t*)

It reads the weights from the program's parameter tree by name and does its
own arithmetic. Departures from the description, both for memory: weights are
upcast a layer at a time, and the head runs over blocks of the vocabulary.

**Weights.** The program's own initialiser from ``--seed``, then the post-norm
scales (``N2``, ``N4``) set to ``post_norm_gain`` (below: at a scale of 1 a
random model of this shape amplifies every rounding through 192 normed
applications until nothing separates one precision from another).
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

#: **Three limits**, over a run's correctness requests (4 x 256 decoded
#: tokens behind prompts of 126, 217, 339 and 644 tokens, the harness's pick
#: from the cell's own levels under ``pad_to`` 1,280): 1,024 judged
#: positions. ``CALIBRATION`` has the readings they were set from (my chip
#: runs, PR 58, one v5e chip, each run its own seed): the sound program's,
#: and beside each the all-bfloat16 control's.
#:
#: 1. ``GAP_RATIO``: the served tokens' mean gap at most 0.4 of **the
#:    all-bfloat16 control's mean gap at the same positions** (paired: a
#:    seed that is hard for one is hard for the other), once
#:    ``GAP_RATIO_MIN_TOKENS`` tokens are judged. The control is this file's
#:    reference with every activation, product, sum, norm and softmax in
#:    bfloat16; the program rounds what its products take and keeps the
#:    stream, the norms, the softmax and the gate in float32, which over 192
#:    layer applications is most of the difference: sound 0.07-0.18 over
#:    thirteen seeds (mean gap 0.00008-0.00036 against 0.0012-0.0022; some
#:    11-38 of 1,024 tokens are not the reference's, at near-ties, against
#:    40-93 of the control's); the control 1 by construction, so a program
#:    computed in the precision under the one the configuration states fails
#:    this limit whatever the second says. The limit stands 2.2 over the one
#:    and 2.5 under the other. What every break of ``broken_tiny`` fails.
#: 2. ``LOGIT_TIE_TOL``: no served token more than 0.15 below the float32
#:    reference's best logit. The harness's one comparison, a backstop.
#:    Derived, not copied from Mistral's (which happens to be the same
#:    number): the logits of this random model spread by 0.9 (the head's rows
#:    are ``0.02 N`` and the state it reads has unit scale: ``0.02
#:    sqrt(2048)``); a token is 192 layer applications deep where Mistral's
#:    cell is 16, but at ``post_norm_gain`` a sublayer adds a tenth of the
#:    stream's scale, so a rounding's relative size (a part in 500 of what a
#:    product takes) is carried and not multiplied, and four passes of 96
#:    such sums leave a logit some 0.005 from the reference's: the largest
#:    of 1,024 gaps at near-ties reads 0.020-0.048 over the seeds, the
#:    control's 0.076-0.110, so the control can pass this limit (it is the
#:    first's to catch); a token drawn blind sits 3 to 4 below the best, and
#:    the breaks of ``broken_tiny`` that leave the mathematics read 0.4 to
#:    4.7. 0.15 is 3.1 times the sound program's largest.
#: 3. ``EXIT_MARGIN``: the pass the head read. The program's outputs carry it
#:    as two counters (``lzy_loop_exit_pass_total`` over
#:    ``lzy_loop_rows_total``: the sum of ``t*`` over the real rows of decode
#:    rounds). The reference gives ``t*`` at every judged decode position
#:    whose cumulative mass stays further than ``EXIT_MARGIN`` from the
#:    threshold in every pass before the one it exits at (a position nearer
#:    than that is one a rounding may flip: it counts as not judged). With
#:    ``R`` rows counted, ``J`` of them judged, the program's sum ``S`` and
#:    the reference's ``S_ref`` over the judged, a program that reads the
#:    reference's pass at every judged position has ``R - J <= S - S_ref <=
#:    T (R - J)`` (a row that is not judged, a warm-up request's or an
#:    over-run round's, read some pass from 1 to ``T``). At the published
#:    threshold of 1 the reference exits at ``T`` wherever no gate
#:    saturates (none did: ``exit_unsure`` 0 in every run), so the upper
#:    bound is met with equality by a sound program (``exit_slack`` 0, 1,036
#:    rows counted against 1,020 judged) and a single judged row read from
#:    another pass breaks it.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``); ``held_to_the_limits`` says how the other two reach it
#: all the same (as ``benchmark/models/brumby.py``).
LOGIT_TIE_TOL = 0.15
GAP_RATIO = 0.4
GAP_RATIO_MIN_TOKENS = 1000
EXIT_MARGIN = 0.02

#: a run's readings after its fourth request: seed, requests/s, the mean gap
#: over the control's, largest gap (the control's); ``unit_gain``: the one
#: run at post-norm scales of 1 (the initialiser's), where precisions read
#: alike; ``broken_tiny``: the program broken one way each, on the CPU at
#: the tiny size in float32 (``benchmark/tests/test_ouro_model_file.py``):
#: the limits it failed, its mean gap (the control's), largest gap, and the
#: exit sum's distance past its bound
CALIBRATION = {
    "sound": [
        (4300000110, 0.30, 0.100, 0.020, 0.099),
        (4300000111, 0.20, 0.154, 0.027, 0.104),
        (4300000112, 0.25, 0.152, 0.042, 0.110),
        (4300000113, 0.35, 0.109, 0.026, 0.100),
        (4300000114, 0.40, 0.071, 0.026, 0.084),
        (4300000120, 0.24, 0.116, 0.048, 0.095),
        (4300000121, 0.24, 0.096, 0.032, 0.084),
        (4300000122, 0.24, 0.176, 0.028, 0.076),
        (4300000123, 0.24, 0.149, 0.027, 0.103),
        (4300000124, 0.24, 0.131, 0.024, 0.080),
        (4300000125, 0.24, 0.103, 0.020, 0.076),
        (4300000130, 0.24, 0.179, 0.033, 0.096),
        (4300000131, 0.24, 0.161, 0.025, 0.089),
    ],
    "unit_gain": [(4300000101, 0.50, 0.991, 0.473, 0.486)],
    "broken_tiny": {
        "sound": ((), 0.00000, 0.00012, 0.0000, 0),
        "a_pass_short": (("LOGIT_TIE_TOL", "GAP_RATIO", "EXIT_MARGIN"),
                         0.41959, 0.00075, 2.5361, 186),
        "pass_0s_keys": (("LOGIT_TIE_TOL", "GAP_RATIO"),
                         1.67106, 0.00121, 4.7045, 0),
        "next_pass_fed_the_unnormed_state": (
            ("LOGIT_TIE_TOL", "GAP_RATIO"), 0.02679, 0.00058, 0.4086, 0),
        "no_attn_post_norm": (("LOGIT_TIE_TOL", "GAP_RATIO"),
                              0.61876, 0.00085, 2.7132, 0),
        "no_mlp_post_norm": (("LOGIT_TIE_TOL", "GAP_RATIO"),
                             0.31101, 0.00136, 2.3949, 0),
        "head_reads_the_pass_before": (
            ("LOGIT_TIE_TOL", "GAP_RATIO", "EXIT_MARGIN"),
            0.41959, 0.00075, 2.5361, 186),
        # every norm's, sublayer's and sum's result rounded to bfloat16
        # beside the products' (1,920 teacher-forced positions): 0.48 of
        # the control's
        "sixteen_bit_activations": (("GAP_RATIO",),
                                    0.00046, 0.00095, 0.0901, 0),
    },
}

def post_norm_gain(cfg) -> float:
    """What ``init_params`` sets every post-norm scale (``N2``, ``N4``: the
    norm on a sublayer's *output*) to, ``(2 x num_hidden_layers) ** -0.5``
    (0.102 at 48 layers; the configuration file's ``assumed`` names it), so
    that the 96 sublayers of a pass add up to a stream of unit scale, the
    scale at which the final norm hands a pass's state to the next.

    The program's initialiser draws every norm scale as 1. Every sublayer
    then adds a unit vector whatever it computed, the stream grows to ten
    times the state that entered the pass, and a rounding's relative size is
    multiplied from sublayer to sublayer and from pass to pass: at full depth
    and an eighth of the width (CPU) the program's logits stood 8.6% of their
    spread from the float32 reference's and the all-bfloat16 control's 14%,
    and on the chip at the published widths a fifth of the served tokens
    were not the reference's and the program's mean gap read 0.99 of the
    control's (``CALIBRATION["unit_gain"]``): no limit on logits can tell
    precisions apart there. No trained model of this family can be in that
    regime (its fourth pass would be noise); at this gain the same CPU
    reading is 0.8% against 1.9%. A scale is a weight, not the program: the
    program is what it was."""
    return (2.0 * cfg.n_layers) ** -0.5


#: positions a block of queries takes at a time, and rows of the head
_QUERY_BLOCK = 512
_VOCAB_BLOCK = 8192


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``OuroConfig``. A key the program cannot honour is refused (by the
    program's own ``from_published``)."""
    from lzy_tpu.models.ouro import OuroConfig

    kind = getattr(jnp, doc["param_dtype"])
    return OuroConfig.from_published(
        doc, **{"dtype": kind, "param_dtype": kind,
                **doc.get("program", {}), **over})


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in one program, in the type
    they are served in: the program's initialiser, then the post-norm
    scales at ``post_norm_gain``."""
    from lzy_tpu.models import ouro

    def make(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.full_like(leaf, post_norm_gain(cfg))
            if len(path) > 1 and path[-2].key.endswith("post_norm")
            else leaf, ouro.init_params(cfg, key))

    return jax.block_until_ready(jax.jit(
        make, out_shardings=out_shardings)(
            jax.random.PRNGKey(seed % (2 ** 31))))


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(x.dtype)).astype(
        x.dtype)


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole head; ``x`` [T, H, D] at
    positions 0 .. T - 1 (the same in every pass), angles in float32
    whatever ``x`` is."""
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


def _attention(q, k, v):
    """Causal softmax attention of one pass of one layer, ``[T, H, D]``
    three times (as many key-value heads as query heads, or fewer), in
    blocks of queries, over keys and values made here and kept nowhere."""
    t, h, d = q.shape
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    outs = []
    for first in range(0, t, _QUERY_BLOCK):
        end = min(first + _QUERY_BLOCK, t)
        s = (jnp.einsum("qhd,shd->hqs", q[first:end], k[:end])
             * d ** -0.5).astype(q.dtype)
        keep = jnp.arange(first, end)[:, None] >= jnp.arange(end)[None, :]
        p = jax.nn.softmax(jnp.where(keep[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("hqs,shd->qhd", p.astype(q.dtype),
                               v[:end]).astype(q.dtype))
    return jnp.concatenate(outs).reshape(t, h * d)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "dt"))
def _layer(x, w, *, heads, kv_heads, theta, eps, dt):
    """One layer of one pass over the whole sequence ``x`` [T, hidden]; the
    weights arrive as they are kept and are upcast here, a layer at a
    time."""
    dt = jnp.dtype(dt)
    with _precision(dt):
        w = jax.tree_util.tree_map(lambda a: a.astype(dt), w)
        t = x.shape[0]

        def product(u, group, name):
            return (u @ group[name]["kernel"]).astype(dt)

        att = w["attn"]
        u = _rms_norm(x, w["attn_norm"]["scale"], eps)
        q = product(u, att, "q_proj").reshape(t, heads, -1)
        k = product(u, att, "k_proj").reshape(t, kv_heads, -1)
        v = product(u, att, "v_proj").reshape(t, kv_heads, -1)
        y = product(_attention(_rope(q, theta), _rope(k, theta), v), att,
                    "o_proj")
        a = (x + _rms_norm(y, w["attn_post_norm"]["scale"], eps)).astype(dt)
        n = _rms_norm(a, w["mlp_norm"]["scale"], eps)
        hid = (jax.nn.silu(product(n, w, "gate_proj"))
               * product(n, w, "up_proj")).astype(dt)
        y = product(hid, w, "down_proj")
        return (a + _rms_norm(y, w["mlp_post_norm"]["scale"], eps)).astype(dt)


def features(params, tokens, cfg, dtype=jnp.float32):
    """Of one sequence ``tokens`` [1, T]: the normed state after every pass
    ``[passes, T, hidden]`` and the gate's value there ``[passes, T]``
    (float32 whatever ``dtype``: the exit rule is the same for the
    control)."""
    dt = jnp.dtype(dtype)
    stack = params["stack"]
    hiddens, gates = [], []
    x = params["embed_tokens"][tokens[0]].astype(dt)
    for _ in range(cfg.total_ut_steps):
        for i in range(cfg.n_layers):
            x = _layer(x, stack[f"layer_{i}"], heads=cfg.n_heads,
                       kv_heads=cfg.n_kv_heads, theta=cfg.rope_theta,
                       eps=cfg.norm_eps, dt=dt.name)
        x = _rms_norm(x, stack["final_norm"]["scale"], cfg.norm_eps)
        with jax.default_matmul_precision("highest"):
            gates.append(x.astype(jnp.float32)
                         @ stack["exit_gate"].astype(jnp.float32)
                         + stack["exit_gate_bias"].astype(jnp.float32))
        hiddens.append(x)
    return jnp.stack(hiddens), jnp.stack(gates)


def exit_masses(gates):
    """The exit distribution's cumulative mass after every pass,
    ``[passes, T]`` float32: ``p_t = lam_t prod_{j<t} (1 - lam_j)`` before
    the last pass, which takes what is left."""
    lam = jax.nn.sigmoid(jnp.asarray(gates, jnp.float32))
    survive = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(survive[:1]), survive[:-1]])
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]])
    return jnp.cumsum(p, axis=0)


def exit_passes(masses, threshold: float, margin: float = 0.0):
    """``t*`` [T] (1 .. passes): the first pass whose cumulative mass reaches
    ``threshold``, else the last; and which positions are *sure*: no pass up
    to and with the one it exits at (the last left out: it exits there
    whatever the mass) has a mass nearer than ``margin`` to the
    threshold."""
    masses = np.asarray(masses)
    passes = masses.shape[0]
    reach = masses[:-1] >= threshold
    t_star = np.where(reach.any(axis=0), reach.argmax(axis=0) + 1, passes)
    near = np.abs(masses[:-1] - threshold) <= margin
    seen = np.arange(1, passes)[:, None] <= t_star[None, :]
    return t_star, ~(near & seen).any(axis=0)


def head_logits(params, x, dtype=jnp.float32):
    """The head over chosen states ``x`` [R, hidden], a block of the
    vocabulary's rows at a time."""
    dt = jnp.dtype(dtype)
    head = params["lm_head"]
    with _precision(dt):
        return jnp.concatenate([
            (x.astype(dt) @ head[first:first + _VOCAB_BLOCK].astype(dt).T
             ).astype(jnp.float32)
            for first in range(0, head.shape[0], _VOCAB_BLOCK)], axis=-1)


def reference(params, tokens, rows, cfg, dtype=jnp.float32):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), read from the state of the
    pass the exit rule names; ``t*`` there, and which of those positions are
    sure by ``EXIT_MARGIN``."""
    rows = np.asarray(rows)
    hiddens, gates = features(params, tokens, cfg, dtype)
    t_star, sure = exit_passes(exit_masses(gates)[:, rows],
                               cfg.early_exit_threshold, EXIT_MARGIN)
    chosen = hiddens[jnp.asarray(t_star - 1), jnp.asarray(rows)]
    return head_logits(params, chosen, dtype), t_star, sure


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32):
    return reference(params, tokens, rows, cfg, dtype)[0]


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def exit_slack(counted_rows: float, counted_sum: float, judged_rows: int,
               judged_sum: int, passes: int) -> float:
    """How far the program's sum of ``t*`` lies outside what a program that
    reads the reference's pass at every judged position can count: 0 inside
    ``R - J <= S - S_ref <= T (R - J)`` (and ``R >= J``)."""
    spare = counted_rows - judged_rows
    if spare < 0:
        return float(-spare)
    off = counted_sum - judged_sum
    return float(max(spare - off, off - passes * spare, 0.0))


def failed_limits(judged, judged_control, slack: float = 0.0) -> list:
    """The names of the limits these readings are over (the first is the
    harness's own and is judged by it all the same)."""
    mine = float(np.mean(judged)) if len(judged) else 0.0
    control = float(np.mean(judged_control)) if len(judged) else 0.0
    out = []
    if len(judged) and float(np.max(judged)) > LOGIT_TIE_TOL:
        out.append("LOGIT_TIE_TOL")
    if len(judged) >= GAP_RATIO_MIN_TOKENS and mine > GAP_RATIO * control:
        out.append("GAP_RATIO")
    if slack > 0:
        out.append("EXIT_MARGIN")
    return out


def held_to_the_limits(exact, chosen, judged, judged_control,
                       slack: float = 0.0) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings three limits.
    Where the second or the third is broken, every chosen token's logit is
    set ``2 x LOGIT_TIE_TOL`` below the reference's best: the largest gap
    the harness then reads is over its limit, and the run comes out not
    correct. So a ``worst_logit_gap`` of exactly ``2 x LOGIT_TIE_TOL`` in a
    result's notes means: the run's ``ouro_judged`` lines on stderr say
    which limit."""
    exact = np.array(exact, np.float32)
    chosen = np.asarray(chosen)
    if set(failed_limits(judged, judged_control, slack)) - {"LOGIT_TIE_TOL"}:
        exact[np.arange(len(chosen)), chosen] = \
            exact.max(axis=-1) - 2.0 * LOGIT_TIE_TOL
    return exact


#: this process's correctness requests so far: the program's gaps and the
#: control's, a pair of arrays a request; and the judged decode positions
#: with the reference's sum of ``t*`` over them (a run is one process, and
#: the harness's only calls of ``logits_at`` are its correctness requests)
_JUDGED: list = []
_EXITS = {"rows": 0, "sum": 0}


def _loop_counters() -> tuple:
    """``(lzy_loop_rows_total, lzy_loop_exit_pass_total)`` as the program's
    registry has them now: what its decode rounds counted, all of them."""
    from lzy_tpu.utils.metrics import REGISTRY

    found = {"lzy_loop_rows_total": 0.0, "lzy_loop_exit_pass_total": 0.0}
    for line in REGISTRY.exposition().splitlines():
        name, _, value = line.rpartition(" ")
        if name in found:
            found[name] = float(value)
    return found["lzy_loop_rows_total"], found["lzy_loop_exit_pass_total"]


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request, once it is
    answered: ``tokens`` [1, T] is the prompt and the served tokens
    (padded), ``rows`` the positions whose logits chose them, so the served
    tokens are ``tokens[0, rows + 1]``. The float32 reference's logits
    there, held to the three limits over the run's requests so far. The
    first served token came out of a prefill program, which counts nothing:
    the decode rounds' are ``rows[1:]``."""
    rows = np.asarray(rows)
    exact, t_star, sure = reference(params, tokens, rows, cfg)
    served = np.asarray(tokens)[0, rows + 1]
    control = np.asarray(reference_logits(
        params, tokens, rows, cfg, jnp.bfloat16)).argmax(axis=-1)
    _JUDGED.append((gaps(exact, served), gaps(exact, control)))
    mine, ctrl = (np.concatenate(x) for x in zip(*_JUDGED))
    _EXITS["rows"] += int(sure[1:].sum())
    _EXITS["sum"] += int(t_star[1:][sure[1:]].sum())
    counted_rows, counted_sum = _loop_counters()
    slack = exit_slack(counted_rows, counted_sum, _EXITS["rows"],
                       _EXITS["sum"], cfg.total_ut_steps)
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"ouro_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((ctrl > 0).sum()),
        "worst_gap": float(mine.max()),
        "control_worst_gap": float(ctrl.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(ctrl.mean()),
        "exit_pass_reference": np.bincount(
            t_star, minlength=cfg.total_ut_steps + 1)[1:].tolist(),
        "exit_unsure": int((~sure).sum()),
        "exit_rows_judged": _EXITS["rows"], "exit_sum_judged": _EXITS["sum"],
        "exit_rows_counted": counted_rows, "exit_sum_counted": counted_sum,
        "exit_slack": slack,
        "failed": failed_limits(mine, ctrl, slack)}}),
        file=sys.stderr, flush=True)
    return held_to_the_limits(exact, served, mine, ctrl, slack)


# -- the counts: bytes and operations, from shapes ----------------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token of context: one entry a (pass, layer),
    in the type the pool holds them in (1,572,864 bytes at 4 passes of 48
    layers of 16 heads of 128)."""
    return 2 * cfg.total_ut_steps * cfg.n_layers * cfg.n_kv_heads \
        * cfg.head_dim * _itemsize(cfg)


def attention_step_bytes(cfg, keys: float) -> float:
    """What the decode read has to move for ``keys`` cached keys read (a
    (pass, layer) each: ``lzy_attn_full_keys_total``): the key and the value
    of every head, 8,192 bytes. The queries and the results (a row's 16
    heads of 128 twice, a (pass, layer)) are moved and not charged."""
    return float(keys) * 2 * cfg.n_kv_heads * cfg.head_dim * _itemsize(cfg)


def stack_bytes(cfg) -> int:
    """One read of the stack's weights: the layers' eight matrices and
    their four norms."""
    d = cfg.d_model
    layer = (2 * cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim * d \
        + 3 * d * cfg.d_ff + 4 * d
    return cfg.n_layers * layer * _itemsize(cfg)


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float) -> float:
    """What one decode round of ``rows`` rows has to move: the stack's
    weights once a pass; the head, the final norm and the gate (what
    ``param_bytes`` holds beside the stack, the embedding table left out: a
    round gathers ``rows`` rows of it); the keys and values of the context
    the resident rows hold, every (pass, layer); and the new token's, a row,
    written."""
    table = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    once = param_bytes - table - stack_bytes(cfg)
    return cfg.total_ut_steps * stack_bytes(cfg) + once \
        + kv_bytes_per_token(cfg) * (resident_tokens + rows)

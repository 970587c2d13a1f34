"""MiniCPM-SALA (``model_type`` ``minicpm_sala``) as the benchmark has to know
it: the program's side, the plain reference, the counts. A configuration file
says ``"model": "minicpm_sala"`` (``benchmark/models/__init__.py`` lists the
names a model file gives).

**The reference** is the architecture's forward pass in straightforward
``jax.numpy`` and float32 at the highest matmul precision, with no cache, no
page table, no kernel and no batching: one sequence. It imports nothing from
``lzy_tpu.models`` or ``lzy_tpu.ops`` (``ops/sparse_attention.py`` and
``ops/mamba2.py`` least of all): it reads the weights from the program's
parameter tree by name and does its own arithmetic. With ``s =
scale_depth / sqrt(L)``, ``L`` the *published* depth::

    h0 = scale_emb * E[token]
    h  = h + s * mixer_i(RMSNorm(h));  h = h + s * down(silu(gate u) * up u)
    logits = head(RMSNorm(h)) / (hidden_size / dim_model_base)

- ``lightning-attn``: q, k, v projections; RMSNorm a head on q and k; rotary
  embedding (rotate-half, the whole head) on q and k; **the plain
  recurrence**, one position after another (``lax.scan``): ``S_t = lambda_h
  S_{t-1} + k_t v_t^T``, ``o_t = q_t^T S_t / sqrt(d)``; RMSNorm on ``o``;
  ``o * sigmoid(gate(u))``; ``o_proj``.
- ``minicpm4``: q, k, v; RMSNorm a head on q and k; no rotary embedding;
  for a sequence whose *prompt* has ``dense_len`` tokens or more, a mask
  built from the published rule, a block of queries at a time: compressed
  keys ``c_j = mean(k[16j : 16j + 32])``; ``p = softmax_j(q . c_j /
  sqrt(d))`` a head over the ``c_j`` that end at or before the query, summed
  over the group's 16 heads; a block of 64 keys scores the largest ``p`` of
  the ``c_j`` that overlap it; the first block and the 32 blocks ending at
  the query's own are read, and of the rest the 64 best (by rank: a tie to
  the lower block); causal softmax attention at ``d^-1/2`` over the chosen
  blocks; ``o * sigmoid(gate(u))``; ``o_proj``. A shorter prompt: plain
  causal attention.

Departures from the publications, all for memory or because the published
configuration is silent (the configuration file lists the latter under
``assumed``): weights are upcast a layer at a time and the MLP and the
attention run over blocks of positions; the window is counted in blocks (the
published code's ``window_size // block_size``); the lightning decay is
``exp(-2^(-8 (h + 1) / H))`` in every layer. ``reference_logits(...,
dtype=bfloat16)`` is the **control**: the same arithmetic with weights,
activations, norms, softmax, compressed keys, scores and the recurrence state
in bfloat16 at the default precision. **The reference is never handed the
program's choices**: it makes its own, and reports them.
"""

from __future__ import annotations

import contextlib
import functools
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: **Five limits**, over a run's correctness requests (4 x 256 decoded
#: tokens behind prompts of 8,664, 10,253, 12,729 and 14,726 tokens, every
#: one past ``dense_len``): 1,024 judged positions, four final states and
#: the choices of every (position, group, sparse layer) the requests read
#: (379,136 triples). ``CALIBRATION`` has the readings they were set from
#: (my chip runs, PR 51, one v5e chip, each run its own seed): the sound
#: program's over nineteen seeds, the all-bfloat16 control's beside each, and
#: four unsound programs'. **With random weights a rounding flips a choice**
#: (14% of the first request's triples are not the reference's, 28-29% of
#: the four requests': a longer context has more near-ties at the 64th
#: rank; the control flips 48%), the flipped queries read other keys, and
#: the lightning layers carry that along: the state a sound program leaves
#: stands 3-4% from the reference's in the first lightning layer and 10-14%
#: in the last (on the CPU, two layers at the published widths with no
#: selection: 0.8 and 1.1%). So the limits are wide, and each names what it
#: still sees.
#:
#: 1. ``LOGIT_TIE_TOL``: no served token more than 5.0 below the float32
#:    reference's best logit. It is the harness's one comparison and a
#:    backstop, **not a limit that tells a single wrong token from a sound
#:    one**: the sound program's largest gap reads 1.27-2.96 over eighteen
#:    seeds and **3.18 on one more** (one flipped choice in the last two
#:    layers, which are sparse, moves one token that far: the tail is heavy,
#:    and a run that reads false refuses a PR, so the limit stands at 5.0
#:    and not at the first calibration's 4.0); the control's 1.80-3.11, a
#:    choice made once a tile 3.28 and a skipped window block 3.25 all pass
#:    it. Its upper reading is **a state spliced into the wrong slot**
#:    (planted; the request decodes from zeros and reads densely): 1,005 of
#:    1,024 tokens are not the reference's, they sit 2.51 below its best on
#:    average (not the 5.6 of a token drawn blind: the sparse layers and the
#:    token itself still speak), the largest **6.78**, and 25 of the 1,024
#:    are over 5.0. So one such token fails this limit once in forty; what
#:    fails that program is the next limit, by a factor of 37.
#: 2. ``GAP_RATIO``: the served tokens' mean gap at most 0.6 of **the
#:    control's mean gap at the same positions** (paired: a seed that is
#:    hard for one is hard for the other). Sound 0.18-0.30; the control 1;
#:    a choice made once a tile 1.80, a skipped window block 1.24, a state
#:    in the wrong slot 22.3. **The one limit that stands a factor of 3
#:    from the control** (0.30 to 1: limits 3 and 5 stand 1.6 from it).
#: 3. ``STATE_REL_TOL``: the lightning state each request leaves in its slot
#:    against the reference's after the same positions, over the heads that
#:    remember more than ``STATE_SLOW_POSITIONS`` positions, relative to the
#:    reference's norm there, the mean over the 14 layers, the largest of a
#:    run's four: at most 0.155. Sound 0.075-0.120; the control 0.19-0.27, a
#:    skipped window block 0.20, a choice made once a tile 0.31, a state in
#:    the wrong slot 1.00 (the room is narrow on both sides, 1.3 and 1.2:
#:    with random weights the flipped choices alone leave 8-12%). **It does
#:    not see a state kept in bfloat16** (0.097, a sound reading): a head
#:    here remembers 256 positions at most, so the roundings of a state
#:    rounded after every program add up to about 1% of it, under the 8-12%
#:    that flipped choices leave. That is the next limit's.
#: 4. ``STATE_COARSE_TOL``: the share of the slot's state entries that a
#:    bfloat16 holds exactly (the low 16 bits of the float32 pattern are 0)
#:    at most 0.01. A state summed in float32 reads 0.00004 (2^-16 is
#:    0.000015); one kept in bfloat16, or rounded to it after every program
#:    (planted with ``lax.reduce_precision``: the TPU compiler drops an
#:    ``astype`` pair), reads 1.0. It would not see a program that rounds
#:    inside and adds in float32 afterwards; ``program_config`` refuses a
#:    state leaf of another type than the configuration states.
#: 5. ``CHOICE_DIFFER_TOL``: the share of (position, group, sparse layer)
#:    triples whose chosen set of blocks is not the reference's, over the
#:    positions the engine read, from the program's own selector run again
#:    over the served sequence (``program_choices``: the same kernels, a
#:    pool of its own: **a replay at batch 1, not the engine's rounds at 16
#:    slots under the live page table**; a wrong choice that only the
#:    engine's packed table makes reaches ``correct`` through limits 2 and
#:    3, which read the timed engine: the wrong-slot fault leaves this share
#:    at 0.285): at most 0.36 over a run's requests so far. Sound
#:    0.2835-0.2937 over four requests (nineteen runs: the prompts' lengths are
#:    the traffic file's, so the share hardly moves with the seed); a choice
#:    made once a tile of 64 queries **0.469** (its first request alone
#:    0.296, under the limit: the lightning layers average over positions,
#:    so neighbouring queries of the deeper layers choose alike, and it is
#:    the longer contexts that tell them apart), a skipped window block
#:    0.995, the control 0.48.
#:
#: The harness makes one comparison (the largest gap of a request against
#: ``LOGIT_TIE_TOL``); ``held_to_the_limits`` says how the other four reach
#: it all the same (as ``benchmark/models/jamba.py``).
LOGIT_TIE_TOL = 5.0
GAP_RATIO = 0.6
GAP_RATIO_MIN_TOKENS = 1000
STATE_SLOW_POSITIONS = 64
STATE_REL_TOL = 0.155
STATE_COARSE_TOL = 0.01
CHOICE_DIFFER_TOL = 0.36

#: a run's readings after its fourth request: seed, tokens that are not the
#: reference's (the control's), mean gap (the control's), largest gap (the
#: control's), the largest state gap of the four, the share of choices that
#: differ, the largest share of state entries a bfloat16 holds (None: not
#: read by that run)
CALIBRATION = {
    "program": [
        (2510000102, 175, 370, 0.0226, 0.1072, 1.282, 2.323, 0.0748, 0.2862,
         None),
        (2510000103, 150, 369, 0.0273, 0.1317, 2.192, 2.703, 0.1062, 0.2937,
         None),
        (2510000201, 172, 382, 0.0298, 0.1166, 1.823, 3.111, 0.0867, 0.2868,
         None),
        (2510000202, 185, 358, 0.0331, 0.1184, 1.397, 2.217, 0.1114, 0.2861,
         None),
        (2510000203, 173, 393, 0.0287, 0.1235, 2.104, 2.402, 0.1093, 0.2889,
         None),
        (2510000204, 184, 394, 0.0332, 0.1234, 2.351, 3.036, 0.1018, 0.2876,
         None),
        (2510000205, 204, 385, 0.0332, 0.1178, 1.473, 1.902, 0.1164, 0.2835,
         None),
        # the cell's six seeds at its rate, under these limits: all correct
        (2510000401, 190, 391, 0.0311, 0.1163, 1.894, 2.856, 0.1138, 0.2884,
         0.000045),
        (2510000402, 186, 412, 0.0309, 0.1330, 1.274, 2.609, 0.1193, 0.2892,
         0.000046),
        (2510000403, 188, 434, 0.0338, 0.1508, 1.998, 2.895, 0.1186, 0.2909,
         0.000045),
        (2510000404, 195, 396, 0.0290, 0.1231, 1.446, 2.822, 0.1089, 0.2871,
         0.000044),
        (2510000405, 174, 372, 0.0282, 0.1238, 2.264, 2.948, 0.1112, 0.2854,
         0.000048),
        (2510000406, 200, 407, 0.0251, 0.1217, 1.519, 2.517, 0.0935, 0.2894,
         0.000045),
        # the traced run that showed the heavy tail of the largest gap
        (2510000407, 171, 410, 0.0287, 0.1330, 3.185, 2.537, 0.0957, 0.2906,
         0.000046),
        # the cell at 0.20 requests/s, and the probe of memory by phase
        (2510000501, 182, 372, 0.0318, 0.1218, 1.658, 2.383, 0.0990, 0.2894,
         0.000046),
        (2510000502, 180, 384, 0.0377, 0.1274, 3.099, 2.942, 0.1089, 0.2881,
         0.000047),
        (2510000503, 183, 397, 0.0334, 0.1277, 1.881, 2.003, 0.1204, 0.2905,
         0.000047),
        (2510000505, 179, 361, 0.0338, 0.1122, 2.957, 2.012, 0.1011, 0.2871,
         0.000045)],
    # 25 of its 1,024 tokens sit more than LOGIT_TIE_TOL below the best
    "state_in_the_wrong_slot": [
        (2510000504, 1005, 353, 2.5095, 0.1126, 6.775, 2.895, 1.0022, 0.2853,
         0.000047)],
    "state_rounded_to_bfloat16": [
        (2510000301, 154, 403, 0.0222, 0.1226, 1.587, 2.214, 0.0973, 0.2893,
         1.0)],
    "choice_made_once_a_tile": [
        (2510000302, 480, 380, 0.2037, 0.1135, 3.283, 1.804, 0.3118, 0.4693,
         0.000047)],
    "window_block_skipped": [
        (2510000303, 426, 396, 0.1384, 0.1117, 3.245, 1.914, 0.2014, 0.9946,
         0.000046)],
    # the control's own state gap and share of choices, a request each
    "control": {"state_gap": (0.193, 0.269), "choices_differ": 0.480},
}

#: positions a turn of the reference's scan takes (unrolled: the recurrence
#: is the same, a turn's dispatch is paid once for all of them)
_SCAN_TURN = 16
#: positions the MLP and the attention take at a time
_ROW_BLOCK = 1024
#: rows of the head the logits take at a time
_VOCAB_BLOCK = 8192
_QUERY_BLOCK = 64


# -- the program's side -------------------------------------------------------

def program_config(doc: dict, **over):
    """The configuration file's published keys as the program's
    ``MiniCPMSalaConfig``. A key the program cannot honour is refused (by
    the program's own ``from_published``), and so is a recurrence state or a
    residual stream of another type than the configuration states."""
    from lzy_tpu.models.minicpm_sala import MiniCPMSalaConfig

    cfg = MiniCPMSalaConfig.from_published(
        doc, dtype=getattr(jnp, doc["param_dtype"]),
        param_dtype=getattr(jnp, doc["param_dtype"]),
        **doc.get("program", {}), **over)
    stated = doc.get("lightning_state_dtype", "float32")
    if jnp.dtype(cfg.state_dtype) != jnp.dtype(stated):
        raise ValueError(
            f"the configuration states lightning_state_dtype {stated}; the "
            f"program keeps its recurrence state in "
            f"{jnp.dtype(cfg.state_dtype)}: a different configuration")
    if doc.get("residual_dtype", "float32") != "float32":
        raise ValueError(
            f"the program keeps its residual stream in float32, the "
            f"configuration says residual_dtype {doc['residual_dtype']!r}")
    return cfg


#: what ``init_params`` multiplies the program's draw of a sparse layer's q
#: and k norm scales by (the program's own initialiser draws them around 1,
#: like the lightning layers'). Softmax logits of standard deviation 4, as
#: sharp as a trained model's: at 1 the block scores of a random model
#: differ by thousandths and a rounding would make every choice. A doubling
#: is exact in every floating-point type.
SPARSE_QK_GAIN = 2.0


def init_params(cfg, seed: int, out_shardings=None):
    """Weights from the seed, on the device, in one program, in the type
    they are served in: the program's initialiser, and ``SPARSE_QK_GAIN`` on
    the sparse layers' q and k norm scales."""
    from lzy_tpu.models import minicpm_sala

    def make(key):
        params = minicpm_sala.init_params(cfg, key)
        for i, kind in enumerate(cfg.mixer_types):
            if kind == "minicpm4":
                for norm in ("q_norm", "k_norm"):
                    leaf = params[f"layer_{i}"][norm]
                    leaf["scale"] = leaf["scale"] * SPARSE_QK_GAIN
        return params

    return jax.block_until_ready(jax.jit(make, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed % (2 ** 31))))


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


def lightning_decay(heads: int) -> np.ndarray:
    """``lambda_h = exp(-2^(-8 (h + 1) / H))`` (assumed: the published
    configuration has no decay key)."""
    return np.exp(-np.exp2(-8.0 * np.arange(1, heads + 1) / heads)).astype(
        np.float32)


@jax.jit
def decayed_recurrence(q, k, v, decay, last):
    """``o_t = q_t^T S_t`` with ``S_t = lambda S_{t-1} + k_t v_t^T`` from a
    zero state, one position after another: ``q``, ``k``, ``v`` [T, H, D],
    ``decay`` [H]. Returns ``(o [T, H, D], S after position last [H, D,
    D])``, in the inputs' type."""
    t, h, d = q.shape
    turns = -(-t // _SCAN_TURN)
    pad = turns * _SCAN_TURN - t

    def padded(x):
        return jnp.pad(x, ((0, pad), (0, 0), (0, 0))).reshape(
            turns, _SCAN_TURN, h, d)

    lam = decay.astype(q.dtype)[:, None, None]

    def turn(carry, xs):
        state, kept = carry
        at, qs, ks, vs = xs
        outs = []
        for i in range(_SCAN_TURN):
            state = lam * state + ks[i][:, :, None] * vs[i][:, None, :]
            outs.append(jnp.sum(qs[i][:, :, None] * state, axis=1))
            kept = jnp.where(at + i == last, state, kept)
        return (state, kept), jnp.stack(outs)

    zero = jnp.zeros((h, d, d), q.dtype)
    (_, kept), o = jax.lax.scan(
        turn, (zero, zero),
        (jnp.arange(turns) * _SCAN_TURN, padded(q), padded(k), padded(v)))
    return o.reshape(turns * _SCAN_TURN, h, d)[:t], kept


def _project(u, w, dt):
    return (u @ w["kernel"].astype(dt)).astype(dt)


#: lightning heads the reference takes at a time (memory: q, k, v and o of
#: 16,640 positions are 272 MB each in float32 at all 32 heads)
_HEAD_BLOCK = 8


def _lightning(u, w, cfg, dt, last):
    t = u.shape[0]
    h, d = cfg.lightning_heads, cfg.lightning_head_dim
    decay = lightning_decay(h)
    outs, states = [], []
    for first in range(0, h, _HEAD_BLOCK):
        n = min(_HEAD_BLOCK, h - first)
        cols = slice(first * d, (first + n) * d)
        q, k, v = ((u @ w[name]["kernel"][:, cols].astype(dt)).astype(
            dt).reshape(t, n, d) for name in ("q_proj", "k_proj", "v_proj"))
        q = _rms_norm(q, w["q_norm"]["scale"].astype(dt), cfg.norm_eps)
        k = _rms_norm(k, w["k_norm"]["scale"].astype(dt), cfg.norm_eps)
        q, k = _rope(q.astype(dt), cfg.rope_theta), _rope(k.astype(dt),
                                                          cfg.rope_theta)
        o, state = decayed_recurrence(
            q, k, v, jnp.asarray(decay[first:first + n]), jnp.int32(last))
        outs.append(_rms_norm(
            (o * d ** -0.5).astype(dt), w["o_norm"]["scale"].astype(dt),
            cfg.norm_eps).astype(dt).reshape(t, n * d))
        states.append(jax.block_until_ready(state))
        del q, k, v, o
    o = jnp.concatenate(outs, axis=1)
    o = o * jax.nn.sigmoid(_project(u, w["gate_proj"], dt))
    return _project(o.astype(dt), w["o_proj"], dt), jnp.concatenate(states)


def compressed_keys(k, spec):
    """``c_j = mean(k[stride j : stride j + kernel])`` for every ``j`` whose
    window lies inside the sequence: ``k`` [T, KV, D] -> [J, KV, D]."""
    t = k.shape[0]
    n = max(0, (t - spec.kernel_size) // spec.kernel_stride + 1)
    at = np.arange(n)[:, None] * spec.kernel_stride \
        + np.arange(spec.kernel_size)[None, :]
    return jnp.mean(k[at], axis=1)


def chosen_blocks(q, ck, first, cfg, n_blocks: int):
    """Steps 2-4 of the published rule for the queries ``q`` [Q, KV, G, D]
    at positions ``first`` .. ``first + Q - 1`` against the compressed keys
    ``ck`` [J, KV, D] of the whole sequence: ``[KV, Q, n_blocks]`` bool
    (``n_blocks``: the sequence's; a block past a query's own is never
    chosen)."""
    spec = cfg.sparse
    n_q, kv, g, d = q.shape
    n_c = ck.shape[0]
    pos = first + jnp.arange(n_q)
    s = jnp.einsum("qkgd,jkd->kgqj", q, ck).astype(jnp.float32) * d ** -0.5
    ends = np.arange(n_c) * spec.kernel_stride + spec.kernel_size - 1
    vis = ends[None, :] <= pos[:, None]                         # [Q, J]
    s = s - jnp.max(jnp.where(vis, s, -1e30), axis=-1, keepdims=True)
    e = jnp.where(vis, jnp.exp(jnp.where(vis, s, 0.0)), 0.0)
    z = e.sum(axis=-1, keepdims=True)
    p = (e / jnp.where(z == 0.0, 1.0, z)).astype(q.dtype).sum(axis=1)
    p = p.astype(jnp.float32)                                   # [KV, Q, J]
    # a block's score: the largest p of the compressed keys that overlap it
    per = spec.block_size // spec.kernel_stride
    back = (spec.kernel_size - 1) // spec.kernel_stride
    padded = jnp.pad(p, ((0, 0), (0, 0), (back, per * (n_blocks + 1))))
    score = functools.reduce(jnp.maximum, [
        padded[..., off:off + per * n_blocks:per]
        for off in range(per + back)])                  # [KV, Q, n_blocks]
    blk = np.arange(n_blocks)
    cur = (pos // spec.block_size)[:, None]
    seen = blk[None, :] <= cur
    forced = seen & ((blk[None, :] < spec.init_blocks)
                     | (cur - blk[None, :] < spec.window_size
                        // spec.block_size))
    cand = seen & ~forced                                   # [Q, n_blocks]
    # rank among the candidates: better score first, the lower block on a tie
    a, b = score[..., :, None], score[..., None, :]
    ahead = (b > a) | ((b == a) & (blk[None, :] < blk[:, None]))
    rank = jnp.sum(ahead & cand[None, :, None, :], axis=-1)
    return forced[None] | (cand[None] & (rank < spec.topk))


@functools.partial(jax.jit, static_argnames=("cfg", "selects"))
def _attend(qb, k, v, ck, first, *, cfg, selects: bool):
    """A block of queries ``qb`` [Q, KV, G, D] from position ``first`` over
    the whole sequence's keys and values [T, KV, D] behind the causal mask
    and, where the request selects, each query's own chosen blocks. Returns
    ``(o [Q, KV, G, D], chosen [KV, Q, blocks] or None)``."""
    spec = cfg.sparse
    n_q, t = qb.shape[0], k.shape[0]
    n_blocks = -(-t // spec.block_size)
    s = jnp.einsum("qkgd,lkd->kgql", qb, k).astype(jnp.float32) \
        * qb.shape[-1] ** -0.5
    keep = jnp.arange(t)[None, :] <= (first + jnp.arange(n_q))[:, None]
    keep = jnp.broadcast_to(keep[None], (k.shape[1], n_q, t))
    chosen = None
    if selects:
        chosen = chosen_blocks(qb, ck, first, cfg, n_blocks)
        keep = keep & jnp.repeat(chosen, spec.block_size, axis=-1)[..., :t]
    s = jnp.where(keep[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(qb.dtype)
    return jnp.einsum("kgql,lkd->qkgd", p, v).astype(qb.dtype), chosen


def _sparse_attention(u, w, cfg, dt, selects: bool):
    """Returns the mixer's output and, where ``selects``, the blocks every
    (group, position) chose: ``[KV, T, blocks]`` bool (None otherwise)."""
    t = u.shape[0]
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kv
    q = _project(u, w["q_proj"], dt).reshape(t, h, d)
    k = _project(u, w["k_proj"], dt).reshape(t, kv, d)
    v = _project(u, w["v_proj"], dt).reshape(t, kv, d)
    q = _rms_norm(q, w["q_norm"]["scale"].astype(dt), cfg.norm_eps).astype(dt)
    k = _rms_norm(k, w["k_norm"]["scale"].astype(dt), cfg.norm_eps).astype(dt)
    q = q.reshape(t, kv, g, d)
    ck = compressed_keys(k, cfg.sparse).astype(dt) if selects else None
    outs, choices = [], []
    for first in range(0, t, _QUERY_BLOCK):
        o, chosen = _attend(q[first:first + _QUERY_BLOCK], k, v, ck,
                            jnp.int32(first), cfg=cfg, selects=selects)
        outs.append(o)
        choices.append(chosen)
        if first % (16 * _QUERY_BLOCK) == 0:
            jax.block_until_ready(o)        # as in ``_mlp``: few in flight
    o = jnp.concatenate(outs).reshape(t, h * d)
    o = o * jax.nn.sigmoid(_project(u, w["gate_proj"], dt))
    return _project(o.astype(dt), w["o_proj"], dt), \
        (jnp.concatenate(choices, axis=1) if selects else None)


@functools.partial(jax.jit, static_argnames=("dt",))
def _mlp_rows(ub, wg, wu, wd, *, dt):
    """A block of rows through the MLP; the weights arrive as they are kept
    and are upcast inside the program (three float32 copies of them side by
    side are 805 MB beside the engine)."""
    with _precision(dt):
        hid = jax.nn.silu((ub @ wg.astype(dt)).astype(dt)) \
            * (ub @ wu.astype(dt)).astype(dt)
        return (hid.astype(dt) @ wd.astype(dt)).astype(dt)


def _mlp(u, w, dt):
    wg, wu, wd = (w[n]["kernel"]
                  for n in ("gate_proj", "up_proj", "down_proj"))
    # one block in flight: a queued program holds its temporaries (three
    # upcast weights, 1 GB) from the moment it is queued, and seventeen
    # queued at once took all the engine left free (16.6 of 16.9 GB)
    return jnp.concatenate([
        jax.block_until_ready(
            _mlp_rows(u[first:first + _ROW_BLOCK], wg, wu, wd, dt=dt))
        for first in range(0, u.shape[0], _ROW_BLOCK)])


def _precision(dt):
    """The highest matmul precision for the reference; the control takes
    the device's default."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def features(params, tokens, cfg, dtype=jnp.float32, last=None,
             prompt_len=None):
    """Hidden states before the final norm ``[T, hidden]`` of one sequence
    ``tokens`` [1, T]; the lightning layers' states ``[H, D(k), D(v)]`` after
    position ``last`` (the sequence's end unless given), in layer order; and
    the sparse layers' choices ``[KV, T, blocks]``, in layer order (empty
    for a sequence served densely). ``prompt_len``: the length the request
    was admitted with, which fixes its mode (the whole of ``tokens`` unless
    given)."""
    dt = jnp.dtype(dtype)
    t = tokens.shape[1]
    last = t - 1 if last is None else last
    selects = (t if prompt_len is None else prompt_len) >= cfg.dense_len
    s = cfg.scale_depth / math.sqrt(cfg.depth)
    states, choices = [], []
    with _precision(dt):
        x = params["embed_tokens"][tokens[0]].astype(dt) * cfg.scale_emb
        for i, kind in enumerate(cfg.mixer_types):
            w = params[f"layer_{i}"]
            u = _rms_norm(x, params[f"layer_{i}_norm"]["scale"].astype(dt),
                          cfg.norm_eps).astype(dt)
            if kind == "minicpm4":
                y, chosen = _sparse_attention(u, w, cfg, dt, selects)
                if chosen is not None:
                    choices.append(chosen)
            else:
                y, state = _lightning(u, w, cfg, dt, last)
                states.append(state)
            x = (x + s * y).astype(dt)
            u = _rms_norm(x, params[f"layer_{i}_mlp_norm"]["scale"].astype(
                dt), cfg.norm_eps).astype(dt)
            x = (x + s * _mlp(u, params[f"layer_{i}_mlp"], dt)).astype(dt)
    return x, states, choices


def head_logits(params, x, cfg, dtype=jnp.float32):
    """The final norm and the head over hidden states ``x`` [R, hidden]."""
    dt = jnp.dtype(dtype)
    head = params["lm_head"]
    with _precision(dt):
        x = _rms_norm(x, params["final_norm"]["scale"].astype(dt),
                      cfg.norm_eps).astype(dt)
        # a block of the vocabulary's rows at a time: the whole head upcast
        # and transposed is 2.4 GB beside the engine
        logits = jnp.concatenate([
            (x @ head[first:first + _VOCAB_BLOCK].astype(dt).T).astype(
                jnp.float32)
            for first in range(0, head.shape[0], _VOCAB_BLOCK)], axis=-1)
    return logits / (cfg.d_model / cfg.dim_model_base)


def reference_logits(params, tokens, rows, cfg, dtype=jnp.float32,
                     prompt_len=None):
    """Logits of one sequence ``tokens`` [1, T] at positions ``rows`` (the
    logits at position i choose token i + 1), float32 unless ``dtype`` asks
    for the control."""
    x, _, _ = features(params, tokens, cfg, dtype, prompt_len=prompt_len)
    return head_logits(params, x[jnp.asarray(rows)], cfg, dtype)


def gaps(exact, chosen) -> np.ndarray:
    """How far below the reference's best logit each chosen token sits."""
    exact = np.asarray(exact)
    return exact.max(axis=-1) - exact[np.arange(len(exact)),
                                      np.asarray(chosen)]


def held_to_the_limits(exact, chosen, judged, judged_control,
                       state_gap: float = 0.0, choice_share: float = 0.0,
                       coarse_share: float = 0.0) -> np.ndarray:
    """``exact`` as the harness is to see it. Its comparison is one
    (``harness/serve.py`` ``warm_and_check``: the largest gap of a request's
    tokens against ``LOGIT_TIE_TOL``), and this file brings five limits.
    Where the judged tokens are at least ``GAP_RATIO_MIN_TOKENS`` and their
    mean gap is over ``GAP_RATIO`` of the control's, or ``state_gap`` (the
    run's largest so far) is over ``STATE_REL_TOL``, or ``coarse_share``
    (the run's largest) over ``STATE_COARSE_TOL``, or ``choice_share`` (the
    run's so far) over ``CHOICE_DIFFER_TOL``, every chosen token's logit is
    set ``2 x LOGIT_TIE_TOL`` below the reference's best: the largest gap
    the harness then reads is over its limit, and the run comes out not
    correct. So a ``worst_logit_gap`` of exactly ``2 x LOGIT_TIE_TOL`` in a
    result's notes means: the run's ``minicpm_sala_judged`` lines on stderr
    say which limit."""
    exact = np.array(exact, np.float32)
    chosen = np.asarray(chosen)
    mine = float(np.mean(judged)) if len(judged) else 0.0
    control = float(np.mean(judged_control)) if len(judged) else 0.0
    if (len(judged) >= GAP_RATIO_MIN_TOKENS and mine > GAP_RATIO * control) \
            or state_gap > STATE_REL_TOL or coarse_share > STATE_COARSE_TOL \
            or choice_share > CHOICE_DIFFER_TOL:
        exact[np.arange(len(chosen)), chosen] = \
            exact.max(axis=-1) - 2.0 * LOGIT_TIE_TOL
    return exact


#: this process's correctness requests so far: the program's gaps and the
#: control's, a pair of arrays a request; the requests' state readings; and
#: (differing, compared) choice triples (a run is one process, and the
#: harness's only calls of ``logits_at`` are its correctness requests)
_JUDGED: list = []
_STATE_GAPS: list = []
_COARSE: list = []
_CHOICES: list = []


# -- the state and the choices the program makes -------------------------------

def slow_heads(heads: int) -> np.ndarray:
    """The heads that remember more than ``STATE_SLOW_POSITIONS`` positions
    (``-log lambda x positions < 1``): what a coarser state loses first."""
    return -np.log(lightning_decay(heads)) * STATE_SLOW_POSITIONS < 1.0


def state_gaps(leaves: dict, states: list, cfg) -> dict:
    """The lightning states a finished request left in the engine
    (``leaves``: ``PagedInferenceEngine.state_leaves()``, ``[slots, H, D(v),
    D(k)]``: the program keeps the transpose of the reference's ``k v^T``)
    against the reference's after the same positions (``states``, in layer
    order). The request's slot is not told: it is the one whose rows lie
    nearest the reference's over all layers. ``all`` and ``slow``: a
    lightning layer each, in layer order."""
    lightning = [i for i, kind in enumerate(cfg.mixer_types)
                 if kind != "minicpm4"]
    by_layer = {int(name.split("layer_")[1].split("'")[0]): leaf
                for name, leaf in leaves.items()
                if name.endswith("['state']")}
    if sorted(by_layer) != lightning or len(states) != len(lightning):
        raise LookupError(
            f"the engine's state leaves are of layers {sorted(by_layer)}; "
            f"the reference has {len(states)} states, of layers {lightning}")
    slow = jnp.asarray(slow_heads(cfg.lightning_heads))[:, None, None]

    def one(leaf, exact):
        exact = jnp.swapaxes(exact.astype(jnp.float32), 1, 2)
        off = jnp.square(leaf.astype(jnp.float32) - exact[None])
        size = jnp.square(exact)
        return (jnp.sqrt(off.sum((1, 2, 3)) / size.sum()),
                jnp.sqrt((off * slow).sum((1, 2, 3)) / (size * slow).sum()))

    whole, slowly = (np.stack(x) for x in zip(*(
        one(by_layer[i], exact) for i, exact in zip(lightning, states))))
    slot = int(whole.mean(axis=0).argmin())
    return {"slot": slot, "all": whole[:, slot].tolist(),
            "slow": slowly[:, slot].tolist(),
            "coarse": float(np.mean([coarse_share(by_layer[i][slot])
                                     for i in lightning]))}


def coarse_share(state) -> float:
    """The share of a float32 state's entries that a bfloat16 holds exactly
    (the low 16 bits of the pattern are 0): 2^-16 of a state that was summed
    in float32, all of one that was kept or rounded in bfloat16."""
    bits = jax.lax.bitcast_convert_type(state.astype(jnp.float32), jnp.uint32)
    return float(jnp.mean((bits & 0xFFFF) == 0))


def control_state_gap(control: list, states: list, cfg) -> float:
    """The control's states ``[H, D, D]`` against the reference's over the
    slow heads, the mean over the layers: what ``state_gaps`` reads of a
    program that is bfloat16 throughout."""
    slow = jnp.asarray(slow_heads(cfg.lightning_heads))[:, None, None]
    return float(np.mean([
        jnp.sqrt((jnp.square(c.astype(jnp.float32) - e) * slow).sum()
                 / (jnp.square(e) * slow).sum())
        for c, e in zip(control, states)]))


def program_choices(params, tokens, cfg, *, prompt_len: int, last: int,
                    kernel: str = "auto") -> list:
    """What the program's own selector chooses over ``tokens[0, :last + 1]``,
    the sequence the engine read: the program's paged module run again,
    batch 1, over a pool of its own: the prompt in chunks of the widest
    prefill program (``sparse_select_prefill``), the served tokens one a
    program (``sparse_select_decode``), as the engine runs them. ``[KV,
    last + 1, blocks]`` bool a sparse layer, in layer order."""
    from lzy_tpu.ops.paged_attention import default_kernel

    page = cfg.sparse.block_size
    width = cfg.widest_prefill
    pages = -(-(last + 1 + width) // page)
    module = cfg.paged_model(
        page_size=page, kv_pages=pages + 1, kv_quant=None,
        kernel=default_kernel() if kernel == "auto" else kernel)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    # the cache alone, from its shapes: ``init`` would draw the weights too
    cache = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
            page_table=table))["cache"])
    told = jnp.asarray([prompt_len], jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(1,), static_argnames=("t",))
    def step(params, cache, ids, real, at, *, t):
        # the positions are the caller's, as they are the engine's: a
        # layer's own index moves by a program's width, pads and all
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.full_like(leaf, at)
            if getattr(path[-1], "key", None) == "index" else leaf, cache)
        _, out = module.apply(
            {"params": params, "cache": cache}, ids, page_table=table,
            valid_len=real, mutable=["cache", "choices"],
            **({"prompt_len": told} if t > 1 else {}))
        chosen = [out["choices"][f"layer_{i}"]["chosen"][0][0]
                  for i, kind in enumerate(cfg.mixer_types)
                  if kind == "minicpm4"]
        return out["cache"], chosen                    # [KV, t, pages] each

    ids = np.asarray(tokens)[0]
    picked = []
    at = 0
    while at <= last:
        t = width if at < prompt_len else 1
        take = min(t, (prompt_len if t > 1 else last + 1) - at)
        chunk = np.zeros((1, t), np.int32)
        chunk[0, :take] = ids[at:at + take]
        cache, chosen = step(params, cache, jnp.asarray(chunk),
                             jnp.asarray([take], jnp.int32), jnp.int32(at),
                             t=t)
        picked.append([c[:, :take] for c in chosen])
        at += take
    return [np.concatenate([np.asarray(p[i]) for p in picked], axis=1)
            for i in range(len(picked[0]))]


def choices_differ(mine: list, exact: list, last: int) -> tuple:
    """``(differing, compared)`` (position, group, layer) triples over the
    positions 0 .. ``last``: a triple differs where the program's set of
    blocks is not the reference's."""
    differing = compared = 0
    for a, b in zip(mine, exact):
        a, b = (np.asarray(x)[:, :last + 1] for x in (a, b))
        # the two count blocks to different widths (a table's, the padded
        # sequence's): past the narrower nothing may be chosen
        n = min(a.shape[-1], b.shape[-1])
        off = (a[..., :n] != b[..., :n]).any(axis=-1) \
            | a[..., n:].any(axis=-1) | b[..., n:].any(axis=-1)
        differing += int(off.sum())
        compared += a.shape[0] * a.shape[1]
    return differing, compared


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
            f"{len(found)} engines serve these weights: the state limit "
            f"reads the one engine of a run")
    return found[0]


def logits_at(params, tokens, rows, cfg):
    """What the harness calls with a correctness request, once it is
    answered: ``tokens`` [1, T] is the prompt and the served tokens
    (padded), ``rows`` the positions whose logits chose them, so the served
    tokens are ``tokens[0, rows + 1]`` and the prompt had ``rows[0] + 1``
    tokens. The float32 reference's logits there, held to the five limits
    over the run's requests so far. The engine has read ``tokens[0,
    :rows[-1] + 1]`` (the last served token was emitted and never fed), so
    that is where the state and the choices are taken."""
    rows = np.asarray(rows)
    prompt_len, last = int(rows[0]) + 1, int(rows[-1])
    x, states, choices = features(params, tokens, cfg, last=last,
                                  prompt_len=prompt_len)
    exact = head_logits(params, x[jnp.asarray(rows)], cfg)
    del x
    served = np.asarray(tokens)[0, rows + 1]
    x, rough_states, rough_choices = features(
        params, tokens, cfg, jnp.bfloat16, last=last, prompt_len=prompt_len)
    control = np.asarray(head_logits(
        params, x[jnp.asarray(rows)], cfg, jnp.bfloat16)).argmax(axis=-1)
    del x
    _JUDGED.append((gaps(exact, served), gaps(exact, control)))
    mine, ctrl = (np.concatenate(x) for x in zip(*_JUDGED))
    state = state_gaps(_serving_engine(params).state_leaves(), states, cfg)
    _STATE_GAPS.append(float(np.mean(state["slow"])))
    _COARSE.append(state["coarse"])
    t0 = time.monotonic()
    if choices:
        _CHOICES.append(choices_differ(
            program_choices(params, tokens, cfg, prompt_len=prompt_len,
                            last=last), choices, last))
    differing, compared = (sum(x) for x in zip(*_CHOICES)) if _CHOICES \
        else (0, 0)
    share = differing / compared if compared else 0.0
    rough = choices_differ(rough_choices, choices, last)
    # the readings the limits are set from, a line a request on stderr
    print(json.dumps({"minicpm_sala_judged": {
        "tokens": len(mine), "differ": int((mine > 0).sum()),
        "control_differ": int((ctrl > 0).sum()),
        "worst_gap": float(mine.max()),
        "over_tie_tol": int((mine > LOGIT_TIE_TOL).sum()),
        "control_worst_gap": float(ctrl.max()),
        "mean_gap": float(mine.mean()),
        "control_mean_gap": float(ctrl.mean()),
        "state_slot": state["slot"], "state_gap": _STATE_GAPS[-1],
        "state_gap_by_layer": [round(g, 4) for g in state["slow"]],
        "state_gap_all_entries": float(np.mean(state["all"])),
        "control_state_gap": control_state_gap(rough_states, states, cfg),
        "state_coarse_share": state["coarse"],
        "choices_compared": compared, "choices_differ": differing,
        "choices_differ_share": share,
        "control_choices_differ_share":
            rough[0] / rough[1] if rough[1] else 0.0,
        "replay_s": round(time.monotonic() - t0, 1)}}),
        file=sys.stderr, flush=True)
    return held_to_the_limits(exact, served, mine, ctrl, max(_STATE_GAPS),
                              share, max(_COARSE))


# -- the counts: bytes and operations, from shapes ----------------------------

def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def _sparse_layers(cfg) -> int:
    return sum(kind == "minicpm4" for kind in cfg.mixer_types)


def _lightning_layers(cfg) -> int:
    return len(cfg.mixer_types) - _sparse_layers(cfg)


def kv_bytes_per_token(cfg) -> int:
    """What one token of context keeps in the pool over the sparse layers:
    keys and values a head in the served type and a sixteenth of a float32
    compressed key a head (4,352 bytes at 4 sparse layers)."""
    each = cfg.n_kv_heads * cfg.head_dim
    return _sparse_layers(cfg) * (
        2 * each * _itemsize(cfg) + each * 4 // cfg.sparse.kernel_stride)


def blocks_read(cfg, context: float) -> float:
    """Blocks a selecting query at ``context`` tokens reads a group: all it
    sees, up to the first, the window's and the best 64."""
    spec = cfg.sparse
    most = spec.init_blocks + spec.window_size // spec.block_size + spec.topk
    return min(context / spec.block_size, float(most))


def sparse_read_step_bytes(cfg, rows: float, blocks: float) -> float:
    """What the sparse decode read of one round must move, over the sparse
    layers: the keys and values of the pages its rows chose, a page of one
    key-value head each. ``blocks``: chosen blocks a selecting row a layer,
    both groups', as the program counted them on the traced rounds
    (``lzy_sparse_blocks_read_total / lzy_sparse_rows_total``): the count
    charges the pages that were chosen, never the context."""
    page = cfg.sparse.block_size * cfg.head_dim * _itemsize(cfg)
    return _sparse_layers(cfg) * rows * blocks * 2 * page


def select_step_bytes(cfg, rows: float, blocks: float) -> float:
    """What the selector of one round must move, over the sparse layers: a
    selecting row's compressed keys, float32, ``block / stride`` a visible
    block a key-value head. ``blocks``: visible blocks a selecting row a
    layer, both groups', as the program counted them
    (``lzy_sparse_blocks_visible_total / lzy_sparse_rows_total``)."""
    per = cfg.sparse.block_size // cfg.sparse.kernel_stride
    return _sparse_layers(cfg) * rows * blocks * per * cfg.head_dim * 4


def lightning_state_bytes(cfg) -> int:
    """One slot's recurrence state over the lightning layers, float32
    (29,360,128 bytes at 14 layers: 32 heads of 128 x 128)."""
    return _lightning_layers(cfg) * cfg.lightning_heads \
        * cfg.lightning_head_dim ** 2 * 4


def lightning_step_bytes(cfg, rows: float) -> float:
    """What ``lightning_state_update`` of one decode round must move: a live
    row's state read and written, every lightning layer; an idle slot's
    state is not moved."""
    return 2.0 * rows * lightning_state_bytes(cfg)


def lightning_scan_flops(cfg, tokens: float) -> float:
    """Arithmetic of the lightning recurrence over ``tokens`` prefill
    positions in chunks of ``chunk_size``: a chunk's masked products (q k^T
    and its product with v: ``2 x 2 c d`` a position a head), what the
    carried state adds and the state's own update (``2 x 2 d d``)."""
    c, d = cfg.chunk_size, cfg.lightning_head_dim
    return _lightning_layers(cfg) * cfg.lightning_heads * tokens \
        * (4.0 * c * d + 4.0 * d * d)


def sparse_prefill_flops(cfg, start: int, tokens: int) -> float:
    """Arithmetic the sparse prefill read of the ``tokens`` queries from
    position ``start`` needs over the sparse layers: ``4 x 128`` a (query,
    chosen key) pair a head. A selecting query at position ``p`` reads the
    positions up to ``p`` of ``min(p // 64 + 1, 97)`` blocks (the traffic's
    requests all select). Bound: compute; the kernel scores the union of a
    tile's pages and masks per query, which is work it does and is not
    charged."""
    spec = cfg.sparse
    most = spec.init_blocks + spec.window_size // spec.block_size + spec.topk
    p = np.arange(start, start + tokens)
    blocks = np.minimum(p // spec.block_size + 1, most)
    keys = (blocks - 1) * spec.block_size + p % spec.block_size + 1
    return _sparse_layers(cfg) * cfg.n_heads * float(keys.sum()) \
        * 4.0 * cfg.head_dim


def decode_step_bytes(cfg, param_bytes: int, resident_tokens: float,
                      rows: float) -> float:
    """What one decode round of ``rows`` rows has to move: every weight once
    (the embedding table is not read: a round gathers ``rows`` rows of it),
    a sparse layer's **chosen** pages and the rows' compressed keys (never
    the whole context: 1 KiB a resident token would count bytes the round
    does not move), and the rows' lightning states read and written."""
    table = cfg.vocab_size * cfg.d_model * _itemsize(cfg)
    context = resident_tokens / rows if rows else 0.0
    kv = cfg.n_kv_heads
    return param_bytes - table \
        + sparse_read_step_bytes(cfg, rows, kv * blocks_read(cfg, context)) \
        + select_step_bytes(cfg, rows,
                            kv * context / cfg.sparse.block_size) \
        + lightning_step_bytes(cfg, rows)

"""The gated delta rule's two programs (``ops/kda.py``) against the
recurrence one position after another, and the gated expert form of
``ops/grouped_experts.py`` against its oracle. Tiny sizes, CPU, Pallas
kernels interpreted (``tests/conftest.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import kda


def _recurrence(q, k, v, alpha, beta, state, rounding=lambda s: s):
    """The plain recurrence in numpy float64, ``state`` as ``S`` [B, H, K,
    V] (the program keeps the transpose)."""
    q, k, v, alpha, beta = (np.asarray(a, np.float64)
                            for a in (q, k, v, alpha, beta))
    state = np.asarray(state, np.float64)
    os_ = []
    for t in range(q.shape[1]):
        state = alpha[:, t][..., None] * state
        write = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = rounding(state + k[:, t][..., None] * write[:, :, None, :])
        os_.append(np.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return np.stack(os_, 1), state


def _inputs(seed=0, bsz=2, t=45, h=8, dk=16, dv=16, strong=True):
    rng = np.random.default_rng(seed)
    f = np.float32

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(rng.normal(size=(bsz, t, h, dk))) * dk ** -0.5).astype(f)
    k = unit(rng.normal(size=(bsz, t, h, dk))).astype(f)
    v = rng.normal(size=(bsz, t, h, dv)).astype(f)
    # decays from 0.999 a position down to exp(-20): a product of
    # k exp(g) with k exp(-g) over a chunk would overflow float32
    rate = rng.normal(size=(bsz, t, h, dk)) * (2.0 if strong else 0.5) - 1
    log_alpha = -np.exp(rate).astype(f)
    beta = (2.0 / (1.0 + np.exp(-rng.normal(size=(bsz, t, h))))).astype(f)
    s0 = rng.normal(size=(bsz, h, dv, dk)).astype(f)
    return q, k, v, log_alpha, beta, s0


def _t(state):
    return np.swapaxes(np.asarray(state), -1, -2)


@pytest.mark.parametrize("chunk", [64, 32, 16, 8])
def test_chunk_scan_is_the_recurrence(chunk):
    """Over chunk boundaries (45 positions: a padded last chunk for every
    chunk size), continued from a carried state, with decays strong enough
    that only the difference of exponents is safe."""
    q, k, v, la, beta, s0 = _inputs()
    want_o, want_s = _recurrence(q, k, v, np.exp(la), beta, _t(s0))
    o, s = kda.kda_chunk_scan(q, k, v, la, beta, jnp.asarray(s0),
                              chunk=chunk)
    assert o.dtype == s.dtype == jnp.float32
    assert np.abs(o - want_o).max() < 1e-4
    assert np.abs(_t(s) - want_s).max() < 1e-4


def test_chunk_scan_in_two_calls_is_one_call():
    q, k, v, la, beta, s0 = _inputs(t=40)
    o, s = kda.kda_chunk_scan(q, k, v, la, beta, jnp.asarray(s0), chunk=16)
    o1, mid = kda.kda_chunk_scan(q[:, :23], k[:, :23], v[:, :23],
                                 la[:, :23], beta[:, :23], jnp.asarray(s0),
                                 chunk=16)
    o2, end = kda.kda_chunk_scan(q[:, 23:], k[:, 23:], v[:, 23:],
                                 la[:, 23:], beta[:, 23:], mid, chunk=16)
    assert np.abs(np.concatenate([o1, o2], 1) - o).max() < 1e-5
    assert np.abs(end - s).max() < 1e-5


def test_chunk_scan_freezes_the_state_past_valid_len():
    """The caller's mask: decay 1 and write 0 at a pad position."""
    q, k, v, la, beta, s0 = _inputs(t=24)
    la[1, 10:] = 0.0
    beta[1, 10:] = 0.0
    _, s = kda.kda_chunk_scan(q, k, v, la, beta, jnp.asarray(s0), chunk=16)
    _, short = kda.kda_chunk_scan(q[:, :10], k[:, :10], v[:, :10],
                                  la[:, :10], beta[:, :10],
                                  jnp.asarray(s0), chunk=16)
    assert np.abs(np.asarray(s)[1] - np.asarray(short)[1]).max() < 1e-6


def test_state_update_is_one_step_of_the_recurrence():
    q, k, v, la, beta, s0 = _inputs(t=6, strong=False)
    live = np.array([True, False])
    state, got = jnp.asarray(s0), []
    for i in range(q.shape[1]):
        before = np.asarray(state)
        o, state = kda.kda_state_update(
            state, q[:, i], k[:, i], v[:, i], np.exp(la[:, i]), beta[:, i],
            jnp.asarray(live))
        got.append(np.asarray(o))
        # the idle row: not moved, bit for bit, and its output 0
        assert np.array_equal(np.asarray(state)[1], before[1])
        assert not got[-1][1].any()
    want_o, want_s = _recurrence(q[:1], k[:1], v[:1], np.exp(la[:1]),
                                 beta[:1], _t(s0[:1]))
    assert state.dtype == jnp.float32
    assert np.abs(np.stack(got, 1)[0] - want_o[0]).max() < 1e-5
    assert np.abs(_t(state)[0] - want_s[0]).max() < 1e-5
    oracle_o, oracle_s = kda.kda_step(
        jnp.asarray(s0), q[:, 0], k[:, 0], v[:, 0], np.exp(la[:, 0]),
        beta[:, 0])
    first_o, first_s = kda.kda_state_update(
        jnp.asarray(s0), q[:, 0], k[:, 0], v[:, 0], np.exp(la[:, 0]),
        beta[:, 0])
    assert np.abs(first_o - oracle_o).max() < 1e-6
    assert np.abs(first_s - oracle_s).max() < 1e-6


def test_state_update_with_no_live_row_moves_nothing():
    q, k, v, la, beta, s0 = _inputs(t=1)
    o, s = kda.kda_state_update(
        jnp.asarray(s0), q[:, 0], k[:, 0], v[:, 0],
        np.ones_like(la[:, 0]), 0.0 * beta[:, 0],
        jnp.zeros((2,), bool))
    assert np.array_equal(np.asarray(s), s0) and not np.asarray(o).any()


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_state_is_float32_over_a_long_slow_recurrence():
    """The precision guard the benchmark's ``correct`` cannot be: channels
    that forget slowly (``alpha`` = exp(-0.001)) with weak writes add
    increments far smaller than the state; over a prompt and a stretch of
    decode positions the program's state stays with the float64 recurrence,
    where a state rounded to bfloat16 after every position is visibly
    another result."""
    bsz, t, h, d = 1, 384, 8, 16
    q, k, v, _, _, _ = _inputs(5, bsz, t + 32, h, d, d)
    la = np.full((bsz, t + 32, h, d), -1e-3, np.float32)
    beta = np.full((bsz, t + 32, h), 0.05, np.float32)
    s0 = np.zeros((bsz, h, d, d), np.float32)
    _, want = _recurrence(q, k, v, np.exp(la), beta, s0)
    _, rough = _recurrence(q, k, v, np.exp(la), beta, s0,
                           lambda s: _bf16(s).astype(np.float64))
    _, state = kda.kda_chunk_scan(
        q[:, :t], k[:, :t], v[:, :t], la[:, :t], beta[:, :t],
        jnp.asarray(s0), chunk=32)
    for i in range(t, t + 32):
        _, state = kda.kda_state_update(
            state, q[:, i], k[:, i], v[:, i], np.exp(la[:, i]), beta[:, i])
    assert state.dtype == jnp.float32
    scale = np.abs(want).mean()
    assert np.abs(_t(state) - want).mean() < 1e-5 * scale
    assert np.abs(rough - want).mean() > 1e-3 * scale


def test_update_kernel_lowers_for_a_tpu_at_published_widths():
    kda.lower_for_tpu(batch=32, heads=64, key_dim=128, value_dim=128)


# -- the experts' two forms ---------------------------------------------------

def _expert_inputs(m=16, latent=128, width=256, e=8, seed=0):
    rng = np.random.default_rng(seed)
    f = np.float32
    x = rng.normal(size=(m, latent)).astype(f)
    wg = (rng.normal(size=(e, latent, width)) * 0.1).astype(f)
    w1 = (rng.normal(size=(e, latent, width)) * 0.1).astype(f)
    w2 = (rng.normal(size=(e, width, latent)) * 0.1).astype(f)
    w = (rng.uniform(size=(m, e)) * (rng.uniform(size=(m, e)) < 0.3)
         ).astype(f)
    return x, wg, w1, w2, w


def _silu(x):
    return x / (1.0 + np.exp(-x))


@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("case", ["mixed", "nobody", "everybody"])
def test_grouped_experts_computes_both_forms(form, case):
    x, wg, w1, w2, w = _expert_inputs()
    if case == "mixed":
        w[:, 3] = 0.0                   # experts nobody chose are skipped
        w[:, 7] = 0.0
    elif case == "nobody":
        w[:] = 0.0
    else:                               # far over any capacity: no drop
        w[:] = 1.0
    if form == "gated":
        want = sum((_silu(x @ wg[i]) * (x @ w1[i]) * w[:, i:i + 1]) @ w2[i]
                   for i in range(w.shape[1]))
        gate = {"gate": wg}
    else:
        want = sum((np.maximum(x @ w1[i], 0) ** 2 * w[:, i:i + 1]) @ w2[i]
                   for i in range(w.shape[1]))
        gate = {}
    with jax.default_matmul_precision("highest"):
        got = gexp.grouped_experts(x, w1, w2, w, **gate)
        oracle = gexp.lax_grouped_experts(x, w1, w2, w, **gate)
    scale = max(1.0, float(np.abs(want).max()))
    assert np.abs(got - want).max() < 1e-4 * scale
    assert np.abs(oracle - want).max() < 1e-4 * scale


def test_the_ungated_call_is_unchanged_by_the_gated_form():
    """Nemotron's call: no ``gate``, the tile of its widths, no VMEM limit
    asked for; and its result is not the gated one."""
    assert gexp._tile(2688, 1024, 2) == 896      # what it was before
    assert gexp._tile(1280, 4096, 2) == 256
    x, wg, w1, w2, w = _expert_inputs()
    with jax.default_matmul_precision("highest"):
        plain = gexp.grouped_experts(x, w1, w2, w)
        positional = gexp.grouped_experts(x, w1, w2, w, gate=None)
        gated = gexp.grouped_experts(x, w1, w2, w, gate=wg)
    assert np.array_equal(np.asarray(plain), np.asarray(positional))
    assert np.abs(plain - gated).max() > 1e-2


@pytest.mark.parametrize("rows", [32, 256])
def test_gated_kernel_lowers_for_a_tpu_at_published_widths(rows):
    gexp.lower_for_tpu(rows=rows, experts=40, latent=4096, width=1280,
                       dtype=jnp.bfloat16, gated=True)

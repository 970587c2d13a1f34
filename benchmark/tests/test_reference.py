"""The plain reference against ``models/llama.py`` at a tiny size."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark.harness import reference
from benchmark.harness.serve import init_params, llama_config

HERE = os.path.dirname(__file__)


@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    with open(os.path.join(HERE, "configs", "tiny-serve.json")) as f:
        doc = json.load(f)
    # float32 compute on the program's side too: then the two differ by
    # summation order alone
    cfg = llama_config(doc, dtype=jnp.float32, remat=False)
    return cfg, init_params(cfg, 5)


def test_logits_agree_with_the_programs_forward(tiny):
    import jax
    import jax.numpy as jnp

    from lzy_tpu.models.llama import Llama

    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, size=(1, 96)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(Llama(cfg).apply({"params": params}, tokens)[0])
    rows = jnp.arange(40, 96)
    ours = np.asarray(reference.logits_at(
        params, tokens, rows, n_layers=cfg.n_layers, theta=cfg.rope_theta,
        eps=cfg.norm_eps))
    assert np.abs(ours - theirs[40:96]).max() < 2e-4
    assert (ours.argmax(-1) == theirs[40:96].argmax(-1)).all()


def test_packed_loss_agrees_with_the_programs_loss(tiny):
    import jax.numpy as jnp

    from lzy_tpu.models import llama

    cfg, params = tiny
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(2, 128)),
                         jnp.int32)
    segments = jnp.asarray(np.stack([
        np.repeat([1, 2, 3], [50, 30, 48]),
        np.repeat([1, 2], [100, 28])]), jnp.int32)
    theirs = float(llama.make_loss_fn(cfg)(
        params, {"tokens": tokens, "segments": segments}))
    ours = reference.loss(params, tokens, segments, n_layers=cfg.n_layers,
                          theta=cfg.rope_theta, eps=cfg.norm_eps)
    assert ours == pytest.approx(theirs, rel=2e-5)
    # and segments matter: the unsegmented loss is another number
    flat = reference.loss(params, tokens, jnp.ones_like(segments),
                          n_layers=cfg.n_layers, theta=cfg.rope_theta,
                          eps=cfg.norm_eps)
    assert abs(flat - ours) > 1e-4

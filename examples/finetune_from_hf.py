"""Pretrained HuggingFace weights → sharded finetune → generate.

The switching-user on-ramp in one runnable file (CPU-friendly; the same
code targets TPU meshes unchanged):

  1. load a (tiny, randomly initialized — no network) HF Llama via
     ``models.hf_interop.load_hf`` — a real checkpoint path works the
     same: ``load_hf("meta-llama/Llama-3.2-1B")``;
  2. shard the imported tree onto an fsdp×tp mesh with the standard
     logical-axis rules and finetune a few steps;
  3. greedy-decode from the finetuned weights with the KV-cache
     ``generate``.

Run: ``python examples/finetune_from_hf.py``
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if "JAX_PLATFORMS" not in os.environ:          # default to CPU off-TPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

import optax  # noqa: E402
import torch  # noqa: E402
from transformers import (  # noqa: E402
    LlamaConfig as HFConfig, LlamaForCausalLM)

from lzy_tpu.models import llama  # noqa: E402
from lzy_tpu.models.generate import generate  # noqa: E402
from lzy_tpu.models.hf_interop import load_hf  # noqa: E402
from lzy_tpu.parallel import (  # noqa: E402
    TrainState, make_eval_step, make_train_step, mesh_for)


def main():
    # 1. a stand-in for LlamaForCausalLM.from_pretrained(<real checkpoint>)
    torch.manual_seed(0)
    hf = LlamaForCausalLM(HFConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=500_000.0,
        tie_word_embeddings=False, attn_implementation="eager")).eval()
    cfg, params = load_hf(hf)
    print(f"imported: {cfg.n_layers} layers, d_model={cfg.d_model}, "
          f"vocab={cfg.vocab_size}")

    # 2. shard + finetune on an fsdp×tp mesh
    mesh = mesh_for(8, fsdp=4, tp=2)
    # logical axes from an abstract init: no second parameter tree
    from lzy_tpu.models.common import param_logical_axes

    abstract = jax.eval_shape(
        lambda: llama.Llama(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    axes = param_logical_axes(abstract)
    tx = optax.adamw(3e-4)
    loss_fn = llama.make_loss_fn(cfg, mesh)
    step, shard_state, _ = make_train_step(
        loss_fn, tx, mesh=mesh, param_logical_axes=axes,
        batch_logical_axes=("batch", "seq"), donate=False)
    state = shard_state(TrainState.create(params, tx))
    eval_step = make_eval_step(loss_fn, mesh=mesh)

    batch = {"tokens": jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (8, 32)))}
    print(f"eval before: {float(eval_step(state.params, batch)['loss']):.3f}")
    for i in range(5):
        state, metrics = step(state, batch)
    print(f"eval after {i + 1} steps: "
          f"{float(eval_step(state.params, batch)['loss']):.3f}")

    # 3. generate from the finetuned weights
    prompt = batch["tokens"][:1, :8]
    out = generate(cfg, jax.device_get(state.params), prompt,
                   max_new_tokens=8, temperature=0.0)
    print(f"generated continuation: {np.asarray(out)[0, 8:].tolist()}")


if __name__ == "__main__":
    main()

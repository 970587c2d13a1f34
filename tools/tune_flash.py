"""Flash-attention block-size sweep AT THE BENCH LEVEL.

Round-1 lesson (recorded in memory/PARITY): isolated kernel timings do not
transfer — block sizes that won a standalone fwd+bwd microbench LOST in the
full train step. This tool therefore sweeps (block_q, block_kv) through the
real bench model and prints MFU per combination, for seq 2048 and 4096.

Usage (on a host with the TPU):
    python tools/tune_flash.py [--seq 2048] [--steps 10]
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time

sys.path.insert(0, ".")

COMBOS = [(256, 256), (256, 512), (512, 256), (512, 512),
          (512, 1024), (1024, 512), (1024, 1024)]


def measure(block_q: int, block_kv: int, seq_len: int, steps: int) -> float:
    import jax
    import optax

    from lzy_tpu.models import count_params, llama, unbox
    from lzy_tpu.parallel import (
        TrainState, chip_peak_tflops, make_train_step, mesh_for, mfu)

    # the module, not the function of the same name that lzy_tpu.ops exports
    fa = importlib.import_module("lzy_tpu.ops.flash_attention")

    # route the model's flash calls through this combo
    orig = fa.flash_attention

    def patched(q, k, v, **kw):
        kw["block_q"], kw["block_kv"] = block_q, block_kv
        return orig(q, k, v, **kw)

    fa.flash_attention = patched
    try:
        cfg = llama.LlamaConfig(
            vocab_size=32_768, d_model=1024, n_layers=20, n_heads=8,
            n_kv_heads=8, d_ff=4096, max_seq_len=seq_len,
            tie_embeddings=True, use_flash_kernel=True,
        )
        batch = 8 if seq_len <= 2048 else 4
        mesh = mesh_for(fsdp=-1)
        boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
        params = unbox(boxed)
        n_params = count_params(params)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(cfg), optax.adamw(3e-4), mesh=mesh,
            param_logical_axes=axes, batch_logical_axes=("batch", "seq"),
        )
        state = shard_state(TrainState.create(params, optax.adamw(3e-4)))
        data = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq_len), 0, cfg.vocab_size)}
        for _ in range(3):
            state, metrics = step(state, data)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, data)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        return mfu(batch * seq_len * steps / dt, n_params,
                   len(jax.devices()),
                   peak_tflops=chip_peak_tflops(
                       jax.devices()[0].device_kind))
    finally:
        fa.flash_attention = orig


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--steps", type=int, default=10)
    args = parser.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU (the sweep is meaningless in interpret mode)",
              file=sys.stderr)
        sys.exit(1)

    print(f"seq={args.seq}  steps={args.steps}")
    print(f"{'block_q':>8} {'block_kv':>8} {'MFU':>8}")
    best = (0.0, None)
    for bq, bkv in COMBOS:
        if args.seq % bq or args.seq % bkv:
            continue
        try:
            value = measure(bq, bkv, args.seq, args.steps)
        except Exception as e:  # noqa: BLE001 — sweep must finish
            print(f"{bq:>8} {bkv:>8}    failed: {type(e).__name__}")
            continue
        print(f"{bq:>8} {bkv:>8} {value:>8.4f}")
        if value > best[0]:
            best = (value, (bq, bkv))
    if best[1]:
        print(f"best: block_q={best[1][0]} block_kv={best[1][1]} "
              f"mfu={best[0]:.4f}")


if __name__ == "__main__":
    main()

"""The latent window read alone (``ops/latent_select.py``
``latent_window_attention`` in a decode round's shape), the kernel
``latent_window_decode`` against the ``lax`` body, by device time at a given
count of live rows: what ``tools/latent_select_bench.py window`` and
``tools/motif_bench.py window`` both run, each at its model's widths."""
import jax
import jax.numpy as jnp
import numpy as np

from lzy_tpu.ops import latent_select as ls
from lzy_tpu.ops import mla


def window_read_table(device_us, *, slots, heads, width, value_dim, window,
                      page, pages_per_seq, blocks, lives, at,
                      block_pages=()):
    """``{live: {"lax": us, "pallas": us, "block<n>": us, ...}}`` a layer's
    read, ``live`` of ``slots`` rows at position ``at`` and the rest idle,
    each row's window pages its own and scratch behind them;
    ``device_us(f, *args)`` gives a call's operations and its ``program``
    in microseconds. The kernel's live rows are held to the ``lax`` body's
    and its idle rows to 0; ``block_pages`` are other block sizes to try
    beside the kernel's own."""
    bf = jnp.bfloat16
    scale = 192 ** -0.5
    pool = jax.random.normal(jax.random.PRNGKey(1), (blocks, page, width),
                             bf) * 0.3
    q = jax.random.normal(jax.random.PRNGKey(2), (slots, 1, heads, width), bf)
    lo = (at - window + 1) // page
    held = at // page - lo + 1

    # jitted once: the live rows change the arguments, not the shapes
    read = {kernel: jax.jit(
        lambda q, pool, table, start, kernel=kernel:
        ls.latent_window_attention(
            q, pool, table, start, window=window, value_dim=value_dim,
            scale=scale, kernel=kernel)) for kernel in ("pallas", "lax")}
    blocked = {n: jax.jit(
        lambda q, pool, table, start, n=n: mla._pallas_mla_attention(
            q, pool, table, start, value_dim=value_dim, scale=scale,
            interpret=False, window=window, name=ls.WINDOW_DECODE_PATH,
            block_pages=n)) for n in block_pages}

    out = {}
    for live in lives:
        table = np.zeros((slots, pages_per_seq), np.int32)
        for r in range(live):
            table[r, lo:lo + held] = 1 + r * held + np.arange(held)
        start = np.full((slots,), -1, np.int32)
        start[:live] = at
        args = (q, pool, jnp.asarray(table), jnp.asarray(start))
        got, want = (np.asarray(read[k](*args), np.float32)
                     for k in ("pallas", "lax"))
        assert not got[live:].any(), live
        gap = float(np.abs(got[:live] - want[:live]).max()) if live else 0.0
        assert gap < 2e-2, (live, gap)
        row = {"against_lax": gap,
               "lax": device_us(read["lax"], *args),
               "pallas": device_us(read["pallas"], *args)}
        for n, f in blocked.items():
            row[f"block{n}"] = device_us(f, *args)["program"]
        out[f"live{live}"] = row
    return out

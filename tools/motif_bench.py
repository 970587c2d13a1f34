"""Times what ``models/motif.py`` added, on the chip at the published
widths, by device time from a trace (the host's clock over a kernel of tens
of microseconds reads its own dispatch):

- ``experts``: ``polynorm_experts`` against ``grouped_experts`` at the same
  4096 x 1280 experts, 48 held and all of them touched, 8 / 40 / 256 rows:
  what PolyNorm's two phases cost over SiLU's one;
- ``mhc``: ``mhc_pre`` + ``mhc_post`` against their ``lax`` forms at 40, 64
  and 256 rows, the same results asserted;
- ``reads``: ``ops/mla.py``'s read at 80 heads, a decode round of 64 slots
  with 40 live at 3,000 positions, and a prefill chunk of 256 behind 2,048
  in calls of 20 heads;
- ``window``: the window layers' read alone, the kernel
  ``latent_window_decode`` against the ``lax`` body at 0 / 8 / 34 / 64 live
  rows of 64 slots (80 heads over 640 lanes under a window of 128);
- ``round`` / ``chunk``: the five-layer program's decode round (64 slots, 40
  live at 3,000) and prefill program (256 positions behind 2,048), an HLO
  instruction each with the ``op_name`` the trace's own metadata holds (a
  breakdown labels a fusion by its kind and its shape alone), summed by
  ``part`` (``tools/part_table.py``).

``chiprun -- python tools/motif_bench.py [experts] [mhc] [reads] [window]
[round] [chunk]``; writes ``chiprun_out/motif_bench.json``."""
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace as tr
from lzy_tpu.ops import grouped_experts as gexp
from lzy_tpu.ops import mhc, mla
from lzy_tpu.ops import polynorm_experts as pne

bf, f32 = jnp.bfloat16, jnp.float32
key = jax.random.PRNGKey(0)
res = {}


def traced(f, *a, n=5):
    """The reduced trace (``harness/trace.py`` ``reduce``) of ``n`` calls of
    ``f`` after one warm call."""
    jax.block_until_ready(f(*a))
    where = tempfile.mkdtemp(prefix="motif_bench_")
    tr.start(where)
    for _ in range(n):
        out = f(*a)
    jax.block_until_ready(out)
    tr.stop()
    reduced = tr.reduce(tr.load(tr.find_xplane(where)))
    shutil.rmtree(where, ignore_errors=True)
    return reduced


def device_us(f, *a, n=5):
    """Device microseconds a call of each operation of ``f``, by the label
    a trace's breakdown gives it, and of the whole program."""
    reduced = traced(f, *a, n=n)
    out = {label.split(":", 1)[1]: round(total / n * 1e6, 1)
           for label, (total, _) in reduced["ops"].items()}
    out["program"] = round(sum(
        sum(times) for times in reduced["modules"].values()) / n * 1e6, 1)
    return out


def experts():
    e, d, w = 48, 4096, 1280
    ks = jax.random.split(key, 6)
    gate, up = (jax.random.normal(k, (e, d, w), bf) * 0.02 for k in ks[:2])
    down = jax.random.normal(ks[2], (e, w, d), bf) * 0.02
    pn = jnp.concatenate([
        1 / 3 + 0.1 * jax.random.normal(ks[3], (e, 3)),
        0.1 * jax.random.normal(ks[4], (e, 1))], axis=-1)
    for rows in (8, 40, 256):
        x = jax.random.normal(ks[5], (rows, d), bf)
        # every expert touched: each row reaches its own and the next 7
        weights = jnp.zeros((rows, e), f32)
        for k in range(8):
            weights = weights.at[jnp.arange(rows),
                                 (jnp.arange(rows) * 7 + k * 6) % e].set(0.25)
        assert bool(jnp.all(jnp.any(weights != 0, axis=0))) or rows == 8
        poly = jax.jit(lambda x, g, u, dn, p, wt: pne.polynorm_experts(
            x, g, u, dn, p, wt, scale=0.5, clamp=0.5, kernel="pallas"))
        silu = jax.jit(lambda x, g, u, dn, wt: gexp.grouped_experts(
            x, u, dn, wt, gate=g))
        mine = poly(x, gate, up, down, pn, weights)
        want = pne.lax_polynorm_experts(x, gate, up, down, pn, weights,
                                        scale=0.5, clamp=0.5)
        err = float(jnp.abs(mine - want).max() / jnp.abs(want).max())
        assert err < 2e-2, err
        touched = int(jnp.sum(jnp.any(weights != 0, axis=0)))
        res[f"experts_rows{rows}"] = {
            "touched": touched, "against_lax_rel": err,
            "read_floor_us": round(touched * 3 * d * w * 2 / 819e9 * 1e6, 1),
            "polynorm": device_us(poly, x, gate, up, down, pn, weights),
            "silu": device_us(silu, x, gate, up, down, weights)}
        print(json.dumps(res), flush=True)


def connections():
    n, d, k = 4, 4096, 24
    ks = jax.random.split(key, 5)
    phi = jax.random.normal(ks[0], (k, n * d), f32) * (n * d) ** -0.5
    alpha = jnp.ones((3,), f32)
    b = 0.5 * jax.random.normal(ks[1], (k,), f32)
    for rows in (40, 64, 256):
        x = jax.random.normal(ks[2], (rows, n * d), f32)
        y = jax.random.normal(ks[3], (rows, d), bf)

        def both(kernel):
            def f(x, phi, alpha, b, y):
                h, mix = mhc.mhc_pre(x, phi, alpha, b, streams=n, sweeps=20,
                                     kernel=kernel)
                return h, mix, mhc.mhc_post(x, y, mix, streams=n,
                                            kernel=kernel)
            return jax.jit(f)

        got, want = (both(kn)(x, phi, alpha, b, y)
                     for kn in ("pallas", "lax"))
        errs = [float(jnp.abs(a - w).max()) for a, w in zip(got, want)]
        assert max(errs) < 1e-3, errs
        res[f"mhc_rows{rows}"] = {
            "against_lax": errs,
            "pallas": device_us(both("pallas"), x, phi, alpha, b, y),
            "lax": device_us(both("lax"), x, phi, alpha, b, y)}
        print(json.dumps(res), flush=True)


def reads():
    page, pages, nb = 64, 192, 64 * 48 + 1
    pool = jax.random.normal(key, (nb, page, 640), bf) * 0.3
    table = 1 + (jnp.arange(64 * pages, dtype=jnp.int32).reshape(64, pages)
                 % (nb - 1))
    start = jnp.full((64,), -1, jnp.int32).at[:40].set(2999)
    q = jax.random.normal(key, (64, 1, 80, 640), bf)
    f = jax.jit(lambda q, pool, t, s: mla.mla_attention(
        q, pool, t, s, value_dim=512, scale=192 ** -0.5, kernel="pallas"))
    res["read_decode_80_heads_40_rows_at_3000"] = device_us(
        f, q, pool, table, start)
    q = jax.random.normal(key, (1, 256, 80, 640), bf)
    one, at = table[:1], jnp.asarray([2048], jnp.int32)
    for heads in (20, 16):
        g = jax.jit(lambda q, pool, t, s, heads=heads: jnp.concatenate([
            mla.mla_attention(q[:, :, i:i + heads], pool, t, s,
                              value_dim=512, scale=192 ** -0.5,
                              kernel="pallas")
            for i in range(0, 80, heads)], axis=2))
        res[f"read_prefill_256_behind_2048_calls_of_{heads}"] = device_us(
            g, q, pool, one, at)
    # a chunk as 32 decode rows of 8 consecutive positions, all 80 heads
    h = jax.jit(lambda q, pool, t, s: mla.mla_attention(
        q.reshape(32, 8, 80, 640), pool, jnp.tile(t, (32, 1)),
        s + 8 * jnp.arange(32, dtype=jnp.int32), value_dim=512,
        scale=192 ** -0.5, kernel="pallas"))
    res["read_prefill_256_behind_2048_as_32_rows_of_8"] = device_us(
        h, q, pool, one, at)
    print(json.dumps(res), flush=True)


def program_ops(decode: bool, rounds=5, least_us=3.0):
    """Device time a call of the five-layer program's operations, an HLO
    instruction each (``tools/part_table.py`` ``traced_ops``: ``[us,
    instruction, the label a trace's breakdown gives it, tf_op, part]``, the
    longest first), and the time by part."""
    from benchmark.models import motif as ref
    from tools.part_table import traced_ops

    with open("benchmark/configs/motif-3-beta-serve-l5-ep8.json") as f:
        cfg = ref.program_config(json.load(f))
    params = ref.init_params(cfg, 7)
    page, pages = 64, cfg.max_seq_len // 64
    slots, live_rows, at = (64, 40, 3000) if decode else (1, 1, 2048)
    t = 1 if decode else 256
    held = (at + t) // page + 1
    module = cfg.paged_model(
        page_size=page, kv_pages=live_rows * held + 1,
        window_pages=live_rows * held + 1, kv_quant=None, kernel="pallas")
    table = np.zeros((slots, pages), np.int32)
    for r in range(live_rows):
        table[r, :held] = 1 + r * held + np.arange(held)
    table = jnp.asarray(table)
    cache = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, t), jnp.int32),
            page_table=table, window_table=table))["cache"])
    live = jnp.zeros((slots,), jnp.int32).at[:live_rows].set(t)
    cache = {layer: dict(leaves, index=(live > 0) * at)
             for layer, leaves in cache.items()}

    @jax.jit
    def step(params, cache, ids, real, table):
        logits, _ = module.apply(
            {"params": params, "cache": cache}, ids, page_table=table,
            window_table=table, valid_len=real, mutable=["cache", "stats"])
        return jnp.argmax(logits[:, -1], -1)

    ids = jax.random.randint(key, (slots, t), 0, cfg.vocab_size)
    return traced_ops(step, params, cache, ids, live, table, rounds=rounds,
                      least_us=least_us)


def window():
    from tools.window_read import window_read_table

    res["window_read_64_slots_at_3000"] = window_read_table(
        device_us, slots=64, heads=80, width=640, value_dim=512, window=128,
        page=64, pages_per_seq=192, blocks=449, lives=(0, 8, 34, 64),
        at=3000, block_pages=(2, 8))
    print(json.dumps(res), flush=True)


which = sys.argv[1:] or ["experts", "mhc", "reads", "window", "round",
                         "chunk"]
for mode, run in (("experts", experts), ("mhc", connections),
                  ("reads", reads), ("window", window)):
    if mode in which:
        run()
if "round" in which:
    res["decode_round_40_of_64_at_3000"] = program_ops(True)
if "chunk" in which:
    res["prefill_256_behind_2048"] = program_ops(False)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/motif_bench.json", "w") as f:
    json.dump(res, f, indent=1)
print(json.dumps({k: v for k, v in res.items()
                  if not isinstance(v, dict) or "ops" not in v}))
for k, v in res.items():
    if isinstance(v, dict) and "ops" in v:
        print(k, json.dumps({x: v[x] for x in ("program_us", "by_part_us")}))

"""Times what ``models/longcat_flash.py`` runs, on the chip at the published
widths, by device time from a trace (the host's clock over a kernel of tens
of microseconds reads its own dispatch):

- ``experts``: ``grouped_experts`` (gated) at 6144 x 2048, 16 held and all
  of them touched, against its ``lax`` form at 8 / 96 / 256 rows, with the
  GB/s of the weights' read (tiles of 6144 x 128 lanes, the narrowest);
- ``reads``: ``ops/mla.py``'s decode read at 64 heads, 128 slots with 32 /
  96 / 128 live rows at 2,048 positions (GB/s of the latents' read), and a
  prefill chunk of 256 behind 2,048 in calls of 16 heads;
- ``round`` / ``chunk``: the four-layer program's decode round (128 slots,
  96 live at 1,750) and prefill programs (64 / 128 / 256 positions behind
  1,024), an HLO instruction each with the ``op_name`` the trace's own
  metadata holds, summed by ``part`` (``tools/part_table.py``; where in a
  round ``grouped_experts`` runs is what XProf's trace viewer shows, its
  rows grouped by the same parts).

``chiprun -- python tools/longcat_bench.py [experts] [reads] [round]
[chunk]``; writes ``chiprun_out/longcat_bench.json``."""
import functools
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
from lzy_tpu.ops import mla

bf, f32 = jnp.bfloat16, jnp.float32
key = jax.random.PRNGKey(0)
res = {}
CONFIG = "benchmark/configs/longcat-flash-omni-serve-l4-ep32.json"


def traced(f, *a, n=5):
    """The reduced trace (``harness/trace.py`` ``reduce``) of ``n`` calls of
    ``f`` after one warm call."""
    jax.block_until_ready(f(*a))
    where = tempfile.mkdtemp(prefix="longcat_bench_")
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


def us_of(got, kernel):
    """Microseconds of the operations whose label begins with the kernel's
    name (a label is the operation's kind and the shape it makes)."""
    return sum(us for label, us in got.items() if label.startswith(kernel))


def experts():
    e, d, w = 16, 6144, 2048
    ks = jax.random.split(key, 4)
    gate, up = (jax.random.normal(k, (e, d, w), bf) * 0.02 for k in ks[:2])
    down = jax.random.normal(ks[2], (e, w, d), bf) * 0.02
    read = e * 3 * d * w * 2
    for rows in (8, 96, 256):
        x = jax.random.normal(ks[3], (rows, d), bf)
        # every expert touched: row r reaches experts r and r + 8
        weights = jnp.zeros((rows, e), f32)
        for k in range(2):
            weights = weights.at[jnp.arange(rows),
                                 (jnp.arange(rows) + 8 * k) % e].set(0.5)
        assert bool(jnp.all(jnp.any(weights != 0, axis=0)))
        kernel = jax.jit(lambda x, g, u, dn, wt: gexp.grouped_experts(
            x, u, dn, wt, gate=g))
        lax = jax.jit(lambda x, g, u, dn, wt: gexp.lax_grouped_experts(
            x, u, dn, wt, gate=g))
        mine, want = (f(x, gate, up, down, weights) for f in (kernel, lax))
        err = float(jnp.abs(mine - want).max() / jnp.abs(want).max())
        assert err < 2e-2, err
        got = device_us(kernel, x, gate, up, down, weights)
        res[f"experts_rows{rows}"] = {
            "against_lax_rel": err, "weights_bytes": read,
            "read_floor_us": round(read / 819e9 * 1e6, 1),
            "kernel": got, "kernel_GBps": round(
                read / us_of(got, "grouped_experts") / 1e3, 1),
            "lax": device_us(lax, x, gate, up, down, weights)}
        print(json.dumps(res), flush=True)


def reads():
    page, pages, slots, at = 64, 128, 128, 2048
    held = at // page + 1
    nb = slots * held + 1
    pool = jax.random.normal(key, (nb, page, 640), bf) * 0.3
    table = np.zeros((slots, pages), np.int32)
    for r in range(slots):
        table[r, :held] = 1 + r * held + np.arange(held)
    table = jnp.asarray(table)
    q = jax.random.normal(key, (slots, 1, 64, 640), bf)
    f = jax.jit(lambda q, pool, t, s: mla.mla_attention(
        q, pool, t, s, value_dim=512, scale=192 ** -0.5, kernel="pallas"))
    for live in (32, 96, 128):
        start = jnp.full((slots,), -1, jnp.int32).at[:live].set(at - 1)
        got = device_us(f, q, pool, table, start)
        need = live * at * 576 * 2
        res[f"read_decode_64_heads_{live}_rows_at_{at}"] = dict(
            got, latent_bytes=need, read_floor_us=round(
                need / 819e9 * 1e6, 1),
            GBps=round(need / us_of(got, "mla_paged_decode") / 1e3, 1))
    q = jax.random.normal(key, (1, 256, 64, 640), bf)
    one, behind = table[:1], jnp.asarray([at - 256], jnp.int32)
    g = jax.jit(lambda q, pool, t, s: jnp.concatenate([
        mla.mla_attention(q[:, :, i:i + 16], pool, t, s, value_dim=512,
                          scale=192 ** -0.5, kernel="pallas")
        for i in range(0, 64, 16)], axis=2))
    res["read_prefill_256_behind_1792_calls_of_16"] = device_us(
        g, q, pool, one, behind)
    print(json.dumps(res), flush=True)


@functools.lru_cache(maxsize=1)
def _model():
    """The benchmark's configuration and its weights, made once."""
    from benchmark.models import longcat_flash as ref

    with open(CONFIG) as f:
        cfg = ref.program_config(json.load(f))
    return cfg, ref.init_params(cfg, 7)


def _program(t: int, slots: int, live_rows: int, at: int):
    cfg, params = _model()
    page, pages = 64, cfg.max_seq_len // 64
    held = (at + t) // page + 1
    module = cfg.paged_model(page_size=page, kv_pages=live_rows * held + 1,
                             kv_quant=None, kernel="pallas")
    table = np.zeros((slots, pages), np.int32)
    for r in range(live_rows):
        table[r, :held] = 1 + r * held + np.arange(held)
    table = jnp.asarray(table)
    cache = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, t), jnp.int32),
            page_table=table))["cache"])
    live = jnp.zeros((slots,), jnp.int32).at[:live_rows].set(t)
    cache = {layer: dict(leaves, index=(live > 0) * at)
             for layer, leaves in cache.items()}

    @jax.jit
    def step(params, cache, ids, real, table):
        logits, upd = module.apply(
            {"params": params, "cache": cache}, ids, page_table=table,
            valid_len=real, mutable=["cache", "stats"])
        return jnp.argmax(logits[:, -1], -1), sum(
            jax.tree_util.tree_leaves(upd["stats"]))

    ids = jax.random.randint(key, (slots, t), 0, cfg.vocab_size)
    return step, (params, cache, ids, live, table)


def program_ops(t, slots, live_rows, at, rounds=5, least_us=20.0):
    """Device time a call of the program's operations, an HLO instruction
    each, the longest first, and the time by part (``tools/part_table.py``
    ``traced_ops``); the round's counts."""
    from tools.part_table import traced_ops

    step, args = _program(t, slots, live_rows, at)
    counts = np.asarray(step(*args)[1])
    got = traced_ops(step, *args, rounds=rounds, least_us=least_us)
    return dict(got, counts=counts.tolist(), ops=got["ops"][:60])


which = sys.argv[1:] or ["experts", "reads", "round", "chunk"]
for mode, run in (("experts", experts), ("reads", reads)):
    if mode in which:
        run()
if "round" in which:
    res["round_128_slots_96_live_at_1750"] = program_ops(1, 128, 96, 1750)
    print(json.dumps(res["round_128_slots_96_live_at_1750"]["by_part_us"]),
          flush=True)
if "chunk" in which:
    for width in (64, 128, 256):
        got = program_ops(width, 1, 1, 1024)
        if width != 256:
            got = {k: got[k] for k in ("program_us", "by_part_us")}
        res[f"chunk_{width}_behind_1024"] = got
        print(width, json.dumps(got["by_part_us"]), got["program_us"],
              flush=True)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/longcat_bench.json", "w") as f:
    json.dump(res, f, indent=1)
print(json.dumps({k: v for k, v in res.items()
                  if not k.startswith(("round", "chunk"))}))

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
  1,024), an HLO instruction each with the ``op_name`` its metadata holds,
  summed by named scope (``dense_ffn``, ``shortcut_experts``, the latent
  reads, the rest), and where in a round ``grouped_experts`` runs: the
  order of the round's kernels and the operations between a layer's expert
  product and the next attention's read.

``chiprun -- python tools/longcat_bench.py [experts] [reads] [round]
[chunk]``; writes ``chiprun_out/longcat_bench.json``."""
import functools
import json
import os
import re
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
SCOPES = ("dense_ffn", "shortcut_experts")


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
    each, the longest first; the time by named scope; the order in which a
    round's kernels ran."""
    step, args = _program(t, slots, live_rows, at)
    text = step.lower(*args).compile().as_text()
    named = dict(re.findall(
        r"%([\w.\-]+) = [^\n]*?op_name=\"([^\"]*)\"", text))
    counts = np.asarray(step(*args)[1])
    reduced, n = traced(step, *args, n=rounds), rounds
    took, label, first = {}, {}, {}
    for start, ns, hlo in reduced["op_events"]:
        name = re.match(r"%?([\w.\-]+)", hlo).group(1)
        took[name] = took.get(name, 0.0) + ns / n / 1e3
        label[name] = tr.op_label(hlo)
        first.setdefault(name, start)
    scopes = {}
    for name, us in took.items():
        path = named.get(name, "")
        scope = next((s for s in SCOPES if f"/{s}/" in path), None)
        if label[name].startswith("mla_paged"):
            scope = "latent_reads"
        elif scope is None and re.search(r"/layer_\d+_attn_\d/", path):
            scope = "attention_projections"
        elif scope == "shortcut_experts" \
                and not label[name].startswith("grouped_experts"):
            scope = "shortcut_experts_rest"
        scopes[scope or "other"] = round(
            scopes.get(scope or "other", 0.0) + us, 1)
    # the first round's kernels and the long operations, in start order:
    # where the expert product ran among the sublayers it skips
    t0 = min(first.values())
    order = [[round((first[name] - t0) / 1e3, 1), round(took[name], 1),
              label[name], re.sub(r"^jit\(step\)/LongcatFlash/", "",
                                  named.get(name, ""))[:70]]
             for name in sorted(first, key=first.get)
             if took[name] >= 60.0
             or label[name].startswith("grouped_experts")
             or label[name].startswith("mla_paged")]
    by_label = {}
    for name, us in took.items():
        by_label[label[name]] = round(by_label.get(label[name], 0.0) + us, 1)
    return {"program_us": round(sum(sum(x) for x in
                                    reduced["modules"].values()) / n * 1e6, 1),
            "counts": counts.tolist(),
            "by_scope_us": scopes,
            "by_label_us": dict(sorted(by_label.items(),
                                       key=lambda kv: -kv[1])[:30]),
            "order": order,
            "ops": [[round(us, 1), name, label[name], named.get(name, "")]
                    for name, us in sorted(took.items(),
                                           key=lambda kv: -kv[1])
                    if us >= least_us][:60]}


which = sys.argv[1:] or ["experts", "reads", "round", "chunk"]
for mode, run in (("experts", experts), ("reads", reads)):
    if mode in which:
        run()
if "round" in which:
    res["round_128_slots_96_live_at_1750"] = program_ops(1, 128, 96, 1750)
    print(json.dumps(res["round_128_slots_96_live_at_1750"]["by_scope_us"]),
          flush=True)
if "chunk" in which:
    for width in (64, 128, 256):
        got = program_ops(width, 1, 1, 1024)
        if width != 256:
            got = {k: got[k] for k in ("program_us", "by_scope_us")}
        res[f"chunk_{width}_behind_1024"] = got
        print(width, json.dumps(got["by_scope_us"]), got["program_us"],
              flush=True)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/longcat_bench.json", "w") as f:
    json.dump(res, f, indent=1)
print(json.dumps({k: v for k, v in res.items()
                  if not k.startswith(("round", "chunk"))}))

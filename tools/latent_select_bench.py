"""Times ``ops/latent_select.py`` on the chip at the published widths: the
choice (``jax.lax.top_k`` over the table's width and over a slice of the
context's) with its compile seconds, the
index, and the read of the chosen split into its gather and its kernel.
``chiprun -- python tools/latent_select_bench.py``; writes
``chiprun_out/latent_select_bench.json``."""
import json
import os
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from lzy_tpu.ops import latent_select as ls
from lzy_tpu.ops import mla


def timeit(f, *a, n=10):
    t0 = time.perf_counter()
    jax.block_until_ready(f(*a))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*a)
    jax.block_until_ready(out)
    return {"ms": (time.perf_counter() - t0) / n * 1e3,
            "first_call_s": first}


page, P, K = 64, 784, 2048
L, NB = page * P, 12545
key = jax.random.PRNGKey(0)
bf = jnp.bfloat16
ik = jax.random.normal(key, (NB, page, 128), bf)
lat = jax.random.normal(key, (NB, page, 640), bf) * 0.3
res = {}


def table(b):
    return 1 + (jnp.arange(b * P, dtype=jnp.int32).reshape(b, P) % (NB - 1))


which = sys.argv[1:] or ["topk", "chosen", "index"]
for ctx in (8191, 32767, 49151):
    for b, t in ((16, 1), (1, 256)):
        tb = table(b)
        live = 3 if t == 1 else 1
        st = jnp.full((b,), -1, jnp.int32).at[:live].set(ctx - t + 1)
        q = jax.random.normal(key, (b, 64, t, 128), bf)
        w = jax.random.normal(key, (b, t, 64), jnp.float32)
        # the pools and the table are arguments: closed over, they would be
        # constants of hundreds of megabytes inside the program
        f = jax.jit(lambda q, w, ik, tb, st: ls.index_scores(
            q, w, ik, tb, st, topk=K, kernel="pallas"))
        tag = f"b{b}_t{t}_ctx{ctx}"
        if "index" in which:
            res[f"index_{tag}"] = timeit(f, q, w, ik, tb, st)
        sc = f(q, w, ik, tb, st)
        pos = jnp.where((st >= 0)[:, None], st[:, None] + jnp.arange(t), -1)
        if "topk" in which:
            g = jax.jit(lambda sc, pos: ls.latent_topk(sc, pos, K))
            res[f"topk_{tag}"] = timeit(g, sc, pos, n=5)
            width = -(-(ctx + 1) // 8192) * 8192
            if width < L:
                g = jax.jit(lambda sc, pos: jax.lax.top_k(
                    jnp.where(jnp.arange(width) <= pos[..., None],
                              sc[..., :width], -jnp.inf), K)[1])
                res[f"topk_sliced{width}_{tag}"] = timeit(g, sc, pos, n=5)
        if "chosen" in which and ctx == 32767:
            idx, n = ls.latent_topk(sc, pos, K)
            qf = jax.random.normal(key, (b, t, 128, 640), bf)
            h = jax.jit(lambda qf, lat, tb, idx, n: ls.latent_chosen_attention(
                qf, lat, tb, idx, n, value_dim=512, scale=0.07,
                kernel="pallas"))
            res[f"chosen_{tag}"] = timeit(h, qf, lat, tb, idx, n, n=5)
            gat = jax.jit(lambda lat, tb, idx: ls.gather_tokens(
                lat, tb, idx.reshape(idx.shape[0], -1)))
            res[f"chosen_gather_{tag}"] = timeit(gat, lat, tb, idx, n=5)
            got = gat(lat, tb, idx).reshape(b * t * K // 128, 128, 640)
            kern = jax.jit(lambda qf, got, n: mla.mla_attention(
                qf.reshape(b * t, 1, 128, 640), got,
                jnp.arange(b * t * K // 128, dtype=jnp.int32).reshape(
                    b * t, K // 128), n.reshape(-1) - 1, value_dim=512,
                scale=0.07, kernel="pallas"))
            res[f"chosen_kernel_{tag}"] = timeit(kern, qf, got, n, n=5)
            # sorted by position: does the gather like neighbours?
            res[f"chosen_gather_sorted_{tag}"] = timeit(
                gat, lat, tb, jnp.sort(idx, axis=-1), n=5)
        print(json.dumps(res), flush=True)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/latent_select_bench.json", "w") as f:
    json.dump(res, f, indent=1)
print(json.dumps(res, indent=1))

#!/usr/bin/env python3
"""The main path, once, on the chip: the quickest proof that it still starts.

    python chip_smoke.py              # one TPU chip, every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

Phases (one chip), each a child process of this script, one after another,
because a chip belongs to one process at a time and the last phase needs the
chip for a server of its own. This parent never imports JAX. The children
share the persistent compile cache (``lzy_tpu/utils/jaxenv.py``).

- ``device``: ``jax.devices()``; anything but a TPU ends the run at once, and
  nothing makes it carry on on the CPU. Builds and loads the native engines.
- ``kernels``: the Pallas flash forward and backward and the paged-attention
  read at Llama-3-8B widths (32 heads, 8 KV heads, head size 128), each
  against a dense reference under a written tolerance.
- ``serve``: ``PagedInferenceEngine`` (``kernel="auto"``, radix cache)
  behind ``GatewayService``, driven through ``llm.generate`` inside a
  workflow; greedy tokens against ``models.generate.generate``.
- ``train``: an ``@op`` that takes five SPMD train steps with the flash
  kernels and the fused cross-entropy.
- ``control-plane``: the deployable binary, ``python -m lzy_tpu.service.serve``
  with the toy model, over gRPC: three requests, SIGTERM, a clean drain.

With ``--chips 4``: ``device``, then ``gang`` (a 1x4
``ShardedPagedInferenceEngine`` against the one-chip engine on the same
parameters) and ``fsdp`` (the train step over ``fsdp=4`` against one device).

The model is ``LlamaConfig.llama3_8b()`` at its published widths and
vocabulary with depth cut to what one chip holds; every key changed is
printed. Weights are random, from ``--seed``. Each phase prints one JSON
line; any failure makes the script exit non-zero; the last line is
``{"ok": true, "device": {...}}`` with the device as JAX reports it. Speeds
printed on the way are information under the device's name, never a claim.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

ONE_CHIP_PHASES = ("device", "kernels", "serve", "train", "control-plane")
FOUR_CHIP_PHASES = ("device", "gang", "fsdp")

# -- tolerances, with their reasons -------------------------------------------

#: kernel against reference, as a share of max(1, max|reference|). bf16
#: keeps 8 bits (2^-9 relative per rounding) and the TPU's default matmul
#: precision rounds float32 operands to bf16 as well, in the kernels and in
#: the program under test alike; sums accumulate in float32. References are
#: computed at the highest matmul precision, so 2e-2 is all the kernel's.
KERNEL_TOL = 2e-2
#: where two correct bf16 programs may part on a greedy token. Logits of
#: this model at random weights have a spread of about 1.3 (0.02 * sqrt(4096))
#: and two programs that round activations at different points differ by
#: about 1e-2 on a logit after eight layers. A token is accepted when a plain
#: float32-logits forward puts it within 0.1 of that position's best logit:
#: ten times the noise, a tenth of the spread, so a wrong token (typically
#: several units short) fails.
LOGIT_TIE_TOL = 0.1
#: fsdp over four chips against one device, relative on the loss: the
#: gradient all-reduce sums in another order and bf16 activations round at
#: other points, five steps long.
FSDP_LOSS_RTOL = 2e-2


# -- configurations -----------------------------------------------------------


def serve_config():
    """Llama-3-8B widths, eight of 32 layers (2.8 B parameters, 5.6 GB in
    bf16), parameters stored in bf16 for serving."""
    import jax.numpy as jnp

    from lzy_tpu.models.llama import LlamaConfig

    return dataclasses.replace(LlamaConfig.llama3_8b(), n_layers=8,
                               param_dtype=jnp.bfloat16)


def train_config():
    """Llama-3-8B widths cut to what 16 GB holds with float32 master weights
    and two Adam moments (12 bytes a parameter, 16 with gradients): one layer
    and tied embeddings, 743 M parameters. A deviceless compile of the step at
    batch 2 x 2048 puts its peak at 13.2 GB, and two layers at 15.8 GB."""
    from lzy_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.llama3_8b(), n_layers=1, tie_embeddings=True,
        use_flash_kernel=True, fused_ce=True)


def changed_keys(cfg) -> dict:
    """Every key that differs from the published configuration."""
    from lzy_tpu.models.llama import LlamaConfig

    base = LlamaConfig.llama3_8b()
    return {f.name: [repr(getattr(base, f.name)), repr(getattr(cfg, f.name))]
            for f in dataclasses.fields(cfg)
            if getattr(base, f.name) != getattr(cfg, f.name)}


# -- small helpers (children only; they import jax) ---------------------------


def compile_doc() -> dict:
    """What JAX compiled in this process, as the program's own build meter
    counted it (``lzy_tpu/utils/jaxenv.py``, on since
    ``enable_compile_cache``): every compile request, the seconds it took (a
    persistent-cache read included) and how many were cache reads. The
    first thing the compile cache has to pay back."""
    from lzy_tpu.utils.jaxenv import build_totals

    totals = build_totals()
    return {"compile_seconds": round(totals["seconds"], 2),
            "compiles": totals["requests"],
            "cache_hits": totals["cache_hits"]}


def _rel_err(got, ref) -> float:
    """max|got - ref| over max(1, max|ref|), in float32 on the host."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.abs(got - ref).max() / max(1.0, np.abs(ref).max()))


def _check(errors: dict, name: str, got, ref, tol: float = KERNEL_TOL):
    err = _rel_err(got, ref)
    errors[name] = round(err, 5)
    if err > tol:
        raise AssertionError(f"{name}: error {err:.4g} over tolerance {tol}")


def _bytes_per_device(tree) -> dict:
    """Bytes each device holds of ``tree``, from ``addressable_shards``: a
    tree that landed whole on device 0 shows."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return dict(sorted(out.items()))


def _init_params(cfg, seed: int):
    """Random weights from ``seed``, made on the device in one program (an
    eager flax init would dispatch every initializer on its own)."""
    import jax

    from lzy_tpu.models import llama, unbox

    params = jax.jit(
        lambda key: unbox(llama.init_params(cfg, key)[0])
    )(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def _local_lzy(label: str):
    """An ``Lzy`` on the in-process runtime with storage in memory."""
    from lzy_tpu import Lzy
    from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig

    registry = DefaultStorageRegistry()
    registry.register_storage(
        "default", StorageConfig(uri=f"mem://chip-smoke-{label}"),
        default=True)
    return Lzy(storage_registry=registry)


def _free(*trees) -> None:
    import jax

    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()


# -- phase: device ------------------------------------------------------------


def phase_device(args) -> dict:
    from lzy_tpu.native.build import load_native_lib

    # built here, from the committed sources, and loaded: a failure raises
    # NativeUnavailable, which the data loader and the p2p slot server would
    # otherwise turn into their Python paths with one warning
    native = {}
    for lib in ("liblzy_slots.so", "liblzy_data.so"):
        load_native_lib(lib)
        native[lib] = "built and loaded"
    return {"native": native}


# -- phase: kernels -----------------------------------------------------------


def _dense_attention(q, k, v, causal, kv_mask=None, segments=None):
    """Plain float32 softmax(QK^T)V over [B, H, T, D]."""
    import jax
    import jax.numpy as jnp

    d, t = q.shape[-1], q.shape[2]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * (d ** -0.5)
        keep = jnp.ones((1, 1, t, t), bool)
        if causal:
            keep = keep & jnp.tril(jnp.ones((t, t), bool))
        if kv_mask is not None:
            keep = keep & kv_mask[:, None, None, :]
        if segments is not None:
            keep = keep & (segments[:, None, :, None]
                           == segments[:, None, None, :])
        s = jnp.where(keep, s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                          v.astype(jnp.float32))


def _dense_paged_read(q, k_pool, v_pool, page_table, positions, quant):
    """Float32 attention over the rows' gathered (and dequantised) blocks."""
    import jax
    import jax.numpy as jnp

    from lzy_tpu.ops.paged_attention import dequantize_kv

    b, t, h, d = q.shape
    kv = k_pool.shape[2]
    keys, vals = k_pool[page_table], v_pool[page_table]
    if quant is not None:
        keys = dequantize_kv(keys, quant.k_scale[page_table],
                             quant.k_zp[page_table], jnp.float32)
        vals = dequantize_kv(vals, quant.v_scale[page_table],
                             quant.v_zp[page_table], jnp.float32)
    keys = keys.reshape(b, -1, kv, d).astype(jnp.float32)
    vals = vals.reshape(b, -1, kv, d).astype(jnp.float32)
    qg = q.reshape(b, t, kv, h // kv, d).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("btkgd,blkd->bkgtl", qg, keys) * (d ** -0.5)
        visible = (jnp.arange(keys.shape[1])[None, None, None, None, :]
                   <= positions[:, None, None, :, None])
        p = jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1)
        return jnp.einsum("bkgtl,blkd->btkgd", p, vals)


def phase_kernels(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lzy_tpu.models.common import cross_entropy_loss
    from lzy_tpu.models.llama import LlamaConfig
    from lzy_tpu.ops.attention import chunked_attention
    from lzy_tpu.ops.chunked_ce import chunked_cross_entropy
    from lzy_tpu.ops.flash_attention import flash_attention
    from lzy_tpu.ops.paged_attention import (
        KVQuant, default_kernel, kernel_path, lower_pallas_for_tpu,
        paged_attention, quantize_kv)

    base = LlamaConfig.llama3_8b()
    h, kv, d = base.n_heads, base.n_kv_heads, base.head_dim
    errors: dict = {}
    flash = jax.jit(flash_attention,
                    static_argnames=("causal", "block_q", "block_kv"))
    # the references as programs too: op by op, every small op is a compile
    # of its own (the first chip run counted 254 in this phase)
    dense = jax.jit(_dense_attention, static_argnames=("causal",))
    dense_paged = jax.jit(_dense_paged_read)
    quantize = jax.jit(quantize_kv)

    def qkv(t, dtype, seed, b=1, heads=h):
        keys = jax.random.split(jax.random.PRNGKey(args.seed + seed), 3)
        return tuple(jax.random.normal(k, (b, heads, t, d), dtype)
                     for k in keys)

    def sq_grads(fn, *operands):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(*operands)

    # the lowered program holds the Mosaic kernel, not an interpretation
    q, k, v = qkv(2048, jnp.bfloat16, 0)
    lowered = flash.lower(q, k, v, causal=True).as_text()
    if "tpu_custom_call" not in lowered:
        raise AssertionError("flash attention lowered without tpu_custom_call")

    # forward, T = 2048, all 32 heads
    for causal in (False, True):
        _check(errors, f"flash_fwd_t2048_causal={causal}",
               flash(q, k, v, causal=causal), dense(q, k, v, causal=causal))
    # block sizes agree with each other
    for blocks in ((256, 256), (1024, 1024)):
        _check(errors, f"flash_fwd_blocks={blocks[0]}",
               flash(q, k, v, causal=True, block_q=blocks[0],
                     block_kv=blocks[1]),
               flash(q, k, v, causal=True))

    # backward against the dense gradient, float32 inputs, T = 512
    q32, k32, v32 = qkv(512, jnp.float32, 1)
    got = sq_grads(lambda *a: flash_attention(*a, causal=True),
                   q32, k32, v32)
    ref = sq_grads(lambda *a: _dense_attention(*a, True), q32, k32, v32)
    for name, g, r in zip("qkv", got, ref):
        _check(errors, f"flash_bwd_t512_d{name}", g, r)

    # forward and backward at max_seq_len 8192, where the backward used to
    # run out of scoped VMEM; the reference is the repo's chunked attention
    # (a dense 8192 x 8192 score matrix for 32 heads is 8.6 GB)
    ql, kl, vl = qkv(base.max_seq_len, jnp.bfloat16, 2)
    got = sq_grads(lambda *a: flash_attention(*a, causal=True), ql, kl, vl)
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(
            lambda *a: chunked_attention(*a, causal=True))(ql, kl, vl)
        ref = sq_grads(lambda *a: chunked_attention(*a, causal=True),
                       ql, kl, vl)
    _check(errors, f"flash_fwd_t{base.max_seq_len}",
           flash(ql, kl, vl, causal=True), ref_out)
    for name, g, r in zip("qkv", got, ref):
        _check(errors, f"flash_bwd_t{base.max_seq_len}_d{name}", g, r)
    del ql, kl, vl, got, ref, ref_out

    # padding mask and packed documents
    qm, km, vm = qkv(512, jnp.bfloat16, 3, b=2)
    mask = jnp.asarray(np.arange(512)[None, :] < np.array([[512], [384]]))
    _check(errors, "flash_kv_mask",
           flash_attention(qm, km, vm, causal=False, kv_mask=mask),
           dense(qm, km, vm, causal=False, kv_mask=mask))
    qs, ks, vs = qkv(1024, jnp.bfloat16, 4, b=2)
    seg = jnp.broadcast_to((jnp.arange(1024) >= 400).astype(jnp.int32),
                           (2, 1024))
    segmented = flash_attention(qs, ks, vs, causal=True, segment_ids=seg,
                                block_q=128, block_kv=128)
    _check(errors, "flash_segments", segmented,
           dense(qs, ks, vs, causal=True, segments=seg))
    moved = flash_attention(
        qs, ks.at[:, :, :10, :].set(0), vs.at[:, :, :10, :].set(0),
        causal=True, segment_ids=seg, block_q=128, block_kv=128)
    leak = float(jnp.abs(moved[:, :, 400:].astype(jnp.float32)
                         - segmented[:, :, 400:].astype(jnp.float32)).max())
    if leak != 0.0:
        raise AssertionError(f"document 1 moved by {leak} when document 0 "
                             f"changed")

    # the logits-free loss at the published vocabulary
    n, vocab = 512, base.vocab_size
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 5), 3)
    feats = jax.random.normal(keys[0], (n, 256), jnp.bfloat16)
    head = jax.random.normal(keys[1], (vocab, 256), jnp.bfloat16) * 0.02
    labels = jax.random.randint(keys[2], (n,), 0, vocab)
    with jax.default_matmul_precision("highest"):
        dense_nll = cross_entropy_loss(
            jnp.einsum("nd,vd->nv", feats.astype(jnp.float32),
                       head.astype(jnp.float32)), labels)
    _check(errors, "chunked_ce",
           jax.jit(chunked_cross_entropy)(feats, head, labels), dense_nll)

    # the paged read that serves: what "auto" resolves to, decode (T = 1) and
    # verify (T = 5), pages of 16 and 64, float and int8 pools; each check
    # is named for the path the call takes (int8 pools are read by lax)
    kernel = default_kernel()
    rng = np.random.default_rng(args.seed)
    batch = 8
    for page in (16, 64):
        pages = base.max_seq_len // page
        n_blocks = batch * pages + 1
        pk = jax.random.split(jax.random.PRNGKey(args.seed + page), 2)
        k_pool = jax.random.normal(pk[0], (n_blocks, page, kv, d),
                                   jnp.bfloat16)
        v_pool = jax.random.normal(pk[1], (n_blocks, page, kv, d),
                                   jnp.bfloat16)
        table = jnp.asarray(rng.permutation(np.arange(1, n_blocks))
                            .reshape(batch, pages).astype(np.int32))
        # decode, the verify window, a prefill chunk (the chunk kernel)
        for t in (1, 5, 64):
            starts = rng.integers(0, base.max_seq_len - t, size=(batch,))
            pos = jnp.asarray(starts[:, None] + np.arange(t)[None, :],
                              jnp.int32)
            qp = jax.random.normal(jax.random.PRNGKey(args.seed + t),
                                   (batch, t, h, d), jnp.bfloat16)
            for quantized in (False, True):
                kp, vp, side = k_pool, v_pool, None
                if quantized:
                    kp, ksc, kzp = quantize(k_pool)
                    vp, vsc, vzp = quantize(v_pool)
                    side = KVQuant(ksc, kzp, vsc, vzp)
                read = jax.jit(lambda *a, side=side: paged_attention(
                    *a, kernel=kernel, dtype=jnp.bfloat16, quant=side))
                path = kernel_path(kernel, t=t, quantized=quantized)
                _check(errors,
                       f"paged_{path}_page{page}_t{t}"
                       f"_{'int8' if quantized else 'bf16'}",
                       read(qp, kp, vp, table, pos),
                       dense_paged(qp, kp, vp, table, pos, side))

    # the Pallas decode kernel lowers, and "auto" is it
    lower_pallas_for_tpu(
        batch=batch, n_heads=h, n_kv_heads=kv, head_dim=d, n_blocks=513,
        page_size=16, pages_per_seq=512, dtype=jnp.bfloat16)
    if kernel != "pallas":
        raise AssertionError(f"'auto' resolves to {kernel!r}, not to the "
                             f"Pallas decode kernel")
    return {"widths": {"heads": h, "kv_heads": kv, "head_dim": d},
            "tolerance": KERNEL_TOL, "errors": errors,
            "flash_lowered_as": "tpu_custom_call", "paged_auto": kernel,
            "paged_pallas": "lowers",
            "paged_decode": _paged_decode_at_serving_shapes(args, h, kv, d)}


def _paged_decode_at_serving_shapes(args, h: int, kv: int, d: int) -> dict:
    """The decode read as a serving replica runs it: 32 slots, tables of 256
    pages of 16, a 7168-page pool (1.75 GiB each for K and V: one layer's
    share of a 7 GiB pool does not exist apart, so this is 4 layers' worth),
    contexts log-normal around 600 tokens with a third of the slots idle.
    The Pallas kernel against the lax read: largest absolute difference, and
    the time of each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lzy_tpu.ops.paged_attention import paged_attention

    slots, pages, page, n_blocks = 32, 256, 16, 7168
    rng = np.random.default_rng(args.seed + 27)
    lens = np.clip(rng.lognormal(np.log(600), 0.8, slots), 1,
                   pages * page).astype(np.int32)
    lens[rng.permutation(slots)[: slots // 3]] = 1        # idle: position 0
    lens[0] = pages * page                                # one full table
    table = np.zeros((slots, pages), np.int32)
    free = rng.permutation(np.arange(1, n_blocks))
    for row, n in enumerate(-(-lens // page)):
        if lens[row] > 1:
            table[row, :n], free = free[:n], free[n:]
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 27), 3)
    k_pool = jax.random.normal(keys[0], (n_blocks, page, kv, d), jnp.bfloat16)
    v_pool = jax.random.normal(keys[1], (n_blocks, page, kv, d), jnp.bfloat16)
    q = jax.random.normal(keys[2], (slots, 1, h, d), jnp.bfloat16)
    operands = (q, k_pool, v_pool, jnp.asarray(table),
                jnp.asarray(lens[:, None] - 1))
    out, ms = {}, {}
    for name in ("pallas", "lax"):
        read = jax.jit(lambda *a, name=name: paged_attention(
            *a, kernel=name, dtype=jnp.bfloat16))
        out[name] = jax.block_until_ready(read(*operands))
        t0 = time.monotonic()
        for _ in range(20):
            got = read(*operands)
        jax.block_until_ready(got)
        ms[name] = round((time.monotonic() - t0) / 20 * 1e3, 4)
    # an idle slot (zeroed table) is 0 to the kernel and read by nobody;
    # the lax read scores the scratch block for it
    live = table[:, 0] != 0
    if np.asarray(out["pallas"], np.float32)[~live].any():
        raise AssertionError("the paged decode kernel read an idle slot")
    diff = float(np.abs(np.asarray(out["pallas"], np.float32)
                        - np.asarray(out["lax"], np.float32))[live].max())
    if not diff <= KERNEL_TOL:
        raise AssertionError(
            f"paged decode kernel differs from lax by {diff} at serving "
            f"shapes (tolerance {KERNEL_TOL})")
    return {"live_tokens": int(lens.sum()), "max_abs_diff": diff,
            "pallas_ms": ms["pallas"], "lax_ms": ms["lax"]}


# -- phase: serve -------------------------------------------------------------


def _extend_prompt(generation, extra: list) -> list:
    """The tool step between two turns of a conversation: the next prompt is
    everything so far plus the user's new tokens."""
    return generation.full_tokens() + list(extra)


def _request_plan(vocab: int, seed: int, new_tokens: int) -> dict:
    """Prompts made from ``seed``. Lengths keep the prefill to the 32- and
    64-wide programs: A and B share their first 64 tokens (four pages of 16),
    C is a conversation of two steps, D is streamed."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def toks(n):
        return [int(x) for x in rng.integers(1, vocab, size=n)]

    shared = toks(64)
    return {"A": shared + toks(32), "B": shared + toks(32), "C1": toks(64),
            "C_extra": toks(32), "D": toks(40), "new_tokens": new_tokens}


def _make_gateway(engine_factory, page_size: int, cfg, seed: int):
    from lzy_tpu.gateway import (
        GatewayService, PrefixAffinityRouter, ReplicaFleet)
    from lzy_tpu.llm.backend import model_digest_for

    fleet = ReplicaFleet(engine_factory)
    gateway = GatewayService(fleet, router=PrefixAffinityRouter(page_size),
                             model_name="chip-smoke")
    try:
        fleet.add_replica()
    except BaseException:
        gateway.close()
        raise
    gateway.model_digest = model_digest_for("chip-smoke", cfg, seed=seed)
    return gateway


def _drive_requests(gateway, plan: dict, label: str) -> dict:
    """The handful of requests, through ``llm.generate`` inside a workflow.
    Touching a result runs the graph so far, which keeps the order fixed."""
    from lzy_tpu import llm, op
    from lzy_tpu.channels.token_stream import TokenStreamChannel

    lzy = _local_lzy(label)
    extend = op(_extend_prompt)
    n = plan["new_tokens"]
    # cache=False: the second pass must reach its own engine, not this
    # workflow's op cache
    kw = dict(max_new_tokens=n, greedy=True, cache=False)
    out: dict = {}
    llm.configure(gateway)
    try:
        t0 = time.monotonic()
        with lzy.workflow(f"chip-smoke-{label}"):
            g_a = llm.generate(plan["A"], **kw)
            out["A"] = (plan["A"], list(g_a.tokens), g_a.ttft_ms)
            hits_before = gateway.fleet.aggregate()["prefix_hit_tokens"]
            g_b = llm.generate(plan["B"], **kw)
            out["B"] = (plan["B"], list(g_b.tokens), g_b.ttft_ms)
            out["cached_prompt_tokens_B"] = (
                gateway.fleet.aggregate()["prefix_hit_tokens"] - hits_before)
            conversation = llm.Conversation(f"chip-smoke-{label}")
            g_c1 = llm.generate(plan["C1"], conversation=conversation, **kw)
            prompt_c2 = extend(g_c1, plan["C_extra"])
            g_c2 = llm.generate(prompt_c2, conversation=conversation, **kw)
            out["C1"] = (plan["C1"], list(g_c1.tokens), g_c1.ttft_ms)
            out["C2"] = (list(g_c2.prompt), list(g_c2.tokens), g_c2.ttft_ms)
            out["C2_routed_by"] = g_c2.routed_by
            stream = TokenStreamChannel()
            g_d = llm.generate(plan["D"], stream=stream, **kw)
            out["D"] = (plan["D"], list(g_d.tokens), g_d.ttft_ms)
            out["D_streamed"] = stream.tokens()
            out["D_stream_status"] = stream.status
        out["wall_seconds"] = time.monotonic() - t0
    finally:
        llm.configure(None)
    for name in ("A", "B", "C1", "C2", "D"):
        if len(out[name][1]) != n:
            raise AssertionError(
                f"request {name} returned {len(out[name][1])} tokens, "
                f"wanted {n}")
    if out["C2"][0] != plan["C1"] + out["C1"][1] + plan["C_extra"]:
        raise AssertionError("conversation step 2 did not extend step 1")
    if out["D_streamed"] != out["D"][1] or out["D_stream_status"] != "ok":
        raise AssertionError("the stream's tokens are not the reply")
    return out


def greedy_gap(cfg, params, prompt: list, tokens: list, pad_to: int) -> float:
    """How far below the best logit the reply's tokens sit under a plain
    forward of the whole sequence (float32 logits, no cache, no engine):
    the largest ``max(logits) - logits[token]`` over the reply. 0.0 means
    every token is this forward's own argmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lzy_tpu.models.llama import Llama

    full = list(prompt) + list(tokens)
    if len(full) > pad_to:
        raise ValueError(f"sequence of {len(full)} over pad_to={pad_to}")
    padded = jnp.asarray([full + [0] * (pad_to - len(full))], jnp.int32)
    plain = dataclasses.replace(cfg, use_flash_kernel=False, fused_ce=False)
    logits = jax.jit(
        lambda p, t: Llama(plain).apply({"params": p}, t))(params, padded)
    logits = np.asarray(logits[0], np.float32)
    # the logits at position i choose token i + 1
    rows = logits[len(prompt) - 1:len(full) - 1]
    chosen = rows[np.arange(len(tokens)), np.asarray(tokens)]
    return float((rows.max(axis=-1) - chosen).max())


def _judge(cfg, params, name: str, prompt, tokens, reference, pad_to: int,
           verdicts: dict) -> None:
    """Equal to the reference, or a near-tie a plain forward admits."""
    if tokens == reference:
        verdicts[name] = "identical"
        return
    first = next(i for i, (a, b) in enumerate(zip(tokens, reference))
                 if a != b)
    gap = max(greedy_gap(cfg, params, prompt, tokens, pad_to),
              greedy_gap(cfg, params, prompt, reference, pad_to))
    verdicts[name] = (f"parts from the reference at token {first}; largest "
                      f"logit gap {gap:.4f}")
    if gap > LOGIT_TIE_TOL:
        raise AssertionError(
            f"{name}: {verdicts[name]}, over the bf16 tie tolerance "
            f"{LOGIT_TIE_TOL}")


def _engine_factory(cfg, params, *, slots: int, page_size: int, pool: dict,
                    gang: int = 0):
    def factory():
        kw = dict(slots=slots, page_size=page_size, kernel="auto", **pool)
        if gang:
            from lzy_tpu.serving.sharded import ShardedPagedInferenceEngine

            engine = ShardedPagedInferenceEngine(cfg, params, tp=gang, **kw)
        else:
            from lzy_tpu.serving import PagedInferenceEngine

            engine = PagedInferenceEngine(cfg, params, **kw)
        # what serve.py's warm start does: every program compiled ahead of
        # the first request
        engine.warmup()
        return engine

    return factory


def _serve_once(cfg, params, plan, label, *, seed, page_size, **factory_kw):
    """One gateway over one engine: build, drive, close, free the pool."""
    gateway = _make_gateway(
        _engine_factory(cfg, params, page_size=page_size, **factory_kw),
        page_size, cfg, seed)
    engine = gateway.fleet.replicas()[0].engine
    try:
        out = _drive_requests(gateway, plan, label)
        out["kernel_path"] = engine.kernel_path
        out["params_bytes_per_device"] = _bytes_per_device(engine.params)
        # the pool's leaves, for the table of who holds what
        out["pool_bytes_per_device"] = _bytes_per_device(engine._payload)
    finally:
        gateway.close()
        _free(engine._payload)
    return out


def _oracle(cfg, params, prompt: list, n: int) -> list:
    import jax.numpy as jnp
    import numpy as np

    from lzy_tpu.models.generate import generate

    out = generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _speeds(out: dict, n: int) -> dict:
    ttft = [out[r][2] for r in ("A", "B", "C1", "C2", "D")
            if out[r][2] is not None]
    return {"ttft_ms": ttft,
            "tokens_per_second": round(5 * n / out["wall_seconds"], 2)}


def phase_serve(args, cfg=None, *, slots: int = 4, page_size: int = 16,
                pool=None, new_tokens: int = 32, pad_to: int = 256) -> dict:
    """``cfg``/``pool`` are the CPU rehearsal's way in (tests/); the script
    itself always serves :func:`serve_config` from a 2 GiB pool."""
    import jax

    cfg = cfg or serve_config()
    pool = pool if pool is not None else {"kv_pool_bytes": 2 << 30}
    t0 = time.monotonic()
    params = _init_params(cfg, args.seed)
    leaves = jax.tree_util.tree_leaves(params)
    result = {
        "changed_from_llama3_8b": changed_keys(cfg),
        "params": sum(x.size for x in leaves),
        "param_bytes": sum(x.nbytes for x in leaves),
        "param_dtypes": sorted({str(x.dtype) for x in leaves}),
        "init_seconds": round(time.monotonic() - t0, 2),
    }
    stats = jax.devices()[0].memory_stats() or {}
    result["peak_bytes_after_init"] = stats.get("peak_bytes_in_use")

    plan = _request_plan(cfg.vocab_size, args.seed, new_tokens)
    common = dict(seed=args.seed, page_size=page_size, slots=slots, pool=pool)
    served = _serve_once(cfg, params, plan, "served", **common)
    if served["cached_prompt_tokens_B"] < 64:
        raise AssertionError(
            f"B shares 64 prompt tokens with A but the radix cache served "
            f"{served['cached_prompt_tokens_B']}")

    verdicts: dict = {}
    for name in ("A", "B", "C1", "C2", "D"):
        prompt, tokens, _ = served[name]
        _judge(cfg, params, f"{name} vs generate()", prompt, tokens,
               _oracle(cfg, params, prompt, new_tokens), pad_to, verdicts)

    result.update({
        "kernel_path": served["kernel_path"],
        "requests": 5, "new_tokens": new_tokens,
        "cached_prompt_tokens_B": served["cached_prompt_tokens_B"],
        "conversation_step2_routed_by": served["C2_routed_by"],
        "verdicts": verdicts, "logit_tie_tolerance": LOGIT_TIE_TOL,
        "pool_bytes": sum(served["pool_bytes_per_device"].values()),
        **_speeds(served, new_tokens),
    })
    return result


# -- phase: train -------------------------------------------------------------


def _train_steps(cfg, *, seed: int, batch: int, seq: int, steps: int,
                 lr: float, fsdp: int, expect_custom_call: bool) -> dict:
    """``steps`` steps of ``make_train_step`` on one fixed batch over the
    first ``fsdp`` devices, each ending in ``block_until_ready``."""
    import jax
    import optax

    from lzy_tpu.models import llama, unbox
    from lzy_tpu.models.common import param_logical_axes
    from lzy_tpu.parallel import TrainState, make_train_step, mesh_for

    mesh = mesh_for(fsdp, fsdp=fsdp)
    boxed = jax.eval_shape(lambda k: llama.init_params(cfg, k)[0],
                           jax.random.PRNGKey(0))
    tx = optax.adamw(lr)
    step, shard_state, batch_sharding = make_train_step(
        llama.make_loss_fn(cfg, mesh), tx, mesh=mesh,
        param_logical_axes=param_logical_axes(boxed),
        batch_logical_axes=("batch", "seq"))
    # on one device shard_state aliases what it is given; on several it
    # copies, and the unsharded tree on device 0 goes when its name does
    # (never .delete(): a replicated leaf's shard may be the same buffer)
    state = shard_state(TrainState.create(_init_params(cfg, seed), tx))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq), 0,
                           cfg.vocab_size), batch_sharding)
    data = {"tokens": tokens}
    lowered = step.lower(state, data).as_text()
    if expect_custom_call and "tpu_custom_call" not in lowered:
        raise AssertionError("train step lowered without tpu_custom_call")
    held = _bytes_per_device(state)
    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.monotonic()
        state, metrics = step(state, data)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        seconds.append(round(time.monotonic() - t0, 3))
    _free(state, data)
    return {"losses": [round(x, 4) for x in losses], "step_seconds": seconds,
            "state_bytes_per_device": held,
            "params": sum(x.size for x in jax.tree_util.tree_leaves(
                unbox(boxed)))}


def _check_losses(losses) -> None:
    import math

    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a fixed batch: {losses}")


def phase_train(args, cfg=None, *, batch: int = 2, seq: int = 2048,
                lr: float = 3e-5, expect_custom_call: bool = True) -> dict:
    from lzy_tpu import op

    cfg = cfg or train_config()

    @op
    def train(seed: int) -> dict:
        return _train_steps(cfg, seed=seed, batch=batch, seq=seq, steps=5,
                            lr=lr, fsdp=1,
                            expect_custom_call=expect_custom_call)

    with _local_lzy("train").workflow("chip-smoke-train"):
        out = dict(train(args.seed))
    _check_losses(out["losses"])
    out.update({
        "changed_from_llama3_8b": changed_keys(cfg), "batch": batch,
        "seq": seq, "steps": 5,
        "flash_lowered_as": "tpu_custom_call" if expect_custom_call
        else "interpreted (asked for by the caller)",
        "tokens_per_second_steady": round(
            batch * seq / min(out["step_seconds"][1:]), 1),
    })
    return out


# -- phase: control-plane -----------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_control_plane(args) -> dict:
    """The deployable binary as a child that owns the chip. This process
    stays off JAX: it is the client."""
    from lzy_tpu.rpc.control import RpcInferenceClient

    work = tempfile.mkdtemp(prefix="chip-smoke-cp-")
    port = _free_port()
    log_path = os.path.join(work, "serve.log")
    cmd = [sys.executable, "-m", "lzy_tpu.service.serve",
           "--db", os.path.join(work, "meta.db"),
           "--storage-uri", f"file://{work}/storage", "--port", str(port),
           "--serve-model", "tiny", "--gateway",
           # the toy model's heads are 16 wide, and the decode kernel's
           # page slices need the lane width (128): ``auto`` would lower
           # at construction and be refused by the compiler at warm-up
           "--serve-kernel", "lax",
           "--replicas", "2", "--serve-slots", "2"]
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        server = subprocess.Popen(cmd, cwd=REPO, stdout=log,
                                  stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 420
        while "control plane serving on" not in open(log_path).read():
            if server.poll() is not None:
                raise AssertionError(
                    f"serve.py exited with {server.returncode} before it "
                    f"served:\n{open(log_path).read()[-3000:]}")
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"serve.py did not serve within 420 s:\n"
                    f"{open(log_path).read()[-3000:]}")
            time.sleep(0.5)
        boot = time.monotonic() - t0
        client = RpcInferenceClient(f"127.0.0.1:{port}")
        first = client.generate([5, 9, 3], max_new_tokens=6, timeout_s=120)
        again = client.generate([5, 9, 3], max_new_tokens=6, timeout_s=120)
        other = client.generate([7, 2, 8, 1], max_new_tokens=6,
                                timeout_s=120)
        for reply in (first, again, other):
            if reply["status"] != "ok" or len(reply["tokens"]) != 6:
                raise AssertionError(f"bad reply over gRPC: {reply}")
        if first["tokens"] != again["tokens"]:
            raise AssertionError(
                f"the same greedy prompt gave {first['tokens']} then "
                f"{again['tokens']}")
        server.send_signal(signal.SIGTERM)
        rc = server.wait(timeout=120)
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
    text = open(log_path).read()
    if rc != 0 or "draining serving plane" not in text:
        raise AssertionError(
            f"serve.py ended with {rc}; its log:\n{text[-3000:]}")
    engines_on = next((line for line in text.splitlines()
                       if line.startswith("serving engines on")), None)
    if args.require_tpu and (engines_on is None
                             or "platform=tpu" not in engines_on):
        raise AssertionError(
            f"serve.py's engines are not on the TPU: {engines_on!r}")
    return {"boot_seconds": round(boot, 2), "requests": 3,
            "tokens": first["tokens"], "exit_code": rc,
            "server_says": engines_on, "compile_seconds": None}


# -- phases on four chips -----------------------------------------------------


def phase_gang(args, cfg=None, *, slots: int = 4, page_size: int = 16,
               pool=None, new_tokens: int = 32, pad_to: int = 256,
               tp: int = 4) -> dict:
    """The one-chip engine first, then the 1x4 gang on the same parameters;
    each frees its pool before the next is built."""
    cfg = cfg or serve_config()
    pool = pool if pool is not None else {"kv_pool_bytes": 2 << 30}
    params = _init_params(cfg, args.seed)
    plan = _request_plan(cfg.vocab_size, args.seed, new_tokens)
    common = dict(seed=args.seed, page_size=page_size, slots=slots, pool=pool)
    solo = _serve_once(cfg, params, plan, "solo", **common)
    gang = _serve_once(cfg, params, plan, "gang", gang=tp, **common)
    for what in ("params_bytes_per_device", "pool_bytes_per_device"):
        held = gang[what]
        if len(held) != tp or min(held.values()) == 0:
            raise AssertionError(f"gang {what}: not every device holds a "
                                 f"share: {held}")
    if max(gang["pool_bytes_per_device"].values()) \
            != min(gang["pool_bytes_per_device"].values()):
        raise AssertionError(
            f"the pool is not split evenly: {gang['pool_bytes_per_device']}")
    verdicts: dict = {}
    for name in ("A", "B", "C1", "D"):
        prompt, tokens, _ = gang[name]
        _judge(cfg, params, f"gang/{name} vs one chip", prompt, tokens,
               solo[name][1], pad_to, verdicts)
    for name, run in (("solo", solo), ("gang", gang)):
        prompt, tokens, _ = run["C2"]
        _judge(cfg, params, f"{name}/C2 vs generate()", prompt, tokens,
               _oracle(cfg, params, prompt, new_tokens), pad_to, verdicts)
    return {
        "changed_from_llama3_8b": changed_keys(cfg), "mesh": f"1x{tp}",
        "verdicts": verdicts, "logit_tie_tolerance": LOGIT_TIE_TOL,
        "one_chip": {"params_bytes_per_device":
                     solo["params_bytes_per_device"],
                     "pool_bytes_per_device": solo["pool_bytes_per_device"],
                     **_speeds(solo, new_tokens)},
        "gang": {"params_bytes_per_device": gang["params_bytes_per_device"],
                 "pool_bytes_per_device": gang["pool_bytes_per_device"],
                 **_speeds(gang, new_tokens)},
    }


def phase_fsdp(args, cfg=None, *, batch: int = 4, seq: int = 2048,
               lr: float = 3e-5, expect_custom_call: bool = True,
               fsdp: int = 4) -> dict:
    """The same five steps on one device, then over ``fsdp`` devices."""
    cfg = cfg or train_config()
    kw = dict(seed=args.seed, batch=batch, seq=seq, steps=5, lr=lr,
              expect_custom_call=expect_custom_call)
    one = _train_steps(cfg, fsdp=1, **kw)
    many = _train_steps(cfg, fsdp=fsdp, **kw)
    _check_losses(one["losses"])
    _check_losses(many["losses"])
    worst = max(abs(a - b) / abs(a)
                for a, b in zip(one["losses"], many["losses"]))
    if worst > FSDP_LOSS_RTOL:
        raise AssertionError(
            f"fsdp={fsdp} losses {many['losses']} against one device "
            f"{one['losses']}: {worst:.4f} over {FSDP_LOSS_RTOL}")
    held = many["state_bytes_per_device"]
    if len(held) != fsdp or max(held.values()) > 1.1 * min(held.values()):
        raise AssertionError(f"train state is not spread evenly: {held}")
    return {"changed_from_llama3_8b": changed_keys(cfg), "batch": batch,
            "seq": seq, "mesh": f"fsdp={fsdp}", "one_device": one,
            "fsdp": many, "loss_rel_diff": round(worst, 5),
            "loss_tolerance": FSDP_LOSS_RTOL}


PHASES = {
    "device": phase_device, "kernels": phase_kernels, "serve": phase_serve,
    "train": phase_train, "control-plane": phase_control_plane,
    "gang": phase_gang, "fsdp": phase_fsdp,
}


# -- a phase as a process; the parent -----------------------------------------


def run_phase(args) -> int:
    """One phase in this process. Every phase but ``control-plane`` takes the
    chip: it turns the compile cache on, counts compiles and refuses to run
    on anything but a TPU."""
    t0 = time.monotonic()
    line = {"phase": args.phase}
    on_chip = args.phase != "control-plane"
    if on_chip:
        from lzy_tpu.utils.jaxenv import device_summary, enable_compile_cache

        cache_dir = enable_compile_cache()
        device = device_summary()
        if args.require_tpu and device["platform"] != "tpu":
            print(f"chip_smoke: JAX found platform={device['platform']!r} "
                  f"({device['kind']}), not a TPU", file=sys.stderr)
            return 1
        if device["count"] < args.chips:
            print(f"chip_smoke: --chips {args.chips} but JAX reports "
                  f"{device['count']} device(s)", file=sys.stderr)
            return 1
        line.update(device)
        line["compile_cache"] = cache_dir or \
            os.environ["JAX_COMPILATION_CACHE_DIR"]
    result = PHASES[args.phase](args)
    if on_chip:
        line.update(compile_doc())
    line.update(result)
    line["seconds"] = round(time.monotonic() - t0, 2)
    print(json.dumps(line), flush=True)
    return 0


def _run_child(name: str, args) -> dict:
    """Run one phase as a child, pass its output through, return its line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--chips", str(args.chips), "--seed", str(args.seed)]
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    line = None
    try:
        for raw in child.stdout:
            print(raw, end="", flush=True)
            try:
                doc = json.loads(raw)
            except ValueError:
                continue
            if isinstance(doc, dict) and doc.get("phase") == name:
                line = doc
        rc = child.wait()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0 or line is None:
        print(f"chip_smoke: phase {name} failed (exit code {rc})",
              file=sys.stderr)
        raise SystemExit(rc or 1)
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the sharded paths and what they are "
                             "compared with, and no other phase")
    parser.add_argument("--seed", type=int, default=0,
                        help="the weights and the prompts are made from it")
    parser.add_argument("--phase", choices=sorted(PHASES), default=None,
                        help="run one phase in this process (what the "
                             "parent starts for each phase)")
    args = parser.parse_args(argv)
    # not an option: the script runs on a TPU or not at all (the CPU
    # rehearsal in tests/ calls the phase functions, not this)
    args.require_tpu = True
    if args.phase:
        return run_phase(args)
    device = None
    for name in FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES:
        line = _run_child(name, args)
        if name == "device":
            device = {k: line[k] for k in ("platform", "kind", "count")}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

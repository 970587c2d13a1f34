"""Benchmark: flagship train-step MFU on the attached TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}, re-printed
with a fuller ``detail`` as each probe ends; the last line is the result.
Baseline per BASELINE.md north star: 40% MFU for an @op train step
(the reference publishes no numbers of its own; 0.40 MFU is the target the
TPU build must reach, so vs_baseline = achieved_mfu / 0.40).

One process, on the chip: ``python bench.py`` fails at once where JAX finds
no TPU, and a failure is a traceback and a non-zero exit code, never a result
line. Progress is staged on stderr. ROADMAP S1 turns this file into cells of
a ``BENCHMARK.json``; until then nobody optimises against its numbers.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "llama_train_step_mfu"


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the benchmark
# --------------------------------------------------------------------------


def pick_config():
    """Model + batch sized for the target: ~350M-param Llama on one v5e chip.

    The PRIMARY config is the fused-CE + full-recompute-remat b16 variant:
    the only headline candidate whose deviceless compile fits 16 GB HBM
    (8.55 GB; the dense b8 config needs 17.1 GB and would RESOURCE_EXHAUST
    the chip). The dense no-remat config survives as the ``dense_b8``
    secondary probe in run().
    """
    from lzy_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=32_768, d_model=1024, n_layers=20, n_heads=8,
        n_kv_heads=8, d_ff=4096, max_seq_len=2048,
        remat=True, remat_policy="nothing", fused_ce=True,
        tie_embeddings=True, use_flash_kernel=True,
    )
    return cfg, 16, 2048, 20, 3


def run() -> None:
    import jax

    from lzy_tpu.parallel import chip_peak_tflops
    from lzy_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU and found platform={platform!r} "
            f"({kind}); there is no CPU mode")
    peak = chip_peak_tflops(kind)
    _log(f"backend up: {len(devices)}x {kind}")

    import optax

    from lzy_tpu.models import count_params, llama, unbox
    from lzy_tpu.parallel import TrainState, make_train_step, mesh_for, mfu

    cfg, batch_size, seq_len, steps, warmup = pick_config()

    mesh = mesh_for(fsdp=-1)
    _log("initializing params...")
    boxed, axes = llama.init_params(cfg, jax.random.PRNGKey(0))
    params = unbox(boxed)
    n_params = count_params(params)
    _log(f"model ready: {n_params/1e6:.0f}M params, batch {batch_size} x seq {seq_len}")

    tx = optax.adamw(3e-4)
    loss_fn = llama.make_loss_fn(cfg, mesh)
    step, shard_state, _ = make_train_step(
        loss_fn, tx, mesh=mesh, param_logical_axes=axes,
        batch_logical_axes=("batch", "seq"),
    )
    state = shard_state(TrainState.create(params, tx))
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (batch_size, seq_len), 0, cfg.vocab_size
        )
    }

    # each step consumes the previous state, so the last loss being ready
    # proves the whole chain executed
    _log("compiling + warmup...")
    for i in range(warmup):
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        _log(f"warmup step {i + 1}/{warmup} done")

    _log(f"timing {steps} steps...")
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    final_loss = float(jax.block_until_ready(metrics["loss"]))
    dt = time.perf_counter() - t0
    step_ms = 1000 * dt / steps
    _log(f"timed: {step_ms:.1f} ms/step, loss {final_loss:.3f}")

    tokens_per_s = batch_size * seq_len * steps / dt
    achieved_mfu = mfu(tokens_per_s, n_params, len(devices),
                       peak_tflops=peak)

    detail = {
        "platform": platform,
        "device_kind": kind,
        "chips": len(devices),
        "params": n_params,
        "tokens_per_s": round(tokens_per_s, 1),
        "step_time_ms": round(step_ms, 2),
        "batch": batch_size,
        "seq_len": seq_len,
    }

    def emit():
        print(json.dumps({
            "metric": METRIC,
            "value": round(achieved_mfu, 4),
            "unit": "mfu_fraction",
            "vs_baseline": round(achieved_mfu / 0.40, 4),
            "detail": detail,
        }), flush=True)

    # headline first; every later emit() repeats it with more detail
    emit()
    # the adam moments (~2x params) are dead weight from here on; freeing
    # them is what lets the extra passes fit in HBM next to the live params
    params = state.params
    _free_buffers(state.opt_state)
    state = None
    extra = step_breakdown(jax, loss_fn, params, batch, step_ms)
    if extra:
        detail.update(extra)
        emit()
    extra = decode_measurement(
        jax, cfg, params,
        batch_size=8,
        prompt_len=128,
        new_tokens=64)
    if extra:
        detail.update(extra)
        emit()
    extra = paged_decode_measurement(
        jax, cfg, params,
        batch_size=8,
        prompt_len=128,
        new_tokens=64,
        page_size=64)
    if extra:
        detail.update(extra)
        emit()
    extra = spec_decode_measurement(
        jax, cfg, params,
        slots=8,
        page_size=64,
        prompt_len=24,
        new_tokens=64,
        spec_tokens=6)
    if extra:
        detail.update(extra)
        emit()
    extra = fleet_decode_measurement(
        jax, cfg, params,
        replicas=2,
        slots=4,
        prompt_len=64,
        new_tokens=32,
        n_requests=8)
    if extra:
        detail.update(extra)
        emit()
    extra = disagg_measurement(
        jax, cfg, params,
        decode_replicas=2,
        slots=4,
        page_size=64,
        long_prompt_len=256,
        short_prompt_len=16,
        new_tokens=32,
        n_requests=8)
    if extra:
        detail.update(extra)
        emit()
    extra = kvtier_measurement(
        jax, cfg, params,
        slots=4,
        page_size=64,
        prompt_len=512,
        new_tokens=16)
    if extra:
        detail.update(extra)
        emit()
    extra = slo_measurement(
        jax, cfg, params,
        slots=4,
        page_size=64,
        long_prompt_len=512,
        new_tokens=16,
        n_victim=32,
        prefill_budget=256)
    if extra:
        detail.update(extra)
        emit()
    extra = llm_op_pipeline_measurement(
        jax, cfg, params,
        replicas=2,
        slots=4,
        page_size=64,
        prompt_len=128,
        new_tokens=32,
        n_conversations=6,
        steps=3)
    if extra:
        detail.update(extra)
        emit()
    extra = agent_pipeline_measurement(
        jax, cfg, params,
        replicas=2,
        slots=4,
        # page <= reply so the speculative prefill covers whole reply
        # pages — the thing the fused TTFT number is measuring
        page_size=32,
        prompt_len=128,
        new_tokens=32,
        n_conversations=6,
        steps=3)
    if extra:
        detail.update(extra)
        emit()
    extra = stream_measurement(
        jax, cfg, params,
        slots=4,
        prompt_len=64,
        new_tokens=64)
    if extra:
        detail.update(extra)
        emit()
    extra = gateway_restart_measurement(
        jax, cfg, params,
        replicas=2,
        slots=2,
        prompt_len=32,
        new_tokens=24)
    if extra:
        detail.update(extra)
        emit()
    extra = capacity_curve_measurement()
    if extra:
        detail.update(extra)
        emit()
    # each extra pass builds a whole second model+optimizer: evict the
    # previous one (buffers AND compiled executables) first or OOM
    _free_buffers(params, batch, metrics)
    params = batch = metrics = None
    jax.clear_caches()
    # secondary probe: the pre-promotion dense no-remat config. Its
    # deviceless compile needs 17.1 GB, so an OOM here is EXPECTED
    # evidence, not a regression — the fused-b16 headline above is
    # what the chip actually serves
    extra = variant_measurement(
        jax, cfg, mesh, n_params, "dense_b8",
        {"fused_ce": False, "remat": False},
        batch_size=8, seq_len=2048)
    if extra:
        detail.update(extra)
        emit()
    jax.clear_caches()
    extra = seq4k_measurement(jax, cfg, mesh, n_params)
    if extra:
        detail.update(extra)
        emit()


def _free_buffers(*trees) -> None:
    """Eagerly release device buffers (GC alone is too late on a 16 GB chip)."""
    import jax

    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "delete"):
                try:
                    leaf.delete()
                except Exception:  # noqa: BLE001 — already deleted/donated
                    pass


def variant_measurement(jax, cfg, mesh, n_params, tag: str, overrides: dict,
                        *, batch_size: int, seq_len: int, steps: int = 10,
                        _raise: bool = False):
    """Best-effort MFU for a config variant (e.g. the logits-free fused CE
    loss, or the seq-4k point) — the evidence for flipping defaults. MFU is
    computed against the HEADLINE model's param count so variants are
    comparable. With ``_raise`` failures propagate (for callers with their
    own retry policy); otherwise they are logged and swallowed."""
    try:
        import dataclasses

        import optax

        from lzy_tpu.models import llama, unbox
        from lzy_tpu.parallel import (
            TrainState, chip_peak_tflops, make_train_step, mfu)

        _log(f"{tag}: building model...")
        vcfg = dataclasses.replace(cfg, **overrides)
        boxed, axes = llama.init_params(vcfg, jax.random.PRNGKey(0))
        tx = optax.adamw(3e-4)
        step, shard_state, _ = make_train_step(
            llama.make_loss_fn(vcfg, mesh), tx, mesh=mesh,
            param_logical_axes=axes, batch_logical_axes=("batch", "seq"),
        )
        state = shard_state(TrainState.create(unbox(boxed), tx))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (batch_size, seq_len), 0, vcfg.vocab_size
        )}
        try:
            _log(f"{tag}: compiling + warmup...")
            for _ in range(2):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            _log(f"{tag}: timing {steps} steps...")
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
        finally:
            _free_buffers(state, batch)
        tokens_per_s = batch_size * seq_len * steps / dt
        value = mfu(tokens_per_s, n_params, len(jax.devices()),
                    peak_tflops=chip_peak_tflops(
                        jax.devices()[0].device_kind))
        _log(f"{tag}: {1000 * dt / steps:.1f} ms/step, mfu {value:.4f}")
        return {f"{tag}_mfu": round(value, 4),
                f"{tag}_step_time_ms": round(1000 * dt / steps, 2)}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        if _raise:
            raise
        _log(f"{tag} skipped: {type(e).__name__}: {e}")
        return {}


def seq4k_measurement(jax, cfg, mesh, n_params, steps: int = 10):
    """Best-effort long-context point: MFU at seq 4096,
    batch halved to keep HBM flat. Never risks the headline metric."""
    # fastest first, then progressively trade FLOPs for memory: dots keeps
    # the MXU outputs (the standard transformer remat point on TPU);
    # nothing_saveable is the max-savings last resort
    attempts = [(False, None), (True, "dots"), (True, "nothing")]
    for remat, policy in attempts:
        try:
            overrides = {"max_seq_len": 4096, "remat": remat}
            if policy is not None:
                overrides["remat_policy"] = policy
            out = variant_measurement(
                jax, cfg, mesh, n_params, "seq4k", overrides,
                batch_size=4, seq_len=4096, steps=steps, _raise=True)
            out["seq4k_batch"] = 4
            if remat:
                out["seq4k_remat"] = policy
            return out
        except Exception as e:  # noqa: BLE001 — diagnostics only
            _log(f"seq4k (remat={remat},{policy}) skipped: "
                 f"{type(e).__name__}: {e}")
            if "RESOURCE_EXHAUSTED" not in str(e):
                return {}
            jax.clear_caches()  # next attempt saves more memory
    return {}


def decode_measurement(jax, cfg, params, *, batch_size: int,
                       prompt_len: int, new_tokens: int):
    """Best-effort serving-path point: KV-cache decode throughput of the
    headline model (batched prefill + one jitted per-token decode step —
    the exact hot loop the continuous-batching engine in lzy_tpu/serving
    drives). The step is jitted ONCE and timed directly, so the metric is
    pure decode — no prefill share, no per-call recompiles; two extra
    compiles total (prefill chunk + step), wrapped so a hiccup never
    loses the headline metric."""
    try:
        import functools

        import jax.numpy as jnp

        from lzy_tpu.models.generate import (
            batched_prefill, decode_config, init_cache, make_prefill_step)
        from lzy_tpu.models.llama import Llama

        dcfg = decode_config(cfg)
        model = Llama(dcfg)
        prompt = jax.random.randint(
            jax.random.PRNGKey(2), (batch_size, prompt_len), 0,
            dcfg.vocab_size)
        _log("decode: compiling + prefill...")
        cache = init_cache(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch_size, 1), jnp.int32)))
        cache, last = batched_prefill(
            model, cache, params, prompt, max_seq_len=dcfg.max_seq_len,
            prefill_step=make_prefill_step(model))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(cache, params, tok):
            logits, updated = model.apply(
                {"params": params, "cache": cache}, tok, mutable=["cache"])
            return (updated["cache"],
                    jnp.argmax(logits[:, -1], -1).astype(jnp.int32))

        cur = jnp.argmax(last, -1).astype(jnp.int32)
        # two warm steps: the first compiles against host-fresh inputs,
        # the second against the jit's own (committed) outputs — with
        # sharded bench params those are distinct compilations, and the
        # second would otherwise land inside the timed window
        cache, cur = step(cache, params, cur[:, None])   # compile + warmup
        cache, cur = step(cache, params, cur[:, None])
        cur.block_until_ready()
        _log(f"decode: timing {new_tokens} steps x batch {batch_size}...")
        t0 = time.perf_counter()
        for _ in range(new_tokens):
            cache, cur = step(cache, params, cur[:, None])
        cur.block_until_ready()
        dt = time.perf_counter() - t0
        tps = batch_size * new_tokens / dt
        _log(f"decode: {1000 * dt / new_tokens:.2f} ms/step, "
             f"{tps:.1f} tok/s")
        return {"decode_tokens_per_s": round(tps, 1),
                "decode_step_ms": round(1000 * dt / new_tokens, 3),
                "decode_batch": batch_size,
                "decode_prompt_len": prompt_len}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"decode skipped: {type(e).__name__}: {e}")
        return {}


def paged_decode_measurement(jax, cfg, params, *, batch_size: int,
                             prompt_len: int, new_tokens: int,
                             page_size: int):
    """Best-effort paged-serving point: decode throughput through the
    PAGED attention path (the hot loop of serving.PagedInferenceEngine),
    measured next to the dense ``decode_tokens_per_s`` so the per-step
    cost of paging is a number, not a guess. Three variants per round so
    the trajectory separates kernel wins from config drift:

    - the NATIVE path (ops/paged_attention: pallas on TPU, the lax
      oracle elsewhere) is the headline ``paged_decode_tokens_per_s``;
    - the LEGACY gather-back-to-dense path rides along as
      ``paged_decode_legacy_tokens_per_s`` (the pre-PR-9 number);
    - the native path over an int8-quantized pool
      (``paged_decode_quant_tokens_per_s``) shows what halved KV bytes
      cost/buy per step at identical shapes.

    ``kernel_path`` (pallas/lax/legacy) and ``kv_quant`` are recorded in
    the row. Pure-throughput shape: identity page tables, the cache
    index parked at ``prompt_len`` (step cost does not depend on what
    the K/V bytes contain). A few extra compiles, wrapped so a hiccup
    never loses the headline metric."""
    try:
        import dataclasses
        import functools

        import jax.numpy as jnp

        from lzy_tpu.models.generate import (
            _set_cache_index, decode_config, init_cache)
        from lzy_tpu.models.llama import Llama
        from lzy_tpu.ops.paged_attention import default_kernel

        pages_per_seq = cfg.max_seq_len // page_size
        n_pages = batch_size * pages_per_seq + 1
        pt = jnp.arange(
            1, batch_size * pages_per_seq + 1, dtype=jnp.int32
        ).reshape(batch_size, pages_per_seq)
        native_kernel = default_kernel()

        # Timing discipline (the BENCH_r06 "lax trails legacy by 14%"
        # postmortem): the two paths compile to BYTE-IDENTICAL optimized
        # HLO on CPU — a side-by-side `.lower().compile().as_text()`
        # dump diffs clean except for metadata — so the measured gap was
        # never a kernel gap. It was ordering noise: each variant timed
        # exactly once, back to back, so whichever ran first paid (or
        # dodged) allocator warmup and cache effects for the others.
        # Fix: build + warm EVERY variant first, then time them in
        # interleaved round-robin rounds and keep the best round per
        # variant. A real kernel regression still loses every round;
        # one-off scheduling hiccups no longer masquerade as one.
        def build_variant(tag, **over):
            dcfg = dataclasses.replace(
                decode_config(cfg), decode_paged=True,
                kv_page_size=page_size, kv_pages=n_pages, **over)
            model = Llama(dcfg)
            _log(f"paged decode[{tag}]: compiling...")
            cache = init_cache(lambda: model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((batch_size, 1), jnp.int32), page_table=pt))
            cache = _set_cache_index(cache, prompt_len)

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(cache, params, tok, pt):
                logits, updated = model.apply(
                    {"params": params, "cache": cache}, tok,
                    page_table=pt, mutable=["cache"])
                return (updated["cache"],
                        jnp.argmax(logits[:, -1], -1).astype(jnp.int32))

            cur = jnp.zeros((batch_size,), jnp.int32)
            # two warm steps — same second-layout reasoning as the dense
            # probe
            cache, cur = step(cache, params, cur[:, None], pt)
            cache, cur = step(cache, params, cur[:, None], pt)
            cur.block_until_ready()
            state = {"cache": cache, "cur": cur}

            def run():
                cache, cur = state["cache"], state["cur"]
                t0 = time.perf_counter()
                for _ in range(new_tokens):
                    cache, cur = step(cache, params, cur[:, None], pt)
                cur.block_until_ready()
                dt = time.perf_counter() - t0
                state["cache"], state["cur"] = cache, cur
                return batch_size * new_tokens / dt, 1000 * dt / new_tokens

            def free():
                _free_buffers(state["cache"])

            return run, free

        # legacy FIRST: the variant proven green on every pre-PR-9 round
        # is banked before the native path gets a chance to hiccup, so
        # the headline can fall back to it instead of vanishing
        out = {"paged_decode_page_size": page_size,
               "paged_decode_kv_quant": "off"}
        variants = []  # [tag, run, free] — mutable so a timing failure
        legacy_built = False  # can drop one variant without losing the rest
        try:
            run, free = build_variant("legacy")
            variants.append(["legacy", run, free])
            legacy_built = True
        except Exception as e:  # noqa: BLE001 — variant is optional
            _log(f"paged decode legacy variant skipped: "
                 f"{type(e).__name__}: {e}")
        native_built = False
        try:
            run, free = build_variant(
                native_kernel, paged_attention_native=True,
                paged_kernel=native_kernel)
            variants.append([native_kernel, run, free])
            native_built = True
        except Exception as e:  # noqa: BLE001 — fall back to legacy
            if not legacy_built:
                raise
            _log(f"paged decode native variant failed "
                 f"({type(e).__name__}: {e}); legacy headline")
        try:
            run, free = build_variant(
                f"{native_kernel}+int8", paged_attention_native=True,
                paged_kernel=native_kernel, kv_quant="int8")
            variants.append([f"{native_kernel}+int8", run, free])
        except Exception as e:  # noqa: BLE001 — variant is optional
            _log(f"paged decode quant variant skipped: "
                 f"{type(e).__name__}: {e}")

        best = {}  # tag -> (tps, step_ms), best round wins
        for rnd in range(3):
            for entry in list(variants):
                tag, run = entry[0], entry[1]
                try:
                    tps_r, ms_r = run()
                except Exception as e:  # noqa: BLE001 — drop variant
                    _log(f"paged decode[{tag}] round {rnd} failed "
                         f"({type(e).__name__}: {e}); dropping variant")
                    variants.remove(entry)
                    best.pop(tag, None)
                    if tag == native_kernel:
                        native_built = False
                    continue
                _log(f"paged decode[{tag}] r{rnd}: {ms_r:.2f} ms/step, "
                     f"{tps_r:.1f} tok/s (page {page_size})")
                if tag not in best or tps_r > best[tag][0]:
                    best[tag] = (tps_r, ms_r)
        for entry in variants:
            entry[2]()

        if "legacy" in best:
            out["paged_decode_legacy_tokens_per_s"] = round(
                best["legacy"][0], 1)
        if native_built and native_kernel in best:
            tps, step_ms = best[native_kernel]
            out["paged_decode_kernel_path"] = native_kernel
        elif "legacy" in best:
            tps, step_ms = best["legacy"]
            out["paged_decode_kernel_path"] = "legacy"
        else:
            raise RuntimeError("no paged decode variant survived timing")
        out["paged_decode_tokens_per_s"] = round(tps, 1)
        out["paged_decode_step_ms"] = round(step_ms, 3)
        quant_tag = f"{native_kernel}+int8"
        if quant_tag in best:
            out["paged_decode_quant_tokens_per_s"] = round(
                best[quant_tag][0], 1)
            out["paged_decode_quant_mode"] = "int8"
        if quant_tag in best:
            try:
                # observed quantizer error on a representative KV sample
                # (feeds the lzy_kernel_dequant_error_ewma gauge; the
                # timing loop's pool holds zeros, whose error would read
                # as 0.0)
                from lzy_tpu.ops.paged_attention import (
                    dequantize_kv, note_dequant_error, quantize_kv)

                sample = jax.random.normal(
                    jax.random.PRNGKey(0), (1024, cfg.head_dim),
                    jnp.float32)
                qs, ss, zs = quantize_kv(sample)
                err = float(jnp.mean(jnp.abs(
                    dequantize_kv(qs, ss, zs, jnp.float32) - sample)))
                out["paged_decode_dequant_err_mean"] = round(
                    note_dequant_error(err), 6)
            except Exception as e:  # noqa: BLE001 — metric is optional
                _log(f"paged decode dequant-error probe skipped: "
                     f"{type(e).__name__}: {e}")
        return out
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"paged decode skipped: {type(e).__name__}: {e}")
        return {}


def _sim_spec_tokens_per_step(proposer, prompt, cont):
    """Host-side replay of the engine's acceptance rule over a KNOWN
    greedy continuation: how many tokens/step would prompt lookup have
    earned on this request? Pure python (no device work) — the workload
    selector below uses it to score candidates."""
    hist = list(prompt) + [int(cont[0])]
    i, rounds, emitted = 1, 0, 0
    while i < len(cont):
        p = proposer.propose(hist)
        rounds += 1
        take = 1
        if p:
            m = 0
            while m < len(p) and i + m < len(cont) \
                    and p[m] == int(cont[i + m]):
                m += 1
            take = min(m + 1, len(cont) - i)
        hist += [int(t) for t in cont[i:i + take]]
        i += take
        emitted += take
    return emitted / rounds if rounds else 1.0


def spec_decode_measurement(jax, cfg, params, *, slots: int,
                            page_size: int, prompt_len: int,
                            new_tokens: int, spec_tokens: int):
    """Best-effort speculative-decoding point (serving/spec.py).

    The headline ``spec_decode_tokens_per_s`` is measured EXACTLY like
    its baseline ``paged_decode_tokens_per_s``: a raw loop over the
    jitted paged forward — here the ``[B, gamma+1]`` verify step with
    host-side n-gram proposal, exact-match acceptance and index rewind
    (the speculative hot loop, minus engine scheduling) — so the two
    numbers differ only by what speculation changes. The engine-level
    pair (``spec_engine_*``, speculation on vs off through the full
    ``PagedInferenceEngine``) rides along as the end-to-end view.

    Speculation is a WORKLOAD-CLASS optimization: it pays on
    repetitive/structured continuations (code, extraction, summaries
    quoting their source) and is a wash on free-form text. Like the
    fleet probe (which must use a shared-prefix workload or affinity is
    structurally unmeasurable), this probe has to measure the class the
    feature targets: a selection pass generates candidate prompts,
    scores each by replaying the acceptance rule over its actual greedy
    continuation (host-side; one batched generate of device work), and
    benchmarks the most repetitive-continuation ones. The acceptance
    rate is reported so a reader can discount the number for less
    repetitive traffic. Wrapped so a hiccup never loses the headline
    metric."""
    try:
        import dataclasses
        import functools

        import jax.numpy as jnp
        import numpy as np

        from lzy_tpu.models.generate import (
            decode_config, generate, init_cache)
        from lzy_tpu.models.llama import Llama
        from lzy_tpu.serving import NgramProposer, PagedInferenceEngine

        _log(f"spec decode: scoring candidate workloads "
             f"(batch {slots}, gamma {spec_tokens})...")
        # constant-token seeds spread over the vocab: the cheapest
        # generator of genuinely repetitive continuations on an arbitrary
        # model; ONE batched generate covers the whole candidate set
        cands = [[t] * prompt_len
                 for t in range(7, cfg.vocab_size, max(cfg.vocab_size // 64,
                                                       1))]
        outs = np.asarray(generate(
            cfg, params, jnp.asarray(cands, jnp.int32),
            max_new_tokens=new_tokens))
        proposer = NgramProposer(max_ngram=3, gamma=spec_tokens)
        scored = sorted(
            ((_sim_spec_tokens_per_step(
                proposer, p, outs[i, prompt_len:].tolist()), p)
             for i, p in enumerate(cands)), key=lambda x: -x[0])
        prompts = [p for _, p in scored[:slots]]
        predicted = round(sum(s for s, _ in scored[:slots]) / slots, 2)

        # -- raw verify loop (methodology twin of paged_decode) ----------
        # runs the NATIVE paged-attention path (pallas on TPU, lax
        # elsewhere): the stream-equals-generate() assertion below then
        # re-proves the native verify's bit-identity on every bench round
        from lzy_tpu.ops.paged_attention import default_kernel

        native_kernel = default_kernel()
        B, gamma, width = slots, spec_tokens, spec_tokens + 1
        pages_per_seq = cfg.max_seq_len // page_size
        pt = jnp.arange(1, B * pages_per_seq + 1, dtype=jnp.int32).reshape(
            B, pages_per_seq)

        def set_index_rows(cache, pos):
            vals = np.asarray(pos, np.int32)
            # one COPIED device array per leaf: jnp.asarray is zero-copy
            # on CPU, so it would alias this numpy buffer straight into
            # a donated jit argument — the same jnp.array-not-asarray
            # rule the engine's _cache property and device mirrors
            # (_pos_dev/_pt_dev) follow
            return jax.tree_util.tree_map_with_path(
                lambda path, leaf: jnp.array(vals) if any(
                    getattr(p, "key", None) == "index" for p in path)
                else leaf, cache)

        def build_and_warm(native: bool):
            dcfg = dataclasses.replace(
                decode_config(cfg), decode_paged=True,
                kv_page_size=page_size, kv_pages=B * pages_per_seq + 1,
                paged_attention_native=native,
                paged_kernel=native_kernel if native else "lax")
            model = Llama(dcfg)

            @functools.partial(jax.jit, donate_argnums=(0,))
            def chunk_step(cache, params, toks, pt):
                logits, upd = model.apply(
                    {"params": params, "cache": cache}, toks,
                    page_table=pt, mutable=["cache"])
                return upd["cache"], jnp.argmax(logits, -1).astype(
                    jnp.int32)

            cache = init_cache(lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32),
                page_table=pt))
            # real prefill (acceptance depends on real logits, unlike
            # the content-independent paged probe): one [B, prompt_len]
            # chunk
            cache, am = chunk_step(cache, params,
                                   jnp.asarray(prompts, jnp.int32), pt)
            am = np.asarray(am)
            # two warm verify calls (fresh-input layout, then committed
            # jit-output layout — distinct compilations under sharded
            # params); any native-path compile failure surfaces HERE,
            # before the timing loop, where the fallback can catch it
            pos0 = np.full((B,), prompt_len, np.int64)
            toks0 = np.zeros((B, width), np.int32)
            cache, _ = chunk_step(set_index_rows(cache, pos0), params,
                                  jnp.asarray(toks0), pt)
            cache, warm = chunk_step(set_index_rows(cache, pos0), params,
                                     jnp.asarray(toks0), pt)
            warm.block_until_ready()
            return chunk_step, cache, am

        # native-first with the same legacy fallback as the paged probe:
        # a kernel hiccup must cost the kernel win, never the whole
        # spec trajectory
        _log("spec decode: compiling + prefill...")
        kernel_path = native_kernel
        try:
            chunk_step, cache, am = build_and_warm(True)
        except Exception as e:  # noqa: BLE001 — fall back to legacy
            _log(f"spec decode native path failed ({type(e).__name__}: "
                 f"{e}); legacy kernel")
            kernel_path = "legacy"
            chunk_step, cache, am = build_and_warm(False)
        # per-row incremental n-gram index (what the engine keeps per
        # slot); its .seq doubles as the row's emitted history
        rows = [proposer.index(list(p) + [int(am[r, -1])])
                for r, p in enumerate(prompts)]
        pos = np.full((B,), prompt_len, np.int64)
        emitted = np.ones((B,), np.int64)   # the prefill's argmax token
        rounds = proposed = accepted = 0
        _log(f"spec decode: predicted {predicted} tok/step; timing "
             f"{B} rows x {new_tokens} tokens...")
        t0 = time.perf_counter()
        while any(emitted < new_tokens):
            toks = np.zeros((B, width), np.int32)
            drafts = []
            for r in range(B):
                d = []
                if emitted[r] < new_tokens:
                    toks[r, 0] = rows[r].seq[-1]
                    d = rows[r].propose()[:gamma]
                    toks[r, 1:1 + len(d)] = d
                drafts.append(d)
            cache = set_index_rows(cache, pos)
            cache, am_dev = chunk_step(cache, params, jnp.asarray(toks), pt)
            am = np.asarray(am_dev)
            for r in range(B):
                if emitted[r] >= new_tokens:
                    continue
                d = drafts[r]
                m = 0
                while m < len(d) and d[m] == int(am[r, m]):
                    m += 1
                take = min(m + 1, int(new_tokens - emitted[r]))
                rows[r].extend((list(d[:m]) + [int(am[r, m])])[:take])
                pos[r] += take
                emitted[r] += take
                proposed += len(d)
                accepted += m
            rounds += 1
        # np.asarray on the argmax already forced every device step
        dt = time.perf_counter() - t0
        tps_raw = B * new_tokens / dt
        acc = round(accepted / proposed, 4) if proposed else 0.0
        tok_step = round(float(B * new_tokens) / (rounds * B), 4)
        # the raw loop reproduces the oracle stream exactly (exact-match
        # acceptance): diverging here would mean a verify-path bug
        sel = {tuple(p): i for i, p in enumerate(cands)}
        for r, p in enumerate(prompts):
            want = outs[sel[tuple(p)], prompt_len:].tolist()
            got = rows[r].seq[prompt_len:prompt_len + new_tokens]
            if got != want:
                raise AssertionError(
                    f"speculative stream diverged from generate() on "
                    f"row {r}")
        _log(f"spec decode: {tps_raw:.1f} tok/s raw verify loop "
             f"(acceptance {acc}, {tok_step} tok/step)")

        # -- engine-level end-to-end pair (speculation on vs off) --------
        def drive(g: int):
            eng = PagedInferenceEngine(
                cfg, params, slots=slots, page_size=page_size,
                max_queue=2 * slots + 2, spec_tokens=g,
                native_attention=kernel_path != "legacy")
            try:
                # two warm requests: layout reasoning as above
                for i in (7, 9):
                    warm = eng.submit([3, 5 + i] * (prompt_len // 2),
                                      max_new_tokens=2 * (g + 1) + 2)
                    while not warm.done:
                        eng.step()
                reqs = [eng.submit(p, max_new_tokens=new_tokens)
                        for p in prompts]
                t0 = time.perf_counter()
                while not all(r.done for r in reqs):
                    eng.step()
                dt = time.perf_counter() - t0
                total = sum(len(r.tokens) for r in reqs)
            finally:
                eng.close()
            return total / dt

        eng_off = drive(0)
        eng_on = drive(spec_tokens)
        _log(f"spec decode: engine {eng_on:.1f} tok/s with speculation "
             f"vs {eng_off:.1f} without")
        return {"spec_decode_tokens_per_s": round(tps_raw, 1),
                "spec_acceptance_rate": acc,
                "spec_tokens_per_step": tok_step,
                "spec_gamma": spec_tokens,
                "spec_decode_kernel_path": kernel_path,
                "spec_decode_kv_quant": "off",
                "spec_engine_decode_tokens_per_s": round(eng_on, 1),
                "spec_engine_off_decode_tokens_per_s": round(eng_off, 1),
                # permanent raw-vs-engine regression gate: how many x
                # the engine's scheduling leaves on the table relative
                # to its own raw verify loop (1.0 = scheduling is free;
                # BENCH_r06 read 3.8 before the one-fence round)
                "engine_overhead_ratio": round(tps_raw / eng_on, 2)}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"spec decode skipped: {type(e).__name__}: {e}")
        return {}


def fleet_decode_measurement(jax, cfg, params, *, replicas: int,
                             slots: int, prompt_len: int,
                             new_tokens: int, n_requests: int):
    """Best-effort serving-fleet point: aggregate decode throughput of a
    multi-replica gateway (lzy_tpu/gateway) over the SAME engines the
    single-engine ``decode_tokens_per_s`` probe models — the fleet number
    next to the single number is the scaling evidence. Drives a
    shared-prefix workload through the prefix-affinity router with one
    client thread per decode slot, and reports the per-replica token
    breakdown so imbalance is a number, not a guess. Wrapped so a hiccup
    never loses the headline metric."""
    try:
        from concurrent import futures as _futures

        from lzy_tpu.gateway import (
            GatewayService, PrefixAffinityRouter, ReplicaFleet)
        from lzy_tpu.serving import InferenceEngine

        _log(f"fleet decode: building {replicas} replicas x "
             f"{slots} slots...")
        fleet = ReplicaFleet(
            lambda: InferenceEngine(cfg, params, slots=slots,
                                    max_queue=2 * n_requests))
        # router chunk 8 so the shared prefixes below span FULL chunks
        # on every config — prompts must share whole chunks or affinity
        # is structurally unmeasurable
        router = PrefixAffinityRouter(8)
        gw = GatewayService(fleet, router=router, model_name="bench",
                            max_waiters=replicas * slots + 2)
        try:
            for _ in range(replicas):
                fleet.add_replica()
            # one shared-prefix FAMILY per replica. A single fleet-wide
            # prefix routes every request to one replica BY DESIGN
            # (prefix affinity doing its job) — but that makes the probe
            # a single-replica number wearing a fleet label: BENCH_r06
            # read fleet_per_replica_tokens {replica-1: 32, replica-2: 0}.
            # Distinct families keep the affinity story AND spread load.
            chunk = prompt_len - prompt_len % 8
            families = [list(range(1 + 64 * f, chunk + 1 + 64 * f))
                        for f in range(replicas)]
            prompts = [families[i % replicas] + [i % 50 + 2, i % 30 + 2]
                       for i in range(n_requests)]
            # seed each family's affinity onto its own replica BEFORE the
            # first route: on an idle fleet the load tie-break is
            # deterministic (lowest replica id), so routing the families
            # cold would pin them all to replica-1 anyway
            for rep, fam in zip(fleet.replicas(), families):
                router.observe(rep.id, fam)
            # warmup: compile prefill + decode once per replica — the jit
            # cache is process-shared but each engine still pays its own
            # first-dispatch costs, which must not land in the timed
            # window of whichever family hits that replica first
            for f in range(replicas):
                gw.generate(prompts[f], max_new_tokens=2, timeout_s=300)
            # engine counters are cumulative — snapshot after warmup so
            # the reported breakdown covers exactly the timed window
            base = {r.id: r.engine.stats().tokens_generated
                    for r in fleet.replicas()}
            _log(f"fleet decode: timing {n_requests} requests x "
                 f"{new_tokens} tokens...")
            t0 = time.perf_counter()
            with _futures.ThreadPoolExecutor(replicas * slots) as pool:
                results = list(pool.map(
                    lambda p: gw.generate(p, max_new_tokens=new_tokens,
                                          timeout_s=300),
                    prompts))
            dt = time.perf_counter() - t0
            total = sum(len(r["tokens"]) for r in results)
            per_replica = {
                r.id: r.engine.stats().tokens_generated - base.get(r.id, 0)
                for r in fleet.replicas()}
            stats = gw.stats()
        finally:
            gw.close()
        tps = total / dt
        _log(f"fleet decode: {tps:.1f} tok/s aggregate over "
             f"{replicas} replicas ({per_replica})")
        return {"fleet_decode_tokens_per_s": round(tps, 1),
                "fleet_replicas": replicas,
                "fleet_slots_per_replica": slots,
                "fleet_per_replica_tokens": per_replica,
                "fleet_prefix_route_rate": stats["prefix_route_rate"]}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"fleet decode skipped: {type(e).__name__}: {e}")
        return {}


def disagg_measurement(jax, cfg, params, *, decode_replicas: int,
                       slots: int, page_size: int, long_prompt_len: int,
                       short_prompt_len: int, new_tokens: int,
                       n_requests: int):
    """Best-effort disaggregated-serving point: TTFT and aggregate decode
    throughput of a prefill-pool + decode-pool gateway
    (lzy_tpu/gateway/disagg) under a MIXED long-prompt/short-prompt
    workload — the traffic shape disaggregation exists for (long prefills
    stall co-resident decodes on a monolithic replica). Reported next to
    the monolithic ``fleet_decode_tokens_per_s`` so the interference win
    is a number. Wrapped so a hiccup never loses the headline metric."""
    try:
        from concurrent import futures as _futures

        from lzy_tpu.gateway import (
            DisaggGatewayService, PrefixAffinityRouter, ReplicaFleet)
        from lzy_tpu.serving import DecodeEngine, PrefillEngine

        _log(f"disagg: building 1 prefill + {decode_replicas} decode "
             f"replicas x {slots} slots (page {page_size})...")
        kw = dict(slots=slots, page_size=page_size,
                  max_queue=2 * n_requests)
        decode_fleet = ReplicaFleet(
            lambda: DecodeEngine(cfg, params, **kw),
            replica_prefix="decode")
        prefill_fleet = ReplicaFleet(
            lambda: PrefillEngine(cfg, params, **kw),
            replica_prefix="prefill")
        gw = DisaggGatewayService(
            decode_fleet, prefill_fleet, page_size=page_size,
            router=PrefixAffinityRouter(page_size),
            prefill_router=PrefixAffinityRouter(page_size),
            prefill_replicas=1, model_name="bench",
            max_waiters=decode_replicas * slots + 2)
        try:
            for _ in range(decode_replicas):
                decode_fleet.add_replica()
            prefill_fleet.add_replica()
            # mixed workload: every other request drags a long prompt
            # through the prefill pool while short ones decode
            long_p = long_prompt_len - long_prompt_len % page_size
            prompts = []
            for i in range(n_requests):
                if i % 2 == 0:
                    prompts.append(list(range(1, long_p + 1)) + [i % 50 + 2])
                else:
                    prompts.append([i % 50 + 2, i % 30 + 3]
                                   + list(range(2, short_prompt_len + 2)))
            # warmup: compile prefill + decode on both pools
            gw.generate(prompts[0], max_new_tokens=2, timeout_s=300)
            gw.generate(prompts[1], max_new_tokens=2, timeout_s=300)
            _log(f"disagg: timing {n_requests} requests x "
                 f"{new_tokens} tokens...")
            t0 = time.perf_counter()
            with _futures.ThreadPoolExecutor(decode_replicas * slots) \
                    as pool:
                results = list(pool.map(
                    lambda p: gw.generate(p, max_new_tokens=new_tokens,
                                          timeout_s=300),
                    prompts))
            dt = time.perf_counter() - t0
            total = sum(len(r["tokens"]) for r in results)
            ttfts = [r["ttft_ms"] for r in results
                     if r.get("ttft_ms") is not None]
            stats = gw.stats()
        finally:
            gw.close()
        tps = total / dt
        ttft_ms = sum(ttfts) / len(ttfts) if ttfts else None
        _log(f"disagg: {tps:.1f} tok/s aggregate, mean TTFT "
             f"{ttft_ms and round(ttft_ms, 1)} ms "
             f"({stats['kv_transfers']} transfers, "
             f"{stats['kv_transfer_skipped_by_cache']} cache-skips, "
             f"{stats['reprefill_fallbacks']} fallbacks)")
        return {"disagg_decode_tokens_per_s": round(tps, 1),
                "disagg_ttft_ms": round(ttft_ms, 2) if ttft_ms else None,
                "disagg_decode_replicas": decode_replicas,
                "disagg_kv_transfers": stats["kv_transfers"],
                "disagg_kv_transfer_bytes": stats["kv_transfer_bytes"],
                "disagg_transfer_skipped_by_cache":
                    stats["kv_transfer_skipped_by_cache"],
                "disagg_reprefill_fallbacks":
                    stats["reprefill_fallbacks"]}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"disagg skipped: {type(e).__name__}: {e}")
        return {}


def kvtier_measurement(jax, cfg, params, *, slots: int, page_size: int,
                       prompt_len: int, new_tokens: int):
    """Best-effort tiered-KV point: TTFT of a shared-system-prompt
    request routed to a COLD replica, with the fleet-global prefix
    index importing the warm sibling's blocks vs the same fleet forced
    to re-prefill (index off). Round-robin routing makes the second
    request land on the cold replica deterministically — the exact
    traffic shape the cross-replica import exists for (autoscale /
    failover cache warm-up). Reports tier hit/miss counts so the win is
    attributable. Wrapped so a hiccup never loses the headline metric."""
    try:
        from lzy_tpu.gateway import (
            GatewayService, GlobalKVIndex, ReplicaFleet, RoundRobinRouter)
        from lzy_tpu.serving import PagedInferenceEngine

        shared_len = prompt_len - prompt_len % page_size
        shared = list(range(1, shared_len + 1))
        blocks = 4 * (shared_len // page_size) + 8

        def run_side(with_index: bool) -> dict:
            fleet = ReplicaFleet(lambda: PagedInferenceEngine(
                cfg, params, slots=slots, page_size=page_size,
                kv_blocks=blocks))
            gw = GatewayService(
                fleet, router=RoundRobinRouter(page_size),
                kv_index=GlobalKVIndex(page_size) if with_index else None,
                model_name="bench")
            try:
                for _ in range(2):
                    fleet.add_replica()
                # warm request: pays the full shared-prefix prefill on
                # replica 1 (and compiles the programs both sides share)
                r1 = gw.generate(shared + [3], max_new_tokens=2,
                                 timeout_s=300)
                gw.tick()    # replicas advertise into the global index
                # cold request: round-robin lands it on replica 2 —
                # with the index it imports r1's blocks, without it the
                # whole shared prompt re-prefills
                r2 = gw.generate(shared + [7], max_new_tokens=new_tokens,
                                 timeout_s=300)
                stats = gw.stats()
                cold = fleet.get(r2["replica"])
                saved = (cold.engine.kv.stats().prefill_tokens_saved
                         if cold is not None else 0)
                return {
                    "ttft_ms": r2["ttft_ms"],
                    "cold_replica": r2["replica"],
                    "warm_replica": r1["replica"],
                    "import_from": r2.get("kv_import_from"),
                    "imports": stats.get("kvtier_imports", 0),
                    "import_bytes": stats.get("kvtier_import_bytes", 0),
                    "fallbacks": stats.get(
                        "kvtier_reprefill_fallbacks", 0),
                    "prefill_tokens_saved": saved,
                }
            finally:
                gw.close()

        _log(f"kvtier: two-replica fleet, {shared_len}-token shared "
             f"prefix, cross-replica import vs forced re-prefill...")
        imp = run_side(True)
        base = run_side(False)
        _log(f"kvtier: import TTFT {imp['ttft_ms']} ms "
             f"({imp['imports']} imports, "
             f"{imp['prefill_tokens_saved']} tokens saved) vs re-prefill "
             f"TTFT {base['ttft_ms']} ms")
        return {
            # the headline: cold-replica TTFT with the sibling import
            "kvtier_prefix_import_ttft_ms": imp["ttft_ms"],
            # the counterfactual: same fleet, index off, full re-prefill
            "kvtier_reprefill_ttft_ms": base["ttft_ms"],
            "kvtier_imports": imp["imports"],
            "kvtier_import_bytes": imp["import_bytes"],
            "kvtier_import_from": imp["import_from"],
            # tier hit/miss per row: hits = staged imports that landed,
            # misses = fallbacks (failed stagings) + the index-off side's
            # structural miss (always re-prefills)
            "kvtier_tier_hits": imp["imports"],
            "kvtier_tier_misses": imp["fallbacks"] + 1,
            "kvtier_prefill_tokens_saved": imp["prefill_tokens_saved"],
            "kvtier_shared_prefix_tokens": shared_len,
        }
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"kvtier skipped: {type(e).__name__}: {e}")
        return {}


def _percentile(values, q: float):
    if not values:
        return None
    xs = sorted(values)
    idx = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[idx]


def slo_measurement(jax, cfg, params, *, slots: int, page_size: int,
                    long_prompt_len: int, new_tokens: int,
                    n_victim: int, prefill_budget: int):
    """Multi-tenant SLO isolation point: victim TTFT p99 under a
    long-prompt aggressor, with the SLO layer (rate limits + KV quota +
    WFQ + chunked prefill) ON vs OFF on the same paged gateway shape.
    The bursty two-tenant workload is the ISSUE-7 scenario: aggressor
    threads hammer 100+-token prompts as fast as admission lets them
    while the victim issues short interactive prompts; the ON/OFF delta
    is the number the layer exists for. Wrapped so a hiccup never loses
    the headline metric."""
    try:
        import threading as _threading

        from lzy_tpu.gateway import (
            GatewayService, PrefixAffinityRouter, ReplicaFleet)
        from lzy_tpu.serving import (
            PagedInferenceEngine, QuotaExceeded, SloLimiter, TenantPolicy,
            TenantTable)

        long_p = max(page_size, long_prompt_len - long_prompt_len
                     % page_size)

        def run_side(slo_on: bool):
            table = None
            if slo_on:
                table = TenantTable(default=TenantPolicy())
                table.set_policy(TenantPolicy(
                    tenant="agg", priority=2, requests_per_s=20.0,
                    burst_s=0.5, max_queued=2,
                    kv_block_quota=3 * (long_p // page_size)))
                table.set_policy(TenantPolicy(tenant="vic", priority=0))
            fleet = ReplicaFleet(lambda: PagedInferenceEngine(
                cfg, params, slots=slots, page_size=page_size,
                max_queue=64, tenants=table,
                prefill_budget=prefill_budget if slo_on else None,
            ).start())
            gw = GatewayService(
                fleet, router=PrefixAffinityRouter(page_size),
                model_name="bench", max_waiters=2 * slots + 4,
                slo=SloLimiter(table) if table is not None else None)
            rejections = 0
            try:
                fleet.add_replica()
                # warm both shapes (prefill buckets + decode) off-clock
                gw.generate(list(range(1, long_p + 1)),
                            max_new_tokens=2, timeout_s=600)
                gw.generate([2, 3], max_new_tokens=2, timeout_s=600)
                stop = _threading.Event()

                def aggress(tid):
                    nonlocal rejections
                    i = 0
                    while not stop.is_set():
                        prompt = [(tid * 31 + 5 * i + j) % 50 + 1
                                  for j in range(long_p)]
                        try:
                            gw.generate(prompt, max_new_tokens=new_tokens,
                                        timeout_s=600, tenant="agg")
                        except QuotaExceeded as e:
                            rejections += 1
                            time.sleep(min(e.retry_after_s or 0.01, 0.05))
                        except Exception:  # noqa: BLE001 — keep hammering
                            time.sleep(0.01)
                        i += 1

                threads = [_threading.Thread(target=aggress, args=(t,),
                                             daemon=True)
                           for t in range(3)]
                for t in threads:
                    t.start()
                time.sleep(0.3)       # let the burst build
                ttfts = []
                for i in range(n_victim):
                    res = gw.generate([7, i % 40 + 2, 9],
                                      max_new_tokens=new_tokens,
                                      timeout_s=600, tenant="vic")
                    if res.get("ttft_ms") is not None:
                        ttfts.append(res["ttft_ms"])
                    time.sleep(0.01)  # bursty-interactive cadence
                stop.set()
                for t in threads:
                    t.join(timeout=60)
            finally:
                gw.close()
            return ttfts, rejections

        _log(f"slo: two-tenant burst, long prompt {long_p}, "
             f"{n_victim} victim probes, budget {prefill_budget}...")
        on_ttfts, on_rejections = run_side(slo_on=True)
        off_ttfts, _ = run_side(slo_on=False)
        p99_on = _percentile(on_ttfts, 0.99)
        p99_off = _percentile(off_ttfts, 0.99)
        _log(f"slo: victim TTFT p99 {p99_on} ms (SLO on) vs {p99_off} ms "
             f"(off); aggressor rejections {on_rejections}")
        return {"slo_ttft_p99_ms": p99_on,
                "slo_ttft_p99_ms_unprotected": p99_off,
                "slo_victim_ttft_p50_ms": _percentile(on_ttfts, 0.5),
                "slo_aggressor_rejections": on_rejections,
                "slo_prefill_budget": prefill_budget}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"slo skipped: {type(e).__name__}: {e}")
        return {}


def llm_op_pipeline_measurement(jax, cfg, params, *, replicas: int,
                                slots: int, page_size: int,
                                prompt_len: int, new_tokens: int,
                                n_conversations: int, steps: int):
    """Workflow-native inference point: interleaved multi-step
    conversations (``llm.generate → tool op → llm.generate``) driven
    through the WORKFLOW surface against a paged gateway fleet, next to
    the same traffic as raw gateway submits — the surface-cost number —
    and with session affinity on vs round-robin routing — the
    conversation-locality number (aggregate radix prefix hit rate).
    Wrapped so a hiccup never loses the headline metric."""
    try:
        from concurrent import futures as _futures

        from lzy_tpu import Lzy, llm, op
        from lzy_tpu.gateway import (
            GatewayService, PrefixAffinityRouter, ReplicaFleet,
            RoundRobinRouter)
        from lzy_tpu.serving import PagedInferenceEngine
        from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig

        @op
        def extend(g, extra: list) -> list:
            return g.full_tokens() + list(extra)

        base_len = max(page_size, prompt_len - prompt_len % page_size)
        prompts = [list(range(1, base_len + 1)) + [i % 50 + 2]
                   for i in range(n_conversations)]

        def build_gw(router):
            fleet = ReplicaFleet(lambda: PagedInferenceEngine(
                cfg, params, slots=slots, page_size=page_size,
                max_queue=4 * n_conversations))
            gw = GatewayService(fleet, router=router, model_name="bench",
                                max_waiters=replicas * slots + 2)
            for _ in range(replicas):
                fleet.add_replica()
            # warm prefill buckets + decode once, off-clock
            gw.generate(prompts[0], max_new_tokens=2, timeout_s=600)
            return gw, fleet

        def drive_workflow(router, tag):
            """steps rounds of one llm_op per conversation, rounds
            barriered (step N+1 needs step N's output), conversations
            fanning out through the graph executor's concurrency."""
            gw, fleet = build_gw(router)
            try:
                llm.configure(gw)
                reg = DefaultStorageRegistry()
                reg.register_storage(
                    "default",
                    StorageConfig(uri=f"mem://bench-llm-{tag}"),
                    default=True)
                lzy = Lzy(storage_registry=reg)
                convs = [llm.Conversation(f"bench-{tag}-{i}")
                         for i in range(n_conversations)]
                total = 0
                t0 = time.perf_counter()
                with lzy.workflow(f"bench-{tag}") as wf:
                    cur = [list(p) for p in prompts]
                    for s in range(steps):
                        gens = []
                        for i, conv in enumerate(convs):
                            g = llm.generate(
                                cur[i], max_new_tokens=new_tokens,
                                greedy=True, cache=False,
                                conversation=conv, timeout_s=600)
                            gens.append(g)
                            cur[i] = extend(g, [60 + i + s])
                        wf.barrier()
                        total += sum(len(list(g.tokens)) for g in gens)
                dt = time.perf_counter() - t0
                agg = fleet.aggregate()
                hit = (agg["prefix_hit_tokens"]
                       / max(1, agg["prefix_lookup_tokens"]))
                return total / dt, round(hit, 4)
            finally:
                llm.configure(None)
                gw.close()

        def drive_raw():
            """The same conversation traffic as raw gateway submits —
            no workflow graph, no session hint (the pre-llm_op client
            shape)."""
            gw, _fleet = build_gw(PrefixAffinityRouter(page_size))
            try:
                def one_conv(i):
                    cur, n = list(prompts[i]), 0
                    for s in range(steps):
                        res = gw.generate(cur,
                                          max_new_tokens=new_tokens,
                                          timeout_s=600, greedy=True)
                        n += len(res["tokens"])
                        cur = cur + res["tokens"] + [60 + i + s]
                    return n
                t0 = time.perf_counter()
                with _futures.ThreadPoolExecutor(n_conversations) as pool:
                    total = sum(pool.map(one_conv,
                                         range(n_conversations)))
                return total / (time.perf_counter() - t0)
            finally:
                gw.close()

        _log(f"llm_op pipeline: {n_conversations} conversations x "
             f"{steps} steps x {new_tokens} tokens, {replicas} "
             f"replicas...")
        tps_aff, hit_aff = drive_workflow(
            PrefixAffinityRouter(page_size), "aff")
        _tps_rr, hit_rr = drive_workflow(RoundRobinRouter(), "rr")
        tps_raw = drive_raw()
        _log(f"llm_op pipeline: {tps_aff:.1f} tok/s via workflow "
             f"(raw gateway {tps_raw:.1f}); radix hit rate "
             f"{hit_aff} affinity vs {hit_rr} round-robin")
        return {"llm_op_pipeline_tokens_per_s": round(tps_aff, 1),
                "llm_op_raw_gateway_tokens_per_s": round(tps_raw, 1),
                "llm_op_affinity_prefix_hit_rate": hit_aff,
                "llm_op_rr_prefix_hit_rate": hit_rr,
                "llm_op_conversations": n_conversations,
                "llm_op_steps": steps}
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"llm_op pipeline skipped: {type(e).__name__}: {e}")
        return {}


def agent_pipeline_measurement(jax, cfg, params, *, replicas: int,
                               slots: int, page_size: int,
                               prompt_len: int, new_tokens: int,
                               n_conversations: int, steps: int):
    """Workflow-aware scheduling point (lzy_tpu/llm/sched.py): the SAME
    agent-pipeline trace — interleaved ``generate → tool op → generate``
    chains — driven FUSED (KV parked across the tool gap + speculative
    next-step prefill, the default) and UNFUSED (``LZY_WFSCHED_FUSE=0``),
    reporting per-step TTFT past step 1 (where the pin and the
    speculation can pay), pipeline throughput, and the admission fan-in
    plane's dedup numbers (identical in-flight greedy rows reaching the
    fleet as ONE engine request). Runs in the CPU-fallback round with
    scaled-down shapes. Wrapped so a hiccup never loses the headline."""
    try:
        from lzy_tpu import Lzy, llm, op
        from lzy_tpu.gateway import (
            GatewayService, PrefixAffinityRouter, ReplicaFleet)
        from lzy_tpu.serving import PagedInferenceEngine
        from lzy_tpu.storage import DefaultStorageRegistry, StorageConfig

        @op
        def extend(g, extra: list) -> list:
            return g.full_tokens() + list(extra)

        base_len = max(page_size, prompt_len - prompt_len % page_size)
        prompts = [list(range(1, base_len + 1)) + [i % 50 + 2]
                   for i in range(n_conversations)]

        def build_gw():
            fleet = ReplicaFleet(lambda: PagedInferenceEngine(
                cfg, params, slots=slots, page_size=page_size,
                max_queue=4 * n_conversations))
            gw = GatewayService(fleet,
                                router=PrefixAffinityRouter(page_size),
                                model_name="bench",
                                max_waiters=replicas * slots + 2)
            for _ in range(replicas):
                fleet.add_replica()
            # warm prefill buckets + decode once, off-clock
            gw.generate(prompts[0], max_new_tokens=2, timeout_s=600)
            return gw, fleet

        def lzy_for(tag):
            reg = DefaultStorageRegistry()
            reg.register_storage(
                "default", StorageConfig(uri=f"mem://bench-agent-{tag}"),
                default=True)
            return Lzy(storage_registry=reg)

        def drive(tag, fused):
            """The pipeline trace once; returns (tok/s, mean TTFT of
            steps >= 2, scheduler stats)."""
            saved = {k: os.environ.get(k)
                     for k in ("LZY_WFSCHED_FUSE", "LZY_WFSCHED_SPECULATE")}
            if not fused:
                os.environ["LZY_WFSCHED_FUSE"] = "0"
                os.environ["LZY_WFSCHED_SPECULATE"] = "0"
            gw, fleet = build_gw()
            try:
                llm.configure(gw)      # scheduler reads the flags here
                lzy = lzy_for(tag)
                convs = [llm.Conversation(f"agent-{tag}-{i}")
                         for i in range(n_conversations)]
                step_ttft, total = [], 0
                t0 = time.perf_counter()
                with lzy.workflow(f"agent-{tag}") as wf:
                    cur = [list(p) for p in prompts]
                    for s in range(steps):
                        gens = []
                        for i, conv in enumerate(convs):
                            g = llm.generate(
                                cur[i], max_new_tokens=new_tokens,
                                greedy=True, cache=False,
                                conversation=conv, timeout_s=600)
                            gens.append(g)
                            cur[i] = extend(g, [60 + i + s])
                        wf.barrier()
                        if s >= 1:     # step 1 has no pin either way
                            step_ttft += [g.ttft_ms for g in gens
                                          if g.ttft_ms is not None]
                        total += sum(len(list(g.tokens)) for g in gens)
                dt = time.perf_counter() - t0
                sched = llm.current_scheduler()
                stats = sched.stats() if sched is not None else {}
                ttft = (sum(step_ttft) / len(step_ttft)
                        if step_ttft else None)
                return total / dt, ttft, stats
            finally:
                llm.configure(None)
                gw.close()
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v

        _log(f"agent pipeline: {n_conversations} chains x {steps} steps "
             f"x {new_tokens} tokens, {replicas} replicas, fused vs "
             f"unfused...")
        tps_fused, ttft_fused, fstats = drive("fused", True)
        tps_plain, ttft_plain, _ = drive("plain", False)

        # the fan-in plane: identical in-flight greedy rows must reach
        # the fleet as exactly ONE engine request
        gw, fleet = build_gw()
        try:
            llm.configure(gw)
            lzy = lzy_for("fanin")
            n_rows = max(4, n_conversations)
            base = gw.stats()["requests_finished"]
            with lzy.workflow("agent-fanin"):
                outs = llm.generate_batch(
                    [list(prompts[0])] * n_rows,
                    max_new_tokens=new_tokens, greedy=True,
                    cache=False, timeout_s=600)
            n_rows = len(list(outs))
            fanin_requests = gw.stats()["requests_finished"] - base
            sched = llm.current_scheduler()
            dedup_hits = (sched.stats()["dedup_hits"]
                          if sched is not None else 0)
        finally:
            llm.configure(None)
            gw.close()

        _log(f"agent pipeline: fused {tps_fused:.1f} tok/s, step TTFT "
             f"{ttft_fused} ms (unfused {tps_plain:.1f} tok/s, "
             f"{ttft_plain} ms); parks {fstats.get('parks', 0)}, "
             f"speculations {fstats.get('speculations', 0)}; fan-in "
             f"{n_rows} rows -> {fanin_requests} engine requests "
             f"({dedup_hits} dedup hits)")
        out = {"agent_pipeline_fused_tokens_per_s": round(tps_fused, 1),
               "agent_pipeline_unfused_tokens_per_s": round(tps_plain, 1),
               "agent_pipeline_fused_parks": fstats.get("parks", 0),
               "agent_pipeline_fused_speculations":
                   fstats.get("speculations", 0),
               "agent_pipeline_fanin_rows": n_rows,
               "agent_pipeline_fanin_engine_requests": fanin_requests,
               "agent_pipeline_dedup_hits": dedup_hits}
        if ttft_fused is not None:
            out["agent_pipeline_fused_step_ttft_ms"] = round(ttft_fused, 3)
        if ttft_plain is not None:
            out["agent_pipeline_unfused_step_ttft_ms"] = \
                round(ttft_plain, 3)
        return out
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"agent pipeline skipped: {type(e).__name__}: {e}")
        return {}


def stream_measurement(jax, cfg, params, *, slots: int, prompt_len: int,
                       new_tokens: int):
    """Best-effort streaming-delivery point (docs/serving.md "Streaming
    delivery"): TTFT (open → first frame) and inter-token p99 over the
    chunked long-poll surface (``serving/streams``) — the exact
    open/poll/ack path ``InferStream`` serves over gRPC, minus the wire,
    so the number isolates the session layer's delivery cadence next to
    the engine's own decode rate. Rides the CPU-fallback path like
    every serving probe."""
    try:
        import numpy as np

        from lzy_tpu.serving import InferenceEngine
        from lzy_tpu.service.inference import InferenceService

        engine = InferenceEngine(cfg, params, slots=slots).start()
        svc = InferenceService(engine, model_name="bench")
        try:
            rng = np.random.default_rng(3)
            prompt = [int(t) for t in rng.integers(
                1, cfg.vocab_size, prompt_len)]
            _log("stream: warming the decode path...")
            svc.generate(prompt, max_new_tokens=4, greedy=True,
                         timeout_s=600)
            _log(f"stream: timing long-poll delivery of {new_tokens} "
                 f"tokens...")
            t_open = time.perf_counter()
            opened = svc.streams.open(prompt, max_new_tokens=new_tokens,
                                      greedy=True, timeout_s=600)
            rid = opened["request_id"]
            arrivals = []
            pos = 0
            ttft = None
            while True:
                frame = svc.streams.poll(rid, pos, wait_s=0.5)
                now = time.perf_counter()
                n = len(frame["tokens"])
                if n and ttft is None:
                    ttft = now - t_open
                arrivals.extend([now] * n)
                pos += n
                if frame["done"]:
                    break
            gaps = (np.diff(np.asarray(arrivals))
                    if len(arrivals) > 1 else np.asarray([0.0]))
            p99 = float(np.quantile(gaps, 0.99))
            _log(f"stream: ttft {1000 * (ttft or 0):.1f} ms, "
                 f"inter-token p99 {1000 * p99:.2f} ms over {pos} "
                 f"tokens")
            return {
                "stream_ttft_ms": round(1000 * (ttft or 0.0), 3),
                "stream_inter_token_p99_ms": round(1000 * p99, 3),
                "stream_tokens": pos,
            }
        finally:
            svc.close()
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"stream skipped: {type(e).__name__}: {e}")
        return {}


def gateway_restart_measurement(jax, cfg, params, *, replicas: int,
                                slots: int, prompt_len: int,
                                new_tokens: int):
    """Best-effort control-plane recovery point (docs/serving.md
    "Control-plane recovery"): kill a journal-backed gateway mid-stream,
    recover a successor (lease re-adoption + fence resubmission), and
    time kill → FIRST post-restart token at the fence — the
    client-visible blackout of a gateway death. Also checks the resumed
    stream is byte-identical to the pre-kill prefix + an uninterrupted
    continuation (greedy), so the number is only reported for a CORRECT
    recovery. Rides the CPU-fallback path like every serving probe."""
    try:
        import numpy as np

        from lzy_tpu.durable.store import OperationStore
        from lzy_tpu.gateway import (
            GatewayJournal, GatewayService, PrefixAffinityRouter,
            ReplicaFleet, recover_gateway, simulate_gateway_death)
        from lzy_tpu.serving import InferenceEngine

        _log(f"gwreco: building {replicas} journal-backed replicas...")
        journal = GatewayJournal(OperationStore(":memory:"))

        def factory():
            return InferenceEngine(cfg, params, slots=slots)

        fleet = ReplicaFleet(factory)
        gw = GatewayService(fleet, router=PrefixAffinityRouter(8),
                            model_name="bench", journal=journal)
        for _ in range(replicas):
            fleet.add_replica()
        rng = np.random.default_rng(5)
        prompt = [int(t) for t in rng.integers(1, cfg.vocab_size,
                                               prompt_len)]
        # warm the decode path so the timed window measures RECOVERY,
        # not a first-compile
        gw.generate(prompt, max_new_tokens=2, greedy=True,
                    timeout_s=600)
        opened = gw.streams.open(prompt, max_new_tokens=new_tokens,
                                 greedy=True, timeout_s=600)
        rid = opened["request_id"]
        pos, seen = 0, []
        deadline = time.perf_counter() + 300
        # fast short polls: the kill must land MID-decode, before the
        # tiny bench model races through the whole budget
        while len(seen) < 2 and time.perf_counter() < deadline:
            frame = gw.streams.poll(rid, pos, wait_s=0.02)
            seen.extend(frame["tokens"])
            pos += len(frame["tokens"])
            if frame["done"]:
                break
        if pos >= new_tokens:
            _log("gwreco skipped: generation finished before the kill "
                 "(model too fast for a mid-decode death)")
            gw.close()
            return {}
        _log(f"gwreco: killing the gateway at fence {pos}...")
        engines = {r.id: r.engine for r in fleet.replicas()}
        t_kill = time.perf_counter()
        simulate_gateway_death(gw)
        fleet2 = ReplicaFleet(factory)
        gw2 = GatewayService(fleet2, router=PrefixAffinityRouter(8),
                             model_name="bench", journal=journal)
        report = recover_gateway(
            gw2, engine_source=lambda r, vms: engines.get(r))
        # first post-restart token AT THE FENCE via the original token
        first_token_ms = None
        final = list(seen)
        while time.perf_counter() < deadline:
            frame = gw2.streams.poll(rid, pos, wait_s=1.0)
            if frame["tokens"] and first_token_ms is None:
                first_token_ms = 1000 * (time.perf_counter() - t_kill)
            final.extend(frame["tokens"])
            pos += len(frame["tokens"])
            if frame["done"]:
                break
        gw2.close()
        if first_token_ms is None or len(final) != new_tokens:
            _log("gwreco skipped: the resumed stream never finished")
            return {}
        if final[:len(seen)] != seen:
            _log("gwreco skipped: fence divergence (NOT reporting a "
                 "broken recovery as a latency number)")
            return {}
        _log(f"gwreco: kill -> first post-restart token "
             f"{first_token_ms:.1f} ms ({len(report.adopted)} adopted, "
             f"{len(report.resubmitted)} resubmitted, recovery "
             f"{1000 * report.recovery_s:.1f} ms)")
        return {
            "gateway_restart_recovery_ms": round(first_token_ms, 3),
            "gateway_restart_adopted": len(report.adopted),
            "gateway_restart_recovery_internal_ms": round(
                1000 * report.recovery_s, 3),
        }
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"gwreco skipped: {type(e).__name__}: {e}")
        return {}


def capacity_curve_measurement():
    """Best-effort operating-curve point (docs/serving.md "Capacity &
    load testing"): the lzy_tpu/load virtual-clock harness replays a
    synthetic multi-tenant trace against fleet-in-threads SimEngine
    gateways and reports TTFT/inter-token p99 vs replica count plus a
    shed-rate frontier — the capacity-model numbers ROADMAP item 3 asks
    bench rounds to publish. Pure CPU + virtual time (no accelerator,
    no model), so it rides the CPU-fallback path unchanged; the replay
    speedup factor (virtual seconds per wall second) is the honesty
    metric that these are simulated hours, not wall hours."""
    try:
        from lzy_tpu.load import (
            FleetConfig, SimProfile, TraceConfig, capacity_artifact)

        _log("capacity: replaying synthetic traces on the virtual "
             "clock (replicas 1/2/4 + overload frontier)...")
        trace = TraceConfig(seed=0, duration_s=480.0, users=24,
                            tenants=8)
        fleet = FleetConfig(replicas=2, profile=SimProfile(
            slots=8, max_queue=48, kv_blocks=384))
        frontier_fleet = FleetConfig(replicas=1, retry_limit=3,
                                     profile=SimProfile(
                                         slots=4, max_queue=16,
                                         kv_blocks=160))
        art = capacity_artifact(trace, fleet, replica_counts=[1, 2, 4],
                                load_factors=[1.0, 5.0],
                                frontier_fleet_cfg=frontier_fleet)
        slo = {str(r["replicas"]): {
            "ttft_p50_ms": r["ttft_p50_ms"],
            "ttft_p99_ms": r["ttft_p99_ms"],
            "itl_p99_ms": r["itl_p99_ms"],
            "requests": r["requests"],
        } for r in art["slo_curve"]}
        frontier = {str(r["load_factor"]): {
            "shed_rate": r["shed_rate"],
            "ttft_p99_ms": r["ttft_p99_ms"],
            "peak_queue_depth": r["peak_queue_depth"],
        } for r in art["shed_frontier"]}
        rep = art["replay"]
        _log(f"capacity: {rep['virtual_s']:.0f} virtual s in "
             f"{rep['wall_s']:.1f}s wall ({rep['speedup_x']:.0f}x); "
             f"ttft p99 by replicas: "
             + ", ".join(f"{k}: {v['ttft_p99_ms']:.0f}ms"
                         for k, v in sorted(slo.items())))
        return {
            "capacity_slo_curve": slo,
            "capacity_shed_frontier": frontier,
            "capacity_virtual_s": rep["virtual_s"],
            "capacity_replay_speedup_x": rep["speedup_x"],
            "capacity_virtual_hours_per_wall_s":
                rep["virtual_hours_per_wall_s"],
        }
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"capacity skipped: {type(e).__name__}: {e}")
        return {}


def step_breakdown(jax, loss_fn, params, batch, step_ms: float, n: int = 5):
    """Best-effort fwd/bwd/opt decomposition of the step time.

    Times a jitted forward (loss only) and a jitted value_and_grad; the
    optimizer share is the remainder of the full step. Two extra compiles —
    wrapped so a backend hiccup here never loses the headline metric.
    Caller must have freed the optimizer moments: params + grads + the
    bwd activations only fit in HBM without them.
    """
    try:
        _log("breakdown: timing fwd-only...")

        def timed(fn, *args):
            fn(*args)  # compile + first-run cost
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn(*args)
            jax.block_until_ready(out)
            return 1000 * (time.perf_counter() - t0) / n

        fwd_ms = timed(jax.jit(loss_fn), params, batch)
        _log("breakdown: timing fwd+bwd...")
        grad_ms = timed(jax.jit(jax.value_and_grad(loss_fn)), params, batch)
        return {
            "fwd_ms": round(fwd_ms, 2),
            "bwd_ms": round(max(grad_ms - fwd_ms, 0.0), 2),
            "opt_ms": round(max(step_ms - grad_ms, 0.0), 2),
        }
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"breakdown skipped: {type(e).__name__}: {e}")
        return {}


if __name__ == "__main__":
    run()

"""Autoregressive generation with a KV cache.

The decode path keeps per-layer key/value caches in HBM (flax ``cache``
collection) so each new token costs O(L) attention reads instead of re-running
the full prefix — the standard TPU decode shape (one jitted single-token step,
cache updated in place via donated buffers).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from lzy_tpu.models.llama import Llama, LlamaConfig


def sample_token(logits: jax.Array, temperature: float, rng: jax.Array,
                 *, top_k: Optional[int] = None,
                 top_p: Optional[float] = None):
    """Shared sampling for every model family's decode loop; logits [B, V] →
    ([B] int32, rng): one split of ``rng`` (greedy too, so that the draw
    order never depends on the mode), then :func:`draw_token` with the
    half that is spent."""
    rng, sub = jax.random.split(rng)
    return draw_token(logits, temperature, sub, top_k=top_k, top_p=top_p), rng


def draw_token(logits: jax.Array, temperature: float, key: jax.Array,
               *, top_k: Optional[int] = None,
               top_p: Optional[float] = None) -> jax.Array:
    """One draw from ``key`` (spent here, not split); logits [B, V] → [B]
    int32. ``temperature<=0`` is greedy; ``top_k`` keeps the k
    highest logits (``<=0`` disables the filter, the common sentinel
    convention); ``top_p`` keeps the smallest nucleus whose probability
    mass reaches p (both filters compose: k first, then p)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None and 0 < top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # the cutoff logit: smallest prefix with mass >= p always keeps the
        # top token (cum >= p is first true AT the token that crosses p)
        crossed = cum >= top_p
        idx = jnp.argmax(crossed, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, idx[..., None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def decode_config(cfg: LlamaConfig, **overrides) -> LlamaConfig:
    """The decode-mode variant of a train config: KV-cache decoding with
    every training-only feature cleared (remat, flash/ring/ulysses
    attention — none apply to single-position steps against a cache).
    The one place this set lives; generate, pp_generate, the serving
    engine, and bench all derive from it."""
    return dataclasses.replace(
        cfg, decode=True, remat=False, use_flash_kernel=False,
        use_ring_attention=False, use_ulysses_attention=False, **overrides)


def init_cache(init_fn):
    """Materialize a model's zeroed KV cache from an abstract init:
    ``init_fn`` is a zero-arg lambda running ``model.init(...)``; eval_shape
    keeps it abstract so no second weight copy ever exists."""
    cache_shapes = jax.eval_shape(init_fn)["cache"]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes)


#: padded prefill widths — prompts are fed through the model in chunks of
#: these shapes, so the number of compiled prefill programs is bounded by
#: the bucket count instead of growing with every distinct prompt length
PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256)


def prefill_width(budget: Optional[int] = None,
                  widest: Optional[int] = None) -> int:
    """The width of a prefill program: the widest bucket that is over
    neither ``budget`` (the prompt tokens a scheduling round may spend;
    None: no bound) nor ``widest`` (what the model says its kernels take:
    ``models/serving.py``). A program costs one read of the weights whatever
    its width up to the chip's balance of arithmetic to bytes (a couple of
    hundred tokens in bfloat16), so a round's budget is spent as one wide
    program, not as several narrow ones. A bound under the smallest bucket
    gives the smallest bucket."""
    bound = min(b for b in (budget, widest, PREFILL_BUCKETS[-1])
                if b is not None)
    return max((w for w in PREFILL_BUCKETS if w <= bound),
               default=PREFILL_BUCKETS[0])


def prefill_plan(t0: int, chunk: int, max_seq_len: int):
    """Chunk schedule for a ``t0``-token prompt: list of
    ``(start, take, width)`` where ``take`` real tokens starting at
    ``start`` run as one forward pass padded to ``width`` (the smallest
    bucket that fits, capped so the padded write never spills past
    ``max_seq_len`` — ``dynamic_update_slice`` would clamp the start and
    overwrite real cache rows). At most ``ceil(t0/chunk)`` passes.

    Only the last chunk is padded, and by less than half its width (the
    buckets double), whatever ``chunk`` is. The tail is NOT cut into
    descending buckets (144 -> 128 + 16): every further program reads the
    weights again, and timed on the chip that costs as much as the pad or
    more at every tail (Mistral-7B at 16 layers on a v5e: a tail padded to
    256 takes 26.5 ms, as 128 + 16 26.8 ms; padded to 128 14.1 ms, as
    64 + 8 25.4 ms: PERF.md section 6, PR 32), with a dispatch more."""
    chunk = max(1, chunk)
    widths = sorted({w for w in PREFILL_BUCKETS if w <= chunk} | {chunk})
    plan = []
    start = 0
    while start < t0:
        take = min(chunk, t0 - start)
        width = next(w for w in widths if w >= take)
        plan.append((start, take, min(width, max_seq_len - start)))
        start += take
    return plan


def _set_cache_index(cache, value: int):
    """Rewrite every ``index`` leaf of a KV-cache tree to ``value`` (host
    side, between jitted calls). Needed after a PADDED prefill chunk: the
    model advanced the index by the padded width, but decoding must resume
    at the true prompt length — the pad slots hold garbage K/V that each
    subsequent decode step overwrites before its mask can see them."""
    def fix(path, leaf):
        if any(getattr(p, "key", None) == "index" for p in path):
            return jnp.full(leaf.shape, value, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def make_prefill_step(model):
    """One jitted prefill pass: run a whole ``[B, W]`` token chunk through
    the decode-mode model (the cache write and causal masking live in
    ``Attention._decode_step``), returning the updated cache and the logits
    at ``last_idx`` (the final REAL position — pad logits are garbage)."""
    @functools.partial(jax.jit, donate_argnums=(0,))
    def prefill_step(cache, params, tokens, last_idx):
        logits, updated = model.apply(
            {"params": params, "cache": cache}, tokens, mutable=["cache"]
        )
        last = jax.lax.dynamic_index_in_dim(
            logits, last_idx, axis=1, keepdims=False)
        return updated["cache"], last

    return prefill_step


def batched_prefill(model, cache, params, prompt, *, chunk: int = 64,
                    max_seq_len: int, prefill_step=None):
    """Write a whole prompt ``[B, T0]`` into the KV cache in
    ``ceil(T0/chunk)`` forward passes (vs T0 sequential single-token device
    calls) over at most ``len(PREFILL_BUCKETS)+1`` compiled shapes.
    Returns ``(cache, last_logits)`` with ``last_logits`` taken at the
    prompt's final position. Pass a shared ``prefill_step`` (from
    :func:`make_prefill_step`) to reuse its jit cache across calls — the
    serving engine does; ``generate`` builds a throwaway one."""
    b, t0 = prompt.shape
    if prefill_step is None:
        prefill_step = make_prefill_step(model)
    last = None
    plan = prefill_plan(t0, chunk, max_seq_len)
    for start, take, width in plan:
        tokens = prompt[:, start:start + take]
        if width != take:
            tokens = jnp.pad(tokens, ((0, 0), (0, width - take)))
        cache, last = prefill_step(
            cache, params, tokens, jnp.asarray(take - 1, jnp.int32))
    _, last_take, last_width = plan[-1]
    if last_take != last_width:
        # final chunk was padded: rewind the index to the true length
        cache = _set_cache_index(cache, t0)
    return cache, last


def _advance_rng(rng: jax.Array, n: int) -> jax.Array:
    """The rng stream after ``n`` sample-and-discard calls — batched prefill
    skips the per-prompt-token sampling the sequential path does, but must
    land on the SAME key so sampled continuations are bit-identical between
    the two paths (each ``sample_token`` call advances via one split)."""
    if n <= 0:
        return rng
    return jax.lax.fori_loop(
        0, n, lambda _, r: jax.random.split(r)[0], rng)


def generate(
    cfg: LlamaConfig,
    params: Any,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    eos_token: Optional[int] = None,
    prefill: str = "batched",
    prefill_chunk: int = 64,
    eos_check_every: int = 8,
) -> jax.Array:
    """Greedy (``temperature=0``) or sampled continuation of ``prompt``
    (``[B, T0]`` int32). Returns ``[B, T0 + max_new_tokens]`` (positions after
    an ``eos_token`` keep repeating it).

    ``prefill="batched"`` (default) runs the prompt through the model in
    ``ceil(T0/prefill_chunk)`` causal-masked forward passes over a bounded
    set of padded shapes (:data:`PREFILL_BUCKETS`); ``"sequential"`` keeps
    the original one-device-call-per-token loop as the reference oracle —
    both produce identical tokens (the batched path advances the sampling
    rng in lockstep with the oracle's per-token sample-and-discard).

    With ``eos_token`` set, the decode loop syncs ``done`` to the host
    every ``eos_check_every`` steps and exits early once every sequence
    has finished, padding the remainder with ``eos_token`` (identical
    output, without burning ``max_new_tokens`` device calls on it).
    """
    b, t0 = prompt.shape
    if t0 + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({t0}) + new tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len})"
        )
    if prefill not in ("batched", "sequential"):
        raise ValueError(
            f"prefill must be 'batched' or 'sequential', got {prefill!r}")
    dcfg = decode_config(cfg)
    model = Llama(dcfg)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    cache = init_cache(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((b, 1), jnp.int32))
    )

    # params are an ARGUMENT (not a closure constant): no baked-in weight copy
    # in the executable, no recompile per weight set; the cache is donated
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(cache, params, token, rng):
        logits, updated = model.apply(
            {"params": params, "cache": cache}, token, mutable=["cache"]
        )
        nxt, rng = sample_token(logits[:, -1], temperature, rng,
                                top_k=top_k, top_p=top_p)
        return updated["cache"], nxt, rng

    if prefill == "sequential":
        # reference oracle: one jitted device call per prompt position
        cur = None
        for t in range(t0):
            cache, cur, rng = step(cache, params, prompt[:, t:t + 1], rng)
    else:
        cache, last_logits = batched_prefill(
            model, cache, params, prompt, chunk=prefill_chunk,
            max_seq_len=cfg.max_seq_len)
        rng = _advance_rng(rng, t0 - 1)
        cur, rng = sample_token(last_logits, temperature, rng,
                                top_k=top_k, top_p=top_p)

    tokens = [prompt]
    done = jnp.zeros((b,), bool)
    for n in range(max_new_tokens):
        if eos_token is not None:
            cur = jnp.where(done, eos_token, cur)
            done = done | (cur == eos_token)
        tokens.append(cur[:, None])
        emitted = n + 1
        if emitted == max_new_tokens:
            break  # the last emitted token needs no further model step
        if (eos_token is not None and eos_check_every > 0
                and emitted % eos_check_every == 0 and bool(done.all())):
            # every sequence has hit eos: the remaining positions are all
            # eos by construction — emit them without any device calls
            tokens.append(jnp.full(
                (b, max_new_tokens - emitted), eos_token, prompt.dtype))
            break
        cache, cur, rng = step(cache, params, cur[:, None], rng)
    return jnp.concatenate(tokens, axis=1)


def pp_generate(
    cfg: LlamaConfig,
    params: Any,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    mesh,
    axis: str = "pp",
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    eos_token: Optional[int] = None,
) -> jax.Array:
    """Decode DIRECTLY from pipeline-staged params — no ``unstack_pp_params``
    dense-tree materialization: each pp rank holds only its stage's weights
    and KV cache, and the token's hidden state rides a ``ppermute`` ring of
    stage applications (sequential per token — the memory shape of pipelined
    decode, not token-level pipelining). Matches the dense ``generate``
    token-for-token (same rng discipline), incl. sampling and ``eos_token``.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from lzy_tpu.models.llama import (
        LlamaStage, RMSNorm, _check_pp_config)

    # decode=True is this function's own business — normalize before the
    # training-entry validator so callers who set it aren't bounced with
    # advice to call the function they are already calling
    cfg = dataclasses.replace(cfg, decode=False)
    k = _check_pp_config(cfg)
    n = mesh.shape[axis]
    if n != cfg.pp_stages:
        raise ValueError(f"mesh {axis}={n} != pp_stages={cfg.pp_stages}")
    b, t0 = prompt.shape
    if t0 + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({t0}) + new tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len})")
    dcfg = decode_config(cfg, pp_stages=0)
    stage = LlamaStage(dcfg, k)
    cache_shapes = jax.eval_shape(
        lambda: stage.init(jax.random.PRNGKey(0),
                           jnp.zeros((b, 1, cfg.d_model), dcfg.dtype),
                           jnp.zeros((b, 1), jnp.int32))["cache"])
    # jnp-coerce the closed-over leaves: callers legitimately pass
    # device_get'd (numpy) trees, and numpy_array[tracer] indexing inside
    # the scan would fail with a TracerArrayConversionError
    embed = jnp.asarray(params["embed_tokens"])
    head = embed if cfg.tie_embeddings else jnp.asarray(params["lm_head"])
    norm_params = jax.tree_util.tree_map(jnp.asarray, params["final_norm"])
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def local(stages_local, prompt_tokens, rng):
        sp = jax.tree_util.tree_map(lambda a: a[0], stages_local)
        rank = lax.axis_index(axis)
        zv = rank * 0
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype) + zv.astype(s.dtype),
            cache_shapes)
        pos0 = jnp.zeros((b, 1), jnp.int32)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def ring_token(cache, tok):
            """One token through all stages; returns last-position logits."""
            h = embed.astype(dcfg.dtype)[tok] + zv.astype(dcfg.dtype)

            def tick(carry, j):
                h, cache = carry

                def run(h, cache):
                    y, upd = stage.apply({"params": sp, "cache": cache}, h,
                                         pos0, mutable=["cache"])
                    return y, upd["cache"]

                # per-device predicate inside the manual region: only the
                # active stage pays the weight + KV-cache sweep (decode is
                # HBM-bound; apply-everywhere-and-select would multiply
                # that traffic by the stage count)
                h, cache = lax.cond(rank == j, run,
                                    lambda h, cache: (h, cache), h, cache)
                return (lax.ppermute(h, axis, perm), cache), None

            (h, cache), _ = lax.scan(tick, (h, cache), jnp.arange(n))
            # after n hops the final stage's output has rotated onto rank 0;
            # a psum of the masked value replicates it (and is f32 — the
            # XLA:CPU AllReducePromotion constraint, see parallel/pipeline)
            final = lax.psum(
                jnp.where(rank == 0, h.astype(jnp.float32), 0.0), axis)
            # EXACTLY the dense model's tail dtypes (norm and head in
            # cfg.dtype, f32 accumulation) — bit-identical logits are what
            # make the sampled path match the dense generate token-for-token
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype).apply(
                {"params": norm_params}, final.astype(dcfg.dtype))
            logits = jnp.einsum(
                "bte,ve->btv", x.astype(dcfg.dtype),
                head.astype(dcfg.dtype),
                preferred_element_type=jnp.float32)
            return cache, logits[:, -1]

        # prefill mirrors the dense generate exactly (it samples-and-
        # discards per prompt token, keeping the rng stream in lockstep so
        # sampled outputs are bit-identical between the two paths)
        def prefill_step(carry, t):
            cache, rng = carry
            cache, logits = ring_token(
                cache, lax.dynamic_slice_in_dim(prompt_tokens, t, 1, axis=1))
            nxt, rng = sample_token(logits, temperature, rng,
                                    top_k=top_k, top_p=top_p)
            return (cache, rng), nxt

        (cache, rng), sampled = lax.scan(
            prefill_step, (cache, rng), jnp.arange(t0))
        cur = sampled[-1]

        def decode_step(carry, _):
            cache, cur, rng, done = carry
            if eos_token is not None:
                cur = jnp.where(done, eos_token, cur)
                done = done | (cur == eos_token)
            emitted = cur
            cache, logits = ring_token(cache, cur[:, None])
            nxt, rng = sample_token(logits, temperature, rng,
                                    top_k=top_k, top_p=top_p)
            return (cache, nxt, rng, done), emitted

        done0 = jnp.zeros((b,), bool)
        (_, _, _, _), toks = lax.scan(
            decode_step, (cache, cur, rng, done0), None,
            length=max_new_tokens)
        return jnp.transpose(toks, (1, 0))       # [B, max_new_tokens]

    stacked_specs = jax.tree_util.tree_map(
        lambda _: P(axis), params["stages"])
    new_tokens = jax.shard_map(
        local, mesh=mesh, in_specs=(stacked_specs, P(), P()),
        out_specs=P(), axis_names={axis},
    )(params["stages"], prompt, rng)
    return jnp.concatenate([prompt, new_tokens.astype(prompt.dtype)], axis=1)

"""Draft-free speculative decoding: n-gram prompt-lookup proposals.

Every engine in the serving stack emits one token per decode step, so
decode throughput is bounded by per-step latency — exactly the wrong
trade on accelerator hardware, where a k-token verify forward costs
barely more than a 1-token step (the KV sweep dominates both). Classic
speculative decoding fixes that with a second, smaller draft model; this
module is the **draft-free** variant (prompt-lookup decoding): the draft
IS the request's own token history.

- :class:`NgramProposer` — longest-suffix n-gram match over
  ``prompt + emitted`` tokens. If the last *n* tokens occurred earlier in
  the sequence, whatever followed that earlier occurrence is proposed as
  the continuation (up to ``gamma`` tokens). Repetitive/structured
  outputs — code, extraction, long-context summarization quoting its
  source — hit constantly; free-form prose rarely matches and simply
  degrades to normal one-token decode.
- The engines (``serving/engine.py``) batch the proposals into ONE
  multi-position verify forward (``[B, gamma+1]`` query positions against
  the live cache — the same chunked decode path batched prefill uses,
  padded to a fixed width so there is exactly one extra compiled
  program), then accept the longest prefix where the proposal matches the
  model's own argmax and roll back everything after it.

Acceptance is **exact-match against the target model's own argmax**, so
greedy output is bit-identical to non-speculative decode and to the
``generate()`` oracle by construction: a token is only ever emitted if
the model itself would have produced it. There is no distribution to
correct (the rejection-sampling machinery of two-model speculation) and
no second set of weights in HBM. Speculation applies to greedy rows
only; sampled rows in the same batch decode one token per step exactly
as before, from the same rng draw order.

Proposed/accepted tokens, verify rounds, the cumulative acceptance rate
and the mean tokens-per-decode-step are exported via
``lzy_tpu.utils.metrics.REGISTRY`` (``lzy_spec_*``) and surfaced through
``InferStats``/``InferFleetStats``.
"""

from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp

from lzy_tpu.utils.metrics import REGISTRY

PROPOSED = REGISTRY.counter(
    "lzy_spec_proposed_tokens_total",
    "speculative tokens proposed by prompt lookup")
ACCEPTED = REGISTRY.counter(
    "lzy_spec_accepted_tokens_total",
    "proposed tokens accepted (matched the model's own argmax)")
VERIFY_STEPS = REGISTRY.counter(
    "lzy_spec_verify_steps_total",
    "multi-position verify forwards (vs one-token decode steps)")
ACCEPT_RATE = REGISTRY.gauge(
    "lzy_spec_acceptance_rate",
    "cumulative accepted / proposed speculative tokens")
DRAFT_TRUNCATED = REGISTRY.counter(
    "lzy_spec_draft_truncated_total",
    "speculative drafts cut short because the KV pool's free list could "
    "not back every proposed position (NoFreeBlocks — speculation never "
    "evicts cached blocks or preempts for a draft)")
TOKENS_PER_STEP = REGISTRY.gauge(
    "lzy_spec_tokens_per_step",
    "mean generated tokens per decode step (1.0 = no speculation win)")


def accept(prop, prop_len, greedy, nxt, pos):
    """On-device speculative acceptance (traced inside verify_step).

    Per row: the longest proposal prefix matching the model's own
    argmax (``m``), the accepted tokens plus the bonus token after
    them for speculating rows, or the single position-0 pick for
    sampled/no-draft rows — bit-identical to the host loop it
    replaces (``m`` via cumprod-of-matches is exactly the while-loop
    prefix walk). Returns ``(packed [B, gamma+2], new_cur [B],
    new_pos [B])`` where ``packed[:, :gamma+1]`` are emit tokens,
    ``packed[:, gamma+1]`` the per-row emit count — ONE array, ONE
    host transfer for the whole round."""
    width = prop.shape[1] + 1            # gamma + 1
    cols = jnp.arange(width - 1, dtype=jnp.int32)
    ok = (prop == greedy[:, :-1]) & (cols[None, :] < prop_len[:, None])
    m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    spec = prop_len > 0                  # rows with a live draft
    bonus = jnp.take_along_axis(greedy, m[:, None], axis=1)[:, 0]
    allc = jnp.arange(width, dtype=jnp.int32)
    prop_w = jnp.pad(prop, ((0, 0), (0, 1)))
    emit = jnp.where(allc[None, :] < m[:, None], prop_w,
                     jnp.where(allc[None, :] == m[:, None],
                               bonus[:, None], 0))
    # non-speculating rows emit exactly the position-0 pick (sampled
    # rows keep their draw; greedy no-draft rows get argmax — which
    # equals the m=0 bonus, so the where is a no-op for them)
    emit = emit.at[:, 0].set(jnp.where(spec, emit[:, 0], nxt))
    count = jnp.where(spec, m + 1, 1).astype(jnp.int32)
    new_cur = jnp.take_along_axis(emit, (count - 1)[:, None],
                                  axis=1)[:, 0]
    packed = jnp.concatenate([emit, count[:, None]], axis=1)
    # rows advance by exactly what they emit — the rollback the host
    # used to do by rewriting index leaves after the fact is now the
    # step's own output, exact by construction
    return packed, new_cur, pos + count


class NgramProposer:
    """Prompt-lookup draft: propose the continuation of the most recent
    earlier occurrence of the current suffix n-gram.

    For ``n`` from ``max_ngram`` down to ``min_ngram``, the last ``n``
    tokens of the sequence are searched for their most recent earlier
    occurrence whose continuation window is FULL (else the longest
    window seen); on a hit, up to ``gamma`` tokens following it are
    proposed. No hit at any ``n`` proposes nothing (the row decodes one
    token as usual). Recency keeps the draft in the current local
    context; the full-window preference matters on a repeating tail (the
    canonical hit: a constant or short-cycle run), where the nearest
    occurrences overlap the suffix and offer only 1-2 continuation
    tokens — a slightly older occurrence of the same cycle proposes the
    whole gamma window, which is what turns a run into gamma+1 tokens
    per step.

    Two entry points with identical results: :meth:`propose` is the
    stateless one-shot scan (tests, offline scoring); :meth:`index`
    returns a per-request :class:`NgramIndex` the engines keep per slot
    — positions are indexed once and extended per emitted token, so a
    proposal is O(occurrences-of-suffix), not O(history), and a 4k-token
    free-form history that never matches costs a dict miss instead of a
    full rescan every decode round.
    """

    def __init__(self, max_ngram: int = 3, gamma: int = 4,
                 min_ngram: int = 1):
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"{min_ngram}..{max_ngram}")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.gamma = gamma

    def propose(self, tokens: Sequence[int]) -> List[int]:
        """Up to ``gamma`` predicted continuation tokens of ``tokens``
        (the row's ``prompt + emitted`` history); ``[]`` when no suffix
        n-gram recurs earlier in the history. One-shot: builds a
        throwaway index — use :meth:`index` on a hot path."""
        return self.index(tokens).propose()

    def index(self, tokens: Sequence[int]) -> "NgramIndex":
        """Incremental per-request lookup state seeded with ``tokens``;
        extend with :meth:`NgramIndex.extend` as the row emits."""
        return NgramIndex(self, tokens)


class NgramIndex:
    """Positions of every (n, chunk) n-gram of one row's history.

    ``extend`` appends tokens and registers the n-grams they complete
    (O(max_ngram) per token); ``propose`` looks the current suffix up
    directly and walks its occurrence list latest-first, stopping at the
    first full-gamma window — the same answer the stateless scan gives,
    without re-reading the history.
    """

    __slots__ = ("proposer", "seq", "_where")

    def __init__(self, proposer: NgramProposer, tokens: Sequence[int]):
        self.proposer = proposer
        self.seq: List[int] = []
        self._where: dict = {}          # (n, chunk) -> [start, ...]
        self.extend(tokens)

    def __len__(self) -> int:
        return len(self.seq)

    def extend(self, tokens: Sequence[int]) -> "NgramIndex":
        seq, where = self.seq, self._where
        lo, hi = self.proposer.min_ngram, self.proposer.max_ngram
        for t in tokens:
            seq.append(int(t))
            total = len(seq)
            for n in range(lo, min(hi, total) + 1):
                where.setdefault(
                    (n, tuple(seq[total - n:])), []).append(total - n)
        return self

    def propose(self) -> List[int]:
        seq = self.seq
        total = len(seq)
        gamma = self.proposer.gamma
        for n in range(min(self.proposer.max_ngram, total - 1),
                       self.proposer.min_ngram - 1, -1):
            occs = self._where.get((n, tuple(seq[total - n:])))
            if not occs:
                continue
            best: List[int] = []
            for start in reversed(occs):
                if start == total - n:
                    continue    # the suffix matching itself
                cont = seq[start + n:start + n + gamma]
                if len(cont) > len(best):
                    best = cont
                if len(best) == gamma:
                    break
            if best:
                return list(best)
        return []

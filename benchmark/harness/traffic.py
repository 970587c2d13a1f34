"""The one general traffic generator. A traffic file fixes the *work*: the
multiset of (prompt length, output length) pairs and of gaps between
arrivals, as stratified quantiles of its distributions, and the order in
which they follow one another. ``--seed`` chooses token ids (and, for a
closed loop, where in the file's cycle of units a run starts), and nothing
else: two seeds offer the same tokens over the same time in the same order.

Why so little is left to the seed (PERF.md, PR 25): with the order of an
open-loop schedule dealt freely by the seed, six seeds' 85th-percentile
time to first token spread by 22%; by 15% with the order dealt in balanced
blocks; by 10% with one fixed cycle entered at a block of the seed's
choosing; while runs of one seed mostly agreed to a few percent: in a queue
the order *is* the work."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

#: pairing prompts with outputs is part of the file's multiset, not of the
#: seed: one fixed permutation
_PAIRING_SEED = 20260927


def quantiles(dist: dict, n: int) -> list:
    """``n`` values at the mid-points of ``n`` equal-probability strata of
    ``dist``, clipped to its ``min``/``max``; whole numbers where the
    distribution says ``"integer": true`` (lengths), else floats (gaps)."""
    levels = dist.get("levels")
    if levels:
        # ``levels`` distinct values, each dealt to its share of the n: a
        # serving engine compiles small programs for every new prompt
        # length, so a cell keeps its prompt lengths to a set that set-up
        # can warm one by one
        values = quantiles(dict(dist, levels=None), levels)
        return [values[min(levels - 1, int((i + 0.5) * levels / n))]
                for i in range(n)]
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        xs = [math.exp(mu + sigma * NormalDist().inv_cdf(u)) for u in us]
    elif kind == "exponential":
        xs = [-dist.get("mean", 1.0) * math.log(1.0 - u) for u in us]
    elif kind == "uniform":
        xs = [dist["min"] + (dist["max"] - dist["min"]) * u for u in us]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    xs = [min(max(x, lo), hi) for x in xs]
    return [int(round(x)) for x in xs] if dist.get("integer", True) else xs


def length_pairs(traffic: dict, n: int) -> list:
    """The file's multiset of ``n`` (prompt, output) pairs. Prompts and
    outputs are stratified apart and paired by one fixed permutation, so
    long prompts do not always meet long answers; a pair over ``max_total``
    gives up output tokens."""
    prompts = quantiles(traffic["prompt_len"], n)
    outputs = quantiles(traffic["output_len"], n)
    order = np.random.default_rng(_PAIRING_SEED).permutation(n)
    cap = traffic.get("max_total")
    pairs = []
    for p, j in zip(prompts, order):
        o = outputs[int(j)]
        if cap is not None:
            o = min(o, cap - p)
        pairs.append((p, o))
    return pairs


def gaps(traffic: dict, n: int, duration_s: float) -> list:
    """``n`` gaps between arrivals that sum to exactly ``duration_s``:
    stratified quantiles of the file's gap distribution, rescaled."""
    raw = quantiles(dict(traffic["gaps"], integer=False), n)
    scale = duration_s / sum(raw)
    return [g * scale for g in raw]


def _rng(seed: int, stream: int) -> np.random.Generator:
    # any whole number up to a little over 2**31: SeedSequence takes it whole
    return np.random.default_rng([int(seed), stream])


def tokens_for(rng: np.random.Generator, n: int, vocab: int) -> list:
    """Uniform token ids in [1, vocab): unshared prompts share no prefix
    beyond chance."""
    return rng.integers(1, vocab, size=n).tolist()


def _deal(values: list, blocks: int) -> list:
    """Sorted ``values`` dealt to ``blocks`` hands in turn, forth and back,
    so that every hand holds an even share of small and large."""
    hands = [[] for _ in range(blocks)]
    for i, v in enumerate(sorted(values)):
        turn, k = divmod(i, blocks)
        hands[k if turn % 2 == 0 else blocks - 1 - k].append(v)
    return hands


def open_loop_segment(traffic: dict, *, seed: int, stream: int,
                      duration_s: float, vocab: int) -> list:
    """One stretch of an open-loop schedule: ``round(rate * duration)``
    requests whose lengths and gaps are the file's multisets for that
    count. Returns dicts with ``due`` (seconds from the stretch's start),
    ``prompt`` and ``max_new_tokens``.

    The schedule is made of blocks of ``block_requests``: every block holds
    an even share of the long and the short prompts and answers and of the
    long and the short gaps (and so lasts about the same time and brings
    about the same number of tokens). Gaps are exponential in distribution,
    but a run of short gaps or of long prompts cannot outlast a block;
    bursts are a cell of their own. The whole schedule is the file's: in
    a queue the order of arrivals *is* the work, and left to the seed it
    moved the tails by more than any change to the program would (module
    docstring). The seed draws the token ids."""
    n = int(round(traffic["requests_per_s"] * duration_s))
    rng = _rng(seed, stream)
    size = int(traffic.get("block_requests", n)) or n
    blocks = max(1, round(n / size))
    by_prompt = _deal(quantiles(traffic["prompt_len"], n), blocks)
    by_output = _deal(quantiles(traffic["output_len"], n), blocks)
    by_gap = _deal(gaps(traffic, n, duration_s), blocks)
    # which prompt meets which answer inside a block is the file's, not the
    # seed's: one fixed permutation for each block
    fixed = np.random.default_rng(_PAIRING_SEED)
    cap = traffic.get("max_total", math.inf)
    paired = []
    for ps, os_ in zip(by_prompt, by_output):
        paired.append([(p, min(os_[int(j)], cap - p))
                       for p, j in zip(ps, fixed.permutation(len(os_)))])
    # the blocks' order and the order inside each block are the file's too:
    # the seed draws the token ids and nothing of the schedule
    out, t = [], 0.0
    for b in fixed.permutation(blocks):
        mine, gs = paired[int(b)], by_gap[int(b)]
        pair_order = fixed.permutation(len(mine))
        gap_order = fixed.permutation(len(gs))
        for k in range(len(mine)):
            # a request arrives half a gap into its own gap, so that the
            # first is not due at 0 and the last not at the very end
            g = gs[int(gap_order[k])]
            p, o = mine[int(pair_order[k])]
            out.append({"due": t + g / 2,
                        "prompt": tokens_for(rng, p, vocab),
                        "max_new_tokens": o})
            t += g
    return out


def unit_deck(traffic: dict, *, seed: int) -> list:
    """The deck of units a closed loop deals from: ``deck_units`` units, each
    one ``generate_batch`` call of ``unit_rows`` prompts under one output
    budget (the call takes one ``max_new_tokens``). Budgets and prompt
    lengths are the file's multisets.

    The deck is made of rounds of ``clients`` units, and client ``c`` takes
    units ``c``, ``c + clients``, ... of it, round and round: one unit of
    each round. Every round holds an even share of the budgets (every
    ``rounds``-th of them in order of size) and of the prompt lengths, so
    the units in flight at any time are the same mix of long and short
    (sorted values are dealt to the rounds forth and back): in a closed loop
    the window holds only some twenty units, and a window that drew the
    short ones would do more prefill for each output token. The rounds form
    one cycle fixed by the file; the seed picks the round it starts at and
    which client takes which place in the rounds."""
    rows, n_units = traffic["unit_rows"], traffic["deck_units"]
    clients = traffic["clients"]
    rounds, rest = divmod(n_units, clients)
    if rest:
        raise ValueError("deck_units must be a multiple of clients")
    budgets = sorted(quantiles(traffic["output_len"], n_units))
    prompts = sorted(quantiles(traffic["prompt_len"], rows * n_units))
    fixed = np.random.default_rng(_PAIRING_SEED)
    made = []
    for r in range(rounds):
        bs = [budgets[_snake(r, i, rounds)]
              for i in fixed.permutation(clients)]
        ps = [prompts[_snake(r, i, rounds)]
              for i in fixed.permutation(rows * clients)]
        made.append([{"max_new_tokens": bs[i],
                      "prompt_lens": ps[i * rows:(i + 1) * rows]}
                     for i in range(clients)])
    rng = _rng(seed, 3)
    first, shift = int(rng.integers(rounds)), int(rng.integers(clients))
    deck = []
    for r in np.roll(fixed.permutation(rounds), -first):
        units = [made[int(r)][int(i)] for i in fixed.permutation(clients)]
        deck += units[shift:] + units[:shift]
    return deck


def _snake(r: int, i: int, rounds: int) -> int:
    """Index of round ``r``'s ``i``-th value in a sorted list dealt to
    ``rounds`` rounds in turn, forth and back, so that the rounds' sums are
    equal to a few parts in a thousand."""
    return i * rounds + (r if i % 2 == 0 else rounds - 1 - r)


def unit_prompts(unit: dict, *, seed: int, client: int, k: int,
                 vocab: int) -> list:
    """Fresh token ids for a client's ``k``-th unit: when the deck comes
    round again the lengths repeat and the prompts do not, so nothing is
    ever served from a cache of this run's own earlier prompts."""
    rng = np.random.default_rng([int(seed), 5, client, k])
    return [tokens_for(rng, n, vocab) for n in unit["prompt_lens"]]


def packed_batches(traffic: dict, *, seed: int, vocab: int):
    """Endless generator of packed training batches: documents of the
    file's length multiset (dealt in the seed's order, cycled), token ids
    Zipf-distributed, packed first-fit into ``[batch, seq]`` rows with
    segment ids from 1 and 0 for padding. Every batch holds ``batch * seq``
    positions, so a step's work does not depend on the seed."""
    batch, seq = traffic["batch"], traffic["seq"]
    lens = quantiles(traffic["doc_len"], traffic["doc_pool"])
    rng = _rng(seed, 4)
    order = rng.permutation(len(lens))
    ranks = np.arange(1, vocab, dtype=np.float64)
    p = ranks ** -float(traffic.get("zipf_a", 1.1))
    cdf = np.cumsum(p / p.sum())
    cursor = 0
    carry = 0          # tokens of a document cut at a row's end, carried on
    while True:
        tokens = np.zeros((batch, seq), np.int32)
        segments = np.zeros((batch, seq), np.int32)
        for row in range(batch):
            fill, seg = 0, 0
            while fill < seq:
                if carry:
                    n, carry = carry, 0
                else:
                    n = min(int(lens[int(order[cursor % len(lens)])]), seq)
                    cursor += 1
                take = min(n, seq - fill)
                carry = n - take
                seg += 1
                ids = np.searchsorted(cdf, rng.random(take)) + 1
                tokens[row, fill:fill + take] = np.minimum(ids, vocab - 1)
                segments[row, fill:fill + take] = seg
                fill += take
        yield {"tokens": tokens, "segments": segments}

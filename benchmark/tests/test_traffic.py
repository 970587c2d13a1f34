"""Rule 1: the seed never changes the amount of work."""

import collections
import json
import os

import pytest

from benchmark.harness import common, traffic as gen

TRAFFIC = os.path.join(common.BENCH_DIR, "traffic")
SEEDS = (0, 7, 2147483659, 2 ** 31 + 12345)


def _load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def test_two_seeds_give_the_same_length_multiset_and_the_same_total_gap():
    tr = _load("chat-steady")
    seen = set()
    for seed in SEEDS:
        reqs = gen.open_loop_segment(tr, seed=seed, stream=2,
                                     duration_s=51.0, vocab=32768)
        assert len(reqs) == round(tr["requests_per_s"] * 51.0)
        pairs = collections.Counter(
            (len(r["prompt"]), r["max_new_tokens"]) for r in reqs)
        dues = [r["due"] for r in reqs]
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 51.0
        gaps = sorted(round(b - a, 9) for a, b in zip(dues, dues[1:]))
        seen.add((tuple(sorted(pairs.items())), len(gaps)))
        assert all(p + o <= tr["max_total"] for p, o in pairs)
    assert len(seen) == 1
    # the gaps themselves: one multiset, summing to the duration
    gs = gen.gaps(tr, 102, 51.0)
    assert sum(gs) == pytest.approx(51.0)


def test_the_schedule_is_the_files_and_the_seed_draws_the_tokens():
    tr = _load("chat-steady")
    runs = [gen.open_loop_segment(tr, seed=s, stream=2, duration_s=51.0,
                                  vocab=32768) for s in SEEDS]
    shape = [[(r["due"], len(r["prompt"]), r["max_new_tokens"]) for r in run]
             for run in runs]
    assert all(s == shape[0] for s in shape[1:])
    assert runs[0][0]["prompt"][:8] != runs[1][0]["prompt"][:8]
    again = gen.open_loop_segment(tr, seed=SEEDS[0], stream=2,
                                  duration_s=51.0, vocab=32768)
    assert [r["prompt"] for r in runs[0]] == [r["prompt"] for r in again]
    # every block brings about the same prompt tokens and lasts about the
    # same time: sorted values are dealt to the blocks forth and back
    prompts = gen.quantiles(tr["prompt_len"], 82)
    hands = gen._deal(prompts, 10)
    assert sorted(x for h in hands for x in h) == sorted(prompts)
    sums = [sum(h) / len(h) for h in hands]
    assert max(sums) < 1.6 * min(sums)     # the two longest cannot be split
    spans = [sum(h) for h in gen._deal(gen.gaps(tr, 82, 51.0), 10)]
    assert max(spans) < 2.0 * min(spans)   # nor can the two longest gaps


def test_prompt_lengths_come_from_the_warmed_set_of_levels():
    for name in ("chat-steady", "batch-backlog"):
        tr = _load(name)
        levels = set(gen.quantiles(tr["prompt_len"],
                                   tr["prompt_len"]["levels"]))
        assert len(levels) <= tr["prompt_len"]["levels"]
        for n in (7, 20, 102, 256):
            assert set(gen.quantiles(tr["prompt_len"], n)) <= levels
        lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
        assert all(lo <= x <= hi for x in levels)


def test_the_deck_of_units_is_one_multiset_in_the_seeds_order():
    tr = _load("batch-backlog")
    decks = [gen.unit_deck(tr, seed=s) for s in SEEDS]
    key = lambda d: sorted((u["max_new_tokens"], tuple(u["prompt_lens"]))
                           for u in d)
    assert all(key(d) == key(decks[0]) for d in decks)
    flat = [[(u["max_new_tokens"], tuple(u["prompt_lens"])) for u in d]
            for d in decks]
    assert len({tuple(f) for f in flat}) > 1
    assert len(decks[0]) == tr["deck_units"]
    # every round of ``clients`` units holds an even share of the budgets
    # and is the same set of units for every seed, in a rotated order
    n = tr["clients"]
    rounds = lambda f: sorted(tuple(sorted(f[i:i + n]))
                              for i in range(0, len(f), n))
    assert all(rounds(f) == rounds(flat[0]) for f in flat)
    for deck in decks:
        sums = [sum(u["max_new_tokens"] for u in deck[i:i + n])
                for i in range(0, len(deck), n)]
        assert max(sums) - min(sums) <= 0.005 * max(sums)
    assert all(len(u["prompt_lens"]) == tr["unit_rows"] for u in decks[0])
    # the deck comes round with the same lengths and other prompts
    a = gen.unit_prompts(decks[0][0], seed=1, client=0, k=0, vocab=32768)
    b = gen.unit_prompts(decks[0][0], seed=1, client=0, k=64, vocab=32768)
    assert [len(p) for p in a] == [len(p) for p in b] and a != b


def test_packed_batches_fill_every_position_whatever_the_seed():
    tr = _load("train-fsdp4")
    tr = dict(tr, batch=2, seq=512, doc_pool=64)
    for seed in SEEDS[:2]:
        it = gen.packed_batches(tr, seed=seed, vocab=1000)
        for _ in range(3):
            b = next(it)
            assert b["tokens"].shape == b["segments"].shape == (2, 512)
            assert (b["segments"] > 0).all()          # no padding
            assert (b["tokens"] >= 1).all() and (b["tokens"] < 1000).all()
            # segment ids rise along a row
            assert (b["segments"][:, 1:] >= b["segments"][:, :-1]).all()

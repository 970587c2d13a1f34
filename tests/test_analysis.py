"""lzy-lint: the tier-1 ratchet + the synthetic violation corpus.

Three layers:

- **corpus**: every violation class is proven CAUGHT on its known-bad
  snippet and SILENT on the paired known-good snippet
  (``tests/analysis_corpus/`` — parsed, never imported);
- **ratchet**: the four passes run over the live ``lzy_tpu`` tree and
  any violation whose fingerprint is not in the checked-in baseline
  (``lzy_tpu/analysis/baseline.json`` — which ships EMPTY) fails
  tier-1.  This is the test that makes the PR 5/6/12 bug classes
  unshippable;
- **budget**: the full-tree run must stay under 10 s of wall clock so
  the ratchet never becomes the test people skip.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from lzy_tpu.analysis import core, load_baseline, load_tree, run_passes

pytestmark = pytest.mark.analysis

CORPUS = Path(__file__).parent / "analysis_corpus"
LIVE_ROOT = Path(__file__).resolve().parents[1] / "lzy_tpu"


@pytest.fixture(scope="module")
def corpus_result():
    index = load_tree(CORPUS, rel_to=CORPUS)
    return run_passes(index)


@pytest.fixture(scope="module")
def live_result():
    import time as _time

    # the lint's own CPU seconds (it runs in this thread alone: 2.9 s on an
    # idle machine), not the wall clock: under six workers and another
    # file's multi-threaded compiles the same work waited for a core and
    # read 10.3 and 11.2 s (ROADMAP D17, PR 52)
    t0 = _time.process_time()
    index = load_tree(LIVE_ROOT)
    result = run_passes(index)
    elapsed = _time.process_time() - t0
    return index, result, elapsed


def _rules_in(result, path: str):
    return {v.rule for v in result.violations if v.path == path}


# -- corpus: each class caught on bad, silent on good -------------------------

CLASS_PAIRS = [
    ("lock-order-inversion",
     "bad_lock_inversion.py", "good_lock_order.py"),
    ("lock-self-reacquire",
     "bad_self_reacquire.py", "good_self_reacquire.py"),
    ("lock-blocking-call",
     "bad_blocking_under_lock.py", "good_blocking_outside_lock.py"),
    ("lock-blocking-call",
     "bad_journal_under_lock.py", "good_journal_outside_lock.py"),
    ("lock-blocking-call",
     "bad_parked_release_under_lock.py",
     "good_parked_release_outside_lock.py"),
    ("jax-donation-alias",
     "bad_donation_alias.py", "good_donation_copy.py"),
    ("jax-traced-python-if",
     "bad_traced_if.py", "good_traced_if.py"),
    ("jax-host-sync-hot-loop",
     "lzy_tpu/serving/bad_host_sync.py",
     "lzy_tpu/serving/good_host_sync.py"),
    ("jax-host-sync-hot-loop",
     "lzy_tpu/serving/bad_shard_host_sync.py",
     "lzy_tpu/serving/good_shard_host_sync.py"),
    ("jax-reupload-hot-loop",
     "lzy_tpu/serving/bad_reupload_hot_loop.py",
     "lzy_tpu/serving/good_reupload_once.py"),
    ("clock-raw-time",
     "bad_raw_clock.py", "good_injected_clock.py"),
    ("chaos-uncaught-error",
     "bad_uncaught_fault.py", "good_caught_fault.py"),
]


class TestCorpus:
    @pytest.mark.parametrize("rule,bad,good", CLASS_PAIRS,
                             ids=[p[0] for p in CLASS_PAIRS])
    def test_bad_caught_good_silent(self, corpus_result, rule, bad,
                                    good):
        assert rule in _rules_in(corpus_result, bad), \
            f"{rule} missed its known-bad snippet {bad}"
        assert not _rules_in(corpus_result, good), \
            f"false positive(s) on {good}: " \
            f"{[v.render() for v in corpus_result.violations if v.path == good]}"

    def test_chaos_contract_side_rules(self, corpus_result):
        rules = _rules_in(corpus_result, "bad_uncaught_fault.py")
        assert "chaos-unregistered-hit" in rules      # corpus.typo
        assert "chaos-unhit-point" in rules           # corpus.dead
        assert "chaos-crash-unhandled" in rules       # corpus.crashy

    def test_blocking_flags_every_category(self, corpus_result):
        msgs = [v.message for v in corpus_result.violations
                if v.path == "bad_blocking_under_lock.py"
                and v.rule == "lock-blocking-call"]
        joined = " | ".join(msgs)
        assert "sleep" in joined
        assert "storage I/O" in joined
        assert "wait" in joined

    def test_donation_flags_both_shapes(self, corpus_result):
        msgs = [v.message for v in corpus_result.violations
                if v.path == "bad_donation_alias.py"]
        assert any("asarray" in m for m in msgs)          # taint shape
        assert any("same expression" in m for m in msgs)  # dup-arg shape

    def test_raw_clock_catches_from_import_too(self, corpus_result):
        lines = [v.line for v in corpus_result.violations
                 if v.path == "bad_raw_clock.py"]
        assert len(lines) >= 4           # import-from + 3+ call sites


class TestSuppressions:
    def test_justified_suppression_silences(self, corpus_result):
        assert not _rules_in(corpus_result, "good_suppression.py")
        suppressed = [v for v in corpus_result.suppressed
                      if v.path == "good_suppression.py"]
        assert suppressed, "the justified disable should still be " \
                           "visible in the suppressed list"

    def test_bare_suppression_is_its_own_violation(self, corpus_result):
        rules = _rules_in(corpus_result, "bad_bare_suppression.py")
        assert "lint-bare-suppression" in rules
        # and it does NOT silence the underlying finding
        assert "clock-raw-time" in rules

    def test_unknown_rule_flagged(self, tmp_path):
        (tmp_path / "x.py").write_text(
            "import time\n"
            "t = time.time()  "
            "# lzy-lint: disable=no-such-rule -- why not\n")
        result = run_passes(load_tree(tmp_path, rel_to=tmp_path))
        rules = {v.rule for v in result.violations}
        assert "lint-unknown-rule" in rules
        assert "clock-raw-time" in rules   # unknown rule silences nothing

    def test_suppression_covers_next_line(self, tmp_path):
        (tmp_path / "x.py").write_text(
            "import time\n"
            "# lzy-lint: disable=clock-raw-time -- fixture justification\n"
            "t = time.time()\n")
        result = run_passes(load_tree(tmp_path, rel_to=tmp_path))
        assert not result.violations
        assert len(result.suppressed) == 1


# -- the ratchet --------------------------------------------------------------

class TestRatchet:
    def test_live_tree_holds_the_baseline(self, live_result):
        _index, result, _elapsed = live_result
        baseline = load_baseline()
        new = baseline.new_violations(result)
        assert not new, (
            "lzy-lint found violation(s) not in the baseline — fix them "
            "or add a justified `# lzy-lint: disable=<rule> -- <why>`:\n"
            + "\n".join(v.render() for v in new))

    def test_baseline_ships_empty(self):
        # the ratchet is at ZERO: accepting a violation into the
        # baseline is a deliberate, reviewed act — this test makes the
        # diff loud
        baseline = load_baseline()
        assert not baseline.accepted, \
            "baseline.json should stay empty; prefer fixing or inline " \
            "suppression with justification"

    def test_every_pass_actually_ran(self, live_result):
        _index, result, _elapsed = live_result
        assert set(result.passes_run) == {"locks", "jax", "clock",
                                          "chaos"}

    def test_wall_clock_budget(self, live_result):
        index, _result, elapsed = live_result
        assert len(index.modules) > 100, "live tree went missing?"
        assert elapsed < 10.0, (
            f"full-tree lzy-lint took {elapsed:.1f}s of CPU — over the 10s "
            f"tier-1 budget; profile the passes before this becomes "
            f"the test everyone skips")

    def test_chaos_registry_is_covered(self, live_result):
        # every registered point hit, every hit registered (the rules
        # would fail the ratchet; this asserts the inventory exists and
        # is non-trivial so a refactor cannot silently empty the pass)
        index, _result, _elapsed = live_result
        from lzy_tpu.analysis.chaos_contracts import registry_summary

        registry = registry_summary(index)
        assert len(registry) >= 19      # 19 points as of PR 14
        assert all(p["hits"] for p in registry)

    def test_lock_inventory_scale(self, live_result):
        # the lock-site extraction underlies every lock rule: if the
        # resolver breaks, the pass goes silently blind — pin the scale
        index, _result, _elapsed = live_result
        from lzy_tpu.analysis.locks import lock_sites

        sites = lock_sites(index)
        assert len(sites) >= 200
        assert any("RequestQueue._lock" in s["lock"] for s in sites)
        assert any("ReplicaFleet._lock" in s["lock"] for s in sites)


# -- the CLI ------------------------------------------------------------------

class TestCli:
    def test_json_output_clean(self, capsys):
        from lzy_tpu.analysis.__main__ import main

        rc = main(["--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["clean"] is True
        assert doc["new_violations"] == []
        assert doc["files"] > 100
        assert doc["lock_sites"]
        assert doc["chaos_registry"]

    def test_list_rules(self, capsys):
        from lzy_tpu.analysis.__main__ import main

        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in core.RULES:
            assert rule in out

    def test_subset_of_passes(self, capsys):
        from lzy_tpu.analysis.__main__ import main

        assert main(["--passes", "clock,chaos"]) == 0
        assert "passes=clock,chaos" in capsys.readouterr().out

    def test_corpus_fails_the_cli(self, capsys):
        from lzy_tpu.analysis.__main__ import main

        rc = main(["--root", str(CORPUS), "--no-baseline"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "[NEW]" in out

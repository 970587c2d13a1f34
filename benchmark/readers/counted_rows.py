"""What the program counted about the traced span's decode rounds, for the
readers that turn a kernel's device time into a share of a roofline. A model
whose layers sow counts (``models/serving.py``: the module's ``STATS``) has
each round's rows and counts on its ``engine.decode.emit`` span (``rows``;
``model_stats``: counter name -> this round's count), so what a traced
round's kernels had to move is known round by round, over the rounds that
ended inside the traced span. **Nothing stands in for the spans**: the
window's counters over a 4 s trace read 107% once (PERF.md section 6, PR
29), so where the traced rounds carry no counts these return None and the
metric is left out of the line."""

_EMIT = "engine.decode.emit"


def traced_rounds(obs):
    span = obs.get("trace_span")
    if not span:
        return []
    return [s["attrs"] for s in obs.get("spans") or []
            if s["name"] == _EMIT and span[0] <= s["end"] <= span[1]
            and "model_stats" in (s.get("attrs") or {})]


def rows_a_round(obs):
    """Mean resident rows of the traced rounds; None without counts."""
    rounds = traced_rounds(obs)
    if not rounds:
        return None
    return sum(r["rows"] for r in rounds) / len(rounds)


def counted_share(obs, numerator, denominator):
    """Sum of one named count over another's, over the traced rounds (a
    fraction); None without counts."""
    rounds = traced_rounds(obs)
    den = sum(r["model_stats"].get(denominator, 0) for r in rounds)
    if not den:
        return None
    return sum(r["model_stats"].get(numerator, 0) for r in rounds) / den

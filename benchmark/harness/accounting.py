"""From timestamps to end-to-end metrics. Pure functions of recorded times,
so that the rules can be tested on synthetic ones.

The rule they share: no unit of work is cut by the window's edge. Serving
throughput counts tokens as they arrive, the one unit that arrives whole;
training counts whole steps between step ends; latencies belong to the
window by the instant a request was due. (Counting whole requests, or whole
units between a client's own boundaries, was tried and is gone: PERF.md,
PR 25, the noise diagnosis.)"""

from __future__ import annotations

from benchmark.harness.common import highest_percentile, percentile


def whole_units(boundaries: list, t_open: float, t_close: float):
    """Of a timeline of units that follow one another, the stretch that lies
    wholly in the window. ``boundaries[i]`` is when unit ``i`` began and
    ``boundaries[i + 1]`` when it ended. Returns ``(first, last)``
    indices into ``boundaries`` of the first boundary at or after ``t_open``
    and the last at or before ``t_close``, or None where fewer than two
    boundaries lie inside."""
    inside = [i for i, t in enumerate(boundaries) if t_open <= t <= t_close]
    if len(inside) < 2:
        return None
    return inside[0], inside[-1]


def arrived_tokens(streams: list, t_open: float, t_close: float) -> int:
    """Output tokens that arrived at the client inside the window.
    ``streams`` holds each request's ``[(arrival time, tokens so far)]``. A
    token is the unit of work here and arrives whole, so the window's edge
    cuts nothing: the rate over this count is taken over all the work and
    all the time of the window, and one request more or less at the edge
    moves it by a token, not by a request."""
    total = 0
    for stamps in streams:
        prev = 0
        for t, n in stamps:
            if t_open <= t <= t_close:
                total += n - prev
            prev = n
    return total


def resident_tokens(rows: list, t0: float, t1: float) -> float:
    """Mean over ``[t0, t1]`` of the context tokens held by the rows that
    were decoding. ``rows`` holds each request's ``(prompt length,
    [(arrival time, tokens so far)])``: between two arrivals of its tokens
    a row is resident with its prompt and the tokens so far."""
    token_seconds = 0.0
    for prompt_len, stamps in rows:
        inside = [s for s in stamps if t0 <= s[0] <= t1]
        for (ta, na), (tb, _) in zip(inside, inside[1:]):
            token_seconds += (prompt_len + na) * (tb - ta)
    return token_seconds / (t1 - t0)


def longest_silence(rows: list, t0: float, t1: float) -> float:
    """The longest stretch of ``[t0, t1]`` in which no token arrived at any
    client. Rounds follow one another at a tenth of a second or less, so a
    second of silence is a stall of the engine, the device or the whole
    process: a run that has one is not like its twins (PERF.md, PR 25)."""
    times = sorted(t for _, stamps in rows for t, _ in stamps if t0 <= t <= t1)
    edges = [t0] + times + [t1]
    return max(b - a for a, b in zip(edges, edges[1:]))


def step_rate(step_ends: list, tokens_per_step: int, t_open: float,
              t_close: float) -> dict:
    """Training: a step is timed from the end of the step before, so the
    steps that count are those between the first and the last step end
    inside the window."""
    cut = whole_units(step_ends, t_open, t_close)
    if cut is None:
        return {"tokens_per_s": None, "steps": 0}
    first, last = cut
    return {"tokens_per_s": (last - first) * tokens_per_step
            / (step_ends[last] - step_ends[first]),
            "steps": last - first}


def open_loop_latencies(requests: list, t_cut: float, *, ttft_q: float,
                        tpot_q: float, tpot_min_tokens: int) -> dict:
    """``requests`` are those *due* in the window, each ``{"due", "sent",
    "stamps": [(time, tokens received so far)], "asked", "error"}`` as they
    stood at ``t_cut`` (the window's end plus the wait for first tokens).

    TTFT is first token minus the instant the request was due. A request
    with no first token by ``t_cut`` is missing: it counts as failed, and
    enters the percentile at ``t_cut - due``, which it has waited at least.
    TPOT is (last - first) / (tokens - 1) on what had been received, over
    the requests with at least ``tpot_min_tokens`` tokens."""
    ttft, tpot, late, missing = [], [], [], 0
    for r in requests:
        stamps = [s for s in r["stamps"] if s[0] <= t_cut]
        if r.get("sent") is not None:
            late.append(r["sent"] - r["due"])
        if not stamps:
            missing += 1
            ttft.append(t_cut - r["due"])
            continue
        ttft.append(stamps[0][0] - r["due"])
        n_first, n_last = stamps[0][1], stamps[-1][1]
        if n_last >= tpot_min_tokens and n_last > n_first:
            tpot.append((stamps[-1][0] - stamps[0][0]) / (n_last - n_first))
    return {
        "ttft": percentile(ttft, ttft_q) if ttft else None,
        "tpot": percentile(tpot, tpot_q) if tpot else None,
        "ttft_p50": percentile(ttft, 50) if ttft else None,
        "ttft_mean": sum(ttft) / len(ttft) if ttft else None,
        "tpot_p50": percentile(tpot, 50) if tpot else None,
        "late_p95": percentile(late, 95) if late else None,
        "late_max": max(late) if late else None,
        "n_ttft": len(ttft), "n_tpot": len(tpot), "missing": missing,
        # each percentile keeps ten samples beyond it
        "tail_rule_kept": bool(ttft_q <= highest_percentile(len(ttft))
                               and tpot_q <= highest_percentile(len(tpot))),
    }

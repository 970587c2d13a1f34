"""Rule 2 on synthetic timestamps: no unit of work is cut by the window's
edge, and the percentile rule."""

import pytest

from benchmark.harness import accounting, common


def test_tokens_are_counted_as_they_arrive_and_only_inside_the_window():
    # one stream: tokens at t = 1..10, one each; another publishes in twos
    one = [(float(t), t) for t in range(1, 11)]
    twos = [(2.5, 2), (4.5, 4), (6.5, 6)]
    assert accounting.arrived_tokens([one, twos], 3.0, 6.0) == 4 + 2
    # a stream that began before the window counts only what arrived in it
    assert accounting.arrived_tokens([one], 8.5, 20.0) == 2
    assert accounting.arrived_tokens([], 0.0, 1.0) == 0


def test_a_request_at_the_edge_moves_the_count_by_its_tokens_inside():
    # a request that finishes just outside the window keeps the tokens that
    # arrived inside it: counting whole requests would lose all 240
    stream = [(i * 10.01 / 240, i) for i in range(1, 241)]
    assert accounting.arrived_tokens([stream], 0, 10) == 239


def test_window_edge_inside_a_step_drops_that_step_and_its_time():
    # steps end every 10 s from t = 3; window [10, 40]: ends inside are 13,
    # 23, 33 -> two whole steps over 20 s, wherever the window cuts
    ends = [3, 13, 23, 33, 43]
    out = accounting.step_rate(ends, 100, 10, 40)
    assert out["steps"] == 2 and out["tokens_per_s"] == pytest.approx(10.0)
    rates = {round(accounting.step_rate(
        [0.5 + 7 * i for i in range(20)], 70, t0, t0 + 51)["tokens_per_s"], 9)
        for t0 in (1.0, 2.5, 4.0, 6.9, 9.3)}
    assert rates == {10.0}


def test_resident_context_is_read_from_token_arrivals_inside_the_span():
    # a row with a prompt of 100, a token every second from t = 1
    row = (100, [(float(t), t) for t in range(1, 11)])
    # span [2, 6]: resident throughout, holding 102, 103, 104, 105 tokens
    assert accounting.resident_tokens([row], 2.0, 6.0) == pytest.approx(
        (102 + 103 + 104 + 105) / 4)
    # a row that left before the span, and one with a single arrival in it
    gone = (50, [(0.1, 1), (0.2, 2)])
    one = (70, [(3.0, 1), (9.0, 2)])
    assert accounting.resident_tokens([gone, one], 2.0, 6.0) == 0.0
    # two rows add
    assert accounting.resident_tokens([row, row], 2.0, 6.0) == pytest.approx(
        2 * 103.5)


def test_training_steps_are_timed_from_the_end_of_the_step_before():
    ends = [0.0, 1.1, 2.2, 3.3, 4.4, 5.5]
    out = accounting.step_rate(ends, 1000, 1.0, 5.0)
    assert out["steps"] == 3            # 1.1 -> 4.4
    assert out["tokens_per_s"] == pytest.approx(3000 / 3.3)
    assert accounting.step_rate([0.0, 9.0], 1000, 1.0, 5.0)[
        "tokens_per_s"] is None


def test_open_loop_latencies_from_due_time_and_missing_requests():
    reqs = [
        {"due": 0.0, "sent": 0.1, "stamps": [(0.5, 1)] + [
            (0.5 + 0.1 * i, 1 + i) for i in range(1, 20)]},
        {"due": 1.0, "sent": 1.0, "stamps": [(1.2, 1), (1.3, 2)]},
        {"due": 2.0, "sent": 2.0, "stamps": []},          # no first token
    ]
    out = accounting.open_loop_latencies(reqs, t_cut=5.0, ttft_q=50,
                                         tpot_q=50, tpot_min_tokens=16)
    assert out["missing"] == 1 and out["n_ttft"] == 3
    # TTFTs 0.5 (from due, not from sent), 0.2, and 3.0 waited at the cut
    assert out["ttft"] == pytest.approx(0.5)
    assert out["n_tpot"] == 1 and out["tpot"] == pytest.approx(0.1)
    assert out["late_p95"] == pytest.approx(0.09, abs=0.011)
    assert out["ttft_mean"] == pytest.approx((0.5 + 0.2 + 3.0) / 3)
    assert out["tail_rule_kept"] is False       # three samples, not ten


def test_percentile_interpolates_and_the_tail_rule():
    xs = list(range(1, 102))                     # 1..101
    assert common.percentile(xs, 50) == 51
    assert common.percentile(xs, 85) == pytest.approx(86.0)
    assert common.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        common.percentile([], 50)
    # the highest percentile that keeps ten samples beyond it
    assert common.highest_percentile(100) == pytest.approx(90.0)
    assert common.highest_percentile(67) >= 85.0
    assert common.highest_percentile(66) < 85.0
    assert common.highest_percentile(5) == 0.0


def test_decode_roofline_reads_resident_context_over_the_traced_span():
    from benchmark.harness import costs, readers

    # two decode rounds of 20 ms each; one row resident with 1000 tokens
    # of context (a prompt of 999 and one token) all through the span
    obs = {"trace": {"modules": {"jit_decode_step": [0.020, 0.020]}},
           "trace_span": (10.0, 14.0), "device_kind": "TPU v5 lite",
           "rows": [(999, [(9.0, 1), (10.0, 1), (14.0, 1)])],
           "model": {"param_bytes": 8.0e9, "kv_bytes_per_token": 65536}}
    need = 8.0e9 + 65536 * 1000
    least = need / costs.PEAKS["TPU v5 lite"]["bytes_per_s"]
    assert readers.decode_roofline(obs, module="jit_decode_step") == \
        pytest.approx(100.0 * least / 0.020)
    assert readers.decode_roofline(dict(obs, trace_span=None),
                                   module="jit_decode_step") is None


def test_longest_silence_is_the_widest_gap_between_any_two_arrivals():
    a = (10, [(1.0, 1), (1.1, 2), (4.0, 3)])
    b = (10, [(1.5, 1), (2.0, 2)])
    # arrivals at 1.0 1.1 1.5 2.0 4.0 in a window [0.5, 5.0]: 2.0 -> 4.0
    assert accounting.longest_silence([a, b], 0.5, 5.0) == pytest.approx(2.0)
    # the window's own edges count: nothing arrives after 4.0 until 9.0
    assert accounting.longest_silence([a, b], 0.5, 9.0) == pytest.approx(5.0)
    assert accounting.longest_silence([], 0.0, 3.0) == pytest.approx(3.0)

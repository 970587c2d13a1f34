"""The trace reduction on a small trace recorded on a v5e: five rounds of a
``decode_step`` (four matmul+tanh fusions) followed by a ``prefill_step``,
each ended by a transfer to the host, under a ``bench.round`` annotation."""

import os

import pytest

from benchmark.harness import trace as xtrace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xtrace.reduce(xtrace.load(RECORDED))


def test_modules_and_their_device_times(reduced):
    assert set(reduced["modules"]) == {"jit_decode_step", "jit_prefill_step"}
    decode = reduced["modules"]["jit_decode_step"]
    assert len(decode) == 5
    # a decode round of the probe took 12.8 us on the device
    assert all(12e-6 < d < 14e-6 for d in decode)
    assert all(4e-6 < d < 6e-6 for d in reduced["modules"]["jit_prefill_step"])


def test_busy_is_the_union_of_op_intervals_inside_the_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # ten short programs in a window of 13 ms: the device is idle nearly all
    # of it, which is what the idle share has to say
    assert reduced["busy_s"] / reduced["window_s"] < 0.02
    assert reduced["collective_s"] == 0.0
    assert reduced["collective_exposed_s"] == 0.0


def test_ops_group_by_module_and_shape(reduced):
    top = xtrace.breakdown(reduced)
    names = [n for n, _ in top["device_ops"]]
    assert names[0].startswith("jit_decode_step:convolution_tanh_fusion_bf16")
    assert all(s > 0 for _, s in top["device_ops"])
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    # gaps are attributed to host events by name, or to none
    assert sum(s for _, s in top["idle_gaps"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_interval_arithmetic():
    u = xtrace.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert u == [(0, 3), (5, 7)] and xtrace.length(u) == 5
    assert xtrace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xtrace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xtrace.subtract([(0, 4)], []) == [(0, 4)]
    assert xtrace.op_label(
        "%fusion.123 = bf16[32,14336]{1,0:T(8,128)} fusion(...)") == \
        "fusion_bf16_32_14336_"
    assert xtrace.module_name("jit_decode_step(123)") == "jit_decode_step"

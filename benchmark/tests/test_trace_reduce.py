"""``trace_reduce`` on the small trace recorded on a v5e (PR 26: five
executions of ``jit_f``, a 1024^3 bfloat16 product with a tanh, about
15.8 us each, 10 ms of host sleep between them)."""

import os

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "fixtures", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(FIXTURE))


def test_busy_union_and_idle_share(reduced):
    # five executions of ~15.8 us; copy-start/-done overlap nothing
    assert reduced["device_planes"] == 1
    assert 75e-6 < reduced["busy_s"] < 85e-6
    assert 0.04 < reduced["window_s"] < 0.08
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])
    assert reduced["idle_share"] > 0.99


def test_per_pattern_time(reduced):
    sec, n = trace_reduce.pattern_time(reduced, "modules", r"^jit_f\(")
    assert n == 5 and 78e-6 < sec < 80e-6
    # by operation name, and by the HLO text of the operation
    sec_op, n_op = trace_reduce.pattern_time(reduced, "ops", r"^convolution_tanh")
    assert n_op == 5 and sec_op == pytest.approx(79.04e-6, rel=1e-3)
    assert trace_reduce.pattern_time(reduced, "ops", r"kind=kOutput")[1] == 5
    assert trace_reduce.pattern_time(reduced, "ops", r"no_such_kernel") == (0.0, 0)
    # programs picked by what ran inside them
    assert trace_reduce.pattern_time(
        reduced, "modules", r"^jit_f", has_op=r"^convolution")[1] == 5
    assert trace_reduce.pattern_time(
        reduced, "modules", r"^jit_f", lacks_op=r"^convolution")[1] == 0


def test_gap_attribution(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # the host slept between the steps: that owns nearly all idle time
    assert gaps["python:$time sleep"] > 0.9 * (
        reduced["window_s"] - reduced["busy_s"])
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0] == "convolution_tanh_fusion" and len(ops) <= 10


def test_union_and_self_times():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ev = [("while", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 5, 6), ("d", 12, 13)]
    own = {n: s for n, _, _, s in trace_reduce.self_times(ev)}
    assert own == {"while": 3, "a": 2, "b": 4, "c": 1, "d": 1}
    assert sum(own.values()) == 11  # every nanosecond once
    assert trace_reduce.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion.12"
    assert trace_reduce.op_family("fusion.12") == "fusion"

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


def test_one_plane_counts_are_the_events():
    """On one device plane a count is the number of events, as before
    counts became per plane: every reading of a one-chip cell stays."""
    trace = trace_reduce.load(FIXTURE)
    (plane,) = trace["devices"].values()
    got = trace_reduce.reduce(trace)
    for table, key in (("modules", "modules"), ("ops", "ops")):
        raw = {}
        for name, _, _ in plane[key]:
            k = name if table == "modules" else trace_reduce.op_name(name)
            raw[k] = raw.get(k, 0) + 1
        assert {k: v["count"] for k, v in got[table].items()} == raw


def _four_planes(steps=3, step_s=0.090, allreduce_s=0.004):
    """Four device planes, each running ``steps`` executions of the
    train step back to back: a fusion, then an all-reduce."""
    devices = {}
    for d in range(4):
        ops, mods = [], []
        for i in range(steps):
            t = 1.0 + i * step_s + d * 1e-5
            mods.append(("jit_step(7)", t, t + step_s))
            ops.append(("%fusion.3 = bf16[8] fusion()", t,
                        t + step_s - allreduce_s))
            ops.append(("%all-reduce.204 = (bf16[8]) all-reduce()",
                        t + step_s - allreduce_s, t + step_s))
        devices[f"/device:TPU:{d}"] = {"ops": ops, "modules": mods}
    return {"devices": devices, "host": {}}


def test_four_planes_read_one_device():
    """On a four-chip trace a time over a count is one device's: the
    step reads its own 90 ms (summed counts read 22.5), the all-reduce
    its 4 ms, and the whole step's FLOPs go over four chips' peak."""
    import harness

    reduced = trace_reduce.reduce(_four_planes())
    assert reduced["device_planes"] == 4
    assert reduced["modules"]["jit_step(7)"]["count"] == 3
    assert reduced["ops"]["all-reduce.204"]["count"] == 3
    cfg = harness.load_json("configs", "bert-base-sst2-dp4.json")
    peak = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
    run = {"trace": reduced, "config": cfg, "peak": peak, "chips": 4,
           "window": {"batch_size": 512, "seq_len": 128}}

    def read(name):
        return harness.load_module(
            os.path.join(harness.BENCH_DIR, "metrics", name + ".py"),
            "m_" + name.replace(".", "_")).read(run)

    assert read("step_device_ms.train") == pytest.approx(90.0)
    assert read("allreduce_device_ms.train") == pytest.approx(4.0)
    import opcount

    flops = opcount.bert_train_step(cfg, 512, 128)["flops"]
    assert read("step_mfu.train") == pytest.approx(
        100 * flops * 3 / reduced["window_s"] / (4 * 197e12))
    assert read("step_mfu.train") < 100


def test_allreduce_reader_finds_nothing_on_one_device(reduced):
    import harness

    reader = harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", "allreduce_device_ms.train.py"),
        "m_allreduce")
    assert reader.read({"trace": reduced}) is None
    assert reader.read({}) is None

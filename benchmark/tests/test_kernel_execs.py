"""``metrics/kernel_execs_per_step.train.py`` on reduced traces written
out by hand: every kernel's events over the steps, whole numbers
summed (a window that opens inside a step loses that step's first
kernels), and None (never an error) where the trace has no such kernel
or no step."""

import os

import harness

READER = harness.load_module(
    os.path.join(harness.BENCH_DIR, "metrics",
                 "kernel_execs_per_step.train.py"),
    "metric_kernel_execs_per_step_train")


def _op(count):
    return {"seconds": 0.1 * count, "count": count, "text": "%x = f32[] x()"}


def test_counts_kernel_events_a_step():
    # 16 steps, the first cut: kda_fwd.1 ran before the trace began;
    # kda_bwd.7 sits in a loop of two trips
    trace = {
        "ops": {"kda_fwd.1": _op(15), "kda_fwd.2": _op(16),
                "kda_bwd.7": _op(32), "flash_attention.3": _op(16),
                "fusion.12": _op(300), "copy.4": _op(30)},
        "modules": {"jit_step(123)": {"seconds": 8.0, "count": 16, "ops": {}},
                    "jit_norms(9)": {"seconds": 0.1, "count": 1, "ops": {}}},
    }
    assert READER.read({"trace": trace}) == 5


def test_nothing_to_read_is_none():
    assert READER.read({}) is None
    assert READER.read({"trace": {"ops": {"fusion.1": _op(4)}, "modules": {
        "jit_step(1)": {"seconds": 1.0, "count": 2, "ops": {}}}}}) is None
    assert READER.read({"trace": {"ops": {"kda_fwd.1": _op(4)},
                                  "modules": {}}}) is None

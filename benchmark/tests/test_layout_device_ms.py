"""``metrics/layout_device_ms.train.py`` on reduced traces written out
by hand: the self time of the operations NAMED copy, reshape or
transpose over the steps, in milliseconds; a fusion whose text holds a
copy and the asynchronous copies are not counted; None (never an
error) where the trace has no step."""

import os

import harness
import pytest

READER = harness.load_module(
    os.path.join(harness.BENCH_DIR, "metrics", "layout_device_ms.train.py"),
    "metric_layout_device_ms_train")

STEPS = {"jit_step(123)": {"seconds": 8.0, "count": 16, "ops": {}},
         "jit_norms(9)": {"seconds": 0.1, "count": 1, "ops": {}}}


def _op(seconds, text="%x = f32[] x()"):
    return {"seconds": seconds, "count": 16, "text": text}


def test_sums_bare_layout_operations_a_step():
    trace = {"modules": STEPS, "ops": {
        "copy.4": _op(0.16), "copy": _op(0.016), "reshape.31": _op(0.32),
        "transpose_7": _op(0.08), "copy-start.2": _op(1.0),
        "copy-done.2": _op(1.0), "copy_fusion.3": _op(1.0),
        "fusion.12": _op(1.0, "%fusion.12 = f32[8] fusion(copy.4)"),
        "kda_fwd.1": _op(1.0)}}
    assert READER.read({"trace": trace}) == pytest.approx(36.0)


@pytest.mark.parametrize("ops,want", [
    ({"fusion.1": _op(1.0), "kda_bwd.2": _op(1.0)}, 0.0),
    ({}, 0.0),
])
def test_steps_without_layout_operations_read_zero(ops, want):
    assert READER.read({"trace": {"modules": STEPS, "ops": ops}}) == want


def test_nothing_to_read_is_none():
    assert READER.read({}) is None
    assert READER.read({"trace": {"ops": {"copy.1": _op(1.0)},
                                  "modules": {}}}) is None
    assert READER.read({"trace": {"ops": {"copy.1": _op(1.0)}, "modules": {
        "jit_norms(9)": {"seconds": 0.1, "count": 1, "ops": {}}}}}) is None

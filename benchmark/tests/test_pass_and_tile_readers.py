"""The readers of the streaming flash kernels' passes and of the
expert tile loop, on run dicts written out by hand:
``flash_fwd_device_ms.train`` / ``flash_bwd_device_ms.train`` (self
time of the operations NAMED by pass over the steps, in ms), and
``moe_tile_fill_pct.train`` / ``moe_us_per_tile.train`` (the step's
own ``moe.*`` counts, with the ``moe.experts`` scope's time); each None
(never an error) where what it reads is missing, as on a program whose
kernels carry no pass name and whose step counts no tiles."""

import os

import harness
import pytest


def _reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH_DIR, "metrics", name + ".py"),
        "metric_" + name.replace(".", "_"))


FWD = _reader("flash_fwd_device_ms.train")
BWD = _reader("flash_bwd_device_ms.train")
FILL = _reader("moe_tile_fill_pct.train")
PER_TILE = _reader("moe_us_per_tile.train")

STEPS = {"jit_step(123)": {"seconds": 8.0, "count": 16, "ops": {}},
         "jit_norms(9)": {"seconds": 0.1, "count": 1, "ops": {}}}


def _op(seconds, text="%x = f32[] custom-call()"):
    return {"seconds": seconds, "count": 16, "text": text}


NAMED = {
    "flash_attention_fwd": _op(0.16), "flash_attention_fwd.1": _op(0.32),
    "flash_attention_dq.2": _op(0.08), "flash_attention_dkv.3": _op(0.24),
    "flash_attention_dkv": _op(0.016),
    # not a pass of the streaming kernels: a family of its own, a
    # one-tile kernel, a fusion whose text names a kernel's output
    "flash_attention.4": _op(1.0), "jvp_jit_flash_attention__.1": _op(1.0),
    "fusion.7": _op(1.0, "%fusion.7 = f32[8] fusion(flash_attention_fwd.1)"),
    "kda_fwd.1": _op(1.0),
}


@pytest.mark.parametrize("reader,want", [(FWD, 30.0), (BWD, 21.0)])
def test_flash_readers_sum_their_pass_a_step(reader, want):
    trace = {"modules": STEPS, "ops": NAMED}
    assert reader.read({"trace": trace}) == pytest.approx(want)


@pytest.mark.parametrize("reader", [FWD, BWD])
@pytest.mark.parametrize("run", [
    {},
    {"trace": None},
    {"trace": {"ops": NAMED}},
    {"trace": {"ops": NAMED, "modules": {}}},
    {"trace": {"ops": NAMED, "modules": {
        "jit_norms(9)": {"seconds": 0.1, "count": 1, "ops": {}}}}},
    # the parent's names: one family for all three kernels
    {"trace": {"modules": STEPS, "ops": {"flash_attention.4": _op(1.0),
                                         "flash_attention.5": _op(1.0)}}},
])
def test_flash_readers_nothing_to_read_is_none(reader, run):
    assert reader.read(run) is None


def _stats(**kw):
    return {"child": {"model_stats": dict(
        {"steps": 10, "moe.pairs_here": 4700, "moe.pairs_routed": 2621440,
         "moe.tiles_run": 300, "moe.rows_run": 76800}, **kw)}}


def test_tile_fill_is_pairs_over_rows():
    assert FILL.read(_stats()) == pytest.approx(100.0 * 4700 / 76800)
    assert FILL.read(_stats(**{"moe.pairs_here": 76800})) == 100.0


@pytest.mark.parametrize("run", [
    {}, {"child": None}, {"child": {"model_stats": None}},
    {"child": {"model_stats": {"steps": 10, "moe.pairs_here": 4700}}},
    _stats(**{"moe.rows_run": 0}),
])
def test_tile_fill_nothing_to_read_is_none(run):
    assert FILL.read(run) is None


SCOPES = {"steps": 8.0, "seconds": {"moe.route": 0.1, "moe.experts": 0.24,
                                    "moe.shared": 0.1}}


def test_us_per_tile_is_scope_time_a_step_over_tiles_a_step():
    # 30 ms a step under moe.experts over 30 tiles a step: 1,000 us a trip
    run = dict(_stats(), scopes=SCOPES)
    assert PER_TILE.read(run) == pytest.approx(1000.0)


@pytest.mark.parametrize("run", [
    _stats(),
    dict(_stats(), scopes=None),
    dict(_stats(), scopes={"steps": 0, "seconds": SCOPES["seconds"]}),
    dict(_stats(), scopes={"steps": 8.0, "seconds": {"moe.route": 0.1}}),
    dict(_stats(**{"moe.tiles_run": 0}), scopes=SCOPES),
    {"scopes": SCOPES, "child": {"model_stats": {"steps": 10}}},
    {"scopes": SCOPES},
])
def test_us_per_tile_nothing_to_read_is_none(run):
    assert PER_TILE.read(run) is None

"""The readers of the program's span sums (PR 27), each on a run made
by hand. None is listed in ``BENCHMARK.json`` yet: they wait for the
serving cell. A parent that lacks the counters gives ``None`` (the
metric is left out of the line), never a raise."""

import os

import pytest

import harness

METRICS = os.path.join(harness.BENCH_DIR, "metrics")

RUN = {
    "window": {"seconds": 45.0},
    "counters": {
        "generate.queue_wait_us": 12_600_000, "generate.queue_wait_n": 252,
        "generate.sched_unit_decode_us": 19_350_000,
        "generate.sched_unit_decode_n": 4_300,
        "generate.readback_wait_us": 18_900_000,
        "generate.sched_idle_us": 9_000_000,
    },
}

CASES = [
    ("queue_wait_ms_per_req.serve", 50.0,
     ["generate.queue_wait_us", "generate.queue_wait_n"]),
    ("sched_unit_host_ms.decode", 4.5,
     ["generate.sched_unit_decode_us", "generate.sched_unit_decode_n"]),
    ("readback_wait_pct.serve", 42.0, ["generate.readback_wait_us"]),
    ("sched_idle_pct.serve", 20.0, ["generate.sched_idle_us"]),
]


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"), name).read


@pytest.mark.parametrize("name,want,_", CASES)
def test_reads_the_hand_made_run(name, want, _):
    assert reader(name)(RUN) == pytest.approx(want)


@pytest.mark.parametrize("name,_,needs", CASES)
def test_missing_counter_reads_none(name, _, needs):
    for gone in needs:
        counters = {k: v for k, v in RUN["counters"].items() if k != gone}
        assert reader(name)(dict(RUN, counters=counters)) is None
    assert reader(name)({"window": RUN["window"]}) is None
    assert reader(name)(dict(RUN, counters=None)) is None


@pytest.mark.parametrize("name", ["queue_wait_ms_per_req.serve",
                                  "sched_unit_host_ms.decode"])
def test_no_spans_in_the_window_reads_none(name):
    zero = {k: 0 for k in RUN["counters"]}
    assert reader(name)(dict(RUN, counters=zero)) is None


@pytest.mark.parametrize("name", ["readback_wait_pct.serve",
                                  "sched_idle_pct.serve"])
def test_no_window_reads_none(name):
    assert reader(name)({"counters": RUN["counters"]}) is None
    assert reader(name)(dict(RUN, window={"seconds": 0})) is None

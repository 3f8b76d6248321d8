"""``utils.metrics.span``: the program's own clock, and ``fit``'s use
of it.

A span does two things and both are pinned here: it always adds its
elapsed microseconds to ``<counter>_us`` and one to ``<counter>_n``
(exception or not), and under a profiler session it lands on the host
plane of the ``.xplane.pb`` with its attributes, nested under the
enclosing span. ``fit`` counts into the process-wide ``REGISTRY``, so
every assertion on it is a DELTA over the call.
"""

import glob
import time

import jax
import pytest

from mlapi_tpu.datasets import load_digits
from mlapi_tpu.models import get_model
from mlapi_tpu.train import fit
from mlapi_tpu.train.loop import PROFILE_SKIP_STEPS, PROFILE_STEPS
from mlapi_tpu.utils.metrics import REGISTRY, MetricsRegistry, span


def _counters(registry=REGISTRY) -> dict:
    return registry.snapshot()["counters"]


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


@pytest.fixture(scope="module")
def digits():
    return load_digits()


def _mlp(digits):
    return get_model(
        "mlp", num_features=digits.num_features,
        num_classes=digits.num_classes, hidden_dims=[32],
    )


# --- the span itself ---------------------------------------------------


def test_span_adds_elapsed_us_and_count():
    reg = MetricsRegistry()
    for _ in range(3):
        with span("t.work", "t.work", registry=reg) as sp:
            time.sleep(0.002)
    c = _counters(reg)
    assert c["t.work_n"] == 3
    assert 3 * 2000 <= c["t.work_us"] < 3 * 2000 + 500_000
    # The span keeps its own interval for whoever logs it.
    assert sp.elapsed_ns >= 2_000_000 and sp.start_ns > 0


def test_span_without_counter_counts_nothing():
    reg = MetricsRegistry()
    with span("t.silent", registry=reg, a=1):
        pass
    assert _counters(reg) == {}


def test_span_nests_and_parent_covers_children():
    reg = MetricsRegistry()
    with span("t.outer", "t.outer", registry=reg):
        with span("t.a", "t.a", registry=reg):
            time.sleep(0.001)
        with span("t.b", "t.b", registry=reg):
            time.sleep(0.001)
    c = _counters(reg)
    assert c["t.outer_n"] == c["t.a_n"] == c["t.b_n"] == 1
    # one microsecond of rounding a span, at most
    assert c["t.a_us"] + c["t.b_us"] <= c["t.outer_us"] + 3


def test_span_is_exception_safe():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        with span("t.outer", "t.outer", registry=reg):
            with span("t.inner", "t.inner", registry=reg):
                time.sleep(0.001)
                raise ValueError("boom")
    c = _counters(reg)
    assert c["t.outer_n"] == c["t.inner_n"] == 1
    assert c["t.inner_us"] >= 1000 and c["t.outer_us"] >= c["t.inner_us"] - 2


def test_span_counter_named_on_the_way_out():
    """The scheduler's shape: the block learns its kind only after the
    work; a block that never names a counter counts nothing."""
    reg = MetricsRegistry()
    with span("t.unit", registry=reg, lane=1) as sp:
        sp.counter = "t.unit_decode"
        sp.set(kind="decode")
    with pytest.raises(StopIteration):
        with span("t.unit", registry=reg, lane=1) as sp:
            raise StopIteration
    c = _counters(reg)
    assert set(c) == {"t.unit_decode_us", "t.unit_decode_n"}
    assert c["t.unit_decode_n"] == 1


def test_default_registry_is_process_wide():
    before = _counters()
    with span("t.global", "t.global"):
        pass
    assert _delta(before, _counters())["t.global_n"] == 1


# --- fit's counters ----------------------------------------------------


def test_fit_counts_steps_and_parts(digits):
    before = _counters()
    r = fit(
        _mlp(digits), digits, steps=6, batch_size=64, learning_rate=1e-3,
        optimizer="adam", eval_every=3,
    )
    d = _delta(before, _counters())
    assert d["fit.step_n"] == d["fit.batch_n"] == d["fit.dispatch_n"] == 6
    assert d["fit.sync_n"] == 2  # the two eval points read the loss
    assert d["fit.eval_n"] == 3  # two in the loop, one after it
    parts = d["fit.batch_us"] + d["fit.dispatch_us"] + d["fit.sync_us"]
    # children of fit.step, each rounded to a microsecond
    assert 0 < parts <= d["fit.step_us"] + 6 * 3
    assert set(r.host_ms_per_step) == {"batch", "dispatch", "sync"}
    assert r.host_ms_per_step["dispatch"] == pytest.approx(
        d["fit.dispatch_us"] / 1e3 / 6
    )


def test_fit_leaving_by_exception_keeps_its_sums(digits, monkeypatch):
    """The benchmark's child ends ``fit`` by raising out of the step;
    whoever holds the process still reads the registry."""
    from mlapi_tpu.train import loop

    class Stop(Exception):
        pass

    real_make = loop.make_train_step

    def make(*a, **kw):
        step, calls = real_make(*a, **kw), []

        def wrapped(*args):
            calls.append(1)
            if len(calls) > 4:
                raise Stop
            return step(*args)

        return wrapped

    monkeypatch.setattr(loop, "make_train_step", make)
    before = _counters()
    with pytest.raises(Stop):
        fit(_mlp(digits), digits, steps=100, batch_size=64,
            learning_rate=1e-3, optimizer="adam")
    d = _delta(before, _counters())
    # four whole steps and the fifth up to the raise
    assert d["fit.step_n"] == d["fit.batch_n"] == d["fit.dispatch_n"] == 5


# --- fit under a profiler session --------------------------------------


def _host_events(trace_dir: str) -> list:
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fit."):
                    out.append((ev.name, ev.start_ns, ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_traced_fit_holds_its_spans_on_the_host_plane(digits, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        fit(_mlp(digits), digits, steps=3, batch_size=64,
            learning_rate=1e-3, optimizer="adam")
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == "fit.step"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    for name in ("fit.batch", "fit.dispatch"):
        inner = [e for e in events if e[0] == name]
        assert len(inner) == 3
        # each lies inside its step's span: one clock, nested
        for (_, s0, sd, _), (_, c0, cd, _) in zip(steps, inner):
            assert s0 <= c0 and c0 + cd <= s0 + sd


def test_profile_dir_traces_a_fixed_run_of_steps_after_the_first(
        digits, tmp_path):
    total = PROFILE_SKIP_STEPS + PROFILE_STEPS + 4
    fit(_mlp(digits), digits, steps=total, batch_size=64,
        learning_rate=1e-3, optimizer="adam", profile_dir=str(tmp_path))
    nums = [e[3]["step_num"] for e in _host_events(str(tmp_path))
            if e[0] == "fit.step"]
    assert nums == list(range(PROFILE_SKIP_STEPS,
                              PROFILE_SKIP_STEPS + PROFILE_STEPS))


def test_profile_dir_on_a_short_run_traces_what_is_left(digits, tmp_path):
    fit(_mlp(digits), digits, steps=PROFILE_SKIP_STEPS + 2, batch_size=64,
        learning_rate=1e-3, optimizer="adam", profile_dir=str(tmp_path))
    nums = [e[3]["step_num"] for e in _host_events(str(tmp_path))
            if e[0] == "fit.step"]
    assert nums == [PROFILE_SKIP_STEPS, PROFILE_SKIP_STEPS + 1]

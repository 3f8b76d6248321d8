"""In-process metrics: counters and latency histograms.

Backs the serving layer's ``/metrics`` endpoint (the ``BASELINE.json``
north-star metric is requests/sec/chip and p50 latency on ``/predict``
— this is where those numbers come from at runtime). The reference has
no metrics at all (SURVEY §5).

Thread-safe enough for the serving model: the event loop plus the
batcher's single dispatch thread. Quantiles come from a reservoir
sample, not fixed buckets, so p50/p99 stay sharp at sub-millisecond
scales without bucket tuning.

:func:`span` is the program's own clock at its layer boundaries: one
context manager that writes a host span into whatever profiler session
is running (on the device trace's clock) and always adds the elapsed
time to a ``<counter>_us`` / ``<counter>_n`` pair. ``fit`` counts into
the process-wide :data:`REGISTRY`; a generative engine counts into its
own ``LatencyStats.sums``, which ``/metrics`` exports.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field


def nearest_rank(values: list[float], q: float) -> float | None:
    """Nearest-rank quantile over unsorted values: what every
    :class:`Histogram` quantile is."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


@dataclass
class Counter:
    name: str
    value: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


@dataclass
class Gauge:
    """A quantity that is read, not summed (the newest training step's
    expert load)."""

    name: str
    value: float = 0

    def set(self, value: float) -> None:
        self.value = value


class Histogram:
    """Reservoir-sampled latency histogram (values in milliseconds)."""

    def __init__(self, name: str, reservoir_size: int = 4096):
        self.name = name
        self.count = 0
        self.total = 0.0
        self._reservoir: list[float] = []
        self._size = reservoir_size
        self._rng = random.Random(0)
        self._lock = threading.Lock()

    def observe(self, value_ms: float) -> None:
        with self._lock:
            self.count += 1
            self.total += value_ms
            if len(self._reservoir) < self._size:
                self._reservoir.append(value_ms)
            else:
                i = self._rng.randrange(self.count)
                if i < self._size:
                    self._reservoir[i] = value_ms

    def quantile(self, q: float) -> float | None:
        with self._lock:
            sample = list(self._reservoir)
        return nearest_rank(sample, q)

    def summary(self) -> dict:
        return {
            "count": self.count,
            "mean_ms": (self.total / self.count) if self.count else None,
            "p50_ms": self.quantile(0.50),
            "p90_ms": self.quantile(0.90),
            "p99_ms": self.quantile(0.99),
        }


class MetricsRegistry:
    """Named counters, gauges + histograms, rendered as one JSON
    object."""

    def __init__(self):
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def counter(self, name: str) -> Counter:
        with self._lock:
            return self._counters.setdefault(name, Counter(name))

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            return self._gauges.setdefault(name, Gauge(name))

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            return self._histograms.setdefault(name, Histogram(name))

    def add_elapsed(self, name: str, ns: int) -> None:
        """One finished span: its time to ``<name>_us`` (rounded to the
        nearest microsecond, so many short spans sum without a bias)
        and one to ``<name>_n``."""
        self.counter(name + "_us").inc((ns + 500) // 1000)
        self.counter(name + "_n").inc()

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        return {
            "counters": {n: c.value for n, c in counters.items()},
            "gauges": {n: g.value for n, g in gauges.items()},
            "histograms": {n: h.summary() for n, h in histograms.items()},
        }


# The process-wide registry: ``fit``'s spans count here, so whoever
# holds the process reads the sums even when ``fit`` left by an
# exception. Serving engines count into a registry of their own.
REGISTRY = MetricsRegistry()

_annotations = None  # (TraceAnnotation, StepTraceAnnotation), on first use


def _annotation_types():
    """``jax.profiler``'s two annotation classes, imported on first use:
    this module stays importable without jax, so a process that only
    counts never pays for the import."""
    global _annotations
    if _annotations is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        _annotations = (TraceAnnotation, StepTraceAnnotation)
    return _annotations


class span:
    """``with span(name, counter, **attrs):`` times a block twice over.

    1. It enters a ``jax.profiler.TraceAnnotation(name, **attrs)`` (a
       ``StepTraceAnnotation`` where ``step_num`` is given): under any
       profiler session (``fit(profile_dir=)``, ``--profiler-port``,
       a ``jax.profiler.trace`` around the caller) the span lands on
       the host plane of the ``.xplane.pb`` on the device trace's own
       clock, nested under the enclosing span of its thread. With no
       session it costs the annotation's flag check.
    2. It adds the elapsed microseconds to ``<counter>_us`` and one to
       ``<counter>_n`` of ``registry`` (:data:`REGISTRY` by default),
       always, exception or not. ``counter=None`` counts nothing.

    A block that learns what it was only while it runs may say so
    before it leaves: ``sp.counter = ...`` and ``sp.set(kind=...)``
    (metadata added to the open annotation). After the block,
    ``start_ns`` and ``elapsed_ns`` hold its ``perf_counter_ns`` start
    and duration.
    """

    __slots__ = ("counter", "registry", "start_ns", "elapsed_ns", "_ann")

    def __init__(self, name: str, counter: str | None = None, *,
                 registry: MetricsRegistry | None = None, **attrs):
        plain, step = _annotation_types()
        self._ann = (step if "step_num" in attrs else plain)(name, **attrs)
        self.counter = counter
        self.registry = REGISTRY if registry is None else registry
        self.start_ns = self.elapsed_ns = 0

    def set(self, **attrs) -> None:
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_ns = time.perf_counter_ns() - self.start_ns
        self._ann.__exit__(*exc)
        if self.counter is not None:
            self.registry.add_elapsed(self.counter, self.elapsed_ns)

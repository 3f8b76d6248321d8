"""Request-side data types for generative serving.

These are the handoff objects between the async front (ASGI handlers,
the collector) and the decode thread: one :class:`GenRequest` per
in-flight generation, :class:`_SyncSink` adapting the synchronous
``generate_text`` path onto the same batch machinery, and
:class:`_PrefixEntry` describing one cached shared-prompt prefix.
Split out of ``engine.py`` (r04) so the batch lifecycle, the prefix
cache, and the speculation phase can live in modules of their own —
they all speak in these types.
"""

from __future__ import annotations

import asyncio
import collections
import threading
import time

from mlapi_tpu.serving import faults
from mlapi_tpu.utils.metrics import MetricsRegistry


class DeadlineExceeded(Exception):
    """A request's wall-clock deadline passed before its generation
    finished: delivered IN-BAND as the stream's terminal error frame
    (NDJSON ``{"error": ..., "code": "deadline_exceeded"}``) and
    mapped to 504 on unary paths. ``stage`` records which dispatch
    boundary noticed — ``queued`` (never dispatched), ``prefill``
    (mid prompt ingestion), or ``decode`` — the same split the
    ``deadline_expired_{stage}`` counters export."""

    code = "deadline_exceeded"

    def __init__(self, stage: str, budget_ms: float | None = None):
        extra = (
            f" (budget {budget_ms:.0f} ms)" if budget_ms is not None else ""
        )
        super().__init__(f"deadline exceeded while {stage}{extra}")
        self.stage = stage


class DrainCancelled(Exception):
    """The server's drain budget ran out with this stream still in
    flight: a proper terminal frame (503-mapped — the client should
    retry against a live replica), not a dropped connection."""

    code = "draining"

    def __init__(self):
        super().__init__("server draining: generation cancelled")


class LatencyStats:
    """Bounded reservoir of per-request latency samples, recorded at
    token DELIVERY time (the ``push`` seam every serving path funnels
    through — chunked, fused, speculative, interleaved): TTFT is
    submit→first-chunk, inter-token is the per-token share of each
    chunk gap. One instance per engine; ``/metrics`` and the bench
    read :meth:`summary`. Thread-safe (pushes come from the decode
    thread, scrapes from the event loop); bounded so a long-lived
    server's memory stays flat.

    ``sums`` holds the engine's elapsed-time sums (``<counter>_us`` /
    ``<counter>_n`` pairs, ``utils.metrics.span``'s currency): the
    request-life waits recorded here at first delivery, and the
    dispatch thread's ``sched_unit_<kind>``, ``readback_wait`` and
    ``sched_idle`` spans. ``/metrics`` exports them as ``generate.*``."""

    def __init__(self, cap: int = 2048):
        self._ttft_ms: collections.deque = collections.deque(maxlen=cap)
        self._itl_ms: collections.deque = collections.deque(maxlen=cap)
        self._lock = threading.Lock()
        self.sums = MetricsRegistry()

    def record_first(self, ms: float) -> None:
        with self._lock:
            self._ttft_ms.append(ms)

    def record_gap(self, ms_per_token: float) -> None:
        with self._lock:
            self._itl_ms.append(ms_per_token)

    @staticmethod
    def _q(xs: list, q: float) -> float | None:
        """Quantile pick; ``xs`` must already be sorted."""
        if not xs:
            return None
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    def summary(self) -> dict:
        """p50/p95 of both series (ms; ``None`` until samples exist).
        Each reservoir is sorted ONCE per call — this sits on the
        admission-estimate path of every deadlined submit, where a
        per-quantile re-sort of 2048 samples would be the dominant
        cost."""
        with self._lock:
            t, i = list(self._ttft_ms), list(self._itl_ms)
        t.sort()
        i.sort()
        r = lambda v: None if v is None else round(v, 2)  # noqa: E731
        return {
            "ttft_p50_ms": r(self._q(t, 0.50)),
            "ttft_p95_ms": r(self._q(t, 0.95)),
            "intertoken_p50_ms": r(self._q(i, 0.50)),
            "intertoken_p95_ms": r(self._q(i, 0.95)),
        }


def _record_push(sink, item) -> None:
    """Shared delivery-time bookkeeping for GenRequest/_SyncSink: fold
    this chunk into the engine's latency reservoirs."""
    if sink.stats is None or not isinstance(item, dict):
        return
    now = time.perf_counter()
    n = len(item.get("token_ids", ())) or 1
    if sink.t_last is None:
        sink.stats.record_first((now - sink.t0) * 1e3)
        if sink.t_claim is not None:
            # The request's life in two waits, on the stamps it
            # carries: submit → the scheduler's claim, claim → first
            # token (formation or admission, prefill, readback).
            sums = sink.stats.sums
            sums.add_elapsed("queue_wait", int((sink.t_claim - sink.t0) * 1e9))
            sums.add_elapsed("prefill_wait", int((now - sink.t_claim) * 1e9))
    else:
        sink.stats.record_gap((now - sink.t_last) * 1e3 / n)
    sink.t_last = now


class GenRequest:
    """One in-flight generation request: its encoded prompt plus an
    asyncio queue the decode loop feeds with token chunks (and a
    ``None`` sentinel when done)."""

    __slots__ = (
        "row", "used", "n_new", "temperature", "seed", "queue", "loop",
        "cancelled", "top_k", "top_p", "stream",
        "prefix_fp", "prefix_kv", "prefix_len", "prefix_lo",
        "prompt_tokens", "stats", "t0", "t_claim", "t_last", "deadline",
        "push_to", "pushed", "staged", "adapter", "tenant",
        "on_done", "_done_fired", "rid",
    )

    def __init__(self, row, used, n_new, temperature, seed, loop,
                 top_k=0, top_p=1.0, prefix=None, stream=False,
                 stats: LatencyStats | None = None,
                 deadline_ms: float | None = None,
                 push_to=None, pushed=None, adapter=None,
                 tenant: str = ""):
        self.row = row            # [bucketed] int32 ids, left-padded
        self.used = used          # real prompt tokens in the row
        self.n_new = n_new
        self.temperature = temperature
        self.seed = seed
        self.loop = loop
        self.top_k = top_k        # 0 disables
        self.top_p = top_p        # 1.0 disables
        # Incremental consumer (NDJSON stream or a stop-sequence
        # watcher): the decode loop keeps at most one chunk in
        # flight so tokens land promptly; non-incremental requests
        # let the loop chain every chunk and sync once (the
        # dispatch-bound single-stream win through a high-RTT
        # attach).
        self.stream = stream
        # Shared-prefix KV entry (the engine's prefix cache); only
        # same-prefix requests batch together.
        if prefix is not None:
            self.prefix_fp = prefix.fp
            self.prefix_kv = prefix.kv
            self.prefix_len = prefix.bucket
            self.prefix_lo = prefix.lo
            # Tokens that actually conditioned the output = prefix
            # real tokens + suffix real tokens (`used` stays the
            # suffix-row count — it drives the pad mask).
            self.prompt_tokens = prefix.used + used
        else:
            self.prefix_fp = None
            self.prefix_kv = None
            self.prefix_len = 0
            self.prefix_lo = 0
            self.prompt_tokens = used
        # Prefill/decode disaggregation (r18, serving/kv_peer.py).
        # push_to = (host, port, xfer): this is a PREFILL-ONLY run on
        # a prefill-role replica — the prompt's KV streams to the
        # named decode replica chunk by chunk and the request ends at
        # its first token. pushed = a PushedKV: this request's prompt
        # KV arrived over the wire — formation installs it instead of
        # prefilling. Both None (every non-disaggregated request):
        # bit-identical to the fields never existing.
        self.push_to = push_to
        self.pushed = pushed
        # Per-tenant LoRA adapter id (serving/adapter_store.py), or
        # None for the base model. _encode resolved it into the HOST
        # store before this request was queued; batch formation turns
        # it into a resident device slot. Requests with different
        # adapters still co-batch (the gathered BGMV path).
        self.adapter = adapter
        # Quota/fairness identity (serving/registry.py TenantLedger,
        # r22): the tenant whose page/slot quota this request reserves
        # against and whose weight scales its deadline slack. Empty =
        # the anonymous tenant (unquotaed, weight 1.0).
        self.tenant = tenant
        # Fired EXACTLY ONCE at this request's terminal frame — normal
        # end, error, deadline, drain, or scheduler stop — so the
        # tenant ledger's live-depth accounting balances on every
        # delivery path. Set by engine.submit; None elsewhere.
        self.on_done = None
        self._done_fired = False
        self.queue: asyncio.Queue = asyncio.Queue()
        self.cancelled = False    # set when the consumer disconnects
        # Staged-for-admission ONCE marker (collector dispatch): a
        # candidate a lane deferred re-dispatches as its own group
        # instead of being re-staged forever.
        self.staged = False
        # Engine latency reservoirs (None for warmup requests): TTFT
        # and inter-token samples recorded as chunks are pushed.
        self.stats = stats
        self.t0 = time.perf_counter()
        # Stamped (``perf_counter``) each time the dispatch thread
        # claims the request for device work: a lane's formation or a
        # live lane's admission. The last claim before the first token
        # splits TTFT into queue wait and prefill wait.
        self.t_claim: float | None = None
        self.t_last: float | None = None
        # The engine's ordinal of this request (``engine.submit``);
        # rides the ``sched.unit`` span that first serves it.
        self.rid = 0
        # Absolute expiry on the ``t0`` clock (``perf_counter``):
        # every dispatch boundary the scheduler owns checks it via
        # ``engine._expire_if_due`` and cancels the row exactly like a
        # client disconnect, after pushing the terminal
        # :class:`DeadlineExceeded` frame. ``None`` = no deadline —
        # the pre-deadline behavior, bit for bit.
        self.deadline = (
            self.t0 + deadline_ms / 1e3 if deadline_ms else None
        )

    def push(self, item) -> None:
        """Thread-safe enqueue from the decode thread."""
        faults.fire("stream_push")
        _record_push(self, item)
        if item is None or isinstance(item, BaseException):
            self.finish()  # terminal frame: balance the ledger
        self.loop.call_soon_threadsafe(self.queue.put_nowait, item)

    def finish(self) -> None:
        """Terminal-frame hook, idempotent: fires ``on_done`` exactly
        once no matter which delivery path ends the request (normal
        sentinel, error frame, deadline, drain sweep, scheduler stop,
        or a disconnect's :meth:`cancel`). Mutated from the decode
        thread and the event loop, but only ever False→True — a rare
        double-fire race would double-exit the ledger, which ``exit``
        clamps at zero."""
        if self._done_fired:
            return
        self._done_fired = True
        cb = self.on_done
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — bookkeeping must not kill delivery
                pass

    def cancel(self) -> None:
        """Consumer is gone: tell the decode loop to stop spending
        device time on this row (a plain bool — read cross-thread,
        worst case one extra chunk decodes). The tenant ledger exits
        here too — a disconnected row may retire without a terminal
        push."""
        self.cancelled = True
        self.finish()


class _PrefixEntry:
    """One cached shared-prompt prefix: its device-resident KV (a
    ``[1, bucket]``-shaped cache pytree), the bucket it was padded to,
    its own left-pad ``lo``, and the real token count."""

    __slots__ = ("fp", "kv", "bucket", "lo", "used")

    def __init__(self, fp, kv, bucket, lo, used):
        self.fp = fp
        self.kv = kv
        self.bucket = bucket
        self.lo = lo
        self.used = used


class _SyncSink:
    """Adapter so the synchronous ``generate_text`` path reuses
    ``_run_batch`` verbatim: collects token chunks into a list instead
    of an asyncio queue."""

    def __init__(self, req: "GenRequest", out_ids: list):
        self.row, self.used, self.n_new = req.row, req.used, req.n_new
        self.temperature, self.seed = req.temperature, req.seed
        self.top_k, self.top_p = req.top_k, req.top_p
        self.prefix_fp, self.prefix_kv = req.prefix_fp, req.prefix_kv
        self.prefix_len, self.prefix_lo = req.prefix_len, req.prefix_lo
        self.stream = req.stream
        self.stats, self.t0, self.t_last = req.stats, req.t0, None
        self.t_claim, self.rid = None, req.rid
        self.deadline = req.deadline
        self.push_to, self.pushed = req.push_to, req.pushed
        self.adapter = req.adapter
        self.tenant = req.tenant
        self._out = out_ids
        self.error: Exception | None = None
        self.cancelled = False
        self.staged = False

    def finish(self) -> None:
        """Parity no-op: the sync path never enters the tenant
        ledger (``engine.submit`` owns enter/exit), but shared
        terminal seams call ``finish`` on every sink type."""

    def push(self, item) -> None:
        faults.fire("stream_push")
        _record_push(self, item)
        if isinstance(item, Exception):
            self.error = item
        elif item is not None:
            self._out.extend(item["token_ids"])

    def cancel(self) -> None:
        """Parity with GenRequest: deadline expiry / drain cancel the
        sink the same way (the decode loop stops scheduling it)."""
        self.cancelled = True

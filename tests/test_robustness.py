"""Serving robustness (r12): deadlines, SLO admission control,
graceful drain, and the deterministic fault-injection harness.

The load-bearing properties:

- **Deadlines end cleanly at every lifecycle stage.** Expiry while
  queued / mid-prefill / mid-decode produces a terminal
  ``DeadlineExceeded`` frame (504 unary, a ``deadline_exceeded``-coded
  NDJSON frame on streams) through the SAME cancellation machinery
  client disconnects use — rows free, pages release, nothing hangs.
- **Infeasible deadlines shed at the door** with a computed
  retry-after, instead of occupying a slot and timing out later.
- **Drain is graceful**: in-flight streams finish inside the budget,
  new admissions shed 503, ``/healthz`` says ``draining``, and
  budget-overrunning streams get proper ``DrainCancelled`` frames.
- **Conservation under injected failure** (the fault matrix): after
  ANY armed fault at ANY registered point, page refcounts return to
  baseline, every stream ends in a well-formed terminal frame, and
  the engine serves fresh work.

Faults are armed via ``serving/faults.py`` (the ``MLAPI_FAULTS``
grammar) — deterministic call-count triggers, zero overhead disarmed.
"""

import asyncio
import time

import httpx
import jax
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving import build_app, faults
from mlapi_tpu.serving.scoring import OverloadedError, ScorePath
from mlapi_tpu.serving.engine import TextGenerationEngine, _SyncSink
from mlapi_tpu.serving.paged_pool import PagePoolExhausted
from mlapi_tpu.serving.requests import DeadlineExceeded, DrainCancelled
from mlapi_tpu.text import ByteTokenizer

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No armed spec may outlive its test — a leaked fault would fail
    unrelated tests in ways that look like real lifecycle bugs."""
    yield
    faults.disarm()


CFG = dict(
    vocab_size=260,
    hidden_size=16,
    num_layers=1,
    num_heads=2,
    max_positions=96,
    compute_dtype="float32",
)

_MODEL = get_model("gpt_lm", **CFG)
_PARAMS = _MODEL.init(jax.random.key(0))


def _engine(**kw) -> TextGenerationEngine:
    kw.setdefault("chunk", 2)  # many dispatch boundaries per request
    kw.setdefault("fused_single", False)  # the chunked (checkable) path
    return TextGenerationEngine(
        _MODEL, _PARAMS, tokenizer=ByteTokenizer(), **kw
    )


async def _collect(gen, timeout=30.0):
    """Drain one stream to its terminal frame: (tokens, error|None).
    Every well-formed stream ends in a ``None`` sentinel or an
    exception — a timeout here IS the hang this file polices."""
    toks: list[int] = []
    while True:
        item = await asyncio.wait_for(gen.queue.get(), timeout)
        if isinstance(item, Exception):
            return toks, item
        if item is None:
            return toks, None
        toks.extend(item["token_ids"])


def _pool_baseline(eng) -> None:
    """The paged conservation invariant: every page back on the free
    list, no residual references (no orphan table rows hold any)."""
    assert eng.kv_pages_in_use == 0, eng.kv_pages_in_use
    ref = eng.pool.ref
    assert int(ref[1:].sum()) == 0, np.nonzero(ref[1:])


def _busy(eng) -> bool:
    """A batch is in flight: on a scheduler lane (the default
    execution model) or in the serial ``_run_batch`` wrapper."""
    return eng.sched_batches_live > 0 or eng._running is not None


async def _settle(eng, timeout=5.0) -> None:
    """Wait for the decode thread to finish its current batch (page
    cleanup runs in the batch's finally)."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while _busy(eng) and loop.time() < deadline:
        await asyncio.sleep(0.02)


# ---------------------------------------------------------------- deadlines


def test_deadline_expires_queued_unary():
    """A deadline already past at formation never reaches the device:
    terminal DeadlineExceeded, stage counter 'queued'."""
    eng = _engine(kv_page_size=4)
    ref = eng.generate_text("hello", max_new_tokens=6)
    with pytest.raises(DeadlineExceeded):
        eng.generate_text("hello", max_new_tokens=6, deadline_ms=1e-4)
    assert eng.deadline_expired_queued == 1
    # The engine is unpoisoned: same request, same stream.
    _pool_baseline(eng)
    again = eng.generate_text("hello", max_new_tokens=6)
    assert again["token_ids"] == ref["token_ids"]


def test_default_deadline_applies_when_request_names_none():
    eng = _engine()
    eng.default_deadline_ms = 1e-4
    with pytest.raises(DeadlineExceeded):
        eng.generate_text("hello", max_new_tokens=4)
    # An explicit generous deadline overrides the default.
    out = eng.generate_text(
        "hello", max_new_tokens=4, deadline_ms=60_000
    )
    assert len(out["token_ids"]) == 4


def test_deadlined_request_declines_fused_fast_path():
    """Fused generation is tier-wide decode CHUNKS, each one typed unit
    with a boundary where the deadline is enforced (the
    whole-generation program, which had no boundary and had to
    decline deadlined requests, left serving with the r20 fold). So a
    deadlined solo request rides the fused widths like its
    deadline-less twin, and the stream is byte-identical."""
    eng = _engine(fused_single=True)
    ref = eng.generate_text("hello", max_new_tokens=6)
    assert eng.fused_calls == 1  # deadline-less solo unary runs fused
    out = eng.generate_text(
        "hello", max_new_tokens=6, deadline_ms=60_000
    )
    assert eng.fused_calls == 2  # no per-path decline gate is left
    assert out["token_ids"] == ref["token_ids"]


async def test_deadline_expires_mid_decode_stream():
    """Deterministic mid-decode expiry: retract the deadline after
    the first chunk arrives — the next chunk boundary must end the
    stream with the terminal frame, free the row, release pages."""
    eng = _engine(kv_page_size=4)
    await eng.start()
    try:
        gen = await eng.submit("abc", max_new_tokens=60, stream=True)
        first = await gen.queue.get()
        assert first["token_ids"]
        gen.deadline = 1e-4  # in the past on the perf_counter clock
        _, err = await _collect(gen)
        assert isinstance(err, DeadlineExceeded), err
        assert eng.deadline_expired_decode >= 1
        await _settle(eng)
        _pool_baseline(eng)
        # The engine still serves.
        ok = await eng.submit("abc", max_new_tokens=3)
        toks, err = await _collect(ok)
        assert err is None and len(toks) == 3
    finally:
        await eng.stop()


async def test_deadline_expires_mid_interleaved_prefill():
    """Stage 'prefill': a long-prompt joiner whose deadline passes
    inside its interleaved chunked-prefill window aborts the window
    (private pages back) with a terminal frame, while the running
    stream is untouched."""
    eng = _engine(
        kv_page_size=4, max_batch=4, prompt_buckets=(4, 8),
    )
    # This test is about EXPIRY; the admission estimator would
    # (correctly) shed the joiner outright here, because an unwarmed
    # test engine's first TTFT samples include XLA compiles.
    eng.admission_control = False
    solo = _engine(prompt_buckets=(4, 8))
    long_p = "abcdefghijklmnopqrst"  # 20 tokens → bucket 24 = 3 chunks
    ref = solo.generate_text("run ab", max_new_tokens=40)
    await eng.start()
    try:
        # Each prefill chunk sleeps, so a ~3-chunk window far outlives
        # the joiner's budget — expiry lands INSIDE the window at a
        # _pf_step boundary, deterministically.
        faults.arm("prefill_chunk:every=1:delay=0.15")
        a = await eng.submit("run ab", max_new_tokens=40, stream=True)
        first = await a.queue.get()
        b = await eng.submit(long_p, max_new_tokens=3, deadline_ms=200)
        _, berr = await _collect(b)
        assert isinstance(berr, DeadlineExceeded), berr
        assert eng.deadline_expired_prefill >= 1
        assert eng.interleaved_prefills == 1  # the window did start
        toks, aerr = await _collect(a)
        assert aerr is None
        assert first["token_ids"] + toks == ref["token_ids"]
        await _settle(eng)
        _pool_baseline(eng)
    finally:
        await eng.stop()


async def test_deadline_http_unary_504_and_stream_frame():
    """HTTP shapes: unary expiry → 504; stream expiry → a terminal
    NDJSON frame carrying code=deadline_exceeded."""
    eng = _engine()
    app = build_app(eng)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://t"
        ) as client:
            r = await client.post(
                "/generate",
                json={"text": "hi", "max_new_tokens": 8,
                      "deadline_ms": 0.001},
            )
            assert r.status_code == 504, r.text
            assert "deadline" in r.json()["detail"]

            r = await client.post(
                "/generate",
                json={"text": "hi", "max_new_tokens": 8,
                      "deadline_ms": 0.001, "stream": True},
            )
            assert r.status_code == 200
            last = [l for l in r.text.splitlines() if l][-1]
            import json as _json

            frame = _json.loads(last)
            assert frame.get("code") == "deadline_exceeded", frame

            r = await client.post(
                "/generate",
                json={"text": "hi", "deadline_ms": -5},
            )
            assert r.status_code == 422
    finally:
        await app.shutdown()


# ------------------------------------------------- admission control


def _seed_latency(eng, ttft_ms=1000.0, itl_ms=50.0, n=40):
    for _ in range(n):
        eng.latency.record_first(ttft_ms)
        eng.latency.record_gap(itl_ms)


def test_admission_estimate_formula():
    """est = backlog/max_batch * (ttft_p95 + default_n * itl_p50)
    + ttft_p95 — and exactly 0 on a cold server (never shed on a
    guess)."""
    eng = _engine()
    assert eng.admission_estimate_ms() == 0.0
    _seed_latency(eng, ttft_ms=1000.0, itl_ms=50.0)
    # Empty queue: just the request's own p95 TTFT.
    assert eng.admission_estimate_ms() == pytest.approx(1000.0)
    with eng._alock:
        eng._deferred.extend(object() for _ in range(2 * eng.max_batch))
    try:
        batch_ms = 1000.0 + eng.default_max_new_tokens * 50.0
        assert eng.admission_estimate_ms() == pytest.approx(
            2 * batch_ms + 1000.0
        )
    finally:
        with eng._alock:
            eng._deferred.clear()


async def test_infeasible_deadline_sheds_with_retry_after():
    eng = _engine()
    _seed_latency(eng, ttft_ms=2000.0)
    await eng.start()
    try:
        with pytest.raises(OverloadedError) as ei:
            await eng.submit("hi", max_new_tokens=4, deadline_ms=100)
        assert eng.shed_deadline_infeasible == 1
        # retry-after ≈ (est - budget) = 1.9 s, floor 1 s.
        assert 1.0 <= ei.value.retry_after_s <= 3.0
        # No deadline → no estimate gate: the request proceeds.
        g = await eng.submit("hi", max_new_tokens=4)
        toks, err = await _collect(g)
        assert err is None and len(toks) == 4
        # --no-admission-control: deadlined requests aren't estimated
        # (the deadline itself still enforces downstream).
        eng.admission_control = False
        g = await eng.submit("hi", max_new_tokens=4, deadline_ms=100)
        await _collect(g)
        assert eng.shed_deadline_infeasible == 1
    finally:
        await eng.stop()


def test_brownout_level_thresholds():
    eng = _engine(max_queue=8)
    assert eng._brownout_level() == 0
    with eng._alock:
        eng._deferred.extend(object() for _ in range(4))
    assert eng._brownout_level() == 1  # >= 50%
    with eng._alock:
        eng._deferred.extend(object() for _ in range(2))
    assert eng._brownout_level() == 2  # >= 75%
    eng.admission_control = False
    assert eng._brownout_level() == 0  # ladder disabled
    with eng._alock:
        eng._deferred.clear()


async def test_brownout_clamps_tokens_and_suppresses_spec(monkeypatch):
    eng = _engine()
    await eng.start()
    try:
        monkeypatch.setattr(eng, "_brownout_level", lambda: 1)
        g = await eng.submit(
            "hi", max_new_tokens=2 * eng.default_max_new_tokens
        )
        toks, err = await _collect(g)
        assert err is None
        assert len(toks) == eng.default_max_new_tokens  # clamped
        assert eng.brownout_tokens_clamped == 1
        # The production spec lever (BatchRun._spec_brownout): blocks
        # under pressure, and its counter ticks at most ONCE per batch
        # run however many chunk boundaries re-confirm the block.
        from mlapi_tpu.serving.batch_run import BatchRun

        br = BatchRun.__new__(BatchRun)
        br.eng = eng
        br._spec_supp_counted = False
        before = eng.brownout_spec_suppressed
        assert br._spec_brownout() is True
        assert br._spec_brownout() is True
        assert eng.brownout_spec_suppressed == before + 1
    finally:
        await eng.stop()


# ------------------------------------------------------------- drain


async def test_drain_completes_inflight_then_sheds():
    """Graceful path: the in-flight stream runs to completion inside
    the budget while new admissions shed 503 + retry-after."""
    eng = _engine(kv_page_size=4)
    await eng.start()
    try:
        gen = await eng.submit("abcd", max_new_tokens=30, stream=True)
        first = await gen.queue.get()
        drain = asyncio.create_task(eng.drain(20.0))
        await asyncio.sleep(0.05)
        assert eng.draining
        with pytest.raises(OverloadedError):
            await eng.submit("x", max_new_tokens=2)
        assert eng.shed_draining == 1
        toks, err = await _collect(gen)
        assert err is None
        assert len(first["token_ids"]) + len(toks) == 30  # ran to the end
        await asyncio.wait_for(drain, 20)
        _pool_baseline(eng)
    finally:
        await eng.stop()


async def test_drain_timeout_cancels_with_terminal_frames():
    """A stream outliving the budget (a slow dispatch inside the
    drain window — injected delay) gets a proper DrainCancelled
    terminal frame; pages return to baseline."""
    eng = _engine(kv_page_size=4)
    await eng.start()
    try:
        faults.arm("decode:every=1:delay=0.05")
        gen = await eng.submit("ab", max_new_tokens=80, stream=True)
        await gen.queue.get()
        await eng.drain(0.3)
        toks, err = await _collect(gen)
        assert isinstance(err, DrainCancelled), (len(toks), err)
        assert eng.faults_injected > 0  # the slow window was real
        await _settle(eng)
        _pool_baseline(eng)
    finally:
        faults.disarm()
        await eng.stop()


async def test_drain_sees_collector_forming_window():
    """A request the collector has claimed off the queue but not yet
    handed to the decode thread (the straggler-collection window) is
    in neither the queue, the staging lists, nor ``_running`` — drain
    must still count it as in-flight work. A premature "idle" verdict
    here turns the claimed stream into an opaque engine-stopped 500
    when the e2e shutdown path stops the engine right after."""
    eng = _engine(max_wait_ms=250.0)  # long straggler window
    await eng.start()
    try:
        gen = await eng.submit("hi", max_new_tokens=4, stream=True)
        await asyncio.sleep(0.05)  # claimed, sitting in the window
        await asyncio.wait_for(eng.drain(20.0), 25)
        await eng.stop()  # what lifespan.shutdown does next
        toks, err = await _collect(gen)
        assert err is None, err
        assert len(toks) == 4
    finally:
        await eng.stop()


async def test_submit_sheds_when_drain_completes_mid_encode():
    """submit() passed the front-door draining check, then suspended
    in the encode executor while drain() completed (idle engine) and
    stop() flushed the queue — the late enqueue would land in a queue
    no collector will ever pop: a stream with no terminal frame. The
    post-encode re-check sheds it exactly like the front door."""
    from mlapi_tpu.serving.scoring import OverloadedError

    eng = _engine()
    await eng.start()
    try:
        real = eng._encode

        def slow_encode(*a, **kw):
            time.sleep(0.4)  # hold submit inside the executor await
            return real(*a, **kw)

        eng._encode = slow_encode
        task = asyncio.create_task(eng.submit("hi", max_new_tokens=2))
        await asyncio.sleep(0.1)  # submit is inside the executor
        await asyncio.wait_for(eng.drain(1.0), 10)  # idle: instant
        await eng.stop()
        with pytest.raises(OverloadedError):
            await asyncio.wait_for(task, 10)
        assert eng.shed_draining >= 1
    finally:
        await eng.stop()


async def test_drain_sweep_covers_collector_carry():
    """The collector's window-incompatible leftovers (``_carry``) are
    in neither the queue, the staging lists, nor a formed batch — the
    budget-exhausted sweep must deliver their DrainCancelled frames
    too, not leave them for a post-budget batch run (followed by an
    opaque engine-stopped 500 at stop())."""
    eng = _engine(kv_page_size=4)
    await eng.start()
    try:
        out: list = []
        sink = _SyncSink(eng._encode("abc", 3, 0.0, 0, None), out)
        eng._carry.append(sink)
        await asyncio.wait_for(eng.drain(0.0), 10)
        assert isinstance(sink.error, DrainCancelled), sink.error
        assert sink.cancelled
    finally:
        eng._carry.clear()
        await eng.stop()


async def test_microbatcher_drain_budget_sheds_queued_503():
    """Budget-exhausted drain sheds still-QUEUED entries with the
    documented OverloadedError (503 + retry-after) — not the opaque
    RuntimeError("batcher stopped") 500 that stop() would raise."""
    from tests.test_batcher import FakeEngine

    eng = FakeEngine()
    b = ScorePath(eng, max_batch=4, max_wait_ms=0.0, max_inflight=1)
    await b.start()
    try:
        row = np.zeros(4, np.float32)
        eng.gate.clear()  # wedge the device
        t_block = asyncio.create_task(b.submit(row))  # holds the slot
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 10.0
        while b.device_calls < 1:  # plug batch is in the executor
            assert loop.time() < deadline
            await asyncio.sleep(0.01)
        t_queued = asyncio.create_task(b.submit(row))  # stuck in queue
        await asyncio.sleep(0.02)
        await asyncio.wait_for(b.drain(0.2), 10)  # budget expires
        with pytest.raises(OverloadedError):
            await asyncio.wait_for(t_queued, 5)
        eng.gate.set()  # let the dispatched batch finish cleanly
        await asyncio.wait_for(t_block, 10)
    finally:
        eng.gate.set()
        await b.stop()


async def test_drain_e2e_healthz_and_shed_over_http():
    """End-to-end: lifespan shutdown flips /healthz to "draining",
    in-flight NDJSON streams finish with their done frame, and new
    /generate requests shed 503 with retry-after."""
    eng = _engine()
    app = build_app(eng, drain_timeout_s=20.0)
    await app.startup()
    transport = httpx.ASGITransport(app=app)
    client = httpx.AsyncClient(transport=transport, base_url="http://t")
    try:
        stream_task = asyncio.create_task(
            client.post(
                "/generate",
                json={"text": "abcd", "max_new_tokens": 40,
                      "stream": True},
            )
        )
        # Wait until the stream is actually decoding (bounded: a
        # stream that already finished must not spin this forever).
        t_end = asyncio.get_running_loop().time() + 30.0
        while not _busy(eng) and not stream_task.done():
            assert asyncio.get_running_loop().time() < t_end
            await asyncio.sleep(0.01)
        shutdown = asyncio.create_task(app.shutdown())
        while not eng.draining:
            await asyncio.sleep(0.01)
        r = await client.get("/healthz")
        assert r.json()["status"] == "draining"
        r = await client.post("/generate", json={"text": "x"})
        assert r.status_code == 503
        assert int(r.headers["retry-after"]) >= 1
        resp = await asyncio.wait_for(stream_task, 30)
        assert resp.status_code == 200
        import json as _json

        last = _json.loads(
            [l for l in resp.text.splitlines() if l][-1]
        )
        assert last.get("done") is True, last  # finished, not killed
        await asyncio.wait_for(shutdown, 30)
    finally:
        await client.aclose()


async def test_microbatcher_drain_and_deadline():
    from tests.test_batcher import FakeEngine

    eng = FakeEngine()
    b = ScorePath(eng, max_batch=4, max_wait_ms=0.0, max_inflight=1)
    await b.start()
    try:
        row = np.zeros(4, np.float32)
        # Deadline: block the device so the queue backs up past the
        # budget; the collector must fail the expired entry with
        # DeadlineExceeded (504), not serve it late.
        eng.gate.clear()
        t_block = asyncio.create_task(b.submit(row))  # occupies the slot
        await asyncio.sleep(0.05)
        t_late = asyncio.create_task(b.submit(row, deadline_ms=10))
        await asyncio.sleep(0.1)  # budget passes while queued
        eng.gate.set()
        with pytest.raises(DeadlineExceeded):
            await asyncio.wait_for(t_late, 10)
        assert b.deadline_expired == 1
        await asyncio.wait_for(t_block, 10)
        # Drain: sheds while draining.
        await b.drain(1.0)
        with pytest.raises(OverloadedError):
            await b.submit(row)
        assert b.shed_draining == 1
    finally:
        await b.stop()


# ---------------------------------- the mid-admission leak window (pinned)


async def test_admission_install_fault_spares_running_batch():
    """THE r12 leak-window pin: alloc-then-raise during the admission
    install leaves kv_pages_in_use at its pre-admission value, the
    rejected joiner gets a clean terminal error, and the running
    batch streams on token-identical to an unfaulted run."""
    # One 64-token page covers the runner's whole cache window, so
    # kv_pages_in_use is CONSTANT for the batch's lifetime — the
    # pre-admission value is deterministic, not a racing snapshot.
    solo = _engine(kv_page_size=64)
    ref = solo.generate_text("abcdef", max_new_tokens=24)
    eng = _engine(kv_page_size=64, max_batch=4)
    await eng.start()
    try:
        a = await eng.submit("abcdef", max_new_tokens=24, stream=True)
        first = await a.queue.get()
        pre = eng.kv_pages_in_use
        assert pre == 1  # the runner's single page
        # table_install raises AFTER the joiner's page allocation
        # (alloc-then-raise); the decode delay keeps the running
        # batch alive across the assertion window.
        faults.arm("table_install:raise,decode:every=1:delay=0.02")
        b = await eng.submit("xyz", max_new_tokens=4)
        _, berr = await _collect(b)
        assert isinstance(berr, faults.InjectedFault), berr
        # Pre-admission refcount restored WHILE the batch still runs
        # (the joiner's freshly-mapped page went back). The release
        # runs on the decode thread AFTER b's terminal frame — wait
        # on the counter instead of racing it (mlapi-lint MLA009,
        # caught r19: the same class as the r17/r18 hand-de-flakes).
        deadline = asyncio.get_running_loop().time() + 30.0
        while eng.kv_pages_in_use != pre:
            assert (
                asyncio.get_running_loop().time() < deadline
            ), eng.kv_pages_in_use
            await asyncio.sleep(0.005)
        assert eng.kv_pages_in_use == pre
        toks, aerr = await _collect(a)
        assert aerr is None
        assert first["token_ids"] + toks == ref["token_ids"]
        await _settle(eng)
        _pool_baseline(eng)
        faults.disarm()
        c = await eng.submit("xyz", max_new_tokens=4)
        toks, cerr = await _collect(c)
        assert cerr is None and len(toks) == 4
    finally:
        await eng.stop()


async def test_pool_exhausted_mid_admission_maps_to_503():
    """An injected PagePoolExhausted on the admission path reaches
    the client as 503 (capacity, not a 500) — via the in-band
    terminal-frame mapping."""
    eng = _engine(kv_page_size=4)
    app = build_app(eng)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://t"
        ) as client:
            faults.arm("pool_alloc:raise")
            r = await client.post(
                "/generate", json={"text": "hi", "max_new_tokens": 4}
            )
            assert r.status_code == 503, r.text
            assert "retry-after" in r.headers
            faults.disarm()
            r = await client.post(
                "/generate", json={"text": "hi", "max_new_tokens": 4}
            )
            assert r.status_code == 200
    finally:
        await app.shutdown()


# ------------------------------------------------------- fault matrix


_SPEC_MODEL = get_model("gpt_lm", **CFG)
_SPEC_PARAMS = _SPEC_MODEL.init(jax.random.key(1))


def _matrix_engine() -> TextGenerationEngine:
    """One engine shape that exercises EVERY injection point: paged
    (pool_alloc / table_install), a draft (spec_verify), small
    prompt buckets so a 20-token prompt takes the chunked-prefill
    path (prefill_chunk), chunk=2 decode (decode), the async
    collector (collector_pop), streams (stream_push), and the host
    tier (tier_spill / tier_restore — the prefix evict/restore leg
    in ``_matrix_traffic``)."""
    return TextGenerationEngine(
        _SPEC_MODEL, _SPEC_PARAMS, tokenizer=ByteTokenizer(),
        chunk=2, fused_single=False, kv_page_size=4, max_batch=4,
        prompt_buckets=(4, 8), draft=(_SPEC_MODEL, _SPEC_PARAMS),
        spec_k=3, kv_tier_bytes=1 << 22,
    )


async def _submit_or_outcome(eng, *a, **kw):
    """submit() may itself fail terminally under an armed fault (a
    dead collector raises RuntimeError; shedding raises
    OverloadedError) — both are WELL-FORMED outcomes, not hangs."""
    try:
        return await eng.submit(*a, **kw), None
    except (RuntimeError, OverloadedError) as e:
        return None, ([], e)


async def _matrix_traffic(eng, tier_leg: bool = False) -> list:
    """Deterministic traffic hitting every seam; returns each
    stream's (tokens, terminal) — raising only on a HANG (wait_for),
    never on an in-band error frame. ``tier_leg`` adds the prefix
    evict/restore rounds that cross the tier_spill / tier_restore
    seams (enabled only for those points — the other 14 cases keep
    the r12 traffic and the r12 runtime)."""
    outcomes = []
    # Solo greedy → speculation engages (spec_verify); streams push.
    g1, out = await _submit_or_outcome(
        eng, "spec ab", max_new_tokens=10, stream=True
    )
    outcomes.append(out if g1 is None else await _collect(g1))
    # Long prompt → chunked prefill; a mid-batch joiner → admission
    # install (+ interleaved window when the long one runs).
    g2, out = await _submit_or_outcome(
        eng, "abcdefghijklmnopqrst", max_new_tokens=8, stream=True
    )
    if g2 is None:
        outcomes.append(out)
    else:
        first = await asyncio.wait_for(g2.queue.get(), 30)
        g3, out3 = await _submit_or_outcome(
            eng, "join", max_new_tokens=4
        )
        if isinstance(first, Exception) or first is None:
            # The stream's FIRST frame was already terminal.
            outcomes.append(
                ([], first if isinstance(first, Exception) else None)
            )
        else:
            t2, e2 = await _collect(g2)
            outcomes.append((first["token_ids"] + t2, e2))
        outcomes.append(
            out3 if g3 is None else await _collect(g3)
        )
    # Prefix evict/restore over the host tier: the entry's page set
    # spills on eviction (tier_spill) and the re-arrival restores it
    # from the blob (tier_restore); a raise at either point must
    # degrade to the pre-tier discard / cold path with the stream
    # still completing. The final evict returns the pool to the
    # page-conservation baseline (prefix entries hold pages by
    # design; a baseline sweep is not a leak).
    if tier_leg and eng.pool is not None and eng.kv_tier is not None:
        g4, out4 = await _submit_or_outcome(
            eng, " q", max_new_tokens=4, prefix="matrix sys"
        )
        outcomes.append(out4 if g4 is None else await _collect(g4))
        await _settle(eng, 10)
        eng.pool.evict_idle(1)           # spill seam
        g5, out5 = await _submit_or_outcome(
            eng, " q", max_new_tokens=4, prefix="matrix sys"
        )
        outcomes.append(out5 if g5 is None else await _collect(g5))
        await _settle(eng, 10)
        eng.pool.evict_idle(1)           # back to the page baseline
    return outcomes


class _ScoreStub:
    """A scoring engine whose label is its row's first feature."""

    max_batch = 16

    def predict_labels(self, batch):
        return [str(float(r[0])) for r in batch], np.full(len(batch), 0.5)


async def _score_traffic(eng) -> list:
    """The scoring leg: a co-resident ``ScorePath`` dispatching its
    micro-batch as a typed ``score`` unit on this engine's scheduler —
    the only traffic that crosses ``score_dispatch``. Outcomes are
    shaped like streams': (result, error-or-None)."""
    sp = ScorePath(_ScoreStub(), model_id="clf", max_wait_ms=0.0,
                   sched_source=lambda: eng.sched)
    await sp.start()
    try:
        res = await asyncio.wait_for(
            asyncio.gather(*[sp.submit(np.full(4, float(i)))
                             for i in range(3)], return_exceptions=True),
            30,
        )
    finally:
        await sp.stop()
    return [([], r) if isinstance(r, Exception) else ([r[0]], None)
            for r in res]


# The engine-lifecycle seams: this matrix drives ENGINE traffic, so
# the router↔replica hop (`router_forward`, which only a router in
# front of replica servers crosses) has its own matrix —
# test_router.py pins raise-at-submit (single failover, no duplicate
# submit), raise-mid-stream (well-formed terminal frame), and delay
# (slowed, byte-complete); test_router_e2e.py pins page-refcount
# conservation on real paged replicas under the same faults. The
# unit-dispatch seam (`sched_unit`, crossed only with --scheduler on)
# likewise has its own matrix in test_scheduler.py: a raise kills one
# lane with pages conserved while the other lane streams on.
_ENGINE_POINTS = tuple(
    p for p in faults.POINTS
    if p not in (
        "router_forward", "sched_unit",
        # The peer-fetch hop (crossed only with --kv-peer-fetch on a
        # hinted replica) has its matrix in test_kv_peer.py: a raise
        # at either point degrades to the cold prefill with pages
        # conserved and streams completing.
        "peer_fetch", "peer_serve",
        # The disaggregation push seams (crossed only on role-split
        # replicas) have their matrix in test_kv_push.py: a raise at
        # either point fails the transfer and the decode replica
        # cold-prefills with kv_pages_in_use conserved on both ends.
        "kv_push_send", "kv_push_recv",
        # The adapter seams (crossed only with --adapter-slots > 0 and
        # a request naming a tenant) have their matrix in
        # test_lora_serving.py: a fetch raise is a counted miss / 404,
        # an install raise rejects the joiner with the pool intact.
        "adapter_fetch", "adapter_install",
    )
)


@pytest.mark.parametrize("action", ["raise", "delay=0.02"])
@pytest.mark.parametrize("point", _ENGINE_POINTS)
async def test_fault_matrix_conservation(point, action):
    """The tentpole invariant sweep: arm each registered point with
    each action, run traffic over every seam, and assert the
    conservation contract — streams TERMINATE (frame or sentinel,
    never a hang), pages/refcounts return to baseline, and the engine
    serves a fresh request afterwards."""
    eng = _matrix_engine()
    await eng.start()
    try:
        faults.arm(f"{point}:{action}")
        outcomes = await _matrix_traffic(
            eng, tier_leg=point.startswith("tier_")
        )
        if point == "score_dispatch":
            outcomes += await _score_traffic(eng)
        if action == "delay=0.02":
            # Delays slow, never break: every stream must COMPLETE.
            for toks, err in outcomes:
                assert err is None, (point, err)
            assert eng.faults_injected > 0
        faults.disarm()
        await _settle(eng, 10)
        _pool_baseline(eng)
        # The engine accepts new work afterward (a dead collector —
        # the collector_pop kill — recovers via stop/start).
        if eng._task.done():
            await eng.stop()
            await eng.start()
        fresh = await eng.submit("after", max_new_tokens=4)
        toks, err = await _collect(fresh)
        assert err is None and len(toks) == 4, (point, action, err)
    finally:
        faults.disarm()
        await eng.stop()


@pytest.mark.heavy
async def test_faulted_admission_churn_soak():
    """Soak: repeated faulted admission churn (every 3rd page alloc
    raises) over many rounds must keep the pool conserved and the
    engine serving — the leak-window fix under sustained fire."""
    eng = _engine(kv_page_size=4, max_batch=4, prompt_buckets=(4, 8))
    await eng.start()
    try:
        for round_i in range(12):
            faults.arm("pool_alloc:every=3:times=2")
            gens = [
                await eng.submit(
                    f"soak {round_i} {i}", max_new_tokens=6,
                    stream=bool(i % 2),
                )
                for i in range(3)
            ]
            for g in gens:
                await _collect(g)  # frame or sentinel; hang = fail
            faults.disarm()
            await _settle(eng, 10)
            _pool_baseline(eng)
        out = await eng.submit("final", max_new_tokens=4)
        toks, err = await _collect(out)
        assert err is None and len(toks) == 4
    finally:
        faults.disarm()
        await eng.stop()


# ------------------------------------------------------- harness unit


def test_fault_spec_grammar():
    rules = faults.parse("pool_alloc:after=3:raise,decode:every=5:delay=0.05")
    assert rules["pool_alloc"].after == 3
    assert rules["pool_alloc"].times == 1  # raise defaults one-shot
    assert rules["decode"].every == 5
    assert rules["decode"].times is None  # delay defaults unlimited
    with pytest.raises(ValueError):
        faults.parse("nonsense:raise")
    with pytest.raises(ValueError):
        faults.parse("decode:bogus=1")
    with pytest.raises(ValueError):
        # after+every in one clause: due() honors a single trigger, so
        # silently preferring one would fire on a schedule the
        # operator did not write — loud instead.
        faults.parse("decode:after=10:every=5:delay=0.05")


def test_fault_triggers_are_call_counted():
    faults.arm("decode:after=2:raise")
    faults.fire("decode")
    faults.fire("decode")  # calls 1-2 pass
    with pytest.raises(faults.InjectedFault):
        faults.fire("decode")  # call 3 fires
    faults.fire("decode")  # one-shot: spent
    assert faults.injected_count() == 1
    faults.disarm()
    faults.fire("decode")  # disarmed: free


def test_disarmed_is_zero_cost_noop():
    faults.disarm()
    for p in faults.POINTS:
        faults.fire(p)
    assert faults.injected_count() == 0


def test_metrics_export_robustness_counters():
    """The /metrics names the dashboards key on exist from request
    zero (not only after the first failure)."""
    eng = _engine(kv_page_size=4)
    app = build_app(eng)

    async def scrape():
        await app.startup()
        try:
            transport = httpx.ASGITransport(app=app)
            async with httpx.AsyncClient(
                transport=transport, base_url="http://t"
            ) as client:
                return (await client.get("/metrics")).json()
        finally:
            await app.shutdown()

    snap = asyncio.run(scrape())
    for name in (
        "generate.shed_queue_full",
        "generate.shed_deadline_infeasible",
        "generate.shed_draining",
        "generate.deadline_expired_queued",
        "generate.deadline_expired_prefill",
        "generate.deadline_expired_decode",
        "generate.brownout_spec_suppressed",
        "generate.brownout_tokens_clamped",
        "generate.faults_injected",
    ):
        assert snap["counters"][name] == 0, name
    assert snap["gauges"]["generate.draining"] == 0


def test_streams_identical_with_faults_disarmed():
    """The acceptance guard in miniature: the robustness layer adds
    ZERO behavior with faults disarmed and no deadline set — greedy
    streams are byte-identical across paged × deadline-checking
    engines (the full {model} × {quant} × {impl} × {layout} identity
    rides the existing suites, which run on this same code)."""
    base = _engine()
    paged = _engine(kv_page_size=4)
    a = base.generate_text("identity", max_new_tokens=16)
    b = paged.generate_text("identity", max_new_tokens=16)
    assert a["token_ids"] == b["token_ids"]
    # A generous deadline changes nothing either.
    c = paged.generate_text(
        "identity", max_new_tokens=16, deadline_ms=600_000
    )
    assert c["token_ids"] == a["token_ids"]

"""Overload behavior: full queues shed immediately (503 +
``Retry-After`` over HTTP, :class:`OverloadedError` at the engine
seam) instead of queueing without bound, and abandoned streams stop
consuming device time.

The reference has no overload story at all — uvicorn's accept queue
is the only backpressure (SURVEY §2: single asyncio loop, blocking
handlers). Here shedding is explicit and observable via /metrics.
"""

import asyncio
import json

import httpx
import jax
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving import InferenceEngine, build_app
from mlapi_tpu.serving.scoring import OverloadedError, ScorePath
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.text import ByteTokenizer
from mlapi_tpu.utils.vocab import LabelVocab

from tests.test_batcher import FakeEngine

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


IRIS_FEATURES = (
    "sepal_length", "sepal_width", "petal_length", "petal_width",
)


@pytest.fixture
def iris_engine():
    """Untrained linear engine — overload mechanics don't care about
    prediction quality, only about queue/shed behavior."""
    model = get_model("linear", num_features=4, num_classes=3)
    return InferenceEngine(
        model,
        model.init(jax.random.key(0)),
        LabelVocab(("Iris-setosa", "Iris-versicolor", "Iris-virginica")),
        IRIS_FEATURES,
    )


GPT_CFG = dict(
    vocab_size=260,
    hidden_size=16,
    num_layers=1,
    num_heads=2,
    max_positions=96,
    compute_dtype="float32",
)


@pytest.fixture
def gen_engine():
    model = get_model("gpt_lm", **GPT_CFG)
    return TextGenerationEngine(
        model,
        model.init(jax.random.key(0)),
        tokenizer=ByteTokenizer(),
    )


async def test_batcher_sheds_fast_when_queue_full():
    """With the device blocked and the queue at 2x capacity, the
    excess requests fail in milliseconds — not after a timeout."""
    eng = FakeEngine()
    eng.gate.clear()  # device "wedged": nothing completes
    b = ScorePath(
        eng, max_batch=4, max_wait_ms=0.0, max_queue=8, max_inflight=1
    )
    await b.start()
    row = np.zeros(4, np.float32)
    try:
        tasks = [asyncio.create_task(b.submit(row)) for _ in range(32)]
        await asyncio.sleep(0.05)  # let the collector drain what it can
        rejected = [
            t
            for t in tasks
            if t.done() and isinstance(t.exception(), OverloadedError)
        ]
        assert rejected, "no request was shed at 4x queue capacity"
        assert b.rejected == len(rejected)
        # Immediacy from task state, not wall-clock (mlapi-lint
        # MLA006, the ADVICE r05 flake class): the device is wedged,
        # so NOTHING can complete by being processed — every task
        # that finished inside the 50 ms window must be a shed, and
        # the device must not have returned a single batch. A
        # timeout-style shed path would leave all 32 tasks pending
        # here (rejected would be empty) instead of failing a clock
        # bound.
        assert all(
            isinstance(t.exception(), OverloadedError)
            for t in tasks if t.done()
        ), "a task completed by processing while the device was wedged"
        assert eng.batch_sizes == [], "the wedged device returned a batch"
        assert b.queue_depth <= 8
    finally:
        eng.gate.set()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await b.stop()


async def test_predict_returns_503_with_retry_after(iris_engine):
    """HTTP contract: queue-full surfaces as 503 + Retry-After, and
    the rejection is visible in /metrics."""
    app = build_app(iris_engine, max_wait_ms=50.0, max_batch=1, max_queue=1)
    await app.startup()
    try:
        # Stall the collector so submissions pile onto the queue: the
        # batch window (50 ms) holds the first request in the
        # collector while the rest hit the 1-deep queue.
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as client:
            payload = {
                "sepal_length": 5.1,
                "sepal_width": 3.5,
                "petal_length": 1.4,
                "petal_width": 0.2,
            }
            rs = await asyncio.gather(
                *(client.post("/predict", json=payload) for _ in range(12))
            )
            codes = sorted(r.status_code for r in rs)
            assert 503 in codes, codes
            assert 200 in codes, codes  # admitted requests still served
            shed = next(r for r in rs if r.status_code == 503)
            assert "retry-after" in shed.headers
            assert int(shed.headers["retry-after"]) >= 1
            m = (await client.get("/metrics")).json()
            assert m["counters"]["batcher.rejected"] >= 1
            assert "batcher.queue_depth" in m["gauges"]
    finally:
        await app.shutdown()


async def test_generate_queue_bounded_503(gen_engine):
    """The generation queue is bounded too: floods of /generate get
    immediate 503s, not unbounded memory growth (VERDICT r2 #5: the
    old queue was unbounded)."""
    engine = gen_engine
    engine.max_queue = 2
    engine.max_wait_s = 0.2  # hold the collector so the queue fills
    app = build_app(engine)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as client:
            rs = await asyncio.gather(
                *(
                    client.post(
                        "/generate",
                        json={"text": "ab", "max_new_tokens": 4},
                    )
                    for _ in range(10)
                )
            )
            codes = sorted(r.status_code for r in rs)
            assert 503 in codes, codes
            assert 200 in codes, codes
            m = (await client.get("/metrics")).json()
            assert m["counters"]["generate.rejected"] >= 1
            assert "generate.queue_depth" in m["gauges"]
    finally:
        await app.shutdown()


async def test_cancelled_request_stops_decode(gen_engine):
    """A cancelled request stops the decode loop before it burns
    device time on the remaining tokens (VERDICT r2 weak #4). The
    request is cancelled before the collector picks it up, so the
    batch must exit after prefill with ZERO chunk decodes —
    deterministic, no race against a fast model."""
    engine = gen_engine
    await engine.start()
    try:
        gen = await engine.submit("ab", max_new_tokens=64)
        gen.cancel()
        for _ in range(200):
            if engine.cancelled_batches:
                break
            await asyncio.sleep(0.02)
        assert engine.cancelled_batches == 1
        assert engine.chunk_calls == 0, (
            "decode ran chunks for a batch whose only consumer was gone"
        )
    finally:
        await engine.stop()


async def test_stream_disconnect_marks_request_cancelled(gen_engine):
    """Client walks away mid-NDJSON-stream → the app layer must
    cancel the underlying GenRequest (via the body iterator's
    finally, run by the server's aclose on disconnect)."""
    engine = gen_engine
    app = build_app(engine)
    await app.startup()
    captured = []
    orig_submit = engine.submit

    async def spying_submit(*a, **kw):
        gen = await orig_submit(*a, **kw)
        captured.append(gen)
        return gen

    engine.submit = spying_submit
    try:
        scope = {
            "type": "http",
            "method": "POST",
            "path": "/generate",
            "headers": [(b"content-type", b"application/json")],
            "query_string": b"",
            "extensions": {
                "mlapi_tpu.body": json.dumps(
                    {"text": "ab", "max_new_tokens": 64, "stream": True}
                ).encode()
            },
        }
        sent = []

        async def receive():
            return {"type": "http.disconnect"}

        async def send(message):
            sent.append(message)
            # Simulate the client vanishing after the first body chunk
            # lands — exactly what Server._dispatch's send raises.
            if message["type"] == "http.response.body" and message.get(
                "body"
            ):
                raise ConnectionResetError("client disconnected mid-stream")

        await app(scope, receive, send)
        assert captured, "handler never submitted a generation request"
        assert captured[0].cancelled, (
            "disconnect did not cancel the in-flight generation"
        )
    finally:
        engine.submit = orig_submit
        await app.shutdown()


async def test_collector_death_errors_queued_requests(gen_engine):
    """ADVICE r2: if the collector dies unexpectedly, requests still
    sitting in the queue must get the error sentinel, not hang."""
    engine = gen_engine
    engine.max_wait_s = 30.0  # collector holds its first batch open
    await engine.start()
    try:
        g1 = await engine.submit("ab", max_new_tokens=4)  # popped by collector
        await asyncio.sleep(0.01)
        g2 = await engine.submit("ba", max_new_tokens=4)  # still queued
        engine._task.cancel()
        item1 = await asyncio.wait_for(g1.queue.get(), 5)
        item2 = await asyncio.wait_for(g2.queue.get(), 5)
        assert isinstance(item1, Exception)
        assert isinstance(item2, Exception)
    finally:
        engine._task = None
        await engine.stop()

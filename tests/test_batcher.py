"""Micro-batcher: coalescing guarantee, ordering, error propagation
(SURVEY §4 'serving perf smoke': N concurrent requests must become
<= ceil(N/B) device calls)."""

import asyncio
import math
import threading

import numpy as np
import pytest

from mlapi_tpu.serving.scoring import ScorePath

pytestmark = pytest.mark.anyio


class FakeEngine:
    """Engine stub: label = str(first feature), optional blocking gate."""

    max_batch = 16

    def __init__(self):
        self.gate = threading.Event()
        self.gate.set()
        self.batch_sizes: list[int] = []

    def predict_labels(self, batch: np.ndarray):
        self.gate.wait()
        self.batch_sizes.append(len(batch))
        return [str(float(row[0])) for row in batch], np.full(len(batch), 0.5)


async def test_coalesces_to_ceil_n_over_b():
    engine = FakeEngine()
    # max_inflight=1: the plug batch holds the ONLY dispatch slot, and
    # slot-first collection means the collector cannot form another
    # batch until the gate opens — the 48 submits all accumulate in
    # the queue first, making the coalescing count deterministic even
    # on a heavily loaded host (this test used to flake under CPU
    # contention when collection raced the submits).
    batcher = ScorePath(
        engine, max_batch=16, max_wait_ms=5.0, max_inflight=1
    )
    await batcher.start()
    try:
        # Plug the dispatch thread so every subsequent submit queues up
        # behind one in-flight batch — deterministic coalescing.
        engine.gate.clear()
        plug = asyncio.create_task(batcher.submit(np.zeros(4)))
        deadline = asyncio.get_running_loop().time() + 10.0
        while batcher.device_calls < 1:  # plug batch is in the executor
            assert asyncio.get_running_loop().time() < deadline, (
                "plug batch never reached the executor"
            )
            await asyncio.sleep(0.01)

        n = 48
        tasks = [
            asyncio.create_task(batcher.submit(np.full(4, i))) for i in range(n)
        ]
        while batcher.requests < n + 1:
            await asyncio.sleep(0.01)
        engine.gate.set()

        results = await asyncio.gather(plug, *tasks)
        assert batcher.device_calls == 1 + math.ceil(n / 16)
        # Every request got its own row's answer back, in order.
        assert [r[0] for r in results[1:]] == [str(float(i)) for i in range(n)]
    finally:
        await batcher.stop()


async def test_single_request_low_latency_path():
    engine = FakeEngine()
    batcher = ScorePath(engine, max_wait_ms=0.0)
    await batcher.start()
    try:
        label, prob = await batcher.submit(np.full(4, 7.0))
        assert label == "7.0" and prob == 0.5
        assert batcher.device_calls == 1
        assert engine.batch_sizes == [1]
    finally:
        await batcher.stop()


async def test_engine_error_propagates_to_caller():
    class BoomEngine(FakeEngine):
        def predict_labels(self, batch):
            raise RuntimeError("device exploded")

    batcher = ScorePath(BoomEngine(), max_wait_ms=0.0)
    await batcher.start()
    try:
        with pytest.raises(RuntimeError, match="device exploded"):
            await batcher.submit(np.zeros(4))
        # Batcher survives the failure and keeps serving.
        assert batcher.device_calls >= 0
    finally:
        await batcher.stop()


async def test_submit_before_start_rejected():
    batcher = ScorePath(FakeEngine())
    with pytest.raises(RuntimeError, match="not started"):
        await batcher.submit(np.zeros(4))

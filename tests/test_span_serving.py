"""The generative engine's own clock (``utils.metrics.span`` in the
unit scheduler, the token readback and the request's life), read the
way an operator reads it: ``/metrics`` after a short paged
``/generate`` run.

Counts are exact and pinned against the counters that were already
there; times are only required to be consistent with each other
(never with the wall clock of this box).

Same tiny-model CFG and engine shapes as ``test_paged_kv`` ON PURPOSE
(conftest ``paged-family``): the compile ladder is paid once.
"""

import asyncio

import httpx
import jax
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving import build_app
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.serving.scheduler import UNIT_KINDS
from mlapi_tpu.text import ByteTokenizer

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


CFG = dict(
    vocab_size=260, hidden_size=32, num_layers=2, num_heads=4,
    max_positions=160, compute_dtype="float32",
)


@pytest.fixture(scope="module")
def engine_parts():
    model = get_model("gpt_lm", **CFG)
    return model, model.init(jax.random.key(0))


def _engine(parts, **kw):
    model, params = parts
    return TextGenerationEngine(
        model, params, tokenizer=ByteTokenizer(), chunk=2,
        fused_single=False, max_wait_ms=0.0, kv_page_size=8, **kw,
    )


async def _generate(client, text, n):
    resp = await client.post(
        "/generate", json={"text": text, "max_new_tokens": n}
    )
    assert resp.status_code == 200, resp.text
    return resp.json()


async def test_metrics_exports_the_span_sums(engine_parts):
    eng = _engine(engine_parts)
    app = build_app(eng)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as c:
            # a lone request, then a burst: formation, decode and (for
            # whoever arrives while a lane is live) in-lane admission
            await _generate(c, "hello", 6)
            outs = await asyncio.gather(*[
                _generate(c, f"prompt number {i}", 4 + i) for i in range(5)
            ])
            assert [len(o["token_ids"]) for o in outs] == [4, 5, 6, 7, 8]
            # the last readback and retirement land after the last frame
            for _ in range(2000):
                if eng.sched is not None and eng.sched.idle:
                    break
                await asyncio.sleep(0.005)
            counters = (await c.get("/metrics")).json()["counters"]
    finally:
        await app.shutdown()
    served = counters["generate.requests"]
    assert served == 6
    # every request's life was stamped: claimed once before its first
    # token, whichever way it reached a lane
    assert counters["generate.queue_wait_n"] == served
    assert counters["generate.prefill_wait_n"] == served
    assert counters["generate.queue_wait_us"] >= 0
    assert counters["generate.prefill_wait_us"] > 0
    # one timed span per counted unit, kind by kind
    for kind in UNIT_KINDS:
        assert counters[f"generate.sched_unit_{kind}_n"] == \
            counters[f"generate.sched_units_{kind}"], kind
        if counters[f"generate.sched_units_{kind}"]:
            assert counters[f"generate.sched_unit_{kind}_us"] > 0, kind
    assert counters["generate.sched_units_prefill"] >= 2
    assert counters["generate.sched_units_decode"] >= 3
    # every chunk dispatched was read back, inside some unit's span
    assert counters["generate.readback_wait_n"] >= \
        counters["generate.chunk_calls"]
    assert counters["generate.readback_wait_us"] > 0
    # the readbacks happen inside units (or a lane's retiring turn)
    busy_us = sum(
        counters[f"generate.sched_unit_{k}_us"]
        for k in (*UNIT_KINDS, "retire")
    )
    assert counters["generate.readback_wait_us"] <= busy_us
    # every lane retired once, and the thread waited for work at least
    # once (before the first request)
    assert counters["generate.sched_unit_retire_n"] == \
        counters["generate.sched_units_prefill"]
    assert counters["generate.sched_idle_n"] >= 1


async def test_unit_trace_entries_carry_the_span_interval(engine_parts):
    eng = _engine(engine_parts)
    await eng.start()
    try:
        sched = eng.sched
        req = await eng.submit("abc", max_new_tokens=6)
        while (await req.queue.get()) is not None:
            pass
    finally:
        await eng.stop()  # joins the dispatch thread: the log is final
    trace = list(sched.trace)
    assert [kind for _, kind, _, _ in trace][0] == "prefill"
    assert all(t0 <= t1 for _, _, t0, t1 in trace)
    # the log's intervals are the spans the sums were made from
    sums = eng.latency.sums.snapshot()["counters"]
    logged_us = sum((t1 - t0) for _, _, t0, t1 in trace) / 1e3
    summed_us = sum(sums.get(f"sched_unit_{k}_us", 0) for k in UNIT_KINDS)
    assert abs(logged_us - summed_us) <= len(trace)  # rounding a unit
    assert req.rid == 1 and req.t_claim is not None
    assert req.t0 <= req.t_claim <= req.t_last

"""Multi-model serving (r22): the scoring fast path, per-tenant
quotas, and weighted-slack fairness — ROADMAP item 1.

What this module pins, all from counters (never wall-clock):

- **Identity**: a generative engine's greedy stream is byte-identical
  to its solo run while a co-resident ScorePath rides its unit queue
  — across {gpt, llama} x {paged, contiguous}. Score units change
  dispatch ORDER, never tokens.
- **Throughput**: the folded scoring path still coalesces N requests
  into <= ceil(N/B) device calls (requests/device_calls >= 3 with a
  formed batch >= 8 rows) — the batched-vs-serial half of the
  acceptance bar, from dispatch counts.
- **One scheduler**: co-resident scoring batches ride the generative
  UnitScheduler as typed ``score`` units (``sched_dispatches`` ==
  ``device_calls``), and the trace shows decode units dispatching
  AFTER score units — neither direction starves the other.
- **Quota pin**: a tenant at its page quota defers (counted per
  tenant AND on the engine) while another tenant's stream completes
  untouched; the deferred group runs after the release — eviction of
  a peer's pages never happens.
- **Tenant brownout first**: one hot tenant's depth clamps ITS
  oversized budgets while the fleet-wide ladder stays at rung 0 and
  an idle tenant keeps its full budget.
- **Surface**: per-model routes, /healthz ``models`` block, and the
  ``model.<id>.*`` / ``tenant.<t>.*`` metric families exist in
  multi-model mode — and do NOT exist in single-model mode (the
  one-entry registry is bit-identical to r21).

Same tiny-model CFG and engine shapes as test_paged_kv/test_scheduler
ON PURPOSE: the module shares the conftest ``paged-family`` cache
window, so registry traffic re-drives the family's compiled
prefill/decode programs instead of re-paying the ladder.
"""

import asyncio
import math
import threading
import types

import httpx
import jax
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving import faults
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.serving.registry import ModelRegistry, TenantLedger
from mlapi_tpu.serving.scoring import ScorePath
from mlapi_tpu.text import ByteTokenizer

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


CFG = dict(
    vocab_size=260,
    hidden_size=32,
    num_layers=2,
    num_heads=4,
    max_positions=160,
    compute_dtype="float32",
)


def _model(kind="gpt_lm"):
    kw = dict(CFG)
    if kind == "llama_lm":
        kw["num_kv_heads"] = 2  # GQA: 4 query heads over 2 KV heads
    return get_model(kind, **kw)


@pytest.fixture(scope="module")
def gpt_params():
    return _model().init(jax.random.key(0))


@pytest.fixture(scope="module")
def llama_params():
    return _model("llama_lm").init(jax.random.key(0))


def _engine(model, params, paged=True, **kw):
    kw.setdefault("chunk", 2)
    # Pin the chunked lifecycle (same as test_scheduler): fused fast
    # paths would collapse a lane to one opaque unit.
    kw.setdefault("fused_single", False)
    # Window 0: formation driven by queue order alone — deterministic.
    kw.setdefault("max_wait_ms", 0.0)
    if paged:
        kw.setdefault("kv_page_size", 8)
    return TextGenerationEngine(
        model, params, tokenizer=ByteTokenizer(), **kw,
    )


class _ScoreStub:
    """Scoring-engine stub for the path-level tests: label =
    str(first feature), optional blocking gate, batch sizes recorded
    (the test_batcher idiom)."""

    max_batch = 16

    def __init__(self):
        self.gate = threading.Event()
        self.gate.set()
        self.batch_sizes: list[int] = []

    def predict_labels(self, batch: np.ndarray):
        self.gate.wait()
        self.batch_sizes.append(len(batch))
        return (
            [str(float(row[0])) for row in batch],
            np.full(len(batch), 0.5),
        )


class _TabStub(_ScoreStub):
    """Enough surface for build_app's registry loop + /predict +
    /healthz: a tabular 4-feature binary classifier."""

    kind = "tabular"
    feature_names = ("f0", "f1", "f2", "f3")
    num_features = 4
    meta = {"stub": True}

    def __init__(self):
        super().__init__()
        self.model = self
        self.vocab = types.SimpleNamespace(labels=["neg", "pos"])

    def warmup(self, full=False):
        pass

    def predict_labels(self, batch: np.ndarray):
        self.gate.wait()
        self.batch_sizes.append(len(batch))
        labels = ["pos" if row[0] > 0 else "neg" for row in batch]
        return labels, np.full(len(batch), 0.75)


async def _collect(req):
    """(tokens, terminal_error_or_None) — errors are in-band."""
    out: list[int] = []
    while True:
        item = await req.queue.get()
        if item is None:
            return out, None
        if isinstance(item, Exception):
            return out, item
        out.extend(item["token_ids"])


async def _wait_for(pred, timeout_s: float = 60.0,
                    interval_s: float = 0.005) -> None:
    """Condition-based wait (MLA006 discipline): generous deadline,
    loud failure — never a tuned iteration budget."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    while not pred():
        if loop.time() >= deadline:
            raise AssertionError(
                f"condition never became true within {timeout_s}s"
            )
        await asyncio.sleep(interval_s)


# Two groups the collector can NEVER window together (and a pending
# group can never join the other's lane): max(bucket) + max(n_new) =
# 128 + 34 > 160 = max_positions, while each alone fits.
_SHORT = ("hello world", 34)
_LONG = ("x" * 100, 8)


# --- identity: scoring co-resident never changes tokens ----------------


@pytest.mark.parametrize(
    "kind,paged",
    [
        ("gpt_lm", True),
        ("gpt_lm", False),
        ("llama_lm", True),
        ("llama_lm", False),
    ],
)
async def test_streams_identical_with_scoring_coresident(
    kind, paged, gpt_params, llama_params
):
    """Greedy multi-vs-solo identity across the model x layout
    matrix: the same engine's solo greedy run, then the same request
    streamed while score units interleave between its decode chunks
    (decode delay armed so the overlap provably happens) — tokens
    byte-identical, and the score units demonstrably rode the unit
    queue."""
    params = gpt_params if kind == "gpt_lm" else llama_params
    eng = _engine(_model(kind), params, paged=paged, sched_max_batches=2)
    ref = eng.generate_text(_SHORT[0], max_new_tokens=16)["token_ids"]
    sp = ScorePath(
        _ScoreStub(), model_id="clf", max_wait_ms=0.0,
        sched_source=lambda: eng.sched,
    )
    await eng.start()
    await sp.start()
    try:
        faults.arm("decode:every=1:delay=0.01")
        r = await eng.submit(_SHORT[0], max_new_tokens=16, stream=True)
        await _wait_for(lambda: eng.sched_batches_live == 1)
        out = await asyncio.gather(
            *[sp.submit(np.full(4, float(i))) for i in range(4)]
        )
        assert [label for label, _ in out] == [
            str(float(i)) for i in range(4)
        ]
        toks, err = await _collect(r)
        assert err is None
        assert toks == ref
        # Counter evidence the scoring traffic used the ONE scheduler.
        assert sp.sched_dispatches == sp.device_calls >= 1
        assert eng.sched_units_score == sp.sched_dispatches
    finally:
        faults.disarm()
        await sp.stop()
        await eng.stop()


# --- throughput: coalescing from dispatch counts -----------------------


async def test_scoring_batched_throughput_vs_serial():
    """The acceptance ratio, from counters: with one batch plugging
    the only dispatch slot, 24 queued requests coalesce into 2 more
    device calls — requests/device_calls >= 3 (serial would be 1.0)
    with a formed batch >= 8 rows."""
    stub = _ScoreStub()
    sp = ScorePath(stub, max_batch=16, max_wait_ms=5.0, max_inflight=1)
    await sp.start()
    try:
        stub.gate.clear()
        plug = asyncio.create_task(sp.submit(np.zeros(4)))
        await _wait_for(lambda: sp.device_calls >= 1)
        n = 24
        tasks = [
            asyncio.create_task(sp.submit(np.full(4, float(i))))
            for i in range(n)
        ]
        await _wait_for(lambda: sp.requests >= n + 1)
        stub.gate.set()
        results = await asyncio.gather(plug, *tasks)
        assert sp.device_calls == 1 + math.ceil(n / 16)
        assert sp.requests / sp.device_calls >= 3.0
        assert max(stub.batch_sizes) >= 8
        assert [r[0] for r in results[1:]] == [
            str(float(i)) for i in range(n)
        ]
    finally:
        await sp.stop()


# --- one scheduler: score units interleave, nobody starves -------------


async def test_score_units_interleave_with_decode(gpt_params):
    """Score units dispatch BETWEEN decode chunks of a live lane:
    every scoring batch rides the unit queue (sched_dispatches ==
    device_calls), decode units keep dispatching after score units
    (trace order — generation not starved), and the scoring results
    resolve while the lane is still producing (scoring not starved)."""
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    sp = ScorePath(
        _ScoreStub(), model_id="clf", max_wait_ms=0.0,
        sched_source=lambda: eng.sched,
    )
    await eng.start()
    await sp.start()
    try:
        faults.arm("decode:every=1:delay=0.02")
        r = await eng.submit(_SHORT[0], max_new_tokens=24, stream=True)
        await _wait_for(lambda: eng.sched_batches_live == 1)
        decode_before = eng.sched_units_decode
        for i in range(5):
            label, prob = await sp.submit(np.full(4, float(i)))
            assert label == str(float(i))
        # All five resolved while the delayed lane was still live —
        # the lane never starved scoring out.
        assert eng.sched_batches_live == 1
        toks, err = await _collect(r)
        assert err is None and len(toks) == 24
        assert sp.sched_dispatches == sp.device_calls == 5
        assert eng.sched_units_score == 5
        # ... and scoring never starved decode: decode units kept
        # dispatching after the first score unit.
        kinds = [k for _, k, _, _ in eng.sched.trace]
        first_score = kinds.index("score")
        assert "decode" in kinds[first_score + 1:]
        assert eng.sched_units_decode > decode_before
    finally:
        faults.disarm()
        await sp.stop()
        await eng.stop()


# --- quota pin: defer the tenant, never evict the peer -----------------


async def test_tenant_quota_defers_not_evicts(gpt_params):
    """Tenant A at its page quota: A's second group defers (counted
    on the engine AND in A's ledger row) while A's first lane streams
    on and tenant B's stream starts and completes untouched. The
    deferral is the QUOTA's (the pool-wide gate never fired), and the
    deferred group runs to completion after A's release — pages move
    by lane retirement, never by evicting B."""
    eng = _engine(_model(), gpt_params, sched_max_batches=3)
    await eng.start()
    try:
        faults.arm("decode:every=1:delay=0.02")
        ra1 = await eng.submit(
            _SHORT[0], max_new_tokens=_SHORT[1], stream=True, tenant="a"
        )
        await _wait_for(lambda: eng.sched_batches_live == 1)
        held = eng.sched._lanes[0].tenant_pages["a"]
        assert held > 0
        # Quota = exactly what A already holds: any growth is over.
        led = TenantLedger(quota_pages={"a": held})
        eng.tenants = led
        ra2 = await eng.submit(
            _LONG[0], max_new_tokens=_LONG[1], stream=True, tenant="a"
        )
        await _wait_for(lambda: eng.sched_tenant_pages_deferred >= 1)
        assert led.deferrals("a") >= 1
        # B starts as a second lane while A's group waits: three lane
        # slots, so ONLY the quota is what defers A.
        rb = await eng.submit(
            "y" * 100, max_new_tokens=8, stream=True, tenant="b"
        )
        tb, eb = await _collect(rb)
        assert eb is None and len(tb) == 8
        assert led.deferrals("b") == 0
        # The pool itself never said no — the distinction the per-
        # tenant counter exists for.
        assert eng.sched_pages_deferred == 0
        faults.disarm()
        (t1, e1), (t2, e2) = await asyncio.gather(
            _collect(ra1), _collect(ra2)
        )
        assert e1 is None and e2 is None
        assert len(t1) == _SHORT[1] and len(t2) == _LONG[1]
        await _wait_for(lambda: eng.kv_pages_in_use == 0)
    finally:
        faults.disarm()
        await eng.stop()


# --- tenant brownout engages before the fleet ladder -------------------


async def test_tenant_brownout_before_fleet(gpt_params):
    """One hot tenant's live depth clamps ITS oversized budget while
    the fleet-wide brownout ladder reads rung 0 and an idle tenant
    keeps its full budget — the tenant degrades itself before it
    degrades anyone."""
    eng = _engine(_model(), gpt_params, max_queue=8)
    led = TenantLedger()
    eng.tenants = led
    await eng.start()
    try:
        # Manufacture tenant depth (2 * 4 >= max_queue 8) with the
        # queue itself empty — exactly the split the rung order is
        # about: tenant pressure without fleet pressure.
        led.enter("a")
        led.enter("a")
        ra = await eng.submit(_SHORT[0], max_new_tokens=64, tenant="a")
        assert ra.n_new == eng.default_max_new_tokens
        assert eng.brownout_tenant_clamped == 1
        assert led.brownouts("a") == 1
        assert eng._brownout_level() == 0   # fleet ladder untouched
        assert eng.brownout_tokens_clamped == 0
        toks, err = await _collect(ra)
        assert err is None
        assert len(toks) == eng.default_max_new_tokens
        # The idle tenant at the same instant: full budget.
        rb = await eng.submit(_SHORT[0], max_new_tokens=40, tenant="b")
        assert rb.n_new == 40
        tb, eb = await _collect(rb)
        assert eb is None and len(tb) == 40
        assert led.brownouts("b") == 0
    finally:
        await eng.stop()


# --- the app surface: routes, healthz, metric families -----------------


async def test_app_multi_model_routes_metrics_healthz(gpt_params):
    """One app over a two-entry registry: per-model routes answer,
    /healthz advertises the model map (what the router's candidate
    filter polls), scoring requests ride the generative scheduler,
    and /metrics grows the model.<id>.* and tenant.<t>.* families."""
    from mlapi_tpu.serving.app import build_app

    gen = _engine(_model(), gpt_params)
    clf = _TabStub()
    models = ModelRegistry({"default": gen, "clf": clf})
    led = TenantLedger(quota_pages={"acme": 64})
    app = build_app(models=models, tenants=led)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as client:
            hz = (await client.get("/healthz")).json()
            assert hz["models"] == {
                "clf": {"kind": "tabular", "default": False},
                "default": {"kind": "generative", "default": True},
            }
            r = await client.post(
                "/models/clf/predict",
                json={"f0": 1.0, "f1": 0.0, "f2": 0.0, "f3": 0.0},
            )
            assert r.status_code == 200
            body = r.json()
            assert body["prediction"] == "pos"
            assert body["probability"] == 0.75
            for path in ("/generate", "/models/default/generate"):
                r = await client.post(
                    path,
                    json={"text": "hi", "max_new_tokens": 4,
                          "tenant": "acme"},
                )
                assert r.status_code == 200
                assert len(r.json()["token_ids"]) == 4
            # Exercise the tenant export path directly: live traffic
            # above balanced its depth back to zero (enter/exit), and
            # the snapshot only lists tenants WITH history.
            led.note_deferral("acme")
            m = (await client.get("/metrics")).json()
            c, g = m["counters"], m["gauges"]
            assert c["model.default.requests"] == 2
            assert c["model.clf.requests"] == 1
            assert c["model.clf.device_calls"] >= 1
            # The one-scheduler claim, end to end through the app:
            # every clf dispatch rode default's unit queue.
            assert (
                c["model.clf.sched_dispatches"]
                == c["model.clf.device_calls"]
                == c["model.default.sched_units_score"]
            )
            assert g["model.default.queue_depth"] == 0
            assert c["tenant.acme.deferrals"] == 1
            assert g["tenant.acme.depth"] == 0
    finally:
        await app.shutdown()


async def test_single_model_surface_unchanged(gpt_params):
    """A one-entry registry is bit-identical to r21: no per-model
    routes, no models block in /healthz, no model.*/tenant.* metric
    families."""
    from mlapi_tpu.serving.app import build_app

    app = build_app(_engine(_model(), gpt_params))
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as client:
            hz = (await client.get("/healthz")).json()
            assert "models" not in hz
            r = await client.post(
                "/models/default/generate",
                json={"text": "hi", "max_new_tokens": 2},
            )
            assert r.status_code == 404
            m = (await client.get("/metrics")).json()
            keys = set(m["counters"]) | set(m.get("gauges", {}))
            assert not any(
                k.startswith(("model.", "tenant.")) for k in keys
            )
    finally:
        await app.shutdown()


# --- soak: sustained mixed traffic (demoted from the tier-1 window) ----


@pytest.mark.slow
@pytest.mark.heavy
async def test_mixed_soak_generation_with_scoring(gpt_params):
    """Sustained mixed rounds — generation waves with scoring bursts
    riding the same unit queue — complete exactly, with every scoring
    dispatch on the scheduler backend and the ledger balanced back to
    zero depth. Duplicates the functional coverage above at iteration
    count (hence slow-marked, outside the 870 s window)."""
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    led = TenantLedger(weights={"a": 2.0})
    eng.tenants = led
    sp = ScorePath(
        _ScoreStub(), model_id="clf", max_wait_ms=0.0,
        sched_source=lambda: eng.sched,
    )
    await eng.start()
    await sp.start()
    try:
        rounds, per_round = 6, 20
        for rnd in range(rounds):
            ra = await eng.submit(
                _SHORT[0], max_new_tokens=12, stream=True, tenant="a"
            )
            rb = await eng.submit(
                _LONG[0], max_new_tokens=6, stream=True, tenant="b"
            )
            scores = await asyncio.gather(
                *[
                    sp.submit(np.full(4, float(i)))
                    for i in range(per_round)
                ]
            )
            assert [s[0] for s in scores] == [
                str(float(i)) for i in range(per_round)
            ]
            (ta, ea), (tb, eb) = await asyncio.gather(
                _collect(ra), _collect(rb)
            )
            assert ea is None and eb is None
            assert len(ta) == 12 and len(tb) == 6
        assert sp.requests == rounds * per_round
        assert sp.sched_dispatches == sp.device_calls
        assert eng.sched_units_score == sp.sched_dispatches
        assert led.depth("a") == 0 and led.depth("b") == 0
        await _wait_for(lambda: eng.kv_pages_in_use == 0)
    finally:
        await sp.stop()
        await eng.stop()

"""Continuous-batching scheduler v2 (``serving/scheduler.py``):
one typed-unit queue across concurrent BatchRuns — DEFAULT-ON since
r20. ``--no-scheduler`` was retired in r22; ``sched_max_batches=1``
is the serial mode, pinning the same machinery to ONE lane (the
``scheduler=`` parameter of the ``_engine`` helper below maps to
exactly that).

The contract these tests pin, layer by layer — all interleaving and
priority claims are asserted from DISPATCH COUNTERS and the bounded
unit trace, never wall-clock:

- **Concurrency**: two bucket-incompatible request groups submitted
  together run as two live lanes with their units interleaved
  (``sched_batches_live_max == 2``; the trace alternates lane ids).
- **Identity**: greedy streams are byte-identical concurrent
  (default) vs serial (``sched_max_batches=1``) across {gpt-MHA,
  llama-GQA} x {none, int8} x {einsum, flash} x {paged, contiguous} —
  the structural consequence of both modes draining the same
  ``BatchRun.units()`` generator.
- **Fused fold (r20)**: a fused-eligible batch's tier-wide decode
  chunks are ordinary units, so a concurrent lane's head-of-line
  stall behind fused traffic is at most ONE fused-chunk dispatch
  (``sched_lane_stall_max``, a counter).
- **SLO policy**: pending groups start in deadline-slack order (the
  r12 ``_carry[0]`` FIFO head-of-line fix), expired requests get
  their terminal frames at unit boundaries (``deadline_expired_*``
  keeps ticking — no unit dispatches after a passed deadline).
- **Faults**: the ``sched_unit`` seam (raise kills ONE lane with its
  pages conserved while the other lane streams on; delay slows but
  never breaks).
- **Arbitration**: a pending group whose worst-case page footprint
  does not fit beside live lanes waits (``sched_pages_deferred``) and
  runs after a release — never a mid-decode ``PagePoolExhausted``.
- **Drain**: the typed-unit queue (pending groups + live lanes) is
  covered by ``drain()`` exactly as ``_carry`` is — terminal frames
  for everything, pool back to baseline.

Same tiny-model CFG and engine shapes as ``test_paged_kv`` ON
PURPOSE: the module shares that family's jax-cache window
(conftest ``paged-family``), so the compile ladder is paid once.
"""

import asyncio

import jax
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving import faults
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.serving.requests import DeadlineExceeded, DrainCancelled
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


def _model(kind="gpt_lm", kv_quant="none", impl="einsum"):
    kw = dict(CFG, kv_quant=kv_quant, decode_attn_impl=impl)
    if kind == "llama_lm":
        kw["num_kv_heads"] = 2  # GQA: 4 query heads over 2 KV heads
    return get_model(kind, **kw)


@pytest.fixture(scope="module")
def gpt_params():
    return _model().init(jax.random.key(0))


@pytest.fixture(scope="module")
def llama_params():
    return _model("llama_lm").init(jax.random.key(0))


def _engine(model, params, paged=True, scheduler=True, **kw):
    kw.setdefault("chunk", 2)
    # Pin the chunked batch lifecycle (same as test_paged_kv): fused
    # fast paths never touch the pool and would collapse a lane to
    # one opaque unit.
    kw.setdefault("fused_single", False)
    # Window 0: formation is driven by queue order alone, so which
    # requests group together is deterministic.
    kw.setdefault("max_wait_ms", 0.0)
    if paged:
        kw.setdefault("kv_page_size", 8)
    # scheduler=False maps to the r22 serial mode: ONE lane on the
    # same machinery (--no-scheduler retired; sched_max_batches=1 IS
    # serial). Forced, not defaulted — the old kwarg clamped to one
    # lane no matter what the lane budget said, and the identity
    # matrix passes both together.
    if not scheduler:
        kw["sched_max_batches"] = 1
    return TextGenerationEngine(
        model, params, tokenizer=ByteTokenizer(), **kw,
    )


async def _collect(req):
    """(tokens, terminal_error_or_None) — never hangs on a live
    engine; errors are in-band."""
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
    """CONDITION-based wait (the MLA006 discipline): poll a counter/
    state predicate under a generous deadline instead of a tuned
    iteration budget. The old ``for _ in range(200): ...sleep(0.01)``
    shape was a hidden 2 s wall-clock assertion — on this drifting
    box (documented ±25-30% and worse) it flaked whenever the
    condition was merely LATE, not wrong. Raises loudly on timeout so
    a genuinely-stuck condition still fails."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    while not pred():
        if loop.time() >= deadline:
            raise AssertionError(
                f"condition never became true within {timeout_s}s"
            )
        await asyncio.sleep(interval_s)


# Two groups the collector can NEVER window together: max(bucket) +
# max(n_new) = 128 + 34 > 160 = max_positions, while each alone fits.
_SHORT = ("hello world", 34)      # 16-bucket, long budget (> 32
                                  # forces window incompatibility)
_LONG = ("x" * 100, 8)            # 128-bucket, short budget


async def _submit_pair(eng):
    ra = await eng.submit(_SHORT[0], max_new_tokens=_SHORT[1], stream=True)
    rb = await eng.submit(_LONG[0], max_new_tokens=_LONG[1], stream=True)
    return ra, rb


# --- concurrency + interleaving (counter-pinned) -----------------------


async def test_two_incompatible_groups_interleave(gpt_params):
    """The flagship concurrency pin PLUS the scheduler-off identity
    for the bucket-incompatible pair (one config pays the extra cache
    tier's compiles; the cross-config identity matrix below reuses
    the family's warm shapes instead)."""
    outs = []
    for scheduler in (True, False):
        eng = _engine(
            _model(), gpt_params, scheduler=scheduler,
            sched_max_batches=2,
        )
        await eng.start()
        try:
            ra, rb = await _submit_pair(eng)
            (ta, ea), (tb, eb) = await asyncio.gather(
                _collect(ra), _collect(rb)
            )
            assert ea is None and eb is None
            assert len(ta) == _SHORT[1] and len(tb) == _LONG[1]
            outs.append((ta, tb))
            if scheduler:
                # Both batches were LIVE at once, asserted from the
                # high-water counter, and their units interleaved:
                # the trace must switch lanes mid-stream (an A,B,A
                # pattern), not run serially.
                assert eng.sched_batches_live_max == 2
                lanes = [lane for lane, kind, t0, t1 in eng.sched.trace]
                switches = sum(
                    1 for i in range(1, len(lanes))
                    if lanes[i] != lanes[i - 1]
                )
                assert switches >= 2, lanes
                # The widened entries: each unit's interval on the
                # dispatch thread's clock, in dispatch order.
                spans = [(t0, t1) for _, _, t0, t1 in eng.sched.trace]
                assert all(t0 <= t1 for t0, t1 in spans)
                assert all(
                    a[1] <= b[0] for a, b in zip(spans, spans[1:])
                ), "one dispatch thread: units never overlap"
                # Unit counters moved for both types of work.
                assert eng.sched_units_decode >= (
                    _SHORT[1] // eng.chunk + _LONG[1] // eng.chunk
                ) - 2
                assert eng.sched_units_prefill >= 2  # one formation each
            # The lane's page release runs on the dispatch thread
            # AFTER the terminal frame is pushed — wait for the
            # condition instead of racing it (the flake this module
            # carried since r15).
            await _wait_for(lambda: eng.kv_pages_in_use == 0)
        finally:
            await eng.stop()
    # Greedy streams byte-identical, scheduler-on vs off.
    assert outs[0] == outs[1]


async def test_scheduler_queue_feeds_queue_depth(gpt_params):
    """Pending groups the collector handed to the scheduler stay
    visible to backpressure/healthz via engine.queue_depth (the
    typed-unit queue, not just the submit queue)."""
    eng = _engine(_model(), gpt_params, sched_max_batches=1)
    await eng.start()
    try:
        blocker = await eng.submit(
            _SHORT[0], max_new_tokens=30, stream=True
        )
        # Wait until the blocker is laned, then park a second group.
        await _wait_for(lambda: eng.sched_batches_live == 1)
        pend = await eng.submit(_LONG[0], max_new_tokens=8, stream=True)
        await _wait_for(lambda: eng.queue_depth >= 1)
        assert (await _collect(blocker))[1] is None
        assert (await _collect(pend))[1] is None
    finally:
        await eng.stop()


# --- identity: scheduler-on == scheduler-off ---------------------------


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contig"])
@pytest.mark.parametrize("impl", ["einsum", "flash"])
@pytest.mark.parametrize("fmt", ["none", "int8"])
@pytest.mark.parametrize("kind", ["gpt_lm", "llama_lm"])
async def test_streams_identical_scheduler_on_off(
    kind, fmt, impl, paged, gpt_params, llama_params
):
    """Scheduler-on vs off byte-identity across the full config
    matrix. The two requests are window-COMPATIBLE but submitted one
    at a time through a zero-width window — default mode may take the
    second via in-lane admission OR as its own lane depending on
    arrival timing, and the streams must be byte-identical either way
    — while every program shape (16-bucket prompts, default tier) is
    one the family window already compiled (test_paged_kv's identity
    matrix), keeping the 16 configs cheap. The bucket-incompatible
    pair's identity is pinned on the flagship config above."""
    params = gpt_params if kind == "gpt_lm" else llama_params
    model = _model(kind, kv_quant=fmt, impl=impl)
    outs = []
    for scheduler in (True, False):
        eng = _engine(
            model, params, paged=paged, scheduler=scheduler,
            sched_max_batches=2,
        )
        await eng.start()
        try:
            ra = await eng.submit("hello", max_new_tokens=12, stream=True)
            rb = await eng.submit(
                "world bb", max_new_tokens=6, stream=True, seed=3
            )
            (ta, ea), (tb, eb) = await asyncio.gather(
                _collect(ra), _collect(rb)
            )
            assert ea is None and eb is None
            assert len(ta) == 12 and len(tb) == 6
            outs.append((ta, tb))
            if not scheduler:
                # The serial escape hatch is the SAME machinery
                # pinned to one lane — not a separate code path.
                assert eng.sched is not None
                assert eng.sched_max_batches == 1
                assert eng.sched_batches_live_max <= 1
                assert eng.sched_units_decode >= 1
        finally:
            await eng.stop()
    assert outs[0] == outs[1]


# --- SLO policy: deadline slack ----------------------------------------


async def test_pending_groups_start_in_deadline_slack_order(gpt_params):
    """The r12 _carry[0] head-of-line fix: with one lane occupied, a
    later-arriving DEADLINED group outranks an earlier deadline-less
    one when the scheduler picks the next formation."""
    eng = _engine(_model(), gpt_params, sched_max_batches=1)
    await eng.start()
    try:
        order: list[str] = []

        async def tagged(req, tag):
            toks, err = await _collect(req)
            order.append(tag)
            return toks, err

        # Slow every decode chunk so the blocker provably outlives
        # both submissions — the ordering claim must not race the
        # blocker's completion (the counters stay the assert; the
        # delay only holds the lane slot open). 0.05 x 20 chunks = a
        # 1 s floor: the r17-documented flake was this floor sitting
        # at 0.4 s while a drifting box took longer than that just to
        # run the two submits' encode hops.
        faults.arm("decode:every=1:delay=0.05")
        blocker = await eng.submit("hold", max_new_tokens=40, stream=True)
        await _wait_for(lambda: eng.sched_batches_live == 1)
        # A first (loose deadline), then B (tighter deadline): pure
        # slack comparison, reservoir-independent — FIFO would run A
        # first, slack priority runs B. (A deadline-LESS group is
        # deliberately not pinned against a generous deadline: once it
        # has queued past ~2x the observed TTFT p95 the policy
        # promotes it — by design it may beat a 60s-slack deadline.)
        # Both incompatible with the blocker's window (128-bucket
        # prompts: 128 + 40 > 160) — a window-COMPATIBLE group would
        # instead be STAGED into the blocker's lane by r20's in-lane
        # admission and never reach the pending queue this test
        # orders. A is confirmed pending before B is submitted, so
        # the collector can never window-merge the two into one
        # group.
        ra = await eng.submit(
            "a" * 100, max_new_tokens=24, stream=True,
            deadline_ms=120000.0,
        )
        await _wait_for(lambda: eng.sched.backlog >= 1)
        rb = await eng.submit(
            _LONG[0], max_new_tokens=8, stream=True, deadline_ms=60000.0
        )
        # Both groups pending BEFORE the blocker's lane can free.
        await _wait_for(lambda: eng.sched.backlog >= 2)
        results = await asyncio.gather(
            _collect(blocker), tagged(ra, "A"), tagged(rb, "B")
        )
        assert results[0][1] is None
        assert order == ["B", "A"]
    finally:
        faults.disarm()
        await eng.stop()


async def test_deadline_expiry_at_unit_boundaries(gpt_params):
    """No unit dispatches after a passed deadline: with every decode
    chunk slowed, a tight-deadline stream ends with DeadlineExceeded
    at a decode boundary and the r12 counters keep ticking under the
    scheduler."""
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    await eng.start()
    try:
        faults.arm("decode:every=1:delay=0.03")
        req = await eng.submit(
            "slow one", max_new_tokens=60, stream=True, deadline_ms=150.0
        )
        toks, err = await _collect(req)
        assert isinstance(err, DeadlineExceeded)
        assert len(toks) < 60
        assert (
            eng.deadline_expired_decode
            + eng.deadline_expired_prefill
            + eng.deadline_expired_queued
        ) >= 1
        faults.disarm()
        # The lane died cleanly: pages conserved, engine serves on.
        await _wait_for(lambda: eng.sched.idle)
        assert eng.kv_pages_in_use == 0
        fresh = await eng.submit("after", max_new_tokens=4)
        toks, err = await _collect(fresh)
        assert err is None and len(toks) == 4
    finally:
        faults.disarm()
        await eng.stop()


# --- the sched_unit fault seam -----------------------------------------


async def test_sched_unit_raise_kills_one_lane_only(gpt_params):
    """The unit-dispatch seam matrix, raise leg: one lane dies with
    the injected error as its waiters' terminal frame and its pages
    released; the OTHER lane streams on token-identical to an
    unfaulted run; the engine serves fresh work after."""
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    await eng.start()
    try:
        # Unfaulted reference for the short group's stream.
        ra, rb = await _submit_pair(eng)
        (ref_a, ea), (ref_b, eb) = await asyncio.gather(
            _collect(ra), _collect(rb)
        )
        assert ea is None and eb is None
        # Same dispatch-thread release race as the flagship test:
        # wait for the condition, don't race it.
        await _wait_for(lambda: eng.kv_pages_in_use == 0)
        # Fault a mid-run unit: both lanes formed (units 1-2), the
        # raise lands on one lane's decode/admit unit.
        faults.arm("sched_unit:after=6:raise")
        ra, rb = await _submit_pair(eng)
        (ta, ea), (tb, eb) = await asyncio.gather(
            _collect(ra), _collect(rb)
        )
        errs = [e for e in (ea, eb) if e is not None]
        assert len(errs) == 1, (ea, eb)
        assert isinstance(errs[0], faults.InjectedFault)
        # The surviving lane's stream is byte-identical to unfaulted.
        if ea is None:
            assert ta == ref_a
        else:
            assert tb == ref_b
        faults.disarm()
        await _wait_for(lambda: eng.sched.idle)
        assert eng.kv_pages_in_use == 0  # refcounts conserved
        fresh = await eng.submit("after", max_new_tokens=4)
        toks, err = await _collect(fresh)
        assert err is None and len(toks) == 4
    finally:
        faults.disarm()
        await eng.stop()


async def test_sched_unit_raise_before_first_unit_conserves_pages(
    gpt_params,
):
    """after=1: call 1 is the formation's own fire, call 2 fires in
    the dispatch loop BEFORE the lane's first generator advance. A
    never-started generator's close() runs no ``finally``, so the
    scheduler must release the formation's pages directly — this was
    a real leak (pool shrank by one formation per early fault)."""
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    await eng.start()
    try:
        faults.arm("sched_unit:after=1:raise")
        req = await eng.submit("hello", max_new_tokens=8, stream=True)
        toks, err = await _collect(req)
        assert isinstance(err, faults.InjectedFault)
        faults.disarm()
        await _wait_for(lambda: eng.sched.idle)
        assert eng.kv_pages_in_use == 0  # the formation's pages back
        fresh = await eng.submit("after", max_new_tokens=4)
        toks, err = await _collect(fresh)
        assert err is None and len(toks) == 4
    finally:
        faults.disarm()
        await eng.stop()


async def test_sched_unit_delay_slows_never_breaks(gpt_params):
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    await eng.start()
    try:
        faults.arm("sched_unit:every=3:delay=0.01")
        ra, rb = await _submit_pair(eng)
        (ta, ea), (tb, eb) = await asyncio.gather(
            _collect(ra), _collect(rb)
        )
        assert ea is None and eb is None
        assert len(ta) == _SHORT[1] and len(tb) == _LONG[1]
        assert eng.faults_injected > 0
        await _wait_for(lambda: eng.kv_pages_in_use == 0)
    finally:
        faults.disarm()
        await eng.stop()


# --- page-budget arbitration -------------------------------------------


async def test_page_budget_defers_second_lane(gpt_params):
    """A group whose worst-case footprint does not fit beside the
    live lane WAITS (counted) instead of racing the pool into a
    mid-decode PagePoolExhausted — and still completes after the
    first lane releases."""
    # 15 usable pages: lane A (16-bucket + 32-tier cache = 48 slots
    # -> 6 pages) fits; group B (16 + 64 = 80 slots -> 10 pages) does
    # not fit beside it (15 - 6 = 9 free), but fits alone. Under r20
    # B first tries in-lane admission into A (window-compatible), is
    # deferred there (64 new tokens exceed A's 48-slot cache), and
    # re-dispatches as its own group — which is what the page gate
    # then defers. The slowed decode keeps A's lane provably alive
    # through that staging round-trip (30 tokens x 0.02 s/chunk-pair
    # = a 0.3 s floor).
    eng = _engine(
        _model(), gpt_params, sched_max_batches=2,
        kv_page_size=8, kv_pages=16,
    )
    await eng.start()
    try:
        faults.arm("decode:every=1:delay=0.02")
        ra = await eng.submit("hold", max_new_tokens=30, stream=True)
        await _wait_for(lambda: eng.sched_batches_live == 1)
        rb = await eng.submit("bbbb", max_new_tokens=64, stream=True)
        (ta, ea), (tb, eb) = await asyncio.gather(
            _collect(ra), _collect(rb)
        )
        assert ea is None and eb is None
        assert len(ta) == 30 and len(tb) == 64
        assert eng.sched_pages_deferred >= 1
        await _wait_for(lambda: eng.kv_pages_in_use == 0)
    finally:
        faults.disarm()
        await eng.stop()


# --- fused chunks stay preemptible across lanes ------------------------


async def test_fused_chunks_bound_cross_lane_stall(gpt_params):
    """A fused-width generation sharing the machine with a plain
    chunked lane never monopolises dispatch: fused chunks are typed
    units yielded at the same boundaries, so the longest same-lane
    dispatch streak while another lane is live stays <= 1 extra
    dispatch (the one fused chunk in flight when the peer arrives)."""
    eng = _engine(
        _model(), gpt_params, fused_single=True, sched_max_batches=2,
    )
    await eng.start()
    try:
        # Slow decode so the two lanes provably overlap.
        faults.arm("decode:every=1:delay=0.01")
        rb = await eng.submit(_LONG[0], max_new_tokens=8, stream=True)
        await _wait_for(lambda: eng.sched_batches_live == 1)
        # Solo non-stream request: fused widths apply (34 new tokens
        # -> one 64-wide fused decode unit per chunk boundary).
        ra = await eng.submit(_SHORT[0], max_new_tokens=34)
        (tb, eb), (ta, ea) = await asyncio.gather(
            _collect(rb), _collect(ra)
        )
        assert ea is None and eb is None
        assert len(ta) == 34 and len(tb) == 8
        assert eng.fused_calls >= 1  # the fused path really ran
        assert eng.sched_batches_live_max == 2  # lanes overlapped
        # Max same-lane streak with >1 lane live: one fused chunk.
        assert eng.sched_lane_stall_max <= 1
        await _wait_for(lambda: eng.kv_pages_in_use == 0)
    finally:
        faults.disarm()
        await eng.stop()


# --- drain covers the typed-unit queue ---------------------------------


async def test_drain_covers_scheduler_queue(gpt_params):
    """drain()'s idle check and budget-exhausted sweep cover pending
    groups and live lanes exactly as they cover _carry: every stream
    gets a proper terminal frame, pool back to baseline."""
    eng = _engine(_model(), gpt_params, sched_max_batches=1)
    await eng.start()
    try:
        # Slowed decode chunks keep the blocker's lane provably alive
        # past the drain budget — the sweep claim must not race its
        # natural completion (0.05 x 30 chunks = a 1.5 s floor; the
        # 0.02 floor flaked on this drifting box when the submits +
        # backlog wait ran past 0.6 s and the blocker finished first,
        # letting the pending group lane and complete naturally).
        faults.arm("decode:every=1:delay=0.05")
        blocker = await eng.submit(
            _SHORT[0], max_new_tokens=60, stream=True
        )
        await _wait_for(lambda: eng.sched_batches_live == 1)
        pend = await eng.submit(_LONG[0], max_new_tokens=8, stream=True)
        await _wait_for(lambda: eng.sched.backlog >= 1)
        gather = asyncio.gather(_collect(blocker), _collect(pend))
        await eng.drain(0.05)  # budget too small: sweep fires
        (tb, ebk), (tp, ep) = await gather
        # Every consumer TERMINATED: completion or DrainCancelled.
        assert ebk is None or isinstance(ebk, DrainCancelled)
        assert ep is None or isinstance(ep, DrainCancelled)
        # The pending group can never have been laned after the sweep.
        assert isinstance(ep, DrainCancelled)
        await _wait_for(lambda: eng.sched.idle)
        assert eng.sched.idle
        assert eng.kv_pages_in_use == 0
    finally:
        faults.disarm()
        await eng.stop()


# --- router backpressure feeds the estimate/brownout -------------------


async def test_router_backpressure_feeds_estimate_and_brownout(
    gpt_params,
):
    eng = _engine(_model(), gpt_params, scheduler=False, max_queue=8)
    # Warm the reservoirs so the estimate has a rate to multiply.
    eng.latency.record_first(100.0)
    eng.latency.record_gap(10.0)
    base = eng.admission_estimate_ms()
    eng.router_queue_depth = 40
    assert eng.admission_estimate_ms() > base
    # Brownout: fleet pressure alone engages the ladder (queue empty).
    assert eng._brownout_level() >= 1
    eng.router_queue_depth = 0
    assert eng._brownout_level() == 0


async def test_router_depth_header_sets_gauge_and_metrics(
    gpt_params, monkeypatch
):
    import httpx

    from mlapi_tpu.serving.app import build_app

    # The header is only trusted on router replicas (spawned ones
    # carry this env; arbitrary direct callers must not inject fleet
    # pressure into admission control).
    monkeypatch.setenv("MLAPI_TPU_REPLICA", "1")
    eng = _engine(_model(), gpt_params, sched_max_batches=2)
    app = build_app(eng, max_wait_ms=0.0)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://t"
        ) as c:
            r = await c.post(
                "/generate",
                json={"text": "hi", "max_new_tokens": 2},
                headers={"x-mlapi-router-depth": "7"},
            )
            assert r.status_code == 200
            assert eng.router_queue_depth == 7
            m = (await c.get("/metrics")).json()
            assert m["gauges"]["generate.router_queue_depth"] == 7
            # The sched observability block is exported.
            for k in (
                "sched_units_prefill", "sched_units_decode",
                "sched_units_spec", "sched_units_admit",
                "sched_units_compact", "sched_deadline_preempts",
                "sched_pages_deferred",
            ):
                assert f"generate.{k}" in m["counters"], k
            assert "generate.sched_queue_depth" in m["gauges"]
            assert "generate.sched_batches_live" in m["gauges"]
            assert m["counters"]["generate.sched_units_decode"] >= 1
            # A direct request (no header) clears the gauge — a stale
            # fleet spike must not keep shedding.
            r = await c.post(
                "/generate", json={"text": "hi", "max_new_tokens": 2}
            )
            assert r.status_code == 200
            assert eng.router_queue_depth == 0
    finally:
        await app.shutdown()


async def test_router_depth_header_ignored_off_replica(
    gpt_params, monkeypatch
):
    """A NON-replica server ignores x-mlapi-router-depth outright: a
    direct caller must not be able to spoof fleet pressure into the
    admission estimate / brownout ladder."""
    import httpx

    from mlapi_tpu.serving.app import build_app

    monkeypatch.delenv("MLAPI_TPU_REPLICA", raising=False)
    monkeypatch.delenv("MLAPI_TPU_REPLICAS", raising=False)
    eng = _engine(_model(), gpt_params, scheduler=False)
    app = build_app(eng, max_wait_ms=0.0)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://t"
        ) as c:
            r = await c.post(
                "/generate",
                json={"text": "hi", "max_new_tokens": 2},
                headers={"x-mlapi-router-depth": "999999"},
            )
            assert r.status_code == 200
            assert eng.router_queue_depth == 0
            assert eng._brownout_level() == 0
    finally:
        await app.shutdown()


# --- churn soak --------------------------------------------------------


@pytest.mark.heavy
@pytest.mark.slow  # 7.2 s measured call — r16 tier-1 buyback (conftest);
# the 16-config identity matrix and unit-counter tests keep tier-1
# scheduler coverage.
async def test_scheduler_churn_soak(gpt_params):
    """Mixed-shape churn through the scheduler: short/long prompts,
    mixed budgets, a few deadlines — every stream terminates properly
    and the pool returns to baseline each round."""
    eng = _engine(_model(), gpt_params, sched_max_batches=2, max_batch=4)
    await eng.start()
    try:
        for round_i in range(6):
            reqs = []
            for j in range(4):
                text = "x" * 100 if (round_i + j) % 3 == 0 else f"p{j}"
                n_new = (8, 24, 40, 12)[j]
                kw = {}
                if j == 3:
                    kw["deadline_ms"] = 30000.0
                reqs.append(await eng.submit(
                    text, max_new_tokens=n_new, stream=True,
                    seed=round_i * 7 + j, **kw,
                ))
            results = await asyncio.gather(*(_collect(r) for r in reqs))
            for toks, err in results:
                assert err is None, err
                assert toks
            await _wait_for(lambda: eng.sched.idle)
            await _wait_for(lambda: eng.kv_pages_in_use == 0)
        assert eng.sched_batches_live_max >= 2
    finally:
        await eng.stop()

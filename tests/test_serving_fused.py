"""Fused-chunk widths as typed units (r20).

A fused-eligible batch decodes TIER-WIDE chunks through the same
``decode_chunk_fn`` seam as every other batch, each fused chunk one
schedulable ``"decode"`` unit — so deadlines, admission, faults and
drain apply to fused traffic with no parallel path to diverge. This
module pins the width policy's contract:

- byte-identity: fused widths change dispatch count, never tokens;
- engagement: ``fused_calls`` ticks once per batch that dispatched at
  least one fused-width chunk, and the fused engine pays strictly
  fewer ``chunk_calls`` than the plain-chunk engine;
- no declines: over-cap budgets ride the widest tier, deadlined
  requests ride fused chunks (both formerly fell back / declined);
- streams pin the plain chunk (incremental delivery), including a
  streaming JOINER admitted mid-generation into a fused lane;
- strict (high-RTT) mode takes fused widths only for shapes the warm
  grid proved compiled, and the warm grid records at the dispatch
  site so the two can never disagree.

Same model CFG as the paged family (vocab 260 / h32 / 2L / 4H /
160 pos, f32) at page 8 / chunk 2 — the module shares that cache
window (conftest) and re-drives its compiled prefill/plain-decode
programs; only the fused-width chunk shapes are new.
"""

import asyncio

import jax
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving.engine import TextGenerationEngine
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


@pytest.fixture(scope="module")
def gpt_params():
    return get_model("gpt_lm", **CFG).init(jax.random.key(0))


def _engine(params, *, fused=True, **kw):
    kw.setdefault("chunk", 2)
    kw.setdefault("kv_page_size", 8)
    kw.setdefault("max_wait_ms", 0.0)
    return TextGenerationEngine(
        get_model("gpt_lm", **CFG), params, tokenizer=ByteTokenizer(),
        fused_single=fused, **kw,
    )


async def _collect(req):
    out: list = []
    frames = 0
    while True:
        item = await req.queue.get()
        if item is None:
            return out, frames, None
        if isinstance(item, Exception):
            return out, frames, item
        out.extend(item["token_ids"])
        frames += 1


PROMPT = "the quick brown fox"  # 19 bytes -> bucket 32


def test_fused_widths_engage_and_match_chunked(gpt_params):
    fused = _engine(gpt_params)
    chunked = _engine(gpt_params, fused=False)
    for kw in (
        dict(max_new_tokens=20),                      # greedy, off-tier n
        dict(max_new_tokens=32),                      # exactly one tier
        dict(max_new_tokens=1),                       # prefill-only
        dict(max_new_tokens=17, temperature=0.9, seed=5),
        dict(max_new_tokens=17, temperature=0.8, top_k=12, top_p=0.9,
             seed=3),
    ):
        a = fused.generate_text(PROMPT, **kw)
        b = chunked.generate_text(PROMPT, **kw)
        assert a["token_ids"] == b["token_ids"], kw
    # n=1 never beats the plain chunk (width_at shrinks to the
    # remaining budget); the other four dispatched fused widths.
    assert fused.fused_calls == 4
    assert chunked.fused_calls == 0
    # The saving the fold keeps: tier-wide chunks are FEWER dispatches
    # of the same program family, not a separate program.
    assert 0 < fused.chunk_calls < chunked.chunk_calls


def test_over_cap_budget_rides_widest_tier(gpt_params):
    """fused_max_new caps the WIDTH ladder, not eligibility: a budget
    over the cap dispatches at the widest rung instead of silently
    falling back to the plain chunk (the r03 gate this replaces)."""
    fused = _engine(gpt_params)            # cap = fused_max_new = 64
    chunked = _engine(gpt_params, fused=False)
    a = fused.generate_text(PROMPT, max_new_tokens=100)
    assert len(a["token_ids"]) == 100
    assert fused.fused_calls == 1          # engaged, 64-wide chunks
    b = chunked.generate_text(PROMPT, max_new_tokens=100)
    assert a["token_ids"] == b["token_ids"]


def test_strict_mode_requires_warmed_fused_shape(gpt_params):
    eng = _engine(gpt_params)
    eng._strict_admit = True             # strict discipline, no warmup
    eng.generate_text(PROMPT, max_new_tokens=32)
    assert eng.fused_calls == 0          # unwarmed shape -> plain chunks
    eng._strict_admit = False
    eng.generate_text(PROMPT, max_new_tokens=32)
    assert eng.fused_calls == 1          # proves itself once allowed
    eng._strict_admit = True
    eng.generate_text(PROMPT, max_new_tokens=32)
    assert eng.fused_calls == 2          # now warmed -> fused in strict


def test_warmup_populates_fused_width_grid(gpt_params):
    """warm() drives REAL solo runs at ladder budgets, so the warmed
    set is populated at the dispatch site — strict mode then takes
    fused widths for exactly the shapes that actually compiled."""
    eng = _engine(gpt_params)
    eng.warmup(full=False)
    # Minimal warmup: first bucket at every ladder width up to the
    # default tier (4, 8, 16, 32 at chunk=2).
    assert len(eng.fused.warmed) >= 4
    eng._strict_admit = True
    eng.generate_text("ab", max_new_tokens=8)
    assert eng.fused_calls >= 1          # warmed shape fused in strict


async def test_formed_batch_rides_fused_widths(gpt_params):
    """A multi-row all-non-streaming batch dispatches fused widths
    exactly like a solo one (the r05 fused_batch flag is gone — width
    policy is per boundary, not per path); every row byte-identical
    to its solo run, mixed greedy/sampled/budgets included."""
    eng = _engine(gpt_params)
    solo = _engine(gpt_params)
    loop = asyncio.get_running_loop()
    specs = [
        ("the quick brown fox", dict(n=12, temp=0.0, seed=0)),
        ("jumps over", dict(n=20, temp=0.8, seed=3)),
        ("the lazy dog", dict(n=5, temp=0.0, seed=0)),
    ]
    reqs = [
        eng._encode(text, kw["n"], kw["temp"], kw["seed"], loop)
        for text, kw in specs
    ]
    await loop.run_in_executor(None, lambda: eng._run_batch(reqs, True))
    assert eng.fused_calls == 1
    assert eng.chunk_calls == 1  # one 32-wide chunk covered all rows
    for (text, kw), r in zip(specs, reqs):
        got, _, err = await _collect(r)
        assert err is None
        ref = solo.generate_text(
            text, max_new_tokens=kw["n"], temperature=kw["temp"],
            seed=kw["seed"],
        )
        assert got == ref["token_ids"], text
        assert len(got) == kw["n"]


async def test_deadlined_request_rides_fused_chunks(gpt_params):
    """Deadlines no longer decline the fused path: a deadlined
    fused-eligible request dispatches tier-wide chunks, and the r12
    expiry sweeps still run at every unit boundary (one seam)."""
    eng = _engine(gpt_params)
    await eng.start()
    try:
        r = await eng.submit(
            PROMPT, max_new_tokens=34, deadline_ms=60000.0,
        )
        toks, _, err = await _collect(r)
        assert err is None
        assert len(toks) == 34
        assert eng.fused_calls == 1      # fused despite the deadline
    finally:
        await eng.stop()
    ref = _engine(gpt_params, fused=False).generate_text(
        PROMPT, max_new_tokens=34
    )
    assert toks == ref["token_ids"]


async def test_streams_identical_across_execution_modes(gpt_params):
    """The identity matrix cell this module owns: fused default
    (scheduler on), fused serial (sched_max_batches=1) and plain
    chunked produce byte-identical streams for the same traffic."""
    engines = [
        _engine(gpt_params),                        # fused, scheduler on
        _engine(gpt_params, sched_max_batches=1),   # fused, serial
        _engine(gpt_params, fused=False),           # plain chunks
    ]
    outs = []
    for eng in engines:
        await eng.start()
        try:
            # Non-stream wave first: submitted together they may group
            # (or lane separately — identical bytes either way) and on
            # the fused engines they ride tier-wide chunks. The stream
            # goes AFTER the wave completes, or it would join the same
            # window and pin the plain width for everyone.
            reqs = [
                await eng.submit(PROMPT, max_new_tokens=20),
                await eng.submit("jumps over", max_new_tokens=17,
                                 temperature=0.9, seed=5),
            ]
            got = []
            for r in reqs:
                toks, _, err = await _collect(r)
                assert err is None
                got.append(toks)
            s = await eng.submit("the lazy dog", max_new_tokens=12,
                                 stream=True)
            toks, _, err = await _collect(s)
            assert err is None
            got.append(toks)
            outs.append(got)
        finally:
            await eng.stop()
    assert outs[0] == outs[1] == outs[2]
    assert engines[0].fused_calls >= 1
    assert engines[1].fused_calls >= 1
    assert engines[2].fused_calls == 0


async def test_streaming_rows_pin_plain_chunks(gpt_params):
    """Incremental delivery wins over width: a streaming request
    decodes at the plain chunk and its consumer sees >1 frames."""
    eng = _engine(gpt_params)
    await eng.start()
    try:
        r = await eng.submit(PROMPT, max_new_tokens=12, stream=True)
        toks, frames, err = await _collect(r)
        assert err is None
        assert frames > 1                # incremental delivery kept
        assert eng.fused_calls == 0
    finally:
        await eng.stop()
    ref = _engine(gpt_params).generate_text(PROMPT, max_new_tokens=12)
    assert toks == ref["token_ids"]


async def test_streaming_joiner_drops_width_mid_generation(gpt_params):
    """Continuous admission reaches fused traffic (the old
    one-program path stranded joiners for a whole generation): a
    streaming joiner installs at a fused-chunk boundary and the width
    drops to the plain chunk while it is live — the joiner streams
    incrementally and both rows stay byte-identical to solo runs."""
    from mlapi_tpu.serving import faults

    eng = _engine(gpt_params)
    await eng.start()
    try:
        # Host budget over the widest rung: 100 new tokens ride
        # 64-wide chunks, so there is a boundary after the first
        # fused chunk for the joiner to install at. The decode delay
        # keeps the window open without wall-clock assertions.
        faults.arm("decode:every=1:delay=0.05")
        host = await eng.submit("hello", max_new_tokens=100)
        # Wait for the first fused-width dispatch to be IN FLIGHT, so
        # the joiner cannot land before it and pin the plain width
        # from the start.
        deadline = asyncio.get_running_loop().time() + 60.0
        while eng.fused_calls < 1:
            assert asyncio.get_running_loop().time() < deadline
            await asyncio.sleep(0.002)
        joiner = await eng.submit("ab", max_new_tokens=4, stream=True)
        (ht, _, he), (jt, jframes, je) = await asyncio.gather(
            _collect(host), _collect(joiner)
        )
        assert he is None and je is None
        assert len(ht) == 100 and len(jt) == 4
        assert eng.admitted == 1         # joined the fused lane
        assert eng.fused_calls == 1      # fused before the joiner
        assert jframes > 1               # streamed at plain width
    finally:
        faults.disarm()
        await eng.stop()
    ref = _engine(gpt_params)
    assert ht == ref.generate_text("hello", max_new_tokens=100)["token_ids"]
    assert jt == ref.generate_text("ab", max_new_tokens=4)["token_ids"]

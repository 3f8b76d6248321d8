"""Page-native prefill + chunked prefill/decode interleaving (r10):
``models/gpt.paged_prefill_fn``, the page-native formation/admission
paths in ``serving/batch_run.py``, the interleaved long-prompt
prefill, page-aligned stacked prefix sharing, and the paged ×
speculative composition.

The contracts these tests pin:

- **Adopt-copy bytes are exactly zero** on the page-native path and
  exactly one prefill copy per formation/admission on the legacy
  adopt path — both sides from dtype/shape arithmetic
  (``ops/quant.kv_tree_bytes``), never wall-clock — with greedy token
  streams IDENTICAL between the two paths across
  {gpt-MHA, llama-GQA} × {none, int8} × {einsum, flash}.
- **Interleaving bounds head-of-line blocking**: a long prompt
  admitted into a running batch delays the running streams by at most
  ONE prefill-chunk dispatch (``engine.interleave_max_stall``),
  short joiners still admit DURING the window, and the long prompt's
  stream is identical with interleaving on, off, and solo.
- **Pool exhaustion mid-prefill rejects loudly** without poisoning
  the pool.
- **Stacked (cross-prefix) groups share ref-counted pages** when the
  store-time page alignment holds (zero adopt bytes, COW divergence
  for partial group-end tiles), and fall back to copy semantics —
  loudly counted — when a cap-clamped entry breaks alignment.
- **Paged × speculative**: solo and batched speculation engage on
  paged batches (streams pinned to the plain engine), the batched
  handoff realigns as a host page-table shift when deltas are page
  multiples and as the counted device row-gather otherwise, and —
  since r11 — the last two declines are LIFTED: strict-admit mode
  (the spec warm grid compiles pool-shaped programs) and
  mesh-sharded pools (flash-extend's ``shard_map`` leg), both pinned
  as passing end-to-end stream-identity tests.
"""

import asyncio

import jax
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.ops.quant import kv_tree_bytes
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.serving.paged_pool import PagePoolExhausted
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
# Long-context variant for the chunked-prefill interleaving tests: a
# 200-token prompt rounds to a [256]-wide bucket (two 128-wide chunks)
# and still leaves decode room inside the window.
# Wide enough that the interleave drill's 200-char joiner (bucket 256)
# is window-compatible with the 130-token stream it joins
# (``_compatible``: 256 + 130 <= max_positions); at 320 the collector
# lanes it as a batch of its own and nothing interleaves.
LONG_CFG = dict(CFG, max_positions=512)


def _model(kind="gpt_lm", kv_quant="none", impl="einsum", cfg=CFG):
    kw = dict(cfg, kv_quant=kv_quant, decode_attn_impl=impl)
    if kind == "llama_lm":
        kw["num_kv_heads"] = 2  # GQA: 4 query heads over 2 KV heads
    return get_model(kind, **kw)


@pytest.fixture(scope="module")
def gpt_params():
    return _model().init(jax.random.key(0))


@pytest.fixture(scope="module")
def llama_params():
    return _model("llama_lm").init(jax.random.key(0))


@pytest.fixture(scope="module")
def long_gpt_params():
    return _model(cfg=LONG_CFG).init(jax.random.key(1))


def _engine(model, params, **kw):
    kw.setdefault("chunk", 2)
    # Pin the chunked batch lifecycle: the fused fast paths build
    # transient in-program caches and never touch the pool.
    kw.setdefault("fused_single", False)
    kw.setdefault("kv_page_size", 8)
    return TextGenerationEngine(
        model, params, tokenizer=ByteTokenizer(), **kw
    )


async def _collect(req) -> list[int]:
    out: list[int] = []
    while True:
        item = await req.queue.get()
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.extend(item["token_ids"])


async def _quiesce(eng, expect: int = 0) -> None:
    """Wait for the decode thread's batch teardown: the completion
    sentinels are pushed BEFORE ``_paged_cleanup`` releases the
    batch's pages (the realign/write-back tail runs after delivery),
    so a pool assert straight after ``gather`` races it."""
    for _ in range(500):
        if eng.kv_pages_in_use == expect:
            return
        await asyncio.sleep(0.01)


def _cache_bytes(model, b: int, width: int) -> int:
    """Exact bytes of a contiguous [b, width] cache tree — what one
    legacy adopt scatter copies (pure eval_shape arithmetic)."""
    return kv_tree_bytes(
        jax.eval_shape(lambda: model.init_cache(b, width))
    )


# --- page-native vs legacy adopt: streams + exact byte accounting ------


@pytest.mark.parametrize("impl", ["einsum", "flash"])
@pytest.mark.parametrize("fmt", ["none", "int8"])
@pytest.mark.parametrize("kind", ["gpt_lm", "llama_lm"])
def test_stream_identical_and_adopt_bytes_exact(
    kind, fmt, impl, gpt_params, llama_params
):
    params = gpt_params if kind == "gpt_lm" else llama_params
    model = _model(kind, fmt, impl)
    native = _engine(model, params)
    legacy = _engine(model, params, prefill_page_native=False)
    prompt = "hello world"  # 11 tokens -> the 16 bucket
    a = native.generate_text(prompt, max_new_tokens=6)
    b = legacy.generate_text(prompt, max_new_tokens=6)
    assert a["token_ids"] == b["token_ids"], (kind, fmt, impl)
    # The whole claim, from dtype/shape arithmetic: page-native moved
    # ZERO adopt bytes; legacy re-copied exactly one [1, 16] cache.
    assert native.prefill_adopt_bytes == 0
    assert legacy.prefill_adopt_bytes == _cache_bytes(model, 1, 16)
    # Every page went back either way.
    assert native.kv_pages_in_use == 0
    assert legacy.kv_pages_in_use == 0


def test_adopt_bytes_accumulate_per_formation(gpt_params):
    model = _model()
    legacy = _engine(model, gpt_params, prefill_page_native=False)
    legacy.generate_text("hello world", max_new_tokens=4)   # bucket 16
    legacy.generate_text("b" * 40, max_new_tokens=4)        # bucket 64
    assert legacy.prefill_adopt_bytes == (
        _cache_bytes(model, 1, 16) + _cache_bytes(model, 1, 64)
    )


async def test_admission_page_native_zero_adopt(gpt_params):
    """Mid-batch admission writes the joiner's bucket straight into
    its mapped pages: zero adopt bytes page-native, exactly one
    [1, bucket] copy per joiner legacy — streams identical."""
    model = _model()
    outs = {}
    for native in (True, False):
        eng = _engine(
            model, gpt_params, max_wait_ms=0.0,
            prefill_page_native=native,
        )
        await eng.start()
        try:
            r1 = await eng.submit("the first long request",
                                  max_new_tokens=48, stream=True)
            head = await r1.queue.get()
            assert not isinstance(head, Exception)
            r2 = await eng.submit("joiner", max_new_tokens=6)
            outs[native] = await asyncio.gather(
                _collect(r1), _collect(r2)
            )
            outs[native][0] = head["token_ids"] + outs[native][0]
            assert eng.admitted >= 1
            if native:
                assert eng.prefill_adopt_bytes == 0
            else:
                # formation (bucket 64) + one admitted joiner
                # (bucket 16), each exactly one cache copy.
                assert eng.prefill_adopt_bytes == (
                    _cache_bytes(model, 1, 64)
                    + _cache_bytes(model, 1, 16)
                )
        finally:
            await eng.stop()
    assert outs[True] == outs[False]


# --- chunked prefill/decode interleaving -------------------------------


async def test_interleaved_long_prompt_bounded_stall(long_gpt_params):
    """The tentpole's serving half: a 200-token prompt admitted into a
    running batch prefills as chunks interleaved with decode — running
    streams stall by at most ONE prefill-chunk dispatch (engine
    counters, not wall-clock), a short joiner still admits during the
    window, and every stream is identical with interleaving on, off,
    and solo."""
    model = _model(cfg=LONG_CFG)
    long_prompt = "x" * 200
    outs = {}
    for ilv in (True, False):
        eng = _engine(
            model, long_gpt_params, max_wait_ms=0.0,
            prefill_interleave=ilv,
        )
        if ilv:
            # Solo reference: the same prompt through formation-time
            # chunked prefill (its own batch, different cache tier) —
            # placement-invariance says the stream cannot move.
            solo = eng.generate_text(long_prompt, max_new_tokens=6)
            assert eng.prefill_chunks >= 2
        await eng.start()
        try:
            r1 = await eng.submit("hi", max_new_tokens=130, stream=True)
            head = await r1.queue.get()
            assert not isinstance(head, Exception)
            r2 = await eng.submit(long_prompt, max_new_tokens=6)
            r3 = await eng.submit("yo", max_new_tokens=4)
            outs[ilv] = await asyncio.gather(
                _collect(r1), _collect(r2), _collect(r3)
            )
            outs[ilv][0] = head["token_ids"] + outs[ilv][0]
            if ilv:
                assert eng.interleaved_prefills == 1
                # THE bound: live decode rows never waited behind more
                # than one consecutive prefill-chunk dispatch.
                assert eng.interleave_max_stall == 1
                assert eng.admitted >= 2  # r2 interleaved + r3 one-shot
                assert outs[ilv][1] == solo["token_ids"]
                assert eng.prefill_adopt_bytes == 0
            assert eng.prefill_chunk_queue_depth == 0
            await _quiesce(eng)
            assert eng.kv_pages_in_use == 0
        finally:
            await eng.stop()
    # Interleaving on/off: every stream byte-identical.
    assert outs[True] == outs[False]


def test_pool_exhaustion_mid_prefill_loud_and_clean(long_gpt_params):
    """A long-prompt prefill that cannot fit the pool fails BEFORE any
    device work, loudly, leaving the pool consistent: the next request
    that fits still serves."""
    model = _model(cfg=LONG_CFG)
    tiny = _engine(
        model, long_gpt_params, kv_page_size=8, kv_pages=10,
    )
    with pytest.raises(PagePoolExhausted, match="kv-pages"):
        tiny.generate_text("x" * 200, max_new_tokens=6)
    assert tiny.kv_pages_in_use == 0
    out = tiny.generate_text("hi", max_new_tokens=2)
    assert len(out["token_ids"]) == 2
    assert tiny.kv_pages_in_use == 0


# --- page-aligned stacked (cross-prefix) sharing -----------------------


async def test_stacked_group_shares_pages_zero_adopt(gpt_params):
    """Two requests behind DIFFERENT prefixes form one stacked batch;
    store-time page alignment makes the right-alignment shifts page
    multiples, so both rows point at their entries' ref-counted pages:
    no widened-stack copy (zero adopt bytes, no fallback), streams
    equal the contiguous engine's."""
    model = _model()
    pa, pb = "You are a helpful bot.", "tl;dr"  # buckets 64 / 16
    cont = _engine(model, gpt_params, kv_page_size=None,
                   max_wait_ms=300.0)
    paged = _engine(model, gpt_params, max_wait_ms=300.0)
    for eng in (cont, paged):
        # Register both entries (their own solo batches), then group.
        eng.generate_text(" q0", max_new_tokens=2, prefix=pa)
        eng.generate_text(" q0", max_new_tokens=2, prefix=pb)
    outs = {}
    for key, eng in (("cont", cont), ("paged", paged)):
        await eng.start()
        try:
            before = eng.batch_calls
            ra = await eng.submit(" qa", max_new_tokens=6, prefix=pa)
            rb = await eng.submit(" qb", max_new_tokens=6, prefix=pb)
            outs[key] = await asyncio.gather(_collect(ra), _collect(rb))
            # One batch served both -> the stacked (mixed) path ran.
            assert eng.batch_calls == before + 1
        finally:
            await eng.stop()
    assert outs["paged"] == outs["cont"]
    assert paged.kv_prefix_copy_fallback == 0
    assert paged.prefill_adopt_bytes == 0  # no widened-stack scatter
    # Only the two entries' own page holds remain.
    entry_holds = sum(
        len(paged.pool.entry_pages(p)) for p in (pa, pb)
    )
    await _quiesce(paged, entry_holds)
    assert paged.kv_pages_in_use == entry_holds


async def test_stacked_group_unaligned_falls_back_loudly(gpt_params):
    """A cap-clamped entry cannot page-align (135 tokens, aligned 144
    > cap 143): a stacked group containing it keeps r09 copy
    semantics, counted in the fallback gauge — streams still match
    the contiguous engine."""
    model = _model()
    pu, pb = "c" * 135, "tl;dr"  # 135 stays unaligned at page 12
    cont = _engine(model, gpt_params, kv_page_size=None,
                   max_wait_ms=300.0)
    paged = _engine(model, gpt_params, kv_page_size=12,
                    max_wait_ms=300.0)
    for eng in (cont, paged):
        eng.generate_text(" q", max_new_tokens=2, prefix=pu)
        eng.generate_text(" q", max_new_tokens=2, prefix=pb)
    outs = {}
    for key, eng in (("cont", cont), ("paged", paged)):
        await eng.start()
        try:
            ra = await eng.submit(" qa", max_new_tokens=4, prefix=pu)
            rb = await eng.submit(" qb", max_new_tokens=4, prefix=pb)
            outs[key] = await asyncio.gather(_collect(ra), _collect(rb))
        finally:
            await eng.stop()
    assert outs["paged"] == outs["cont"]
    assert paged.kv_prefix_copy_fallback >= 1
    assert paged.prefill_adopt_bytes > 0  # the widened stack copied


async def test_stacked_same_width_shares_with_cow(gpt_params):
    """Two DISTINCT cap-clamped prefixes of the same (unaligned)
    width: shifts are zero (page multiples), so the stacked group
    SHARES pages, and the partial group-end tile diverges per row by
    COW — the sharing + divergence composition, pinned against the
    contiguous engine."""
    model = _model()
    p1, p2 = "c" * 135, "d" * 135
    cont = _engine(model, gpt_params, kv_page_size=None,
                   max_wait_ms=300.0)
    paged = _engine(model, gpt_params, kv_page_size=12,
                    max_wait_ms=300.0)
    for eng in (cont, paged):
        eng.generate_text(" q", max_new_tokens=2, prefix=p1)
        eng.generate_text(" q", max_new_tokens=2, prefix=p2)
    cows_before = paged.pool.cow_copies
    adopt_before = paged.prefill_adopt_bytes
    outs = {}
    for key, eng in (("cont", cont), ("paged", paged)):
        await eng.start()
        try:
            r1 = await eng.submit(" qa", max_new_tokens=4, prefix=p1)
            r2 = await eng.submit(" qb", max_new_tokens=4, prefix=p2)
            outs[key] = await asyncio.gather(_collect(r1), _collect(r2))
        finally:
            await eng.stop()
    assert outs["paged"] == outs["cont"]
    assert paged.kv_prefix_copy_fallback == 0     # shared, not copied
    assert paged.prefill_adopt_bytes == adopt_before
    assert paged.pool.cow_copies >= cows_before + 2  # one per row
    # Wait out the batch teardown before reusing the pool from this
    # thread, then: the shared pages came out unscathed.
    await _quiesce(paged, sum(
        len(paged.pool.entry_pages(p)) for p in (p1, p2)
    ))
    again = paged.generate_text(" qa", max_new_tokens=4, prefix=p1)
    assert again["token_ids"] == outs["paged"][0]


# --- paged × speculative ----------------------------------------------

T_CFG = dict(
    vocab_size=260, hidden_size=48, num_layers=3, num_heads=4,
    max_positions=160, compute_dtype="float32",
)
D_CFG = dict(
    vocab_size=260, hidden_size=24, num_layers=1, num_heads=2,
    max_positions=160, compute_dtype="float32",
)


@pytest.fixture(scope="module")
def spec_models():
    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    return target, target.init(jax.random.key(0)), draft, draft.init(
        jax.random.key(1)
    )


def test_solo_spec_engages_on_paged_batches(spec_models):
    """The r09 'spec phases decline paged batches' guard LIFTS for
    solo speculation: it needs no realign, only per-round page
    mapping. Stream pinned to the draft-less contiguous engine."""
    target, tp, draft, dp = spec_models
    plain = _engine(target, tp, kv_page_size=None)
    spec = _engine(target, tp, draft=(draft, dp), spec_k=3)
    for prompt in ("speculate on pages", "another stream"):
        a = plain.generate_text(prompt, max_new_tokens=20)
        b = spec.generate_text(prompt, max_new_tokens=20)
        assert a["token_ids"] == b["token_ids"], prompt
    assert spec.spec_rounds > 0  # it actually engaged
    assert spec.kv_pages_in_use == 0


@pytest.mark.parametrize("page,counter", [
    (1, "spec_realign_table_ops"),   # deltas always page multiples
    (8, "spec_realign_repacks"),     # delta 7: sub-page -> row gather
])
async def test_batched_spec_paged_realign(spec_models, page, counter):
    """Batched speculation on a paged batch: rows with different
    budgets desynchronize (draft == target -> full acceptance, so the
    handoff delta is exactly n_new1 - n_new2 = 7) and the realign runs
    as a host table shift at page 1 / the counted device row-gather
    at page 8. Streams pinned to the draft-less contiguous engine."""
    target, tp, _, _ = spec_models
    plain = _engine(target, tp, kv_page_size=None, max_wait_ms=2000.0)
    spec = _engine(
        target, tp, kv_page_size=page, draft=(target, tp), spec_k=4,
        max_wait_ms=2000.0,
    )
    outs = {}
    for key, eng in (("plain", plain), ("spec", spec)):
        await eng.start()
        try:
            r1 = await eng.submit("aaaa", max_new_tokens=11)
            r2 = await eng.submit("bbbb", max_new_tokens=4)
            outs[key] = await asyncio.gather(_collect(r1), _collect(r2))
        finally:
            await eng.stop()
    assert outs["spec"] == outs["plain"]
    assert spec.spec_rounds > 0
    # The batched phase pushes each row's terminal sentinel INSIDE the
    # round loop the moment its budget is met; the handoff realign —
    # and the counter this test pins — runs on the decode thread after
    # the loop breaks. gather() returning therefore does NOT mean the
    # batch tail ran: condition-wait on the counter itself (bounded
    # poll on counters, never a bare sleep as synchronization).
    for _ in range(500):
        if getattr(spec, counter) >= 1:
            break
        await asyncio.sleep(0.01)
    assert getattr(spec, counter) >= 1, counter
    await _quiesce(spec)
    assert spec.kv_pages_in_use == 0


def test_paged_spec_strict_admit_engages(spec_models):
    """FORMER DECLINE PIN, now a passing end-to-end test (r11): in
    strict (high-RTT) mode the spec warm grid compiles POOL-SHAPED
    verify/realign programs for paged engines (``SpecPhase.warm``
    branches on ``eng.pool``), so paged batches speculate without a
    mid-batch compile — and an engine whose paged shapes were NOT
    warmed still declines safely inside the phase (the warmed-key
    gate, unchanged)."""
    target, tp, draft, dp = spec_models
    plain = _engine(target, tp, kv_page_size=None)
    ref = plain.generate_text("declined", max_new_tokens=8)

    strict = _engine(
        target, tp, draft=(draft, dp), prompt_buckets=(16,),
        max_batch=2,
    )
    pages_before = strict.kv_pages_in_use
    shapes = strict.spec.warm()
    assert shapes >= 2  # solo + one batched size, paged-shaped
    # Null-table warm writes die in the null page: pool untouched.
    assert strict.kv_pages_in_use == pages_before
    strict._strict_admit = True
    out = strict.generate_text("declined", max_new_tokens=8)
    assert out["token_ids"] == ref["token_ids"]
    assert strict.spec_rounds > 0  # the decline is gone

    # Unwarmed strict engine: the phase's own gate still declines —
    # correct output, no speculation, no mid-batch compile.
    cold = _engine(target, tp, draft=(draft, dp))
    cold._strict_admit = True
    out = cold.generate_text("declined", max_new_tokens=8)
    assert out["token_ids"] == ref["token_ids"]
    assert cold.spec_rounds == 0


def test_paged_spec_mesh_sharded_pool_engages(spec_models):
    """FORMER DECLINE PIN, now a passing end-to-end test (r11): spec
    over a MESH-SHARDED pool. The einsum verify partitions as a plain
    GSPMD gather+einsum; the flash verify routes through the
    flash-extend ``shard_map`` leg (``extend_attention_tp`` /
    ``paged_extend_attention_tp``) so the opaque kernel runs per head
    shard. Streams pinned to the draft-less contiguous engine for
    BOTH impls."""
    import dataclasses

    from mlapi_tpu.parallel import create_mesh

    target, tp, draft, dp = spec_models
    plain = _engine(target, tp, kv_page_size=None)
    ref = plain.generate_text("declined", max_new_tokens=8)
    mesh = create_mesh((1, 2), devices=jax.devices()[:2])
    for impl in ("einsum", "flash"):
        t_i = dataclasses.replace(target, decode_attn_impl=impl)
        d_i = dataclasses.replace(draft, decode_attn_impl=impl)
        meshed = _engine(t_i, tp, draft=(d_i, dp), mesh=mesh)
        out = meshed.generate_text("declined", max_new_tokens=8)
        assert out["token_ids"] == ref["token_ids"], impl
        assert meshed.spec_rounds > 0, impl  # the decline is gone
        assert meshed.kv_pages_in_use == 0


# --- observability ------------------------------------------------------


async def test_metrics_exports_prefill_gauges(gpt_params):
    import httpx

    from mlapi_tpu.serving import build_app

    eng = _engine(_model(), gpt_params)
    eng.generate_text("warm the reservoirs", max_new_tokens=4)
    app = build_app(eng)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as c:
            snap = (await c.get("/metrics")).json()
        cnt, g = snap["counters"], snap["gauges"]
        assert cnt["generate.prefill_adopt_bytes"] == 0
        assert cnt["generate.kv_prefix_copy_fallback"] == 0
        assert cnt["generate.interleaved_prefills"] == 0
        assert cnt["generate.spec_realign_table_ops"] == 0
        assert cnt["generate.spec_realign_repacks"] == 0
        assert g["generate.prefill_chunk_queue_depth"] == 0
        assert g["generate.interleave_max_stall"] == 0
        # The latency reservoirs saw the warm request above.
        assert g["generate.ttft_p50_ms"] is not None
        assert g["generate.intertoken_p50_ms"] is not None
    finally:
        await app.shutdown()


# --- soak: interleaved admissions under churn (heavy) -------------------


@pytest.mark.heavy
@pytest.mark.slow  # 7.1 s measured call — r16 tier-1 buyback (conftest);
# interleaving correctness stays pinned by the counter-based tests.
async def test_interleaved_churn_no_leaks(long_gpt_params):
    """Several consecutive interleaved long-prompt admissions against
    a continuously-decoding stream: every window must activate, every
    page return, and the stall bound must hold across the whole run."""
    model = _model(cfg=LONG_CFG)
    eng = _engine(model, long_gpt_params, max_wait_ms=0.0)
    refs = [
        eng.generate_text("x" * (129 + 7 * i), max_new_tokens=5)
        ["token_ids"]
        for i in range(3)
    ]
    await eng.start()
    try:
        r1 = await eng.submit("hi", max_new_tokens=200, stream=True)
        head = await r1.queue.get()
        assert not isinstance(head, Exception)
        longs = [
            await eng.submit("x" * (129 + 7 * i), max_new_tokens=5)
            for i in range(3)
        ]
        outs = await asyncio.gather(
            _collect(r1), *[_collect(r) for r in longs]
        )
        assert [o for o in outs[1:]] == refs
        assert eng.interleaved_prefills >= 1
        assert eng.interleave_max_stall <= 1
        await _quiesce(eng)
        assert eng.kv_pages_in_use == 0
    finally:
        await eng.stop()

"""Paged KV cache: block-granular page pool + page-table flash-decode
+ copy-on-write prefix sharing (``serving/paged_pool.py``, the paged
seams in ``ops/quant.py``, ``ops/pallas.paged_decode_attention``, the
paged ``BatchRun`` lifecycle, ``--kv-page-size``).

The contract these tests pin, layer by layer:

- **Host bookkeeping**: page alloc/free round-trips, refcounts,
  LRU eviction of prefix page sets under pressure, and the LOUD
  :class:`PagePoolExhausted` reject — never a silent spill.
- **Device seams**: a paged layer (pool + table) appends and reads
  byte-identically to the contiguous layout, both cache formats,
  scalar and per-row positions; the page-table kernel matches the
  contiguous kernel over gathered pages.
- **The serving stack**: greedy token streams are IDENTICAL between
  paged and contiguous allocation across {MHA, GQA} x {none, int8} x
  {einsum, flash} — solo, continuously-admitted, and behind shared
  prefixes (whose pages are ref-shared, diverging by COW, never
  copied per row).
- **The capacity model**: padding waste and slot capacity come from
  dtype/shape arithmetic (never wall-clock).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.ops.quant import (
    init_kv_cache,
    kv_cache_append,
    kv_cache_kv,
    kv_cache_seq_len,
    kv_page_bytes,
    make_paged_pools,
    paged_cache_tree,
)
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.serving.paged_pool import PagePool, PagePoolExhausted
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


def _engine(model, params, paged, **kw):
    kw.setdefault("chunk", 2)
    # Pin the chunked batch lifecycle: the fused fast paths build
    # their own transient in-program caches and never touch the pool.
    kw.setdefault("fused_single", False)
    if paged:
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


# --- host bookkeeping --------------------------------------------------


def test_pool_alloc_free_roundtrip():
    pool = PagePool(_model(), page_size=8, num_pages=9)
    assert pool.pages_total == 8  # page 0 is the null page, not capacity
    a = pool.alloc(3)
    assert 0 not in a and len(set(a.tolist())) == 3
    assert pool.pages_in_use == 3
    assert pool.pages_shared == 0
    pool.retain(a)  # second holder
    assert pool.pages_shared == 3  # ref > 1 counts, null excluded
    pool.release(a)  # first holder gone; still held
    assert pool.pages_in_use == 3
    assert pool.pages_shared == 0
    pool.release(a)
    assert pool.pages_in_use == 0
    # Freed pages are allocatable again; the whole pool round-trips.
    b = pool.alloc(8)
    assert pool.pages_in_use == 8
    with pytest.raises(PagePoolExhausted):
        pool.alloc(1)
    pool.release(b)
    assert pool.pages_in_use == 0


def test_pool_double_release_is_loud():
    pool = PagePool(_model(), page_size=8, num_pages=4)
    a = pool.alloc(1)
    pool.release(a)
    with pytest.raises(AssertionError, match="below zero"):
        pool.release(a)


def test_pool_pressure_evicts_lru_entry_pages():
    pool = PagePool(_model(), page_size=8, num_pages=7)
    e1, e2 = pool.alloc(2), pool.alloc(2)
    pool.put_entry_pages("sys-a", e1)
    pool.put_entry_pages("sys-b", e2)
    pool.entry_pages("sys-a")  # touch: b is now LRU... a is MRU
    # 2 free pages left; asking for 4 must evict entry sets — LRU
    # ("sys-b"? no: insertion a,b then touch a -> b older) first.
    got = pool.alloc(4)
    assert len(got) == 4
    assert pool.entry_evictions >= 1
    # A row-referenced entry set is NOT evictable: pin one and fill.
    pool2 = PagePool(_model(), page_size=8, num_pages=4)
    e = pool2.alloc(2)
    pool2.put_entry_pages("sys", e)
    pool2.retain(e)  # a live batch row shares these pages
    with pytest.raises(PagePoolExhausted):
        pool2.alloc(2)
    # Atomic lookup+holds: the row references ride the same lock as
    # the lookup (a bare lookup-then-retain would race drop_entry).
    pool3 = PagePool(_model(), page_size=8, num_pages=4)
    e3 = pool3.alloc(1)
    pool3.put_entry_pages("sys", e3)
    got = pool3.entry_pages("sys", holds=2)
    assert np.array_equal(got, e3)
    pool3.drop_entry("sys")  # entry hold gone; rows still hold 2
    assert pool3.pages_in_use == 1
    pool3.release(e3)
    pool3.release(e3)
    assert pool3.pages_in_use == 0


def test_pool_eviction_counter_exact_under_concurrency():
    """Regression pin for the r16 mlapi-lint MLA002 fix: evictions
    run concurrently from the decode thread (alloc pressure) and the
    event loop (brownout ``evict_idle``), and ``entry_evictions`` —
    scraped by /metrics as ``generate.kv_entry_evictions`` — was
    bumped OUTSIDE the pool lock, so concurrent evictions could lose
    updates. The counter must now be exact: every registered entry
    evicted exactly once, counted exactly once, whatever the thread
    interleaving."""
    import threading

    n_entries = 24
    pool = PagePool(_model(), page_size=8, num_pages=n_entries + 2)
    for i in range(n_entries):
        pool.put_entry_pages(f"sys-{i}", pool.alloc(1))
    assert pool.pages_in_use == n_entries

    def churn():
        while pool.evict_idle(3):
            pass

    threads = [threading.Thread(target=churn) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # The pop-is-the-claim protocol means each entry evicts once; the
    # COUNTER matching it exactly is what the lock fix guarantees.
    assert pool.entry_evictions == n_entries
    assert pool.pages_in_use == 0
    assert pool.exhaustions == 0


# --- device seams ------------------------------------------------------


@pytest.mark.parametrize("fmt", ["none", "int8"])
def test_paged_append_and_read_match_contiguous(fmt):
    page, npv, b = 8, 4, 2
    heads, hd = CFG["num_heads"], CFG["hidden_size"] // CFG["num_heads"]
    m = get_model("gpt_lm", **dict(CFG, kv_quant=fmt))
    pools = make_paged_pools(m, 10, page)
    tab = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    lay_p = {**pools["layer_0"], "table": jnp.asarray(tab)}
    lay_c = init_kv_cache(b, npv * page, heads, hd, jnp.float32, fmt)
    k = jax.random.normal(jax.random.key(0), (b, 3, heads, hd))
    v = jax.random.normal(jax.random.key(1), (b, 3, heads, hd))
    # Scalar-pos block write (serving layout), spanning a page edge.
    lay_p = kv_cache_append(lay_p, k, v, jnp.int32(6), jnp.float32)
    lay_c = kv_cache_append(lay_c, k, v, jnp.int32(6), jnp.float32)
    # Per-row-pos single-token write (speculation layout).
    k1 = jax.random.normal(jax.random.key(2), (b, 1, heads, hd))
    v1 = jax.random.normal(jax.random.key(3), (b, 1, heads, hd))
    pv = jnp.asarray(np.array([9, 12], np.int32))
    lay_p = kv_cache_append(lay_p, k1, v1, pv, jnp.float32)
    lay_c = kv_cache_append(lay_c, k1, v1, pv, jnp.float32)
    kp, vp = kv_cache_kv(lay_p, jnp.float32)
    kc, vc = kv_cache_kv(lay_c, jnp.float32)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(kc))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(vc))
    assert kv_cache_seq_len({"layer_0": lay_p}) == npv * page


@pytest.mark.parametrize("fmt", ["none", "int8"])
def test_paged_kernel_matches_contiguous_kernel(fmt):
    from mlapi_tpu.ops.pallas import decode_attention, paged_decode_attention
    from mlapi_tpu.ops.quant import kv_quantize

    b, npv, page, kvh, d, pool_pages, h = 2, 4, 8, 2, 16, 12, 4
    q = jax.random.normal(jax.random.key(0), (b, 1, h, d), jnp.float32)
    pk = jax.random.normal(
        jax.random.key(1), (pool_pages, page, kvh, d), jnp.float32
    )
    pv = jax.random.normal(
        jax.random.key(2), (pool_pages, page, kvh, d), jnp.float32
    )
    # Non-contiguous, per-row-distinct page placement incl. the null
    # page on unallocated tail tiles.
    tab = jnp.asarray(np.array([[2, 5, 7, 0], [1, 3, 0, 0]], np.int32))
    L = npv * page
    mask = (
        jnp.arange(L)[None, :] <= jnp.asarray([[20], [10]])
    ).astype(jnp.float32)
    if fmt == "int8":
        kq, ks = kv_quantize(pk)
        vq, vs = kv_quantize(pv)
        k_op = {"q": kq, "scale": ks}
        v_op = {"q": vq, "scale": vs}
        kc = {
            "q": kq[tab].reshape(b, L, kvh, d),
            "scale": ks[tab].reshape(b, L, kvh, 1),
        }
        vc = {
            "q": vq[tab].reshape(b, L, kvh, d),
            "scale": vs[tab].reshape(b, L, kvh, 1),
        }
    else:
        k_op, v_op = pk, pv
        kc = pk[tab].reshape(b, L, kvh, d)
        vc = pv[tab].reshape(b, L, kvh, d)
    out = paged_decode_attention(q, k_op, v_op, tab, mask, interpret=True)
    ref = decode_attention(q, kc, vc, mask, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


# --- token-identical serving streams -----------------------------------


@pytest.mark.parametrize("impl", ["einsum", "flash"])
@pytest.mark.parametrize("fmt", ["none", "int8"])
@pytest.mark.parametrize("kind", ["gpt_lm", "llama_lm"])
def test_stream_token_identical_paged_vs_contiguous(
    kind, fmt, impl, gpt_params, llama_params
):
    params = gpt_params if kind == "gpt_lm" else llama_params
    model = _model(kind, fmt, impl)
    cont = _engine(model, params, paged=False)
    paged = _engine(model, params, paged=True)
    for prompt in ("hello world", "b" * 40):  # in-bucket + bucket-2
        a = cont.generate_text(prompt, max_new_tokens=8)
        b = paged.generate_text(prompt, max_new_tokens=8)
        assert a["token_ids"] == b["token_ids"], (kind, fmt, impl, prompt)
    # Every page went back: batches release their tables at the end.
    assert paged.kv_pages_in_use == 0


def test_long_prompt_chunked_prefill_paged():
    # A prompt past the largest bucket takes the page-native chunked
    # extend path (one paged_extend_fn program per fixed-width block):
    # 200 tokens round up to a [256]-wide prompt served as two
    # 128-wide extend blocks straight into pool pages.
    model = get_model("gpt_lm", **dict(CFG, max_positions=320))
    params = model.init(jax.random.key(1))
    cont = _engine(model, params, paged=False)
    paged = _engine(model, params, paged=True)
    prompt = "x" * 200
    a = cont.generate_text(prompt, max_new_tokens=8)
    b = paged.generate_text(prompt, max_new_tokens=8)
    assert a["token_ids"] == b["token_ids"]
    assert cont.prefill_chunks >= 2 and paged.prefill_chunks >= 2


# --- prefix sharing + copy-on-write ------------------------------------


def test_prefix_hit_shares_pages_not_copies(gpt_params):
    model = _model()
    paged = _engine(model, gpt_params, paged=True)  # page 8 | bucket 16
    pre = "You are a helpful bot."
    paged.generate_text(" q1", max_new_tokens=6, prefix=pre)
    # The entry's page set is pool-resident after the first batch...
    entry_pages = paged.pool.entry_pages(pre)
    assert entry_pages is not None and len(entry_pages) > 0
    in_use_after_first = paged.kv_pages_in_use
    paged.generate_text(" q2", max_new_tokens=6, prefix=pre)
    # ...and a second request re-POINTS at it: no new permanent pages,
    # no COW at an aligned prefix bucket (64 % 8 == 0), zero copies.
    assert paged.kv_pages_in_use == in_use_after_first
    assert paged.pool.cow_copies == 0
    assert paged.prefix_hits >= 1


def test_cow_divergence_after_shared_prefix(gpt_params):
    # Entries page-align their buckets at store time (r10), so COW
    # only arises when the model window cannot FIT the aligned bucket:
    # a 135-token prefix hits cap 143 (aligned would be 144), stays
    # unaligned, and the suffix's first tokens land mid-page — every
    # row must diverge the shared tail page by COPY-ON-WRITE, and the
    # shared pages must come out unscathed (the first suffix replays
    # identically afterwards).
    model = _model()
    cont = _engine(model, gpt_params, paged=False)
    paged = _engine(model, gpt_params, paged=True, kv_page_size=12)
    pre = "b" * 135
    outs = {}
    for sfx in (" alpha", " other beta"):
        a = cont.generate_text(sfx, max_new_tokens=8, prefix=pre)
        b = paged.generate_text(sfx, max_new_tokens=8, prefix=pre)
        assert a["token_ids"] == b["token_ids"], sfx
        outs[sfx] = b["token_ids"]
    assert paged.pool.cow_copies >= 2  # one divergence per batch
    # Divergence left the shared prefix pages intact: replay matches.
    again = paged.generate_text(" alpha", max_new_tokens=8, prefix=pre)
    assert again["token_ids"] == outs[" alpha"]


def test_prefix_entry_eviction_releases_pages(gpt_params):
    model = _model()
    paged = _engine(model, gpt_params, paged=True)
    paged.prefix.max_entries = 1
    paged.generate_text(" q", max_new_tokens=4, prefix="first prefix")
    held = paged.kv_pages_in_use
    assert held > 0
    # Registering a second prefix evicts the first entry — and its
    # page set's entry hold with it.
    paged.generate_text(" q", max_new_tokens=4, prefix="second prefix")
    assert paged.pool.entry_pages("first prefix") is None


# --- pool exhaustion ---------------------------------------------------


def test_oom_of_pages_loud_reject(gpt_params):
    model = _model()
    tiny = _engine(
        model, gpt_params, paged=True, kv_page_size=8, kv_pages=3
    )
    with pytest.raises(PagePoolExhausted, match="kv-pages"):
        tiny.generate_text("does not fit", max_new_tokens=16)
    # The reject left the pool consistent: nothing leaked, and a
    # request that FITS still serves.
    assert tiny.kv_pages_in_use == 0
    small = _engine(
        model, gpt_params, paged=True, kv_page_size=8, kv_pages=4
    )
    out = small.generate_text("hi", max_new_tokens=2)
    assert len(out["token_ids"]) == 2


# --- continuous batching on page tables --------------------------------


async def test_paged_admission_growth_compaction_parity(gpt_params):
    model = _model()
    outs = {}
    for paged in (False, True):
        eng = _engine(model, gpt_params, paged=paged, max_wait_ms=0.0)
        await eng.start()
        try:
            r1 = await eng.submit("the first long request",
                                  max_new_tokens=48, stream=True)
            # Wait for r1's FIRST chunk: its batch is then provably
            # running when the joiners arrive (admission, not a new
            # batch) — the counter assert below is deterministic.
            head = await r1.queue.get()
            assert not isinstance(head, Exception)
            r2 = await eng.submit("joiner", max_new_tokens=6)
            r3 = await eng.submit("another joiner arrives",
                                  max_new_tokens=6)
            outs[paged] = await asyncio.gather(
                _collect(r1), _collect(r2), _collect(r3)
            )
            outs[paged][0] = head["token_ids"] + outs[paged][0]
            if paged:
                # Growth and compaction ran as TABLE ops and the
                # batch returned every page. The release runs on the
                # DISPATCH thread after the terminal frames — wait on
                # the counter instead of racing it (the MLA006
                # discipline; this site flaked once the r18 family
                # reordering shifted its timing).
                assert eng.admitted >= 1
                deadline = asyncio.get_running_loop().time() + 60.0
                while eng.kv_pages_in_use != 0:
                    assert (
                        asyncio.get_running_loop().time() < deadline
                    ), eng.kv_pages_in_use
                    await asyncio.sleep(0.005)
        finally:
            await eng.stop()
    assert outs[True] == outs[False]


# --- TP shard_map wrapper (ROADMAP open item) --------------------------


def test_flash_decode_tp_shard_map_stream_parity(gpt_params):
    from mlapi_tpu.parallel import create_mesh

    model = _model("gpt_lm", "int8", "flash")
    solo = _engine(model, gpt_params, paged=True)
    mesh = create_mesh((1, 2), devices=jax.devices()[:2])
    tp = _engine(model, gpt_params, paged=True, mesh=mesh)
    # The engine pinned the mesh on the model, so cached_attend wraps
    # the kernel in shard_map over the model axis (4 query / 4 KV
    # heads split 2 ways) instead of leaving the opaque pallas_call
    # to GSPMD.
    assert tp.model.mesh is mesh
    for prompt in ("hello world", "sharded decode"):
        a = solo.generate_text(prompt, max_new_tokens=8)
        b = tp.generate_text(prompt, max_new_tokens=8)
        assert a["token_ids"] == b["token_ids"], prompt


def test_tp_wrapper_kernel_level_parity():
    from mlapi_tpu.ops.pallas import (
        decode_attention,
        decode_attention_tp,
        paged_decode_attention,
        paged_decode_attention_tp,
    )
    from mlapi_tpu.parallel import create_mesh

    mesh = create_mesh((1, 2), devices=jax.devices()[:2])
    b, npv, page, kvh, d, h = 2, 2, 8, 2, 16, 4
    q = jax.random.normal(jax.random.key(0), (b, 1, h, d), jnp.float32)
    pk = jax.random.normal(jax.random.key(1), (6, page, kvh, d))
    pv = jax.random.normal(jax.random.key(2), (6, page, kvh, d))
    tab = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    L = npv * page
    mask = (
        jnp.arange(L)[None, :] <= jnp.asarray([[12], [9]])
    ).astype(jnp.float32)
    plain = paged_decode_attention(q, pk, pv, tab, mask, interpret=True)
    tp = paged_decode_attention_tp(
        mesh, q, pk, pv, tab, mask, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(tp), atol=1e-6
    )
    kc = pk[tab].reshape(b, L, kvh, d)
    vc = pv[tab].reshape(b, L, kvh, d)
    plain_c = decode_attention(q, kc, vc, mask, interpret=True)
    tp_c = decode_attention_tp(mesh, q, kc, vc, mask, interpret=True)
    np.testing.assert_allclose(
        np.asarray(plain_c), np.asarray(tp_c), atol=1e-6
    )


# --- observability + the capacity model --------------------------------


async def test_metrics_exports_page_pool_gauges(gpt_params):
    import httpx

    from mlapi_tpu.serving import build_app

    eng = _engine(_model(), gpt_params, paged=True)
    app = build_app(eng)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as c:
            snap = (await c.get("/metrics")).json()
        g = snap["gauges"]
        assert g["generate.kv_pages_total"] == eng.kv_pages_total > 0
        assert g["generate.kv_pages_in_use"] == 0
        assert g["generate.kv_pages_shared"] == 0
        assert g["generate.kv_page_utilization"] == 0.0
        assert g["generate.kv_page_bytes"] == eng.kv_page_bytes()
    finally:
        await app.shutdown()


def test_capacity_model_exact_arithmetic(gpt_params):
    """The paged-capacity claim, pinned from shapes alone: pool bytes
    per token equal contiguous bytes per token (paging adds
    indirection, not byte overhead), so any sequence shorter than its
    tier strictly beats the contiguous slot — waste bounded by one
    page."""
    page = 8
    model = _model()
    eng = _engine(model, gpt_params, paged=True, kv_page_size=page)
    page_b = eng.kv_page_bytes()
    assert page_b == kv_page_bytes(model, page)
    for bucket in eng.prompt_buckets:
        total = eng._cache_len(bucket, eng.default_max_new_tokens)
        abstract = jax.eval_shape(lambda t=total: model.init_cache(1, t))
        slot_b = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for layer in abstract.values()
            for leaf in layer.values()
        )
        # Exact identity: page_bytes * (total / page) == slot bytes.
        assert page_b * total == slot_b * page
        # A typical half-full prompt + default budget wastes less than
        # one page under paging; the contiguous slot wastes the tier
        # remainder.
        used = bucket // 2 + eng.default_max_new_tokens
        paged_bytes = -(-used // page) * page_b
        waste_paged = paged_bytes - used * page_b // page
        assert waste_paged < page_b
        assert paged_bytes <= slot_b


# --- soak: page-table churn under sequential load (heavy) --------------


@pytest.mark.heavy
@pytest.mark.slow  # 5.5 s measured call — demoted from the tier-1
# window in the r16 wall-clock buyback (see conftest); leak coverage
# stays: every non-soak paged test asserts pages_in_use==0 teardown.
def test_paged_churn_no_leaks(gpt_params):
    """Soak the page lifecycle: many sequential batches across plain,
    prefix-shared, COW-diverging, and OOM-rejected traffic — the pool
    must end with only entry page sets held and a clean free list
    (every alloc matched by a release). The prefix is cap-clamped
    (135 tokens: aligned 144 > cap 143) so it stays UNALIGNED at page
    12 and every suffix batch still exercises the COW divergence —
    store-time alignment (r10) removes it for alignable entries."""
    model = _model()
    eng = _engine(model, gpt_params, paged=True, kv_page_size=12)
    pre = "b" * 135
    for i in range(6):
        eng.generate_text(f"plain {i}", max_new_tokens=10)
        eng.generate_text(f" sfx {i}", max_new_tokens=6, prefix=pre)
    entry_pages = eng.pool.entry_pages(pre)
    assert entry_pages is not None
    # Only the entry's own holds remain.
    assert eng.kv_pages_in_use == len(entry_pages)
    assert np.all(eng.pool.ref[entry_pages] == 1)
    assert eng.pool.cow_copies >= 6

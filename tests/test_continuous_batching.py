"""Continuous batching: requests are admitted into a RUNNING decode
batch at chunk boundaries (tier-aligned admission — the design
analyzed and built in r03), instead of waiting for
the whole batch to finish.

The load-bearing property is *token-exactness*: a request admitted
mid-batch, into any free row, at any decode position, with any
temperature/seed, produces byte-identical tokens to the same request
run solo through ``generate_text``. That is what per-row pad masks,
per-row position shifts, per-row PRNG streams, and per-row
sampling-step indices buy (``models/gpt.py::_pick_token``,
``admit_scatter_fn``).
"""

import asyncio

import jax
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.text import ByteTokenizer

pytestmark = pytest.mark.anyio

CFG = dict(
    vocab_size=260,
    hidden_size=32,
    num_layers=2,
    num_heads=4,
    max_positions=96,
    compute_dtype="float32",
)


@pytest.fixture
def anyio_backend():
    return "asyncio"


def _engine(**kw) -> TextGenerationEngine:
    model = get_model("gpt_lm", **CFG)
    return TextGenerationEngine(
        model,
        model.init(jax.random.key(0)),
        tokenizer=ByteTokenizer(),
        chunk=2,  # many admission boundaries even for short runs
        # These tests exercise the CHUNKED path's admission machinery;
        # the batch-1 fused fast path would (correctly) serve the solo
        # requests in one dispatch and never form a joinable batch.
        fused_single=False,
        **kw,
    )


async def _collect(gen) -> list[int]:
    """Drain one request's stream to completion."""
    out: list[int] = []
    while True:
        item = await gen.queue.get()
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.extend(item["token_ids"])


async def test_admitted_request_matches_solo_run():
    """A request submitted while another is mid-decode joins the
    RUNNING batch (no second batch is started) and its tokens —
    greedy AND seeded-sampled — equal the solo run's."""
    eng = _engine()
    await eng.start()
    try:
        solo_a = eng.generate_text("abcdef", max_new_tokens=40, seed=1)
        solo_b = eng.generate_text(
            "xyz", max_new_tokens=6, temperature=0.9, seed=7, top_k=40
        )
        base_batches = eng.batch_calls

        a = await eng.submit("abcdef", max_new_tokens=40, seed=1)
        first = await a.queue.get()  # prefill done → batch is running
        b = await eng.submit(
            "xyz", max_new_tokens=6, temperature=0.9, seed=7, top_k=40
        )
        got_b = await _collect(b)
        got_a = first["token_ids"] + await _collect(a)

        assert eng.admitted >= 1, "request was not admitted mid-batch"
        assert eng.batch_calls - base_batches == 1, (
            "joiner started its own batch instead of joining"
        )
        assert got_a == solo_a["token_ids"]
        assert got_b == solo_b["token_ids"]
    finally:
        await eng.stop()


async def test_admission_grows_batch_along_pow2_chain():
    """A solo batch (device batch 1) grows 1→2→4 as joiners arrive;
    every output stays exact."""
    eng = _engine(max_batch=4)
    await eng.start()
    try:
        solos = [
            eng.generate_text(
                t, max_new_tokens=n, temperature=temp, seed=s
            )["token_ids"]
            for t, n, temp, s in _REQS
        ]
        gens = []
        first_chunks = []
        for i, (t, n, temp, s) in enumerate(_REQS):
            g = await eng.submit(
                t, max_new_tokens=n, temperature=temp, seed=s
            )
            gens.append(g)
            if i == 0:
                first_chunks.append(await g.queue.get())
        outs = []
        for i, g in enumerate(gens):
            got = await _collect(g)
            if i == 0:
                got = first_chunks[0]["token_ids"] + got
            outs.append(got)
        assert outs == solos
        assert eng.growths >= 1, "batch never grew for the joiners"
        assert eng.admitted >= 1
    finally:
        await eng.stop()


_REQS = [
    ("abcdefabcdef", 48, 0.0, 0),
    ("zz", 8, 0.8, 3),
    ("qqq", 6, 0.0, 0),
    ("mn", 10, 1.1, 11),
]


async def test_incompatible_joiner_waits_for_next_batch():
    """A joiner whose token budget cannot fit the running cache is
    NOT admitted (and NOT truncated): it is swept into its own batch
    after the running one ends, and completes in full."""
    eng = _engine()
    await eng.start()
    try:
        base = eng.batch_calls
        a = await eng.submit("abcd", max_new_tokens=24, seed=2)
        await a.queue.get()
        # 64 new tokens can never fit behind a running cache of
        # total=80 at pos>=17 — must wait.
        b = await eng.submit("xy", max_new_tokens=64)
        got_b = await _collect(b)
        await _collect(a)
        assert len(got_b) == 64, "joiner was truncated, not deferred"
        assert eng.batch_calls - base == 2, (
            "incompatible joiner should have formed a second batch"
        )
    finally:
        await eng.stop()


async def test_swept_incompatible_requests_split_into_batches():
    """Two deferred requests that are window-incompatible WITH EACH
    OTHER (each valid alone) must be re-checked at sweep time and
    served in separate batches — not blindly batched and truncated
    (code-review regression)."""
    eng = _engine()
    eng._strict_admit = True  # force both arrivals to defer
    await eng.start()
    try:
        a = await eng.submit("abcd", max_new_tokens=24)
        await a.queue.get()
        # bucket 64 + 30 fits (94 <= 96); bucket 16 + 70 fits (86);
        # together 64 + 70 = 134 > 96 — incompatible pair.
        r1 = await eng.submit("a" * 40, max_new_tokens=30)
        r2 = await eng.submit("xy", max_new_tokens=70)
        got1 = await _collect(r1)
        got2 = await _collect(r2)
        await _collect(a)
        assert len(got1) == 30, "r1 truncated by an incompatible batch"
        assert len(got2) == 70, "r2 truncated by an incompatible batch"
    finally:
        await eng.stop()


async def test_cancelled_pending_joiner_is_dropped():
    """A request cancelled while waiting for admission is dropped at
    the next boundary without occupying a device row."""
    eng = _engine()
    await eng.start()
    try:
        a = await eng.submit("abcd", max_new_tokens=30)
        await a.queue.get()
        b = await eng.submit("xy", max_new_tokens=4)
        b.cancel()
        await _collect(a)
        assert eng.admitted == 0
    finally:
        await eng.stop()


async def test_strict_mode_gates_unwarmed_shapes():
    """After a full warmup, admission only takes warmed
    (bucket, cache, batch) shapes — anything else defers to the next
    batch instead of compiling mid-run."""
    eng = _engine()
    eng._strict_admit = True  # warmed sets empty → nothing admissible
    await eng.start()
    try:
        base = eng.batch_calls
        a = await eng.submit("abcd", max_new_tokens=24)
        await a.queue.get()
        b = await eng.submit("xy", max_new_tokens=4)
        got_b = await _collect(b)
        await _collect(a)
        assert len(got_b) == 4
        assert eng.admitted == 0, "strict mode admitted an unwarmed shape"
        assert eng.batch_calls - base == 2
    finally:
        await eng.stop()


async def test_warmup_populates_admission_grid(monkeypatch):
    """Full warmup records the admission/growth shape sets and turns
    strict gating on; a subsequent joiner with a warmed shape IS
    admitted under strict mode."""
    monkeypatch.setenv("MLAPI_TPU_WARMUP", "full")
    eng = _engine(max_batch=2, prompt_buckets=(16,))
    eng.warmup()
    assert eng._strict_admit
    assert eng._warmed_joiner == {16}, "joiner prefill bucket not warmed"
    assert eng._warmed_growth, "no growth shapes warmed"
    total = 16 + 32  # bucket + default tier (default_max_new_tokens=32)
    assert (16, total, 1) in eng._warmed_scatter
    assert (1, 2, total) in eng._warmed_growth
    await eng.start()
    try:
        a = await eng.submit("abcd", max_new_tokens=32, seed=4)
        await a.queue.get()
        b = await eng.submit("xy", max_new_tokens=2, seed=9)
        got_b = await _collect(b)
        await _collect(a)
        solo_b = eng.generate_text("xy", max_new_tokens=2, seed=9)
        assert got_b == solo_b["token_ids"]
        assert eng.admitted >= 1, (
            "warmed shape was not admitted under strict mode"
        )
    finally:
        await eng.stop()


async def test_admission_at_nondefault_tier_with_eager_compiles(monkeypatch):
    """With strict gating on, a batch running at a HIGHER cache tier
    than the warmed default still admits joiners when the attach is
    low-RTT: the expensive prefill is warmed per bucket, and the
    trivial scatter is allowed to compile on demand."""
    monkeypatch.setenv("MLAPI_TPU_WARMUP", "full")
    eng = _engine(max_batch=2, prompt_buckets=(16,))
    eng.warmup()
    assert eng._strict_admit
    assert eng._admit_eager  # CPU attach: sub-ms dispatch RTT
    await eng.start()
    try:
        # n_new=48 > default 32 → cache tier 64, total 80: a shape no
        # scatter was warmed for.
        a = await eng.submit("abcd", max_new_tokens=48, seed=2)
        await a.queue.get()
        assert (16, 80, 1) not in eng._warmed_scatter
        b = await eng.submit("xy", max_new_tokens=4, seed=5)
        got_b = await _collect(b)
        await _collect(a)
        solo = eng.generate_text("xy", max_new_tokens=4, seed=5)
        assert got_b == solo["token_ids"]
        assert eng.admitted >= 1, "long-tier batch refused a joiner"
    finally:
        await eng.stop()


def test_window_edge_request_gets_partial_final_chunk():
    """When max_positions clamps the cache, (total - bucket) need not
    be a chunk multiple; the final decode chunk must run at the
    remainder size so a window-edge request still receives every
    token it was promised (code-review regression: the whole-chunk
    stop errored it as truncated — and the pre-r03 loop silently ran
    past the cache end)."""
    model = get_model("gpt_lm", **CFG)
    eng = TextGenerationEngine(
        model, model.init(jax.random.key(0)),
        tokenizer=ByteTokenizer(), chunk=16,
    )
    # 70-char prompt → oversize exact bucket 70; n_new=24 fits the
    # model window (94 <= 96) but total clamps to 96: room is 26 =
    # one 16-chunk + a 10-remainder.
    out = eng.generate_text("x" * 70, max_new_tokens=24)
    assert len(out["token_ids"]) == 24


def test_chunked_prefill_matches_single_prefill():
    """A prompt longer than the largest bucket prefills in
    fixed-width extend_core chunks (one compiled program per cache
    tier, traced offset) and produces the same tokens as a single
    full-width prefill — greedy and seeded-sampled."""
    cfg = dict(CFG, max_positions=320)
    model = get_model("gpt_lm", **cfg)
    params = model.init(jax.random.key(0))
    tok = ByteTokenizer()
    chunked = TextGenerationEngine(
        model, params, tokenizer=tok, chunk=4,
        prompt_buckets=(16, 64, 128),
    )
    wide = TextGenerationEngine(
        model, params, tokenizer=tok, chunk=4,
        prompt_buckets=(16, 64, 256),
    )
    text = "abcdefgh" * 25  # 200 tokens: chunked 2x128 vs one 256
    for kw in (
        dict(max_new_tokens=8),
        dict(max_new_tokens=8, temperature=0.9, seed=4, top_k=30),
    ):
        a = chunked.generate_text(text, **kw)
        b = wide.generate_text(text, **kw)
        assert a["token_ids"] == b["token_ids"], kw
    assert chunked.prefill_chunks == 4  # 2 chunks x 2 runs
    assert wide.prefill_chunks == 0


async def test_staggered_soak_every_stream_exact():
    """Randomized staggered arrivals across buckets, lengths, and
    sampling configs: every stream must match its solo run exactly,
    through any mix of admission, compaction, and growth."""
    rng = np.random.default_rng(0)
    eng = _engine(max_batch=4)
    cases = []
    for i in range(10):
        n = int(rng.integers(2, 30))
        temp = float(rng.choice([0.0, 0.7, 1.2]))
        text = "ab" * int(rng.integers(1, 12))
        cases.append((text, n, temp, i))
    solos = [
        eng.generate_text(t, max_new_tokens=n, temperature=temp, seed=s)[
            "token_ids"
        ]
        for t, n, temp, s in cases
    ]
    await eng.start()
    try:
        gens = []
        for t, n, temp, s in cases:
            gens.append(
                await eng.submit(
                    t, max_new_tokens=n, temperature=temp, seed=s
                )
            )
            await asyncio.sleep(float(rng.uniform(0, 0.02)))
        outs = [await _collect(g) for g in gens]
        assert outs == solos
    finally:
        await eng.stop()

"""Int8 KV-cache quantization (`ops/quant.py` kv helpers, the
``kv_quant`` model field, engine ``--kv-quant``).

Decode at generation scale is CACHE-bandwidth-bound: every token
re-reads every layer's [B, L, H, D] K/V from HBM, so storing the cache
as int8 payload + per-token-per-head f32 scales halves the per-token
decode HBM and doubles how many continuous-batching slots / prefix
entries / spec mirrors fit a chip. These tests pin the three claims:

- **Bytes, exactly**: deterministic per-slot cache bytes from
  ``addressable_shards[...].data.nbytes`` match closed-form arithmetic,
  and the bf16 gpt-small ratio clears the committed >= 1.9x.
- **Quality, measured**: teacher-forced greedy top-1 agreement vs the
  full-precision cache >= 0.99 over >= 256 tokens x 8 prompts.
- **The SERVING stack, not just the model**: prefix hit/widen
  round-trips, continuous admission, fused batched speculation, and
  composition with int8 weights + a (1, 1, 2)-style TP mesh all run
  on the quantized format and stay byte-identical where the bf16
  contract says they must.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.ops.quant import (
    kv_cache_seq_len,
    kv_greedy_agreement,
    kv_quantize,
    maybe_dequant_kv,
)
from mlapi_tpu.parallel.layout import bytes_per_device
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.text import ByteTokenizer

# Tiny fast config for path coverage (f32 compute: the cache baseline
# is f32, ratio ~3.2x at D=16).
CFG = dict(
    vocab_size=260,
    hidden_size=32,
    num_layers=2,
    num_heads=2,
    max_positions=160,
    compute_dtype="float32",
)
# "gpt-small" for the committed numbers: bf16 cache, head_dim 128 —
# the shape class where int8+f32-scales clears the >= 1.9x bf16 bar
# (2D / (D + 4) at D = 128 -> 1.94x).
SMALL = dict(
    vocab_size=260,
    hidden_size=256,
    num_layers=2,
    num_heads=2,
    max_positions=320,
    compute_dtype="bfloat16",
)

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


def _model(kv_quant="int8", **over):
    return get_model("gpt_lm", **{**CFG, **over}, kv_quant=kv_quant)


@pytest.fixture(scope="module")
def params():
    return _model().init(jax.random.key(0))


def _engine(params, kv_quant="int8", **kw):
    kw.setdefault("chunk", 2)
    kw.setdefault("fused_single", False)
    return TextGenerationEngine(
        _model(kv_quant), params, tokenizer=ByteTokenizer(), **kw
    )


async def _collect(gen) -> list[int]:
    out: list[int] = []
    while True:
        item = await gen.queue.get()
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.extend(item["token_ids"])


# --- the quantization math --------------------------------------------


def test_kv_quantize_per_token_head_scales_bound_error():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 7, 3, 16)).astype(np.float32)
    q, s = kv_quantize(jnp.asarray(x))
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.shape == (2, 7, 3, 1)  # one scale per (token, head)
    back = np.asarray(q, np.float32) * np.asarray(s)
    assert np.all(np.abs(back - x) <= np.asarray(s) / 2 + 1e-8)
    # All-zero blocks stay exactly zero with a nonzero scale.
    q0, s0 = kv_quantize(jnp.zeros((1, 2, 1, 8)))
    assert np.all(np.asarray(q0) == 0) and np.all(np.asarray(s0) == 1.0)


def test_init_cache_format_and_exact_bytes():
    """Per-slot cache bytes, EXACT: addressable-shard bytes equal the
    closed-form int8-payload + f32-scale arithmetic, for both
    families (and GQA shrinks the llama cache by the group factor)."""
    m = _model()
    total = 64
    cache = m.init_cache(1, total)
    layer = cache["layer_0"]
    assert sorted(layer) == ["k_q", "k_scale", "v_q", "v_scale"]
    assert layer["k_q"].dtype == jnp.int8
    assert layer["k_scale"].dtype == jnp.float32
    assert kv_cache_seq_len(cache) == total
    h, d = m.num_heads, m.head_dim
    expect = m.num_layers * 2 * (total * h * d + total * h * 4)
    assert bytes_per_device(cache) == expect
    base = _model("none").init_cache(1, total)
    expect_base = m.num_layers * 2 * total * h * d * 4  # f32
    assert bytes_per_device(base) == expect_base

    lm = get_model(
        "llama_lm", vocab_size=64, hidden_size=32, num_layers=1,
        num_heads=4, num_kv_heads=2, max_positions=64,
        compute_dtype="float32", kv_quant="int8",
    )
    lc = lm.init_cache(2, 16)
    assert lc["layer_0"]["k_q"].shape == (2, 16, 2, 8)  # KVH, not H
    assert bytes_per_device(lc) == 2 * (2 * 16 * 2 * 8 + 2 * 16 * 2 * 4)


def test_gpt_small_bf16_slot_bytes_ratio_ge_1_9():
    """The committed byte claim at identical bucket/tier config:
    engine-reported per-slot KV bytes (addressable_shards nbytes)
    drop >= 1.9x vs the bf16 cache, and the number is deterministic
    across engines (it is what /metrics exports)."""
    model = get_model("gpt_lm", **SMALL)
    real = model.init(jax.random.key(0))
    tok = ByteTokenizer()
    eng_b = TextGenerationEngine(model, real, tokenizer=tok)
    qmodel = dataclasses.replace(model, kv_quant="int8")
    eng_q = TextGenerationEngine(qmodel, real, tokenizer=tok)
    b, q = eng_b.kv_cache_slot_bytes(), eng_q.kv_cache_slot_bytes()
    assert b >= 1.9 * q, (b, q)
    eng_q2 = TextGenerationEngine(qmodel, real, tokenizer=tok)
    assert eng_q2.kv_cache_slot_bytes() == q


async def test_metrics_exports_kv_slot_bytes(params):
    import httpx

    from mlapi_tpu.serving import build_app

    eng = _engine(params)
    app = build_app(eng)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as c:
            snap = (await c.get("/metrics")).json()
        assert (
            snap["gauges"]["generate.kv_cache_bytes_per_slot"]
            == eng.kv_cache_slot_bytes()
        )
    finally:
        await app.shutdown()


# --- decode quality ----------------------------------------------------


def test_greedy_agreement_gpt_small_256_tokens():
    """The measured decode-quality guard: teacher-forced greedy top-1
    agreement of the int8 cache vs the bf16 cache >= 0.99 over
    256 tokens x 8 prompts on bf16 gpt-small."""
    model = get_model("gpt_lm", **SMALL)
    params = model.init(jax.random.key(0))
    tok = ByteTokenizer()
    prompts = [
        "the quick brown fox", "serving engines batch",
        "checkpoints commit", "tpu programs compile",
        "the draft proposes", "sharding follows mesh",
        "decode reads the cache", "quantize the kv cache",
    ]
    width = max(len(tok.token_ids(p)) for p in prompts)
    rows = np.full((len(prompts), width), tok.pad_id, np.int32)
    pads = np.zeros((len(prompts),), np.int32)
    for i, p in enumerate(prompts):
        ids = tok.token_ids(p)
        rows[i, width - len(ids):] = ids
        pads[i] = width - len(ids)
    agr = kv_greedy_agreement(
        model, params, jnp.asarray(rows), 257, pad_lens=pads
    )
    assert agr >= 0.99, agr


def test_generate_stream_matches_full_precision(params):
    """At the tiny f32 config the quantized-cache greedy stream is
    token-identical to full precision end to end (engine path)."""
    a = _engine(params, "none").generate_text("hello", max_new_tokens=24)
    b = _engine(params, "int8").generate_text("hello", max_new_tokens=24)
    assert a["token_ids"] == b["token_ids"]


def test_llama_gqa_kv_quant_decodes():
    m = get_model(
        "llama_lm", vocab_size=260, hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, max_positions=96,
        compute_dtype="float32", kv_quant="int8",
    )
    p = m.init(jax.random.key(2))
    out = np.asarray(m.generate(
        p, jnp.asarray(np.arange(6, dtype=np.int32)[None]),
        max_new_tokens=8,
    ))
    assert out.shape == (1, 8) and (out >= 0).all()


def test_bad_kv_quant_value_rejected():
    with pytest.raises(ValueError, match="kv_quant"):
        _model("int4")


def test_maybe_dequant_kv_boundary():
    q, s = kv_quantize(jnp.ones((1, 4, 2, 8)))
    out = maybe_dequant_kv({"q": q, "scale": s}, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-2)
    arr = jnp.ones((2, 2))
    assert maybe_dequant_kv(arr) is arr
    with pytest.raises(TypeError, match="quantized pairs"):
        maybe_dequant_kv({"weird": arr})


def test_flash_and_ring_dequant_at_boundary():
    """The documented kernel-boundary policy: quantized K/V pairs fed
    to the full-sequence kernels dequantize at entry and match the
    same kernel on the dequantized arrays."""
    from mlapi_tpu.ops.pallas import flash_attention

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
    kq, ks = kv_quantize(k)
    vq, vs = kv_quantize(v)
    ref = flash_attention(
        q, kq.astype(jnp.float32) * ks, vq.astype(jnp.float32) * vs,
        causal=True, interpret=True,
    )
    got = flash_attention(
        q, {"q": kq, "scale": ks}, {"q": vq, "scale": vs},
        causal=True, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=1e-5
    )


# --- the serving stack on the quantized format -------------------------


def test_prefix_cache_int8_hit_and_widen(params):
    """Prefix KVs store, hit, and widen in int8: a prefix-cached
    request equals the inline concatenation, the entry's KV pytree is
    really int8 on device, and the cross-batch widen preserves the
    format and the right-aligned content."""
    eng = _engine(params)
    prefix = "the quick brown fox "
    via = eng.generate_text("tail", prefix=prefix, max_new_tokens=8)
    concat = eng.generate_text(prefix + "tail", max_new_tokens=8)
    assert via["token_ids"] == concat["token_ids"]
    assert eng.prefix_misses == 1
    entry = eng.prefix.entry(prefix)  # second use: a hit
    assert eng.prefix_hits >= 1
    leaf = entry.kv["layer_0"]
    assert leaf["k_q"].dtype == jnp.int8

    wide = eng.prefix.widen(entry.kv, entry.bucket, entry.bucket + 16)
    wlayer = wide["layer_0"]
    assert wlayer["k_q"].dtype == jnp.int8
    assert wlayer["k_q"].shape[1] == entry.bucket + 16
    np.testing.assert_array_equal(
        np.asarray(wlayer["k_q"])[:, 16:], np.asarray(leaf["k_q"])
    )
    np.testing.assert_array_equal(
        np.asarray(wlayer["k_scale"])[:, 16:],
        np.asarray(leaf["k_scale"]),
    )
    # And a repeat request (an entry HIT) still matches.
    again = eng.generate_text("tail", prefix=prefix, max_new_tokens=8)
    assert again["token_ids"] == concat["token_ids"]


async def test_streaming_int8_matches_sync(params):
    """A ``stream=True`` consumer (one chunk in flight, prompt token
    delivery) over the int8 cache gets the same stream as the sync
    path."""
    eng = _engine(params)
    await eng.start()
    try:
        ref = eng.generate_text("stream me", max_new_tokens=12)
        gen = await eng.submit("stream me", max_new_tokens=12,
                               stream=True)
        chunks = []
        while True:
            item = await gen.queue.get()
            if item is None:
                break
            assert not isinstance(item, Exception), item
            chunks.append(item["token_ids"])
        assert len(chunks) >= 2  # actually incremental
        assert sum(chunks, []) == ref["token_ids"]
    finally:
        await eng.stop()


async def test_continuous_admission_int8(params):
    """A request admitted into a RUNNING int8-cache batch produces
    byte-identical tokens to its solo run (the continuous-batching
    exactness contract, on the quantized format)."""
    eng = _engine(params)
    await eng.start()
    try:
        solo_a = eng.generate_text("abcdef", max_new_tokens=40, seed=1)
        solo_b = eng.generate_text(
            "xyz", max_new_tokens=6, temperature=0.9, seed=7, top_k=40
        )
        base_batches = eng.batch_calls
        a = await eng.submit("abcdef", max_new_tokens=40, seed=1)
        first = await a.queue.get()
        b = await eng.submit(
            "xyz", max_new_tokens=6, temperature=0.9, seed=7, top_k=40
        )
        got_b = await _collect(b)
        got_a = first["token_ids"] + await _collect(a)
        assert eng.admitted >= 1, "request was not admitted mid-batch"
        assert eng.batch_calls - base_batches == 1
        assert got_a == solo_a["token_ids"]
        assert got_b == solo_b["token_ids"]
    finally:
        await eng.stop()


def _spec_pair(kv_quant="int8"):
    t_cfg = dict(
        vocab_size=260, hidden_size=48, num_layers=2, num_heads=4,
        max_positions=256, compute_dtype="float32", kv_quant=kv_quant,
    )
    d_cfg = dict(
        vocab_size=260, hidden_size=24, num_layers=1, num_heads=2,
        max_positions=256, compute_dtype="float32", kv_quant=kv_quant,
    )
    target = get_model("gpt_lm", **t_cfg)
    draft = get_model("gpt_lm", **d_cfg)
    return target, target.init(jax.random.key(0)), draft, \
        draft.init(jax.random.key(1))


async def test_batched_spec_int8():
    """A formed all-greedy batch runs BATCHED SPECULATION rounds with
    BOTH caches (target and draft mirror) in int8, and each stream
    equals its draft-less solo run. (r20: the retired whole-batch
    fused-spec program is gone — the rounds run as typed ``spec``
    units through the one execution model.)"""
    target, tp, draft, dp = _spec_pair()
    tok = ByteTokenizer()
    plain = TextGenerationEngine(
        target, tp, tokenizer=tok, max_wait_ms=2000.0
    )
    eng = TextGenerationEngine(
        target, tp, tokenizer=tok, max_wait_ms=2000.0,
        draft=(draft, dp), spec_k=3,
    )
    assert eng.kv_quant == "int8"
    texts = ["the quick brown", "a serving engine"]
    solos = [
        plain.generate_text(t, max_new_tokens=12)["token_ids"]
        for t in texts
    ]
    await eng.start()
    try:
        gens = [
            await eng.submit(t, max_new_tokens=12) for t in texts
        ]
        outs = [await _collect(g) for g in gens]
        assert eng.spec_rounds > 0 and eng.spec_drafted > 0, (
            eng.spec_rounds, eng.batch_calls
        )
        assert outs == solos
    finally:
        await eng.stop()


async def test_host_spec_phase_int8():
    """The HOST spec phase (chunked path with a draft): solo greedy
    speculation on int8 caches emits the exact draft-less stream."""
    target, tp, draft, dp = _spec_pair()
    tok = ByteTokenizer()
    plain = TextGenerationEngine(target, tp, tokenizer=tok)
    eng = TextGenerationEngine(
        target, tp, tokenizer=tok, draft=(draft, dp), spec_k=3,
        fused_single=False,
    )
    ref = plain.generate_text("hello world", max_new_tokens=24)
    got = eng.generate_text("hello world", max_new_tokens=24)
    assert got["token_ids"] == ref["token_ids"]
    assert eng.spec_rounds > 0, "spec phase never engaged"


def test_composes_with_int8_weights_and_tp_mesh(tmp_path):
    """--quantize int8 + --kv-quant int8 + a (1, 1, 2)-style TP mesh:
    int8 weights serve from the TP layout, the cache quantizes per
    token, and the stream equals the unsharded full-precision one."""
    from mlapi_tpu.checkpoint import save_checkpoint
    from mlapi_tpu.models.quantized import QuantizedModel
    from mlapi_tpu.parallel import create_mesh
    from mlapi_tpu.serving import InferenceEngine

    cfg = dict(CFG)
    model = get_model("gpt_lm", **cfg)
    ck = tmp_path / "ck"
    save_checkpoint(
        ck, model.init(jax.random.key(1)), step=1,
        config={
            "model": "gpt_lm", "model_kwargs": cfg,
            "tokenizer": ByteTokenizer().fingerprint(),
        },
    )
    mesh = create_mesh((1, 1, 2), devices=jax.devices()[:2])
    eng = InferenceEngine.from_checkpoint(
        ck, quantize="int8", kv_quant="int8", mesh=mesh
    )
    assert isinstance(eng.model, QuantizedModel)
    assert eng.model.kv_quant == "int8"  # forwarded from the inner
    assert eng.meta["kv_quant"] == "int8"
    # Byte-identical to the SAME quantization config off the mesh
    # (the weights-only precedent: test_quantized_mesh_serving).
    ref = InferenceEngine.from_checkpoint(
        ck, quantize="int8", kv_quant="int8"
    )
    a = eng.generate_text("hello world", max_new_tokens=10)
    b = ref.generate_text("hello world", max_new_tokens=10)
    assert a["token_ids"] == b["token_ids"]


def test_kv_quant_rejected_for_non_generative(tmp_path):
    from mlapi_tpu.checkpoint import save_checkpoint
    from mlapi_tpu.datasets import load_iris
    from mlapi_tpu.serving import InferenceEngine
    from mlapi_tpu.train import fit

    iris = load_iris()
    model = get_model(
        "linear", num_features=iris.num_features,
        num_classes=iris.num_classes,
    )
    r = fit(model, iris, steps=50, learning_rate=0.1)
    ck = tmp_path / "ck"
    save_checkpoint(
        ck, r.params, step=50,
        config={
            "model": "linear",
            "model_kwargs": {
                "num_features": iris.num_features,
                "num_classes": iris.num_classes,
            },
        },
        vocab=iris.vocab,
    )
    with pytest.raises(ValueError, match="generative"):
        InferenceEngine.from_checkpoint(ck, kv_quant="int8")

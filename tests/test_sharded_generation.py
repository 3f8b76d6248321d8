"""Mesh-aware GENERATIVE serving (VERDICT r03 "Next" #2): a
TextGenerationEngine on a (data, model) mesh — params in the model's
declared Megatron TP layout, decode/fused programs partitioned by
GSPMD — must emit byte-identical streams to the single-device engine,
through the full HTTP stack, on 8 virtual CPU devices (SURVEY §4
"distributed without a cluster")."""

import asyncio

import httpx
import jax
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.serving import build_app
from mlapi_tpu.serving.engine import TextGenerationEngine
from mlapi_tpu.text import ByteTokenizer

pytestmark = pytest.mark.anyio

CFG = dict(
    vocab_size=260,
    hidden_size=32,
    num_layers=2,
    num_heads=4,
    max_positions=160,
    compute_dtype="float32",
)
D_CFG = dict(CFG, hidden_size=16, num_layers=1)

PROMPT = "the quick brown fox"


@pytest.fixture
def anyio_backend():
    return "asyncio"


@pytest.fixture(scope="module")
def gpt_and_params():
    model = get_model("gpt_lm", **CFG)
    return model, model.init(jax.random.key(0))


def _engine(model, params, *, mesh=None, **kw):
    return TextGenerationEngine(
        model, params, tokenizer=ByteTokenizer(), chunk=8, mesh=mesh, **kw
    )


def test_params_live_in_tp_layout(gpt_and_params, mesh_1x4):
    model, params = gpt_and_params
    eng = _engine(model, params, mesh=mesh_1x4)
    qkv = eng.params["layer_0"]["qkv"]["kernel"]
    assert "model" in tuple(qkv.sharding.spec), qkv.sharding
    wte = eng.params["wte"]
    assert "model" in tuple(wte.sharding.spec), wte.sharding


def test_sharded_streams_match_unsharded(gpt_and_params, mesh_1x4):
    model, params = gpt_and_params
    sharded = _engine(model, params, mesh=mesh_1x4)
    local = _engine(model, params)
    for kw in (
        dict(max_new_tokens=20),                       # fused greedy
        dict(max_new_tokens=17, temperature=0.8, top_k=12, seed=3),
    ):
        a = sharded.generate_text(PROMPT, **kw)
        b = local.generate_text(PROMPT, **kw)
        assert a["token_ids"] == b["token_ids"], kw
    assert sharded.fused_calls == 2   # fast path engages on the mesh
    # The chunked path too (streams stay chunked on a mesh).
    sharded_c = _engine(model, params, mesh=mesh_1x4, fused_single=False)
    c = sharded_c.generate_text(PROMPT, max_new_tokens=20)
    assert c["token_ids"] == local.generate_text(
        PROMPT, max_new_tokens=20
    )["token_ids"]
    assert sharded_c.chunk_calls > 0


def test_sharded_spec_with_draft_on_mesh(gpt_and_params, mesh_1x4):
    """The draft rides the same mesh: speculative rounds run with both
    param trees sharded and stay byte-identical to plain greedy."""
    model, params = gpt_and_params
    draft = get_model("gpt_lm", **D_CFG)
    dp = draft.init(jax.random.key(1))
    spec = _engine(model, params, mesh=mesh_1x4, draft=(draft, dp))
    assert spec.draft_params["wte"].sharding.mesh.shape == {
        "data": 1, "model": 4
    }
    plain = _engine(model, params)
    a = spec.generate_text(PROMPT, max_new_tokens=24)
    b = plain.generate_text(PROMPT, max_new_tokens=24)
    assert a["token_ids"] == b["token_ids"]
    assert spec.spec_rounds > 0 and spec.spec_drafted > 0


def test_llama_generates_on_mesh(mesh_1x4):
    model = get_model(
        "llama_lm", vocab_size=260, hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, max_positions=160,
        compute_dtype="float32",
    )
    params = model.init(jax.random.key(0))
    sharded = _engine(model, params, mesh=mesh_1x4)
    local = _engine(model, params)
    a = sharded.generate_text(PROMPT, max_new_tokens=16)
    b = local.generate_text(PROMPT, max_new_tokens=16)
    assert a["token_ids"] == b["token_ids"]


async def test_generate_over_http_on_2x4_mesh(gpt_and_params, mesh_2x4):
    """The full HTTP stack over a (2, 4) mesh: non-stream (fused),
    stream (chunked, byte-equal), seeded sampling reproducible."""
    model, params = gpt_and_params
    engine = _engine(model, params, mesh=mesh_2x4)
    app = build_app(engine)
    await app.startup()
    try:
        transport = httpx.ASGITransport(app=app)
        async with httpx.AsyncClient(
            transport=transport, base_url="http://test"
        ) as client:
            r = await client.post(
                "/generate",
                json={"text": PROMPT, "max_new_tokens": 12},
            )
            assert r.status_code == 200, r.text
            ids = r.json()["token_ids"]
            assert len(ids) == 12
            local = _engine(model, params)
            assert ids == local.generate_text(
                PROMPT, max_new_tokens=12
            )["token_ids"]

            s = await client.post(
                "/generate",
                json={"text": PROMPT, "max_new_tokens": 12,
                      "stream": True},
            )
            assert s.status_code == 200
            import json as _json

            last = _json.loads(s.text.strip().splitlines()[-1])
            assert last["done"] is True
            assert last["token_ids"] == ids

            m = (await client.get("/metrics")).json()["counters"]
            assert m["generate.fused_calls"] >= 1
    finally:
        await app.shutdown()

"""Speculative decoding (`ops/speculative.py`): greedy-exact stream,
full acceptance with draft == target, cache bookkeeping across
fully-accepted rounds (the draft's unfed k-th proposal), and the
budget/window fallbacks."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.ops.speculative import speculative_generate
from mlapi_tpu.text import ByteTokenizer

T_CFG = dict(
    vocab_size=260, hidden_size=48, num_layers=3, num_heads=4,
    max_positions=160, compute_dtype="float32",
)
D_CFG = dict(
    vocab_size=260, hidden_size=24, num_layers=1, num_heads=2,
    max_positions=160, compute_dtype="float32",
)


def _greedy_ref(model, params, prompt, n):
    return np.asarray(
        model.generate(params, jnp.asarray(prompt), max_new_tokens=n)
    )[0].tolist()


def _train_repeater(model, seed=0):
    tok = ByteTokenizer()
    pattern = np.asarray(tok.token_ids("abcab" * 12), np.int32)
    seqs = np.tile(pattern, (32, 1))
    x, y = seqs[:, :-1], seqs[:, 1:]
    params = model.init(jax.random.key(seed))
    tx = optax.adam(3e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        def loss_fn(p):
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    for _ in range(90):
        params, opt, _ = step(params, opt)
    return params


@pytest.mark.parametrize("budget", ["round", "ragged", "one"])
@pytest.mark.parametrize("k", [1, 3, 5])
def test_stream_equals_plain_greedy_random_models(k, budget):
    """Exactness holds regardless of draft quality: random draft +
    random target — every emitted token is the target's greedy
    choice, whether the budget is whole rounds of ``k + 1``, ends in
    a budget-capped round, or is a single token."""
    n = {"round": 4 * (k + 1), "ragged": 4 * (k + 1) + 1, "one": 1}[budget]
    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(0))
    dp = draft.init(jax.random.key(1))
    prompt = np.arange(9, dtype=np.int32)[None] % 200 + 3
    # One reference program for every case: greedy decoding's first n
    # tokens do not depend on how many follow.
    ref = _greedy_ref(target, tp, prompt, 25)[:n]
    got, stats = speculative_generate(
        target, tp, draft, dp, prompt, max_new_tokens=n, k=k,
    )
    assert got == ref, (k, n, stats)
    assert stats.emitted + stats.fallback_steps + 1 == n


def test_draft_equals_target_accepts_everything():
    """With draft == target every proposal matches: acceptance is
    100% and every full round emits k+1 tokens — also exercises the
    fully-accepted round's draft bookkeeping (the unfed k-th
    proposal)."""
    target = get_model("gpt_lm", **T_CFG)
    tp = target.init(jax.random.key(0))
    prompt = np.arange(7, dtype=np.int32)[None] % 150 + 5
    ref = _greedy_ref(target, tp, prompt, 25)
    got, stats = speculative_generate(
        target, tp, target, tp, prompt, max_new_tokens=25, k=3,
    )
    assert got == ref
    assert stats.acceptance_rate == 1.0, stats
    assert stats.tokens_per_round == 4.0  # k+1 every round


@pytest.mark.heavy  # in-suite training/soak — fast profile: -m 'not heavy'
def test_trained_draft_accepts_on_domain():
    """A small draft trained on the same pattern as the target
    accepts a meaningful fraction — the speedup story, measured."""
    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = _train_repeater(target)
    dp = _train_repeater(draft, seed=3)
    tok = ByteTokenizer()
    prompt = np.asarray(tok.token_ids("abcababcab"), np.int32)[None]
    ref = _greedy_ref(target, tp, prompt, 30)
    got, stats = speculative_generate(
        target, tp, draft, dp, prompt, max_new_tokens=30, k=4,
    )
    assert got == ref
    assert stats.acceptance_rate > 0.5, (
        f"in-domain draft only accepted {stats.acceptance_rate:.2f}"
    )


def test_llama_family_supported():
    cfg = dict(T_CFG, hidden_size=32, num_layers=2)
    cfg.pop("num_heads")
    target = get_model("llama_lm", **cfg, num_heads=4, num_kv_heads=2)
    tp = target.init(jax.random.key(0))
    prompt = np.arange(6, dtype=np.int32)[None] % 120 + 3
    ref = _greedy_ref(target, tp, prompt, 12)
    got, stats = speculative_generate(
        target, tp, target, tp, prompt, max_new_tokens=12, k=2,
    )
    assert got == ref
    assert stats.acceptance_rate == 1.0


def test_window_edge_falls_back_to_plain_steps():
    """Near the model window there is no room for a k+1 block: the
    loop degrades to plain steps and still emits the exact stream."""
    cfg = dict(T_CFG, max_positions=48)
    target = get_model("gpt_lm", **cfg)
    tp = target.init(jax.random.key(0))
    prompt = np.arange(8, dtype=np.int32)[None] % 100 + 3
    n = 40  # prompt + n == max_positions: the tail has no block room
    ref = _greedy_ref(target, tp, prompt, n)
    got, stats = speculative_generate(
        target, tp, target, tp, prompt, max_new_tokens=n, k=4,
    )
    assert got == ref
    assert stats.fallback_steps > 0


@pytest.fixture
def anyio_backend():
    return "asyncio"


def _engines():
    from mlapi_tpu.serving.engine import TextGenerationEngine

    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(0))
    dp = draft.init(jax.random.key(1))
    tok = ByteTokenizer()
    # fused_single=False: these tests exercise the HOST spec phase and
    # its admission handoff; the batch-1 fused fast path would serve
    # the solo requests as one program and never run host rounds.
    plain = TextGenerationEngine(
        target, tp, tokenizer=tok, chunk=4, fused_single=False,
    )
    spec = TextGenerationEngine(
        target, tp, tokenizer=tok, chunk=4, draft=(draft, dp), spec_k=3,
        fused_single=False,
    )
    return plain, spec


def test_engine_spec_stream_matches_plain_engine():
    """--draft-checkpoint serving: a single greedy request decodes
    through speculative rounds and emits exactly what the draft-less
    engine emits; sampled requests bypass speculation entirely."""
    plain, spec = _engines()
    ref = plain.generate_text("abcabcab", max_new_tokens=24)
    got = spec.generate_text("abcabcab", max_new_tokens=24)
    assert got["token_ids"] == ref["token_ids"]
    assert spec.spec_rounds > 0, "speculation never engaged"

    base_rounds = spec.spec_rounds
    s_ref = plain.generate_text("ab", max_new_tokens=8,
                                temperature=0.8, seed=3)
    s_got = spec.generate_text("ab", max_new_tokens=8,
                               temperature=0.8, seed=3)
    assert s_got["token_ids"] == s_ref["token_ids"]
    assert spec.spec_rounds == base_rounds, "sampled request speculated"


@pytest.mark.anyio
async def test_engine_spec_hands_off_to_admission():
    """A joiner arriving mid-speculation is admitted: the spec phase
    yields at a round boundary and the normal loop takes over — both
    streams stay exact."""
    import asyncio

    plain, spec = _engines()
    ref_a = plain.generate_text("abcabcab", max_new_tokens=40)
    ref_b = plain.generate_text("xyz", max_new_tokens=6)
    await spec.start()
    try:
        a = await spec.submit("abcabcab", max_new_tokens=40)
        first = await a.queue.get()
        b = await spec.submit("xyz", max_new_tokens=6)
        got_b = []
        while True:
            item = await b.queue.get()
            if item is None:
                break
            assert not isinstance(item, Exception), item
            got_b.extend(item["token_ids"])
        got_a = list(first["token_ids"])
        while True:
            item = await a.queue.get()
            if item is None:
                break
            assert not isinstance(item, Exception), item
            got_a.extend(item["token_ids"])
        assert got_a == ref_a["token_ids"]
        assert got_b == ref_b["token_ids"]
        assert spec.admitted >= 1, "joiner was not admitted"
        # After the joiner finished, the long stream's tail must have
        # RE-engaged speculation (draft-cache replay), not decoded
        # token-at-a-time forever.
        assert spec.spec_rounds >= 2, spec.spec_rounds
    finally:
        await spec.stop()


def test_batch_and_vocab_validation():
    target = get_model("gpt_lm", **T_CFG)
    tp = target.init(jax.random.key(0))
    with pytest.raises(ValueError, match="single-row"):
        speculative_generate(
            target, tp, target, tp,
            np.zeros((2, 4), np.int32), max_new_tokens=4,
        )
    other = get_model("gpt_lm", **dict(D_CFG, vocab_size=128))
    with pytest.raises(ValueError, match="vocabulary"):
        speculative_generate(
            target, tp, other, other.init(jax.random.key(1)),
            np.zeros((1, 4), np.int32), max_new_tokens=4,
        )

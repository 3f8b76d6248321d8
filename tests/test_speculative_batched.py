"""Batched speculative decoding (`ops/speculative.py` batched path):
per-row cache write positions let a WHOLE BATCH of greedy streams
speculate in lockstep rounds while each row advances by its own
acceptance length — the layout change the scalar-``pos`` design
deliberately deferred (rowpos support in `models/gpt.py`'s
`cached_attend` / mask helpers).

The pin is the same as single-row speculation: every row's emitted
stream is byte-identical to its SOLO plain greedy stream, for any
draft quality — desynchronized rows must not leak into each other's
cache or mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.models import get_model
from mlapi_tpu.ops.speculative import (
    speculative_generate,
    speculative_generate_batched,
)

T_CFG = dict(
    vocab_size=260, hidden_size=48, num_layers=3, num_heads=4,
    max_positions=160, compute_dtype="float32",
)
D_CFG = dict(
    vocab_size=260, hidden_size=24, num_layers=1, num_heads=2,
    max_positions=160, compute_dtype="float32",
)


def _solo_refs(model, params, prompts, n):
    return [
        np.asarray(
            model.generate(
                params, jnp.asarray(p[None]), max_new_tokens=n
            )
        )[0].tolist()
        for p in prompts
    ]


@pytest.mark.parametrize("k", [1, 3])
def test_every_row_matches_its_solo_greedy_stream(k):
    """Random draft + random target, 3 different prompts: rows accept
    different lengths each round (desync from round one) and every
    stream must still equal its solo run exactly."""
    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(0))
    dp = draft.init(jax.random.key(1))
    prompts = np.stack([
        (np.arange(9, dtype=np.int32) % 200) + 3,
        (np.arange(9, dtype=np.int32)[::-1] % 180) + 7,
        (np.full(9, 42, dtype=np.int32)),
    ])
    n = 22
    refs = _solo_refs(target, tp, prompts, n)
    got, stats = speculative_generate_batched(
        target, tp, draft, dp, prompts, max_new_tokens=n, k=k,
    )
    assert got == refs, (k, stats)
    assert all(len(g) == n for g in got)


def test_batched_matches_single_row_library():
    """The batched path and the single-row library emit identical
    streams for the same row (same round algebra, different cache
    layout)."""
    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(2))
    dp = draft.init(jax.random.key(3))
    prompt = (np.arange(8, dtype=np.int32) % 150) + 5
    solo, _ = speculative_generate(
        target, tp, draft, dp, prompt[None], max_new_tokens=18, k=3,
    )
    batched, _ = speculative_generate_batched(
        target, tp, draft, dp, prompt[None], max_new_tokens=18, k=3,
    )
    assert batched[0] == solo


def test_draft_equals_target_full_acceptance_batched():
    target = get_model("gpt_lm", **T_CFG)
    tp = target.init(jax.random.key(0))
    prompts = np.stack([
        (np.arange(7, dtype=np.int32) % 150) + 5,
        (np.arange(7, dtype=np.int32) % 90) + 11,
    ])
    n = 21
    refs = _solo_refs(target, tp, prompts, n)
    got, stats = speculative_generate_batched(
        target, tp, target, tp, prompts, max_new_tokens=n, k=4,
    )
    assert got == refs
    assert stats.acceptance_rate == 1.0, stats


def test_llama_family_batched():
    cfg = dict(T_CFG, hidden_size=32, num_layers=2)
    cfg.pop("num_heads")
    target = get_model("llama_lm", **cfg, num_heads=4, num_kv_heads=2)
    tp = target.init(jax.random.key(0))
    prompts = np.stack([
        (np.arange(6, dtype=np.int32) % 120) + 3,
        (np.arange(6, dtype=np.int32) % 77) + 9,
    ])
    n = 12
    refs = _solo_refs(target, tp, prompts, n)
    got, stats = speculative_generate_batched(
        target, tp, target, tp, prompts, max_new_tokens=n, k=2,
    )
    assert got == refs
    assert stats.acceptance_rate == 1.0


def test_window_headroom_validated():
    cfg = dict(T_CFG, max_positions=32)
    target = get_model("gpt_lm", **cfg)
    tp = target.init(jax.random.key(0))
    prompts = (np.arange(8, dtype=np.int32) % 100)[None] + 3
    with pytest.raises(ValueError, match="cache slots"):
        speculative_generate_batched(
            target, tp, target, tp, prompts, max_new_tokens=24, k=4,
        )


def test_sampled_batched_rows_do_not_depend_on_their_batch():
    """Batched SAMPLED speculation: every row of a batch of three is
    byte-identical to a batch holding that row alone with the same
    seed — per-row seeds, desynchronized positions, budget-capped
    last rounds and all. (The host-loop ``speculative_sample`` is not
    the byte reference: it serves a budget-1 tail with a plain step.)"""
    from mlapi_tpu.ops.speculative import speculative_sample_batched

    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(0))
    dp = draft.init(jax.random.key(1))
    prompts = np.stack([
        (np.arange(8, dtype=np.int32) % 200) + 3,
        (np.arange(8, dtype=np.int32)[::-1] % 150) + 7,
        (np.full(8, 31, dtype=np.int32)),
    ])
    n, k, temp, seeds = 17, 3, 0.9, [5, 11, 42]
    refs = [
        speculative_sample_batched(
            target, tp, draft, dp, prompts[i][None],
            max_new_tokens=n, k=k, temperature=temp, seeds=[seeds[i]],
        )[0][0]
        for i in range(3)
    ]
    got, stats = speculative_sample_batched(
        target, tp, draft, dp, prompts,
        max_new_tokens=n, k=k, temperature=temp, seeds=seeds,
    )
    assert got == refs, stats
    assert stats.rounds > 0


def test_sampled_batched_draft_equals_target_accepts_all():
    from mlapi_tpu.ops.speculative import speculative_sample_batched

    target = get_model("gpt_lm", **T_CFG)
    tp = target.init(jax.random.key(2))
    prompts = np.stack([
        (np.arange(6, dtype=np.int32) % 150) + 5,
        (np.arange(6, dtype=np.int32) % 90) + 11,
    ])
    got, stats = speculative_sample_batched(
        target, tp, target, tp, prompts,
        max_new_tokens=16, k=4, temperature=0.8,
        top_k=12, top_p=0.9, seeds=[1, 2],
    )
    assert all(len(g) == 16 for g in got)
    assert stats.acceptance_rate == 1.0, stats


def test_sampled_batched_greedy_delegates():
    from mlapi_tpu.ops.speculative import speculative_sample_batched

    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(0))
    dp = draft.init(jax.random.key(1))
    prompts = (np.arange(7, dtype=np.int32) % 150)[None] + 5
    ref, _ = speculative_generate_batched(
        target, tp, draft, dp, prompts, max_new_tokens=12, k=3,
    )
    got, _ = speculative_sample_batched(
        target, tp, draft, dp, prompts,
        max_new_tokens=12, k=3, temperature=0.0,
    )
    assert got == ref


def test_uneven_finish_rows_ride_as_dummies():
    """All rows share max_new_tokens, but acceptance differences make
    rows REACH the budget at different rounds; late rows must finish
    correctly after early rows froze."""
    target = get_model("gpt_lm", **T_CFG)
    draft = get_model("gpt_lm", **D_CFG)
    tp = target.init(jax.random.key(5))
    dp = draft.init(jax.random.key(6))
    prompts = np.stack([
        (np.arange(10, dtype=np.int32) % 200) + 3,
        (np.arange(10, dtype=np.int32) * 7 % 190) + 4,
        (np.arange(10, dtype=np.int32) * 3 % 170) + 6,
        (np.full(10, 99, dtype=np.int32)),
    ])
    n = 33  # not a multiple of k+1: forces budget-capped last rounds
    refs = _solo_refs(target, tp, prompts, n)
    got, stats = speculative_generate_batched(
        target, tp, draft, dp, prompts, max_new_tokens=n, k=4,
    )
    assert got == refs, stats

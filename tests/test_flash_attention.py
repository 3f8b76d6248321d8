"""Pallas flash-attention kernel vs the XLA baseline — interpret mode
on CPU (SURVEY §4: no TPU needed for correctness), compiled parity
behind ``requires_tpu``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlapi_tpu.ops import full_attention
from mlapi_tpu.ops.pallas import flash_attention, flash_attention_on_mesh

B, L, H, D = 2, 64, 4, 16


def _qkv(seed=0, dtype=jnp.float32, l=L):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (B, l, H, D), dtype) for k in ks)


def test_matches_full_attention():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, block_q=32, interpret=True)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_matches_with_padding_mask():
    q, k, v = _qkv(seed=1)
    lengths = np.array([L - 3, 9])
    mask = (np.arange(L)[None, :] < lengths[:, None]).astype(np.float32)
    out = flash_attention(q, k, v, jnp.asarray(mask), block_q=32, interpret=True)
    ref = full_attention(q, k, v, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_causal_matches():
    q, k, v = _qkv(seed=2)
    out = flash_attention(q, k, v, causal=True, block_q=16, interpret=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fully_masked_rows_are_zero_not_nan():
    q, k, v = _qkv(seed=3)
    mask = np.zeros((B, L), np.float32)  # nothing valid at all
    out = flash_attention(q, k, v, jnp.asarray(mask), block_q=32, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_block_q_larger_than_sequence_is_clamped():
    q, k, v = _qkv(seed=4, l=16)
    out = flash_attention(q, k, v, block_q=128, interpret=True)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_indivisible_block_degrades_to_dividing_halving():
    """A block that doesn't divide L halves until it does (32 → 16
    for L=48) instead of erroring — so growing the performance default
    can never turn a working length into a crash."""
    q, k, v = _qkv(seed=5, l=48)
    out = flash_attention(q, k, v, block_q=32, interpret=True)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gradients_match_full_attention():
    """flash is differentiable (custom VJP: Pallas kernels in both
    directions) — grads must match the reference."""
    q, k, v = _qkv(seed=7)
    lengths = np.array([L - 6, 23])
    mask = jnp.asarray(
        (np.arange(L)[None, :] < lengths[:, None]).astype(np.float32)
    )

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, mask, block_q=32, interpret=True) ** 2
        )

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, mask) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_bert_flash_backend_matches_full():
    """attention_impl='flash' is logit-identical to 'full' (interpret
    mode here; the compiled path is covered by the TPU-marked test)."""
    from mlapi_tpu.models import get_model

    cfg = dict(
        num_classes=2, vocab_size=128, hidden_size=32, num_layers=2,
        num_heads=4, intermediate_size=64, max_positions=64,
        compute_dtype="float32",
    )
    full = get_model("bert_classifier", **cfg)
    flash = get_model("bert_classifier", **cfg, attention_impl="flash")
    params = full.init(jax.random.key(0))
    ids = np.ones((2, 64), np.int32)
    ids[0, 40:] = 0
    ids[1, 11:] = 0
    ref = jax.jit(full.apply)(params, jnp.asarray(ids))
    out = jax.jit(flash.apply)(params, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.requires_tpu
def test_compiled_on_tpu_matches():
    q, k, v = _qkv(seed=6, dtype=jnp.bfloat16, l=256)
    lengths = np.array([200, 117])
    mask = (np.arange(256)[None, :] < lengths[:, None]).astype(np.float32)
    out = flash_attention(q, k, v, jnp.asarray(mask))
    ref = full_attention(q, k, v, jnp.asarray(mask))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_gradients_match_with_k_tiling_and_causal():
    """Both backward kernels accumulate across tiles: exercise
    multiple q- AND k-tiles (4x4 grid) with causal + padding mask —
    the online-softmax recompute path, not a single-tile degenerate."""
    q, k, v = _qkv(seed=8)
    lengths = np.array([L - 6, 23])
    mask = jnp.asarray(
        (np.arange(L)[None, :] < lengths[:, None]).astype(np.float32)
    )

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, mask, causal=True, block_q=16, block_k=16,
            interpret=True,
        )
        return jnp.sum(out ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, mask, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_forward_lse_is_consistent_under_k_tiling():
    """Forward output must not depend on the k-tile size (the online
    carry is exact, not approximate)."""
    q, k, v = _qkv(seed=9)
    a = flash_attention(q, k, v, block_q=16, block_k=64, interpret=True)
    b = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.requires_tpu
def test_compiled_grad_has_no_quadratic_tensor():
    """VERDICT r1 done-criterion: the compiled grad path must not
    materialise an [L, L] score tensor in HBM — check the optimized
    HLO for any buffer with two trailing L-sized dims."""
    l = 512
    q, k, v = _qkv(seed=10, dtype=jnp.bfloat16, l=l)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    txt = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(q, k, v)
        .compile()
        .as_text()
    )
    import re

    quadratic = re.findall(rf"\[(?:\d+,)*{l},{l}\]", txt)
    assert not quadratic, f"found [L,L] buffers in HLO: {quadratic[:5]}"


def test_gqa_forward_matches_repeated_reference():
    """GQA-native kernel (kv BlockSpec indexes hi // group) must equal
    attention over explicitly repeated K/V heads."""
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 8))
    k = jax.random.normal(ks[1], (2, 32, 2, 8))  # 2 kv heads, group=2
    v = jax.random.normal(ks[2], (2, 32, 2, 8))
    lengths = np.array([30, 17])
    mask = jnp.asarray(
        (np.arange(32)[None, :] < lengths[:, None]).astype(np.float32)
    )
    out = flash_attention(q, k, v, mask, interpret=True)
    ref = full_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), mask
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gqa_gradients_fold_onto_shared_kv_heads():
    """d/dK, d/dV of the GQA kernel must equal the repeated-reference
    grads summed over each group (the VJP's fold-back)."""
    ks = jax.random.split(jax.random.key(22), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 8))
    k = jax.random.normal(ks[1], (1, 32, 2, 8))
    v = jax.random.normal(ks[2], (1, 32, 2, 8))

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True) ** 2
        )

    def loss_ref(q, k, v):
        kf = jnp.repeat(k, 2, axis=2)
        vf = jnp.repeat(v, 2, axis=2)
        return jnp.sum(full_attention(q, kf, vf, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_gqa_rejects_indivisible_heads():
    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 3, 8))
    with pytest.raises(ValueError, match="multiple of"):
        flash_attention(q, kv, kv, interpret=True)


@pytest.mark.requires_tpu
def test_gqa_compiled_on_tpu_matches():
    """The hi // group BlockSpec must survive real Mosaic lowering."""
    ks = jax.random.split(jax.random.key(23), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 256, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 256, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = full_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def _windowed_reference(q, k, v, window, mask=None):
    """Oracle: full attention with an explicit sliding-window mask."""
    import mlapi_tpu.ops.attention as att

    lq, lk = q.shape[1], k.shape[1]
    dist = np.arange(lq)[:, None] - np.arange(lk)[None, :]
    win = (dist >= 0) & (dist < window)
    keep = np.broadcast_to(win, (q.shape[0],) + win.shape).astype(np.float32)
    if mask is not None:
        keep = keep * np.asarray(mask)[:, None, :]
    s = np.einsum(
        "bqhd,bkhd->bhqk", np.asarray(q, np.float32), np.asarray(k, np.float32)
    ) / q.shape[-1] ** 0.5
    s = s + (1.0 - keep[:, None]) * att.NEG
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p * keep[:, None]
    denom = np.maximum(p.sum(-1, keepdims=True), 1e-30)
    return np.einsum(
        "bhqk,bkhd->bqhd", p / denom, np.asarray(v, np.float32)
    )


def test_sliding_window_matches_masked_reference():
    q, k, v = _qkv(seed=31)
    out = flash_attention(
        q, k, v, causal=True, window=10, block_q=16, block_k=16,
        interpret=True,
    )
    ref = _windowed_reference(q, k, v, 10)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_sliding_window_with_padding_mask_and_grads():
    q, k, v = _qkv(seed=32)
    lengths = np.array([L - 4, 37])
    mask = jnp.asarray(
        (np.arange(L)[None, :] < lengths[:, None]).astype(np.float32)
    )
    out = flash_attention(
        q, k, v, mask, causal=True, window=12, block_q=16, block_k=16,
        interpret=True,
    )
    ref = _windowed_reference(q, k, v, 12, mask=mask)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    # Grads: keys outside every query's window must get ZERO gradient.
    def loss(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, window=8, block_q=16, block_k=16,
                interpret=True,
            )[:, -1]  # only the last query row contributes
            ** 2
        )

    dk = jax.grad(loss, argnums=1)(q, k, v)
    dk = np.asarray(dk)
    assert np.abs(dk[:, : L - 8]).max() == 0.0  # outside the last row's window
    assert np.abs(dk[:, L - 8 :]).max() > 0.0


def test_window_tile_skip_is_exact_at_tile_boundaries():
    """Window == block size: whole tiles drop; result still exact."""
    q, k, v = _qkv(seed=33)
    out = flash_attention(
        q, k, v, causal=True, window=16, block_q=16, block_k=16,
        interpret=True,
    )
    ref = _windowed_reference(q, k, v, 16)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_window_requires_causal():
    q, k, v = _qkv(seed=34)
    with pytest.raises(ValueError, match="window requires causal"):
        flash_attention(q, k, v, window=8, interpret=True)


def test_randomized_differential_sweep():
    """Fuzz the kernel against the einsum oracle across random
    (shape, mask, GQA group, window, blocks, causal) configs — one
    seeded float32 sweep, so failures reproduce exactly (bf16
    numerics are covered separately by the requires_tpu tests)."""
    rng = np.random.default_rng(2026)
    for trial in range(12):
        b = int(rng.integers(1, 3))
        lq = int(rng.choice([16, 32, 48, 64]))
        h = int(rng.choice([2, 4]))
        d = int(rng.choice([8, 16]))
        group = int(rng.choice([1, 2]))
        kvh = h // group
        causal = bool(rng.integers(0, 2))
        window = (
            int(rng.choice([8, 16])) if causal and rng.integers(0, 2) else None
        )
        bq = int(rng.choice([16, 32]))
        bk = int(rng.choice([16, 32]))  # mismatched blocks included
        ks = jax.random.split(jax.random.key(trial), 3)
        q = jax.random.normal(ks[0], (b, lq, h, d))
        k = jax.random.normal(ks[1], (b, lq, kvh, d))
        v = jax.random.normal(ks[2], (b, lq, kvh, d))
        lengths = rng.integers(1, lq + 1, size=b)
        mask = jnp.asarray(
            (np.arange(lq)[None, :] < lengths[:, None]).astype(np.float32)
        )
        out = flash_attention(
            q, k, v, mask, causal=causal, window=window,
            block_q=bq, block_k=bk, interpret=True,
        )
        # Oracle: the shared references (no third masking copy).
        kf = jnp.repeat(k, group, axis=2) if group > 1 else k
        vf = jnp.repeat(v, group, axis=2) if group > 1 else v
        if causal:
            ref = _windowed_reference(q, kf, vf, window or lq, mask=mask)
        else:
            ref = np.asarray(full_attention(q, kf, vf, mask))
        np.testing.assert_allclose(
            np.asarray(out), ref, atol=2e-5,
            err_msg=f"trial {trial}: b={b} l={lq} h={h} d={d} "
                    f"group={group} causal={causal} window={window} "
                    f"bq={bq} bk={bk}",
        )


# (block_q, block_k, window, L, causal): the six windowed cases the
# shrunken grids were checked on, then causal alone and not causal.
SCHEDULES = [
    (16, 16, 8, 64, True), (16, 16, 16, 64, True), (32, 16, 24, 128, True),
    (16, 32, 40, 128, True), (32, 32, 32, 256, True), (16, 16, 50, 128, True),
    (32, 16, None, 128, True), (16, 32, None, 64, False),
]


@pytest.mark.parametrize("order", ["forward", "dq", "dkv"])
@pytest.mark.parametrize("bq,bk,window,l,causal", SCHEDULES)
def test_tile_schedule_walks_every_kept_pair_once(bq, bk, window, l, causal,
                                                  order):
    """White-box: the walk a kernel's last grid dimension takes. Every
    kept (q, k) pair lies in exactly one step's tile (a query head's,
    for dk/dv's walk over a group of 3); no step's tile is dead; a tile
    not flagged masked keeps every pair; the steps that revisit one
    output block are consecutive, open and close where the flags say,
    and come in the order the rectangular grids had (q-major for the
    forward and dq, k-major over the group's heads for dk/dv)."""
    from mlapi_tpu.ops.pallas.flash_attention import (
        _FIRST, _LAST, _MASKED, _tile_schedule, _walk,
    )

    group = 3 if order == "dkv" else 0
    qi, ki, g, flags = _walk(
        _tile_schedule(l, l, bq, bk, causal, window), group)
    dist = np.arange(l)[:, None] - np.arange(l)[None, :]
    kept = (dist >= 0) & (dist < (window or l)) if causal else dist == dist
    seen = np.zeros((max(group, 1), l, l), np.int32)
    for t in range(len(qi)):
        rows = slice(qi[t] * bq, (qi[t] + 1) * bq)
        cols = slice(ki[t] * bk, (ki[t] + 1) * bk)
        tile = kept[rows, cols]
        assert tile.any(), f"step {t}: dead tile ({qi[t]}, {ki[t]})"
        assert bool(flags[t] & _MASKED) == (not tile.all()), f"step {t}"
        seen[g[t], rows, cols] += tile
    np.testing.assert_array_equal(seen, np.broadcast_to(kept, seen.shape))
    # The order, and the runs that revisit one output block.
    steps = list(zip(qi, ki, g))
    key = (lambda s: (s[1], s[2], s[0])) if group else (lambda s: s)
    assert steps == sorted(steps, key=key)
    run = ki if group else qi
    edges = np.flatnonzero(np.diff(run)) + 1
    assert len(set(run)) == len(edges) + 1  # each run is one block of steps
    first = np.zeros(len(run), bool)
    first[np.r_[0, edges]] = True
    last = np.zeros(len(run), bool)
    last[np.r_[edges - 1, len(run) - 1]] = True
    np.testing.assert_array_equal((flags & _FIRST) != 0, first)
    np.testing.assert_array_equal((flags & _LAST) != 0, last)


def test_window_with_mismatched_blocks_matches_reference():
    """The shrunken k-grid's diagonal-tile arithmetic differs per
    q-tile alignment when block_q != block_k — exercise both
    directions through the actual kernel."""
    q, k, v = _qkv(seed=35)
    for bq, bk in [(16, 32), (32, 16)]:
        out = flash_attention(
            q, k, v, causal=True, window=24, block_q=bq, block_k=bk,
            interpret=True,
        )
        ref = _windowed_reference(q, k, v, 24)
        np.testing.assert_allclose(
            np.asarray(out), ref, atol=1e-5, err_msg=f"bq={bq} bk={bk}"
        )


@pytest.mark.parametrize("shape", [(8, 1), (2, 4), (1, 4)])
def test_on_mesh_matches_the_plain_call(shape):
    """The ``shard_map`` wrapper the models use on a mesh (GSPMD
    cannot partition a compiled Mosaic kernel): batch rows over
    ``data``, heads over ``model``, values AND gradients equal to the
    unsharded call. B=2 over data=8 does not divide — that dimension
    stays whole on every device instead of failing."""
    from mlapi_tpu.parallel import create_mesh

    mesh = create_mesh(shape)
    q, k, v = _qkv(seed=11)
    mask = jnp.asarray(
        (np.arange(L)[None, :] < np.array([L - 5, 17])[:, None]), jnp.float32
    )

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    plain = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, mask, block_q=32, interpret=True)
    sharded = lambda q, k, v: flash_attention_on_mesh(  # noqa: E731
        mesh, q, k, v, mask, block_q=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(jax.jit(sharded)(q, k, v)), np.asarray(plain(q, k, v)),
        atol=1e-6,
    )
    for got, want in zip(
        jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v),
        jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v),
    ):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert flash_attention_on_mesh(None, q, k, v, mask, interpret=True).shape == q.shape


# -- one-tile sequences: the row-blocked kernels ------------------------
# (a batch row's heads in one grid step, statistics [B, H, L] along
# lanes, operands in the model's own [B, L, H*D] layout)

def _oracle(q, k, v, mask, causal=False, window=None):
    """Plain float32 masked softmax attention, differentiable; K/V
    heads repeated for GQA; rows with no key to attend come out 0."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    lq, lk = q.shape[1], k.shape[1]
    keep = jnp.broadcast_to(mask[:, None, None, :] > 0, (q.shape[0], 1, lq, lk))
    if causal:
        dist = jnp.arange(lq)[:, None] - jnp.arange(lk)[None, :]
        keep = keep & (dist >= 0) & (dist < (window or lk))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    s = jnp.where(keep, s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True)) * keep
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# name: (B, L, H, KVH, D, lengths, causal, window)
ONE_TILE = {
    "padded-with-a-fully-masked-row": (3, 32, 4, 4, 16, [32, 9, 0], False, None),
    "causal-d64": (1, 128, 2, 2, 64, [128], True, None),
    "causal-window": (2, 128, 2, 2, 16, [128, 77], True, 24),
    "gqa-8-over-2": (2, 32, 8, 2, 16, [30, 17], True, None),
    "three-local-heads-of-a-tp-shard": (2, 128, 3, 3, 64, [128, 50], False, None),
    "l512-padded-causal": (1, 512, 2, 2, 16, [400], True, None),
    "l512-gqa-d64-window": (1, 512, 2, 1, 64, [512], True, 100),
}


def _one_tile_case(name):
    b, l, h, kvh, d, lengths, causal, window = ONE_TILE[name]
    ks = jax.random.split(jax.random.key(len(name)), 3)
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, kvh, d))
    v = jax.random.normal(ks[2], (b, l, kvh, d))
    mask = jnp.asarray(
        np.arange(l)[None, :] < np.asarray(lengths)[:, None], jnp.float32
    )
    kw = dict(causal=causal, window=window)
    from mlapi_tpu.ops.pallas.flash_attention import _one_tile_heads

    assert _one_tile_heads(q, k, l, l) == h  # the new blocking takes it
    return (q, k, v), mask, kw


@pytest.mark.parametrize("name", list(ONE_TILE))
def test_one_tile_forward_matches_oracle(name):
    (q, k, v), mask, kw = _one_tile_case(name)
    out = flash_attention(q, k, v, mask, interpret=True, **kw)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_oracle(q, k, v, mask, **kw)), atol=2e-5
    )


@pytest.mark.parametrize("name", list(ONE_TILE))
def test_one_tile_gradients_match_oracle(name):
    (q, k, v), mask, kw = _one_tile_case(name)
    w = jax.random.normal(jax.random.key(99), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, mask, interpret=True, **kw)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _oracle(q, k, v, mask, **kw)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("name", ["gqa-8-over-2", "causal-d64"])
def test_one_tile_lse_and_its_cotangent_match_jnp_flash(name):
    """``flash_attention_with_lse`` returns ``[B, H, L]`` equal to the
    pure-jnp twin's, and a loss on BOTH outputs differentiates the
    same (the LSE cotangent folds into delta inside the dq kernel):
    what ring attention's merge trains through. (Cases in which every
    query sees a key: through a fully masked row's LSE the twin's
    plain autodiff gives NaN where the kernels give 0.)"""
    from mlapi_tpu.ops.pallas import flash_attention_with_lse
    from mlapi_tpu.ops.pallas.flash_attention import _jnp_flash

    (q, k, v), mask, kw = _one_tile_case(name)
    scale = 1.0 / q.shape[-1] ** 0.5

    def kernel(q, k, v):
        return flash_attention_with_lse(q, k, v, mask, interpret=True, **kw)

    def twin(q, k, v):
        return _jnp_flash(q, k, v, mask, kw["causal"], scale, kw["window"])

    out, lse = kernel(q, k, v)
    ref_out, ref_lse = twin(q, k, v)
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5)

    def loss(fn):
        def f(q, k, v):
            o, s = fn(q, k, v)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(s))
        return f

    for a, b in zip(jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(twin), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _flash_counts():
    from mlapi_tpu.utils.metrics import REGISTRY

    c = REGISTRY.snapshot()["counters"]
    return (c.get("flash.calls_traced", 0), c.get("flash.calls_row_blocked", 0))


def test_counters_rise_once_per_traced_one_tile_call():
    """The blocking is chosen at trace time and counted there: one
    trace, one count, and nothing when the cached program runs
    again."""
    q = jax.random.normal(jax.random.key(40), (1, 24, 2, 8))  # a shape of its own
    traced, blocked = _flash_counts()
    flash_attention(q, q, q, interpret=True)
    assert _flash_counts() == (traced + 1, blocked + 1)
    flash_attention(q, q, q, interpret=True)
    assert _flash_counts() == (traced + 1, blocked + 1)
    from mlapi_tpu.ops.pallas import flash_attention_with_lse

    flash_attention_with_lse(q, q, q, interpret=True)
    assert _flash_counts() == (traced + 2, blocked + 2)


def test_multi_tile_sequences_keep_the_streaming_kernels():
    """L = 1024 at the default 512 blocks is four tiles a head: the
    call is traced but NOT row-blocked, and the streaming kernels are
    still right, forward and backward."""
    ks = jax.random.split(jax.random.key(41), 3)
    q, k, v = (jax.random.normal(x, (1, 1024, 2, 16)) for x in ks)
    mask = jnp.asarray(np.arange(1024)[None, :] < 900, jnp.float32)
    traced, blocked = _flash_counts()
    out = flash_attention(q, k, v, mask, causal=True, interpret=True)
    assert _flash_counts() == (traced + 1, blocked)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_oracle(q, k, v, mask, causal=True)),
        atol=2e-5,
    )
    w = jax.random.normal(jax.random.key(42), q.shape)
    got = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, mask, causal=True, interpret=True) * w), argnums=(0, 1, 2)
    )(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        _oracle(q, k, v, mask, causal=True) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# name: (H, KVH, causal, window) at 1 x 64 positions in tiles of 16:
# four q-tiles, with full, masked and dead tiles where causal.
STREAMED = {
    "causal": (2, 2, True, None),
    "window": (2, 2, True, 40),
    "gqa-6-over-1": (6, 1, True, None),
    "not-causal": (2, 2, False, None),
}


def _streamed_grads(q, k, v, mask, causal, window):
    """(out, lse) and the gradient of a sum over both in q, k, v, of
    the streaming kernels at tiles of 16."""
    from mlapi_tpu.ops.pallas import flash_attention_with_lse

    def run(q, k, v):
        return flash_attention_with_lse(
            q, k, v, mask, causal=causal, window=window, block_q=16,
            block_k=16, interpret=True)

    def loss(q, k, v):
        out, lse = run(q, k, v)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse))

    return (*run(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))


@pytest.mark.parametrize("name", sorted(STREAMED))
def test_no_mask_is_an_all_ones_mask(name):
    """``mask=None`` drops the mask operand and, on full tiles, every
    mask multiply; an all-ones mask keeps them. Where every factor is
    1 each of those is an exact identity, so out, lse, dq, dk and dv
    agree to float32 rounding, and both with ``_jnp_flash``. (To the
    last bit where the CPU code has no fused multiply-add,
    ``XLA_FLAGS=--xla_cpu_max_isa=SSE4_2``; with FMAs XLA contracts
    the two programs' products apart by an ulp here and there.)"""
    from mlapi_tpu.ops.pallas.flash_attention import _jnp_flash

    h, kvh, causal, window = STREAMED[name]
    ks = jax.random.split(jax.random.key(43), 3)
    q = jax.random.normal(ks[0], (1, 64, h, 8))
    k, v = (jax.random.normal(x, (1, 64, kvh, 8)) for x in ks[1:])
    bare = _streamed_grads(q, k, v, None, causal, window)
    ones = _streamed_grads(q, k, v, jnp.ones((1, 64)), causal, window)
    for a, b in zip(bare, ones):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)

    def oracle(q, k, v):
        return _jnp_flash(q, k, v, None, causal, 8 ** -0.5, window)

    def loss(q, k, v):
        out, lse = oracle(q, k, v)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse))

    want = (*oracle(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    for a, b in zip(bare, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_padding_mask_zeroes_keys_in_full_tiles(causal):
    """A key mask still counts on tiles the schedule calls full: keys
    16-31 (all of k-tile 1, which the last two q-tiles see whole) get
    no weight and no gradient, and the rest agrees with the oracle."""
    ks = jax.random.split(jax.random.key(44), 3)
    q, k, v = (jax.random.normal(x, (1, 64, 2, 8)) for x in ks)
    mask = jnp.asarray((np.arange(64) // 16 != 1)[None], jnp.float32)
    w = jax.random.normal(jax.random.key(45), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    def kern(q, k, v):
        return flash_attention(q, k, v, mask, causal=causal, block_q=16,
                               block_k=16, interpret=True)

    def ref(q, k, v):
        return _oracle(q, k, v, mask, causal=causal)

    np.testing.assert_allclose(np.asarray(kern(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5)
    got = jax.grad(loss(kern), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    for dkv in got[1:]:
        assert np.abs(np.asarray(dkv)[:, 16:32]).max() == 0.0


def test_tile_counters_rise_once_per_traced_streaming_call():
    """The sizes of a head's tile schedule, which the kernels' names and
    ``_tile_schedule`` now give in place of a counter: 1 x 96 causal in
    tiles of 16 is 21 live tiles, the 6 on the diagonal masked; under a
    window of 16, 11, all masked; not causal, all 36 full."""
    from mlapi_tpu.ops.pallas.flash_attention import _tile_schedule

    def sizes(causal, window):
        tiles = _tile_schedule(96, 96, 16, 16, causal, window)
        return len(tiles), sum(m for *_, m in tiles)

    assert sizes(True, None) == (21, 6)
    assert sizes(True, 16) == (11, 11)
    assert sizes(False, None) == (36, 0)


_PASSES = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


@pytest.mark.parametrize("call", [
    dict(length=96, window=None, block_q=16, block_k=16),
    dict(length=96, window=16, block_q=16, block_k=16),
    dict(length=24, window=None),  # the default blocks cover it: one tile
])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_streaming_kernels_carry_their_pass_names(count_primitives, call,
                                                  direction):
    """A streaming call's ``pallas_call``s are named by their pass
    (``flash_attention_fwd``, ``_dq``, ``_dkv``: the names the chip's
    trace prints, each under the ``flash_attention`` prefix), one of
    each in a differentiated call; a one-tile call keeps its kernels
    unnamed, so it carries none of the three."""
    call = dict(call)
    q = jax.random.normal(jax.random.key(47), (1, call.pop("length"), 2, 8))

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True, **call)

    fn = attend if direction == "forward" else jax.grad(
        lambda *a: jnp.sum(attend(*a)), argnums=(0, 1, 2))
    counts = count_primitives(jax.make_jaxpr(fn)(q, q, q).jaxpr)
    got = tuple(counts[name] for name in _PASSES)
    if "block_q" not in call:
        assert got == (0, 0, 0)
        assert counts["_fwd_rows_kernel"] == 1
    else:
        assert got == ((1, 0, 0) if direction == "forward" else (1, 1, 1))


@pytest.mark.parametrize("wrap", ["no_checkpoint", "bare_checkpoint"])
def test_names_on_the_forward_results_lower_to_nothing(
        monkeypatch, count_primitives, wrap):
    """``_flash_fwd`` names ``out`` and ``lse`` for a recomputing caller
    whose ``jax.checkpoint`` has a policy (``models/kimi_linear.py``).
    A differentiated call under no checkpoint (BERT's step), or under a
    bare one, is what it was without them: the same lowered program
    text with the names taken out, so the same gradients. A bare
    checkpoint keeps nothing and runs the forward kernel twice."""
    import importlib
    import re

    fa = importlib.import_module("mlapi_tpu.ops.pallas.flash_attention")
    ks = jax.random.split(jax.random.key(43), 4)
    q, k, v, w = (jax.random.normal(x, (2, 128, 2, 64)) for x in ks)
    mask = jnp.asarray(np.arange(128)[None, :] < np.array([[128], [70]]),
                       jnp.float32)

    def lowered():
        def attend(q, k, v):   # the un-jitted body: no cached trace
            return fa.flash_attention.__wrapped__(
                q, k, v, mask, interpret=True)

        if wrap == "bare_checkpoint":
            attend = jax.checkpoint(attend)
        grad = jax.jit(jax.grad(
            lambda *a: jnp.sum(attend(*a) * w), argnums=(0, 1, 2)))
        # the interpreter's helper functions carry a running number
        text = re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      grad.lower(q, k, v).as_text())
        counts = count_primitives(jax.make_jaxpr(grad)(q, k, v).jaxpr)
        return text, grad(q, k, v), counts["_fwd_rows_kernel"]

    text, got, n_forward = lowered()
    assert n_forward == (2 if wrap == "bare_checkpoint" else 1)
    named = []
    monkeypatch.setattr(
        fa, "checkpoint_name", lambda x, name: named.append(name) or x)
    plain_text, want, _ = lowered()
    assert named[:2] == list(fa.REMAT_NAMES)   # the patch was traced
    assert text == plain_text
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "h, kvh, lq, lk, d, itemsize, want",
    [
        (12, 12, 128, 128, 64, 2, 12),   # the benchmark cell: all heads
        (12, 12, 512, 512, 64, 2, 12),   # GPT-2 prefill at one tile
        (3, 3, 128, 128, 64, 2, 3),      # TP (1,4): the LOCAL heads, whole
        (8, 2, 256, 256, 64, 2, 8),      # GQA: whole groups
        (32, 8, 512, 512, 128, 2, 32),   # llama-like, bf16: fits whole
        (32, 8, 512, 512, 128, 4, 16),   # float32: half, 4 kv heads a step
        (64, 64, 512, 512, 128, 4, 8),   # only multiples of 8 below H
        (12, 12, 512, 512, 128, 4, 0),   # 12 has none: streaming kernels
        (16, 16, 512, 512, 64, 4, 16),
        (16, 2, 512, 512, 256, 4, 8),    # hb a multiple of the group (8)
    ],
)
def test_heads_per_step_rule(h, kvh, lq, lk, d, itemsize, want):
    """``_row_heads``, the one-tile kernels' heads per grid step, as a
    pure function of the shapes: the largest divisor of H that keeps
    GQA groups whole, that Mosaic can block (all of H, or a multiple
    of 8 with 128-lane-aligned q and kv slabs) and that fits the VMEM
    budget; 0 sends the call to the streaming kernels."""
    from mlapi_tpu.ops.pallas.flash_attention import (
        _ROW_VMEM_BUDGET, _row_heads, _row_vmem_bytes,
    )

    hb = _row_heads(h, kvh, lq, lk, d, itemsize)
    assert hb == want
    if hb:
        group = h // kvh
        assert h % hb == 0 and hb % group == 0
        assert hb == h or (hb % 8 == 0 and (hb // group * d) % 128 == 0)
        assert _row_vmem_bytes(hb, hb // group, lq, lk, d, itemsize) <= _ROW_VMEM_BUDGET

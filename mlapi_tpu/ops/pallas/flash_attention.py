"""Fused attention kernels (flash-attention) in Pallas, both passes.

Why a kernel at all: stock XLA materialises the ``[B, H, L, L]``
score tensor in HBM between the two attention matmuls once L is big
enough that fusion gives up — at L=2048, BERT-base shapes, that is
256 MB of HBM traffic per layer. Here scores never exist at full
size anywhere: the forward streams K/V through VMEM in ``block_k``
tiles with the online-softmax recurrence (running max ``m``, running
normaliser ``l``, rescaled accumulator), and the backward recomputes
probabilities tile-by-tile from the saved log-sum-exp instead of
storing them. HBM sees Q/K/V/O (+ per-row LSE) only, in both
directions — no ``[L, L]`` tensor in the compiled HLO.

Two sets of kernels, chosen from the shapes at trace time (no flag):

**One-tile sequences** (both sequences fit one block after
``_fit_block``: every L up to the 512 default, BERT's and GPT-2's
training lengths among them). There is no recurrence to carry, so one
grid step takes ALL the heads of a batch row (``_row_heads``: as many
as a VMEM budget allows, in whole GQA groups) and does a plain softmax
per head in registers — grid ``(B, H/hb)``, no scratch. Operands stay
in the model's own layout: ``[B, L, H, D]`` is read as ``[B, L, H*D]``
(a bitcast), a head is a static D-wide lane slice, and the wrapper
transposes nothing. The row statistics (LSE; delta = Σ_d dO·O, made in
the dq kernel) are ``[B, H, L]`` float32 with L along lanes. Why: on a
v5e at BERT-base, batch 128 x 128, the streaming kernels below ran
1,536 grid steps of one ``[128, 64]`` head at 0.64 us each, wrote the
LSE as ``[B, H, L, 1]`` (a 128-lane tile a number: 100.7 MB for 0.79 MB
of statistics) and sat between eight full-size layout copies a layer —
12.5% of the kernels' byte roofline (``PERF.md``, PR 26-28).

- forward ``(B, H/hb)``: scores ``[Lq, Lk]`` a head; the LSE column is
  laid along lanes off the diagonal.
- backward dq and dk/dv, each ``(B, H/hb)``: probabilities
  recomputed TRANSPOSED (``[Lk, Lq]``) so the statistics broadcast from
  rows and dk/dv are plain products; a kv head's query group sums in
  registers (GQA-native). Causal and window are a mask inside the tile.

**Longer sequences** stream (TPU: the grid is iterated sequentially,
last dimension innermost; VMEM scratch persists across grid steps,
which is what carries the online-softmax state between K tiles).
A static tile schedule (``_tile_schedule``, built once a trace from
the lengths, blocks, causality and window) classes every (q-tile,
k-tile) pair as dead (no kept pair), masked (the causal diagonal or
the window's edge cuts it) or full, and each kernel's last grid
dimension walks the live pairs alone, from int32 tables handed to the
index maps by scalar prefetch (``_walk``):

- forward: ``(B, H, live tiles)``, q-major — a q-tile's live k-tiles
  in ascending order, its output accumulating across them.
- backward dq: ``(B, H, live tiles)``, the same walk.
- backward dk/dv: ``(B, KVH, group × live tiles)``, k-major — for one
  k-tile its kv head's query heads in turn, each head's live q-tiles
  ascending, accumulating consecutively into the KVH-wide dk/dv block
  (GQA-native; no repeated K/V in either pass).

A dead tile is no grid step (no copy, no compute): causal attention
does about half the work, a window O(L·window). A full tile runs the
body without the positional mask; without a key mask (``mask=None``,
the LM callers) neither the mask operand nor its multiply exists.

Per-program VMEM is a few ``block×block`` f32 tiles (~2-3 MB at the
default 512/512 blocks — measured 2x faster than 128/128 at L=8192
on v5e, where the sequential grid's per-step overhead dominates small
tiles) — inside the ~16 MB budget at any L. These kernels still
transpose to ``[B, H, L, D]`` and keep their statistics as
``[B, H, L, 1]`` (the same padding; no cell and no trace measures it).
Longer sequences still belong to the sequence-parallel path
(``mlapi_tpu.ops.ring_attention``).

Layout convention matches ``mlapi_tpu.ops.attention``: ``q, k, v``
are ``[B, L, H, D]``, ``mask`` is binary ``[B, L]`` over keys or
None; fully masked query rows return zeros (all three attention impls
agree).
Grouped-query attention is native in both passes: ``k``/``v`` may
carry ``H / group`` heads and the kernels index kv head ``h //
group`` — the repeated K/V tensor never exists in HBM.
Matmuls run native-dtype inputs with f32 accumulation on the MXU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlapi_tpu.utils.metrics import REGISTRY

# Python float (not a jax scalar — kernels may not capture traced
# constants); same finite large-negative as mlapi_tpu.ops.attention.NEG.
_NEG = -1e30
# Scratch lane width: TPU vector lanes are 128 wide; the forward's
# row-state scratch (m, l) is kept lane-replicated, and read and written
# whole (``_spread``).
_LANES = 128


# Bits of a walk's flags table (``_walk``): the step's tile is cut by
# the causal diagonal or the window's edge; the step opens / closes a
# run of steps that revisit one output block.
_MASKED, _FIRST, _LAST = 1, 2, 4


def _keep_tile(mask_ref, positional, qi, ki, block_q, block_k, shape,
               window=None):
    """Binary keep-mask for one (q-tile, k-tile) score block: the key
    mask's row (``mask_ref`` None: the caller gave none) times, when
    ``positional``, causality and the window (keys within the last
    ``window`` positions of each query: ``q_pos - k_pos < window``).
    None where nothing is masked: every pair is kept."""
    keep = None
    if mask_ref is not None:
        keep = mask_ref[0, 0][None, :].astype(jnp.float32)
    if positional:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        keep = _times(keep, q_pos >= k_pos)
        if window is not None:
            keep = _times(keep, q_pos - k_pos < window)
    return keep


def _times(keep, cut):
    """``keep * cut``, where a None ``keep`` keeps every pair."""
    return cut.astype(jnp.float32) if keep is None else keep * cut


@functools.lru_cache(maxsize=None)
def _tile_schedule(lq, lk, block_q, block_k, causal, window):
    """The live (q-tile, k-tile) pairs, q-major, each with whether the
    causal diagonal or the window's edge cuts it: ``((qi, ki, masked),
    ...)``. A tile holds every distance ``q - k`` from ``lo`` to
    ``hi``; causal attention keeps the distances in ``[0, window)``
    (``window`` None: ``[0, inf)``), so a tile is dead when none of its
    distances is kept and full when all are. Not causal: every tile is
    full. Static at trace time; every q-tile and every k-tile of a
    causal call keeps its diagonal, so every output block is written."""
    top = math.inf if window is None else window - 1
    tiles = []
    for qi in range(lq // block_q):
        for ki in range(lk // block_k):
            lo = qi * block_q - (ki + 1) * block_k + 1
            hi = (qi + 1) * block_q - 1 - ki * block_k
            if not causal:
                tiles.append((qi, ki, False))
            elif hi >= 0 and lo <= top:
                tiles.append((qi, ki, lo < 0 or hi > top))
    return tuple(tiles)


@functools.lru_cache(maxsize=None)
def _walk(tiles, group):
    """One kernel's last grid dimension as four int32 tables for scalar
    prefetch (SMEM: a few KB at L = 8192): step ``n`` works on q-tile
    ``qi[n]`` against k-tile ``ki[n]``, for query head ``g[n]`` of a kv
    head's group, with ``flags[n]`` (``_MASKED``, ``_FIRST``,
    ``_LAST``). ``group`` 0: q-major, the forward's and dq's walk (a
    q-tile's live k-tiles ascending; ``g`` is 0 and the head is a grid
    dimension). Else k-major, dk/dv's: for each k-tile, the group's
    query heads in turn and each head's live q-tiles ascending, all
    revisiting the k-tile's dk/dv block."""
    if group:
        steps = [(qi, ki, g, m)
                 for ki in sorted({t[1] for t in tiles})
                 for g in range(group)
                 for qi, kj, m in tiles if kj == ki]
        run = [s[1] for s in steps]
    else:
        steps = [(qi, ki, 0, m) for qi, ki, m in tiles]
        run = [s[0] for s in steps]
    last = len(steps) - 1
    flags = [
        _MASKED * s[3]
        | _FIRST * (n == 0 or run[n - 1] != run[n])
        | _LAST * (n == last or run[n + 1] != run[n])
        for n, s in enumerate(steps)
    ]
    qi, ki, g, _ = zip(*steps)
    return tuple(np.asarray(c, np.int32) for c in (qi, ki, g, flags))


def _tile_kinds(tiles):
    """The static tile kinds (masked or not) a schedule holds."""
    return tuple(sorted({m for *_, m in tiles}))


def _by_kind(flags, kinds, body):
    """``body(masked)`` for the step's tile kind: one body a kind the
    walk holds, each under its predicate when it holds both."""
    if len(kinds) == 1:
        body(kinds[0])
        return
    masked = (flags & _MASKED) != 0
    pl.when(masked)(lambda: body(True))
    pl.when(jnp.logical_not(masked))(lambda: body(False))


def _split_mask(refs, key_mask):
    """``(mask_ref or None, the refs after it)``: the key mask is an
    operand only when the caller gave one."""
    return (refs[0], refs[1:]) if key_mask else (None, refs)


def _spread(row, width):
    """A lane-replicated ``[n, 128]`` row statistic laid over ``width``
    lanes: whole copies of its vregs where ``width`` is a multiple of
    128, a lane slice where it is narrower (small test blocks), else a
    broadcast of its first column."""
    if width % _LANES == 0:
        return jnp.tile(row, (1, width // _LANES))
    if width < _LANES:
        return row[:, :width]
    return jnp.broadcast_to(row[:, :1], (row.shape[0], width))


def _fwd_kernel(
    qt, kt, gt, ft, q_ref, k_ref, v_ref, *refs, scale, block_q, block_k,
    window, key_mask, kinds,
):
    """One step of the online softmax. The running max ``m`` and sum
    ``l`` stay ``[block_q, 128]`` lane-replicated from end to end: read
    whole, combined with a row's ``[block_q, 1]`` max or sum by a
    broadcast that ends in full vregs, laid over the tile and the
    accumulator by whole-vreg copies (``_spread``) and stored whole.
    Sliced to a ``[block_q, 1]`` column and broadcast back every step,
    the same arithmetic took 14.4 ms of a TPU v5e's time a call where
    this takes 6.9 (1 x 8192, 48/8 heads of 128, causal; ``PERF.md``
    section 5), with results equal bit for bit."""
    del gt
    mask_ref, (o_ref, lse_ref, m_s, l_s, acc_s) = _split_mask(refs, key_mask)
    n = pl.program_id(2)
    flags = ft[n]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _step(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = (
            jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [block_q, block_k]
        keep = _keep_tile(
            mask_ref, masked, qt[n], kt[n], block_q, block_k, s.shape, window
        )
        if keep is not None:
            s = s + (1.0 - keep) * _NEG

        m_prev = m_s[:]                                # [block_q, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _spread(m_new, block_k))
        if keep is not None:
            # exp(NEG - NEG) == 1 on lanes with no valid key; * keep
            # zeroes them so fully-masked rows come out 0, not NaN.
            p = p * keep
        l_s[:] = alpha * l_s[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * _spread(alpha, acc_s.shape[1]) + (
            jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        m_s[:] = m_new

    _by_kind(flags, kinds, _step)

    @pl.when((flags & _LAST) != 0)
    def _finish():
        l = _spread(l_s[:], acc_s.shape[1])
        o_ref[0, 0] = (acc_s[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # [block_q, 1] column write — sublane-aligned, no relayout.
        lse_ref[0, 0] = m_s[:, :1] + jnp.log(jnp.maximum(l_s[:, :1], 1e-30))


def _jnp_flash(q, k, v, mask, causal, scale, window=None):
    """Pure-jnp (out, lse) with the kernel's exact conventions —
    identical masking/NEG/lse semantics, differentiable by plain
    autodiff (the lse cotangent flows through ``jnp.log``).

    Exists because the Pallas HLO *interpreter* cannot run inside a
    vma-checked ``shard_map`` (jax 0.9: its internal block slicing
    mixes the interpreter's unvarying loop indices with varying
    operands — ``dynamic_slice requires varying manual axes to
    match``). On CPU tests of the ring x flash composition this path
    carries the math; the kernels themselves are interpreter-tested
    outside shard_map (tests/test_flash_attention.py), and on TPU the
    real kernels run everywhere, shard_map included.
    """
    if k.shape[2] != q.shape[2]:  # GQA: broadcast kv heads
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    keep = _with_mask(mask, q, k).astype(jnp.float32)[:, None, None, :]
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        dist = jnp.arange(lq)[:, None] - jnp.arange(lk)[None, :]
        tri = dist >= 0
        if window is not None:
            tri = tri & (dist < window)
        keep = keep * tri[None, None]
    s = s + (1.0 - keep) * _NEG
    m = jnp.max(s, axis=-1)                      # [B,H,Lq]
    p = jnp.exp(s - m[..., None]) * keep
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    out = (o / denom).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


def _vma_of(x):
    """The varying-manual-axes of ``x``'s aval (empty outside a
    vma-checked ``shard_map``)."""
    return jax.typeof(x).vma


def _inside_vma_shard_map(x):
    """True when tracing inside a vma-checked shard_map (the aval
    carries varying-manual-axes) — static at trace time."""
    return bool(_vma_of(x))


def _out_struct(shape, dtype, like):
    # Inside shard_map, pallas_call outputs must declare which mesh
    # axes they vary over (vma); mirror the query operand's type so
    # the kernels compose with the ring/sequence-parallel paths.
    vma = _vma_of(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# -- one-tile sequences (module docstring) -------------------------------

# Working set one grid step may claim, and the scoped-VMEM limit handed
# to Mosaic for it (a v5e core has 128 MiB; the default limit of 16 MiB
# is a compiler default, not the chip's size).
_ROW_VMEM_BUDGET = 40 << 20
_ROW_VMEM_LIMIT = 48 << 20


def _row_vmem_bytes(hb, kvb, lq, lk, d, itemsize):
    """VMEM one grid step of the heaviest one-tile kernel (dk/dv)
    needs: its double-buffered blocks (q, do; k, v, dk, dv; LSE, delta,
    mask) plus the float32 score-sized tiles and accumulators that are
    live while one head is worked on."""
    blocks = (
        itemsize * (2 * lq * hb * d + 4 * lk * kvb * d)
        + 4 * (2 * hb * lq + lk)
    )
    return 2 * blocks + 4 * (8 * lq * lk + 2 * lk * d)


def _row_heads(h, kvh, lq, lk, d, itemsize):
    """Query heads per grid step of the one-tile kernels: the largest
    divisor of ``h`` that keeps whole GQA groups together, that Mosaic
    can block (all of H, or a multiple of 8 whose q and kv lane widths
    are multiples of 128), and whose working set fits
    ``_ROW_VMEM_BUDGET``. 0: none does, the streaming kernels run."""
    group = h // kvh
    for hb in range(h, 0, -1):
        if h % hb or hb % group:
            continue
        kvb = hb // group
        if hb != h and (hb % 8 or (hb * d) % 128 or (kvb * d) % 128):
            continue
        if _row_vmem_bytes(hb, kvb, lq, lk, d, itemsize) <= _ROW_VMEM_BUDGET:
            return hb
    return 0


def _one_tile_heads(q, k, block_q, block_k, v=None):
    """``_row_heads`` for these operands when the (fitted) blocks cover
    both sequences whole, else 0. From shapes alone, so the forward,
    the backward and the counter agree. Value heads of another width
    than the query/key heads (latent attention) stream at any length:
    the one-tile kernels slice every operand by one ``d``."""
    _, lq, h, d = q.shape
    _, lk, kvh, _ = k.shape
    if lq != block_q or lk != block_k or (
        v is not None and v.shape[-1] != d
    ):
        return 0
    return _row_heads(h, kvh, lq, lk, d, q.dtype.itemsize)


def _head(ref, i, d):
    """Head ``i``'s ``[L, D]`` window of a ``(1, L, heads*D)`` block."""
    return ref[0, :, i * d:(i + 1) * d]


def _nt(a, b):
    """``a @ b.T`` with float32 accumulation."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _lanes(col, eye):
    """A ``[n, 1]`` column laid along lanes as ``[1, n]``: picked off
    the diagonal and summed over sublanes (exact: one term a lane), so
    HBM holds n numbers a head and not n padded tiles."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _fwd_rows_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                     scale, causal, window, hb, group, d):
    lq, lk = q_ref.shape[1], k_ref.shape[1]
    # The mask is the batch row's: made once, shared by every head.
    keep = _keep_tile(mask_ref, causal, 0, 0, lq, lk, (lq, lk), window)
    bias = (1.0 - keep) * _NEG
    eye = _eye(lq)
    outs = []
    for i in range(hb):
        q, k, v = _head(q_ref, i, d), _head(k_ref, i // group, d), _head(
            v_ref, i // group, d)
        s = _nt(q, k) * scale + bias                   # [lq, lk]
        m = jnp.max(s, axis=-1, keepdims=True)
        # exp(NEG - NEG) == 1 on rows with no valid key; * keep zeroes
        # them so fully-masked rows come out 0, not NaN.
        p = jnp.exp(s - m) * keep
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        outs.append((o * (1.0 / l)).astype(o_ref.dtype))
        lse_ref[0, i:i + 1, :] = _lanes(m + jnp.log(l), eye)
    # One full-width store: a head's D lanes written alone start
    # mid-tile for every other head at D = 64 (0.42 against 0.33 ms a
    # call at the benchmark cell's shapes on a v5e, PR 28).
    o_ref[0] = jnp.concatenate(outs, axis=-1)


def _keep_tile_t(mask_ref, causal, lq, lk, window):
    """``_keep_tile`` of the one tile, transposed: ``[lk, lq]`` (or a
    ``[lk, 1]`` column when nothing depends on the query)."""
    row = mask_ref[0, 0][None, :].astype(jnp.float32)        # [1, lk]
    keep = jnp.sum(jnp.where(_eye(lk), row, 0.0), axis=1, keepdims=True)
    if causal:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (lk, lq), 0)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (lk, lq), 1)
        keep = keep * (q_pos >= k_pos)
        if window is not None:
            keep = keep * (q_pos - k_pos < window)
    return keep


def _p_ds_t(q, k, v, do, lse, delta, keep, bias, scale):
    """Recompute one head's probabilities and score gradients from the
    saved statistics, TRANSPOSED (``[lk, lq]``): the statistics are
    ``[1, lq]`` rows along lanes, as HBM holds them, and dv/dk come out
    of plain products. Masked lanes give exp(NEG - lse), large but
    finite (lse >= NEG + log(eps)); * keep zeroes them."""
    p = jnp.exp(_nt(k, q) * scale + bias - lse) * keep
    ds = p * (_nt(v, do) - delta) * scale
    return p, ds


def _bwd_dq_rows_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, o_ref,
                        lse_ref, g_lse_ref, dq_ref, delta_ref, *, scale,
                        causal, window, hb, group, d):
    """dq, and delta_i = Σ_d dO_i · O_i for the dk/dv kernel to read:
    made here from the tiles already in VMEM, so XLA re-lays no
    float32 ``[B, L, H*D]`` product for a reduction over D. A cotangent
    on the LSE folds in exactly: ∂lse_i/∂s_ij = p_ij, so
    ds_ij = p_ij·(dp_ij - (delta_i - g_lse_i))·scale."""
    lq, lk = q_ref.shape[1], k_ref.shape[1]
    keep = _keep_tile_t(mask_ref, causal, lq, lk, window)
    bias = (1.0 - keep) * _NEG
    eye = _eye(lq)
    for i in range(hb):
        k, do = _head(k_ref, i // group, d), _head(do_ref, i, d)
        delta = _lanes(
            jnp.sum(
                do.astype(jnp.float32)
                * _head(o_ref, i, d).astype(jnp.float32),
                axis=-1, keepdims=True,
            ),
            eye,
        ) - g_lse_ref[0, i:i + 1, :]
        delta_ref[0, i:i + 1, :] = delta
        _, ds = _p_ds_t(
            _head(q_ref, i, d), k, _head(v_ref, i // group, d), do,
            lse_ref[0, i:i + 1, :], delta, keep, bias, scale,
        )
        # dq = ds . k, contracting the k dim of the transposed tile.
        dq_ref[0, :, i * d:(i + 1) * d] = jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)


def _bwd_dkv_rows_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, *, scale, causal,
                         window, hb, group, d):
    """dk/dv for the ``hb // group`` kv heads of the step: each sums
    over its ``group`` query heads in registers (GQA-native, no
    repeated K/V)."""
    lq, lk = q_ref.shape[1], k_ref.shape[1]
    keep = _keep_tile_t(mask_ref, causal, lq, lk, window)
    bias = (1.0 - keep) * _NEG
    for j in range(hb // group):
        k, v = _head(k_ref, j, d), _head(v_ref, j, d)
        dk = jnp.zeros((lk, d), jnp.float32)
        dv = jnp.zeros((lk, d), jnp.float32)
        for i in range(j * group, (j + 1) * group):
            q, do = _head(q_ref, i, d), _head(do_ref, i, d)
            p, ds = _p_ds_t(
                q, k, v, do, lse_ref[0, i:i + 1, :],
                delta_ref[0, i:i + 1, :], keep, bias, scale,
            )
            dv = dv + jnp.dot(p.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
            dk = dk + jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
        dk_ref[0, :, j * d:(j + 1) * d] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, j * d:(j + 1) * d] = dv.astype(dv_ref.dtype)


def _row_specs(q, k, hb):
    """Block specs of the one-tile kernels over grid ``(B, H/hb)``: q
    and kv lane slabs (``hb`` query heads and their kv heads), the
    mask row, the statistics rows."""
    _, lq, h, d = q.shape
    _, lk, kvh, _ = k.shape
    kvb = hb * kvh // h
    return (
        pl.BlockSpec((1, lq, hb * d), lambda bi, hi: (bi, 0, hi)),
        pl.BlockSpec((1, lk, kvb * d), lambda bi, hi: (bi, 0, hi)),
        pl.BlockSpec((1, 1, lk), lambda bi, hi: (bi, 0, 0)),
        pl.BlockSpec((1, hb, lq), lambda bi, hi: (bi, hi, 0)),
    )


def _row_call(kernel, hb, q, k, interpret, out_shape, out_specs, in_specs,
              **static):
    """The ``pallas_call`` of one one-tile kernel: grid ``(B, H/hb)``,
    ``hb`` query heads (and their kv heads) a step."""
    b, _, h, d = q.shape
    return pl.pallas_call(
        functools.partial(
            kernel, hb=hb, group=h // k.shape[2], d=d, **static
        ),
        grid=(b, h // hb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ROW_VMEM_LIMIT
        ),
        interpret=interpret,
    )


def _fwd_rows(q, k, v, mask, causal, scale, interpret, window, hb):
    b, lq, h, _ = q.shape
    q_spec, kv_spec, mask_spec, stat_spec = _row_specs(q, k, hb)
    q3, k3, v3 = (x.reshape(*x.shape[:2], -1) for x in (q, k, v))
    out, lse = _row_call(
        _fwd_rows_kernel, hb, q, k, interpret,
        out_shape=[
            _out_struct(q3.shape, q.dtype, q),
            _out_struct((b, h, lq), jnp.float32, q),
        ],
        out_specs=[q_spec, stat_spec],
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        scale=scale, causal=causal, window=window,
    )(q3, k3, v3, mask.astype(jnp.float32)[:, None, :])
    return out.reshape(q.shape), lse


def _bwd_rows(q, k, v, mask, out, lse, g, g_lse, causal, scale, interpret,
              window, hb):
    b, lq, h, _ = q.shape
    q_spec, kv_spec, mask_spec, stat_spec = _row_specs(q, k, hb)
    q3, k3, v3, g3, o3 = (
        x.reshape(*x.shape[:2], -1) for x in (q, k, v, g, out)
    )
    mask3 = mask.astype(jnp.float32)[:, None, :]
    stat = _out_struct((b, h, lq), jnp.float32, q)
    static = dict(scale=scale, causal=causal, window=window)
    dq, delta = _row_call(
        _bwd_dq_rows_kernel, hb, q, k, interpret,
        out_shape=[_out_struct(q3.shape, q.dtype, q), stat],
        out_specs=[q_spec, stat_spec],
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec, q_spec, q_spec,
                  stat_spec, stat_spec],
        **static,
    )(q3, k3, v3, mask3, g3, o3, lse, g_lse.astype(jnp.float32))
    dk, dv = _row_call(
        _bwd_dkv_rows_kernel, hb, q, k, interpret,
        out_shape=[
            _out_struct(k3.shape, k.dtype, q),
            _out_struct(v3.shape, v.dtype, q),
        ],
        out_specs=[kv_spec, kv_spec],
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec, q_spec, stat_spec,
                  stat_spec],
        **static,
    )(q3, k3, v3, mask3, g3, lse, delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _with_mask(mask, q, k):
    """The key mask the one-tile kernels and ``_jnp_flash`` read: the
    caller's, or all ones where it gave none."""
    if mask is None:
        return jnp.ones((q.shape[0], k.shape[1]), jnp.float32)
    return mask


def _stream_call(kernel, name, tables, grid, in_specs, out_specs, out_shape,
                 scratch_shapes, interpret, **static):
    """The ``pallas_call`` of one streaming kernel, as a function of the
    kernel's own operands: its grid's last dimension walks ``tables``
    (``_walk``), which ride ahead of them as scalar prefetch. ``name``
    is what the chip's trace calls the kernel (``flash_attention_fwd``,
    ``_dq``, ``_dkv``: one pass each, all under the ``flash_attention``
    prefix)."""
    call = pl.pallas_call(
        functools.partial(kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )
    return lambda *operands: call(*tables, *operands)


def _fwd(q, k, v, mask, causal, scale, block_q, block_k, interpret,
         window=None):
    hb = _one_tile_heads(q, k, block_q, block_k, v)
    if hb:
        return _fwd_rows(q, k, v, _with_mask(mask, q, k), causal, scale,
                         interpret, window, hb)
    b, lq, h, d = q.shape
    lk, dv = k.shape[1], v.shape[-1]
    # GQA: k/v may carry fewer heads than q (validated in _prepare);
    # the kv BlockSpec indexes `hi // group`, so each query head
    # streams its group's K/V block straight from HBM — no repeated
    # K/V tensor is ever materialised.
    group = h // k.shape[2]
    tiles = _tile_schedule(lq, lk, block_q, block_k, causal, window)
    # [B, L, H, D] -> [B, H, L, D]: heads become a grid dimension.
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

    # Index maps take the grid indices, then the four tables.
    def q_map(bi, hi, n, qi, ki, g, f):
        return (bi, hi, qi[n], 0)

    def kv_map(bi, hi, n, qi, ki, g, f):
        return (bi, hi // group, ki[n], 0)

    def mask_map(bi, hi, n, qi, ki, g, f):
        return (bi, 0, ki[n])

    # [B, 1, L]: TPU lowering wants the last two block dims tile-
    # aligned or equal to the array dims; a (1, 1, block_k) block
    # satisfies that where a (1, block_k) block over [B, L] cannot
    # when B > 1.
    masks = () if mask is None else (mask[:, None, :],)
    # LSE rides as [B, H, L, 1]: Mosaic requires the last two block
    # dims tile-aligned (8, 128) or equal to the array dims; a
    # (1, 1, block_q) block over [B, H, L] fails that for H > 1,
    # while (1, 1, block_q, 1) passes (block_q % 8 == 0, trailing
    # 1 == array dim) and keeps the row state sublane-aligned.
    # Scores run over the query/key width ``d``, values and the output
    # over ``dv`` (the same unless the value heads are narrower).
    out, lse = _stream_call(
        _fwd_kernel, "flash_attention_fwd", _walk(tiles, 0),
        (b, h, len(tiles)),
        [pl.BlockSpec((1, 1, block_q, d), q_map),
         pl.BlockSpec((1, 1, block_k, d), kv_map),
         pl.BlockSpec((1, 1, block_k, dv), kv_map)]
        + [pl.BlockSpec((1, 1, block_k), mask_map)] * len(masks),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv), q_map),
            pl.BlockSpec((1, 1, block_q, 1), q_map),
        ],
        out_shape=[
            _out_struct((b, h, lq, dv), q.dtype, q),
            _out_struct((b, h, lq, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, dv), jnp.float32),      # output acc
        ],
        interpret=interpret, scale=scale, block_q=block_q,
        block_k=block_k, window=window, key_mask=mask is not None,
        kinds=_tile_kinds(tiles),
    )(qt, kt, vt, *masks)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def _recompute_ds(q, k, v, do, lse, delta, keep, scale):
    """One tile's probabilities and score gradients, recomputed from
    the saved LSE. All matmuls take native-dtype (bf16) operands with
    f32 accumulation — the MXU recipe; f32 lives only in the
    softmax-recompute elementwise math. Masked lanes give exp(NEG -
    lse), large but finite (lse >= NEG + log(eps)); * keep zeroes
    them, so no NaN even for fully-masked rows."""
    s = (
        jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [block_q, block_k]
    if keep is not None:
        s = s + (1.0 - keep) * _NEG
    p = jnp.exp(s - lse)
    if keep is not None:
        p = p * keep
    dp = jax.lax.dot_general(
        do, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_q, block_k]
    return p, p * (dp - delta) * scale


def _bwd_dq_kernel(
    qt, kt, gt, ft, q_ref, k_ref, v_ref, *refs, scale, block_q, block_k,
    window, key_mask, kinds,
):
    del gt
    mask_ref, (do_ref, lse_ref, delta_ref, dq_ref, dq_s) = _split_mask(
        refs, key_mask)
    n = pl.program_id(2)
    flags = ft[n]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    def _step(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        keep = _keep_tile(mask_ref, masked, qt[n], kt[n], block_q, block_k,
                          (block_q, block_k), window)
        # lse, delta: [block_q, 1] columns.
        _, ds = _recompute_ds(q, k, v_ref[0, 0], do_ref[0, 0],
                              lse_ref[0, 0], delta_ref[0, 0], keep, scale)
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _by_kind(flags, kinds, _step)

    @pl.when((flags & _LAST) != 0)
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    qt, kt, gt, ft, q_ref, k_ref, v_ref, *refs, scale, block_q, block_k,
    window, key_mask, kinds,
):
    """dk/dv for ONE kv head: the grid is (B, KVH, group × live tiles)
    and the k-major walk brings all of a kv head's query heads and
    live q-tiles for one k-tile consecutively into its dk/dv block (the
    revisit pattern Pallas requires), which is what makes the backward
    GQA-native with no repeated K/V tensor."""
    del gt
    mask_ref, (do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s,
               dv_s) = _split_mask(refs, key_mask)
    n = pl.program_id(2)
    flags = ft[n]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _step(masked):
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        keep = _keep_tile(mask_ref, masked, qt[n], kt[n], block_q, block_k,
                          (block_q, block_k), window)
        p, ds = _recompute_ds(q, k_ref[0, 0], v_ref[0, 0], do,
                              lse_ref[0, 0], delta_ref[0, 0], keep, scale)
        # dv += pᵀ · dO ; dk += dsᵀ · q — contractions over the q dim,
        # no explicit transpose materialised.
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _by_kind(flags, kinds, _step)

    @pl.when((flags & _LAST) != 0)
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(q, k, v, mask, out, lse, g, causal, scale, block_q, block_k,
         interpret, g_lse=None, window=None):
    hb = _one_tile_heads(q, k, block_q, block_k, v)
    if hb:
        return _bwd_rows(
            q, k, v, _with_mask(mask, q, k), out, lse, g, g_lse, causal,
            scale, interpret, window, hb,
        )
    b, lq, h, d = q.shape
    lk, dv = k.shape[1], v.shape[-1]
    kvh = k.shape[2]
    group = h // kvh
    qt, ot, gt = (x.transpose(0, 2, 1, 3) for x in (q, out, g))
    kt, vt = (x.transpose(0, 2, 1, 3) for x in (k, v))  # [B, KVH, L, D]
    # delta_i = Σ_d dO_i · O_i — one cheap fused elementwise+reduce in
    # XLA; saves the backward kernels a dot each per tile. A cotangent
    # on the LSE output folds in here exactly: ∂lse_i/∂s_ij = p_ij, so
    # ds_ij = p_ij·(dp_ij - (delta_i - g_lse_i))·scale — the kernels
    # need no change.
    delta = jnp.sum(
        gt.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1
    )  # [B, H, L]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    # Row vectors ride as [B, H, L, 1] (same Mosaic tiling reason as
    # the forward's LSE output — see the comment in _fwd).
    rows = (lse[..., None], delta[..., None])
    masks = () if mask is None else (mask[:, None, :],)
    tiles = _tile_schedule(lq, lk, block_q, block_k, causal, window)
    static = dict(
        interpret=interpret, scale=scale, block_q=block_q, block_k=block_k,
        window=window, key_mask=mask is not None, kinds=_tile_kinds(tiles),
    )

    # -- dq: the forward's q-major walk -------------------------------
    def q_map(bi, hi, n, qi, ki, g, f):
        return (bi, hi, qi[n], 0)

    def kv_map(bi, hi, n, qi, ki, g, f):
        return (bi, hi // group, ki[n], 0)

    def mask_map(bi, hi, n, qi, ki, g, f):
        return (bi, 0, ki[n])

    # q, k and dq are ``d`` wide; v, dO and dv ``dv`` wide.
    q_spec = pl.BlockSpec((1, 1, block_q, d), q_map)
    row_spec = pl.BlockSpec((1, 1, block_q, 1), q_map)
    dq = _stream_call(
        _bwd_dq_kernel, "flash_attention_dq", _walk(tiles, 0),
        (b, h, len(tiles)),
        [q_spec, pl.BlockSpec((1, 1, block_k, d), kv_map),
         pl.BlockSpec((1, 1, block_k, dv), kv_map)]
        + [pl.BlockSpec((1, 1, block_k), mask_map)] * len(masks)
        + [pl.BlockSpec((1, 1, block_q, dv), q_map), row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_out_struct(qt.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        **static,
    )(qt, kt, vt, *masks, gt, *rows)

    # -- dk/dv: the k-major walk over each kv head's group ------------
    def hq_map(bi, kvi, n, qi, ki, g, f):
        return (bi, kvi * group + g[n], qi[n], 0)

    def k_map(bi, kvi, n, qi, ki, g, f):
        return (bi, kvi, ki[n], 0)

    k_spec, v_spec = (pl.BlockSpec((1, 1, block_k, w), k_map) for w in (d, dv))
    row_spec_t = pl.BlockSpec((1, 1, block_q, 1), hq_map)
    tables = _walk(tiles, group)
    dk, dv = _stream_call(
        _bwd_dkv_kernel, "flash_attention_dkv", tables,
        (b, kvh, len(tables[0])),
        [pl.BlockSpec((1, 1, block_q, d), hq_map), k_spec, v_spec]
        + [pl.BlockSpec((1, 1, block_k), mask_map)] * len(masks)
        + [pl.BlockSpec((1, 1, block_q, dv), hq_map), row_spec_t,
           row_spec_t],
        out_specs=[k_spec, v_spec],
        out_shape=[
            _out_struct(kt.shape, k.dtype, q),
            _out_struct(vt.shape, v.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        **static,
    )(qt, kt, vt, *masks, gt, *rows)

    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, mask, causal, scale, block_q, block_k, interpret,
           window=None):
    """(out, lse) with a joint VJP — lse cotangents cost nothing extra
    (they fold into the delta term, see ``_bwd``), which is what lets
    ring attention compose flash blocks and still train through the
    log-sum-exp merge."""
    return _fwd(
        q, k, v, mask, causal, scale, block_q, block_k, interpret, window
    )


# What a recomputing caller should keep of a differentiated call: the
# forward's two results as the rule returns them (``jax.checkpoint``
# with ``save_only_these_names(*REMAT_NAMES)`` then runs the forward
# kernel once a step). Under no checkpoint, or a bare one, the names
# are identities that lower to nothing.
REMAT_NAMES = ("flash.out", "flash.lse")


def _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k, interpret,
               window=None):
    out, lse = map(checkpoint_name, _fwd(
        q, k, v, mask, causal, scale, block_q, block_k, interpret, window
    ), REMAT_NAMES)
    return (out, lse), (q, k, v, mask, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, mask, out, lse = res
    g_o, g_lse = g
    # GQA is native in BOTH backward kernels now: the dkv grid runs
    # per kv head with its whole group accumulating consecutively, so
    # no repeated K/V tensor exists in the backward either.
    dq, dk, dv = _bwd(
        q, k, v, mask, out, lse, g_o, causal, scale, block_q, block_k,
        interpret, g_lse=g_lse, window=window,
    )
    return dq, dk, dv, None if mask is None else jnp.zeros_like(mask)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _counted_flash(q, k, v, mask, causal, scale, block_q, block_k,
                   interpret, window):
    """``_flash`` behind the two entry points, counted where the
    blocking is chosen: once a TRACE in ``utils.metrics.REGISTRY``
    (``flash.calls_traced``; ``flash.calls_row_blocked`` when the
    one-tile kernels take the call; ``flash.calls_windowed`` when it
    carries a window; ``flash.calls_gqa`` when K/V heads are fewer than
    query heads). Nothing is counted per step."""
    REGISTRY.counter("flash.calls_traced").inc()
    if _one_tile_heads(q, k, block_q, block_k, v):
        REGISTRY.counter("flash.calls_row_blocked").inc()
    if window is not None:
        REGISTRY.counter("flash.calls_windowed").inc()
    if k.shape[2] != q.shape[2]:
        REGISTRY.counter("flash.calls_gqa").inc()
    return _flash(
        q, k, v, mask, causal, scale, block_q, block_k, interpret, window,
    )


def _fit_block(requested: int, length: int) -> int:
    b = min(requested, length)
    while length % b:
        b //= 2  # terminates: 1 divides everything
    return b


def _prepare(q, k, v, mask, causal, scale, block_q, block_k,
             window=None):
    """Shared wrapper preamble: validation, scale default, block
    clamping, the key mask as float32 (None stays None: the streaming
    kernels then carry no mask operand). Returns (mask, scale, block_q,
    block_k)."""
    _, lq, h, d = q.shape
    lk = k.shape[1]
    if causal and lq != lk:
        raise ValueError(
            f"causal attention needs aligned q/k lengths, got {lq} vs {lk}"
        )
    if window is not None and (not causal or window < 1):
        raise ValueError(
            "window requires causal=True and window >= 1 "
            f"(got causal={causal}, window={window})"
        )
    if k.shape[2] != v.shape[2]:
        raise ValueError(
            f"k and v head counts disagree: {k.shape[2]} vs {v.shape[2]}"
        )
    if k.shape[3] != d:
        raise ValueError(
            f"q and k head widths disagree: {d} vs {k.shape[3]} (only "
            "the value heads may have a width of their own)"
        )
    if h % k.shape[2]:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads "
            f"({k.shape[2]}) for grouped-query attention"
        )
    scale = (1.0 / d**0.5) if scale is None else scale
    # Fit each block to its sequence: clamp, then halve until it
    # divides (512 → 256 → …) so any L a smaller power-of-two block
    # handles keeps working when the default grows (L=768 runs at 256,
    # not a ValueError). Explicitly-passed non-divisible blocks also
    # degrade to the nearest dividing halving rather than erroring.
    block_q = _fit_block(block_q, lq)
    block_k = _fit_block(block_k, lk)
    if mask is not None:
        mask = mask.astype(jnp.float32)
    return mask, scale, block_q, block_k


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret", "window"
    ),
)
def flash_attention(
    q,
    k,
    v,
    mask=None,
    *,
    causal: bool = False,
    scale=None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    window: int | None = None,
):
    """Fused softmax attention. ``q, k, v``: ``[B, L, H, D]``;
    ``mask``: optional binary ``[B, L]`` over keys. Returns
    ``[B, L, H, D]`` in ``q.dtype``. ``v`` may have a head width of
    its own (latent attention: scores over 192, values over 128); the
    output then has ``v``'s, and the call streams at any length.

    Differentiable end to end in Pallas: the forward saves the per-row
    log-sum-exp and the backward recomputes probability tiles from it
    (dq, then dk/dv) — no ``[L, L]`` tensor in HBM in either pass. A
    sequence that is one tile takes the row-blocked kernels, a longer
    one streams K/V in ``block_k`` tiles with the online-softmax
    recurrence (module docstring).
    ``interpret=True`` runs the Pallas interpreter (CPU testing).

    Int8-KV policy (the three-way split, see
    ``ops/quant.maybe_dequant_kv``): quantized ``{"q", "scale"}`` K/V
    operands dequantize AT THIS BOUNDARY (one fused convert+multiply
    feeding the kernel's first tile load): a full-sequence call reads
    K/V once for L queries' worth of work (at short L it is bound by
    bytes all the same — 12.5% of that roofline before PR 28 — but by
    q, k, v, o and the cotangents alike, so the K/V byte format is
    not the lever here). The DECODE read, which re-reads the whole
    cache for one token a step, runs as its own kernel
    (``ops/pallas/decode_attention``) that DMAs int8 payload+scale
    tiles to VMEM and dequantizes per tile in registers; the einsum
    decode path dequantizes at the read seam (``kv_cache_kv``).
    """
    from mlapi_tpu.ops.quant import maybe_dequant_kv

    k = maybe_dequant_kv(k, q.dtype)
    v = maybe_dequant_kv(v, q.dtype)
    mask, scale, block_q, block_k = _prepare(
        q, k, v, mask, causal, scale, block_q, block_k, window
    )
    if interpret and _inside_vma_shard_map(q):
        out, _ = _jnp_flash(q, k, v, mask, causal, scale, window)
        return out
    out, _ = _counted_flash(
        q, k, v, mask, causal, scale, block_q, block_k, interpret, window
    )
    return out


def flash_attention_on_mesh(mesh, q, k, v, mask=None, **kwargs):
    """:func:`flash_attention` for operands that live on ``mesh``
    (``None``: the plain call). A compiled Mosaic kernel is opaque to
    GSPMD — the TPU compiler refuses a sharded operand outright
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map") — so each device runs the kernel on its
    own batch rows (the ``data``/``fsdp`` axes) and its own heads (the
    ``model`` axis). Attention is independent across both, so the
    shards concatenate exactly. A dimension its axes do not divide (an
    eval batch of odd size, 12 heads over 8) stays whole on every
    device."""
    if mesh is None:
        return flash_attention(q, k, v, mask, **kwargs)
    from mlapi_tpu.ops.quant import maybe_dequant_kv
    from mlapi_tpu.parallel.mesh import batch_shard_axes, fit_spec

    P = jax.sharding.PartitionSpec
    k = maybe_dequant_kv(k, q.dtype)
    v = maybe_dequant_kv(v, q.dtype)
    rows = tuple(a for a in batch_shard_axes(mesh) if a in mesh.axis_names)
    heads = "model" if "model" in mesh.axis_names else None
    want = P(rows or None, None, heads, None)
    q_spec, kv_spec = fit_spec(q.shape, want, mesh), fit_spec(k.shape, want, mesh)
    if q_spec[2] != kv_spec[2]:  # GQA: the axis must divide both
        q_spec, kv_spec = (P(s[0], None, None, None) for s in (q_spec, kv_spec))
    masks = () if mask is None else (mask,)

    def local(q, k, v, *m):
        return flash_attention(q, k, v, *m, **kwargs)

    # check_vma=False for the reason decode_attention's wrapper gives.
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec) + (P(q_spec[0], None),) * len(masks),
        out_specs=q_spec, check_vma=False,
    )(q, k, v, *masks)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret", "window"
    ),
)
def flash_attention_with_lse(
    q,
    k,
    v,
    mask=None,
    *,
    causal: bool = False,
    scale=None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    window: int | None = None,
):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``[B, H, L]`` — the quantity that lets independently
    computed attention blocks be merged exactly (numerically safe
    weighted average). Used by ``ring_attention``'s flash block mode;
    differentiable through BOTH outputs. Same int8-KV policy as
    :func:`flash_attention`: quantized K/V pairs dequantize at entry
    (the in-kernel int8 tile path belongs to the decode kernel,
    ``decode_attention``)."""
    from mlapi_tpu.ops.quant import maybe_dequant_kv

    k = maybe_dequant_kv(k, q.dtype)
    v = maybe_dequant_kv(v, q.dtype)
    mask, scale, block_q, block_k = _prepare(
        q, k, v, mask, causal, scale, block_q, block_k, window
    )
    if interpret and _inside_vma_shard_map(q):
        return _jnp_flash(q, k, v, mask, causal, scale, window)
    return _counted_flash(
        q, k, v, mask, causal, scale, block_q, block_k, interpret, window
    )

"""Fused flash-decode/flash-extend kernels: split-K attention that
reads the KV cache — int8 payload included — in-kernel, for
single-token decode steps AND multi-token extend spans.

Why decode gets its own kernel: the serving hot path is the decode
step, and it is memory-bound, not compute-bound. Every generated
token re-reads every layer's ``[B, L, KVH, D]`` K and V from HBM to
do O(B·H·L·D) FLOPs — an arithmetic intensity of ~1 FLOP/byte, three
orders below the MXU's knee. The only lever is bytes moved, and the
einsum decode path moves the wrong ones: with an int8 cache it
dequantizes at the read seam (``ops/quant.kv_cache_kv``), so the
full-precision cache materializes between the dequant and the einsum
and the int8 format's 2x HBM saving is realized in *storage* only.
This kernel is where the saving reaches the read: int8 payload +
per-token-per-head f32 scale tiles are DMA'd to VMEM and dequantized
per tile in registers — int8 is what crosses HBM on the decode read.

Shape of the computation (one query per row, against a long cache):

- **Split-K over the cache length.** The grid is ``(B, L/block_k)``:
  each program owns one k-tile of one batch row and computes a
  partial ``(acc, m, l)`` triple — un-normalized output, running max,
  running normalizer — for EVERY query head (the whole ``[H, D]``
  query block rides into each program; it is tiny). A second,
  pure-jnp stage merges the per-tile triples with the standard
  log-sum-exp algebra. No ``[B, L]`` probability tensor and no
  full-precision cache ever exist in HBM: HBM sees q, the stored
  cache tiles, the ``[B, L]`` key mask, and ``[B, nk, H, D + 2]``
  f32 partials (acc ``D`` + m + l per head-tile — noise next to one
  cache read).
- **GQA-native.** K/V stay at ``KVH`` heads in their STORED
  ``[B, L, KVH, D]`` layout (no transpose — a transposed copy of the
  cache would cost the very read we are saving); queries are grouped
  in-register, ``group = H // KVH`` consecutive query heads per KV
  head, and each KV head's tile is loaded once for its whole group.
- **Both cache formats through one seam.** ``k``/``v`` operands are
  either plain arrays (bf16/f32 tiles load directly) or the
  ``{"q" int8, "scale" f32}`` pairs of the int8 cache format
  (``ops/quant``), dequantized per tile with exactly
  ``kv_dequantize``'s arithmetic. Same operand convention as
  ``flash_attention``'s quantized K/V — but handled IN-kernel, not at
  the boundary.
- **Masking = ``decode_valid_and_shift`` semantics.** The ``[B, L]``
  binary key mask carries everything the decode layout encodes —
  per-row ``pos``, ``n_pad`` pad holes, shared-prefix regions,
  optional windows — so the kernel needs no position algebra of its
  own. Tiles whose mask is entirely zero (cache slots beyond ``pos``)
  skip their compute under ``pl.when``: a half-full cache does half
  the dot-products, the split-K analog of causal tile skipping.

Dead-tile DMA note: the BlockSpec copy of a skipped tile still
happens (the predicate gates compute, not the pipelined copy), so the
byte win of skipping is bounded; the format win (int8 vs full
precision) applies to every tile.

**Flash-extend (the U-token variant).** Every multi-token attention
span the server runs — chunked prefill blocks, admission
mini-prefills, shared-prefix suffixes, speculative verify blocks —
is the SAME computation with a Q tile of U rows instead of one:
still bandwidth-bound (U is a chunk width or ``k+1``, tiny next to
the cache length), still a read of the whole stored cache per
dispatch. :func:`extend_attention` / :func:`paged_extend_attention`
keep the decode kernels' grid ``(B, L/block_k)`` (paged: ``(B, NP)``
with the same scalar-prefetched table index map), ride a
``[B, U, L]`` key mask — ``extend_positions_and_mask`` already
encodes the causal intra-span structure (query ``u`` sees cache
slots ``<= pos0 + u``), so the kernel again needs no position
algebra — and emit per-tile partials for all ``U x H`` query rows,
merged by the SAME pure-jnp log-sum-exp stage 2. Rows are laid out
``[KVH, U, group]``-flat so each KV head's whole query group is one
contiguous slice per program (one k-tile load serves U·group rows),
and the post-merge transpose back to ``[B, U, H, D]`` touches a tiny
f32 tensor. With this kernel the int8 read saving (and GQA's
KV-width read) covers EVERY token the server processes, not just
decode steps — the einsum extend path materialized a full-precision,
query-head-width cache operand per chunk.

``interpret=True`` runs the Pallas interpreter (CPU CI); who sets it
is ``utils.platform.pallas_interpret``. The COMPILED kernels are
lowered by Mosaic in tier-1 for a described v5e at GPT-2-small
geometry (``tests/test_tpu_compile.py``) and checked against a
float32 oracle on the chip (``tools/chip_kernels.py``). Under a
model-axis mesh a compiled ``pallas_call`` is an opaque custom call
to GSPMD, so the ``*_tp`` wrappers run it under ``shard_map`` on each
shard's local heads — the compiled program holds the kernel per shard
and no all-gather of the head-sharded cache (same test file).

Layouts Mosaic dictated (each was refused in the obvious form): the
key mask rides as one ``(U, block)`` tile per k-tile
(:func:`_tile_mask`); int8 scales ride as ``(block, KVH)`` tiles
(:func:`_squeeze_scale`); a KV head's ``[block, D]`` tile is read
straight off the ref, never sliced out of a loaded
``[block, KVH, D]`` value (:func:`_head_tiles`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Same finite large-negative as the sibling kernels (a kernel may not
# capture traced constants; -inf breaks the masked-row algebra).
_NEG = -1e30


def _head_tiles(k_ref, ks_ref, v_ref, vs_ref, dtype):
    """``j -> (k_j, v_j)``: one KV head's ``[block_k, D]`` tiles out of
    the ``(1, block_k, KVH, D)`` blocks the BlockSpec copies brought
    to VMEM. The int8 path dequantizes HERE, per head, in registers,
    with ``kv_dequantize``'s exact arithmetic (convert to the compute
    dtype, multiply by the per-(token, head) scale) — the
    full-precision tile never exists in HBM. Scales arrive as
    ``(1, block_k, KVH)`` tiles (see :func:`_squeeze_scale`); head
    ``j``'s column broadcasts along the lanes of its ``[block_k, D]``
    payload."""
    # One head at a time straight off the refs: the whole
    # ``[block_k, KVH, D]`` tile as a VALUE costs a padded copy
    # (KVH=12 pads to the 16/32-row sublane tile, D=64 to 128 lanes).
    if ks_ref is None:
        return lambda j: (k_ref[0, :, j, :], v_ref[0, :, j, :])

    def head(j):
        return (
            k_ref[0, :, j, :].astype(dtype)
            * ks_ref[0, :, j:j + 1].astype(dtype),
            v_ref[0, :, j, :].astype(dtype)
            * vs_ref[0, :, j:j + 1].astype(dtype),
        )

    return head


def _squeeze_scale(scale):
    """``f32[..., KVH, 1]`` (the stored int8-cache scale format) ->
    ``[..., KVH]``. A block with a minor dimension of 1 is padded to a
    full 128-lane tile per (token, head): at ``block_k=512`` the four
    double-buffered scale tiles alone asked Mosaic for 16 MB of VMEM
    ("Scoped allocation with size 27.53M and limit 16.00M") and every
    tile DMA moved ~10x the int8 payload it scales. With KVH minor a
    scale tile is ``block_k x 128`` lanes. (The STORED format still
    pads in HBM; changing it is a cache-format change, not a kernel
    repair.)"""
    return scale[..., 0]


def _decode_kernel(
    q_ref, *refs, scale, kv_heads, group, quantized,
):
    """One (batch row, k-tile) program: partial ``(acc, m, l)`` for
    all H = kv_heads * group query heads against this tile.

    ``refs`` is the remaining (inputs..., outputs...) ref list; the
    scale refs exist only in the quantized signature — the bf16/f32
    path carries no scale operands at all (a dead operand would still
    be DMA'd per tile, taxing the exact bandwidth-bound read this
    kernel optimizes)."""
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, mask_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, mask_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    keep = mask_ref[0, 0, 0]  # [block_k]
    # Split-K tile skipping: a tile with no valid key (every slot
    # beyond pos, or inside a pad hole spanning the tile) contributes
    # the identity triple; the dots are skipped.
    live = jnp.any(keep > 0)

    @pl.when(jnp.logical_not(live))
    def _dead():
        acc_ref[0, 0] = jnp.zeros_like(acc_ref[0, 0])
        m_ref[0, 0] = jnp.full_like(m_ref[0, 0], _NEG)
        l_ref[0, 0] = jnp.zeros_like(l_ref[0, 0])

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]  # [H, D]
        head = _head_tiles(k_ref, ks_ref, v_ref, vs_ref, q.dtype)
        nkeep = (1.0 - keep) * _NEG  # [block_k]

        # Per-KV-head 2D dots (kv_heads/group are static: the loop
        # unrolls at trace time). Grouped queries: KV head j serves
        # query heads [j*group, (j+1)*group) — jnp.repeat's layout,
        # shared with every attention impl in ops/.
        for j in range(kv_heads):
            rows = slice(j * group, (j + 1) * group)
            kj, vj = head(j)  # [block_k, D] each
            s = (
                jax.lax.dot_general(
                    q[rows], kj,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [group, block_k]
            s = s + nkeep[None, :]
            m = jnp.max(s, axis=-1, keepdims=True)  # [group, 1]
            # exp(NEG - NEG) == 1 on lanes with no valid key; * keep
            # zeroes them (no NaN for fully-masked rows).
            p = jnp.exp(s - m) * keep[None, :]
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = jax.lax.dot_general(
                p.astype(vj.dtype), vj,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, D]
            acc_ref[0, 0, rows, :] = acc
            m_ref[0, 0, rows, :] = m
            l_ref[0, 0, rows, :] = l


def _extend_kernel(
    q_ref, *refs, scale, kv_heads, group, u, quantized,
):
    """One (batch row, k-tile) program of the U-token extend grid:
    partial ``(acc, m, l)`` for ALL ``U x H`` query rows against this
    tile. The decode kernel's body with a Q tile of U rows: the
    per-KV-head loop is unchanged, each KV head's tile is loaded once
    and serves its whole query group across all U span positions
    (``U * group`` rows per 2D dot — still one small matmul against
    one streamed tile). Rows land ``[KVH, U, group]``-flat in the
    partials so each head's slice is contiguous; the caller transposes
    back after the merge. ``mask_ref`` carries a PER-QUERY-ROW
    ``[U, block_k]`` mask — the causal intra-span structure (span
    position ``u`` attends cache slots ``<= pos0 + u``) arrives
    encoded in it, exactly as pads/prefixes/windows do."""
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, mask_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, mask_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    keep = mask_ref[0, 0]  # [U, block_k]
    # A tile dead for EVERY span position skips its dots (leading
    # tiles of a mostly-empty cache, pad holes spanning the tile).
    live = jnp.any(keep > 0)

    @pl.when(jnp.logical_not(live))
    def _dead():
        acc_ref[0, 0] = jnp.zeros_like(acc_ref[0, 0])
        m_ref[0, 0] = jnp.full_like(m_ref[0, 0], _NEG)
        l_ref[0, 0] = jnp.zeros_like(l_ref[0, 0])

    @pl.when(live)
    def _step():
        q = q_ref[0]  # [U, H, D]
        head = _head_tiles(k_ref, ks_ref, v_ref, vs_ref, q.dtype)
        # Per-row mask penalties, repeated group-wise to match the
        # u-major [U * group] row layout of each KV head's dot.
        nkeep = jnp.repeat((1.0 - keep) * _NEG, group, axis=0)
        keep_g = jnp.repeat(keep, group, axis=0)  # [U*group, block_k]

        for j in range(kv_heads):
            qj = q[:, j * group:(j + 1) * group, :].reshape(
                u * group, -1
            )  # [U*group, D], row = u*group + g
            kj, vj = head(j)  # [block_k, D] each
            s = (
                jax.lax.dot_general(
                    qj, kj,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [U*group, block_k]
            s = s + nkeep
            m = jnp.max(s, axis=-1, keepdims=True)
            # exp(NEG - NEG) == 1 on fully-masked rows; * keep zeroes
            # them (no NaN for span positions with no valid key —
            # all-pad query rows exist in ragged chunks).
            p = jnp.exp(s - m) * keep_g
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = jax.lax.dot_general(
                p.astype(vj.dtype), vj,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [U*group, D]
            rows = slice(j * u * group, (j + 1) * u * group)
            acc_ref[0, 0, rows, :] = acc
            m_ref[0, 0, rows, :] = m
            l_ref[0, 0, rows, :] = l


def _fit_block(requested: int, length: int) -> int:
    """Largest halving of ``requested`` that divides ``length``. Any
    dividing block >= 8 (the f32 sublane) is kept — a small legal
    blocking beats one whole-length tile, which loses the split-K
    grid and can blow VMEM at long L. Only truly awkward lengths
    (odd test-harness totals like ``p + n_steps + 1``, where the
    halvings bottom out at < 8) fall back to a single block equal to
    the array dim (always legal, and those lengths are small).
    Serving cache tiers are ``bucket + 2^k * chunk``, which fit real
    tiles."""
    b = min(requested, length)
    while length % b:
        b //= 2
    if b < 8 and b < length:
        return length
    return b


def _tile_mask(mask, n_tiles: int, block: int):
    """``[B, U, n_tiles * block]`` key mask -> f32 ``[B, n_tiles, U,
    block]``, one k-tile's mask per leading index. Mosaic wants a
    block's last two dims divisible by (8, 128) or EQUAL to the
    array's: tiling the key axis out of the minor dimension makes the
    ``(U, block)`` mask block equal to the array's last two dims at
    any tile width — a 16-token page included — where a ``block``-wide
    window of the ``[.., L]`` row is refused below 128 lanes. The
    transpose moves ``B*U*L`` floats, noise next to the cache read."""
    b, u, _ = mask.shape
    tiled = mask.astype(jnp.float32).reshape(b, u, n_tiles, block)
    return tiled.transpose(0, 2, 1, 3)


def _unpack(x):
    """An operand is a plain ``[B, L, KVH, D]`` array or an int8
    ``{"q", "scale"}`` pair (``ops/quant``'s format, ONE definition —
    the same predicate ``maybe_dequant_kv`` uses). Returns
    ``(payload, scale_or_None)``."""
    from mlapi_tpu.ops.quant import _is_quant_leaf

    if isinstance(x, dict):
        if _is_quant_leaf(x):
            return x["q"], x["scale"]
        raise TypeError(
            "decode_attention takes arrays or {'q', 'scale'} quantized "
            f"pairs, got dict with keys {sorted(x)}"
        )
    return x, None


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def decode_attention(
    q,
    k,
    v,
    mask,
    *,
    scale=None,
    block_k: int = 512,
    interpret: bool = False,
):
    """Single-query flash-decode attention over a stored KV cache.

    ``q``: ``[B, 1, H, D]``; ``k``/``v``: ``[B, L, KVH, D]`` arrays
    (any float dtype) or int8 ``{"q", "scale"}`` pairs
    (``scale f32[B, L, KVH, 1]``); ``mask``: binary ``[B, L]`` over
    keys (build it with ``models.gpt.decode_valid_and_shift`` for the
    serving layout). Returns ``[B, 1, H, D]`` in ``q.dtype``.

    Numerics match the einsum decode oracle (``gpt.cached_attend``) to
    reassociation error: f32 accumulation on every dot, probabilities
    cast to the value dtype for the PV contraction, normalization by
    the merged ``l`` after the split-K reduction.
    """
    kq, ks = _unpack(k)
    vq, vs = _unpack(v)
    quantized = ks is not None
    if quantized != (vs is not None):
        raise ValueError("k and v must share one cache format")
    b, one, h, d = q.shape
    if one != 1:
        # U-token dispatch (r11): block extends no longer fall to the
        # einsum path — they are the same bandwidth-bound read with a
        # taller Q tile. The only thing the kernel genuinely cannot
        # tile is a span whose mask lacks the per-query-row (causal
        # intra-span) structure, so that stays a loud error.
        if mask.ndim != 3 or mask.shape[:2] != (b, one):
            raise ValueError(
                f"multi-token q {q.shape} needs a per-query-row "
                f"[B, U, L] mask (got {mask.shape}): a [B, L] decode "
                "mask cannot express the causal intra-span structure"
            )
        return extend_attention(
            q, k, v, mask, scale=scale, block_k=block_k,
            interpret=interpret,
        )
    lk, kvh = kq.shape[1], kq.shape[2]
    if kq.shape != vq.shape or kq.shape[3] != d:
        raise ValueError(
            f"cache shapes disagree with q: k {kq.shape}, v {vq.shape}, "
            f"q {q.shape}"
        )
    if h % kvh:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({kvh})"
        )
    group = h // kvh
    scale = (1.0 / d**0.5) if scale is None else scale
    bk = _fit_block(block_k, lk)
    nk = lk // bk

    mask4 = _tile_mask(mask[:, None, :], nk, bk)  # [B, nk, 1, bk]

    q_spec = pl.BlockSpec((1, 1, h, d), lambda bi, ki: (bi, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, kvh, d), lambda bi, ki: (bi, ki, 0, 0))
    sc_spec = pl.BlockSpec((1, bk, kvh), lambda bi, ki: (bi, ki, 0))
    mask_spec = pl.BlockSpec((1, 1, 1, bk), lambda bi, ki: (bi, ki, 0, 0))
    part_spec = pl.BlockSpec((1, 1, h, d), lambda bi, ki: (bi, ki, 0, 0))
    row_spec = pl.BlockSpec((1, 1, h, 1), lambda bi, ki: (bi, ki, 0, 0))

    # Scale operands exist ONLY on the quantized path: the kernel
    # signature (and its BlockSpec copies) carries exactly what the
    # cache format stores.
    if quantized:
        operands = (
            q, kq, _squeeze_scale(ks), vq, _squeeze_scale(vs), mask4
        )
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec, mask_spec]
    else:
        operands = (q, kq, vq, mask4)
        in_specs = [q_spec, kv_spec, kv_spec, mask_spec]

    acc, m, l = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, kv_heads=kvh, group=group,
            quantized=quantized,
        ),
        grid=(b, nk),
        in_specs=in_specs,
        out_specs=[part_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, nk, h, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nk, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nk, h, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    return _splitk_merge(acc, m, l, q.dtype)


def _splitk_merge_rows(acc, m, l):
    """Split-K reduction: merge the per-tile (acc, m, l) triples with
    the log-sum-exp algebra, per row. All-dead rows (l == 0
    everywhere) come out exactly zero — a decode step always has
    >= 1 valid key (the token it just wrote); an extend span's
    all-pad query rows come out zero and are never read. Shared
    verbatim by the contiguous and paged kernels AND by the decode
    and extend row layouts: the page table changes WHERE a tile's
    bytes live and the Q-tile height changes how many rows merge —
    never the merge arithmetic."""
    m_max = jnp.max(m, axis=1)                       # [B, R, 1]
    alpha = jnp.exp(m - m_max[:, None])              # [B, nk, R, 1]
    l_tot = jnp.sum(alpha * l, axis=1)               # [B, R, 1]
    acc_tot = jnp.sum(alpha * acc, axis=1)           # [B, R, D]
    return acc_tot / jnp.maximum(l_tot, 1e-30)


def _splitk_merge(acc, m, l, dtype):
    """Decode-layout stage 2: rows ARE the query heads."""
    out = _splitk_merge_rows(acc, m, l)
    return out.astype(dtype)[:, None]                # [B, 1, H, D]


def _splitk_merge_extend(acc, m, l, dtype, u, kvh, group):
    """Extend-layout stage 2: rows are ``[KVH, U, group]``-flat (each
    KV head's query group contiguous per program); un-flatten back to
    the caller's ``[B, U, H, D]`` — a transpose of a tiny f32 tensor,
    noise next to the cache read the kernel just did."""
    out = _splitk_merge_rows(acc, m, l)              # [B, KVH*U*g, D]
    b, _, d = out.shape
    out = out.reshape(b, kvh, u, group, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, u, kvh * group, d).astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def extend_attention(
    q,
    k,
    v,
    mask,
    *,
    scale=None,
    block_k: int = 512,
    interpret: bool = False,
):
    """Flash-extend: U-token-query split-K attention over a stored KV
    cache — the multi-token twin of :func:`decode_attention`.

    ``q``: ``[B, U, H, D]``; ``k``/``v``: ``[B, L, KVH, D]`` arrays
    (any float dtype) or int8 ``{"q", "scale"}`` pairs; ``mask``:
    binary ``[B, U, L]`` over keys PER SPAN POSITION (build it with
    ``models.gpt.extend_positions_and_mask`` — its causal intra-span
    structure is what lets U positions attend correctly inside one
    program). Returns ``[B, U, H, D]`` in ``q.dtype``.

    Same grid, same per-tile int8 in-register dequant, same GQA
    grouping, same log-sum-exp merge as the decode kernel — the Q
    tile just carries U rows, so chunked prefill / admission /
    speculative-verify spans stream the cache at its STORED byte
    format, like decode steps do.
    """
    kq, ks = _unpack(k)
    vq, vs = _unpack(v)
    quantized = ks is not None
    if quantized != (vs is not None):
        raise ValueError("k and v must share one cache format")
    b, u, h, d = q.shape
    lk, kvh = kq.shape[1], kq.shape[2]
    if kq.shape != vq.shape or kq.shape[3] != d:
        raise ValueError(
            f"cache shapes disagree with q: k {kq.shape}, v {vq.shape}, "
            f"q {q.shape}"
        )
    if mask.shape != (b, u, lk):
        raise ValueError(
            f"extend mask {mask.shape} must be [B, U, L] = "
            f"[{b}, {u}, {lk}] (per-span-position key validity)"
        )
    if h % kvh:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({kvh})"
        )
    group = h // kvh
    scale = (1.0 / d**0.5) if scale is None else scale
    bk = _fit_block(block_k, lk)
    nk = lk // bk
    rows = kvh * u * group  # the [KVH, U, group]-flat partial layout

    mask4 = _tile_mask(mask, nk, bk)  # [B, nk, U, bk]

    q_spec = pl.BlockSpec((1, u, h, d), lambda bi, ki: (bi, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, kvh, d), lambda bi, ki: (bi, ki, 0, 0))
    sc_spec = pl.BlockSpec((1, bk, kvh), lambda bi, ki: (bi, ki, 0))
    mask_spec = pl.BlockSpec((1, 1, u, bk), lambda bi, ki: (bi, ki, 0, 0))
    part_spec = pl.BlockSpec((1, 1, rows, d), lambda bi, ki: (bi, ki, 0, 0))
    row_spec = pl.BlockSpec((1, 1, rows, 1), lambda bi, ki: (bi, ki, 0, 0))

    if quantized:
        operands = (
            q, kq, _squeeze_scale(ks), vq, _squeeze_scale(vs), mask4
        )
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec, mask_spec]
    else:
        operands = (q, kq, vq, mask4)
        in_specs = [q_spec, kv_spec, kv_spec, mask_spec]

    acc, m, l = pl.pallas_call(
        functools.partial(
            _extend_kernel, scale=scale, kv_heads=kvh, group=group,
            u=u, quantized=quantized,
        ),
        grid=(b, nk),
        in_specs=in_specs,
        out_specs=[part_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, nk, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nk, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nk, rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    return _splitk_merge_extend(acc, m, l, q.dtype, u, kvh, group)


def _paged_kernel(table_ref, q_ref, *refs, scale, kv_heads, group,
                  quantized):
    """The paged grid's kernel body IS the contiguous kernel body: the
    scalar-prefetched page table is consumed entirely by the BlockSpec
    index maps (it decides which pool page each program's k-tile DMA
    reads); the math never sees it."""
    del table_ref
    _decode_kernel(
        q_ref, *refs, scale=scale, kv_heads=kv_heads, group=group,
        quantized=quantized,
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q,
    k,
    v,
    table,
    mask,
    *,
    scale=None,
    interpret: bool = False,
):
    """Page-table flash-decode: split-K single-query attention whose
    k-tiles are POOL PAGES selected per program by a scalar-prefetched
    page table — the ROADMAP's "a page table is one more BlockSpec
    index map", literally.

    ``q``: ``[B, 1, H, D]``; ``k``/``v``: ``[P, page, KVH, D]`` pool
    arrays (any float dtype) or int8 ``{"q", "scale"}`` pool pairs
    (``scale f32[P, page, KVH, 1]``); ``table``: int32 ``[B, NP]``
    pool-page ids per virtual tile; ``mask``: binary ``[B, NP*page]``
    over VIRTUAL key slots (the same ``decode_valid_and_shift`` mask
    the contiguous kernel takes — paging is invisible to the slot
    algebra). Returns ``[B, 1, H, D]`` in ``q.dtype``.

    The grid is ``(B, NP)`` — one program per (row, virtual tile), the
    tile size pinned to the page size so the BlockSpec copy of tile
    ``ki`` is exactly ``pool[table[b, ki]]``: sequences scattered
    across non-contiguous pages stream through the SAME kernel body as
    the contiguous layout, with the int8 in-register dequantization
    and dead-tile ``pl.when`` skipping intact. Null-page tiles
    (unallocated table entries) DMA the reserved page and are fully
    masked — their programs take the dead-tile branch.
    """
    from jax.experimental.pallas import tpu as pltpu

    kq, ks = _unpack(k)
    vq, vs = _unpack(v)
    quantized = ks is not None
    if quantized != (vs is not None):
        raise ValueError("k and v must share one cache format")
    b, one, h, d = q.shape
    if one != 1:
        # U-token dispatch (r11) — the paged twin of the extend
        # dispatch in :func:`decode_attention`.
        if mask.ndim != 3 or mask.shape[:2] != (b, one):
            raise ValueError(
                f"multi-token q {q.shape} needs a per-query-row "
                f"[B, U, NP*page] mask (got {mask.shape})"
            )
        return paged_extend_attention(
            q, k, v, table, mask, scale=scale, interpret=interpret,
        )
    page, kvh = kq.shape[1], kq.shape[2]
    np_tiles = table.shape[1]
    if kq.shape != vq.shape or kq.shape[3] != d:
        raise ValueError(
            f"pool shapes disagree with q: k {kq.shape}, v {vq.shape}, "
            f"q {q.shape}"
        )
    if mask.shape != (b, np_tiles * page):
        raise ValueError(
            f"mask {mask.shape} must cover the virtual layout "
            f"[{b}, {np_tiles * page}]"
        )
    if h % kvh:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({kvh})"
        )
    group = h // kvh
    scale = (1.0 / d**0.5) if scale is None else scale

    mask4 = _tile_mask(mask[:, None, :], np_tiles, page)  # [B, NP, 1, page]

    q_spec = pl.BlockSpec((1, 1, h, d), lambda bi, ki, t: (bi, 0, 0, 0))
    # THE page-table indirection: tile ki of row bi is pool page
    # t[bi, ki]. Everything else is the contiguous kernel's spec set
    # with the table ref riding as a trailing index-map argument.
    kv_spec = pl.BlockSpec(
        (1, page, kvh, d), lambda bi, ki, t: (t[bi, ki], 0, 0, 0)
    )
    sc_spec = pl.BlockSpec(
        (1, page, kvh), lambda bi, ki, t: (t[bi, ki], 0, 0)
    )
    mask_spec = pl.BlockSpec(
        (1, 1, 1, page), lambda bi, ki, t: (bi, ki, 0, 0)
    )
    part_spec = pl.BlockSpec((1, 1, h, d), lambda bi, ki, t: (bi, ki, 0, 0))
    row_spec = pl.BlockSpec((1, 1, h, 1), lambda bi, ki, t: (bi, ki, 0, 0))

    if quantized:
        operands = (
            kq, _squeeze_scale(ks), vq, _squeeze_scale(vs), mask4
        )
        in_specs = [kv_spec, sc_spec, kv_spec, sc_spec, mask_spec]
    else:
        operands = (kq, vq, mask4)
        in_specs = [kv_spec, kv_spec, mask_spec]

    acc, m, l = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, kv_heads=kvh, group=group,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, np_tiles),
            in_specs=[q_spec, *in_specs],
            out_specs=[part_spec, row_spec, row_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, np_tiles, h, d), jnp.float32),
            jax.ShapeDtypeStruct((b, np_tiles, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, np_tiles, h, 1), jnp.float32),
        ],
        interpret=interpret,
    )(table, q, *operands)

    return _splitk_merge(acc, m, l, q.dtype)


def _paged_extend_kernel(table_ref, q_ref, *refs, scale, kv_heads,
                         group, u, quantized):
    """The paged extend grid's kernel body IS the contiguous extend
    body — the scalar-prefetched table is consumed by the BlockSpec
    index maps, exactly as in the decode pair."""
    del table_ref
    _extend_kernel(
        q_ref, *refs, scale=scale, kv_heads=kv_heads, group=group,
        u=u, quantized=quantized,
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_extend_attention(
    q,
    k,
    v,
    table,
    mask,
    *,
    scale=None,
    interpret: bool = False,
):
    """Page-table flash-extend: U-token split-K attention whose
    k-tiles are POOL PAGES selected per program by the scalar-
    prefetched page table — :func:`paged_decode_attention` with a Q
    tile of U rows. A span may START mid-page and CROSS page
    boundaries freely: the ``[B, U, NP*page]`` virtual-slot mask
    (``extend_positions_and_mask`` over the virtual layout) carries
    all of that, the same way paging is invisible to the decode
    kernel's slot algebra.

    ``q``: ``[B, U, H, D]``; ``k``/``v``: ``[P, page, KVH, D]`` pool
    arrays or int8 ``{"q", "scale"}`` pool pairs; ``table``: int32
    ``[B, NP]``. Returns ``[B, U, H, D]`` in ``q.dtype``.
    """
    from jax.experimental.pallas import tpu as pltpu

    kq, ks = _unpack(k)
    vq, vs = _unpack(v)
    quantized = ks is not None
    if quantized != (vs is not None):
        raise ValueError("k and v must share one cache format")
    b, u, h, d = q.shape
    page, kvh = kq.shape[1], kq.shape[2]
    np_tiles = table.shape[1]
    if kq.shape != vq.shape or kq.shape[3] != d:
        raise ValueError(
            f"pool shapes disagree with q: k {kq.shape}, v {vq.shape}, "
            f"q {q.shape}"
        )
    if mask.shape != (b, u, np_tiles * page):
        raise ValueError(
            f"extend mask {mask.shape} must cover the virtual layout "
            f"[{b}, {u}, {np_tiles * page}]"
        )
    if h % kvh:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({kvh})"
        )
    group = h // kvh
    scale = (1.0 / d**0.5) if scale is None else scale
    rows = kvh * u * group

    mask4 = _tile_mask(mask, np_tiles, page)  # [B, NP, U, page]

    q_spec = pl.BlockSpec((1, u, h, d), lambda bi, ki, t: (bi, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, page, kvh, d), lambda bi, ki, t: (t[bi, ki], 0, 0, 0)
    )
    sc_spec = pl.BlockSpec(
        (1, page, kvh), lambda bi, ki, t: (t[bi, ki], 0, 0)
    )
    mask_spec = pl.BlockSpec(
        (1, 1, u, page), lambda bi, ki, t: (bi, ki, 0, 0)
    )
    part_spec = pl.BlockSpec(
        (1, 1, rows, d), lambda bi, ki, t: (bi, ki, 0, 0)
    )
    row_spec = pl.BlockSpec(
        (1, 1, rows, 1), lambda bi, ki, t: (bi, ki, 0, 0)
    )

    if quantized:
        operands = (
            kq, _squeeze_scale(ks), vq, _squeeze_scale(vs), mask4
        )
        in_specs = [kv_spec, sc_spec, kv_spec, sc_spec, mask_spec]
    else:
        operands = (kq, vq, mask4)
        in_specs = [kv_spec, kv_spec, mask_spec]

    acc, m, l = pl.pallas_call(
        functools.partial(
            _paged_extend_kernel, scale=scale, kv_heads=kvh,
            group=group, u=u, quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, np_tiles),
            in_specs=[q_spec, *in_specs],
            out_specs=[part_spec, row_spec, row_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, np_tiles, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, np_tiles, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, np_tiles, rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(table, q, *operands)

    return _splitk_merge_extend(acc, m, l, q.dtype, u, kvh, group)


def _head_sharded_call(mesh, fn, q, k, v, head_axis_specs, extras):
    """shard_map a decode kernel over the TP ``model`` axis so the
    compiled ``pallas_call`` — an opaque custom call GSPMD cannot see
    into — runs PER SHARD on its local head slice instead of risking
    an all-gather of the head-sharded cache operands around it (the
    ROADMAP open item this wrapper closes). ``head_axis_specs`` maps
    each of (q, k, v) — arrays or {"q","scale"} pairs — to its
    PartitionSpec; ``extras`` are replicated operands (mask, table).

    Every per-KV-head loop iteration in the kernel is independent, so
    sharding heads is exact: each shard computes its own query-head
    group's full softmax (m/l normalizers are per head) and the
    outputs concatenate back over the head axis."""
    q_spec, kv_spec = head_axis_specs
    rep = jax.sharding.PartitionSpec()

    def tree_spec(operand):
        if isinstance(operand, dict):
            return {name: kv_spec for name in operand}
        return kv_spec

    # check_vma=False: the kernels' out_shapes carry no varying-axes
    # type (they are written for the unsharded call), and the Pallas
    # interpreter the CPU tests run cannot trace under the vma checker.
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(q_spec, tree_spec(k), tree_spec(v),
                  *([rep] * len(extras))),
        out_specs=q_spec,
        check_vma=False,
    )
    return mapped(q, k, v, *extras)


def decode_attention_tp(
    mesh, q, k, v, mask, *, scale=None, block_k: int = 512,
    interpret: bool = False, axis: str = "model",
):
    """:func:`decode_attention` under model-axis tensor parallelism:
    q ``[B, 1, H, D]`` and the cache operands ``[B, L, KVH, D]`` are
    head-sharded over ``axis``; the mask is replicated. Requires the
    axis size to divide KVH (the caller falls back to the unwrapped
    kernel otherwise — GSPMD then decides, as before)."""
    P = jax.sharding.PartitionSpec
    return _head_sharded_call(
        mesh,
        lambda q_, k_, v_, m_: decode_attention(
            q_, k_, v_, m_, scale=scale, block_k=block_k,
            interpret=interpret,
        ),
        q, k, v,
        (P(None, None, axis, None), P(None, None, axis, None)),
        (mask,),
    )


def paged_decode_attention_tp(
    mesh, q, k, v, table, mask, *, scale=None, interpret: bool = False,
    axis: str = "model",
):
    """:func:`paged_decode_attention` under model-axis TP: the pools
    ``[P, page, KVH, D]`` shard on their head axis, the page table and
    mask replicate (page ids are head-invariant — every shard walks
    the same table over its own head slice of the pool)."""
    P = jax.sharding.PartitionSpec
    return _head_sharded_call(
        mesh,
        lambda q_, k_, v_, t_, m_: paged_decode_attention(
            q_, k_, v_, t_, m_, scale=scale, interpret=interpret,
        ),
        q, k, v,
        (P(None, None, axis, None), P(None, None, axis, None)),
        (table, mask),
    )


def extend_attention_tp(
    mesh, q, k, v, mask, *, scale=None, block_k: int = 512,
    interpret: bool = False, axis: str = "model",
):
    """:func:`extend_attention` under model-axis TP — the extend leg
    of :func:`_head_sharded_call`. Sharding is identical to the
    decode wrapper's (q ``[B, U, H, D]`` and the cache operands
    head-sharded over ``axis``, the ``[B, U, L]`` mask replicated):
    the Q tile's extra rows change nothing about head independence —
    every shard computes full per-head softmaxes for its own query
    group across all U span positions. This is what lets speculative
    verify and chunked prefill run kernel-native over MESH-SHARDED
    caches (the last paged x spec decline's mesh half)."""
    P = jax.sharding.PartitionSpec
    return _head_sharded_call(
        mesh,
        lambda q_, k_, v_, m_: extend_attention(
            q_, k_, v_, m_, scale=scale, block_k=block_k,
            interpret=interpret,
        ),
        q, k, v,
        (P(None, None, axis, None), P(None, None, axis, None)),
        (mask,),
    )


def paged_extend_attention_tp(
    mesh, q, k, v, table, mask, *, scale=None, interpret: bool = False,
    axis: str = "model",
):
    """:func:`paged_extend_attention` under model-axis TP: pools
    shard on their head axis, the table and the ``[B, U, NP*page]``
    mask replicate — the composition the mesh-sharded-pool
    speculative-verify path dispatches."""
    P = jax.sharding.PartitionSpec
    return _head_sharded_call(
        mesh,
        lambda q_, k_, v_, t_, m_: paged_extend_attention(
            q_, k_, v_, t_, m_, scale=scale, interpret=interpret,
        ),
        q, k, v,
        (P(None, None, axis, None), P(None, None, axis, None)),
        (table, mask),
    )

"""Ring attention — sequence-parallel softmax attention over a mesh axis.

Long context is a first-class capability here even though the
reference has no sequence models at all (SURVEY §2: max "sequence" is
4 tabular features, ``main.py:10-14``): a sequence too long for one
chip's HBM is split into per-device blocks along a ``seq`` mesh axis,
and attention runs blockwise with the K/V blocks rotating around the
ring via ``lax.ppermute`` — ICI-neighbor traffic only, overlapped by
XLA with the per-block matmuls. Softmax is accumulated online
(running max / denominator / numerator, the flash-attention
recurrence), so no device ever materialises an ``[L, L]`` score
matrix: per-device memory is O(L·L/n) score blocks and O(L/n·D)
activations.

Two entry points:

- ``ring_attention``       — the per-device computation, for use
                             inside an existing ``shard_map`` (axis
                             name + size passed in).
- ``ring_self_attention``  — convenience wrapper that shard_maps over
                             a mesh for you, given globally-sharded
                             ``[B, L, H, D]`` arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mlapi_tpu.ops.attention import NEG
from mlapi_tpu.utils.platform import pallas_interpret


def _varying_like(x, like):
    """Cast ``x`` to carry ``like``'s varying-manual-axes (vma) type.

    Constants minted inside shard_map are "unvarying"; mixing them
    with varying values in loop carries / lax.switch branches is a
    type mismatch in jax 0.9's vma checker. ``lax.pcast`` refuses
    axes a value already varies over, so cast only the missing ones.
    """
    want = jax.typeof(like).vma
    have = jax.typeof(x).vma
    missing = tuple(a for a in want if a not in have)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def ring_attention(
    q,
    k,
    v,
    mask=None,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    scale=None,
    block_impl: str = "einsum",
    zigzag: bool = False,
):
    """Blockwise ring attention for ONE device's sequence block.

    Call inside ``shard_map`` over ``axis_name``. ``q, k, v`` are the
    local blocks ``[B, Lb, H, D]`` (global L = Lb * axis_size, blocks
    laid out in ring order), ``mask`` the local binary key mask
    ``[B, Lb]``. Returns the local output block ``[B, Lb, H, D]`` in
    ``q.dtype``.

    ``axis_size`` must be the static size of ``axis_name`` (it sets
    the ring-step count; ``lax.axis_index`` is traced so it cannot).

    ``block_impl`` picks the per-block attention: ``"einsum"`` (XLA,
    the default) or ``"flash"`` — each ring step runs the Pallas
    flash kernel on its local block and the per-block (out, lse)
    pairs are merged exactly (SP × kernel composition). Both are
    differentiable (the flash VJP carries lse cotangents).

    Int8-KV boundary policy: a quantized ``{"q", "scale"}`` K/V
    operand dequantizes HERE, at the ring entry, before the blocks
    start rotating — the ppermute'd K/V blocks and the online-softmax
    state stay full-precision (rotating payload+scale pairs and
    dequantizing per ring step would re-do the multiply axis_size
    times for zero HBM savings: the blocks live on-device either
    way). See ``ops/quant.maybe_dequant_kv`` for the full rationale.
    """
    from mlapi_tpu.ops.quant import maybe_dequant_kv

    k = maybe_dequant_kv(k, q.dtype)
    v = maybe_dequant_kv(v, q.dtype)
    if zigzag:
        if not (causal and block_impl == "flash"):
            raise ValueError(
                "zigzag layout applies to causal flash-block ring "
                "attention (it balances causal work; non-causal work "
                "is already balanced)"
            )
        return _ring_flash_zigzag(
            q, k, v, mask, axis_name=axis_name, axis_size=axis_size,
            scale=scale,
        )
    if block_impl == "flash":
        return _ring_flash(
            q, k, v, mask, axis_name=axis_name, axis_size=axis_size,
            causal=causal, scale=scale,
        )
    if block_impl != "einsum":
        raise ValueError(f"unknown block_impl {block_impl!r}")
    b, lb, h, d = q.shape
    scale = (1.0 / d**0.5) if scale is None else scale
    if mask is None:
        mask = jnp.ones((b, lb), jnp.float32)
    mask = mask.astype(jnp.float32)

    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def update(src, kb, vb, maskb, m, l, o):
        """One online-softmax block update: fold the K/V block that
        originated on device ``src`` into (m, l, o) — running max
        [B,H,Lb], denominator [B,H,Lb], numerator [B,Lb,H,D]. Matmuls
        take native-dtype (bf16) inputs with f32 accumulation — the
        MXU recipe; only the softmax bookkeeping lives in f32."""
        scores = (
            jnp.einsum(
                "bqhd,bkhd->bhqk", q, kb,
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        keep = maskb[:, None, None, :]  # [B,1,1,Lk] binary
        if causal:
            q_pos = my_idx * lb + jnp.arange(lb)
            k_pos = src * lb + jnp.arange(lb)
            keep = keep * (q_pos[:, None] >= k_pos[None, :])[None, None, :, :]
        scores = scores + (1.0 - keep) * NEG

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # exp(NEG - m_new) saturates to exp(0)=1 when a whole block is
        # masked — the explicit * keep zeroes those lanes, keeping the
        # recurrence NaN-free with finite masking (see ops.attention).
        p = jnp.exp(scores - m_new[..., None]) * keep
        corr = jnp.exp(m - m_new)  # [B,H,Lq]
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(q.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, o

    # The accumulators must carry q's varying-manual-axes type (JAX
    # tracks which mesh axes a value varies over inside shard_map;
    # fresh zeros are "unvarying" and would mismatch the loop carry).
    def varying(x):
        return _varying_like(x, q)

    # Block 0 (our own K/V) outside the loop, then rotate-and-fold
    # axis_size-1 times — permute first, so no rotation result is ever
    # computed and discarded (XLA can't DCE a collective in the body).
    m, l, o = update(
        my_idx, k, v, mask,
        varying(jnp.full((b, h, lb), NEG, jnp.float32)),
        varying(jnp.zeros((b, h, lb), jnp.float32)),
        varying(jnp.zeros((b, lb, h, d), jnp.float32)),
    )

    def body(t, carry):
        m, l, o, kb, vb, maskb = carry
        kb, vb, maskb = jax.lax.ppermute(
            (kb, vb, maskb), axis_name, perm=perm
        )
        # After t rotations we hold the block originally on device
        # (my_idx - t) mod n.
        m, l, o = update((my_idx - t) % axis_size, kb, vb, maskb, m, l, o)
        return m, l, o, kb, vb, maskb

    _, l, o, *_ = jax.lax.fori_loop(1, axis_size, body, (m, l, o, k, v, mask))

    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]  # [B,Lq,H,1]
    return (o / denom).astype(q.dtype)


def zigzag_perm(length: int, n: int) -> "np.ndarray":
    """Global→zigzag index permutation: the sequence splits into
    ``2n`` stripes and device ``i`` gets stripes ``(i, 2n-1-i)``.

    Why: under the plain layout, causal ring attention is load-
    imbalanced — device 0's block attends 1 block while device n-1's
    attends all n, and since devices run in lockstep between
    ``ppermute`` steps, wall time is ~n full-block flash units. With
    the zigzag pairing every (holder, source) step costs EXACTLY two
    half-block units on every device:

    - past   (src < self): both local stripes attend the source's
      EARLY stripe only (its late stripe is entirely in their future)
      → ``flash(q, k_early)``: 2 half-units.
    - future (src > self): only the local LATE stripe attends, but it
      attends BOTH source stripes → ``flash(q_late, k)``: 2 half-units.
    - diagonal: local causal flash over the pair (local order is
      globally ascending, so plain causal masking is exact): ~2.

    Total causal wall time: n × 2 half-units ≈ half of the plain
    layout — the standard zigzag/striped ring-attention trick,
    expressed as one gather before ``shard_map`` and its inverse
    after.
    """
    import numpy as np

    if length % (2 * n):
        raise ValueError(
            f"zigzag needs length divisible by 2*n ({2 * n}), got {length}"
        )
    ls = length // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * ls, (i + 1) * ls))
        order.extend(range((2 * n - 1 - i) * ls, (2 * n - i) * ls))
    return np.asarray(order, np.int32)


def _ring_flash_zigzag(q, k, v, mask, *, axis_name, axis_size, scale):
    """Causal ring attention over the ZIGZAG layout: the local block
    is two stripes (early half E at global stripe ``i``, late half L
    at stripe ``2n-1-i``). See :func:`zigzag_perm` for the balance
    argument. Inputs/outputs are in zigzag order; callers permute.
    """
    from mlapi_tpu.ops.pallas import flash_attention_with_lse

    b, lb, h, d = q.shape
    half = lb // 2

    def varying(x):
        return _varying_like(x, q)

    if mask is None:
        mask = varying(jnp.ones((b, lb), jnp.float32))
    mask = mask.astype(jnp.float32)
    interpret = pallas_interpret()
    flash = functools.partial(
        flash_attention_with_lse, scale=scale, interpret=interpret
    )

    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def block(src, kb, vb, maskb):
        """(out, lse) of the local stripe-pair against source ``src``'s
        stripe-pair. Each branch costs two half-block flash units."""

        def past(args):
            kb, vb, maskb = args
            # Source's early stripe is past for BOTH local stripes;
            # its late stripe is future for both.
            return flash(q, kb[:, :half], vb[:, :half], maskb[:, :half])

        def diag(args):
            kb, vb, maskb = args
            return flash(q, kb, vb, maskb, causal=True)

        def future(args):
            kb, vb, maskb = args
            # Only the local LATE stripe attends (both source stripes
            # precede it); the early stripe sees nothing here.
            o_l, lse_l = flash(q[:, half:], kb, vb, maskb)
            o = jnp.concatenate(
                [varying(jnp.zeros((b, half, h, d), q.dtype)), o_l], axis=1
            )
            lse = jnp.concatenate(
                [varying(jnp.full((b, h, half), NEG, jnp.float32)), lse_l],
                axis=-1,
            )
            return o, lse

        return jax.lax.switch(
            jnp.sign(src - my_idx) + 1, [past, diag, future], (kb, vb, maskb)
        )

    def merge(o1, s1, o2, s2):
        m = jnp.maximum(s1, s2)
        w1 = jnp.exp(s1 - m)
        w2 = jnp.exp(s2 - m)
        wsum = jnp.maximum(w1 + w2, 1e-30)
        w1t = (w1 / wsum).transpose(0, 2, 1)[..., None]
        w2t = (w2 / wsum).transpose(0, 2, 1)[..., None]
        o = o1.astype(jnp.float32) * w1t + o2.astype(jnp.float32) * w2t
        return o.astype(o1.dtype), m + jnp.log(wsum)

    o_acc, lse_acc = block(my_idx, k, v, mask)
    o_acc, lse_acc = varying(o_acc), varying(lse_acc)

    def body(t, carry):
        o_acc, lse_acc, kb, vb, maskb = carry
        kb, vb, maskb = jax.lax.ppermute(
            (kb, vb, maskb), axis_name, perm=perm
        )
        o_b, lse_b = block((my_idx - t) % axis_size, kb, vb, maskb)
        o_acc, lse_acc = merge(o_acc, lse_acc, o_b, lse_b)
        return o_acc, lse_acc, kb, vb, maskb

    o_acc, *_ = jax.lax.fori_loop(
        1, axis_size, body, (o_acc, lse_acc, k, v, mask)
    )
    return o_acc.astype(q.dtype)


def _ring_flash(q, k, v, mask, *, axis_name, axis_size, causal, scale):
    """Ring attention whose per-block computation is the Pallas flash
    kernel: each step computes ``flash(q, k_block, v_block)`` with its
    log-sum-exp, and blocks merge by the exact lse-weighted average

        m = max(s1, s2); o = (o1·e^{s1-m} + o2·e^{s2-m}) / (e^{s1-m}+e^{s2-m})

    Causal structure is whole-block: a K/V block strictly in the past
    attends fully (plain flash), the diagonal block runs causal flash
    (positions align — both offsets are ``my_idx·Lb``), and future
    blocks are skipped via an lse of -inf-like ``NEG`` so they carry
    zero merge weight. ``lax.switch`` on the traced block origin keeps
    it one compiled program.
    """
    from mlapi_tpu.ops.pallas import flash_attention_with_lse

    b, lb, h, d = q.shape

    # Everything entering flash / the lax.switch must carry q's
    # varying-manual-axes type: constants minted inside shard_map
    # (the default mask, the future-branch zeros) are "unvarying"
    # and would mismatch varying branch outputs / kernel operands.
    def varying(x):
        return _varying_like(x, q)

    if mask is None:
        mask = varying(jnp.ones((b, lb), jnp.float32))
    mask = mask.astype(jnp.float32)
    interpret = pallas_interpret()
    flash = functools.partial(
        flash_attention_with_lse, scale=scale, interpret=interpret
    )

    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def block(src, kb, vb, maskb):
        """(out, lse) of q against one K/V block."""
        if not causal:
            return flash(q, kb, vb, maskb)

        def past(args):
            kb, vb, maskb = args
            return flash(q, kb, vb, maskb)

        def diag(args):
            kb, vb, maskb = args
            return flash(q, kb, vb, maskb, causal=True)

        def future(args):
            return (
                varying(jnp.zeros((b, lb, h, d), q.dtype)),
                varying(jnp.full((b, h, lb), NEG, jnp.float32)),
            )

        # sign(src - my_idx): -1 past, 0 diagonal, +1 future.
        return jax.lax.switch(
            jnp.sign(src - my_idx) + 1, [past, diag, future], (kb, vb, maskb)
        )

    def merge(o1, s1, o2, s2):
        m = jnp.maximum(s1, s2)
        w1 = jnp.exp(s1 - m)
        w2 = jnp.exp(s2 - m)
        wsum = jnp.maximum(w1 + w2, 1e-30)
        w1t = (w1 / wsum).transpose(0, 2, 1)[..., None]  # [B,Lb,H,1]
        w2t = (w2 / wsum).transpose(0, 2, 1)[..., None]
        o = o1.astype(jnp.float32) * w1t + o2.astype(jnp.float32) * w2t
        return o.astype(o1.dtype), m + jnp.log(wsum)

    o_acc, lse_acc = block(my_idx, k, v, mask)
    o_acc, lse_acc = varying(o_acc), varying(lse_acc)

    def body(t, carry):
        o_acc, lse_acc, kb, vb, maskb = carry
        kb, vb, maskb = jax.lax.ppermute(
            (kb, vb, maskb), axis_name, perm=perm
        )
        o_b, lse_b = block((my_idx - t) % axis_size, kb, vb, maskb)
        o_acc, lse_acc = merge(o_acc, lse_acc, o_b, lse_b)
        return o_acc, lse_acc, kb, vb, maskb

    o_acc, *_ = jax.lax.fori_loop(
        1, axis_size, body, (o_acc, lse_acc, k, v, mask)
    )
    return o_acc.astype(q.dtype)


def ring_self_attention(
    mesh,
    q,
    k,
    v,
    mask=None,
    *,
    seq_axis: str = "seq",
    batch_axis: str | None = "data",
    head_axis: str | None = None,
    causal: bool = False,
    scale=None,
    block_impl: str = "einsum",
    zigzag: bool = False,
):
    """Ring attention over globally-shaped ``[B, L, H, D]`` arrays.

    Shards L over ``mesh``'s ``seq_axis`` (and B over ``batch_axis``
    when the mesh has it), runs :func:`ring_attention` per device, and
    returns the global ``[B, L, H, D]`` result. L must divide evenly
    by the seq-axis size; pad upstream (padded keys masked out via
    ``mask``).

    ``head_axis`` additionally shards the head dim (tensor parallel —
    attention is independent per head, so SP x TP composes with no
    extra communication: K/V rotation stays within each head shard).

    ``zigzag=True`` (causal flash only) interleaves the sequence so
    each device holds stripes ``(i, 2n-1-i)`` — balancing causal work
    to two half-block flash units per ring step on EVERY device
    (~2x wall-time win over the plain layout; see :func:`zigzag_perm`).
    The permutation is one gather before ``shard_map`` and its
    inverse after; callers see plain global order.

    Quantized ``{"q", "scale"}`` K/V operands dequantize at THIS
    boundary, before the shard_map (specs and the ring payload are
    full-precision arrays — see :func:`ring_attention`).
    """
    from mlapi_tpu.ops.quant import maybe_dequant_kv

    k = maybe_dequant_kv(k, q.dtype)
    v = maybe_dequant_kv(v, q.dtype)
    n = mesh.shape[seq_axis]
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"{seq_axis!r} of size {n}; pad first"
        )
    bspec = batch_axis if batch_axis in mesh.axis_names else None
    if bspec and q.shape[0] % mesh.shape[bspec]:
        bspec = None  # e.g. a single-request serving batch on a DP mesh
    hspec = head_axis if head_axis in mesh.axis_names else None
    if hspec and q.shape[2] % mesh.shape[hspec]:
        hspec = None
    qkv_spec = P(bspec, seq_axis, hspec, None)
    mask_spec = P(bspec, seq_axis)

    inner = functools.partial(
        ring_attention,
        axis_name=seq_axis,
        axis_size=n,
        causal=causal,
        scale=scale,
        block_impl=block_impl,
        zigzag=zigzag,
    )
    mapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    if mask is None:
        mask = jnp.ones(q.shape[:2], jnp.float32)
    if zigzag:
        # Interleave so each device's CONTIGUOUS shard_map slice is
        # its stripe pair; undo on the way out. One gather each way.
        perm = jnp.asarray(zigzag_perm(q.shape[1], n))
        inv = jnp.argsort(perm)
        out = mapped(
            q[:, perm], k[:, perm], v[:, perm], mask[:, perm]
        )
        return out[:, inv]
    # shard_map reshards inputs to in_specs itself, eagerly or under
    # jit — no explicit placement needed here.
    return mapped(q, k, v, mask)

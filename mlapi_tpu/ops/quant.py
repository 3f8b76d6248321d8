"""Weight-only int8 quantization for serving.

Decode is weight-bandwidth-bound on TPU (every generated token re-reads
every matmul weight from HBM), so halving the bytes per weight is worth
up to ~2x decode throughput and exactly 2x parameter HBM — which is
also the difference between a model fitting one chip or not. This is
*weight-only* quantization: activations stay in the model's compute
dtype, and the dequantized product `q * scale` feeds the matmul inside
the jitted program, where XLA fuses the convert+multiply into the dot's
operand read — the full-precision weight tensor is never materialized
in HBM.

Scheme: symmetric per-channel int8 over the LAST axis (for an
``[in, out]`` kernel that is per-output-channel — the standard choice;
for an ``[vocab, hidden]`` embedding it is per-hidden-column). A
quantized leaf is replaced by ``{"q": int8[...], "scale": f32[...,1]}``
(scale keeps the reduced axes at length 1 so dequantization is one
broadcast multiply). Vectors (layernorm scales, biases) and small
tensors stay float — they are noise in both HBM and accuracy terms.

The reference (`/root/reference/main.py`) serves a pickled sklearn
model with no numeric-format control at all; this module exists for
the generative/serving scale the reference never reaches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Leaves smaller than this stay float: quantizing a 1 KB bias saves
# nothing and costs accuracy.
MIN_QUANT_SIZE = 4096


def _is_quant_leaf(x) -> bool:
    return isinstance(x, dict) and set(x) == {"q", "scale"}


def quantize_tree(params, *, min_size: int = MIN_QUANT_SIZE):
    """Quantize every float leaf with ``ndim >= 2`` and
    ``size >= min_size`` to per-channel symmetric int8; other leaves
    pass through unchanged. Host-side, one pass, no device programs —
    call once at checkpoint load."""

    def leaf(x):
        a = np.asarray(x)
        if (
            a.ndim < 2
            or a.size < min_size
            or not np.issubdtype(a.dtype, np.floating)
        ):
            return x
        amax = np.max(np.abs(a), axis=tuple(range(a.ndim - 1)),
                      keepdims=True)
        scale = (amax / 127.0).astype(np.float32)
        scale = np.where(scale == 0.0, 1.0, scale)
        q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
        return {"q": q, "scale": scale}

    return jax.tree.map(leaf, params)


def dequantize_tree(params, dtype=jnp.float32):
    """Traced inverse: expand every quantized leaf back to ``dtype``
    inside a jitted program. XLA fuses the convert+multiply into each
    weight's consumer, so the expansion costs no extra HBM round
    trip."""

    def leaf(x):
        if _is_quant_leaf(x):
            return x["q"].astype(dtype) * x["scale"].astype(dtype)
        return x

    return jax.tree.map(leaf, params, is_leaf=_is_quant_leaf)


# --- int8 KV-cache quantization ----------------------------------------
#
# Decode at generation scale is CACHE-bandwidth-bound, not just
# weight-bound: every decoded token re-reads every layer's [B, L, H, D]
# K and V from HBM, and past modest batch x context the cache bytes
# dominate the weights. The same move that halved weight HBM applies:
# store the cache as int8 with SYMMETRIC PER-TOKEN-PER-HEAD scales
# (amax over the head_dim axis), quantize fused into the append path,
# dequantize fused into the attention read — the full-precision cache
# is never materialized in HBM. A quantized cache layer is
# ``{"k_q": int8[B, L, H, D], "k_scale": f32[B, L, H, 1], "v_q": ...,
# "v_scale": ...}`` (this repo's cache layout is [B, L, H, D]; the
# scale keeps the reduced axis at length 1 so dequantization is one
# broadcast multiply, exactly like the weight scheme above).
#
# Per-token-per-head granularity is the accuracy sweet spot for KV:
# per-tensor scales are wrecked by attention-sink outlier tokens, while
# finer-than-head granularity buys nothing the f32 softmax doesn't
# already absorb. The f32 scale costs 4 bytes per (token, head) next
# to D int8 payload bytes — <= 2x total reduction asymptotically in D.

KV_FORMATS = ("none", "int8")


def kv_is_quantized_layer(layer: dict) -> bool:
    """Is this per-layer cache dict in the quantized format?"""
    return "k_q" in layer


# --- paged KV-cache layout ---------------------------------------------
#
# The contiguous layouts above allocate one [B, L, H, D] buffer per
# batch slot, sized to the slot's whole cache TIER — every sequence
# pays for its padded tier length, and a shared prefix is COPIED into
# every row. The paged layout breaks the cache into fixed-size pages
# and adds one indirection: a per-layer device POOL of pages plus a
# per-row PAGE TABLE mapping virtual tiles to pool pages. A paged
# layer reuses the contiguous key names with pool-shaped leaves and
# carries the table alongside:
#
#   ``{"k": [P, page, H, D], "v": ..., "table": int32[B, NP]}``
#   (int8: the payload+scale quartet with the same pool leading dims)
#
# so ``kv_is_quantized_layer`` keeps working and the presence of
# ``"table"`` is the ONE paged predicate. Virtual slot ``v`` of row
# ``b`` lives at ``pool[table[b, v // page], v % page]``; page id 0 is
# the permanently-reserved NULL page — unallocated table entries point
# at it, its reads are always masked (a row only reads slots it
# wrote), and dummy/finished rows write their dead tokens into it.
# Allocation, refcounts, sharing and copy-on-write are HOST metadata
# (serving/paged_pool.py); these seams only do the device arithmetic.

KV_PAGED_NULL = 0  # reserved pool page id: unallocated / dead writes


def kv_is_paged_layer(layer: dict) -> bool:
    """Is this per-layer cache dict in the paged (pool + page-table)
    layout?"""
    return isinstance(layer, dict) and "table" in layer


def kv_layer_page_size(layer: dict) -> int:
    """Tokens per page of a paged layer (pool dim 1)."""
    leaf = layer["k_q"] if kv_is_quantized_layer(layer) else layer["k"]
    return leaf.shape[1]


def _paged_coords(layer: dict, pos, u: int):
    """``(pids, offs)`` both ``[B, u]`` for virtual slots
    ``[pos, pos+u)`` (``pos`` scalar or ``[B]``) of every row."""
    table = layer["table"]
    page = kv_layer_page_size(layer)
    b = table.shape[0]
    posv = pos[:, None] if jnp.ndim(pos) else pos
    vpos = jnp.broadcast_to(posv + jnp.arange(u)[None, :], (b, u))
    pids = jnp.take_along_axis(table, vpos // page, axis=1)
    return pids, vpos % page


def make_paged_pools(model, num_pages: int, page_size: int) -> dict:
    """Device page pools for every layer of ``model``'s cache format:
    each contiguous ``[1, page, H, D]``-shaped leaf becomes a
    ``[num_pages, page, H, D]`` pool (scales ride along for int8).
    Page 0 is the null page — callers must never allocate it."""
    proto = jax.eval_shape(lambda: model.init_cache(1, page_size))
    return {
        ln: {
            name: jnp.zeros((num_pages,) + leaf.shape[1:], leaf.dtype)
            for name, leaf in layer.items()
        }
        for ln, layer in proto.items()
    }


def paged_cache_tree(pools: dict, table) -> dict:
    """Assemble the paged cache pytree a decode/extend program takes:
    every layer's pool leaves plus that layer's page-table mirror.
    ``table`` is the HOST ``[B, NP]`` int32 array (the source of
    truth); each layer gets its OWN device upload — donated programs
    reject the same buffer appearing twice, and per-layer ``[B, NP]``
    int32 uploads are noise next to one cache read. ``pools`` may be
    bare pool layers or a previous program's returned cache (stale
    tables are replaced)."""
    host = np.asarray(table, np.int32)
    return {
        ln: {
            **{n: a for n, a in layer.items() if n != "table"},
            "table": jnp.asarray(host),
        }
        for ln, layer in pools.items()
    }


def paged_pools_of(cache: dict) -> dict:
    """Inverse of :func:`paged_cache_tree`: strip the table mirrors,
    keeping the (possibly donated-updated) pool arrays."""
    return {
        ln: {n: a for n, a in layer.items() if n != "table"}
        for ln, layer in cache.items()
    }


def kv_page_bytes(model, page_size: int) -> int:
    """Exact per-page device bytes across every layer — pure
    dtype/shape arithmetic (the capacity-model unit the paged tests
    assert against, never wall-clock)."""
    proto = jax.eval_shape(lambda: model.init_cache(1, page_size))
    return sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for layer in proto.values()
        for leaf in layer.values()
    )


def kv_tree_bytes(tree) -> int:
    """Exact device bytes of a cache pytree from dtype/shape
    arithmetic alone — the unit the adopt-copy accounting uses
    (``generate.prefill_adopt_bytes``): an adopt scatter moves exactly
    the bytes of the contiguous tree it copies into pool pages, so the
    gauge is deterministic, never wall-clock."""
    return sum(
        int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(tree)
    )


def kv_quantize(x):
    """``[..., D]`` float K or V block → ``(q int8[..., D],
    scale f32[..., 1])``, symmetric per-token-per-head (amax over the
    last axis). Runs inside the jitted append, so XLA fuses the
    abs-max/divide/round into the cache write."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype):
    """Traced inverse: int8 payload x broadcast scale → ``dtype``.
    WHERE this expansion happens decides whether the full-precision
    tensor crosses HBM — on the einsum read path (``kv_cache_kv``)
    the dequantized operand materializes at the read seam, so int8
    saves storage but not read traffic there; only the flash
    decode/extend kernels (``ops/pallas/decode_attention``), which
    run this exact arithmetic per tile in registers, keep int8 on
    the bus for the read — since r11 that covers every cache-reading
    span (decode steps AND multi-token extends), not just decode.
    See :func:`maybe_dequant_kv` for the full three-way policy."""
    return q.astype(dtype) * scale.astype(dtype)


def kv_cache_append(layer: dict, k_new, v_new, pos, cdt) -> dict:
    """Write a ``[B, U, H, D]`` K/V block into a fixed-shape cache
    layer at slot ``pos`` — THE append seam both cache formats share
    (every decoder family's prefill/decode/extend writes through it).

    ``pos`` scalar: one fused slice-update writes every row at the
    same slot (the serving layout). ``pos`` per-row ``[B]``: the write
    vmaps over rows so each lands at its own slot (batched
    speculation's desynchronized layout). For a quantized layer the
    block is quantized first and the int8 payload + f32 scale written
    by the same slice-updates — quantization is fused into the append,
    and the full-precision block dies in registers.
    """
    if kv_is_quantized_layer(layer):
        kq, ks = kv_quantize(k_new)
        vq, vs = kv_quantize(v_new)
        updates = {"k_q": kq, "k_scale": ks, "v_q": vq, "v_scale": vs}
    else:
        updates = {"k": k_new.astype(cdt), "v": v_new.astype(cdt)}

    if kv_is_paged_layer(layer):
        # Paged write: ONE scatter per leaf lands every row's block at
        # its table-mapped pool coordinates — scalar and per-row pos,
        # single-token and U-token blocks, all through the same index
        # arithmetic (a block may span pages; the [B, U] coordinate
        # arrays express that for free). Rows whose table entry is the
        # null page (dummies, finished rows) scatter their dead tokens
        # there; null-page slots are never read unmasked.
        pids, offs = _paged_coords(layer, pos, k_new.shape[1])
        out = {"table": layer["table"]}
        for name, upd in updates.items():
            out[name] = layer[name].at[pids, offs].set(
                upd.astype(layer[name].dtype)
            )
        return out

    if jnp.ndim(pos):
        row_write = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(
                c, n, (p,) + (0,) * (c.ndim - 1)
            )
        )
        return {
            name: row_write(layer[name], upd, pos)
            for name, upd in updates.items()
        }
    return {
        name: jax.lax.dynamic_update_slice(
            layer[name], upd, (0, pos) + (0,) * (upd.ndim - 2)
        )
        for name, upd in updates.items()
    }


def kv_cache_kv(layer: dict, cdt):
    """The attention-read seam: a cache layer → ``(k, v)`` in the
    compute dtype. Quantized layers dequantize here, INSIDE the jitted
    program, right at the einsum operand — see :func:`kv_dequantize`
    for why this reads int8 from HBM, not floats. Paged layers GATHER
    their pool pages into the contiguous ``[B, L, H, D]`` oracle
    layout first (``pool[table]`` + reshape) — the einsum decode path
    over a paged cache is the contiguous reference with one extra
    gather, which is exactly what makes it the parity oracle for the
    page-table flash kernel (the kernel reads the pages in place)."""
    if kv_is_paged_layer(layer):
        table = layer["table"]

        def gather(pool):
            g = pool[table]  # [B, NP, page, ...]
            return g.reshape((g.shape[0], -1) + g.shape[3:])

        if kv_is_quantized_layer(layer):
            return (
                kv_dequantize(
                    gather(layer["k_q"]), gather(layer["k_scale"]), cdt
                ),
                kv_dequantize(
                    gather(layer["v_q"]), gather(layer["v_scale"]), cdt
                ),
            )
        return gather(layer["k"]), gather(layer["v"])
    if kv_is_quantized_layer(layer):
        return (
            kv_dequantize(layer["k_q"], layer["k_scale"], cdt),
            kv_dequantize(layer["v_q"], layer["v_scale"], cdt),
        )
    return layer["k"], layer["v"]


def kv_cache_seq_len(cache: dict) -> int:
    """Static sequence capacity of a cache pytree, any layout: the
    contiguous buffer length, or pages-per-row x page size for the
    paged layout (the VIRTUAL length every mask/position helper sees —
    paging changes where bytes live, never the slot arithmetic)."""
    layer = cache["layer_0"]
    leaf = layer["k_q"] if kv_is_quantized_layer(layer) else layer["k"]
    if kv_is_paged_layer(layer):
        return layer["table"].shape[1] * leaf.shape[1]
    return leaf.shape[1]


def init_kv_cache(batch: int, max_len: int, heads: int, head_dim: int,
                  cdt, kv_quant: str = "none") -> dict:
    """One layer's fixed-shape KV buffers in the requested format —
    the single definition of both cache layouts (each decoder family's
    ``init_cache`` maps it over its layers)."""
    if kv_quant == "int8":
        return {
            "k_q": jnp.zeros((batch, max_len, heads, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, heads, 1), jnp.float32),
            "v_q": jnp.zeros((batch, max_len, heads, head_dim), jnp.int8),
            "v_scale": jnp.zeros((batch, max_len, heads, 1), jnp.float32),
        }
    if kv_quant != "none":
        raise ValueError(
            f"unknown kv_quant {kv_quant!r}; expected one of {KV_FORMATS}"
        )
    return {
        "k": jnp.zeros((batch, max_len, heads, head_dim), cdt),
        "v": jnp.zeros((batch, max_len, heads, head_dim), cdt),
    }


@functools.lru_cache(maxsize=32)
def _forced_argmax_fn(model, n_steps: int):
    """Jitted teacher-forced decode: prefill the prompt, then feed a
    FIXED token stream through ``decode_step`` and emit each step's
    argmax — the per-step top-1 prediction of the model's cache
    format, decoupled from error compounding (a free-running
    comparison is meaningless past the first divergence)."""

    def _run(params, prompt_ids, forced, n_pad):
        p = prompt_ids.shape[1]
        cache, _ = model.prefill_core(
            params, prompt_ids, n_pad, p + n_steps + 1
        )

        def step(carry, tok):
            cache, pos = carry
            logits, cache = model.decode_step(
                params, cache, tok[:, None], pos, n_pad
            )
            return (cache, pos + 1), jnp.argmax(
                logits, axis=-1
            ).astype(jnp.int32)

        (_, _), outs = jax.lax.scan(
            step, (cache, jnp.int32(p)), forced.T
        )
        return outs.T

    return jax.jit(_run)


def kv_greedy_agreement(model, params, prompt_ids, max_new_tokens: int,
                        pad_lens=None, quant_overrides=None) -> float:
    """The decode-quality guard for int8 KV caches: greedy top-1
    token agreement of the int8-cache decode vs the full-precision
    cache, TEACHER-FORCED on the full-precision greedy stream.

    The reference stream is the ``kv_quant="none"`` model's greedy
    generation; both cache formats then replay that exact stream and
    the per-step argmaxes are compared. The first token is excluded —
    it comes from the prefill forward, which attends full-precision
    in-register under BOTH formats and cannot disagree — so every
    compared position actually read the quantized cache. ``model`` is
    the base decoder config (any decoder family with the ``kv_quant``
    field); returns the agreement fraction in ``[0, 1]``.

    ``quant_overrides``: extra dataclass fields replaced on the
    QUANTIZED side only — e.g. ``{"decode_attn_impl": "flash"}`` pins
    the flash-decode kernel's int8 tile path against the
    full-precision EINSUM reference (the oracle both decode impls
    answer to), so the guard then covers kernel math and quantization
    error together.
    """
    import dataclasses

    if max_new_tokens < 2:
        # Position 0 comes from the prefill forward and is excluded,
        # so a 1-token window would compare nothing (NaN, not 1.0).
        raise ValueError("kv_greedy_agreement needs max_new_tokens >= 2")
    base = dataclasses.replace(model, kv_quant="none")
    quant = dataclasses.replace(
        model, kv_quant="int8", **(quant_overrides or {})
    )
    b, p = prompt_ids.shape
    n_pad = (
        jnp.zeros((b,), jnp.int32) if pad_lens is None
        else jnp.asarray(pad_lens, jnp.int32)
    )
    ref = base.generate(
        params, prompt_ids, max_new_tokens=max_new_tokens,
        pad_lens=None if pad_lens is None else pad_lens,
    )
    forced = jnp.asarray(ref)[:, :-1]  # step t predicts ref[:, t+1]
    got = _forced_argmax_fn(quant, max_new_tokens - 1)(
        params, jnp.asarray(prompt_ids), forced, n_pad
    )
    return float(
        np.mean(np.asarray(got) == np.asarray(ref)[:, 1:])
    )


def maybe_dequant_kv(x, dtype=None):
    """Kernel-boundary leg of the THREE-WAY int8-KV dequant policy.
    Where a quantized ``{"q", "scale"}`` K/V operand expands depends
    on which path is reading and what bounds it:

    1. **Prefill / full-sequence kernels (here — Pallas flash,
       ring)**: dequantize AT THE KERNEL BOUNDARY, one fused
       convert+multiply feeding the first tile load. These shapes are
       MXU-bound (O(L²) FLOPs over O(L) bytes), so teaching them an
       int8 tile path would complicate every kernel for a read that
       isn't the bottleneck. (These kernels attend a LIVE full
       sequence; cache-backed spans are leg 2's.)
    2. **Cache reads, ``decode_attn_impl="flash"``
       (``ops/pallas/decode_attention``)**: dequantize PER TILE
       IN-KERNEL — int8 payload + scale tiles DMA to VMEM and expand
       in registers. Cache reads are bandwidth-bound (O(U·L) FLOPs
       over O(L) bytes at small U), so the byte format of the read
       IS the lever: this is the only leg where int8 crosses HBM on
       the attention read. Since r11 this leg covers single-token
       decode steps AND multi-token extend spans (chunked prefill,
       admission, speculative verify) — flash-extend is the same
       tile path with a U-row Q tile.
    3. **Cache reads, ``decode_attn_impl="einsum"``
       (``kv_cache_kv``)**: dequantize at the read seam feeding the
       decode/extend einsum — the reference oracle. The
       full-precision operand materializes between the dequant and
       the einsum, so this leg realizes the int8 saving in storage
       only.

    Anything that is neither an array nor a quant pair is rejected
    loudly."""
    if isinstance(x, dict):
        if _is_quant_leaf(x):
            return kv_dequantize(
                x["q"], x["scale"], dtype or x["scale"].dtype
            )
        raise TypeError(
            "attention kernels take arrays or {'q', 'scale'} quantized "
            f"pairs, got dict with keys {sorted(x)}"
        )
    return x


def is_quantized(params) -> bool:
    found = False

    def leaf(x):
        nonlocal found
        found = found or _is_quant_leaf(x)
        return x

    jax.tree.map(leaf, params, is_leaf=_is_quant_leaf)
    return found


def quantized_bytes(params) -> tuple[int, int]:
    """(bytes as stored, bytes if fully f32) — the HBM story."""
    stored = full = 0

    def leaf(x):
        nonlocal stored, full
        if _is_quant_leaf(x):
            stored += x["q"].size + 4 * x["scale"].size
            full += 4 * x["q"].size
        else:
            a = np.asarray(x)
            stored += a.nbytes
            full += a.nbytes
        return x

    jax.tree.map(leaf, params, is_leaf=_is_quant_leaf)
    return stored, full

"""Kernel agreement on the attached backend: every Pallas kernel of
the main path against a plain float32 ``jax.numpy`` oracle, at the
head geometry ``chip_smoke.py`` serves (BERT-base / GPT-2 small:
12 heads x 64), in ONE process.

    python -m tools.chip_kernels            # the five kernels, both
                                            # cache formats; flash also
                                            # at the benchmark cell's
                                            # shapes and at L = 1024,
                                            # timed beside XLA's own,
                                            # and streaming at 1 x 8192
                                            # with 64/8 heads under a
                                            # window and 48/8 causal;
                                            # the gated delta rule's
                                            # and the experts' backward
                                            # kernel's agreement rows
    python -m tools.chip_kernels --tp       # decode_attention_tp on a
                                            # (1, 4) mesh vs the
                                            # unsharded kernel
    python -m tools.chip_kernels --kda      # the gated delta rule
                                            # alone: kernels against
                                            # kda_chunked and the
                                            # literal recurrence, the
                                            # layer's seven-operand
                                            # call against the plain
                                            # path and ms a call of it
                                            # at the cell
                                            # kimi-linear.pretrain_8k's
                                            # shapes beside kda_chunked
                                            # plus XLA's norms
                                            # (--kda-tile 32 64 and
                                            # --kda-unroll 2 4 sweep
                                            # the kernel's two knobs)
    python -m tools.chip_kernels --experts  # the held experts' grouped
                                            # product's backward: the
                                            # kernel against the XLA
                                            # loop at the Laguna cell's
                                            # expert layer (8,192 tokens
                                            # x 8 of 256, 32 held of
                                            # 2048 x 512), max error and
                                            # ms a call forward and
                                            # backward
    python -m tools.chip_kernels --tiny     # CPU rehearsal sizes

``interpret`` follows the one rule (``utils.platform.
pallas_interpret``): compiled everywhere but the CPU backend. Prints
one JSON line per case and a last line ``{"kernels": ..., "device":
...}``; exits non-zero if any case is outside ``TOL``.

``chip_smoke.py`` runs this as a child (its parent stays off jax).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# Operands are unit-variance bf16; every dot accumulates in f32 and
# the probabilities are cast to bf16 for the PV contraction, so the
# kernels and the f32 oracle differ by bf16 rounding of O(1) values.
# int8 caches are compared against the oracle over the DEQUANTIZED
# cache, so quantization error itself is not in the budget.
TOL = 3e-2


def _oracle(q, k, v, mask):
    """Plain masked softmax attention in float32. ``q [B,U,H,D]``,
    ``k/v [B,L,H,D]``, ``mask [B,U,L]`` (1 = attend)."""
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("buhd,blhd->bhul", q, k) / q.shape[-1] ** 0.5
    s = jnp.where(mask[:, None] > 0, s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True)) * (mask[:, None] > 0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhul,blhd->buhd", p, v)


def run(tiny: bool, tp: bool) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlapi_tpu.ops.pallas import (
        decode_attention,
        decode_attention_tp,
        extend_attention,
        flash_attention,
        paged_decode_attention,
        paged_extend_attention,
    )
    from mlapi_tpu.ops.quant import kv_dequantize, kv_quantize
    from mlapi_tpu.utils.platform import pallas_interpret

    interp = pallas_interpret()
    heads, dim = (4, 16) if tiny else (12, 64)
    b, lk, page, span = (2, 64, 8, 4) if tiny else (4, 1024, 16, 16)
    fl_b, fl_l = (2, 32) if tiny else (8, 128)
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def err(out, ref):
        return float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))

    rows: list[dict] = []

    def case(name, out, ref):
        e = err(out, ref)
        finite = bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
        row = {"kernel": name, "max_abs_err": e, "tol": TOL,
               "shape": list(out.shape), "interpret": interp,
               "within_tol": finite and e <= TOL}
        print(json.dumps(row), flush=True)
        rows.append(row)

    # Per-row cache fill: row i holds pos[i] valid slots.
    pos = np.linspace(lk // 3, lk - span - 1, b).astype(np.int32)
    slots = np.arange(lk)
    dec_mask = jnp.asarray(slots[None] < pos[:, None], jnp.float32)
    ext_mask = jnp.asarray(
        slots[None, None]
        <= (pos[:, None] + np.arange(span)[None])[..., None],
        jnp.float32,
    )
    k_full, v_full = normal(b, lk, heads, dim), normal(b, lk, heads, dim)
    q1, qu = normal(b, 1, heads, dim), normal(b, span, heads, dim)

    if tp:
        from mlapi_tpu.parallel import create_mesh

        mesh = create_mesh((1, 4))
        one = decode_attention(q1, k_full, v_full, dec_mask, interpret=interp)
        sharded = decode_attention_tp(
            mesh, q1, k_full, v_full, dec_mask, interpret=interp
        )
        case("decode_attention_tp-vs-decode_attention",
             sharded, one.astype(jnp.float32))
        case("decode_attention_tp-vs-oracle", sharded,
             _oracle(q1, k_full, v_full, dec_mask[:, None]))
        return rows

    # flash: padded-batch (BERT) and causal (GPT prefill) forms, and
    # the backward kernels through the custom VJP.
    fq, fk, fv = (normal(fl_b, fl_l, heads, dim) for _ in range(3))
    lens = np.linspace(fl_l // 2, fl_l, fl_b).astype(np.int32)
    fmask = jnp.asarray(np.arange(fl_l)[None] < lens[:, None], jnp.float32)
    pad = jnp.broadcast_to(fmask[:, None], (fl_b, fl_l, fl_l))
    case("flash_attention-padded",
         flash_attention(fq, fk, fv, fmask, interpret=interp)
         * fmask[:, :, None, None].astype(jnp.bfloat16),
         _oracle(fq, fk, fv, pad) * fmask[:, :, None, None])
    tri = jnp.broadcast_to(
        jnp.tril(jnp.ones((fl_l, fl_l), jnp.float32)), (fl_b, fl_l, fl_l)
    )
    case("flash_attention-causal",
         flash_attention(fq, fk, fv, causal=True, interpret=interp),
         _oracle(fq, fk, fv, tri))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    g_kernel = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interp)), argnums=(0, 1, 2)
    )(fq, fk, fv)
    g_ref = jax.grad(loss(lambda q, k, v: _oracle(q, k, v, tri)),
                     argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (fq, fk, fv)))
    for name, gk, gr in zip("qkv", g_kernel, g_ref):
        # Gradients scale with the loss; compare relative to their
        # own magnitude so the same TOL applies.
        norm = float(jnp.max(jnp.abs(gr))) or 1.0
        case(f"flash_attention-grad-d{name}", gk.astype(jnp.float32) / norm,
             gr / norm)

    # The benchmark cell's own attention (bert-base.finetune: 128 rows
    # x 128 tokens, padded lengths; one tile, so the row-blocked
    # kernels) and a streaming-path witness at L = 1024: forward and
    # the three gradients against the oracle, and the time of one
    # forward + backward beside XLA's own full_attention.
    from mlapi_tpu.ops import full_attention

    def timed(fn, *args, n=20):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    for tag, cb, cl, causal in (
        ("cell", *((2, 32) if tiny else (128, 128)), False),
        ("l1024", *((2, 64) if tiny else (4, 1024)), True),
    ):
        cq, ck, cv = (normal(cb, cl, heads, dim) for _ in range(3))
        clens = np.linspace(cl // 8, cl, cb).astype(np.int32)
        cmask = jnp.asarray(np.arange(cl)[None] < clens[:, None], jnp.float32)
        valid = cmask[:, :, None, None]  # padded query rows: not compared
        allow = jnp.broadcast_to(cmask[:, None], (cb, cl, cl))
        if causal:
            allow = allow * jnp.tril(jnp.ones((cl, cl), jnp.float32))
        blocks = {} if not (tiny and causal) else {"block_q": 32, "block_k": 32}

        def kern(q, k, v):
            return flash_attention(q, k, v, cmask, causal=causal,
                                   interpret=interp, **blocks) * valid.astype(
                                       jnp.bfloat16)

        def xla(q, k, v):
            return full_attention(q, k, v, cmask, causal=causal) * valid.astype(
                jnp.bfloat16)

        def ref(q, k, v):
            return _oracle(q, k, v, allow) * valid

        case(f"flash_attention-{tag}", kern(cq, ck, cv), ref(cq, ck, cv))
        g_kernel = jax.grad(loss(kern), argnums=(0, 1, 2))(cq, ck, cv)
        g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(
            *(x.astype(jnp.float32) for x in (cq, ck, cv)))
        for name, gk, gr in zip("qkv", g_kernel, g_ref):
            norm = float(jnp.max(jnp.abs(gr))) or 1.0
            case(f"flash_attention-{tag}-grad-d{name}",
                 gk.astype(jnp.float32) / norm, gr / norm)

        def timed_grad(fn):
            # Operands as a model holds them: [B, L, H*D] projections,
            # split into heads inside the program.
            flat = [x.reshape(cb, cl, heads * dim) for x in (cq, ck, cv)]
            split = lambda *xs: loss(fn)(  # noqa: E731
                *(x.reshape(cb, cl, heads, dim) for x in xs))
            return timed(jax.jit(jax.grad(split, argnums=(0, 1, 2))), *flat)

        print(json.dumps({
            "timing": f"flash_attention-{tag}", "what": "forward + backward, "
            "host clock around 20 drained calls, ms a call",
            "shape": [cb, cl, heads, dim], "causal": causal,
            "kernel_ms": timed_grad(kern),
            "xla_full_attention_ms": timed_grad(xla),
        }), flush=True)

    # The streaming kernels at the calls of the cell
    # laguna-xs2.pretrain_8k: 1 x 8192, 64 query heads over 8 K/V heads
    # with a window of 512 (the windowed grids of all three kernels)
    # and 48 over 8 causal, forward and gradients against _jnp_flash in
    # float32 at the highest precision. The oracle holds [heads, L, L]
    # scores, so it follows ONE K/V head's group (the last): that
    # group's rows of out and dq and that head's dk, dv depend on no
    # other group.
    from mlapi_tpu.ops.pallas.flash_attention import _jnp_flash

    sl, sd, skv = (256, 16, 2) if tiny else (8192, 128, 8)
    sblocks = {"block_q": 64, "block_k": 64} if tiny else {}
    for tag, sh, window in (
        ("swa", 8 if tiny else 64, 64 if tiny else 512),
        ("gqa", 6 if tiny else 48, None),
    ):
        group = sh // skv
        sq = normal(1, sl, sh, sd)
        sk, sv = normal(1, sl, skv, sd), normal(1, sl, skv, sd)
        last = slice(sh - group, sh)

        def kern(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   interpret=interp, **sblocks)

        def ref(q, k, v):
            with jax.default_matmul_precision("highest"):
                return _jnp_flash(q, k, v, jnp.ones((1, sl), jnp.float32),
                                  True, sd ** -0.5, window)[0]

        part = [x.astype(jnp.float32) for x in
                (sq[:, :, last], sk[:, :, -1:], sv[:, :, -1:])]
        name = f"flash_attention-{tag}-{sh}over{skv}"
        case(name, kern(sq, sk, sv)[:, :, last], jax.jit(ref)(*part))
        g_kernel = jax.jit(jax.grad(loss(kern), argnums=(0, 1, 2)))(sq, sk, sv)
        g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(*part)
        for nm, gk, gr, sel in zip("qkv", g_kernel, g_ref,
                                   (last, slice(-1, None), slice(-1, None))):
            norm = float(jnp.max(jnp.abs(gr))) or 1.0
            case(f"{name}-grad-d{nm}",
                 gk[:, :, sel].astype(jnp.float32) / norm, gr / norm)

    # The four cache-read kernels x both stored formats.
    n_pages = lk // page
    order = rng.permutation(b * n_pages) + 1          # page 0 = null page
    table = jnp.asarray(order.reshape(b, n_pages), jnp.int32)

    def pool_of(full):
        pool = jnp.zeros((b * n_pages + 1, page, heads, full.shape[-1]),
                         full.dtype)
        return pool.at[table.reshape(-1)].set(
            full.reshape(b * n_pages, page, heads, full.shape[-1])
        )

    for fmt in ("bf16", "int8"):
        if fmt == "int8":
            kq, ks = kv_quantize(k_full)
            vq, vs = kv_quantize(v_full)
            k_op = {"q": kq, "scale": ks}
            v_op = {"q": vq, "scale": vs}
            k_ref = kv_dequantize(kq, ks, jnp.bfloat16)
            v_ref = kv_dequantize(vq, vs, jnp.bfloat16)
            k_pool = {"q": pool_of(kq), "scale": pool_of(ks)}
            v_pool = {"q": pool_of(vq), "scale": pool_of(vs)}
        else:
            k_op, v_op, k_ref, v_ref = k_full, v_full, k_full, v_full
            k_pool, v_pool = pool_of(k_full), pool_of(v_full)
        ref1 = _oracle(q1, k_ref, v_ref, dec_mask[:, None])
        refu = _oracle(qu, k_ref, v_ref, ext_mask)
        case(f"decode_attention-{fmt}",
             decode_attention(q1, k_op, v_op, dec_mask, interpret=interp),
             ref1)
        case(f"extend_attention-{fmt}",
             extend_attention(qu, k_op, v_op, ext_mask, interpret=interp),
             refu)
        case(f"paged_decode_attention-{fmt}",
             paged_decode_attention(q1, k_pool, v_pool, table, dec_mask,
                                    interpret=interp), ref1)
        case(f"paged_extend_attention-{fmt}",
             paged_extend_attention(qu, k_pool, v_pool, table, ext_mask,
                                    interpret=interp), refu)
    return rows


def run_kda(tiny: bool, timing: bool = True, tiles=(),
            unrolls=()) -> list[dict]:
    """The gated delta rule (``ops/pallas/kda.py``). Agreement of the
    bare rule at small sizes (2 rows x 2 heads of 128, 200 positions: a
    padded tail and several carried states; the 4-D entry over the same
    lane-slab kernels): float32 operands against the literal recurrence
    (``benchmark/reference``), and at ``bfloat16`` products the kernels
    beside ``kda_chunked``, each against the same oracle, as norm of
    the difference over the oracle's norm, output and the five
    gradients (without ``timing``, in the smoke, the bare kernels'
    bfloat16 rows are left to the layer's). Then the layer's
    seven-operand call (``kda_layer``: the
    normalisations and the gate inside) at the benchmark cell's call (1
    x 8192 x 32 x 128), float32 and bfloat16 products, each against the
    plain path (``kda_plain``) in float32: one row a dtype, the worst of
    ``y`` and the seven gradients. With ``timing``, ms a call of it
    forward and forward + backward beside ``kda_plain``
    (``kda_chunked`` plus XLA's normalisations), one JSON line a
    variant."""
    import os

    import jax
    import jax.numpy as jnp

    from mlapi_tpu.models.kimi_linear import kda_chunked, kda_plain
    from mlapi_tpu.ops.pallas import kda as kk
    from mlapi_tpu.utils.platform import pallas_interpret

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference import kimi_linear as ref

    interp = pallas_interpret()
    rows: list[dict] = []

    def operands(b, l, h, d, seed=0):
        ks = jax.random.split(jax.random.key(seed), 6)
        unit = lambda a: a / jnp.linalg.norm(  # noqa: E731
            a, axis=-1, keepdims=True)
        return (unit(jax.random.normal(ks[0], (b, l, h, d))) * d ** -0.5,
                unit(jax.random.normal(ks[1], (b, l, h, d))),
                jax.random.normal(ks[2], (b, l, h, d)),
                -jax.random.uniform(ks[3], (b, l, h, d), maxval=0.2),
                jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, h))),
                jax.random.normal(ks[5], (b, l, h, d)))

    def both(fn, args, probe):
        """``fn``'s result and its cotangents under ``probe``, jitted
        with the operands as ARGUMENTS: closed over, 134 MB arrays
        become constants of the program (minutes of compiling)."""
        def run(probe, *args):
            y, vjp = jax.vjp(fn, *args)
            return (y,) + vjp(probe)

        return jax.jit(run)(probe, *args)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def row(name, e, tol, a, *others):
        finite = all(bool(jnp.all(jnp.isfinite(x))) for x in (a, *others))
        r = {"kernel": name, "rel_err": e, "tol": tol,
             "shape": list(a.shape), "interpret": interp,
             "within_tol": finite and e <= tol}
        print(json.dumps(r), flush=True)
        rows.append(r)

    *args, probe = operands(2, 64 if tiny else 200, 2, 128)
    want = both(ref.delta_rule, args, probe)
    # float32: rounding only. bfloat16 products: what kda_chunked
    # itself reads against the same oracle (0.003 at these sizes).
    variants = [
        ("kernel-float32", lambda *a: kk.kda_kernels(
            *a, compute_dtype="float32", interpret=interp), 1e-4),
        ("kernel-bfloat16", lambda *a: kk.kda_kernels(
            *a, compute_dtype="bfloat16", interpret=interp), 2e-2),
        ("chunked-bfloat16", lambda *a: kda_chunked(
            *a, chunk=32, compute_dtype="bfloat16"), 2e-2)]
    if not timing:
        # the smoke's time: bfloat16 products are held by the layer's
        # row below, which compiles the same kernels
        del variants[1]
    for tag, fn, tol in variants:
        got = both(fn, args, probe)
        for name, a, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                              got, want):
            row(f"kda-{tag}-{name}", rel(a, w), tol, a)

    # The cell's call, the seven operands as the model's projections
    # leave them: [B, L, H*D] slabs, raw q and k.
    b, l, h, d = (1, 128, 2, 128) if tiny else (1, 8192, 32, 128)
    ks = jax.random.split(jax.random.key(1), 8)
    wide = lambda i: jax.random.normal(ks[i], (b, l, h * d))  # noqa: E731
    layer = (wide(0), wide(1), wide(2),
             -jax.random.uniform(ks[3], (b, l, h * d), maxval=0.2),
             jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, h))),
             jax.nn.sigmoid(wide(5)),
             1.0 + 0.1 * jax.random.normal(ks[6], (d,)))
    probe = wide(7)

    def kernels(cdt):
        return lambda *a: kk.kda_layer(
            *a, eps=1e-5, compute_dtype=cdt, interpret=interp)

    def plain(cdt):
        return lambda *a: kda_plain(
            *a, eps=1e-5, chunk=32, compute_dtype=cdt)

    with jax.default_matmul_precision("highest"):
        want = both(plain("float32"), layer, probe)
    # 128 carried states and sums over 8,192 positions re-associated:
    # float32 against float32 reads 1e-5, not 1e-6
    for cdt, tol in (("float32", 1e-3), ("bfloat16", 2e-2)):
        got = both(kernels(cdt), layer, probe)
        errs = [rel(a, w) for a, w in zip(got, want)]
        worst = max(range(8), key=errs.__getitem__)
        row(f"kda-layer-{cdt}-worst-of-y-and-7-grads", errs[worst], tol,
            got[worst], *got)

    if not timing:
        return rows

    def timed(fn, *args, n=2 if tiny else 10):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    def fwd_and_both(fn):
        def loss(probe, *a):
            return jnp.sum(fn(*a) * probe)

        return {"forward_ms": timed(fn, *layer),
                "forward_backward_ms": timed(
                    jax.grad(loss, argnums=tuple(range(1, 8))),
                    probe, *layer)}

    line = {"timing": "kda", "shape": [b, l, h, d],
            "compute_dtype": "bfloat16", "what": "host clock around "
            "drained calls, ms a call of the layer's seven-operand call",
            "interpret": interp}
    print(json.dumps({**line, "path": "kda_chunked + XLA norms",
                      **fwd_and_both(plain("bfloat16"))}), flush=True)
    for tile in tiles or (kk._TILE,):
        for unroll in unrolls or (kk._UNROLL,):
            kk._TILE, kk._UNROLL = tile, unroll
            print(json.dumps({**line, "path": "kernels", "tile": tile,
                              "heads_side_by_side": unroll,
                              **fwd_and_both(kernels("bfloat16"))}),
                  flush=True)
    return rows


def run_experts(tiny: bool, timing: bool = True) -> list[dict]:
    """The backward of the held experts' grouped product by its Pallas
    kernel (``ops/pallas/grouped_ffn.py``) against the XLA loop's
    (``models.experts.grouped_ffn`` with ``kernel`` off), at the
    expert layer of the cell ``laguna-xs2.pretrain_8k``: 8,192 tokens,
    each routed to 8 of 256 experts uniformly at random (distinct),
    planned over the 32 held (2048 x 512, an eighth of the pairs),
    bfloat16 products. Agreement: the five gradients of ``sum(y *
    probe)`` as max error over the loop's largest value, one row each
    (``y`` is the loop's on both paths). With ``timing``, ms a call of
    the forward and of that gradient (the backward alone: it needs
    nothing the forward makes, as in the model, whose recomputation
    leaves the forward out), and the two over the tiles in use: what
    ``moe_us_per_tile.train`` reads a trip."""
    import jax
    import jax.numpy as jnp

    from mlapi_tpu.models import experts
    from mlapi_tpu.utils.platform import pallas_interpret

    interp = pallas_interpret()
    rows_out: list[dict] = []
    t, k, n_experts, tile, count, hid, inter = (
        (512, 4, 32, 128, 8, 1024, 128) if tiny
        else (8192, 8, 256, 256, 32, 2048, 512))
    ks = jax.random.split(jax.random.key(7), 7)
    _, idx = jax.lax.top_k(jax.random.normal(ks[0], (t, n_experts)), k)
    plan = jax.jit(lambda i: experts.plan(i, 0, count, tile)[:3])(idx)
    x = jax.random.normal(ks[1], (t, hid), jnp.bfloat16)
    w = jax.random.uniform(ks[2], (t * k,))
    wg, wu = (0.05 * jax.random.normal(r, (count, hid, inter), jnp.bfloat16)
              for r in ks[3:5])
    wd = 0.05 * jax.random.normal(ks[5], (count, inter, hid), jnp.bfloat16)
    probe = jax.random.normal(ks[6], (t, hid))
    ops = (x, w, wg, wu, wd)

    def path(kernel):
        def fn(x, w, wg, wu, wd, *plan):
            return experts.grouped_ffn(x, w, wg, wu, wd, *plan, tile, k,
                                       kernel)
        return fn

    def grad(fn):
        return jax.jit(jax.grad(
            lambda x, w, wg, wu, wd, probe, *plan: jnp.sum(
                fn(x, w, wg, wu, wd, *plan) * probe),
            argnums=(0, 1, 2, 3, 4)))

    got, want = (grad(path(kernel))(*ops, probe, *plan)
                 for kernel in (True, False))
    for name, a, b in zip(("dx", "dw", "dwg", "dwu", "dwd"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        e = float(jnp.max(jnp.abs(a - b))) / scale
        finite = bool(jnp.all(jnp.isfinite(a)))
        r = {"kernel": f"grouped_ffn-laguna-{name}", "max_abs_err": e,
             "tol": TOL, "shape": list(a.shape), "interpret": interp,
             "within_tol": finite and e <= TOL}
        print(json.dumps(r), flush=True)
        rows_out.append(r)
    if not timing:
        return rows_out

    def timed(fn, *args, n=2 if tiny else 10):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    tiles = int(plan[2])
    f = timed(jax.jit(path(False)), *ops, *plan)
    line = {"timing": "grouped_ffn-laguna", "tokens": t, "k": k,
            "held": count, "hidden": hid, "intermediate": inter,
            "tile": tile, "tiles_in_use": tiles, "interpret": interp,
            "what": "host clock around drained calls, ms a call; the "
            "forward is the loop on either path", "forward_ms": f}
    for name, kernel in (("kernel_backward", True), ("xla_loop", False)):
        b = timed(grad(path(kernel)), *ops, probe, *plan)
        print(json.dumps({**line, "path": name, "backward_ms": b,
                          "us_per_tile": 1e3 * (f + b) / max(tiles, 1)}),
              flush=True)
    return rows_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("tools.chip_kernels")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tp", action="store_true")
    ap.add_argument("--kda", action="store_true")
    ap.add_argument("--experts", action="store_true")
    ap.add_argument("--kda-tile", type=int, nargs="*", default=())
    ap.add_argument("--kda-unroll", type=int, nargs="*", default=())
    args = ap.parse_args(argv)

    from mlapi_tpu.utils.platform import (
        apply_platform_override,
        device_report,
        enable_compile_cache,
    )

    apply_platform_override()
    enable_compile_cache()
    if args.kda:
        rows = run_kda(args.tiny, True, args.kda_tile, args.kda_unroll)
    elif args.experts:
        rows = run_experts(args.tiny)
    else:
        rows = run(args.tiny, args.tp)
        if not args.tp:
            rows += run_kda(args.tiny, timing=False)
            rows += run_experts(args.tiny, timing=False)
    bad = [r["kernel"] for r in rows if not r["within_tol"]]
    print(json.dumps({
        "kernels": len(rows), "failed": bad, "tol": TOL,
        "worst": max(r.get("max_abs_err", 0.0) for r in rows),
        "device": device_report(),
    }), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

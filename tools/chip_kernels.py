"""Kernel agreement on the attached backend: every Pallas kernel of
the main path against a plain float32 ``jax.numpy`` oracle, at the
head geometry ``chip_smoke.py`` serves (BERT-base / GPT-2 small:
12 heads x 64), in ONE process.

    python -m tools.chip_kernels            # the five kernels, both
                                            # cache formats; flash also
                                            # at the benchmark cell's
                                            # shapes and at L = 1024,
                                            # timed beside XLA's own
    python -m tools.chip_kernels --tp       # decode_attention_tp on a
                                            # (1, 4) mesh vs the
                                            # unsharded kernel
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("tools.chip_kernels")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tp", action="store_true")
    args = ap.parse_args(argv)

    from mlapi_tpu.utils.platform import (
        apply_platform_override,
        device_report,
        enable_compile_cache,
    )

    apply_platform_override()
    enable_compile_cache()
    rows = run(args.tiny, args.tp)
    bad = [r["kernel"] for r in rows if not r["within_tol"]]
    print(json.dumps({
        "kernels": len(rows), "failed": bad, "tol": TOL,
        "worst": max(r["max_abs_err"] for r in rows),
        "device": device_report(),
    }), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

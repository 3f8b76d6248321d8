"""Decide the SURVEY §7 criteo Pallas-gather question with a
decomposed on-chip profile (VERDICT r04 "Next" #6).

The committed cost analysis says the criteo-widedeep step is
memory-bound (0.69 flops/byte; 2.0 ms HBM roofline vs 13.0 ms
measured on-chip in r04). The 6.5x gap has two candidate owners:

* the EMBEDDING GATHER — 26 tables of 100k x 16 rows read at
  scattered 64-byte granularity (plus the backward's scatter-add),
  which cannot stream at peak HBM bandwidth, or
* everything else (optimizer sweep over the 170 MB of tables, MLP,
  host input feed).

This probe separates them on the attached backend, each stage
synced with ``jax.block_until_ready``:

1. ``gather_random``     — the real access pattern: random ids into
                           [F, V, D] tables, forward gather only.
2. ``gather_sequential`` — iota ids (coalesced rows): the same
                           program with a streamable pattern; the
                           random-vs-sequential ratio IS the
                           scatter penalty.
3. ``gather_grad``       — forward + scatter-add backward, random
                           ids (training's actual embedding cost).
4. ``apply_fwd``         — the full model forward.
5. ``train_step_dense`` / ``train_step_sparse`` — the full jitted
                           step, dense-recsys control vs the preset's
                           TRUE-sparse embedding update
                           (train/sparse_embed.py), interleaved, on
                           THIS attach's topology (recorded with
                           device count; compare only same-topology
                           numbers).

Decision rule, recorded with the output: a Pallas gather kernel can
only help the portion of (3) above the streaming floor implied by
(2). If stages (2)+(3) are a small fraction of (5), the step is
bound elsewhere (tables optimizer sweep / MLP) and the kernel is
DECLINED with this profile as the evidence; if (3) dominates (5) and
sits far above (2)'s floor, the kernel is justified and this file's
numbers size its budget.

Runs in ~1 min on-chip; CPU runs exercise the harness only (the
ratios are meaningless off-TPU). Emits one JSON line per stage plus
a summary.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Match the criteo-widedeep preset's operating point (batch_size
# 1024, config.py) so train_step re-measures the committed 13.0 ms
# basis rather than a 4x workload.
B, F, V, D = 1024, 26, 100_000, 16
REPS = 50  # sub-ms stages: amortize transport/dispatch overheads


def main() -> int:
    from bench import _choose_backend

    probe, note, env = _choose_backend()
    os.environ.update(env)
    from mlapi_tpu.utils.platform import apply_platform_override

    apply_platform_override()

    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    print(json.dumps({"stage": "backend", "backend": backend,
                      "batch": B, "note": note}), flush=True)

    key = jax.random.key(0)
    tables = jax.random.normal(key, (F, V, D), jnp.float32)
    ids_rand = jax.random.randint(jax.random.key(1), (B, F), 0, V,
                                  jnp.int32)
    ids_seq = (
        jnp.arange(B, dtype=jnp.int32)[:, None]
        + jnp.arange(F, dtype=jnp.int32)[None, :]
    ) % V
    feat = jnp.arange(F, dtype=jnp.int32)[None, :]

    @jax.jit
    def gather(t, ids):
        return t[feat, ids]  # [B, F, D]

    @jax.jit
    def gather_grad(t, ids):
        def loss(tt):
            return jnp.sum(tt[feat, ids] ** 2)

        return jax.grad(loss)(t)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / REPS

    res = {}
    # Read+write byte models per stage: the gathers read B*F rows and
    # write a [B, F, D] output; the grad additionally materializes
    # the FULL dense [F, V, D] table cotangent (zero-init + scatter-
    # add writes) — the dominant traffic, ~25x the forward's.
    row_bytes = B * F * D * 4
    table_bytes = F * V * D * 4
    stage_bytes = {
        "gather_random": 2 * row_bytes,
        "gather_sequential": 2 * row_bytes,
        "gather_grad": 2 * row_bytes + 2 * table_bytes,
    }
    for stage, fn, ids in (
        ("gather_random", gather, ids_rand),
        ("gather_sequential", gather, ids_seq),
        ("gather_grad", gather_grad, ids_rand),
    ):
        dt = timed(fn, tables, ids)
        res[stage] = {
            "ms": round(dt * 1e3, 3),
            "bytes_model_gb": round(stage_bytes[stage] / 1e9, 3),
            "attained_gb_s": round(stage_bytes[stage] / 1e9 / dt, 2),
        }
        print(json.dumps({"stage": stage, **res[stage]}), flush=True)

    # Full model + train step via the bench's own machinery.
    from mlapi_tpu.config import get_preset
    from mlapi_tpu.datasets import get_dataset
    from mlapi_tpu.models import get_model

    cfg = get_preset("criteo-widedeep")
    model = get_model(cfg.model, **cfg.model_kwargs)
    splits = get_dataset(cfg.dataset, **cfg.dataset_kwargs)
    x = jnp.asarray(splits.x_train[:B], jnp.float32)
    y = jnp.asarray(splits.y_train[:B], jnp.int32)
    params = model.init(jax.random.key(2))

    apply_jit = jax.jit(model.apply)
    dt = timed(apply_jit, params, x)
    res["apply_fwd"] = {"ms": round(dt * 1e3, 3)}
    print(json.dumps({"stage": "apply_fwd", **res["apply_fwd"]}),
          flush=True)

    from mlapi_tpu.train.loop import _make_optimizer, make_train_step
    from mlapi_tpu.train.sparse_embed import make_sparse_recsys_step

    def build_step(kind):
        p0 = model.init(jax.random.key(2))
        if kind == "sparse":
            base = _make_optimizer("adamw", cfg.learning_rate)
            init, step = make_sparse_recsys_step(
                model, base, cfg.learning_rate
            )
            return p0, init(p0), step
        tx = _make_optimizer("recsys-adamw", cfg.learning_rate,
                             model=model, params=p0)
        return p0, tx.init(p0), make_train_step(model.apply, tx)

    # Dense control vs the preset's TRUE-sparse step, INTERLEAVED
    # (this box's absolute throughput drifts; the ratio is the
    # result). params/opt_state are DONATED: chained runs, one
    # block_until_ready per window.
    steps = {"train_step_dense": build_step("dense"),
             "train_step_sparse": build_step("sparse")}
    for k, (p0, s0, step) in steps.items():
        p, s, warm_loss = step(p0, s0, x, y)  # compile + warm
        jax.block_until_ready(warm_loss)  # the warm step must NOT leak in
        steps[k] = (p, s, step)
    totals = {k: 0.0 for k in steps}
    executed = 4 * (REPS // 4)  # windows x steps actually run
    for _ in range(4):
        for k in steps:
            p, s, step = steps[k]
            t0 = time.perf_counter()
            loss = None
            for _ in range(REPS // 4):
                p, s, loss = step(p, s, x, y)
            jax.block_until_ready(loss)
            totals[k] += time.perf_counter() - t0
            steps[k] = (p, s, step)
    # Single-process topology: no mesh here — compare only against
    # same-topology numbers, never across (the committed bench basis
    # ran the bench's own topology).
    for k in totals:
        res[k] = {"ms": round(totals[k] / executed * 1e3, 3),
                  "devices": len(jax.devices()),
                  "mesh": None}
        print(json.dumps({"stage": k, **res[k]}), flush=True)
    res["train_step"] = res["train_step_dense"]  # summary basis
    print(json.dumps({
        "stage": "sparse_speedup",
        "x": round(res["train_step_dense"]["ms"]
                   / res["train_step_sparse"]["ms"], 2),
    }), flush=True)

    embed_ms = res["gather_grad"]["ms"]
    step_ms = res["train_step"]["ms"]
    floor_ms = res["gather_sequential"]["ms"]
    verdict = {
        "embed_fraction_of_step": round(embed_ms / step_ms, 3)
        if step_ms else None,
        "scatter_penalty_vs_sequential": round(
            res["gather_random"]["ms"] / floor_ms, 2
        ) if floor_ms else None,
        "kernel_justified_if": "embed_fraction large AND penalty >> 1",
        "backend": backend,
    }
    print(json.dumps({"stage": "summary", **verdict}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

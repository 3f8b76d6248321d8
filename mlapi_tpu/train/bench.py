"""Training throughput bench: step time, examples/s, and MFU.

"Actually fast, not just correct" needs a number (VERDICT r2 #3): for
each ladder preset this measures the steady-state jitted train step —
the same ``make_train_step`` program ``fit`` runs — and reports:

- ``step_ms``:      wall time per optimizer step (K steps dispatched
                    back-to-back, one device sync at the end — the
                    realistic pipeline, since each step consumes the
                    previous step's donated state).
- ``examples_per_s``: batch_size / step time.
- ``flops_per_step``: XLA's own count (``compiled.cost_analysis()``),
                    not a hand model — includes forward, backward and
                    the optimizer update.
- ``mfu``:          flops_per_step / step_time / peak_flops, where
                    peak is the chip's bf16 matmul peak. Reported only
                    on TPU (CPU "peak" is not a meaningful basis).

The bench measures the attached accelerator: without one it FAILS
unless the CPU was asked for by name (``JAX_PLATFORMS=cpu`` /
``MLAPI_TPU_PLATFORM=cpu`` — what the tests do), and a step whose
FLOPs XLA cannot count is an error, not a row with ``mfu: null``.

Usage::

    python -m mlapi_tpu.train --bench                  # default presets
    python -m mlapi_tpu.train --bench --preset sst2-bert --bench-steps 20
"""

from __future__ import annotations

import time
from typing import Any

import jax
import numpy as np

# Peak dense matmul throughput (bf16, per chip) keyed by the EXACT
# ``device_kind`` string JAX reports. MFU against the bf16 peak is the
# community convention even when parts of the program run f32; the
# denominator is what the MXU could do. Sources: Google Cloud TPU
# documentation, the "TPU v5e" / "TPU v4" / "TPU v5p" / "TPU v6e"
# system-architecture pages.
_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e: 197 TFLOP/s bf16
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e/Trillium
}

# Peak HBM bandwidth (bytes/s, per chip) — the roofline's other axis.
# Same pages.
_PEAK_BW = {
    "TPU v5 lite": 819e9,    # v5e: 819 GB/s
    "TPU v4": 1228e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
}


def _peak_for(device, table=_PEAK_FLOPS) -> float:
    """The table's entry for exactly this ``device_kind``. A device
    that is not in the table is an error, never a default: a prefix
    match once gave every unknown kind the first row's peak."""
    kind = getattr(device, "device_kind", None)
    if kind not in table:
        raise KeyError(
            f"no published peak for device_kind {kind!r}: add it, with "
            f"its source, to the tables in {__name__} "
            f"(known: {sorted(table)})"
        )
    return table[kind]


def bytes_per_device(tree) -> int:
    """Max-over-devices of the bytes a pytree's shards occupy locally
    (``addressable_shards[...].data.nbytes``) — the committed,
    deterministic measure of the FSDP memory win (wall-clock on this
    box swings ±25-30%; byte counts do not). A replicated leaf costs
    its full ``nbytes`` on EVERY device; an fsdp-sharded leaf 1/axis
    of it. Host numpy leaves count once (single-device placement)."""
    per_dev: dict = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            for s in shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        elif hasattr(leaf, "nbytes"):
            per_dev[None] = per_dev.get(None, 0) + leaf.nbytes
    return max(per_dev.values(), default=0)


def bench_train(
    preset,
    *,
    bench_steps: int = 50,
    warmup_steps: int = 3,
    batch_size: int | None = None,
    optimizer: str | None = None,
    use_mesh: bool = True,
    mesh_shape: tuple[int, ...] | None = None,
) -> dict[str, Any]:
    """Measure the training step of one ladder preset (by name) or an
    explicit ``TrainConfig`` on the attached backend. Returns a flat
    dict of numbers (JSON-ready).

    ``mesh_shape`` overrides the preset's mesh (FSDP-vs-DP memory
    sweeps: run once per shape and compare the per-device state
    bytes)."""
    from mlapi_tpu.config import get_preset
    from mlapi_tpu.datasets import get_dataset
    from mlapi_tpu.models import get_model
    from mlapi_tpu.parallel import (
        mesh_for_config,
        place_train_state,
        shard_batch_for_mesh,
    )
    from mlapi_tpu.train.loop import _make_optimizer, make_train_step

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            f"--bench measures the accelerator, but JAX found only "
            f"{jax.default_backend()!r}; set JAX_PLATFORMS=cpu to bench "
            "the CPU backend on purpose"
        )
    cfg = get_preset(preset) if isinstance(preset, str) else preset
    splits = get_dataset(cfg.dataset, **cfg.dataset_kwargs)
    model = get_model(cfg.model, **cfg.model_kwargs)
    bs = batch_size or cfg.batch_size or min(256, len(splits.x_train))

    bench_mesh_shape = mesh_shape or cfg.mesh_shape
    # The same rule fit's CLI applies (parallel.mesh.mesh_for_config):
    # a mesh that does not fit several visible devices is an error.
    mesh = mesh_for_config(bench_mesh_shape) if use_mesh else None

    params = model.init(jax.random.key(cfg.seed))
    # Same task resolution as fit: explicit dataset marker first,
    # label-shape fallback — the bench must time the exact program
    # fit runs (LM presets use the shifted, pad-masked objective).
    task = splits.extras.get(
        "task", "lm" if np.asarray(splits.y_train).ndim == 2 else "classify"
    )
    opt_name = optimizer or cfg.optimizer
    if opt_name.startswith("recsys-sparse-"):
        # The sparse-embedding step (train/sparse_embed.py): the bench
        # must time the exact program fit runs for this optimizer.
        from mlapi_tpu.train.sparse_embed import make_sparse_recsys_step

        base = _make_optimizer(
            opt_name[len("recsys-sparse-"):], cfg.learning_rate
        )
        init_opt, step_fn = make_sparse_recsys_step(
            model, base, cfg.learning_rate, task=task,
            weight_decay=cfg.weight_decay,
        )
    else:
        tx = _make_optimizer(
            opt_name, cfg.learning_rate, model=model, params=params,
        )
        init_opt = tx.init
        step_fn = None  # built below, once state shardings are known
    if mesh is not None:
        # The SAME placement fit uses (parallel.mesh.place_train_state):
        # params in the model's (FSDP-augmented) layout, optimizer
        # state placed explicitly in the matching shardings, step
        # outputs pinned — the bench must measure the same program
        # AND the same memory layout.
        params, opt_state, state_shardings = place_train_state(
            model, params, init_opt, mesh
        )
    else:
        opt_state = init_opt(params)
        state_shardings = None
    if step_fn is None:
        step_fn = make_train_step(
            model.apply, tx, weight_decay=cfg.weight_decay, task=task,
            state_shardings=state_shardings,
        )
    elif state_shardings is not None:
        # Sparse path on a mesh: rebuild with the output pin, exactly
        # like fit does.
        _, step_fn = make_sparse_recsys_step(
            model, base, cfg.learning_rate, task=task,
            weight_decay=cfg.weight_decay,
            state_shardings=state_shardings,
        )

    # Per-device state bytes, BEFORE the first step donates the
    # buffers. This is the FSDP headline number: (1, 8, 1) must report
    # ~1/8th the replicated (8, 1, 1) bytes for every leaf above the
    # sharding threshold.
    param_bytes_per_device = bytes_per_device(params)
    opt_bytes_per_device = bytes_per_device(opt_state)

    # One fixed batch, reused: this measures the step program, not the
    # host data pipeline (which fit's (seed, step)-keyed batching does
    # off the device critical path anyway).
    x = np.asarray(splits.x_train[:bs])
    y = np.asarray(splits.y_train[:bs], np.int32)
    if len(x) < bs:
        reps = -(-bs // len(x))
        x = np.tile(x, (reps,) + (1,) * (x.ndim - 1))[:bs]
        y = np.tile(y, (reps,) + (1,) * (y.ndim - 1))[:bs]
    if mesh is not None:
        x, y = shard_batch_for_mesh((x, y), mesh)

    # XLA's own flop + byte counts for the whole step (fwd + bwd +
    # optimizer). Bytes accessed is the roofline's other axis: with a
    # measured step time, flops/peak vs bytes/bandwidth says which
    # resource binds — the committed, quantitative basis for kernel
    # decisions like SURVEY §7's "Pallas embedding gather only if
    # profiling demands it" (criteo).
    cost = step_fn.lower(params, opt_state, x, y).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    flops = float((cost or {}).get("flops", 0.0))
    bytes_accessed = float((cost or {}).get("bytes accessed", 0.0))
    if not flops or not bytes_accessed:
        raise RuntimeError(
            f"XLA's cost analysis counted no FLOPs/bytes for the "
            f"{cfg.name} step ({cost!r}): MFU and the roofline cannot "
            "be reported"
        )

    for _ in range(warmup_steps):
        params, opt_state, loss = step_fn(params, opt_state, x, y)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(bench_steps):
        params, opt_state, loss = step_fn(params, opt_state, x, y)
    jax.block_until_ready(loss)
    total = time.perf_counter() - t0
    final_loss = float(loss)

    step_s = total / bench_steps
    dev = jax.devices()[0]
    n_dev = mesh.size if mesh is not None else 1
    mfu = (
        round(flops / step_s / (_peak_for(dev) * n_dev), 4)
        if on_tpu else None
    )
    # Roofline verdict: compare the step's FLOP time at peak MXU rate
    # with its BYTE time at peak HBM bandwidth. Whichever dominates is
    # the resource this program is bound by — the quantitative answer
    # to "would a hand kernel help here" (a Pallas gather cannot beat
    # the HBM roofline a memory-bound step already sits on).
    roofline = None
    if on_tpu:
        peak, bw = _peak_for(dev), _peak_for(dev, _PEAK_BW)
        t_flops = flops / (peak * n_dev)
        t_bytes = bytes_accessed / (bw * n_dev)
        roofline = {
            "t_flops_ms": round(t_flops * 1e3, 3),
            "t_bytes_ms": round(t_bytes * 1e3, 3),
            "bound": "memory" if t_bytes > t_flops else "compute",
            "attained_bw_gb_s": round(
                bytes_accessed / step_s / 1e9, 1
            ),
            "peak_bw_gb_s": round(bw * n_dev / 1e9, 1),
        }
    return {
        "preset": cfg.name,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "cpu"),
        "devices": n_dev,
        "mesh": list(bench_mesh_shape) if mesh is not None else None,
        "batch_size": int(bs),
        "param_bytes_per_device": int(param_bytes_per_device),
        "opt_bytes_per_device": int(opt_bytes_per_device),
        "step_ms": round(step_s * 1e3, 3),
        "examples_per_s": round(bs / step_s, 1),
        "flops_per_step": flops,
        "bytes_per_step": bytes_accessed,
        "tflops_per_s": round(flops / step_s / 1e12, 2),
        "mfu": mfu,
        "roofline": roofline,
        "final_loss": final_loss,
    }


# docs-gpt rides along so training perf covers the LM objective too
# (next-token CE over [B, L, V] logits — a different program shape
# than the classifier steps).
DEFAULT_BENCH_PRESETS = (
    "fashion-mlp", "criteo-widedeep", "sst2-bert", "docs-gpt",
)

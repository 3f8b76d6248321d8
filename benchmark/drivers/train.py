"""Driver of the ``train`` entry kind: one ``fit`` call in one child
(``train_child.py``), then the plain reference in another
(``reference_child.py``), then the comparison that decides
``correct``. Imports no jax."""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys

import numpy as np

import harness

# ---- the comparison -------------------------------------------------------
B1 = 0.9  # Adam's first-moment decay: after one step mu = (1 - b1) * g


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, the gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns the worst leaf, its name, and the median leaf."""
    med = statistics.median(ref.values())
    gaps = {}
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        gaps[k] = abs(prog[k] - r) / max(r, med)
    where = max(gaps, key=gaps.get)
    return {"worst": gaps[where], "where": where,
            "median": statistics.median(gaps.values())}


def readings(prog: dict, ref: dict, by_leaf: bool = False) -> tuple[dict, dict]:
    """Every number this driver can compare; the cell's ``limits``
    name the ones that are. ``by_leaf`` adds every leaf's
    ``grad_dir_gap`` to the second result (tools only)."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss{i}_gap"] = abs(lp - lr)
    g_scale = ref["grad_norms"]
    g = leaf_gaps({k: v / (1 - B1) for k, v in prog["mu_norms"].items()},
                  ref["grad_norms"])
    # leaves whose gradient is nought to rounding in the reference
    # (a key's bias under softmax) move under Adam by round-off alone
    med = statistics.median(g_scale.values())
    moved = {k for k, v in g_scale.items() if v >= 1e-3 * med}
    d = leaf_gaps(prog["delta_norms"], ref["delta_norms"], keep=moved)
    # the first gradient's DIRECTION: per leaf, on the sampled
    # elements, the part of (program - reference) that stands
    # perpendicular to the reference, over the reference's norm (the
    # sine of the angle between them, about). Rounding noise turns a
    # gradient without changing its length, so this, and no gap of
    # norms, tells products in 8 bits from the configuration's
    # bfloat16. The part ALONG the reference is left to grad_norm_gap:
    # a shift common to every row's predicted share rescales the whole
    # gradient and reads large where the classes all but cancel in it
    # (PERF.md, PR 26).
    turn = {}
    for k in moved:
        r = np.asarray(ref["grad_sample"][k], np.float64)
        e = np.asarray(prog["grad_sample"][k], np.float64) - r
        rr = float(r @ r)
        turn[k] = float(np.linalg.norm(e - (e @ r) / rr * r) / rr ** 0.5)
    t_where = max(turn, key=turn.get)
    out.update({
        "grad_norm_gap": g["worst"], "grad_norm_gap_median": g["median"],
        "delta_norm_gap": d["worst"], "delta_norm_gap_median": d["median"],
        "grad_dir_gap": turn[t_where],
        "grad_dir_gap_median": statistics.median(turn.values()),
    })
    where = {"grad_leaf": g["where"], "delta_leaf": d["where"],
             "grad_dir_leaf": t_where,
             "leaves_left_out": sorted(set(g_scale) - moved)}
    if by_leaf:
        where["grad_dir_by_leaf"] = turn
    return out, where


def compare(prog: dict, ref: dict, limits: dict) -> tuple[harness.Checks, dict]:
    checks = harness.Checks()
    if len(prog["losses"]) != len(ref["losses"]) or len(ref["losses"]) < 3:
        checks.add("losses_missing", 1, 0, exact=True)
        return checks, {}
    got, where = readings(prog, ref)
    for name, limit in limits.items():
        checks.add(name, got[name], limit)
    where["readings"] = got
    return checks, where


def load_samples(path: str) -> dict:
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


# ---- one run --------------------------------------------------------------
def sized(cell: dict, config: dict, rehearse: bool) -> tuple[dict, dict]:
    """The cell and its configuration as they are run: at the
    published sizes, or at the rehearsal's on the CPU."""
    config = dict(config)
    if rehearse:
        config.update(config.get("rehearsal", {}))
        tiny = cell.get("rehearsal", {})
        cell = {**cell, **tiny,
                "traffic": {**cell["traffic"], **tiny.get("traffic", {})}}
    return cell, config


def make_job(args, cell: dict, config: dict, work: str) -> dict:
    """What ``train_child.py`` and ``reference_child.py`` are told."""
    return {
        "kind": "train", "seed": args.seed, "seconds": args.seconds,
        "config": config, "cell": cell, "trace": bool(args.trace),
        "trace_dir": os.path.join(work, "trace"), "trace_after_s": 1.0,
        "trace_seconds": cell.get("trace_seconds", 3.0),
        "warm_steps": cell["warm_steps"], "lag": cell.get("lag", 4),
        "batches_path": os.path.join(work, "batches.npz"),
        "grad_path": os.path.join(work, "program_grad.npz"),
        "reference_grad_path": os.path.join(work, "reference_grad.npz"),
        "result_path": os.path.join(work, "program.json"),
        "block": cell.get("reference_block", 32),
        "fault": os.environ.get("BENCH_TEST_FAULT") if args.rehearse else None,
        "rehearse": args.rehearse,
    }


def run(ctx: dict) -> dict:
    args = ctx["args"]
    cell, config = sized(ctx["cell"], ctx["config"], args.rehearse)
    work = os.path.join(harness.CACHE, "run", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    env = harness.cache_env(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    job = make_job(args, cell, config, work)
    trace_dir = job["trace_dir"]
    job_path = os.path.join(work, "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    log = os.path.join(harness.CACHE, "logs", args.workload + ".train.log")
    rc = harness.run_child([os.path.join(harness.BENCH_DIR, "train_child.py"),
                            job_path], env=env, timeout=1100, log_path=log)
    if rc == 3:
        raise harness.NoChip("the training child found no accelerator")
    if rc != 0 or not os.path.exists(job["result_path"]):
        print(harness.tail(log), file=sys.stderr)
        raise SystemExit(f"benchmark: the training child exited {rc}")
    with open(job["result_path"]) as f:
        prog = json.load(f)
    prog["grad_sample"] = load_samples(job["grad_path"])
    peak = harness.device_gate(prog["device"], ctx["entry"]["chips"],
                               ctx["peaks"], args.rehearse)

    # the reference: the first three steps on the same rows, in a
    # process of its own (the program's state is gone with its child)
    ref_job = dict(job, result_path=os.path.join(work, "reference.json"))
    ref_path = os.path.join(work, "ref_job.json")
    with open(ref_path, "w") as f:
        json.dump(ref_job, f)
    rlog = os.path.join(harness.CACHE, "logs", args.workload + ".ref.log")
    rc = harness.run_child(
        [os.path.join(harness.BENCH_DIR, "reference_child.py"), ref_path],
        env=env, timeout=600, log_path=rlog)
    if rc != 0:
        print(harness.tail(rlog), file=sys.stderr)
        raise SystemExit(f"benchmark: the reference child exited {rc}")
    with open(ref_job["result_path"]) as f:
        ref = json.load(f)
    ref["grad_sample"] = load_samples(job["reference_grad_path"])
    checks, where = compare(prog, ref, cell["limits"])

    window = prog["t_window_end"] - prog["t_window_start"]
    t = cell["traffic"]
    tokens = prog["steps"] * t["batch_size"] * t["seq_len"]
    mem = prog["memory"] or {}
    trace = harness.reduce_trace(trace_dir, env) if args.trace else None
    if trace:  # kept beside the run's other files for a look by hand
        shutil.copy(os.path.join(trace_dir, "reduced.json"), work)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({
        "steps": prog["steps"], "window_s": window,
        "losses": prog["losses"], "reference_losses": ref["losses"],
        "reference_seconds": ref["seconds"], "worst_leaves": where,
        "setup_compiles": prog["setup_compiles"],
        "compiles_in_window": prog["compiles_in_window"],
        "compile_seconds": prog["compile_seconds"],
        "memory": mem, "train_tokens_per_s": tokens / window,
        "setup_s": prog["t_window_start"] - ctx["t0"],
    }), file=sys.stderr)
    device = dict(prog["device"],
                  memory_peak_bytes=mem.get("memory_peak_bytes"))
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
    return {
        "checks": checks, "attempted": prog["steps"], "failed": 0,
        "device": device, "trace": trace, "peak": peak,
        "config": config, "cell": cell,
        "end_to_end": {
            "train_tokens_per_s": tokens / window if window > 0 else None,
            "setup_s": prog["t_window_start"] - ctx["t0"],
        },
        "window": {"seconds": window, "steps": prog["steps"],
                   "tokens": tokens, "batch_size": t["batch_size"],
                   "seq_len": t["seq_len"]},
        "child": prog,
    }

"""Driver of the ``train_lm`` entry kind: a language model trained by
one ``fit`` call in one child (``train_lm_child.py``), then the plain
reference in another (``reference_lm_child.py``), then the comparison
that decides ``correct``, which is ``drivers/train.py``'s own
(loaded, not copied). Nothing here or in the children names a family:
the configuration's file names its reference (``reference``: a module
of ``reference/`` with ``make_params`` / ``param_spec`` / ``draw`` /
``train_steps``) and its ``task``, the cell's file names the rows'
generator (``traffic.generator``: ``<module>.<function>`` of this
directory). With ``--trace 1`` the raw trace is kept until
``scope_time.py`` has summed device self-time by ``named_scope``.
Imports no jax."""

from __future__ import annotations

import json
import os
import shutil
import sys

import harness

base = harness.load_module(
    os.path.join(harness.BENCH_DIR, "drivers", "train.py"), "driver_train")
compare, sized, load_samples, B1 = (
    base.compare, base.sized, base.load_samples, base.B1)


def make_job(args, cell: dict, config: dict, work: str) -> dict:
    """What the two children are told: ``drivers/train.py``'s job plus
    where the scope times go."""
    job = base.make_job(args, cell, config, work)
    job.update(kind="train_lm",
               op_scopes_path=os.path.join(work, "op_scopes.json"),
               scope_path=os.path.join(work, "scopes.json"))
    return job


def scope_times(trace_dir: str, out: str, env: dict) -> dict | None:
    """Device self-time by ``named_scope``, read from the raw trace in
    a process of its own, off the chip. None where the trace carries
    no scope."""
    e = dict(env, JAX_PLATFORMS="cpu")
    log = os.path.join(harness.CACHE, "logs", "scope_time.log")
    rc = harness.run_child(
        [os.path.join(harness.BENCH_DIR, "scope_time.py"), trace_dir, out],
        env=e, timeout=240, log_path=log)
    if rc != 0:
        print("benchmark: scope reduction failed:\n" + harness.tail(log),
              file=sys.stderr)
        return None
    with open(out) as f:
        return json.load(f)


def run(ctx: dict) -> dict:
    args = ctx["args"]
    cell, config = sized(ctx["cell"], ctx["config"], args.rehearse)
    work = os.path.join(harness.CACHE, "run", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    env = harness.cache_env(os.environ)
    if args.rehearse:
        env = harness.rehearsal_env(env, ctx["entry"]["chips"])
    job = make_job(args, cell, config, work)
    trace_dir = job["trace_dir"]
    job_path = os.path.join(work, "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    log = os.path.join(harness.CACHE, "logs", args.workload + ".train.log")
    rc = harness.run_child(
        [os.path.join(harness.BENCH_DIR, "train_lm_child.py"), job_path],
        env=env, timeout=1500, log_path=log)
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
        [os.path.join(harness.BENCH_DIR, "reference_lm_child.py"), ref_path],
        env=env, timeout=900, log_path=rlog)
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
    trace = scopes = None
    if args.trace:
        trace = harness.reduce_trace(trace_dir, env)
        if os.path.exists(job["op_scopes_path"]):
            scopes = scope_times(trace_dir, job["scope_path"], env)
    if trace:  # kept beside the run's other files for a look by hand
        shutil.copy(os.path.join(trace_dir, "reduced.json"), work)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({
        "steps": prog["steps"], "window_s": window,
        "losses": prog["losses"], "reference_losses": ref["losses"],
        "reference_seconds": ref["seconds"], "worst_leaves": where,
        "pairs_here_first_steps": [prog.get("check_pairs_here"),
                                   ref.get("pairs_here")],
        "model_stats": prog.get("model_stats"), "scopes": scopes,
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
        "device": device, "trace": trace, "scopes": scopes, "peak": peak,
        "chips": ctx["entry"]["chips"],
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

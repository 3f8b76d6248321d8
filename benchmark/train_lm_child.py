"""The child that holds the chip for a cell of the ``train_lm`` entry.

``train_child.py``'s shape: ONE ``mlapi_tpu.train.loop.fit`` call; the
step callable that ``make_train_step`` returns is wrapped (``fit`` has
no hook) to hand the first three steps to the reference, mark the
window, keep the host at most ``lag`` steps ahead of the device and end
the loop when the window closes. The object that was checked is the
object that is timed. Nothing here names a family: the reference module
(``config["reference"]``), the task (``config["task"]``) and the rows'
generator (``cell["traffic"]["generator"]``) come from the files. A
step may return values beside ``(params, opt_state, loss)`` (a
sparse-expert model's load a step, as device scalars): the wrapper
hands them on untouched, and sums those of the window's steps after
the window has closed.
"""

from __future__ import annotations

import collections
import importlib
import json
import sys
import time


B1 = 0.9  # Adam's first-moment decay, as the configuration states it


class WindowClosed(Exception):
    pass


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    cfg, cell, seed = job["config"], job["cell"], job["seed"]
    prog = cfg["program"]
    # first of all: a tree without the model stops here, in a second
    from mlapi_tpu.models import get_model

    model = get_model(prog["model"], **prog["model_kwargs"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from child_common import (CompileCounter, chip_or_exit, flatten,
                              memory_peak_bytes, unflatten)
    from mlapi_tpu.train import loop

    ref = importlib.import_module("reference." + cfg["reference"])
    compiles = CompileCounter()
    device = chip_or_exit(job)
    t = cell["traffic"]
    gen_module, gen_name = t["generator"].rsplit(".", 1)
    x, y = getattr(importlib.import_module(gen_module), gen_name)(
        t, seed, cfg["vocab_size"])

    class Splits:
        x_train, y_train = x, y
        x_test, y_test = x[:0], y[:0]
        extras = {"task": cfg["task"]}

    params0 = unflatten(ref.make_params(seed, cfg))
    out = {"losses": [], "check_stats": []}
    st = {"i": 0, "phase": "check", "t_start": None, "steps": 0,
          "lag": collections.deque(), "trace_on": False, "trace_t0": None}
    batches, window_stats = [], []
    warm_steps = job["warm_steps"]
    check_steps = 3

    norms = jax.jit(lambda tree: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree))

    @jax.jit
    def delta_norms(p, lo, hi):
        p0 = unflatten(ref.draw(ref.param_spec(cfg), ref.seed_key(lo, hi)))
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0)

    grad_sample = jax.jit(lambda mu: {
        k: ref.numerics.sample(v.astype(jnp.float32)) / (1 - B1)
        for k, v in flatten(mu).items()})

    def find_mu(opt_state):
        for part in jax.tree.leaves(
                opt_state, is_leaf=lambda s: hasattr(s, "mu")):
            if hasattr(part, "mu"):
                return part.mu
        raise RuntimeError("no Adam first moment in the optimizer state")

    original_make = loop.make_train_step
    if job.get("fault"):  # tests only: break the step UNDER the wrapper
        import faults_lm
        faults_lm.plant_train(job["fault"], loop)
    real_make = loop.make_train_step

    def make_train_step(*a, **kw):
        step = real_make(*a, **kw)

        def wrapped(params, opt_state, bx, by):
            i = st["i"]
            st["i"] += 1
            if st["phase"] == "window":
                now = time.time()
                if job["trace"] and not st["trace_on"] and st["trace_t0"] is None \
                        and now - st["t_start"] >= job["trace_after_s"]:
                    jax.profiler.start_trace(job["trace_dir"])
                    st["trace_on"], st["trace_t0"] = True, time.time()
                elif st["trace_on"] and time.time() - st["trace_t0"] >= job["trace_seconds"]:
                    jax.block_until_ready(st["lag"][-1])
                    jax.profiler.stop_trace()
                    st["trace_on"] = False
                    out["trace_window_s"] = time.time() - st["trace_t0"]
                if now - st["t_start"] >= job["seconds"]:
                    jax.block_until_ready(st["lag"][-1])
                    st["t_end"] = time.time()
                    out["compiles_in_window"] = compiles.since_mark()
                    raise WindowClosed
            if i == 0 and job["trace"]:
                st["shapes"] = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (params, opt_state, bx, by))
            params, opt_state, loss, *rest = step(params, opt_state, bx, by)
            if i < check_steps:
                batches.append((np.asarray(bx), np.asarray(by)))
                out["losses"].append(loss)
                out["check_stats"].extend(rest)
                if i == 0:
                    # Adam's first moment after one step is (1 - b1) * g:
                    # the first gradient as the optimizer got it
                    out["mu_norms"] = norms(find_mu(opt_state))
                    out["grad_sample"] = grad_sample(find_mu(opt_state))
                if i == check_steps - 1:
                    lo, hi = ref.split_seed(seed)
                    out["delta_norms"] = delta_norms(
                        params, jnp.int32(lo), jnp.int32(hi))
            if st["phase"] == "window":
                st["steps"] += 1
                window_stats.extend(rest)
                st["lag"].append(loss)
                if len(st["lag"]) > job["lag"]:
                    jax.block_until_ready(st["lag"].popleft())
            elif i + 1 >= warm_steps:
                # every shape is compiled and the checked steps are
                # behind us: drain the device and open the window
                jax.block_until_ready(loss)
                out["setup_compiles"] = compiles.count
                compiles.mark()
                st["phase"], st["t_start"] = "window", time.time()
                st["lag"].append(loss)
            return (params, opt_state, loss, *rest)

        wrapped.lower = st["lower"] = step.lower
        return wrapped

    loop.make_train_step = make_train_step
    try:
        loop.fit(
            model, Splits, steps=10 ** 9, batch_size=t["batch_size"],
            learning_rate=prog["learning_rate"], optimizer=prog["optimizer"],
            weight_decay=prog.get("weight_decay", 0.0),
            seed=seed & 0x7FFFFFFF, mesh=None, init_params=params0,
        )
    except WindowClosed:
        pass
    finally:
        loop.make_train_step = original_make
    if st["trace_on"]:
        jax.profiler.stop_trace()
    if job["trace"] and cfg.get("scopes"):
        # where each operation of the step lies among the model's
        # named scopes: the compiled program's own text says (the
        # trace does not); the window is closed, so this compile (a
        # hit in the persistent cache) costs the run nothing it reports
        import scope_time
        text = st["lower"](*st["shapes"]).compile().as_text()
        with open(job["op_scopes_path"], "w") as f:
            json.dump({"scopes": cfg["scopes"],
                       "op_names": scope_time.op_scopes_of(text)}, f)
    np.savez(job["batches_path"],
             **{f"x{i}": b[0] for i, b in enumerate(batches)},
             **{f"y{i}": b[1] for i, b in enumerate(batches)})
    np.savez(job["grad_path"], **jax.device_get(out["grad_sample"]))
    flat = lambda tree: {k: float(v) for k, v in flatten(tree).items()}

    def summed(stats: list) -> dict | None:
        """The steps' statistics: counts summed, a maximum kept, of a
        ratio (a float) the median step's."""
        if not stats:
            return None
        rows = jax.device_get(stats)
        total: dict = {"steps": len(rows)}
        for k in rows[0]:
            col = [row[k].item() for row in rows]
            total[k] = (float(np.median(col)) if isinstance(col[0], float)
                        else max(col) if k.endswith("_max") else sum(col))
        return total

    check = jax.device_get(out["check_stats"])
    result = {
        "device": device,
        "losses": [float(v) for v in out["losses"]],
        "mu_norms": flat(out["mu_norms"]),
        "delta_norms": flat(out["delta_norms"]),
        "t_window_start": st["t_start"], "t_window_end": st["t_end"],
        "steps": st["steps"],
        "model_stats": summed(window_stats),
        "check_pairs_here": [int(s["moe.pairs_here"]) for s in check
                             if "moe.pairs_here" in s],
        "setup_compiles": out.get("setup_compiles"),
        "compiles_in_window": out["compiles_in_window"],
        "compile_seconds": compiles.seconds(),
        "memory": memory_peak_bytes(),
        "trace_window_s": out.get("trace_window_s"),
    }
    with open(job["result_path"], "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

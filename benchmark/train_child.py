"""The child that holds the chip for a training cell.

Calls ``mlapi_tpu.train.loop.fit`` with the model and hyperparameters
the preset names — the loop, its host batch pipeline (``batch_at``)
and the jitted step are the program's. The one thing wrapped is the
step callable that ``make_train_step`` returns, because ``fit`` has no
hook: the wrapper sees each call's batch, loss and state (to hand the
first three steps to the reference), marks the window, keeps the host
at most ``lag`` steps ahead of the device, and ends the loop when the
window closes. The object that was checked is the object that is
timed: one ``fit`` call, one compiled step, one state.
"""

from __future__ import annotations

import collections
import json
import sys
import time


B1 = 0.9  # Adam's first-moment decay, as the configuration states it


class WindowClosed(Exception):
    pass


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from child_common import (CompileCounter, chip_or_exit, flatten,
                              memory_peak_bytes, mesh_or_none, unflatten)
    import traffic
    from reference import bert as ref

    from mlapi_tpu.models import get_model
    from mlapi_tpu.train import loop

    compiles = CompileCounter()
    device = chip_or_exit(job)
    cfg, cell, seed = job["config"], job["cell"], job["seed"]
    mesh = mesh_or_none(cfg)
    prog = cfg["program"]
    t = cell["traffic"]
    model = get_model(prog["model"], **prog["model_kwargs"])
    x, y = traffic.train_rows(t, seed, cfg["vocab_size"])

    class Splits:
        x_train, y_train = x, y
        x_test, y_test = x[:0], y[:0]
        extras = {"task": "classify"}

    params0 = unflatten(ref.make_params(seed, cfg))
    out = {"losses": []}
    st = {"i": 0, "phase": "check", "t_start": None, "steps": 0,
          "lag": collections.deque(), "trace_on": False, "trace_t0": None}
    batches = []
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
        import faults
        faults.plant_train(job["fault"], loop)
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
            params, opt_state, loss = step(params, opt_state, bx, by)
            if i < check_steps:
                batches.append((np.asarray(bx), np.asarray(by)))
                out["losses"].append(loss)
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
            return params, opt_state, loss

        wrapped.lower = step.lower
        return wrapped

    loop.make_train_step = make_train_step
    try:
        loop.fit(
            model, Splits, steps=10 ** 9, batch_size=t["batch_size"],
            learning_rate=prog["learning_rate"], optimizer=prog["optimizer"],
            weight_decay=prog.get("weight_decay", 0.0),
            seed=seed & 0x7FFFFFFF, mesh=mesh, init_params=params0,
        )
    except WindowClosed:
        pass
    finally:
        loop.make_train_step = original_make
    if st["trace_on"]:
        jax.profiler.stop_trace()
    np.savez(job["batches_path"],
             **{f"x{i}": b[0] for i, b in enumerate(batches)},
             **{f"y{i}": b[1] for i, b in enumerate(batches)})
    np.savez(job["grad_path"], **jax.device_get(out["grad_sample"]))
    flat = lambda tree: {k: float(v) for k, v in flatten(tree).items()}
    result = {
        "device": device,
        "losses": [float(v) for v in out["losses"]],
        "mu_norms": flat(out["mu_norms"]),
        "delta_norms": flat(out["delta_norms"]),
        "t_window_start": st["t_start"], "t_window_end": st["t_end"],
        "steps": st["steps"],
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

"""The plain reference of a ``train_lm`` cell, in a process of its own,
once the program's child has gone (its state freed, its memory peak
read). Imports nothing of the program; the reference module is the one
the configuration's file names. ``python reference_lm_child.py
<job.json>``."""

from __future__ import annotations

import importlib
import json
import sys
import time


def train(job: dict) -> dict:
    import numpy as np

    ref = importlib.import_module("reference." + job["config"]["reference"])
    z = np.load(job["batches_path"])
    n = len([k for k in z.files if k.startswith("x")])
    batches = [(z[f"x{i}"], z[f"y{i}"]) for i in range(n)]
    params = ref.make_params(job["seed"], job["config"])
    # ``seed``: the state is updated in place (at published widths two
    # copies of it do not fit beside the gradient)
    out = ref.train_steps(params, batches, job["config"],
                          precision=job.get("precision", "float32"),
                          fault=job.get("reference_fault"), seed=job["seed"])
    np.savez(job["reference_grad_path"], **out.pop("grad_sample"))
    return out


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    import jax

    t0 = time.time()
    out = train(job)
    out["seconds"] = time.time() - t0
    out["device"] = {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind}
    with open(job["result_path"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

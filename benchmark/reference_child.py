"""The plain reference, in a process of its own, once the program's
child has gone (its state freed, its memory peak read). Imports
nothing of the program. ``python reference_child.py <job.json>``."""

from __future__ import annotations

import json
import sys
import time


def train(job: dict) -> dict:
    import numpy as np

    from reference import bert

    z = np.load(job["batches_path"])
    n = len([k for k in z.files if k.startswith("x")])
    batches = [(z[f"x{i}"], z[f"y{i}"]) for i in range(n)]
    params = bert.make_params(job["seed"], job["config"])
    out = bert.train_steps(params, batches, job["config"],
                           precision=job.get("precision", "float32"),
                           block=job.get("block", 32),
                           fault=job.get("reference_fault"))
    np.savez(job["reference_grad_path"], **out.pop("grad_sample"))
    return out


def generate(job: dict) -> dict:
    from reference import gpt2

    params = gpt2.make_params(job["seed"], job["config"])
    rows = [(r["prompt"], r["served"]) for r in job["rows"]]
    served, _ = gpt2.served_gaps(
        params, rows, job["config"], pad_to=job["pad_to"],
        block=job.get("block", 8))
    out = {"served_gaps": served, "control_gaps": {}}
    # only tools/control_serve.py asks for controls; a run never does
    for precision in job.get("controls") or ():
        _, gaps = gpt2.served_gaps(
            params, rows, job["config"], pad_to=job["pad_to"],
            block=job.get("block", 8), control=precision)
        out["control_gaps"][precision] = gaps
    return out


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    import jax

    t0 = time.time()
    out = {"train": train, "generate": generate}[job["kind"]](job)
    out["seconds"] = time.time() - t0
    out["device"] = {"platform": jax.devices()[0].platform,
                     "kind": jax.devices()[0].device_kind}
    with open(job["result_path"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""``control_train.py`` for a cell of the ``train_lm`` entry: what the
program, the CONTROL and the planted faults do to the numbers that
decide ``correct``, each through the run's own ``compare`` against the
cell's limits. Not part of a benchmark run. The reference module, the
task and the rows' generator are the ones the configuration's and the
cell's files name.

    python benchmark/tools/control_train_lm.py <cell> [--tiny] [--program]
        [--what int8_all,drop_half,state_unchanged] [--what-on N] <seed> ...

One process for all seeds: the program's seeds first
(``train_lm_child.main`` with the job a run would give it and a
one-second window: the same ``fit`` call, the same wrapped step; its
first three batches are then the rows every other reading follows),
then the float32 reference and, for each name of ``--what``, the
reference put in the program's place: ``int8_all`` (the CONTROL: every
projection's product on the int8 grid, forward and backward) or a fault
(``drop_half``, ``state_unchanged``). Also prints how many (token,
expert) pairs went to a held expert in each of the three steps, program
beside reference: selections that flipped near a tie are the difference.
Prints one JSON line per reading. ``--tiny`` runs the rehearsal widths
on the CPU.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

FAULTS = ("drop_half", "state_unchanged")


def load(cell_name: str, tiny: bool):
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", "train_lm.py"),
        "driver_train_lm")
    cell = harness.load_json("workloads", cell_name + ".json")
    config = harness.load_json("configs", cell["config"] + ".json")
    cell, config = driver.sized(cell, config, tiny)
    return driver, cell, config


def program(driver, cell_name: str, cell: dict, config: dict, seed: int,
            tiny: bool) -> tuple[dict, list]:
    """The timed path, in this process: what a run's child reports,
    and the batches its first three steps were fed."""
    import numpy as np

    import train_lm_child

    work = os.path.join(harness.CACHE, "run", cell_name + ".control")
    os.makedirs(work, exist_ok=True)
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0, rehearse=tiny)
    job = driver.make_job(args, cell, config, work)
    job["fault"] = None
    job_path = os.path.join(work, "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    train_lm_child.main(job_path)
    gc.collect()
    with open(job["result_path"]) as f:
        prog = json.load(f)
    prog["grad_sample"] = driver.load_samples(job["grad_path"])
    with np.load(job["batches_path"]) as z:
        batches = [(z[f"x{i}"], z[f"y{i}"]) for i in range(3)]
    return prog, batches


def readings(cell_name: str, seed: int, tiny: bool, what: list[str],
             prog_and_batches=None) -> list:
    """One line per reading of one seed: the program (where
    ``prog_and_batches`` brings it) and each name of ``what``, through
    the run's own ``compare``."""
    driver, cell, cfg = load(cell_name, tiny)
    ref_mod = importlib.import_module("reference." + cfg["reference"])
    t = cell["traffic"]
    b = t["batch_size"]
    rows = []
    if prog_and_batches:
        prog, batches = prog_and_batches
        rows.append(("program", prog))
    else:
        gen_module, gen_name = t["generator"].rsplit(".", 1)
        x, y = getattr(importlib.import_module(gen_module), gen_name)(
            t, seed, cfg["vocab_size"])
        batches = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b])
                   for i in range(3)]

    def follow(**kw):
        # the state is updated in place: fresh weights for each reading
        return ref_mod.train_steps(ref_mod.make_params(seed, cfg), batches,
                                   cfg, seed=seed, **kw)

    ref = follow()
    for name in what:
        got = follow(**({"fault": name} if name in FAULTS
                        else {"precision": name}))
        rows.append((name, {
            "losses": got["losses"], "delta_norms": got["delta_norms"],
            "grad_sample": got["grad_sample"],
            "check_pairs_here": got.get("pairs_here"),
            "mu_norms": {k: v * (1 - driver.B1)
                         for k, v in got["grad_norms"].items()}}))
    out = []
    for name, as_prog in rows:
        checks, where = driver.compare(as_prog, ref, cell["limits"])
        out.append({
            "seed": seed, "reading": name, "correct": checks.ok,
            "checks": checks.as_dict(), **where.pop("readings"),
            **{k: v for k, v in where.items() if k.endswith("leaf")},
            "pairs_here": [as_prog.get("check_pairs_here"),
                           ref.get("pairs_here")]})
    return out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser("control_train_lm.py")
    ap.add_argument("cell")
    ap.add_argument("seeds", nargs="+", type=int)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--what", default="int8_all,drop_half,state_unchanged")
    ap.add_argument("--what-on", type=int, default=None, metavar="N",
                    help="read --what on the first N seeds only")
    a = ap.parse_args(argv[1:])
    for k, v in harness.cache_env(os.environ).items():
        if k in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR"):
            os.environ.setdefault(k, v)
    if a.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    what = [w for w in a.what.split(",") if w]
    progs = {}
    if a.program:
        # the program's seeds first, then its compiled step is let go
        # before the float32 reference asks for the chip's memory
        driver, cell, cfg = load(a.cell, a.tiny)
        for seed in a.seeds:
            progs[seed] = program(driver, a.cell, cell, cfg, seed, a.tiny)
            print(f"program seed {seed} done", file=sys.stderr, flush=True)
        import jax
        jax.clear_caches()
        gc.collect()
    for n, seed in enumerate(a.seeds):
        todo = what if a.what_on is None or n < a.what_on else []
        for row in readings(a.cell, seed, a.tiny, todo, progs.get(seed)):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

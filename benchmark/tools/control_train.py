"""Read, on the chip at the cell's own size, what the program, the
CONTROLS and the planted faults of a training cell do to the numbers
that decide ``correct`` (steps 3 and 4 of "How correct is decided"),
each through the run's own ``compare`` against the cell's limits, so
that every reading ends as ``correct`` true or false. Not part of a
benchmark run.

    python benchmark/tools/control_train.py <cell> [--tiny] [--program]
        [--what int8_all,int8,drop_half,state_unchanged] [--what-on N]
        [--by-leaf] <seed> [<seed> ...]

One process for all seeds (the step compiles once): the program's
seeds first, then the references. For each seed:

- with ``--program``, the timed path itself: ``train_child.main`` with
  the job a run would give it and a one-second window (the same
  ``fit`` call, the same wrapped step); its first three batches are
  then the rows every other reading follows;
- the float32 reference follows the three steps;
- each name of ``--what`` is the reference put in the program's
  place: ``int8_all`` (the CONTROL: every product of the step on the
  int8 grid), ``int8`` (the forward products only), or a fault
  (``drop_half``, ``state_unchanged``).

Prints one JSON line per reading. ``--tiny`` runs the rehearsal widths
on the CPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import traffic  # noqa: E402

FAULTS = ("drop_half", "state_unchanged")


def load(cell_name: str, tiny: bool):
    train = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", "train.py"), "driver_train")
    cell = harness.load_json("workloads", cell_name + ".json")
    config = harness.load_json("configs", cell["config"] + ".json")
    cell, config = train.sized(cell, config, tiny)
    return train, cell, config


def program(train, cell_name: str, cell: dict, config: dict, seed: int,
            tiny: bool) -> tuple[dict, list]:
    """The timed path, in this process: what a run's child reports,
    and the batches its first three steps were fed."""
    import numpy as np

    import train_child

    work = os.path.join(harness.CACHE, "run", cell_name + ".control")
    os.makedirs(work, exist_ok=True)
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0, rehearse=tiny)
    job = train.make_job(args, cell, config, work)
    job["fault"] = None
    job_path = os.path.join(work, "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    train_child.main(job_path)
    gc.collect()
    with open(job["result_path"]) as f:
        prog = json.load(f)
    prog["grad_sample"] = train.load_samples(job["grad_path"])
    with np.load(job["batches_path"]) as z:
        batches = [(z[f"x{i}"], z[f"y{i}"]) for i in range(3)]
    return prog, batches


def readings(cell_name: str, seed: int, tiny: bool, what: list[str],
             prog_and_batches=None, by_leaf: bool = False) -> list:
    """One line per reading of one seed: the program (where
    ``prog_and_batches`` brings it) and each name of ``what``, through
    the run's own ``compare``."""
    from reference import bert

    train, cell, cfg = load(cell_name, tiny)
    t = cell["traffic"]
    b = t["batch_size"]
    rows = []
    if prog_and_batches:
        prog, batches = prog_and_batches
        rows.append(("program", prog))
    else:
        x, y = traffic.train_rows(t, seed, cfg["vocab_size"])
        batches = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b])
                   for i in range(3)]
    params = bert.make_params(seed, cfg)
    block = min(cell.get("reference_block", 32), b // 2)
    ref = bert.train_steps(params, batches, cfg, block=block)
    for name in what:
        kw = {"fault": name} if name in FAULTS else {"precision": name}
        got = bert.train_steps(params, batches, cfg, block=block, **kw)
        rows.append((name, {
            "losses": got["losses"], "delta_norms": got["delta_norms"],
            "grad_sample": got["grad_sample"],
            "mu_norms": {k: v * (1 - train.B1)
                         for k, v in got["grad_norms"].items()}}))
    out = []
    for name, as_prog in rows:
        checks, where = train.compare(as_prog, ref, cell["limits"])
        row = {"seed": seed, "reading": name, "correct": checks.ok,
               "checks": checks.as_dict(), **where.pop("readings"),
               **{k: v for k, v in where.items() if k.endswith("leaf")}}
        if by_leaf:
            row["grad_dir_by_leaf"] = train.readings(
                as_prog, ref, by_leaf=True)[1]["grad_dir_by_leaf"]
        out.append(row)
    return out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser("control_train.py")
    ap.add_argument("cell")
    ap.add_argument("seeds", nargs="+", type=int)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--what", default="int8_all,drop_half,state_unchanged")
    ap.add_argument("--what-on", type=int, default=None, metavar="N",
                    help="read --what on the first N seeds only")
    ap.add_argument("--by-leaf", action="store_true",
                    help="add every leaf's grad_dir_gap to each line")
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
        train, cell, cfg = load(a.cell, a.tiny)
        for seed in a.seeds:
            progs[seed] = program(train, a.cell, cell, cfg, seed, a.tiny)
            print(f"program seed {seed} done", file=sys.stderr, flush=True)
        import jax
        jax.clear_caches()
        gc.collect()
    for n, seed in enumerate(a.seeds):
        todo = what if a.what_on is None or n < a.what_on else []
        for row in readings(a.cell, seed, a.tiny, todo, progs.get(seed),
                            a.by_leaf):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Find the highest rate an open-loop serving cell sustains, once,
when the cell is defined (not part of a benchmark run):

    python benchmark/tools/sweep_rate.py <cell> <seconds> <seed> <rate> [<rate> ...]

Writes the table to ``benchmark/sweeps/<cell>.json``; the cell's file
then gets four fifths of the knee as its fixed ``rate_per_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402


def main(argv: list[str]) -> int:
    cell_name, seconds, seed = argv[1], float(argv[2]), int(argv[3])
    rates = [float(r) for r in argv[4:]]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {w["name"]: w for w in bench["workloads"]}[cell_name]
    cell = harness.load_json("workloads", cell_name + ".json")
    config = harness.load_json("configs", entry["config"] + ".json")
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", config["entry"] + ".py"),
        "driver_" + config["entry"])
    args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                              trace=0, rehearse=False)
    table = driver.sweep({"t0": harness.now(), "args": args, "entry": entry,
                          "cell": cell, "config": config}, rates, seconds)
    out = os.path.join(harness.BENCH_DIR, "sweeps")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, cell_name + ".json"), "w") as f:
        json.dump({"cell": cell_name, "seed": seed, "table": table}, f,
                  indent=2)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Read, on the chip at the cell's own size and load, what the CONTROL
of a serving cell reads (step 3 of "How correct is decided"). Not part
of a benchmark run.

    python benchmark/tools/control_serve.py <cell> <seconds> <seed> [<seed> ...]

For each seed: one short run of the cell through its own driver (the
same server, warm-up, load and sample of finished requests); the
reference then scores, at every position of the same prompts and
served tokens, the token that the lower precision (``int8``,
``float8_e4m3fn``) would have put first. Prints one JSON line per
seed: the program's widest served gap and each control's.
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
    cell_name, seconds = argv[1], float(argv[2])
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {w["name"]: w for w in bench["workloads"]}[cell_name]
    cell = harness.load_json("workloads", cell_name + ".json")
    config = harness.load_json("configs", entry["config"] + ".json")
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", config["entry"] + ".py"),
        "driver_" + config["entry"])
    for seed in (int(s) for s in argv[3:]):
        args = argparse.Namespace(workload=cell_name, seed=seed,
                                  seconds=seconds, trace=0, rehearse=False)
        run = driver.run({
            "t0": harness.now(), "args": args, "entry": entry, "cell": cell,
            "config": config, "peaks": harness.load_json("peaks.json"),
            "controls": ["int8", "float8_e4m3fn"]})
        ref = run["reference"]
        print(json.dumps({
            "seed": seed, "requests": len(ref["served_gaps"]),
            "served_logit_gap": max(ref["served_gaps"]),
            **{f"control_{k}": max(v)
               for k, v in ref["control_gaps"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

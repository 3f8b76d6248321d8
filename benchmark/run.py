"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = one cell, measured once: set up, warm up, measure for
``--seconds``, check the outputs against the plain reference, print
one JSON line last. This process never imports jax (a chip belongs to
one process); the work runs in children that the cell's driver
(``drivers/<entry>.py``) starts one after another.

Everything that belongs to one cell, configuration or per-layer metric
is a file found by the name in ``BENCHMARK.json``:
``workloads/<cell>.json``, ``configs/<config>.json``,
``metrics/<metric>.py``, ``drivers/<entry>.py`` (see README.md).

``--rehearse`` is the CPU rehearsal: tiny widths from the
configuration's ``rehearsal`` block, the same control flow, exit code
3, never a chip result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def per_layer(bench: dict, cell_name: str, run: dict) -> dict:
    """Every per-layer metric that lists this cell (or lists none),
    read by its own ``metrics/<name>.py``. A reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        path = os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py")
        reader = harness.load_module(path, "metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths; exit code 3")
    args = ap.parse_args(argv)
    t0 = harness.now()

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell_dir = os.path.join(harness.BENCH_DIR, "workloads")
    if args.rehearse and os.environ.get("BENCH_TEST_CELLS"):
        # tests only: a cell file of the tests' own, not listed in
        # BENCHMARK.json, so that a driver no cell uses yet stays tried
        cell_dir = os.environ["BENCH_TEST_CELLS"]
        with open(os.path.join(cell_dir, args.workload + ".json")) as f:
            cells = {args.workload: {"name": args.workload, "chips": 1,
                                     "config": json.load(f)["config"]}}
    if args.workload not in cells:
        print(f"benchmark: no cell {args.workload!r} in BENCHMARK.json; "
              f"cells: {sorted(cells)}", file=sys.stderr)
        return 2
    entry = cells[args.workload]
    cell = harness.load_json(cell_dir, args.workload + ".json")
    config = harness.load_json("configs", entry["config"] + ".json")
    peaks = harness.load_json("peaks.json")
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", config["entry"] + ".py"),
        "driver_" + config["entry"])

    run = driver.run({
        "t0": t0, "args": args, "entry": entry, "cell": cell,
        "config": config, "peaks": peaks,
    })

    e2e = {}
    for m in bench["end_to_end"]:
        if "workloads" in m and args.workload not in m["workloads"]:
            continue
        if run["end_to_end"].get(m["name"]) is not None:
            e2e[m["name"]] = {"value": float(run["end_to_end"][m["name"]]),
                              "unit": m["unit"]}
    line = {
        "correct": bool(run["checks"].ok) and not args.rehearse,
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": per_layer(bench, args.workload, run) if args.trace else e2e,
        "device": run["device"],
    }
    if args.trace and run.get("trace"):
        line["breakdown"] = run["trace"]["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    line["checks"] = run["checks"].as_dict()
    run["checks"].print()
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())

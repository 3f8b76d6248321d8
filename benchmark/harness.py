"""What every driver and child of the benchmark shares: where things
are, how a child is run to its end, the device gate, quantiles, and
the result line. The parent side imports no jax."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE = os.path.join(BENCH_DIR, ".cache")


class NoChip(SystemExit):
    """JAX found no accelerator, too few chips, or a device that is
    not in ``peaks.json``: exit code 3, no result line."""

    def __init__(self, why: str):
        print(f"benchmark: {why}", file=sys.stderr)
        super().__init__(3)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cache_env(env: dict) -> dict:
    """The environment a child gets: the compile cache at the place
    ``JAX_COMPILATION_CACHE_DIR`` names, else at the program's own
    fixed path inside the checkout (``.jax_compile_cache``); nothing
    under a temporary name."""
    env = dict(env)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_compile_cache"))
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONPATH"] = os.pathsep.join(
        [BENCH_DIR, ROOT] + [p for p in env.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    env.pop("BENCH_RUN", None)
    return env


def run_child(argv: list[str], *, env: dict, timeout: float,
              log_path: str) -> int:
    """One child to its end; its output goes to ``log_path``."""
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    with open(log_path, "w") as log:
        p = subprocess.Popen([sys.executable, *argv], cwd=ROOT, env=env,
                             stdout=log, stderr=subprocess.STDOUT)
        try:
            return p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            return 124


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def device_gate(device: dict, chips: int, peaks: dict, rehearse: bool) -> dict:
    """The peaks entry of the device the child ran on, or NoChip."""
    if rehearse:
        return next(iter(peaks["devices"].values()))
    if device.get("platform") != "tpu":
        raise NoChip(f"ran on {device!r}: no accelerator")
    if device.get("count", 0) < chips:
        raise NoChip(f"{device.get('count')} chips visible, cell needs {chips}")
    peak = peaks["devices"].get(device.get("kind"))
    if peak is None:
        raise NoChip(f"device kind {device.get('kind')!r} is not in peaks.json")
    return peak


def cell_peak(run: dict) -> dict:
    """The peak rates of all the chips the cell runs on: what a reader
    divides by where its work is the whole step's (the global batch's)
    and its time one device's (``trace_reduce`` times are per device
    plane). One chip's ``peaks.json`` entry times ``run["chips"]``."""
    return {k: v * run["chips"] for k, v in run["peak"].items()}


def rehearsal_env(env: dict, chips: int) -> dict:
    """A CPU rehearsal's child environment: the CPU platform, with as
    many virtual devices as the cell has chips unless ``XLA_FLAGS``
    already sets a count."""
    env = dict(env, JAX_PLATFORMS="cpu")
    flag = "--xla_force_host_platform_device_count"
    if chips > 1 and flag not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {flag}={chips}".strip()
    return env


def nearest_rank(sorted_vals: list, q: float):
    """Nearest-rank quantile of an ascending list (copied from
    ``mlapi_tpu/utils/metrics.py``): the smallest value with at least
    ``q`` of the sample at or below it."""
    if not sorted_vals:
        return None
    k = max(1, math.ceil(q * len(sorted_vals)))
    return sorted_vals[min(k, len(sorted_vals)) - 1]


def reduce_trace(trace_dir: str, env: dict) -> dict | None:
    """Reduce the trace in a process of its own, off the chip."""
    out = os.path.join(trace_dir, "reduced.json")
    e = dict(env)
    e["JAX_PLATFORMS"] = "cpu"
    rc = run_child([os.path.join(BENCH_DIR, "trace_reduce.py"), trace_dir, out],
                   env=e, timeout=240,
                   log_path=os.path.join(CACHE, "logs", "trace_reduce.log"))
    if rc != 0:
        print("benchmark: trace reduction failed:\n"
              + tail(os.path.join(CACHE, "logs", "trace_reduce.log")),
              file=sys.stderr)
        return None
    with open(out) as f:
        return json.load(f)


class Checks:
    """The numbers compared for ``correct``, each beside its limit."""

    def __init__(self):
        self.rows: list[dict] = []

    def add(self, name: str, value, limit, *, exact: bool = False) -> None:
        ok = (value is not None and not (isinstance(value, float)
                                         and math.isnan(value))
              and (value == limit if exact else value <= limit))
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self) -> dict:
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def print(self) -> None:
        for r in self.rows:
            print(f"check {r['name']}: value {r['value']!r} limit "
                  f"{r['limit']!r} {'ok' if r['ok'] else 'FAILED'}",
                  file=sys.stderr)


def now() -> float:
    return time.time()

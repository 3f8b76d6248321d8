"""Device self-time of a traced training run by ``jax.named_scope``.

The chip's trace names an operation by its HLO text and carries no
scope (looked at on a v5e, PR 29: an ``XLA Ops`` event's stats are its
offset and duration only), and ``trace_reduce.reduce`` keeps the HLO
name alone. The scope of an operation is in the COMPILED program's
text, as each instruction's ``metadata={op_name="jit(step)/.../kda/
while/body/..."}``: the child that held the chip writes that map
(``op_scopes.json``: HLO instruction name -> ``op_name``) after its
window has closed, and this file joins it with the raw trace, read
with ``jax.profiler.ProfileData`` and nothing else.

    python scope_time.py <trace dir> <out.json>   (reads <trace dir>/../op_scopes.json
                                                   and the scopes named there)

An operation counts towards every scope on its path (``kda.core`` lies
inside ``kda``): forward, recomputation and backward of a scope all
carry its name (``jvp(kda)``, ``rematted_computation/kda``,
``transpose(jvp(...))/checkpoint/kda``). Self time, as
``trace_reduce.self_times``: a ``while`` spans the operations of its
body, and every nanosecond is counted once. Stays off the accelerator.
"""

from __future__ import annotations

import json
import os
import re
import sys

import trace_reduce

_WRAPPED = re.compile(r"^(?:[\w.\-]+\()+|\)+$")


def path_scopes(op_name: str, wanted: set) -> set:
    """The wanted scopes among the components of one ``op_name``:
    ``jit(step)/jvp(kda)/kda.core/while`` -> ``{"kda", "kda.core"}``."""
    found = set()
    for part in op_name.split("/"):
        part = _WRAPPED.sub("", part)
        if part in wanted:
            found.add(part)
    return found


def op_scopes_of(hlo_text: str) -> dict:
    """HLO instruction name -> ``op_name`` for every instruction of a
    compiled program's text that carries one."""
    rx = re.compile(
        r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*metadata=\{[^}]*op_name="([^"]*)"',
        re.M)
    return dict(rx.findall(hlo_text))


def reduce(trace_path: str, op_names: dict, wanted: list,
           step_pattern: str = r"^jit_step\(") -> dict:
    """``{"steps": executions of the step program in the trace,
    "seconds": {scope: device self-seconds}, "unscoped_s": ...,
    "mapped_share": share of device self-time whose operation the map
    knows}``; averaged over the device planes."""
    loaded = trace_reduce.load(trace_path)
    devices = loaded["devices"]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    want = set(wanted)
    cache: dict = {}
    seconds = {s: 0.0 for s in wanted}
    total = mapped = unscoped = 0.0
    steps = 0
    rx = re.compile(step_pattern)
    for d in devices.values():
        steps += sum(1 for name, _, _ in d["modules"] if rx.search(name))
        for name, _, _, own in trace_reduce.self_times(d["ops"]):
            k = trace_reduce.op_name(name)
            total += own
            if k not in op_names:
                continue
            mapped += own
            if k not in cache:
                cache[k] = path_scopes(op_names[k], want)
            for s in cache[k]:
                seconds[s] += own
            if not cache[k]:
                unscoped += own
    n = len(devices)
    return {"steps": steps / n,
            "seconds": {s: v / n for s, v in seconds.items()},
            "unscoped_s": unscoped / n, "total_s": total / n,
            "mapped_share": mapped / total if total else None}


def main(argv: list[str]) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    trace_dir, dst = argv[1], argv[2]
    with open(os.path.join(os.path.dirname(trace_dir.rstrip("/")),
                           "op_scopes.json")) as f:
        m = json.load(f)
    out = reduce(trace_dir, m["op_names"], m["scopes"])
    with open(dst, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

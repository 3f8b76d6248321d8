"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy union and idle share, device time per program
and per operation, and the longest idle gaps by what the host was
doing. Read with ``jax.profiler.ProfileData`` and nothing else.

Run as a program it stays off the accelerator (it forces the CPU
platform before importing jax): ``python trace_reduce.py <trace dir or
.xplane.pb> <out.json>``. A chip belongs to one process, so the
harness reduces the trace in a process of its own once the traced
child has gone.

Trace layout on a TPU (looked at by hand, PR 26): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per execution of a compiled program, named ``jit_<fn>(<hash>)``),
``XLA Ops`` (one event per HLO operation; the name is the HLO text and
starts with ``%<op name> = ``) and ``Async XLA Ops``; the host is the
plane ``/host:CPU`` with one line per thread. Times are nanoseconds.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# The profiler's own start and stop calls span the untraced time
# before and after the window: they are not part of it.
_NOT_WINDOW = re.compile(r"start_trace|stop_trace|profiler\.py")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: operations that differ only in the
    compiler's running number are one entry of a breakdown."""
    return re.sub(r"[.\-_]?\d+$", "", name) or name


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def load(path: str) -> dict:
    """The trace as plain lists: ``{"devices": {plane: {"ops": [(name,
    start, end)], "modules": [...]}}, "host": {thread: [(name, start,
    end)]}}``, times in seconds."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    devices, host = {}, {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    d[key].append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = host.setdefault(line.name, [])
                for e in line.events:
                    s = e.start_ns * 1e-9
                    evs.append((e.name, s, s + e.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def _host_index(host: dict):
    """Host events as arrays (names, starts, ends) for the overlap
    search; the profiler's own calls are dropped."""
    import numpy as np

    names, starts, ends = [], [], []
    for thread, evs in host.items():
        t = thread.split("/")[0]
        for name, s, e in evs:
            if not _NOT_WINDOW.search(name):
                names.append(f"{t}:{name}")
                starts.append(s)
                ends.append(e)
    return names, np.asarray(starts), np.asarray(ends)


def _gap_owner(gap: tuple[float, float], index) -> str:
    """The most specific host event that covers at least half the gap:
    the shortest one among those overlapping it by half or more."""
    import numpy as np

    names, starts, ends = index
    if not names:
        return "host:untraced"
    a, b = gap
    overlap = np.minimum(ends, b) - np.maximum(starts, a)
    ok = overlap >= 0.5 * (b - a)
    if not ok.any():
        return "host:untraced"
    length = np.where(ok, ends - starts, np.inf)
    return names[int(np.argmin(length))]


def self_times(events: list) -> list:
    """``(name, start, end, self_seconds)`` per event of ONE line.
    Events of a line nest (a ``while`` spans the operations of its
    body): an event's self time is its duration minus its children's,
    so that sums over operations count every nanosecond once."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    while stack:
        out.append(tuple(stack.pop()))
    return out


def reduce(trace: dict, *, top: int = 10, gaps_examined: int = 200) -> dict:
    """Busy union, idle share, per-program and per-operation device
    time, and the idle gaps by owner. ``busy_s`` is averaged over the
    device planes; gaps are read on the first plane. A program is
    keyed by its full event name (``jit__run(<hash>)``: the program's
    jitted functions share one Python name today, the hash tells them
    apart) and carries the self time of the operation families that
    ran inside its executions, so that a metric can pick programs by
    what they contain."""
    import bisect

    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    starts, ends = [], []
    for d in devices.values():
        for _, s, e in d["ops"] + d["modules"]:
            starts.append(s)
            ends.append(e)
    for evs in trace["host"].values():
        for name, s, e in evs:
            if not _NOT_WINDOW.search(name):
                starts.append(s)
                ends.append(e)
    if not starts:
        raise ValueError("the trace holds no event")
    t0, t1 = min(starts), max(ends)
    busy_each, per_op, per_module = [], {}, {}
    first_busy = None
    for plane in sorted(devices):
        d = devices[plane]
        merged = union([(s, e) for _, s, e in d["ops"]])
        if first_busy is None:
            first_busy = merged
        busy_each.append(sum(e - s for s, e in merged))
        mods = sorted(d["modules"], key=lambda ev: ev[1])
        mod_starts = [m[1] for m in mods]
        for name, s, e in mods:
            acc = per_module.setdefault(
                name, {"seconds": 0.0, "count": 0, "ops": {}})
            acc["seconds"] += e - s
            acc["count"] += 1
        for name, s, e, own in self_times(d["ops"]):
            k = op_name(name)
            acc = per_op.setdefault(k, [0.0, 0, name])
            acc[0] += own
            acc[1] += 1
            i = bisect.bisect_right(mod_starts, s) - 1
            if i >= 0 and s < mods[i][2]:
                ops = per_module[mods[i][0]]["ops"]
                fam = op_family(k)
                ops[fam] = ops.get(fam, 0.0) + own
    n = len(devices)
    busy = sum(busy_each) / n
    families: dict[str, float] = {}
    for k, (sec, _, _) in per_op.items():
        families[op_family(k)] = families.get(op_family(k), 0.0) + sec / n
    gaps = []
    edges = [(t0, t0)] + list(first_busy or []) + [(t1, t1)]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 > e0:
            gaps.append((e0, s1))
    gaps.sort(key=lambda g: g[0] - g[1])
    owners: dict[str, float] = {}
    index = _host_index(trace["host"])
    for g in gaps[:gaps_examined]:
        who = _gap_owner(g, index)
        owners[who] = owners.get(who, 0.0) + (g[1] - g[0])
    rest = sum(b - a for a, b in gaps[gaps_examined:])
    if rest > 0:
        owners["(shorter gaps, not attributed)"] = rest
    return {
        "window_s": t1 - t0,
        "busy_s": busy,
        "idle_share": 1.0 - busy / (t1 - t0) if t1 > t0 else None,
        "device_planes": n,
        "ops": {k: {"seconds": v[0] / n, "count": v[1], "text": v[2][:400]}
                for k, v in per_op.items()},
        "modules": {k: {"seconds": v["seconds"] / n, "count": v["count"],
                        "ops": {f: sec / n for f, sec in v["ops"].items()}}
                    for k, v in per_module.items()},
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                families.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                owners.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def pattern_time(reduced: dict, table: str, pattern: str, *,
                 has_op: str | None = None,
                 lacks_op: str | None = None) -> tuple[float, int]:
    """Device seconds and event count of the entries of ``table``
    (``"ops"`` or ``"modules"``) whose name — for an operation also
    its HLO text — matches the regular expression ``pattern``. For
    programs, ``has_op`` / ``lacks_op`` keep those in whose executions
    an operation family matching the expression ran / did not run.
    (0, 0) when nothing matches."""
    rx = re.compile(pattern)
    has = re.compile(has_op) if has_op else None
    lacks = re.compile(lacks_op) if lacks_op else None
    sec, cnt = 0.0, 0
    for k, v in reduced[table].items():
        if not (rx.search(k) or (table == "ops"
                                 and rx.search(v.get("text", "")))):
            continue
        inside = v.get("ops", {})
        if has and not any(has.search(f) for f in inside):
            continue
        if lacks and any(lacks.search(f) for f in inside):
            continue
        sec += v["seconds"]
        cnt += v["count"]
    return sec, cnt


def main(argv: list[str]) -> int:
    src, dst = argv[1], argv[2]
    out = reduce(load(src))
    with open(dst, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

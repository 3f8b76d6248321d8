"""Device milliseconds a train step spent in bare layout operations:
the trace's operations whose NAME is ``copy``, ``reshape`` or
``transpose`` with at most the compiler's running number, their self
time summed, over the step program's executions. It falls when what
XLA sees between a layer's projections and a kernel needs no other
layout than the projections' own.

By name and not by HLO text: a fusion's text may hold a ``copy`` and
still do arithmetic. None where the trace holds no step; 0.0 where it
holds steps and no such operation."""

import re

import harness
import trace_reduce

P = harness.load_json("metrics", "layout_device_ms.train.json")


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    _, steps = trace_reduce.pattern_time(tr, "modules", P["step"])
    if not steps:
        return None
    layout = re.compile(P["layout"])
    seconds = sum(op["seconds"] for name, op in tr[P["table"]].items()
                  if layout.match(name))
    return 1e3 * seconds / steps

"""Pallas kernel executions a train step: for every delta-rule or flash
kernel of the compiled step (an operation name of the trace), its
events over the step program's executions, to the nearest whole number,
summed. A count, not a time: it falls when a block's recomputation
keeps what its forward kernels made instead of running them again.

Whole numbers a kernel, because the step is one static program and the
traced window opens inside a step: that step's program event is counted
and the kernels that ran before the trace began are not (two of them
in this cell), so events over executions read 10.875 for 11. None
where the trace holds no such kernel or no step."""

import re

import harness
import trace_reduce

P = harness.load_json("metrics", "kernel_execs_per_step.train.json")


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    _, steps = trace_reduce.pattern_time(tr, "modules", P["step"])
    if not steps:
        return None
    kernel = re.compile(P["kernels"])
    execs = sum(round(op["count"] / steps)
                for name, op in tr[P["table"]].items() if kernel.search(name))
    return execs or None

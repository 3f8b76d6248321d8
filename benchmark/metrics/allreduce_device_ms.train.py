"""Device milliseconds a train step, one device's, in the step
program's all-reduce operations: the self time of the operation
families the JSON's ``families`` matches (``all-reduce``, and the
asynchronous ``all-reduce-start`` / ``all-reduce-done``) inside the
executions of the step program, over those executions. On a data
mesh this is the gradient all-reduce that GSPMD inserts; it falls
where the exchange is hidden behind the backward pass or made
smaller.

A time and not a roofline share: an all-reduce that overlaps other
work has a self time that is not its transfer time. None where the
trace holds no step or no all-reduce (a step on one device has none)."""

import re

import harness

P = harness.load_json("metrics", "allreduce_device_ms.train.json")


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step = re.compile(P["step"])
    fam = re.compile(P["families"])
    seconds, steps = 0.0, 0.0
    for name, mod in tr.get("modules", {}).items():
        if step.search(name):
            steps += mod["count"]
            seconds += sum(s for f, s in mod["ops"].items() if fam.match(f))
    if not steps or not seconds:
        return None
    return 1e3 * seconds / steps

"""Device time of one compiled train step: seconds of the step
program's executions in the trace over their count."""

import harness
import trace_reduce

P = harness.load_json("metrics", "step_device_ms.train.json")


def read(run):
    if not run.get("trace"):
        return None
    sec, n = trace_reduce.pattern_time(run["trace"], P["table"], P["pattern"])
    return 1e3 * sec / n if n else None

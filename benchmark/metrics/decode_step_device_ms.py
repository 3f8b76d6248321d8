"""Device time of one decode step: seconds of the decode-chunk
programs' executions in the trace over executions times the engine's
chunk (steps per dispatch)."""

import harness
import trace_reduce

P = harness.load_json("metrics", "decode_step_device_ms.json")


def step_seconds(run):
    tr = run.get("trace")
    chunk = (run.get("child") or {}).get("decode_chunk")
    if not tr or not chunk:
        return None
    sec, n = trace_reduce.pattern_time(tr, P["table"], P["pattern"],
                                       has_op=P["has_op"])
    return sec / (n * chunk) if n else None


def read(run):
    s = step_seconds(run)
    return None if s is None else 1e3 * s

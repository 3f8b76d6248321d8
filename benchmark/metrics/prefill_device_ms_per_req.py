"""Device time of admitting one request: seconds of every program of
the model file that is not a decode chunk (bucket prefill, joiner
prefill, sampler, scatter) in the trace, over the requests whose
first token came inside the traced window."""

import harness
import trace_reduce

P = harness.load_json("metrics", "decode_step_device_ms.json")


def read(run):
    tr = run.get("trace")
    n_req = (run.get("client") or {}).get("first_tokens_in_trace")
    if not tr or not n_req:
        return None
    sec, n = trace_reduce.pattern_time(tr, P["table"], P["pattern"],
                                       lacks_op=P["has_op"])
    return 1e3 * sec / n_req if n else None

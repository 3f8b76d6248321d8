"""The whole train step's share of the chip's peak in a Laguna cell,
idle time included: operations one step needs
(``opcount_laguna.train_step``: forward + backward, the held experts by
the pairs that were here, attention by the pairs its mask keeps, no
recompute, optimizer not counted) times the step executions in the
traced window, over the window, over the peak bf16 rate."""

import harness
import opcount_laguna
import trace_reduce

P = harness.load_json("metrics", "step_device_ms.train.json")


def read(run):
    tr = run.get("trace")
    stats = (run.get("child") or {}).get("model_stats") or {}
    if not tr or not stats.get("steps"):
        return None
    _, n = trace_reduce.pattern_time(tr, P["table"], P["pattern"])
    if not n:
        return None
    w = run["window"]
    work = opcount_laguna.train_step(
        run["config"], w["batch_size"], w["seq_len"],
        stats.get("moe.pairs_here", 0) / stats["steps"])
    return 100.0 * work["flops"] * n / tr["window_s"] / run["peak"]["bf16_flops_per_s"]

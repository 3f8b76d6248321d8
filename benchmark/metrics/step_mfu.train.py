"""The whole train step's share of the chips' peak, idle time
included: operations one step needs (``opcount.bert_train_step``:
forward + backward, no recompute, optimizer not counted) times the
step executions in the traced window, over the window, over the peak
bf16 rate of all the cell's chips (the work is the global batch's,
the count one device's)."""

import harness
import opcount
import trace_reduce

P = harness.load_json("metrics", "step_device_ms.train.json")


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    _, n = trace_reduce.pattern_time(tr, P["table"], P["pattern"])
    if not n:
        return None
    w = run["window"]
    work = opcount.bert_train_step(run["config"], w["batch_size"], w["seq_len"])
    peak = harness.cell_peak(run)["bf16_flops_per_s"]
    return 100.0 * work["flops"] * n / tr["window_s"] / peak

"""The flash attention kernels' share of their roofline in a train
step: the least time the chip could take for one layer's forward and
backward call (``opcount.flash_call``), times layers and steps, over
the device time of the kernels' events in the trace."""

import harness
import opcount
import trace_reduce

P = harness.load_json("metrics", "flash_train_roofline.json")


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    fwd, _ = trace_reduce.pattern_time(tr, P["table"], P["forward"])
    bwd, _ = trace_reduce.pattern_time(tr, P["table"], P["backward"])
    _, steps = trace_reduce.pattern_time(tr, "modules", P["step"])
    if not steps or fwd + bwd <= 0:
        return None
    cfg, w = run["config"], run["window"]
    heads = cfg["num_attention_heads"]
    args = (w["batch_size"], heads, w["seq_len"], cfg["hidden_size"] // heads)
    need = sum(
        opcount.roofline_seconds(c["flops"], c["bytes"], run["peak"])
        for c in (opcount.flash_call(*args, causal=False, backward=False),
                  opcount.flash_call(*args, causal=False, backward=True)))
    return 100.0 * need * cfg["num_hidden_layers"] * steps / (fwd + bwd)

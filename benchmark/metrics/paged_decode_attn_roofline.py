"""The paged cache-read kernel's share of its roofline: the least time
the chip could take to read the VALID cached keys and values of the
window's mean batch once (``opcount.decode_attention_call``), over the
device time of one kernel call in the trace."""

import harness
import opcount
import trace_reduce

P = harness.load_json("metrics", "paged_decode_attn_roofline.json")
shape = harness.load_module(
    harness.os.path.join(harness.BENCH_DIR, "metrics",
                         "step_mfu.decode.py"),
    "metric_step_mfu_decode").mean_batch_and_context


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    sec, n = trace_reduce.pattern_time(tr, P["table"], P["pattern"])
    rc = shape(run)
    if not n or rc is None:
        return None
    rows, ctx = rc
    cfg = run["config"]
    call = opcount.decode_attention_call(rows * ctx, cfg["n_head"],
                                         cfg["n_embd"] // cfg["n_head"])
    need = opcount.roofline_seconds(call["flops"], call["bytes"],
                                    harness.cell_peak(run))
    return 100.0 * need / (sec / n)

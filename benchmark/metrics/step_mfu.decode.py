"""The whole decode step against the chip's roofline: the least time
one chip could take for a step of the window's mean batch (every
matmul weight read once in bfloat16, the VALID cache tokens read once,
2 operations per weight per row plus attention: ``opcount.
gpt2_decode_step``) over the device time of one decode step in the
trace. Memory bandwidth bounds a decode step at these batch sizes, so
this is a share of the peak bytes/s."""

import harness
import opcount

step_seconds = harness.load_module(
    harness.os.path.join(harness.BENCH_DIR, "metrics",
                         "decode_step_device_ms.py"),
    "metric_decode_step").step_seconds


def mean_batch_and_context(run):
    """Mean live rows per decode step (tokens per chunk call over the
    chunk) and mean valid cache tokens per row (prompt + half the
    output), from the window's own requests."""
    calls = (run.get("counters") or {}).get("generate.chunk_calls")
    chunk = (run.get("child") or {}).get("decode_chunk")
    c = run["client"]
    if not calls or not chunk or not c["output_tokens"]:
        return None
    rows = c["summary"]["tokens_ok"] / calls / chunk
    ctx = (sum(c["prompt_tokens"]) / len(c["prompt_tokens"])
           + 0.5 * sum(c["output_tokens"]) / len(c["output_tokens"]))
    return rows, ctx


def read(run):
    s = step_seconds(run)
    shape = mean_batch_and_context(run)
    if s is None or shape is None:
        return None
    rows, ctx = shape
    work = opcount.gpt2_decode_step(run["config"], rows, rows * ctx)
    return 100.0 * opcount.roofline_seconds(
        work["flops"], work["bytes"], run["peak"]) / s

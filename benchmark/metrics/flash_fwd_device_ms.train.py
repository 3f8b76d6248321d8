"""Device milliseconds a train step in the streaming flash attention
forward kernel: the self time of the trace's operations NAMED as the
JSON's ``kernels`` says (``flash_attention_fwd`` and its compiler
numbers), over the step program's executions. With
``flash_bwd_device_ms.train`` it splits the ``flash_attention`` family
by pass: a change to the forward moves this one alone.

None where the trace holds no step or no such operation (a program
whose streaming kernels carry no pass name: it shows one family,
``flash_attention``)."""

import re

import harness
import trace_reduce

P = harness.load_json("metrics", "flash_fwd_device_ms.train.json")


def kernel_ms(run, pattern):
    """Self time a step of the operations whose name ``pattern``
    matches, in ms; None where the trace has no step or no such
    operation."""
    tr = run.get("trace") or {}
    if not tr.get("modules"):
        return None
    _, steps = trace_reduce.pattern_time(tr, "modules", P["step"])
    kernel = re.compile(pattern)
    ops = [op["seconds"] for name, op in tr.get(P["table"], {}).items()
           if kernel.match(name)]
    if not steps or not ops:
        return None
    return 1e3 * sum(ops) / steps


def read(run):
    return kernel_ms(run, P["kernels"])

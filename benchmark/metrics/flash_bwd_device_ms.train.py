"""Device milliseconds a train step in the two streaming flash attention
backward kernels: the self time of the trace's operations NAMED as the
JSON's ``kernels`` says (``flash_attention_dq``, ``flash_attention_dkv``
and their compiler numbers), over the step program's executions. With
``flash_fwd_device_ms.train`` it splits the ``flash_attention`` family
by pass: a change to the backward moves this one alone.

None where the trace holds no step or no such operation (a program
whose streaming kernels carry no pass name: it shows one family,
``flash_attention``). The sum is ``flash_fwd_device_ms.train``'s,
over this metric's own names."""

import os

import harness

P = harness.load_json("metrics", "flash_bwd_device_ms.train.json")
FWD = harness.load_module(
    os.path.join(harness.BENCH_DIR, "metrics", "flash_fwd_device_ms.train.py"),
    "metric_flash_fwd_device_ms_train")


def read(run):
    return FWD.kernel_ms(run, P["kernels"])

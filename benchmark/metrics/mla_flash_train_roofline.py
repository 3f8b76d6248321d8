"""The streaming flash attention kernels' share of their roofline in a
train step whose latent-attention layers call them at unequal widths
(scores over ``qk_nope + qk_rope``, values over ``v_head_dim``,
causal): the least time the chip could take for one layer's forward
and backward call (``opcount_kimi_linear.mla_flash_call``; the forward
a recomputing step runs a second time is NOT counted), times those
layers and the steps, over the device time of the kernels' events."""

import harness
import opcount_kimi_linear as oc
import trace_reduce

P = harness.load_json("metrics", "mla_flash_train_roofline.json")


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    sec, _ = trace_reduce.pattern_time(tr, P["table"], P["kernels"])
    _, steps = trace_reduce.pattern_time(tr, "modules", P["step"])
    if not steps or sec <= 0:
        return None
    cfg, w = run["config"], run["window"]
    args = (w["batch_size"], cfg["num_attention_heads"], w["seq_len"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])
    need = sum(
        oc.roofline_seconds(c["flops"], c["bytes"], harness.cell_peak(run))
        for c in (oc.mla_flash_call(*args, causal=True, backward=False),
                  oc.mla_flash_call(*args, causal=True, backward=True)))
    layers = sum(m == "mla" for m, _ in oc.layer_kinds(cfg))
    return 100.0 * need * layers * steps / sec

"""The gated delta rule's share of its roofline: the least time the
chip could take for the recurrence's own work
(``opcount_kimi_linear.kda_core``, forward + backward, what the literal
recurrence needs; recomputation not counted), times the KDA layers and
the steps, over the device time of the operations under the
``kda.core`` scope (``scope_time.py``)."""

import harness
import opcount_kimi_linear as oc


def read(run):
    sc = run.get("scopes")
    if not sc or not sc.get("steps") or not sc["seconds"].get("kda.core"):
        return None
    cfg, w = run["config"], run["window"]
    lin = cfg["linear_attn_config"]
    args = (w["batch_size"], lin["num_heads"], w["seq_len"],
            lin["head_dim"], lin["head_dim"])
    need = sum(
        oc.roofline_seconds(c["flops"], c["bytes"], harness.cell_peak(run))
        for c in (oc.kda_core(*args, backward=False),
                  oc.kda_core(*args, backward=True)))
    layers = sum(m == "kda" for m, _ in oc.layer_kinds(cfg))
    return 100.0 * need * layers * sc["steps"] / sc["seconds"]["kda.core"]

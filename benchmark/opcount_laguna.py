"""Operations and bytes the Laguna configuration's ALGORITHM needs,
from shapes alone (``opcount.py``'s rules: a multiply-add is two
operations, recomputed operations are not counted, nothing looks at
what implements a call). Functions take the configuration file's own
keys.
"""

from __future__ import annotations

import opcount


def layers(cfg: dict) -> list:
    """``(kind, query heads, ffn)`` of the layers held here:
    ``full_attention`` | ``sliding_attention``, the layer's own head
    count, ``dense`` | ``sparse``; published layers 0 ..
    ``num_hidden_layers - 1``."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def attn_weights(cfg: dict, heads: int) -> int:
    """Matmul weights of one attention layer of ``heads`` query heads:
    q and o, k and v over the K/V heads, the headwise gate."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * heads * d + 2 * h * cfg["num_key_value_heads"] * d
            + h * heads)


def expert_weights(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_weights(cfg: dict) -> int:
    """Matmul weights EVERY token meets here: attention, the dense
    MLP, the shared expert and the router of each sparse layer, the
    head (the embedding is a lookup). The routed experts are counted
    by the pairs that reach them (``train_step``)."""
    h = cfg["hidden_size"]
    total = h * cfg["vocab_size"]
    for _, heads, ffn in layers(cfg):
        total += attn_weights(cfg, heads)
        if ffn == "dense":
            total += 3 * h * cfg["intermediate_size"]
        else:
            total += (3 * h * cfg["shared_expert_intermediate_size"]
                      + h * cfg.get("router_width", cfg["num_experts"]))
    return total


def kept_pairs(seq: int, window: int | None) -> float:
    """(query, key) pairs a causal mask keeps in one row of ``seq``
    positions: ``L (L + 1) / 2``, or with a window that counts the
    position itself ``L W - W (W - 1) / 2``."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2
    return seq * window - window * (window - 1) / 2


def flash_call(batch: int, heads: int, kv_heads: int, seq: int, d: int,
               window: int | None, backward: bool,
               itemsize: int = 2) -> dict:
    """One fused causal attention call of ``heads`` query heads over
    ``kv_heads`` K/V heads, counting only the pairs the mask keeps.
    Forward: ``QK^T`` and ``PV``; reads q, k, v, writes o and the row
    statistics (float32, one a query head and position). Backward: dV,
    dP, dQ, dK (2x the forward; the scores a fused kernel recomputes
    are NOT counted); reads q, k, v, o, dO and the statistics, writes
    dq, dk, dv. Every tensor once: K/V are ``kv_heads`` wide however
    many query heads read them."""
    fwd = 2.0 * batch * heads * kept_pairs(seq, window) * 2 * d
    wide = batch * seq * heads * d * itemsize       # q, o, dO, dq
    narrow = batch * seq * kv_heads * d * itemsize  # k, v, dk, dv
    stats = batch * seq * heads * 4.0
    if backward:
        return {"flops": 2.0 * fwd,
                "bytes": 4.0 * wide + 4.0 * narrow + stats}
    return {"flops": fwd, "bytes": 2.0 * wide + 2.0 * narrow + stats}


def layer_calls(cfg: dict, kind: str, batch: int, seq: int) -> list:
    """The forward and the backward call of one layer of ``kind``."""
    heads = next(h for k, h, _ in layers(cfg) if k == kind)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    args = (batch, heads, cfg["num_key_value_heads"], seq, cfg["head_dim"],
            window)
    return [flash_call(*args, backward=False), flash_call(*args, backward=True)]


def train_step(cfg: dict, batch: int, seq: int, pairs_here: float) -> dict:
    """Forward + backward of one step over ``batch * seq`` positions
    (padded ones included: the step computes them). 6 operations per
    matmul weight per token over ``token_weights``; the held experts by
    the (token, expert) pairs that were here (``pairs_here``: a step's,
    all sparse layers); every attention layer at 3x its forward call;
    no recompute, optimizer not counted (rotary, norms and gates are
    under 0.1% and left out). Bytes: float32 weights read, gradients
    written, AdamW state read and written (28 B/param) - activations
    left out."""
    tokens = batch * seq
    flops = 6.0 * token_weights(cfg) * tokens
    flops += 6.0 * expert_weights(cfg) * pairs_here
    held = 0
    for kind, _, ffn in layers(cfg):
        flops += 3.0 * layer_calls(cfg, kind, batch, seq)[0]["flops"]
        held += (ffn == "sparse") * cfg["num_experts"]
    n_params = (token_weights(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
                + held * expert_weights(cfg))
    return {"flops": flops, "bytes": 28.0 * n_params}


roofline_seconds = opcount.roofline_seconds


def flash_share(run: dict, kind: str, scope: str):
    """Percent of their roofline that the flash calls of the layers of
    ``kind`` reached in a traced run: the least time for one layer's
    forward and backward call, times those layers and the steps, over
    the device time under ``scope`` (``run["scopes"]``, from
    ``scope_time.py``). None where the run has no such scope."""
    sc = run.get("scopes")
    if not sc or not sc.get("steps") or not sc["seconds"].get(scope):
        return None
    cfg, w = run["config"], run["window"]
    need = sum(roofline_seconds(c["flops"], c["bytes"], run["peak"])
               for c in layer_calls(cfg, kind, w["batch_size"], w["seq_len"]))
    n = sum(k == kind for k, _, _ in layers(cfg))
    return 100.0 * need * n * sc["steps"] / sc["seconds"][scope]

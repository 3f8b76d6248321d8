"""Operations and bytes the Kimi-Linear configuration's ALGORITHM
needs, from shapes alone (``opcount.py``'s rules: a multiply-add is two
operations, recomputed operations are not counted, nothing looks at
what implements a call). Functions take the configuration file's own
keys.
"""

from __future__ import annotations

import opcount


def layer_kinds(cfg: dict) -> list:
    """``(mixer, ffn)`` of the layers held here: ``kda`` | ``mla``,
    ``dense`` | ``moe``; published layers 1 .. ``num_hidden_layers``."""
    lin = cfg["linear_attn_config"]
    return [("kda" if i in lin["kda_layers"] else "mla",
             "dense" if i <= cfg["first_k_dense_replace"] else "moe")
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def kda_weights(cfg: dict) -> int:
    """Matmul weights of one KDA mixer: q, k, v, o; the decay's and the
    gate's low-rank pairs; the write strength."""
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    ck = lin["num_heads"] * lin["head_dim"]
    r = cfg.get("kda_gate_rank") or lin["head_dim"]
    return 4 * h * ck + 2 * (h * r + r * ck) + h * lin["num_heads"]


def mla_weights(cfg: dict) -> int:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lat = cfg["kv_lora_rank"]
    return (h * nh * (nope + rope) + h * (lat + rope)
            + lat * nh * (nope + vd) + nh * vd * h)


def expert_weights(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_weights(cfg: dict) -> int:
    """Matmul weights EVERY token meets here: the mixers, the dense
    MLP, the shared expert and the router of each expert layer, the
    head (the embedding is a lookup). The routed experts are counted
    by the pairs that reach them (``train_step``)."""
    h = cfg["hidden_size"]
    total = h * cfg["vocab_size"]
    for mixer, ffn in layer_kinds(cfg):
        total += kda_weights(cfg) if mixer == "kda" else mla_weights(cfg)
        if ffn == "dense":
            total += 3 * h * cfg["intermediate_size"]
        else:
            total += (expert_weights(cfg) * cfg["num_shared_experts"]
                      + h * cfg.get("router_width", cfg["num_experts"]))
    return total


def mla_flash_call(batch: int, heads: int, seq: int, qk_dim: int,
                   v_dim: int, *, causal: bool, backward: bool,
                   itemsize: int = 2) -> dict:
    """One fused attention call whose scores run over ``qk_dim`` and
    whose values over ``v_dim``. Forward: ``QK^T`` and ``PV``; reads q,
    k, v, writes o. Backward: dV, dP, dQ, dK (2x the forward; the
    scores a fused kernel recomputes are NOT counted); reads q, k, v,
    o, do, writes dq, dk, dv."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    fwd = 2.0 * batch * heads * pairs * (qk_dim + v_dim)
    row = batch * heads * seq * itemsize
    if backward:
        return {"flops": 2.0 * fwd, "bytes": row * (4.0 * qk_dim + 4.0 * v_dim)}
    return {"flops": fwd, "bytes": row * (2.0 * qk_dim + 2.0 * v_dim)}


def kda_core(batch: int, heads: int, seq: int, k_dim: int, v_dim: int, *,
             backward: bool, itemsize: int = 2) -> dict:
    """The gated delta rule's own work, as the literal recurrence has
    it (what any implementation must do; a chunked one does other
    products, and is read against this count): per position and head
    the decay of ``S`` (``k_dim * v_dim``), ``k^T S``, the rank-one
    write and ``S^T q`` (``2 * k_dim * v_dim`` each). Forward reads q,
    k, g (``k_dim``), v (``v_dim``), beta, writes o. Backward: twice
    the forward's operations; reads those and o, do, writes dq, dk, dg,
    dv, dbeta. The state never has to leave the chip's fast memory."""
    fwd = 7.0 * batch * heads * seq * k_dim * v_dim
    row = batch * heads * seq * itemsize
    if backward:
        return {"flops": 2.0 * fwd,
                "bytes": row * (6.0 * k_dim + 4.0 * v_dim + 2.0)}
    return {"flops": fwd, "bytes": row * (3.0 * k_dim + 2.0 * v_dim + 1.0)}


def train_step(cfg: dict, batch: int, seq: int, pairs_here: float) -> dict:
    """Forward + backward of one step over ``batch * seq`` positions
    (padded ones included: the step computes them). 6 operations per
    matmul weight per token over ``token_weights``; the held experts by
    the (token, expert) pairs that were here (``pairs_here``: a step's,
    all expert layers); causal latent attention and the delta rule at
    3x their forward; no recompute, optimizer not counted (the short
    convolutions, norms and gates are under 0.1% and left out). Bytes:
    float32 weights read, gradients written, AdamW state read and
    written (28 B/param) - activations left out."""
    tokens = batch * seq
    lin = cfg["linear_attn_config"]
    kinds = layer_kinds(cfg)
    flops = 6.0 * token_weights(cfg) * tokens
    flops += 6.0 * expert_weights(cfg) * pairs_here
    n_mla = sum(m == "mla" for m, _ in kinds)
    n_kda = len(kinds) - n_mla
    flops += 3.0 * n_mla * mla_flash_call(
        batch, cfg["num_attention_heads"], seq,
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], causal=True, backward=False)["flops"]
    flops += 3.0 * n_kda * kda_core(
        batch, lin["num_heads"], seq, lin["head_dim"], lin["head_dim"],
        backward=False)["flops"]
    n_moe = sum(f == "moe" for _, f in kinds)
    n_params = (token_weights(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
                + n_moe * cfg["num_experts"] * expert_weights(cfg))
    return {"flops": flops, "bytes": 28.0 * n_params}


roofline_seconds = opcount.roofline_seconds

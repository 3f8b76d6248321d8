"""Operations and bytes the ALGORITHM needs, from shapes alone.

Nothing here looks at what implements a call (a kernel, a fusion, a
padded cache): a roofline share divides the least time the chip could
take for this much work by the device time the trace measured, so it
cannot pass 100% unless a count here is too high. A multiply-add is
two operations. Recomputed operations are not counted. Functions take
the configuration file's own keys.
"""

from __future__ import annotations


def roofline_seconds(flops: float, byts: float, peak: dict) -> float:
    """The least time one chip could take: the larger of operations
    over peak bf16 FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])


# -- attention ----------------------------------------------------------
def attention_flops(batch: int, heads: int, q_len: int, kv_len: int,
                    head_dim: int, *, causal: bool = False) -> float:
    """Forward QK^T and PV: 2 products of 2*q*kv*d each per head. A
    causal square call needs only the lower triangle (q*(q+1)/2
    pairs)."""
    pairs = q_len * (q_len + 1) / 2 if causal else q_len * kv_len
    return 4.0 * batch * heads * pairs * head_dim


def flash_call(batch: int, heads: int, seq: int, head_dim: int, *,
               causal: bool, backward: bool, itemsize: int = 2) -> dict:
    """One fused attention call over ``[B, L, H, D]`` q, k, v.
    Forward: the two products; reads q, k, v, writes o. Backward: the
    four products the gradient needs (dV, dP, dQ, dK: 2x the forward;
    the scores a fused kernel recomputes are NOT counted); reads
    q, k, v, o, do, writes dq, dk, dv."""
    fwd = attention_flops(batch, heads, seq, seq, head_dim, causal=causal)
    tensor = batch * heads * seq * head_dim * itemsize
    if backward:
        return {"flops": 2.0 * fwd, "bytes": 8.0 * tensor}
    return {"flops": fwd, "bytes": 4.0 * tensor}


def decode_attention_call(kv_tokens: int, kv_heads: int, head_dim: int,
                          layers: int = 1, itemsize: int = 2) -> dict:
    """Cache-read attention of one decode step over ``kv_tokens`` VALID
    cached tokens summed over the batch (not the padded cache length):
    reads K and V once, 4*d operations per (token, head)."""
    return {
        "flops": 4.0 * kv_tokens * kv_heads * head_dim * layers,
        "bytes": 2.0 * kv_tokens * kv_heads * head_dim * itemsize * layers,
    }


# -- GPT-2 ----------------------------------------------------------------
def gpt2_matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product per token: the blocks'
    kernels and the tied head (``wte`` once). Biases, layer norms and
    the position table are left out (under 0.2%)."""
    h = cfg["n_embd"]
    return cfg["n_layer"] * 12 * h * h + cfg["vocab_size"] * h


def gpt2_decode_step(cfg: dict, batch: int, kv_tokens: int, *,
                     weight_itemsize: int = 2, kv_itemsize: int = 2) -> dict:
    """One decode step of ``batch`` rows whose caches hold
    ``kv_tokens`` valid tokens in all: every matmul weight read once,
    the valid cache read once, 2 operations per weight per row plus
    attention over the valid tokens."""
    h, heads = cfg["n_embd"], cfg["n_head"]
    w = gpt2_matmul_params(cfg)
    att = decode_attention_call(kv_tokens, heads, h // heads,
                                cfg["n_layer"], kv_itemsize)
    return {"flops": 2.0 * w * batch + att["flops"],
            "bytes": float(w * weight_itemsize) + att["bytes"]}


def gpt2_prefill(cfg: dict, tokens: int, seq: int) -> dict:
    """Causal forward over ``tokens`` prompt tokens in rows of ``seq``;
    the head is applied to the last position of each row only."""
    h, heads = cfg["n_embd"], cfg["n_head"]
    rows = tokens / max(seq, 1)
    dense = 2.0 * cfg["n_layer"] * 12 * h * h * tokens
    head = 2.0 * cfg["vocab_size"] * h * rows
    att = cfg["n_layer"] * attention_flops(rows, heads, seq, seq,
                                           h // heads, causal=True)
    return {"flops": dense + head + att,
            "bytes": float(gpt2_matmul_params(cfg) * 2)}


# -- BERT -----------------------------------------------------------------
def bert_matmul_params(cfg: dict) -> int:
    """Non-embedding matmul weights: per layer q, k, v, out (4 h^2)
    and the two FFN kernels (2 h i); pooler and classifier see one
    position per row and are left out."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * i)


def bert_train_step(cfg: dict, batch: int, seq: int) -> dict:
    """Forward + backward of one step over ``batch * seq`` positions
    (padded ones included: the step computes them). 6 operations per
    matmul weight per token, attention 3x its forward (no recompute
    counted), optimizer not counted. Bytes: float32 weights read,
    gradients written, AdamW state read and written (28 B/param) —
    activations are left out, so the bound is compute's."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    tokens = batch * seq
    dense = 6.0 * bert_matmul_params(cfg) * tokens
    att = 3.0 * cfg["num_hidden_layers"] * attention_flops(
        batch, heads, seq, seq, h // heads)
    n_params = (bert_matmul_params(cfg)
                + (cfg["vocab_size"] + cfg["max_position_embeddings"]
                   + cfg["type_vocab_size"]) * h)
    return {"flops": dense + att, "bytes": 28.0 * n_params}

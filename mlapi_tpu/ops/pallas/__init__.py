"""Pallas TPU kernels — the hand-scheduled hot ops.

XLA fusion covers most of this framework (SURVEY §2: the reference's
only native code is transitive BLAS, so "native" here means kernels
against the TPU's own memory hierarchy). These kernels exist where
hand control of VMEM/MXU beats the XLA default:

- ``flash_attention`` — fused attention: scores, softmax and the
  probability-value contraction stay in VMEM per q-block; the [L, L]
  score matrix never touches HBM.
- ``decode_attention`` — split-K flash-decode for the serving hot
  path: single-query attention over the stored KV cache, int8
  payload + scale tiles dequantized per tile in registers — int8 is
  what crosses HBM on the decode read.
- ``extend_attention`` — flash-extend, the U-token-query twin: every
  multi-token span (chunked prefill, admission mini-prefills,
  speculative verify) streams the stored cache through the same
  split-K grid, so the byte saving covers every token the server
  processes, not just decode steps.
"""

from mlapi_tpu.ops.pallas.decode_attention import (
    decode_attention,
    decode_attention_tp,
    extend_attention,
    extend_attention_tp,
    paged_decode_attention,
    paged_decode_attention_tp,
    paged_extend_attention,
    paged_extend_attention_tp,
)
from mlapi_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_on_mesh,
    flash_attention_with_lse,
)

__all__ = [
    "decode_attention",
    "decode_attention_tp",
    "extend_attention",
    "extend_attention_tp",
    "paged_decode_attention",
    "paged_decode_attention_tp",
    "paged_extend_attention",
    "paged_extend_attention_tp",
    "flash_attention",
    "flash_attention_on_mesh",
    "flash_attention_with_lse",
]

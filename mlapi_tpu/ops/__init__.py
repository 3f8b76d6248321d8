"""TPU-native operator library.

The hot ops behind the model zoo, written against the hardware rather
than any reference implementation (the reference, ``main.py:21-22``,
has exactly one "op": a 1x4 sklearn matmul — everything here is the
capability scaled up TPU-first):

- ``attention``       — stable full softmax attention (the baseline).
- ``ring_attention``  — sequence-parallel blockwise attention with KV
                        rotation over a mesh axis (long-context path).
- ``quant``           — weight-only int8 quantization (serving HBM).
- ``speculative``     — draft-propose / target-verify decoding.
"""

from mlapi_tpu.ops.attention import full_attention
from mlapi_tpu.ops.quant import dequantize_tree, quantize_tree
from mlapi_tpu.ops.ring_attention import ring_attention, ring_self_attention
from mlapi_tpu.ops.speculative import (
    speculative_generate,
    speculative_generate_batched,
    speculative_sample,
    speculative_sample_batched,
)

__all__ = [
    "full_attention",
    "ring_attention",
    "ring_self_attention",
    "quantize_tree",
    "dequantize_tree",
    "speculative_generate",
    "speculative_generate_batched",
    "speculative_sample",
    "speculative_sample_batched",
]

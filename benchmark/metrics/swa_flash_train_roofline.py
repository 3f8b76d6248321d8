"""The flash attention kernels' share of their roofline on the
sliding-attention layers of a Laguna train step (window-512 banded
attention, 64 query heads over 8 K/V heads at the published widths):
the least time the chip could take for one layer's forward and backward
call (``opcount_laguna.flash_call``: only the pairs the mask keeps,
every tensor once; recomputation not counted), times those layers and
the steps, over the device time of the operations under the
``attn.sliding.core`` scope (``scope_time.py``: the flash call alone, its
three kernels and what XLA lays out around them)."""

import opcount_laguna as oc


def read(run):
    return oc.flash_share(run, "sliding_attention", "attn.sliding.core")

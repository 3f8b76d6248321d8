"""``opcount`` against counts made by hand."""

import json
import os

import pytest

import opcount

BENCH = os.path.dirname(os.path.dirname(__file__))


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_decode_step():
    c = cfg("gpt2-124m")
    # weights in a product: 12 layers x (768x2304 + 768x768 + 2 x 768x3072)
    # = 12 x 7,077,888 = 84,934,656; head 50257 x 768 = 38,597,376
    w = 84_934_656 + 38_597_376
    assert opcount.gpt2_matmul_params(c) == w
    # 16 rows, 100 cached tokens each: K and V of 1600 tokens, 12 layers
    step = opcount.gpt2_decode_step(c, 16, 1600)
    kv_bytes = 2 * 1600 * 768 * 2 * 12          # 58,982,400
    assert step["bytes"] == w * 2 + kv_bytes
    att = 4 * 1600 * 12 * 64 * 12               # 58,982,400
    assert step["flops"] == 2 * w * 16 + att
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # bandwidth bounds it: 306 MB / 819 GB/s = 0.374 ms vs 0.02 ms of math
    assert opcount.roofline_seconds(step["flops"], step["bytes"], peak) == \
        pytest.approx((w * 2 + kv_bytes) / 819e9)


def test_bert_base_step():
    c = cfg("bert-base-sst2")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) = 84,934,656 matmul weights
    assert opcount.bert_matmul_params(c) == 84_934_656
    step = opcount.bert_train_step(c, 128, 128)
    dense = 6 * 84_934_656 * 16384              # 8.349e12
    att = 3 * 12 * 4 * 128 * 12 * 128 * 128 * 64  # 2.319e11
    assert step["flops"] == pytest.approx(dense + att)
    assert step["flops"] == pytest.approx(8.58e12, rel=5e-3)


def test_flash_call():
    # [128, 128, 12, 64]: forward 4 x 128 x 12 x 128 x 128 x 64 = 6.442e9
    f = opcount.flash_call(128, 12, 128, 64, causal=False, backward=False)
    assert f["flops"] == 4 * 128 * 12 * 128 * 128 * 64
    tensor = 128 * 12 * 128 * 64 * 2            # 25,165,824 B
    assert f["bytes"] == 4 * tensor
    b = opcount.flash_call(128, 12, 128, 64, causal=False, backward=True)
    assert b["flops"] == 2 * f["flops"] and b["bytes"] == 8 * tensor
    causal = opcount.flash_call(1, 1, 128, 64, causal=True, backward=False)
    assert causal["flops"] == 4 * (128 * 129 / 2) * 64
    d = opcount.decode_attention_call(1000, 12, 64)
    assert d == {"flops": 4.0 * 1000 * 12 * 64, "bytes": 2.0 * 1000 * 12 * 64 * 2}

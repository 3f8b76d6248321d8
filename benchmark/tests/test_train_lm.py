"""The ``train_lm`` entry and the ``kimi-linear.pretrain_8k`` cell, at
a size a test run can hold (the rehearsal widths, on the CPU): a sound
rehearsal passes every check and ends with exit code 3; the CONTROL
(``int8_all``) and each planted fault end as not correct through the
driver's own ``compare``; the yardstick's new pieces (the operation
counts, the scope reader, the rows' generator, the metric readers)
compute what they say."""

import json
import os
import sys

import numpy as np
import pytest

from test_correct import BENCH, over_limit, rehearse

CELL = "kimi-linear.pretrain_8k"


def config():
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sound():
    return rehearse(CELL, 5)


def test_sound_rehearsal_passes_every_check(sound):
    assert set(sound) == {"grad_dir_gap_median", "delta_norm_gap",
                          "loss2_gap", "loss3_gap"}
    assert over_limit(sound) == []


@pytest.mark.parametrize("fault", ["state_unchanged", "drop_half"])
def test_fault_under_the_wrapper_is_caught(fault):
    checks = rehearse(CELL, 5, fault)
    assert "delta_norm_gap" in over_limit(checks), checks


def test_control_and_faults_end_as_not_correct(sound):
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import control_train_lm

    rows = {r["reading"]: r for r in control_train_lm.readings(
        CELL, 5, True, ["int8_all", "drop_half", "state_unchanged"])}
    assert not any(r["correct"] for r in rows.values()), rows
    assert "grad_dir_gap_median" in over_limit(rows["int8_all"]["checks"])
    assert rows["int8_all"]["grad_dir_gap_median"] >= 2 * \
        sound["grad_dir_gap_median"]["value"]
    assert "delta_norm_gap" in over_limit(rows["drop_half"]["checks"])
    assert "delta_norm_gap" in over_limit(rows["state_unchanged"]["checks"])


def test_configuration_keeps_every_published_width():
    cfg = config()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["router_width"]) == (5, 8, 20480, 256)
    widths = {"hidden_size": 2304, "intermediate_size": 9216,
              "moe_intermediate_size": 1024, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "num_attention_heads": 32,
              "num_experts_per_token": 8, "head_dim": 72}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["linear_attn_config"]["head_dim"] == 128
    kw = cfg["program"]["model_kwargs"]
    assert (kw["hidden_size"], kw["num_experts"], kw["experts_held"],
            kw["num_layers"], kw["vocab_size"]) == (
                2304, 256, [0, 8], 5, 20480)


def test_operation_counts():
    import opcount_kimi_linear as oc

    cfg = config()
    assert oc.layer_kinds(cfg) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]
    assert oc.kda_weights(cfg) == 39_460_864
    assert oc.mla_weights(cfg) == 29_114_368
    assert oc.expert_weights(cfg) == 7_077_888
    # 4 KDA + 1 MLA + dense MLP + 4 x (shared + router) + head
    assert oc.token_weights(cfg) == (
        4 * 39_460_864 + 29_114_368 + 3 * 2304 * 9216
        + 4 * (7_077_888 + 2304 * 256) + 2304 * 20480)
    f = oc.mla_flash_call(1, 32, 8192, 192, 128, causal=True, backward=False)
    assert f["flops"] == 2.0 * 32 * (8192 * 8193 / 2) * 320
    assert f["bytes"] == 32 * 8192 * 2 * 640
    b = oc.mla_flash_call(1, 32, 8192, 192, 128, causal=True, backward=True)
    assert b["flops"] == 2 * f["flops"]
    k = oc.kda_core(1, 32, 8192, 128, 128, backward=False)
    assert k["flops"] == 7.0 * 32 * 8192 * 128 * 128
    step = oc.train_step(cfg, 1, 8192, 8192.0)
    more = oc.train_step(cfg, 1, 8192, 16384.0)
    assert more["flops"] - step["flops"] == 6.0 * 7_077_888 * 8192
    assert 1.8e13 < step["flops"] < 2.0e13


def test_scope_of_an_operation_from_the_compiled_text():
    import scope_time

    text = '''
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/jvp(kda)/kda.core/while/body/dot_general" stack_frame_id=3}
  ROOT %copy.2 = f32[8]{0} copy(%fusion.7), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/moe.route/gather"}
  %add.1 = f32[8]{0} add(%a, %b)
'''
    names = scope_time.op_scopes_of(text)
    assert set(names) == {"fusion.7", "copy.2"}
    want = {"kda", "kda.core", "moe.route", "mla"}
    assert scope_time.path_scopes(names["fusion.7"], want) == {"kda", "kda.core"}
    assert scope_time.path_scopes(names["copy.2"], want) == {"moe.route"}
    # a parameter named after a layer is no scope
    assert scope_time.path_scopes("jit(step)/params['kda']/mul", want) == set()


def test_scope_times_on_the_recorded_trace():
    """The recorded v5e trace (PR 26) joined with a map of two of its
    operations: their self time lands on their scopes, the rest is not
    mapped."""
    import scope_time
    import trace_reduce

    path = os.path.join(BENCH, "fixtures", "tiny_v5e.xplane.pb")
    loaded = trace_reduce.load(path)
    ops = next(iter(loaded["devices"].values()))["ops"]
    names = sorted({trace_reduce.op_name(n) for n, _, _ in ops})[:2]
    out = scope_time.reduce(
        path, {names[0]: "jit(f)/jvp(kda)/mul", names[1]: "jit(f)/add"},
        ["kda", "mla"], step_pattern=r"^jit_")
    assert out["steps"] >= 1
    assert out["seconds"]["kda"] > 0 and out["seconds"]["mla"] == 0
    assert out["unscoped_s"] > 0 and 0 < out["mapped_share"] < 1


def test_packed_rows():
    import traffic_lm

    spec = {"rows": 32, "seq_len": 512, "documents": 600, "base_seed": 29,
            "zipf_s": 1.0, "length": {"dist": "lognormal", "median": 70,
                                      "sigma": 1.2, "lo": 16, "hi": 512}}
    x, y = traffic_lm.packed_rows(spec, 2 ** 31 + 5, 300)
    again, _ = traffic_lm.packed_rows(spec, 2 ** 31 + 5, 300)
    other, _ = traffic_lm.packed_rows(spec, 7, 300)
    assert x.shape == (32, 512) and x.dtype == np.int32 and y is not None
    assert np.array_equal(x, again) and not np.array_equal(x, other)
    assert x.max() < 300 and x.min() == 0
    filled = (x != 0).sum(axis=1)
    # padding is a row's tail only, and most of a row is tokens
    assert all((row[:n] != 0).all() and (row[n:] == 0).all()
               for row, n in zip(x, filled))
    assert 0.6 < filled.mean() / 512 <= 1.0
    # Zipf: id 1 is the most frequent
    assert np.bincount(x[x > 0]).argmax() == 1
    with pytest.raises(ValueError, match="documents fill only"):
        traffic_lm.packed_rows({**spec, "documents": 10}, 7, 300)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A run without a trace, without scopes (the trace carried none)
    or of a program without the counters: the metric is left out."""
    import harness

    names = ["step_mfu.kimi_train", "mla_flash_train_roofline",
             "kda_device_ms.train", "moe_device_ms.train",
             "kda_core_roofline", "moe_pairs_here_pct.train",
             "moe_load_max_over_mean.train"]
    run = {"trace": None, "scopes": None, "child": {}, "config": config(),
           "window": {"batch_size": 1, "seq_len": 8192}, "peak": {}}
    for name in names:
        reader = harness.load_module(
            os.path.join(BENCH, "metrics", name + ".py"), "m_" + name)
        assert reader.read(run) is None, name


def test_readers_on_a_made_up_run():
    import harness

    peak = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
    run = {
        "config": config(), "peak": peak,
        "window": {"batch_size": 1, "seq_len": 8192},
        "scopes": {"steps": 2.0, "seconds": {
            "kda": 0.8, "kda.core": 0.4, "mla": 0.1, "moe.route": 0.02,
            "moe.experts": 0.06, "moe.shared": 0.04, "lm_head": 0.05}},
        "child": {"model_stats": {
            "steps": 10, "moe.pairs_routed": 10 * 262144,
            "moe.pairs_here": 10 * 8192, "moe.expert_load_max": 512,
            "moe.load_max_over_mean": 2.0}},
        "trace": {"window_s": 2.0, "modules": {
            "jit_step(123)": {"seconds": 1.9, "count": 2, "ops": {}}},
            "ops": {"flash_attention.3": {"seconds": 0.1, "count": 8,
                                          "text": "%flash_attention.3 = ..."}}},
    }

    def read(name):
        return harness.load_module(
            os.path.join(BENCH, "metrics", name + ".py"), "m_" + name).read(run)

    assert read("kda_device_ms.train") == pytest.approx(400.0)
    assert read("moe_device_ms.train") == pytest.approx(60.0)
    assert read("moe_pairs_here_pct.train") == pytest.approx(3.125)
    assert read("moe_load_max_over_mean.train") == pytest.approx(2.0)
    assert 0 < read("kda_core_roofline") < 100
    assert 0 < read("mla_flash_train_roofline") < 100
    assert 0 < read("step_mfu.kimi_train") < 100

"""The ``laguna-xs2.pretrain_8k`` cell of the ``train_lm`` entry, at a
size a test run can hold (the rehearsal widths, on the CPU): a sound
rehearsal passes every check and ends with exit code 3; the CONTROL
(``int8_all``), the harness's planted faults and two of this model's
own (the window left off, the gate left out) end as not correct
through the driver's own ``compare``; every width of the
configuration's file is the catalog row's; the operation counts and
the four readers compute what they say."""

import json
import os
import sys

import pytest

from test_correct import BENCH, over_limit, rehearse

CELL = "laguna-xs2.pretrain_8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAMES = ["step_mfu.laguna_train", "swa_flash_train_roofline",
         "gqa_flash_train_roofline", "attn_device_ms.train"]


def config():
    with open(os.path.join(BENCH, "configs", "laguna-xs2-ep8.json")) as f:
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


@pytest.fixture(scope="module")
def control_rows():
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import control_train_lm

    return {r["reading"]: r for r in control_train_lm.readings(
        CELL, 5, True, ["int8_all", "drop_half", "state_unchanged",
                        "no_window", "no_gate", "plain_rope"])}


@pytest.mark.parametrize("name,number", [
    ("int8_all", "grad_dir_gap_median"), ("drop_half", "delta_norm_gap"),
    ("state_unchanged", "delta_norm_gap"),
    ("no_window", "delta_norm_gap"), ("no_gate", "grad_dir_gap_median"),
])
def test_control_and_faults_end_as_not_correct(sound, control_rows, name,
                                               number):
    """Through the accepted tool, which hands the model's own names on
    as it hands ``int8_all`` on."""
    row = control_rows[name]
    assert not row["correct"], row
    assert number in over_limit(row["checks"])
    if name == "int8_all":
        assert row[number] >= 2 * sound[number]["value"]


def test_plain_rotary_is_further_off_than_the_program(sound, control_rows):
    """At the rehearsal's widths the scores are a hundredth of the
    published ones, so YaRN's table and factor move little; plain
    rotary in their place still moves the worst leaf's three-step
    change several times further from the reference than the sound
    program stands."""
    assert control_rows["plain_rope"]["delta_norm_gap"] > \
        3 * sound["delta_norm_gap"]["value"]


def test_configuration_keeps_every_published_width():
    cfg = config()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["router_width"], cfg["experts_held"]) == (
                5, 32, 12544, 256, [0, 32])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in cfg["reduced"]:
                assert cfg[k] == v, k
            else:
                assert cfg["published"][k] == v, k
    kw = cfg["program"]["model_kwargs"]
    assert (kw["hidden_size"], kw["head_dim"], kw["num_kv_heads"],
            kw["sliding_window"], kw["intermediate_size"],
            kw["moe_intermediate_size"], kw["shared_expert_intermediate_size"],
            kw["num_experts"], kw["num_experts_per_tok"],
            kw["moe_routed_scaling_factor"], kw["experts_held"],
            kw["num_layers"], kw["vocab_size"]) == (
                2048, 128, 8, 512, 8192, 512, 512, 256, 8, 2.5, [0, 32], 5,
                12544)
    assert kw["heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert kw["layer_types"] == cfg["layer_types"]
    assert kw["rope_full"] == cfg["rope_parameters"]["full_attention"]
    assert kw["rope_sliding"] == cfg["rope_parameters"]["sliding_attention"]
    # the weights are the configuration's: a seed of its own, stated
    assert isinstance(cfg["weights_seed"], int)
    assert "weights_seed" in cfg["assumed"]["weights"]


def test_operation_counts():
    import opcount_laguna as oc

    cfg = config()
    assert oc.layers(cfg) == [
        ("full_attention", 48, "dense"), ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"), ("full_attention", 48, "sparse")]
    # q + o, k + v, the headwise gate
    assert oc.attn_weights(cfg, 64) == 2 * 2048 * 8192 + 2 * 2048 * 1024 \
        + 2048 * 64 == 37_879_808
    assert oc.attn_weights(cfg, 48) == 29_458_432
    assert oc.expert_weights(cfg) == 3_145_728
    # 2 full + 3 sliding + dense MLP + 4 x (shared + router) + head
    assert oc.token_weights(cfg) == (
        2 * 29_458_432 + 3 * 37_879_808 + 3 * 2048 * 8192
        + 4 * (3_145_728 + 2048 * 256) + 2048 * 12544) == 263_258_112
    # a position sees itself and the 511 before it; the first 511 fewer
    assert oc.kept_pairs(8192, 512) == 8192 * 512 - 512 * 511 / 2 == 4_063_488
    assert oc.kept_pairs(8192, None) == 8192 * 8193 / 2
    assert oc.kept_pairs(4, 2) == 7 and oc.kept_pairs(4, 9) == 10
    f = oc.flash_call(1, 64, 8, 8192, 128, 512, False)
    assert f["flops"] == 2.0 * 64 * 4_063_488 * 256
    # q, o 64 heads wide; k, v 8; one float32 statistic a head and row
    assert f["bytes"] == 8192 * (2 * 64 * 128 * 2 + 2 * 8 * 128 * 2 + 64 * 4)
    b = oc.flash_call(1, 64, 8, 8192, 128, 512, True)
    assert b["flops"] == 2 * f["flops"]
    assert b["bytes"] == 8192 * (4 * 64 * 128 * 2 + 4 * 8 * 128 * 2 + 64 * 4)
    g = oc.flash_call(1, 48, 8, 8192, 128, None, False)
    assert g["flops"] == 2.0 * 48 * (8192 * 8193 / 2) * 256
    assert [c["flops"] for c in oc.layer_calls(cfg, "full_attention", 1, 8192)] \
        == [g["flops"], 2 * g["flops"]]
    step = oc.train_step(cfg, 1, 8192, 32768.0)
    more = oc.train_step(cfg, 1, 8192, 65536.0)
    assert more["flops"] - step["flops"] == 6.0 * 3_145_728 * 32768
    assert step["flops"] == (
        6.0 * 263_258_112 * 8192 + 6.0 * 3_145_728 * 32768
        + 3 * (2 * g["flops"] + 3 * f["flops"]))
    assert 1.9e13 < step["flops"] < 2.0e13
    # 691.6M parameters: what the configuration's 11.07 GB is 16 B of
    assert step["bytes"] == 28.0 * 691_623_936 - 28.0 * 5 * 2 * 2048 - 28.0 * 2048


def _read(name, run):
    import harness

    return harness.load_module(
        os.path.join(BENCH, "metrics", name + ".py"), "m_" + name).read(run)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A run without a trace, without scopes (the trace carried none,
    or the program has no such scope) or without the counters: the
    metric is left out, nothing raises."""
    run = {"trace": None, "scopes": None, "child": {}, "config": config(),
           "window": {"batch_size": 1, "seq_len": 8192}, "peak": {}}
    for name in NAMES:
        assert _read(name, run) is None, name
    run["scopes"] = {"steps": 2.0, "seconds": {"kda": 0.3, "moe.route": 0.1}}
    for name in NAMES[1:]:
        assert _read(name, run) is None, name


def test_readers_on_a_made_up_run():
    import harness

    peak = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
    run = {
        "config": config(), "peak": peak, "chips": 1,
        "window": {"batch_size": 1, "seq_len": 8192},
        "scopes": {"steps": 2.0, "seconds": {
            "attn.sliding": 0.3, "attn.sliding.core": 0.06,
            "attn.full": 0.2, "attn.full.core": 0.1, "attn.rope": 0.02,
            "attn.gate": 0.01, "moe.route": 0.02, "moe.experts": 0.06,
            "moe.shared": 0.04, "lm_head": 0.05}},
        "child": {"model_stats": {
            "steps": 10, "moe.pairs_routed": 10 * 262144,
            "moe.pairs_here": 10 * 32768, "moe.expert_load_max": 2048,
            "moe.load_max_over_mean": 2.0}},
        "trace": {"window_s": 2.0, "modules": {
            "jit_step(123)": {"seconds": 1.9, "count": 2, "ops": {}}},
            "ops": {}},
    }
    assert _read("attn_device_ms.train", run) == pytest.approx(250.0)
    # three sliding layers, 0.676 + 1.352 ms a layer at the peak
    assert _read("swa_flash_train_roofline", run) == pytest.approx(
        100 * 3 * 2 * (133152374784.0 * 3 / 197e12) / 0.06)
    # two full layers, 4.19 + 8.37 ms a layer
    assert _read("gqa_flash_train_roofline", run) == pytest.approx(
        100 * 2 * 2 * (824734384128.0 * 3 / 197e12) / 0.1)
    # 19.7 TFLOP a step, 2 steps in 2 s of a 197 TFLOP/s chip
    assert _read("step_mfu.laguna_train", run) == pytest.approx(
        100 * 19704915689472.0 / 197e12)
    assert _read("moe_pairs_here_pct.train", run) == pytest.approx(12.5)
    assert _read("moe_device_ms.train", run) == pytest.approx(60.0)

"""``correct`` has to come out false when it should (steps 2 and 3 of
"How correct is decided"), at a size a test run can hold: the
rehearsal widths on the CPU.

- the CONTROL: the reference one precision down (every product on the
  int8 grid), put in the program's place and sent through the run's
  own ``compare``, ends as not correct;
- the timed path broken underneath a run (a step that returns its
  state unchanged, half of the batch left out, a token altered where it
  is produced): the harness's own run, minus its look for a chip, ends
  with a number over its limit.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(__file__))
ROOT = os.path.dirname(BENCH)
CELLS = os.path.join(os.path.dirname(__file__), "cells")


def rehearse(cell: str, seed: int, fault: str | None = None,
             cells: str | None = None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_TEST_FAULT", None)
    env.pop("BENCH_TEST_CELLS", None)
    if fault:
        env["BENCH_TEST_FAULT"] = fault
    if cells:
        env["BENCH_TEST_CELLS"] = cells
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert r.returncode == 3, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is False
    return line["checks"]


def over_limit(checks: dict) -> list[str]:
    return [k for k, c in checks.items()
            if c["value"] is None or c["value"] > c["limit"]]


@pytest.fixture(scope="module")
def sound_train():
    return rehearse("bert-base.finetune", 5)


def test_sound_training_run_passes(sound_train):
    assert over_limit(sound_train) == []


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "delta_norm_gap"),
    ("drop_half", "delta_norm_gap"),
])
def test_training_fault_is_caught(fault, number):
    checks = rehearse("bert-base.finetune", 5, fault)
    assert number in over_limit(checks), checks


def test_training_control_and_faults_end_as_not_correct(sound_train):
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import control_train

    rows = {r["reading"]: r for r in control_train.readings(
        "bert-base.finetune", 5, True,
        ["int8_all", "drop_half", "state_unchanged"])}
    assert not any(r["correct"] for r in rows.values()), rows
    assert "grad_dir_gap_median" in over_limit(rows["int8_all"]["checks"])
    assert rows["int8_all"]["grad_dir_gap_median"] >= 2 * \
        sound_train["grad_dir_gap_median"]["value"]
    assert "delta_norm_gap" in over_limit(rows["drop_half"]["checks"])
    assert "delta_norm_gap" in over_limit(rows["state_unchanged"]["checks"])


def test_unlisted_cell_is_refused_outside_a_test():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gpt2.chat_tiny", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, text=True, capture_output=True, timeout=60,
        env=dict(os.environ, BENCH_TEST_CELLS=CELLS))
    assert r.returncode == 2 and not r.stdout.strip()


def test_serving_fault_and_control():
    sound = rehearse("gpt2.chat_tiny", 5, cells=CELLS)
    assert over_limit(sound) == []
    broken = rehearse("gpt2.chat_tiny", 5, "alter_token", cells=CELLS)
    assert "served_logit_gap" in over_limit(broken), broken
    # the control at the same prompts and tokens: int8 puts other
    # tokens first, which the reference scores below its best
    from reference import gpt2

    with open(os.path.join(BENCH, "configs", "gpt2-124m.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    params = gpt2.make_params(5, cfg)
    rows = [([10 + (i * 7 + j) % 100 for j in range(40)],
             [20 + (i + j) % 100 for j in range(24)]) for i in range(4)]
    _, control = gpt2.served_gaps(params, rows, cfg, pad_to=64, block=4,
                                  control="int8")
    assert max(control) > 3 * max(sound["served_logit_gap"]["value"], 1e-6)

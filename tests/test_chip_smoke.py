"""``chip_smoke.py``'s control flow, rehearsed on the CPU backend, and
the two process-level helpers it leans on (the placeable compile
cache, the peaks table). The chip itself is not here: what these
tests pin is that the smoke can only say ``"ok": true`` for a run
whose every phase reported a TPU, and that a failed phase stops it.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(*args, timeout):
    # One plain CPU device: the rehearsal's tiny models are not the
    # place to exercise the 8-virtual-device mesh the suite runs on.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.heavy
def test_rehearsal_runs_every_one_chip_phase_and_is_never_ok():
    r = _run_smoke("--rehearse", timeout=560)
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    phases = [row["phase"] for row in rows if "phase" in row]
    assert phases == [
        "1-iris-train", "1-iris-serve", "2-bert-train", "3-bert-serve",
        "4-gpt-checkpoint", "4-generate-default",
        "4-generate-paged-flash-int8", "4-generate-agreement", "5-kernels",
    ], (phases, r.stdout[-2000:], r.stderr[-2000:])
    for name in ("4-generate-default", "4-generate-paged-flash-int8"):
        row = next(x for x in rows if x.get("phase") == name)
        assert row["tokens"] == {"unary": 32, "stream": 24, "long": 16,
                                 "pair_a": 20, "pair_b": 20}
        assert row["counters"]["prefill_chunks"] >= 2
    assert rows[-1]["ok"] is False and rows[-1]["rehearsal"] is True
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_without_a_chip_the_smoke_fails_at_its_first_phase():
    """No rehearsal option, chip hidden: the first phase's own device
    report is not a TPU, and that ends the run — non-zero, no verdict,
    no later phase."""
    r = _run_smoke(timeout=280)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "1-iris-serve" not in r.stdout
    assert "not on a TPU" in r.stderr


def test_a_failed_phase_stops_the_run(monkeypatch, capsys):
    smoke = _load_smoke()
    ran = []

    def boom(sm):
        ran.append("iris")
        raise AssertionError("phase made to fail")

    monkeypatch.setattr(smoke, "phase_iris", boom)
    for later in ("phase_bert", "phase_generate", "phase_kernels"):
        monkeypatch.setattr(smoke, later, lambda sm, n=later: ran.append(n))
    with pytest.raises(AssertionError, match="made to fail"):
        smoke.main(["--rehearse"])
    assert ran == ["iris"]
    assert '"ok"' not in capsys.readouterr().out


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_success_line_is_exactly_the_contract(capsys):
    smoke = _load_smoke()
    assert smoke.finish([TPU, TPU, TPU], rehearse=False, chips=1) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )


@pytest.mark.parametrize("devices,rehearse,chips", [
    ([TPU, {"platform": "cpu", "kind": "cpu", "count": 1}], False, 1),
    ([TPU, TPU], True, 1),    # a rehearsal is never a pass
    ([TPU, TPU], False, 4),   # asked for four chips, saw one
])
def test_no_ok_unless_every_phase_ran_on_the_chips_asked_for(
    capsys, devices, rehearse, chips
):
    smoke = _load_smoke()
    assert smoke.finish(devices, rehearse=rehearse, chips=chips) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is False


def test_four_chip_verdict_reports_the_widest_view(capsys):
    smoke = _load_smoke()
    four = dict(TPU, count=4)
    assert smoke.finish([four, four, four], rehearse=False, chips=4) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": four}


# -- the compile cache, placeable from outside ---------------------------
@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_jax_alone_when_placed(
    monkeypatch, cache_config, tmp_path
):
    from mlapi_tpu.utils.platform import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(
    monkeypatch, cache_config
):
    from mlapi_tpu.utils import platform

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_compile_cache")
    assert platform.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert platform.enable_compile_cache() == want  # same path every call
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_compile_cache/" in ignored

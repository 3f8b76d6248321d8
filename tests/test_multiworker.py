"""SO_REUSEPORT multi-worker serving: ``--workers N`` spawns N fresh
server processes sharing one listening port, kernel-balanced per
connection — the CPU-attach scale-out past the single asyncio loop's
~one-core ceiling (built in r03).

Integration test: real subprocesses, real sockets, real HTTP.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from mlapi_tpu.checkpoint import save_checkpoint
from mlapi_tpu.datasets import load_iris
from mlapi_tpu.models import get_model
from mlapi_tpu.train import fit

ROW = {
    "sepal_length": 5.1,
    "sepal_width": 3.5,
    "petal_length": 1.4,
    "petal_width": 0.2,
}


@pytest.fixture(scope="module")
def iris_checkpoint(tmp_path_factory):
    iris = load_iris()
    model = get_model(
        "linear", num_features=iris.num_features,
        num_classes=iris.num_classes,
    )
    result = fit(model, iris, steps=200, learning_rate=0.1,
                 weight_decay=1e-3)
    path = tmp_path_factory.mktemp("ckpt") / "iris"
    save_checkpoint(
        path,
        result.params,
        step=result.steps,
        config={
            "model": "linear",
            "model_kwargs": {
                "num_features": iris.num_features,
                "num_classes": iris.num_classes,
            },
            "feature_names": list(iris.feature_names),
        },
        vocab=iris.vocab,
    )
    return path


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(port: int, path: str, timeout: float = 5.0) -> dict:
    # One fresh connection per call — SO_REUSEPORT balances per
    # connection, so keep-alive pooling would pin us to one worker.
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def _post(port: int, path: str, body: dict, timeout: float = 5.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"content-type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.mark.heavy  # in-suite training/soak — fast profile: -m 'not heavy'
def test_two_workers_share_one_port(iris_checkpoint):
    port = _free_port()
    env = dict(
        os.environ,
        MLAPI_TPU_PLATFORM="cpu",
        MLAPI_TPU_WARMUP="minimal",
    )
    sup = subprocess.Popen(
        [
            sys.executable, "-m", "mlapi_tpu.serving",
            "--checkpoint", str(iris_checkpoint),
            "--port", str(port), "--workers", "2",
        ],
        env=env,
    )
    try:
        # Wait for at least one worker to come up (cold JAX import on
        # a shared 1-core box is slow).
        deadline = time.time() + 180
        up = False
        while time.time() < deadline:
            if sup.poll() is not None:
                pytest.fail(f"supervisor died rc={sup.returncode}")
            try:
                if _get(port, "/healthz", timeout=2)["status"] == "ok":
                    up = True
                    break
            except Exception:
                time.sleep(1.0)
        assert up, "no worker became healthy in time"

        # Distinct connections spread across BOTH worker processes.
        pids = set()
        for _ in range(120):
            try:
                pids.add(_get(port, "/healthz")["pid"])
            except Exception:
                time.sleep(0.2)  # second worker may still be booting
            if len(pids) >= 2:
                break
            time.sleep(0.1)  # 120 instant probes can beat the second boot
        assert len(pids) == 2, f"connections all landed on one worker: {pids}"
        assert sup.pid not in pids, "supervisor must not serve traffic"

        # The actual serving contract works through the shared port.
        out = _post(port, "/predict", ROW)
        assert set(out) == {"prediction", "probability"}
        assert out["prediction"].startswith("Iris-")
    finally:
        sup.send_signal(signal.SIGTERM)
        try:
            sup.wait(20)
        except subprocess.TimeoutExpired:
            sup.kill()
            sup.wait(10)
    # SIGTERM to the supervisor must also stop the WORKERS (its
    # handler runs the shutdown fan-out) — no orphans still bound to
    # the port.
    deadline = time.time() + 15
    while time.time() < deadline:
        alive = [p for p in pids if os.path.isdir(f"/proc/{p}")]
        if not alive:
            break
        time.sleep(0.5)
    assert not alive, f"workers {alive} orphaned after supervisor SIGTERM"


def test_worker_flag_requires_explicit_port(iris_checkpoint):
    r = subprocess.run(
        [
            sys.executable, "-m", "mlapi_tpu.serving",
            "--checkpoint", str(iris_checkpoint),
            "--port", "0", "--workers", "2",
        ],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    assert "explicit --port" in r.stderr

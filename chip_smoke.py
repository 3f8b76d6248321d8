"""Proof that the main path runs on the locally attached TPU: train →
serve → generate through the normal entry points, at BERT-base and
GPT-2-small widths, with every Pallas kernel of the path compiled.

    python chip_smoke.py              # one chip, phases 1-5
    python chip_smoke.py --chips 4    # the two cross-chip paths and
                                      # what they are compared with,
                                      # nothing else
    python chip_smoke.py --rehearse   # CPU rehearsal at tiny widths:
                                      # same control flow, never "ok"

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
— printed only when every phase ran on a TPU and passed. Everything
else worth knowing is a JSON line before it. Any phase that fails
raises; nothing is caught and carried past.

This process never imports jax: a chip belongs to one process, and
the trainer, the servers and the kernel check each need it. They run
as children, one after another, each gone before the next starts;
device facts come from their own reports (the trainer's summary line,
the server's ``/healthz``). Checkpoints, data and prompts are made
from seeds in a scratch directory this script creates and removes; the
one thing it leaves in the checkout is the compile cache
(``.jax_compile_cache/``, unless ``JAX_COMPILATION_CACHE_DIR`` places
it elsewhere).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

IRIS_ROW = {"sepal_length": 5.1, "sepal_width": 3.5,
            "petal_length": 1.4, "petal_width": 0.2}
IRIS_CSV = b"sepal_length,species\n5.1,Iris-setosa\n6.2,Iris-virginica\n"

# GPT-2 small (Radford et al. 2019; HF ``gpt2`` config.json): the
# published widths, bf16. Rehearsal shrinks every width.
GPT2_SMALL = dict(vocab_size=50257, hidden_size=768, num_layers=12,
                  num_heads=12, max_positions=1024,
                  compute_dtype="bfloat16")
GPT_TINY = dict(vocab_size=260, hidden_size=32, num_layers=2,
                num_heads=4, max_positions=512, compute_dtype="float32")
BERT_TINY = dict(num_classes=2, vocab_size=30522, hidden_size=32,
                 num_layers=2, num_heads=2, intermediate_size=64,
                 max_positions=64, attention_impl="flash")

# Losses of the same preset, same seed, same steps on another mesh:
# same math, a different reduction order in bf16 matmuls.
LOSS_TOL = 5e-2

_CKPT_SRC = """
import json, sys
import jax
from mlapi_tpu.utils.platform import (
    apply_platform_override, device_report, enable_compile_cache,
)
apply_platform_override()
enable_compile_cache()
from mlapi_tpu.checkpoint import save_checkpoint
from mlapi_tpu.models import get_model
from mlapi_tpu.text import ByteTokenizer
cfg, out, seed = json.loads(sys.argv[1]), sys.argv[2], int(sys.argv[3])
model = get_model("gpt_lm", **cfg)
params = model.init(jax.random.key(seed))
save_checkpoint(out, params, step=0,
                config={"model": "gpt_lm", "model_kwargs": cfg,
                        "tokenizer": ByteTokenizer().fingerprint()})
print(json.dumps({
    "params": sum(int(x.size) for x in jax.tree.leaves(params)),
    "device": device_report(),
}))
"""


_T0 = time.time()


def say(**row) -> None:
    """One JSON line; ``t`` is seconds since the smoke started."""
    print(json.dumps({**row, "t": round(time.time() - _T0, 1)}), flush=True)


class Smoke:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        self.devices: list[dict] = []
        self.env = dict(os.environ)
        # Only the generative engine reads this. Its default warm-up
        # grid (3 buckets x 4 batch sizes, each a prefill, a decode
        # chunk and a compaction program, plus the fused and admission
        # ladders) costs tens of cold GPT-2-width compiles per server;
        # the smoke's few requests compile what they touch on demand
        # instead. The cut of the grid itself is ROADMAP S5.
        self.env.setdefault("MLAPI_TPU_WARMUP", "minimal")

    # -- children ------------------------------------------------------
    def saw(self, phase: str, device: dict) -> None:
        """Record a phase's OWN device report. Outside a rehearsal a
        phase that did not run on a TPU fails the run at once."""
        self.devices.append(device)
        if not self.rehearse and device.get("platform") != "tpu":
            raise SystemExit(
                f"chip_smoke: phase {phase!r} ran on {device!r}, not on "
                "a TPU (use --rehearse for the CPU rehearsal)"
            )

    def child(self, phase: str, cmd: list[str], timeout: float) -> dict:
        """Run one child to its end; its last stdout line is JSON."""
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, *cmd], cwd=ROOT, env=self.env, text=True,
            stdout=subprocess.PIPE, timeout=timeout,
        )
        if r.returncode != 0:
            raise SystemExit(
                f"chip_smoke: {phase}: {' '.join(cmd[:4])}… exited "
                f"{r.returncode}\n{r.stdout[-3000:]}"
            )
        lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
        for ln in lines[:-1]:
            print(ln, flush=True)
        out = json.loads(lines[-1])
        out["child_seconds"] = round(time.time() - t0, 1)
        return out

    def train(self, phase: str, args: list[str], timeout: float) -> dict:
        s = self.child(phase, ["-m", "mlapi_tpu.train", *args], timeout)
        self.saw(phase, s["device"])
        say(phase=phase, **{k: s[k] for k in (
            "name", "steps", "first_loss", "final_loss", "test_accuracy",
            "wall_seconds", "child_seconds", "mesh",
            "param_bytes_per_device", "device")})
        return s


class Server:
    """``python -m mlapi_tpu.serving`` as a child: up on entry (its
    /healthz answered and named its backend), gone on exit."""

    def __init__(self, smoke: Smoke, phase: str, args: list[str],
                 startup_timeout: float):
        self.smoke, self.phase, self.args = smoke, phase, args
        self.startup_timeout = startup_timeout
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.log_path = os.path.join(smoke.work, f"{phase}.log")

    def __enter__(self) -> "Server":
        t0 = time.time()
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mlapi_tpu.serving", *self.args,
             "--host", "127.0.0.1", "--port", str(self.port)],
            cwd=ROOT, env=self.smoke.env, stdout=self.log,
            stderr=subprocess.STDOUT,
        )
        try:
            self.health = self._wait_healthy(t0 + self.startup_timeout)
        except BaseException:
            self._stop()
            raise
        self.setup_seconds = round(time.time() - t0, 1)
        self.smoke.saw(self.phase, {
            "platform": self.health["backend"],
            "kind": self.health["device_kind"],
            "count": self.health["device_count"],
        })
        return self

    def __exit__(self, *exc) -> None:
        self._stop()

    def _wait_healthy(self, deadline: float) -> dict:
        while time.time() < deadline:
            if self.proc.poll() is not None:
                break
            try:
                status, body = self.request("GET", "/healthz", timeout=2)
                if status == 200:
                    return json.loads(body)
            except OSError:
                pass
            time.sleep(0.5)
        self.log.flush()
        with open(self.log_path) as f:
            tail = f.read()[-3000:]
        raise SystemExit(
            f"chip_smoke: {self.phase}: server not healthy "
            f"(exit code {self.proc.poll()})\n{tail}"
        )

    def _stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None, timeout: float = 300):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body, headers=headers or {})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def post_json(self, path: str, payload: dict) -> dict:
        status, body = self.request(
            "POST", path, json.dumps(payload).encode(),
            {"content-type": "application/json"},
        )
        if status != 200:
            raise SystemExit(
                f"chip_smoke: {self.phase}: POST {path} -> {status} "
                f"{body[:500]!r}"
            )
        return body

    def counters(self) -> dict:
        status, body = self.request("GET", "/metrics")
        assert status == 200, status
        return json.loads(body)["counters"]


def concurrently(*calls):
    """Run the calls on threads; re-raise the first failure."""
    out: list = [None] * len(calls)
    errs: list = []

    def run(i, fn):
        try:
            out[i] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i, fn))
               for i, fn in enumerate(calls)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    return out


# -- phases -------------------------------------------------------------
def phase_iris(sm: Smoke) -> None:
    """The paper's three capabilities: train + checkpoint, /predict
    with the Iris JSON schema, /files/ with a multipart CSV + token."""
    ck = os.path.join(sm.work, "iris")
    s = sm.train("1-iris-train", ["--preset", "iris-linear", "--out", ck],
                 timeout=600)
    assert s["test_accuracy"] >= 0.9, s
    with Server(sm, "1-iris-serve", ["--checkpoint", ck], 300) as srv:
        pred = json.loads(srv.post_json("/predict", IRIS_ROW))
        assert pred["prediction"] == "Iris-setosa", pred
        assert 0.0 < pred["probability"] <= 1.0, pred
        boundary = "chipsmoke"
        form = (
            f"--{boundary}\r\nContent-Disposition: form-data; "
            'name="token"\r\n\r\ntok123\r\n'
            f"--{boundary}\r\nContent-Disposition: form-data; "
            'name="file"; filename="iris.csv"\r\n'
            "Content-Type: text/csv\r\n\r\n"
        ).encode() + IRIS_CSV + f"\r\n--{boundary}--\r\n".encode()
        status, body = srv.request(
            "POST", "/files/", form,
            {"content-type": f"multipart/form-data; boundary={boundary}"},
        )
        files = json.loads(body)
        assert status == 200 and files["token"] == "tok123", (status, files)
        assert files["file"]["rows"] == 2, files
        say(phase="1-iris-serve", setup_seconds=srv.setup_seconds,
            predict=pred, files_rows=files["file"]["rows"],
            backend=srv.health["backend"])


def bert_train_args(sm: Smoke, steps: int) -> list[str]:
    if not sm.rehearse:
        return ["--preset", "sst2-bert", "--steps", str(steps)]
    cfg = os.path.join(sm.work, "bert_tiny.yaml")
    with open(cfg, "w") as f:
        # JSON is YAML: the sst2-bert preset with every width shrunk.
        json.dump({
            "name": "sst2-bert-tiny", "model": "bert_classifier",
            "model_kwargs": BERT_TINY, "dataset": "sst2",
            "dataset_kwargs": {"max_len": 32, "n_train": 256,
                               "n_test": 64},
            "steps": steps, "batch_size": 32, "optimizer": "adamw",
            "learning_rate": 1e-3, "mesh_shape": [2, 4],
        }, f)
    return ["--config", cfg, "--steps", str(steps)]


def phase_bert(sm: Smoke) -> None:
    """Trainer at full width (flash attention forward AND backward,
    compiled), then the scoring server on that checkpoint."""
    ck = os.path.join(sm.work, "bert")
    steps = 8 if sm.rehearse else 40
    s = sm.train("2-bert-train",
                 bert_train_args(sm, steps) + ["--out", ck], timeout=1500)
    assert s["final_loss"] == s["final_loss"], s  # not NaN
    assert s["final_loss"] < s["first_loss"], (
        f"loss did not fall: {s['first_loss']} -> {s['final_loss']}"
    )
    texts = ["a moving and wonderful film", "dull, lifeless and far too long",
             "the cast is superb", "i want those two hours back"]
    with Server(sm, "3-bert-serve", ["--checkpoint", ck], 900) as srv:
        before = srv.counters()
        single = [json.loads(srv.post_json("/predict", {"text": t}))
                  for t in texts[:2]]
        mid = srv.counters()
        batch = concurrently(*[
            (lambda t=t: json.loads(srv.post_json("/predict", {"text": t})))
            for t in texts
        ])
        after = srv.counters()
        for p in single + batch:
            assert p["prediction"] in ("negative", "positive"), p
            assert 0.5 <= p["probability"] <= 1.0, p
        say(phase="3-bert-serve", setup_seconds=srv.setup_seconds,
            single=single, concurrent=batch,
            device_calls_single=(mid["batcher.device_calls"]
                                 - before["batcher.device_calls"]),
            device_calls_concurrent=(after["batcher.device_calls"]
                                     - mid["batcher.device_calls"]),
            requests=after["batcher.requests"])
        assert after["batcher.requests"] - before["batcher.requests"] == 6


def gpt_checkpoint(sm: Smoke) -> tuple[str, dict]:
    cfg = GPT_TINY if sm.rehearse else GPT2_SMALL
    ck = os.path.join(sm.work, "gpt")
    if not os.path.exists(ck):
        r = sm.child("4-gpt-checkpoint",
                     ["-c", _CKPT_SRC, json.dumps(cfg), ck, str(SEED)], 600)
        sm.saw("4-gpt-checkpoint", r["device"])
        say(phase="4-gpt-checkpoint", model_kwargs=cfg, **r)
    return ck, cfg


def generate(srv: Server, text: str, n: int, stream: bool = False) -> list:
    """One /generate request; returns its token ids and checks that
    exactly ``n`` arrived (streams: per-chunk lines must add up to the
    final line's full list)."""
    body = srv.post_json("/generate", {
        "text": text, "max_new_tokens": n, "stream": stream})
    if not stream:
        ids = json.loads(body)["token_ids"]
    else:
        lines = [json.loads(ln) for ln in body.splitlines() if ln]
        done = lines[-1]
        assert done.get("done") is True, done
        chunks = [t for ln in lines[:-1] for t in ln["token_ids"]]
        ids = done["token_ids"]
        assert chunks == ids, (len(chunks), len(ids))
    assert len(ids) == n, f"asked {n} tokens, got {len(ids)}"
    return ids


def drive_generate(sm: Smoke, phase: str, flags: list[str], ck: str,
                   long_len: int) -> dict:
    """A few /generate requests against one server: streaming, not,
    a prompt past the largest bucket, two concurrent."""
    # Short enough for the smallest prompt bucket (16), the one shape
    # the minimal warm-up compiles: the unary and streaming requests
    # then share its prefill and decode programs.
    prompt = "The engine"
    long_prompt = ("all work and no play makes jack a dull boy. "
                   * 40)[:long_len]
    with Server(sm, phase, ["--checkpoint", ck, *flags], 1100) as srv:
        c0 = srv.counters()
        out = {
            "unary": generate(srv, prompt, 32),
            "stream": generate(srv, prompt, 24, stream=True),
            "long": generate(srv, long_prompt, 16),
        }
        out["pair_a"], out["pair_b"] = concurrently(
            lambda: generate(srv, "hello world", 20),
            lambda: generate(srv, "goodbye moon", 20),
        )
        c1 = srv.counters()
        moved = {k: c1[f"generate.{k}"] - c0[f"generate.{k}"] for k in (
            "requests", "batch_calls", "chunk_calls", "prefill_chunks",
            "fused_calls")}
        say(phase=phase, flags=flags, setup_seconds=srv.setup_seconds,
            warmup=sm.env["MLAPI_TPU_WARMUP"], tokens={k: len(v) for k, v in out.items()}, counters=moved,
            backend=srv.health["backend"])
        assert moved["requests"] == 5, moved
        assert moved["batch_calls"] >= 4, moved          # pair may co-batch
        assert moved["prefill_chunks"] >= 2, moved       # the long prompt
        assert moved["fused_calls"] >= 1, moved          # non-streaming
    return out


def agreeing_prefix(a: dict, b: dict) -> dict:
    def prefix(x, y):
        n = 0
        while n < min(len(x), len(y)) and x[n] == y[n]:
            n += 1
        return n
    return {k: [prefix(a[k], b[k]), len(a[k])] for k in a}


def phase_generate(sm: Smoke) -> None:
    """The generative server twice, one after the other: default flags
    (contiguous KV, einsum) and the paged split-K kernels over int8
    pages with page-native chunked prefill."""
    ck, cfg = gpt_checkpoint(sm)
    long_len = 150 if sm.rehearse else 300
    default = drive_generate(sm, "4-generate-default", [], ck, long_len)
    paged = drive_generate(
        sm, "4-generate-paged-flash-int8",
        ["--kv-page-size", "16", "--decode-attn-impl", "flash",
         "--kv-quant", "int8"], ck, long_len)
    say(phase="4-generate-agreement",
        note="greedy streams, default vs paged+flash+int8: "
             "[agreeing prefix, length] per request (need not be equal: "
             "int8 KV in bf16)",
        prefix=agreeing_prefix(default, paged))


def phase_kernels(sm: Smoke) -> None:
    args = ["-m", "tools.chip_kernels"] + (["--tiny"] if sm.rehearse else [])
    r = sm.child("5-kernels", args, 900)
    sm.saw("5-kernels", r["device"])
    say(phase="5-kernels", **r)
    assert not r["failed"], r


def phase_four_chips(sm: Smoke) -> None:
    """Only what exists across chips, and what it is compared with."""
    # (i) TP serving: decode/extend kernels under shard_map, 12 heads
    # over 4 — then the same checkpoint, same requests, on one device.
    ck, _ = gpt_checkpoint(sm)
    long_len = 150 if sm.rehearse else 300
    tp = drive_generate(
        sm, "F-generate-tp-1x4",
        ["--mesh-shape", "1,4", "--decode-attn-impl", "flash"], ck, long_len)
    one = drive_generate(
        sm, "F-generate-one-device",
        ["--decode-attn-impl", "flash"], ck, long_len)
    say(phase="F-generate-agreement",
        note="[agreeing prefix, length] per request, TP (1,4) vs one device",
        prefix=agreeing_prefix(tp, one))
    r = sm.child("F-kernel-tp", ["-m", "tools.chip_kernels", "--tp"]
                 + (["--tiny"] if sm.rehearse else []), 900)
    sm.saw("F-kernel-tp", r["device"])
    say(phase="F-kernel-tp", **r)
    assert not r["failed"], r

    # (ii) DP and TP training against the one-device run.
    steps = 4 if sm.rehearse else 20
    runs = {}
    for shape in ("1,1", "4,1", "1,4"):
        runs[shape] = sm.train(
            f"F-bert-train-{shape}",
            bert_train_args(sm, steps) + ["--mesh-shape", shape],
            timeout=1500)
    ref = runs["1,1"]
    for shape in ("4,1", "1,4"):
        for key in ("first_loss", "final_loss"):
            d = abs(runs[shape][key] - ref[key])
            assert d <= LOSS_TOL, (shape, key, runs[shape][key], ref[key])
    # Tensor parallelism really spreads the parameters (at BERT-base
    # widths all but the 30522-row embedding, which 4 does not
    # divide; the tiny rehearsal model is nearly all embedding).
    share = 1.0 if sm.rehearse else 0.5
    assert (runs["1,4"]["param_bytes_per_device"]
            < share * ref["param_bytes_per_device"]), runs["1,4"]
    say(phase="F-bert-agreement", tol=LOSS_TOL, losses={
        s: [r["first_loss"], r["final_loss"]] for s, r in runs.items()},
        param_bytes_per_device={
            s: r["param_bytes_per_device"] for s, r in runs.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("chip_smoke")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths; never prints ok")
    args = ap.parse_args(argv)

    sm = Smoke(args.rehearse)
    t0 = time.time()
    try:
        if args.chips == 4:
            phase_four_chips(sm)
        else:
            phase_iris(sm)
            phase_bert(sm)
            phase_generate(sm)
            phase_kernels(sm)
    finally:
        shutil.rmtree(sm.work, ignore_errors=True)
    say(phases_seconds=round(time.time() - t0, 1))
    return finish(sm.devices, args.rehearse, args.chips)


def finish(devices: list[dict], rehearse: bool, chips: int) -> int:
    """The verdict line. ``ok`` only for a run whose every phase
    reported a TPU; the device is the widest view any phase had (a
    one-device comparison leg of --chips 4 does not shrink it)."""
    device = max(devices, key=lambda d: d["count"])
    on_tpu = all(d["platform"] == "tpu" for d in devices)
    if rehearse or not on_tpu or device["count"] < chips:
        say(ok=False, rehearsal=rehearse, device=device)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

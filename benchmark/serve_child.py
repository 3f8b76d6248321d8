"""The child that holds the chip for a serving cell.

Makes the weights from the seed (``reference/gpt2.py make_params``,
one jitted call on the device), writes them as the checkpoint the
program loads, then calls the same ``main()`` that ``python -m
mlapi_tpu.serving`` calls with the cell's flags: same app, same
engine, same HTTP server. It adds only what the program lacks today:

- a control channel (JSON lines on stdin, replies on the inherited
  stdout; the server's own output goes to stderr): ``stats`` (compile
  counter, memory, device, the engine's dispatch round trip and
  decode chunk), ``mark`` (start of the measured window, for the
  compile counter), ``trace_start <dir>`` / ``trace_stop``.

The engine is built by ``main()`` from the CLI's flags alone: what
the CLI cannot express (a generative batch over the constructor's 8,
other prompt buckets) no cell can time. The constructor is wrapped
only to keep a handle on the engine for ``stats``.
"""

from __future__ import annotations

import json
import os
import sys
import threading


def write_checkpoint(job: dict) -> None:
    """The seed's weights, in the program's checkpoint format, at a
    fixed place under ``benchmark/.cache`` (one slot per
    configuration, rewritten when the seed changes)."""
    import shutil

    out = job["checkpoint_dir"]
    marker = os.path.join(out, "BENCH_SEED")
    want = json.dumps([job["seed"], job["config"]["program"]])
    if os.path.exists(marker) and open(marker).read() == want:
        return
    shutil.rmtree(out, ignore_errors=True)
    import jax

    from child_common import unflatten
    from reference import gpt2

    from mlapi_tpu.checkpoint import save_checkpoint
    from mlapi_tpu.text import ByteTokenizer

    prog = job["config"]["program"]
    params = unflatten(gpt2.make_params(job["seed"], job["config"]))
    save_checkpoint(out, params, step=0, config={
        "model": prog["model"], "model_kwargs": prog["model_kwargs"],
        "tokenizer": ByteTokenizer().fingerprint()})
    del params
    with open(marker, "w") as f:
        f.write(want)


def control_loop(reply, compiles, captured: dict) -> None:
    import jax

    from child_common import device_report, memory_peak_bytes

    for line in sys.stdin:
        try:
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "stats":
                eng = captured.get("engine")
                rtt = None
                if eng is not None:
                    from mlapi_tpu.serving.engine import _dispatch_rtt_ms
                    rtt = _dispatch_rtt_ms()
                out = {
                    "device": device_report(), "memory": memory_peak_bytes(),
                    "compiles": compiles.count,
                    "compiles_since_mark": compiles.since_mark(),
                    "compile_seconds": compiles.seconds(),
                    "dispatch_rtt_ms": rtt,
                    "decode_chunk": getattr(eng, "chunk", None),
                    "max_batch": getattr(eng, "max_batch", None),
                    "prompt_buckets": list(getattr(eng, "prompt_buckets", ())),
                }
            elif cmd == "mark":
                compiles.mark()
                out = {"ok": True}
            elif cmd == "trace_start":
                jax.profiler.start_trace(msg["dir"])
                out = {"ok": True}
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                out = {"ok": True}
            else:
                out = {"error": f"unknown command {cmd!r}"}
        except Exception as e:  # noqa: BLE001 — the channel must answer
            out = {"error": f"{type(e).__name__}: {e}"}
        reply.write(json.dumps(out) + "\n")
        reply.flush()


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    # replies on the real stdout; anything the program prints goes to
    # the log with its stderr
    reply = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    from child_common import CompileCounter, chip_or_exit

    compiles = CompileCounter()
    chip_or_exit(job)
    write_checkpoint(job)

    from mlapi_tpu.serving import engine as engine_mod

    captured: dict = {}
    real_init = engine_mod.TextGenerationEngine.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        captured["engine"] = self

    engine_mod.TextGenerationEngine.__init__ = init
    if job.get("fault"):
        import faults
        faults.plant_serve(job["fault"])
    threading.Thread(target=control_loop, args=(reply, compiles, captured),
                     daemon=True, name="bench-control").start()

    from mlapi_tpu.serving.__main__ import main as serve_main

    serve_main(["--checkpoint", job["checkpoint_dir"], "--host", "127.0.0.1",
                "--port", str(job["port"]), *job["cell"]["server_flags"]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""Driver of the ``generate`` entry kind: the serving ``main()`` in one
child (``serve_child.py``), load from this process (``traffic.py``),
then the plain reference in another child, then the comparison that
decides ``correct``. Imports no jax."""

from __future__ import annotations

import asyncio
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import harness
import traffic

SPEC_KEYS = ("loop", "rate_per_s", "clients", "requests", "ramp_s",
             "base_seed", "prompt_tokens", "max_new_tokens")


class Server:
    """``serve_child.py`` as a child: healthy on entry, gone on exit."""

    def __init__(self, job: dict, env: dict, log_path: str):
        self.job, self.env, self.log_path = job, env, log_path
        self.port = job["port"]

    def __enter__(self) -> "Server":
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(harness.BENCH_DIR, "serve_child.py"),
             self.job["job_path"]],
            cwd=harness.ROOT, env=self.env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self.log, text=True)
        try:
            self.health = self._wait_healthy(time.time() + 1100)
        except BaseException:
            self._stop()
            raise
        return self

    def __exit__(self, *exc) -> None:
        self._stop()

    def _wait_healthy(self, deadline: float) -> dict:
        while time.time() < deadline and self.proc.poll() is None:
            try:
                status, body = self.get("/healthz", timeout=2)
                if status == 200 and json.loads(body).get("status") == "ok":
                    return json.loads(body)
            except (OSError, http.client.HTTPException, ValueError):
                pass
            time.sleep(0.25)
        self.log.flush()
        if self.proc.poll() == 3:
            raise harness.NoChip("the serving child found no accelerator")
        print(harness.tail(self.log_path), file=sys.stderr)
        raise SystemExit("benchmark: the server did not become healthy "
                         f"(exit code {self.proc.poll()})")

    def _stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        self.log.close()

    def get(self, path: str, timeout: float = 30):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def metrics(self) -> dict:
        status, body = self.get("/metrics")
        if status != 200:
            raise SystemExit(f"benchmark: GET /metrics -> {status}")
        return json.loads(body)

    def control(self, **msg) -> dict:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise SystemExit("benchmark: the serving child's control "
                             "channel closed")
        out = json.loads(line)
        if "error" in out:
            raise SystemExit(f"benchmark: control {msg}: {out['error']}")
        return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def warm_up(srv: Server, cell: dict, seed: int) -> dict:
    """Touch every program the cell's traffic can reach, before the
    window: rounds of bursts of 1, 2, 4 … ``max_burst`` simultaneous
    requests drawn from the cell's own length distributions (a burst
    of k forms batches of up to k rows, whose rows finish one after
    another, so the batch walks its halving chain; late members join
    running batches), until a whole round compiles nothing new."""
    w = cell["warm_up"]
    spec = {**{k: cell["traffic"][k] for k in SPEC_KEYS
               if k in cell["traffic"]},
            "loop": "burst", "ramp_s": 0.0}
    rounds, sent, failed = [], 0, 0
    for rnd in range(w["max_rounds"]):
        before = srv.control(cmd="stats")["compiles"]
        k = 1
        while k <= w["max_burst"]:
            plan = traffic.request_plan(
                {**spec, "requests": k},
                seed * 1000003 + rnd * 101 + k, 0.0)
            res = asyncio.run(traffic.drive(
                "127.0.0.1", srv.port, spec, plan, 0.0,
                settle_s=900.0, timeout=900.0))
            sent += len(res["outcomes"])
            failed += sum(1 for o in res["outcomes"] if not o.ok)
            k *= 2
        new = srv.control(cmd="stats")["compiles"] - before
        rounds.append(new)
        if new == 0 and rnd + 1 >= w.get("min_rounds", 2):
            break
    return {"rounds_new_programs": rounds, "sent": sent, "failed": failed}


def sample_for_reference(outcomes: list, plan_by_idx: dict, n: int,
                         seed: int) -> list[dict]:
    """``n`` of the requests the window finished, drawn from the seed,
    with the longest (prompt + served) in it."""
    import random

    done = [o for o in outcomes if o.ok]
    if not done:
        return []
    length = lambda o: len(plan_by_idx[o.idx]["prompt"]) + len(o.ids)
    longest = max(done, key=length)
    rest = [o for o in done if o is not longest]
    random.Random(seed).shuffle(rest)
    rows = []
    for o in [longest] + rest[:max(0, n - 1)]:
        prompt = plan_by_idx[o.idx]["prompt"]
        rows.append({"idx": o.idx,
                     "prompt": [4 + b for b in prompt.encode("ascii")],
                     "served": o.ids})
    return rows


def prepare(ctx: dict):
    """The run's directory, the children's environment, the serving
    child's job and where its log goes."""
    args, cell = ctx["args"], ctx["cell"]
    config = dict(ctx["config"])
    if args.rehearse:
        config.update(config.get("rehearsal", {}))
        cell = {**cell, **cell.get("rehearsal", {}),
                "traffic": {**cell["traffic"],
                            **cell.get("rehearsal", {}).get("traffic", {})}}
    work = os.path.join(harness.CACHE, "run", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    env = harness.cache_env(os.environ)
    env.setdefault("MLAPI_TPU_WARMUP", "minimal")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    job = {
        "kind": "generate", "seed": args.seed, "config": config, "cell": cell,
        "port": free_port(), "job_path": os.path.join(work, "job.json"),
        "checkpoint_dir": os.path.join(
            harness.CACHE, "ckpt", ctx["entry"]["config"]
            + (".tiny" if args.rehearse else "")),
        "fault": os.environ.get("BENCH_TEST_FAULT") if args.rehearse else None,
        "rehearse": args.rehearse,
    }
    with open(job["job_path"], "w") as f:
        json.dump(job, f)
    log = os.path.join(harness.CACHE, "logs", args.workload + ".serve.log")
    return work, env, job, log


def run(ctx: dict) -> dict:
    args = ctx["args"]
    work, env, job, log = prepare(ctx)
    cell, config = job["cell"], job["config"]
    trace_dir = os.path.join(work, "trace")
    spec = cell["traffic"]
    with Server(job, env, log) as srv:
        t_healthy = time.time()
        warm = warm_up(srv, cell, args.seed)
        plan = traffic.request_plan(spec, args.seed, args.seconds)
        plan_by_idx = {r["idx"]: r for r in plan}
        samples: list[dict] = []
        state: dict = {}

        async def on_window(t_open: float):
            loop = asyncio.get_running_loop()
            state["counters0"] = await loop.run_in_executor(None, srv.metrics)
            await loop.run_in_executor(None, lambda: srv.control(cmd="mark"))
            state["t_open"] = t_open

        async def sampler(t_end: float):
            loop = asyncio.get_running_loop()
            while time.time() < t_end:
                await asyncio.sleep(1.0)
                if "t_open" in state:
                    m = await loop.run_in_executor(None, srv.metrics)
                    samples.append(m.get("gauges", {}))

        async def tracer():
            if not args.trace:
                return
            loop = asyncio.get_running_loop()
            while "t_open" not in state:
                await asyncio.sleep(0.05)
            await asyncio.sleep(cell.get("trace_after_s", 2.0))
            await loop.run_in_executor(
                None, lambda: srv.control(cmd="trace_start", dir=trace_dir))
            state["trace_t0"] = time.time()
            await asyncio.sleep(cell.get("trace_seconds", 3.0))
            state["trace_t1"] = time.time()
            await loop.run_in_executor(
                None, lambda: srv.control(cmd="trace_stop"))

        async def play():
            t_end = time.time() + spec.get("ramp_s", 0.0) + args.seconds
            extra = [asyncio.create_task(sampler(t_end)),
                     asyncio.create_task(tracer())]
            res = await traffic.drive("127.0.0.1", srv.port, spec, plan,
                                      args.seconds, on_window=on_window)
            await asyncio.gather(*extra)
            return res

        result = asyncio.run(play())
        counters1 = srv.metrics()
        stats = srv.control(cmd="stats")
    # the server has gone: its state is freed, its peak was read
    summary = traffic.summarise(result, harness.nearest_rank)
    peak = harness.device_gate(stats["device"], ctx["entry"]["chips"],
                               ctx["peaks"], args.rehearse)
    mine = [o for o in result["outcomes"]
            if result["t_open"] <= o.due < result["t_close"]]
    rows = sample_for_reference(mine, plan_by_idx,
                                cell["reference"]["requests"], args.seed)
    ref_job = {
        "kind": "generate", "seed": args.seed, "config": config, "rows": rows,
        "pad_to": cell["reference"]["pad_to"],
        "block": cell["reference"].get("block", 8),
        "controls": ctx.get("controls"),
        "result_path": os.path.join(work, "reference.json"),
    }
    ref_path = os.path.join(work, "ref_job.json")
    with open(ref_path, "w") as f:
        json.dump(ref_job, f)
    rlog = os.path.join(harness.CACHE, "logs", args.workload + ".ref.log")
    ref = {"served_gaps": [], "seconds": None}
    if rows:
        rc = harness.run_child(
            [os.path.join(harness.BENCH_DIR, "reference_child.py"), ref_path],
            env=env, timeout=600, log_path=rlog)
        if rc != 0:
            print(harness.tail(rlog), file=sys.stderr)
            raise SystemExit(f"benchmark: the reference child exited {rc}")
        with open(ref_job["result_path"]) as f:
            ref = json.load(f)

    checks = harness.Checks()
    lim = cell["limits"]
    checks.add("served_logit_gap",
               max(ref["served_gaps"]) if ref["served_gaps"] else None,
               lim["served_logit_gap"])
    checks.add("requests_compared_short",
               max(0, lim["min_requests_compared"] - len(rows)), 0, exact=True)
    unanswered = sum(1 for o in mine if o.done is None and o.error is None)
    wrong = sum(1 for o in mine if o.done is not None and not o.ok)
    checks.add("answers_never_came", unanswered, 0, exact=True)
    checks.add("answers_short_or_inconsistent", wrong, 0, exact=True)

    c0 = state.get("counters0", {}).get("counters", {})
    c1 = counters1.get("counters", {})
    delta = {k: c1[k] - c0.get(k, 0) for k in c1
             if isinstance(c1[k], (int, float))}
    trace = harness.reduce_trace(trace_dir, env) if args.trace else None
    if trace:
        shutil.copy(os.path.join(trace_dir, "reduced.json"), work)
    shutil.rmtree(trace_dir, ignore_errors=True)
    mem = stats["memory"] or {}
    setup_s = result["t_open"] - ctx["t0"]
    shed = {k: v for k, v in delta.items()
            if ("shed_" in k or "brownout_" in k or "rejected" in k) and v}
    print(json.dumps({
        "dispatch_rtt_ms": stats["dispatch_rtt_ms"],
        "decode_chunk": stats["decode_chunk"], "max_batch": stats["max_batch"],
        "prompt_buckets": stats["prompt_buckets"], "memory": mem,
        "warm_up": warm, "programs_before_window": stats["compiles"]
        - stats["compiles_since_mark"],
        "compiles_in_window": stats["compiles_since_mark"],
        "compile_seconds": stats["compile_seconds"],
        "healthy_after_s": t_healthy - ctx["t0"], "setup_s": setup_s,
        "sent": len(mine), "completed": len(mine) - summary["failed"],
        "failed": summary["failed"], "shed_or_clamped": shed,
        "errors": summary["errors"],
        "reference_seconds": ref.get("seconds"),
        "requests_compared": len(rows),
        "tokens_compared": sum(len(r["served"]) for r in rows),
        **{k: summary[k] for k in ("out_tokens_per_s", "ttft_p50_ms",
                                   "ttft_p95_ms", "tpot_p50_ms",
                                   "tpot_p95_ms", "loadgen_late_p95_ms",
                                   "completed_in_window")},
    }), file=sys.stderr)
    device = dict(stats["device"], memory_peak_bytes=mem.get("memory_peak_bytes"))
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
    return {
        "checks": checks, "attempted": summary["attempted"],
        "failed": summary["failed"], "device": device, "trace": trace,
        "peak": peak, "chips": ctx["entry"]["chips"],
        "config": config, "cell": cell,
        "end_to_end": {
            # run.py keeps the ones BENCHMARK.json lists for the cell
            **{k: summary[k] for k in ("out_tokens_per_s", "ttft_p95_ms",
                                       "tpot_p50_ms", "tpot_p95_ms")},
            "setup_s": setup_s,
        },
        "window": {"seconds": summary["window_s"], **summary},
        "client": {"summary": summary,
                   "first_tokens_in_trace": sum(
                       1 for o in result["outcomes"] if o.first is not None
                       and state.get("trace_t0", 0) <= o.first
                       < state.get("trace_t1", 0)),
                   "prompt_tokens": [len(plan_by_idx[o.idx]["prompt"])
                                     for o in mine if o.ok],
                   "output_tokens": [len(o.ids) for o in mine if o.ok]},
        "counters": delta, "gauges": samples, "child": stats,
        "reference": ref,
    }


def sweep(ctx: dict, rates: list[float], seconds: float) -> list[dict]:
    """Find the knee once, when a cell is defined: ONE server, warmed
    up as in a run, then the cell's open loop at each of ``rates`` for
    ``seconds``, lowest first, each from a drained queue. A rate is
    sustained when the requests left waiting at the close of its
    window are no more than the engine serves at once and none failed;
    the table goes to ``sweeps/<cell>.json`` (tools/sweep_rate.py)."""
    args = ctx["args"]
    _, env, job, log = prepare(ctx)
    cell = job["cell"]
    table = []
    with Server(job, env, log) as srv:
        warm_up(srv, cell, args.seed)
        for rate in sorted(rates):
            spec = {**cell["traffic"], "rate_per_s": rate}
            plan = traffic.request_plan(spec, args.seed, seconds)

            async def on_window(t_open):
                await asyncio.get_running_loop().run_in_executor(
                    None, lambda: srv.control(cmd="mark"))

            res = asyncio.run(traffic.drive(
                "127.0.0.1", srv.port, spec, plan, seconds,
                on_window=on_window))
            summ = traffic.summarise(res, harness.nearest_rank)
            mine = [o for o in res["outcomes"]
                    if res["t_open"] <= o.due < res["t_close"]]
            waiting = sum(1 for o in mine
                          if o.first is None or o.first > res["t_close"])
            stats = srv.control(cmd="stats")
            table.append({
                "rate_per_s": rate, "seconds": seconds,
                "sent": summ["attempted"], "failed": summ["failed"],
                "waiting_for_first_token_at_close": waiting,
                "compiles_in_window": stats["compiles_since_mark"],
                **{k: summ[k] for k in (
                    "out_tokens_per_s", "ttft_p50_ms", "ttft_p95_ms",
                    "tpot_p50_ms", "tpot_p95_ms", "loadgen_late_p95_ms")},
            })
            print(json.dumps(table[-1]), file=sys.stderr, flush=True)
    return table

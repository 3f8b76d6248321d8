"""CLI: serve a checkpoint over HTTP.

Replaces the reference's ``uvicorn main:app --reload`` (``README.md:16``)
with a first-class entry point::

    python -m mlapi_tpu.serving --checkpoint /path/to/ckpt --port 8000

For a quick demo without a pre-trained checkpoint (trains Iris on the
attached backend in ~a second, the whole reference pipeline end to
end)::

    python -m mlapi_tpu.serving --demo-iris --port 8000
"""

from __future__ import annotations

import argparse
import asyncio
import tempfile

from mlapi_tpu.serving import InferenceEngine, Server, build_app
from mlapi_tpu.serving.engine import NotServable
from mlapi_tpu.utils.logging import get_logger

_log = get_logger("serving.main")


def _demo_iris_checkpoint() -> str:
    from mlapi_tpu.checkpoint import save_checkpoint
    from mlapi_tpu.datasets import load_iris
    from mlapi_tpu.models import get_model
    from mlapi_tpu.train import fit

    iris = load_iris()
    model = get_model(
        "linear", num_features=iris.num_features, num_classes=iris.num_classes
    )
    result = fit(model, iris, steps=500, learning_rate=0.1, weight_decay=1e-3)
    _log.info("demo Iris trained: test_accuracy=%.4f", result.test_accuracy)
    path = tempfile.mkdtemp(prefix="mlapi_tpu_iris_")
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


def _watch_and_reexec(argv) -> int:
    """Dev loop (the reference's ``uvicorn --reload``,
    ``README.md:16``): run the server as a child process, poll the
    package's ``.py`` mtimes, and restart the child on any change.
    The child carries a marker env var so it serves instead of
    watching."""
    import os
    import signal
    import subprocess
    import sys
    import time

    import mlapi_tpu

    root = os.path.dirname(os.path.abspath(mlapi_tpu.__file__))

    def snapshot() -> dict:
        mt = {}
        for dirpath, _, files in os.walk(root):
            for f in files:
                if f.endswith(".py"):
                    p = os.path.join(dirpath, f)
                    try:
                        mt[p] = os.stat(p).st_mtime
                    except OSError:
                        pass
        return mt

    env = dict(os.environ, MLAPI_TPU_RELOAD_CHILD="1")
    cmd = [sys.executable, "-m", "mlapi_tpu.serving", *argv]
    while True:
        snap = snapshot()
        child = subprocess.Popen(cmd, env=env)
        restart = False
        try:
            while True:
                time.sleep(0.5)
                if child.poll() is not None:
                    # A crashed child (e.g. a transient syntax error
                    # mid-edit) must NOT end the watch — that's the
                    # state a dev-reload loop exists to recover from.
                    # Keep watching; the next change respawns it.
                    _log.warning(
                        "server exited with code %d; waiting for a "
                        "source change to restart", child.returncode,
                    )
                    while snapshot() == snap:
                        time.sleep(0.5)
                    restart = True
                    break
                if snapshot() != snap:
                    _log.info("source change detected; restarting server")
                    restart = True
                    break
        except KeyboardInterrupt:
            restart = False
        if child.poll() is None:
            child.send_signal(signal.SIGTERM)
            try:
                child.wait(10)
            except subprocess.TimeoutExpired:
                child.kill()
        if not restart:
            return 0


def _forwarded_engine_flags(args) -> list:
    """The engine/app flags a topology supervisor (``--workers``,
    ``--router``) forwards verbatim to every child server process —
    one list so the two supervisors cannot drift (a flag added to one
    but not the other would silently serve a different engine config
    per topology)."""
    cmd: list = []
    if args.max_batch is not None:
        cmd += ["--max-batch", str(args.max_batch)]
    if getattr(args, "quantize", None):
        cmd += ["--quantize", args.quantize]
    if getattr(args, "kv_quant", None):
        cmd += ["--kv-quant", args.kv_quant]
    if getattr(args, "decode_attn_impl", None):
        cmd += ["--decode-attn-impl", args.decode_attn_impl]
    if getattr(args, "kv_page_size", None):
        cmd += ["--kv-page-size", str(args.kv_page_size)]
    if getattr(args, "kv_pages", None):
        cmd += ["--kv-pages", str(args.kv_pages)]
    if getattr(args, "kv_tier_bytes", 0):
        cmd += ["--kv-tier-bytes", str(args.kv_tier_bytes)]
    if getattr(args, "kv_tier_disk_dir", None):
        # Children may share one dir: blob filenames are pid-scoped,
        # each process indexes only its own files (the bytes budget is
        # per-process), and the startup sweep only unlinks files
        # whose owner pid is dead. Forwarded independently of the
        # bytes flag so a mis-paired config fails in the child
        # exactly as it would single-process (main() also rejects it
        # before supervising).
        cmd += ["--kv-tier-disk-dir", args.kv_tier_disk_dir]
    if getattr(args, "kv_peer_fetch", False):
        cmd += ["--kv-peer-fetch"]
    if getattr(args, "adapter_slots", 0):
        cmd += ["--adapter-slots", str(args.adapter_slots)]
        if getattr(args, "adapter_store_bytes", 0):
            cmd += ["--adapter-store-bytes", str(args.adapter_store_bytes)]
        if getattr(args, "adapter_disk_dir", None):
            # Same shared-dir discipline as --kv-tier-disk-dir: blob
            # filenames are pid-scoped, so children can share one dir.
            cmd += ["--adapter-disk-dir", args.adapter_disk_dir]
        for spec in getattr(args, "adapter", None) or ():
            cmd += ["--adapter", spec]
    if getattr(args, "replica_role", "mixed") != "mixed":
        # A uniform role for every child (the role-split supervisor
        # appends its own per-child --replica-role AFTER these, and
        # argparse's last occurrence wins).
        cmd += ["--replica-role", args.replica_role]
    if not getattr(args, "prefill_page_native", True):
        cmd += ["--no-prefill-page-native"]
    if not getattr(args, "prefill_interleave", True):
        cmd += ["--no-prefill-interleave"]
    cmd += ["--sched-max-batches",
            str(getattr(args, "sched_max_batches", 2))]
    # Multi-model + multi-tenant config replicates to every child:
    # the whole fleet serves the same registry under the same quota
    # table (per-model replica groups come from children launched
    # with DIFFERENT --model sets via --replica-urls).
    for spec in getattr(args, "model", None) or ():
        cmd += ["--model", spec]
    for flag, key in (
        ("--tenant-pages", "tenant_pages"),
        ("--tenant-slots", "tenant_slots"),
        ("--tenant-weight", "tenant_weight"),
    ):
        for spec in getattr(args, key, None) or ():
            cmd += [flag, spec]
    if getattr(args, "mesh_shape", None):
        cmd += ["--mesh-shape", args.mesh_shape]
    if getattr(args, "draft_checkpoint", None):
        cmd += ["--draft-checkpoint", args.draft_checkpoint]
    if getattr(args, "spec_sample", False):
        cmd += ["--spec-sample"]
    if getattr(args, "default_deadline_ms", None) is not None:
        cmd += ["--default-deadline-ms", str(args.default_deadline_ms)]
    if not getattr(args, "admission_control", True):
        cmd += ["--no-admission-control"]
    cmd += ["--drain-timeout-s", str(getattr(args, "drain_timeout_s", 10.0))]
    return cmd


def _supervise_workers(n: int, ckpt: str, args) -> int:
    """SO_REUSEPORT worker pool: spawn ``n`` fresh server processes
    all bound to the same (host, port), restart any that die, fan out
    SIGTERM on shutdown. This is the CPU-attach scale-out (one asyncio
    loop saturates one core at ~6-8k req/s); the TPU is
    single-process-exclusive, so TPU scale-out is more chips on a DP
    mesh, not more processes — workers are pinned to CPU unless the
    operator overrides ``MLAPI_TPU_PLATFORM`` themselves."""
    import os
    import signal
    import subprocess
    import sys
    import time

    env = dict(os.environ, MLAPI_TPU_WORKER="1")
    if not env.get("MLAPI_TPU_PLATFORM"):
        env["MLAPI_TPU_PLATFORM"] = "cpu"
        _log.info(
            "--workers: pinning workers to CPU (MLAPI_TPU_PLATFORM=cpu); "
            "the TPU is single-process-exclusive — scale TPU serving "
            "with more chips, not more processes"
        )
    cmd = [
        sys.executable, "-m", "mlapi_tpu.serving",
        "--checkpoint", ckpt, "--host", args.host, "--port", str(args.port),
        "--max-wait-ms", str(args.max_wait_ms),
        *_forwarded_engine_flags(args),
    ]
    # systemd/docker stop the supervisor with SIGTERM; without a
    # handler the finally below never runs and the workers are
    # orphaned still bound to the port (SO_REUSEPORT would then let a
    # restarted service share it with the stale set, silently).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))

    children = [subprocess.Popen(cmd, env=env) for _ in range(n)]
    spawned_at = [time.time()] * n
    restart_at = [0.0] * n   # earliest next respawn (backoff)
    backoff = [0.5] * n      # doubles on fast deaths, resets on survival
    fast_deaths = 0          # consecutive across ALL workers
    _log.info("spawned %d workers on %s:%d", n, args.host, args.port)
    try:
        while True:
            time.sleep(0.5)
            for i, c in enumerate(children):
                if c is None:
                    continue
                rc = c.poll()
                if rc is None:
                    continue
                lived = time.time() - spawned_at[i]
                if lived < 5.0:
                    # Died during/just after startup: back off — a
                    # persistent boot failure (bad checkpoint, bind
                    # error) must not crash-loop at full import cost.
                    fast_deaths += 1
                    backoff[i] = min(30.0, backoff[i] * 2)
                    if fast_deaths >= 3 * n:
                        _log.error(
                            "workers keep dying at startup (rc=%d); "
                            "giving up", rc,
                        )
                        return 1
                else:
                    fast_deaths = 0
                    backoff[i] = 0.5
                _log.warning(
                    "worker %d (pid %d) exited rc=%d after %.1fs; "
                    "restarting in %.1fs", i, c.pid, rc, lived, backoff[i],
                )
                restart_at[i] = time.time() + backoff[i]
                spawned_at[i] = time.time() + backoff[i]
                children[i] = None  # placeholder until respawn

            for i, c in enumerate(children):
                if c is None and time.time() >= restart_at[i]:
                    children[i] = subprocess.Popen(cmd, env=env)
                    spawned_at[i] = time.time()
    except KeyboardInterrupt:
        pass
    finally:
        # SIGTERM fan-out, then wait out the workers' DRAIN budget
        # (plus startup/teardown slack) before escalating to SIGKILL —
        # the supervisor must never cut a drain short that it also
        # configured.
        for c in children:
            if c is not None and c.poll() is None:
                c.send_signal(signal.SIGTERM)
        deadline = (
            time.time() + getattr(args, "drain_timeout_s", 10.0) + 5.0
        )
        for c in children:
            if c is None:
                continue
            try:
                c.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                c.kill()
    return 0


def _supervise_router(ckpt: str | None, args) -> int:
    """``--router`` topology: N full engine replicas (separate
    processes, each the whole r13 stack on its own port — ports
    ``--port``+1..N) under one prefix-affinity router serving the
    front ``--port`` in THIS process. Replica discovery speaks the
    same env convention as the multi-host rendezvous trio
    (``parallel/distributed.py``): the supervisor exports
    ``MLAPI_TPU_REPLICAS=host:p1,host:p2`` (+ per-child
    ``MLAPI_TPU_REPLICA_ID``) to everything it spawns, and a router
    over externally-launched replicas (other hosts, k8s pods) reads
    the same variable — or ``--replica-urls`` — instead of spawning.

    Replicas are pinned to CPU unless the operator overrides
    ``MLAPI_TPU_PLATFORM`` (same rule as ``--workers``: the TPU is
    single-process-exclusive — a TPU fleet is one replica per host
    with ``--replica-urls`` across hosts, not N processes on one
    chip). Dead replicas respawn with backoff; while one is down the
    router routes around it (HRW moves only ITS affinity slice) and
    the health poll folds it back in when it returns."""
    import os
    import signal as _signal
    import subprocess
    import sys
    import time

    from mlapi_tpu.parallel.distributed import (
        REPLICAS_ENV_VAR,
        replica_endpoints_from_env,
    )
    from mlapi_tpu.serving.router import Router, build_router_app

    # Role-split topology (r18): --prefill-replicas P --decode-replicas D
    # spawns P prefill-role + D decode-role replicas instead of
    # --replicas mixed ones. Pools size independently per role group
    # (--prefill-kv-pages / --decode-kv-pages override --kv-pages for
    # their group: prompt-only working sets vs prompt+generation).
    n_pre = getattr(args, "prefill_replicas", 0)
    n_dec = getattr(args, "decode_replicas", 0)
    roles: list | None = None
    if args.replica_urls:
        endpoints = replica_endpoints_from_env(args.replica_urls)
        spawn = False
    else:
        endpoints = replica_endpoints_from_env()  # $MLAPI_TPU_REPLICAS
        spawn = not endpoints
        if spawn:
            n = n_pre + n_dec if (n_pre or n_dec) else args.replicas
            endpoints = [
                (args.host, args.port + 1 + i) for i in range(n)
            ]
            if n_pre or n_dec:
                roles = ["prefill"] * n_pre + ["decode"] * n_dec
    if not endpoints:
        raise SystemExit("--router: no replica endpoints")
    env_spec = ",".join(f"{h}:{p}" for h, p in endpoints)

    cmds: list = []
    if spawn:
        base_env = dict(
            os.environ, MLAPI_TPU_REPLICA="1", **{REPLICAS_ENV_VAR: env_spec}
        )
        if not base_env.get("MLAPI_TPU_PLATFORM"):
            base_env["MLAPI_TPU_PLATFORM"] = "cpu"
            _log.info(
                "--router: pinning replicas to CPU (MLAPI_TPU_PLATFORM="
                "cpu); TPU fleets run one replica per host via "
                "--replica-urls"
            )
        for i, (h, p) in enumerate(endpoints):
            role_flags: list = []
            if roles is not None:
                role_flags += ["--replica-role", roles[i]]
                # Per-role pool sizing, appended AFTER the shared
                # flags so argparse's last-occurrence rule makes it
                # the group's --kv-pages override.
                per_role = (
                    getattr(args, "prefill_kv_pages", None)
                    if roles[i] == "prefill"
                    else getattr(args, "decode_kv_pages", None)
                )
                if per_role is not None:
                    role_flags += ["--kv-pages", str(per_role)]
            cmds.append(
                (
                    [
                        sys.executable, "-m", "mlapi_tpu.serving",
                        "--checkpoint", ckpt, "--host", h, "--port", str(p),
                        "--max-wait-ms", str(args.max_wait_ms),
                        *_forwarded_engine_flags(args),
                        *role_flags,
                    ],
                    dict(base_env, MLAPI_TPU_REPLICA_ID=str(i)),
                )
            )

    async def _run() -> int:
        router = Router(
            endpoints,
            policy=args.route_policy,
            affinity_prefix_bytes=args.affinity_prefix_bytes,
            health_poll_s=args.health_poll_s,
            queue_depth_limit=args.queue_depth_limit,
            # Gate routing on a passed health poll: a replica still
            # compiling its warmup grids must not eat traffic.
            assume_live=False,
            roles=roles,
        )
        # Per-model front routes mirror the replicas' own surface:
        # every --model id plus the implicit default entry (replicas
        # in multi-model mode serve /models/default/* too).
        mids = [
            spec.partition("=")[0].strip()
            for spec in (getattr(args, "model", None) or ())
        ]
        server = Server(
            build_router_app(
                router, model_ids=(["default"] + mids) if mids else None
            ),
            host=args.host, port=args.port,
        )
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop_ev.set)
            except (NotImplementedError, RuntimeError):
                pass

        # fork+exec through the executor: this loop IS the router's
        # serving loop, and Popen blocks the calling thread for the
        # whole spawn (mlapi-lint MLA008, caught r19). Startup has no
        # traffic yet, but the respawn loop below shares the shape —
        # one helper, both sites off the loop.
        def _spawn(i: int):
            return subprocess.Popen(cmds[i][0], env=cmds[i][1])

        children: list = [
            await loop.run_in_executor(None, _spawn, i)
            for i in range(len(cmds))
        ]
        spawned_at = [time.time()] * len(children)
        restart_at = [0.0] * len(children)
        backoff = [0.5] * len(children)

        async def _respawn_loop():
            # Same backoff discipline as the --workers supervisor, but
            # no global give-up: the router's whole job is serving on
            # the replicas that ARE up while a bad one crash-loops at
            # bounded cost.
            while True:
                await asyncio.sleep(0.5)
                for i, c in enumerate(children):
                    if c is not None and c.poll() is not None:
                        lived = time.time() - spawned_at[i]
                        backoff[i] = (
                            0.5 if lived >= 5.0
                            else min(30.0, backoff[i] * 2)
                        )
                        _log.warning(
                            "replica %d (pid %d) exited rc=%d after "
                            "%.1fs; respawning in %.1fs",
                            i, c.pid, c.returncode, lived, backoff[i],
                        )
                        restart_at[i] = time.time() + backoff[i]
                        children[i] = None
                    elif c is None and time.time() >= restart_at[i]:
                        # Respawn happens MID-TRAFFIC: the fork+exec
                        # must not stall in-flight relays (MLA008).
                        children[i] = await loop.run_in_executor(
                            None, _spawn, i
                        )
                        spawned_at[i] = time.time()

        respawn = None
        try:
            # Inside the try: a front server that fails to bind (port
            # taken) must still run the finally's SIGTERM fan-out —
            # never orphan N engine replicas behind a dead router.
            await server.start()
            _log.info(
                "router (%s) on %s:%d over replicas %s",
                args.route_policy, args.host, server.port, env_spec,
            )
            if spawn:
                respawn = asyncio.create_task(_respawn_loop())
            await stop_ev.wait()
        finally:
            if respawn is not None:
                respawn.cancel()
            # Drain the FLEET: fan SIGTERM to the replicas (each sheds
            # new work and drains under its own --drain-timeout-s)
            # while the router keeps relaying in-flight streams and
            # answering /healthz "degraded" — the layer above sees a
            # draining fleet, never connection-refused mid-stream.
            for c in children:
                if c is not None and c.poll() is None:
                    c.send_signal(_signal.SIGTERM)
            deadline = time.time() + args.drain_timeout_s + 5.0
            while time.time() < deadline and any(
                c is not None and c.poll() is None for c in children
            ):
                await asyncio.sleep(0.2)
            for c in children:
                if c is not None and c.poll() is None:
                    c.kill()
            await server.stop()
        return 0

    try:
        return asyncio.run(_run())
    except KeyboardInterrupt:
        return 0


def main(argv=None) -> None:
    from mlapi_tpu.utils.platform import (
        apply_platform_override,
        enable_compile_cache,
    )

    apply_platform_override()
    enable_compile_cache()
    parser = argparse.ArgumentParser("mlapi_tpu.serving")
    parser.add_argument("--checkpoint", help="committed checkpoint dir")
    parser.add_argument(
        "--demo-iris", action="store_true", help="train Iris now and serve it"
    )
    parser.add_argument(
        "--model", action="append", metavar="ID=CHECKPOINT",
        help="multi-model serving (repeatable): ADD model ID from "
             "CHECKPOINT to this process's registry, served at "
             "/models/ID/{generate|predict}. --checkpoint stays the "
             "DEFAULT model (id 'default', owns the legacy /generate "
             "and /predict routes). Generative entries get their own "
             "BatchRun lanes; classification/recsys entries get the "
             "scoring fast path — formed micro-batches ride the "
             "first generative entry's unit scheduler as typed "
             "'score' units between decode chunks (one HBM, one "
             "dispatch thread, one policy). Watch model.<id>.* on "
             "/metrics",
    )
    parser.add_argument(
        "--tenant-pages", action="append", metavar="TENANT=N",
        help="per-tenant KV page quota (repeatable; paged engines): "
             "a tenant holding reservations may not grow past N "
             "pages — further group starts defer (counted in "
             "generate.sched_tenant_pages_deferred and "
             "tenant.<t>.deferrals) until its own pages free. "
             "Unlisted tenants are unquotaed",
    )
    parser.add_argument(
        "--tenant-slots", action="append", metavar="TENANT=N",
        help="per-tenant adapter-slot quota (repeatable; with "
             "--adapter-slots): same deferral discipline as "
             "--tenant-pages, over device adapter slots",
    )
    parser.add_argument(
        "--tenant-weight", action="append", metavar="TENANT=W",
        help="per-tenant scheduling weight (repeatable; default "
             "1.0): deadline slack divides by W in the unit "
             "scheduler's pick policy, so a weight-2 tenant's "
             "requests look twice as urgent at equal slack. "
             "Starvation-safe: alternation floors still apply",
    )
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--max-batch", type=int, default=None)
    parser.add_argument(
        "--max-wait-ms", type=float, default=0.2, help="micro-batch window"
    )
    parser.add_argument(
        "--workers", type=int, default=1,
        help="number of SO_REUSEPORT server processes (CPU-attach "
             "scale-out; needs an explicit --port)",
    )
    parser.add_argument(
        "--router", action="store_true",
        help="scale-out topology: spawn --replicas full engine "
             "replicas (separate processes on ports --port+1..N) and "
             "serve a prefix-affinity front-end router on --port — "
             "repeated prompt prefixes land on the replica whose "
             "pool pages / kv-tier blobs are already warm "
             "(rendezvous hashing; power-of-two-choices fallback when "
             "the preferred replica sheds/drains/overloads). With "
             "--replica-urls (or $MLAPI_TPU_REPLICAS) the router "
             "mounts over externally-launched replicas instead of "
             "spawning",
    )
    parser.add_argument(
        "--replicas", type=int, default=2,
        help="with --router: how many engine replica processes to "
             "spawn (default 2)",
    )
    parser.add_argument(
        "--replica-role", choices=["prefill", "decode", "mixed"],
        default="mixed",
        help="prefill/decode disaggregation role (r18, generative "
             "checkpoints): 'prefill' replicas take the first hop of "
             "role-split generative traffic — they run the prompt's "
             "chunked prefill and PUSH each finished chunk's KV to "
             "the decode replica the router named (POST /kv/push, "
             "the r17 blob wire format at chunk granularity); "
             "'decode' replicas stage pushed chunks and activate the "
             "stream with ZERO local prefill FLOPs the moment the "
             "last chunk lands (generate.kv_push_applied moves while "
             "prefix_builds and prefill_chunks stay flat). 'mixed' "
             "(default) serves both phases — an all-mixed fleet is "
             "bit-identical to the flag never existing. Roles "
             "specialize routing and pool sizing, not capability: "
             "either role still serves a plain /generate end to end "
             "(the router's role-starved fallback)",
    )
    parser.add_argument(
        "--prefill-replicas", type=int, default=0,
        help="with --router: spawn this many PREFILL-role replicas "
             "(combined with --decode-replicas, replaces --replicas; "
             "ports still derive from --port). The router sends new "
             "generative requests to the prefill pool (p2c by load) "
             "and the stream to the HRW-chosen decode replica; a "
             "role-starved fleet degrades to mixed routing, counted "
             "in router.role_fallback_mixed",
    )
    parser.add_argument(
        "--decode-replicas", type=int, default=0,
        help="with --router: spawn this many DECODE-role replicas "
             "(see --prefill-replicas)",
    )
    parser.add_argument(
        "--prefill-kv-pages", type=int, default=None,
        help="with --prefill-replicas and --kv-page-size: the "
             "prefill pool's --kv-pages override — prefill replicas "
             "hold prompt-only working sets (no generation tail), so "
             "their pool sizes independently of the decode pool's",
    )
    parser.add_argument(
        "--decode-kv-pages", type=int, default=None,
        help="with --decode-replicas and --kv-page-size: the decode "
             "pool's --kv-pages override (prompt + generation "
             "working sets)",
    )
    parser.add_argument(
        "--replica-urls", default=None,
        help="with --router: comma-separated host:port replica "
             "endpoints to route over instead of spawning (multi-host "
             "fleets; same format as $MLAPI_TPU_REPLICAS)",
    )
    parser.add_argument(
        "--affinity-prefix-bytes", type=int, default=64,
        help="with --router: how many leading BYTES of the request's "
             "prompt prefix (the 'prefix' field when present, else "
             "'text') feed the rendezvous hash — the affinity key. "
             "The router never tokenizes",
    )
    parser.add_argument(
        "--route-policy", choices=["affinity", "round_robin"],
        default="affinity",
        help="with --router: 'affinity' (prefix-hash rendezvous "
             "routing, the default) or 'round_robin' (the A/B "
             "baseline the bench compares against — every replica "
             "rebuilds every prefix)",
    )
    parser.add_argument(
        "--health-poll-s", type=float, default=0.5,
        help="with --router: per-replica /healthz + /metrics poll "
             "cadence (liveness, draining, queue depth)",
    )
    parser.add_argument(
        "--queue-depth-limit", type=int, default=None,
        help="with --router: a replica whose scraped queue depth plus "
             "router-side in-flight exceeds this is skipped by "
             "routing until it recedes (default: no limit — replica "
             "admission control sheds instead)",
    )
    parser.add_argument(
        "--quantize", choices=["int8"], default=None,
        help="weight-only quantization at load: half the parameter "
             "HBM, dequantization fused into each matmul "
             "(single-chip serving only)",
    )
    parser.add_argument(
        "--kv-quant", choices=["int8"], default=None,
        help="store decode KV caches as int8 payload + per-token-"
             "per-head f32 scales: ~2x less decode HBM per cached "
             "token, ~2x the cache/prefix/slot budget; quantize "
             "fused into the append, dequantize into the attention "
             "read. Generative checkpoints only; composes with "
             "--quantize and --mesh-shape (the draft's cache rides "
             "the same format)",
    )
    parser.add_argument(
        "--decode-attn-impl", choices=["einsum", "flash"], default=None,
        help="decode-step attention: 'einsum' (reference oracle; "
             "dequantizes an int8 cache at the read seam) or 'flash' "
             "(Pallas split-K flash-decode kernel that reads int8 "
             "cache tiles IN-kernel — the --kv-quant byte saving "
             "reaches the decode read, not just storage). Generative "
             "checkpoints only; the draft, if any, rides the same "
             "impl",
    )
    parser.add_argument(
        "--kv-page-size", type=int, default=None,
        help="paged KV cache: allocate decode caches as fixed-size "
             "pages of this many tokens from a device-resident pool "
             "(page tables per sequence) instead of contiguous "
             "per-slot tier buffers — near-zero padding waste, "
             "ref-counted shared prefix pages with copy-on-write, "
             "O(table) batch growth/compaction. Token streams are "
             "pinned identical to contiguous allocation; composes "
             "with --kv-quant and --decode-attn-impl flash (the "
             "kernel reads pages via a page-table index map). "
             "Generative checkpoints only",
    )
    parser.add_argument(
        "--kv-pages", type=int, default=None,
        help="with --kv-page-size: total pool pages (default: the "
             "contiguous-equivalent budget — max_batch slots at the "
             "default cache tier). A full pool rejects loudly; watch "
             "generate.kv_page_utilization on /metrics",
    )
    parser.add_argument(
        "--kv-tier-bytes", type=int, default=0,
        help="hierarchical KV tier: keep up to this many bytes of "
             "EVICTED prefix KV page sets in host RAM (LRU), in their "
             "stored format (--kv-quant int8 halves the spill "
             "bandwidth) — a re-arrival restores by device_put with "
             "zero prefill FLOPs instead of paying a cold prefill; "
             "streams are pinned token-identical across evict+restore "
             "vs never-evicted. Multiplies the effective prefix "
             "budget by the host-RAM/HBM ratio. 0 (default) disables "
             "the tier: evictions discard as before. Watch "
             "generate.kv_prefix_restore_hits / kv_tier_bytes_in_use "
             "on /metrics. Generative checkpoints only",
    )
    parser.add_argument(
        "--kv-tier-disk-dir", default=None,
        help="with --kv-tier-bytes: back the tier's blob payloads "
             "with .npz files under this directory (only the index "
             "stays in RAM; the bytes budget then bounds disk use). "
             "Files are per-process and inert across restarts — a "
             "stale blob that no longer matches the live pool "
             "geometry is dropped, never restored wrong",
    )
    parser.add_argument(
        "--kv-peer-fetch", action="store_true", default=False,
        help="peer-to-peer prefix-KV fetch between router replicas: "
             "serve this replica's warm prefix blobs on GET "
             "/kv/prefix (stored format — int8 KV crosses the wire "
             "at half the bytes) and, on a local miss, fetch the "
             "blob from the replica the router's x-mlapi-warm-peer "
             "hint names instead of cold-prefilling — a failover, "
             "drain, or depth overflow costs one host-to-host copy, "
             "not an O(P^2) re-prefill. Off (default): bit-identical "
             "to r16. Watch generate.kv_peer_fetch_hits / "
             "kv_peer_serve_bytes on /metrics. Generative "
             "checkpoints only",
    )
    parser.add_argument(
        "--prefill-page-native", action=argparse.BooleanOptionalAction,
        default=True,
        help="with --kv-page-size: prefill writes K/V straight into "
             "pool pages through the page table (default) — the "
             "contiguous-then-adopt copy drops to exactly zero bytes "
             "(generate.prefill_adopt_bytes reads 0). "
             "--no-prefill-page-native keeps the r09 adopt path for "
             "comparison; token streams are pinned identical either "
             "way",
    )
    parser.add_argument(
        "--prefill-interleave", action=argparse.BooleanOptionalAction,
        default=True,
        help="with --kv-page-size: a long prompt admitted into a "
             "running batch prefills as chunked dispatches "
             "interleaved one-for-one with decode chunks (default) — "
             "in-flight streams stall by at most ONE prefill-chunk "
             "dispatch instead of the whole prompt "
             "(generate.interleave_max_stall pins the bound). "
             "--no-prefill-interleave defers long joiners to their "
             "own batch",
    )
    # r22: `--no-scheduler` retired on schedule (deprecated r20,
    # kept one release r21). The scheduler IS the execution model;
    # the one thing the flag still did — pin a single lane — is
    # `--sched-max-batches 1`, same machinery, same token streams.
    # Passing the dead flag now errors at parse, which is the
    # scheduled removal behaving exactly like the r21 retirements.
    parser.add_argument(
        "--sched-max-batches", type=int, default=2,
        help="how many batches may be live at once (lanes); 1 pins "
             "the legacy serial semantics on the same machinery "
             "(what --no-scheduler, retired r22, used to do). Paged "
             "engines additionally gate new lanes on the pool's "
             "free-page budget (generate.sched_pages_deferred counts "
             "waits)",
    )
    parser.add_argument(
        "--draft-checkpoint", default=None,
        help="speculative decoding: a smaller same-tokenizer "
             "checkpoint whose proposals the target verifies in one "
             "block — speeds up single-stream greedy generation",
    )
    parser.add_argument(
        "--spec-sample", action="store_true",
        help="with --draft-checkpoint: also speculate SAMPLED "
             "(temperature > 0) single-stream requests via "
             "acceptance-rejection — exact target distribution, but "
             "streams under concurrent admission churn are not "
             "byte-reproducible per seed (solo runs are)",
    )
    parser.add_argument(
        "--adapter-slots", type=int, default=0,
        help="many-adapter LoRA serving: device-resident (A, B) slot "
             "pool size — up to this many tenants' adapters resident "
             "in HBM at once over the ONE shared base model "
             "(HBM cost: base + N x generate.adapter_slot_bytes). "
             "Requests name tenants via the 'adapter' field; mixed-"
             "tenant batches apply per-row deltas via gathered BGMV. "
             "0 (default) disables the subsystem entirely. "
             "Generative checkpoints only",
    )
    parser.add_argument(
        "--adapter-store-bytes", type=int, default=0,
        help="with --adapter-slots: host-side adapter store LRU "
             "byte budget (default 256 MiB when unset) — evicted "
             "device slots refill from here without a peer fetch",
    )
    parser.add_argument(
        "--adapter-disk-dir", default=None,
        help="with --adapter-slots: spill directory for the host "
             "adapter store (same pid-scoped blob discipline as "
             "--kv-tier-disk-dir)",
    )
    parser.add_argument(
        "--adapter", action="append", default=None, metavar="ID=PATH",
        help="preload an adapter into the host store at startup "
             "(repeatable): PATH is an exported adapter file "
             "(models/lora.py export_adapter wire format) registered "
             "under ID — the file's embedded id must match",
    )
    parser.add_argument(
        "--default-deadline-ms", type=float, default=None,
        help="end-to-end wall-clock budget applied to requests that "
             "name no deadline_ms of their own: expiry at any "
             "dispatch boundary (queue wait, prefill chunk, decode "
             "chunk, spec round) ends the request with a "
             "deadline_exceeded terminal frame (504 unary). Default: "
             "no deadline",
    )
    parser.add_argument(
        "--admission-control", action=argparse.BooleanOptionalAction,
        default=True,
        help="SLO-aware admission: estimate queue-wait + TTFT from "
             "the live p95 reservoirs and shed deadlined requests "
             "that cannot finish in time at the door (503 + computed "
             "retry-after); sustained queue pressure engages the "
             "brownout ladder (clamp max_new_tokens, suppress "
             "speculation, evict idle prefix pages) before shedding. "
             "--no-admission-control disables the estimate and the "
             "ladder (deadlines still enforce)",
    )
    parser.add_argument(
        "--drain-timeout-s", type=float, default=10.0,
        help="graceful-drain budget on shutdown (SIGTERM/SIGINT): new "
             "admissions shed 503 and /healthz reports \"draining\" "
             "while in-flight streams run to completion; streams "
             "still live after the budget are cancelled with proper "
             "terminal frames. The --workers supervisor waits this "
             "long after SIGTERM before SIGKILL",
    )
    parser.add_argument(
        "--mesh-shape", default=None,
        help="serve sharded over a (data, model) device mesh, e.g. "
             "'1,4' or '2,4' — params follow the model's declared TP "
             "layout (classification AND generative engines; the "
             "draft, if any, rides the same mesh). Shape must cover "
             "the visible devices",
    )
    parser.add_argument(
        "--profiler-port", type=int, default=0,
        help="start a jax.profiler server on this port (XProf/TensorBoard "
             "can attach live)",
    )
    parser.add_argument(
        "--reload", action="store_true",
        help="dev loop: restart the server when package sources change",
    )
    args = parser.parse_args(argv)

    if args.reload:
        import os
        import sys

        if os.environ.get("MLAPI_TPU_RELOAD_CHILD") != "1":
            sys.exit(
                _watch_and_reexec(argv if argv is not None else sys.argv[1:])
            )

    if args.profiler_port:
        import jax.profiler

        jax.profiler.start_server(args.profiler_port)
        _log.info("jax profiler server on port %d", args.profiler_port)

    import os
    import sys

    # A router over external replicas spawns no engine of its own —
    # the only mode that needs no checkpoint.
    router_external = args.router and bool(
        args.replica_urls or os.environ.get("MLAPI_TPU_REPLICAS")
    )
    if not args.checkpoint and not args.demo_iris and not router_external:
        parser.error("need --checkpoint or --demo-iris")
    if args.kv_tier_disk_dir and not args.kv_tier_bytes:
        # Validate BEFORE a topology supervisor forks: the same
        # mis-pair must be equally loud in every mode (the engine
        # would reject it anyway, but only inside each child).
        parser.error("--kv-tier-disk-dir requires --kv-tier-bytes > 0")
    if (
        args.adapter_store_bytes or args.adapter_disk_dir or args.adapter
    ) and not args.adapter_slots:
        # Same before-the-fork loudness as the kv-tier mis-pair: the
        # engine rejects it anyway, but only inside each child.
        parser.error(
            "--adapter-store-bytes/--adapter-disk-dir/--adapter "
            "require --adapter-slots > 0"
        )
    if args.router and args.workers > 1:
        parser.error(
            "--router and --workers are different topologies (distinct "
            "ports with affinity vs one shared port); pick one"
        )
    if (args.prefill_replicas or args.decode_replicas) and not args.router:
        parser.error(
            "--prefill-replicas/--decode-replicas describe a --router "
            "topology; without the router nothing routes by role"
        )
    if (args.prefill_replicas or args.decode_replicas) and (
        args.replica_urls or os.environ.get("MLAPI_TPU_REPLICAS")
    ):
        parser.error(
            "--prefill-replicas/--decode-replicas spawn the role "
            "topology; over an external fleet (--replica-urls / "
            "$MLAPI_TPU_REPLICAS) launch the replicas with "
            "--replica-role yourself"
        )
    if (
        args.prefill_kv_pages or args.decode_kv_pages
    ) and not args.kv_page_size:
        # Same before-the-fork loudness as the tier mis-pair below.
        parser.error(
            "--prefill-kv-pages/--decode-kv-pages require "
            "--kv-page-size (they size the paged pool per role group)"
        )
    if router_external:
        ckpt = args.checkpoint
    else:
        ckpt = args.checkpoint or _demo_iris_checkpoint()

    is_worker = os.environ.get("MLAPI_TPU_WORKER") == "1"
    is_replica = os.environ.get("MLAPI_TPU_REPLICA") == "1"
    if args.router and not is_replica:
        if args.port == 0 and not router_external:
            parser.error("--router needs an explicit --port (replica "
                         "ports derive from it: --port+1..N)")
        sys.exit(_supervise_router(ckpt, args))
    if args.workers > 1 and not is_worker:
        if args.port == 0:
            parser.error("--workers needs an explicit --port "
                         "(every worker binds the same one)")
        sys.exit(_supervise_workers(args.workers, ckpt, args))

    # Multi-host bootstrap, parity with train/__main__:47 (a no-op on
    # a plain single host): a multi-host serving deployment exports
    # the same MLAPI_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID trio and
    # every process joins the rendezvous BEFORE touching devices —
    # jax.devices() below then spans the pod, so --mesh-shape can name
    # a global mesh. NOT in --workers children: the SO_REUSEPORT pool
    # is single-host CPU scale-out and every child inherits the SAME
    # PROCESS_ID — N workers claiming one rendezvous slot would wedge
    # the pool (a worker is a replica, not a pod rank). Same for
    # --router replica children: the HTTP replica set is its OWN
    # discovery plane ($MLAPI_TPU_REPLICAS), not pod ranks.
    if not is_worker and not is_replica:
        from mlapi_tpu.parallel import initialize_from_env

        initialize_from_env()

    mesh = None
    if args.mesh_shape:
        import math

        import jax

        from mlapi_tpu.parallel import create_mesh

        try:
            shape = tuple(int(d) for d in args.mesh_shape.split(","))
        except ValueError:
            parser.error(
                f"--mesh-shape {args.mesh_shape!r} is not a "
                "comma-separated list of integers (e.g. '1,4')"
            )
        if not shape or any(d < 1 for d in shape):
            parser.error(
                f"--mesh-shape {args.mesh_shape!r}: every dimension "
                "must be a positive integer"
            )
        need = math.prod(shape)
        devices = jax.devices()
        if need > len(devices):
            parser.error(
                f"--mesh-shape {args.mesh_shape} needs {need} devices; "
                f"{len(devices)} visible"
            )
        # A shape smaller than the host's device count serves on the
        # first `need` devices (create_mesh's rule).
        mesh = create_mesh(shape)
    try:
        engine = InferenceEngine.from_checkpoint(
            ckpt, quantize=args.quantize,
            kv_quant=args.kv_quant,
            decode_attn_impl=args.decode_attn_impl,
            kv_page_size=args.kv_page_size,
            kv_pages=args.kv_pages,
            prefill_page_native=args.prefill_page_native,
            prefill_interleave=args.prefill_interleave,
            kv_tier_bytes=args.kv_tier_bytes,
            kv_tier_disk_dir=args.kv_tier_disk_dir,
            kv_peer_fetch=args.kv_peer_fetch,
            replica_role=args.replica_role,
            draft_checkpoint=args.draft_checkpoint,
            spec_sample=args.spec_sample,
            sched_max_batches=args.sched_max_batches,
            adapter_slots=args.adapter_slots,
            adapter_store_bytes=args.adapter_store_bytes,
            adapter_disk_dir=args.adapter_disk_dir,
            mesh=mesh,
        )
    except NotServable as e:
        parser.error(str(e))
    for spec in args.adapter or ():
        # Startup preload: ID=PATH into the host store (device slots
        # install lazily, at the first request naming the tenant).
        from mlapi_tpu.serving.adapter_store import load_adapter

        aid, _, path = spec.partition("=")
        if not aid or not path:
            parser.error(f"--adapter {spec!r}: expected ID=PATH")
        try:
            file_aid, payload, rank, nbytes = load_adapter(path)
        except (OSError, ValueError) as e:
            parser.error(f"--adapter {spec!r}: {e}")
        if file_aid != aid:
            parser.error(
                f"--adapter {spec!r}: file embeds adapter id "
                f"{file_aid!r} — ids must match (rename the export, "
                "not the flag)"
            )
        engine.register_adapter(aid, payload)
        _log.info(
            "preloaded adapter %r (rank %d, %d bytes)", aid, rank, nbytes
        )
    models = None
    if args.model:
        # Multi-model registry: --checkpoint is the default entry;
        # each --model ID=CHECKPOINT adds one. Extra entries load
        # with stock engine knobs — the tuned flags (--kv-page-size,
        # --quantize, ...) configure the DEFAULT model; per-entry
        # tuning is a config file's job, not a flag matrix's.
        import re as _re

        from mlapi_tpu.serving.registry import ModelRegistry

        engines = {"default": engine}
        for spec in args.model:
            mid, _, mpath = spec.partition("=")
            mid = mid.strip()
            if not mid or not mpath:
                parser.error(f"--model {spec!r}: expected ID=CHECKPOINT")
            if not _re.fullmatch(r"[A-Za-z0-9._-]+", mid):
                parser.error(
                    f"--model {spec!r}: id must be URL-path-safe "
                    "([A-Za-z0-9._-]+)"
                )
            if mid in engines:
                parser.error(f"--model {spec!r}: duplicate model id")
            try:
                engines[mid] = InferenceEngine.from_checkpoint(mpath)
            except (OSError, ValueError) as e:
                parser.error(f"--model {spec!r}: {e}")
        models = ModelRegistry(engines)
    tenants = None
    if args.tenant_pages or args.tenant_slots or args.tenant_weight:
        from mlapi_tpu.serving.registry import TenantLedger, parse_tenant_kv

        try:
            tenants = TenantLedger(
                quota_pages=parse_tenant_kv(
                    args.tenant_pages, "--tenant-pages"
                ),
                quota_slots=parse_tenant_kv(
                    args.tenant_slots, "--tenant-slots"
                ),
                weights=parse_tenant_kv(
                    args.tenant_weight, "--tenant-weight", cast=float
                ),
            )
        except ValueError as e:
            parser.error(str(e))
    app = build_app(
        engine, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        default_deadline_ms=args.default_deadline_ms,
        drain_timeout_s=args.drain_timeout_s,
        admission_control=args.admission_control,
        models=models, tenants=tenants,
    )
    server = Server(app, host=args.host, port=args.port,
                    reuse_port=is_worker)

    async def _serve_until_signalled():
        # SIGTERM (systemd/docker stop, the --workers supervisor) and
        # SIGINT take the GRACEFUL path: stop accepting, run the
        # app's shutdown hooks — which drain in-flight streams under
        # --drain-timeout-s before the hard stop — then exit. Without
        # this, SIGTERM killed the process mid-decode and every live
        # stream ended as a dropped connection.
        import signal as _signal

        loop = asyncio.get_running_loop()
        stop_ev = asyncio.Event()
        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop_ev.set)
            except (NotImplementedError, RuntimeError):
                pass  # non-main thread / platforms without support
        await server.start()
        await stop_ev.wait()
        _log.info(
            "shutdown signal: draining (budget %.1fs)",
            args.drain_timeout_s,
        )
        # Drain with the LISTENER STILL OPEN: for the whole budget the
        # load balancer's /healthz polls see "draining" and late
        # arrivals shed 503 + retry-after — not connection-refused.
        # Closing first would make both unreachable and (on runtimes
        # whose wait_closed waits out open handlers) let a long stream
        # outlive the budget into the supervisor's SIGKILL.
        target = app.state.get("batcher") or engine
        drain = getattr(target, "drain", None)
        if drain is not None:
            try:
                await drain(args.drain_timeout_s)
            except Exception:
                _log.exception("drain failed; hard stop follows")
        # Already drained, so the shutdown hook's own drain() returns
        # immediately — this closes the listener and stops the engine.
        await server.stop()

    try:
        asyncio.run(_serve_until_signalled())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()

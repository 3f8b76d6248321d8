"""Multi-host bootstrap.

The reference has no distributed anything (SURVEY §2: "Distributed
communication backend: absent"). Here the multi-host story is JAX's
own runtime: every host calls ``jax.distributed.initialize`` before
touching devices; afterwards ``jax.devices()`` spans the whole pod
and the same mesh/sharding code runs unchanged — collectives ride ICI
within a slice and DCN across slices, compiled by XLA, no hand-rolled
transport.

Bootstrap is env-driven so launchers (GKE, mpi-run style wrappers,
bare SSH loops) only need to export three variables::

    MLAPI_TPU_COORDINATOR=host0:8476
    MLAPI_TPU_NUM_PROCESSES=4
    MLAPI_TPU_PROCESS_ID=2   # this host's rank

On Cloud TPU VMs all three are auto-detected by JAX, so
``initialize_from_env`` with no env set still calls
``jax.distributed.initialize()`` bare when
``MLAPI_TPU_MULTIHOST=auto`` is set. With nothing set it is a no-op
(single host).
"""

from __future__ import annotations

import os

from mlapi_tpu.utils.logging import get_logger

_log = get_logger("parallel.distributed")


def initialize_from_env() -> bool:
    """Initialise JAX's distributed runtime from the environment.

    Returns True if the distributed runtime is (now) initialised.
    Safe to call on every entry point: a plain single-host run (no env
    vars) is a no-op, and a second call in an already-initialised
    process (e.g. a sweep script looping over configs) is too.
    """
    import jax

    if jax.distributed.is_initialized():
        return True

    coordinator = os.environ.get("MLAPI_TPU_COORDINATOR")
    if coordinator:
        missing = [
            v
            for v in ("MLAPI_TPU_NUM_PROCESSES", "MLAPI_TPU_PROCESS_ID")
            if v not in os.environ
        ]
        if missing:
            raise ValueError(
                "MLAPI_TPU_COORDINATOR is set but "
                f"{', '.join(missing)} is not — all three multi-host "
                "variables must be exported together"
            )
        try:
            num = int(os.environ["MLAPI_TPU_NUM_PROCESSES"])
            pid = int(os.environ["MLAPI_TPU_PROCESS_ID"])
        except ValueError:
            raise ValueError(
                "MLAPI_TPU_NUM_PROCESSES and MLAPI_TPU_PROCESS_ID must be "
                "integers"
            ) from None
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num,
            process_id=pid,
        )
        _log.info(
            "multi-host: process %d/%d, coordinator %s, %d global devices",
            pid, num, coordinator, jax.device_count(),
        )
        return True
    if os.environ.get("MLAPI_TPU_MULTIHOST") == "auto":
        # Cloud TPU VM: everything auto-detected from the metadata env.
        jax.distributed.initialize()
        _log.info(
            "multi-host (auto): %d global devices across %d processes",
            jax.device_count(), jax.process_count(),
        )
        return True
    return False


REPLICAS_ENV_VAR = "MLAPI_TPU_REPLICAS"


def replica_endpoints_from_env(
    spec: str | None = None,
) -> list[tuple[str, int]]:
    """Serving-replica discovery — the HTTP sibling of the rendezvous
    trio above. The ``--router`` topology supervisor exports::

        MLAPI_TPU_REPLICAS=host0:8001,host0:8002
        MLAPI_TPU_REPLICA_ID=0   # per spawned replica, its slot

    to every process it spawns, exactly the launcher convention the
    multi-host trio uses (env-driven so GKE manifests, SSH loops, and
    tests all speak it); a router pointed at externally-launched
    replicas (other hosts, other supervisors) reads the same variable
    instead of spawning. Returns ``[]`` when unset — single-process
    serving has no replica set. Malformed entries are loud: a typo'd
    fleet definition must not silently route to half the fleet.
    """
    if spec is None:
        spec = os.environ.get(REPLICAS_ENV_VAR, "")
    endpoints: list[tuple[str, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, sep, port = part.rpartition(":")
        if not sep or not host or not port.isdigit():
            raise ValueError(
                f"bad replica endpoint {part!r} (want host:port) in "
                f"${REPLICAS_ENV_VAR} / --replica-urls"
            )
        endpoints.append((host, int(port)))
    return endpoints

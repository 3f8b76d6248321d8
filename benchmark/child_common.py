"""What the children that hold the chip share: the compile counter,
the device report, the memory reading. Imported only in a child."""

from __future__ import annotations

import threading
import time


class CompileCounter:
    """Counts what JAX compiles (or fetches from its persistent cache)
    through ``jax.monitoring``: one ``backend_compile`` duration event
    per program that the process had not seen. ``mark()`` starts a
    span; ``since_mark()`` is what the measured window reads."""

    KEY = "backend_compile"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.events: list[tuple[float, str, float]] = []
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.KEY in event:
            with self._lock:
                self.events.append((time.time(), event, duration))

    @property
    def count(self) -> int:
        with self._lock:
            return len(self.events)

    def mark(self) -> None:
        self._mark = self.count

    def since_mark(self) -> int:
        return self.count - self._mark

    def seconds(self) -> float:
        with self._lock:
            return sum(d for _, _, d in self.events)


NO_CHIP = 3  # a child's exit code when JAX found no accelerator


def device_report() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def chip_or_exit(job: dict) -> dict:
    """The device report; outside a rehearsal a child that finds no
    TPU stops at once (a measurement path never falls back)."""
    device = device_report()
    if not job.get("rehearse") and device["platform"] != "tpu":
        print(f"benchmark: no accelerator: {device!r}", flush=True)
        raise SystemExit(NO_CHIP)
    return device


def mesh_or_none(cfg: dict):
    """The mesh ``fit`` gets: None where the configuration's ``mesh``
    holds one device (``mesh_for_config([1, 1])`` would give a (1, 1)
    mesh, not None, and a meshed ``fit`` is another program), else
    ``mesh_for_config`` of it. A child that sees fewer devices than the
    configuration's ``chips`` stops at once, as without a chip."""
    import math

    import jax

    if math.prod(cfg["mesh"]) == 1:
        return None
    if len(jax.devices()) < cfg["chips"]:
        print(f"benchmark: {len(jax.devices())} devices visible, the "
              f"configuration needs {cfg['chips']}", flush=True)
        raise SystemExit(NO_CHIP)
    from mlapi_tpu.parallel.mesh import mesh_for_config

    return mesh_for_config(cfg["mesh"])


def memory_peak_bytes() -> dict | None:
    """The fullest chip's peak, as the backend reports it. On a TPU
    ``peak_bytes_in_use`` counts live arrays only; what a running
    program needs on top (its temporaries: activations, scratch) is
    ``peak_bytes_reserved`` (looked at on the chip, PR 26: a BERT-base
    step reads 1.47 GB in use and 9.25 GB reserved, and the compiler's
    own ``memory_analysis`` says 10.5 GB). The peak is their sum."""
    import jax

    best = None
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        row = {"peak_bytes_in_use": int(stats["peak_bytes_in_use"]),
               "peak_bytes_reserved": int(stats.get("peak_bytes_reserved", 0))}
        row["memory_peak_bytes"] = (row["peak_bytes_in_use"]
                                    + row["peak_bytes_reserved"])
        if best is None or row["memory_peak_bytes"] > best["memory_peak_bytes"]:
            best = row
    return best


def unflatten(flat: dict) -> dict:
    """``{"a.b.c": x}`` -> ``{"a": {"b": {"c": x}}}``: the references
    name their weights by dotted path, the program nests dicts."""
    out: dict = {}
    for k, v in flat.items():
        d = out
        parts = k.split(".")
        for q in parts[:-1]:
            d = d.setdefault(q, {})
        d[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out

"""Process-level JAX set-up the entry points share: which platform,
where compiled programs are cached, and who decides ``interpret``.

``JAX_PLATFORMS`` works as JAX documents. ``MLAPI_TPU_PLATFORM`` is
this package's own spelling of the same choice, applied by the CLIs
that call :func:`apply_platform_override`: the ``--workers`` and
``--router`` supervisors set it to ``cpu`` in their children's
environment (a chip belongs to one process), the tests set it on
spawned servers, and an operator may set it by hand. It wins over ``JAX_PLATFORMS`` because it is applied to the
config after import.
"""

from __future__ import annotations

import os
from pathlib import Path

# One fixed place inside the checkout (git-ignored). The directory is
# part of the persistent cache's key, so it must not move between
# runs: never a temp dir, never per-process.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def apply_platform_override(env_var: str = "MLAPI_TPU_PLATFORM") -> str | None:
    """Set ``jax_platforms`` from ``env_var`` (e.g. ``cpu``); returns
    the value applied, if any. Call before any JAX computation —
    backends initialise lazily, so the config set here is the one the
    first device query reads."""
    platform = os.environ.get(env_var)
    if platform:
        import jax

        if jax.config.jax_platforms != platform:
            jax.config.update("jax_platforms", platform)
    return platform or None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return the directory it uses. ``JAX_COMPILATION_CACHE_DIR`` set:
    JAX reads it itself and nothing is set in code. Unset: the cache
    lives at :data:`COMPILE_CACHE_DIR`. Called from the CLIs'
    ``main()``, never at import and never from the tests."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def pallas_interpret() -> bool:
    """THE rule for every Pallas call site: interpret only on the CPU
    backend (the CI backend). On TPU the compiled kernel runs; any
    other accelerator attempts a real lowering and fails loudly —
    silently interpreting there would be orders slower than the
    einsum path the kernels exist to beat."""
    import jax

    return jax.default_backend() == "cpu"


def device_report() -> dict:
    """The device as JAX reports it — what every entry point prints
    so a caller can tell a chip run from a CPU run."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }

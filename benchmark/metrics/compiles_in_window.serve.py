"""Programs compiled (or fetched from the persistent cache) inside
the measured window, from the child's ``jax.monitoring`` listener.
It should read 0: every shape is warmed up before the window."""


def read(run):
    child = run.get("child") or {}
    for key in ("compiles_in_window", "compiles_since_mark"):
        if child.get(key) is not None:
            return child[key]
    return None

"""Device milliseconds a step under the ``kda`` scope (the gated
delta-rule mixers: projections, convolutions, the chunked scan, gate
and output; forward + recomputation + backward), from the run's
``scopes`` (``scope_time.py``)."""


def read(run):
    sc = run.get("scopes")
    if not sc or not sc.get("steps") or "kda" not in sc["seconds"]:
        return None
    return 1e3 * sc["seconds"]["kda"] / sc["steps"]

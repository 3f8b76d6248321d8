"""How late the load generator sent a request after it was due, 95th
percentile over the window's requests: a starved generator must not
be read as a fast server."""


def read(run):
    return run["client"]["summary"].get("loadgen_late_p95_ms")

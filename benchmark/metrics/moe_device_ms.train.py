"""Device milliseconds a step under the ``moe.*`` scopes (router and
plan, the held experts' grouped products, the shared expert; forward +
recomputation + backward), from the run's ``scopes``
(``scope_time.py``)."""


def read(run):
    sc = run.get("scopes")
    if not sc or not sc.get("steps"):
        return None
    parts = [v for k, v in sc["seconds"].items() if k.startswith("moe.")]
    return 1e3 * sum(parts) / sc["steps"] if parts else None

"""Device milliseconds a step under the ``attn.*`` scopes of a Laguna
train step (``attn.sliding`` + ``attn.full``, which hold ``attn.rope``,
``attn.gate`` and the two ``.core`` scopes: projections, rotary, the
flash call, the headwise gate and the output projection; forward +
recomputation + backward), from the run's ``scopes``
(``scope_time.py``)."""


def read(run):
    sc = run.get("scopes")
    if not sc or not sc.get("steps"):
        return None
    parts = [sc["seconds"][k] for k in ("attn.sliding", "attn.full")
             if k in sc["seconds"]]
    return 1e3 * sum(parts) / sc["steps"] if parts else None

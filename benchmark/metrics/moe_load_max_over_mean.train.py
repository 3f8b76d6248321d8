"""The fullest held expert's pairs over the mean held expert's, in the
least even expert layer of a step (``moe.load_max_over_mean`` as the
step itself computed it: 1.0 perfectly even, at most the number held;
no token is dropped at any ratio, the work follows the pairs); the
median step of the window."""


def read(run):
    stats = (run.get("child") or {}).get("model_stats") or {}
    return stats.get("moe.load_max_over_mean") or None

"""Share of the (token, expert) pairs the router made that went to an
expert held here, over the window's steps: ``moe.pairs_here /
moe.pairs_routed`` as the step itself counted them (held / router
width in the mean: 8 / 256 = 3.1%)."""


def read(run):
    stats = (run.get("child") or {}).get("model_stats") or {}
    if not stats.get("moe.pairs_routed"):
        return None
    return 100.0 * stats["moe.pairs_here"] / stats["moe.pairs_routed"]

"""Share of the rows the expert tile loop computes that hold a pair:
``moe.pairs_here / moe.rows_run`` over the window's steps, as the step
itself counted them (``experts.load_stats``: ``rows_run`` is the loop's
trips times its tile). The rest is padding, each held expert's group
rounded up to whole tiles, which the loop's products pay for in full:
low where few pairs reach each held expert, high where every expert's
group fills its tiles. None where the step counts no rows."""


def read(run):
    stats = (run.get("child") or {}).get("model_stats") or {}
    if not stats.get("moe.rows_run"):
        return None
    return 100.0 * stats["moe.pairs_here"] / stats["moe.rows_run"]

"""Mean over the window's samples (one a second) of the paged pool's
``generate.kv_page_utilization`` gauge: pages in use over pages
allocated."""


def read(run):
    vals = [g["generate.kv_page_utilization"] for g in run.get("gauges") or []
            if g.get("generate.kv_page_utilization") is not None]
    return 100.0 * sum(vals) / len(vals) if vals else None

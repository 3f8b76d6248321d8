"""Time per output token, 95th percentile over ALL the window's
requests of (last token frame - first) / (tokens - 1); a failed
request is the slowest. Beside the end-to-end median (`tpot_p50_ms`)
without a bound of its own: over two sets of four 45 s runs its
quartiles lie 7.7% of the median apart (PR 26), more than half of the
widest bound the contract admits."""


def read(run):
    v = run["client"]["summary"].get("tpot_p95_ms")
    return None if v is None or v == float("inf") else v

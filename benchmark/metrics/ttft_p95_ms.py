"""Time to first token, 95th percentile over ALL the window's requests
(first stream frame minus the time the request was DUE; a failed
request is the slowest). Not an end-to-end metric of the cells of
PR 26: at 0.8 of the knee its quartiles lie 19-30% of the median
apart over sets of 250-request runs, and a second set of the same
seeds read 29% under the first, wider than any bound the contract
admits, so it stands here without one."""


def read(run):
    v = run["client"]["summary"].get("ttft_p95_ms")
    return None if v is None or v == float("inf") else v

"""Output tokens the clients received per decode-chunk dispatch of
the engine (``generate.chunk_calls`` after minus before the window):
how full the engine's decode units are."""


def read(run):
    calls = (run.get("counters") or {}).get("generate.chunk_calls")
    tokens = run["client"]["summary"]["tokens_ok"]
    return tokens / calls if calls else None

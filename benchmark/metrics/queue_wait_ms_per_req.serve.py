"""Milliseconds a request waited between ``submit`` and the dispatch
thread's claim of it (a lane's formation, or a live lane's admission):
``generate.queue_wait_us`` over ``generate.queue_wait_n``, after minus
before the window. The part of time-to-first-token that is queueing,
on the program's own clock; ``generate.prefill_wait_*`` is the rest."""


def read(run):
    c = run.get("counters") or {}
    n = c.get("generate.queue_wait_n")
    if not n or c.get("generate.queue_wait_us") is None:
        return None
    return c["generate.queue_wait_us"] / n / 1e3

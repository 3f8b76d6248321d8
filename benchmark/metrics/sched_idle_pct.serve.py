"""Share of the window's wall time that the dispatch thread had
nothing to dispatch (waiting for work, or for pages to be released):
``generate.sched_idle_us`` after minus before the window, over the
window's seconds. The counters are read as the window opens and once
its last request has been answered, so the numerator may hold a
little of the settle time after the close."""


def read(run):
    us = (run.get("counters") or {}).get("generate.sched_idle_us")
    seconds = (run.get("window") or {}).get("seconds")
    if us is None or not seconds:
        return None
    return 100.0 * us / 1e6 / seconds

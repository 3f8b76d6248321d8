"""Share of the window's wall time that the dispatch thread spent
blocked in token readbacks (``np.asarray`` of a chunk's device output
in ``DispatchChain.drain``): ``generate.readback_wait_us`` after minus
before the window, over the window's seconds. The counters are read
as the window opens and once its last request has been answered, so
the numerator may hold a little of the settle time after the close."""


def read(run):
    us = (run.get("counters") or {}).get("generate.readback_wait_us")
    seconds = (run.get("window") or {}).get("seconds")
    if us is None or not seconds:
        return None
    return 100.0 * us / 1e6 / seconds

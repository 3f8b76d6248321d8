"""Milliseconds of the dispatch thread per decode unit of the unit
scheduler: ``generate.sched_unit_decode_us`` over
``generate.sched_unit_decode_n``, after minus before the window. A
unit's span holds its host work (page mapping, uploads, the dispatch)
and whatever token readback it waited for."""


def read(run):
    c = run.get("counters") or {}
    n = c.get("generate.sched_unit_decode_n")
    if not n or c.get("generate.sched_unit_decode_us") is None:
        return None
    return c["generate.sched_unit_decode_us"] / n / 1e3

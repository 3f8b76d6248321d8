"""Device microseconds of one trip of the expert tile loop: the device
time a step under the ``moe.experts`` scope (``scope_time.py``: the
grouped products forward, recomputed and backward, with their gathers
and scatter-adds) over the tiles a step ran (``moe.tiles_run``, one
forward's count, summed over the window's steps by the child). With
``moe_tile_fill_pct.train`` it tells a loop that pays for padding from
one whose every trip costs too much. None where the run has no scopes,
no ``moe.experts`` time or no count of tiles."""


def read(run):
    sc = run.get("scopes") or {}
    stats = (run.get("child") or {}).get("model_stats") or {}
    seconds = (sc.get("seconds") or {}).get("moe.experts")
    if not (sc.get("steps") and seconds and stats.get("steps")
            and stats.get("moe.tiles_run")):
        return None
    tiles_a_step = stats["moe.tiles_run"] / stats["steps"]
    return 1e6 * seconds / sc["steps"] / tiles_a_step
